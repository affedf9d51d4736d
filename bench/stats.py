"""Small statistics shared by the harness and the metric readers."""

from __future__ import annotations

import math


def percentile(xs, q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]): no interpolation, so the
    value reported is one that occurred."""
    if not len(xs):
        raise ValueError("no samples")
    ordered = sorted(xs)
    k = max(0, min(len(ordered) - 1, math.ceil(q / 100.0 * len(ordered)) - 1))
    return ordered[k]


def mean(xs):
    return sum(xs) / len(xs) if len(xs) else None
