"""The work a truncated rank-1 SVD update requires, counted from its shapes.

The roofline metrics divide the least time the chip could take for the
updates applied in a traced window by the device time they took.  The work
is what the updates need, whatever implements them: padding, relayouts and
recomputation are not counted.

A round applies up to ``max_depth`` consecutive events of a stream in one
pass over its state, so the least bytes are the state's read and write once
per (stream, round), and each event's pair ``a``, ``b`` read once.  The
operations are each event's own.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def state_bytes(m: int, n: int, r: int, itemsize: int = 4) -> int:
    """HBM bytes of one pass over a stream's state: read and write the
    unpadded factors ``U`` (m, r), ``V`` (n, r) and ``s`` (r,)."""
    return itemsize * 2 * (m * r + n * r + r)


def pair_bytes(m: int, n: int, itemsize: int = 4) -> int:
    """HBM bytes of one event's pair: read ``a`` (m,) and ``b`` (n,)."""
    return itemsize * (m + n)


def update_flops(m: int, n: int, r: int) -> float:
    """Operations of one truncated update: Brand's projections and
    deflections ``4r(m+n)``, the two basis rotations ``2r(r+1)(m+n)`` and the
    (r+1)-sized secular core ``24(r+1)^3``."""
    return 4.0 * r * (m + n) + 2.0 * r * (r + 1) * (m + n) + 24.0 * (r + 1) ** 3


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """Published peaks of one chip; a device missing from the table is an
    error, never a default."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path.name}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def least_seconds(events: int, stream_rounds: int, m: int, n: int, r: int,
                  peak: dict, itemsize: int = 4) -> tuple[float, str]:
    """The least time ``events`` updates, applied in ``stream_rounds``
    passes over a stream's state, could take on a chip with ``peak``, and
    which bound sets it (``"memory"`` or ``"compute"``)."""
    nbytes = (stream_rounds * state_bytes(m, n, r, itemsize)
              + events * pair_bytes(m, n, itemsize))
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    t_cmp = events * update_flops(m, n, r) / peak["flops_per_s"]
    return (t_mem, "memory") if t_mem >= t_cmp else (t_cmp, "compute")
