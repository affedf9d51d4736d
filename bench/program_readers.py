"""Readers of the program's own host-path spans and counters.

Spans come from the run record (``run["spans"]``, as in ``readers``);
counters and histograms from ``repro.obs.registry()``, which the harness
turns on for the traced window alone, so its series hold that window.  Each
reader returns None where the program records nothing to read (a program
without these spans or counters).
"""

from __future__ import annotations


def _series(name: str) -> list:
    from repro import obs

    return [m for m in obs.registry().series() if m.name == name]


def _counter_sum(name: str):
    """A counter summed over its label sets (the shards), or None where the
    program never made it."""
    found = _series(name)
    return sum(m.value for m in found) if found else None


def counter_ratio(num: str, den: str):
    """Counter ``num`` per unit of counter ``den``, over all shards."""
    n, d = _counter_sum(num), _counter_sum(den)
    return n / d if n is not None and d else None


def histogram_mean(name: str):
    """Mean observation of a histogram over all shards."""
    found = _series(name)
    count = sum(h.count for h in found)
    return sum(h.sum for h in found) / count if count else None


def self_time_per_round_ms(run, name: str):
    """Self time of the ``name`` spans (each less its child spans, found by
    their ``parent`` links), summed and divided by the window's
    ``flush_round`` spans, in ms."""
    spans = run["spans"]
    own = [e for e in spans if e.get("name") == name]
    rounds = sum(1 for e in spans if e.get("name") == "flush_round")
    if not own or not rounds:
        return None
    children: dict = {}
    for e in spans:
        parent = e.get("args", {}).get("parent")
        if parent is not None:
            children[parent] = children.get(parent, 0.0) + e["dur"]
    total = sum(e["dur"] - children.get(e["args"]["id"], 0.0) for e in own)
    return total / rounds / 1e3


def starved_share(run):
    """Rounds sealed while nothing older was in flight, as a share (%) of
    the window's rounds (``SvdFleet.stats()`` flushes)."""
    starved, flushes = _counter_sum("starved_rounds"), run["stats"].get("flushes")
    return 100.0 * starved / flushes if starved is not None and flushes else None
