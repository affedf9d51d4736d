"""What the per-layer metric readers share.  Each reader in
``bench/metrics/<metric>.py`` takes the run record the harness builds for a
traced run and returns a number, or None where it finds nothing to read:

* ``spans``: the program's ``repro.obs`` spans (Chrome 'X' events, µs)
  recorded in the window;
* ``stats``: the change of ``SvdFleet.stats()`` over the window;
* ``trace``: ``trace_reduce.reduce`` of the profiler trace, or None;
* ``config``, ``device_kind``, ``late_s`` (open loop: send minus due, s).
"""

from __future__ import annotations

import numpy as np

from bench import stats, work


def _spans(run, name):
    return [e for e in run["spans"] if e.get("name") == name]


def flush_host_ms(run):
    """Mean host time of a flush round less the waits for older rounds
    inside it (``flush_round`` minus its ``reap`` children)."""
    rounds = _spans(run, "flush_round")
    if not rounds:
        return None
    reaps = _spans(run, "reap")
    selfs = []
    for f in rounds:
        f0, f1 = f["ts"], f["ts"] + f["dur"]
        inner = sum(r["dur"] for r in reaps
                    if r["tid"] == f["tid"] and f0 <= r["ts"] and r["ts"] + r["dur"] <= f1)
        selfs.append(f["dur"] - inner)
    return stats.mean(selfs) / 1e3


def pump_host_ms(run):
    """Mean duration of the ``pump`` spans that sealed a round."""
    pumps = [e["dur"] for e in _spans(run, "pump")
             if e.get("args", {}).get("dispatched", 0) > 0]
    return stats.mean(pumps) / 1e3 if pumps else None


def round_events(run):
    """Events applied per engine round over the window."""
    rounds = run["stats"]["rounds"]
    return run["stats"]["applied"] / rounds if rounds else None


def stream_rounds(run):
    """Passes over a stream's state in the window: the streams of each
    ``flush_round`` span, summed.  None where the spans do not hold every
    round the counters saw (the span buffer is bounded)."""
    rounds = _spans(run, "flush_round")
    if not rounds or len(rounds) != run["stats"].get("flushes"):
        return None
    return sum(e.get("args", {}).get("streams", 0) for e in rounds)


def update_roofline(run):
    """The least time the window's updates could take on this chip, as a
    share of the device's busy time in the window."""
    tr, applied, passes = run["trace"], run["stats"]["applied"], stream_rounds(run)
    if tr is None or not applied or not passes or tr["busy_s"] <= 0:
        return None
    c = run["config"]
    least, _ = work.least_seconds(applied, passes, c["m"], c["n"], c["rank"],
                                  work.peaks(run["device_kind"]))
    return 100.0 * least / tr["busy_s"]


def idle_share(run):
    tr = run["trace"]
    return None if tr is None else 100.0 * tr["idle_share"]


def gen_late_p99_ms(run):
    late = run.get("late_s")
    if late is None or not len(late):
        return None
    return 1e3 * stats.percentile(list(np.asarray(late)), 99)
