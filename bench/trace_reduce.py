"""Reduce a profiler trace to the device metrics of a window.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain dict (kept small enough to commit as test data); ``reduce`` turns
that dict into busy time, idle share, device time per executable and per
op, and the longest idle gaps labelled by what the harness was doing.

Busy time is the union of the intervals in which an operation ran on a
device (the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane), averaged
over the devices used.  The window is the ``bench.window`` annotation the
harness wraps around the traced window, on the same clock as the device.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
HOST_PREFIX = "bench."


def short_name(line: str, name: str) -> str:
    """An op's HLO instruction name (the trace gives its whole text) or an
    executable's name without its fingerprint."""
    if line == OPS_LINE:
        return name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\(\d+\)$", "", name)


def extract(xplane_path) -> dict:
    """The events ``reduce`` needs, from one ``.xplane.pb``.

    ``device``: ``[plane, line, name, start_ns, dur_ns]`` for every event
    on the ops and modules lines of each device plane; ``host``:
    ``[name, start_ns, dur_ns]`` for the harness's own ``bench.*``
    annotations."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(xplane_path))
    device, host = [], []
    for plane in data.planes:
        on_device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if on_device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if on_device:
                    device.append([plane.name, line.name, short_name(line.name, ev.name),
                                   float(ev.start_ns), float(ev.duration_ns)])
                elif ev.name.startswith(HOST_PREFIX):
                    host.append([ev.name, float(ev.start_ns), float(ev.duration_ns)])
    return {"device": device, "host": host}


def _union(intervals):
    """Merged, sorted ``[start, end]`` intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append([s, e])
    return out


def _top(pairs, k=10):
    return [[name, secs] for name, secs in
            sorted(pairs.items(), key=lambda kv: -kv[1])[:k]]


def reduce(trace: dict) -> dict | None:
    """Device metrics of the traced window, or None where the trace holds
    no window annotation or no device operation inside it."""
    windows = [h for h in trace["host"] if h[0] == WINDOW]
    if not windows:
        return None
    _, w0, wdur = max(windows, key=lambda h: h[2])
    w1 = w0 + wdur
    planes = sorted({d[0] for d in trace["device"]})
    busy_ns, merged_all, op_time, module_time = [], [], {}, {}
    for plane in planes:
        ops = [[d[3], d[3] + d[4]] for d in trace["device"]
               if d[0] == plane and d[1] == OPS_LINE]
        merged = _clip(_union(ops), w0, w1)
        busy_ns.append(sum(e - s for s, e in merged))
        merged_all.append(merged)
    for plane, line, name, start, dur in trace["device"]:
        inside = _clip([[start, start + dur]], w0, w1)
        if not inside:
            continue
        secs = (inside[0][1] - inside[0][0]) / 1e9
        table = op_time if line == OPS_LINE else module_time
        table[name] = table.get(name, 0.0) + secs
    if not busy_ns or not any(busy_ns):
        return None
    busy_s = sum(busy_ns) / len(busy_ns) / 1e9
    window_s = wdur / 1e9
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "module_s": module_time,
        "device_ops": _top(op_time),
        "idle_gaps": _idle_gaps(merged_all[0], w0, w1, trace["host"]),
    }


def _idle_gaps(merged, w0, w1, host, k=10):
    """The ``k`` longest gaps between device operations in the window,
    each named by the innermost harness annotation covering its middle."""
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [h for h in host if h[0] != WINDOW]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = 0.5 * (s + e)
        cover = [h for h in spans if h[1] <= mid <= h[1] + h[2]]
        label = min(cover, key=lambda h: h[2])[0] if cover else "bench.none"
        out.append([label, (e - s) / 1e9])
    return out
