"""One run of one cell: set-up, warm-up, the measured window, the check.

The window drives ``repro.fleet.SvdFleet`` through its public surface
(``enqueue`` / ``pump`` / ``poll``): frontend (``ContinuousBatcher``) ->
``SvdService`` -> ``SvdEngine`` -> the fused Pallas update.  The harness
takes nothing else from the program but its spans and counters.
"""

from __future__ import annotations

import contextlib
import tempfile
import time
from collections import Counter
from pathlib import Path

import numpy as np

from bench import reference, stats, traffic, trace_reduce

IDLE_SLEEP_S = 1e-4       # the client's pause when it has nothing to do
LATE_LIMIT_S = 60.0       # an answer may come this long after the window
SEED_EVENTS = 32          # a float32 seed state's rounding, counted in events


class CompileCounter:
    """Counts executables built (compiled or loaded from the persistent
    cache) while ``active``: the window should build none."""

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def _annotation(traced: bool):
    if traced:
        import jax

        return jax.profiler.TraceAnnotation
    return lambda name: contextlib.nullcontext()


def _stats(fleet) -> dict:
    import dataclasses

    return dataclasses.asdict(fleet.stats())


def build_fleet(config: dict):
    from repro.api import UpdatePolicy
    from repro.fleet import SvdFleet

    svc = config["service"]
    return SvdFleet(svc["shards"], policy=UpdatePolicy(method=svc["method"]),
                    devices="auto", continuous=True, max_batch=svc["max_batch"],
                    max_in_flight=svc["max_in_flight"], max_depth=svc["max_depth"])


def register_streams(fleet, u, s, v) -> list[str]:
    from repro.api import SvdState

    ids = [f"s{i}" for i in range(u.shape[0])]
    for i, sid in enumerate(ids):
        fleet.register(sid, SvdState.from_factors(u[i], s[i], v[i]))
    return ids


def warm_up(fleet, gen, ids, rounds) -> None:
    """Seal one round of each ``(depth, width)``: ``width`` streams with
    ``depth`` events each.  These events are the start of each stream's
    chain and are checked like every other."""
    offset = 0
    for depth, width in rounds:
        for j in range(width):
            i = (offset + j) % len(ids)
            a, b = gen.next(i, depth)
            for t in range(depth):
                fleet.enqueue(ids[i], a[t], b[t])
        offset += width
        fleet.pump()
        fleet.drain()
    fleet.poll()


def closed_loop(fleet, gen, ids, mix, seconds, ann) -> dict:
    """Every stream keeps ``outstanding`` events unseen; a stream submits
    its next events when earlier ones become visible.

    Events become visible a round at a time, so the count at the window's
    end moves in whole rounds.  The cumulative count is read between the
    last completion inside the window and the first after it, in
    proportion to the time each side of the end: the window's work, over
    all its time, with no step at each round."""
    outstanding = int(mix["outstanding"])
    tok_stream: dict = {}

    def submit(i, count):
        with ann("bench.generate"):
            a, b = gen.next(i, count)
        with ann("bench.enqueue"):
            for t in range(count):
                tok_stream[fleet.enqueue(ids[i], a[t], b[t])] = i
        return count

    t0 = time.perf_counter()
    attempted = sum(submit(i, outstanding) for i in range(len(ids)))
    points = [(0.0, 0)]
    while True:
        with ann("bench.pump"):
            fleet.pump()
        with ann("bench.poll"):
            toks = fleet.poll()
        now = time.perf_counter() - t0
        if not toks:
            time.sleep(IDLE_SLEEP_S)
            continue
        points.append((now, points[-1][1] + len(toks)))
        refill = Counter(tok_stream.pop(t) for t in toks)
        if now >= seconds:
            break
        for i, count in sorted(refill.items()):
            attempted += submit(i, count)
    with ann("bench.drain"):
        fleet.drain()
    for tok in fleet.poll():
        tok_stream.pop(tok)
    (ta, ca), (tb, cb) = points[-2], points[-1]
    done = ca + (cb - ca) * (seconds - ta) / (tb - ta)
    return {"visible": done, "attempted": attempted, "failed": len(tok_stream),
            "window_s": seconds, "completions": points}


def open_loop(fleet, gen, ids, due, target, ann) -> dict:
    """Send each event at its due time, whatever the system does; stamp
    its latency from the due time to the poll that shows it."""
    n = len(due)
    tok_event: dict = {}
    sent = np.full(n, np.nan)
    seen = np.full(n, np.nan)
    i = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        while i < n and due[i] <= now:
            s = int(target[i])
            with ann("bench.generate"):
                a, b = gen.next(s, 1)
            with ann("bench.enqueue"):
                tok_event[fleet.enqueue(ids[s], a[0], b[0])] = i
            sent[i] = now
            i += 1
            now = time.perf_counter() - t0
        with ann("bench.pump"):
            fleet.pump()
        with ann("bench.poll"):
            toks = fleet.poll()
        now = time.perf_counter() - t0
        for tok in toks:
            seen[tok_event.pop(tok)] = now
        if i == n and not tok_event:
            break
        if now > due[-1] + LATE_LIMIT_S:
            break
        wait = due[i] - now if i < n else IDLE_SLEEP_S
        if wait > 0:
            time.sleep(min(wait, IDLE_SLEEP_S))
    with ann("bench.drain"):
        fleet.drain()
    return {"latency_s": seen - due, "late_s": sent - due, "attempted": n,
            "failed": int(np.isnan(seen).sum()), "window_s": float(due[-1])}


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def check(state_of, gen, config, picked, failed) -> tuple[bool, dict, list]:
    """Judge the sampled streams' states against the float64 reference and
    the configuration's limits.  ``state_of(i)`` is stream ``i``'s
    ``(u, s, v)``: the program's after the window, or the control's.

    Rounding error in a float32 update chain grows in step with the chain's
    length from an offset that the float32 seed state sets, and a chain is
    as long as the window lets the program make it.  So each stream's
    reading is divided by the events it was sent plus ``SEED_EVENTS``: a
    faster program makes longer chains, not larger numbers.  Returns
    whether every number is within its limit, ``{name: [number, limit]}``,
    and each sampled stream's readings."""
    rows = []
    for i in picked:
        u, s, v = (np.asarray(x, np.float64) for x in state_of(i))
        if u.shape != (config["m"], config["rank"]) or s.shape != (config["rank"],):
            reading = {"recon_rel": float("inf"), "sigma_rel": float("inf")}
        else:
            reading = reference.compare(u, s, v, *gen.reference(i, *gen.seed_state(i)))
        rows.append({"stream": int(i), "events": int(gen.count[i]), **reading})
    got = {"failed_events": int(failed)}
    for k in ("recon_rel", "sigma_rel"):
        got[f"{k}_per_event"] = max(r[k] / (r["events"] + SEED_EVENTS) for r in rows)
    lim = config["limits"]
    if set(lim) != set(got):
        raise KeyError(f"limits name {sorted(lim)}; the check compares {sorted(got)}")
    ok = all(got[k] <= lim[k] for k in lim)
    return ok, {k: [got[k], lim[k]] for k in lim}, rows


def program_state(fleet, ids):
    """``state_of`` for ``check``: the program's state of stream ``i``."""
    def state_of(i):
        st = fleet.state(ids[i])
        return st.u, st.s, st.v
    return state_of


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, devices,
             t_start: float, keep: dict | None = None) -> dict:
    """One run; returns the result line.  ``keep``, where given, receives
    the fleet and the generator for callers that read more (calibration).
    The program runs at the configuration's ``matmul_precision``."""
    import jax

    with jax.default_matmul_precision(spec["config"]["matmul_precision"]):
        return _run_cell(spec, seed, seconds, trace, devices, t_start, keep)


def _run_cell(spec, seed, seconds, trace, devices, t_start, keep):
    import jax

    config, mix = spec["config"], spec["mix"]
    counter = CompileCounter()
    gen = traffic.event_model(config, seed)
    u, s, v = gen.device_init()
    fleet = build_fleet(config)
    ids = register_streams(fleet, u, s, v)
    del u, s, v
    warm_up(fleet, gen, ids, traffic.warm_rounds(mix, config))
    if mix["loop"] == "open":
        due, target = traffic.open_schedule(mix, config["streams"], seconds, seed)

    ann = _annotation(trace)
    tracedir = tempfile.TemporaryDirectory() if trace else None
    if trace:
        from repro import obs

        obs.enable()
        obs.clear_trace()
        obs.start_tracing()
        # the Python tracer would time every Python call of the host path,
        # which is most of what a round costs; device and host-runtime
        # tracing stay on
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tracedir.name, profiler_options=opts)
    stats0 = _stats(fleet)
    setup_s = time.perf_counter() - t_start
    counter.active = True
    with ann(trace_reduce.WINDOW):
        if mix["loop"] == "open":
            loop = open_loop(fleet, gen, ids, due, target, ann)
        else:
            loop = closed_loop(fleet, gen, ids, mix, seconds, ann)
    counter.active = False
    stats1 = _stats(fleet)
    reduced = None
    spans = []
    if trace:
        jax.profiler.stop_trace()
        obs.stop_tracing()
        spans = obs.trace_events()
        obs.disable()
        found = sorted(Path(tracedir.name).rglob("*.xplane.pb"))
        reduced = trace_reduce.reduce(trace_reduce.extract(found[-1])) if found else None
        tracedir.cleanup()
    print(f"compiles_in_window: {counter.count}", flush=True)
    peak_bytes = memory_peak(devices)

    picked = traffic.sample_streams(gen.count, config["sample_streams"], seed)
    ok, checks, rows = check(program_state(fleet, ids), gen, config, picked,
                             loop["failed"])

    e2e = {"setup_s": setup_s}
    if "visible" in loop:
        e2e["events_per_s"] = loop["visible"] / loop["window_s"]
    else:
        lat = np.where(np.isnan(loop["latency_s"]), np.inf, loop["latency_s"])
        e2e["visible_p99_ms"] = 1e3 * stats.percentile(list(lat), 99)
        e2e["visible_p50_ms"] = 1e3 * stats.percentile(list(lat), 50)
    dev = devices[0]
    result = {"correct": bool(ok), "attempted": int(loop["attempted"]),
              "failed": int(loop["failed"]), "metrics": {},
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices), "memory_peak_bytes": peak_bytes}}
    if trace:
        run = {"spans": spans, "stats": {k: stats1[k] - stats0[k] for k in stats1},
               "trace": reduced, "config": config, "device_kind": dev.device_kind,
               "late_s": loop.get("late_s")}
        units = {m["name"]: m["unit"] for m in spec["per_layer"]}
        for name, read in spec["readers"].items():
            value = read(run)
            if value is not None:
                result["metrics"][name] = {"value": float(value), "unit": units[name]}
        if reduced is not None:
            result["device"]["busy_s"] = reduced["busy_s"]
            result["device"]["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
    else:
        for m in spec["end_to_end"]:
            result["metrics"][m["name"]] = {"value": float(e2e[m["name"]]),
                                            "unit": m["unit"]}
    result["checks"] = checks
    if keep is not None:
        keep.update(fleet=fleet, gen=gen, ids=ids, picked=picked, loop=loop,
                    stats=(stats0, stats1), rows=rows)
    return result
