import os
import sys

# ``--cpu-mesh`` asks for the 8-fake-device CPU rehearsal; it must be set
# before jax initializes (jax locks the device count).  Without it the bench
# runs on the platform JAX finds, and says which.
if "--cpu-mesh" in sys.argv:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"

"""Fleet tier vs single service: sustained throughput + latency SLO
(DESIGN.md §13).

The block above MUST stay first: jax locks the device count on first
init (same contract as bench_dist.py) — the fleet arms run on 8 fake CPU
devices.  Fake devices share ONE physical core, so the fleet's win here is
NOT device parallelism: it is continuous batching's round shape.  A
standalone service dispatches one-event-per-stream rounds (depth 1,
re-stacking every stream's state each wave); a backlogged fleet shard seals
rank-k scan columns (depth up to MAX_DEPTH), so the same event count ships
in ~ROUNDS/MAX_DEPTH fewer engine rounds with ~MAX_DEPTH-fold less host-side
state re-stacking.  On a real accelerator mesh the per-shard device pinning
adds device parallelism on top.

Two experiments, shared geometry (small factors: host-overhead-bound, the
regime the fleet tier targets — million-stream populations of modest rank):

1. **Sustained enqueue throughput** (closed loop): feed STREAMS x ROUNDS
   events as fast as the admission layer accepts them, drain, report
   events/s.  Arms: single service; fleet at 2/4/8 shards.  Acceptance:
   fleet@8 >= 1.5x single.

2. **Enqueue-to-visible latency** (open loop): Poisson arrivals at
   LOAD x the single service's sustained rate, driven through
   ``common.open_loop``; every event's token is stamped when its flush
   round retires.  Arms: single service with fixed flush boundaries
   (autoflush at FIXED_BATCH); fleet@8 with the same fixed boundaries
   (continuous=False); fleet@8 with continuous batching.  Acceptance:
   continuous p99 < fixed-boundary p99 at the same offered load.

CSV rows (benchmarks/run.py style):
  bench_fleet/throughput/<arm>,us_total,events_per_s=...
  bench_fleet/latency/<arm>,p99_us,p50_us=... rate_hz=...

and a machine-readable summary at benchmarks/BENCH_fleet.json.
"""

import json
import time
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.common import bench_metadata, emit, open_loop
from repro import obs
from repro.api import SvdState, UpdatePolicy
from repro.fleet import SvdFleet
from repro.serve import SvdService

M, N, RANK = 64, 96, 8
STREAMS = 64
ROUNDS = 32            # events per stream, closed-loop experiment
MAX_DEPTH = 32
SHARD_COUNTS = (2, 4, 8)
REPEAT = 3

OPEN_EVENTS = 768      # open-loop experiment length
LOAD = 0.5             # offered rate as a fraction of single sustained rate
FIXED_BATCH = 16       # fixed-boundary arms autoflush at this fill count

OUT = Path(__file__).parent / "BENCH_fleet.json"
POLICY = UpdatePolicy(method="direct")


def _states():
    rng = np.random.default_rng(0)
    return [
        SvdState.from_factors(
            np.linalg.qr(rng.normal(size=(M, RANK)))[0],
            np.sort(np.abs(rng.normal(size=RANK)))[::-1].copy(),
            np.linalg.qr(rng.normal(size=(N, RANK)))[0],
        )
        for _ in range(STREAMS)
    ]


def _traffic(count: int):
    rng = np.random.default_rng(1)
    return [
        (f"s{i % STREAMS}",
         jnp.asarray(rng.normal(size=M)), jnp.asarray(rng.normal(size=N)))
        for i in range(count)
    ]


def _single(max_batch: int = STREAMS) -> SvdService:
    svc = SvdService(max_batch=max_batch, max_in_flight=2, policy=POLICY)
    for i, st in enumerate(_states()):
        svc.register(f"s{i}", st)
    return svc


def _fleet(shards: int, *, continuous: bool = True,
           max_batch: int = STREAMS) -> SvdFleet:
    # devices deliberately unpinned: fake CPU devices share one core, and
    # XLA compiles per (executable, device) — pinning shard i to device i
    # would multiply every (batch-bucket x depth-bucket) compile by 8 for
    # zero parallelism.  On a real mesh pass devices="auto".
    fl = SvdFleet(
        shards,
        policy=POLICY,
        max_batch=max_batch,
        max_depth=MAX_DEPTH,
        max_in_flight=2,
        continuous=continuous,
    )
    for i, st in enumerate(_states()):
        fl.register(f"s{i}", st)
    return fl


# ---------------------------------------------------------------------------
# 1. sustained enqueue throughput (closed loop)
# ---------------------------------------------------------------------------


def _feed_drain(make) -> tuple[float, object]:
    tgt = make()
    traffic = _traffic(STREAMS * ROUNDS)
    t0 = time.perf_counter()
    for sid, a, b in traffic:
        tgt.enqueue(sid, a, b)
    tgt.drain()
    return time.perf_counter() - t0, tgt


def _prewarm() -> None:
    """AOT-compile the full (batch-bucket x depth-bucket) executable grid.

    Round shapes depend on retire timing (which streams a window catches),
    so no single warm pass covers every shape later passes may seal.  But
    bucket padding (powers of two) makes the whole space enumerable: ~40
    executables, compiled once here, shared by every arm — the same
    warmed-set contract the service replays on restore (DESIGN.md §12/§13).
    """
    from repro.api import warmup

    for b in (1, 2, 4, 8, 16, 32, 64):
        warmup(POLICY, m=M, n=N, batch=b, rank=RANK)
        for k in (2, 4, 8, 16, 32):
            if k <= MAX_DEPTH:
                warmup(POLICY, m=M, n=N, batch=b, rank=RANK, k=k)


def bench_throughput() -> dict:
    arms: dict = {"single": _single}
    for k in SHARD_COUNTS:
        arms[f"fleet{k}"] = lambda k=k: _fleet(k)

    _prewarm()
    # one host-path warm pass per arm (executables are already compiled)
    for make in arms.values():
        _feed_drain(make)

    events = STREAMS * ROUNDS
    best: dict = {name: (float("inf"), None) for name in arms}
    for _ in range(REPEAT):       # interleaved: drift hits all arms equally
        for name, make in arms.items():
            t, tgt = _feed_drain(make)
            if t < best[name][0]:
                best[name] = (t, tgt)

    out = {}
    for name, (t, tgt) in best.items():
        stats = tgt.stats() if hasattr(tgt, "stats") and callable(tgt.stats) \
            else tgt.stats
        out[name] = {
            "seconds": t,
            "events_per_s": events / t,
            "rounds": stats.rounds,
            "scan_rounds": stats.scan_rounds,
            "max_depth": stats.max_depth,
            "max_batch": stats.max_batch,
        }
        emit(f"bench_fleet/throughput/{name}", t * 1e6,
             f"events_per_s={events / t:.0f} rounds={stats.rounds} "
             f"scan_rounds={stats.scan_rounds}")
    ratio = out["fleet8"]["events_per_s"] / out["single"]["events_per_s"]
    out["fleet8_vs_single"] = ratio
    emit("bench_fleet/throughput/fleet8_vs_single",
         best["fleet8"][0] * 1e6, f"speedup={ratio:.2f}x")
    return out


# ---------------------------------------------------------------------------
# 2. enqueue-to-visible latency under Poisson open-loop load
# ---------------------------------------------------------------------------


def _run_open_loop(make, rate_hz: float, *, seed: int) -> dict:
    tgt = make()
    traffic = _traffic(OPEN_EVENTS)
    arrivals = [0.0]
    from benchmarks.common import poisson_arrivals

    arrivals = poisson_arrivals(rate_hz, OPEN_EVENTS, seed=seed)

    is_fleet = isinstance(tgt, SvdFleet)

    def enqueue(ev):
        sid, a, b = ev
        return tgt.enqueue(sid, a, b)

    def tick():
        if is_fleet:
            tgt.pump()
        return tgt.poll() if is_fleet else tgt.take_visible()

    return open_loop(enqueue, tick, tgt.drain, traffic, arrivals)


def bench_latency(single_rate_hz: float) -> dict:
    rate = LOAD * single_rate_hz
    arms = {
        "single_fixed": lambda: _single(max_batch=FIXED_BATCH),
        "fleet8_fixed": lambda: _fleet(8, continuous=False,
                                       max_batch=FIXED_BATCH),
        "fleet8_continuous": lambda: _fleet(8),
    }
    out: dict = {"offered_rate_hz": rate}
    for name, make in arms.items():
        _run_open_loop(make, rate, seed=2)          # warm shapes
        res = _run_open_loop(make, rate, seed=3)    # measured
        out[name] = res
        emit(f"bench_fleet/latency/{name}", res["p99_us"],
             f"p50_us={res['p50_us']:.0f} rate_hz={rate:.0f} "
             f"sustained_hz={res['sustained_rate_hz']:.0f}")
    out["continuous_vs_fixed_p99"] = (
        out["fleet8_fixed"]["p99_us"] / out["fleet8_continuous"]["p99_us"])
    emit("bench_fleet/latency/continuous_vs_fixed",
         out["fleet8_continuous"]["p99_us"],
         f"p99_reduction={out['continuous_vs_fixed_p99']:.2f}x")
    return out


def run() -> dict:
    dev = jax.devices()
    print(f"bench_fleet: platform {dev[0].platform} ({dev[0].device_kind}) "
          f"x{len(dev)}", flush=True)
    # metrics on for every arm (uniform cost, so arm ratios are untouched):
    # per-shard serve_* gauges, fleet_* rollups and the emit() bench_us rows
    # all land in one registry the summary can count.
    obs.enable()
    throughput = bench_throughput()
    latency = bench_latency(throughput["single"]["events_per_s"])
    reg = obs.registry()
    shard_series = sorted({
        dict(m.labels)["shard"] for m in reg.series()
        if "shard" in dict(m.labels)
    })
    obs_block = {
        "series": len(reg.series()),
        "shards_reporting": shard_series,
        "fleet_applied": reg.aggregate("fleet_applied"),
    }
    obs.disable()
    summary = {
        "meta": bench_metadata(),
        "obs": obs_block,
        "m": M, "n": N, "rank": RANK,
        "streams": STREAMS, "rounds": ROUNDS, "max_depth": MAX_DEPTH,
        "open_events": OPEN_EVENTS, "load_fraction": LOAD,
        "fixed_batch": FIXED_BATCH,
        "throughput": throughput,
        "latency": latency,
        "accept": {
            "fleet8_ge_1p5x_single":
                throughput["fleet8_vs_single"] >= 1.5,
            "continuous_p99_below_fixed":
                latency["continuous_vs_fixed_p99"] > 1.0,
        },
    }
    OUT.write_text(json.dumps(summary, indent=2) + "\n")
    print(f"wrote {OUT}")
    return summary


if __name__ == "__main__":
    run()
