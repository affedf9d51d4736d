"""The network-traffic PCA deployment (``netpca-1008x41-r8-s1024``) on the
CPU: rank-deficient sliding windows (row rank 4 in a rank-8 state) through
the normal path, judged by the harness's check against the float64 slot
reference, and a run of its cell at a reduced size reading ``correct``."""

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402

from bench import harness, traffic  # noqa: E402
from bench import spec as bench_spec  # noqa: E402

SEED = 2**32 + 23
CELL = "netpca-1008x41-r8-s1024.backlog"
CONFIG = json.loads((ROOT / "bench/configs/netpca-1008x41-r8-s1024.json").read_text())


def _small(streams: int, m: int) -> dict:
    return dict(CONFIG, streams=streams, m=m, sample_streams=streams,
                service=dict(CONFIG["service"], max_batch=streams))


def test_fleet_keeps_rank_deficient_windows_on_the_reference():
    config = _small(8, 64)
    with jax.default_matmul_precision(config["matmul_precision"]):
        gen = traffic.event_model(config, SEED)
        fleet = harness.build_fleet(config)
        ids = harness.register_streams(fleet, *gen.device_init())
        for i in range(config["streams"]):
            a, b = gen.next(i, 16)
            for t in range(len(a)):
                fleet.enqueue(ids[i], a[t], b[t])
        fleet.pump()
        fleet.drain()
        fleet.poll()
    ok, checks, rows = harness.check(harness.program_state(fleet, ids), gen, config,
                                     list(range(config["streams"])), 0)
    assert ok, checks
    assert all(r["sigma_rel"] <= 1e-5 and r["recon_rel"] <= 1e-5 for r in rows), rows


def test_cell_at_reduced_size_is_correct():
    spec = bench_spec.cell_spec(CELL, ROOT)
    spec["config"] = _small(4, 128)
    res = harness.run_cell(spec, SEED, 2.0, False, jax.devices()[:1], time.perf_counter())
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
