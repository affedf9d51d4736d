import os
import sys

# ``--cpu-mesh`` asks for the 8-fake-device CPU rehearsal; it must be set
# before jax initializes (jax locks the device count).  Without it the bench
# runs on the platform JAX finds, and says which.
if "--cpu-mesh" in sys.argv:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"

"""Distributed-layer benchmarks on 8 fake CPU devices (DESIGN.md §7).

The block above MUST stay first: jax locks the device count on first
init (same contract as launch/dryrun.py).

1. **Sharded vs single-device batched updates** — B stacked truncated rank-1
   updates through ``SvdEngine.update_truncated_batch`` with and without the
   ``mesh=`` shard_map dispatch.  (Fake CPU devices share one physical core,
   so this measures dispatch overhead + correctness of the path, not real
   parallel speedup; on a real mesh each device runs B/8 updates.)

2. **Bytes on the wire: compressed vs dense all-reduce** — the dense DP
   gradient pmean against ``optim.compression.compress_decompress`` under
   shard_map, both analytically (``dist.collectives.factor_wire_bytes``) and
   measured from the compiled HLO (``launch.roofline.collective_bytes``):
   the compressed path must move only O((m+n)·r) per layer.

CSV rows (benchmarks/run.py style) + benchmarks/BENCH_dist.json.
"""

import json
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from benchmarks.common import emit, time_fn
from repro.api import SvdState
from repro.core.engine import SvdEngine
from repro.core.svd_update import TruncatedSvd
from repro.dist.collectives import factor_wire_bytes
from repro.launch.mesh import auto_mesh
from repro.launch.roofline import collective_bytes
from repro.optim.compression import (
    CompressionState,
    compress_decompress,
    compression_init,
)

BATCHES = [8, 32, 64]
M, N, RANK = 32, 48, 8
GRAD_M, GRAD_N, GRAD_RANK = 256, 512, 8   # compressed-allreduce layer geometry

OUT = Path(__file__).parent / "BENCH_dist.json"


def _trunc_problem(rng, b):
    us = np.stack([np.linalg.qr(rng.normal(size=(M, RANK)))[0] for _ in range(b)])
    vs = np.stack([np.linalg.qr(rng.normal(size=(N, RANK)))[0] for _ in range(b)])
    ss = np.sort(np.abs(rng.normal(size=(b, RANK))), axis=1)[:, ::-1].copy()
    t = TruncatedSvd(jnp.asarray(us), jnp.asarray(ss), jnp.asarray(vs))
    return t, jnp.asarray(rng.normal(size=(b, M))), jnp.asarray(rng.normal(size=(b, N)))


def bench_sharded_updates(mesh) -> list[dict]:
    rng = np.random.default_rng(0)
    engine = SvdEngine(method="direct")
    rows = []
    for b in BATCHES:
        t, a, bb = _trunc_problem(rng, b)

        us_single = time_fn(lambda t, a, bb: engine.update_truncated_batch(t, a, bb).s,
                            t, a, bb)
        us_shard = time_fn(
            lambda t, a, bb: engine.update_truncated_batch(
                t, a, bb, mesh=mesh, batch_axis="data").s,
            t, a, bb,
        )
        row = {
            "kind": "trunc_batch", "B": b, "m": M, "n": N, "rank": RANK,
            "single_us": us_single, "sharded_us": us_shard,
            "sharded_over_single": us_shard / us_single,
            "devices": jax.device_count(),
        }
        rows.append(row)
        emit(f"bench_dist/trunc/B={b}/single", us_single,
             f"updates_per_s={b / us_single * 1e6:.0f}")
        emit(f"bench_dist/trunc/B={b}/sharded8", us_shard,
             f"updates_per_s={b / us_shard * 1e6:.0f} ratio={row['sharded_over_single']:.2f}")
    return rows


def _hlo_collective_bytes(jitted, *args) -> dict:
    return collective_bytes(jax.jit(jitted).lower(*args).compile().as_text(),
                            jax.device_count())


def bench_wire(mesh) -> dict:
    m, n, r = GRAD_M, GRAD_N, GRAD_RANK
    n_dev = jax.device_count()
    rng = np.random.default_rng(1)
    g_all = jnp.asarray(rng.normal(size=(n_dev, m, n)), jnp.float32)
    state = compression_init(jax.random.PRNGKey(0), m, n, r)

    def dense(g):
        return jax.lax.pmean(g, "data")

    def compressed(g_local, st):
        g_hat, st2 = compress_decompress(st, g_local[0], axis_name="data")
        return g_hat[None], st2._replace(error=st2.error[None])

    dense_fn = shard_map(dense, mesh=mesh, in_specs=(P("data"),), out_specs=P("data"))
    comp_fn = shard_map(
        compressed, mesh=mesh,
        in_specs=(P("data"), P()),
        out_specs=(P("data"), CompressionState(
            v_basis=P(), error=P("data"), tracker=SvdState(P(), P(), P()))),
    )

    hlo_dense = _hlo_collective_bytes(dense_fn, g_all)
    hlo_comp = _hlo_collective_bytes(comp_fn, g_all, state)
    analytic = factor_wire_bytes(m, n, r, n_workers=n_dev)

    dense_bytes = sum(v for k, v in hlo_dense.items() if k != "count")
    comp_bytes = sum(v for k, v in hlo_comp.items() if k != "count")
    result = {
        "layer": {"m": m, "n": n, "rank": r},
        "analytic": analytic,
        "hlo_dense_bytes_per_device": dense_bytes,
        "hlo_compressed_bytes_per_device": comp_bytes,
        "hlo_ratio": dense_bytes / comp_bytes if comp_bytes else None,
        "hlo_detail": {"dense": hlo_dense, "compressed": hlo_comp},
    }
    emit("bench_dist/wire/dense", 0.0, f"bytes={dense_bytes:.0f}")
    emit("bench_dist/wire/compressed", 0.0,
         f"bytes={comp_bytes:.0f} ratio={result['hlo_ratio']:.1f} "
         f"analytic_ratio={analytic['ratio']:.1f}")
    return result


def run() -> dict:
    dev = jax.devices()
    print(f"bench_dist: platform {dev[0].platform} ({dev[0].device_kind}) "
          f"x{len(dev)}", flush=True)
    mesh = auto_mesh((jax.device_count(),), ("data",))
    summary = {
        "devices": jax.device_count(),
        "sharded_updates": bench_sharded_updates(mesh),
        "wire": bench_wire(mesh),
    }
    OUT.write_text(json.dumps(summary, indent=2))
    print(f"wrote {OUT}", flush=True)
    return summary


if __name__ == "__main__":
    jax.config.update("jax_enable_x64", True)
    run()
