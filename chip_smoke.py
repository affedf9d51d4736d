"""Chip smoke test: the streaming SVD service, end to end, on a TPU.

    python chip_smoke.py             # one chip: SvdService, 256 streams
    python chip_smoke.py --chips 4   # SvdFleet(devices="auto"), one shard per chip

One chip: 256 float32 streams, each a rank-32 truncated state at
(m, n) = (4096, 4096) seeded from a rank-8 matrix (~1 MiB per stream, 256 MiB
of factors in HBM).  Each stream receives 16 rank-1 events, one ``Sparse`` op
(nnz 4096, rank 4) and one ``RemoveRows`` op (16 rows), all through
``enqueue``/``enqueue_op``, then ``drain()``.  The true rank never exceeds 28,
so the rank-32 state holds every intermediate exactly and the float64 numpy
reference of a sampled stream is exact, not a truncation.

``--chips 4`` runs only the fleet: the same traffic on a 4-shard
``SvdFleet(devices="auto")``, settled (``settle()``), checked bitwise against
one ``SvdService`` settling the same traffic, with every shard's states on
that shard's own device.

Exits nonzero, printing no result, when JAX finds no TPU or any phase fails.
The last line of standard output is the JSON result.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# The written float32 tolerance (and why it has its value) lives with the
# kernel: repro.kernels.fused_update.F32_ERROR_BUDGET.

STREAMS, M, N, R = 256, 4096, 4096, 32
SEED_RANK, EVENTS = 8, 16
NNZ, SPARSE_RANK, REMOVED = 4096, 4, 16
SAMPLED = 4


def make_traffic(streams, m, n, r, seed=0):
    """Seed states (float32 factors, built in bulk) and every stream's events."""
    rng = np.random.default_rng(seed)
    left = np.concatenate([rng.standard_normal((streams, m, SEED_RANK)),
                           rng.standard_normal((streams, m, r - SEED_RANK))], 2)
    right = np.concatenate([rng.standard_normal((streams, n, SEED_RANK)),
                            rng.standard_normal((streams, n, r - SEED_RANK))], 2)
    qu, ru = np.linalg.qr(left)
    qv, rv = np.linalg.qr(right)
    # A0 = L R^T (rank 8) = Qu (Ru[:, :8] Rv[:, :8]^T) Qv^T: an r x r core SVD
    core = ru[:, :, :SEED_RANK] @ np.swapaxes(rv[:, :, :SEED_RANK], 1, 2)
    x, s, yt = np.linalg.svd(core)
    u = (qu @ x).astype(np.float32)
    v = (qv @ np.swapaxes(yt, 1, 2)).astype(np.float32)
    s = s.astype(np.float32)
    s[:, SEED_RANK:] = 0.0
    a = rng.standard_normal((EVENTS, streams, m)).astype(np.float32)
    b = rng.standard_normal((EVENTS, streams, n)).astype(np.float32)
    sparse = []
    removed = []
    for _ in range(streams):
        rows = rng.choice(m, SPARSE_RANK, replace=False)[rng.integers(0, SPARSE_RANK, NNZ)]
        sparse.append((rows.astype(np.int32), rng.integers(0, n, NNZ).astype(np.int32),
                       rng.standard_normal(NNZ).astype(np.float32)))
        removed.append(tuple(sorted(rng.choice(m, REMOVED, replace=False).tolist())))
    return (u, s, v), (a, b), sparse, removed


def reference(i, seed_state, pairs, sparse, removed):
    """float64 numpy: the sampled stream's matrix after its traffic as exact
    factors ``P @ Q.T`` (rank <= 28), and its singular values."""
    u, s, v = (x[i].astype(np.float64) for x in seed_state)
    a, b = (x[:, i].astype(np.float64) for x in pairs)
    rows, cols, vals = sparse[i]
    hot, slot = np.unique(rows, return_inverse=True)
    w = np.zeros((hot.size, v.shape[0]))
    np.add.at(w, (slot, cols), vals.astype(np.float64))
    e = np.zeros((u.shape[0], hot.size))
    e[hot, np.arange(hot.size)] = 1.0
    p = np.delete(np.concatenate([u * s, a.T, e], 1), list(removed[i]), axis=0)
    q = np.concatenate([v, b.T, w.T], 1)
    core = np.linalg.qr(p)[1] @ np.linalg.qr(q)[1].T
    return p, q, np.linalg.svd(core, compute_uv=False)


def stream_errors(state, p, q, sigma):
    u, s, v = (np.asarray(x, np.float64) for x in (state.u, state.s, state.v))
    mat = p @ q.T
    recon = float(np.linalg.norm((u * s) @ v.T - mat) / np.linalg.norm(mat))
    r = s.shape[0]
    ref = np.zeros(r)
    ref[:min(r, sigma.size)] = sigma[:r]
    sig = float(np.max(np.abs(s - ref)) / sigma[0])
    return recon, sig


def feed(target, ids, pairs, sparse, removed):
    """All traffic through the public enqueue surface, in arrival order."""
    from repro.updates import RemoveRows, Sparse

    a, b = pairs
    for e in range(a.shape[0]):
        for i, sid in enumerate(ids):
            target.enqueue(sid, a[e, i], b[e, i])
    for i, sid in enumerate(ids):
        target.enqueue_op(sid, Sparse(*sparse[i], rank=SPARSE_RANK))
    for i, sid in enumerate(ids):
        target.enqueue_op(sid, RemoveRows(removed[i]))


def check_sampled(states, seed_state, pairs, sparse, removed, sampled):
    worst = (0.0, 0.0)
    for i in sampled:
        recon, sig = stream_errors(states[i], *reference(
            i, seed_state, pairs, sparse, removed))
        print(f"stream {i}: recon_rel={recon:.3e} sigma_rel={sig:.3e}", flush=True)
        worst = (max(worst[0], recon), max(worst[1], sig))
    from repro.kernels.fused_update import F32_ERROR_BUDGET as budget

    if not (worst[0] <= budget["recon_rel"] and worst[1] <= budget["sigma_rel"]):
        raise AssertionError(f"f32 error {worst} outside the budget {budget}")
    return worst


def run_service(streams=STREAMS, m=M, n=N, r=R):
    import jax
    import jax.numpy as jnp

    from repro.api import SvdState, UpdatePolicy, engine_for
    from repro.serve import SvdService

    t0 = time.perf_counter()
    seed_state, pairs, sparse, removed = make_traffic(streams, m, n, r)
    print(f"setup: traffic generated in {time.perf_counter() - t0:.1f} s", flush=True)

    # the engine a flush round of these streams dispatches on
    policy = UpdatePolicy()
    u, s, v = seed_state
    eng = engine_for(policy, SvdState.from_factors(u[0], s[0], v[0]))
    print(f"route: truncated ({m}, {n}, r={r}) float32 -> {eng.method}; "
          f"RemoveRows -> row drop + QR/SVD refactor (no engine step)", flush=True)
    t0 = time.perf_counter()
    hlo = eng.aot_compiled(batch=streams, m=m, n=n, rank=r,
                           dtype=jnp.float32).as_text()
    print(f"compile: flush executable (B={streams}) in "
          f"{time.perf_counter() - t0:.1f} s; tpu_custom_call in HLO: "
          f"{'tpu_custom_call' in hlo}", flush=True)
    if jax.default_backend() == "tpu" and (
            eng.method != "fused" or "tpu_custom_call" not in hlo):
        raise AssertionError("the flush does not run the fused Pallas kernel")

    svc = SvdService(policy=policy, max_batch=streams)
    ids = [f"s{i}" for i in range(streams)]
    for i, sid in enumerate(ids):
        svc.register(sid, SvdState.from_factors(u[i], s[i], v[i]))
    t0 = time.perf_counter()
    feed(svc, ids, pairs, sparse, removed)
    svc.drain()
    print(f"traffic: {svc.stats.applied} events applied, {svc.stats.rounds} batched "
          f"rounds, max batch {svc.stats.max_batch}, "
          f"{time.perf_counter() - t0:.1f} s including compiles", flush=True)

    sampled = np.linspace(0, streams - 1, SAMPLED).astype(int)
    states = {i: svc.state(ids[i]) for i in sampled}
    for st in states.values():
        if st.u.shape != (m - REMOVED, r) or st.u.dtype != jnp.float32:
            raise AssertionError(f"unexpected state {st.u.shape} {st.u.dtype}")
        if not all(bool(jnp.all(jnp.isfinite(x))) for x in (st.u, st.s, st.v)):
            raise AssertionError("non-finite factors")
    check_sampled(states, seed_state, pairs, sparse, removed, sampled)


def run_fleet(streams=STREAMS, m=M, n=N, r=R, shards=4):
    import jax

    from repro.api import SvdState, UpdatePolicy
    from repro.fleet import SvdFleet
    from repro.serve import SvdService

    seed_state, pairs, sparse, removed = make_traffic(streams, m, n, r)
    policy = UpdatePolicy()
    ids = [f"s{i}" for i in range(streams)]
    # nothing autoflushes: every event waits for settle()
    fleet = SvdFleet(shards, policy=policy, devices="auto", continuous=False,
                     max_batch=streams + 1)
    one = SvdService(policy=policy, max_batch=streams + 1)
    u, s, v = seed_state
    for target in (fleet, one):
        for i, sid in enumerate(ids):
            target.register(sid, SvdState.from_factors(u[i], s[i], v[i]))
        feed(target, ids, pairs, sparse, removed)

    t0 = time.perf_counter()
    got = fleet.settle(ids)
    jax.block_until_ready(got)
    print(f"fleet: {shards} shards settled {streams} streams in "
          f"{time.perf_counter() - t0:.1f} s including compiles", flush=True)
    want = one.settle(ids)
    for sh in fleet.shards:
        members = [i for i, sid in enumerate(ids) if fleet.shard_of(sid) == sh.index]
        on = {d for i in members for x in (got[i].u, got[i].s, got[i].v)
              for d in x.devices()}
        print(f"shard {sh.index}: {len(members)} streams on {sorted(map(str, on))}",
              flush=True)
        if on != {sh.device}:
            raise AssertionError(f"shard {sh.index} states on {on}, not {sh.device}")
    if len({sh.device for sh in fleet.shards}) != shards:
        raise AssertionError("shards share a device")
    for i in range(streams):
        for x, y in zip((got[i].u, got[i].s, got[i].v), (want[i].u, want[i].s, want[i].v)):
            if not np.array_equal(np.asarray(x), np.asarray(y)):
                raise AssertionError(f"stream {i}: fleet != one service (settle)")
    print("fleet == one service, bitwise, on every stream", flush=True)
    sampled = np.linspace(0, streams - 1, SAMPLED).astype(int)
    check_sampled({i: got[i] for i in sampled}, seed_state, pairs, sparse,
                  removed, sampled)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs; "
              f"found {len(devices)}", file=sys.stderr)
        return 2

    from repro.api import enable_compilation_cache

    enable_compilation_cache(ROOT / ".jax_cache")   # JAX_COMPILATION_CACHE_DIR wins
    print(f"device: {devices[0].device_kind} x{len(devices)}", flush=True)
    if args.chips == 4:
        run_fleet()
    else:
        run_service()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
