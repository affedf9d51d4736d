"""Distributed-semantics tests on 8 fake CPU devices (subprocess: the device
count must be forced before jax initializes, and only for these tests)."""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run(code: str) -> dict:
    script = textwrap.dedent(code)
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=420,
        env={
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
            "PYTHONPATH": str(REPO / "src"),
            "PATH": "/usr/bin:/bin",
            "JAX_PLATFORMS": "cpu",
            "HOME": "/tmp",
        },
    )
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-4000:]}"
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sharded_train_step_matches_single_device():
    """One sharded train step on a 4x2 mesh == the unsharded step."""
    out = _run("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro import configs
        from repro.models.registry import build_model
        from repro.dist import sharding as sh
        from repro.optim.adamw import adamw_init, adamw_update, AdamWState
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = configs.get_smoke("nemotron-4-15b").replace(vocab_pad_to=16)
        api = build_model(cfg)
        params = api.init(jax.random.PRNGKey(0))
        opt = adamw_init(params)
        rng = np.random.default_rng(0)
        batch = {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)), jnp.int32),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)), jnp.int32),
        }

        def step(params, opt, batch):
            loss, grads = jax.value_and_grad(api.train_loss)(params, batch)
            p2, o2, g = adamw_update(grads, opt, params, lr=1e-3)
            return p2, o2, loss

        p_ref, o_ref, loss_ref = jax.jit(step)(params, opt, batch)

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        p_specs = sh.param_pspecs(params)
        b_specs = sh.batch_pspecs(batch, multi_pod=False)
        ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                    is_leaf=lambda x: isinstance(x, P))
        o_specs = AdamWState(step=P(), m=p_specs, v=p_specs)
        with mesh:
            p_sh, o_sh, loss_sh = jax.jit(
                step, in_shardings=(ns(p_specs), ns(o_specs), ns(b_specs))
            )(params, opt, batch)

        dl = abs(float(loss_ref) - float(loss_sh))
        dp = max(float(jnp.max(jnp.abs(a - b)))
                 for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_sh)))
        print(json.dumps({"dloss": dl, "dparams": dp,
                          "devices": jax.device_count()}))
    """)
    assert out["devices"] == 8
    assert out["dloss"] < 1e-5
    assert out["dparams"] < 1e-4


def test_compressed_allreduce_under_shard_map():
    """Compressed DP all-reduce == dense pmean for rank<r gradients, and the
    HLO carries only the small factors across the wire."""
    out = _run("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.optim.compression import (CompressionState, compression_init,
                                             compress_decompress)
        from repro.api import SvdState

        mesh = jax.make_mesh((8,), ("data",))
        m, n, r = 16, 12, 4
        rng = np.random.default_rng(0)
        # per-shard gradients share a rank-2 structure + shard-specific coeffs
        u = rng.normal(size=(m, 2)); v = rng.normal(size=(n, 2))
        coeffs = rng.normal(size=(8, 2, 2))
        g_all = jnp.asarray(np.stack([u @ c @ v.T for c in coeffs]))  # (8, m, n)
        state = compression_init(jax.random.PRNGKey(0), m, n, r)

        def body(g_local, state):
            g_hat, st2 = compress_decompress(state, g_local[0], axis_name="data")
            # the error-feedback buffer is PER-WORKER (local residual); the
            # basis and tracker are replicated (built from psum'd factors)
            return g_hat[None], st2._replace(error=st2.error[None])

        out_state_specs = CompressionState(
            v_basis=P(), error=P("data"),
            tracker=SvdState(P(), P(), P()),   # api-era tracker container
        )
        fn = shard_map(body, mesh=mesh,
                       in_specs=(P("data"), P()),
                       out_specs=(P("data"), out_state_specs))
        g_hat, st = jax.jit(fn)(g_all, state)
        dense_mean = np.mean(np.asarray(g_all), axis=0)
        got = np.asarray(g_hat)[0]  # pmean'd: every shard holds the mean
        rel = float(np.linalg.norm(got - dense_mean) / np.linalg.norm(dense_mean))
        print(json.dumps({"rel": rel, "err_shape": list(st.error.shape)}))
    """)
    assert out["rel"] < 1e-4


def test_sharded_engine_batch_matches_single_device():
    """SvdEngine mesh dispatch: batched updates sharded over an 8-device
    fake mesh == the single-device batched result (auto-padded B)."""
    out = _run("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.engine import SvdEngine
        from repro.core.svd_update import TruncatedSvd

        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        B, m, n, r = 12, 8, 10, 4   # B % 8 != 0: exercises auto-pad
        u = np.stack([np.linalg.qr(rng.normal(size=(m, m)))[0] for _ in range(B)])
        v = np.stack([np.linalg.qr(rng.normal(size=(n, n)))[0] for _ in range(B)])
        s = np.abs(rng.normal(size=(B, m)))
        a = rng.normal(size=(B, m)); b = rng.normal(size=(B, n))
        args = tuple(jnp.asarray(x) for x in (u, s, v, a, b))

        eng = SvdEngine(method="direct")
        ref = eng.update_batch(*args)
        shd = eng.update_batch(*args, mesh=mesh, batch_axis="data")
        d_full = max(float(jnp.max(jnp.abs(x - y)))
                     for x, y in zip(jax.tree.leaves(ref), jax.tree.leaves(shd)))

        t = TruncatedSvd(args[0][:, :, :r], args[1][:, :r], args[2][:, :, :r])
        ref_t = eng.update_truncated_batch(t, args[3], args[4])
        shd_t = eng.update_truncated_batch(t, args[3], args[4],
                                           mesh=mesh, batch_axis="data")
        d_tr = max(float(jnp.max(jnp.abs(x - y)))
                   for x, y in zip(jax.tree.leaves(ref_t), jax.tree.leaves(shd_t)))
        print(json.dumps({"d_full": d_full, "d_trunc": d_tr,
                          "b_out": int(shd.u.shape[0]),
                          "devices": jax.device_count()}))
    """)
    assert out["devices"] == 8
    assert out["b_out"] == 12          # padding sliced off
    assert out["d_full"] <= 1e-4
    assert out["d_trunc"] <= 1e-4


def test_distributed_merge_and_basis_agreement():
    """dist.merge.distributed_merge under shard_map: 8 per-worker trackers
    all_gather their small factors and every worker reconstructs the SVD of
    the row-stacked matrix; compression.agree_basis lands the consensus V."""
    out = _run("""
        import json
        import jax
        jax.config.update("jax_enable_x64", True)  # suite-wide numerics default
        import jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.core.svd_update import TruncatedSvd
        from repro.dist.merge import distributed_merge

        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        m, n, r = 10, 12, 4
        M = rng.normal(size=(8 * m, 3)) @ rng.normal(size=(n, 3)).T  # rank 3

        us, ss, vs = [], [], []
        for w in range(8):
            uu, sv, vt = np.linalg.svd(M[w*m:(w+1)*m], full_matrices=False)
            us.append(uu[:, :r]); ss.append(sv[:r]); vs.append(vt[:r].T)
        local = TruncatedSvd(jnp.asarray(np.stack(us)), jnp.asarray(np.stack(ss)),
                             jnp.asarray(np.stack(vs)))

        def body(t):
            # every worker returns the SAME merged (8m, r) factors — the
            # all_gather inside distributed_merge is the only wire traffic
            return distributed_merge(jax.tree.map(lambda x: x[0], t), "data")

        fn = shard_map(body, mesh=mesh,
                       in_specs=(TruncatedSvd(P("data"), P("data"), P("data")),),
                       out_specs=TruncatedSvd(P(), P(), P()),
                       check_vma=False)
        merged = jax.jit(fn)(local)
        rec = (np.asarray(merged.u) * np.asarray(merged.s)) @ np.asarray(merged.v).T
        uu, sv, vt = np.linalg.svd(M)
        opt = (uu[:, :r] * sv[:r]) @ vt[:r]
        err = float(np.abs(rec - opt).max())

        # --- agree_basis: the consumer path. Per-worker CompressionStates
        # whose trackers hold the shard SVDs; after agreement every worker's
        # v_basis is the consensus right basis and its tracker is an
        # orthonormal truncated SVD of its OWN row block of the consensus.
        from repro.optim.compression import CompressionState, agree_basis, compression_init

        st0 = compression_init(jax.random.PRNGKey(0), m, n, r)
        states = CompressionState(
            v_basis=jnp.broadcast_to(st0.v_basis, (8, n, r)),
            error=jnp.zeros((8, m, n)),
            tracker=local,
        )

        def agree_body(st):
            out = agree_basis(jax.tree.map(lambda x: x[0], st), axis_name="data")
            return jax.tree.map(lambda x: x[None], out)

        per_worker = CompressionState(v_basis=P("data"), error=P("data"),
                                      tracker=TruncatedSvd(P("data"), P("data"), P("data")))
        agreed = jax.jit(shard_map(agree_body, mesh=mesh,
                                   in_specs=(per_worker,), out_specs=per_worker,
                                   check_vma=False))(states)
        # consensus: every worker holds the same v_basis (merged right basis)
        vb = np.asarray(agreed.v_basis)
        v_spread = float(np.abs(vb - vb[0]).max())
        # invariant: every worker's tracker.u is orthonormal again
        tu = np.asarray(agreed.tracker.u)
        orth = max(float(np.abs(tu[w].T @ tu[w] - np.eye(r)).max()) for w in range(8))
        # each tracker reconstructs its own row block of the global rank-r SVD
        block = max(
            float(np.abs((tu[w] * np.asarray(agreed.tracker.s)[w])
                         @ np.asarray(agreed.tracker.v)[w].T
                         - opt[w*m:(w+1)*m]).max())
            for w in range(8)
        )
        print(json.dumps({"err": err, "shape": list(merged.u.shape),
                          "v_spread": v_spread, "orth": orth, "block": block}))
    """)
    assert out["err"] < 1e-4
    assert out["shape"] == [80, 4]
    assert out["v_spread"] < 1e-8
    assert out["orth"] < 1e-8
    assert out["block"] < 1e-4


def test_param_specs_cover_all_archs():
    """Every arch's full-size param tree gets divisibility-consistent specs
    on the production mesh (the dry-run precondition)."""
    out = _run("""
        import json
        import jax
        from repro import configs
        from repro.models.registry import build_model
        from repro.dist import sharding as sh

        bad = []
        for arch in configs.ARCH_IDS:
            cfg = configs.get(arch)
            api = build_model(cfg)
            shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))
            specs = sh.param_pspecs(shapes)
            flat_s, _ = jax.tree_util.tree_flatten_with_path(shapes)
            flat_p = jax.tree.leaves(specs, is_leaf=lambda x: hasattr(x, "_cls") or True)
            flat_p = jax.tree_util.tree_flatten_with_path(
                specs, is_leaf=lambda x: isinstance(x, type(jax.sharding.PartitionSpec()))
            )[0]
            mesh_size = {"data": 16, "model": 16}
            for (path, shape), (_, spec) in zip(flat_s, flat_p):
                for dim, ax in zip(shape.shape, tuple(spec) + (None,) * 10):
                    if ax is None:
                        continue
                    axes = ax if isinstance(ax, tuple) else (ax,)
                    total = 1
                    for a in axes:
                        total *= mesh_size[a]
                    if dim % total:
                        bad.append([arch, jax.tree_util.keystr(path), dim, str(ax)])
        print(json.dumps({"bad": bad}))
    """)
    assert out["bad"] == [], out["bad"]
