"""The fused megakernel route (kernels.fused_update, DESIGN.md §11).

Four layers of pins:

* **numerics** — the fused body against the direct route / a dense f64 SVD,
  across single, batched, truncated, repeated-spectrum and zero-update
  geometries.  Degenerate trailing ``v`` columns (null-space basis for the
  n-m zero singular values) are an arbitrary orthonormal choice across
  differently-compiled paths, so full-update comparisons pin ``v[:, :m]``;
* **dispatch** — ``UpdatePolicy(method="fused")`` and geometry-aware
  ``auto`` resolve to the shared fused engine, including the mesh-sharded
  path on 8 fake devices (subprocess — device count precedes jax init);
* **mixed precision** — bf16 storage stays inside the documented
  ``BF16_ERROR_BUDGET`` against an f64 dense reference, single-shot and
  over an 8-update drift;
* **rank-k scan lowering** — long RankK schedules lower to ONE
  ``("rank1_scan", ...)`` step, trace cost is flat in k, and results match
  the dense reference.
"""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import api
from repro.api import SvdState, UpdatePolicy
from repro.core.engine import SvdEngine, default_engine
from repro.core.svd_update import (
    TruncatedSvd,
    _svd_update_impl,
    _svd_update_truncated_impl,
)
from repro.kernels import fused_update as F
from repro.updates import RankK
from repro.updates import planner

RNG = np.random.default_rng(17)
REPO = Path(__file__).resolve().parent.parent


def _problem(m, n):
    a_mat = RNG.uniform(1, 9, (m, n))
    u, s, vt = np.linalg.svd(a_mat)
    return (jnp.asarray(u), jnp.asarray(s), jnp.asarray(vt.T),
            jnp.asarray(RNG.normal(size=m)), jnp.asarray(RNG.normal(size=n)))


def _dense(u, s, v):
    m, n = u.shape[0], v.shape[0]
    smat = np.zeros((m, n))
    np.fill_diagonal(smat, np.asarray(s)[: min(m, n)])
    return np.asarray(u) @ smat @ np.asarray(v).T


def _close(x, y, atol=1e-9):
    np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=atol)


# ---------------------------------------------------------------------------
# numerics: fused body vs direct route / dense reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,n", [(4, 6), (8, 8), (12, 20), (32, 48)])
def test_fused_full_matches_direct(m, n):
    u, s, v, a, b = _problem(m, n)
    ref = _svd_update_impl(u, s, v, a, b, method="direct")
    out = F.fused_update_xla(u, s, v, a, b)
    _close(out[0], ref.u)
    _close(out[1], ref.s)
    _close(out[2][:, :m], ref.v[:, :m])
    _close(out[3], ref.d_left)
    _close(out[4], ref.d_right)


def test_fused_full_repeated_singular_values():
    m, n = 8, 10
    u = jnp.asarray(np.linalg.qr(RNG.normal(size=(m, m)))[0])
    v = jnp.asarray(np.linalg.qr(RNG.normal(size=(n, n)))[0])
    s = jnp.asarray(np.array([3.0, 3.0, 3.0, 2.0, 1.0, 1.0, 0.5, 0.25]))
    a = jnp.asarray(RNG.normal(size=m))
    b = jnp.asarray(RNG.normal(size=n))
    fu, fs, fv, _, _ = F.fused_update_xla(u, s, v, a, b)
    target = _dense(u, s, v) + np.outer(np.asarray(a), np.asarray(b))
    _close(np.sort(np.asarray(fs))[::-1],
           np.linalg.svd(target, compute_uv=False))
    rec = (np.asarray(fu)[:, :m] * np.asarray(fs)[None, :m]) @ np.asarray(fv)[:, :m].T
    _close(rec, target)


def test_fused_zero_update_is_identityish():
    m, n = 6, 9
    u, s, v, _, b = _problem(m, n)
    fu, fs, fv, _, _ = F.fused_update_xla(u, s, v, jnp.zeros(m), b)
    _close(np.sort(np.asarray(fs))[::-1][:m], np.asarray(s))
    rec = (np.asarray(fu)[:, :m] * np.asarray(fs)[None, :m]) @ np.asarray(fv)[:, :m].T
    _close(rec, _dense(u, s, v))


def test_fused_clustered_spectrum_stays_accurate():
    """Gaps just above the deflation tolerance — the hard bracket case for
    the shortened (16 bisect + 6 Newton) fused secular loop."""
    m, n = 8, 12
    u = jnp.asarray(np.linalg.qr(RNG.normal(size=(m, m)))[0])
    v = jnp.asarray(np.linalg.qr(RNG.normal(size=(n, n)))[0])
    s_np = np.linspace(5.0, 1.0, m)
    s_np[1] = s_np[0] * (1 - 1e-11)
    s_np[3] = s_np[2] * (1 - 1e-9)
    s = jnp.asarray(np.sort(s_np)[::-1].copy())
    a = jnp.asarray(1e-3 * RNG.normal(size=m))
    b = jnp.asarray(RNG.normal(size=n))
    fu, fs, fv, _, _ = F.fused_update_xla(u, s, v, a, b)
    target = _dense(u, s, v) + np.outer(np.asarray(a), np.asarray(b))
    _close(np.sort(np.asarray(fs))[::-1],
           np.linalg.svd(target, compute_uv=False), atol=1e-10)
    rec = (np.asarray(fu)[:, :m] * np.asarray(fs)[None, :m]) @ np.asarray(fv)[:, :m].T
    _close(rec, target, atol=1e-10)


def test_fused_truncated_matches_direct():
    m, n, r = 14, 18, 5
    u = jnp.asarray(np.linalg.qr(RNG.normal(size=(m, r)))[0])
    v = jnp.asarray(np.linalg.qr(RNG.normal(size=(n, r)))[0])
    s = jnp.asarray(np.sort(np.abs(RNG.normal(size=r)))[::-1].copy())
    a = jnp.asarray(RNG.normal(size=m))
    b = jnp.asarray(RNG.normal(size=n))
    ref = _svd_update_truncated_impl(TruncatedSvd(u, s, v), a, b)
    out = F.fused_update_truncated_xla(u, s, v, a, b)
    _close(out[0], ref.u, atol=1e-10)
    _close(out[1], ref.s, atol=1e-10)
    _close(out[2], ref.v, atol=1e-10)


# ---------------------------------------------------------------------------
# the Pallas kernel (interpret mode) agrees with its jnp body
# ---------------------------------------------------------------------------


def test_pallas_interpret_matches_body_full():
    m, n = 6, 9
    u, s, v, a, b = _problem(m, n)
    ref = F.fused_update_xla(u, s, v, a, b)
    out = F.fused_update_pallas(u, s, v, a, b, interpret=True)
    for got, want, name in zip(out, ref, ("u", "s", "v", "dl", "dr")):
        got = got[:, :m] if name == "v" else got
        want = want[:, :m] if name == "v" else want
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-12, err_msg=name)


def test_pallas_interpret_matches_body_truncated():
    m, n, r = 10, 12, 4
    u = jnp.asarray(np.linalg.qr(RNG.normal(size=(m, r)))[0])
    v = jnp.asarray(np.linalg.qr(RNG.normal(size=(n, r)))[0])
    s = jnp.asarray(np.sort(np.abs(RNG.normal(size=r)))[::-1].copy())
    a = jnp.asarray(RNG.normal(size=m))
    b = jnp.asarray(RNG.normal(size=n))
    ref = F.fused_update_truncated_xla(u, s, v, a, b)
    out = F.fused_update_truncated_pallas(u, s, v, a, b, interpret=True)
    for got, want in zip(out, ref):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-12)


def test_pallas_interpret_batched_matches_items():
    b_sz, m, n = 3, 5, 7
    cols = [[] for _ in range(5)]
    for _ in range(b_sz):
        for c, x in zip(cols, _problem(m, n)):
            c.append(x)
    u, s, v, a, bb = (jnp.stack(c) for c in cols)
    out = F.fused_update_pallas_batched(u, s, v, a, bb, interpret=True)
    for i in range(b_sz):
        ref = F.fused_update_xla(u[i], s[i], v[i], a[i], bb[i])
        np.testing.assert_allclose(np.asarray(out[0][i]), np.asarray(ref[0]),
                                   atol=1e-12)
        np.testing.assert_allclose(np.asarray(out[1][i]), np.asarray(ref[1]),
                                   atol=1e-12)
        np.testing.assert_allclose(np.asarray(out[2][i][:, :m]),
                                   np.asarray(ref[2][:, :m]), atol=1e-12)


# ---------------------------------------------------------------------------
# dispatch: engine + api routes
# ---------------------------------------------------------------------------


def test_engine_fused_batch_matches_loop_of_singles():
    b_sz, m, n = 5, 10, 13
    cols = [[] for _ in range(5)]
    for _ in range(b_sz):
        for c, x in zip(cols, _problem(m, n)):
            c.append(x)
    u, s, v, a, bb = (jnp.stack(c) for c in cols)
    eng = SvdEngine(method="fused")
    out = eng.update_batch(u, s, v, a, bb)
    for i in range(b_sz):
        ref = eng.update(u[i], s[i], v[i], a[i], bb[i])
        _close(out.u[i], ref.u, atol=1e-10)
        _close(out.s[i], ref.s, atol=1e-10)
        _close(out.v[i][:, :m], ref.v[:, :m], atol=1e-10)


def test_auto_policy_resolves_to_fused_with_geometry():
    pol = UpdatePolicy()
    assert pol.resolve_method(48, m=32) == "fused"
    # no geometry: the pre-fused auto rule is unchanged
    assert pol.resolve_method(9) == "direct"
    assert pol.resolve_method(256) == "fmm"
    # geometry over the VMEM budget falls back too
    assert pol.resolve_method(4096, m=4096, n=4096) == "fmm"


def test_api_fused_route_is_engine_executable():
    u, s, v, a, b = _problem(12, 16)
    ref = default_engine("fused").update(u, s, v, a, b)
    out = api.update(SvdState.from_factors(u, s, v), a, b,
                     UpdatePolicy(method="fused"))
    for got, want in ((out.u, ref.u), (out.s, ref.s), (out.v, ref.v)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=0)
    # auto + full state geometry resolves to the same fused engine entry
    out2 = api.update(SvdState.from_factors(u, s, v), a, b, UpdatePolicy())
    np.testing.assert_allclose(np.asarray(out2.s), np.asarray(ref.s),
                               rtol=0, atol=0)


def test_fused_supported_boundaries():
    assert F.fused_supported(32, 48)
    assert not F.fused_supported(48, 32)          # full path needs m <= n
    assert F.fused_supported(256, 256, dtype=jnp.float32)
    assert not F.fused_supported(256, 256, dtype=jnp.float64)
    assert not F.fused_supported(2048, 2048)
    # truncated residency depends on k = rank+1, not m*n
    assert F.fused_supported(4096, 4096, rank=15, dtype=jnp.float32)
    assert not F.fused_supported(65536, 65536, rank=255, dtype=jnp.float32)


def test_fused_mesh_route_on_8_devices():
    """UpdatePolicy(method='fused', mesh=...) == the fused engine mesh path
    bitwise, and matches unsharded fused numerics (8 fake CPU devices)."""
    script = textwrap.dedent("""
        import json
        import jax
        jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp, numpy as np
        from repro import api
        from repro.core.engine import default_engine

        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(5)
        B, m, n = 16, 8, 10
        us, ss, vs = [], [], []
        for _ in range(B):
            x = rng.uniform(1, 9, (m, n))
            u, s, vt = np.linalg.svd(x)
            us.append(u); ss.append(s); vs.append(vt.T)
        args = tuple(jnp.asarray(np.stack(x)) for x in (us, ss, vs))
        a = jnp.asarray(rng.normal(size=(B, m)))
        b = jnp.asarray(rng.normal(size=(B, n)))

        eng = default_engine("fused")
        ref = eng.update_batch(*args, a, b, mesh=mesh, batch_axis="data")
        pol = api.UpdatePolicy(method="fused", mesh=mesh, batch_axis="data")
        out = api.update(api.SvdState.from_factors(*args), a, b, pol)
        d_mesh = max(float(jnp.max(jnp.abs(x - y))) for x, y in
                     zip((out.u, out.s, out.v), (ref.u, ref.s, ref.v)))
        local = eng.update_batch(*args, a, b)
        d_num = max(
            float(jnp.max(jnp.abs(out.s - local.s))),
            float(jnp.max(jnp.abs(out.u - local.u))),
            float(jnp.max(jnp.abs(out.v[..., :m] - local.v[..., :m]))),
        )
        print(json.dumps({"devices": jax.device_count(),
                          "d_mesh": d_mesh, "d_num": d_num}))
    """)
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=420,
        env={
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
            "PYTHONPATH": str(REPO / "src"),
            "PATH": "/usr/bin:/bin",
            "JAX_PLATFORMS": "cpu",
            "HOME": "/tmp",
        },
    )
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-4000:]}"
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 8
    assert out["d_mesh"] == 0.0   # same engine cache entry -> bitwise
    assert out["d_num"] < 1e-10


# ---------------------------------------------------------------------------
# mixed precision: bf16 storage inside the documented budget
# ---------------------------------------------------------------------------


def test_bf16_single_update_within_budget():
    m, n = 32, 48
    u, s, v, a, b = _problem(m, n)
    target = _dense(u, s, v) + np.outer(np.asarray(a), np.asarray(b))
    s_ref = np.linalg.svd(target, compute_uv=False)

    pol = UpdatePolicy(method="fused", storage_dtype=jnp.bfloat16)
    out = api.update(SvdState.from_factors(u, s, v), a, b, pol)
    assert out.s.dtype == jnp.bfloat16
    assert out.u.dtype == jnp.bfloat16

    got = np.sort(np.asarray(out.s, dtype=np.float64))[::-1][:m]
    sigma_rel = float(np.max(np.abs(got - s_ref) / s_ref.max()))
    assert sigma_rel < F.BF16_ERROR_BUDGET["sigma_rel"], sigma_rel

    uo = np.asarray(out.u, dtype=np.float64)
    vo = np.asarray(out.v, dtype=np.float64)
    so = np.asarray(out.s, dtype=np.float64)
    rec = (uo[:, :m] * so[None, :m]) @ vo[:, :m].T
    recon_rel = float(np.max(np.abs(rec - target)) / np.abs(target).max())
    assert recon_rel < F.BF16_ERROR_BUDGET["recon_rel"], recon_rel


def test_bf16_drift_within_budget_over_8_updates():
    m, n, k = 32, 48, 8
    u, s, v, _, _ = _problem(m, n)
    target = _dense(u, s, v)
    st = SvdState.from_factors(u, s, v)
    pol = UpdatePolicy(method="fused", storage_dtype=jnp.bfloat16)
    for _ in range(k):
        a = RNG.normal(size=m)
        b = RNG.normal(size=n)
        target = target + np.outer(a, b)
        st = api.update(st, jnp.asarray(a), jnp.asarray(b), pol)
    s_ref = np.linalg.svd(target, compute_uv=False)
    got = np.sort(np.asarray(st.s, dtype=np.float64))[::-1][:m]
    drift = float(np.max(np.abs(got - s_ref) / s_ref.max()))
    assert drift < F.BF16_ERROR_BUDGET["drift_sigma_rel"], drift


# ---------------------------------------------------------------------------
# rank-k scan lowering (updates.planner <-> api.update_rank_k)
# ---------------------------------------------------------------------------


def test_long_rank_k_lowers_to_single_scan_step():
    st = SvdState.from_dense(np.asarray(RNG.normal(size=(6, 8))))
    k_long = planner._SCAN_MIN
    op = RankK(np.zeros((6, k_long)), np.zeros((8, k_long)))
    plan = planner.lower(op, st)
    assert plan == (("rank1_scan", (), "rank_k", k_long),)
    # short runs keep the unrolled per-pair lowering
    op8 = RankK(np.zeros((6, 8)), np.zeros((8, 8)))
    plan8 = planner.lower(op8, st)
    assert len(plan8) == 8 and all(s[0] == "rank1" for s in plan8)


def test_rank_k_scan_matches_dense_reference():
    m, n, k = 6, 8, 20
    x = RNG.normal(size=(m, n))
    uk = RNG.normal(size=(m, k))
    vk = RNG.normal(size=(n, k))
    out = api.apply(SvdState.from_dense(x), RankK(uk, vk),
                    UpdatePolicy(method="direct"))
    ref = np.linalg.svd(x + uk @ vk.T, compute_uv=False)
    _close(np.sort(np.asarray(out.s))[::-1][: min(m, n)], ref)


def test_update_rank_k_truncated_matches_sequential():
    m, n, r, k = 10, 12, 4, 20
    t = TruncatedSvd(
        jnp.asarray(np.linalg.qr(RNG.normal(size=(m, r)))[0]),
        jnp.asarray(np.sort(np.abs(RNG.normal(size=r)))[::-1].copy()),
        jnp.asarray(np.linalg.qr(RNG.normal(size=(n, r)))[0]),
    )
    va = jnp.asarray(RNG.normal(size=(k, m)))
    vb = jnp.asarray(RNG.normal(size=(k, n)))
    pol = UpdatePolicy(method="direct")
    out = api.update_rank_k(api.as_state(t), va, vb, pol)
    st = api.as_state(t)
    for i in range(k):
        st = api.update(st, va[i], vb[i], pol)
    _close(out.s, st.s, atol=1e-9)
    _close(out.u, st.u, atol=1e-8)


def test_rank_k_trace_cost_is_flat_in_k():
    """The scan lowering's point: tracing a k=64 schedule must cost the same
    number of jaxpr equations as k=8 (one scan, k only in the carry)."""
    eng = SvdEngine(method="direct")
    fn = eng._rank_k_fn()

    def n_eqns(k):
        m, n = 6, 8
        args = (jnp.zeros((m, m)), jnp.zeros(m), jnp.zeros((n, n)),
                jnp.zeros((k, m)), jnp.zeros((k, n)))
        return len(jax.make_jaxpr(fn)(*args).jaxpr.eqns)

    assert n_eqns(8) == n_eqns(64)


def test_apply_many_scan_path_matches_apply():
    m, n, k = 5, 7, 18
    xs = [RNG.normal(size=(m, n)) for _ in range(2)]
    ops = [RankK(RNG.normal(size=(m, k)), RNG.normal(size=(n, k)))
           for _ in range(2)]
    pol = UpdatePolicy(method="direct")
    outs = api.apply_many([SvdState.from_dense(x, rank=4) for x in xs], ops, pol)
    for x, op, out in zip(xs, ops, outs):
        ref = api.apply(SvdState.from_dense(x, rank=4), op, pol)
        _close(out.s, ref.s, atol=1e-9)
