"""The float32 contract of the main path, pinned on the CPU at small size.

A TPU runs the update in float32, so the routes ``method="auto"`` picks must
hold ``F32_ERROR_BUDGET`` against a float64 numpy reference.  The suite runs
with x64 on: every array here is made float32 explicitly.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro import api
from repro.api import SvdState, UpdatePolicy
from repro.kernels.fused_update import F32_ERROR_BUDGET
from repro.updates import Compose, RemoveCols, RemoveRows

F32 = np.float32


def _errors(state, ref, rank):
    u, s, v = (np.asarray(x, np.float64) for x in (state.u, state.s, state.v))
    k = s.shape[0]
    recon = np.linalg.norm((u * s) @ v[:, :k].T - ref) / np.linalg.norm(ref)
    sigma = np.linalg.svd(ref, compute_uv=False)
    want = np.zeros(k)
    want[:min(k, rank)] = sigma[:min(k, rank)]
    return recon, np.max(np.abs(s - want)) / sigma[0]


def _assert_budget(state, ref, rank):
    assert state.s.dtype == jnp.float32
    recon, sig = _errors(state, ref, rank)
    assert recon <= F32_ERROR_BUDGET["recon_rel"], recon
    assert sig <= F32_ERROR_BUDGET["sigma_rel"], sig


@pytest.mark.parametrize("full", [True, False], ids=["full", "truncated"])
def test_auto_route_holds_f32_budget_rank_budgeted(full):
    rng = np.random.default_rng(0)
    m, n, seed_rank, events = (48, 64, 6, 6) if full else (160, 128, 6, 12)
    ref = rng.normal(size=(m, seed_rank)) @ rng.normal(size=(seed_rank, n))
    state = (SvdState.from_dense(ref.astype(F32)) if full
             else SvdState.from_dense(ref.astype(F32), rank=24))
    policy = UpdatePolicy()
    assert api.engine_for(policy, state).method == "fused"
    for _ in range(events):
        a, b = rng.normal(size=m).astype(F32), rng.normal(size=n).astype(F32)
        state = api.update(state, a, b, policy)
        ref = ref + np.outer(a, b).astype(np.float64)
    _assert_budget(state, ref, seed_rank + events)


def test_f32_downdate_onto_degenerate_spectrum():
    # repeated singular values (3, 3, 3, 2, 2, 1) plus exact zeros: the
    # untouched directions of a delete keep them, a structurally
    # degenerate spectrum the update must not mis-pair
    rng = np.random.default_rng(1)
    m, n, r = 40, 30, 10
    qu = np.linalg.qr(rng.normal(size=(m, r)))[0]
    qv = np.linalg.qr(rng.normal(size=(n, r)))[0]
    s = np.array([3, 3, 3, 2, 2, 1, 0, 0, 0, 0], np.float64)
    ref = (qu * s) @ qv.T
    state = SvdState.from_factors(qu.astype(F32), s.astype(F32), qv.astype(F32))
    op = Compose((RemoveRows((0, 7, 19)), RemoveCols((2, 5))))
    out = api.apply(state, op, UpdatePolicy())
    assert out.u.shape == (m - 3, r) and out.v.shape == (n - 2, r)
    _assert_budget(out, np.asarray(op.apply_dense(ref)), 6)


def test_auto_never_resolves_f32_to_fmm():
    policy = UpdatePolicy()
    # above the FMM tree floor and beyond the fused budget
    m, n = 400, 500
    for dt, want in ((np.float32, "direct"), (np.float64, "fmm")):
        assert policy.resolve_method(n, m=m, n=n, dtype=dt) == want
        st = SvdState.from_factors(np.eye(m, dtype=dt), np.ones(m, dt),
                                   np.eye(n, dtype=dt))
        assert api.engine_for(policy, st).method == want
    # bf16 storage computes in f32: no fmm either
    assert policy.replace(storage_dtype=jnp.bfloat16).resolve_method(
        n, m=m, n=n, dtype=np.float32) == "direct"
