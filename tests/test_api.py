"""``repro.api`` surface stability: __all__ snapshot, SvdState/UpdatePolicy
semantics, and policy-keyed plan-cache folding (zero recompiles across
policy-equal calls)."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import api
from repro.api import SvdState, UpdatePolicy

RNG = np.random.default_rng(7)

# The public surface the next PRs build on — additions require updating this
# snapshot deliberately; removals/renames are API breaks.
API_SURFACE = [
    "METHODS",
    "SvdState",
    "UpdatePolicy",
    "apply",          # structured perturbations (repro.updates, DESIGN §10)
    "apply_many",
    "as_state",
    "compilation_cache_entries",  # persistent-warmup observability (DESIGN §13)
    "enable_compilation_cache",   # cross-process AOT warmup (DESIGN §13)
    "engine_for",
    "update",
    "update_many",
    "update_rank_k",  # scan-lowered rank-k schedules (DESIGN §11)
    "warmup",
]


def _full_state(m, n):
    a_mat = RNG.uniform(1, 9, (m, n))
    u, s, vt = np.linalg.svd(a_mat)
    return SvdState.from_factors(u, s, vt.T)


def _trunc_state(m, n, r):
    return SvdState.from_factors(
        np.linalg.qr(RNG.normal(size=(m, r)))[0],
        np.sort(np.abs(RNG.normal(size=r)))[::-1].copy(),
        np.linalg.qr(RNG.normal(size=(n, r)))[0],
    )


# ---------------------------------------------------------------------------
# surface snapshot
# ---------------------------------------------------------------------------


def test_api_all_snapshot():
    assert sorted(api.__all__) == API_SURFACE
    for name in api.__all__:
        assert getattr(api, name) is not None


# ---------------------------------------------------------------------------
# SvdState
# ---------------------------------------------------------------------------


def test_state_full_vs_truncated_geometry():
    full = _full_state(8, 10)
    assert full.is_full and not full.is_batched
    assert (full.m, full.n, full.rank) == (8, 10, 8)
    tr = _trunc_state(8, 10, 3)
    assert not tr.is_full
    assert tr.geometry != full.geometry

    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), tr, _trunc_state(8, 10, 3))
    assert stacked.is_batched and stacked.batch == 2


def test_state_from_dense_and_materialize():
    a_mat = RNG.uniform(1, 9, (6, 9))
    full = SvdState.from_dense(a_mat)
    np.testing.assert_allclose(np.asarray(full.materialize()), a_mat, atol=1e-9)
    tr = SvdState.from_dense(a_mat, rank=2)
    assert tr.rank == 2 and not tr.is_full
    # best rank-2 approximation
    u, s, vt = np.linalg.svd(a_mat)
    opt = (u[:, :2] * s[:2]) @ vt[:2]
    np.testing.assert_allclose(np.asarray(tr.materialize()), opt, atol=1e-9)
    with pytest.raises(ValueError, match="m <= n"):
        SvdState.from_dense(a_mat.T)
    with pytest.raises(ValueError, match="rank"):
        SvdState.from_dense(a_mat, rank=7)


def test_state_truncate_and_immutability():
    full = _full_state(8, 10)
    tr = full.truncate(3)
    assert tr.rank == 3 and tr.u.shape == (8, 3) and tr.v.shape == (10, 3)
    with pytest.raises(ValueError, match="truncate"):
        tr.truncate(5)
    with pytest.raises(dataclasses.FrozenInstanceError):
        full.s = jnp.zeros(8)


def test_as_state_coercions():
    from repro.core.svd_update import TruncatedSvd

    tr = _trunc_state(8, 10, 3)
    legacy = TruncatedSvd(tr.u, tr.s, tr.v)
    st = api.as_state(legacy)
    assert isinstance(st, SvdState)
    assert st.u is legacy.u
    assert api.as_state(st) is st
    st2 = api.as_state((tr.u, tr.s, tr.v))
    assert st2.rank == 3


def test_state_is_pytree_with_three_leaves():
    """Diagnostics-free SvdState must keep TruncatedSvd's leaf count, so
    existing stacked/sharded tree code keeps working."""
    tr = _trunc_state(8, 10, 3)
    assert len(jax.tree.leaves(tr)) == 3
    mapped = jax.tree.map(lambda x: x * 2, tr)
    assert isinstance(mapped, SvdState)


# ---------------------------------------------------------------------------
# UpdatePolicy
# ---------------------------------------------------------------------------


def test_policy_frozen_hashable_equal():
    p1 = UpdatePolicy(method="fmm", fmm_p=24)
    p2 = UpdatePolicy(method="fmm", fmm_p=24)
    assert p1 == p2 and hash(p1) == hash(p2)
    assert len({p1: 1, p2: 2}) == 1
    assert p1 != UpdatePolicy(method="fmm", fmm_p=25)
    with pytest.raises(dataclasses.FrozenInstanceError):
        p1.method = "direct"
    assert p1.replace(method="direct").method == "direct"


def test_policy_validation_and_resolution():
    with pytest.raises(ValueError, match="unknown method"):
        UpdatePolicy(method="magic")
    with pytest.raises(ValueError, match="truncate_to"):
        UpdatePolicy(truncate_to=0)
    assert UpdatePolicy(method="pallas").resolve_method(64) == "kernel"
    assert UpdatePolicy(method="auto").resolve_method(8) == "direct"
    assert UpdatePolicy(method="auto").resolve_method(128) == "fmm"
    with pytest.raises(NotImplementedError, match="benchmark"):
        UpdatePolicy(method="fast").resolve_method(8)


def test_policy_truncation_rule():
    full = _full_state(8, 10)
    a = jnp.asarray(RNG.normal(size=8))
    b = jnp.asarray(RNG.normal(size=10))
    out = api.update(full, a, b, UpdatePolicy(method="direct", truncate_to=3))
    assert out.rank == 3 and not out.is_full
    ref = api.update(full, a, b, UpdatePolicy(method="direct"))
    np.testing.assert_allclose(np.asarray(out.s), np.asarray(ref.s[:3]), rtol=0, atol=0)


# ---------------------------------------------------------------------------
# policy-keyed plan cache: equal policies -> one engine, zero recompiles
# ---------------------------------------------------------------------------


def test_policy_equal_calls_share_engine_and_plan_cache():
    # fmm_p=21 gives this test a private default-engine key: counts are ours
    p1 = UpdatePolicy(method="direct", fmm_p=21)
    p2 = UpdatePolicy(method="direct", fmm_p=21)
    st = _trunc_state(9, 11, 3)
    eng = api.engine_for(p1, st)
    assert api.engine_for(p2, st) is eng

    b, m, n, r = 4, 9, 11, 3
    stacked = jax.tree.map(
        lambda *xs: jnp.stack(xs), *[_trunc_state(m, n, r) for _ in range(b)]
    )
    a1 = jnp.asarray(RNG.normal(size=(b, m)))
    b1 = jnp.asarray(RNG.normal(size=(b, n)))
    api.update(stacked, a1, b1, p1)
    base = eng.cache_info()

    # only batch CONTENTS change -> zero recompiles (no new cache entries,
    # pure hits), even across distinct-but-equal policy objects
    for pol in (p1, p2, UpdatePolicy(method="direct", fmm_p=21)):
        a2 = jnp.asarray(RNG.normal(size=(b, m)))
        b2 = jnp.asarray(RNG.normal(size=(b, n)))
        api.update(stacked, a2, b2, pol)
    info = eng.cache_info()
    assert info.misses == base.misses, "policy-equal call recompiled"
    assert info.entries == base.entries
    assert info.hits == base.hits + 3


def test_policy_difference_is_a_different_engine():
    st = _trunc_state(9, 11, 3)
    e1 = api.engine_for(UpdatePolicy(method="direct", fmm_p=21), st)
    e2 = api.engine_for(UpdatePolicy(method="direct", fmm_p=22), st)
    e3 = api.engine_for(UpdatePolicy(method="direct", fmm_p=21, deflate_rtol=1e-10), st)
    assert e1 is not e2 and e1 is not e3


# ---------------------------------------------------------------------------
# update_many grouping
# ---------------------------------------------------------------------------


def test_update_many_groups_mixed_geometries():
    pol = UpdatePolicy(method="direct")
    states = [
        _trunc_state(8, 10, 3),
        _full_state(6, 7),
        _trunc_state(8, 10, 3),
        _trunc_state(12, 10, 3),
    ]
    A = [jnp.asarray(RNG.normal(size=s.m)) for s in states]
    B = [jnp.asarray(RNG.normal(size=s.n)) for s in states]
    outs = api.update_many(states, A, B, pol)
    assert len(outs) == 4
    for st, a, b, out in zip(states, A, B, outs):
        ref = api.update(st, a, b, pol)
        np.testing.assert_allclose(np.asarray(out.s), np.asarray(ref.s),
                                   rtol=0, atol=1e-12)
        assert out.is_full == st.is_full

    with pytest.raises(ValueError, match="pair up"):
        api.update_many(states, A[:2], B, pol)


def test_update_many_rejects_batched_states():
    tr = _trunc_state(8, 10, 3)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), tr, tr)
    with pytest.raises(ValueError, match="unbatched"):
        api.update_many([stacked], [jnp.zeros((2, 8))], [jnp.zeros((2, 10))])


def test_warmup_precompiles_policy_geometry():
    pol = UpdatePolicy(method="direct", fmm_p=23)  # private engine key
    info = api.warmup(pol, m=8, n=10, batch=4, rank=3, dtype=jnp.float64)
    assert info.entries == 1
    st = jax.tree.map(
        lambda *xs: jnp.stack(xs), *[_trunc_state(8, 10, 3) for _ in range(4)]
    )
    eng = api.engine_for(pol, st)
    api.update(st, jnp.zeros((4, 8)), jnp.zeros((4, 10)), pol)
    assert eng.cache_info().hits >= 1


def test_enable_compilation_cache_defers_to_env(tmp_path, monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` wins over the caller's directory; the
    caller's is used only when the variable is unset."""
    from jax.experimental.compilation_cache import compilation_cache

    env_dir, arg_dir = tmp_path / "env", tmp_path / "arg"
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in names}
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(env_dir))
        assert api.enable_compilation_cache(arg_dir) == env_dir
        assert jax.config.jax_compilation_cache_dir == str(env_dir)
        assert env_dir.is_dir() and not arg_dir.exists()
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert api.enable_compilation_cache(arg_dir) == arg_dir
        assert jax.config.jax_compilation_cache_dir == str(arg_dir)
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
