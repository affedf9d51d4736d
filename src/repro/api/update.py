"""``repro.api.update`` / ``update_many`` — the single entry point for every
rank-1 SVD update path (DESIGN.md §8).

Dispatch is a pure function of *state geometry + policy*:

    state.is_full   state.is_batched   policy.mesh     route
    -------------   ----------------   -----------     ------------------------------
    yes             no                 (ignored)       engine.update            (single)
    yes             yes                None            engine.update_batch      (vmap)
    yes             yes                Mesh            shard_map'd batched update
    no              no                 (ignored)       engine.update_truncated  (Brand)
    no              yes                None            engine.update_truncated_batch
    no              yes                Mesh            shard_map'd truncated batch

All routes resolve to shared plan-cached ``core.engine.SvdEngine``
executables (``default_engine`` keyed by the policy's numerics fields), so
policy-equal calls never recompile and every route is bit-identical to the
engine executable it resolves to (golden-pinned in
``tests/test_api_compat.py``).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro.api.policy import UpdatePolicy
from repro.api.state import SvdState, as_state
from repro.core.engine import (
    SvdEngine,
    default_engine,
    group_indices,
    stack_trees,
    unstack_tree,
)
from repro.core.svd_update import TruncatedSvd

__all__ = ["engine_for", "update", "update_many", "update_rank_k", "warmup"]

_DEFAULT_POLICY = UpdatePolicy()


def engine_from_key(policy: UpdatePolicy, problem_n: int, *,
                    m: int | None = None, n: int | None = None,
                    rank: int | None = None, dtype=None) -> SvdEngine:
    """The ONE place a policy's ``engine_key`` unpacks into ``default_engine``
    — every layer (api, dist.merge, serve) resolves through here, so the
    shared-plan-cache invariant ("equal policies never recompile") has a
    single definition.  The optional geometry lets ``method="auto"`` prefer
    the fused megakernel when the problem fits its VMEM budget.  The key's
    trailing sketch fields (oversample, power_iters) key the planner's
    schedule cache, not the engine — the rank-1 executables are
    sketch-independent, so they are dropped here."""
    (method, fmm_p, sign_fix, deflate_rtol, precision, storage_dtype,
     _sketch_os, _sketch_pi) = policy.engine_key(problem_n, m=m, n=n, rank=rank,
                                                 dtype=dtype)
    return default_engine(
        method,
        fmm_p=fmm_p,
        sign_fix=sign_fix,
        deflate_rtol=deflate_rtol,
        precision=precision,
        storage_dtype=storage_dtype,
    )


def engine_for(policy: UpdatePolicy, state: SvdState) -> SvdEngine:
    """The shared plan-cached engine a (policy, state-geometry) pair runs on.

    Two equal policies — or any two callers with the same numerics knobs —
    return the SAME engine instance, hence one plan cache:

    >>> import numpy as np
    >>> from repro import api
    >>> st = api.SvdState.from_dense(np.eye(4, 6), rank=2)
    >>> pol = api.UpdatePolicy(method="direct")
    >>> api.engine_for(pol, st) is api.engine_for(pol.replace(truncate_to=2), st)
    True
    """
    if state.is_full:
        return engine_from_key(policy, state.n, m=state.m, n=state.n,
                               dtype=state.s.dtype)
    return engine_from_key(policy, state.rank + 1, m=state.m, n=state.n,
                           rank=state.rank, dtype=state.s.dtype)


def _apply_storage_dtype(policy: UpdatePolicy, st: SvdState, a, b):
    """Cast state + perturbation to the policy's storage dtype (bf16 mode).

    The cast IS the policy: engine geometry keys then carry the narrow
    dtype, and the engine's compute_dtype upcasts inside the update."""
    if policy.storage_dtype is None:
        return st, a, b
    dt = jnp.dtype(policy.storage_dtype)
    if st.dtype == dt:
        return st, jnp.asarray(a, dt), jnp.asarray(b, dt)
    st = SvdState(
        u=st.u.astype(dt), s=st.s.astype(dt), v=st.v.astype(dt),
        d_left=None if st.d_left is None else st.d_left.astype(dt),
        d_right=None if st.d_right is None else st.d_right.astype(dt),
        mesh=st.mesh,
    )
    return st, jnp.asarray(a, dt), jnp.asarray(b, dt)


def _finish(state: SvdState, out: SvdState, policy: UpdatePolicy) -> SvdState:
    if policy.truncate_to is not None and policy.truncate_to < out.rank:
        out = out.truncate(policy.truncate_to)
    return out


def update(state, a, b, policy: UpdatePolicy | None = None) -> SvdState:
    """SVD of ``state + a b^T`` under ``policy`` — full, truncated, single or
    stacked, local or mesh-sharded, decided by geometry (module doc table).

    ``state``: any SVD container (``SvdState`` preferred; ``TruncatedSvd`` /
    ``SvdUpdateResult`` / ``(u, s, v)`` are coerced).  ``a``: (..., m),
    ``b``: (..., n), with the leading batch axis iff the state is stacked.
    Returns an ``SvdState`` (full states keep eigen diagnostics).

    >>> import numpy as np
    >>> from repro import api
    >>> rng = np.random.default_rng(0)
    >>> x = rng.normal(size=(4, 6))
    >>> st = api.SvdState.from_dense(x)               # full paper state
    >>> a, b = rng.normal(size=4), rng.normal(size=6)
    >>> out = api.update(st, a, b, api.UpdatePolicy(method="direct"))
    >>> out.shape, out.rank
    ((4, 6), 4)
    >>> ref = np.linalg.svd(x + np.outer(a, b), compute_uv=False)
    >>> bool(np.allclose(out.s, ref, atol=1e-10))     # matches a fresh SVD
    True

    The same entry point runs the truncated streaming route when the state
    is truncated — geometry picks the dispatch:

    >>> tr = api.SvdState.from_dense(x, rank=2)
    >>> api.update(tr, a, b).rank                     # default policy
    2
    """
    policy = policy if policy is not None else _DEFAULT_POLICY
    st = as_state(state)
    st, a, b = _apply_storage_dtype(policy, st, a, b)
    eng = engine_for(policy, st)
    mesh = policy.mesh if policy.mesh is not None else st.mesh
    if st.is_full:
        if st.is_batched:
            res = eng.update_batch(st.u, st.s, st.v, a, b, mesh=mesh,
                                   batch_axis=policy.batch_axis)
        else:
            res = eng.update(st.u, st.s, st.v, a, b)
        out = SvdState(u=res.u, s=res.s, v=res.v, d_left=res.d_left,
                       d_right=res.d_right, mesh=st.mesh)
    else:
        t = TruncatedSvd(u=st.u, s=st.s, v=st.v)
        if st.is_batched:
            t2 = eng.update_truncated_batch(t, a, b, mesh=mesh,
                                            batch_axis=policy.batch_axis)
        else:
            t2 = eng.update_truncated(t, a, b)
        out = SvdState(u=t2.u, s=t2.s, v=t2.v, mesh=st.mesh)
    return _finish(st, out, policy)


def update_many(
    states: Sequence,
    A,
    B,
    policy: UpdatePolicy | None = None,
) -> tuple[SvdState, ...]:
    """Many independent rank-1 updates in as few engine calls as possible.

    ``states[i]`` absorbs ``A[i] B[i]^T``.  States sharing a geometry
    ``(m, n, rank, dtype, fullness)`` are stacked along a batch axis and
    dispatched as ONE batched (possibly mesh-sharded) call through
    ``update``; results come back unstacked, in input order.  This is the
    generalized form of the grouped-update loops optim/serve carried by
    hand.

    >>> import numpy as np
    >>> from repro import api
    >>> rng = np.random.default_rng(1)
    >>> sts = [api.SvdState.from_dense(rng.normal(size=(4, 5)), rank=2)
    ...        for _ in range(3)]
    >>> A = [rng.normal(size=4) for _ in range(3)]
    >>> B = [rng.normal(size=5) for _ in range(3)]
    >>> outs = api.update_many(sts, A, B)             # one batched engine call
    >>> len(outs), outs[0].rank
    (3, 2)
    """
    policy = policy if policy is not None else _DEFAULT_POLICY
    sts = [as_state(s) for s in states]
    if len(sts) != len(A) or len(sts) != len(B):
        raise ValueError(
            f"states/A/B must pair up: {len(sts)} states, {len(A)} a-vectors, "
            f"{len(B)} b-vectors"
        )
    for i, st in enumerate(sts):
        if st.is_batched:
            raise ValueError(
                f"update_many takes unbatched states; state {i} is stacked "
                f"(u {st.u.shape}) — call update() on it directly"
            )

    out: list[SvdState | None] = [None] * len(sts)
    for idxs in group_indices([st.geometry for st in sts]).values():
        if len(idxs) == 1:
            i = idxs[0]
            out[i] = update(sts[i], A[i], B[i], policy)
            continue
        # drop diagnostics before stacking: members may differ in whether
        # they carry d_left/d_right, and batched dispatch recomputes them
        stacked = stack_trees(
            [SvdState(u=sts[i].u, s=sts[i].s, v=sts[i].v) for i in idxs]
        )
        a_stack = jnp.stack([jnp.asarray(A[i]) for i in idxs])
        b_stack = jnp.stack([jnp.asarray(B[i]) for i in idxs])
        batched = update(stacked, a_stack, b_stack, policy)
        for j, i in enumerate(idxs):
            out[i] = unstack_tree(batched, j).replace(mesh=sts[i].mesh)
    return tuple(out)


def update_rank_k(state, A, B, policy: UpdatePolicy | None = None) -> SvdState:
    """SVD of ``state + A^T B`` applied as k sequential rank-1 updates through
    ONE ``lax.scan`` — trace/compile cost is k-independent (the hot path for
    long ``repro.updates`` schedules; ``updates.planner`` lowers k >=
    ``_SCAN_MIN`` schedules here).

    ``A``: (k, m) rows of left vectors, ``B``: (k, n) rows of right vectors
    (leading batch axis before k iff the state is stacked).  ``truncate_to``
    falls back to the unrolled per-pair path (the rule must re-apply between
    pairs, which a scan carry of fixed rank cannot express).

    >>> import numpy as np
    >>> from repro import api
    >>> rng = np.random.default_rng(2)
    >>> x = rng.normal(size=(4, 6))
    >>> st = api.SvdState.from_dense(x)
    >>> A = rng.normal(size=(3, 4)); B = rng.normal(size=(3, 6))
    >>> out = api.update_rank_k(st, A, B, api.UpdatePolicy(method="direct"))
    >>> ref = np.linalg.svd(x + A.T @ B, compute_uv=False)
    >>> bool(np.allclose(out.s, ref, atol=1e-9))
    True
    """
    policy = policy if policy is not None else _DEFAULT_POLICY
    st = as_state(state)
    if policy.truncate_to is not None and policy.truncate_to < st.rank:
        out = st
        k = jnp.asarray(A).shape[-2]
        for i in range(k):
            out = update(out, jnp.asarray(A)[..., i, :], jnp.asarray(B)[..., i, :],
                         policy)
        return out
    st, A, B = _apply_storage_dtype(policy, st, A, B)
    eng = engine_for(policy, st)
    mesh = policy.mesh if policy.mesh is not None else st.mesh
    if st.is_full:
        if st.is_batched:
            res = eng.update_rank_k_batch(st.u, st.s, st.v, A, B, mesh=mesh,
                                          batch_axis=policy.batch_axis)
        else:
            res = eng.update_rank_k(st.u, st.s, st.v, A, B)
        out = SvdState(u=res.u, s=res.s, v=res.v, d_left=res.d_left,
                       d_right=res.d_right, mesh=st.mesh)
    else:
        t = TruncatedSvd(u=st.u, s=st.s, v=st.v)
        if st.is_batched:
            t2 = eng.update_truncated_rank_k_batch(t, A, B, mesh=mesh,
                                                   batch_axis=policy.batch_axis)
        else:
            t2 = eng.update_truncated_rank_k(t, A, B)
        out = SvdState(u=t2.u, s=t2.s, v=t2.v, mesh=st.mesh)
    return _finish(st, out, policy)


def warmup(
    policy: UpdatePolicy,
    *,
    m: int,
    n: int,
    batch: int | None = None,
    rank: int | None = None,
    k: int | None = None,
    dtype=jnp.float32,
    cache_dir=None,
):
    """AOT-compile the executable a (policy, geometry) pair will use, before
    traffic arrives (serving cold-start control).  ``rank=None`` warms the
    full route, else the truncated one; ``batch=None`` warms single-instance;
    ``k`` warms the rank-k scan route.  With ``policy.storage_dtype`` set the
    warmed geometry uses the storage dtype (what real casts will carry).

    ``cache_dir`` additionally persists the compiled binaries in the XLA
    compilation cache (``api.enable_compilation_cache``): a LATER process
    warming the same (policy, geometry) replays them from disk instead of
    recompiling — warmup survives restarts.

    >>> import jax.numpy as jnp
    >>> from repro import api
    >>> pol = api.UpdatePolicy(method="direct")
    >>> info = api.warmup(pol, m=4, n=5, rank=2, dtype=jnp.float64)
    >>> info.entries >= 1          # the (policy, geometry) plan is cached
    True
    """
    if cache_dir is not None:
        from repro.api.cache import enable_compilation_cache

        enable_compilation_cache(cache_dir)
    if policy.storage_dtype is not None:
        dtype = policy.storage_dtype
    eng = engine_from_key(policy, n if rank is None else rank + 1,
                          m=m, n=n, rank=rank, dtype=dtype)
    return eng.warmup(batch=batch, m=m, n=n, rank=rank, k=k, dtype=dtype)
