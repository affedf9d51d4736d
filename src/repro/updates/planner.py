"""Lowering structured-perturbation ops onto the rank-1 engine (DESIGN.md §10).

``apply(state, op, policy)`` compiles any ``repro.updates.ops`` op into a
minimal *schedule* of existing ``repro.api`` calls and executes it:

* ``RankK``      -> k plan-cached rank-1 ``api.update`` dispatches;
* ``DenseDelta`` -> top-``rank`` randomized sketch of the delta
  (``updates.sketch.sketch_svd``, O(m·n·rank) — no LAPACK SVD anywhere),
  then rank-1 steps;
* ``Sparse``     -> top-``rank`` sketch through the COO projection kernel
  (``sketch.sparse_sketch_svd`` + ``kernels.sparse_proj``) at
  O((m+n)·rank² + nnz·rank) — the delta is never densified;
* ``AppendRows`` / ``AppendCols`` -> zero-pad the state's geometry, then one
  rank-1 step per component of the appended block (dense blocks sketch at
  their full block rank — exact; pre-factored blocks bind directly);
* ``Decay``      -> folded into the singular values for FREE — zero engine
  dispatches;
* ``RemoveRows`` / ``RemoveCols`` -> delete the factor rows, then one tall
  QR + r x r SVD re-orthonormalizes (``_refactor``) — zero engine
  dispatches, exact in float32 (rank-1 zeroing steps cancel in the squared
  spectrum and leave ~0.1 s_max of spurious singular value there);
* ``Window``     -> decay fold + RemoveRows of everything before the last
  ``size`` rows;
* ``Compose``    -> children's schedules concatenated in order, geometry
  threaded through appends and removes.

All low-rank extraction funnels through ``op_low_rank_factors`` — the ONE
sketch entry point (``serve.svd_service`` lowers its op events through the
same helper, so planner and serve can never drift).  The policy's
``sketch_oversample`` / ``sketch_power_iters`` knobs fold into the schedule
cache key, and ``warmup_plan`` AOT-warms the jitted sketch executables
alongside the engine geometries — no sketch compile on the hot path.

``apply_many(states, ops, policy)`` executes many (state, op) pairs in
lockstep waves: at each wave, every op's next rank-1 step is batched with all
same-geometry steps of the *other* ops into ONE ``api.update_many`` engine
dispatch — a planned rank-k update of B streams costs k batched calls, not
B*k singles (``benchmarks/bench_updates.py`` measures the gap).

Schedules are cached by ``(op.spec(), state geometry)`` — the schedule cache
mirrors the engine's plan cache one level up: re-applying a same-shaped op
never re-plans (``schedule_cache_info()``).
"""

from __future__ import annotations

import threading
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro import obs as _obs
from repro.api.policy import UpdatePolicy
from repro.api.state import SvdState, as_state
from repro.api.update import update, update_rank_k, warmup
from repro.updates.ops import (
    AppendCols,
    AppendRows,
    Compose,
    Decay,
    DenseDelta,
    RankK,
    Sparse,
    UpdateOp,
)
from repro.updates.sketch import (_small_svd, sketch_svd, sparse_sketch_svd,
                                  warmup_sketch)

__all__ = [
    "apply",
    "apply_many",
    "lower",
    "op_low_rank_factors",
    "schedule_cache_clear",
    "schedule_cache_info",
    "warmup_plan",
]

_DEFAULT_SKETCH = UpdatePolicy().sketch_params


def _sketch_params(policy: UpdatePolicy | None) -> tuple[int, int]:
    return _DEFAULT_SKETCH if policy is None else policy.sketch_params


class ScheduleCacheInfo(NamedTuple):
    hits: int
    misses: int
    entries: int


_cache: dict[tuple, tuple] = {}
_hits = 0
_misses = 0
_lock = threading.Lock()


def schedule_cache_info() -> ScheduleCacheInfo:
    with _lock:
        return ScheduleCacheInfo(_hits, _misses, len(_cache))


def schedule_cache_clear() -> None:
    global _hits, _misses
    with _lock:
        _cache.clear()
        _hits = 0
        _misses = 0


# ---------------------------------------------------------------------------
# Lowering: op spec -> schedule of abstract steps
#
#   ("decay", path)                 s *= lam            (free)
#   ("pad_rows", p) / ("pad_cols", p)                   (free)
#   ("drop_rows", idx) / ("drop_cols", idx)             (shrink + refactor)
#   ("rank1", path, kind, i)        one engine dispatch
#   ("rank1_scan", path, kind, k)   k dispatches through ONE lax.scan
#
# ``path`` locates the source op inside Compose nesting; ``i`` names the
# component.  Steps are static (no array data) — data binds at execution.
# Downdates (RemoveRows / RemoveCols / Window) need no rank-1 step: deleting
# rows of ``A = U S V^T`` deletes rows of ``U``, and one QR plus an r x r SVD
# (``_refactor``) restores an exact SVD of the smaller matrix.
#
# Long component runs (k >= _SCAN_MIN) lower to a single scanned step
# (``api.update_rank_k``): trace/compile cost stays k-independent instead of
# unrolling k copies of the update body into the jaxpr.  Short runs stay
# unrolled — they interleave with other ops' steps in ``apply_many`` waves.
# ---------------------------------------------------------------------------

_SCAN_MIN = 17


def _component_steps(path: tuple, kind: str, count: int) -> list:
    if count >= _SCAN_MIN:
        return [("rank1_scan", path, kind, count)]
    return [("rank1", path, kind, i) for i in range(count)]


def _build(spec: tuple, m: int, n: int, rank: int, is_full: bool, path: tuple):
    kind = spec[0]
    if kind == "rank_k":
        return _component_steps(path, kind, spec[1]), (m, n)
    if kind == "dense_delta":
        return _component_steps(path, kind, spec[1]), (m, n)
    if kind == "sparse":
        return _component_steps(path, kind, spec[2]), (m, n)
    if kind == "decay":
        return [("decay", path)], (m, n)
    if kind in ("append_rows", "append_cols"):
        if is_full:
            raise ValueError(
                f"{kind} requires a truncated state: a full (square-basis) "
                f"state cannot zero-pad its geometry — truncate first"
            )
        p, q = spec[1], spec[2]
        pad = ("pad_rows", p) if kind == "append_rows" else ("pad_cols", p)
        steps = [pad] + _component_steps(path, kind, q)
        out = (m + p, n) if kind == "append_rows" else (m, n + p)
        return steps, out
    if kind in ("remove_rows", "remove_cols"):
        if is_full:
            raise ValueError(
                f"{kind} requires a truncated state: a full (square-basis) "
                f"state cannot shrink its geometry — truncate first"
            )
        idx = spec[1]
        axis, dim = ("rows", m) if kind == "remove_rows" else ("cols", n)
        if idx[-1] >= dim:
            raise ValueError(
                f"{kind} index {idx[-1]} out of range for {dim} {axis}"
            )
        out = (m - len(idx), n) if kind == "remove_rows" else (m, n - len(idx))
        if rank > min(out):
            raise ValueError(
                f"{kind}{idx} shrinks the geometry to {out}, below the "
                f"state's rank {rank} — truncate first"
            )
        drop = ("drop_rows", idx) if kind == "remove_rows" else ("drop_cols", idx)
        return [drop], out
    if kind == "window":
        if is_full:
            raise ValueError(
                "window requires a truncated state: a full (square-basis) "
                "state cannot shrink its geometry — truncate first"
            )
        size = spec[1]
        cut = m - size
        steps = [("decay", path)]
        if cut <= 0:
            return steps, (m, n)
        out = (size, n)
        if rank > min(out):
            raise ValueError(
                f"window({size}) shrinks the geometry to {out}, below the "
                f"state's rank {rank} — truncate first"
            )
        steps.append(("drop_rows", tuple(range(cut))))
        return steps, out
    if kind == "compose":
        steps: list = []
        for j, child in enumerate(spec[1]):
            sub, (m, n) = _build(child, m, n, rank, is_full, path + (j,))
            steps.extend(sub)
        return steps, (m, n)
    raise ValueError(f"unknown op spec {spec!r}")


def lower(op: UpdateOp, state, policy: UpdatePolicy | None = None) -> tuple:
    """The cached schedule for ``op`` applied to ``state``'s geometry.

    The cache key folds the policy's ``sketch_params`` — sketch-knob changes
    can never serve a schedule planned under different accuracy settings.

    >>> import numpy as np
    >>> from repro.api import SvdState
    >>> from repro.updates.ops import Compose, Decay, RankK
    >>> st = SvdState.from_dense(np.eye(4, 6), rank=2)
    >>> op = Compose((Decay(0.9), RankK(np.zeros((4, 2)), np.zeros((6, 2)))))
    >>> lower(op, st)
    (('decay', (0,)), ('rank1', (1,), 'rank_k', 0), ('rank1', (1,), 'rank_k', 1))
    """
    global _hits, _misses
    st = as_state(state)
    key = (op.spec(), st.m, st.n, st.rank, st.is_full, _sketch_params(policy))
    with _lock:
        plan = _cache.get(key)
        if plan is not None:
            _hits += 1
        else:
            _misses += 1
    if plan is not None:
        if _obs.enabled():
            _obs.registry().counter("planner_schedule_cache_hits").inc()
        return plan
    if _obs.enabled():
        _obs.registry().counter("planner_schedule_cache_misses").inc()
    with _obs.span("schedule_compile", op=key[0][0], m=st.m, n=st.n,
                   rank=st.rank):
        steps, _ = _build(key[0], st.m, st.n, st.rank, st.is_full, ())
    plan = tuple(steps)
    with _lock:
        _cache[key] = plan
    return plan


# ---------------------------------------------------------------------------
# Execution: bind step data from the op, dispatch through repro.api
# ---------------------------------------------------------------------------


def _resolve(op: UpdateOp, path: tuple) -> UpdateOp:
    for j in path:
        op = op.ops[j]
    return op


def op_low_rank_factors(op, m: int, n: int,
                        policy: UpdatePolicy | None = None):
    """(u, s, v) rank-1 components of an op's low-rank block at geometry
    (m, n) — the ONE sketch entry point for planner AND serve (no dense
    ``jnp.linalg.svd`` anywhere on this path).

    ``DenseDelta`` sketches at its rank budget; ``Sparse`` sketches through
    the COO projection kernel; dense append blocks sketch at their full
    block rank (``l >= rank(block)`` — exact); pre-factored append blocks
    bind as carried.  Everything runs inside the jitted sketch executables,
    so ``warmup_plan`` / serve restore AOT-cover it and no per-op host work
    remains.
    """
    oversample, power_iters = _sketch_params(policy)
    if isinstance(op, DenseDelta):
        return sketch_svd(jnp.asarray(op.delta), op.rank,
                          oversample=oversample, power_iters=power_iters)
    if isinstance(op, Sparse):
        # single-pass two-sided sketch: no power_iters knob (sketch module doc)
        return sparse_sketch_svd(op.rows, op.cols, op.vals, m=m, n=n,
                                 k=op.rank, oversample=oversample)
    if isinstance(op, AppendRows) and op.rows is not None:
        return sketch_svd(jnp.asarray(op.rows), op.block_rank,
                          oversample=oversample, power_iters=power_iters)
    if isinstance(op, AppendCols) and op.cols is not None:
        return sketch_svd(jnp.asarray(op.cols), op.block_rank,
                          oversample=oversample, power_iters=power_iters)
    if isinstance(op, (AppendRows, AppendCols)):  # pre-factored block
        return (jnp.asarray(op.u), jnp.asarray(op.s), jnp.asarray(op.v))
    raise TypeError(f"{type(op).__name__} has no low-rank block to extract")


def _block_factors(op, ctx: dict, path: tuple, cur: SvdState,
                   policy: UpdatePolicy | None):
    """Per-apply memo over ``op_low_rank_factors`` (one sketch per block).

    ``Sparse`` needs the CURRENT geometry (appends earlier in a Compose may
    have grown it); appends use their own block shape, deltas their own.
    """
    key = (path, "factors")
    if key not in ctx:
        ctx[key] = op_low_rank_factors(op, cur.m, cur.n, policy)
    return ctx[key]


def _zeros_like_batch(ref, length: int):
    """Zero filler matching ``ref``'s leading (batch) dims with a trailing
    axis of ``length``."""
    return jnp.zeros(ref.shape[:-1] + (length,), ref.dtype)


def _col(x, i: int):
    """Column ``i`` off the last axis — a static slice (cheap on the hot
    path; ``x[..., :, i]`` would lower to a full gather)."""
    return lax.index_in_dim(x, i, axis=-1, keepdims=False)


def _bind(cur: SvdState, op: UpdateOp, step: tuple, ctx: dict,
          policy: UpdatePolicy | None = None):
    """The (a, b) pair of one rank-1 step, shaped for the CURRENT geometry."""
    _, path, kind, i = step
    src = _resolve(op, path)
    if kind == "rank_k":
        return _col(jnp.asarray(src.u), i), _col(jnp.asarray(src.v), i)
    if kind in ("dense_delta", "sparse"):
        u, s, v = _block_factors(src, ctx, path, cur, policy)
        return _col(u, i) * lax.index_in_dim(s, i, axis=-1), _col(v, i)
    u, s, v = _block_factors(src, ctx, path, cur, policy)
    comp = _col(u, i) * lax.index_in_dim(s, i, axis=-1)
    if kind == "append_rows":
        # the block's rows live at the bottom of the (already padded) state
        a = jnp.concatenate([_zeros_like_batch(comp, cur.m - src.p), comp], axis=-1)
        return a, _col(v, i)
    # append_cols: the block's columns live at the right edge
    v_i = _col(v, i)
    b = jnp.concatenate([_zeros_like_batch(v_i, cur.n - src.p), v_i], axis=-1)
    return comp, b


def _bind_block(cur: SvdState, op: UpdateOp, step: tuple, ctx: dict,
                policy: UpdatePolicy | None = None):
    """The full (k, m)/(k, n) pair blocks of one scanned rank-k step."""
    _, path, kind, _count = step
    src = _resolve(op, path)
    if kind == "rank_k":
        return (jnp.swapaxes(jnp.asarray(src.u), -1, -2),
                jnp.swapaxes(jnp.asarray(src.v), -1, -2))
    u, s, v = _block_factors(src, ctx, path, cur, policy)
    comp = jnp.swapaxes(u * s[..., None, :], -1, -2)      # (..., k, rows)
    vt = jnp.swapaxes(v, -1, -2)                          # (..., k, cols)
    if kind in ("dense_delta", "sparse"):
        return comp, vt
    if kind == "append_rows":
        z = jnp.zeros(comp.shape[:-1] + (cur.m - src.p,), comp.dtype)
        return jnp.concatenate([z, comp], axis=-1), vt
    # append_cols
    z = jnp.zeros(vt.shape[:-1] + (cur.n - src.p,), vt.dtype)
    return comp, jnp.concatenate([z, vt], axis=-1)


def _pad_rows(cur: SvdState, p: int) -> SvdState:
    pad = jnp.zeros(cur.u.shape[:-2] + (p, cur.rank), cur.u.dtype)
    return cur.replace(u=jnp.concatenate([cur.u, pad], axis=-2))


def _pad_cols(cur: SvdState, p: int) -> SvdState:
    pad = jnp.zeros(cur.v.shape[:-2] + (p, cur.rank), cur.v.dtype)
    return cur.replace(v=jnp.concatenate([cur.v, pad], axis=-2))


def _orthonormal(x):
    """``x``'s columns re-orthonormalized in order (QR, signs kept): exact
    for the orthonormal ones, a completion for the arbitrary zero-s ones."""
    q, r = jnp.linalg.qr(x)
    d = jnp.diagonal(r, axis1=-2, axis2=-1)
    return q * jnp.where(d < 0, -1.0, 1.0).astype(q.dtype)[..., None, :]


def _refactor(basis, s, other):
    """SVD of ``basis diag(s) other^T`` where ``other`` is orthonormal but
    ``basis`` lost rows (a downdate): a tall QR plus the r x r
    Jordan-Wielandt core SVD, exact in any precision.  ``basis`` must keep
    at least ``r`` rows (``_build`` validates)."""
    with jax.default_matmul_precision("highest"):
        q, rq = jnp.linalg.qr(basis)
        x, sig, y = _small_svd(rq * s[..., None, :])
        return q @ _orthonormal(x), sig, other @ _orthonormal(y)


def _drop_rows(cur: SvdState, idx: tuple) -> SvdState:
    """Delete rows ``idx`` of the represented matrix (rows of ``u``)."""
    u, s, v = _refactor(jnp.delete(cur.u, jnp.array(idx), axis=-2), cur.s, cur.v)
    return cur.replace(u=u, s=s, v=v)


def _drop_cols(cur: SvdState, idx: tuple) -> SvdState:
    """Delete columns ``idx`` of the represented matrix (rows of ``v``)."""
    v, s, u = _refactor(jnp.delete(cur.v, jnp.array(idx), axis=-2), cur.s, cur.u)
    return cur.replace(u=u, s=s, v=v)


def _exec_free(cur: SvdState, op: UpdateOp, step: tuple) -> SvdState:
    """Execute a zero-dispatch step (decay fold / geometry pad / shrink)."""
    if step[0] == "decay":
        lam = jnp.asarray(_resolve(op, step[1]).lam)
        return cur.replace(s=cur.s * lam)
    if step[0] == "pad_rows":
        return _pad_rows(cur, step[1])
    if step[0] == "pad_cols":
        return _pad_cols(cur, step[1])
    if step[0] == "drop_rows":
        return _drop_rows(cur, step[1])
    return _drop_cols(cur, step[1])


def apply(state, op: UpdateOp, policy: UpdatePolicy | None = None) -> SvdState:
    """SVD of ``op.apply_dense(state.materialize())`` by planned rank-1
    updates — the single structured entry point (also ``repro.api.apply``).

    ``state`` is any SVD container (full or truncated, single or stacked);
    geometry + policy pick the engine route of every lowered rank-1 step,
    exactly as in ``api.update``.  Geometry-changing ops (appends, removes,
    window) require a truncated state.

    >>> import numpy as np
    >>> from repro import api
    >>> from repro.updates import RankK
    >>> rng = np.random.default_rng(0)
    >>> x = rng.normal(size=(4, 6))
    >>> uk, vk = rng.normal(size=(4, 2)), rng.normal(size=(6, 2))
    >>> out = api.apply(api.SvdState.from_dense(x), RankK(uk, vk))
    >>> ref = np.linalg.svd(x + uk @ vk.T, compute_uv=False)
    >>> bool(np.allclose(out.s, ref, atol=1e-9))
    True
    """
    st = as_state(state)
    plan = lower(op, st, policy)
    ctx: dict = {}
    for step in plan:
        if step[0] == "rank1":
            a, b = _bind(st, op, step, ctx, policy)
            st = update(st, a, b, policy)
        elif step[0] == "rank1_scan":
            va, vb = _bind_block(st, op, step, ctx, policy)
            st = update_rank_k(st, va, vb, policy)
        else:
            st = _exec_free(st, op, step)
    return st


def apply_many(
    states: Sequence,
    ops: Sequence[UpdateOp],
    policy: UpdatePolicy | None = None,
) -> tuple[SvdState, ...]:
    """Apply ``ops[i]`` to ``states[i]`` with cross-op step batching.

    Execution runs in lockstep waves: free steps (decay folds, geometry
    pads) advance immediately; then every op's next rank-1 step joins one
    ``api.update_many`` dispatch, which groups same-geometry steps into
    single batched engine calls.  A rank-k update of B same-geometry streams
    therefore costs k batched dispatches instead of B*k sequential singles.

    >>> import numpy as np
    >>> from repro import api
    >>> from repro.updates import Decay, RankK
    >>> rng = np.random.default_rng(1)
    >>> sts = [api.SvdState.from_dense(rng.normal(size=(4, 5)), rank=3)
    ...        for _ in range(3)]
    >>> ops = [RankK(rng.normal(size=(4, 2)), rng.normal(size=(5, 2))),
    ...        RankK(rng.normal(size=(4, 2)), rng.normal(size=(5, 2))),
    ...        Decay(0.5)]
    >>> outs = api.apply_many(sts, ops)
    >>> len(outs), outs[2].rank
    (3, 3)
    >>> bool(np.allclose(outs[2].s, 0.5 * np.asarray(sts[2].s)))
    True
    """
    sts = [as_state(s) for s in states]
    if len(sts) != len(ops):
        raise ValueError(f"{len(sts)} states but {len(ops)} ops")
    for i, st in enumerate(sts):
        if st.is_batched:
            raise ValueError(
                f"apply_many takes unbatched states; state {i} is stacked "
                f"(u {st.u.shape}) — call apply() on it directly"
            )
    plans = [lower(op, st, policy) for op, st in zip(ops, sts)]

    out: list[SvdState | None] = [None] * len(sts)
    groups: dict[tuple, list[int]] = {}
    for i, (st, plan) in enumerate(zip(sts, plans)):
        groups.setdefault((st.geometry, plan), []).append(i)

    for (_, plan), idxs in groups.items():
        if len(idxs) == 1:
            i = idxs[0]
            out[i] = apply(sts[i], ops[i], policy)
            continue
        # same plan + geometry: stack ONCE, run the whole schedule batched —
        # every rank-1 step is one engine dispatch for the whole group, and
        # the stack/unstack cost is paid once, not once per step
        group_ops = [ops[i] for i in idxs]
        ctxs: list[dict] = [{} for _ in idxs]
        cur = SvdState(
            u=jnp.stack([sts[i].u for i in idxs]),
            s=jnp.stack([sts[i].s for i in idxs]),
            v=jnp.stack([sts[i].v for i in idxs]),
        )
        for step in plan:
            if step[0] == "rank1":
                # _bind only reads the (shared) geometry off ``cur``, so
                # the stacked state binds each member's unbatched vectors
                pairs = [
                    _bind(cur, op, step, ctx, policy)
                    for op, ctx in zip(group_ops, ctxs)
                ]
                a = jnp.stack([p[0] for p in pairs])
                b = jnp.stack([p[1] for p in pairs])
                cur = update(cur, a, b, policy)
            elif step[0] == "rank1_scan":
                blocks = [
                    _bind_block(cur, op, step, ctx, policy)
                    for op, ctx in zip(group_ops, ctxs)
                ]
                va = jnp.stack([p[0] for p in blocks])
                vb = jnp.stack([p[1] for p in blocks])
                cur = update_rank_k(cur, va, vb, policy)
            elif step[0] == "decay":
                lams = jnp.stack(
                    [jnp.asarray(_resolve(op, step[1]).lam) for op in group_ops]
                )
                cur = cur.replace(s=cur.s * lams[:, None])
            elif step[0] == "pad_rows":
                cur = _pad_rows(cur, step[1])
            elif step[0] == "pad_cols":
                cur = _pad_cols(cur, step[1])
            elif step[0] == "drop_rows":
                cur = _drop_rows(cur, step[1])
            else:
                cur = _drop_cols(cur, step[1])
        for j, i in enumerate(idxs):
            out[i] = SvdState(u=cur.u[j], s=cur.s[j], v=cur.v[j],
                              mesh=sts[i].mesh)
    return tuple(out)


def _sketch_sites(spec: tuple, m: int, n: int):
    """Sketch geometries ``(m, n, k, nnz-or-None)`` the schedule will run,
    threading geometry through appends exactly like ``_build``."""
    kind = spec[0]
    if kind == "dense_delta":
        return [(m, n, spec[1], None)], (m, n)
    if kind == "sparse":
        return [(m, n, spec[2], spec[1])], (m, n)
    if kind == "append_rows":
        sites = [(spec[1], n, spec[2], None)] if spec[3] == "dense" else []
        return sites, (m + spec[1], n)
    if kind == "append_cols":
        sites = [(m, spec[1], spec[2], None)] if spec[3] == "dense" else []
        return sites, (m, n + spec[1])
    if kind == "remove_rows":
        return [], (m - len(spec[1]), n)
    if kind == "remove_cols":
        return [], (m, n - len(spec[1]))
    if kind == "window":
        return [], (min(m, spec[1]), n)
    if kind == "compose":
        sites: list = []
        for child in spec[1]:
            sub, (m, n) = _sketch_sites(child, m, n)
            sites.extend(sub)
        return sites, (m, n)
    return [], (m, n)  # rank_k / decay: no extraction


def warmup_plan(
    policy: UpdatePolicy,
    op: UpdateOp,
    *,
    m: int,
    n: int,
    rank: int | None = None,
    batch: int | None = None,
    dtype=jnp.float64,
):
    """AOT-warm every engine geometry ``op``'s schedule will dispatch
    (appends shift the geometry mid-schedule; each distinct one is warmed),
    plus every jitted sketch executable the schedule's extractions run
    (dense-delta / sparse / dense append blocks, at the policy's sketch
    knobs) — no compile of any kind on the hot path.

    Returns the list of ``(m, n)`` geometries warmed.
    """
    r = rank if rank is not None else m
    spec = op.spec()
    oversample, power_iters = _sketch_params(policy)
    for sm, sn, sk, snnz in _sketch_sites(spec, m, n)[0]:
        warmup_sketch(m=sm, n=sn, k=sk, nnz=snnz, batch=batch,
                      oversample=oversample, power_iters=power_iters,
                      dtype=dtype)
    steps, _ = _build(spec, m, n, r, rank is None, ())
    geoms: list[tuple[int, int]] = []
    entries: dict[tuple[int, int, int | None], UpdatePolicy | None] = {}
    cur_m, cur_n = m, n
    for step in steps:
        if step[0] == "pad_rows":
            cur_m += step[1]
        elif step[0] == "pad_cols":
            cur_n += step[1]
        elif step[0] == "drop_rows":
            cur_m -= len(step[1])
        elif step[0] == "drop_cols":
            cur_n -= len(step[1])
        elif step[0] in ("rank1", "rank1_scan"):
            k = step[3] if step[0] == "rank1_scan" else None
            entries.setdefault((cur_m, cur_n, k), policy)
            if (cur_m, cur_n) not in geoms:
                geoms.append((cur_m, cur_n))
    for (gm, gn, k), pol in entries.items():
        warmup(pol, m=gm, n=gn, batch=batch, rank=rank, k=k, dtype=dtype)
    return geoms
