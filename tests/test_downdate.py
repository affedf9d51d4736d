"""Downdates as first-class ops (ISSUE 9): ``RemoveRows`` / ``RemoveCols``
/ ``Window`` — op algebra, planner lowering, exact-reference parity on the
single / batched / truncated / mesh-sharded routes, ill-conditioned
deletions (in-span residual ``r_b -> 0``, repeated singular values),
remove-then-reappend round-trips, the geometry-shrinking ``apply_many``
grouping, serve wiring, and ``dist.merge`` compatibility.

Parity contract (same as every other op): the downdated state's
``materialize()`` must match the top-rank reconstruction of
``op.apply_dense(A)`` — deletion is exact rank-1 algebra, not an
approximation, whenever the data's rank fits the state's budget.
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
from repro.dist.merge import merge_tree
from repro.updates import (
    AppendCols,
    AppendRows,
    Compose,
    Decay,
    RankK,
    RemoveCols,
    RemoveRows,
    Window,
    apply_many,
    lower,
    skeleton_from_spec,
    spec_from_json,
    spec_to_json,
    warmup_plan,
)
from repro.updates.planner import _SCAN_MIN

RNG = np.random.default_rng(909)
REPO = Path(__file__).resolve().parent.parent


def _lowrank(m, n, r, rng=RNG):
    return rng.normal(size=(m, r)) @ rng.normal(size=(r, n))


def _top_r(dense, r):
    u, s, vt = np.linalg.svd(np.asarray(dense), full_matrices=False)
    return (u[:, :r] * s[:r]) @ vt[:r]


def _roomy_state(m, n, data_rank, state_rank, rng=RNG):
    return SvdState.from_dense(jnp.asarray(_lowrank(m, n, data_rank, rng)),
                               rank=state_rank)


def _assert_parity(state, op, *, atol=1e-10):
    out = api.apply(state, op)
    dense = np.asarray(op.apply_dense(np.asarray(state.materialize())))
    rec = _top_r(dense, out.rank)
    np.testing.assert_allclose(np.asarray(out.materialize()), rec, atol=atol)
    return out


# ---------------------------------------------------------------------------
# op algebra: dense semantics, geometry, specs, validation
# ---------------------------------------------------------------------------


def test_remove_dense_semantics_and_geometry():
    a_mat = RNG.normal(size=(5, 4))
    np.testing.assert_allclose(
        np.asarray(RemoveRows((1, 3)).apply_dense(a_mat)),
        np.delete(a_mat, (1, 3), axis=0),
    )
    np.testing.assert_allclose(
        np.asarray(RemoveCols(2).apply_dense(a_mat)),
        np.delete(a_mat, 2, axis=1),
    )
    np.testing.assert_allclose(
        np.asarray(Window(3, lam=0.5).apply_dense(a_mat)),
        0.5 * a_mat[-3:],
    )
    assert RemoveRows((1, 3)).out_shape(5, 4) == (3, 4)
    assert RemoveCols(2).out_shape(5, 4) == (5, 3)
    assert Window(3).out_shape(5, 4) == (3, 4)
    assert Window(9).out_shape(5, 4) == (5, 4)   # already fits: no shrink


def test_remove_batched_dense_semantics():
    a_mat = RNG.normal(size=(3, 5, 4))
    np.testing.assert_allclose(
        np.asarray(RemoveRows((0, 4)).apply_dense(a_mat)),
        np.delete(a_mat, (0, 4), axis=1),
    )
    np.testing.assert_allclose(
        np.asarray(Window(2).apply_dense(a_mat)), a_mat[:, -2:],
    )


def test_remove_idx_normalization_and_validation():
    assert RemoveRows((3, 0, 1)).idx == (0, 1, 3)   # sorted
    assert RemoveCols(np.int64(2)).idx == (2,)      # int-likes accepted
    with pytest.raises(ValueError, match="unique"):
        RemoveRows((1, 1))
    with pytest.raises(ValueError, match="non-negative"):
        RemoveCols((-1,))
    with pytest.raises(ValueError, match="at least one"):
        RemoveRows(())
    with pytest.raises(ValueError, match="size"):
        Window(0)
    with pytest.raises(ValueError, match="out of range"):
        RemoveRows(9).apply_dense(np.zeros((3, 2)))


def test_remove_specs_hashable_json_and_skeletons():
    for op in (RemoveRows((0, 2)), RemoveCols(1), Window(4, lam=0.7)):
        spec = op.spec()
        hash(spec)   # hashable: planner schedule-cache key
        assert spec_from_json(json.loads(json.dumps(spec_to_json(spec)))) == spec
        skel = skeleton_from_spec(spec)
        assert jax.tree.structure(skel) == jax.tree.structure(op)
    # Remove ops are pure metadata: zero array leaves ride the snapshot
    assert jax.tree.leaves(RemoveRows((0, 2))) == []
    assert len(jax.tree.leaves(Window(4, lam=0.7))) == 1


# ---------------------------------------------------------------------------
# planner lowering: schedule shapes, validation
# ---------------------------------------------------------------------------


def test_remove_lowering_steps():
    st = _roomy_state(8, 6, 2, 3)
    # deletes are a factor-row drop + refactor: zero engine dispatches
    plan = lower(RemoveRows((1, 5)), st)
    assert plan == (("drop_rows", (1, 5)),)
    plan = lower(Window(6, lam=0.9), st)
    assert plan == (("decay", ()), ("drop_rows", (0, 1)))
    # fits already: decay fold only, zero engine dispatches
    assert lower(Window(8), st) == (("decay", ()),)


def test_remove_long_runs_lower_to_one_scan():
    st = _roomy_state(_SCAN_MIN + 8, 6, 2, 3)
    idx = tuple(range(_SCAN_MIN))
    plan = lower(RemoveRows(idx), st)
    assert plan == (("drop_rows", idx),)


def test_remove_requires_truncated_state():
    full = SvdState.from_dense(jnp.asarray(_lowrank(4, 5, 2)))
    for op in (RemoveRows(0), RemoveCols(0), Window(3)):
        with pytest.raises(ValueError, match="truncated"):
            api.apply(full, op)


def test_remove_validates_bounds_and_rank():
    st = _roomy_state(6, 5, 2, 4)
    with pytest.raises(ValueError, match="out of range"):
        api.apply(st, RemoveRows(6))
    with pytest.raises(ValueError, match="below the state's rank"):
        api.apply(st, RemoveCols((0, 1)))       # (6, 3) < rank 4
    with pytest.raises(ValueError, match="below the state's rank"):
        api.apply(st, Window(3))                # (3, 5) < rank 4


# ---------------------------------------------------------------------------
# parity: single / truncated routes (the acceptance identity)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("make_op", [
    lambda m, n: RemoveRows(0),
    lambda m, n: RemoveRows((1, m - 1)),
    lambda m, n: RemoveCols((0, n - 2)),
    lambda m, n: Window(m - 2),
    lambda m, n: Window(m - 1, lam=0.9),
    lambda m, n: Compose((Decay(0.8), RemoveRows(2), RemoveCols(1))),
    lambda m, n: Compose((RemoveCols(0), RemoveCols(0))),  # shifting indices
], ids=["rows0", "rows2", "cols2", "window", "window-lam", "mixed",
        "cols-twice"])
@pytest.mark.parametrize("geom", [(8, 6), (6, 8), (9, 9)])
def test_remove_parity_truncated(geom, make_op):
    m, n = geom
    st = _roomy_state(m, n, 2, 4)
    _assert_parity(st, make_op(m, n))


def test_window_equals_decay_plus_remove_rows():
    st = _roomy_state(9, 6, 2, 4)
    win = api.apply(st, Window(6, lam=0.85))
    explicit = api.apply(st, Compose((Decay(0.85), RemoveRows((0, 1, 2)))))
    np.testing.assert_allclose(np.asarray(win.materialize()),
                               np.asarray(explicit.materialize()), atol=1e-10)


def test_remove_scan_parity_matches_unrolled():
    """A >= _SCAN_MIN deletion list (one lax.scan dispatch) matches both the
    dense reference and the unrolled per-index schedule."""
    m, n = _SCAN_MIN + 10, 7
    st = _roomy_state(m, n, 2, 4)
    idx = tuple(range(1, _SCAN_MIN + 1))
    out = _assert_parity(st, RemoveRows(idx), atol=1e-9)
    unrolled = st
    for k, j in enumerate(idx):
        unrolled = api.apply(unrolled, RemoveRows(j - k))  # indices shift
    np.testing.assert_allclose(np.asarray(out.materialize()),
                               np.asarray(unrolled.materialize()), atol=1e-9)


def test_remove_then_reappend_round_trip():
    """Delete rows, then append fresh ones: the workhorse sliding-stream
    cycle.  Parity against the dense reference end-to-end."""
    rng = np.random.default_rng(3)
    m, n = 8, 6
    dense = _lowrank(m, n, 2, rng)
    st = SvdState.from_dense(jnp.asarray(dense), rank=4)
    new_rows = rng.normal(size=(2, m)) @ dense      # stays in the row space
    op = Compose((RemoveRows((0, 1)), AppendRows(new_rows)))
    out = _assert_parity(st, op)
    assert out.geometry[:2] == (m, n)


def test_remove_parity_against_dense_svd_of_deleted_matrix():
    """The literal acceptance sentence: api.apply(state, RemoveCols(idx))
    .materialize() == dense SVD of the column-deleted matrix."""
    dense = _lowrank(7, 9, 3)
    st = SvdState.from_dense(jnp.asarray(dense), rank=5)
    out = api.apply(st, RemoveCols((2, 6)))
    u, s, vt = np.linalg.svd(np.delete(dense, (2, 6), axis=1),
                             full_matrices=False)
    rec = (u[:, :5] * s[:5]) @ vt[:5]
    np.testing.assert_allclose(np.asarray(out.materialize()), rec, atol=1e-10)


# ---------------------------------------------------------------------------
# ill-conditioning: in-span deletions, repeated singular values
# ---------------------------------------------------------------------------


def test_remove_column_exactly_in_span():
    """Removing a column whose indicator e_j lies EXACTLY in span(V) drives
    the augmentation residual r_b to 0 — the engine's guarded normalization
    (residual > 1e-12 gate) must keep the downdate finite and exact."""
    rng = np.random.default_rng(5)
    m, n = 7, 6
    # A = u1 e_2^T + u2 w^T with w ⊥ e_2: V-span contains e_2 exactly
    e2 = np.zeros(n); e2[2] = 1.0
    w = rng.normal(size=n); w[2] = 0.0
    dense = np.outer(rng.normal(size=m), e2) + np.outer(rng.normal(size=m), w)
    st = SvdState.from_dense(jnp.asarray(dense), rank=4)
    out = api.apply(st, RemoveCols(2))
    got = np.asarray(out.materialize())
    assert np.isfinite(got).all()
    np.testing.assert_allclose(
        got, _top_r(np.delete(dense, 2, axis=1), 4), atol=1e-9)


def test_remove_nearly_in_span_column():
    """r_b -> 0 continuously: perturb the in-span construction by eps and
    pin the error budget explicitly."""
    rng = np.random.default_rng(6)
    m, n = 7, 6
    e2 = np.zeros(n); e2[2] = 1.0
    w = rng.normal(size=n); w[2] = 0.0
    for eps in (1e-6, 1e-10, 1e-13):
        dense = (np.outer(rng.normal(size=m), e2)
                 + np.outer(rng.normal(size=m), w)
                 + eps * np.outer(rng.normal(size=m), rng.normal(size=n)))
        st = SvdState.from_dense(jnp.asarray(dense), rank=4)
        got = np.asarray(api.apply(st, RemoveCols(2)).materialize())
        assert np.isfinite(got).all()
        # the deleted matrix has rank <= 3 + an eps-sized tail the rank-4
        # state absorbs; near-defective spectra amplify cancellation noise
        # to ~1e-7, so the budget here is looser than the exact-span case
        np.testing.assert_allclose(
            got, _top_r(np.delete(dense, 2, axis=1), 4), atol=1e-6)


def test_remove_row_with_repeated_singular_values():
    """Downdating a state with degenerate spectrum (repeated s_i) exercises
    the secular solver's clustered-root path."""
    rng = np.random.default_rng(7)
    m, n, r = 8, 6, 4
    qu, _ = np.linalg.qr(rng.normal(size=(m, r)))
    qv, _ = np.linalg.qr(rng.normal(size=(n, r)))
    s = np.array([3.0, 3.0, 3.0, 1.0])      # triple singular value
    dense = (qu * s) @ qv.T
    st = SvdState.from_dense(jnp.asarray(dense), rank=r + 1)
    got = np.asarray(api.apply(st, RemoveRows((0, 3))).materialize())
    assert np.isfinite(got).all()
    np.testing.assert_allclose(
        got, _top_r(np.delete(dense, (0, 3), axis=0), r + 1), atol=1e-9)


def test_remove_zero_column_is_a_no_op_downdate():
    """Deleting an all-zero column: the rank-1 step is a strict no-op
    (a = 0) and only the geometry shrinks."""
    rng = np.random.default_rng(8)
    dense = _lowrank(6, 5, 2, rng)
    dense[:, 3] = 0.0
    st = SvdState.from_dense(jnp.asarray(dense), rank=3)
    _assert_parity(st, RemoveCols(3))


# ---------------------------------------------------------------------------
# batched routes: stacked states, apply_many geometry-shrinking groups
# ---------------------------------------------------------------------------


def _stack(states):
    return SvdState(u=jnp.stack([s.u for s in states]),
                    s=jnp.stack([s.s for s in states]),
                    v=jnp.stack([s.v for s in states]))


@pytest.mark.parametrize("op", [
    RemoveRows((0, 4)), RemoveCols(1), Window(5, lam=0.9),
], ids=["rows", "cols", "window"])
def test_remove_parity_batched_stacked(op):
    rng = np.random.default_rng(12)
    sts = [_roomy_state(7, 6, 2, 4, rng) for _ in range(3)]
    out = api.apply(_stack(sts), op)
    for j, st in enumerate(sts):
        ref = _top_r(op.apply_dense(np.asarray(st.materialize())), 4)
        np.testing.assert_allclose(np.asarray(out.materialize())[j], ref,
                                   atol=1e-10)


def test_apply_many_groups_shrinking_schedules():
    """The ISSUE small-fix audit, pinned: same-(geometry, plan) downdates
    take the batched group path — whose rank-1 pairs bind from the STATE,
    not per-member op data — and match per-state singles exactly."""
    rng = np.random.default_rng(13)
    sts = [_roomy_state(7, 6, 2, 4, rng) for _ in range(4)]
    ops = [RemoveRows((1, 5))] * 4
    outs = apply_many(sts, ops)
    singles = [api.apply(st, op) for st, op in zip(sts, ops)]
    for got, want in zip(outs, singles):
        assert got.geometry[:2] == (5, 6)
        np.testing.assert_allclose(np.asarray(got.materialize()),
                                   np.asarray(want.materialize()), atol=1e-10)


def test_apply_many_mixed_shrinking_and_preserving_groups():
    """Different plans (and different post-op geometries) never share a
    group; every member still matches its own single-path result."""
    rng = np.random.default_rng(14)
    sts = [_roomy_state(7, 6, 2, 3, rng) for _ in range(5)]
    ops = [RemoveRows(0), RemoveRows(0), RemoveCols((1, 2)),
           Window(5, lam=0.5),
           RankK(rng.normal(size=(7, 2)), rng.normal(size=(6, 2)))]
    outs = apply_many(sts, ops)
    for st, op, got in zip(sts, ops, outs):
        want = api.apply(st, op)
        assert got.geometry == want.geometry
        np.testing.assert_allclose(np.asarray(got.materialize()),
                                   np.asarray(want.materialize()), atol=1e-10)


def test_apply_many_batched_scan_group():
    """Long deletion lists group-batch (one stacked drop + refactor)."""
    rng = np.random.default_rng(15)
    m = _SCAN_MIN + 6
    sts = [_roomy_state(m, 6, 2, 3, rng) for _ in range(3)]
    ops = [RemoveRows(tuple(range(_SCAN_MIN)))] * 3
    outs = apply_many(sts, ops)
    for st, op, got in zip(sts, ops, outs):
        ref = _top_r(op.apply_dense(np.asarray(st.materialize())), 3)
        np.testing.assert_allclose(np.asarray(got.materialize()), ref,
                                   atol=1e-9)


# ---------------------------------------------------------------------------
# warmup / planner bookkeeping through shrinking geometries
# ---------------------------------------------------------------------------


def test_warmup_plan_tracks_shrinking_geometries():
    pol = UpdatePolicy()
    op = Compose((RemoveRows((0, 1)), RemoveCols(0)))
    geoms = warmup_plan(pol, op, m=8, n=6, rank=3)
    # deletes dispatch no engine step: nothing to warm
    assert geoms == []


# ---------------------------------------------------------------------------
# serve wiring: enqueue_op validation + flush parity
# ---------------------------------------------------------------------------


def test_serve_enqueue_remove_and_window():
    from repro.serve.svd_service import SvdService

    rng = np.random.default_rng(21)
    svc = SvdService(max_batch=64)
    dense = {}
    for sid in ("a", "b"):
        d = _lowrank(8, 6, 2, rng)
        dense[sid] = d
        svc.register(sid, SvdState.from_dense(jnp.asarray(d), rank=3))
    svc.enqueue_op("a", RemoveRows((0, 5)))
    svc.enqueue_op("a", Window(5, lam=0.9))
    svc.enqueue_op("b", RemoveCols(2))
    assert svc._effective_shape("a") == (5, 6)
    assert svc._effective_shape("b") == (8, 5)
    while svc.flush():
        pass
    ref_a = Window(5, lam=0.9).apply_dense(
        RemoveRows((0, 5)).apply_dense(dense["a"]))
    ref_b = RemoveCols(2).apply_dense(dense["b"])
    for sid, ref in (("a", ref_a), ("b", ref_b)):
        np.testing.assert_allclose(
            np.asarray(svc.state(sid).materialize()),
            _top_r(np.asarray(ref), 3), atol=1e-9)


def test_serve_enqueue_remove_validation():
    from repro.serve.svd_service import SvdService

    svc = SvdService()
    svc.register("s", _roomy_state(6, 5, 2, 3))
    with pytest.raises(ValueError, match="out of range"):
        svc.enqueue_op("s", RemoveRows(6))
    with pytest.raises(ValueError, match="below its rank"):
        svc.enqueue_op("s", RemoveCols((0, 1, 2)))
    # validation runs against the EFFECTIVE (post-queue) geometry
    svc.enqueue_op("s", RemoveRows((0, 1)))
    with pytest.raises(ValueError, match="out of range"):
        svc.enqueue_op("s", RemoveRows(4))      # only 4 rows will remain
    # pairs enqueued after a queued downdate must match the shrunk geometry
    with pytest.raises(ValueError, match="geometry"):
        svc.enqueue("s", jnp.zeros(6), jnp.zeros(5))
    svc.enqueue("s", jnp.zeros(4), jnp.zeros(5))


# ---------------------------------------------------------------------------
# dist.merge compatibility: downdated shards merge like any truncated state
# ---------------------------------------------------------------------------


def test_merge_tree_after_downdates():
    """Shards that shrank by different amounts still merge: row blocks
    concatenate in order, and the merged SVD matches the dense stack."""
    rng = np.random.default_rng(31)
    base = _lowrank(12, 6, 2, rng)
    st0 = SvdState.from_dense(jnp.asarray(base[:6]), rank=4)
    st1 = SvdState.from_dense(jnp.asarray(base[6:]), rank=4)
    down0 = api.apply(st0, RemoveRows(1))
    down1 = api.apply(st1, Window(4, lam=1.0))
    merged = merge_tree([down0, down1], rank=4)
    ref = np.concatenate([np.delete(base[:6], 1, axis=0), base[6:][-4:]])
    np.testing.assert_allclose(np.asarray(merged.materialize()),
                               _top_r(ref, 4), atol=1e-9)


# ---------------------------------------------------------------------------
# mesh-sharded route (8 fake devices, subprocess)
# ---------------------------------------------------------------------------


def test_mesh_sharded_downdate_parity_on_8_devices():
    script = textwrap.dedent("""
        import json
        import jax
        jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp, numpy as np
        from repro import api
        from repro.updates import RemoveCols, RemoveRows, Window

        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        B, m, n, r = 8, 7, 6, 3

        def lowrank(m, n, q):
            return rng.normal(size=(m, q)) @ rng.normal(size=(q, n))

        dense = np.stack([lowrank(m, n, 2) for _ in range(B)])
        sts = [api.SvdState.from_dense(jnp.asarray(d), rank=r) for d in dense]
        stacked = api.SvdState(
            u=jnp.stack([s.u for s in sts]),
            s=jnp.stack([s.s for s in sts]),
            v=jnp.stack([s.v for s in sts]),
        )
        pol = api.UpdatePolicy(method="direct", mesh=mesh, batch_axis="data")

        def top_r(d, k):
            u, s, vt = np.linalg.svd(d, full_matrices=False)
            return (u[:, :k] * s[:k]) @ vt[:k]

        errs = {}
        for name, op in [("rows", RemoveRows((0, 4))),
                         ("cols", RemoveCols(1)),
                         ("window", Window(5, lam=0.9))]:
            out = api.apply(stacked, op, pol)
            e = 0.0
            for i in range(B):
                ref = top_r(np.asarray(op.apply_dense(dense[i])), r)
                e = max(e, float(np.abs(
                    np.asarray(out.materialize()[i]) - ref).max()))
            errs[name] = e
        errs["devices"] = jax.device_count()
        print(json.dumps(errs))
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
    for name in ("rows", "cols", "window"):
        assert out[name] < 1e-8, (name, out[name])
