"""The structured-perturbation op algebra (DESIGN.md §10).

The paper gives one primitive — absorb ``a b^T`` into an SVD — but real
streaming workloads arrive as *structured* perturbations: mini-batch rank-k
gradient updates, row/column appends from new users, forgetting-factor decay
on stale streams (Peña & Sauer, arXiv:1809.03285; Deng et al.,
arXiv:2401.09703).  This module is the declarative layer: each op is a
frozen, registered-pytree dataclass with an *exact reference semantics*
``op.apply_dense(A)``; ``repro.updates.planner`` lowers any op onto a
minimal schedule of plan-cached rank-1 engine dispatches.

Ops:

* ``RankK(u, v)`` — ``A + u @ v^T`` with ``u (…, m, k)``, ``v (…, n, k)``.
* ``AppendRows(rows)`` / ``AppendCols(cols)`` — grow the matrix by new rows
  ``(p, n)`` / columns ``(m, p)``; ``from_svd`` carries a pre-factored block
  (the form ``dist.merge`` feeds) so lowering skips the dense SVD.
* ``DenseDelta(delta, rank)`` — ``A + delta`` lowered through a top-``rank``
  randomized sketch of ``delta`` (exact when ``rank >= rank(delta)``).
* ``Sparse(rows, cols, vals, rank)`` — ``A + S`` for a static-nnz COO delta;
  the lowering cost scales with nnz (``updates.sketch`` +
  ``kernels.sparse_proj``), never densifying m x n.
* ``Decay(lam)`` — ``lam * A``; folds into the singular values for free
  (zero engine dispatches).
* ``RemoveRows(idx)`` / ``RemoveCols(idx)`` — *downdates*: delete rows /
  columns by static index.  The planner drops the factor rows and
  re-orthonormalizes with one tall QR + an r x r core SVD — exact, no
  engine dispatch, no LAPACK SVD anywhere.
* ``Window(size)`` — sliding-window convenience: keep the last ``size``
  rows (optionally decayed by ``lam``); lowers to
  ``Compose(Decay, RemoveRows(oldest...))``.
* ``Compose(ops)`` — apply a tuple of ops left-to-right.

Every op also carries:

* ``out_shape(m, n)`` — the geometry after the op (appends grow it);
* ``spec()`` — a hashable structural descriptor (type + static shape info,
  no array data).  It keys the planner's schedule cache and serializes into
  ``ServiceSnapshot`` aux JSON, from which ``skeleton_from_spec`` rebuilds a
  placeholder-leaf op with the identical pytree structure (checkpoint
  restore).

>>> import numpy as np
>>> from repro.updates import RankK, Decay, Compose
>>> a_mat = np.ones((2, 3))
>>> op = Compose((Decay(0.5), RankK(np.ones((2, 1)), np.ones((3, 1)))))
>>> np.asarray(op.apply_dense(a_mat))
array([[1.5, 1.5, 1.5],
       [1.5, 1.5, 1.5]])
>>> op.spec()
('compose', (('decay',), ('rank_k', 1)))
>>> op.out_shape(2, 3)
(2, 3)
"""

from __future__ import annotations

import dataclasses
import operator
from functools import partial

import jax
import jax.numpy as jnp

__all__ = [
    "AppendCols",
    "AppendRows",
    "Compose",
    "Decay",
    "DenseDelta",
    "RankK",
    "RemoveCols",
    "RemoveRows",
    "Sparse",
    "UpdateOp",
    "Window",
    "skeleton_from_spec",
    "spec_from_json",
    "spec_to_json",
]


def _normalize_idx(idx, what: str) -> tuple:
    """Sorted tuple of unique non-negative ints (static meta — keys the
    schedule cache and serializes into snapshot aux)."""
    try:
        idx = (operator.index(idx),)
    except TypeError:
        pass
    try:
        out = tuple(int(i) for i in idx)
    except TypeError:
        raise TypeError(f"{what} takes an int or a sequence of ints; "
                        f"got {idx!r}") from None
    if not out:
        raise ValueError(f"{what} needs at least one index")
    if any(i < 0 for i in out):
        raise ValueError(f"{what} indices must be non-negative; got {out}")
    if len(set(out)) != len(out):
        # a duplicate index names one row twice: refuse the ambiguity
        raise ValueError(f"{what} indices must be unique; got {out}")
    return tuple(sorted(out))


class UpdateOp:
    """Base class (isinstance anchor) for structured-perturbation ops."""

    def apply_dense(self, a_mat):
        """Exact reference semantics on a dense matrix."""
        raise NotImplementedError

    def out_shape(self, m: int, n: int) -> tuple[int, int]:
        """Geometry after the op (appends grow it; everything else keeps it)."""
        return (m, n)

    def spec(self) -> tuple:
        """Hashable structural descriptor: planner cache key + snapshot aux."""
        raise NotImplementedError

    def replace(self, **kw):
        return dataclasses.replace(self, **kw)


@partial(jax.tree_util.register_dataclass, data_fields=["u", "v"], meta_fields=[])
@dataclasses.dataclass(frozen=True)
class RankK(UpdateOp):
    """``A + u @ v^T``: a rank-k perturbation, e.g. a mini-batch of gradient
    sketches.  ``u``: (…, m, k), ``v``: (…, n, k); a leading batch axis means
    one rank-k update per stacked problem.

    >>> import numpy as np
    >>> op = RankK(np.eye(3, 2), np.eye(4, 2))
    >>> op.k, op.spec()
    (2, ('rank_k', 2))
    """

    u: jax.Array
    v: jax.Array

    @property
    def k(self) -> int:
        return self.u.shape[-1]

    def apply_dense(self, a_mat):
        return jnp.asarray(a_mat) + jnp.einsum(
            "...mk,...nk->...mn", jnp.asarray(self.u), jnp.asarray(self.v)
        )

    def spec(self) -> tuple:
        return ("rank_k", self.k)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["rows", "u", "s", "v"],
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class AppendRows(UpdateOp):
    """Grow the matrix by ``p`` new rows: ``[A; rows]``.

    Two storage modes: dense ``rows (p, n)``, or a pre-factored block
    ``from_svd(u, s, v)`` (``u (p, q)``, ``s (q,)``, ``v (n, q)``) — the form
    a ``dist.merge`` shard already carries, lowered without any dense SVD.

    >>> import numpy as np
    >>> AppendRows(np.zeros((2, 5))).out_shape(3, 5)
    (5, 5)
    """

    rows: jax.Array | None = None
    u: jax.Array | None = None
    s: jax.Array | None = None
    v: jax.Array | None = None

    def __post_init__(self):
        dense = self.rows is not None
        factored = self.u is not None and self.s is not None and self.v is not None
        if dense == factored:
            raise ValueError("AppendRows takes either rows= or from_svd factors")

    @classmethod
    def from_svd(cls, u, s, v) -> "AppendRows":
        return cls(rows=None, u=u, s=s, v=v)

    @property
    def p(self) -> int:
        """Number of appended rows."""
        return self.rows.shape[0] if self.rows is not None else self.u.shape[0]

    @property
    def block_rank(self) -> int:
        """Rank budget of the lowering (q components)."""
        if self.rows is not None:
            return min(self.rows.shape[0], self.rows.shape[1])
        return self.s.shape[0]

    def apply_dense(self, a_mat):
        block = self.rows
        if block is None:
            block = jnp.einsum("pq,q,nq->pn", self.u, self.s, self.v)
        return jnp.concatenate([jnp.asarray(a_mat), jnp.asarray(block)], axis=0)

    def out_shape(self, m: int, n: int) -> tuple[int, int]:
        return (m + self.p, n)

    def spec(self) -> tuple:
        mode = "dense" if self.rows is not None else "factored"
        return ("append_rows", self.p, self.block_rank, mode)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["cols", "u", "s", "v"],
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class AppendCols(UpdateOp):
    """Grow the matrix by ``p`` new columns: ``[A, cols]``.

    ``from_svd(u, s, v)`` carries a pre-factored block (``u (m, q)``,
    ``s (q,)``, ``v (p, q)``).

    >>> import numpy as np
    >>> AppendCols(np.zeros((3, 2))).out_shape(3, 5)
    (3, 7)
    """

    cols: jax.Array | None = None
    u: jax.Array | None = None
    s: jax.Array | None = None
    v: jax.Array | None = None

    def __post_init__(self):
        dense = self.cols is not None
        factored = self.u is not None and self.s is not None and self.v is not None
        if dense == factored:
            raise ValueError("AppendCols takes either cols= or from_svd factors")

    @classmethod
    def from_svd(cls, u, s, v) -> "AppendCols":
        return cls(cols=None, u=u, s=s, v=v)

    @property
    def p(self) -> int:
        return self.cols.shape[1] if self.cols is not None else self.v.shape[0]

    @property
    def block_rank(self) -> int:
        if self.cols is not None:
            return min(self.cols.shape[0], self.cols.shape[1])
        return self.s.shape[0]

    def apply_dense(self, a_mat):
        block = self.cols
        if block is None:
            block = jnp.einsum("mq,q,pq->mp", self.u, self.s, self.v)
        return jnp.concatenate([jnp.asarray(a_mat), jnp.asarray(block)], axis=1)

    def out_shape(self, m: int, n: int) -> tuple[int, int]:
        return (m, n + self.p)

    def spec(self) -> tuple:
        mode = "dense" if self.cols is not None else "factored"
        return ("append_cols", self.p, self.block_rank, mode)


@partial(
    jax.tree_util.register_dataclass, data_fields=["delta"], meta_fields=["rank"]
)
@dataclasses.dataclass(frozen=True)
class DenseDelta(UpdateOp):
    """``A + delta`` lowered through a top-``rank`` randomized sketch of
    ``delta`` (``updates.sketch.sketch_svd`` — O(m·n·rank), no LAPACK SVD).

    Exact when ``rank >= rank(delta)``; otherwise the lowering absorbs a
    near-best rank-``rank`` approximation of the delta (the reference
    semantics ``apply_dense`` stays the exact dense sum — parity tests
    should feed deltas within the sketch budget; the policy's
    ``sketch_oversample`` / ``sketch_power_iters`` knobs tune the tail).

    >>> import numpy as np
    >>> DenseDelta(np.ones((3, 4)), rank=1).spec()
    ('dense_delta', 1)
    """

    delta: jax.Array
    rank: int = 1

    def __post_init__(self):
        if self.rank < 1:
            raise ValueError(f"sketch rank must be >= 1; got {self.rank}")

    def apply_dense(self, a_mat):
        return jnp.asarray(a_mat) + jnp.asarray(self.delta)

    def spec(self) -> tuple:
        return ("dense_delta", self.rank)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["rows", "cols", "vals"],
    meta_fields=["rank"],
)
@dataclasses.dataclass(frozen=True)
class Sparse(UpdateOp):
    """``A + S`` for a static-nnz COO sparse delta ``S[rows[e], cols[e]] +=
    vals[e]`` — the representation-learning workload (each event touches a
    few rows of an embedding matrix; Deng et al., arXiv:2401.09703).

    ``rows``/``cols``/``vals``: (…, nnz) int/int/float with a leading batch
    axis iff one sparse delta per stacked problem.  ``nnz`` is static (it
    keys the schedule cache); streams with varying event counts pad to a
    bucket size with zero-valued entries at coordinate (0, 0) — exact
    no-ops.  Duplicate coordinates accumulate.  ``rank`` budgets the
    lowering (``rank >= rank(S)`` is exact; nnz entries touching ``r`` rows
    or ``c`` columns have ``rank(S) <= min(r, c) <= nnz``).

    The planner lowers through ``updates.sketch.sparse_sketch_svd`` +
    ``kernels.sparse_proj`` at O((m+n)·k² + nnz·k) — never densifying m·n.

    >>> import numpy as np
    >>> op = Sparse(np.array([0, 2]), np.array([1, 0]), np.array([5.0, -1.0]))
    >>> op.nnz, op.spec()
    (2, ('sparse', 2, 1))
    >>> np.asarray(op.apply_dense(np.zeros((3, 2))))
    array([[ 0.,  5.],
           [ 0.,  0.],
           [-1.,  0.]])
    """

    rows: jax.Array
    cols: jax.Array
    vals: jax.Array
    rank: int = 1

    def __post_init__(self):
        if self.rank < 1:
            raise ValueError(f"sketch rank must be >= 1; got {self.rank}")

    @property
    def nnz(self) -> int:
        """Static entry count (padding entries included)."""
        return self.vals.shape[-1]

    def apply_dense(self, a_mat):
        a_mat = jnp.asarray(a_mat)
        rows = jnp.asarray(self.rows)
        cols = jnp.asarray(self.cols)
        vals = jnp.asarray(self.vals)

        def one(base, r, c, v):
            return base.at[r, c].add(v)

        if vals.ndim == 1:
            if a_mat.ndim == 2:
                return one(a_mat, rows, cols, vals)
            return jax.vmap(lambda base: one(base, rows, cols, vals))(a_mat)
        if a_mat.ndim == 2:
            a_mat = jnp.broadcast_to(a_mat, vals.shape[:-1] + a_mat.shape)
        return jax.vmap(one)(a_mat, rows, cols, vals)

    def spec(self) -> tuple:
        return ("sparse", self.nnz, self.rank)


@partial(jax.tree_util.register_dataclass, data_fields=["lam"], meta_fields=[])
@dataclasses.dataclass(frozen=True)
class Decay(UpdateOp):
    """Forgetting-factor rescale ``lam * A`` — folds into the singular values
    for free (the planner emits zero engine dispatches for it).

    >>> import numpy as np
    >>> np.asarray(Decay(0.5).apply_dense(np.full((1, 2), 4.0)))
    array([[2., 2.]])
    """

    lam: jax.Array | float

    def apply_dense(self, a_mat):
        return jnp.asarray(self.lam) * jnp.asarray(a_mat)

    def spec(self) -> tuple:
        return ("decay",)


@partial(jax.tree_util.register_dataclass, data_fields=[], meta_fields=["idx"])
@dataclasses.dataclass(frozen=True)
class RemoveRows(UpdateOp):
    """Delete rows ``idx`` (static, unique, sorted): the downdate dual of
    ``AppendRows``.  Lowering drops the rows of ``u`` and re-factors (one
    tall QR + an r x r core SVD; no engine dispatch).  Carries no array
    data: the whole op is static metadata.

    >>> import numpy as np
    >>> op = RemoveRows((2, 0))
    >>> op.idx, op.spec(), op.out_shape(4, 3)
    ((0, 2), ('remove_rows', (0, 2)), (2, 3))
    >>> np.asarray(op.apply_dense(np.arange(12.0).reshape(4, 3)))
    array([[ 3.,  4.,  5.],
           [ 9., 10., 11.]])
    """

    idx: tuple

    def __post_init__(self):
        object.__setattr__(self, "idx", _normalize_idx(self.idx, "RemoveRows"))

    @property
    def p(self) -> int:
        """Number of removed rows."""
        return len(self.idx)

    def apply_dense(self, a_mat):
        a_mat = jnp.asarray(a_mat)
        if self.idx[-1] >= a_mat.shape[-2]:
            raise ValueError(
                f"RemoveRows{self.idx} out of range for {a_mat.shape[-2]} rows"
            )
        return jnp.delete(a_mat, jnp.array(self.idx), axis=-2)

    def out_shape(self, m: int, n: int) -> tuple[int, int]:
        return (m - self.p, n)

    def spec(self) -> tuple:
        return ("remove_rows", self.idx)


@partial(jax.tree_util.register_dataclass, data_fields=[], meta_fields=["idx"])
@dataclasses.dataclass(frozen=True)
class RemoveCols(UpdateOp):
    """Delete columns ``idx``: the downdate dual of ``AppendCols`` (the
    ``SVD.remove_column`` algebra, batched and LAPACK-free — drop the rows
    of ``v``, then re-factor as ``RemoveRows`` does).

    >>> import numpy as np
    >>> op = RemoveCols(1)
    >>> op.idx, op.spec(), op.out_shape(2, 3)
    ((1,), ('remove_cols', (1,)), (2, 2))
    >>> np.asarray(op.apply_dense(np.arange(6.0).reshape(2, 3)))
    array([[0., 2.],
           [3., 5.]])
    """

    idx: tuple

    def __post_init__(self):
        object.__setattr__(self, "idx", _normalize_idx(self.idx, "RemoveCols"))

    @property
    def p(self) -> int:
        """Number of removed columns."""
        return len(self.idx)

    def apply_dense(self, a_mat):
        a_mat = jnp.asarray(a_mat)
        if self.idx[-1] >= a_mat.shape[-1]:
            raise ValueError(
                f"RemoveCols{self.idx} out of range for {a_mat.shape[-1]} cols"
            )
        return jnp.delete(a_mat, jnp.array(self.idx), axis=-1)

    def out_shape(self, m: int, n: int) -> tuple[int, int]:
        return (m, n - self.p)

    def spec(self) -> tuple:
        return ("remove_cols", self.idx)


@partial(jax.tree_util.register_dataclass, data_fields=["lam"],
         meta_fields=["size"])
@dataclasses.dataclass(frozen=True)
class Window(UpdateOp):
    """Sliding-window convenience: keep the LAST ``size`` rows (rows append
    at the bottom, so the oldest stream entries leave first), with an
    optional forgetting factor ``lam`` on the survivors.  Lowers to
    ``Compose(Decay(lam), RemoveRows(range(m - size)))`` — a decay fold plus
    one row drop + re-factor; a no-op shrink when the state already fits
    (``m <= size``).

    >>> import numpy as np
    >>> op = Window(2)
    >>> op.spec(), op.out_shape(5, 3), op.out_shape(1, 3)
    (('window', 2), (2, 3), (1, 3))
    >>> np.asarray(Window(2, lam=0.5).apply_dense(np.arange(8.0).reshape(4, 2)))
    array([[2. , 2.5],
           [3. , 3.5]])
    """

    size: int
    lam: jax.Array | float = 1.0

    def __post_init__(self):
        if not isinstance(self.size, int) or self.size < 1:
            raise ValueError(f"window size must be an int >= 1; got {self.size}")

    def apply_dense(self, a_mat):
        a_mat = jnp.asarray(a_mat)
        m = a_mat.shape[-2]
        kept = a_mat[..., max(0, m - self.size):, :]
        return jnp.asarray(self.lam) * kept

    def out_shape(self, m: int, n: int) -> tuple[int, int]:
        return (min(m, self.size), n)

    def spec(self) -> tuple:
        return ("window", self.size)


@partial(jax.tree_util.register_dataclass, data_fields=["ops"], meta_fields=[])
@dataclasses.dataclass(frozen=True)
class Compose(UpdateOp):
    """Apply a tuple of ops left-to-right: ``Compose((f, g))`` is "f, then g".

    >>> import numpy as np
    >>> op = Compose((Decay(2.0), Decay(3.0)))
    >>> float(op.apply_dense(np.ones((1, 1)))[0, 0])
    6.0
    """

    ops: tuple

    def __post_init__(self):
        object.__setattr__(self, "ops", tuple(self.ops))
        for child in self.ops:
            if not isinstance(child, UpdateOp):
                raise TypeError(f"Compose takes UpdateOps; got {type(child)}")

    def apply_dense(self, a_mat):
        out = jnp.asarray(a_mat)
        for child in self.ops:
            out = child.apply_dense(out)
        return out

    def out_shape(self, m: int, n: int) -> tuple[int, int]:
        for child in self.ops:
            m, n = child.out_shape(m, n)
        return (m, n)

    def spec(self) -> tuple:
        return ("compose", tuple(child.spec() for child in self.ops))


# ---------------------------------------------------------------------------
# Spec serialization: planner cache keys are the tuple form; ServiceSnapshot
# aux JSON carries the list form; skeletons rebuild placeholder-leaf ops with
# the exact pytree structure of the originals (checkpoint treedefs).
# ---------------------------------------------------------------------------


def spec_to_json(spec: tuple):
    """Tuple spec -> JSON-able nested lists."""
    return [spec_to_json(x) if isinstance(x, tuple) else x for x in spec]


def spec_from_json(spec) -> tuple:
    """JSON nested lists -> hashable tuple spec."""
    return tuple(spec_from_json(x) if isinstance(x, list) else x for x in spec)


def skeleton_from_spec(spec: tuple) -> UpdateOp:
    """Placeholder-leaf op with the pytree structure the spec describes —
    what ``ServiceSnapshot.skeleton`` unflattens restored leaves into.

    >>> import jax, numpy as np
    >>> op = RankK(np.zeros((3, 2)), np.zeros((4, 2)))
    >>> skel = skeleton_from_spec(op.spec())
    >>> jax.tree.structure(skel) == jax.tree.structure(op)
    True
    """
    kind = spec[0]
    if kind == "rank_k":
        return RankK(u=0.0, v=0.0)
    if kind in ("append_rows", "append_cols"):
        cls = AppendRows if kind == "append_rows" else AppendCols
        if spec[3] == "dense":
            return cls(0.0)
        return cls.from_svd(0.0, 0.0, 0.0)
    if kind == "dense_delta":
        return DenseDelta(delta=0.0, rank=spec[1])
    if kind == "sparse":
        return Sparse(rows=0.0, cols=0.0, vals=0.0, rank=spec[2])
    if kind == "decay":
        return Decay(lam=0.0)
    if kind == "remove_rows":
        return RemoveRows(spec[1])
    if kind == "remove_cols":
        return RemoveCols(spec[1])
    if kind == "window":
        return Window(size=spec[1], lam=0.0)
    if kind == "compose":
        return Compose(tuple(skeleton_from_spec(c) for c in spec[1]))
    raise ValueError(f"unknown op spec {spec!r}")
