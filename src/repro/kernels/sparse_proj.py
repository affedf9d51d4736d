"""Pallas TPU kernel: sparse gather/scatter projection (DESIGN.md §12).

The sparse-update hot spot: given a static-nnz COO perturbation
``S[rows[e], cols[e]] += vals[e]`` and a dense ``(src, k)`` factor block,
compute the projected ``(dst, k)`` core

    out[rows[e], :] += vals[e] * mat[cols[e], :]        for every entry e

i.e. ``out = S @ mat``.  Swapping ``rows``/``cols`` gives ``S^T @ mat``.
This is the ONLY dense contact the ``Sparse`` op's lowering makes with the
matrix geometry — cost O(nnz * k) plus the O((m+n) * k) range-finder
matmuls, never the O(m * n) a densified delta would pay.

Kernel shape (a genuinely new one for ``kernels/``): the COO coordinate
vectors live whole in SMEM (scalar memory — indices drive control flow and
dynamic addressing), the dense factor block and the output live in VMEM,
and the grid walks nnz in blocks with output revisiting — each program
gathers ``block_e`` source rows at dynamic indices and scatter-accumulates
them at dynamic destinations (``ref[pl.ds(idx, 1), :]``).  Padding entries
(``vals == 0`` at coordinate (0, 0)) are harmless by construction: they add
zero.

Batching: ``sparse_project_pallas_batched`` folds the batch axis into the
grid exactly like ``cauchy_matmul_pallas_batched``; the ``custom_vmap``
rule on the dispatching ``sparse_project`` routes ``jax.vmap`` there — ONE
launch for B sparse projections, not B sequential calls.

Off-TPU the dispatch runs ``sparse_project_xla`` — a dense XLA
``segment_sum`` over the gathered/scaled rows, which vmaps natively and is
the reference the interpret-mode kernel is pinned against in
``tests/test_sparse_proj.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import custom_batching
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "sparse_project",
    "sparse_project_pallas",
    "sparse_project_pallas_batched",
    "sparse_project_xla",
]


# ---------------------------------------------------------------------------
# Reference / fallback: one XLA segment-sum, vmaps natively
# ---------------------------------------------------------------------------


def _broadcast_batch(rows, cols, vals, mat):
    """Broadcast all four operands to a common leading batch shape.

    ``vals`` (..., nnz) and ``mat`` (..., src, k) define the batch; shared
    (unbatched) coordinate vectors broadcast up to it — the common case of
    one COO pattern projected against a batch of factor blocks.
    """
    rows = jnp.asarray(rows, jnp.int32)
    cols = jnp.asarray(cols, jnp.int32)
    vals = jnp.asarray(vals)
    mat = jnp.asarray(mat)
    lead = jnp.broadcast_shapes(vals.shape[:-1], mat.shape[:-2])
    return (
        jnp.broadcast_to(rows, lead + rows.shape[-1:]),
        jnp.broadcast_to(cols, lead + cols.shape[-1:]),
        jnp.broadcast_to(vals, lead + vals.shape[-1:]),
        jnp.broadcast_to(mat, lead + mat.shape[-2:]),
    )


def sparse_project_xla(rows, cols, vals, mat, out_rows: int):
    """``out[r, :] = sum_e [rows[e] == r] * vals[e] * mat[cols[e], :]``.

    ``rows``/``cols``/``vals``: (..., nnz); ``mat``: (..., src, k).  Leading
    batch axes broadcast zip-wise (the XLA scatter-add vmaps natively);
    operands missing the batch axes (e.g. shared coordinates under batched
    values) broadcast up.
    """
    vals = jnp.asarray(vals)
    if vals.ndim > 1:
        rows, cols, vals, mat = _broadcast_batch(rows, cols, vals, mat)
        return jax.vmap(
            lambda r, c, v, m_: sparse_project_xla(r, c, v, m_, out_rows)
        )(rows, cols, vals, mat)
    mat = jnp.asarray(mat)
    gathered = vals[:, None] * mat[jnp.asarray(cols), :]        # (nnz, k)
    return jax.ops.segment_sum(gathered, jnp.asarray(rows),
                               num_segments=out_rows)


# ---------------------------------------------------------------------------
# Pallas kernels: COO coordinates in SMEM, factors in VMEM, nnz in the grid
# ---------------------------------------------------------------------------


def _kernel(rows_ref, cols_ref, vals_ref, mat_ref, out_ref, *, block_e: int):
    blk = pl.program_id(0)

    @pl.when(blk == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    base = blk * block_e

    def body(e, carry):
        r = rows_ref[base + e]
        c = cols_ref[base + e]
        val = vals_ref[base + e]
        out_ref[pl.ds(r, 1), :] += val * mat_ref[pl.ds(c, 1), :]
        return carry

    # int32 throughout: under x64 int literals are int64, which Mosaic rejects
    jax.lax.fori_loop(jnp.int32(0), jnp.int32(block_e), body, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("out_rows", "block_e", "interpret"))
def sparse_project_pallas(
    rows: jax.Array,
    cols: jax.Array,
    vals: jax.Array,
    mat: jax.Array,
    out_rows: int,
    *,
    block_e: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Single-instance kernel: ``rows``/``cols``/``vals`` (nnz,), ``mat``
    (src, k) -> (out_rows, k).  nnz is padded to a ``block_e`` multiple with
    zero-valued entries at coordinate (0, 0) — an exact no-op."""
    nnz = vals.shape[0]
    be = min(block_e, max(8, nnz))
    pad_e = (-nnz) % be
    rows_p = jnp.pad(rows.astype(jnp.int32), (0, pad_e))
    cols_p = jnp.pad(cols.astype(jnp.int32), (0, pad_e))
    vals_p = jnp.pad(vals, (0, pad_e))
    grid = ((nnz + pad_e) // be,)
    return pl.pallas_call(
        functools.partial(_kernel, block_e=be),
        grid=grid,
        in_specs=[
            pl.BlockSpec(rows_p.shape, lambda i: (i * 0,), memory_space=pltpu.SMEM),
            pl.BlockSpec(cols_p.shape, lambda i: (i * 0,), memory_space=pltpu.SMEM),
            pl.BlockSpec(vals_p.shape, lambda i: (i * 0,), memory_space=pltpu.SMEM),
            pl.BlockSpec(mat.shape, lambda i: (i * 0, i * 0)),
        ],
        out_specs=pl.BlockSpec((out_rows, mat.shape[1]), lambda i: (i * 0, i * 0)),
        out_shape=jax.ShapeDtypeStruct((out_rows, mat.shape[1]), mat.dtype),
        interpret=interpret,
    )(rows_p, cols_p, vals_p, mat)


def _kernel_batched(rows_ref, cols_ref, vals_ref, mat_ref, out_ref, *,
                    block_e: int):
    b = pl.program_id(0)
    blk = pl.program_id(1)

    @pl.when(blk == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    base = blk * block_e

    def body(e, carry):
        r = rows_ref[b, base + e]
        c = cols_ref[b, base + e]
        val = vals_ref[b, base + e]
        out_ref[0, pl.ds(r, 1), :] += val * mat_ref[0, pl.ds(c, 1), :]
        return carry

    # int32 throughout: under x64 int literals are int64, which Mosaic rejects
    jax.lax.fori_loop(jnp.int32(0), jnp.int32(block_e), body, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("out_rows", "block_e", "interpret"))
def sparse_project_pallas_batched(
    rows: jax.Array,
    cols: jax.Array,
    vals: jax.Array,
    mat: jax.Array,
    out_rows: int,
    *,
    block_e: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Batched kernel: (B, nnz) coordinates, (B, src, k) factors -> (B,
    out_rows, k).  Grid (B, nnz/BE) — batch outermost, exactly the
    ``cauchy_matmul_pallas_batched`` fold."""
    bsz, nnz = vals.shape
    be = min(block_e, max(8, nnz))
    pad_e = (-nnz) % be
    rows_p = jnp.pad(rows.astype(jnp.int32), ((0, 0), (0, pad_e)))
    cols_p = jnp.pad(cols.astype(jnp.int32), ((0, 0), (0, pad_e)))
    vals_p = jnp.pad(vals, ((0, 0), (0, pad_e)))
    grid = (bsz, (nnz + pad_e) // be)
    src, k = mat.shape[-2:]
    return pl.pallas_call(
        functools.partial(_kernel_batched, block_e=be),
        grid=grid,
        in_specs=[
            pl.BlockSpec(rows_p.shape, lambda b, i: (b * 0, i * 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(cols_p.shape, lambda b, i: (b * 0, i * 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(vals_p.shape, lambda b, i: (b * 0, i * 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, src, k), lambda b, i: (b, i * 0, i * 0)),
        ],
        out_specs=pl.BlockSpec((1, out_rows, k), lambda b, i: (b, i * 0, i * 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, out_rows, k), mat.dtype),
        interpret=interpret,
    )(rows_p, cols_p, vals_p, mat)


# ---------------------------------------------------------------------------
# Dispatch: Pallas (custom_vmap batch-in-grid) on TPU, XLA elsewhere
# ---------------------------------------------------------------------------


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


@functools.lru_cache(maxsize=None)
def _pallas_project_vmapped(out_rows: int):
    @custom_batching.custom_vmap
    def f(rows, cols, vals, mat):
        return sparse_project_pallas(rows, cols, vals, mat, out_rows,
                                     interpret=_interpret_default())

    @f.def_vmap
    def _f_vmap(axis_size, in_batched, rows, cols, vals, mat):
        def bcast(x, batched):
            return x if batched else jnp.broadcast_to(x, (axis_size,) + x.shape)

        args = [bcast(x, b) for x, b in zip((rows, cols, vals, mat), in_batched)]
        if args[2].ndim > 2:  # nested vmap: collapse leading axes into one batch
            lead = args[2].shape[:-1]
            args = [x.reshape((-1,) + x.shape[len(lead):]) for x in args]
            out = sparse_project_pallas_batched(*args, out_rows,
                                                interpret=_interpret_default())
            return out.reshape(lead + out.shape[1:]), True
        out = sparse_project_pallas_batched(*args, out_rows,
                                            interpret=_interpret_default())
        return out, True

    return f


def sparse_project(rows, cols, vals, mat, out_rows: int, *,
                   interpret: bool | None = None):
    """Dispatching entry: ``out = S @ mat`` for the static-nnz COO ``S``.

    ``interpret`` forces interpret-mode Pallas (tests); otherwise Pallas on
    TPU (vmap folds the batch into the grid), the XLA segment-sum fallback
    elsewhere.  Leading batch axes on all four operands run batched.
    """
    vals = jnp.asarray(vals)
    batched = vals.ndim > 1 or jnp.asarray(mat).ndim > 2
    if interpret is not None:
        if batched:
            r, c, v, m_ = _broadcast_batch(rows, cols, vals, mat)
            lead = v.shape[:-1]
            out = sparse_project_pallas_batched(
                r.reshape((-1,) + r.shape[-1:]),
                c.reshape((-1,) + c.shape[-1:]),
                v.reshape((-1,) + v.shape[-1:]),
                m_.reshape((-1,) + m_.shape[-2:]),
                out_rows, interpret=interpret)
            return out.reshape(lead + out.shape[1:])
        return sparse_project_pallas(
            jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32), vals,
            jnp.asarray(mat), out_rows, interpret=interpret)
    if jax.default_backend() == "tpu":
        f = _pallas_project_vmapped(out_rows)
        if batched:
            return jax.vmap(f)(*_broadcast_batch(rows, cols, vals, mat))
        return f(jnp.asarray(rows), jnp.asarray(cols), vals, jnp.asarray(mat))
    return sparse_project_xla(rows, cols, vals, mat, out_rows)
