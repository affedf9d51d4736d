"""The ONE bisection+Newton secular loop body (kernel + reference + fused).

``kernels.secular_newton`` (the Pallas kernel), ``kernels.ref`` (its pure-jnp
oracle) and ``kernels.fused_update`` (the fused megakernel's secular phase)
all iterate the same fixed-count hybrid solve of

    w(mu) = 1 + rho * sum_j zc2_j / (dc_j - mu),   mu = anchor + tau,

on a precomputed difference tensor ``diff = dc - anchor``.  Before this
module the loop body was copy-pasted between the kernel and the reference —
they could drift silently.  Now there is exactly one definition; the only
degree of freedom is the layout (``poles_axis``): the secular kernel tiles
roots along the last axis (diff ``(N, BM)``), the fused kernel keeps roots
along the first (diff ``(K, K)``).

Everything here is plain jnp on values (no refs, no pallas imports), so the
same function body traces inside a Pallas kernel, inside jit, and in
interpret mode unchanged.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

__all__ = ["deflation_rtols", "secular_iterate"]


def deflation_rtols(dtype, rtol: float | None = None):
    """``(gap_rtol, z_rtol)`` of the deflation test, relative to ``scale =
    max(|d|, rho ||z||^2)``: poles closer than ``gap_rtol * scale`` merge,
    and a merged weight deflates when ``rho z_i^2 <= gap_rtol * scale``
    (``z_rtol is None``) or else when ``rho |z_i| ||z|| <= z_rtol * scale``.
    An explicit ``rtol`` (``UpdatePolicy.deflate_rtol``) sets the gap
    tolerance.
    """
    eps = float(jnp.finfo(dtype).eps)
    if jnp.dtype(dtype).itemsize >= 8:
        return (64.0 * eps if rtol is None else rtol), None
    return (8.0 * eps if rtol is None else rtol), 8.0 * eps


def secular_iterate(
    diff,
    zc2,
    rho,
    lo,
    hi,
    *,
    n_bisect: int = 58,
    n_newton: int = 4,
    poles_axis: int = 0,
):
    """Fixed-count bisection + projected-Newton solve of the secular equation.

    ``diff[j, i] = dc_j - anchor_i`` when ``poles_axis == 0`` (roots on the
    last axis, ``zc2``: (N,), ``lo``/``hi``/result: (M,)), or
    ``diff[i, j] = dc_j - anchor_i`` when ``poles_axis == 1`` (roots on the
    first axis).  ``zc2`` must already be zeroed at invalid sources.  Returns
    the per-root offset ``tau`` with ``w(anchor + tau) ~= 0``, clipped to the
    bracket.  ``zc2``/``lo``/``hi`` may instead come 2-D, already shaped to
    broadcast against ``diff`` (``(1, N)``/``(M, 1)`` for ``poles_axis == 1``)
    — the layout Mosaic needs; ``tau`` then keeps that 2-D shape.
    """
    dt = diff.dtype
    flat = lo.ndim == 1
    if flat:
        # 1-D roots/poles: lift into the 2-D layout (poles broadcast along
        # the roots axis, roots along the poles axis) the body runs in.
        zc2 = jnp.expand_dims(zc2, 1 - poles_axis)
        lo = jnp.expand_dims(lo, poles_axis)
        hi = jnp.expand_dims(hi, poles_axis)

    # Bisection only ever looks at the SIGN of w, so it gets a w-only
    # evaluation; the derivative reduction (inv*inv) — ~40% of the work per
    # iteration — is computed only inside the Newton steps that use it.
    def _inv(tau):
        # Unguarded reciprocal + one select: 1/0 is a trap-free inf in
        # IEEE and the where picks 0 at exact-pole slots (deflated
        # entries, collapsed brackets).  No grads flow through here, so
        # the usual double-where safe-divide dance would only cost two
        # extra tensor passes per secular iteration.
        delta = diff - tau
        return jnp.where(delta == 0.0, 0.0, 1.0 / delta)

    def _sum(x):
        return jnp.sum(x, axis=poles_axis, keepdims=True)

    def w_only(tau):
        return 1.0 + rho * _sum(zc2 * _inv(tau))

    def w_of(tau):
        inv = _inv(tau)
        r = zc2 * inv
        return 1.0 + rho * _sum(r), rho * _sum(r * inv)

    def bis_step(_, carry):
        lo_c, hi_c = carry
        mid = 0.5 * (lo_c + hi_c)
        w = w_only(mid)
        go_right = w < 0.0  # w increasing on the bracket: root above mid
        return jnp.where(go_right, mid, lo_c), jnp.where(go_right, hi_c, mid)

    # int32 trip counts: under x64 a Python-int loop index is int64, which
    # the TPU kernel compiler rejects
    lo_f, hi_f = lax.fori_loop(jnp.int32(0), jnp.int32(n_bisect), bis_step,
                               (lo, hi))

    # Safeguarded pole-free Newton.  The anchor is always a pole of w, so
    # roots hugging it (tau -> 0) stall plain Newton: the linear model of a
    # near-hyperbola lands outside the bracket and every iteration degrades
    # to a bisection halving.  Iterating on f(tau) = tau * w(tau) instead
    # removes exactly that singularity — the anchor's term tau * rho*z_a^2 /
    # (0 - tau) is constant — and f is smooth on the whole bracket (all
    # other poles lie outside it), so Newton on f is quadratic even for
    # pole-hugging roots.  Each step first folds the sign at the current
    # iterate into the bracket, then takes the f-Newton step only if it
    # lands strictly inside; otherwise it bisects.  Worst case is therefore
    # n_bisect + n_newton halvings, typical is quadratic — which is what
    # lets the fused megakernel run 16+6 instead of 58+4.
    def newton_step(_, carry):
        lo_c, hi_c, tau_c = carry
        w, wp = w_of(tau_c)
        go_right = w < 0.0
        lo_n = jnp.where(go_right, tau_c, lo_c)
        hi_n = jnp.where(go_right, hi_c, tau_c)
        fp = w + tau_c * wp
        safe_fp = jnp.where(fp == 0.0, jnp.finfo(dt).tiny, fp)
        cand = tau_c - tau_c * w / safe_fp
        # CLOSED-interval acceptance.  After the fold, tau_c is itself one
        # of the bracket endpoints, and the step direction (sign of w, with
        # f' > 0) always points into the bracket — so cand can only land ON
        # an endpoint when the increment underflows, i.e. tau_c is already a
        # root at fp resolution.  A strict test would reject exactly that
        # converged iterate and a midpoint fallback would throw it away,
        # degrading the whole loop to plain bisection.
        inside = (cand >= lo_n) & (cand <= hi_n)
        tau_n = jnp.where(inside, cand, 0.5 * (lo_n + hi_n))
        return lo_n, hi_n, tau_n

    tau0 = 0.5 * (lo_f + hi_f)
    _, _, tau = lax.fori_loop(jnp.int32(0), jnp.int32(n_newton), newton_step,
                              (lo_f, hi_f, tau0))
    return jnp.squeeze(tau, poles_axis) if flat else tau
