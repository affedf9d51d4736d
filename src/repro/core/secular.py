"""Secular-equation machinery for the symmetric diagonal-plus-rank-1 eigenproblem.

Solves  eig(D + rho * z z^T)  where D = diag(d), d ascending, rho > 0, via the
secular equation (paper Eq. 11 / Golub 1973):

    w(mu) = 1 + rho * sum_k z_k^2 / (d_k - mu) = 0.

Numerical structure (paper §3.1 + the Gu–Eisenstat corrections it cites):

* Bunch–Nielsen–Sorensen deflation: tiny ``|z_i|`` and (near-)repeated ``d_i``
  are deflated before the solve. Repeated entries are merged with Givens
  rotations whose (c, s) pairs are recorded for the eigenvector back
  transformation. Everything is static-shape (masks + permutations), so the
  whole pipeline jits.
* Roots are represented as (anchor index, tau) with ``mu_i = d[anchor_i] +
  tau_i`` and the anchor chosen as the *nearest* pole. All downstream
  difference computations use ``d_j - mu_i = (d_j - d_anchor) - tau`` which is
  accurate even when the root is within eps of a pole. This is what makes the
  scaled-Cauchy eigenvectors orthogonal to working precision.
* Hybrid solver: fixed-count bisection (guaranteed bracket) + Newton polish,
  vectorized over all roots (no data-dependent control flow).
* Loewner reweighting (Gu–Eisenstat / LAPACK dlaed3): ``zhat`` is recomputed
  from the solved roots so that the Cauchy-column eigenvectors are numerically
  orthogonal:  zhat_j^2 = prod_i (mu_i - d_j) / (rho * prod_{i!=j} (d_i - d_j)).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.secular_body import deflation_rtols

__all__ = [
    "DeflationResult",
    "SecularRoots",
    "deflate",
    "apply_givens_columns",
    "secular_solve",
    "loewner_zhat",
    "mu_minus_d",
]


# ---------------------------------------------------------------------------
# Deflation
# ---------------------------------------------------------------------------


class DeflationResult(NamedTuple):
    """Static-shape description of a deflated D + rho z z^T problem.

    All arrays have length n (the original size); ``keep`` marks retained
    entries, ``n_keep`` counts them. ``compact`` is a permutation putting
    retained entries first (stable, so retained d stays ascending).
    """

    d: jax.Array          # (n,) diagonal, ascending (unchanged values)
    z: jax.Array          # (n,) z after Givens merging (zeros at deflated slots)
    keep: jax.Array       # (n,) bool
    n_keep: jax.Array     # () int32
    givens_a: jax.Array   # (n,) int32 first coordinate of rotation i (or i)
    givens_b: jax.Array   # (n,) int32 second coordinate of rotation i (or i)
    givens_c: jax.Array   # (n,) rotation cosines (1.0 where identity)
    givens_s: jax.Array   # (n,) rotation sines   (0.0 where identity)
    any_rot: jax.Array    # () bool — fast-path skip flag
    compact: jax.Array    # (n,) int32 permutation, retained-first


def deflate(d: jax.Array, z: jax.Array, rho: jax.Array, *, rtol: float | None = None) -> DeflationResult:
    """BNS deflation for ``D + rho z z^T`` (rho > 0, d ascending).

    LAPACK-style duplicate merging: each entry is compared against the *last
    retained* entry (not just its neighbor), so duplicate chains interrupted
    by tiny-z entries still merge correctly.
    """
    n = d.shape[0]
    dt = d.dtype
    gap_rtol, z_rtol = deflation_rtols(dt, rtol)

    znorm2 = jnp.sum(z * z)
    scale = jnp.maximum(jnp.max(jnp.abs(d)), jnp.abs(rho) * znorm2) + jnp.finfo(dt).tiny
    tol = gap_rtol * scale

    def negligible(zv):
        if z_rtol is None:
            return jnp.abs(rho) * zv * zv <= tol
        return jnp.abs(rho) * jnp.abs(zv) * jnp.sqrt(znorm2) <= z_rtol * scale

    def step(carry, i):
        z_arr, last = carry
        zi = z_arr[i]
        tiny_i = negligible(zi)
        have_last = last >= 0
        lastc = jnp.maximum(last, 0)
        zl = z_arr[lastc]
        gap = d[i] - d[lastc]
        r = jnp.sqrt(zl * zl + zi * zi)
        safe_r = jnp.where(r > 0, r, 1.0)
        c = jnp.where(r > 0, zi / safe_r, 1.0)
        s = jnp.where(r > 0, -zl / safe_r, 0.0)
        offdiag = jnp.abs(c * s * gap)
        do_rot = have_last & (~tiny_i) & (offdiag <= tol) & (jnp.abs(zl) > 0)
        if z_rtol is not None:
            # 32-bit rule: d is left in place (no LAPACK-style diagonal
            # update), so the rotation also drops s^2 * gap from the
            # deflated diagonal entry — bound that too (the 64-bit rule
            # predates it and is kept bit-for-bit)
            do_rot = do_rot & (jnp.abs(s * s * gap) <= tol)
        c = jnp.where(do_rot, c, 1.0)
        s = jnp.where(do_rot, s, 0.0)
        z_new = jnp.where(do_rot, z_arr.at[lastc].set(0.0).at[i].set(r), z_arr)
        new_last = jnp.where(tiny_i, last, i)
        a_idx = jnp.where(do_rot, lastc, i).astype(jnp.int32)
        b_idx = jnp.asarray(i, jnp.int32)
        return (z_new, new_last), (a_idx, b_idx, c, s)

    last0 = jnp.asarray(-1, jnp.arange(1).dtype)  # default int dtype (x64-aware)
    (z_merged, _), (gas, gbs, cs, ss) = lax.scan(step, (z, last0), jnp.arange(n))

    # deflate tiny z entries
    keep = ~negligible(z_merged)
    z_final = jnp.where(keep, z_merged, 0.0)
    n_keep = jnp.sum(keep).astype(jnp.int32)

    # retained-first stable permutation (retained d remains ascending)
    compact = jnp.argsort(jnp.where(keep, 0, 1), stable=True).astype(jnp.int32)
    any_rot = jnp.any(ss != 0.0)

    return DeflationResult(d, z_final, keep, n_keep, gas, gbs, cs, ss, any_rot, compact)


def apply_givens_columns(
    w: jax.Array,
    a_idx: jax.Array,
    b_idx: jax.Array,
    c: jax.Array,
    s: jax.Array,
    any_rot: jax.Array,
) -> jax.Array:
    """Apply the recorded deflation rotations to *columns* of ``w``.

    Deflation produced B' = R_k ... R_1 B R_1^T ... R_k^T, so eigenvectors of
    B are Q = R_1^T ... R_k^T Q'. Right-multiplying a row space:
    ``w @ (R_1^T R_2^T ...)`` — apply the recorded rotations in forward order,
    each mixing columns (a_i, b_i):
        col_a' = c col_a + s col_b,   col_b' = -s col_a + c col_b.
    """
    n = w.shape[1]
    if n < 2:
        return w

    def do_apply(w0):
        def step(wc, i):
            ai = a_idx[i]
            bi = b_idx[i]
            ci = c[i]
            si = s[i]
            col_a = wc[:, ai]
            col_b = wc[:, bi]
            new_a = ci * col_a + si * col_b
            new_b = -si * col_a + ci * col_b
            wc = wc.at[:, ai].set(new_a).at[:, bi].set(new_b)
            return wc, None

        out, _ = lax.scan(step, w0, jnp.arange(n))
        return out

    return lax.cond(any_rot, do_apply, lambda w0: w0, w)


# ---------------------------------------------------------------------------
# Secular solve
# ---------------------------------------------------------------------------


class SecularRoots(NamedTuple):
    """Roots of the secular equation on the *compacted* retained problem.

    Entry ``i`` (for ``i < n_keep``) is the root in the i-th retained
    interval:  mu_i = dc[anchor[i]] + tau[i].  Entries ``i >= n_keep`` are
    padding (mu = dc[i], tau = 0).
    """

    mu: jax.Array       # (n,) root values (padding: dc)
    anchor: jax.Array   # (n,) int32 anchor pole index into dc
    tau: jax.Array      # (n,) offset from anchor pole
    valid: jax.Array    # (n,) bool — i < n_keep


def _eval_w_and_deriv(dc, zc2, rho, anchor_vals, tau, valid_src):
    """Evaluate w(mu) = 1 + rho * sum_j zc2_j / (dc_j - mu) and w'(mu).

    mu is represented as anchor_vals + tau (per root).  Shapes: roots along
    axis 0, sources along axis 1.  ``valid_src`` masks padded sources.
    """
    # delta[i, j] = dc_j - mu_i computed stably
    delta = (dc[None, :] - anchor_vals[:, None]) - tau[:, None]
    safe = jnp.where(delta == 0.0, 1.0, delta)
    inv = jnp.where(valid_src[None, :], 1.0 / safe, 0.0)
    w = 1.0 + rho * jnp.sum(zc2[None, :] * inv, axis=1)
    wp = rho * jnp.sum(zc2[None, :] * inv * inv, axis=1)  # w'(mu) = rho sum z^2/delta^2
    return w, wp


@partial(jax.jit, static_argnames=("n_bisect", "n_newton"))
def secular_solve(
    dc: jax.Array,
    zc: jax.Array,
    rho: jax.Array,
    n_keep: jax.Array,
    *,
    n_bisect: int = 58,
    n_newton: int = 4,
) -> SecularRoots:
    """Solve the secular equation for the compacted problem (rho > 0).

    ``dc``: (n,) retained poles first (ascending over the first ``n_keep``),
    ``zc``: matching z values (nonzero over retained), padding arbitrary.
    Returns all n roots with validity mask.
    """
    n = dc.shape[0]
    dt = dc.dtype
    idx = jnp.arange(n)
    valid = idx < n_keep
    valid_src = valid

    zc2 = jnp.where(valid, zc * zc, 0.0)
    znorm2 = jnp.sum(zc2)

    # interval (dc_i, dc_{i+1}) for i < n_keep-1; last: (dc_{k-1}, dc_{k-1}+rho*|z|^2)
    is_last = idx == (n_keep - 1)
    d_right = jnp.roll(dc, -1)  # dc_{i+1}; junk at last retained, fixed below
    right = jnp.where(is_last, dc + rho * znorm2, d_right)
    left = dc
    width = right - left

    # --- anchor selection: evaluate w at the midpoint; w is increasing on the
    # interval, so w(mid) > 0 => root in left half (anchor = left pole i),
    # else right half (anchor = right pole i+1, tau negative).
    mid_anchor_vals = left
    mid_tau = 0.5 * width
    w_mid, _ = _eval_w_and_deriv(dc, zc2, rho, mid_anchor_vals, mid_tau, valid_src)
    # For the last interval the "right end" dc_{k-1}+rho|z|^2 is not a pole, so
    # there is no cancellation risk on the right — always anchor it left.
    use_left = (w_mid > 0.0) | is_last

    anchor_idx = jnp.where(use_left, idx, jnp.minimum(idx + 1, n - 1)).astype(jnp.int32)
    anchor_vals = jnp.where(use_left, left, right)
    # tau brackets relative to anchor. The last root is always left-anchored,
    # so its bracket must span the whole interval, not the left half.
    lo = jnp.where(use_left, 0.0, -0.5 * width)
    hi = jnp.where(is_last, width, jnp.where(use_left, 0.5 * width, 0.0))

    # --- bisection (vectorized, fixed count)
    def bis_step(_, carry):
        lo_c, hi_c = carry
        tmid = 0.5 * (lo_c + hi_c)
        w, _ = _eval_w_and_deriv(dc, zc2, rho, anchor_vals, tmid, valid_src)
        go_right = w < 0.0  # w increasing: root above tmid
        lo_n = jnp.where(go_right, tmid, lo_c)
        hi_n = jnp.where(go_right, hi_c, tmid)
        return lo_n, hi_n

    lo, hi = lax.fori_loop(0, n_bisect, bis_step, (lo, hi))
    tau = 0.5 * (lo + hi)

    # --- Newton polish (projected into the bracket)
    def newton_step(_, tau_c):
        w, wp = _eval_w_and_deriv(dc, zc2, rho, anchor_vals, tau_c, valid_src)
        step = w / jnp.maximum(wp, jnp.finfo(dt).tiny)
        tau_n = tau_c - step
        tau_n = jnp.clip(tau_n, lo, hi)
        return tau_n

    tau = lax.fori_loop(0, n_newton, newton_step, tau)

    mu = anchor_vals + tau
    mu = jnp.where(valid, mu, dc)
    tau = jnp.where(valid, tau, 0.0)
    anchor_idx = jnp.where(valid, anchor_idx, idx.astype(jnp.int32))
    return SecularRoots(mu, anchor_idx, tau, valid)


def mu_minus_d(roots: SecularRoots, dc: jax.Array) -> jax.Array:
    """Accurate difference matrix  delta[i, j] = mu_i - dc_j  (n, n)."""
    anchor_vals = dc[roots.anchor]
    return (anchor_vals[:, None] - dc[None, :]) + roots.tau[:, None]


# ---------------------------------------------------------------------------
# Loewner reweighting
# ---------------------------------------------------------------------------


def loewner_zhat(
    dc: jax.Array,
    zc: jax.Array,
    rho: jax.Array,
    roots: SecularRoots,
) -> jax.Array:
    """Gu–Eisenstat zhat from the solved roots (compacted problem).

    zhat_j^2 = prod_{i<k} (mu_i - dc_j) / (rho * prod_{i<k, i!=j} (dc_i - dc_j))

    computed with accurate differences (anchored representation) in
    log-magnitude space. The ratio is mathematically positive; signs are
    inherited from the original z. Padded entries return 0.
    """
    n = dc.shape[0]
    dt = dc.dtype
    idx = jnp.arange(n)
    valid = roots.valid  # (n,) roots mask == sources mask (same count)

    # numerator: prod_i (mu_i - dc_j) over valid roots i
    delta = mu_minus_d(roots, dc)  # (roots i, poles j)
    num = jnp.where(valid[:, None], delta, 1.0)
    log_num = jnp.sum(jnp.log(jnp.abs(num) + jnp.finfo(dt).tiny), axis=0)  # (j,)

    # denominator: prod_{i != j} (dc_i - dc_j) over valid i, valid j
    dd = dc[:, None] - dc[None, :]
    offdiag = (idx[:, None] != idx[None, :]) & valid[:, None]
    den = jnp.where(offdiag, dd, 1.0)
    log_den = jnp.sum(jnp.log(jnp.abs(den) + jnp.finfo(dt).tiny), axis=0)  # (j,)

    log_zhat2 = log_num - log_den - jnp.log(jnp.abs(rho))
    zhat = jnp.sign(zc) * jnp.exp(0.5 * log_zhat2)
    zhat = jnp.where(valid, zhat, 0.0)
    return zhat
