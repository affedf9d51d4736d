"""Rank-1 SVD update (paper Algorithm 6.1) and the streaming truncated variant.

Given A = U diag(s) V^T (m <= n, U: m x m, V: n x n, s: (m,)) and vectors
a (m,), b (n,), computes the SVD of  A + a b^T  in O(n^2 log(1/eps)):

  STEP 1   b~ = A b, a~ = A^T a, beta = b^T b, alpha = a^T a
  STEP 2/3 2x2 Schur of [[beta,1],[1,0]] / [[alpha,1],[1,0]] — analytic;
           the eigenvalues are rho_12 = beta/2 ± sqrt(beta^2/4 + 1), so one is
           always positive and one always negative (static signs).
  STEP 4-7 four diagonal-plus-rank-1 eigen-updates (core.eigh_update): two for
           the left subspace (A A^T + ...), two for the right (A^T A + ...).
  STEP 8   singular values = sqrt of updated eigenvalues.

Additions over the paper (see DESIGN.md §1): Loewner reweighting + deflation
live in eigh_update; a structured O(n^2 p) sign fix restores
U_n diag(s_n) V_n[:, :m]^T ≈ A + a b^T (the paper computes left/right updates
independently and never reconciles signs).

This module is implementation: the unjitted, vmap-clean bodies
(``_svd_update_impl`` / ``_svd_update_truncated_impl``) that
``core.engine.SvdEngine`` jits/vmaps, plus the two result containers.  The
public entry point for every update path is ``repro.api.update`` (DESIGN.md
§8); the pre-api module-level call shapes were removed after the migration.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.eigh_update import apply_update, eigenvalues, make_plan, materialize_q
from repro.kernels.fused_update import repair_null_columns

__all__ = ["SvdUpdateResult", "TruncatedSvd"]


class SvdUpdateResult(NamedTuple):
    u: jax.Array       # (m, m) updated left singular vectors
    s: jax.Array       # (m,)  updated singular values, descending
    v: jax.Array       # (n, n) updated right singular vectors
    # diagnostics
    d_left: jax.Array  # (m,) eigenvalues of (A+ab^T)(A+ab^T)^T, descending
    d_right: jax.Array # (n,) eigenvalues of (A+ab^T)^T(A+ab^T), descending


def _rank2_symmetric_split(beta):
    """Analytic Schur of [[beta, 1], [1, 0]] (paper STEP 2/3).

    Returns (rho_pos, rho_neg, q_pos, q_neg): eigenvalues (one positive, one
    negative — det = -1) and unit eigenvectors [rho_i, 1]/sqrt(1+rho_i^2).
    """
    h = 0.5 * beta
    r = jnp.sqrt(h * h + 1.0)
    rho_pos = h + r
    # det = -1, so -1/rho_pos is the same root without h - r's cancellation
    # (2% off at beta ~ 1e3 in float32); float64 keeps its historical form
    rho_neg = h - r if jnp.finfo(beta.dtype).bits > 32 else -1.0 / rho_pos
    n_pos = jnp.sqrt(1.0 + rho_pos * rho_pos)
    n_neg = jnp.sqrt(1.0 + rho_neg * rho_neg)
    q_pos = jnp.stack([rho_pos, 1.0]) / n_pos
    q_neg = jnp.stack([rho_neg, 1.0]) / n_neg
    return rho_pos, rho_neg, q_pos, q_neg


def _double_update(q0, d0, w1, w2, rho_pos, rho_neg, *, method, fmm_p, want_g,
                   deflate_rtol=None):
    """Two chained symmetric rank-1 eigen-updates of Q0 diag(d0) Q0^T.

    Returns (d_final ascending, Q_final, G) with Q_final = Q0 @ G and G
    materialized only when ``want_g`` (used by the sign fix).
    """
    build_fmm = method == "fmm"
    z1 = q0.T @ w1
    plan1 = make_plan(d0, z1, rho_pos, rho_positive=True, build_fmm=build_fmm, fmm_p=fmm_p,
                      deflate_rtol=deflate_rtol)
    q1 = apply_update(plan1, q0, method=method)
    d1 = eigenvalues(plan1)

    z2 = q1.T @ w2
    plan2 = make_plan(d1, z2, rho_neg, rho_positive=False, build_fmm=build_fmm, fmm_p=fmm_p,
                      deflate_rtol=deflate_rtol)
    q2 = apply_update(plan2, q1, method=method)
    d2 = eigenvalues(plan2)

    g = None
    if want_g:
        g1 = materialize_q(plan1, method=method)
        g = apply_update(plan2, g1, method=method)
    return d2, q2, g


def _svd_update_impl(
    u: jax.Array,
    s: jax.Array,
    v: jax.Array,
    a: jax.Array,
    b: jax.Array,
    *,
    method: str = "direct",
    fmm_p: int = 20,
    sign_fix: bool = True,
    deflate_rtol: float | None = None,
    compute_dtype=None,
) -> SvdUpdateResult:
    """Unjitted Algorithm 6.1 body — pure, static-shape, and vmap-clean.

    ``core.engine`` maps this over a leading batch axis; ``svd_update`` is the
    jitted single-instance wrapper.  ``compute_dtype`` (mixed precision):
    inputs may be stored narrower (bf16) — the fused route upcasts inside the
    kernel, the phase-chain routes upcast here and cast results back.
    """
    m = u.shape[0]
    n = v.shape[0]
    if m > n:
        raise ValueError("svd_update expects m <= n; transpose the problem (swap u/v, a/b).")

    if method == "fused":
        # one-kernel route: whole update resident (kernels.fused_update);
        # the storage->compute cast happens inside the body/kernel.
        from repro.kernels import ops as _kops

        out = _kops.fused_update(u, s, v, a, b, sign_fix=sign_fix,
                                 deflate_rtol=deflate_rtol,
                                 compute_dtype=compute_dtype)
        return SvdUpdateResult(u=out[0], s=out[1], v=out[2],
                               d_left=out[3], d_right=out[4])

    store_dt = u.dtype
    if compute_dtype is not None and jnp.dtype(compute_dtype) != store_dt:
        cdt = jnp.dtype(compute_dtype)
        res = _svd_update_impl(
            u.astype(cdt), s.astype(cdt), v.astype(cdt),
            a.astype(cdt), b.astype(cdt),
            method=method, fmm_p=fmm_p, sign_fix=sign_fix,
            deflate_rtol=deflate_rtol,
        )
        return SvdUpdateResult(*(x.astype(store_dt) for x in res))

    dt = u.dtype
    s = s.astype(dt)

    # STEP 1 — structured products (A never materialized)
    vtb = v.T @ b                                     # (n,)
    b_t = u @ (s * vtb[:m])                           # b~ = A b        (m,)
    uta = u.T @ a                                     # (m,)
    a_t = v @ jnp.concatenate([s * uta, jnp.zeros((n - m,), dt)])  # a~ = A^T a (n,)
    beta = jnp.dot(b, b)
    alpha = jnp.dot(a, a)

    d_u = s * s                                       # (m,)
    d_v = jnp.concatenate([s * s, jnp.zeros((n - m,), dt)])  # (n,)

    # STEP 2 — left split:  b~ a^T + a b~^T + beta a a^T
    rho1, rho2, qp, qn = _rank2_symmetric_split(beta)
    a1 = qp[0] * a + qp[1] * b_t
    b1 = qn[0] * a + qn[1] * b_t

    # STEP 3 — right split:  a~ b^T + b a~^T + alpha b b^T
    rho3, rho4, qp_v, qn_v = _rank2_symmetric_split(alpha)
    a2 = qp_v[0] * b + qp_v[1] * a_t
    b2 = qn_v[0] * b + qn_v[1] * a_t

    # STEPS 4-7 — chained eigen-updates
    d_left, u_n, g_u = _double_update(
        u, d_u, a1, b1, rho1, rho2, method=method, fmm_p=fmm_p, want_g=sign_fix,
        deflate_rtol=deflate_rtol,
    )
    d_right, v_n, g_v = _double_update(
        v, d_v, a2, b2, rho3, rho4, method=method, fmm_p=fmm_p, want_g=sign_fix,
        deflate_rtol=deflate_rtol,
    )

    # STEP 8 — singular values, descending order
    ord_l = jnp.argsort(-d_left)
    ord_r = jnp.argsort(-d_right)
    d_left_s = d_left[ord_l]
    d_right_s = d_right[ord_r]
    u_n = u_n[:, ord_l]
    v_n = v_n[:, ord_r]
    s_n = jnp.sqrt(jnp.clip(d_left_s, 0.0, None))

    if sign_fix:
        # diag_i = u_i^T (A + a b^T) v_i computed from the structured factors:
        #   = sum_k s_k G_u[k, i] G_v[k, i] + (a^T u_i)(b^T v_i)
        g_u = g_u[:, ord_l]
        g_v = g_v[:, ord_r]
        core = jnp.einsum("k,ki,ki->i", s, g_u, g_v[:m, :m])
        au = uta @ g_u                                 # a^T U G_u  (m,)
        bv = vtb @ g_v[:, :m]                          # b^T V G_v  (m,)
        diag = core + au * bv
        flip = jnp.where(diag < 0, -1.0, 1.0).astype(dt)
        v_n = v_n.at[:, :m].multiply(flip[None, :])

    return SvdUpdateResult(u=u_n, s=s_n, v=v_n, d_left=d_left_s, d_right=d_right_s)


# ---------------------------------------------------------------------------
# Streaming truncated rank-1 SVD update (Brand augmentation + Algorithm 6.1)
# ---------------------------------------------------------------------------


class TruncatedSvd(NamedTuple):
    u: jax.Array  # (m, r)
    s: jax.Array  # (r,) descending
    v: jax.Array  # (n, r)


def _svd_update_truncated_impl(
    tsvd: TruncatedSvd,
    a: jax.Array,
    b: jax.Array,
    *,
    method: str = "direct",
    fmm_p: int = 20,
    deflate_rtol: float | None = None,
    compute_dtype=None,
) -> TruncatedSvd:
    """Unjitted truncated-update body (vmap-clean, see ``core.engine``).

    Accepts any (u, s, v)-carrying container (``TruncatedSvd`` or an
    ``repro.api.SvdState``); returns ``TruncatedSvd``."""
    u, s, v = tsvd.u, tsvd.s, tsvd.v

    if method == "fused":
        from repro.kernels import ops as _kops

        out = _kops.fused_update_truncated(u, s, v, a, b,
                                           deflate_rtol=deflate_rtol,
                                           compute_dtype=compute_dtype)
        return TruncatedSvd(u=out[0], s=out[1], v=out[2])

    if compute_dtype is not None and jnp.dtype(compute_dtype) != u.dtype:
        cdt = jnp.dtype(compute_dtype)
        store_dt = u.dtype
        res = _svd_update_truncated_impl(
            TruncatedSvd(u.astype(cdt), s.astype(cdt), v.astype(cdt)),
            a.astype(cdt), b.astype(cdt),
            method=method, fmm_p=fmm_p, deflate_rtol=deflate_rtol,
        )
        return TruncatedSvd(*(x.astype(store_dt) for x in res))

    m, r = u.shape
    n = v.shape[0]
    dt = u.dtype

    p_vec = u.T @ a
    a_perp = a - u @ p_vec
    ra = jnp.linalg.norm(a_perp)
    safe_ra = jnp.where(ra > 1e-12, ra, 1.0)
    p_unit = jnp.where(ra > 1e-12, a_perp / safe_ra, 0.0)
    ra = jnp.where(ra > 1e-12, ra, 0.0)

    q_vec = v.T @ b
    b_perp = b - v @ q_vec
    rb = jnp.linalg.norm(b_perp)
    safe_rb = jnp.where(rb > 1e-12, rb, 1.0)
    q_unit = jnp.where(rb > 1e-12, b_perp / safe_rb, 0.0)
    rb = jnp.where(rb > 1e-12, rb, 0.0)

    # K = diag([s, 0]) + [p; ra] [q; rb]^T   of size (r+1, r+1)
    s_aug = jnp.concatenate([s, jnp.zeros((1,), dt)])
    ak = jnp.concatenate([p_vec, ra[None]])
    bk = jnp.concatenate([q_vec, rb[None]])
    eye = jnp.eye(r + 1, dtype=dt)
    res = _svd_update_impl(eye, s_aug, eye, ak, bk, method=method, fmm_p=fmm_p,
                           sign_fix=True, deflate_rtol=deflate_rtol)
    uk, sk, vk = res.u, res.s, res.v
    if jnp.dtype(dt).itemsize <= 4:
        uk, sk, vk = repair_null_columns(uk, sk[None], vk)
        sk = sk[0]

    u_aug = jnp.concatenate([u, p_unit[:, None]], axis=1)   # (m, r+1)
    v_aug = jnp.concatenate([v, q_unit[:, None]], axis=1)   # (n, r+1)
    u_new = u_aug @ uk[:, :r]
    v_new = v_aug @ vk[:, :r]
    return TruncatedSvd(u=u_new, s=sk[:r], v=v_new)
