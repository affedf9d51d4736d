"""Fused rank-1 SVD update: the whole of Algorithm 6.1 in one kernel body.

The engine's other routes run the update as a chain of separate XLA
dispatches — project, deflate, secular solve, Cauchy rotation, sign fix —
with every intermediate bouncing through HBM, which is why *full* batched
updates historically ran at ~1.4x over the per-update loop while truncated
ones reached 12.5x (BENCH_engine.json).  This module is the designated
hot-path fix (ROADMAP): ONE body that keeps the whole per-update state
resident, expressed so the SAME code traces

* as a plain-jnp XLA fusion (``fused_update_xla``) — the CPU path and the
  natural ``jax.vmap`` target, and
* inside a Pallas kernel (``fused_update_pallas`` /
  ``fused_update_pallas_batched``) — grid ``(B,)``, one program per update,
  everything in VMEM; ``interpret=True`` executes the body on CPU in tests.

To make the body kernel-clean it eliminates every construct that is slow
under vmap or unsupported in Mosaic:

* **no argsort / gather** — the eigenvalue orders of all four phases are
  static reversals (``d = s^2`` is descending, negation flips), and the one
  data-dependent reorder (deflated passthrough values interleaving secular
  roots) is done with a stable comparison-matrix rank + one-hot permutation
  matmul (MXU-friendly);
* **no lax.cond / per-rotation scan** — the direct path's sequential Givens
  deflation chain (a both-branches scan under vmap that copies the full
  (B, m, n) operand per step — the actual 1.4x bottleneck) is replaced by a
  closed-form grouped Householder merge of (near-)coincident poles, built
  as one dense (k, k) matrix from masks;
* **shared secular loop** — the bisection/Newton iteration is
  ``kernels.secular_body.secular_iterate``, the same body the standalone
  secular kernel and its oracle use.  The Newton phase is a *safeguarded
  pole-free* iteration on ``f(tau) = tau * w(tau)`` (smooth across the
  anchor pole, bracket maintained every step — see ``secular_body``), so
  each Newton step is at worst one more bisection halving and typically
  quadratic.  That lets the fused defaults run 16 bisection + 6 Newton
  steps (vs the standalone kernel's 58+4): the bisections localize into
  the Newton basin (observed requirement is ~12 even for clustered
  spectra; 16 doubles the margin) and the pole-free Newton then converges
  to machine precision — even pole-hugging streaming roots measure
  ~1e-13 one-step error.  The secular loop is the fused hot path, so
  dropping the dead rounds is a ~35% end-to-end win at (32, 48).  Parity
  vs the 58+4 direct route stays at working-precision level even for
  clustered spectra just above the deflation gap (tests/test_fused.py).

Mixed precision: the body takes a ``compute_dtype`` — bf16/f16 *storage*
factors are upcast on entry (inside the kernel, after the bf16 HBM->VMEM
load — that is the bandwidth win on TPU), the secular solve and all
rotations run in f32/f64, and outputs are cast back to the storage dtype.
The documented error budget for bf16 storage is ``BF16_ERROR_BUDGET``
(enforced in tests/test_fused.py, table in DESIGN.md §11).

Deflation semantics vs the direct path: coincident-pole handling merges by
pole *gap* (``gap <= rtol * scale``) instead of by Givens off-diagonal
size.  Exact duplicates (the n-m structural zeros of the right-hand
problem, repeated deflated eigenvalues feeding later phases) merge
identically; *near*-coincident poles may deflate slightly differently —
both choices perturb the problem by O(rtol * scale), so the routes agree
to the tolerances the parity tests pin.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.secular_body import deflation_rtols, secular_iterate

__all__ = [
    "BF16_ERROR_BUDGET",
    "F32_ERROR_BUDGET",
    "FUSED_VMEM_BUDGET",
    "FUSED_VMEM_LIMIT",
    "fused_supported",
    "fused_update_xla",
    "fused_update_truncated_xla",
    "fused_update_pallas",
    "fused_update_pallas_batched",
    "fused_update_truncated_pallas",
    "fused_update_truncated_pallas_batched",
    "null_columns",
    "repair_null_columns",
]


# Routing budget: the fused body's unpadded working set (DESIGN.md §11).
FUSED_VMEM_BUDGET = 8 * 1024 * 1024

# What one program may claim of a TPU core's VMEM (a v5e core has 128 MiB;
# Mosaic's default scoped limit, 16 MiB, is below the smoke geometry's
# ~25 MiB).  ``fused_supported`` admits only geometries whose lane-padded
# estimate (``_vmem_bytes``) fits it, so every admitted geometry compiles.
FUSED_VMEM_LIMIT = 100 * 1024 * 1024

# bf16-storage error budget vs the f64 dense reference (DESIGN.md §11).
# Pinned by tests/test_fused.py; measured on the bench geometry (32, 48)
# with ~4x headroom over observed worst cases.  bf16 eps ~= 7.8e-3: one
# update costs a few eps in sigma, reconstruction is dominated by the bf16
# quantization of the stored factors themselves, and sequential-update
# drift grows roughly linearly (Peña–Sauer-style accumulation).
BF16_ERROR_BUDGET = {
    "sigma_rel": 5e-2,        # max_i |s_i - s_ref_i| / s_ref_0, single update
    "recon_rel": 8e-2,        # ||U S V^T - ref||_F / ||ref||_F, single update
    "drift_sigma_rel": 2e-1,  # sigma_rel after 8 sequential updates
}


# float32 error budget vs the float64 dense reference, for rank-budgeted
# streams (true rank <= state rank) after tens of events.  chip_smoke.py's
# traffic measures <= 3.6e-6 reconstruction and <= 3.1e-5 sigma error on a
# TPU v5e; these keep ~30x / ~3x of that, and the log-space Loewner weights
# the kernel used before (2.7e-4 reconstruction on the same run) fail.
F32_ERROR_BUDGET = {
    "recon_rel": 1e-4,   # ||U S V^T - ref||_F / ||ref||_F
    "sigma_rel": 1e-4,   # max_i |s_i - s_ref_i| / s_ref_0
}


def _compute_dtype_for(storage_dtype) -> jnp.dtype:
    dt = jnp.dtype(storage_dtype)
    return jnp.dtype(jnp.float32) if dt.itemsize <= 2 else dt


def _padded(rows: int, cols: int) -> int:
    """Elements of a (rows, cols) f32 VMEM tile set: (8, 128)-padded."""
    return -(-rows // 8) * 8 * (-(-cols // 128) * 128)


def _vmem_bytes(m: int, n: int, rank: int | None, itemsize: int) -> int:
    """Lane-padded VMEM one program of the Pallas kernel claims: its
    double-buffered input and output blocks plus the body's largest
    temporaries.  Calibrated on compiles for v5e (the (4096, 4096, 32)
    truncated body measures 25.4 MiB; this estimates 25.2 + core)."""
    if rank is None:
        blocks = _padded(m, m) + _padded(n, n) + 3 * _padded(1, max(m, n))
        core = 10 * (_padded(m + 2, m + 2) + _padded(n, n))
        return itemsize * (4 * blocks + core)
    k = rank + 1
    blocks = _padded(m, rank) + _padded(n, rank) + _padded(1, m) + _padded(1, n)
    temps = 2 * (_padded(m, k) + _padded(n, k))
    return itemsize * (4 * blocks + temps + 12 * _padded(k, k))


def fused_supported(m: int, n: int, rank: int | None = None,
                    dtype=jnp.float32) -> bool:
    """Whether the fused body's working set fits ``FUSED_VMEM_BUDGET`` and
    its lane-padded VMEM fits ``FUSED_VMEM_LIMIT``.

    ``rank=None`` is the full update (working set dominated by the dense
    (n, n) phase operators); otherwise the truncated route, whose secular
    core is (rank+1)-sized with (m, rank+1)/(n, rank+1) factor blocks.
    """
    isz = _compute_dtype_for(dtype).itemsize
    if rank is None:
        if m > n:
            return False
        est = (10 * n * n + 10 * m * m + 8 * (m + n)) * isz
    else:
        k = rank + 1
        est = (10 * k * k + 4 * k * (m + n) + 8 * (m + n)) * isz
    return (est <= FUSED_VMEM_BUDGET
            and _vmem_bytes(m, n, rank, isz) <= FUSED_VMEM_LIMIT)


# ---------------------------------------------------------------------------
# kernel-clean primitives
# ---------------------------------------------------------------------------
#
# The body is written in 2-D only: a vector is a (1, k) row, and a (k, 1)
# column where it indexes roots.  Mosaic's 1-D vector layouts fail to
# relayout (full reductions of a 1-D vector, for one), and it has no
# ``rev``; rows, columns, transposes and matmuls all lower.


def _iota(shape, dim: int):
    return lax.broadcasted_iota(jnp.int32, shape, dim)


def _mm(a, b):
    return jnp.dot(a, b, precision=lax.Precision.HIGHEST,
                   preferred_element_type=a.dtype)


def _mm_t(a, b):
    """``a @ b.T`` without materializing the transpose."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=a.dtype)


def _sum(x, axis=None):
    return jnp.sum(x, axis=axis, keepdims=True)


def _pm1(neg, dt):
    """-1 where ``neg``, else +1, in ``dt`` (two Python-float branches
    would select in float64 under x64, which the TPU kernel compiler
    rejects)."""
    return 1.0 - 2.0 * neg.astype(dt)


def _prod_rows(x):
    """Product over axis 0, keeping it (Mosaic has no ``reduce_prod``): a
    halving tree of row-block products."""
    while x.shape[0] > 1:
        h = x.shape[0] // 2
        top = x[:h] * x[h:2 * h]
        x = top if x.shape[0] == 2 * h else jnp.concatenate([top, x[2 * h:]], 0)
    return x


def _flip(x):
    """Reverse the columns of a row or matrix: a matmul with the iota-built
    exchange matrix (one exact nonzero per output)."""
    k = x.shape[-1]
    return _mm(x, (_iota((k, k), 0) + _iota((k, k), 1) == k - 1).astype(x.dtype))


def _flip2(x):
    return _flip(_flip(x).T).T


def _stable_sort_perm(mu_row, mu_col, iota_r, iota_c):
    """One-hot permutation P with P[i, r] = 1 iff stable-rank(mu_i) == r.

    ``x_sorted = x @ P`` (rows), ``Q_sorted = Q @ P`` (columns) — the
    argsort-free reorder used for the phase output ordering.
    """
    lt = mu_row < mu_col                                     # mu_j <  mu_i
    eq = (mu_row == mu_col) & (iota_c < iota_r)
    # int32 sums: under x64 an int sum widens to int64, which Mosaic lacks
    rank = (jnp.sum(lt, axis=1, keepdims=True, dtype=jnp.int32)
            + jnp.sum(eq, axis=1, keepdims=True, dtype=jnp.int32))   # (k, 1)
    return (rank == iota_c).astype(mu_row.dtype)


# ---------------------------------------------------------------------------
# null columns of a 32-bit truncated core
# ---------------------------------------------------------------------------


def null_columns(s):
    """Where the singular values ``s`` (along the last axis) are at most
    ``sqrt(eps) * max(s)``: below that the squared spectrum the chains
    solve holds no more digits of them, and a 32-bit update cannot take
    their right vectors from the left ones (nothing to divide by)."""
    return s <= jnp.sqrt(jnp.finfo(s.dtype).eps) * jnp.max(s, axis=-1, keepdims=True)


def _last_to_residual(q, null):
    """``q`` (k, k) orthonormal with its null columns turned, by one
    reflection inside their span, so that the last column (where null) is
    the augmented unit vector ``e_{k-1}`` less its part along the kept
    columns.  Kept columns are returned exactly."""
    k = q.shape[1]
    dt = q.dtype
    col = _iota((1, k), 1) == k - 1
    e = (_iota((k, 1), 0) == k - 1).astype(dt)
    kept = jnp.where(null, jnp.zeros((), dt), q)
    t = e - _sum(kept * _sum(e * kept, 0), 1)
    tn2 = _sum(t * t, 0)
    t = t / jnp.sqrt(jnp.where(tn2 > 0.5, tn2, 1.0))
    w = _sum(jnp.where(col, q, jnp.zeros((), dt)), 1) - t
    wn2 = _sum(w * w, 0)
    # skip it where e_{k-1} lies mostly in the kept span (the residual then
    # carries a real direction) or the last column is there already
    last_null = _sum((null & col).astype(dt), 1) > 0.5
    turn = null & last_null & (tn2 > 0.5) & (wn2 > jnp.finfo(dt).eps)
    reflected = q - (2.0 / jnp.where(turn, wn2, 1.0)) * w * _sum(w * q, 0)
    return jnp.where(turn, reflected, q)


def repair_null_columns(u, s, v):
    """The 32-bit SVD ``(u, s, v)`` of a truncated update's (k, k) core
    (``s`` a (1, k) row), repaired on its null columns
    (``null_columns(s)``).

    * The chains resolve a singular value through its square, so one below
      ``sqrt(eps) * s_max`` comes out with an error at least half its size:
      no digits.  Kept with its arbitrary pair of null vectors, it is a
      spurious term that the next event takes for data and grows.  Null
      values are set to zero.
    * Where an event lies in the state's span to rounding, the Brand
      residual direction (the core's last coordinate) is rounding noise,
      not orthogonal to the state's basis.  The null columns of ``u`` and
      ``v`` are turned inside their span so that the last one, which
      truncation drops, is that coordinate: the columns kept then carry
      none of it.

    Kept columns and values are returned exactly.
    """
    null = null_columns(s)
    return (_last_to_residual(u, null), jnp.where(null, jnp.zeros((), s.dtype), s),
            _last_to_residual(v, null))


# ---------------------------------------------------------------------------
# one diagonal-plus-rank-1 eigen phase:  eig(diag(d) + rho z z^T),  rho > 0
# ---------------------------------------------------------------------------


def _phase(d, z, rho, *, rtol, n_bisect, n_newton):
    """Eigen-update of ``diag(d) + rho z z^T`` (d ascending, rho > 0).

    ``d``/``z`` are (1, k) rows.  Returns ``(mu_sorted, Phi)``: eigenvalues
    ascending (a row) and the dense (k, k) rotation with eigenvector columns
    in that order (``W_new = W @ Phi``).  Structured as Householder-merge ->
    tiny-z deflation -> bracketed secular solve (anchored) -> Loewner zhat ->
    scaled-Cauchy columns -> stable one-hot output permutation; every step
    is masks + matmuls + the two fixed-count secular loops.  Per-root
    quantities are (k, 1) columns, per-pole ones (1, k) rows, so every
    (k, k) tensor is [root i, pole j].
    """
    k = d.shape[1]
    dt = d.dtype
    tiny = jnp.finfo(dt).tiny
    gap_rtol, z_rtol = deflation_rtols(dt, rtol)

    idx = _iota((1, k), 1)
    iota_r = _iota((k, k), 0)
    iota_c = _iota((k, k), 1)
    eye = (iota_r == iota_c).astype(dt)
    dc = d.T

    z2_raw = z * z
    zn2_raw = _sum(z2_raw)
    scale = jnp.maximum(jnp.max(jnp.abs(d), axis=1, keepdims=True),
                        rho * zn2_raw) + tiny
    tol = gap_rtol * scale

    # -- group (near-)coincident poles: leader = first pole within gap tol.
    # d is ascending so {j <= i : d_i - d_j <= tol} is a suffix; the min is
    # the group leader.  log2(k) rounds of leader <- leader[leader] close
    # chains (a gather, expressed as a one-hot matvec for the MXU).
    ok = (iota_c <= iota_r) & ((dc - d) <= tol)
    leader = jnp.min(jnp.where(ok, iota_c, jnp.int32(k)), axis=1, keepdims=True)
    for _ in range(max(1, math.ceil(math.log2(max(k, 2))))):
        hop = (leader == iota_c).astype(dt)
        leader = _mm(hop, leader.astype(dt)).astype(jnp.int32)
    leader_row = leader.astype(dt).T.astype(jnp.int32)

    # -- grouped Householder merge: per group H z|_g = r e_rep (disjoint
    # supports, so all groups share one dense symmetric-orthogonal H).
    same = leader == leader_row
    sf = same.astype(dt)
    is_rep = (leader_row == idx).astype(dt)
    gz2 = _mm(z2_raw, sf)                       # group ||z||^2, broadcast
    z_rep = _mm(z * is_rep, sf)                 # group rep's z, broadcast
    sgn = _pm1(z_rep >= 0.0, dt)
    r_vec = sgn * jnp.sqrt(gz2)                 # r = -sign(z_rep) ||z_g||
    wv = z - r_vec * is_rep                     # Householder vector (no
    gn2 = _mm(wv * wv, sf)                      # cancellation by sign choice)
    denom = jnp.where(gn2 > 0.0, gn2, 1.0)
    hh = eye - jnp.where(same & (gn2.T > 0.0),
                         2.0 * wv.T * wv / denom.T, 0.0)
    z_m = r_vec * is_rep                        # merged z: exact zeros off-rep

    # -- tiny-z deflation on the merged weights
    z2 = z_m * z_m
    if z_rtol is None:
        keep = rho * z2 > tol
    else:
        keep = rho * jnp.abs(z_m) * jnp.sqrt(zn2_raw) > z_rtol * scale
    z2k = jnp.where(keep, z2, 0.0)
    zn2 = _sum(z2k)
    keep_c = keep.astype(dt).T > 0.5

    # -- brackets: (d_i, next kept pole) per kept i; last kept gets the
    # Weyl cap d_i + rho ||z||^2.  Merging guarantees kept gaps > tol.
    big = jnp.asarray(jnp.finfo(dt).max, dt) * 0.25
    cand = jnp.where((iota_c > iota_r) & keep,
                     jnp.broadcast_to(d, (k, k)), big)
    nxt = jnp.min(cand, axis=1, keepdims=True)
    is_last = keep_c & (nxt >= 0.5 * big)
    right = jnp.where(is_last, dc + rho * zn2, nxt)
    left = dc
    width = jnp.where(keep_c, right - left, 0.0)

    # -- anchor by midpoint sign (w increasing on the bracket); the last
    # interval's right end is not a pole, so it always anchors left.
    delta_mid = (d - left) - 0.5 * width
    safe_mid = jnp.where(delta_mid == 0.0, 1.0, delta_mid)
    inv_mid = jnp.where(delta_mid != 0.0, 1.0 / safe_mid, 0.0)
    w_mid = 1.0 + rho * _sum(z2k * inv_mid, 1)
    use_left = (w_mid > 0.0) | is_last
    anchor = jnp.where(use_left, left, right)
    lo = jnp.where(use_left, 0.0, -0.5 * width)
    hi = jnp.where(is_last, width, jnp.where(use_left, 0.5 * width, 0.0))

    diff = d - anchor                           # (roots, poles), anchored
    tau = secular_iterate(diff, z2k, rho, lo, hi,
                          n_bisect=n_bisect, n_newton=n_newton, poles_axis=1)
    tau = jnp.where(keep_c, tau, 0.0)
    mu = jnp.where(keep_c, anchor + tau, dc)

    # -- Loewner zhat (Gu–Eisenstat), anchored deltas
    delta_md = (anchor - d) + tau               # mu_i - d_j
    if dt.itemsize <= 4:
        # interlaced ratio products (LAPACK dlaed3's pairing): each factor
        # is O(1) — root i over pole i (i < j) or over its bracket's right
        # pole (i >= j; the last root keeps its bare delta) — so no log/exp:
        # a TPU's float32 log is good to ~1e-5 only
        den = jnp.where(iota_r < iota_c, dc - d, right - d)
        ratio = jnp.abs(delta_md) / jnp.where(is_last, 1.0, jnp.abs(den))
        zhat2 = _prod_rows(jnp.where(keep_c, ratio, 1.0))
        zhat = jnp.sign(z_m) * jnp.sqrt(zhat2 / rho)
    else:
        num = jnp.where(keep_c, delta_md, 1.0)
        log_num = _sum(jnp.log(jnp.abs(num) + tiny), 0)
        den = jnp.where((iota_r != iota_c) & keep_c, dc - d, 1.0)
        log_den = _sum(jnp.log(jnp.abs(den) + tiny), 0)
        log_zhat2 = log_num - log_den - jnp.log(rho)
        zhat = jnp.sign(z_m) * jnp.exp(0.5 * log_zhat2)
    zhat = jnp.where(keep, zhat, 0.0)

    # -- scaled-Cauchy eigenvector columns; deflated columns pass through
    cden = (dc - anchor.T) - tau.T              # [j, i] = d_j - mu_i, anchored
    safe = jnp.where(cden == 0.0, 1.0, cden)
    invc = jnp.where(cden != 0.0, 1.0 / safe, 0.0)
    nrm2 = _sum((zhat * zhat).T * invc * invc, 0)
    colnorm = jnp.where(keep, jnp.sqrt(nrm2), 1.0)
    qt = jnp.where(keep, zhat.T * invc / colnorm, eye)

    mu_row = mu.T
    perm = _stable_sort_perm(mu_row, mu, iota_r, iota_c)
    phi = _mm(_mm(hh, qt), perm)
    return _mm(mu_row, perm), phi


def _chain(d0_asc, z1, z2w, rho_pos, rho_neg, *, rtol, n_bisect, n_newton):
    """Two chained phases (paper STEPS 4-5 or 6-7) in ascending coords.

    ``z1``/``z2w`` are the two update vectors (rows) already rotated into
    the ascending basis of ``d0_asc``; ``rho_pos > 0 > rho_neg`` (static
    signs from the 2x2 Schur split).  The rho<0 phase solves the negated
    problem (eig(D + rho zz^T) = -eig(-D + |rho| zz^T), reversed order),
    which in ascending coordinates is a pure double flip.  Returns final
    eigenvalues (ascending) and the composed operator G with
    Q_final = Q0_asc @ G.
    """
    kw = dict(rtol=rtol, n_bisect=n_bisect, n_newton=n_newton)
    mu1, phi1 = _phase(d0_asc, z1, rho_pos, **kw)
    z2 = _mm(z2w, phi1)
    mu_b, phi_b = _phase(_flip(-mu1), _flip(z2), -rho_neg, **kw)
    mu2 = _flip(-mu_b)
    phi2 = _flip2(phi_b)
    return mu2, _mm(phi1, phi2)


# ---------------------------------------------------------------------------
# the fused Algorithm 6.1 body (full update) + Brand truncated body
# ---------------------------------------------------------------------------


def _fused_body(u, s, v, a, b, *, sign_fix=True, deflate_rtol=None,
                n_bisect=16, n_newton=6, compute_dtype=None):
    """One full rank-1 SVD update, resident end to end.

    Same contract as ``core.svd_update._svd_update_impl`` (m <= n enforced
    by callers; shapes static), with ``s``/``a``/``b`` as (1, k) rows:
    returns ``(u, s, v, d_left, d_right)`` (vectors as rows) with
    descending singular values and the structured sign fix applied.
    """
    m = u.shape[0]
    n = v.shape[0]
    store_dt = u.dtype
    cdt = jnp.dtype(compute_dtype) if compute_dtype is not None \
        else _compute_dtype_for(store_dt)
    u = u.astype(cdt)
    s = s.astype(cdt)
    v = v.astype(cdt)
    a = a.astype(cdt)
    b = b.astype(cdt)
    kw = dict(rtol=deflate_rtol, n_bisect=n_bisect, n_newton=n_newton)

    # STEP 1 — structured products (A never materialized)
    vtb = _mm(b, v)
    b_t = _mm_t(s * vtb[:, :m], u)
    uta = _mm(a, u)
    sv = s * uta
    if n > m:   # static: a zero-length vector has no Mosaic layout
        sv = jnp.concatenate([sv, jnp.zeros((1, n - m), cdt)], 1)
    a_t = _mm_t(sv, v)
    beta = _sum(b * b)
    alpha = _sum(a * a)

    # STEP 2/3 — analytic 2x2 Schur of [[beta, 1], [1, 0]]: eigenvalues
    # h ± sqrt(h^2+1) (one positive, one negative), unit vectors
    # [rho_i, 1] / sqrt(1 + rho_i^2).
    def split(c):
        h = 0.5 * c
        r = jnp.sqrt(h * h + 1.0)
        rho_p = h + r
        # det = -1: -1/rho_p is h - r without its float32 cancellation
        rho_n = h - r if cdt.itemsize > 4 else -1.0 / rho_p
        np_ = jnp.sqrt(1.0 + rho_p * rho_p)
        nn_ = jnp.sqrt(1.0 + rho_n * rho_n)
        return rho_p, rho_n, (rho_p / np_, 1.0 / np_), (rho_n / nn_, 1.0 / nn_)

    rho1, rho2, qp, qn = split(beta)
    a1 = qp[0] * a + qp[1] * b_t
    b1 = qn[0] * a + qn[1] * b_t
    rho3, rho4, qpv, qnv = split(alpha)
    a2 = qpv[0] * b + qpv[1] * a_t
    b2 = qnv[0] * b + qnv[1] * a_t

    # STEPS 4-7 — chained eigen-updates; s^2 is descending, so ascending
    # order is a static flip on both sides (right side: n-m zeros lead).
    d0u = _flip(s * s)
    z1u = _flip(_mm(a1, u))
    z2u = _flip(_mm(b1, u))
    d_left_asc, g_u_asc = _chain(d0u, z1u, z2u, rho1, rho2, **kw)

    va2 = _mm(a2, v)
    vb2 = _mm(b2, v)

    # STEP 8 (left) — descending outputs; ascending -> descending is a
    # double flip back into the original (descending) coordinates of u.
    g_u = _flip2(g_u_asc)
    d_left = _flip(d_left_asc)
    s_n = jnp.sqrt(jnp.clip(d_left, 0.0, None))
    u_n = _mm(u, g_u)

    if n - m > 2:
        # Structural-zero compression.  A full m<n state gives the right
        # problem n-m poles that are *structurally* zero (the null-space
        # directions of A), and the rank-1 update only excites the 2-dim
        # slice of that null space spanned by the null components of a2/b2.
        # Instead of dragging n-m dead coordinates through both phases, build
        # an orthonormal M (two Householders) whose first two columns span
        # that slice, solve the chain on m+2 coordinates, and pass the other
        # n-m-2 null directions through untouched (eigenvalue exactly 0).
        # Shrinks every right-side tensor from (n+1)^2-ish to (m+2)^2 —
        # at (32, 48) that is 2.1x fewer secular elements on the right.
        k0 = n - m
        c1 = va2[:, m:]
        c2 = vb2[:, m:]
        eps = jnp.finfo(cdt).eps
        tiny = jnp.finfo(cdt).tiny
        idx0 = _iota((1, k0), 1)
        e1 = (idx0 == 0).astype(cdt)
        e2 = (idx0 == 1).astype(cdt)

        # q1, q2: Gram-Schmidt on (c1, c2) with branchless fallbacks so the
        # basis stays orthonormal even when a2/b2 have no null component.
        na2 = jnp.sqrt(_sum(va2 * va2))
        r11 = jnp.sqrt(_sum(c1 * c1))
        q1 = jnp.where(r11 > eps * na2, c1, e1)
        q1 = q1 / jnp.sqrt(_sum(q1 * q1))
        c2p = c2 - _sum(q1 * c2) * q1
        r22 = jnp.sqrt(_sum(c2p * c2p))
        nb2 = jnp.sqrt(_sum(vb2 * vb2))
        f1 = e1 - q1 * q1[:, 0:1]     # fallbacks orthogonal to q1; at least
        f2 = e2 - q1 * q1[:, 1:2]     # one has norm^2 >= 1/2
        fb = jnp.where(_sum(f1 * f1) >= _sum(f2 * f2), f1, f2)
        q2 = jnp.where(r22 > eps * (na2 + nb2), c2p, fb)
        q2 = q2 - _sum(q1 * q2) * q1
        q2 = q2 / jnp.sqrt(_sum(q2 * q2))

        # M = H1 @ H2: exactly orthogonal, M[:, 0] = ±q1, M[:, 1] ≈ ±q2.
        eye0 = (_iota((k0, k0), 0) == _iota((k0, k0), 1)).astype(cdt)
        sgn1 = _pm1(q1[:, 0:1] < 0.0, cdt)
        w1 = q1 + sgn1 * e1           # ||w1||^2 = 2 + 2|q1[0]| >= 2
        h1 = eye0 - (2.0 / _sum(w1 * w1)) * (w1.T * w1)
        q2h = _mm(q2, h1) * (1.0 - e1)          # coord 0 exactly 0
        q2h = q2h / jnp.sqrt(jnp.maximum(_sum(q2h * q2h), tiny))
        sgn2 = _pm1(q2h[:, 1:2] < 0.0, cdt)
        w2 = q2h + sgn2 * e2
        h2 = eye0 - (2.0 / _sum(w2 * w2)) * (w2.T * w2)
        mq = _mm(h1, h2)
        m2 = mq[:, :2]

        # chained eigen-updates on the m+2 active coordinates (ascending:
        # the two compressed zero poles lead, then s^2 ascending).
        d0v = jnp.concatenate([jnp.zeros((1, 2), cdt), _flip(s * s)], 1)
        z1v = jnp.concatenate([_mm(c1, m2), _flip(va2[:, :m])], 1)
        z2v = jnp.concatenate([_mm(c2, m2), _flip(vb2[:, :m])], 1)
        d_act_asc, g_act = _chain(d0v, z1v, z2v, rho3, rho4, **kw)

        v_null = v[:, m:]
        v_act = jnp.concatenate([_mm(v_null, m2), _flip(v[:, :m])], 1)
        v_rot = _mm(v_act, g_act)
        v_inert = _mm(v_null, mq[:, 2:])
        v_n = jnp.concatenate([_flip(v_rot), v_inert], 1)
        d_right = jnp.concatenate([_flip(d_act_asc),
                                   jnp.zeros((1, k0 - 2), cdt)], 1)
        # old-v coordinates of the first m new right vectors (descending),
        # for the sign fix: rows 2.. of g_act are the v[:, :m] coords in
        # ascending order on both axes.
        gv_mm = _flip2(g_act[2:, :])[:, :m]
        btva = jnp.concatenate([_mm(vtb[:, m:], m2), _flip(vtb[:, :m])], 1)
        bv = _flip(_mm(btva, g_act))[:, :m]
    else:
        d0v = s * s
        if n > m:
            d0v = jnp.concatenate([d0v, jnp.zeros((1, n - m), cdt)], 1)
        d_right_asc, g_v_asc = _chain(_flip(d0v), _flip(va2), _flip(vb2),
                                      rho3, rho4, **kw)
        g_v = _flip2(g_v_asc)
        d_right = _flip(d_right_asc)
        v_n = _mm(v, g_v)
        gv_mm = g_v[:m, :m]
        bv = _mm(vtb, g_v[:, :m])

    if sign_fix:
        # diag_i = u_i^T (A + a b^T) v_i from the structured factors
        core = _sum((s.T * g_u) * gv_mm, 0)
        au = _mm(uta, g_u)
        diag = core + au * bv
        flip = _pm1(diag < 0.0, cdt)
        if n > m:
            flip = jnp.concatenate([flip, jnp.ones((1, n - m), cdt)], 1)
        v_n = v_n * flip

    if cdt.itemsize <= 4:
        # 32-bit: the left and right chains resolve a cluster of singular
        # values far below s_max only to eps * s_max^2 / gap, each in its
        # own basis, so their vectors pair up rotated (~1e-1 rad for the
        # four ~32s of a Sparse op on a 4096-scale stream).  Take the right
        # vectors from the left ones instead, v_i = (A + a b^T)^T u_i / s_i:
        # paired by construction, sign included.  Below sqrt(eps) * s_max
        # that division amplifies u's error too far; keep the chain there
        # (the truncated routes then repair those columns:
        # ``repair_null_columns``).
        au = _mm(uta, g_u)
        w = _mm(v[:, :m], s.T * g_u) + b.T * au     # (A + a b^T)^T u_new
        ok = ~null_columns(s_n)
        head = jnp.where(ok, w / jnp.where(ok, s_n, 1.0), v_n[:, :m])
        v_n = head if n == m else jnp.concatenate([head, v_n[:, m:]], 1)

    return (u_n.astype(store_dt), s_n.astype(store_dt), v_n.astype(store_dt),
            d_left.astype(store_dt), d_right.astype(store_dt))


def _fused_truncated_body(u, s, v, a, b, *, deflate_rtol=None, n_bisect=28,
                          n_newton=4, compute_dtype=None):
    """Brand augmentation + the fused core, resident end to end.

    Same contract as ``core.svd_update._svd_update_truncated_impl``:
    ``u``: (m, r), ``s``: (1, r), ``v``: (n, r) -> same shapes
    (``a``/``b`` are (1, m)/(1, n) rows).
    """
    m, r = u.shape
    store_dt = u.dtype
    cdt = jnp.dtype(compute_dtype) if compute_dtype is not None \
        else _compute_dtype_for(store_dt)
    uc = u.astype(cdt)
    sc = s.astype(cdt)
    vc = v.astype(cdt)
    ac = a.astype(cdt)
    bc = b.astype(cdt)

    p_vec = _mm(ac, uc)
    a_perp = ac - _mm_t(p_vec, uc)
    ra = jnp.sqrt(_sum(a_perp * a_perp))
    ok_a = ra > 1e-12
    p_unit = jnp.where(ok_a, a_perp / jnp.where(ok_a, ra, 1.0), 0.0)
    ra = jnp.where(ok_a, ra, 0.0)

    q_vec = _mm(bc, vc)
    b_perp = bc - _mm_t(q_vec, vc)
    rb = jnp.sqrt(_sum(b_perp * b_perp))
    ok_b = rb > 1e-12
    q_unit = jnp.where(ok_b, b_perp / jnp.where(ok_b, rb, 1.0), 0.0)
    rb = jnp.where(ok_b, rb, 0.0)

    s_aug = jnp.concatenate([sc, jnp.zeros((1, 1), cdt)], 1)
    ak = jnp.concatenate([p_vec, ra], 1)
    bk = jnp.concatenate([q_vec, rb], 1)
    eye = (_iota((r + 1, r + 1), 0) == _iota((r + 1, r + 1), 1)).astype(cdt)
    uu, ss, vv, _, _ = _fused_body(
        eye, s_aug, eye, ak, bk, sign_fix=True, deflate_rtol=deflate_rtol,
        n_bisect=n_bisect, n_newton=n_newton, compute_dtype=cdt,
    )
    if cdt.itemsize <= 4:
        uu, ss, vv = repair_null_columns(uu, ss, vv)

    u_aug = jnp.concatenate([uc, p_unit.T], axis=1)
    v_aug = jnp.concatenate([vc, q_unit.T], axis=1)
    u_new = _mm(u_aug, uu[:, :r])
    v_new = _mm(v_aug, vv[:, :r])
    return (u_new.astype(store_dt), ss[:, :r].astype(store_dt),
            v_new.astype(store_dt))


# ---------------------------------------------------------------------------
# XLA entry points (jit / vmap targets)
# ---------------------------------------------------------------------------


def _vectors_as_rows(body, u, s, v, a, b, **kw):
    # 1-D in and out at the XLA boundary; rows inside the body.
    out = body(u, s[None], v, a[None], b[None], **kw)
    return tuple(x[0] if i in (1, 3, 4) else x for i, x in enumerate(out))


@functools.partial(jax.jit, static_argnames=(
    "sign_fix", "n_bisect", "n_newton", "compute_dtype"))
def fused_update_xla(u, s, v, a, b, *, sign_fix=True, deflate_rtol=None,
                     n_bisect=16, n_newton=6, compute_dtype=None):
    """The fused body as one XLA fusion (CPU path; vmaps cleanly)."""
    return _vectors_as_rows(_fused_body, u, s, v, a, b, sign_fix=sign_fix,
                            deflate_rtol=deflate_rtol, n_bisect=n_bisect,
                            n_newton=n_newton, compute_dtype=compute_dtype)


@functools.partial(jax.jit, static_argnames=(
    "n_bisect", "n_newton", "compute_dtype"))
def fused_update_truncated_xla(u, s, v, a, b, *, deflate_rtol=None,
                               n_bisect=16, n_newton=6, compute_dtype=None):
    return _vectors_as_rows(_fused_truncated_body, u, s, v, a, b,
                            deflate_rtol=deflate_rtol, n_bisect=n_bisect,
                            n_newton=n_newton, compute_dtype=compute_dtype)


# ---------------------------------------------------------------------------
# Pallas entry points — grid (B,), one program per update, all phases in VMEM
# ---------------------------------------------------------------------------


# Per-update operands travel as (B, rows, cols) with a (1, rows, cols)
# block per program: vectors as (B, 1, k), so every block spans its
# array's last two dims — the only shape Mosaic accepts for k % 128 != 0.


def _kernel(*refs, body, n_in, statics):
    outs = body(*(r[0] for r in refs[:n_in]), **statics)
    for ref, out in zip(refs[n_in:], outs):
        ref[0] = out


def _batched_call(body, statics, args, out_shapes, interpret):
    dt = args[0].dtype
    bsz = args[0].shape[0]
    args = [x.astype(dt).reshape(bsz, 1, -1) if x.ndim == 2 else x
            for x in args]
    shapes = [sh if len(sh) == 2 else (1,) + sh for sh in out_shapes]

    def specs(shs):
        # i * 0, not 0: an int literal is int64 under x64, which Mosaic rejects
        return [pl.BlockSpec((1,) + sh, lambda i: (i, i * 0, i * 0)) for sh in shs]

    out = pl.pallas_call(
        functools.partial(_kernel, body=body, n_in=len(args), statics=statics),
        grid=(bsz,),
        in_specs=specs([x.shape[1:] for x in args]),
        out_specs=specs(shapes),
        out_shape=[jax.ShapeDtypeStruct((bsz,) + sh, dt) for sh in shapes],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=FUSED_VMEM_LIMIT),
        interpret=interpret,
    )(*args)
    return [o.reshape((bsz,) + sh) for o, sh in zip(out, out_shapes)]


@functools.partial(jax.jit, static_argnames=(
    "sign_fix", "n_bisect", "n_newton", "compute_dtype", "interpret"))
def fused_update_pallas_batched(u, s, v, a, b, *, sign_fix=True,
                                deflate_rtol=None, n_bisect=16, n_newton=6,
                                compute_dtype=None, interpret=False):
    """B stacked fused updates, batch folded into the Pallas grid.

    ``u``: (B, m, m), ``s``: (B, m), ``v``: (B, n, n), ``a``: (B, m),
    ``b``: (B, n) -> the 5-tuple of stacked ``SvdUpdateResult`` leaves.
    """
    m = u.shape[-1]
    n = v.shape[-1]
    statics = dict(sign_fix=sign_fix, deflate_rtol=deflate_rtol,
                   n_bisect=n_bisect, n_newton=n_newton,
                   compute_dtype=compute_dtype)
    return _batched_call(_fused_body, statics, (u, s, v, a, b),
                         [(m, m), (m,), (n, n), (m,), (n,)], interpret)


def fused_update_pallas(u, s, v, a, b, **kw):
    """Single fused update via the (B,)-grid kernel with B = 1."""
    out = fused_update_pallas_batched(u[None], s[None], v[None],
                                      a[None], b[None], **kw)
    return tuple(x[0] for x in out)


@functools.partial(jax.jit, static_argnames=(
    "n_bisect", "n_newton", "compute_dtype", "interpret"))
def fused_update_truncated_pallas_batched(u, s, v, a, b, *, deflate_rtol=None,
                                          n_bisect=16, n_newton=6,
                                          compute_dtype=None, interpret=False):
    """B stacked fused truncated updates (Brand + fused core per program)."""
    _, m, r = u.shape
    n = v.shape[-2]
    statics = dict(deflate_rtol=deflate_rtol, n_bisect=n_bisect,
                   n_newton=n_newton, compute_dtype=compute_dtype)
    return _batched_call(_fused_truncated_body, statics, (u, s, v, a, b),
                         [(m, r), (r,), (n, r)], interpret)


def fused_update_truncated_pallas(u, s, v, a, b, **kw):
    out = fused_update_truncated_pallas_batched(u[None], s[None], v[None],
                                                a[None], b[None], **kw)
    return tuple(x[0] for x in out)
