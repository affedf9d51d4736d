"""The plain reference and the comparison that decides ``correct``.

The reference is float64 numpy and imports nothing of the program: the
traffic generator hands it each sampled stream's matrix as exact factors
``L R^T`` (seed state plus every event the stream was sent), and this module
measures the program's state against it:

* ``recon_rel = ||U diag(s) V^T - L R^T||_F / ||L R^T||_F``
* ``sigma_rel = max_i |s_i - sigma_i| / sigma_0`` against the reference's
  top ``r`` singular values.

``bench.harness.check`` divides each by the number of events the stream was
sent, since float32 rounding grows with the chain.

Both are taken in factored form (QR of the stacked factors), never forming
the (m, n) matrix.

``control_state`` is the control: the same reference semantics as a plain
per-event truncated update (Brand's augmentation, a dense SVD of the
(r+1)-sized core), computed with every matrix product at the ``high``
precision, three bf16 passes, emulated explicitly so it reads the same on
any platform.  Put in the program's place, it has to come out not correct.
"""

from __future__ import annotations

import numpy as np


def _r(x):
    return np.linalg.qr(x, mode="r")


def compare(u, s, v, left, right) -> dict:
    """``recon_rel`` and ``sigma_rel`` of the state ``(u, s, v)`` against
    the reference matrix ``left @ right.T``; inf where the state is not
    finite."""
    u, s, v = (np.asarray(x, np.float64) for x in (u, s, v))
    if not all(np.isfinite(x).all() for x in (u, s, v)):
        return {"recon_rel": float("inf"), "sigma_rel": float("inf")}
    sigma = np.linalg.svd(_r(left) @ _r(right).T, compute_uv=False)
    norm = float(np.sqrt(np.sum(sigma ** 2)))
    diff = _r(np.concatenate([u * s, -left], 1)) @ _r(np.concatenate([v, right], 1)).T
    r = s.shape[0]
    ref = np.zeros(r)
    ref[:min(r, sigma.size)] = sigma[:r]
    return {"recon_rel": float(np.linalg.norm(diff) / norm),
            "sigma_rel": float(np.max(np.abs(s - ref)) / sigma[0])}


# ---------------------------------------------------------------------------
# the control: the reference's semantics, per event, at `high` precision
# ---------------------------------------------------------------------------


def _mm_high(x, y):
    """``x @ y`` in three bf16 passes (hi*hi + hi*lo + lo*hi), accumulated
    in float32: what ``precision="high"`` computes on a TPU."""
    import jax.numpy as jnp
    from jax import lax

    def split(z):
        hi = z.astype(jnp.bfloat16).astype(jnp.float32)
        return hi, (z - hi).astype(jnp.bfloat16).astype(jnp.float32)

    (xh, xl), (yh, yl) = split(x), split(y)
    dot = lambda p, q: jnp.matmul(p, q, precision=lax.Precision.HIGHEST)
    return dot(xh, yh) + dot(xh, yl) + dot(xl, yh)


def _brand_step(carry, ab):
    """One plain truncated rank-1 update of ``(u, s, v)`` by ``a b^T``."""
    import jax.numpy as jnp

    u, s, v = carry
    a, b = ab
    r = s.shape[0]
    p = _mm_high(a[None], u)[0]
    ap = a - _mm_high(u, p[:, None])[:, 0]
    ra = jnp.linalg.norm(ap)
    q = _mm_high(b[None], v)[0]
    bq = b - _mm_high(v, q[:, None])[:, 0]
    rb = jnp.linalg.norm(bq)
    pu = jnp.where(ra > 0, ap / jnp.where(ra > 0, ra, 1.0), 0.0)
    qv = jnp.where(rb > 0, bq / jnp.where(rb > 0, rb, 1.0), 0.0)
    k = jnp.diag(jnp.concatenate([s, jnp.zeros(1, s.dtype)]))
    k = k + jnp.outer(jnp.concatenate([p, ra[None]]), jnp.concatenate([q, rb[None]]))
    uk, sk, vkt = jnp.linalg.svd(k)
    u2 = _mm_high(jnp.concatenate([u, pu[:, None]], 1), uk[:, :r])
    v2 = _mm_high(jnp.concatenate([v, qv[:, None]], 1), vkt.T[:, :r])
    return (u2, sk[:r], v2), None


_CONTROL_SCAN = None


def control_state(u0, s0, v0, a, b):
    """The control's state after the events ``a`` (count, m), ``b``
    (count, n) from ``(u0, s0, v0)``, float32 on the default device."""
    import jax
    import jax.numpy as jnp

    global _CONTROL_SCAN
    if _CONTROL_SCAN is None:
        _CONTROL_SCAN = jax.jit(lambda c, e: jax.lax.scan(_brand_step, c, e)[0])
    f32 = lambda x: jnp.asarray(np.asarray(x, np.float32))
    with jax.default_matmul_precision("highest"):
        out = _CONTROL_SCAN((f32(u0), f32(s0), f32(v0)), (f32(a), f32(b)))
    return tuple(np.asarray(x) for x in out)
