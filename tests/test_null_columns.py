"""Rank-deficient float32 states through the truncated update routes.

A sliding window whose rows lie in a rank-``k`` row space, kept as a rank-8
truncated SVD: a new row overwrites the oldest (``a = e_slot``, ``b = x_new
- x_old``), so ``b`` lies in the state's row space to rounding.  With
``k < r`` the state has null columns, and every route must keep ``V``
orthonormal and the spectrum on the float64 reference after every event;
``k = r`` (full rank) pins the other side of the null-column test.
"""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.svd_update import TruncatedSvd, _svd_update_truncated_impl
from repro.kernels import fused_update as F

M, N, R = 64, 24, 8
EVENTS = 16
TOL = 1e-5


def _window(k: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    g = rng.choice([-1.0, 1.0], size=(N, k))
    rows = rng.integers(-1024, 1025, size=(M, k)) / 4096.0
    x, s, yt = np.linalg.svd(rows @ g.T, full_matrices=False)
    s = np.where(np.arange(R) < k, s[:R], 0.0)
    state = tuple(z.astype(np.float32) for z in (x[:, :R], s, yt.T[:, :R]))
    return rng, g, rows, state


def _fused_xla(u, s, v, a, b):
    return F.fused_update_truncated_xla(u, s, v, a, b)


def _fused_pallas(u, s, v, a, b):
    out = F.fused_update_truncated_pallas_batched(
        u[None], s[None], v[None], a[None], b[None], interpret=True)
    return tuple(x[0] for x in out)


@partial(jax.jit)
def _direct(u, s, v, a, b):
    return tuple(_svd_update_truncated_impl(TruncatedSvd(u, s, v), a, b,
                                            method="direct"))


ROUTES = {"fused_xla": _fused_xla, "fused_pallas_interpret": _fused_pallas,
          "direct": _direct}


@pytest.mark.parametrize("k", [4, R], ids=["rank4_of_8", "full_rank"])
@pytest.mark.parametrize("route", list(ROUTES))
def test_slot_chain_keeps_v_orthonormal_and_sigma(route, k):
    rng, g, rows, (u, s, v) = _window(k)
    update = ROUTES[route]
    for t in range(EVENTS):
        slot = t % M
        new = rng.integers(-1024, 1025, size=k) / 4096.0
        a = np.zeros(M, np.float32)
        a[slot] = 1.0
        b = ((new - rows[slot]) @ g.T).astype(np.float32)
        rows[slot] = new
        with jax.default_matmul_precision("highest"):
            u, s, v = (np.asarray(x, np.float64) for x in update(
                *(jnp.asarray(x, jnp.float32) for x in (u, s, v, a, b))))
        sigma = np.linalg.svd(rows @ g.T, compute_uv=False)
        ref = np.zeros(R)
        ref[:min(R, sigma.size)] = sigma[:R]
        sigma_rel = np.max(np.abs(s - ref)) / sigma[0]
        ortho = np.max(np.abs(v.T @ v - np.eye(R)))
        assert ortho <= TOL, (t, ortho)
        assert sigma_rel <= TOL, (t, sigma_rel)


def test_null_columns_and_repair_keep_kept_columns_exactly():
    s = jnp.asarray([[4.0, 2.0, 1e-7, 1e-8, 0.0]], jnp.float32)
    assert np.asarray(F.null_columns(s)).tolist() == [[False, False, True, True, True]]
    q = np.linalg.qr(np.random.default_rng(1).normal(size=(5, 5)))[0].astype(np.float32)
    u, s2, v = (np.asarray(x) for x in F.repair_null_columns(jnp.asarray(q), s,
                                                              jnp.asarray(q)))
    for basis in (u, v):
        np.testing.assert_array_equal(basis[:, :2], q[:, :2])
        np.testing.assert_allclose(basis.T @ basis, np.eye(5), atol=1e-6)
        # the dropped last column is the core's last coordinate less its
        # part along the kept columns, so the other null columns avoid it
        np.testing.assert_allclose(basis[4, 2:4], 0.0, atol=1e-6)
    np.testing.assert_array_equal(s2[0], [4.0, 2.0, 0.0, 0.0, 0.0])
