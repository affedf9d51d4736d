"""Ahead-of-time compiles for a described TPU v5e: the Pallas kernels of the
main path must pass the chip's own compiler (Mosaic), which interpret mode
never consults — block alignment, unsupported primitives, VMEM limits.

Nothing here runs on a chip: the topology is described, not attached, so
each test compiles a kernel for it and checks the compiled HLO.  The
topology is described inside a module-scoped fixture (never at import), so
every pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU compiler.  All shapes are explicit float32 (the
suite runs with x64 on), and the persistent compilation cache is off around
the compiles (an entry compiled for an absent chip cannot be read back).
"""

import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels import fused_update as F
from repro.kernels.sparse_proj import sparse_project_pallas

F32 = jnp.float32
SMOKE = (4096, 4096, 32)     # chip_smoke.py: (m, n, r) per stream
SMOKE_BATCH = 256            # chip_smoke.py: streams per flush round


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(sharding, fn, *shapes, dtypes=None):
    dtypes = dtypes or (F32,) * len(shapes)
    args = [jax.ShapeDtypeStruct(sh, dt, sharding=sharding)
            for sh, dt in zip(shapes, dtypes)]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("m,n", [(32, 48), (64, 64), (256, 256)])
def test_fused_full_compiles(one_chip, m, n):
    assert F.fused_supported(m, n, dtype=F32)
    _compile(one_chip, F.fused_update_pallas, (m, m), (m,), (n, n), (m,), (n,))


def test_fused_full_batched_compiles(one_chip):
    b, m, n = 8, 32, 48
    _compile(one_chip, F.fused_update_pallas_batched,
             (b, m, m), (b, m), (b, n, n), (b, m), (b, n))


def test_fused_truncated_compiles_at_smoke_geometry(one_chip):
    m, n, r = SMOKE
    assert F.fused_supported(m, n, r, dtype=F32)
    _compile(one_chip, F.fused_update_truncated_pallas,
             (m, r), (r,), (n, r), (m,), (n,))


def test_fused_truncated_compiles_at_largest_admitted_geometry(one_chip):
    # fused_supported's edge at r=32: every geometry it admits must compile
    m = n = 7000
    r = 32
    assert F.fused_supported(m, n, r, dtype=F32)
    assert not F.fused_supported(m + 500, n + 500, r, dtype=F32)
    _compile(one_chip, F.fused_update_truncated_pallas_batched,
             (8, m, r), (8, r), (8, n, r), (8, m), (8, n))


def test_fused_truncated_batched_compiles_at_smoke_geometry(one_chip):
    m, n, r = SMOKE
    b = SMOKE_BATCH
    compiled = _compile(one_chip, F.fused_update_truncated_pallas_batched,
                        (b, m, r), (b, r), (b, n, r), (b, m), (b, n))
    mem = compiled.memory_analysis()
    state = b * (m + n) * r * 4
    # arguments are the stacked states plus the stacked pairs; no hidden copy
    assert mem.argument_size_in_bytes < 1.1 * state
    assert mem.output_size_in_bytes < 1.1 * state


def test_sparse_proj_compiles(one_chip):
    nnz, m, k = 4096, 4096, 64
    _compile(one_chip,
             lambda r, c, v, x: sparse_project_pallas(r, c, v, x, out_rows=m),
             (nnz,), (nnz,), (nnz,), (m, k),
             dtypes=(jnp.int32, jnp.int32, F32, F32))
