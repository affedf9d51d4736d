"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — required by the dry-run contract.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_host_mesh", "auto_mesh"]


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: specs are placement hints
    the compiler propagates (jax >= 0.7 otherwise defaults to ``Explicit``
    axes, whose sharding-in-types rejects the engine's vmapped bodies)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi_pod adds a leading pod=2 axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(data: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU runs)."""
    n = jax.device_count()
    if data is None:
        data = n // model
    return auto_mesh((data, model), ("data", "model"))


# ``batch_sharding`` / ``batch_pad`` live in ``repro.dist.sharding`` (the
# one sharding home, DESIGN.md §7); the transitional re-exports that used
# to sit here were removed with the rest of the pre-api surface.
