"""Elastic scaling: re-mesh (training) and re-shard (serving) on restart.

Checkpoints store full (host-gathered) arrays, so they are mesh-independent.
On restart, ``plan_mesh`` inspects the devices that are actually alive and
chooses the largest (data, model) factorization consistent with the model's
TP divisibility constraints; ``reshard`` places a restored pytree onto the
new mesh. At 1000+-node scale this is the recover-with-fewer-pods path: a
dead pod shrinks the data axis, training continues at reduced global batch.

The serving tier has the same failover shape at a different granularity:
a fleet snapshot (``repro.fleet.FleetSnapshot``) is shard-count-independent
the way a training checkpoint is mesh-independent, so
``SvdFleet.restore(..., num_shards="auto")`` asks ``plan_shard_count`` to
size the restored fleet to the devices that actually came back; the
per-stream state regroup (``FleetSnapshot.regrouped``) is the serving
analogue of ``reshard`` — pure data movement, bitwise.
"""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding

from repro.dist import sharding as sh
from repro.launch.mesh import auto_mesh

__all__ = ["plan_mesh", "plan_shard_count", "reshard", "largest_factorization"]


def largest_factorization(n: int, max_model: int = 16) -> tuple[int, int]:
    """(data, model) with model as large as possible, model | n, model <= max."""
    for m in range(min(max_model, n), 0, -1):
        if n % m == 0:
            return n // m, m
    return n, 1


def plan_mesh(max_model: int = 16):
    n = jax.device_count()
    data, model = largest_factorization(n, max_model)
    return auto_mesh((data, model), ("data", "model"))


def plan_shard_count(max_shards: int | None = None, *, devices=None) -> int:
    """Fleet shard count for the devices actually alive: one service shard
    per device (each shard's flush rounds pin to its own device,
    ``fleet.placement.plan_devices``), optionally capped.  The serving twin
    of ``plan_mesh`` — called by ``SvdFleet.restore(num_shards="auto")``."""
    n = len(devices) if devices is not None else jax.device_count()
    if n < 1:
        raise ValueError("no live devices to plan shards for")
    return min(n, max_shards) if max_shards is not None else n


def reshard(tree, mesh):
    """Place a host pytree onto ``mesh`` per the standard param rules."""
    specs = sh.param_pspecs(tree)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs
    )
