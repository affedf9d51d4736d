"""``UpdatePolicy`` — every tuning knob of a rank-1 SVD update, in one frozen
hashable object (DESIGN.md §8).

Before this layer, callers hand-threaded ``method=``, ``fmm_p=``, ``mesh=``,
``batch_axis=`` and truncation decisions through optim, serve, dist and
train.  A policy captures all of them once; ``repro.api.update`` dispatches
from *state geometry + policy*, and the policy's numerics fields fold into
the engine plan-cache key (``core.engine.default_engine``), so policy-equal
calls share one compiled plan — equal policies can never recompile.

Hashability is load-bearing: policies are dict keys for engine lookup and
legal ``static_argnums`` for jitted consumers.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

from repro.core.eigh_update import _FMM_MIN_N  # auto-resolution matches core's floor

__all__ = ["UpdatePolicy", "METHODS", "policy_from_legacy"]

# "pallas" is the public name for the Pallas Cauchy-kernel route (engine name
# "kernel" is kept as an alias).  "fused" is the single-kernel megakernel
# route (kernels.fused_update): the whole update resident per batch element —
# auto prefers it whenever the geometry fits its VMEM budget.  "fast"
# (Gerasoulis FAST, core.fast) is part of the enum for completeness but is a
# host-side numpy benchmark baseline — it cannot run inside the jitted engine
# and dispatch rejects it with a pointer to benchmarks/framework_bench.py.
METHODS = ("auto", "direct", "fmm", "fast", "pallas", "kernel", "fused")


@dataclasses.dataclass(frozen=True)
class UpdatePolicy:
    """Declarative description of HOW a rank-1 update should run.

    Numerics:
      method        auto | direct | fmm | pallas | fused (| kernel alias | fast: bench only)
      fmm_p         Chebyshev interpolation order of the FMM route
      sign_fix      reconcile left/right singular-vector signs (paper gap)
      deflate_rtol  deflation tolerance override (None = core default)
      precision     jax matmul precision for the update ("highest", ...; None = default)
      storage_dtype keep SvdState factors in this dtype (e.g. jnp.bfloat16);
                    16-bit storage computes in f32 inside the engine — the
                    mixed-precision mode, error budget in DESIGN.md §11

    Sketching (the randomized range-finder every DenseDelta/Sparse lowering
    runs through — ``updates.sketch``, DESIGN.md §12):
      sketch_oversample   extra sample columns beyond the target rank; the
                          sketch is exact when rank + oversample covers the
                          delta's true rank
      sketch_power_iters  subspace (power) iterations sharpening truncating
                          DENSE sketches (a dense pass is a cheap GEMM); the
                          sparse single-pass path has no power iterations by
                          design — its accuracy lever is sketch_oversample

    Placement:
      mesh         jax.sharding.Mesh to spread a batched update over (None = local)
      batch_axis   mesh axis name carrying the batch

    Truncation rule:
      truncate_to  keep only the top-r triplets of every result (None = keep all)

    Observability (``repro.obs``, DESIGN.md §15):
      health_every  sample the numerical-health probes every N flush rounds
                    in the serve/fleet tiers (None = never).  Purely a
                    monitoring cadence — probes run OUTSIDE the update's
                    traced path, so this knob is deliberately NOT part of
                    ``engine_key``: it can never cause a recompile or
                    change a result.

    Policies are plain frozen dataclasses — build once, ``replace`` to vary:

    >>> from repro.api import UpdatePolicy
    >>> pol = UpdatePolicy(method="fmm", fmm_p=12)
    >>> pol.replace(truncate_to=8).truncate_to
    8
    >>> hash(pol) == hash(UpdatePolicy(method="fmm", fmm_p=12))
    True
    >>> UpdatePolicy(method="svd")
    Traceback (most recent call last):
        ...
    ValueError: unknown method 'svd'; one of ('auto', 'direct', 'fmm', 'fast', 'pallas', 'kernel', 'fused')
    """

    method: str = "auto"
    fmm_p: int = 20
    sign_fix: bool = True
    deflate_rtol: float | None = None
    precision: str | None = None
    storage_dtype: Any = None
    sketch_oversample: int = 8
    sketch_power_iters: int = 1
    mesh: Any = None
    batch_axis: str = "data"
    truncate_to: int | None = None
    health_every: int | None = None

    def __post_init__(self):
        if self.method not in METHODS:
            raise ValueError(f"unknown method {self.method!r}; one of {METHODS}")
        if self.truncate_to is not None and self.truncate_to < 1:
            raise ValueError(f"truncate_to must be >= 1; got {self.truncate_to}")
        if self.sketch_oversample < 0:
            raise ValueError(
                f"sketch_oversample must be >= 0; got {self.sketch_oversample}"
            )
        if self.sketch_power_iters < 0:
            raise ValueError(
                f"sketch_power_iters must be >= 0; got {self.sketch_power_iters}"
            )
        if self.health_every is not None and self.health_every < 1:
            raise ValueError(
                f"health_every must be >= 1 or None; got {self.health_every}"
            )
        if self.storage_dtype is not None:
            # canonicalize to np.dtype: hashable, comparable, serializable
            object.__setattr__(self, "storage_dtype", np.dtype(self.storage_dtype))

    def replace(self, **kw) -> "UpdatePolicy":
        return dataclasses.replace(self, **kw)

    # -- engine folding -----------------------------------------------------

    def resolve_method(self, problem_n: int, *, m: int | None = None,
                       n: int | None = None, rank: int | None = None,
                       dtype=None) -> str:
        """Concrete engine method for a problem of secular size ``problem_n``
        (``n`` for full updates, ``rank + 1`` for truncated ones).

        ``auto`` prefers the fused megakernel whenever enough geometry is
        known (``m``, plus ``n``/``rank`` where they differ from
        ``problem_n``) and it fits the kernel's VMEM budget; otherwise it
        falls back to the FMM-above-the-tree-floor rule — except for a
        32-bit (or narrower) ``dtype``, where FMM returns NaN and ``auto``
        stays ``direct``.  Callers without geometry get the pre-fused
        behavior unchanged:

        >>> from repro.api import UpdatePolicy
        >>> UpdatePolicy(method="fmm").resolve_method(problem_n=256)
        'fmm'
        >>> UpdatePolicy().resolve_method(problem_n=9)  # auto: below FMM floor
        'direct'
        >>> UpdatePolicy(method="pallas").resolve_method(64)  # public kernel name
        'kernel'
        >>> UpdatePolicy().resolve_method(48, m=32)  # auto + geometry: fused
        'fused'
        >>> UpdatePolicy().resolve_method(256, dtype="float32")  # never fmm in f32
        'direct'
        """
        if self.method == "fast":
            raise NotImplementedError(
                "method='fast' (Gerasoulis FAST) is the host-side numpy "
                "benchmark baseline — see benchmarks/framework_bench.py; it "
                "is not a jittable engine route. Use auto/direct/fmm/pallas/fused."
            )
        if self.method == "pallas":
            return "kernel"
        if self.method == "auto":
            dt = np.dtype(self.storage_dtype if self.storage_dtype is not None
                          else dtype if dtype is not None else np.float32)
            if m is not None:
                from repro.kernels.fused_update import fused_supported

                if fused_supported(m, n if n is not None else problem_n,
                                   rank, dtype=dt):
                    return "fused"
            # FMM pays off only above the tree floor; tiny problems (incl.
            # every truncated (r+1)-sized core) run the stable direct route.
            if dtype is not None and dt.itemsize <= 4:
                return "direct"
            return "fmm" if problem_n >= _FMM_MIN_N else "direct"
        return self.method

    def engine_key(self, problem_n: int, *, m: int | None = None,
                   n: int | None = None, rank: int | None = None,
                   dtype=None) -> tuple:
        """The (method, fmm_p, sign_fix, deflate_rtol, precision,
        storage_dtype, sketch_oversample, sketch_power_iters) tuple that
        keys compiled artifacts — the policy's full numerics fold.  The
        first six select ``core.engine.default_engine`` (the rank-1 plan
        cache); the sketch fields key the planner's schedule cache + the
        jitted ``updates.sketch`` executables (the engine body itself is
        sketch-independent)."""
        return (
            self.resolve_method(problem_n, m=m, n=n, rank=rank, dtype=dtype),
            self.fmm_p,
            self.sign_fix,
            self.deflate_rtol,
            self.precision,
            self.storage_dtype,
            self.sketch_oversample,
            self.sketch_power_iters,
        )

    @property
    def sketch_params(self) -> tuple[int, int]:
        """(oversample, power_iters) — the schedule-cache fold of the
        range-finder knobs (``updates.planner.lower``)."""
        return (self.sketch_oversample, self.sketch_power_iters)


def policy_from_legacy(
    policy: UpdatePolicy | None,
    method: str = "direct",
    mesh: Any = None,
    batch_axis: str = "data",
) -> UpdatePolicy:
    """Back-compat fold: consumers that still accept the pre-api ``method=``
    / ``mesh=`` / ``batch_axis=`` kwargs turn them into a policy here — one
    definition of the legacy-to-policy mapping for every layer."""
    if policy is not None:
        return policy
    return UpdatePolicy(method=method, mesh=mesh, batch_axis=batch_axis)
