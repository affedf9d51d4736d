"""Batch-first SVD-update engine (DESIGN.md §4).

The paper's O(n^2 log(1/eps)) rank-1 update only pays off at system scale
when many updates run per step. ``SvdEngine`` is the subsystem that makes
that the default shape of the computation:

* **Plan cache.** Every distinct update geometry — (kind, batch, m, n, rank,
  dtype) x (method, fmm_p, sign_fix) — gets one cached, jitted executable.
  Trace + secular/FMM plan construction ("the plan") is paid once per
  geometry; every later call with that geometry is a cache hit that goes
  straight to the compiled batched update. ``warmup`` AOT-compiles a
  geometry ahead of traffic (serving cold-start control).

* **Batched entry points.** ``update_batch`` / ``update_truncated_batch``
  vmap Algorithm 6.1 over a leading batch axis of stacked (u, s, v) states
  and (a, b) perturbations. Under ``method="kernel"`` the hot Cauchy product
  lowers to ONE Pallas launch with the batch folded into the grid
  (``kernels.cauchy_matmul.cauchy_matmul_pallas_batched`` via the
  ``custom_vmap`` rule in ``kernels.ops``); under ``method="fmm"`` the
  Chebyshev-FMM plans batch as stacked tensors.

* **Sharding.** An optional ``jax.sharding.Sharding`` for the batch axis
  (build one with ``repro.dist.batch_sharding``) is applied to the stacked
  inputs, so a flush of B updates spreads over the mesh's data axis.

* **Mesh-aware dispatch.** ``update_batch`` / ``update_truncated_batch``
  accept ``mesh=`` + ``batch_axis=`` and then dispatch through
  ``shard_map``: the batch axis is split over the mesh axis and each shard
  runs the vmapped update — under ``method="kernel"`` one per-shard Pallas
  Cauchy launch with the local batch folded into its grid.  The update is
  embarrassingly parallel over the batch, so NOTHING crosses the wire
  inside the engine; only consumers' small factor collectives do
  (``repro.dist.collectives``).  Batches are auto-padded to the mesh axis
  size (no-op tail entries, results sliced off).

Consumers: ``optim.spectral`` / ``optim.compression`` group equal-geometry
parameters and make one engine call per group; ``serve.svd_service``
micro-batches streaming (a, b) pairs into engine flushes.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import AxisType, PartitionSpec

from repro import obs as _obs
from repro.core.svd_update import (
    SvdUpdateResult,
    TruncatedSvd,
    _svd_update_impl,
    _svd_update_truncated_impl,
)

__all__ = [
    "EngineCacheInfo",
    "SvdEngine",
    "default_engine",
    "group_indices",
    "stack_trees",
    "truncated_geometry",
    "unstack_tree",
]


# ---------------------------------------------------------------------------
# Group/stack/unstack helpers shared by every batching consumer
# (optim.spectral, optim.compression, serve.svd_service).
# ---------------------------------------------------------------------------


def truncated_geometry(tsvd: "TruncatedSvd") -> tuple:
    """Batching-group key for a truncated SVD state: ``(m, n, rank, dtype)``.

    States sharing this key can be stacked into one
    ``update_truncated_batch`` call — the single definition every batching
    consumer groups by."""
    m, r = tsvd.u.shape
    return (m, tsvd.v.shape[0], r, tsvd.u.dtype)


def group_indices(keys) -> dict:
    """``{key: [indices with that key]}`` preserving first-seen order."""
    groups: dict = {}
    for i, k in enumerate(keys):
        groups.setdefault(k, []).append(i)
    return groups


def stack_trees(trees):
    """Stack a sequence of identically-structured pytrees along a new
    leading batch axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def unstack_tree(tree, i: int):
    """Slice batch element ``i`` out of a stacked pytree."""
    return jax.tree.map(lambda x: x[i], tree)


class EngineCacheInfo(NamedTuple):
    hits: int
    misses: int
    entries: int


@dataclass
class _CacheEntry:
    fn: Callable[..., Any]          # jitted batched/single update
    compiled: Any = None            # AOT executable after warmup()
    calls: int = 0


def _geometry(kind: str, *arrays: jax.Array) -> tuple:
    return (kind,) + tuple((a.shape, jnp.result_type(a)) for a in arrays)


class SvdEngine:
    """Plan-cached, vmap-able rank-1 SVD update engine.

    One engine per (method, fmm_p, sign_fix) configuration; geometries are
    cached inside. Thread-safe: the serve layer flushes from request
    threads.
    """

    def __init__(
        self,
        *,
        method: str = "direct",
        fmm_p: int = 20,
        sign_fix: bool = True,
        deflate_rtol: float | None = None,
        precision: str | None = None,
        storage_dtype=None,
        sharding: jax.sharding.Sharding | None = None,
    ):
        if method not in ("direct", "fmm", "kernel", "fused"):
            raise ValueError(f"unknown method {method!r}")
        self.method = method
        self.fmm_p = fmm_p
        self.sign_fix = sign_fix
        self.deflate_rtol = deflate_rtol
        self.precision = precision
        # Mixed precision: with a 16-bit storage dtype the factors arrive
        # narrow; every impl then computes in f32 (in-kernel upcast on the
        # fused route, explicit cast on the phase-chain routes).
        self.storage_dtype = None if storage_dtype is None else jnp.dtype(storage_dtype)
        self.compute_dtype = (
            jnp.dtype(jnp.float32)
            if self.storage_dtype is not None and self.storage_dtype.itemsize <= 2
            else None
        )
        self.sharding = sharding
        self._cache: dict[tuple, _CacheEntry] = {}
        self._hits = 0
        self._misses = 0
        self._lock = threading.Lock()

    # -- plan cache ---------------------------------------------------------

    def cache_info(self) -> EngineCacheInfo:
        return EngineCacheInfo(self._hits, self._misses, len(self._cache))

    def cache_clear(self) -> None:
        with self._lock:
            self._cache.clear()
            self._hits = 0
            self._misses = 0

    def _entry(self, key: tuple, build: Callable[[], Callable]) -> _CacheEntry:
        with self._lock:
            ent = self._cache.get(key)
            hit = ent is not None
            if hit:
                self._hits += 1
            else:
                self._misses += 1
                ent = _CacheEntry(fn=build())
                self._cache[key] = ent
            ent.calls += 1
        if _obs.enabled():
            _obs.registry().counter(
                "engine_plan_cache_hits" if hit else "engine_plan_cache_misses"
            ).inc()
        return ent

    def _constrain(self, *arrays: jax.Array) -> tuple:
        if self.sharding is None:
            return arrays
        return tuple(jax.device_put(a, self.sharding) for a in arrays)

    # -- builders -----------------------------------------------------------

    def _with_precision(self, fn: Callable) -> Callable:
        """Wrap an impl so tracing runs under the configured matmul precision
        (``None``: "highest" — a TPU otherwise rounds f32 matmul operands to
        bf16, far outside the update's f32 error budget)."""
        prec = self.precision or "highest"

        def wrapped(*args):
            with jax.default_matmul_precision(prec):
                return fn(*args)

        return wrapped

    def _full_impl(self) -> Callable:
        impl = partial(
            _svd_update_impl,
            method=self.method,
            fmm_p=self.fmm_p,
            sign_fix=self.sign_fix,
            deflate_rtol=self.deflate_rtol,
            compute_dtype=self.compute_dtype,
        )
        return self._with_precision(lambda u, s, v, a, b: impl(u, s, v, a, b))

    def _trunc_impl(self) -> Callable:
        impl = partial(
            _svd_update_truncated_impl,
            method=self.method,
            fmm_p=self.fmm_p,
            deflate_rtol=self.deflate_rtol,
            compute_dtype=self.compute_dtype,
        )
        return self._with_precision(lambda t, a, b: impl(t, a, b))

    # -- rank-k scan impls ---------------------------------------------------
    # A sequence of k rank-1 pairs applied through ONE lax.scan, so a long
    # repro.updates schedule traces k-independently (updates.planner lowers
    # k >= _SCAN_MIN schedules here). Diagnostics are the LAST step's.

    def _rank_k_fn(self) -> Callable:
        """Unjitted scan-of-updates body (exposed for trace-cost tests)."""
        impl = self._full_impl()

        def fn(u, s, v, va, vb):
            def step(carry, ab):
                res = impl(*carry, ab[0], ab[1])
                return (res.u, res.s, res.v), (res.d_left, res.d_right)

            (u2, s2, v2), (dls, drs) = jax.lax.scan(step, (u, s, v), (va, vb))
            return SvdUpdateResult(u=u2, s=s2, v=v2,
                                   d_left=dls[-1], d_right=drs[-1])

        return fn

    def _trunc_rank_k_fn(self) -> Callable:
        impl = self._trunc_impl()

        def fn(t, va, vb):
            def step(carry, ab):
                res = impl(TruncatedSvd(*carry), ab[0], ab[1])
                return (res.u, res.s, res.v), None

            carry, _ = jax.lax.scan(step, (t.u, t.s, t.v), (va, vb))
            return TruncatedSvd(*carry)

        return fn

    def _build_single(self) -> Callable:
        return jax.jit(self._full_impl())

    def _batch_jit_kwargs(self) -> dict:
        # Batched builders bake the batch sharding into the jit, so AOT
        # executables from warmup() accept the _constrain()-ed inputs.
        return {} if self.sharding is None else {"in_shardings": self.sharding}

    def _build_batch(self) -> Callable:
        return jax.jit(jax.vmap(self._full_impl()), **self._batch_jit_kwargs())

    def _build_truncated(self) -> Callable:
        return jax.jit(self._trunc_impl())

    def _build_truncated_batch(self) -> Callable:
        return jax.jit(jax.vmap(self._trunc_impl()), **self._batch_jit_kwargs())

    def _build_rank_k(self) -> Callable:
        return jax.jit(self._rank_k_fn())

    def _build_rank_k_batch(self) -> Callable:
        return jax.jit(jax.vmap(self._rank_k_fn()), **self._batch_jit_kwargs())

    def _build_trunc_rank_k(self) -> Callable:
        return jax.jit(self._trunc_rank_k_fn())

    def _build_trunc_rank_k_batch(self) -> Callable:
        return jax.jit(jax.vmap(self._trunc_rank_k_fn()), **self._batch_jit_kwargs())

    # -- mesh-aware (shard_map) builders ------------------------------------
    # Per-shard: the same vmapped impl, batch split over one mesh axis. The
    # update is independent per batch element, so there are no collectives
    # inside — check_vma is off because shard_map's replication checker has
    # nothing to verify here and trips on Pallas/custom_vmap internals on
    # the kernel path.

    @staticmethod
    def _shard_batch(fn: Callable, mesh, axis: str, n_in: int) -> Callable:
        # The batch split is a placement the compiler propagates, so run it
        # on Auto axes even when the caller's mesh is Explicit (the
        # ``jax.make_mesh`` default): the padded tail is then sliced off the
        # sharded result like any other array.
        mesh = jax.sharding.Mesh(mesh.devices, mesh.axis_names,
                                 axis_types=(AxisType.Auto,) * len(mesh.axis_names))
        spec = PartitionSpec(axis)
        return jax.jit(shard_map(jax.vmap(fn), mesh=mesh, in_specs=(spec,) * n_in,
                                 out_specs=spec, check_vma=False))

    def _build_batch_shard_map(self, mesh, axis: str) -> Callable:
        return self._shard_batch(self._full_impl(), mesh, axis, 5)

    def _build_truncated_batch_shard_map(self, mesh, axis: str) -> Callable:
        return self._shard_batch(self._trunc_impl(), mesh, axis, 3)

    @staticmethod
    def _mesh_axis_size(mesh, axis: str) -> int:
        try:
            return dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
        except KeyError:
            raise ValueError(
                f"mesh has no axis {axis!r}; axes: {mesh.axis_names}"
            ) from None

    @staticmethod
    def _pad_batch(arrays: tuple, size: int) -> tuple[tuple, int]:
        """Pad the leading batch dim to a multiple of ``size`` by repeating
        the last entry (a real but discarded update). Returns (padded, B)."""
        b = arrays[0].shape[0]
        pad = (-b) % size
        if pad == 0:
            return arrays, b
        padded = tuple(
            jnp.concatenate([x, jnp.repeat(x[-1:], pad, axis=0)]) for x in arrays
        )
        return padded, b

    # -- entry points -------------------------------------------------------

    @staticmethod
    def _call(ent: _CacheEntry, *args):
        # Prefer the AOT executable from warmup(): jit's dispatch cache is
        # NOT populated by lower().compile(), so calling ent.fn would retrace.
        # AOT executables only take concrete arrays — under an outer trace
        # (jit / lax.cond / shard_map consumers) fall back to the jitted fn.
        tracer_cls = getattr(jax.core, "Tracer", None)
        traced = tracer_cls is not None and any(
            isinstance(x, tracer_cls) for x in jax.tree.leaves(args)
        )
        if ent.compiled is not None and not traced:
            try:
                return ent.compiled(*args)
            except (TypeError, ValueError):
                pass  # tracer/sharding mismatch leaked past the check — retrace
        return ent.fn(*args)

    def update(self, u, s, v, a, b) -> SvdUpdateResult:
        """Single Algorithm-6.1 update (plan-cached jit)."""
        key = _geometry("single", u, s, v, a, b)
        ent = self._entry(key, self._build_single)
        return self._call(ent, u, s, v, a, b)

    def update_batch(self, u, s, v, a, b, *, mesh=None, batch_axis: str = "data") -> SvdUpdateResult:
        """B stacked updates in one call.

        ``u``: (B, m, m), ``s``: (B, m), ``v``: (B, n, n), ``a``: (B, m),
        ``b``: (B, n). Returns an ``SvdUpdateResult`` whose leaves carry the
        leading batch axis. Equivalent to B independent ``svd_update`` calls.

        With ``mesh`` the batch is split over ``batch_axis`` and dispatched
        through ``shard_map`` — each device runs its local slice of the
        batch; B is auto-padded up to the axis size and the padding sliced
        off the result.
        """
        if u.ndim != 3:
            raise ValueError(f"update_batch expects stacked (B, m, m) u; got {u.shape}")
        if mesh is None:
            key = _geometry("batch", u, s, v, a, b)
            ent = self._entry(key, self._build_batch)
            return self._call(ent, *self._constrain(u, s, v, a, b))
        size = self._mesh_axis_size(mesh, batch_axis)
        (u, s, v, a, b), b_orig = self._pad_batch((u, s, v, a, b), size)
        key = ("shard", mesh, batch_axis) + _geometry("batch", u, s, v, a, b)
        ent = self._entry(key, partial(self._build_batch_shard_map, mesh, batch_axis))
        out = self._call(ent, u, s, v, a, b)
        return jax.tree.map(lambda x: x[:b_orig], out)

    def update_truncated(self, tsvd: TruncatedSvd, a, b) -> TruncatedSvd:
        """Single streaming truncated update (plan-cached jit)."""
        key = _geometry("trunc", tsvd.u, tsvd.s, tsvd.v, a, b)
        ent = self._entry(key, self._build_truncated)
        return self._call(ent, tsvd, a, b)

    def update_truncated_batch(
        self, tsvd: TruncatedSvd, a, b, *, mesh=None, batch_axis: str = "data"
    ) -> TruncatedSvd:
        """B stacked rank-r streaming updates in one call.

        ``tsvd`` leaves: u (B, m, r), s (B, r), v (B, n, r); ``a``: (B, m),
        ``b``: (B, n). Returns a stacked ``TruncatedSvd``.  With ``mesh``
        the batch is split over ``batch_axis`` via ``shard_map`` (auto-padded
        to the axis size, padding sliced off).
        """
        if tsvd.u.ndim != 3:
            raise ValueError(
                f"update_truncated_batch expects stacked (B, m, r) u; got {tsvd.u.shape}"
            )
        if mesh is None:
            key = _geometry("trunc_batch", tsvd.u, tsvd.s, tsvd.v, a, b)
            ent = self._entry(key, self._build_truncated_batch)
            u_, s_, v_, a_, b_ = self._constrain(tsvd.u, tsvd.s, tsvd.v, a, b)
            return self._call(ent, TruncatedSvd(u_, s_, v_), a_, b_)
        size = self._mesh_axis_size(mesh, batch_axis)
        (u_, s_, v_, a_, b_), b_orig = self._pad_batch(
            (tsvd.u, tsvd.s, tsvd.v, a, b), size
        )
        key = ("shard", mesh, batch_axis) + _geometry("trunc_batch", u_, s_, v_, a_, b_)
        ent = self._entry(
            key, partial(self._build_truncated_batch_shard_map, mesh, batch_axis)
        )
        out = self._call(ent, TruncatedSvd(u_, s_, v_), a_, b_)
        return jax.tree.map(lambda x: x[:b_orig], out)

    # -- rank-k (scan) entry points -----------------------------------------

    def update_rank_k(self, u, s, v, va, vb) -> SvdUpdateResult:
        """k sequential rank-1 updates through one lax.scan.

        ``va``: (k, m), ``vb``: (k, n) — rank-1 pairs applied in row order.
        Trace/compile cost is k-independent (one step body); diagnostics
        (``d_left``/``d_right``) are the final step's.
        """
        key = _geometry("rank_k", u, s, v, va, vb)
        ent = self._entry(key, self._build_rank_k)
        return self._call(ent, u, s, v, va, vb)

    def update_rank_k_batch(self, u, s, v, va, vb, *, mesh=None,
                            batch_axis: str = "data") -> SvdUpdateResult:
        """B stacked k-step scans: ``u`` (B, m, m), ``va`` (B, k, m), ...."""
        if u.ndim != 3:
            raise ValueError(f"update_rank_k_batch expects stacked (B, m, m) u; got {u.shape}")
        if mesh is None:
            key = _geometry("rank_k_batch", u, s, v, va, vb)
            ent = self._entry(key, self._build_rank_k_batch)
            return self._call(ent, *self._constrain(u, s, v, va, vb))
        size = self._mesh_axis_size(mesh, batch_axis)
        (u, s, v, va, vb), b_orig = self._pad_batch((u, s, v, va, vb), size)
        key = ("shard", mesh, batch_axis) + _geometry("rank_k_batch", u, s, v, va, vb)
        ent = self._entry(
            key,
            lambda: self._shard_batch(self._rank_k_fn(), mesh, batch_axis, 5),
        )
        out = self._call(ent, u, s, v, va, vb)
        return jax.tree.map(lambda x: x[:b_orig], out)

    def update_truncated_rank_k(self, tsvd: TruncatedSvd, va, vb) -> TruncatedSvd:
        """k sequential truncated updates through one lax.scan."""
        key = _geometry("trunc_rank_k", tsvd.u, tsvd.s, tsvd.v, va, vb)
        ent = self._entry(key, self._build_trunc_rank_k)
        return self._call(ent, TruncatedSvd(tsvd.u, tsvd.s, tsvd.v), va, vb)

    def update_truncated_rank_k_batch(self, tsvd: TruncatedSvd, va, vb, *,
                                      mesh=None, batch_axis: str = "data") -> TruncatedSvd:
        """B stacked k-step truncated scans (mesh-shardable like the rest)."""
        if tsvd.u.ndim != 3:
            raise ValueError(
                f"update_truncated_rank_k_batch expects stacked (B, m, r) u; got {tsvd.u.shape}"
            )
        if mesh is None:
            key = _geometry("trunc_rank_k_batch", tsvd.u, tsvd.s, tsvd.v, va, vb)
            ent = self._entry(key, self._build_trunc_rank_k_batch)
            u_, s_, v_, va_, vb_ = self._constrain(tsvd.u, tsvd.s, tsvd.v, va, vb)
            return self._call(ent, TruncatedSvd(u_, s_, v_), va_, vb_)
        size = self._mesh_axis_size(mesh, batch_axis)
        (u_, s_, v_, va_, vb_), b_orig = self._pad_batch(
            (tsvd.u, tsvd.s, tsvd.v, va, vb), size
        )
        key = ("shard", mesh, batch_axis) + _geometry(
            "trunc_rank_k_batch", u_, s_, v_, va_, vb_
        )
        ent = self._entry(
            key,
            lambda: self._shard_batch(self._trunc_rank_k_fn(), mesh, batch_axis, 3),
        )
        out = self._call(ent, TruncatedSvd(u_, s_, v_), va_, vb_)
        return jax.tree.map(lambda x: x[:b_orig], out)

    # -- warmup -------------------------------------------------------------

    def warmup(
        self,
        *,
        batch: int | None,
        m: int,
        n: int,
        rank: int | None = None,
        k: int | None = None,
        dtype=jnp.float32,
    ) -> EngineCacheInfo:
        """AOT-compile the executable for one geometry before traffic.

        ``rank=None`` warms the full-update path, otherwise the truncated
        path; ``batch=None`` warms the single-instance variant; ``k`` warms
        the rank-k scan variant (k sequential pairs per call). The cache key
        includes ``dtype`` — warm with the dtype real traffic uses (default
        float32 matches ``compression_init``/``spectral_init`` trackers;
        pass ``jnp.float64`` for x64 workloads).
        """
        self._warm_entry(batch=batch, m=m, n=n, rank=rank, k=k, dtype=dtype)
        return self.cache_info()

    def aot_compiled(
        self,
        *,
        batch: int | None,
        m: int,
        n: int,
        rank: int | None = None,
        k: int | None = None,
        dtype=jnp.float32,
    ):
        """The AOT-compiled executable for one geometry (warming it first).

        Exposes the compiled object itself — ``cost_analysis()`` /
        ``memory_analysis()`` feed the launch-layer roofline cells
        (``repro.launch.perf_iter``) without re-lowering outside the shared
        plan cache.
        """
        return self._warm_entry(batch=batch, m=m, n=n, rank=rank, k=k,
                                dtype=dtype).compiled

    def _warm_entry(
        self,
        *,
        batch: int | None,
        m: int,
        n: int,
        rank: int | None = None,
        k: int | None = None,
        dtype=jnp.float32,
    ) -> _CacheEntry:
        dt = jnp.dtype(dtype)

        def sds(*shape):
            return jax.ShapeDtypeStruct(shape, dt)

        def vshape(*shape):
            # perturbation-pair shapes: (m,)/(n,) or (k, m)/(k, n) under scan
            return shape if k is None else (k,) + shape

        if rank is None:
            pair = (sds(*vshape(m)), sds(*vshape(n)))
            if batch is None:
                args = (sds(m, m), sds(m), sds(n, n), *pair)
                kind = "single" if k is None else "rank_k"
                build = self._build_single if k is None else self._build_rank_k
            else:
                pair = tuple(jax.ShapeDtypeStruct((batch,) + p.shape, dt) for p in pair)
                args = (sds(batch, m, m), sds(batch, m), sds(batch, n, n), *pair)
                kind = "batch" if k is None else "rank_k_batch"
                build = self._build_batch if k is None else self._build_rank_k_batch
            key = _geometry(kind, *args)
            ent = self._entry(key, build)
            if ent.compiled is None:
                with _obs.span("aot_warmup", kind=kind, batch=batch or 0,
                               m=m, n=n, k=k or 0):
                    ent.compiled = ent.fn.lower(*args).compile()
        else:
            pair = (sds(*vshape(m)), sds(*vshape(n)))
            if batch is None:
                leaves = (sds(m, rank), sds(rank), sds(n, rank))
                kind = "trunc" if k is None else "trunc_rank_k"
                build = self._build_truncated if k is None else self._build_trunc_rank_k
            else:
                pair = tuple(jax.ShapeDtypeStruct((batch,) + p.shape, dt) for p in pair)
                leaves = (sds(batch, m, rank), sds(batch, rank), sds(batch, n, rank))
                kind = "trunc_batch" if k is None else "trunc_rank_k_batch"
                build = (self._build_truncated_batch if k is None
                         else self._build_trunc_rank_k_batch)
            key = _geometry(kind, *leaves, *pair)
            ent = self._entry(key, build)
            if ent.compiled is None:
                with _obs.span("aot_warmup", kind=kind, batch=batch or 0,
                               m=m, n=n, rank=rank, k=k or 0):
                    ent.compiled = ent.fn.lower(TruncatedSvd(*leaves), *pair).compile()
        return ent


# ---------------------------------------------------------------------------
# Module-level default engines — one per configuration, shared plan caches.
# ---------------------------------------------------------------------------

_default_engines: dict[tuple, SvdEngine] = {}
_default_lock = threading.Lock()


def default_engine(
    method: str = "direct",
    *,
    fmm_p: int = 20,
    sign_fix: bool = True,
    deflate_rtol: float | None = None,
    precision: str | None = None,
    storage_dtype=None,
) -> SvdEngine:
    """Process-wide shared engine for a configuration (shared plan cache).

    The key covers every numerics knob an ``repro.api.UpdatePolicy`` carries,
    so policy-equal callers (old facades, the api layer, consumers) land on
    the SAME engine instance and plan cache — policy folds into the cache key.
    """
    sd = None if storage_dtype is None else jnp.dtype(storage_dtype)
    key = (method, fmm_p, sign_fix, deflate_rtol, precision, sd)
    with _default_lock:
        eng = _default_engines.get(key)
        if eng is None:
            eng = SvdEngine(method=method, fmm_p=fmm_p, sign_fix=sign_fix,
                            deflate_rtol=deflate_rtol, precision=precision,
                            storage_dtype=sd)
            _default_engines[key] = eng
        return eng
