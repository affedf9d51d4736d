"""Streaming rank-1 SVD-update service: async micro-batched engine flushes,
checkpointable to disk (DESIGN.md §9).

The serving story for the paper's machinery: many concurrent streams (one
per user/session/adapter) each own a truncated ``repro.api.SvdState`` that
evolves by rank-1 updates — personalization vectors folding into low-rank
adapters, per-tenant gradient sketches, online covariance trackers. Issuing
those updates one at a time wastes the hardware; this service queues them
and flushes *one batched engine call* per round:

    svc = SvdService(max_batch=64, policy=UpdatePolicy(method="auto"))
    svc.register("user-1", api.SvdState.from_dense(m1, rank=8))
    svc.enqueue("user-1", a, b)        # cheap: just queues
    svc.enqueue("user-2", a2, b2)
    svc.enqueue_op("user-1", RankK(u_blk, v_blk))   # structured: rank-k bucket
    svc.enqueue_op("user-2", AppendRows(new_rows))  # growing matrix event
    svc.flush()                        # one batched truncated update
    svc.save("/ckpts/svd", step=1)     # versioned snapshot; survives restart

* Structured events (``repro.updates`` ops): ``enqueue_op`` lowers
  geometry-preserving ops (``RankK``, ``DenseDelta``, ``Compose`` of them)
  into the pair FIFO — a rank-k op becomes a k-deep flush bucket whose
  steps batch with other streams' heads (``DenseDelta`` sketches through
  the planner's shared ``op_low_rank_factors`` range-finder — serve and
  planner can never drift) — while geometry-changing appends and ``Decay``
  folds stay whole and apply through the planner at flush.  ``Sparse``
  events stay whole too (snapshots carry their COO leaves bitwise) but
  expand into their rank-1 pairs at the head of a flush round — the
  deterministic sketch makes pre/post-snapshot expansion bitwise identical
  — so sparse events batch into rounds like every other pair.  Downdates
  (``RemoveRows``/``RemoveCols``/``Window``) stay whole like appends —
  geometry-shrinking, validated against the stream's effective shape at
  enqueue, planned onto the rank-1 engine at flush (GDPR-style "forget
  these rows now" across per-user streams).
  Snapshots (v3+) carry ops bitwise (``pending_ops``/``pending_order``).
* Cold-start control: every flush records its ``(kind, geometry)`` in the
  warmed set; snapshots persist it and ``restore`` eagerly ``api.warmup``s
  each entry, so the first post-failover flush never compiles under
  traffic.

* Per-stream ordering: a stream's queued pairs are applied in FIFO order;
  each flush round takes at most one pending pair per stream (they are
  sequential updates to the same state, so they cannot share a batch).
* Micro-batching: ``enqueue`` auto-flushes once ``max_batch`` streams have
  a pending pair. Batches are padded up to bucket sizes (powers of two) so
  the engine's plan cache sees a handful of geometries, not every B.
* Async double-buffered flushing: a flush round *dispatches* its batched
  engine call and returns — stream states become JAX async futures and the
  host keeps enqueueing while the device computes. Dispatched rounds are
  tracked in an in-flight buffer; once ``max_in_flight`` rounds are
  outstanding, the next round first blocks on the oldest (backpressure),
  so the host can never run unboundedly ahead of the device.
  ``jax.block_until_ready`` is otherwise only issued at the explicit
  barriers: ``drain()`` and ``snapshot()``.
* Checkpointing: ``snapshot()`` captures the whole service — every stream's
  ``SvdState``, every pending FIFO, the policy and the batching config — as
  a versioned ``ServiceSnapshot`` pytree; ``save``/``restore`` persist it
  through ``train.checkpoint`` (atomic, checksummed, self-describing via
  the aux spec). Restore is **exact**: a restored service produces bitwise
  the same factors as one that never stopped (DESIGN.md §9 contract,
  ``tests/test_serve_checkpoint.py``).
* Policy: an ``UpdatePolicy`` names the numerics (method/fmm_p/...) and the
  placement — ``policy.mesh`` spreads every flush's batch axis over the
  mesh via the engine's shard_map dispatch.  A legacy ``engine=`` override
  wins over the policy-derived engine.  The mesh is *runtime placement*,
  not state: snapshots record that a mesh was set but never serialize it —
  pass ``mesh=`` (or a full ``policy=``) to ``restore`` on the new topology.
* Multi-worker: per-worker shard streams combine into one global truncated
  SVD via ``merge_streams`` (the ``repro.dist.merge`` log-depth tree).

The LM engine (``serve.engine``) serves tokens; this serves spectra.
"""

from __future__ import annotations

import contextvars
import dataclasses
import threading
import time
import warnings
from collections import OrderedDict, deque
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as _obs
from repro.api import SvdState, UpdatePolicy, as_state
from repro.api.update import engine_from_key, warmup as _api_warmup
from repro.core.engine import (
    SvdEngine,
    group_indices,
    truncated_geometry,
)
from repro.core.svd_update import TruncatedSvd
from repro.dist.merge import merge_tree
from repro.kernels.fused_update import null_columns
from repro.train import checkpoint as _checkpoint
from repro.updates import ops as _ops
from repro.updates import planner as _planner
from repro.updates import sketch as _sketch

__all__ = [
    "SNAPSHOT_VERSION",
    "ServiceSnapshot",
    "SvdService",
    "SvdServiceStats",
]

# v4 and v6 are NOT service formats: the fleet tier's FleetSnapshot (which
# embeds per-shard ServiceSnapshots) took them on the shared version line —
# see ``repro.fleet.fleet.FLEET_SNAPSHOT_VERSION`` and DESIGN.md §14's table.
# v7 (current) added the ``obs_metrics`` registry capture (DESIGN.md §15).
SNAPSHOT_VERSION = 7
_SNAPSHOT_FORMAT = "repro.serve.ServiceSnapshot"

# UpdatePolicy fields a snapshot records verbatim. ``mesh`` is deliberately
# absent: it names live devices of THIS process; the restoring process
# supplies its own (see module doc).
_POLICY_SPEC_FIELDS = (
    "method",
    "fmm_p",
    "sign_fix",
    "deflate_rtol",
    "precision",
    "storage_dtype",
    "sketch_oversample",
    "sketch_power_iters",
    "batch_axis",
    "truncate_to",
    "health_every",
)

# policy fields added after SNAPSHOT_VERSION was minted: old snapshots lack
# them, so restore falls back to each field's UpdatePolicy default
_POLICY_SPEC_DEFAULTS = {
    "storage_dtype": None,
    "sketch_oversample": 8,
    "sketch_power_iters": 1,
    "health_every": None,
}


def _obs_rows(rows) -> tuple:
    """Re-hash registry snapshot rows after a JSON round trip (the aux spec
    turns tuples into lists; pytree metadata must be hashable)."""
    return tuple(
        (name, tuple((str(k), str(v)) for k, v in labels), kind, state)
        for name, labels, kind, state in rows
    )


def _policy_spec(policy: UpdatePolicy) -> dict:
    spec = {f: getattr(policy, f) for f in _POLICY_SPEC_FIELDS}
    if spec["storage_dtype"] is not None:
        spec["storage_dtype"] = np.dtype(spec["storage_dtype"]).name
    spec["had_mesh"] = policy.mesh is not None
    return spec


def _policy_from_spec(spec: dict, mesh=None) -> UpdatePolicy:
    kw = {
        f: spec.get(f, _POLICY_SPEC_DEFAULTS.get(f))
        for f in _POLICY_SPEC_FIELDS
    }
    return UpdatePolicy(mesh=mesh, **kw)


@dataclass
class SvdServiceStats:
    enqueued: int = 0
    applied: int = 0
    flushes: int = 0
    rounds: int = 0          # batched engine calls (one per geometry group)
    max_batch: int = 0       # largest batch (incl. bucket padding) dispatched
    backpressure_waits: int = 0   # rounds that had to wait for an older one
    in_flight_peak: int = 0       # most rounds ever outstanding at once
    ops_applied: int = 0          # structured (non-pair) events applied
    scan_rounds: int = 0          # depth-batched (rank-k scan) engine calls
    max_depth: int = 0            # deepest scan column ever dispatched


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["states", "pending_a", "pending_b", "pending_ops"],
    meta_fields=[
        "version",
        "stream_ids",
        "policy_spec",
        "max_batch",
        "pad_to_bucket",
        "max_in_flight",
        "stats",
        "pending_order",
        "warmed",
        "obs_metrics",
    ],
)
@dataclasses.dataclass(frozen=True)
class ServiceSnapshot:
    """Versioned, self-describing capture of a whole ``SvdService``.

    A registered pytree: the array leaves are every stream's (u, s, v)
    factors plus its pending FIFO — rank-1 pairs stacked as two ``(k_i, m)``
    / ``(k_i, n)`` arrays, structured events (``repro.updates`` ops: decay,
    appends) as op pytrees in ``pending_ops``, with ``pending_order`` (one
    ``"p"``/``"o"`` marker string per stream) recording how pairs and ops
    interleave in FIFO order.  Everything non-array — stream ids, the policy
    spec, bucket/backpressure config, stats counters, the warmed
    ``(kind, geometry)`` set — is pytree metadata, mirrored into the JSON
    ``aux`` spec so a fresh process can rebuild the tree structure before it
    has loaded a single array (``skeleton``; op structure rebuilds through
    ``repro.updates.ops.skeleton_from_spec``).

    Versioning: ``version`` is written into both the pytree and the aux
    spec; ``load`` refuses snapshots newer than this build understands and
    upgrades older ones in place.  v1 -> v2 added ``pending_ops`` /
    ``pending_order`` / ``warmed``; v1 snapshots (all-pair FIFOs, nothing
    warmed) load as v2 with the empty defaults — their leaf list is
    unchanged, so restore stays bitwise.  v2 -> v3 added ``Sparse`` op
    events (their COO leaves ride ``pending_ops`` bitwise), the sketch
    policy knobs in ``policy_spec``, and ``sketch_*`` warmed kinds — no
    structural change, so v1/v2 snapshots load as v3 unchanged (the sketch
    knobs fall back to their ``UpdatePolicy`` defaults); the bump exists so
    pre-sparse builds refuse v3 snapshots cleanly instead of failing inside
    ``skeleton_from_spec``.  v3 -> v5 added downdate op events
    (``RemoveRows``/``RemoveCols``/``Window``) riding ``pending_ops`` —
    Remove ops are pure metadata (zero leaves; indices live in the aux
    spec), ``Window`` carries its ``lam`` leaf — again no structural change,
    so v1–v3 snapshots load unchanged; pre-downdate builds refuse v5
    cleanly.  v4 was never a service format (the fleet tier's
    ``FleetSnapshot`` took it on the shared version line), so the service
    skips from 3 to 5.  v5 -> v7 added ``obs_metrics`` — a
    ``repro.obs.MetricsRegistry.snapshot()`` capture (hashable metadata,
    zero array leaves, empty when obs is disabled) so telemetry counters
    survive failover exactly like the stats counters do; v1–v5 snapshots
    load with the empty default, and v6 was the fleet tier's again.
    """

    states: tuple          # tuple[SvdState, ...] — diagnostics-free, per stream
    pending_a: tuple       # tuple[(k_i, m_i) array, ...] queued a-vectors, FIFO
    pending_b: tuple       # tuple[(k_i, n_i) array, ...] queued b-vectors, FIFO
    pending_ops: tuple = ()   # tuple[tuple[UpdateOp, ...], ...] per stream, FIFO
    version: int = SNAPSHOT_VERSION
    stream_ids: tuple = ()
    policy_spec: tuple = ()   # tuple of (field, value) pairs (hashable meta)
    max_batch: int = 64
    pad_to_bucket: bool = True
    max_in_flight: int = 2
    stats: tuple = ()         # SvdServiceStats counters as (name, value) pairs
    pending_order: tuple = () # per stream: "p"/"o" markers in FIFO order
    warmed: tuple = ()        # (kind, batch, m, n, rank, dtype_str) tuples
    obs_metrics: tuple = ()   # MetricsRegistry.snapshot() rows (v7+; hashable)

    def aux(self) -> dict:
        """The JSON spec persisted next to the arrays (checkpoint ``aux=``)."""
        return {
            "format": _SNAPSHOT_FORMAT,
            "version": self.version,
            "stream_ids": list(self.stream_ids),
            "policy": dict(self.policy_spec),
            "max_batch": self.max_batch,
            "pad_to_bucket": self.pad_to_bucket,
            "max_in_flight": self.max_in_flight,
            "stats": dict(self.stats),
            "pending_order": list(self.pending_order),
            "pending_ops": [
                [_ops.spec_to_json(op.spec()) for op in stream_ops]
                for stream_ops in self.pending_ops
            ],
            "warmed": [list(w) for w in self.warmed],
            "obs_metrics": [list(r) for r in self.obs_metrics],
        }

    @classmethod
    def skeleton(cls, aux: dict) -> "ServiceSnapshot":
        """A structure-only snapshot (placeholder leaves) built from an aux
        spec — its treedef is what ``load`` unflattens restored leaves into.

        v1 aux specs (no ``pending_ops``/``pending_order``/``warmed``) get
        the empty defaults: the tree gains no leaves, so v1 leaf lists
        unflatten unchanged (the in-place upgrade path).
        """
        n = len(aux["stream_ids"])
        op_specs = aux.get("pending_ops", [[] for _ in range(n)])
        return cls(
            states=tuple(SvdState(u=0.0, s=0.0, v=0.0) for _ in range(n)),
            pending_a=tuple(0.0 for _ in range(n)),
            pending_b=tuple(0.0 for _ in range(n)),
            pending_ops=tuple(
                tuple(_ops.skeleton_from_spec(_ops.spec_from_json(sp)) for sp in sps)
                for sps in op_specs
            ),
            version=SNAPSHOT_VERSION,
            stream_ids=tuple(aux["stream_ids"]),
            policy_spec=tuple((k, v) for k, v in aux["policy"].items()),
            max_batch=aux["max_batch"],
            pad_to_bucket=aux["pad_to_bucket"],
            max_in_flight=aux["max_in_flight"],
            stats=tuple((k, v) for k, v in aux["stats"].items()),
            pending_order=tuple(aux.get("pending_order", ())),
            warmed=tuple(tuple(w) for w in aux.get("warmed", ())),
            obs_metrics=_obs_rows(aux.get("obs_metrics", ())),
        )

    def save(self, ckpt_dir, step: int, *, keep: int = 3):
        """Persist through ``train.checkpoint`` (atomic + checksummed)."""
        return _checkpoint.save(ckpt_dir, step, self, aux=self.aux())

    @classmethod
    def load(cls, ckpt_dir, step: int | None = None) -> tuple[int, "ServiceSnapshot"]:
        """Load ``(step, snapshot)`` from a checkpoint directory.

        Leaves come back exactly as saved (numpy, bitwise-identical — no
        dtype cast, no device transfer); they join device computation on
        the first flush after restore.
        """
        step, aux = _checkpoint.load_aux(ckpt_dir, step)
        if aux is None or aux.get("format") != _SNAPSHOT_FORMAT:
            raise ValueError(
                f"checkpoint at step {step} is not a ServiceSnapshot "
                f"(aux format: {None if aux is None else aux.get('format')!r})"
            )
        if aux["version"] > SNAPSHOT_VERSION:
            raise ValueError(
                f"snapshot version {aux['version']} is newer than this build "
                f"understands (<= {SNAPSHOT_VERSION})"
            )
        _, leaves = _checkpoint.restore(ckpt_dir, None, step)
        treedef = jax.tree.structure(cls.skeleton(aux))
        return step, jax.tree.unflatten(treedef, leaves)


def _bucket(b: int, cap: int) -> int:
    """Smallest power of two >= b (clamped to cap) — bounds plan-cache size."""
    p = 1
    while p < b:
        p <<= 1
    return min(p, max(cap, 1))


def _depth_bucket(run: int, cap: int) -> int:
    """Largest power of two <= min(run, cap) — the scan depth a stream's
    consecutive-pair backlog dispatches as.  Flooring (not ceiling) keeps
    depth groups exact: a stream never pads its OWN column with no-op pairs
    (scan outputs are kept, so k-padding would have to be bitwise-identity;
    B-padding outputs are discarded, so zero pairs are safe there)."""
    run = min(run, max(cap, 1))
    p = 1
    while p * 2 <= run:
        p <<= 1
    return p


# -- round marshalling ------------------------------------------------------
#
# A pair group moves between per-stream arrays and the engine's stacked batch
# through a few compiled calls each way: done eagerly, a 256 x 8 group is
# thousands of single-op dispatches on the host.  The executables take at
# most _MARSHAL_CHUNK streams each, because the TPU compiler's time grows
# faster than the buffers an executable moves: a whole 256 x 8 group (4,865
# buffers) compiles for v5e in over a minute, a 32-stream chunk (608) in ~3
# s.  They are keyed, like the engine's, by batch, depth and geometry, so
# the warmed set names every executable ``restore`` has to build.

_MARSHAL_CHUNK = 32

# labels of the service whose flush round is running on this thread: a
# marshalling executable traces inside that call
_marshal_labels: contextvars.ContextVar = contextvars.ContextVar(
    "marshal_labels", default={})


def _count_marshal_trace(direction: str) -> None:
    """Count one trace of a marshalling executable (a shape new to this
    process) in the registry counter ``round_marshal_traces``."""
    if _obs.enabled():
        _obs.registry().counter("round_marshal_traces", direction=direction,
                                **_marshal_labels.get()).inc()


@partial(jax.jit, static_argnames="k")
def _stack_chunk(states, a_vecs, b_vecs, n_valid, *, k: int):
    """One chunk of ``_assemble_round``; rows from ``n_valid`` on are
    padding, whose pairs are zeroed."""
    _count_marshal_trace("assemble")
    batch = len(states)
    # one concatenate and a free reshape per stack: jnp.stack's expand_dims
    # per row traces and lowers about twice as slowly
    t_stack = TruncatedSvd(*(jnp.concatenate(xs).reshape(batch, *xs[0].shape)
                             for xs in zip(*states)))
    dt = t_stack.u.dtype
    live = (jnp.arange(batch) < n_valid)[:, None, None]

    def pairs(vecs):
        x = jnp.concatenate(vecs).astype(dt).reshape(batch, k, -1)
        x = jnp.where(live, x, jnp.zeros((), dt))
        return x[:, 0] if k == 1 else x

    return t_stack, pairs(a_vecs), pairs(b_vecs)


@jax.jit
def _join_chunks(parts: list):
    _count_marshal_trace("assemble")
    return jax.tree.map(lambda *xs: jnp.concatenate(xs), *parts)


@jax.jit
def _cut_chunks(out: TruncatedSvd) -> list:
    _count_marshal_trace("writeback")
    return [jax.tree.map(lambda x: x[lo:lo + _MARSHAL_CHUNK], out)
            for lo in range(0, out.u.shape[0], _MARSHAL_CHUNK)]


@jax.jit
def _unstack_chunk(out: TruncatedSvd) -> list:
    _count_marshal_trace("writeback")
    return list(zip(*(jnp.unstack(x) for x in out)))


def _assemble_round(states, a_vecs, b_vecs, n_valid: int, *, k: int):
    """Stack a group's ``(u, s, v)`` states and its pairs (``k`` a stream,
    stream-major, each stream's in FIFO order) into the engine's batch:
    a ``TruncatedSvd`` of stacked factors and ``(B, m)`` / ``(B, n)`` pairs
    at ``k == 1``, ``(B, k, m)`` / ``(B, k, n)`` otherwise, cast to the
    states' dtype.  Rows from ``n_valid`` on are batch padding: the caller
    repeats its last state and pairs there, and their pairs are zeroed
    (no-op updates whose outputs are discarded; scan columns are never
    padded, see ``_depth_bucket``)."""
    c = _MARSHAL_CHUNK
    parts = [_stack_chunk(states[lo:lo + c], a_vecs[lo * k:(lo + c) * k],
                          b_vecs[lo * k:(lo + c) * k], n_valid - lo, k=k)
             for lo in range(0, len(states), c)]
    return parts[0] if len(parts) == 1 else _join_chunks(parts)


def _split_round(out: TruncatedSvd) -> list:
    """The engine's stacked output as one ``(u, s, v)`` per batch row
    (padding rows too, so the executables stay keyed by the padded batch)."""
    chunks = [out] if out.u.shape[0] <= _MARSHAL_CHUNK else _cut_chunks(out)
    return [row for chunk in chunks for row in _unstack_chunk(chunk)]


# bucket bounds of the ``null_columns`` histogram: a count of columns
_COLUMN_BUCKETS = (0.0, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0)


@jax.jit
def _null_counts(s):
    """Null columns (``kernels.fused_update.null_columns``) of each of a
    round's stacked states, from its ``s`` output."""
    return jnp.sum(null_columns(s), axis=-1, dtype=jnp.int32)


def _warm_marshal(batch: int, m: int, n: int, r: int, k: int, dt) -> None:
    """Build the marshalling executables of a warmed round shape, by
    calling them on zeros as a round would call them (pairs in the states'
    dtype, on the default device).  The zeros are host arrays: eager
    ``jnp.zeros`` would compile executables of their own."""
    z = lambda *shape: np.zeros(shape, dt)  # noqa: E731
    state, a, b = (z(m, r), z(r), z(n, r)), z(m), z(n)
    t_stack, _, _ = _assemble_round([state] * batch, [a] * (batch * k),
                                    [b] * (batch * k), batch, k=k)
    _split_round(t_stack)
    _null_counts(z(batch, r))


def _is_ready(x) -> bool:
    fn = getattr(x, "is_ready", None)
    return True if fn is None else fn()


class SvdService:
    """Async micro-batching front end over the batched truncated-update
    engine, checkpointable via ``snapshot``/``save``/``restore``."""

    def __init__(
        self,
        *,
        engine: SvdEngine | None = None,
        method: str = "direct",
        max_batch: int = 64,
        pad_to_bucket: bool = True,
        max_in_flight: int = 2,
        policy: UpdatePolicy | None = None,
    ):
        if max_in_flight < 0:
            raise ValueError(f"max_in_flight must be >= 0; got {max_in_flight}")
        self.policy = policy if policy is not None else UpdatePolicy(method=method)
        self.engine = engine            # explicit override; None -> policy-derived
        self.max_batch = max_batch
        self.pad_to_bucket = pad_to_bucket
        # 0 = synchronous (every round blocks before returning — the bench
        # baseline); 1 = single buffer; 2 = double buffering (default): the
        # device computes round k while the host assembles round k+1.
        self.max_in_flight = max_in_flight
        self.stats = SvdServiceStats()
        self._streams: OrderedDict[str, SvdState] = OrderedDict()
        # FIFO of events per stream, each carrying a visibility token:
        # ("pair", a, b, token) | ("op", UpdateOp, token)
        self._pending: dict[str, deque] = {}
        self._eff_shape: dict[str, tuple] = {}   # post-queue (m, n) per stream
        # per dispatched round: (device outputs, tokens it carried, round
        # number, null-column counts (made only while obs is on) and how many
        # of their rows are streams)
        self._in_flight: deque[tuple[list, list, int, list]] = deque()
        self._warmed: set[tuple] = set()         # (kind, batch, m, n, r, dtype)
        self._next_token = 0                     # visibility tokens (runtime-only)
        self._next_round = 0                     # round numbers (runtime-only)
        # token -> perf_counter_ns at enqueue, filled only while obs is on;
        # a stamp leaves with its event (sealed, evicted, settled, replaced)
        self._enqueued_ns: dict[int, int] = {}
        self._visible: list[int] = []            # retired tokens, FIFO, undrained
        self._lock = threading.RLock()
        # observability (repro.obs, DESIGN.md §15): the fleet tier grafts
        # per-shard labels on; the health monitor follows policy.health_every
        self._obs_labels: dict = {}
        self._health: "_obs.HealthMonitor | None" = None
        self._stat_gauges: tuple | None = None   # cached (field, gauge) handles

    # -- visibility tokens ---------------------------------------------------
    #
    # Every enqueued event gets a monotonically increasing token; a token
    # becomes *visible* when the flush round that applied it has retired
    # (its device outputs are concrete).  Enqueue-to-visible is the latency
    # the fleet benchmark reports; the continuous-batching frontend polls
    # ``take_visible`` after every pump.  Tokens are runtime state — they are
    # NOT snapshotted (a restored service issues fresh ones).

    def _issue_token(self) -> int:
        t = self._next_token
        self._next_token += 1
        return t

    def take_visible(self) -> list[int]:
        """Drain and return tokens whose updates are now visible (their
        round retired — or was applied synchronously).  Reaps ready rounds
        first, so polling callers see completions without blocking."""
        with self._lock:
            self._reap_ready()
            out, self._visible = self._visible, []
            return out

    def _engine_for(self, rank: int, m: int | None = None, n: int | None = None,
                    dtype=None) -> SvdEngine:
        # given the full truncated geometry, resolved as api.engine_for and
        # api.warmup resolve it: method="auto" picks the fused kernel there
        if self.engine is not None:
            return self.engine
        return engine_from_key(self.policy, rank + 1, m=m, n=n, rank=rank,
                               dtype=dtype)

    def _record_warm(self, kind: str, batch, m: int, n: int, r: int, dt) -> bool:
        """Track the (kind, geometry) set flushes have compiled — snapshotted
        so ``restore`` can ``api.warmup`` them eagerly before traffic.
        Returns whether the entry is new."""
        key = (kind, batch, m, n, r, jnp.dtype(dt).name)
        fresh = key not in self._warmed
        self._warmed.add(key)
        return fresh

    # -- stream lifecycle ---------------------------------------------------

    def register(self, stream_id: str, state) -> None:
        """Create (or replace) a stream with its current truncated SVD
        (any container — coerced to a diagnostics-free ``SvdState``, so
        every stream snapshots to exactly three array leaves).

        Replacing drops any pending pairs — they were queued against the old
        state (and may not even match the new geometry).
        """
        with self._lock:
            st = as_state(state)
            self._streams[stream_id] = SvdState(u=st.u, s=st.s, v=st.v)
            if self._enqueued_ns:
                for ev in self._pending.get(stream_id, ()):
                    self._enqueued_ns.pop(ev[-1], None)
            self._pending[stream_id] = deque()
            self._eff_shape[stream_id] = (st.m, st.n)

    def evict(self, stream_id: str) -> SvdState:
        """Drop a stream and return its state with its OWN queue applied.

        Other streams' pending events are left queued — eviction of one user
        must not advance anyone else's state.
        """
        with self._lock:
            state = self._streams[stream_id]
            queue = self._pending.get(stream_id, deque())
            while queue:
                state = self._apply_event(state, queue[0])
                self._token_visible(queue.popleft())
            del self._streams[stream_id]
            self._pending.pop(stream_id, None)
            self._eff_shape.pop(stream_id, None)
            return state

    def _token_visible(self, ev: tuple) -> None:
        """Mark a consumed event's token visible (``None`` = an expanded
        Sparse pair whose op token rides the LAST expanded pair)."""
        if ev[-1] is not None:
            self._visible.append(ev[-1])
            self._enqueued_ns.pop(ev[-1], None)

    def _apply_one(self, state: SvdState, a, b) -> SvdState:
        eng = self._engine_for(state.rank, state.m, state.n, state.s.dtype)
        self._record_warm("trunc", None, state.m, state.n, state.rank, state.dtype)
        t = eng.update_truncated(TruncatedSvd(state.u, state.s, state.v), a, b)
        return SvdState(u=t.u, s=t.s, v=t.v)

    def _apply_event(self, state: SvdState, ev: tuple) -> SvdState:
        """Apply one FIFO event to a single stream's state.

        Counts ``stats.applied``/``stats.ops_applied`` on success; callers
        pop the event from its queue AFTER this returns (failure-atomic:
        a raising engine call leaves the event queued for retry).
        """
        if ev[0] == "pair":
            out = self._apply_one(state, ev[1], ev[2])
            self.stats.applied += 1
            return out
        op = ev[1]
        self._record_op_warm(state, op)
        out = _planner.apply(state, op, self.policy)
        self.stats.applied += 1
        self.stats.ops_applied += 1
        return SvdState(u=out.u, s=out.s, v=out.v)

    def _record_op_warm(self, state: SvdState, op) -> None:
        """Record every single-update geometry an op's schedule dispatches
        (appends shift it mid-schedule) plus every sketch site the lowering
        runs through, so restore warms those too."""
        m, n = state.m, state.n
        for sm, sn, sk, snnz in _planner._sketch_sites(op.spec(), m, n)[0]:
            kind = "sketch_dense" if snnz is None else "sketch_sparse"
            self._record_warm(kind, snnz, sm, sn, sk, state.dtype)
        for step in _planner.lower(op, state, self.policy):
            if step[0] == "pad_rows":
                m += step[1]
            elif step[0] == "pad_cols":
                n += step[1]
            elif step[0] == "drop_rows":
                m -= len(step[1])
            elif step[0] == "drop_cols":
                n -= len(step[1])
            elif step[0] in ("rank1", "rank1_scan"):
                # scan steps dispatch the same truncated geometry (the k-loop
                # is inside the executable), so one warm record covers both
                self._record_warm("trunc", None, m, n, state.rank, state.dtype)

    def _effective_shape(self, stream_id: str) -> tuple[int, int]:
        """Stream geometry AFTER every queued event (appends grow it) — the
        geometry new enqueues must match.  Maintained incrementally: state
        changes and queue drains cancel out, so only ``register`` and
        ``enqueue_op`` ever move it (enqueue stays O(1) at any queue depth).
        """
        return self._eff_shape[stream_id]

    def state(self, stream_id: str) -> SvdState:
        """Current state — pending (unflushed) pairs are NOT yet applied.

        The returned factors may still be in-flight async futures; reading
        their values blocks transparently (JAX async dispatch)."""
        with self._lock:
            return self._streams[stream_id]

    def settle(self, stream_ids) -> list[SvdState]:
        """Apply each named stream's OWN queued events and return the settled
        states, in ``stream_ids`` order (other streams' queues untouched).

        This is the query-time primitive: ``merge_streams`` settles before
        merging, and the fleet tier (``repro.fleet``) settles each shard's
        members before the cross-shard merge — both see states as of *every*
        enqueued event, wherever the stream lives.  Runs under the service
        lock; the per-event applies dispatch async and the returned states
        may be futures (read = transparent block, like ``state()``).
        """
        with self._lock:
            states = []
            for sid in stream_ids:
                state = self._streams[sid]
                queue = self._pending[sid]
                while queue:
                    state = self._apply_event(state, queue[0])
                    self._token_visible(queue.popleft())
                self._streams[sid] = state
                states.append(state)
            return states

    def merge_streams(
        self,
        stream_ids,
        *,
        target: str | None = None,
        rank: int | None = None,
    ) -> SvdState:
        """Hierarchically merge several streams into one truncated SVD.

        The multi-worker story: each worker feeds its own stream (a shard
        tracker over its row block of a logically-shared matrix — per-tenant
        gradient sketches, federated covariance shards) and the service
        periodically combines them with the log-depth rank-1-update merge
        (``repro.dist.merge.merge_tree``) — row blocks concatenate in
        ``stream_ids`` order.  Each stream's OWN pending pairs are applied
        first (the merge must see current states; other streams' queues are
        untouched).  With ``target`` the result is registered as a new
        stream; the source streams keep evolving independently.

        The snapshot (queue drain) happens under the service lock; the
        log-depth merge itself — including its first-call jit compile —
        runs OUTSIDE it, so concurrent ``enqueue``/``flush`` traffic on
        other streams is never stalled.  The merge reflects the states as
        of the snapshot.
        """
        states = self.settle(stream_ids)
        merged = merge_tree(states, rank=rank, engine=self.engine,
                            policy=self.policy)
        if target is not None:
            with self._lock:
                self.register(target, merged)
        return merged

    def pending(self, stream_id: str | None = None) -> int:
        with self._lock:
            if stream_id is not None:
                return len(self._pending[stream_id])
            return sum(len(q) for q in self._pending.values())

    def in_flight(self) -> int:
        """Dispatched-but-unretired flush rounds (after reaping ready ones)."""
        with self._lock:
            self._reap_ready()
            return len(self._in_flight)

    # -- the hot path -------------------------------------------------------

    def enqueue(self, stream_id: str, a: jax.Array, b: jax.Array) -> int:
        """Queue one rank-1 perturbation ``a b^T`` for a stream; returns the
        event's visibility token (see ``take_visible``).

        Auto-flushes when ``max_batch`` streams have a pending head event.
        The flush only *dispatches* device work (async); enqueue never waits
        for it unless the in-flight buffer is full (backpressure).
        """
        with self._lock:
            if stream_id not in self._streams:
                raise KeyError(f"unknown stream {stream_id!r}; register() first")
            # match the geometry the stream will have once queued appends
            # flush — reject HERE: at flush time a bad pair would poison a
            # whole geometry group (events are popped before the engine call)
            m, n = self._effective_shape(stream_id)
            if a.shape != (m,) or b.shape != (n,):
                raise ValueError(
                    f"pair shapes {a.shape}/{b.shape} do not match stream "
                    f"{stream_id!r} geometry ({m},)/({n},)"
                )
            token = self._issue_token()
            if _obs.enabled():
                self._enqueued_ns[token] = time.perf_counter_ns()
            self._pending[stream_id].append(("pair", a, b, token))
            self.stats.enqueued += 1
            self._maybe_autoflush()
            return token

    def enqueue_op(self, stream_id: str, op: "_ops.UpdateOp") -> None:
        """Queue one structured perturbation (a ``repro.updates`` op).

        Geometry-preserving ops lower into the pair FIFO at enqueue time —
        ``RankK`` becomes k pairs (a "rank-k flush bucket": k flush rounds,
        each batched with the other streams' heads), ``DenseDelta`` sketches
        into ``rank`` pairs, ``Compose`` decomposes child-by-child.
        Geometry-changing ops (appends and the ``RemoveRows`` /
        ``RemoveCols`` / ``Window`` downdates) and ``Decay`` stay whole as
        op events: they re-plan the stream's geometry at flush; decay folds
        into the singular values without an engine dispatch.  ``Sparse``
        deltas also stay whole — snapshots then carry their O(nnz) COO
        leaves bitwise instead of sketched pairs — and expand into their
        ``rank`` pairs only when they reach the head of a flush round.
        FIFO order with previously queued pairs is preserved either way.
        Returns the token of the op's LAST lowered event — visible once the
        whole op has applied.
        """
        with self._lock:
            if stream_id not in self._streams:
                raise KeyError(f"unknown stream {stream_id!r}; register() first")
            if not isinstance(op, _ops.UpdateOp):
                raise TypeError(f"enqueue_op takes a repro.updates op; got {type(op)}")
            m, n = self._effective_shape(stream_id)
            events, out_shape = self._lower_op_events(op, m, n, stream_id)
            events = [ev + (self._issue_token(),) for ev in events]
            if _obs.enabled():
                now = time.perf_counter_ns()
                self._enqueued_ns.update((ev[-1], now) for ev in events)
            self._pending[stream_id].extend(events)
            self._eff_shape[stream_id] = out_shape
            self.stats.enqueued += len(events)
            self._maybe_autoflush()
            return events[-1][-1]

    def _lower_op_events(self, op, m: int, n: int, sid: str) -> tuple[list, tuple]:
        """Lower an op into FIFO events at the (m, n) geometry; returns
        ``(events, geometry after the op)``."""
        if isinstance(op, _ops.Compose):
            events: list = []
            for child in op.ops:
                sub, (m, n) = self._lower_op_events(child, m, n, sid)
                events.extend(sub)
            return events, (m, n)
        if isinstance(op, _ops.RankK):
            u, v = jnp.asarray(op.u), jnp.asarray(op.v)
            if u.shape != (m, op.k) or v.shape != (n, op.k):
                raise ValueError(
                    f"RankK factors {u.shape}/{v.shape} do not match stream "
                    f"{sid!r} geometry ({m},{op.k})/({n},{op.k})"
                )
            return [("pair", u[:, i], v[:, i]) for i in range(op.k)], (m, n)
        if isinstance(op, _ops.DenseDelta):
            delta = jnp.asarray(op.delta)
            if delta.shape != (m, n):
                raise ValueError(
                    f"DenseDelta shape {delta.shape} does not match stream "
                    f"{sid!r} geometry ({m}, {n})"
                )
            # the planner's shared range-finder (updates.sketch) — the ONE
            # low-rank extraction path; serve can never drift from plan
            self._record_warm("sketch_dense", None, m, n, op.rank, delta.dtype)
            du, ds, dv = _planner.op_low_rank_factors(op, m, n, self.policy)
            return (
                [("pair", du[:, i] * ds[i], dv[:, i]) for i in range(op.rank)],
                (m, n),
            )
        if isinstance(op, _ops.Sparse):
            rows, cols = jnp.asarray(op.rows), jnp.asarray(op.cols)
            vals = jnp.asarray(op.vals)
            if not (rows.shape == cols.shape == vals.shape and vals.ndim == 1):
                raise ValueError(
                    f"Sparse coordinates must be matching 1-D (nnz,) arrays; "
                    f"got {rows.shape}/{cols.shape}/{vals.shape} for stream "
                    f"{sid!r}"
                )
            # queued WHOLE so snapshots carry the COO leaves bitwise (v3);
            # _flush_round expands the head into its rank pairs — the
            # deterministic sketch makes pre/post-restore expansion identical
            return [("op", op)], (m, n)
        if isinstance(op, (_ops.AppendRows, _ops.AppendCols)):
            width_ok = (
                (op.rows.shape[1] == n if op.rows is not None else op.v.shape[0] == n)
                if isinstance(op, _ops.AppendRows)
                else (op.cols.shape[0] == m if op.cols is not None else op.u.shape[0] == m)
            )
            if not width_ok:
                raise ValueError(
                    f"{type(op).__name__} block does not match stream {sid!r} "
                    f"geometry ({m}, {n})"
                )
            return [("op", op)], op.out_shape(m, n)
        if isinstance(op, (_ops.RemoveRows, _ops.RemoveCols, _ops.Window)):
            # downdates stay whole like appends (geometry-changing; zero or
            # one data leaf, so snapshots carry them bitwise for free) —
            # reject bad indices HERE, not at flush, where a poisoned event
            # would stay queued forever under the failure-atomicity contract
            if isinstance(op, _ops.RemoveRows) and op.idx[-1] >= m:
                raise ValueError(
                    f"RemoveRows{op.idx} out of range for stream {sid!r} "
                    f"geometry ({m}, {n})"
                )
            if isinstance(op, _ops.RemoveCols) and op.idx[-1] >= n:
                raise ValueError(
                    f"RemoveCols{op.idx} out of range for stream {sid!r} "
                    f"geometry ({m}, {n})"
                )
            out = op.out_shape(m, n)
            rank = self._streams[sid].rank
            if rank > min(out):
                raise ValueError(
                    f"{type(op).__name__} shrinks stream {sid!r} to {out}, "
                    f"below its rank {rank} — truncate first"
                )
            return [("op", op)], out
        return [("op", op)], op.out_shape(m, n)   # Decay and future scalars

    def _expand_sparse_head(self, sid: str) -> None:
        """Lower the ``Sparse`` op at the head of ``sid``'s queue into its
        ``rank`` pairs, in place — O((m+n)·rank + nnz) through the planner's
        shared range-finder, never densifying.  Factors are computed BEFORE
        the pop so a raising sketch leaves the event queued (the flush
        failure-atomicity contract)."""
        op = self._pending[sid][0][1]
        tok = self._pending[sid][0][-1]
        st = self._streams[sid]
        self._record_warm(
            "sketch_sparse", op.nnz, st.m, st.n, op.rank,
            jnp.asarray(op.vals).dtype,
        )
        u, s, v = _planner.op_low_rank_factors(op, st.m, st.n, self.policy)
        self._pending[sid].popleft()
        # the op's token rides the LAST expanded pair (visible = whole op done)
        self._pending[sid].extendleft(
            ("pair", u[:, i] * s[i], v[:, i],
             tok if i == op.rank - 1 else None)
            for i in range(op.rank - 1, -1, -1)
        )
        # one structured event became ``rank`` pair events; keep the
        # enqueued-vs-applied ledger balanced
        self.stats.enqueued += op.rank - 1
        self.stats.ops_applied += 1

    def _maybe_autoflush(self) -> None:
        ready = sum(1 for q in self._pending.values() if q)
        if ready >= self.max_batch:
            self._flush_round()

    def flush(self) -> int:
        """Dispatch ALL pending pairs (possibly several rounds); returns the
        number of updates applied.  Rounds are dispatched asynchronously —
        use ``drain()`` for a completion barrier."""
        with self._lock:
            applied = 0
            while any(self._pending.values()):
                applied += self._flush_round()
            return applied

    def drain(self) -> int:
        """Flush everything, then block until all dispatched work is done
        (the shutdown / handoff barrier). Returns the number applied."""
        with self._lock:
            applied = self.flush()
            self._barrier()
            return applied

    # -- in-flight buffer management ----------------------------------------

    def _reap_ready(self) -> None:
        """Retire finished rounds without blocking (oldest-first); their
        tokens become visible."""
        while self._in_flight and all(_is_ready(x) for x in self._in_flight[0][0]):
            _, tokens, _, nulls = self._in_flight.popleft()
            self._retired(tokens, nulls)

    def _retire_oldest(self) -> None:
        outputs, tokens, round_no, nulls = self._in_flight.popleft()
        with _obs.span("reap", outputs=len(outputs), round=round_no):
            jax.block_until_ready(outputs)
        self._retired(tokens, nulls)

    def _retired(self, tokens, nulls) -> None:
        """A round's outputs are concrete: its tokens become visible, and
        its null-column counts go into the ``null_columns`` histogram, one
        observation per (stream, round)."""
        self._visible.extend(tokens)
        if nulls and _obs.enabled():
            hist = _obs.registry().histogram("null_columns", bounds=_COLUMN_BUCKETS,
                                             **self._obs_labels)
            for counts, n_valid in nulls:
                for c in np.asarray(counts)[:n_valid].tolist():
                    hist.observe(c)

    # -- observability (repro.obs) ------------------------------------------

    def _publish_stats(self) -> None:
        """Mirror the stats counter bag into the metrics registry (gauges —
        idempotent re-publication after every flush; the fleet tier labels
        each shard's series and ``registry().aggregate`` rolls them up)."""
        reg = _obs.registry()
        cache_key = (reg, reg.generation)
        if self._stat_gauges is None or self._stat_gauges[0] != cache_key:
            self._stat_gauges = (cache_key, [
                (f.name, reg.gauge(f"serve_{f.name}", **self._obs_labels))
                for f in dataclasses.fields(SvdServiceStats)])
        for name, gauge in self._stat_gauges[1]:
            gauge.set(getattr(self.stats, name))

    def _observe_waits(self, tokens, t_ns: int) -> None:
        """Pop the enqueue stamps of sealed tokens into the ``queue_wait_us``
        histogram: enqueue to the dispatch of the round that took them."""
        stamps = self._enqueued_ns
        if not _obs.enabled():
            for tok in tokens:
                stamps.pop(tok, None)
            return
        hist = _obs.registry().histogram("queue_wait_us", **self._obs_labels)
        for tok in tokens:
            t0 = stamps.pop(tok, None)
            if t0 is not None:
                hist.observe((t_ns - t0) / 1e3)

    def _health_monitor(self) -> "_obs.HealthMonitor":
        if self._health is None:
            self._health = _obs.HealthMonitor(
                every=self.policy.health_every or 1, **self._obs_labels)
        return self._health

    def _barrier(self) -> None:
        """Block until every dispatched round AND every stream state is
        concrete — the only place (besides backpressure) the service waits
        on the device."""
        while self._in_flight:
            self._retire_oldest()
        jax.block_until_ready(list(self._streams.values()))

    def flush_round(self, *, max_depth: int = 1) -> int:
        """Dispatch ONE flush round (public form — the continuous-batching
        frontend's seal primitive; ``repro.fleet.frontend``).

        ``max_depth > 1`` enables depth batching: a stream whose queue head
        is a run of consecutive rank-1 pairs contributes up to ``max_depth``
        of them as one scan column (power-of-two floored), and the round
        groups by ``(geometry, depth)`` — depth-k groups dispatch through
        the engine's ``update_truncated_rank_k_batch`` ``lax.scan`` route,
        ONE engine call applying ``B x k`` events.  The scan applies a
        stream's pairs in FIFO order (per-stream ordering by data
        dependence), and the scan executable is bitwise-identical to the k
        sequential single updates it replaces (pinned in tests/test_fleet.py).
        """
        with self._lock:
            return self._flush_round(max_depth=max_depth)

    def has_capacity(self) -> bool:
        """True when a ``flush_round`` would dispatch WITHOUT blocking on an
        older round (the frontend's pump guard).  Reaps finished rounds."""
        with self._lock:
            if self.max_in_flight == 0:
                return True
            self._reap_ready()
            return len(self._in_flight) < self.max_in_flight

    def _flush_round(self, *, max_depth: int = 1) -> int:
        """One round: pair-headed streams group by (geometry, depth) into
        batched engine calls (at most one event per stream at depth 1, up to
        ``max_depth`` consecutive pairs as a scan column otherwise);
        op-headed streams (appends, decay folds) apply through the planner —
        all dispatched async.  Each round is one ``flush_round`` trace span;
        with obs enabled the stats bag mirrors into the registry afterwards
        and the health monitor samples on its ``policy.health_every`` cadence.
        """
        live_ids = [sid for sid, q in self._pending.items() if q]
        if not live_ids:
            return 0
        round_no = self._next_round
        self._next_round += 1
        labels = _marshal_labels.set(self._obs_labels)
        try:
            with _obs.span("flush_round", streams=len(live_ids),
                           max_depth=max_depth, round=round_no):
                applied = self._flush_round_impl(live_ids, max_depth, round_no)
        finally:
            _marshal_labels.reset(labels)
        if _obs.enabled():
            self._publish_stats()
        return applied

    def _flush_round_impl(self, live_ids: list, max_depth: int, round_no: int) -> int:
        # Backpressure: bound how far the host can run ahead of the device.
        self._reap_ready()
        # nothing older in flight: the device finished its work and waited
        # for this round on the host
        starved = not self._in_flight
        while self.max_in_flight > 0 and len(self._in_flight) >= self.max_in_flight:
            self._retire_oldest()
            self.stats.backpressure_waits += 1

        applied = 0        # pair updates dispatched through batched calls
        ops_applied = 0    # structured heads (already counted by _apply_event)
        round_outputs: list = []
        round_tokens: list = []
        round_nulls: list = []

        # structured heads: per-stream planner application (geometry may
        # change mid-event, so they cannot share a batch)
        round_ids = []
        for sid in live_ids:
            head = self._pending[sid][0]
            if head[0] == "op" and isinstance(head[1], _ops.Sparse):
                # expand a Sparse head into its rank pairs IN PLACE so sparse
                # events batch into pair rounds like everything else; the
                # deterministic sketch makes this bitwise-identical whether
                # it runs before or after a snapshot/restore cycle
                self._expand_sparse_head(sid)
                head = self._pending[sid][0]
            if head[0] == "op":
                # apply BEFORE popping: a raising engine call leaves the
                # event queued, mirroring the pair path's peek-don't-pop
                # failure atomicity below
                self._streams[sid] = self._apply_event(self._streams[sid], head)
                ev = self._pending[sid].popleft()
                if ev[-1] is not None:
                    round_tokens.append(ev[-1])
                    if self._enqueued_ns:
                        self._observe_waits(ev[-1:], time.perf_counter_ns())
                round_outputs.extend(jax.tree.leaves(self._streams[sid]))
                ops_applied += 1
            else:
                round_ids.append(sid)

        # depth per stream: how many consecutive pair heads ride this round
        # as one scan column (1 = the classic one-event-per-stream round)
        depths = {}
        for sid in round_ids:
            if max_depth > 1:
                run = 0
                for ev in self._pending[sid]:
                    if ev[0] != "pair":
                        break
                    run += 1
                    if run >= max_depth:
                        break
                depths[sid] = _depth_bucket(run, max_depth)
            else:
                depths[sid] = 1

        # health sampling: decide once per round; the first depth-1 group's
        # (pre-state, pair, post-state) triple feeds one probe after dispatch
        sample_due = (
            _obs.enabled() and self.policy.health_every is not None
            and self._health_monitor().due()
        )
        probe_args = None

        keys = [truncated_geometry(self._streams[sid]) + (depths[sid],)
                for sid in round_ids]

        for (m, n, r, dt, k), idxs in group_indices(keys).items():
            sids = [round_ids[i] for i in idxs]
            # peek, don't pop: if the engine call raises (first-compile OOM,
            # backend error), the pairs stay queued and a retry re-applies
            # them — flush stays failure-atomic per group
            bsz = len(sids)
            pad = 0
            if self.pad_to_bucket:
                # a group can exceed max_batch (retry after a failed flush
                # accumulates streams) — never pad negative, just dispatch big
                pad = max(0, _bucket(bsz, self.max_batch) - bsz)
            with _obs.span("assemble", batch=bsz + pad, depth=k):
                states = [self._streams[sid] for sid in sids]
                evs = [q[j] for q in (self._pending[sid] for sid in sids)
                       for j in range(k)]
                # batch padding repeats the last stream; the executable
                # zeroes the padded pairs
                evs += evs[-k:] * pad
                t_stack, a_stack, b_stack = _assemble_round(
                    [(s.u, s.s, s.v) for s in states + states[-1:] * pad],
                    [ev[1] for ev in evs], [ev[2] for ev in evs], bsz, k=k,
                )

            eng = self._engine_for(r, m, n, dt)
            fresh = False
            if self.policy.mesh is None:
                kind = "trunc_batch" if k == 1 else f"trunc_scan{k}"
                fresh = self._record_warm(kind, bsz + pad, m, n, r, dt)
            with _obs.span("dispatch", m=m, n=n, rank=r, batch=bsz + pad,
                           depth=k):
                if k == 1:
                    out = eng.update_truncated_batch(
                        t_stack, a_stack, b_stack,
                        mesh=self.policy.mesh, batch_axis=self.policy.batch_axis,
                    )
                else:
                    out = eng.update_truncated_rank_k_batch(
                        t_stack, a_stack, b_stack,
                        mesh=self.policy.mesh, batch_axis=self.policy.batch_axis,
                    )
                    self.stats.scan_rounds += 1
                    self.stats.max_depth = max(self.stats.max_depth, k)
            dispatched_ns = time.perf_counter_ns() if self._enqueued_ns else 0
            with _obs.span("writeback", streams=bsz):
                first = len(round_tokens)
                rows = _split_round(out)
                for sid, (u, s, v) in zip(sids, rows):
                    self._streams[sid] = SvdState(u=u, s=s, v=v)
                    for _ in range(k):
                        ev = self._pending[sid].popleft()
                        if ev[-1] is not None:
                            round_tokens.append(ev[-1])
                if self._enqueued_ns:
                    self._observe_waits(round_tokens[first:], dispatched_ns)
            if sample_due and probe_args is None and k == 1:
                probe_args = (states[0].u, states[0].s, states[0].v,
                              a_stack[0], b_stack[0], *rows[0])
            round_outputs.extend(jax.tree.leaves(out))
            if _obs.enabled() or fresh:
                # built with the round shape's other executables, so a
                # window traced later builds nothing; kept only while obs
                # is on, and read once the round is concrete
                nulls = _null_counts(out.s)
                if _obs.enabled():
                    round_nulls.append((nulls, bsz))
                    round_outputs.append(nulls)
            applied += bsz * k
            self.stats.rounds += 1
            self.stats.max_batch = max(self.stats.max_batch, bsz + pad)

        if self.max_in_flight == 0:
            jax.block_until_ready(round_outputs)       # synchronous mode
            self._retired(round_tokens, round_nulls)
        else:
            self._in_flight.append((round_outputs, round_tokens, round_no,
                                    round_nulls))
            self.stats.in_flight_peak = max(
                self.stats.in_flight_peak, len(self._in_flight)
            )
            if _obs.enabled():
                # made on every round, so a window with none starved reads 0
                starved_rounds = _obs.registry().counter(
                    "starved_rounds", **self._obs_labels)
                if starved:
                    starved_rounds.inc()
        self.stats.flushes += 1
        self.stats.applied += applied
        if probe_args is not None:
            # separate jitted probe over the just-flushed factors — outside
            # the update's traced path; forces the sampled state concrete
            self._health_monitor().sample_update(
                *probe_args, deflate_rtol=self.policy.deflate_rtol)
        return applied + ops_applied

    # -- checkpointing ------------------------------------------------------

    def snapshot(self) -> ServiceSnapshot:
        """Capture the whole service as a versioned pytree.

        This is a barrier: in-flight rounds are retired and every stream
        state is forced concrete first, so the snapshot is a consistent
        point on every stream's timeline — states as of all *flushed*
        updates, pending FIFOs holding exactly the unflushed ones.
        """
        with self._lock:
            self._barrier()
            states, pend_a, pend_b, pend_ops, orders = [], [], [], [], []
            for sid, st in self._streams.items():
                states.append(st)
                a_vecs, b_vecs, stream_ops, order = [], [], [], []
                geom_m, geom_n = st.m, st.n
                geom_changed = False
                for ev in self._pending[sid]:
                    if ev[0] == "pair" and not geom_changed:
                        a_vecs.append(jnp.asarray(ev[1]))
                        b_vecs.append(jnp.asarray(ev[2]))
                        order.append("p")
                    elif ev[0] == "pair":
                        # a queued append changed the geometry: later pairs
                        # no longer fit the rectangular (k_i, m)/(k_i, n)
                        # stacks — carry them as rank-1 RankK op leaves
                        # (bitwise: restore unwraps k=1 RankK back to pairs)
                        stream_ops.append(
                            _ops.RankK(jnp.asarray(ev[1])[:, None],
                                       jnp.asarray(ev[2])[:, None])
                        )
                        order.append("o")
                    else:
                        stream_ops.append(ev[1])
                        order.append("o")
                        if ev[1].out_shape(geom_m, geom_n) != (geom_m, geom_n):
                            geom_changed = True
                if a_vecs:
                    pend_a.append(jnp.stack(a_vecs))
                    pend_b.append(jnp.stack(b_vecs))
                else:
                    pend_a.append(np.zeros((0, geom_m), st.u.dtype))
                    pend_b.append(np.zeros((0, geom_n), st.v.dtype))
                pend_ops.append(tuple(stream_ops))
                orders.append("".join(order))
            return ServiceSnapshot(
                states=tuple(states),
                pending_a=tuple(pend_a),
                pending_b=tuple(pend_b),
                pending_ops=tuple(pend_ops),
                version=SNAPSHOT_VERSION,
                stream_ids=tuple(self._streams),
                policy_spec=tuple(_policy_spec(self.policy).items()),
                max_batch=self.max_batch,
                pad_to_bucket=self.pad_to_bucket,
                max_in_flight=self.max_in_flight,
                stats=tuple(dataclasses.asdict(self.stats).items()),
                pending_order=tuple(orders),
                warmed=tuple(sorted(self._warmed)),
                # telemetry rides the snapshot like the stats bag does —
                # captured only when obs is on (empty tuple otherwise)
                obs_metrics=(_obs.registry().snapshot()
                             if _obs.enabled() else ()),
            )

    def save(self, ckpt_dir, step: int, *, keep: int = 3):
        """``snapshot()`` + atomic write through ``train.checkpoint``."""
        return self.snapshot().save(ckpt_dir, step, keep=keep)

    @classmethod
    def from_snapshot(
        cls,
        snap: ServiceSnapshot,
        *,
        mesh=None,
        engine: SvdEngine | None = None,
        policy: UpdatePolicy | None = None,
    ) -> "SvdService":
        """Rebuild a service from a snapshot.

        ``policy`` (full override) or ``mesh`` (grafted onto the recorded
        policy spec) re-establish placement on the restoring topology;
        with neither, the recorded numerics run unsharded.
        """
        spec = dict(snap.policy_spec)
        if policy is None:
            if spec.get("had_mesh") and mesh is None:
                warnings.warn(
                    "snapshot was taken under a mesh-sharded policy but "
                    "restore got no mesh= (and no policy=): flushes will run "
                    "unsharded on this process",
                    stacklevel=2,
                )
            policy = _policy_from_spec(spec, mesh=mesh)
        svc = cls(
            engine=engine,
            max_batch=snap.max_batch,
            pad_to_bucket=snap.pad_to_bucket,
            max_in_flight=snap.max_in_flight,
            policy=policy,
        )
        n_streams = len(snap.stream_ids)
        pend_ops = snap.pending_ops or ((),) * n_streams
        orders = snap.pending_order or (None,) * n_streams
        for sid, st, pa, pb, sops, order in zip(
            snap.stream_ids, snap.states, snap.pending_a, snap.pending_b,
            pend_ops, orders,
        ):
            # onto the device here (a transfer, bitwise): numpy leaves would
            # each cost an eager convert compile at the first flush
            u, s, v = jax.device_put((st.u, st.s, st.v))
            svc._streams[sid] = SvdState(u=u, s=s, v=v)
            n_pairs = np.asarray(pa).shape[0]
            if order is None:
                order = "p" * n_pairs          # v1 snapshots: all-pair FIFOs
            queue: deque = deque()
            pi = oi = 0
            # visibility tokens are runtime-only: restored events get fresh
            # ones (nobody is waiting on the old process's tokens)
            for marker in order:
                if marker == "p":
                    queue.append(("pair", pa[pi], pb[pi], svc._issue_token()))
                    pi += 1
                    continue
                op = sops[oi]
                oi += 1
                if isinstance(op, _ops.RankK):
                    # k=1 RankK leaves are pairs the snapshot wrapped to keep
                    # the pair stacks rectangular past a geometry change
                    for i in range(op.k):
                        queue.append(("pair", jnp.asarray(op.u)[:, i],
                                      jnp.asarray(op.v)[:, i],
                                      svc._issue_token()))
                else:
                    queue.append(("op", op, svc._issue_token()))
            svc._pending[sid] = queue
            m_eff, n_eff = svc._streams[sid].m, svc._streams[sid].n
            for ev in queue:
                if ev[0] == "op":
                    m_eff, n_eff = ev[1].out_shape(m_eff, n_eff)
            svc._eff_shape[sid] = (m_eff, n_eff)
        svc.stats = SvdServiceStats(**dict(snap.stats))
        if snap.obs_metrics:
            _obs.registry().restore(snap.obs_metrics)
        svc._warmed = {tuple(w) for w in snap.warmed}
        # cold-start control (ROADMAP item): eagerly AOT-warm every
        # (kind, geometry) the snapshotted service had compiled, so the first
        # post-restore flush hits the plan cache instead of compiling under
        # traffic.  Skipped when an explicit engine override is active (its
        # plans are caller-managed) or the policy re-shards over a mesh (the
        # shard_map route keys on the live mesh, which warmup cannot AOT).
        if engine is None and policy.mesh is None:
            for kind, batch, m, n, r, dtype_name in svc._warmed:
                if kind in ("sketch_dense", "sketch_sparse"):
                    # sketch executables warm by running on zeros (the jit
                    # call cache, not the engine plan cache); ``batch`` slot
                    # carries nnz for the sparse kind
                    _sketch.warmup_sketch(
                        m=m, n=n, k=r,
                        oversample=policy.sketch_oversample,
                        power_iters=policy.sketch_power_iters,
                        nnz=batch if kind == "sketch_sparse" else None,
                        dtype=jnp.dtype(dtype_name),
                    )
                    continue
                # depth-batched rounds record "trunc_scan<k>" — the scan
                # depth rides the kind string (the warm tuple is fixed-width)
                scan_k = (int(kind[len("trunc_scan"):])
                          if kind.startswith("trunc_scan") else None)
                _api_warmup(
                    svc.policy, m=m, n=n,
                    batch=batch if kind != "trunc" else None,
                    rank=r, k=scan_k, dtype=jnp.dtype(dtype_name),
                )
                if kind != "trunc":
                    # a batched round also runs the marshalling executables
                    _warm_marshal(batch, m, n, r, scan_k or 1,
                                  jnp.dtype(dtype_name))
        return svc

    @classmethod
    def restore(
        cls,
        ckpt_dir,
        *,
        step: int | None = None,
        mesh=None,
        engine: SvdEngine | None = None,
        policy: UpdatePolicy | None = None,
        cache_dir=None,
    ) -> tuple[int, "SvdService"]:
        """Load the latest (or ``step``-th) snapshot and rebuild the service.

        Returns ``(step, service)``.  Restore-exactness contract: the
        restored service, fed the same post-snapshot traffic, produces
        bitwise-identical factors to the service that never stopped
        (DESIGN.md §9; kill-and-resume test in test_serve_checkpoint.py).

        ``cache_dir`` (opt-in) enables the persistent XLA compilation cache
        BEFORE the warmed-geometry set re-warms (``api.
        enable_compilation_cache``): a restore on a machine that has flushed
        these geometries before recompiles NOTHING — warmup replays cached
        binaries (the fresh-process proof is in tests/test_fleet.py).
        """
        if cache_dir is not None:
            from repro.api import enable_compilation_cache

            enable_compilation_cache(cache_dir)
        step, snap = ServiceSnapshot.load(ckpt_dir, step)
        return step, cls.from_snapshot(snap, mesh=mesh, engine=engine, policy=policy)
