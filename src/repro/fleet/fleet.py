"""``SvdFleet`` — the mesh-sharded service tier (DESIGN.md §13).

One host-side ``SvdService`` owns every stream it serves; the mesh can
parallelize a flush's batch axis but never the stream population.  The
fleet partitions the population itself: ``num_shards`` independent services
(``fleet.shard.FleetShard``), streams assigned by deterministic hashed
placement (``fleet.placement``), each shard running its own FIFOs, bucket
rounds, in-flight buffer and continuous-batching admission window
(``fleet.frontend``).  The public surface is the service's —
``register`` / ``enqueue`` / ``enqueue_op`` / ``state`` / ``flush`` /
``drain`` / ``merge_streams`` — so a caller scales from one service to a
fleet by swapping the constructor.

Cross-shard composition happens ONLY at query time: ``query`` settles each
member stream on its own shard, then runs the hierarchical Iwen–Ong merge
(``dist.merge.merge_tree``) over the settled states in ``stream_ids``
order — exact for globally low-rank data, near-optimal otherwise.  The
settle path applies each stream's queue through the same per-stream
``_apply_event`` sequence a standalone service would, so a fleet query
over enqueued traffic is BITWISE-equal to the single-service reference
(the acceptance test in tests/test_fleet.py) — placement cannot change
what a query returns.  Flushed (batched-round) states carry the usual
XLA caveat: executables compiled for different batch compositions may
round reductions in different orders, so cross-topology comparisons of
flush-applied states are exact only to ulp-level tolerance — the
same-composition replay guarantees (snapshot restore) remain bitwise.

``FleetSnapshot`` (snapshot **v8**) captures the whole tier — one
``ServiceSnapshot`` (v7 payload) per shard plus the placement spec — and
restores bitwise, kill-and-resume, across processes.  Because placement is
pure data, restore accepts a DIFFERENT shard count: ``regrouped`` re-places
every stream's leaves (state + pending FIFO, moved wholesale and bitwise)
under the new spec before services are rebuilt — the elastic path
(``train.elastic.plan_shard_count`` picks the count from live devices).
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial

import jax

from repro import obs as _obs
from repro.api import UpdatePolicy
from repro.api.state import SvdState
from repro.dist.merge import merge_tree
from repro.fleet.placement import PlacementSpec, plan_devices, shard_of
from repro.fleet.shard import FleetShard
from repro.serve.svd_service import ServiceSnapshot, SvdService, SvdServiceStats
from repro.train import checkpoint as _checkpoint

__all__ = ["FLEET_SNAPSHOT_VERSION", "FleetSnapshot", "SvdFleet"]

# The snapshot version line is shared with serve: v1-v3, v5 and v7 are
# single-service ``ServiceSnapshot`` formats (DESIGN.md §9/§12/§14/§15); v4
# was the first fleet-level format (v3 service payloads); v6 carried v5
# service payloads (downdate ops in the FIFOs); v8 carries v7 payloads
# (obs-metrics rows riding each shard's snapshot metadata, DESIGN.md §15).
# v4/v6 fleet snapshots still load — the payload loader accepts any service
# version <= 7, and missing obs rows restore as empty.
FLEET_SNAPSHOT_VERSION = 8
_SNAPSHOT_FORMAT = "repro.fleet.FleetSnapshot"

# fleet-level config a snapshot records (admission shape; devices are
# runtime placement and deliberately absent, like the service's mesh)
_CONFIG_FIELDS = ("continuous", "max_depth", "max_backlog")


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["shards"],
    meta_fields=["version", "placement", "config"],
)
@dataclasses.dataclass(frozen=True)
class FleetSnapshot:
    """Versioned capture of a whole fleet: per-shard ``ServiceSnapshot``
    payloads (the array leaves) + the placement spec and admission config
    (metadata, mirrored into the JSON aux so a fresh process rebuilds the
    exact routing table before loading a single array)."""

    shards: tuple            # tuple[ServiceSnapshot, ...], index = shard id
    version: int = FLEET_SNAPSHOT_VERSION
    placement: PlacementSpec = PlacementSpec(1)
    config: tuple = ()       # (field, value) pairs of _CONFIG_FIELDS

    def aux(self) -> dict:
        return {
            "format": _SNAPSHOT_FORMAT,
            "version": self.version,
            "placement": self.placement.to_json(),
            "config": dict(self.config),
            "shards": [s.aux() for s in self.shards],
        }

    @classmethod
    def skeleton(cls, aux: dict) -> "FleetSnapshot":
        return cls(
            shards=tuple(ServiceSnapshot.skeleton(sa) for sa in aux["shards"]),
            version=FLEET_SNAPSHOT_VERSION,
            placement=PlacementSpec.from_json(aux["placement"]),
            config=tuple(aux["config"].items()),
        )

    def save(self, ckpt_dir, step: int, *, keep: int = 3):
        return _checkpoint.save(ckpt_dir, step, self, aux=self.aux())

    @classmethod
    def load(cls, ckpt_dir, step: int | None = None) -> tuple[int, "FleetSnapshot"]:
        step, aux = _checkpoint.load_aux(ckpt_dir, step)
        if aux is None or aux.get("format") != _SNAPSHOT_FORMAT:
            raise ValueError(
                f"checkpoint at step {step} is not a FleetSnapshot "
                f"(aux format: {None if aux is None else aux.get('format')!r})"
            )
        if aux["version"] > FLEET_SNAPSHOT_VERSION:
            raise ValueError(
                f"snapshot version {aux['version']} is newer than this build "
                f"understands (<= {FLEET_SNAPSHOT_VERSION})"
            )
        _, leaves = _checkpoint.restore(ckpt_dir, None, step)
        treedef = jax.tree.structure(cls.skeleton(aux))
        return step, jax.tree.unflatten(treedef, leaves)

    # -- elastic re-placement ----------------------------------------------

    def regrouped(self, num_shards: int) -> "FleetSnapshot":
        """The same fleet under ``placement.replaced(num_shards)``: every
        stream's snapshot leaves (state + pending FIFO stacks + op pytrees +
        order string) move WHOLESALE to the shard the new spec hashes it to
        — pure pytree surgery, bitwise, no engine dispatch.  Warmed sets
        union into every new shard (a warm superset costs only warmup time);
        per-shard stats counters reset (they are per-process observability,
        not stream state).
        """
        if num_shards == self.placement.num_shards:
            return self
        new_spec = self.placement.replaced(num_shards)
        if not self.shards:
            return FleetSnapshot(shards=(), placement=new_spec,
                                 config=self.config)
        proto = self.shards[0]       # shards share the service config
        warmed = tuple(sorted({w for s in self.shards for w in s.warmed}))
        zero_stats = tuple(
            dataclasses.asdict(SvdServiceStats()).items()
        )
        buckets: list[list] = [[] for _ in range(num_shards)]
        for snap in self.shards:
            for i, sid in enumerate(snap.stream_ids):
                buckets[shard_of(new_spec, sid)].append((
                    sid, snap.states[i], snap.pending_a[i], snap.pending_b[i],
                    snap.pending_ops[i] if snap.pending_ops else (),
                    snap.pending_order[i] if snap.pending_order else "",
                ))
        shards = tuple(
            ServiceSnapshot(
                states=tuple(e[1] for e in bucket),
                pending_a=tuple(e[2] for e in bucket),
                pending_b=tuple(e[3] for e in bucket),
                pending_ops=tuple(e[4] for e in bucket),
                version=proto.version,
                stream_ids=tuple(e[0] for e in bucket),
                policy_spec=proto.policy_spec,
                max_batch=proto.max_batch,
                pad_to_bucket=proto.pad_to_bucket,
                max_in_flight=proto.max_in_flight,
                stats=zero_stats,
                pending_order=tuple(e[5] for e in bucket),
                warmed=warmed,
            )
            for bucket in buckets
        )
        return FleetSnapshot(shards=shards, placement=new_spec,
                             config=self.config)


class SvdFleet:
    """A population-sharded ``SvdService``: same surface, ``num_shards``
    independent engines' worth of admission capacity.

        fleet = SvdFleet(num_shards=8, policy=UpdatePolicy(method="auto"))
        fleet.register("user-1", api.SvdState.from_dense(m1, rank=8))
        fleet.enqueue("user-1", a, b)       # routed, admitted, maybe sealed
        merged = fleet.query(["user-1", "user-2"])   # cross-shard Iwen-Ong
        fleet.save("/ckpts/fleet", step=1)  # FleetSnapshot v8

    ``continuous=True`` (default) runs each shard behind its admission
    window (``fleet.frontend``); ``False`` degrades every shard to the
    plain fixed-boundary service (the benchmark control arm).
    ``devices="auto"`` pins shard ``i`` to device ``i mod n_devices``
    (``placement.plan_devices``); None leaves placement to the process
    default (single-device hosts).
    """

    def __init__(
        self,
        num_shards: int = 1,
        *,
        policy: UpdatePolicy | None = None,
        max_batch: int = 64,
        pad_to_bucket: bool = True,
        max_in_flight: int = 2,
        continuous: bool = True,
        max_depth: int = 8,
        max_backlog: int | None = None,
        placement: PlacementSpec | None = None,
        devices=None,
    ):
        self.placement = (placement if placement is not None
                          else PlacementSpec(num_shards))
        if self.placement.num_shards != num_shards:
            raise ValueError(
                f"placement spec is for {self.placement.num_shards} shards; "
                f"fleet has {num_shards}"
            )
        self.policy = policy if policy is not None else UpdatePolicy()
        self.continuous = continuous
        self.max_depth = max_depth
        self.max_backlog = max_backlog
        if devices == "auto":
            devices = plan_devices(num_shards, mesh=self.policy.mesh)
        elif devices is None:
            devices = (None,) * num_shards
        self.shards = tuple(
            FleetShard(
                i,
                policy=self.policy,
                max_batch=max_batch,
                pad_to_bucket=pad_to_bucket,
                max_in_flight=max_in_flight,
                continuous=continuous,
                max_depth=max_depth,
                max_backlog=max_backlog,
                device=devices[i % len(devices)],
            )
            for i in range(num_shards)
        )

    @property
    def num_shards(self) -> int:
        return len(self.shards)

    def shard_of(self, stream_id: str) -> int:
        return shard_of(self.placement, stream_id)

    def _shard(self, stream_id: str) -> FleetShard:
        return self.shards[self.shard_of(stream_id)]

    # -- the service surface, routed ----------------------------------------

    def register(self, stream_id: str, state) -> None:
        self._shard(stream_id).register(stream_id, state)

    def enqueue(self, stream_id: str, a, b) -> tuple[int, int]:
        """Route + admit one rank-1 event; returns its fleet-level
        visibility token ``(shard, token)`` (see ``poll``)."""
        t0 = time.perf_counter_ns() if _obs.enabled() else None
        sh = self.shard_of(stream_id)
        token = self.shards[sh].enqueue(stream_id, a, b)
        if t0 is not None:
            self.shards[sh].count_enqueue(time.perf_counter_ns() - t0)
        return (sh, token)

    def enqueue_op(self, stream_id: str, op) -> tuple[int, int]:
        sh = self.shard_of(stream_id)
        return (sh, self.shards[sh].enqueue_op(stream_id, op))

    def state(self, stream_id: str) -> SvdState:
        return self._shard(stream_id).service.state(stream_id)

    def evict(self, stream_id: str) -> SvdState:
        return self._shard(stream_id).service.evict(stream_id)

    def pending(self) -> int:
        return sum(s.pending() for s in self.shards)

    def pump(self) -> int:
        """One admission pass over every shard (the fleet event loop tick);
        returns events dispatched."""
        return sum(s.pump() for s in self.shards)

    def poll(self) -> list[tuple[int, int]]:
        """Newly visible fleet tokens ``(shard, token)`` across all shards."""
        out = []
        for i, s in enumerate(self.shards):
            out.extend((i, t) for t in s.poll())
        return out

    def flush(self) -> int:
        return sum(s.flush() for s in self.shards)

    def drain(self) -> int:
        return sum(s.drain() for s in self.shards)

    def stats(self) -> SvdServiceStats:
        """Fleet-aggregate counters (sum over shards; ``max_*`` fields max).

        With ``repro.obs`` enabled the aggregate is also published as
        ``fleet_<field>`` gauges — the rollup view over the per-shard
        ``serve_<field>{shard=i}`` series each shard publishes on flush.
        """
        agg = SvdServiceStats()
        for s in self.shards:
            st = s.service.stats
            for f in dataclasses.fields(SvdServiceStats):
                if f.name.startswith("max_") or f.name.endswith("_peak"):
                    setattr(agg, f.name,
                            max(getattr(agg, f.name), getattr(st, f.name)))
                else:
                    setattr(agg, f.name,
                            getattr(agg, f.name) + getattr(st, f.name))
        if _obs.enabled():
            reg = _obs.registry()
            for f in dataclasses.fields(SvdServiceStats):
                reg.gauge(f"fleet_{f.name}").set(getattr(agg, f.name))
        return agg

    # -- query-time cross-shard composition ---------------------------------

    def settle(self, stream_ids) -> list[SvdState]:
        """Per-stream settled states in ``stream_ids`` order (each shard
        applies its own members' queues; no cross-shard traffic)."""
        by_shard: dict[int, list[str]] = {}
        for sid in stream_ids:
            by_shard.setdefault(self.shard_of(sid), []).append(sid)
        settled: dict[str, SvdState] = {}
        for sh, sids in by_shard.items():
            for sid, st in zip(sids, self.shards[sh].service.settle(sids)):
                settled[sid] = st
        return [settled[sid] for sid in stream_ids]

    def query(self, stream_ids, *, rank: int | None = None) -> SvdState:
        """Truncated SVD of the row-concatenation of the named streams
        (``stream_ids`` order), wherever they live: settle on the owning
        shards, then ONE hierarchical merge (``dist.merge.merge_tree``) —
        the only point where shards compose, and it moves just the
        ``(m + n + 1) * r`` factor floats per stream."""
        states = self.settle(stream_ids)
        return merge_tree(states, rank=rank, policy=self.policy)

    def merge_streams(
        self,
        stream_ids,
        *,
        target: str | None = None,
        rank: int | None = None,
    ) -> SvdState:
        """Service-compatible alias of ``query``; with ``target`` the merged
        state registers as a new stream on ITS hashed shard."""
        merged = self.query(stream_ids, rank=rank)
        if target is not None:
            self.register(target, merged)
        return merged

    # -- checkpointing -------------------------------------------------------

    def snapshot(self) -> FleetSnapshot:
        """Barrier + capture every shard (consistent per shard; shards are
        independent, so the fleet snapshot is the tuple of shard points)."""
        return FleetSnapshot(
            shards=tuple(s.snapshot() for s in self.shards),
            version=FLEET_SNAPSHOT_VERSION,
            placement=self.placement,
            config=tuple((f, getattr(self, f)) for f in _CONFIG_FIELDS),
        )

    def save(self, ckpt_dir, step: int, *, keep: int = 3):
        return self.snapshot().save(ckpt_dir, step, keep=keep)

    @classmethod
    def from_snapshot(
        cls,
        snap: FleetSnapshot,
        *,
        mesh=None,
        policy: UpdatePolicy | None = None,
        devices=None,
    ) -> "SvdFleet":
        """Rebuild a fleet from a snapshot (same shard count as ``snap`` —
        re-place first via ``snap.regrouped`` for an elastic restore).

        Per-shard services rebuild through ``SvdService.from_snapshot``,
        including the eager warmed-geometry ``api.warmup`` replay; combined
        with a persistent ``cache_dir`` (see ``restore``) that replay
        compiles nothing.
        """
        cfg = dict(snap.config)
        n = len(snap.shards)
        proto_policy = policy
        services = [
            SvdService.from_snapshot(s, mesh=mesh, policy=policy)
            for s in snap.shards
        ]
        fleet = cls.__new__(cls)
        fleet.placement = snap.placement
        fleet.policy = (services[0].policy if services else
                        (proto_policy if proto_policy is not None
                         else UpdatePolicy(mesh=mesh)))
        fleet.continuous = bool(cfg.get("continuous", True))
        fleet.max_depth = int(cfg.get("max_depth", 8))
        fleet.max_backlog = cfg.get("max_backlog")
        if devices == "auto":
            devices = plan_devices(n, mesh=fleet.policy.mesh)
        elif devices is None:
            devices = (None,) * max(n, 1)
        fleet.shards = tuple(
            FleetShard(
                i,
                continuous=fleet.continuous,
                max_depth=fleet.max_depth,
                max_backlog=fleet.max_backlog,
                device=devices[i % len(devices)],
                service=services[i],
            )
            for i in range(n)
        )
        return fleet

    @classmethod
    def restore(
        cls,
        ckpt_dir,
        *,
        step: int | None = None,
        num_shards: int | str | None = None,
        mesh=None,
        policy: UpdatePolicy | None = None,
        devices=None,
        cache_dir=None,
    ) -> tuple[int, "SvdFleet"]:
        """Load the latest (or ``step``-th) fleet snapshot and rebuild.

        ``num_shards``: None keeps the recorded shard count; an int
        re-places every stream under ``placement.replaced(num_shards)``
        (elastic restore — bitwise per stream, tests/test_fleet.py);
        ``"auto"`` asks ``train.elastic.plan_shard_count`` to size the
        fleet to the devices actually alive (the failover path).
        ``cache_dir`` enables the persistent compilation cache BEFORE the
        warmed-set replay, so a warm cache restores with zero recompiles.
        """
        if cache_dir is not None:
            from repro.api import enable_compilation_cache

            enable_compilation_cache(cache_dir)
        step, snap = FleetSnapshot.load(ckpt_dir, step)
        if num_shards == "auto":
            from repro.train.elastic import plan_shard_count

            num_shards = plan_shard_count()
        if num_shards is not None and num_shards != len(snap.shards):
            snap = snap.regrouped(int(num_shards))
        return step, cls.from_snapshot(snap, mesh=mesh, policy=policy,
                                       devices=devices)
