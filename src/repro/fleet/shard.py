"""One fleet shard: a ``SvdService`` partition plus its admission frontend.

A shard is the unit of ownership: every stream hashed to shard ``i``
(``placement.shard_of``) lives in shard ``i``'s service — its state, its
FIFO, its flush rounds, its in-flight buffer are all private to the shard.
Shards therefore flush **independently**: shard ``i`` sealing a round never
waits on shard ``j``'s device work, and the per-shard bucket rounds keep
each shard's plan-cache geometry set as small as a standalone service's.
Cross-shard composition happens only at query time (``fleet.SvdFleet``
merges settled states through ``dist.merge``).
"""

from __future__ import annotations

import time

import jax

from repro import obs as _obs
from repro.api import UpdatePolicy
from repro.fleet.frontend import ContinuousBatcher
from repro.serve.svd_service import SvdService

__all__ = ["FleetShard"]


class FleetShard:
    """Shard ``index``: one ``SvdService`` + one ``ContinuousBatcher``.

    The shard's service is a COMPLETE standalone service (snapshot,
    restore, merge, eviction all work per shard); the shard wrapper adds
    identity, device pinning and the admission frontend.
    """

    def __init__(
        self,
        index: int,
        *,
        policy: UpdatePolicy | None = None,
        max_batch: int = 64,
        pad_to_bucket: bool = True,
        max_in_flight: int = 2,
        continuous: bool = True,
        max_depth: int = 8,
        max_backlog: int | None = None,
        device=None,
        service: SvdService | None = None,
    ):
        self.index = index
        self.device = device
        self.service = service if service is not None else SvdService(
            max_batch=max_batch,
            pad_to_bucket=pad_to_bucket,
            max_in_flight=max_in_flight,
            policy=policy,
        )
        # per-shard series in the obs registry: every serve_* gauge and
        # health_* probe this shard publishes carries shard=<index>, and
        # registry().aggregate(...) rolls them into fleet totals
        self.service._obs_labels = {"shard": str(index)}
        self._timers: tuple | None = None   # cached (registry key, counters)
        self.frontend = ContinuousBatcher(
            self.service,
            max_depth=max_depth,
            max_backlog=max_backlog,
            device=device,
            continuous=continuous,
        )

    # thin delegation — the fleet routes per stream, shards do the work

    def _place(self, tree):
        # Flush rounds run under jax.default_device(self.device), which
        # places new arrays but never moves committed ones: arrays built on
        # another device would pull the whole round there.  Commit every
        # leaf to the shard's device on the way in.
        return tree if self.device is None else jax.device_put(tree, self.device)

    def register(self, stream_id: str, state) -> None:
        self.service.register(stream_id, self._place(state))

    def enqueue(self, stream_id: str, a, b) -> int:
        t0 = time.perf_counter_ns() if _obs.enabled() else None
        a, b = self._place((a, b))
        if t0 is not None:
            self._timer_counters()[1].inc(time.perf_counter_ns() - t0)
        return self.frontend.admit(stream_id, a, b)

    def _timer_counters(self) -> tuple:
        """``(enqueue_host_ns, place_host_ns, enqueue_timed)`` of this shard,
        cached until the registry is swapped or reset."""
        reg = _obs.registry()
        key = (reg, reg.generation)
        if self._timers is None or self._timers[0] != key:
            labels = self.service._obs_labels
            self._timers = (key, tuple(
                reg.counter(name, **labels)
                for name in ("enqueue_host_ns", "place_host_ns", "enqueue_timed")))
        return self._timers[1]

    def count_enqueue(self, host_ns: int) -> None:
        """Record one timed enqueue (route, place, admit) of this shard."""
        enqueue_ns, _, timed = self._timer_counters()
        enqueue_ns.inc(host_ns)
        timed.inc()

    def enqueue_op(self, stream_id: str, op) -> int:
        return self.frontend.admit_op(stream_id, self._place(op))

    def pending(self) -> int:
        return self.service.pending()

    def poll(self) -> list[int]:
        return self.frontend.poll()

    def pump(self) -> int:
        return self.frontend.pump()

    def flush(self) -> int:
        return self.service.flush()

    def drain(self) -> int:
        # through the frontend: it seals maximally deep/wide rounds first,
        # then runs the service's blocking barrier
        return self.frontend.drain()

    def snapshot(self):
        return self.service.snapshot()
