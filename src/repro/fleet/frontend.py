"""Continuous-batching admission over one ``SvdService`` (DESIGN.md §13).

The plain service flushes at FIXED boundaries: a round dispatches when
``max_batch`` streams have a pending head (or on an explicit ``flush()``),
and it always takes exactly one event per stream.  Under an open-loop load
that is the latency shape of a bus schedule — an event that just missed a
round waits for the next boundary, and at moderate rates the boundary only
arrives when enough OTHER streams have queued (p99 = the batch-fill time).

This frontend replaces the boundary with an **admission window**:

    admit(...)  ->  [open window: per-stream FIFOs accumulate]
                        |  event-loop tick (pump) finds device capacity
                        |  (in-flight < max_in_flight)
                        v
                    seal: flush_round(max_depth) dispatches EVERYTHING
                    pending — wide (all ready streams) and deep (backlogged
                    streams contribute up to max_depth consecutive pairs as
                    one rank-k scan column)

* A round is sealed at the next ``pump`` tick with device capacity — never
  at a fill count, and never per admit (per-admit sealing freezes rounds
  at one event each and pays a full dispatch per event).  While the device
  is busy, arriving events join the open window, so the NEXT round's batch
  grows with load: light traffic gets small prompt rounds (minimum
  latency), heavy traffic gets wide+deep rounds (maximum throughput).
  That adaptivity IS continuous batching.
* Ordering correctness needs no locks beyond the service's: a stream's
  events sit in ONE per-stream FIFO, a round takes only a FIFO *prefix*,
  and a depth-k column applies its pairs in FIFO order inside the scan —
  so every stream's updates form a single data-dependence chain no matter
  how windows cut it (the proof obligation pinned by
  ``test_continuous_ordering_*`` in tests/test_fleet.py).
* Backpressure is per shard: past ``max_backlog`` pending events the next
  ``admit`` blocks on the oldest in-flight round before queueing — the
  host can neither run unboundedly ahead of the device (service
  ``max_in_flight``) nor buffer unboundedly many events (this bound).

Visibility: ``admit`` returns the service's enqueue token; ``poll()``
drains tokens whose round has retired.  Enqueue-to-visible is the fleet
SLO — ``benchmarks/bench_fleet.py`` reports its p50/p99.
"""

from __future__ import annotations

from repro import obs as _obs
from repro.serve.svd_service import SvdService

__all__ = ["ContinuousBatcher"]


class ContinuousBatcher:
    """Capacity-triggered admission over one shard's ``SvdService``.

    ``max_depth``: deepest rank-k scan column a sealed round may take from
    one stream's backlog (1 = classic one-event-per-stream rounds).
    ``max_backlog``: pending-event bound that blocks ``admit`` (None = the
    service's ``max_in_flight`` bounds host run-ahead on its own).
    ``device``: pin this shard's dispatches to one device
    (``placement.plan_devices``); None = the process default.
    """

    def __init__(
        self,
        service: SvdService,
        *,
        max_depth: int = 8,
        max_backlog: int | None = None,
        device=None,
        continuous: bool = True,
    ):
        if max_depth < 1:
            raise ValueError(f"max_depth must be >= 1; got {max_depth}")
        self.service = service
        self.max_depth = max_depth
        self.max_backlog = max_backlog
        self.device = device
        # continuous=False degrades to the service's own fixed boundaries
        # (autoflush at max_batch) — the benchmark's control arm
        self.continuous = continuous

    # -- admission ----------------------------------------------------------

    def admit(self, stream_id: str, a, b) -> int:
        """Admit one rank-1 event into the open window; returns its
        visibility token.  Admission NEVER seals: cutting a round per admit
        would freeze the round size at whatever the admission interval
        allows (one-event rounds on a host that outpaces its device, and
        every such round burns a full dispatch).  Rounds are sealed by the
        caller's event-loop tick (``pump``), by backpressure, or by
        ``drain`` — each sees the whole window and cuts maximally wide +
        deep rounds, which is what makes the batching *continuous*: the
        window between two ticks automatically spans however many events
        the load delivered."""
        self._backpressure()
        return self._enqueue(lambda: self.service.enqueue(stream_id, a, b))

    def admit_op(self, stream_id: str, op) -> int:
        """Admit one structured (``repro.updates``) event; returns the token
        of its last lowered sub-event (visible = whole op applied)."""
        self._backpressure()
        return self._enqueue(lambda: self.service.enqueue_op(stream_id, op))

    def _enqueue(self, do):
        if self.continuous:
            # suppress the service's count-triggered autoflush: the window
            # seals on CAPACITY, not on fill (restored below so explicit
            # service.flush()/drain() calls keep their semantics)
            saved, self.service.max_batch = self.service.max_batch, 1 << 30
            try:
                return do()
            finally:
                self.service.max_batch = saved
        return do()

    def _backpressure(self) -> None:
        if self.max_backlog is None or not self.continuous:
            return
        if self.service.pending() < self.max_backlog:
            return
        with _obs.span("backpressure", **self.service._obs_labels):
            while self.service.pending() >= self.max_backlog:
                # blocked: the window is as deep as allowed — wait for the
                # oldest round, then seal, freeing FIFO space
                with self.service._lock:
                    if self.service._in_flight:
                        self.service._retire_oldest()
                        self.service.stats.backpressure_waits += 1
                if not self.pump():
                    break   # nothing dispatchable: bound is all queued ops

    # -- sealing ------------------------------------------------------------

    def pump(self, *, once: bool = False) -> int:
        """Seal rounds while the device has capacity and events are pending;
        returns the number of events dispatched.  Never blocks: when the
        in-flight buffer is full the window simply stays open (that is the
        continuous-batching admission the module doc describes).  This is
        the event-loop tick — callers with their own loop (the fleet, the
        benchmark driver) call it between arrivals."""
        # no span for a tick that can seal nothing: an open loop ticks far
        # more often than it seals, and such spans would fill the buffer
        if (not self.continuous or not self.service.pending()
                or not self.service.has_capacity()):
            return 0
        dispatched = 0
        with _obs.span("pump", **self.service._obs_labels) as sp:
            while self.service.pending() and self.service.has_capacity():
                if self.device is not None:
                    import jax

                    with jax.default_device(self.device):
                        n = self.service.flush_round(max_depth=self.max_depth)
                else:
                    n = self.service.flush_round(max_depth=self.max_depth)
                if n == 0:
                    break
                dispatched += n
                if once:
                    break
            sp.set(dispatched=dispatched)
        return dispatched

    def poll(self) -> list[int]:
        """Newly visible tokens (their rounds retired); non-blocking."""
        return self.service.take_visible()

    def drain(self) -> int:
        """Seal everything (deep rounds, retiring in-flight work as needed)
        and block until visible — the shutdown/snapshot barrier."""
        n = 0
        if self.continuous:
            while self.service.pending():
                d = self.pump()
                n += d
                if not d:
                    # in-flight buffer full: wait for the oldest round, then
                    # keep sealing (service.drain alone would seal depth-1)
                    with self.service._lock:
                        if not self.service._in_flight:
                            break
                        self.service._retire_oldest()
        return n + self.service.drain()
