"""repro.obs — metrics registry, span tracing and numerical health
(DESIGN.md §15).

Pinned here:
* registry semantics: typed series, labels, kind conflicts, aggregation;
* exporter goldens: exact Prometheus text and JSON for a small registry;
* zero overhead when disabled: obs on/off changes neither results (bitwise)
  nor jaxprs (equation-count equal) — instrumentation lives strictly
  outside traced code;
* snapshot/restore: registry rows ride ServiceSnapshot (v7) and
  FleetSnapshot (v8) through the aux JSON round trip;
* engine/planner cache counters mirror the public cache_info() numbers;
* the health watchdog warns (HealthWarning) on a drifted state and counts
  the trip;
* the host path's spans and counters: a round's spans reach a
  ``jax.profiler`` trace nested as recorded, ``id``/``parent``/``round``
  links, the dropped-span count, starved rounds, queue waits, and the
  fleet's enqueue timers.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import api, obs
from repro.api import SvdState, UpdatePolicy
from repro.core.engine import SvdEngine
from repro.obs import metrics as obs_metrics
from repro.serve.svd_service import SNAPSHOT_VERSION, ServiceSnapshot, SvdService
from repro.updates import RankK
from repro.updates.planner import lower, schedule_cache_info

RNG = np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _isolated_obs():
    """Fresh registry + disabled obs around every test (obs state is
    process-global by design; tests must not leak into each other)."""
    prev = obs_metrics.set_registry(obs_metrics.MetricsRegistry())
    obs.disable()
    obs.stop_tracing()
    obs.clear_trace()
    yield
    obs.stop_tracing()
    obs.clear_trace()
    obs.disable()
    obs_metrics.set_registry(prev)


def _state(m=12, n=9, rank=None, rng=RNG):
    dense = jnp.asarray(rng.standard_normal((m, n)))
    return SvdState.from_dense(dense, rank=rank if rank is not None else min(m, n))


def _event(m=12, n=9, rng=RNG):
    return (jnp.asarray(rng.standard_normal(m)),
            jnp.asarray(rng.standard_normal(n)))


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    reg = obs.registry()
    c = reg.counter("events")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert reg.counter("events") is c          # same handle per key

    g = reg.gauge("depth")
    g.set(3)
    g.max(7)
    g.max(2)                                    # running max keeps 7
    assert g.value == 7.0

    h = reg.histogram("lat", bounds=(1.0, 10.0))
    for x in (0.5, 5.0, 50.0):
        h.observe(x)
    assert h.count == 3
    assert h.sum == pytest.approx(55.5)
    assert h.value["counts"] == [1, 1, 1]       # one per bucket incl. +Inf


def test_kind_conflict_raises():
    reg = obs.registry()
    reg.counter("x")
    with pytest.raises(TypeError, match="already registered as counter"):
        reg.gauge("x")


def test_labels_make_independent_series_and_aggregate_sums():
    reg = obs.registry()
    reg.counter("applied", shard="0").inc(3)
    reg.counter("applied", shard="1").inc(4)
    assert reg.get("applied", shard="0").value == 3
    assert reg.get("applied") is None           # unlabeled series never made
    assert reg.aggregate("applied") == 7.0


# ---------------------------------------------------------------------------
# exporter goldens
# ---------------------------------------------------------------------------


def test_prometheus_export_golden():
    reg = obs.registry()
    reg.counter("flushes", shard="0").inc(2)
    reg.gauge("depth").set(3)
    h = reg.histogram("lat_us", bounds=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    golden = "\n".join([
        '# TYPE depth gauge',
        'depth 3',
        '# TYPE flushes_total counter',
        'flushes_total{shard="0"} 2',
        '# TYPE lat_us histogram',
        'lat_us_bucket{le="1"} 1',
        'lat_us_bucket{le="10"} 2',
        'lat_us_bucket{le="+Inf"} 2',
        'lat_us_sum 5.5',
        'lat_us_count 2',
    ]) + "\n"
    assert reg.to_prometheus() == golden


def test_json_export_golden():
    reg = obs.registry()
    reg.counter("flushes", shard="0").inc(2)
    reg.gauge("depth").set(3)
    rows = json.loads(reg.to_json())
    assert rows == [
        {"name": "depth", "labels": {}, "kind": "gauge", "value": 3.0},
        {"name": "flushes", "labels": {"shard": "0"}, "kind": "counter",
         "value": 2},
    ]


def test_registry_snapshot_restore_round_trip():
    reg = obs.registry()
    reg.counter("c", shard="2").inc(9)
    reg.gauge("g").set(1.5)
    h = reg.histogram("h", bounds=(1.0,))
    h.observe(0.5)
    h.observe(2.0)
    rows = reg.snapshot()
    # rows must be hashable: they ride pytree METADATA in ServiceSnapshot
    hash(rows)
    # the aux JSON round trip turns tuples into lists — restore accepts both
    rows_json = json.loads(json.dumps(rows))
    fresh = obs_metrics.MetricsRegistry()
    obs_metrics.set_registry(fresh)
    try:
        fresh.restore(rows_json)
        assert fresh.get("c", shard="2").value == 9
        assert fresh.get("g").value == 1.5
        assert fresh.get("h").value["counts"] == [1, 1]
    finally:
        obs_metrics.set_registry(reg)


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------


def test_span_disabled_is_shared_noop():
    s1 = obs.span("a", x=1)
    s2 = obs.span("b")
    assert s1 is s2                             # the singleton: no allocation
    with s1 as sp:
        sp.set(y=2)
    assert obs.trace_events() == []


def test_chrome_trace_shape():
    obs.start_tracing()
    with obs.span("outer", depth=2):
        with obs.span("inner") as sp:
            sp.set(batch=4)
    obs.stop_tracing()
    doc = json.loads(obs.chrome_trace())
    assert doc["displayTimeUnit"] == "ms"
    by_name = {e["name"]: e for e in doc["traceEvents"]}
    assert set(by_name) == {"outer", "inner"}
    for e in by_name.values():
        assert e["ph"] == "X"
        assert e["dur"] >= 0.0
    # spans link to the live span they opened in; the outermost has none
    outer_id = by_name["outer"]["args"]["id"]
    assert by_name["outer"]["args"] == {"depth": 2, "id": outer_id}
    assert by_name["inner"]["args"] == {"batch": 4, "id": outer_id + 1,
                                        "parent": outer_id}
    # inner nests inside outer on the monotonic clock
    assert by_name["outer"]["ts"] <= by_name["inner"]["ts"]
    assert (by_name["inner"]["ts"] + by_name["inner"]["dur"]
            <= by_name["outer"]["ts"] + by_name["outer"]["dur"] + 1e-3)


def test_span_feeds_duration_histogram_when_enabled():
    obs.enable()
    obs.start_tracing()
    with obs.span("flush_round"):
        pass
    obs.stop_tracing()
    h = obs.registry().get("span_duration_us", span="flush_round")
    assert h is not None and h.count == 1


# ---------------------------------------------------------------------------
# zero overhead when disabled
# ---------------------------------------------------------------------------


def test_disabled_obs_is_bitwise_and_jaxpr_invisible():
    pol = UpdatePolicy(method="direct")
    st = _state()
    a, b = _event()

    off = api.update(st, a, b, pol)
    n_off = len(jax.make_jaxpr(
        lambda u, s, v, aa, bb: api.update(SvdState(u, s, v), aa, bb, pol)
    )(st.u, st.s, st.v, a, b).eqns)

    obs.enable()
    obs.start_tracing()
    on = api.update(st, a, b, pol)
    n_on = len(jax.make_jaxpr(
        lambda u, s, v, aa, bb: api.update(SvdState(u, s, v), aa, bb, pol)
    )(st.u, st.s, st.v, a, b).eqns)
    obs.stop_tracing()

    # identical executable, identical result — obs never touches traced code
    assert n_on == n_off
    for name in ("u", "s", "v"):
        np.testing.assert_array_equal(np.asarray(getattr(on, name)),
                                      np.asarray(getattr(off, name)))


def test_disabled_sites_record_nothing():
    # a full service flush with obs disabled must leave the registry empty
    svc = SvdService(max_batch=2, policy=UpdatePolicy(method="direct"))
    svc.register("s0", _state())
    svc.enqueue("s0", *_event())
    svc.drain()
    assert obs.registry().series() == []
    assert obs.trace_events() == []


# ---------------------------------------------------------------------------
# engine / planner counters mirror cache_info
# ---------------------------------------------------------------------------


def test_engine_counters_match_cache_info():
    obs.enable()
    eng = SvdEngine()
    rng = np.random.default_rng(5)
    m, n = 6, 8                                # update_batch wants square u, v
    u = jnp.asarray(np.linalg.qr(rng.standard_normal((m, m)))[0])
    v = jnp.asarray(np.linalg.qr(rng.standard_normal((n, n)))[0])
    s = jnp.asarray(np.sort(np.abs(rng.standard_normal(m)))[::-1].copy())
    a, b = _event(m, n, rng)
    stack = (jnp.stack([u]), jnp.stack([s]), jnp.stack([v]),
             jnp.stack([a]), jnp.stack([b]))
    eng.update_batch(*stack)
    eng.update_batch(*stack)
    info = eng.cache_info()
    reg = obs.registry()
    assert reg.get("engine_plan_cache_misses").value == info.misses == 1
    assert reg.get("engine_plan_cache_hits").value == info.hits == 1


def test_planner_counters_match_schedule_cache_info():
    obs.enable()
    rng = np.random.default_rng(3)
    st = _state(10, 8, 4, rng)
    op = RankK(jnp.asarray(rng.standard_normal((10, 2))),
               jnp.asarray(rng.standard_normal((8, 2))))
    before = schedule_cache_info()
    lower(op, st)
    lower(op, st)
    after = schedule_cache_info()
    reg = obs.registry()
    hits = getattr(reg.get("planner_schedule_cache_hits"), "value", 0)
    misses = getattr(reg.get("planner_schedule_cache_misses"), "value", 0)
    assert hits == after.hits - before.hits >= 1
    assert misses == after.misses - before.misses


# ---------------------------------------------------------------------------
# snapshot plumbing: registry rows ride service / fleet snapshots
# ---------------------------------------------------------------------------


def test_service_snapshot_round_trips_obs_rows():
    obs.enable()
    pol = UpdatePolicy(method="direct", health_every=1)
    svc = SvdService(max_batch=2, policy=pol)
    svc.register("s0", _state())
    svc.enqueue("s0", *_event())
    svc.drain()
    snap = svc.snapshot()
    assert snap.version == SNAPSHOT_VERSION == 7
    assert snap.obs_metrics                    # rows captured while enabled

    # aux JSON round trip (what checkpoint save/load does to metadata)
    snap2 = ServiceSnapshot.skeleton(snap.aux())
    assert snap2.obs_metrics == snap.obs_metrics
    hash(snap2.obs_metrics)                    # still pytree-metadata safe

    applied = obs.registry().get("serve_applied").value
    obs_metrics.set_registry(obs_metrics.MetricsRegistry())
    svc2 = SvdService.from_snapshot(snap)
    assert obs.registry().get("serve_applied").value == applied
    assert svc2.stats.applied == svc.stats.applied


def test_fleet_snapshot_round_trips_obs_rows():
    from repro.fleet.fleet import FLEET_SNAPSHOT_VERSION, SvdFleet

    obs.enable()
    fleet = SvdFleet(num_shards=2, policy=UpdatePolicy(method="direct"),
                     max_batch=2)
    rng = np.random.default_rng(7)
    for i in range(4):
        fleet.register(f"f{i}", _state(10, 7, 3, rng))
    for i in range(4):
        fleet.enqueue(f"f{i}", *_event(10, 7, rng))
    fleet.drain()
    fleet.stats()                              # publishes fleet_* gauges
    snap = fleet.snapshot()
    assert snap.version == FLEET_SNAPSHOT_VERSION == 8

    per_shard = obs.registry().get("serve_applied", shard="0")
    assert per_shard is not None
    total = obs.registry().aggregate("serve_applied")

    obs_metrics.set_registry(obs_metrics.MetricsRegistry())
    SvdFleet.from_snapshot(snap)
    assert obs.registry().aggregate("serve_applied") == total


def test_old_snapshot_without_obs_rows_still_loads():
    svc = SvdService(max_batch=2, policy=UpdatePolicy(method="direct"))
    svc.register("s0", _state())
    svc.drain()
    aux = svc.snapshot().aux()
    del aux["obs_metrics"]                     # what a v5-era aux looks like
    snap = ServiceSnapshot.skeleton(aux)
    assert snap.obs_metrics == ()


# ---------------------------------------------------------------------------
# serve wiring: spans + stats gauges + health sampling
# ---------------------------------------------------------------------------


def test_serve_flush_emits_spans_and_stats_gauges():
    obs.enable()
    obs.start_tracing()
    svc = SvdService(max_batch=2, policy=UpdatePolicy(method="direct",
                                                      health_every=1))
    svc.register("s0", _state())
    svc.register("s1", _state())
    for _ in range(2):
        svc.enqueue("s0", *_event())
        svc.enqueue("s1", *_event())
    svc.drain()
    obs.stop_tracing()

    names = {e["name"] for e in obs.trace_events()}
    assert {"flush_round", "dispatch"} <= names
    reg = obs.registry()
    assert reg.get("serve_applied").value == svc.stats.applied == 4
    for probe in ("health_ortho_drift", "health_secular_residual",
                  "health_deflation_fraction", "health_bf16_headroom"):
        assert reg.get(probe) is not None, probe


def test_health_watchdog_warns_and_counts_on_drifted_state():
    obs.enable()
    rng = np.random.default_rng(11)
    st = _state(10, 8, 4, rng)
    drifted_u = st.u * 1.05                    # ||UᵀU - I|| ≈ 0.1 >> 1e-3
    mon = obs.HealthMonitor(every=1)
    with pytest.warns(obs.HealthWarning, match="ortho_drift"):
        mon.sample_state(drifted_u, st.s, st.v)
    warned = obs.registry().get("health_warnings_total", probe="ortho_drift")
    assert warned is not None and warned.value == 1


def test_healthy_state_does_not_warn():
    import warnings

    obs.enable()
    st = _state(10, 8, 4)
    mon = obs.HealthMonitor(every=1)
    with warnings.catch_warnings():
        warnings.simplefilter("error", obs.HealthWarning)
        mon.sample_state(st.u, st.s, st.v)
    assert obs.registry().get("health_ortho_drift").value < 1e-6


def test_probe_update_on_exact_update_is_clean():
    pol = UpdatePolicy(method="direct")
    rng = np.random.default_rng(13)
    st = _state(12, 9, rng=rng)                # full-rank: update is exact
    a, b = _event(12, 9, rng)
    out = api.update(st, a, b, pol)
    rep = obs.probe_update(st.u, st.s, st.v, a, b, out.u, out.s, out.v)
    assert rep.ortho_drift < 1e-8
    assert rep.secular_residual < 1e-6
    assert 0.0 <= rep.deflation_fraction <= 1.0
    assert rep.bf16_headroom > 0.0


def test_health_every_cadence():
    obs.enable()
    mon = obs.HealthMonitor(every=3)
    # samples on every 3rd flush tick
    assert [mon.due() for _ in range(7)] == [
        False, False, True, False, False, True, False]


# ---------------------------------------------------------------------------
# the host path: round spans, links, counters
# ---------------------------------------------------------------------------


@pytest.fixture
def rounds_stay_in_flight(monkeypatch):
    """Dispatched rounds never read as finished, so they leave the
    in-flight buffer only through ``_retire_oldest`` (the ``reap`` span),
    however fast the CPU backend computes them."""
    from repro.serve import svd_service

    monkeypatch.setattr(svd_service, "_is_ready", lambda x: False)


def _two_stream_service(**kw):
    svc = SvdService(max_batch=4, policy=UpdatePolicy(method="direct"), **kw)
    for sid in ("s0", "s1"):
        svc.register(sid, _state())
    return svc


def _enqueue_both(svc):
    for sid in ("s0", "s1"):
        svc.enqueue(sid, *_event())


def test_round_spans_reach_the_profiler_nested(tmp_path):
    from jax.profiler import ProfileData

    svc = _two_stream_service()
    _enqueue_both(svc)
    obs.start_tracing()
    with jax.profiler.trace(str(tmp_path)):
        svc.flush_round()
        svc.drain()
    obs.stop_tracing()

    found = sorted(tmp_path.rglob("*.xplane.pb"))
    assert found
    spans = {}
    for plane in ProfileData.from_file(str(found[-1])).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    spans.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    names = ("repro.flush_round", "repro.assemble", "repro.dispatch",
             "repro.writeback")
    for name in names:
        assert len(spans.get(name, [])) == 1, (name, sorted(spans))
    (f0, f1), *children = (spans[name][0] for name in names)
    end = f0
    for c0, c1 in children:                     # in order, inside the round
        assert end <= c0 <= c1 <= f1
        end = c1


def test_round_spans_link_to_their_flush_round():
    svc = _two_stream_service()
    _enqueue_both(svc)
    obs.start_tracing()
    svc.flush_round()
    obs.stop_tracing()
    evs = obs.trace_events()
    (flush,) = [e for e in evs if e["name"] == "flush_round"]
    assert "parent" not in flush["args"]
    for name in ("assemble", "dispatch", "writeback"):
        (child,) = [e for e in evs if e["name"] == name]
        assert child["args"]["parent"] == flush["args"]["id"], name
    ids = [e["args"]["id"] for e in evs]
    assert len(set(ids)) == len(ids)


def test_reap_carries_the_round_it_retires(rounds_stay_in_flight):
    svc = _two_stream_service(max_in_flight=1)
    obs.start_tracing()
    _enqueue_both(svc)
    svc.flush_round()
    _enqueue_both(svc)
    svc.flush_round()                           # backpressure retires round 0
    svc.drain()                                 # the barrier retires round 1
    obs.stop_tracing()
    evs = obs.trace_events()
    rounds = [e["args"]["round"] for e in evs if e["name"] == "flush_round"]
    reaps = [e["args"]["round"] for e in evs if e["name"] == "reap"]
    assert rounds == [0, 1]
    assert reaps == [0, 1]


def test_trace_buffer_overflow_counts_dropped_spans(monkeypatch):
    from repro.obs import trace as obs_trace

    monkeypatch.setattr(obs_trace, "_MAX_EVENTS", 2)
    obs.start_tracing()
    for _ in range(5):
        with obs.span("tick"):
            pass
    obs.stop_tracing()
    assert len(obs.trace_events()) == 2
    assert obs.registry().get("trace_spans_dropped").value == 3


def test_pump_that_seals_nothing_records_no_span(rounds_stay_in_flight):
    from repro.fleet.frontend import ContinuousBatcher

    svc = _two_stream_service(max_in_flight=1)
    batcher = ContinuousBatcher(svc, max_depth=1)
    obs.start_tracing()
    assert batcher.pump() == 0                  # nothing pending
    _enqueue_both(svc)
    assert batcher.pump() == 2                  # seals one round
    _enqueue_both(svc)
    assert batcher.pump() == 0                  # pending, but no capacity
    obs.stop_tracing()
    pumps = [e for e in obs.trace_events() if e["name"] == "pump"]
    assert [e["args"]["dispatched"] for e in pumps] == [2]


def test_starved_rounds_count_rounds_with_nothing_in_flight(rounds_stay_in_flight):
    obs.enable()
    svc = _two_stream_service(max_in_flight=2)
    _enqueue_both(svc)
    svc.flush_round()                           # nothing in flight: starved
    _enqueue_both(svc)
    svc.flush_round()                           # round 0 still in flight
    svc.drain()
    _enqueue_both(svc)
    svc.flush_round()                           # first after the drain: starved
    assert svc.stats.flushes == 3
    assert obs.registry().get("starved_rounds").value == 2


def test_queue_wait_counts_every_sealed_event():
    obs.enable()
    svc = _two_stream_service()
    rng = np.random.default_rng(17)
    for _ in range(3):
        _enqueue_both(svc)
    svc.enqueue_op("s0", RankK(jnp.asarray(rng.standard_normal((12, 2))),
                               jnp.asarray(rng.standard_normal((9, 2)))))
    svc.register("s2", _state())
    svc.enqueue("s2", *_event())
    svc.evict("s2")                             # applied outside a round
    svc.drain()
    hist = obs.registry().get("queue_wait_us")
    # 6 pairs + the op's 2 sealed in rounds; the evicted one was not sealed
    assert svc.stats.applied == 9
    assert hist.count == 8
    assert hist.sum > 0.0
    assert svc._enqueued_ns == {}               # no stamp outlives its event


def test_fleet_enqueue_timers():
    from repro.fleet.fleet import SvdFleet

    obs.enable()
    fleet = SvdFleet(1, policy=UpdatePolicy(method="direct"), devices="auto",
                     max_batch=4)
    fleet.register("f0", _state())
    for _ in range(3):
        fleet.enqueue("f0", *_event())
    fleet.drain()
    reg = obs.registry()
    assert reg.get("enqueue_timed", shard="0").value == 3
    enqueue_ns = reg.get("enqueue_host_ns", shard="0").value
    place_ns = reg.get("place_host_ns", shard="0").value
    assert enqueue_ns >= place_ns > 0
    assert reg.get("queue_wait_us", shard="0").count == 3


def test_disabled_fleet_path_records_nothing():
    from repro.fleet.fleet import SvdFleet

    fleet = SvdFleet(1, policy=UpdatePolicy(method="direct"), devices="auto",
                     max_batch=4)
    for sid in ("f0", "f1"):
        fleet.register(sid, _state())
        fleet.enqueue(sid, *_event())
    fleet.pump()
    fleet.drain()
    assert obs.registry().series() == []
    assert obs.trace_events() == []
    assert fleet.shards[0].service._enqueued_ns == {}
    assert obs.span("flush_round", round=0) is obs.span("assemble")
