"""The readers of the program's host-path spans and counters, on synthetic
run records and a fresh registry: the number each metric reports, and None
where the program recorded nothing."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec as bench_spec  # noqa: E402
from repro.obs import metrics as obs_metrics  # noqa: E402


def _reader(metric):
    return bench_spec.load_reader(ROOT / "bench" / "metrics" / f"{metric}.backlog.py")


@pytest.fixture
def reg():
    fresh = obs_metrics.MetricsRegistry()
    prev = obs_metrics.set_registry(fresh)
    yield fresh
    obs_metrics.set_registry(prev)


def _span(name, ts, dur, sid, parent=None):
    args = {"id": sid}
    if parent is not None:
        args["parent"] = parent
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "pid": 1, "tid": 7,
            "args": args}


def _run(spans=(), flushes=None):
    stats = {"applied": 0, "rounds": 0}
    if flushes is not None:
        stats["flushes"] = flushes
    return {"spans": list(spans), "stats": stats, "trace": None,
            "config": {"m": 8, "n": 8, "rank": 2}, "device_kind": "cpu",
            "late_s": None}


def _rounds():
    # two rounds (µs); the first round's assemble holds a nested span of
    # 100 µs, which its self time leaves out
    return [
        _span("flush_round", 0, 5000, 1),
        _span("assemble", 10, 2000, 2, parent=1),
        _span("schedule_compile", 20, 100, 3, parent=2),
        _span("dispatch", 2020, 500, 4, parent=1),
        _span("writeback", 2530, 400, 5, parent=1),
        _span("flush_round", 6000, 4000, 6),
        _span("assemble", 6010, 1300, 7, parent=6),
        _span("dispatch", 7320, 500, 8, parent=6),
        _span("writeback", 7830, 200, 9, parent=6),
    ]


def test_span_readers(reg):
    run = _run(_rounds())
    assert _reader("assemble_host_ms")(run) == pytest.approx((1900 + 1300) / 2 / 1e3)
    assert _reader("writeback_host_ms")(run) == pytest.approx((400 + 200) / 2 / 1e3)


@pytest.mark.parametrize("metric", ["assemble_host_ms", "writeback_host_ms"])
def test_span_readers_read_nothing_without_their_spans(reg, metric):
    name = metric.split("_")[0]
    assert _reader(metric)(_run()) is None
    no_rounds = [e for e in _rounds() if e["name"] != "flush_round"]
    assert _reader(metric)(_run(no_rounds)) is None
    # a program that records rounds but not this stage (the parent's spans)
    old = [e for e in _rounds() if e["name"] != name]
    assert _reader(metric)(_run(old)) is None


def test_counter_readers_sum_over_shards(reg):
    for shard, (enq, place, timed) in {"0": (3_000_000, 2_000_000, 2),
                                       "1": (1_000_000, 600_000, 2)}.items():
        reg.counter("enqueue_host_ns", shard=shard).inc(enq)
        reg.counter("place_host_ns", shard=shard).inc(place)
        reg.counter("enqueue_timed", shard=shard).inc(timed)
        reg.counter("starved_rounds", shard=shard).inc(3)
    for x in (1000.0, 3000.0, 8000.0):
        reg.histogram("queue_wait_us", shard="0").observe(x)
    reg.histogram("queue_wait_us", shard="1").observe(4000.0)
    run = _run(flushes=8)
    assert _reader("enqueue_host_us")(run) == pytest.approx(1000.0)
    assert _reader("place_host_us")(run) == pytest.approx(650.0)
    assert _reader("queue_wait_ms")(run) == pytest.approx(4.0)
    assert _reader("starved_rounds")(run) == pytest.approx(75.0)


def test_starved_rounds_reads_zero_when_made_but_never_counted(reg):
    reg.counter("starved_rounds", shard="0")
    assert _reader("starved_rounds")(_run(flushes=5)) == 0.0


COUNTER_METRICS = ["enqueue_host_us", "place_host_us", "queue_wait_ms", "starved_rounds"]


@pytest.mark.parametrize("metric", COUNTER_METRICS)
def test_counter_readers_read_nothing_without_their_series(reg, metric):
    assert _reader(metric)(_run(flushes=4)) is None


@pytest.mark.parametrize("metric", COUNTER_METRICS)
def test_counter_readers_read_nothing_from_an_empty_window(reg, metric):
    for name in ("enqueue_host_ns", "place_host_ns", "enqueue_timed", "starved_rounds"):
        reg.counter(name, shard="0")
    reg.histogram("queue_wait_us", shard="0")
    assert _reader(metric)(_run(flushes=0)) is None
