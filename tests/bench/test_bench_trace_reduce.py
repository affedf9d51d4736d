"""The trace reducer: busy union, idle share, device time per executable
and per op, idle gaps named by the harness's annotations."""

import gzip
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace_reduce  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
D0, D1 = "/device:TPU:0", "/device:TPU:1"
OPS, MODS = trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE


def _hand_trace():
    # window 1000..2000 ns; device 0 ops overlap (100..300 with 200..400 in
    # the window's frame) and one op sticks out past the window's end
    return {
        "host": [["bench.window", 1000.0, 1000.0],
                 ["bench.pump", 1400.0, 300.0],
                 ["bench.enqueue", 1450.0, 50.0]],
        "device": [
            [D0, OPS, "fusion.1", 1100.0, 200.0],
            [D0, OPS, "fusion.2", 1200.0, 200.0],
            [D0, OPS, "custom-call", 1800.0, 400.0],
            [D0, MODS, "jit_update", 1100.0, 300.0],
            [D1, OPS, "fusion.1", 1000.0, 100.0],
            ["/host:CPU", OPS, "ignored", 1000.0, 1000.0],
        ],
    }


def test_hand_trace():
    trace = _hand_trace()
    trace["device"] = [d for d in trace["device"] if d[0].startswith("/device")]
    red = trace_reduce.reduce(trace)
    # device 0 busy 300 (1100..1400) + 200 (1800..2000); device 1 busy 100
    assert red["busy_s"] == pytest.approx((500 + 100) / 2 / 1e9)
    assert red["window_s"] == pytest.approx(1e-6)
    assert red["idle_share"] == pytest.approx(1 - 300 / 1000)
    assert red["module_s"] == {"jit_update": pytest.approx(3e-7)}
    ops = dict(red["device_ops"])
    assert ops["fusion.1"] == pytest.approx(3e-7) and ops["custom-call"] == pytest.approx(2e-7)
    # gaps of device 0: 1400..1800 (inside the pump, not the enqueue), 1000..1100
    assert red["idle_gaps"][0] == ["bench.pump", pytest.approx(4e-7)]
    assert red["idle_gaps"][1] == ["bench.none", pytest.approx(1e-7)]


def test_no_window_or_no_device_op_reads_nothing():
    trace = _hand_trace()
    assert trace_reduce.reduce({"host": trace["host"][1:], "device": trace["device"]}) is None
    assert trace_reduce.reduce({"host": trace["host"], "device": []}) is None


def _busy_by_sweep(trace):
    """Busy time of device 0 in the window by an independent sweep over
    sorted interval edges (count of open ops > 0)."""
    (w0, wdur), = [(h[1], h[2]) for h in trace["host"] if h[0] == "bench.window"]
    edges = []
    for plane, line, _, start, dur in trace["device"]:
        if plane == D0 and line == OPS:
            s, e = max(start, w0), min(start + dur, w0 + wdur)
            if e > s:
                edges += [(s, 1), (e, -1)]
    busy, depth, last = 0.0, 0, None
    for x, step in sorted(edges, key=lambda t: (t[0], -t[1])):
        if depth > 0:
            busy += x - last
        depth += step
        last = x
    return busy / 1e9


def test_recorded_chip_trace():
    """A traced window of the backlog cell recorded on a TPU v5e (8 s,
    two 2048-event rounds), reduced to the events the reducer reads."""
    trace = json.loads(gzip.decompress((DATA / "trace_backlog.json.gz").read_bytes()))
    red = trace_reduce.reduce(trace)
    expect = json.loads((DATA / "trace_backlog.expect.json").read_text())
    assert red["busy_s"] == pytest.approx(_busy_by_sweep(trace), rel=1e-9)
    assert red["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-12)
    assert red["window_s"] == pytest.approx(expect["window_s"], rel=1e-12)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert [name for name, _ in red["device_ops"]] == expect["top_ops"]
    # the update executable holds most of the busy time; the Pallas kernel
    # runs inside its scan loop
    assert red["module_s"]["jit_fn"] > 0.8 * red["busy_s"]
    assert "fused_update_truncated_pallas_batched.3" in expect["top_ops"]
    assert len(red["idle_gaps"]) == 10
    assert red["idle_gaps"][0][0] == "bench.enqueue"
