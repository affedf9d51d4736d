"""Every cell of BENCHMARK.json loads by name, with its configuration, mix
and metric readers, so a change that adds files is checked without a chip."""

import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec as bench_spec  # noqa: E402
from bench import traffic  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_benchmark_file_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for path in BENCH["paths"]:
        assert (ROOT / path).is_dir()


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_by_name(cell):
    spec = bench_spec.cell_spec(cell, ROOT)
    config, mix = spec["config"], spec["mix"]
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec["per_layer"], "every cell reports a per-layer metric"
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert callable(spec["readers"][m["name"]])
    assert config["events"]["model"] in traffic.EVENT_MODELS
    assert set(config["limits"]) == {"failed_events", "recon_rel_per_event",
                                     "sigma_rel_per_event"}
    assert config["matmul_precision"] in ("highest", "high", "default")
    assert mix["loop"] in ("open", "closed")
    rounds = traffic.warm_rounds(mix, config)
    assert rounds and all(1 <= w <= config["streams"] for _, w in rounds)
    assert all(d <= config["service"]["max_depth"] for d, _ in rounds)
    entry = next(c for c in BENCH["configs"] if c["name"] == spec["cell"]["config"])
    assert entry["source"] == config["source"] and entry["reduced"] == config["reduced"]


def test_readers_return_none_where_nothing_is_read():
    empty = {"spans": [], "stats": {"applied": 0, "rounds": 0}, "trace": None,
             "config": {"m": 8, "n": 8, "rank": 2}, "device_kind": "cpu", "late_s": None}
    for m in BENCH["per_layer"]:
        read = bench_spec.load_reader(ROOT / "bench" / "metrics" / f"{m['name']}.py")
        assert read(empty) is None
