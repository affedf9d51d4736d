"""Find a cell's configuration, traffic mix and metric readers by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
files live at fixed places under ``bench/``:

* ``bench/configs/<config>.json`` (the path ``BENCHMARK.json`` gives),
* ``bench/mixes/<traffic>.json``,
* ``bench/metrics/<metric>.py``, one reader per per-layer metric, with a
  function ``read(run) -> float | None``.

A later change adds a cell, configuration, mix or metric by adding files
and entries; nothing here names one.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def load_reader(path: Path):
    """The ``read`` function of a metric reader file."""
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + path.stem.replace(".", "_").replace("-", "_"), path)
    if mod_spec is None or mod_spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell_spec(name: str, root: Path = ROOT) -> dict:
    """Everything one run of cell ``name`` needs, loaded from its files."""
    root = Path(root)
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    mix = json.loads((root / "bench" / "mixes" / f"{cell['traffic']}.json").read_text())
    end_to_end = [m for m in bench["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    readers = {m["name"]: load_reader(root / "bench" / "metrics" / f"{m['name']}.py")
               for m in per_layer}
    return {"cell": cell, "config": config, "mix": mix, "end_to_end": end_to_end,
            "per_layer": per_layer, "readers": readers}
