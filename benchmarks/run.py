"""Benchmark harness: one module per paper table/figure + framework benches.

Prints ``name,us_per_call,derived`` CSV rows. Roofline terms for the
(arch x shape x mesh) grid come from the dry-run artifacts
(benchmarks/dryrun/*.json, generated by repro.launch.dryrun) and are
summarized at the end when present.
"""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path


def _summarize_dryrun() -> None:
    d = Path(__file__).parent / "dryrun"
    if not d.exists():
        return
    for f in sorted(d.glob("*.json")):
        r = json.loads(f.read_text())
        rt = r["roofline"]
        dom = max(
            ("compute", rt["t_compute_s"]),
            ("memory", rt["t_memory_s"]),
            ("collective", rt["t_collective_s"]),
            key=lambda kv: kv[1],
        )
        name = f"dryrun/{r['arch']}/{r['shape']}/{r['mesh']}"
        if r.get("method", "baseline") != "baseline":
            name += f"/{r['method']}"
        us = dom[1] * 1e6
        print(f"{name},{us:.1f},bottleneck={dom[0]}")


_IN_PROCESS = ("fig1_2_runtime", "fig3_error_vs_p", "table2_accuracy",
               "table1_complexity", "framework_bench", "bench_engine",
               "bench_serve", "bench_updates")


def main() -> None:
    """Run every bench in a child process of its own: this parent never
    touches JAX, so a child that needs the accelerator can take it.
    ``--cpu-mesh`` is passed on to bench_dist/bench_fleet (their 8-fake-
    device CPU rehearsal)."""
    root = Path(__file__).parent.parent
    print("name,us_per_call,derived", flush=True)
    for name in _IN_PROCESS:
        code = ("import jax; jax.config.update('jax_enable_x64', True); "
                f"from benchmarks import {name}; {name}.run()")
        subprocess.run([sys.executable, "-c", code], cwd=root, check=True)
    extra = ["--cpu-mesh"] if "--cpu-mesh" in sys.argv[1:] else []
    for mod in ("benchmarks.bench_dist", "benchmarks.bench_fleet"):
        subprocess.run([sys.executable, "-m", mod, *extra], cwd=root, check=True)
    _summarize_dryrun()


if __name__ == "__main__":
    main()
