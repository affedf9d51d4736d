"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload trackers-4096-r32.backlog --seed 7 \
        --seconds 10 --trace 0

The cell, its configuration, traffic mix and metric readers are found by
name from ``BENCHMARK.json``.  Exits nonzero, printing no result, where JAX
finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from bench.spec import cell_spec

    spec = cell_spec(args.workload, ROOT)
    import jax

    import repro.fleet

    if not Path(repro.fleet.__file__).resolve().is_relative_to(ROOT / "src"):
        print(f"run.py: the program is not in this checkout ({repro.fleet.__file__})",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    chips = spec["cell"]["chips"]
    if devices[0].platform != "tpu":
        print(f"run.py: no TPU (JAX platform {devices[0].platform!r})", file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"run.py: the cell needs {chips} chips; JAX found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.api import enable_compilation_cache

    from bench.harness import run_cell

    enable_compilation_cache(ROOT / ".jax_cache")   # JAX_COMPILATION_CACHE_DIR wins
    result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                      devices[:chips], T_START)
    for name, (value, limit) in result["checks"].items():
        print(f"check {name}: {value!r} (limit {limit!r})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
