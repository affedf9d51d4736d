"""Readings that set a cell's limits and its open-loop rate, on the chip.

    python3 bench/calibrate.py limits --workload W --seconds S --seeds 1,2,3 \
        [--control 3]
    python3 bench/calibrate.py chains --workload W --seeds 1,2 \
        --checkpoints 64,256,1024,2048 [--control 2]
    python3 bench/calibrate.py sweep --workload W --seconds S --seed 5 \
        --rates 1000,2000,3000

``limits`` runs the cell once per seed in one process and prints, per seed,
the program's numbers as ``harness.check`` compares them (the lower reading
of each limit) and, for the first ``--control`` seeds, the control's: the
reference at ``high`` precision put in the program's place, over the same
streams and events, judged by the same ``harness.check`` against the
configuration's limits (the upper reading).  Each sampled stream's chain
length and raw readings are printed beside them.

``chains`` sets the cell up as a run does, then drives only its sampled
streams, through the same fleet, to each checkpoint's chain length, and
judges the program (and the control) at every checkpoint: the readings of
chains longer than a window reaches today, as a faster program would make
them.

``sweep`` runs an open-loop cell at each rate and prints its latency and
the median latency of each quarter of the window: a backlog that grows
shows as quarters that climb.  The highest rate whose quarters stay flat is
the knee.

One JSON object per line on standard output; nothing is written to disk.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _control(gen, picked, config):
    """The control put in the program's place for the sampled streams,
    judged by ``harness.check``."""
    from bench import harness, reference

    def state_of(i):
        return reference.control_state(*gen.seed_state(i), *gen.events_of(i))

    ok, checks, rows = harness.check(state_of, gen, config, picked, 0)
    return {"correct": ok, "checks": checks, "rows": rows}


def limits(spec, args, devices):
    import numpy as np

    from bench import harness

    for n, seed in enumerate(args.seeds):
        keep = {}
        res = harness.run_cell(spec, seed, args.seconds, False, devices,
                               time.perf_counter(), keep=keep)
        out = {"seed": seed, "correct": res["correct"], "checks": res["checks"],
               "rows": keep["rows"], "metrics": res["metrics"],
               "chain": int(np.max(keep["gen"].count))}
        if n < args.control:
            keep["fleet"] = None
            out["control"] = _control(keep["gen"], keep["picked"], spec["config"])
        print(json.dumps(out), flush=True)
        keep.clear()


def chains(spec, args, devices):
    import jax

    from bench import harness, reference, traffic

    config, mix = spec["config"], spec["mix"]
    for n, seed in enumerate(args.seeds):
        with jax.default_matmul_precision(config["matmul_precision"]):
            gen = traffic.event_model(config, seed)
            fleet = harness.build_fleet(config)
            ids = harness.register_streams(fleet, *gen.device_init())
            harness.warm_up(fleet, gen, ids, traffic.warm_rounds(mix, config))
            picked = traffic.sample_streams(gen.count, config["sample_streams"], seed)
            control = {i: gen.seed_state(i) for i in picked} if n < args.control else None
            applied = dict.fromkeys(picked, 0)      # events in each control state
            for target in args.checkpoints:
                done = {i: int(gen.count[i]) for i in picked}
                for i in picked:
                    if target > done[i]:
                        a, b = gen.next(i, target - done[i])
                        for t in range(len(a)):
                            fleet.enqueue(ids[i], a[t], b[t])
                fleet.pump()
                fleet.drain()
                fleet.poll()
                ok, checks, rows = harness.check(harness.program_state(fleet, ids),
                                                 gen, config, picked, 0)
                out = {"seed": seed, "checkpoint": target, "correct": ok,
                       "checks": checks, "rows": rows}
                if control is not None:
                    for i in picked:
                        a, b = gen.events_of(i)
                        if len(a) > applied[i]:
                            control[i] = reference.control_state(
                                *control[i], a[applied[i]:], b[applied[i]:])
                            applied[i] = len(a)
                    ok, checks, rows = harness.check(control.get, gen, config, picked, 0)
                    out["control"] = {"correct": ok, "checks": checks, "rows": rows}
                print(json.dumps(out), flush=True)
        del fleet, gen


def sweep(spec, args, devices):
    import numpy as np

    from bench import harness, stats

    warm = spec["mix"]["warm"]
    for n, rate in enumerate(args.rates):
        # one warm-up per process: later rates find every program built
        spec["mix"] = dict(spec["mix"], rate_per_s=rate,
                           warm=warm if n == 0 else [{"depth": 1, "widths": [1, 1]}])
        keep = {}
        res = harness.run_cell(spec, args.seed, args.seconds, False, devices,
                               time.perf_counter(), keep=keep)
        loop = keep["loop"]
        lat = np.where(np.isnan(loop["latency_s"]), np.inf, loop["latency_s"])
        quarters = [1e3 * stats.percentile(list(q), 50) for q in np.array_split(lat, 4)]
        st0, st1 = keep["stats"]
        print(json.dumps({
            "rate_per_s": rate, "correct": res["correct"], "failed": res["failed"],
            "p50_ms": 1e3 * stats.percentile(list(lat), 50),
            "p99_ms": 1e3 * stats.percentile(list(lat), 99),
            "p50_ms_by_quarter": quarters,
            "late_p99_ms": 1e3 * stats.percentile(list(loop["late_s"]), 99),
            "events_per_round": (st1["applied"] - st0["applied"])
            / max(1, st1["flushes"] - st0["flushes"]),
            "max_depth": st1["max_depth"], "metrics": res["metrics"]}), flush=True)
        keep.clear()


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("mode", choices=("limits", "chains", "sweep"))
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seconds", type=float)
    parser.add_argument("--seeds", type=lambda s: [int(x) for x in s.split(",")])
    parser.add_argument("--seed", type=int)
    parser.add_argument("--control", type=int, default=0)
    parser.add_argument("--rates", type=lambda s: [float(x) for x in s.split(",")])
    parser.add_argument("--checkpoints", type=lambda s: [int(x) for x in s.split(",")])
    args = parser.parse_args(argv)

    import jax

    from bench.spec import cell_spec
    from repro.api import enable_compilation_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"calibrate.py: no TPU (JAX platform {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    enable_compilation_cache(ROOT / ".jax_cache")
    spec = cell_spec(args.workload, ROOT)
    modes = {"limits": limits, "chains": chains, "sweep": sweep}
    modes[args.mode](spec, args, devices[:spec["cell"]["chips"]])
    return 0


if __name__ == "__main__":
    sys.exit(main())
