"""The harness's ``correct`` at a size a test run can hold, on the CPU: a
sound run passes, the control (the reference at ``high`` precision in the
program's place) fails, and so does a run with the timed path broken
underneath.  The look for a chip is ``bench/run.py``'s; this drives the
rest of a run (``harness.run_cell``), judged by the drift configuration's
own limits.  Rank 32, the cell's rank, is what makes the control's error
per event exceed them; at rank 8 it stays below."""

import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import harness, reference  # noqa: E402
from repro.core import engine as engine_mod  # noqa: E402
from repro.core.svd_update import TruncatedSvd  # noqa: E402

SEED = 2**32 + 11
LIMITS = json.loads((ROOT / "bench/configs/drift-4096-r32.json").read_text())["limits"]


def _spec(loop="closed"):
    config = {"name": "tiny-drift", "streams": 4, "m": 256, "n": 192, "rank": 32,
              "dtype": "float32", "matmul_precision": "highest", "events": {"model": "drift", "seed_scale": 100},
              "service": {"shards": 1, "method": "auto", "max_batch": 4,
                          "max_in_flight": 2, "max_depth": 4},
              "sample_streams": 4, "limits": dict(LIMITS)}
    if loop == "closed":
        mix = {"loop": "closed", "outstanding": 8,
               "warm": [{"depth": "max_depth", "widths": ["streams", "streams"]}]}
        e2e = [{"name": "setup_s", "unit": "s"}, {"name": "events_per_s", "unit": "events/s"}]
    else:
        mix = {"loop": "open", "rate_per_s": 100, "zipf": 0.99,
               "warm": [{"depth": 1, "widths": [1, "streams"]}, {"depth": 2, "widths": [1, 2]},
                        {"depth": 4, "widths": [1, 1]}]}
        e2e = [{"name": "setup_s", "unit": "s"}, {"name": "visible_p99_ms", "unit": "ms"},
               {"name": "visible_p50_ms", "unit": "ms"}]
    return {"cell": {"name": f"tiny-drift.{loop}", "chips": 1}, "config": config,
            "mix": mix, "end_to_end": e2e, "per_layer": [], "readers": {}}


def _run(keep=None, seconds=1.0, loop="closed", trace=False):
    return harness.run_cell(_spec(loop), SEED, seconds, trace, jax.devices()[:1],
                            time.perf_counter(), keep=keep)


def _broken(fault):
    """Wrap the engine's batched truncated updates with ``fault``."""
    def wrap(orig):
        def call(self, tsvd, a, b, **kw):
            return fault(tsvd, orig(self, tsvd, a, b, **kw))
        return call
    return wrap


def _unchanged(before, after):
    return before


def _half_batch(before, after):
    keep = jnp.arange(after.u.shape[0]) < after.u.shape[0] // 2
    pick = lambda x, y: jnp.where(keep.reshape((-1,) + (1,) * (x.ndim - 1)), x, y)
    return TruncatedSvd(*(pick(x, y) for x, y in zip(after, before)))


def _altered(before, after):
    return TruncatedSvd(after.u, after.s * (1 + 1e-3), after.v)


def test_sound_run_is_correct_and_the_control_is_not():
    keep = {}
    res = _run(keep, seconds=4.0)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res["checks"]) == list(LIMITS)
    gen = keep["gen"]

    def control(i):
        return reference.control_state(*gen.seed_state(i), *gen.events_of(i))

    ok, checks, _ = harness.check(control, gen, _spec()["config"], keep["picked"], 0)
    assert ok is False, (checks, gen.count)


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _altered],
                         ids=["state_unchanged", "half_batch_left_out", "answer_altered"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    for name in ("update_truncated_batch", "update_truncated_rank_k_batch"):
        orig = getattr(engine_mod.SvdEngine, name)
        monkeypatch.setattr(engine_mod.SvdEngine, name, _broken(fault)(orig))
    res = _run()
    assert res["correct"] is False, res["checks"]
    assert np.isfinite(res["metrics"]["events_per_s"]["value"])


def test_open_loop_run_is_correct_and_counts_every_event():
    res = _run(seconds=1.5, loop="open")
    assert res["correct"], res["checks"]
    assert res["attempted"] == 150 and res["failed"] == 0
    p50, p99 = (res["metrics"][k]["value"] for k in ("visible_p50_ms", "visible_p99_ms"))
    assert 0 < p50 <= p99 < 60e3
