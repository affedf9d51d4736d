"""The ``null_columns.backlog`` metric: the service's ``null_columns``
histogram, read by its reader, on a fresh registry.  A rank-4 state of rank
budget 8 retires with 4 null columns, a full-rank one with none, and with
``repro.obs`` off nothing is recorded."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402

from bench import spec as bench_spec  # noqa: E402
from repro import obs  # noqa: E402
from repro.api import SvdState, UpdatePolicy  # noqa: E402
from repro.obs import metrics as obs_metrics  # noqa: E402
from repro.serve import SvdService  # noqa: E402

READ = bench_spec.load_reader(ROOT / "bench" / "metrics" / "null_columns.backlog.py")
M, N, R = 64, 24, 8


@pytest.fixture
def reg():
    fresh = obs_metrics.MetricsRegistry()
    prev = obs_metrics.set_registry(fresh)
    was = obs.enabled()
    yield fresh
    (obs.enable if was else obs.disable)()
    obs_metrics.set_registry(prev)


def _service_run(k: int, traced: bool):
    """Two streams whose windows have row rank ``k``, two slot events each,
    flushed as one depth-2 round."""
    rng = np.random.default_rng(k)
    svc = SvdService(policy=UpdatePolicy(method="fused"), max_batch=4)
    (obs.enable if traced else obs.disable)()
    for i in range(2):
        g = rng.choice([-1.0, 1.0], size=(N, k))
        rows = rng.integers(-1024, 1025, size=(M, k)) / 4096.0
        x, s, yt = np.linalg.svd(rows @ g.T, full_matrices=False)
        s = np.where(np.arange(R) < k, s[:R], 0.0)
        svc.register(f"s{i}", SvdState.from_factors(
            *(z.astype(np.float32) for z in (x[:, :R], s, yt.T[:, :R]))))
        for slot in range(2):
            new = rng.integers(-1024, 1025, size=k) / 4096.0
            a = np.zeros(M, np.float32)
            a[slot] = 1.0
            svc.enqueue(f"s{i}", a, ((new - rows[slot]) @ g.T).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        svc.flush_round(max_depth=2)
        svc.drain()
    return READ({})


def test_null_columns_reads_four_on_a_rank_four_of_eight_state(reg):
    assert _service_run(4, traced=True) == pytest.approx(4.0)
    assert sum(h.count for h in reg.series() if h.name == "null_columns") == 2


def test_null_columns_reads_zero_on_a_full_rank_state(reg):
    assert _service_run(R, traced=True) == 0.0


def test_null_columns_records_nothing_with_obs_off(reg):
    assert _service_run(4, traced=False) is None
    assert not [m for m in reg.series() if m.name == "null_columns"]
