"""The traffic generator on the CPU at a tiny size: the same seed gives the
same events, the Zipf shares follow 1/H, and every event model's float64
reference is the matrix the events build."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import traffic  # noqa: E402

SEED = 2**33 + 17     # larger than 32 signed bits hold


def _config(model):
    ev = {"latent": {"model": "latent", "latent_rank": 3, "seed_rank": 2},
          "drift": {"model": "drift", "seed_scale": 100},
          "slots": {"model": "slots", "row_rank": 2}}[model]
    return {"streams": 3, "m": 24, "n": 16, "rank": 5 if model != "slots" else 4,
            "events": ev}


def _events(model, seed, counts=(3, 5, 2)):
    gen = traffic.event_model(_config(model), seed)
    states = gen.device_init()
    out = [gen.next(i, c) for i, c in enumerate(counts)] + [gen.next(1, 4)]
    return gen, [np.asarray(x) for x in states], out


@pytest.mark.parametrize("model", ["latent", "drift", "slots"])
def test_same_seed_same_events(model):
    _, s1, e1 = _events(model, SEED)
    _, s2, e2 = _events(model, SEED)
    _, s3, e3 = _events(model, SEED + 1)
    for x, y in zip(s1, s2):
        np.testing.assert_array_equal(x, y)
    for (a1, b1), (a2, b2) in zip(e1, e2):
        np.testing.assert_array_equal(a1, a2)
        np.testing.assert_array_equal(b1, b2)
    assert not all(np.array_equal(a1, a3) and np.array_equal(b1, b3)
                   for (a1, b1), (a3, b3) in zip(e1, e3))


@pytest.mark.parametrize("model", ["latent", "drift", "slots"])
def test_reference_is_the_matrix_the_events_build(model):
    gen, (u, s, v), events = _events(model, SEED)
    for i in range(3):
        u0, s0, v0 = (x[i].astype(np.float64) for x in (u, s, v))
        mat = (u0 * s0) @ v0.T
        a, b = gen.events_of(i)
        mat += a.astype(np.float64).T @ b.astype(np.float64)
        left, right = gen.reference(i, *gen.seed_state(i))
        np.testing.assert_allclose(left @ right.T, mat, rtol=0, atol=1e-9 * np.abs(mat).max())


def test_replayed_events_equal_the_sent_ones():
    gen, _, events = _events("latent", SEED)
    a, b = gen.events_of(1)
    np.testing.assert_array_equal(a, np.concatenate([events[1][0], events[3][0]]))
    np.testing.assert_array_equal(b, np.concatenate([events[1][1], events[3][1]]))


def test_slot_events_replace_the_oldest_row():
    gen, _, _ = _events("slots", SEED, counts=(0, 0, 0))
    a, b = gen.next(0, 30)            # wraps the 24-row window
    assert a.shape == (30, 24) and (a.sum(1) == 1).all()
    assert [int(np.argmax(row)) for row in a] == [j % 24 for j in range(30)]
    # a row written twice: the second event's b removes the first row again
    first = gen.w0[0, 0] @ gen.g[0].T
    np.testing.assert_array_equal(b[0] + first, gen.w.take(0, 0, 1)[0] @ gen.g[0].T)


@pytest.mark.parametrize("streams,theta", [(256, 0.99), (40, 0.5)])
def test_zipf_shares_follow_harmonic(streams, theta):
    counts = traffic.zipf_counts(streams, 100_000, theta)
    assert counts.sum() == 100_000
    assert counts[0] / 100_000 == pytest.approx(traffic.harmonic_share(streams, theta), abs=1e-5)
    for k in (1, 9, streams - 1):
        assert counts[k] / counts[0] == pytest.approx((k + 1) ** -theta, rel=2e-3 * (k + 1))
    if streams == 256:     # the hottest of 256 streams takes about 16%
        assert 0.155 < traffic.harmonic_share(256, 0.99) < 0.165


def test_open_schedule_is_the_same_work_in_another_order():
    mix = {"rate_per_s": 500.0, "zipf": 0.99}
    due1, tgt1 = traffic.open_schedule(mix, 64, 4.0, SEED)
    due2, tgt2 = traffic.open_schedule(mix, 64, 4.0, SEED)
    due3, tgt3 = traffic.open_schedule(mix, 64, 4.0, SEED + 1)
    np.testing.assert_array_equal(due1, due2)
    np.testing.assert_array_equal(tgt1, tgt2)
    assert len(due1) == len(due3) == 2000
    assert (np.diff(due1) > 0).all()
    assert due1[-1] == pytest.approx(4.0, rel=0.01)
    gaps = lambda due: np.sort(np.diff(np.concatenate([[0.0], due])))
    np.testing.assert_allclose(gaps(due1), gaps(due3), rtol=1e-6)
    assert sorted(np.bincount(tgt1, minlength=64)) == sorted(np.bincount(tgt3, minlength=64))
    assert not np.array_equal(tgt1, tgt3)


def test_warm_rounds_resolve_names():
    config = {"streams": 6, "service": {"max_depth": 4}}
    mix = {"warm": [{"depth": "max_depth", "widths": ["streams", "streams"]},
                    {"depth": 2, "widths": [1, 3]}]}
    assert traffic.warm_rounds(mix, config) == [(4, 6), (2, 1), (2, 2), (2, 3)]


def test_sample_streams_include_the_longest_chain():
    counts = np.array([3, 9, 1, 4, 4])
    picked = traffic.sample_streams(counts, 3, SEED)
    assert picked[0] == 1 and len(set(picked)) == 3
    assert picked == traffic.sample_streams(counts, 3, SEED)
