"""The work functions of the roofline metrics against hand counts, and the
peaks table."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import work  # noqa: E402


@pytest.mark.parametrize("m,n,r,state,pair,flops", [
    # U, V, s read and written: 2 x (131072 + 131072 + 32) floats; a and b
    # read: 8192 floats; 4*32*8192 + 2*32*33*8192 + 24*33^3 operations
    (4096, 4096, 32, 4 * 2 * 262176, 4 * 8192, 1048576 + 17301504 + 862488),
    # 2 x (8064 + 328 + 8) floats written and read; a and b: 1049 floats
    (1008, 41, 8, 4 * 2 * 8400, 4 * 1049, 33568 + 151056 + 17496),
])
def test_update_work_matches_hand_counts(m, n, r, state, pair, flops):
    assert work.state_bytes(m, n, r) == state
    assert work.pair_bytes(m, n) == pair
    assert work.update_flops(m, n, r) == flops


def test_a_deep_round_passes_over_the_state_once():
    peak = work.peaks("TPU v5 lite")
    shape = (4096, 4096, 32)
    # 256 streams x depth 8: 2048 events in 256 passes over a state
    deep, bound = work.least_seconds(2048, 256, *shape, peak)
    nbytes = 256 * work.state_bytes(*shape) + 2048 * work.pair_bytes(*shape[:2])
    assert bound == "memory" and deep == pytest.approx(nbytes / peak["hbm_bytes_per_s"])
    shallow, _ = work.least_seconds(2048, 2048, *shape, peak)
    assert 7 < shallow / deep < 8


def test_both_shapes_are_memory_bound_on_v5e():
    peak = work.peaks("TPU v5 lite")
    ridge = peak["flops_per_s"] / peak["hbm_bytes_per_s"]
    assert 230 < ridge < 250
    for shape, intensity in (((4096, 4096, 32), 9.0), ((1008, 41, 8), 2.8)):
        one = work.state_bytes(*shape) + work.pair_bytes(*shape[:2])
        assert work.update_flops(*shape) / one == pytest.approx(intensity, rel=0.02)
        for depth in (1, 8):
            assert work.least_seconds(10 * depth, 10, *shape, peak)[1] == "memory"


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.peaks("cpu")
