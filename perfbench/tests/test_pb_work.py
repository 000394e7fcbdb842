"""Work counts against hand arithmetic, and the peak table."""
import pytest

from perfbench import work


def test_select_work_by_hand():
    # (B, K, d) = (256, 3, 26): 2BKd^2 + 2BKd + 5BK FLOPs; A^-1, theta,
    # per-arm costs in, X in, scores and arms out, 4 bytes each.
    flops, nbytes = work.select_work(256, 3, 26)
    assert flops == 2 * 256 * 3 * 676 + 2 * 256 * 3 * 26 + 5 * 256 * 3
    assert flops == 1_082_112
    assert nbytes == 4 * (3 * 676 + 3 * 26 + 9 + 256 * 26 + 256 * 3 + 256)
    assert nbytes == 39_180


def test_update_work_by_hand():
    flops, nbytes = work.update_work(256, 3, 26)
    assert flops == 256 * (9 * 676 + 5 * 26) + 3 * 2 * 676 == 1_594_840
    stats = 4 * (2 * 3 * 676 + 2 * 3 * 26 + 3 * 3)
    assert stats == work.stats_bytes(3, 26) == 16_884
    assert nbytes == 2 * stats + 4 * (256 * 26 + 3 * 256) == 63_464


def test_grid_call_work_by_hand():
    flops, nbytes = work.grid_call_work(40, 1824, 3, 26)
    per_step = (2 * 3 * 676 + 2 * 3 * 26 + 15) + (9 * 676 + 5 * 26
                                                   + 3 * 2 * 676)
    assert flops == 40 * 1824 * per_step
    assert nbytes == 40 * (2 * 16_884 + 4 * 1824 * (26 + 6 + 4))


def test_least_time_names_its_bound():
    pk = work.peaks("TPU v5 lite")
    assert pk == (197e12, 819e9)
    t, bound = work.least_s(*work.select_work(256, 3, 26), pk)
    assert bound == "bandwidth" and t == pytest.approx(39_180 / 819e9)
    t, bound = work.least_s(1e12, 1.0, pk)
    assert bound == "compute" and t == pytest.approx(1e12 / 197e12)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="TPU v9"):
        work.peaks("TPU v9")
