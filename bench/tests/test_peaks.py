"""The peaks table and the Algorithm-1 work count."""

import pytest

from bench import work


def test_solve_work_for_a_row_total():
    table = work.load()
    assert table["algorithm1_row"] == {**table["algorithm1_row"],
                                       "flops": 7680, "bytes": 84}
    assert work.solve_work(200_000) == (1_536_000_000, 16_800_000)


def test_least_time_takes_the_larger_bound():
    t, bound = work.least_seconds(200_000, "TPU v5 lite")
    assert bound == "bytes"
    assert t == pytest.approx(16_800_000 / 819e9)


def test_unknown_device_is_refused():
    with pytest.raises(KeyError, match="no peaks for device kind"):
        work.peak("cpu")
    with pytest.raises(KeyError):
        work.least_seconds(10, "TPU v4")
