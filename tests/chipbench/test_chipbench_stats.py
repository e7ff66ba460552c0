"""The window's arithmetic: whole-window means and the quartile spread."""
import statistics

import pytest

import chipbench_testkit  # noqa: F401  (puts the checkout on sys.path)
from chipbench import stats


def test_per_op_is_all_the_time_over_all_the_operations():
    assert stats.per_op(12.0, 4) == 3.0
    assert stats.per_op(12.0, 0) is None


def test_spread_uses_the_standard_library_quartiles():
    xs = [10.0, 10.2, 9.9, 10.4, 10.1, 9.8]
    q1, q2, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / q2)
    assert stats.megabytes(2_500_000) == 2.5
