"""The percentile, rate and spread arithmetic on hand-worked samples."""

import statistics

import numpy as np
import pytest

import arith


@pytest.mark.parametrize("values, q, want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    ([5, 1, 4, 2, 3], 0, 1.0),
    ([5, 1, 4, 2, 3], 100, 5.0),
    ([10, 20, 30, 40, 50], 95, 48.0),          # 40 + 0.8 * 10
    ([0.0, 1.0], 25, 0.25),
    ([7.0], 99, 7.0),
])
def test_percentile_by_hand(values, q, want):
    assert arith.percentile(values, q) == pytest.approx(want, abs=1e-12)


@pytest.mark.parametrize("q", [1, 50, 90, 95, 99])
def test_percentile_is_numpys_linear_method(q):
    xs = np.random.default_rng(q).lognormal(size=1001)
    assert arith.percentile(list(xs), q) == pytest.approx(
        float(np.percentile(xs, q)), rel=1e-12)


@pytest.mark.parametrize("bad", [-1, 100.5])
def test_percentile_refuses(bad):
    with pytest.raises(ValueError):
        arith.percentile([1, 2], bad)
    with pytest.raises(ValueError):
        arith.percentile([], 50)


@pytest.mark.parametrize("n, q, ok", [
    (199, 95, False), (200, 95, True), (999, 99, False), (1000, 99, True),
    (20, 50, True), (19, 50, False)])
def test_ten_samples_beyond_the_percentile(n, q, ok):
    assert arith.supports_percentile(n, q) is ok


def test_rate_is_all_work_over_all_time():
    # three requests of 10 units in 1 s, 1 s and 2 s: 7.5 a second, not
    # the 10 a second that the median request would give
    assert arith.rate(30, 4.0) == 7.5
    with pytest.raises(ValueError):
        arith.rate(1, 0.0)


def test_spread_is_the_contracts():
    runs = [3.2087, 3.2260, 3.2192, 3.2397, 3.2360, 3.2288]
    q1, _, q3 = statistics.quantiles(runs, n=4)
    assert arith.spread(runs) == pytest.approx(
        (q3 - q1) / statistics.median(runs))
    # by hand: exclusive quartiles of six sorted values sit at 1.75 and 5.25
    s = sorted(runs)
    by_hand = ((s[4] + 0.25 * (s[5] - s[4])) - (s[0] + 0.75 * (s[1] - s[0])))
    assert arith.spread(runs) == pytest.approx(by_hand / ((s[2] + s[3]) / 2))
    # numpy's quartiles lie closer together: not the rule
    assert arith.spread(runs) > (np.percentile(runs, 75)
                                 - np.percentile(runs, 25)) / np.median(runs)
