"""TPU-backend statistics: jnp-lowered mean/var/std/sum/max/min and the
explicit shard_map Welford path (reference area: StatCounter aggregation in
``test/test_spark_basic.py``/functional tests, SURVEY §4; BASELINE config 2).
"""

import numpy as np
import pytest

import bolt_tpu as bolt
from bolt_tpu.utils import allclose


def _x():
    rs = np.random.RandomState(4)
    return rs.randn(8, 4, 5)


@pytest.mark.parametrize("name", ["mean", "var", "std", "sum", "max", "min"])
def test_stats_default_axis(mesh, name):
    x = _x()
    b = bolt.array(x, mesh)
    got = getattr(b, name)().toarray()
    expected = getattr(x, name)(axis=0)
    assert allclose(got, expected)


@pytest.mark.parametrize("name", ["mean", "var", "std", "sum", "max", "min"])
@pytest.mark.parametrize("axis", [(0,), (0, 1), (1, 2), (2,), None])
def test_stats_axes(mesh, name, axis):
    x = _x()
    b = bolt.array(x, mesh, axis=(0, 1))
    got = getattr(b, name)(axis=axis).toarray()
    np_axis = axis if axis is not None else (0, 1)  # default: all key axes
    expected = np.asarray(getattr(x, name)(axis=np_axis))
    assert allclose(got, expected)


def test_stats_keepdims(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    out = b.mean(axis=(0,), keepdims=True)
    assert out.split == 1
    assert allclose(out.toarray(), x.mean(axis=0, keepdims=True))


def test_stats_split_bookkeeping(mesh):
    x = _x()
    b = bolt.array(x, mesh, axis=(0, 1))
    assert b.sum(axis=(0,)).split == 1
    assert b.sum(axis=(0, 1)).split == 0
    assert b.sum(axis=(2,)).split == 2


def test_welford_stats(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    counter = b.stats()
    assert counter.count() == 8
    assert allclose(counter.mean(), x.mean(axis=0))
    assert allclose(counter.variance(), x.var(axis=0))
    assert allclose(counter.stdev(), x.std(axis=0))
    assert allclose(counter.max(), x.max(axis=0))
    assert allclose(counter.min(), x.min(axis=0))


def test_welford_partial_axis(mesh):
    x = _x()
    b = bolt.array(x, mesh, axis=(0, 1))
    counter = b.stats(axis=(1,))
    assert counter.count() == 4
    assert allclose(counter.mean(), x.mean(axis=1))
    assert allclose(counter.variance(), x.var(axis=1))


def test_welford_value_axis(mesh):
    # stats() accepts value axes, matching mean()/_stat (VERDICT r1 weak-6)
    x = _x()
    b = bolt.array(x, mesh)
    counter = b.stats(axis=(1,))
    assert counter.count() == x.shape[1]
    assert allclose(counter.mean(), x.mean(axis=1))
    assert allclose(counter.variance(), x.var(axis=1))
    assert allclose(counter.max(), x.max(axis=1))
    # mixed key + value axes
    counter = b.stats(axis=(0, 2))
    assert allclose(counter.mean(), x.mean(axis=(0, 2)))
    assert allclose(counter.variance(), x.var(axis=(0, 2)))
    # parity with the local oracle per axis set
    lo = bolt.array(x)
    for axes in [(0,), (1,), (2,), (0, 1), (0, 2), (1, 2)]:
        a = lo.stats(axis=axes)
        t = b.stats(axis=axes)
        assert allclose(a.mean(), t.mean())
        assert allclose(a.variance(), t.variance())
    # out-of-range still rejected
    with pytest.raises(ValueError):
        b.stats(axis=(9,))


def test_welford_cache_bounded(mesh):
    # the welford executable cache is the shared bounded LRU, not an
    # unbounded private dict
    import bolt_tpu.tpu.stats as stats_mod
    assert not hasattr(stats_mod, "_WELFORD_CACHE")


def test_sum_bit_exact_integral(mesh):
    # integral floats: sum is bit-exact regardless of reduction order
    # (BASELINE north-star parity condition for config 1)
    x = np.arange(8.0 * 6).reshape(8, 6)
    b = bolt.array(x, mesh)
    assert allclose(b.sum().toarray(), x.sum(axis=0))
    assert float(b.sum(axis=(0, 1)).toarray()) == float(x.sum())


def test_var_std_ddof(mesh):
    x = _x()
    b, lo = bolt.array(x, mesh), bolt.array(x)
    assert allclose(b.var(axis=(0,), ddof=1).toarray(), x.var(axis=0, ddof=1))
    assert allclose(b.std(axis=(0,), ddof=1).toarray(), x.std(axis=0, ddof=1))
    # the local backend inherits ddof from ndarray: same expression works
    assert allclose(np.asarray(lo.var(axis=0, ddof=1)), x.var(axis=0, ddof=1))
    # default stays population (ddof=0), matching StatCounter
    assert allclose(b.var(axis=(0,)).toarray(), x.var(axis=0))


def test_ptp(mesh):
    x = _x()
    b, lo = bolt.array(x, mesh), bolt.array(x)
    assert allclose(b.ptp(axis=(0,)).toarray(), np.ptp(x, axis=0))
    assert allclose(b.ptp(axis=(0, 1, 2)).toarray(), np.ptp(x))
    # key-axis default on TPU; ndarray-convention (all axes) locally —
    # the documented reduction-family asymmetry
    assert allclose(b.ptp().toarray(), np.ptp(x, axis=0))
    assert float(np.asarray(lo.ptp().toarray())) == np.ptp(x)
    assert allclose(np.asarray(lo.ptp(axis=1).toarray()), np.ptp(x, axis=1))


def test_var_fractional_ddof(mesh):
    x = _x()
    b, lo = bolt.array(x, mesh), bolt.array(x)
    assert allclose(b.var(axis=(0,), ddof=1.5).toarray(),
                    x.var(axis=0, ddof=1.5))
    assert allclose(np.asarray(lo.var(axis=0, ddof=1.5)),
                    x.var(axis=0, ddof=1.5))
