"""TPU-backend map/filter/reduce, including non-aligned axes that force an
``_align`` swap (reference area: ``test/test_spark_functional.py``,
SURVEY §4)."""

from operator import add

import numpy as np
import pytest

import bolt_tpu as bolt
from bolt_tpu.utils import allclose

from tests.generic import filter_suite, map_suite, reduce_suite


def _x():
    rs = np.random.RandomState(3)
    return rs.randn(8, 4, 5)


def test_map(mesh):
    map_suite(_x(), bolt.array(_x(), mesh))


def test_filter(mesh):
    filter_suite(_x(), bolt.array(_x(), mesh))


def test_reduce(mesh):
    reduce_suite(_x(), bolt.array(_x(), mesh))


def test_map_nonaligned_axis(mesh):
    x = _x()
    b = bolt.array(x, mesh)  # keys = (0,)
    # mapping over axis 1 forces an implicit swap (reference _align)
    out = b.map(lambda v: v.sum(), axis=(1,))
    assert out.split == 1
    expected = np.asarray([x[:, i, :].sum() for i in range(x.shape[1])])
    assert allclose(out.toarray(), expected)


def test_map_value_axis_pair(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    out = b.map(lambda v: v * 2, axis=(0, 2))
    # result keys = (axis0, axis2) leading
    expected = np.transpose(x, (0, 2, 1)) * 2
    assert allclose(out.toarray(), expected)


def test_map_value_shape_check(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    out = b.map(lambda v: v.sum(axis=0), value_shape=(5,))
    assert allclose(out.toarray(), np.asarray([v.sum(axis=0) for v in x]))
    with pytest.raises(ValueError):
        b.map(lambda v: v.sum(axis=0), value_shape=(3,))


def test_map_dtype_arg(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    out = b.map(lambda v: v, dtype=np.float32)
    assert out.dtype == np.float32


def test_map_nontraceable_fallback(mesh):
    x = _x()
    b = bolt.array(x, mesh)

    def hostile(v):
        # .item() and float() force concrete values: not jax-traceable
        return np.full((2,), float(np.asarray(v).sum()))

    with pytest.warns(bolt.HostFallbackWarning, match="hostile"):
        out = b.map(hostile)
    expected = np.asarray([hostile(v) for v in x])
    assert allclose(out.toarray(), expected)


def test_filter_nontraceable_fallback_warns(mesh):
    x = _x()
    b = bolt.array(x, mesh)

    def hostile(v):
        return bool(np.asarray(v).sum() > 0)   # np coercion: not traceable

    with pytest.warns(bolt.HostFallbackWarning, match="filter"):
        out = b.filter(hostile)
    expected = np.asarray([v for v in x if v.sum() > 0])
    assert allclose(out.toarray(), expected)


def test_reduce_nontraceable_fallback_warns(mesh):
    x = _x()
    b = bolt.array(x, mesh)

    def hostile(a, c):
        return np.asarray(a) + np.asarray(c)   # np coercion: not traceable

    with pytest.warns(bolt.HostFallbackWarning, match="reduce"):
        out = b.reduce(hostile)
    assert allclose(out.toarray(), x.sum(axis=0))


def test_buggy_traceable_funcs_raise_not_fallback(mesh):
    """A genuine bug in a jax-compatible callable must SURFACE, not silently
    reroute through the 100x-slower host oracle (VERDICT r1 weak-1: only
    trace-type errors may trigger the fallback)."""
    import warnings as _warnings
    x = _x()
    b = bolt.array(x, mesh)
    with _warnings.catch_warnings():
        # any HostFallbackWarning here is itself a failure
        _warnings.simplefilter("error", bolt.HostFallbackWarning)
        with pytest.raises(AttributeError):
            b.map(lambda v: v.nonexistent_attr)          # typo
        with pytest.raises(TypeError):
            b.map(lambda v: v.reshape(3))                # bad reshape
        with pytest.raises((TypeError, ValueError)):
            b.filter(lambda v: (v + np.ones(7)).sum() > 0)  # shape mismatch
        with pytest.raises((TypeError, ValueError)):
            b.reduce(lambda a, c: a @ np.ones((99, 2)))  # bad matmul shapes


def test_filter_on_value_axis(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    out = b.filter(lambda v: v[0, 0] > 0, axis=(1,))
    expected = np.asarray([x[:, i, :] for i in range(x.shape[1])
                           if x[0, i, 0] > 0])
    assert allclose(out.toarray(), expected)
    assert out.split == 1


def test_reduce_errors(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    with pytest.raises(ValueError):
        # shape-changing reducer is invalid
        b.reduce(lambda a, c: (a + c)[:2])


def test_reduce_single_record(mesh):
    x = np.ones((1, 3))
    b = bolt.array(x, mesh)
    assert allclose(b.reduce(add).toarray(), x.sum(axis=0))


# ----------------------------------------------------------------------
# pending (lazy-count) filter semantics: the survivor count syncs to host
# only when the shape is needed, and toarray batches it with the data fetch
# ----------------------------------------------------------------------

def test_filter_is_pending_until_shape_read(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    out = b.filter(lambda v: v.sum() > 0)
    assert out.pending
    expected = np.asarray([v for v in x if v.sum() > 0])
    assert out.shape == expected.shape        # resolves: one scalar sync
    assert not out.pending
    assert allclose(out.toarray(), expected)


def test_filter_toarray_without_prior_resolution(mesh):
    # the batched-fetch fast path: toarray on a still-pending result
    x = _x()
    b = bolt.array(x, mesh)
    out = b.filter(lambda v: v[0, 0] > 0)
    assert out.pending
    expected = np.asarray([v for v in x if v[0, 0] > 0])
    assert allclose(out.toarray(), expected)
    # the fetched count resolves the device side as a side effect, so later
    # consumers pay neither a re-transfer nor a count sync
    assert not out.pending
    assert allclose(out.toarray(), expected)
    assert out.split == 1


def test_filter_repr_does_not_sync(mesh):
    x = _x()
    out = bolt.array(x, mesh).filter(lambda v: v.sum() > 0)
    r = repr(out)
    assert "pending" in r
    assert out.pending  # repr must not have forced the count sync


def test_filter_dtype_known_while_pending(mesh):
    x = _x()
    out = bolt.array(x, mesh).filter(lambda v: v.sum() > 0)
    assert out.dtype == x.dtype
    assert out.pending


def test_filter_fuses_deferred_chain(mesh):
    # map defers; filter consumes the chain inside its own fused program
    x = _x()
    b = bolt.array(x, mesh)
    out = b.map(lambda v: v * 2).map(lambda v: v - 1).filter(
        lambda v: v.sum() > -20)
    y = x * 2 - 1
    expected = np.asarray([v for v in y if v.sum() > -20])
    assert expected.shape[0] not in (0, x.shape[0])  # a real subset
    assert allclose(out.toarray(), expected)


def test_filter_empty_and_full(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    none = b.filter(lambda v: v.sum() > 1e9)
    assert none.shape == (0,) + x.shape[1:]
    assert none.toarray().shape == (0,) + x.shape[1:]
    everything = b.filter(lambda v: v.sum() > -1e9)
    assert allclose(everything.toarray(), x)


def test_filter_chains_into_map(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    out = b.filter(lambda v: v.sum() > 0).map(lambda v: v + 1)
    expected = np.asarray([v + 1 for v in x if v.sum() > 0])
    assert allclose(out.toarray(), expected)


def test_filter_toarray_large_buffer_path(mesh, monkeypatch):
    # above the batched-fetch size cap, toarray resolves first (scalar
    # count sync + sliced fetch) instead of shipping the padded buffer
    import bolt_tpu.tpu.array as mod
    monkeypatch.setattr(mod, "_PENDING_FETCH_MAX_BYTES", 0)
    x = _x()
    out = bolt.array(x, mesh).filter(lambda v: v.sum() > 0)
    expected = np.asarray([v for v in x if v.sum() > 0])
    assert allclose(out.toarray(), expected)
    assert not out.pending


def test_filter_eager_path_for_large_inputs(mesh, monkeypatch):
    # above the fused-path size cap a filter whose survivors are needed
    # as an array takes the memory-safe two-phase route (count synced to
    # the host, survivor-sized gather output); the filter itself defers
    # at every size (ISSUE 30), so the size is asked where the buffer is
    # built and not in front of the terminals that build none
    import bolt_tpu.tpu.array as mod
    monkeypatch.setattr(mod, "_FILTER_FUSED_MAX_BYTES", 0)
    x = _x()
    b = bolt.array(x, mesh)
    out = b.filter(lambda v: v.sum() > 0)
    assert out.pending              # nothing dispatched yet
    expected = np.asarray([v for v in x if v.sum() > 0])
    assert out.shape == expected.shape
    assert not out.pending          # the two-phase path leaves it concrete
    assert any(k[0] == "filter-gather" for k in mod._JIT_CACHE)
    assert allclose(out.toarray(), expected)
    assert out.split == 1
    # value-axis filter goes through _align then the same path
    out2 = b.filter(lambda v: v[0, 0] > 0, axis=(1,))
    exp2 = np.asarray([x[:, i, :] for i in range(x.shape[1])
                       if x[0, i, 0] > 0])
    assert allclose(out2.toarray(), exp2)


def test_filter_eager_gather_bucketed_one_executable(mesh, monkeypatch):
    # VERDICT r3 weak-5: two HBM-scale filters with DIFFERENT survivor
    # counts in the same power-of-two band reuse ONE compiled gather —
    # the executable is keyed on the bucket, not the exact count
    import bolt_tpu.tpu.array as mod
    monkeypatch.setattr(mod, "_FILTER_FUSED_MAX_BYTES", 0)
    x = _x()
    b = bolt.array(x, mesh)

    def n_gathers():
        return sum(1 for k in mod._JIT_CACHE if k[0] == "filter-gather")

    # record i sums to 4*i: thresholds drawing 3 and 4 survivors land in
    # the same power-of-two bucket (4)
    x = np.arange(8, dtype=float)[:, None, None] * np.ones((8, 2, 2))
    b = bolt.array(x, mesh)
    before = n_gathers()
    out1 = b.filter(lambda v: v.sum() > 18.0)     # 3 survivors
    out2 = b.filter(lambda v: v.sum() > 14.0)     # 4 survivors
    n1, n2 = out1.shape[0], out2.shape[0]
    assert (n1, n2) == (3, 4)
    assert mod._gather_bucket(n1, x.shape[0]) == \
        mod._gather_bucket(n2, x.shape[0])
    assert n_gathers() == before + 1              # one bucket, one compile
    assert allclose(out1.toarray(), x[5:])
    assert allclose(out2.toarray(), x[4:])


def test_gather_bucket_bands():
    from bolt_tpu.tpu.array import _gather_bucket
    assert _gather_bucket(0, 100) == 1
    assert _gather_bucket(1, 100) == 1
    assert _gather_bucket(3, 100) == 4
    assert _gather_bucket(4, 100) == 4
    assert _gather_bucket(5, 100) == 8
    assert _gather_bucket(97, 100) == 100          # capped at n
