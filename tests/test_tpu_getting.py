"""TPU-backend indexing vs numpy (reference area:
``test/test_spark_getting.py``, SURVEY §4; BASELINE config 4 exercises the
boolean-mask path via filter)."""

import numpy as np
import pytest

import bolt_tpu as bolt
from bolt_tpu.utils import allclose


def _x():
    rs = np.random.RandomState(6)
    return rs.randn(8, 4, 5)


def test_slices(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    assert allclose(b[:].toarray(), x)
    assert allclose(b[2:6].toarray(), x[2:6])
    assert allclose(b[:, 1:3].toarray(), x[:, 1:3])
    assert allclose(b[::2, :, ::2].toarray(), x[::2, :, ::2])
    assert allclose(b[1:7:2, ::-1].toarray(), x[1:7:2, ::-1])


def test_ints_squeeze(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    out = b[3]
    assert out.shape == x[3].shape
    assert out.split == 0
    assert allclose(out.toarray(), x[3])
    out = b[:, 2]
    assert out.split == 1
    assert allclose(out.toarray(), x[:, 2])
    assert allclose(b[-1, -2, -3].toarray(), np.asarray(x[-1, -2, -3]))


def test_lists_orthogonal(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    assert allclose(b[[0, 3, 5]].toarray(), x[[0, 3, 5]])
    # per-axis advanced indices apply orthogonally (np.ix_ semantics)
    out = b[[0, 1], :, [0, 2, 4]]
    expected = x[np.ix_([0, 1], range(4), [0, 2, 4])]
    assert allclose(out.toarray(), expected)
    assert allclose(b[:, [3, 1]].toarray(), x[:, [3, 1]])
    assert allclose(b[[-1, 0]].toarray(), x[[-1, 0]])


def test_bool_arrays(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    kmask = x[:, 0, 0] > 0
    assert allclose(b[kmask].toarray(), x[kmask])
    vmask = np.array([True, False, True, False, True])
    assert allclose(b[:, :, vmask].toarray(), x[:, :, vmask])


def test_mixed(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    out = b[2:7, [0, 3], ::2]
    expected = x[2:7][:, [0, 3]][:, :, ::2]
    assert allclose(out.toarray(), expected)
    out = b[1, :, [0, 4]]
    expected = x[1][:, [0, 4]]
    assert allclose(out.toarray(), expected)


def test_split_bookkeeping(mesh):
    x = _x()
    b = bolt.array(x, mesh, axis=(0, 1))
    assert b[0].split == 1
    assert b[0, 0].split == 0
    assert b[:, 0].split == 1
    assert b[:, :, 0].split == 2


def test_errors(mesh):
    b = bolt.array(_x(), mesh)
    with pytest.raises(ValueError):
        b[0, 0, 0, 0]
    with pytest.raises(IndexError):
        b[99]


def test_negative_and_mixed_index_forms(mesh):
    # negatives, reversed slices, empty slices, ndarray indices, and
    # int+list+slice mixes — full numpy-oracle parity
    rs = np.random.RandomState(50)
    x = rs.randn(16, 6, 4)
    b = bolt.array(x, mesh)
    assert allclose(b[-1].toarray(), x[-1])
    assert allclose(b[-3:].toarray(), x[-3:])
    assert allclose(b[..., -2:].toarray(), x[..., -2:])
    assert allclose(b[[-1, 0, 2]].toarray(), x[[-1, 0, 2]])
    assert allclose(b[::-1].toarray(), x[::-1])
    assert allclose(b[np.array([1, 3])].toarray(), x[np.array([1, 3])])
    assert allclose(np.asarray(b[2, -1, ::2].toarray()), x[2, -1, ::2])
    assert b[5:2].toarray().shape == x[5:2].shape
    assert allclose(b[1, [0, 2], :].toarray(), x[1][[0, 2], :])


def test_take_parity(mesh):
    # ndarray.take: inherited locally, compiled program on TPU
    x = _x()
    b, lo = bolt.array(x, mesh), bolt.array(x)
    for kwargs in [dict(indices=[2, 0, 5]), dict(indices=[1, -1], axis=0),
                   dict(indices=[3, 1], axis=1),
                   dict(indices=[0, 2, 4], axis=2),
                   dict(indices=[[0, 1], [2, 3]], axis=0),
                   dict(indices=7)]:
        ref = x.take(**kwargs)
        t = b.take(**kwargs)
        l = lo.take(**kwargs)
        assert np.asarray(t.toarray()).shape == ref.shape, kwargs
        assert allclose(t.toarray(), ref), kwargs
        assert allclose(np.asarray(l), ref), kwargs
    # split bookkeeping
    assert b.take([1, 0], axis=0).split == 1
    assert b.take(0, axis=0).split == 0
    assert b.take([1, 0], axis=2).split == 1
    assert b.take([[0, 1], [2, 3]], axis=0).split == 2
    # errors match numpy's classes
    with pytest.raises(IndexError):
        b.take([9999])               # OOB for the flattened 160 elements
    with pytest.raises(IndexError):
        b.take([8], axis=0)
    # deferred chains fuse in
    assert allclose(bolt.array(x, mesh).map(lambda v: v * 2)
                    .take([1, 3], axis=0).toarray(), (x * 2).take([1, 3], 0))


def test_take_numpy_dtype_and_mode_semantics(mesh):
    # numpy's exact quirks: float NDARRAYS rejected, float sequences and
    # scalars truncate, bools are 0/1 indices, mode= clips/wraps
    x = _x()
    b, lo = bolt.array(x, mesh), bolt.array(x)
    for args in [([True, False],), ([2.7],), (1.5,), ([-1.5],)]:
        ref = x.take(*args)
        assert allclose(np.asarray(b.take(*args).toarray()), ref), args
        assert allclose(np.asarray(lo.take(*args)), ref), args
    with pytest.raises(TypeError):
        b.take(np.array([1.5]))
    with pytest.raises(TypeError):
        b.take(np.array([], dtype=float))
    assert allclose(np.asarray(b.take([9999], mode="clip").toarray()),
                    x.take([9999], mode="clip"))
    assert allclose(np.asarray(b.take([-3, 175], axis=None, mode="wrap").toarray()),
                    x.take([-3, 175], mode="wrap"))
    with pytest.raises(ValueError):
        b.take([0], mode="nope")


# ----------------------------------------------------------------------
# basic-slice getitem deferred as a window on the chain (ISSUE 25): the
# slice is traced inside the program of whatever reads it
# ----------------------------------------------------------------------

import operator                                   # noqa: E402

from bolt_tpu import engine, obs                  # noqa: E402


def _double(v):
    return v * 2


def _rowsum(v):
    return v.sum(axis=0)


# name -> (before the index, the index, after it); each runs on the TPU
# array and on the local oracle alike
_WINDOW_FORMS = {
    "key_slice": (None, lambda a: a[2:7], None),
    "value_slice": (None, lambda a: a[:, 1:3, 2:5], None),
    "int_on_key_axis": (None, lambda a: a[3], None),
    "int_on_value_axis": (None, lambda a: a[:, 2], None),
    "negative_bounds": (None, lambda a: a[-6:-1, :-1], None),
    "empty_slice": (None, lambda a: a[4:4], None),
    "slice_of_slice": (None, lambda a: a[1:7, :, 1:][2:5, 1:], None),
    "slice_then_map": (None, lambda a: a[2:7], lambda a: a.map(_double)),
    "map_then_key_slice": (lambda a: a.map(_double), lambda a: a[2:7], None),
}

_TERMINALS = {
    "sum": lambda a: a.sum(axis=(0,)),
    "mean": lambda a: a.mean(axis=(0,)),
    "std": lambda a: a.std(axis=(0,)),
    "var": lambda a: a.var(axis=(0,)),
    "max": lambda a: a.max(axis=(0,)),
    "min": lambda a: a.min(axis=(0,)),
    "ptp": lambda a: a.ptp(axis=(0,)),
    "reduce_add": lambda a: a.reduce(operator.add, axis=(0,)),
}


def _ask(a, form, terminal, cache=False):
    pre, index, post = _WINDOW_FORMS[form]
    if pre is not None:
        a = pre(a)
    a = index(a)
    if cache:
        a = a.cache()             # the slice materialised: two programs
    if post is not None:
        a = post(a)
    return np.asarray(_TERMINALS[terminal](a).toarray())


@pytest.mark.filterwarnings("ignore::RuntimeWarning")   # numpy on empties
@pytest.mark.parametrize("terminal", sorted(_TERMINALS))
@pytest.mark.parametrize("form", sorted(_WINDOW_FORMS))
def test_window_fuses_into_the_terminal_that_reads_it(mesh, form, terminal):
    x = _x()
    b, lo = bolt.array(x, mesh), bolt.array(x)
    try:
        want = _ask(lo, form, terminal)
    except (ValueError, TypeError) as exc:
        # a zero-size max/min/ptp or reduce: the same refusal
        with pytest.raises(type(exc)):
            _ask(b, form, terminal)
        return
    n0 = engine.counters()
    two = _ask(b, form, terminal, cache=True)
    n1 = engine.counters()
    got = _ask(b, form, terminal)
    n2 = engine.counters()
    assert got.shape == want.shape and got.dtype == want.dtype
    if np.array_equal(two, want, equal_nan=True):
        # bit for bit where it was
        assert np.array_equal(got, want, equal_nan=True)
    assert np.allclose(got, want, equal_nan=True)
    # one launch fewer than with the slice materialised, and the window
    # counted once (reduce over axis 0 of a keyless result re-splits
    # first: of the materialised slice that is a view since PR 26, so
    # there the two ways launch the same number)
    resplit = (form, terminal) == ("int_on_key_axis", "reduce_add")
    assert n2["dispatches"] - n1["dispatches"] \
        == n1["dispatches"] - n0["dispatches"] - (0 if resplit else 1)
    # ... which is ONE launch (ptp subtracts its two extrema in a second
    # tiny program; the re-split of a deferred window is a program)
    two_step = terminal == "ptp" or resplit
    assert n2["dispatches"] - n1["dispatches"] == (2 if two_step else 1)
    assert n2["getitems_fused"] - n1["getitems_fused"] == 1
    assert n1["getitems_fused"] == n0["getitems_fused"]


@pytest.mark.parametrize("form", sorted(_WINDOW_FORMS))
def test_window_is_deferred_and_materialises_like_a_chain(mesh, form):
    x = _x()
    pre, index, post = _WINDOW_FORMS[form]
    b, want = bolt.array(x, mesh), bolt.array(x)
    if pre is not None:
        b, want = pre(b), pre(want)
    n0 = engine.counters()["dispatches"]
    f0 = engine.counters()["getitems_fused"]
    w, want = index(b), np.asarray(index(want))
    assert engine.counters()["dispatches"] == n0      # nothing launched
    assert w.deferred and w.shape == want.shape
    assert w.split == (0 if form == "int_on_key_axis" else 1)
    if post is not None:
        w, want = post(w), np.asarray(post(bolt.array(want)))
    got = w.toarray()                    # one program, as today's getitem
    assert engine.counters()["dispatches"] == n0 + 1
    assert engine.counters()["getitems_fused"] == f0  # not fused: launched
    assert allclose(got, want)
    assert not w.deferred                # the chain is retired
    assert allclose(w.toarray(), want)


def test_window_of_a_window_is_one_entry_and_key_windows_move_up(mesh):
    from bolt_tpu.tpu.array import _Window
    x = _x()
    b = bolt.array(x, mesh)
    w = b[1:7, :, 1:][2:5, 1:][:, 0]
    (win,) = w._chain[1]
    assert win == _Window((3, 1, 1), (3, 1, 4), (1,), 1)
    assert allclose(w.toarray(), x[1:7, :, 1:][2:5, 1:][:, 0])
    # a key window commutes with the per-record maps before it: the map
    # runs over the window's records only
    m = b.map(_double).map(_rowsum)[2:7][1:3]
    assert [type(f) is _Window for f in m._chain[1]] == [True, False, False]
    assert m._chain[1][0] == _Window((3,), (2,), (), 1)
    assert allclose(m.toarray(), (x * 2).sum(axis=1)[3:5])
    # ... but not with a with_keys map, whose keys it would shift
    k = b.map(lambda kv: kv[1] + kv[0][0], with_keys=True)[2:7]
    assert [type(f) is _Window for f in k._chain[1]] == [False, True]
    want = (x + np.arange(8).reshape(8, 1, 1))[2:7]
    assert allclose(k.toarray(), want)
    assert allclose(b[2:7].map(lambda kv: kv[1] + kv[0][0], with_keys=True)
                    .toarray(), x[2:7] + np.arange(5).reshape(5, 1, 1))
    # a value window after maps that kept the value shape stays in place
    v = b.map(_double)[:, 1:3]
    assert [type(f) is _Window for f in v._chain[1]] == [False, True]
    assert allclose(v.sum(axis=(0,)).toarray(), (x * 2)[:, 1:3].sum(axis=0))


def test_window_over_two_key_axes_carries_the_split(mesh):
    x = np.random.RandomState(3).randn(4, 6, 5, 3)
    b = bolt.array(x, mesh, axis=(0, 1))
    w = b[2]
    assert (w.split, w.shape) == (1, (6, 5, 3))
    assert allclose(w.map(_double).sum(axis=(0,)).toarray(),
                    (x[2] * 2).sum(axis=0))
    w = b.map(_double, axis=(0, 1))[1:3, 4]
    assert (w.split, w.shape) == (1, (2, 5, 3))
    assert allclose(w.mean(axis=(0,)).toarray(), (x * 2)[1:3, 4].mean(axis=0))
    assert allclose(b[1, 2].toarray(), x[1, 2]) and b[1, 2].split == 0
    assert allclose(b[:, 2][1:3].first(), x[1, 2])
    from bolt_tpu import analysis
    rep = analysis.check(b.map(_double, axis=(0, 1))[1:3, 4].map(_rowsum))
    assert not [d for d in rep.diagnostics if d.severity == "error"]
    assert [s.split for s in rep.stages] == [2, 1, 1, 1]
    assert tuple(rep.stages[-1].shape) == (2, 3)


def _streamed(x, mesh):
    return bolt.fromcallback(lambda idx: x[idx], x.shape, mesh,
                             dtype=x.dtype)


_FALLBACKS = {
    "array_index": (lambda b: b, lambda a: a[[0, 3, 5]]),
    "boolean_mask": (lambda b: b,
                     lambda a: a[np.arange(8) % 3 == 0]),
    "step_2": (lambda b: b, lambda a: a[::2]),
    "negative_step": (lambda b: b, lambda a: a[:, ::-1]),
    "value_window_after_a_shape_changing_map": (
        lambda b: b.map(_rowsum), lambda a: a[:, 1:4]),
    "filtered_source": (
        lambda b: b.filter(lambda v: v.mean() > -10), lambda a: a[2:7]),
    "pending_statistic": (lambda b: b.sum(axis=(1,)), lambda a: a[2:7]),
}


@pytest.mark.parametrize("case", sorted(_FALLBACKS))
def test_forms_a_window_cannot_serve_keep_the_eager_program(mesh, case):
    x = _x()
    source, index = _FALLBACKS[case]
    src, lo = source(bolt.array(x, mesh)), source(bolt.array(x))
    n0 = engine.counters()["getitems_fused"]
    obs.enable()
    try:
        out = index(src)
        spans = [s.name for s in obs.spans()]
    finally:
        obs.disable()
        obs.clear()
    assert spans.count("array.getitem") == 1      # launched, as before
    assert not out.deferred
    assert allclose(out.toarray(), np.asarray(index(lo)))
    assert allclose(out.sum(axis=(0,)).toarray(),
                    np.asarray(index(lo)).sum(axis=0))
    assert engine.counters()["getitems_fused"] == n0


def test_streamed_source_keeps_the_eager_program(mesh):
    x = _x()
    src = _streamed(x, mesh)
    n0 = engine.counters()["getitems_fused"]
    out = src[2:7]
    assert not out.deferred and not src.streaming   # materialised, sliced
    assert allclose(out.toarray(), x[2:7])
    assert engine.counters()["getitems_fused"] == n0


def test_windowed_chain_is_never_donated_and_its_base_stays_readable(mesh):
    x = _x()
    with engine.donation(0):
        # control: the same shape of chain without a window IS donated
        m = bolt.array(x, mesh).map(_double)
        n0 = engine.counters()["donations"]
        m.sum().toarray()
        assert engine.counters()["donations"] == n0 + 1
        # a window over a base nobody else holds any more
        b = bolt.array(x, mesh)
        w = b[2:7].map(_double)
        del b
        for read in (lambda: w.sum().toarray(),
                     lambda: w.reduce(operator.add).toarray(),
                     lambda: w.filter(lambda v: v.mean() > -10).sum()
                     .toarray(),
                     lambda: w.toarray()):
            read()
            assert engine.counters()["donations"] == n0 + 1
        assert allclose(w.toarray(), x[2:7] * 2)
        # and the array it was cut from keeps answering
        b = bolt.array(x, mesh)
        b[2:7].sum().toarray()
        b[2:7].cache()
        assert allclose(b.toarray(), x)
        assert engine.counters()["donations"] == n0 + 1
    keys = [k for k in engine._CACHE if any(
        type(f).__name__ == "_Window"
        for part in k if isinstance(part, tuple) for f in part)]
    # donate is False in every key that holds a window
    assert keys and not [k for k in keys if any(p is True for p in k)]


def test_window_keeps_its_base_alive_until_cached(mesh):
    import weakref
    x = _x()
    big = bolt.array(x, mesh)
    ref = weakref.ref(big._data)
    small = big[:2]
    del big
    assert ref() is not None             # the view holds the whole base
    small.cache()
    assert ref() is None                 # cut loose
    assert allclose(small.toarray(), x[:2])
