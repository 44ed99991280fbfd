"""``__array_function__``: the non-ufunc numpy API on the TPU backend —
device-served with NUMPY semantics where the dispatch table covers it,
explicit (warned) host fallback otherwise (VERDICT r2 missing-3).  The
local backend is the oracle: it IS an ndarray, so plain numpy defines
every expected value."""

import numpy as np
import pytest

import bolt_tpu as bolt
from bolt_tpu.tpu import npdispatch


def _x():
    return np.random.RandomState(31).randn(16, 6, 4)


# (name, call) — run against the TPU bolt array; expectation is the same
# call on the raw numpy array (numpy semantics, not bolt's key-axis
# defaults)
DEVICE_CASES = [
    ("sum", lambda a: np.sum(a)),
    ("sum-axis", lambda a: np.sum(a, axis=1)),
    ("sum-keepdims", lambda a: np.sum(a, axis=(0, 2), keepdims=True)),
    ("prod", lambda a: np.prod(a / 2)),
    ("mean", lambda a: np.mean(a)),
    ("var", lambda a: np.var(a)),
    ("var-ddof", lambda a: np.var(a, ddof=1)),
    ("std-axis", lambda a: np.std(a, axis=0)),
    ("min", lambda a: np.min(a)),
    ("amax", lambda a: np.amax(a, axis=2)),
    ("ptp", lambda a: np.ptp(a, axis=1)),
    ("all", lambda a: np.all(a > -99)),
    ("any", lambda a: np.any(a > 1, axis=0)),
    ("cumsum", lambda a: np.cumsum(a)),
    ("cumsum-axis", lambda a: np.cumsum(a, axis=1)),
    ("cumprod-axis", lambda a: np.cumprod(a, axis=2)),
    ("argmax", lambda a: np.argmax(a)),
    ("argmin-axis", lambda a: np.argmin(a, axis=1)),
    ("quantile", lambda a: np.quantile(a, 0.3)),
    ("quantile-vector", lambda a: np.quantile(a, [0.2, 0.8], axis=0)),
    ("percentile", lambda a: np.percentile(a, 75)),
    ("median", lambda a: np.median(a)),
    ("median-axis", lambda a: np.median(a, axis=1)),
    ("sort", lambda a: np.sort(a, axis=0)),
    ("sort-flat", lambda a: np.sort(a, axis=None)),
    ("argsort", lambda a: np.argsort(a, axis=2, kind="stable")),
    ("take", lambda a: np.take(a, [3, 1], axis=0)),
    ("take-flat", lambda a: np.take(a, [5, 0, 17])),
    ("repeat", lambda a: np.repeat(a, 2, axis=1)),
    ("nonzero", lambda a: np.nonzero(a > 1.5)),
    ("ravel", lambda a: np.ravel(a)),
    ("transpose", lambda a: np.transpose(a, (0, 2, 1))),
    ("squeeze", lambda a: np.squeeze(a[0:1])),
    ("swapaxes", lambda a: np.swapaxes(a, 1, 2)),
    ("count_nonzero", lambda a: np.count_nonzero(np.round(a))),
    ("count_nonzero-axis", lambda a: np.count_nonzero(np.round(a), axis=1)),
    ("diff", lambda a: np.diff(a)),
    ("diff-axis0-n2", lambda a: np.diff(a, n=2, axis=0)),
    ("diff-n0", lambda a: np.diff(a, n=0)),
    ("flip", lambda a: np.flip(a)),
    ("flip-axis", lambda a: np.flip(a, 1)),
    ("flip-neg-axis", lambda a: np.flip(a, (-1, 0))),
    ("moveaxis", lambda a: np.moveaxis(a, 1, 2)),
    ("moveaxis-neg", lambda a: np.moveaxis(a, -1, 1)),
    ("moveaxis-multi", lambda a: np.moveaxis(a, (1, 2), (2, 1))),
    ("clip", lambda a: np.clip(a, -0.5, 0.5)),
    ("round", lambda a: np.round(a, 1)),
    ("real", lambda a: np.real(a)),
    ("imag", lambda a: np.imag(a)),
    ("diagonal", lambda a: np.diagonal(a, 0, 1, 2)),
    ("trace", lambda a: np.trace(a, 0, 1, 2)),
    ("searchsorted", lambda a: np.searchsorted(a, [0.0, 0.5])),
]


@pytest.mark.parametrize("name,call", DEVICE_CASES,
                         ids=[c[0] for c in DEVICE_CASES])
def test_numpy_semantics_parity(mesh, name, call):
    x = _x()
    if name == "searchsorted":
        x = np.sort(x.ravel())
    b = bolt.array(x, mesh)
    expect = call(x)
    got = call(b)

    def norm(v):
        if isinstance(v, tuple):
            return tuple(np.asarray(i) for i in v)
        return np.asarray(v.toarray() if hasattr(v, "toarray") else v)

    g, e = norm(got), norm(expect)
    if isinstance(e, tuple):
        assert all(np.array_equal(a, b_) for a, b_ in zip(g, e)), name
    else:
        assert g.shape == e.shape, (name, g.shape, e.shape)
        assert np.allclose(g, e, equal_nan=True), name


def test_device_served_no_gather(mesh, monkeypatch):
    # the acceptance check: np.sum(b) runs ON DEVICE — no toarray, no
    # __array__, and instrument() shows the stat-family program running
    import bolt_tpu.profile as profile
    x = _x()
    b = bolt.array(x, mesh)
    monkeypatch.setattr(
        type(b), "toarray",
        lambda self: (_ for _ in ()).throw(AssertionError("gathered!")))
    monkeypatch.setattr(
        type(b), "__array__",
        lambda self, dtype=None: (_ for _ in ()).throw(
            AssertionError("implicit __array__!")))
    with profile.instrument() as stats:
        # .cache() dispatches each LAZY stat on device — still no
        # toarray/__array__ anywhere in the path
        out = np.sum(b).cache()
        np.mean(b, axis=0).cache()
        np.sort(b, axis=1)
        np.concatenate([b, b], axis=2)
    assert out.mode == "tpu" and out.split == 0
    assert stats.get("stat", {}).get("calls", 0) >= 2
    assert stats.get("sort", {}).get("calls", 0) == 1
    assert stats.get("concat", {}).get("calls", 0) == 1


def test_np_sort_functional_does_not_mutate(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    s = np.sort(b, axis=0)
    assert np.allclose(b.toarray(), x)              # original untouched
    assert np.allclose(s.toarray(), np.sort(x, axis=0))
    # deferred chain: np.sort of a mapped array leaves the map intact
    m = bolt.array(x, mesh).map(lambda v: v * 2)
    s2 = np.sort(m, axis=0)
    assert np.allclose(s2.toarray(), np.sort(x * 2, axis=0))
    assert np.allclose(m.toarray(), x * 2)


def test_concatenate_mixed_operands(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    lo = bolt.array(x)
    # device-first: stays on device
    out = np.concatenate([b, lo, x], axis=0)
    assert hasattr(out, "mode") and out.mode == "tpu"
    assert np.allclose(out.toarray(), np.concatenate([x, x, x], axis=0))
    # host-first: falls back to plain numpy (host result)
    out2 = np.concatenate([x, b], axis=0)
    assert isinstance(out2, np.ndarray)
    assert np.allclose(out2, np.concatenate([x, x], axis=0))


def test_concatenate_axis_none_and_one_program(mesh):
    # axis=None flattens every operand, like numpy — including mixed
    # ranks and split>1 (r3 review finding: this used to crash)
    x = _x()
    b = bolt.array(x, mesh, axis=(0, 1))
    out = np.concatenate([b, b], axis=None)
    assert np.allclose(out.toarray(), np.concatenate([x, x], axis=None))
    assert out.split == 1
    mixed = np.concatenate([b, bolt.array(x[0, 0], mesh)], axis=None)
    assert np.allclose(mixed.toarray(),
                       np.concatenate([x, x[0, 0]], axis=None))
    # n operands are ONE compiled program, not n-1 pairwise copies
    from bolt_tpu.tpu import array as array_mod
    b1 = bolt.array(x, mesh)
    n_before = sum(1 for k in array_mod._JIT_CACHE if k[0] == "concat")
    out = np.concatenate([b1, b1, b1, b1], axis=1)
    assert np.allclose(out.toarray(), np.concatenate([x] * 4, axis=1))
    assert sum(1 for k in array_mod._JIT_CACHE
               if k[0] == "concat") == n_before + 1


class _Duck:
    """A foreign duck array implementing __array_function__."""

    def __array_function__(self, func, types, args, kwargs):
        return "duck-served"


def test_nep18_defers_to_unknown_duck_types(mesh):
    # an operand type we don't recognize gets NotImplemented so ITS
    # handler runs (r3 review finding: bolt used to hijack the call)
    b = bolt.array(_x(), mesh)
    assert np.concatenate([b, _Duck()]) == "duck-served"


def test_searchsorted_rejects_float_sorter(mesh):
    x = np.sort(np.random.RandomState(13).randn(8))
    for b in (bolt.array(x), bolt.array(x, mesh)):
        with pytest.raises(TypeError, match="integer"):
            b.searchsorted(0.0, sorter=np.array([0.2, 2.9, 1.5, 0, 1, 2, 3, 4]))


def test_unsupported_kwargs_fall_back_correctly(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    # out= cannot be served on device; host fallback still honours it
    out = np.zeros(())
    np.sum(b, out=out)
    assert np.allclose(out, x.sum())
    # dtype= falls back and matches numpy exactly
    assert np.allclose(np.sum(b, dtype=np.float32), x.sum(dtype=np.float32))
    # unhandled function (np.trim_zeros) → host path, numpy result
    v = bolt.array(np.array([0.0, 0.0, 1.0, 2.0, 0.0]), mesh)
    st = np.trim_zeros(v)
    assert isinstance(st, np.ndarray)
    assert np.allclose(st, [1.0, 2.0])


def test_implicit_gather_warns_once_above_threshold(mesh, monkeypatch):
    x = _x()
    b = bolt.array(x, mesh)
    monkeypatch.setattr(npdispatch, "IMPLICIT_GATHER_WARN_BYTES", 64)
    monkeypatch.setattr(npdispatch, "_warned", [False])
    with pytest.warns(UserWarning, match="implicitly gathered"):
        np.asarray(b)
    # once per session: the second gather is silent
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        np.asarray(b)
    # explicit toarray never warns
    monkeypatch.setattr(npdispatch, "_warned", [False])
    with _w.catch_warnings():
        _w.simplefilter("error")
        b.toarray()


def test_small_gather_is_silent(mesh, monkeypatch):
    monkeypatch.setattr(npdispatch, "_warned", [False])
    b = bolt.array(_x(), mesh)          # ~3 KB << threshold
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        np.asarray(b)


def test_shape_ndim_size(mesh):
    b = bolt.array(_x(), mesh)
    assert np.shape(b) == (16, 6, 4)
    assert np.ndim(b) == 3
    assert np.size(b) == 384
    assert np.size(b, 1) == 6


def test_np_diff_validation(mesh):
    b = bolt.array(_x(), mesh)
    with pytest.raises(ValueError, match="non-negative"):
        np.diff(b, n=-1)
    with pytest.raises(ValueError):
        np.diff(b, axis=7)
    # prepend/append aren't device-served: host fallback, same answer
    got = np.diff(b, axis=0, prepend=0.0)
    assert np.allclose(got, np.diff(_x(), axis=0, prepend=0.0))
    # bool diff is XOR, like numpy (subtract rejects bool)
    xb = _x() > 0
    gb = np.diff(bolt.array(xb, mesh), axis=0)
    assert gb.dtype == np.bool_
    assert np.array_equal(np.asarray(gb.toarray()), np.diff(xb, axis=0))


def test_np_flip_validation(mesh):
    b = bolt.array(_x(), mesh)
    with pytest.raises(ValueError):
        np.flip(b, 5)                   # out-of-range axis
    with pytest.raises(ValueError):
        np.flip(b, (1, -2))             # duplicate after normalization


def test_np_moveaxis_validation(mesh):
    b = bolt.array(_x(), mesh)
    with pytest.raises(ValueError):
        np.moveaxis(b, 0, -4)            # doubly-negative destination
    with pytest.raises(ValueError):
        np.moveaxis(b, (0, 1), (0, 0))   # repeated destination
    with pytest.raises(ValueError):
        np.moveaxis(b, 5, 0)             # out-of-range source
    with pytest.raises(ValueError):
        np.moveaxis(b, (0, 1), (0,))     # length mismatch


def test_np_split(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    for args in [(4,), (2, 1), (np.array([3, 9]),), ([2, -4],),
                 ([3, 1],)]:
        got = np.split(b, *args) if len(args) == 1 \
            else np.split(b, args[0], axis=args[1])
        want = np.split(x, *args) if len(args) == 1 \
            else np.split(x, args[0], axis=args[1])
        assert len(got) == len(want), args
        for g, w in zip(got, want):
            assert hasattr(g, "mode") and g.mode == "tpu", args
            assert np.allclose(np.asarray(g.toarray()), w), args
    # strict split of a non-dividing count errors like numpy; the
    # array_split form serves it
    with pytest.raises(ValueError, match="equal division"):
        np.split(b, 5)
    got = np.array_split(b, 5)
    want = np.array_split(x, 5)
    assert [g.shape for g in got] == [w.shape for w in want]
    for g, w in zip(got, want):
        assert np.allclose(np.asarray(g.toarray()), w)
    with pytest.raises(ValueError):
        np.split(b, 0)
    # numpy's probe semantics: a 0-d array is a SECTION count, float
    # index entries raise like numpy's slices
    got = np.split(b, np.array(4))
    assert len(got) == 4 and got[0].shape[0] == 4
    with pytest.raises(TypeError):
        np.split(b, [2.5])


def test_np_where(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    # 3-arg: device-served, bolt result, numpy broadcasting
    out = np.where(b > 0, b, 0.0)
    assert hasattr(out, "mode") and out.mode == "tpu" and out.split == 1
    assert np.allclose(np.asarray(out.toarray()), np.where(x > 0, x, 0.0))
    out2 = np.where(x > 1, b, b * -1.0)        # host cond + two device
    assert np.allclose(np.asarray(out2.toarray()),
                       np.where(x > 1, x, -x))
    out3 = np.where(b[0] > 0, 1.0, np.arange(4.0))   # broadcast scalars
    assert np.allclose(np.asarray(out3.toarray()),
                       np.where(x[0] > 0, 1.0, np.arange(4.0)))
    # 1-arg form IS nonzero
    got = np.where(bolt.array((x > 1).astype(int), mesh))
    want = np.where((x > 1).astype(int))
    assert len(got) == len(want)
    assert all(np.array_equal(a, b_) for a, b_ in zip(got, want))
    with pytest.raises(ValueError, match="both or neither"):
        np.where(b, 1.0)
    # a broadcast-prepended axis displaces the keys: split drops to 0
    # even when the leading sizes coincide (r3 review finding)
    cond = np.ones((16, 16, 6, 4), bool)
    out4 = np.where(cond, b, 0.0)
    assert out4.shape == (16, 16, 6, 4) and out4.split == 0
    assert np.allclose(np.asarray(out4.toarray()),
                       np.where(cond, x, 0.0))
    # foreign-mesh operand rejected loudly
    import jax
    other = bolt.array(x, jax.make_mesh((4, 2), ("a", "b")))
    with pytest.raises(ValueError, match="different meshes"):
        np.where(b > 0, b, other)


def test_np_histogram_and_bincount(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    c, e = np.histogram(b, bins=8)
    cn, en = np.histogram(x, bins=8)
    assert np.array_equal(c, cn) and np.allclose(e, en)
    c2, e2 = np.histogram(b, bins=6, range=(-1, 1), density=True)
    cn2, en2 = np.histogram(x, bins=6, range=(-1, 1), density=True)
    assert np.allclose(c2, cn2) and np.allclose(e2, en2)
    # explicit bin-edge arrays fall back to the host path, same answer
    edges = np.linspace(-2, 2, 5)
    c3, e3 = np.histogram(b, bins=edges)
    cn3, _ = np.histogram(x, bins=edges)
    assert np.array_equal(c3, cn3)
    iv = bolt.array((np.abs(x[0]) * 4).astype(np.int64).ravel(), mesh)
    ivn = (np.abs(x[0]) * 4).astype(np.int64).ravel()
    assert np.array_equal(np.bincount(iv), np.bincount(ivn))
    assert np.array_equal(np.bincount(iv, minlength=20),
                          np.bincount(ivn, minlength=20))
    # 2-d input: numpy's exact error on both backends
    with pytest.raises(ValueError):
        np.bincount(bolt.array((np.abs(x) * 4).astype(np.int64), mesh))
    # numpy's edge-case rejections hold on the device path too
    with pytest.raises(ValueError, match="negative"):
        np.bincount(iv, minlength=-1)
    with pytest.raises(ValueError, match="finite"):
        np.histogram(b, bins=4, range=(np.nan, np.nan))
    with pytest.raises(ValueError, match="finite"):
        np.histogram(b, bins=4, range=(0.0, np.inf))


def test_np_unique_and_dot(mesh):
    x = np.floor(_x() * 2)
    b = bolt.array(x, mesh)
    u, c = np.unique(b, return_counts=True)
    un, cn = np.unique(x, return_counts=True)
    assert np.array_equal(u, un) and np.array_equal(c, cn)
    # unsupported unique options take the host path, same answer
    u2, inv = np.unique(b, return_inverse=True)
    un2, invn = np.unique(x, return_inverse=True)
    assert np.array_equal(u2, un2) and np.array_equal(inv, invn)
    # np.dot with a device left operand stays on device
    w = np.random.RandomState(3).randn(4, 2)
    d = np.dot(b, w)
    assert hasattr(d, "mode") and d.mode == "tpu"
    assert np.allclose(d.toarray(), x @ w)


# ----------------------------------------------------------------------
# round 4 (VERDICT r3 next-2): the dispatch tail — stack family, layout
# expanders, contractions, cov/corrcoef.  Each case runs on a split=1
# array over the 1-d mesh AND a split=2 array over the 2-d mesh; the
# expectation is plain numpy on the host array.
# ----------------------------------------------------------------------

def _x2():
    return np.random.RandomState(41).randn(8, 6, 4)


TAIL_CASES = [
    ("expand_dims", lambda a: np.expand_dims(a, 1)),
    ("expand_dims-multi", lambda a: np.expand_dims(a, (0, -1))),
    ("expand_dims-boundary", lambda a: np.expand_dims(a, 2)),
    ("broadcast_to-prepend", lambda a: np.broadcast_to(a, (3,) + np.shape(a))),
    ("broadcast_to-same", lambda a: np.broadcast_to(a, np.shape(a))),
    ("tile-scalar", lambda a: np.tile(a, 2)),
    ("tile-tuple", lambda a: np.tile(a, (2, 1, 3))),
    ("tile-longer", lambda a: np.tile(a, (2, 1, 1, 2))),
    ("roll-flat", lambda a: np.roll(a, 5)),
    ("roll-axis", lambda a: np.roll(a, 3, axis=0)),
    ("roll-multi", lambda a: np.roll(a, (1, -2), axis=(0, 2))),
    ("roll-neg-axis", lambda a: np.roll(a, 2, axis=-1)),
    ("rot90-values", lambda a: np.rot90(a, 1, axes=(1, 2))),
    ("rot90-k2-cross", lambda a: np.rot90(a, 2, axes=(0, 2))),
    ("rot90-k0", lambda a: np.rot90(a, 4, axes=(1, 2))),
    ("pad-scalar", lambda a: np.pad(a, 2)),
    ("pad-pairs", lambda a: np.pad(a, ((1, 2), (0, 1), (2, 0)))),
    ("pad-const", lambda a: np.pad(a, 1, constant_values=7.5)),
    ("pad-reflect", lambda a: np.pad(a, 2, mode="reflect")),
    ("pad-reflect-odd", lambda a: np.pad(a, 2, mode="reflect",
                                         reflect_type="odd")),
    ("pad-symmetric", lambda a: np.pad(a, 1, mode="symmetric")),
    ("pad-wrap", lambda a: np.pad(a, 3, mode="wrap")),
    ("pad-edge", lambda a: np.pad(a, 2, mode="edge")),
    ("stack-0", lambda a: np.stack([a, a])),
    ("stack-mid", lambda a: np.stack([a, a, a], axis=2)),
    ("stack-neg", lambda a: np.stack([a, a], axis=-1)),
    ("vstack", lambda a: np.vstack([a, a])),
    ("hstack", lambda a: np.hstack([a, a])),
    ("dstack", lambda a: np.dstack([a, a])),
    ("append-axis", lambda a: np.append(a, np.ones_like(np.asarray(a)),
                                        axis=1)),
    ("append-flat", lambda a: np.append(a, [1.0, 2.0])),
    ("einsum-explicit", lambda a: np.einsum("ijk,ijk->ij", a, a)),
    ("einsum-contract-keys", lambda a: np.einsum("ijk->k", a)),
    ("einsum-implicit", lambda a: np.einsum("ijk,kl", a,
                                            np.ones((4, 5)))),
    ("einsum-transpose-out", lambda a: np.einsum("ijk->kji", a)),
    ("tensordot-axes", lambda a: np.tensordot(
        a, np.ones((6, 4, 3)), axes=([1, 2], [0, 1]))),
    ("tensordot-int", lambda a: np.tensordot(a, np.ones((6, 4)), axes=2)),
    ("inner-vec", lambda a: np.inner(a, np.arange(4.0))),
    ("outer", lambda a: np.outer(a, np.arange(3.0))),
    ("atleast-1d", lambda a: np.atleast_1d(a)),
    ("atleast-3d", lambda a: np.atleast_3d(a)),
    ("copy", lambda a: np.copy(a)),
]


@pytest.mark.parametrize("layout", ["keys1d", "keys2d"])
@pytest.mark.parametrize("name,call", TAIL_CASES,
                         ids=[c[0] for c in TAIL_CASES])
def test_dispatch_tail_parity(request, layout, name, call):
    if layout == "keys1d":
        m, axis = request.getfixturevalue("mesh"), (0,)
    else:
        m, axis = request.getfixturevalue("mesh2d"), (0, 1)
    x = _x2()
    b = bolt.array(x, m, axis=axis)
    if name == "rot90-values" and layout == "keys2d":
        # on the split=2 layout axes (1, 2) straddle the key/value
        # boundary: the odd rotation rejects like transpose does
        with pytest.raises(ValueError, match="swap"):
            call(b)
        return
    expect = call(x)
    got = call(b)
    g = np.asarray(got.toarray() if hasattr(got, "toarray") else got)
    e = np.asarray(expect)
    assert g.shape == e.shape, (name, g.shape, e.shape)
    assert np.allclose(g, e, equal_nan=True), name


@pytest.mark.parametrize("layout", ["keys1d", "keys2d"])
def test_cov_corrcoef_parity(request, layout):
    m = request.getfixturevalue("mesh" if layout == "keys1d" else "mesh2d")
    axis = (0,) if layout == "keys1d" else (0, 1)
    x = np.random.RandomState(42).randn(8, 6)
    b = bolt.array(x, m, axis=axis)
    assert np.allclose(np.cov(b), np.cov(x))
    assert np.allclose(np.cov(b, rowvar=False), np.cov(x, rowvar=False))
    assert np.allclose(np.cov(b, bias=True), np.cov(x, bias=True))
    assert np.allclose(np.cov(b, ddof=0), np.cov(x, ddof=0))
    assert np.allclose(np.corrcoef(b), np.corrcoef(x))
    assert np.allclose(np.corrcoef(b, rowvar=False),
                       np.corrcoef(x, rowvar=False))
    # 1-d: 0-d result, like numpy
    v = x[:, 0]
    bv = bolt.array(v, m) if layout == "keys1d" else bolt.array(v, m)
    assert np.shape(np.cov(bv)) == np.shape(np.cov(v)) == ()
    assert np.allclose(np.cov(bv), np.cov(v))
    assert np.allclose(np.corrcoef(bv), np.corrcoef(v))


def test_dispatch_tail_stays_on_device(mesh, monkeypatch):
    # the acceptance check for the round-4 tail: these calls may not
    # gather — toarray/__array__ are booby-trapped
    x = _x2()
    b = bolt.array(x, mesh)
    monkeypatch.setattr(
        type(b), "toarray",
        lambda self: (_ for _ in ()).throw(AssertionError("gathered!")))
    monkeypatch.setattr(
        type(b), "__array__",
        lambda self, dtype=None: (_ for _ in ()).throw(
            AssertionError("implicit __array__!")))
    np.expand_dims(b, 0)
    np.broadcast_to(b, (2, 8, 6, 4))
    np.tile(b, (2, 1, 1))
    np.roll(b, 3, axis=1)
    np.rot90(b, axes=(1, 2))
    np.pad(b, 1)
    np.stack([b, b], axis=1)
    np.vstack([b, b])
    np.hstack([b, b])
    np.dstack([b, b])
    np.append(b, b, axis=0)
    np.einsum("ijk,ijk->i", b, b)
    np.tensordot(b, np.ones((4, 2)), axes=([2], [0]))
    np.inner(b, np.ones(4))
    np.outer(b, np.ones(3))
    np.copy(b)
    np.atleast_3d(b)


def test_dispatch_tail_deferred_chains_fuse(mesh):
    # a deferred map fuses into the tail's ONE compiled program and the
    # original chain stays intact
    x = _x2()
    b = bolt.array(x, mesh).map(lambda v: v * 2.0)
    out = np.stack([b, b], axis=0)
    assert np.allclose(out.toarray(), np.stack([x * 2, x * 2], axis=0))
    s = np.roll(b, 2, axis=0)
    assert np.allclose(s.toarray(), np.roll(x * 2, 2, axis=0))
    assert np.allclose(b.toarray(), x * 2)


def test_dispatch_tail_rejections(mesh):
    x = _x2()
    b = bolt.array(x, mesh)
    # numpy-exact rejections on the device path
    with pytest.raises(ValueError, match="repeated axis"):
        np.expand_dims(b, (0, 0))
    with pytest.raises(np.exceptions.AxisError):
        np.expand_dims(b, 9)
    with pytest.raises(ValueError):
        np.broadcast_to(b, (2, 2, 2))
    with pytest.raises(np.exceptions.AxisError):
        np.roll(b, 1, axis=5)
    with pytest.raises(ValueError, match="must be different"):
        np.rot90(b, axes=(1, 1))
    with pytest.raises(ValueError, match="len\\(axes\\)"):
        np.rot90(b, axes=(0, 1, 2))
    with pytest.raises(ValueError, match="out of range"):
        np.rot90(b, axes=(0, 5))
    # odd rotations across the key/value boundary: the transpose rule
    with pytest.raises(ValueError, match="swap"):
        np.rot90(b, 1, axes=(0, 1))
    # even rotations are pure flips — allowed across the boundary
    assert np.allclose(np.rot90(b, 2, axes=(0, 1)).toarray(),
                       np.rot90(x, 2, axes=(0, 1)))
    with pytest.raises(ValueError, match="negative"):
        np.pad(b, -1)
    with pytest.raises(TypeError, match="integral"):
        np.pad(b, 1.5)
    with pytest.raises(ValueError, match="unsupported keyword"):
        np.pad(b, 1, mode="edge", constant_values=3)
    with pytest.raises(ValueError, match="same shape"):
        np.stack([b, bolt.array(x[:4], mesh)])
    with pytest.raises(np.exceptions.AxisError):
        np.stack([b, b], axis=7)
    with pytest.raises(ValueError, match="2 dimensions"):
        np.cov(bolt.array(np.random.RandomState(1).randn(2, 3, 4), mesh))
    with pytest.raises(ValueError, match="ddof"):
        np.cov(bolt.array(np.random.RandomState(1).randn(4, 3), mesh),
               ddof=1.5)


def test_dispatch_tail_fallbacks_stay_correct(mesh):
    # unsupported forms take the warned host path but remain
    # numpy-correct
    x = _x2()
    b = bolt.array(x, mesh)
    out = np.einsum("i...,i...->...", b, b)     # ellipsis: device (r4)
    assert hasattr(out, "mode") and out.mode == "tpu"
    assert np.allclose(out.toarray(), np.einsum("i...,i...->...", x, x))
    out2 = np.pad(b, 1, mode="mean")                 # stat mode: host
    assert np.allclose(out2, np.pad(x, 1, mode="mean"))
    out3 = np.pad(b, 1, mode="linear_ramp", end_values=2.0)
    assert np.allclose(out3, np.pad(x, 1, mode="linear_ramp",
                                    end_values=2.0))
    # weighted cov: host path, numpy-exact
    w = np.arange(1, 7)
    out4 = np.cov(bolt.array(x[:, :, 0], mesh), fweights=w)
    assert np.allclose(out4, np.cov(x[:, :, 0], fweights=w))


def test_einsum_key_survival_and_mxu_policy(mesh, mesh2d):
    # keys survive when the anchor's key labels lead the output
    x = _x2()
    b = bolt.array(x, mesh)
    out = np.einsum("ijk,kl->ijl", b, np.ones((4, 3)))
    assert out.split == 1
    assert np.allclose(out.toarray(),
                       np.einsum("ijk,kl->ijl", x, np.ones((4, 3))))
    # keys contracted: re-keyed to split=0
    out2 = np.einsum("ijk->jk", b)
    assert out2.split == 0
    # split=2 anchor over the 2-d mesh, both keys surviving
    b2 = bolt.array(x, mesh2d, axis=(0, 1))
    out3 = np.einsum("ijk,k->ij", b2, np.arange(4.0))
    assert out3.split == 2
    assert np.allclose(out3.toarray(), np.einsum("ijk,k->ij", x,
                                                 np.arange(4.0)))


def test_stack_family_split_bookkeeping(mesh, mesh2d):
    x = _x2()
    b = bolt.array(x, mesh)
    assert np.stack([b, b], axis=0).split == 2     # new leading key axis
    assert np.stack([b, b], axis=1).split == 1     # value-side insert
    assert np.expand_dims(b, 0).split == 2
    assert np.expand_dims(b, 1).split == 1         # at the boundary: value
    assert np.broadcast_to(b, (2,) + x.shape).split == 2
    assert np.tile(b, (3, 1, 1, 1)).split == 2
    b2 = bolt.array(x, mesh2d, axis=(0, 1))
    assert np.stack([b2, b2], axis=1).split == 3   # inserted among keys
    assert np.roll(b2, 1, axis=0).split == 2


def test_dispatch_tail_review_edges(mesh):
    # round-4 review findings: numpy-exact edge behavior
    x = _x2()
    b = bolt.array(x, mesh)
    # empty shift/axis tuples broadcast to zero rolls — unchanged copy
    assert np.allclose(np.roll(b, 1, axis=()).toarray(),
                       np.roll(x, 1, axis=()))
    assert np.allclose(np.roll(b, (), axis=()).toarray(), x)
    assert np.allclose(np.roll(b, (), axis=0).toarray(), x)
    # stack-family shape clashes are numpy's ValueError, not a jax
    # TypeError from inside the trace
    with pytest.raises(ValueError, match="must match exactly"):
        np.vstack([b, np.ones((3, 6, 4))[..., :3]])
    with pytest.raises(ValueError, match="same number of dimensions"):
        np.hstack([b, np.ones(3)])
    # non-default casting routes to the host path so numpy's TypeError
    # is preserved
    with pytest.raises(TypeError, match="Cannot cast"):
        np.stack([b.astype(np.float32), b], casting="no", dtype=np.float64)


# ----------------------------------------------------------------------
# round 4 batch 2: nan-reductions, norms, sampling helpers — device-
# served with numpy semantics, both mesh layouts
# ----------------------------------------------------------------------

def _xnan():
    x = np.random.RandomState(43).randn(8, 6, 4)
    x.ravel()[::17] = np.nan
    return x


TAIL2_CASES = [
    ("nansum", lambda a: np.nansum(a)),
    ("nansum-axis", lambda a: np.nansum(a, axis=1)),
    ("nanmean-keepdims", lambda a: np.nanmean(a, axis=(0, 2),
                                              keepdims=True)),
    ("nanvar-ddof", lambda a: np.nanvar(a, axis=0, ddof=1)),
    ("nanstd", lambda a: np.nanstd(a)),
    ("nanmin-axis", lambda a: np.nanmin(a, axis=2)),
    ("nanmax", lambda a: np.nanmax(a)),
    ("nanprod-axis", lambda a: np.nanprod(a / 2, axis=1)),
    ("nanmedian-axis", lambda a: np.nanmedian(a, axis=0)),
    ("nanquantile", lambda a: np.nanquantile(a, 0.3)),
    ("nanquantile-vector", lambda a: np.nanquantile(a, [0.2, 0.8],
                                                    axis=0)),
]

TAIL2_CLEAN = [
    ("norm-fro-all", lambda a: np.linalg.norm(a)),
    ("norm-axis", lambda a: np.linalg.norm(a, axis=2)),
    ("norm-ord1", lambda a: np.linalg.norm(a, ord=1, axis=1)),
    ("norm-inf", lambda a: np.linalg.norm(a, ord=np.inf, axis=0)),
    ("average", lambda a: np.average(a)),
    ("average-axis", lambda a: np.average(a, axis=1)),
    ("average-weights", lambda a: np.average(
        a, axis=1, weights=np.arange(1.0, 7.0))),
    ("average-full-weights", lambda a: np.average(
        a, weights=np.abs(np.asarray(a)) + 1.0)),
    ("isin", lambda a: np.isin(np.round(a), [0.0, 1.0, -1.0])),
    ("isin-invert", lambda a: np.isin(np.round(a), [0.0], invert=True)),
    ("digitize", lambda a: np.digitize(a, np.linspace(-2, 2, 9))),
    ("digitize-right", lambda a: np.digitize(a, np.linspace(-2, 2, 9),
                                             right=True)),
    ("interp", lambda a: np.interp(a, np.linspace(-3, 3, 11),
                                   np.linspace(0.0, 1.0, 11))),
    ("gradient-axis", lambda a: np.gradient(a, axis=1)),
    ("gradient-spacing", lambda a: np.gradient(a, 0.5, axis=2)),
]


@pytest.mark.parametrize("layout", ["keys1d", "keys2d"])
@pytest.mark.parametrize(
    "name,call", TAIL2_CASES + TAIL2_CLEAN,
    ids=[c[0] for c in TAIL2_CASES + TAIL2_CLEAN])
def test_dispatch_tail2_parity(request, layout, name, call):
    if layout == "keys1d":
        m, axis = request.getfixturevalue("mesh"), (0,)
    else:
        m, axis = request.getfixturevalue("mesh2d"), (0, 1)
    x = _xnan() if (name.startswith("nan")) else _x2()[:8]
    b = bolt.array(x, m, axis=axis)
    expect = call(x)
    got = call(b)

    def norm(v):
        return np.asarray(v.toarray() if hasattr(v, "toarray") else v)

    g, e = norm(got), norm(expect)
    assert g.shape == e.shape, (name, g.shape, e.shape)
    assert np.allclose(g, e, equal_nan=True), name


def test_dispatch_tail2_details(mesh):
    x = _x2()[:8]
    b = bolt.array(x, mesh)
    # gradient over every axis returns a list of device arrays
    outs = np.gradient(b)
    expects = np.gradient(x)
    assert isinstance(outs, list) and len(outs) == 3
    for o, e in zip(outs, expects):
        assert o.mode == "tpu" and o.split == 1
        assert np.allclose(o.toarray(), e)
    # average(returned=True) matches numpy's (avg, sum-of-weights) pair
    avg, scl = np.average(b, axis=0, returned=True)
    ea, es = np.average(x, axis=0, returned=True)
    assert np.allclose(avg.toarray(), ea) and np.allclose(scl, es)
    w = np.arange(1.0, 7.0)
    avg2, scl2 = np.average(b, axis=1, weights=w, returned=True)
    ea2, es2 = np.average(x, axis=1, weights=w, returned=True)
    assert np.allclose(avg2.toarray(), ea2) and np.allclose(scl2, es2)
    # keys survive value-axis reductions, die on key-axis ones
    assert np.nansum(bolt.array(_xnan(), mesh), axis=2).split == 1
    assert np.nansum(bolt.array(_xnan(), mesh), axis=0).split == 0
    assert np.linalg.norm(b, axis=2).split == 1
    # numpy-exact rejections
    with pytest.raises(ValueError, match="Length of weights"):
        np.average(b, axis=1, weights=np.arange(5.0))
    with pytest.raises(ZeroDivisionError):
        np.average(b, axis=1, weights=np.zeros(6))
    with pytest.raises(ValueError, match="at least 2 elements"):
        np.gradient(bolt.array(x[:1], mesh), axis=0)
    with pytest.raises(ValueError, match="same length"):
        np.interp(b, np.arange(4.0), np.arange(5.0))
    with pytest.raises(ValueError, match="1-D"):
        np.interp(b, np.ones((2, 2)), np.ones((2, 2)))
    # nan-aware semantics really differ from the plain reductions here
    xb = bolt.array(_xnan(), mesh)
    assert np.isnan(float(np.asarray(np.sum(xb).toarray())))
    assert not np.isnan(float(np.asarray(np.nansum(xb).toarray())))


def test_dispatch_tail2_split_matches_method_convention(mesh, mesh2d):
    # review finding (round 4): split must follow the AXIS-based rule of
    # BoltArrayTPU._stat, not shape coincidence — square arrays are the
    # trap
    x = np.random.RandomState(44).randn(8, 8, 4)   # square leading dims
    b = bolt.array(x, mesh)
    assert np.nansum(b, axis=0).split == b.sum(axis=0).split == 0
    assert np.nansum(b, axis=1).split == b.sum(axis=1).split == 1
    assert np.nanmean(b, axis=0, keepdims=True).split == \
        b.mean(axis=0, keepdims=True).split == 1
    assert np.linalg.norm(b, axis=0).split == 0
    assert np.linalg.norm(b, axis=2).split == 1
    assert np.average(b, axis=0).split == 0
    b2 = bolt.array(x, mesh2d, axis=(0, 1))
    assert np.nansum(b2, axis=0).split == 1
    assert np.nansum(b2, axis=(0, 1)).split == 0
    assert np.nanvar(b2, axis=2).split == 2
    # vector-q nanquantile prepends a flat KEY axis, the quantile-method
    # convention
    assert np.nanquantile(b, [0.2, 0.8], axis=1).split == \
        b.quantile([0.2, 0.8], axis=1).split == 2
    # integer data: the promoted-float path computes instead of crashing
    ib = bolt.array(np.arange(24).reshape(4, 6), mesh)
    assert np.allclose(np.asarray(np.nanquantile(ib, 0.3).toarray()),
                       np.nanquantile(np.arange(24).reshape(4, 6), 0.3))
    assert np.allclose(np.asarray(np.nanmedian(ib).toarray()),
                       np.median(np.arange(24).reshape(4, 6)))
    # unsorted bins: numpy's exact rejection, not silent garbage
    with pytest.raises(ValueError, match="monotonically"):
        np.digitize(b, np.array([3.0, 1.0, 2.0]))
    # decreasing bins are legal and numpy-identical
    bins = np.array([2.0, 1.0, -1.0, -2.0])
    assert np.array_equal(np.asarray(np.digitize(b, bins).toarray()),
                          np.digitize(x, bins))


# ----------------------------------------------------------------------
# round 4 batch 3: np.linalg decompositions on device (jnp.linalg in
# one fused program; keys survive as batch dims)
# ----------------------------------------------------------------------

def _spd():
    g = np.random.RandomState(45).randn(16, 5, 5)
    return np.einsum("bij,bkj->bik", g, g) + np.eye(5)


def _tall():
    return np.random.RandomState(46).randn(12, 5)


LINALG_CASES = [
    ("inv", lambda a: np.linalg.inv(a), _spd),
    ("det", lambda a: np.linalg.det(a), _spd),
    ("cholesky", lambda a: np.linalg.cholesky(a), _spd),
    ("cholesky-upper", lambda a: np.linalg.cholesky(a, upper=True), _spd),
    ("eigvalsh", lambda a: np.linalg.eigvalsh(a), _spd),
    ("matrix_power", lambda a: np.linalg.matrix_power(a, 3), _spd),
    ("matrix_power-neg", lambda a: np.linalg.matrix_power(a, -1), _spd),
    ("svd-vals", lambda a: np.linalg.svd(a, compute_uv=False), _tall),
    ("qr-r", lambda a: np.abs(np.linalg.qr(a, mode="r")), _tall),
    ("matrix_rank", lambda a: np.linalg.matrix_rank(a), _tall),
    ("pinv", lambda a: np.linalg.pinv(a), _tall),
    ("norm-nuc", lambda a: np.linalg.norm(a, ord="nuc", axis=(0, 1)),
     _tall),
]


@pytest.mark.parametrize("name,call,make", LINALG_CASES,
                         ids=[c[0] for c in LINALG_CASES])
def test_linalg_parity(mesh, name, call, make):
    x = make()
    b = bolt.array(x, mesh)
    e = call(x)
    g = call(b)
    gv = np.asarray(g.toarray() if hasattr(g, "toarray") else g)
    assert gv.shape == np.shape(e), (name, gv.shape, np.shape(e))
    assert np.allclose(gv, e, rtol=1e-6, atol=1e-8), name


def test_linalg_multi_output_and_batch_split(mesh, mesh2d):
    sq, m = _spd(), _tall()
    b = bolt.array(sq, mesh)
    bm = bolt.array(m, mesh)
    # slogdet / eigh / svd / qr return tuples of device arrays
    sgn, ld = np.linalg.slogdet(b)
    esgn, eld = np.linalg.slogdet(sq)
    assert sgn.mode == ld.mode == "tpu"
    assert np.allclose(sgn.toarray(), esgn)
    assert np.allclose(ld.toarray(), eld)
    w, v = np.linalg.eigh(b)
    assert np.allclose(w.toarray(), np.linalg.eigh(sq)[0])
    recon = np.einsum("bij,bj,bkj->bik", np.asarray(v.toarray()),
                      np.asarray(w.toarray()), np.asarray(v.toarray()))
    assert np.allclose(recon, sq)
    u, s, vh = np.linalg.svd(bm)
    assert np.allclose(s.toarray(), np.linalg.svd(m, compute_uv=False))
    assert np.allclose(
        np.asarray(u.toarray())[:, :5] * np.asarray(s.toarray())
        @ np.asarray(vh.toarray()), m)
    q, r = np.linalg.qr(bm)
    assert np.allclose(np.asarray(q.toarray()) @ np.asarray(r.toarray()),
                       m)
    # batched: the leading key axis survives as a batch dim
    assert np.linalg.inv(b).split == 1
    assert np.linalg.eigh(b)[0].split == 1
    # solve with a host rhs stays on device; lstsq returns numpy's
    # 4-tuple with a plain-int rank
    rhs = np.random.RandomState(47).randn(16, 5, 2)
    assert np.allclose(np.linalg.solve(b, rhs).toarray(),
                       np.linalg.solve(sq, rhs))
    vec = np.random.RandomState(48).randn(12)
    x_, res, rank, sv = np.linalg.lstsq(bm, vec, rcond=None)
    ex, eres, erank, esv = np.linalg.lstsq(m, vec, rcond=None)
    assert np.allclose(x_.toarray(), ex) and rank == erank
    assert np.allclose(res.toarray(), eres)
    assert np.allclose(sv.toarray(), esv)
    # 2-d mesh: batch split caps at the batch rank
    b2 = bolt.array(sq, mesh2d, axis=(0,))
    assert np.linalg.det(b2).split == 1


def test_linalg_rejections_and_uplo(mesh):
    m = _tall()
    bm = bolt.array(m, mesh)
    with pytest.raises(np.linalg.LinAlgError, match="square"):
        np.linalg.inv(bm)
    with pytest.raises(np.linalg.LinAlgError, match="square"):
        np.linalg.det(bm)
    with pytest.raises(np.linalg.LinAlgError, match="two-dimensional"):
        np.linalg.svd(bolt.array(m[:, 0], mesh))
    with pytest.raises(ValueError, match="UPLO"):
        np.linalg.eigh(bolt.array(_spd(), mesh), UPLO="X")
    # UPLO reads ONLY the named triangle of an asymmetric input
    asym = np.random.RandomState(49).randn(5, 5)
    ba = bolt.array(asym, mesh)
    for uplo in ("L", "U"):
        assert np.allclose(
            np.asarray(np.linalg.eigvalsh(ba, UPLO=uplo).toarray()),
            np.linalg.eigvalsh(asym, UPLO=uplo)), uplo
    # vector matrix_rank is a plain scalar like numpy
    assert np.linalg.matrix_rank(bolt.array(np.zeros(5), mesh)) == 0
    assert np.linalg.matrix_rank(bolt.array(np.ones(5), mesh)) == 1


def test_batch23_review_edges(mesh):
    # round-4 review findings on batches 2/3: numpy-exact edges
    x = np.random.RandomState(50).randn(8, 6, 4)
    b = bolt.array(x, mesh)
    # positional ddof for nanvar/nanstd (numpy's 5th positional slot)
    assert np.allclose(np.asarray(np.nanvar(b, 0, None, None, 1).toarray()),
                       np.nanvar(x, 0, None, None, 1))
    assert np.allclose(np.asarray(np.nanstd(b, 1, None, None, 1).toarray()),
                       np.nanstd(x, 1, None, None, 1))
    # duplicate consecutive bin edges are legal, like numpy
    bins = np.array([1.0, 1.0, 2.0])
    assert np.array_equal(np.asarray(np.digitize(b, bins).toarray()),
                          np.digitize(x, bins))
    # interp period=0: numpy's exact rejection, not silent NaNs
    with pytest.raises(ValueError, match="non-zero"):
        np.interp(b, np.arange(4.0), np.arange(4.0), period=0)
    # q is a traced operand: sweeping quantiles reuses ONE executable
    # (fresh shape so no earlier test could have seeded the cache entry)
    from bolt_tpu.tpu import array as array_mod
    bq = bolt.array(np.random.RandomState(54).randn(8, 5, 3), mesh)
    n0 = sum(1 for k in array_mod._JIT_CACHE if k[0] == "nanquantile")
    for qv in (0.1, 0.4, 0.9):
        np.nanquantile(bq, qv)
    assert sum(1 for k in array_mod._JIT_CACHE
               if k[0] == "nanquantile") == n0 + 1
    # matrix_rank: rtol is RELATIVE, tol ABSOLUTE, hermitian honoured
    d = np.diag([10.0, 1.0, 0.1])
    bd = bolt.array(d, mesh)
    assert int(np.asarray(np.linalg.matrix_rank(bd, rtol=0.05).toarray())) \
        == np.linalg.matrix_rank(d, rtol=0.05) == 2
    assert int(np.asarray(np.linalg.matrix_rank(bd, tol=0.05).toarray())) \
        == np.linalg.matrix_rank(d, tol=0.05) == 3
    h = np.diag([2.0, -1.0, 1e-12])
    bh = bolt.array(h, mesh)
    assert int(np.asarray(
        np.linalg.matrix_rank(bh, hermitian=True).toarray())) \
        == np.linalg.matrix_rank(h, hermitian=True)
    # lstsq residuals follow numpy's conventions (empty for
    # underdetermined systems)
    u = np.random.RandomState(51).randn(3, 5)
    bu = bolt.array(u, mesh)
    rhs = np.random.RandomState(52).randn(3)
    _, res_g, _, _ = np.linalg.lstsq(bu, rhs, rcond=None)
    _, res_e, _, _ = np.linalg.lstsq(u, rhs, rcond=None)
    assert np.shape(np.asarray(res_g.toarray())) == np.shape(res_e) == (0,)
    # broadcast rhs with extra leading dims: solve re-keys to 0
    sq = _spd()
    bs = bolt.array(sq, mesh)
    rhs2 = np.random.RandomState(53).randn(2, 16, 5, 5)
    out = np.linalg.solve(bs, rhs2)
    assert out.split == 0
    assert np.allclose(out.toarray(), np.linalg.solve(sq, rhs2))
    # eigvalsh is its own single-output program, not eigh-minus-vectors
    from bolt_tpu.tpu import array as am
    np.linalg.eigvalsh(bs)
    assert any(k[0] == "linalg_eigvalsh" for k in am._JIT_CACHE)


# ----------------------------------------------------------------------
# round 4 batch 4: triangles, diagonals, products, selection
# ----------------------------------------------------------------------

TAIL4_CASES = [
    ("tril", lambda a: np.tril(a[:, :, 0])),
    ("tril-k", lambda a: np.tril(a[:, :, 0], -1)),
    ("triu-k", lambda a: np.triu(a[:, :, 0], 2)),
    ("diag-2d", lambda a: np.diag(a[:, :, 0], 1)),
    ("diag-1d", lambda a: np.diag(a[:, 0, 0])),
    ("diagflat", lambda a: np.diagflat(a[:, :2, 0])),
    ("vander", lambda a: np.vander(a[:, 0, 0], 4)),
    ("kron", lambda a: np.kron(a, np.ones((1, 2, 2)))),
    ("select", lambda a: np.select([a > 0.5, a < -0.5], [a, -a],
                                   default=7.0)),
    ("compress", lambda a: np.compress(
        np.array([True, False] * 4), a, axis=0)),
    ("extract", lambda a: np.extract(np.asarray(a) > 0, a)),
    ("convolve", lambda a: np.convolve(a[:, 0, 0],
                                       np.array([0.5, 1.0, 0.5]))),
    ("correlate-full", lambda a: np.correlate(
        a[:, 0, 0], np.array([0.5, 1.0, 0.5]), "full")),
]


@pytest.mark.parametrize("layout", ["keys1d", "keys2d"])
@pytest.mark.parametrize("name,call", TAIL4_CASES,
                         ids=[c[0] for c in TAIL4_CASES])
def test_dispatch_tail4_parity(request, layout, name, call):
    if layout == "keys1d":
        m, axis = request.getfixturevalue("mesh"), (0,)
    else:
        m, axis = request.getfixturevalue("mesh2d"), (0, 1)
    x = _x2()[:8]
    b = bolt.array(x, m, axis=axis)
    if layout == "keys2d" and name in ("diag-1d", "vander", "convolve",
                                       "correlate-full"):
        pytest.skip("1-d slice of a 2-d-keys array has a single key "
                    "axis")
    expect = call(x)
    got = call(b)
    g = np.asarray(got.toarray() if hasattr(got, "toarray") else got)
    e = np.asarray(expect)
    assert g.shape == e.shape, (name, g.shape, e.shape)
    assert np.allclose(g, e, equal_nan=True), name


def test_dispatch_tail4_details(mesh):
    x = _x2()[:8]
    b = bolt.array(x, mesh)
    # compress/extract are static host-condition paths; a device
    # condition (dynamic shape) falls back but stays correct
    cond = np.asarray(x[:, 0, 0]) > 0
    out = np.compress(cond, b, axis=0)
    assert out.mode == "tpu"
    assert np.allclose(out.toarray(), np.compress(cond, x, axis=0))
    dev_cond = (b[:, 0, 0] > 0)
    out2 = np.extract(dev_cond, b)
    assert np.allclose(np.asarray(out2), np.extract(cond, x))
    # numpy-exact rejections
    with pytest.raises(ValueError, match="same length"):
        np.select([b > 0], [b, b])
    with pytest.raises(ValueError, match="one-dimensional"):
        np.vander(b)
    with pytest.raises(ValueError, match="1- or 2-d"):
        np.diag(b)
    with pytest.raises(ValueError, match="mode"):
        np.convolve(b[:, 0, 0], np.ones(3), mode="bogus")
    # split bookkeeping: triangles/diag keep keys, 2-d diag reduces
    assert np.tril(b[:, :, 0]).split == 1
    assert np.diag(b[:, 0, 0]).split == 1
    assert np.diag(b[:, :, 0]).split == 0   # diagonal of keys x values


def test_batch4_review_edges(mesh):
    x = _x2()[:8]
    b = bolt.array(x, mesh)
    # over-long compress condition with trailing False entries is legal
    cond = np.array([True, False] * 4 + [False, False])
    assert np.allclose(np.compress(cond, b, axis=0).toarray(),
                       np.compress(cond, x, axis=0))
    with pytest.raises(IndexError, match="out of bounds"):
        np.compress(np.array([False] * 9 + [True]), b, axis=0)
    # select's default dtype participates in promotion; 0 vs 0.0 must
    # not collide in the executable cache
    iv = bolt.array(np.arange(8), mesh)
    o_int = np.select([iv > 3], [iv], default=0)
    o_flt = np.select([iv > 3], [iv], default=0.0)
    assert np.asarray(o_int.toarray()).dtype.kind == "i"
    assert np.asarray(o_flt.toarray()).dtype.kind == "f"
    # scalar convolve operands promote like numpy
    v = bolt.array(np.arange(6.0), mesh)
    assert np.allclose(np.asarray(np.convolve(v, 2.0).toarray()),
                       np.convolve(np.arange(6.0), 2.0))
    # multi-output linalg results carry numpy's attribute API
    sq = _spd()
    bs = bolt.array(sq, mesh)
    r = np.linalg.slogdet(bs)
    assert np.allclose(np.asarray(r.sign.toarray()),
                       np.linalg.slogdet(sq).sign)
    e = np.linalg.eigh(bs)
    assert np.allclose(np.asarray(e.eigenvalues.toarray()),
                       np.linalg.eigh(sq).eigenvalues)
    s = np.linalg.svd(bolt.array(_tall(), mesh))
    assert hasattr(s, "S") and hasattr(s, "Vh")
    q = np.linalg.qr(bolt.array(_tall(), mesh))
    assert hasattr(q, "Q") and hasattr(q, "R")
    # 1-d inputs get numpy's at-least-two-dimensional message
    with pytest.raises(np.linalg.LinAlgError, match="two-dimensional"):
        np.linalg.inv(bolt.array(np.arange(4.0), mesh))


# ----------------------------------------------------------------------
# round 4 batch 5: np.fft, apply_along_axis, einsum ellipsis
# ----------------------------------------------------------------------

FFT_CASES = [
    ("fft", lambda a: np.fft.fft(a)),
    ("fft-n-axis", lambda a: np.fft.fft(a, n=10, axis=1)),
    ("ifft", lambda a: np.fft.ifft(a, axis=0)),
    ("rfft", lambda a: np.fft.rfft(a)),
    ("irfft-roundtrip", lambda a: np.fft.irfft(np.fft.rfft(a), n=4)),
    ("hfft", lambda a: np.fft.hfft(a)),
    ("ihfft", lambda a: np.fft.ihfft(a)),
    ("fft2", lambda a: np.fft.fft2(a)),
    ("ifft2", lambda a: np.fft.ifft2(a)),
    ("rfft2", lambda a: np.fft.rfft2(a)),
    ("fftn-axes", lambda a: np.fft.fftn(a, axes=(0, 2))),
    ("fftn-s", lambda a: np.fft.fftn(a, s=(6, 3), axes=(1, 2))),
    ("rfftn", lambda a: np.fft.rfftn(a)),
    ("irfftn-roundtrip", lambda a: np.fft.irfftn(np.fft.rfftn(a),
                                                 s=np.shape(a))),
    ("fft-ortho", lambda a: np.fft.fft(a, norm="ortho")),
    ("fft-forward", lambda a: np.fft.fft(a, norm="forward")),
    ("fftshift", lambda a: np.fft.fftshift(a)),
    ("fftshift-axis", lambda a: np.fft.fftshift(a, axes=1)),
    ("ifftshift", lambda a: np.fft.ifftshift(a, axes=(0, 2))),
    ("apply-scalar", lambda a: np.apply_along_axis(
        lambda v: v.sum(), 1, a)),
    ("apply-vector", lambda a: np.apply_along_axis(
        lambda v: v[:2] * 2.0, 2, a)),
    ("apply-matrix", lambda a: np.apply_along_axis(
        lambda v: np.outer(v[:2], v[:2]), 0, a)),
    ("einsum-ellipsis", lambda a: np.einsum("i...,i...->...", a, a)),
    ("einsum-ellipsis-keep", lambda a: np.einsum("...j->...", a)),
    ("einsum-ellipsis-implicit", lambda a: np.einsum("...ij", a)),
    ("einsum-ellipsis-mixed", lambda a: np.einsum(
        "...i,ij->...j", a, np.ones((4, 2)))),
]


@pytest.mark.parametrize("layout", ["keys1d", "keys2d"])
@pytest.mark.parametrize("name,call", FFT_CASES,
                         ids=[c[0] for c in FFT_CASES])
def test_dispatch_tail5_parity(request, layout, name, call):
    if layout == "keys1d":
        m, axis = request.getfixturevalue("mesh"), (0,)
    else:
        m, axis = request.getfixturevalue("mesh2d"), (0, 1)
    x = _x2()[:8]
    b = bolt.array(x, m, axis=axis)
    expect = call(x)
    got = call(b)
    g = np.asarray(got.toarray() if hasattr(got, "toarray") else got)
    e = np.asarray(expect)
    assert g.shape == e.shape, (name, g.shape, e.shape)
    assert np.allclose(g, e, equal_nan=True), name


def test_dispatch_tail5_details(mesh):
    x = _x2()[:8]
    b = bolt.array(x, mesh)
    # fft along a value axis keeps the keys; apply_along_axis keeps the
    # keys ahead of the applied axis
    assert np.fft.fft(b, axis=2).split == 1
    assert np.apply_along_axis(lambda v: v.sum(), 2, b).split == 1
    assert np.apply_along_axis(lambda v: v.sum(), 0, b).split == 0
    # device results really are device-resident
    assert np.fft.fft(b).mode == "tpu"
    # non-traceable func1d takes the warned host fallback, same answer
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        out = np.apply_along_axis(
            lambda v: float(np.asarray(v).sum()), 1, b)
    assert np.allclose(out, np.apply_along_axis(lambda v: v.sum(), 1, x))
    # numpy's explicit-output-needs-ellipsis rule holds (host raises)
    with pytest.raises(ValueError, match="ellipsis"):
        np.einsum("i...,...->i", b, bolt.array(x[0], mesh))
    # einsum ellipsis key survival: broadcast dims lead the output, so
    # keys survive only when the anchor's keys are the leading
    # broadcast/batch labels
    assert np.einsum("i...,i...->...", b, b).split == 0
    assert np.einsum("...k,kj->...j", b, np.ones((4, 3))).split == 1


def test_batch5_review_edges(mesh):
    x = _x2()[:8]
    b = bolt.array(x, mesh)
    # unhashable kwargs VALUES fall back instead of crashing the cache
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        out = np.apply_along_axis(
            lambda v, w=None: v * w[0], 1, b, w=[2.0, 3.0])
    assert np.allclose(np.asarray(out),
                       np.apply_along_axis(
                           lambda v, w=None: v * w[0], 1, x,
                           w=[2.0, 3.0]))
    # explicit EMPTY einsum output still requires '...' when broadcast
    # dims exist — numpy's exact error, not a wrong-shaped result
    b2 = bolt.array(x[:, :, 0], mesh)
    with pytest.raises(ValueError, match="ellipsis"):
        np.einsum("i...->", b2)


# ----------------------------------------------------------------------
# round 4 batch 6: set operations, complex views, cleanup helpers
# ----------------------------------------------------------------------

def test_set_operations_parity(mesh):
    rs = np.random.RandomState(55)
    a = rs.randint(0, 20, 64).astype(float)
    c = rs.randint(10, 30, 48).astype(float)
    ba, bc = bolt.array(a, mesh), bolt.array(c, mesh)
    assert np.array_equal(np.intersect1d(ba, bc), np.intersect1d(a, c))
    assert np.array_equal(np.intersect1d(ba, c), np.intersect1d(a, c))
    assert np.array_equal(np.union1d(ba, bc), np.union1d(a, c))
    assert np.array_equal(np.setdiff1d(ba, bc), np.setdiff1d(a, c))
    assert np.array_equal(np.setxor1d(ba, bc), np.setxor1d(a, c))
    # return_indices: warned host fallback, numpy-exact triple
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        r = np.intersect1d(ba, bc, return_indices=True)
    e = np.intersect1d(a, c, return_indices=True)
    assert all(np.array_equal(np.asarray(i), j) for i, j in zip(r, e))


def test_complex_and_cleanup_parity(mesh, mesh2d):
    x = _x2()[:8]
    for m, axis in ((mesh, (0,)), (mesh2d, (0, 1))):
        b = bolt.array(x, m, axis=axis)
        assert np.allclose(np.asarray(np.sinc(b).toarray()), np.sinc(x))
        assert np.allclose(np.asarray(np.i0(b).toarray()), np.i0(x))
        p = np.cumsum(np.abs(x), axis=2)
        bp = bolt.array(p, m, axis=axis)
        assert np.allclose(np.asarray(np.unwrap(bp).toarray()),
                           np.unwrap(p))
        assert np.allclose(
            np.asarray(np.unwrap(bp, period=3.0, axis=1).toarray()),
            np.unwrap(p, period=3.0, axis=1))
        y = x.copy()
        y[0, 0, 0], y[1, 1, 1], y[2, 2, 2] = np.nan, np.inf, -np.inf
        by = bolt.array(y, m, axis=axis)
        assert np.allclose(np.asarray(np.nan_to_num(by).toarray()),
                           np.nan_to_num(y))
        assert np.allclose(
            np.asarray(np.nan_to_num(by, nan=-1, posinf=9).toarray()),
            np.nan_to_num(y, nan=-1, posinf=9))
        assert np.array_equal(np.asarray(np.isposinf(by).toarray()),
                              np.isposinf(y))
        assert np.array_equal(np.asarray(np.isneginf(by).toarray()),
                              np.isneginf(y))
        z = x[:, :, 0] + 1j * x[:, :, 1]
        bz = bolt.array(z, m, axis=axis)
        assert np.allclose(np.asarray(np.angle(bz).toarray()),
                           np.angle(z))
        assert np.allclose(np.asarray(np.angle(bz, deg=True).toarray()),
                           np.angle(z, deg=True))
        assert np.angle(bz).split == b.split


def test_histogram2d_dd_parity(mesh):
    rs = np.random.RandomState(56)
    x, y = rs.randn(512), rs.randn(512)
    bx, by = bolt.array(x, mesh), bolt.array(y, mesh)
    h, ex, ey = np.histogram2d(bx, by, bins=8)
    hn, exn, eyn = np.histogram2d(x, y, bins=8)
    assert np.allclose(h, hn) and h.dtype == hn.dtype
    assert np.allclose(ex, exn) and np.allclose(ey, eyn)
    h2 = np.histogram2d(bx, by, bins=[4, 6],
                        range=[[-2, 2], [-3, 3]], density=True)[0]
    h2n = np.histogram2d(x, y, bins=[4, 6],
                         range=[[-2, 2], [-3, 3]], density=True)[0]
    assert np.allclose(h2, h2n) and h2.dtype == h2n.dtype
    # per-dimension None range entries: numpy-legal, host fallback
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        hnr = np.histogram2d(bx, by, bins=6, range=[[0, 1], None])[0]
    hnrn = np.histogram2d(x, y, bins=6, range=[[0, 1], None])[0]
    assert np.allclose(hnr, hnrn)
    s = rs.randn(256, 3)
    bs = bolt.array(s, mesh)
    hd, edges = np.histogramdd(bs, bins=4)
    hdn, edgesn = np.histogramdd(s, bins=4)
    assert np.allclose(hd, hdn) and hd.dtype == hdn.dtype
    assert all(np.allclose(a, b_) for a, b_ in zip(edges, edgesn))
    hd2 = np.histogramdd(bs, bins=(3, 4, 5), density=True)[0]
    hd2n = np.histogramdd(s, bins=(3, 4, 5), density=True)[0]
    assert np.allclose(hd2, hd2n)
    # array bin edges: warned host fallback, numpy-exact
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        hfb = np.histogram2d(bx, by, bins=[np.linspace(-2, 2, 5),
                                           np.linspace(-2, 2, 4)])[0]
    hfbn = np.histogram2d(x, y, bins=[np.linspace(-2, 2, 5),
                                      np.linspace(-2, 2, 4)])[0]
    assert np.allclose(hfb, hfbn)


# ----------------------------------------------------------------------
# round 4 batch 8: flips, integration, nan-aware cumulatives/arg stats
# ----------------------------------------------------------------------

TAIL8_CASES = [
    ("flipud", lambda a: np.flipud(a)),
    ("fliplr", lambda a: np.fliplr(a)),
    ("trapezoid", lambda a: np.trapezoid(a)),
    ("trapezoid-dx-axis", lambda a: np.trapezoid(a, dx=0.5, axis=1)),
    ("trapezoid-x", lambda a: np.trapezoid(a, np.linspace(0, 1, 4),
                                           axis=2)),
    ("ediff1d", lambda a: np.ediff1d(a)),
    ("ediff1d-ends", lambda a: np.ediff1d(a, to_end=[9.0],
                                          to_begin=[-1.0, -2.0])),
    ("nancumsum-flat", lambda a: np.nancumsum(a)),
    ("nancumsum-axis", lambda a: np.nancumsum(a, axis=1)),
    ("nancumprod-axis", lambda a: np.nancumprod(a, axis=2)),
    ("nanargmax-flat", lambda a: np.nanargmax(a)),
    ("nanargmax-axis", lambda a: np.nanargmax(a, axis=1)),
    ("nanargmin-axis", lambda a: np.nanargmin(a, axis=0)),
    ("fix", lambda a: np.fix(a * 3)),
]


@pytest.mark.parametrize("name,call", TAIL8_CASES,
                         ids=[c[0] for c in TAIL8_CASES])
def test_dispatch_tail8_parity(mesh, name, call):
    x = _xnan() if "nan" in name else _x2()[:8]
    if name in ("ediff1d", "ediff1d-ends"):
        x = x[:, 0, 0].copy()
    b = bolt.array(x, mesh)
    expect = call(x)
    got = call(b)
    g = np.asarray(got.toarray() if hasattr(got, "toarray") else got)
    e = np.asarray(expect)
    assert g.shape == e.shape, (name, g.shape, e.shape)
    assert np.allclose(g, e, equal_nan=True), name


def test_cross_parity(mesh):
    v3 = np.random.RandomState(57).randn(16, 3)
    b3 = bolt.array(v3, mesh)
    w = np.array([1.0, 0.5, 0.25])
    assert np.allclose(np.asarray(np.cross(b3, w).toarray()),
                       np.cross(v3, w))
    assert np.cross(b3, w).split == 1
    other = np.random.RandomState(58).randn(16, 3)
    assert np.allclose(np.asarray(np.cross(b3, other).toarray()),
                       np.cross(v3, other))
    # 2-vector cross products (scalar result per pair)
    v2 = v3[:, :2]
    b2 = bolt.array(v2, mesh)
    assert np.allclose(np.asarray(np.cross(b2, v2[::-1]).toarray()),
                       np.cross(v2, v2[::-1]))


def test_tail8_split_bookkeeping(mesh):
    x = _xnan()
    b = bolt.array(x, mesh)
    assert np.nancumsum(b, axis=2).split == 1
    assert np.nancumsum(b).split == 1            # flat key convention
    assert np.nanargmax(b, axis=1).split == 1
    assert np.nanargmax(b, axis=0).split == 0
    assert np.trapezoid(b, axis=2).split == 1
    assert np.flipud(b).split == 1


def test_batch8_review_edges(mesh):
    v3 = np.random.RandomState(59).randn(16, 3)
    b3 = bolt.array(v3, mesh)
    # non-default cross axes fall back, numpy-correct
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        out = np.cross(b3, v3[::-1], axisc=0)
    assert np.allclose(out, np.cross(v3, v3[::-1], axisc=0))
    # mixed 2x3 vectors: numpy's deprecated-but-working path
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        mixed = np.cross(bolt.array(v3[:, :2], mesh), np.ones(3))
        expect = np.cross(v3[:, :2], np.ones(3))
    assert np.allclose(np.asarray(mixed), expect)


# ----------------------------------------------------------------------
# round-5 dispatch tail (VERDICT r4 missing-4): take_along_axis,
# lexsort, meshgrid/block/broadcast_arrays, insert/delete/resize, the
# last np.linalg utilities, fft frequency grids, nonsymmetric-eig
# policy — device-served with numpy semantics, both mesh layouts
# ----------------------------------------------------------------------

TAIL9_CASES = [
    ("take_along_axis", lambda a: np.take_along_axis(
        a, np.argsort(np.asarray(a), axis=2), axis=2)),
    ("take_along_axis-key", lambda a: np.take_along_axis(
        a, np.zeros((1, 6, 4), dtype=int), axis=0)),
    ("take_along_axis-neg", lambda a: np.take_along_axis(
        a, np.full((8, 6, 1), -1), axis=2)),
    ("take_along_axis-flat", lambda a: np.take_along_axis(
        a, np.array([0, 17, 5]), axis=None)),
    ("lexsort-seq", lambda a: np.lexsort(
        (np.round(a[:, 0, 0]), np.round(a[:, 1, 0])))),
    ("meshgrid-ij", lambda a: np.meshgrid(
        a[:, 0, 0], np.arange(3.0), indexing="ij")[0]),
    ("meshgrid-xy", lambda a: np.meshgrid(
        a[:, 0, 0], np.arange(3.0), indexing="xy")[1]),
    ("block-flat", lambda a: np.block([a[:, 0, 0], a[:, 1, 1]])),
    ("block-2d", lambda a: np.block(
        [[a[:, :, 0], a[:, :, 1]], [a[:, :, 2], a[:, :, 3]]])),
    ("broadcast_arrays", lambda a: np.broadcast_arrays(
        a, np.ones((1, 6, 1)))[1]),
    ("broadcast_arrays-self", lambda a: np.broadcast_arrays(
        a, np.ones(4))[0]),
    ("insert-int", lambda a: np.insert(a, 2, 5.0, axis=1)),
    ("insert-flat", lambda a: np.insert(a, 3, [1.0, 2.0])),
    ("insert-arr", lambda a: np.insert(a, [1, 3], 0.0, axis=2)),
    ("delete-int", lambda a: np.delete(a, 2, axis=1)),
    ("delete-neg", lambda a: np.delete(a, -1, axis=0)),
    ("delete-slice", lambda a: np.delete(a, slice(1, 4), axis=1)),
    ("delete-arr", lambda a: np.delete(a, [0, 2], axis=2)),
    ("delete-flat", lambda a: np.delete(a, [0, 5, 7])),
    ("resize-up", lambda a: np.resize(a, (10, 6, 4))),
    ("resize-reshape", lambda a: np.resize(a, (4, 12, 4))),
    ("resize-flat", lambda a: np.resize(a, 100)),
    ("linalg-cond", lambda a: np.linalg.cond(
        a[:4, :4, 0] + 3 * np.eye(4))),
    ("linalg-cond-1", lambda a: np.linalg.cond(
        a[:4, :4, 0] + 3 * np.eye(4), p=1)),
    ("linalg-multi_dot", lambda a: np.linalg.multi_dot(
        [a[:, :, 0], np.ones((6, 5)), np.linspace(0, 1, 5)])),
]


@pytest.mark.parametrize("layout", ["keys1d", "keys2d"])
@pytest.mark.parametrize("name,call", TAIL9_CASES,
                         ids=[c[0] for c in TAIL9_CASES])
def test_dispatch_tail9_parity(request, layout, name, call):
    if layout == "keys1d":
        m, axis = request.getfixturevalue("mesh"), (0,)
    else:
        m, axis = request.getfixturevalue("mesh2d"), (0, 1)
    x = _x2()
    b = bolt.array(x, m, axis=axis)
    expect = call(x)
    got = call(b)

    def norm(v):
        return np.asarray(v.toarray() if hasattr(v, "toarray") else v)

    g, e = norm(got), norm(expect)
    assert g.shape == e.shape, (name, g.shape, e.shape)
    assert np.allclose(g, e, equal_nan=True), name


def test_tail9_partition_invariants(mesh):
    """partition's within-partition order is unspecified, so parity is
    the INVARIANT (kth element in sorted place, partitions as sets),
    not array equality."""
    x = _x2()
    b = bolt.array(x, mesh)
    for kth in (0, 3, -1):
        got = np.asarray(np.partition(b, kth, axis=2).toarray())
        k = kth + 4 if kth < 0 else kth
        srt = np.sort(x, axis=2)
        assert np.allclose(got[..., k], srt[..., k])
        assert np.allclose(np.sort(got, axis=2), srt)
        assert (got[..., :k] <= got[..., k:k + 1]).all()
        assert (got[..., k + 1:] >= got[..., k:k + 1]).all()
    # flat + key-axis forms
    gf = np.asarray(np.partition(b, 10, axis=None).toarray())
    assert np.allclose(np.sort(gf), np.sort(x, axis=None))
    assert (gf[:10] <= gf[10]).all()
    g0 = np.asarray(np.partition(b, 2, axis=0).toarray())
    assert np.allclose(g0[2], np.sort(x, axis=0)[2])
    # argpartition: indices select the same invariant values
    ai = np.asarray(np.argpartition(b, 3, axis=2).toarray())
    vals = np.take_along_axis(x, ai, axis=2)
    assert np.allclose(vals[..., 3], np.sort(x, axis=2)[..., 3])
    # kth validation matches numpy on both backends
    lo = bolt.array(x)
    for t in (lo, b):
        with pytest.raises(ValueError, match="out of bounds"):
            np.partition(t, 99, axis=2)


def test_tail9_linalg_details(mesh):
    rs = np.random.RandomState(47)
    A = rs.randn(6, 4, 6, 4) + 5 * np.eye(24).reshape(6, 4, 6, 4)
    bA = bolt.array(A, mesh, axis=(0,))
    got = np.linalg.tensorinv(bA, ind=2)
    assert np.allclose(np.asarray(got.toarray()),
                       np.linalg.tensorinv(A, ind=2), atol=1e-8)
    bvec = rs.randn(6, 4)
    gs = np.linalg.tensorsolve(bA, bolt.array(bvec, mesh))
    assert np.allclose(np.asarray(gs.toarray()),
                       np.linalg.tensorsolve(A, bvec), atol=1e-8)
    with pytest.raises(ValueError, match="Invalid ind"):
        np.linalg.tensorinv(bA, ind=0)
    # nonsymmetric eig: explicit documented policy, not a silent gather
    sq = bolt.array(rs.randn(4, 4), mesh)
    with pytest.raises(NotImplementedError, match="nonsymmetric"):
        np.linalg.eig(sq)
    with pytest.raises(NotImplementedError, match="nonsymmetric"):
        np.linalg.eigvals(sq)
    with pytest.raises(np.linalg.LinAlgError):
        np.linalg.cond(bolt.array(rs.randn(5), mesh))


def test_tail9_fftfreq(mesh):
    # a 0-d device scalar arises from a full reduction
    d = bolt.array(np.full(4, 0.25), mesh).mean()
    assert d.ndim == 0
    got = np.fft.fftfreq(8, d)
    assert np.allclose(np.asarray(got.toarray()), np.fft.fftfreq(8, 0.25))
    got = np.fft.rfftfreq(9, d)
    assert np.allclose(np.asarray(got.toarray()), np.fft.rfftfreq(9, 0.25))


def test_tail9_put_along_axis_policy(mesh):
    b = bolt.array(_x2(), mesh)
    # the host fallback would mutate a discarded copy — loud reject
    with pytest.raises(TypeError, match="immutable"):
        np.put_along_axis(b, np.zeros((8, 6, 1), dtype=int), 0.0, axis=2)
    # numpy target + device indices still works through the host path
    host = _x2()
    idx = bolt.array(np.zeros((8, 6, 1)).astype(int), mesh)
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("ignore")
        np.put_along_axis(host, idx, 7.0, axis=2)
    assert (host[:, :, 0] == 7.0).all()


def test_tail9_validation_parity(mesh):
    x = _x2()
    lo, tp = bolt.array(x), bolt.array(x, mesh)
    for b in (lo, tp):
        with pytest.raises(ValueError):
            np.take_along_axis(b, np.zeros((8, 6), dtype=int), axis=2)
        with pytest.raises(IndexError):
            np.take_along_axis(b, np.full((8, 6, 1), 9), axis=2)
        with pytest.raises(IndexError):
            np.delete(b, 99, axis=0)
        with pytest.raises(IndexError):
            np.insert(b, 99, 0.0, axis=0)
        with pytest.raises(IndexError):
            np.insert(b, [99], 0.0, axis=0)   # array selector too
        with pytest.raises(ValueError):
            np.meshgrid(b[:, 0, 0], np.arange(3.0), indexing="bogus")
    # lexsort ties: stable on both backends
    k1 = np.array([3, 1, 3, 1, 2, 2, 0, 0], dtype=float)
    k2 = np.array([1, 1, 0, 0, 1, 1, 0, 0], dtype=float)
    got = np.lexsort((bolt.array(k1, mesh), bolt.array(k2, mesh)))
    assert np.array_equal(np.asarray(got.toarray()), np.lexsort((k1, k2)))
    # single 2-d key array: rows are the key sequence, last row primary
    karr = np.stack([k1, k2])
    g2 = np.lexsort(bolt.array(karr, mesh))
    assert np.array_equal(np.asarray(g2.toarray()), np.lexsort(karr))


def test_tail9_split_bookkeeping(mesh):
    x = _x2()
    b = bolt.array(x, mesh)
    assert np.take_along_axis(
        b, np.argsort(np.asarray(x), axis=2), axis=2).split == 1
    assert np.partition(b, 2, axis=2).split == 1
    assert np.delete(b, 1, axis=1).split == 1
    assert np.insert(b, 1, 0.0, axis=1).split == 1
    assert np.resize(b, (10, 6, 4)).split == 1
    assert np.linalg.multi_dot([b[:, :, 0], np.ones((6, 2))]).split == 1
    # a 1-d first operand is contracted away: no fabricated key axis
    assert np.linalg.multi_dot(
        [b[:, 0, 0], np.ones((8, 6)), np.ones((6, 2))]).split == 0
    outs = np.broadcast_arrays(b, np.ones(4))
    assert isinstance(outs, tuple) and outs[0].split == 1
    grids = np.meshgrid(b[:, 0, 0], np.arange(3.0))
    assert isinstance(grids, list)


def test_advice_r4_edges(mesh):
    """ADVICE r4 fixes: histogram2d validation + edge dtypes, hstack's
    first-array axis rule."""
    rs = np.random.RandomState(61)
    b16 = bolt.array(rs.randn(16), mesh)
    b8 = bolt.array(rs.randn(8), mesh)
    # mismatched lengths: numpy's eager ValueError, not a trace error
    with pytest.raises(ValueError, match="same length"):
        np.histogram2d(b16, b8)
    # >1-d samples are not silently flattened — numpy rejects them, and
    # the host fallback surfaces its exact error on both backends
    x2 = rs.randn(4, 4)
    with pytest.raises(ValueError):
        np.histogram2d(x2, x2)
    with pytest.raises(ValueError):
        np.histogram2d(bolt.array(x2, mesh), bolt.array(x2, mesh))
    # edges come back float64 even under x64-off production numerics
    h, ex, ey = np.histogram2d(b16, bolt.array(rs.randn(16), mesh))
    assert ex.dtype == np.float64 and ey.dtype == np.float64
    hd, edges = np.histogramdd(bolt.array(rs.randn(16, 3), mesh))
    assert all(e.dtype == np.float64 for e in edges)
    # hstack with a 1-d first operand and 2-d second: numpy's error
    # (decided from the FIRST array alone) on both backends
    for first, second in ((b16, rs.randn(2, 2)),):
        with pytest.raises(ValueError):
            np.hstack([first, second])
        with pytest.raises(ValueError):
            np.hstack([np.asarray(first), second])


def test_every_table_entry_documented():
    """Every ``_TABLE`` entry must appear by name in docs/API.md's
    inventory (VERDICT r4 hygiene: headline claims regenerate from
    artifacts — the doc list cannot silently lag the dispatch table)."""
    import os
    api_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "API.md")
    with open(api_path) as f:
        api = f.read()
    missing = sorted({f.__name__ for f in npdispatch._TABLE
                      if f.__name__ not in api})
    assert not missing, "npdispatch._TABLE entries undocumented in " \
        "docs/API.md: %s" % missing
