"""``var()`` / ``std()`` as one pass of shifted moments (ISSUE 61,
``bolt_tpu/tpu/moments.py``) against float64 NumPy.

The form is ``d = x - c; var = (sum(d*d) - sum(d)**2 / n) / (n - ddof)``
about a pilot ``c``, the mean of a leading corner of at least ``sqrt(n)``
elements.  What these tests hold it to:

* within four times the error of the two-pass form (``jnp.var``) on every
  case, and under an absolute bound, so a case does not pass because both
  forms are lost;
* data far from zero (mean 1e4 and 1e6, deviation 1): the UNSHIFTED form
  ``sum(x*x) - sum(x)**2 / n`` loses every digit there and must FAIL the
  same bound, so nobody simplifies the pilot away;
* an outlier as the very first element, which a one-element pilot would
  take as its centre;
* NaN and infinities as NumPy propagates them; a constant array;
* complex, integer and boolean inputs keep ``jnp.var``, bit for bit.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bolt_tpu as bolt
from bolt_tpu import engine
from bolt_tpu.tpu import moments

SHAPE = (32, 6, 8, 8)
EVERY = (0, 1, 2, 3)
AXES = {"all": EVERY, "keys": (0,), "values": (1, 2, 3)}
F32 = np.float32
# relative error every case must stay under whatever the two-pass form
# reads (float32 sums of some 1e4 elements; the unshifted form reads 1 to
# 1e9 on the offset cases)
BOUND = 2e-3
FLOOR = 2e-5          # below this an error is rounding, not a form's


def _data(kind):
    rng = np.random.default_rng(61)
    if kind == "lattice12":
        # the benchmark's data: integers of 12 bits held as float32
        return rng.integers(-2048, 2048, SHAPE).astype(F32)
    if kind == "mean1e4":
        return (1e4 + rng.standard_normal(SHAPE)).astype(F32)
    if kind == "mean1e6":
        return (1e6 + rng.standard_normal(SHAPE)).astype(F32)
    if kind == "outlier_first":
        x = rng.standard_normal(SHAPE).astype(F32)
        x[0, 0, 0, 0] = 1e4
        return x
    raise ValueError(kind)


KINDS = ("lattice12", "mean1e4", "mean1e6", "outlier_first")
OFFSET = ("mean1e4", "mean1e6")


@pytest.fixture(scope="module")
def one_device():
    return jax.make_mesh((1,), ("k",))


@pytest.fixture
def meshes(mesh, one_device):
    return {1: one_device, 8: mesh}


def _err(got, ref):
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    assert got.shape == ref.shape
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


def _unshifted(x, axes, ddof, keepdims):
    n = float(np.prod([x.shape[a] for a in axes]))
    s1 = jnp.sum(x, axis=axes, keepdims=keepdims)
    s2 = jnp.sum(x * x, axis=axes, keepdims=keepdims)
    return (s2 - s1 * s1 / n) / (n - ddof)


def _finish(name, v):
    return np.sqrt(np.maximum(np.asarray(v, np.float64), 0)) \
        if name == "std" else np.asarray(v, np.float64)


@pytest.mark.parametrize("devices", [1, 8])
@pytest.mark.parametrize("window", [False, True], ids=["whole", "window"])
@pytest.mark.parametrize("axes", sorted(AXES))
@pytest.mark.parametrize("ddof,keepdims", [(0, False), (1, False), (1, True)],
                         ids=["ddof0", "ddof1", "ddof1-keepdims"])
@pytest.mark.parametrize("name", ["var", "std"])
@pytest.mark.parametrize("kind", KINDS)
def test_against_float64(meshes, kind, name, ddof, keepdims, axes, window,
                         devices):
    x = _data(kind)
    ax = AXES[axes]
    b = bolt.array(x, meshes[devices])
    if window:
        b, x = b[4:20], x[4:20]
    ref = getattr(np, name)(x.astype(np.float64), axis=ax, ddof=ddof,
                            keepdims=keepdims)
    start = engine.counters()["one_pass_moment_launches"]
    got = getattr(b, name)(axis=ax, ddof=ddof, keepdims=keepdims).toarray()
    assert engine.counters()["one_pass_moment_launches"] == start + 1
    assert got.dtype == F32
    two = getattr(jnp, name)(jnp.asarray(x), axis=ax, ddof=ddof,
                             keepdims=keepdims)
    err, err_two = _err(got, ref), _err(two, ref)
    tol = min(max(4 * err_two, FLOOR), BOUND)
    assert err <= tol, (err, err_two)
    if kind in OFFSET:
        # the tolerance means something: the unshifted one-pass form
        # fails it at its widest
        bad = _err(_finish(name, _unshifted(jnp.asarray(x), ax, ddof,
                                            keepdims)), ref)
        assert bad > BOUND >= tol, bad


@pytest.mark.parametrize("name", ["var", "std"])
@pytest.mark.parametrize("axes", sorted(AXES))
@pytest.mark.parametrize("value", [0.1, -3.0, 1e6, 0.0])
def test_a_constant_array_has_no_spread(mesh, value, axes, name):
    x = np.full(SHAPE, value, F32)
    got = np.asarray(getattr(bolt.array(x, mesh), name)(
        axis=AXES[axes]).toarray())
    # a power-of-two pilot of one value IS the value: every deviation 0
    assert np.all(got == 0), got.max()


@pytest.mark.parametrize("name", ["var", "std"])
@pytest.mark.parametrize("where", ["in_the_pilot", "past_the_pilot"])
@pytest.mark.parametrize("bad", [np.nan, np.inf, -np.inf])
def test_nan_and_inf_propagate_as_numpy_s(mesh, bad, where, name):
    x = np.random.default_rng(3).standard_normal((16, 40)).astype(F32)
    col = 0 if where == "in_the_pilot" else 39
    x[3, col] = bad
    x[9, col] = bad
    with np.errstate(all="ignore"):
        ref = getattr(np, name)(x.astype(np.float64), axis=1)
    got = np.asarray(getattr(bolt.array(x, mesh), name)(axis=(1,)).toarray())
    assert np.isnan(ref[3]) and np.isnan(ref[9])
    np.testing.assert_array_equal(np.isnan(got), np.isnan(ref))
    ok = ~np.isnan(ref)
    np.testing.assert_allclose(got[ok], ref[ok], rtol=1e-5)


@pytest.mark.parametrize("name", ["var", "std"])
@pytest.mark.parametrize("axes", ["all", "values"])
@pytest.mark.parametrize("dtype", ["int8", "uint8", "int32", "int64", "bool",
                                   "complex64", "complex128"])
def test_other_dtypes_keep_jnp_s_form_bit_for_bit(mesh, dtype, axes, name):
    rng = np.random.default_rng(5)
    if dtype == "bool":
        x = rng.integers(0, 2, SHAPE).astype(bool)
    elif dtype.startswith("complex"):
        x = (rng.standard_normal(SHAPE)
             + 1j * rng.standard_normal(SHAPE)).astype(dtype)
    else:
        x = rng.integers(0, 100, SHAPE).astype(dtype)
    ax = AXES[axes]
    start = engine.counters()["one_pass_moment_launches"]
    got = np.asarray(getattr(bolt.array(x, mesh), name)(axis=ax).toarray())
    assert engine.counters()["one_pass_moment_launches"] == start
    # jnp's own program over the same sharded buffer: the same bits
    want = np.asarray(jax.jit(lambda d: getattr(jnp, name)(d, axis=ax))(
        bolt.array(x, mesh).tojax()))
    assert got.dtype == want.dtype and np.array_equal(got, want)
    direct = getattr(moments, name)(jnp.asarray(x), axis=ax)
    assert np.array_equal(np.asarray(direct), np.asarray(
        getattr(jnp, name)(jnp.asarray(x), axis=ax)))


@pytest.mark.parametrize("name", ["var", "std"])
@pytest.mark.parametrize("mode", ["bf16", "f32"])
def test_accumulate_modes_within_their_limits(mesh, mode, name):
    x = (3.0 + np.random.default_rng(7).standard_normal(SHAPE)).astype(F32)
    ref = getattr(np, name)(x.astype(np.float64), axis=0)
    m = bolt.array(x, mesh)
    got, _ = bolt.compute(getattr(m, name)(), m.sum(), accumulate=mode)
    got = np.asarray(got.toarray())
    assert got.dtype == F32
    if mode == "f32":
        # for a float32 pipeline "f32" IS the default arithmetic
        m2 = bolt.array(x, mesh)
        exact, _ = bolt.compute(getattr(m2, name)(), m2.sum())
        assert np.array_equal(got, np.asarray(exact.toarray()))
        np.testing.assert_allclose(got, ref, rtol=1e-5)
    else:
        # bf16 VALUES (8 bits), float32 sums: the documented ~1e-2
        np.testing.assert_allclose(got, ref, rtol=3e-2)


@pytest.mark.parametrize("name", ["var", "std"])
@pytest.mark.parametrize("dtype", ["float16", "bfloat16", "float32",
                                   "float64"])
def test_every_real_float_width_and_jnp_s_result_dtype(dtype, name):
    x = jnp.asarray(2.0 + np.random.default_rng(9).standard_normal(
        (64, 48)), dtype)
    got = getattr(moments, name)(x, axis=(0,), ddof=1)
    want = getattr(jnp, name)(x, axis=(0,), ddof=1)
    assert got.dtype == want.dtype == x.dtype
    # 16-bit values are summed in float32 (as jnp's), and only the
    # finish is rounded to 16 bits
    tol = {"float16": 2e-3, "bfloat16": 2e-2, "float32": 1e-5,
           "float64": 1e-12}[dtype]
    np.testing.assert_allclose(np.asarray(got, np.float64),
                               np.asarray(want, np.float64), rtol=tol)


@pytest.mark.parametrize("axis,want", [
    (None, (0, 1, 2)), (1, (1,)), (-1, (2,)), ((0, -1), (0, 2)), ((), ()),
    (np.int64(1), (1,)), ([0, 2], (0, 2))])
def test_axis_spellings(axis, want):
    x = jnp.asarray(np.random.default_rng(11).standard_normal((6, 5, 4)), F32)
    np.testing.assert_allclose(
        np.asarray(moments.var(x, axis=axis)),
        np.var(np.asarray(x, np.float64), axis=want), rtol=1e-5, atol=1e-12)
    np.testing.assert_allclose(
        np.asarray(moments.std(x, axis=axis, keepdims=True)),
        np.std(np.asarray(x, np.float64), axis=want, keepdims=True),
        rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("shape,axes", [
    ((16, 200, 64, 64), (0, 1, 2, 3)), ((3200, 200, 64, 64), (0, 1, 2, 3)),
    ((3200, 200, 64, 64), (1, 2, 3)), ((3200, 200, 64, 64), (0,)),
    ((1, 7), (0, 1)), ((5,), (0,)), ((2, 3), (1,)), ((1000003,), (0,))])
def test_the_pilot_is_a_corner_of_at_least_sqrt_n(shape, axes):
    x = jax.ShapeDtypeStruct(shape, F32)
    c = jax.eval_shape(lambda v: moments._pilot(v, axes), x)
    kept = tuple(1 if a in axes else s for a, s in enumerate(shape))
    assert c.shape == kept
    jaxpr = jax.make_jaxpr(lambda v: moments._pilot(v, axes))(x)
    corner = [e for e in jaxpr.eqns if e.primitive.name == "slice"]
    block = corner[0].outvars[0].aval.shape if corner else shape
    n = int(np.prod([shape[a] for a in axes]))
    m = int(np.prod([block[a] for a in axes]))
    assert m * m >= n and m & (m - 1) == 0
    # a corner along EVERY reduced axis: under twice the root of each
    assert all(block[a] ** 2 < 4 * shape[a] for a in axes), block
    assert all(block[a] == shape[a] for a in range(len(shape))
               if a not in axes)


def test_a_zero_size_and_a_one_element_reduction_read_as_jnp_s():
    empty = jnp.zeros((0, 3), F32)
    assert np.all(np.isnan(np.asarray(moments.var(empty, axis=(0,)))))
    one = jnp.ones((1, 3), F32)
    assert np.all(np.asarray(moments.var(one, axis=(0,))) == 0)
    assert np.all(np.isnan(np.asarray(moments.var(one, axis=(0,), ddof=1))))


@pytest.mark.parametrize("name", ["var", "std"])
def test_np_functions_and_chunked_views_take_the_one_pass(mesh, name):
    x = _data("mean1e4")
    ref = getattr(np, name)(x.astype(np.float64), axis=0)
    start = engine.counters()["one_pass_moment_launches"]
    got = np.asarray(getattr(np, name)(bolt.array(x, mesh), axis=0))
    cv = bolt.array(x, mesh).chunk(size=(3,), axis=(0,))
    chunked = np.asarray(getattr(cv, name)().toarray())
    assert engine.counters()["one_pass_moment_launches"] == start + 2
    np.testing.assert_allclose(got, ref, rtol=1e-4)
    np.testing.assert_allclose(chunked, ref, rtol=1e-4)


def test_a_fused_group_counts_each_of_its_moment_members(mesh):
    x = _data("lattice12")
    m = bolt.array(x, mesh).map(lambda v: v * 0.5)
    start = engine.counters()
    var, std, total = bolt.compute(m.var(), m.std(), m.sum())
    now = engine.counters()
    assert now["dispatches"] - start["dispatches"] == 1
    assert (now["one_pass_moment_launches"]
            - start["one_pass_moment_launches"]) == 2
    np.testing.assert_allclose(np.asarray(std.toarray()),
                               np.std(x.astype(np.float64) * 0.5, axis=0),
                               rtol=1e-5)


def test_the_square_of_a_sum_does_not_overflow():
    # deviations whose squares fit float32 (1e32) and whose SUM's square
    # does not (1.6e39): s1 * s1 / n read inf here and the variance 0
    x = np.zeros(4096, F32)
    x[64:] = 1e16
    got = float(moments.var(jnp.asarray(x)))
    ref = float(np.var(x.astype(np.float64)))
    assert np.isfinite(got) and abs(got - ref) <= 1e-3 * ref, (got, ref)


@pytest.mark.parametrize("axes,whole,corner", [
    ((0, 1, 2, 3), (0,), (3200, 16, 8, 8)),     # sharded keys, cornered values
    ((0,), (0,), (3200, 200, 64, 64)),          # keys alone: the mean itself
    ((0, 1, 2, 3), (), (64, 16, 8, 8)),
    ((1, 2, 3), (0,), (3200, 16, 8, 8))])       # a kept axis named: no matter
def test_the_pilot_takes_named_axes_whole(axes, whole, corner):
    # what a caller that knows the mesh asks for a SHARDED reduced axis
    # (multistat._stat_expr): no static window of it, so nothing for GSPMD
    # to re-shard
    shape = (3200, 200, 64, 64)
    jaxpr = jax.make_jaxpr(lambda v: moments._pilot(v, axes, whole))(
        jax.ShapeDtypeStruct(shape, F32))
    cut = [e for e in jaxpr.eqns if e.primitive.name == "slice"]
    assert (cut[0].outvars[0].aval.shape if cut else shape) == corner


@pytest.mark.parametrize("name", ["var", "std"])
def test_a_whole_axis_changes_the_pilot_and_not_the_answer(name):
    x = _data("mean1e4")
    ref = getattr(np, name)(x.astype(np.float64))
    for whole in ((), (0,), (0, 1, 2, 3)):
        got = getattr(moments, name)(jnp.asarray(x), axis=EVERY, whole=whole)
        assert _err(got, ref) <= BOUND
