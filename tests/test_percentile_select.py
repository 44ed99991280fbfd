"""The percentile of a record by selection (``bolt_tpu/ops/select.py``:
two exact order statistics found by bisection over the bits of the values,
and ``jnp.quantile``'s own interpolation) against ``jnp.percentile``'s sort
TO THE BIT and against ``np.percentile`` in float64 (PR 37).

The benchmark's cell cannot hold this arithmetic: coherence and phase do
not see a series' baseline (PERF.md, section 6, PR 36, item 7).  These
tests do.  The reference is ``jnp.percentile`` as ``ops.normalize`` used
to call it: inside a jitted program with the percentile a Python float, so
that XLA folds its weights from constants (called eagerly, or with an
integer percentile, XLA computes ``q / 100`` at run time as a product with
a rounded reciprocal and the weights differ in their last bit)."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bolt_tpu as bolt
from bolt_tpu import ops
from bolt_tpu.ops import select

EDGE = select._SELECT_FROM              # the shortest selected record
WHOLE = 100.0 * 3 / 8                   # q * (n - 1) whole at n = 1 + 8 j
PERCS = [0.0, 0.01, 20.0, 37.3, 50.0, 99.9, 100.0, WHOLE]
LENGTHS = [2, 7, EDGE - 1, EDGE, 1000, 10240, 1025]
KINDS = ["normal", "ties14", "constant", "posinf", "neginf", "nan",
         "zeros", "denormal", "f32max"]


def rows(kind, n, seed=0, count=3):
    """``count`` seeded float32 rows of ``n`` values of one kind."""
    rng = np.random.default_rng([seed, n, KINDS.index(kind)])
    x = rng.standard_normal((count, n)).astype(np.float32)
    some = rng.random((count, n)) < max(0.1, 1.5 / n)
    if kind == "ties14":                # the cell's kind: 14-bit counts
        x = rng.integers(4000, 4000 + max(2, n // 4),
                         (count, n)).astype(np.float32)
    elif kind == "constant":
        x[:] = 3.0
    elif kind == "posinf":
        x[some] = np.inf
    elif kind == "neginf":
        x[some] = -np.inf
    elif kind == "nan":                 # the FIRST row alone holds one
        x[0, n // 2] = np.nan
    elif kind == "zeros":
        x = np.where(some, np.float32(-0.0), x)
        x = np.where(rng.random((count, n)) < 0.4, np.float32(0.0), x)
    elif kind == "denormal":
        x = (rng.integers(-40, 40, (count, n)) * 1.4e-45).astype(np.float32)
    elif kind == "f32max":
        big = np.finfo(np.float32).max
        x[some] = np.where(rng.random(some.sum()) < 0.5, big, -big)
    return x


@functools.lru_cache(maxsize=None)
def routes(perc, axis, keepdims=False):
    """``(reference, by the rule, by selection whatever the length)``,
    each one jitted function of the array."""
    return (jax.jit(lambda v: jnp.percentile(v, perc, axis=axis,
                                             keepdims=keepdims)),
            jax.jit(lambda v: select.percentile(v, perc, axis, keepdims)),
            jax.jit(lambda v: select._select(
                jnp.asarray(v), perc, axis % np.ndim(v), keepdims)))


def same_bits(got, want):
    """Equal to the bit, a zero's sign apart (a record that holds both
    zeros counts them as one value, as the sort's comparator does)."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape
    view = {2: np.int16, 4: np.int32, 8: np.int64}[got.dtype.itemsize]
    got = np.where(got == 0, 0, got).astype(got.dtype)
    want = np.where(want == 0, 0, want).astype(want.dtype)
    return np.array_equal(got.view(view), want.view(view))


def near_numpy(got, x, perc, axis, x64):
    """Within one float32 ulp of ``np.percentile`` in float64 wherever
    that is finite (the ulp that of the larger of the two order
    statistics, which is what the interpolation rounds at; and the
    smallest normal float32, because XLA's CPU backend flushes a denormal
    RESULT to zero on both routes).  With x64 off ``jnp.quantile`` takes
    ``q * (n - 1)`` in float32, so the interpolation WEIGHT is off by up
    to an ulp of the index (1e-3 at 10,240 points) and the answer by that
    share of the gap between the two order statistics, and its two
    products round on their own: the parent's arithmetic, held to the bit
    by the other assertion and to this much here."""
    with np.errstate(invalid="ignore", over="ignore"):
        wide = np.asarray(x).astype(np.float64)
        want = np.percentile(wide, perc, axis=axis)
        srt = np.sort(wide, axis=axis)
        n = wide.shape[axis]
        at = perc / 100 * (n - 1)
        pair = np.take(srt, [int(np.floor(at)), int(np.ceil(at))], axis=axis)
        scale = np.maximum(np.abs(pair).max(axis=axis), np.abs(want))
        ulp = np.spacing(scale.astype(np.float32)).astype(np.float64)
        ok = np.isfinite(want) & np.isfinite(scale)
        room = ulp + np.finfo(np.float32).tiny
        if not x64:
            gap = np.abs(np.diff(pair, axis=axis)).squeeze(axis)
            room = 2 * room + n * 2.0 ** -23 * np.where(ok, gap, 0.0)
        return np.all(np.abs(np.asarray(got, np.float64) - want)[ok]
                      <= room[ok])


# ---------------------------------------------------------------------
# (a) the helper: every percentile x length x kind of row, by the rule and
# by selection at any length, x64 on (the tests' mode: the interpolation
# in float64) and off (the chip's: in float32)
# ---------------------------------------------------------------------

def _cases():
    out = []
    for perc in PERCS:
        for n in LENGTHS:
            if (perc == WHOLE) != (n == 1025):
                continue                # the whole index has its own length
            for kind in KINDS:
                out.append(pytest.param(
                    perc, n, kind, id="p%g-n%d-%s" % (perc, n, kind)))
    return out


@pytest.mark.parametrize("x64", [True, False], ids=["x64", "x32"])
@pytest.mark.parametrize("perc,n,kind", _cases())
def test_selection_is_the_sorts_percentile_to_the_bit(perc, n, kind, x64):
    x = rows(kind, n)
    with jax.enable_x64(x64):
        by_sort, by_rule, by_select = routes(perc, 1)
        want = np.asarray(by_sort(x))
        for got in (by_rule(x), by_select(x)):
            got = np.asarray(got)
            assert same_bits(got, want), (got, want)
            assert near_numpy(got, x, perc, 1, x64)
        if kind == "nan":
            assert np.isnan(got[0]) and not np.isnan(got[1:]).any()
        else:
            assert not np.isnan(want).any() or kind in ("posinf", "neginf")


def test_the_rule_is_a_length_and_the_keys_width_alone():
    assert select.regime(EDGE, np.float32) == "select"
    assert select.regime(EDGE - 1, np.float32) == "sort"
    # "kernel" is a selection too: longer records of whole groups of 128
    # lanes of float32, which a program for one TPU device runs on a tile
    # in VMEM (PR 40; tests/test_percentile_kernel.py)
    assert select.regime(10240, np.float32) == "kernel"
    assert select.regime(10240 + 1, np.float32) == "select"
    assert select.regime(64, np.float32) == "sort"
    with pytest.raises(TypeError, match="bits of floats"):
        select.percentile(np.arange(4), 20.0, 0)
    # a key of another width has that many passes: the length moves with it
    assert select.regime(2 * EDGE - 1, np.float64) == "sort"
    assert select.regime(2 * EDGE, np.float64) == "select"
    assert select.regime(EDGE // 2, jnp.bfloat16) == "select"


# ---------------------------------------------------------------------
# (b) other dtypes: keys of 16 and of 64 bits, and what normalize promotes
# to float32 first (bfloat16, float16, integers)
# ---------------------------------------------------------------------

@pytest.mark.parametrize("x64", [True, False], ids=["x64", "x32"])
@pytest.mark.parametrize("n", [7, EDGE, 10240])
@pytest.mark.parametrize("perc", [20.0, 99.9])
@pytest.mark.parametrize("dtype", ["bfloat16", "float16", "int16", "int32",
                                   "float32", "float64"])
def test_every_input_dtype_gives_the_sorts_answer(dtype, perc, n, x64):
    if dtype == "float64" and not x64:
        pytest.skip("no float64 with x64 off")
    kind = "ties14" if dtype.startswith("int") else "normal"
    with jax.enable_x64(x64):
        x = jnp.asarray(rows(kind, n)).astype(dtype)
        by_sort, by_rule, by_select = routes(perc, 1)
        # as normalize hands it over: promoted to float32 first
        f32 = x.astype(jnp.promote_types(x.dtype, jnp.float32))
        want = by_sort(f32)
        assert same_bits(by_rule(f32), want)
        assert same_bits(by_select(f32), want)
        # and a float of any width over keys of that width
        if not dtype.startswith("int"):
            want = by_sort(x)
            assert want.dtype == x.dtype
            assert same_bits(by_rule(x), want)
            assert same_bits(by_select(x), want)


@pytest.mark.parametrize("dtype", ["bfloat16", "int16", "float32"])
@pytest.mark.parametrize("t", [EDGE - 1, EDGE], ids=lambda t: "T%d" % t)
def test_normalize_promotes_and_takes_the_same_baseline(mesh, dtype, t):
    rng = np.random.default_rng(t)
    x = rng.integers(40, 100, (8, 4, t)).astype(dtype)
    dff = ops.normalize(bolt.array(x, mesh, axis=(0, 1)), "percentile",
                        20.0, axis=0).toarray()
    assert dff.dtype == np.float32
    f32 = np.asarray(x, np.float32)

    def parent(v):
        base = jnp.percentile(v, 20.0, axis=0, keepdims=True)
        return (v - base) / jnp.where(base >= 0, base + 0.0, base - 0.0)
    assert np.array_equal(dff, np.asarray(jax.jit(jax.vmap(jax.vmap(
        parent)))(f32)))


# ---------------------------------------------------------------------
# (c) the value axis first, last and in the middle of a 3-d record
# ---------------------------------------------------------------------

@pytest.mark.parametrize("keepdims", [False, True], ids=["drop", "keep"])
@pytest.mark.parametrize("axis", [0, 1, 2, -1, -3])
@pytest.mark.parametrize("n", [7, EDGE])
def test_any_axis_of_a_record(n, axis, keepdims):
    shape = [2, 3, 4]
    shape[axis] = n
    rng = np.random.default_rng(n + axis)
    x = rng.integers(-9, 9, shape).astype(np.float32) / 4
    by_sort, by_rule, by_select = routes(37.3, axis, keepdims)
    want = by_sort(x)
    assert same_bits(by_rule(x), want)
    assert same_bits(by_select(x), want)
    # under vmap, as a record function of a map runs
    stack = np.stack([x, -x, x + 1])
    got = jax.jit(jax.vmap(
        lambda v: select.percentile(v, 37.3, axis, keepdims)))(stack)
    assert same_bits(got, jax.jit(jax.vmap(lambda v: jnp.percentile(
        v, 37.3, axis=axis, keepdims=keepdims)))(stack))


# ---------------------------------------------------------------------
# (d) ops.normalize itself: dF/F against NumPy's percentile on each side
# of the crossover, and against the program the parent lowered
# ---------------------------------------------------------------------

@pytest.mark.parametrize("t", [EDGE // 2, EDGE - 1, EDGE, 2 * EDGE],
                         ids=lambda t: "T%d" % t)
@pytest.mark.parametrize("perc", [20.0, 50.0])
def test_dff_holds_numpys_percentile_either_side_of_the_crossover(
        mesh, t, perc):
    rng = np.random.default_rng(t)
    x = (rng.integers(4000, 10000, (16, 4, t))
         * (1 + 0.001 * rng.standard_normal((16, 4, 1)))).astype(np.float32)
    before = bolt.engine.counters()
    dff = ops.normalize(bolt.array(x, mesh, axis=(0, 1)), "percentile",
                        perc, axis=0).toarray()
    after = bolt.engine.counters()
    took = "select" if t >= EDGE else "sort"
    other = "sort" if t >= EDGE else "select"
    assert after["percentile_%s_lowerings" % took] \
        > before["percentile_%s_lowerings" % took]
    assert after["percentile_%s_lowerings" % other] \
        == before["percentile_%s_lowerings" % other]
    x64 = x.astype(np.float64)
    base = np.percentile(x64, perc, axis=-1, keepdims=True)
    # dF/F is O(0.1) in float32: a few ulp of 1.0 after the division
    assert np.max(np.abs(dff - (x64 - base) / base)) < 1e-6
    wrong = np.percentile(x64, perc + 5, axis=-1, keepdims=True)
    assert np.max(np.abs(dff - (x64 - wrong) / wrong)) > 1e-3
    # the parent's program: jnp.percentile in the same expression
    def parent(v):
        base = jnp.percentile(v, perc, axis=0, keepdims=True)
        return (v - base) / jnp.where(base >= 0, base + 0.0, base - 0.0)
    assert np.array_equal(dff, np.asarray(jax.jit(jax.vmap(jax.vmap(
        parent)))(x)))
    # and the oracle (mode='local') is NumPy's own percentile, untouched
    local = ops.normalize(bolt.array(x), "percentile", perc,
                          axis=1).toarray()
    base32 = np.percentile(x, perc, axis=-1, keepdims=True)
    assert np.array_equal(local, (x - base32) / base32)


# ---------------------------------------------------------------------
# (e) a block of records selected where the array lies (PR 47): inside
# ``select.block_of`` a selection whose operand IS the block's rows is
# bound with the base and the offset beside the rows, for the kernel to
# read in place.  Off a one-device TPU program it lowers to the passes
# over the rows, so here every case is the fallback's arm
# (``tests/test_percentile_kernel.py`` interprets the kernel's)
# ---------------------------------------------------------------------

LONG = select._KERNEL_FROM              # the primitive's shortest record
BASE = 200                              # records of the base: 8 divides it


def _binds(jaxpr):
    """Every ``percentile_select`` equation of ``jaxpr``, nested ones
    among them."""
    from bolt_tpu.tpu import blocks
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "percentile_select":
            out.append(eqn)
            continue
        for inner in blocks._sub_jaxprs(eqn):
            out += _binds(inner)
    return out


def _base(kind, start, block):
    """``BASE`` rows of ``LONG`` values of ``kind``; the last two rows of
    the block at ``start`` hold a NaN and both zeros."""
    x = rows(kind, LONG, seed=start + block, count=BASE)
    x[start + block - 1, 5] = np.nan
    x[start + block - 2, :4] = [0.0, -0.0, -0.0, 0.0]
    return x


def _of_a_block(perc, block, step):
    """The percentile of every record of the ``block`` rows of a base
    from ``start``, traced as ``tpu/array.py :: _blocked_run`` traces a
    block's maps (``step`` None: with nobody saying where the rows
    lie)."""
    def fn(base, start):
        part = jax.lax.dynamic_slice_in_dim(base, start, block)
        one = jax.vmap(lambda v: select.percentile(v, perc, 0, True))
        if step is None:
            return one(part)
        with select.block_of(part, base, start, step):
            return one(part)
    return fn


@pytest.mark.parametrize("x64", [True, False], ids=["x64", "x32"])
@pytest.mark.parametrize("kind", ["ties14", "zeros", "normal"])
@pytest.mark.parametrize("block,start", [
    (72, 72), (72, BASE - 72), (40, 80), (8, 192), (BASE, 0)],
    ids=["a-start-between-tiles", "the-last-block-starts-early",
         "a-block-under-one-tile", "one-vreg-of-records", "the-whole-base"])
def test_a_block_in_the_base_is_the_slices_percentile_to_the_bit(
        block, start, kind, x64):
    assert BASE % 72 and select._tile(72, LONG)[0] == 64    # what the ids say
    x = _base(kind, start, block)
    perc = 20.0
    with jax.enable_x64(x64):
        based = _of_a_block(perc, block, 8)
        sliced = _of_a_block(perc, block, None)
        by_sort = lambda base, at: jnp.percentile(
            jax.lax.dynamic_slice_in_dim(base, at, block), perc, axis=1,
            keepdims=True)
        at = jnp.int32(start)
        (bind,) = _binds(jax.make_jaxpr(based)(x, at).jaxpr)
        rows_, base, offset = bind.invars
        assert rows_.aval.shape == (block, LONG) and bind.params["lead"] == 1
        assert base.aval.shape == x.shape and offset.aval.shape == ()
        (bind,) = _binds(jax.make_jaxpr(sliced)(x, at).jaxpr)
        assert len(bind.invars) == 1
        got = np.asarray(jax.jit(based)(x, at))
        assert same_bits(got, jax.jit(sliced)(x, at))
        assert same_bits(got, jax.jit(by_sort)(x, at))
        assert np.isnan(got[-1, 0]) and not np.isnan(got[:-1]).any()
        # and the program is the slice's own: the base and the offset
        # are operands the passes do not read
        assert jax.jit(based).lower(x, at).as_text() \
            == jax.jit(sliced).lower(x, at).as_text()


@pytest.mark.parametrize("why,block,step,lead", [
    ("blocks-off-the-sublanes", 72, 4, 0),
    ("a-record-that-is-no-series", 72, 8, 1)])
def test_a_block_the_kernel_could_not_read_in_place_stays_a_slice(
        why, block, step, lead):
    shape = (BASE,) + (3,) * lead + (LONG,)
    x = np.zeros(shape, np.float32)

    def fn(base, start):
        part = jax.lax.dynamic_slice_in_dim(base, start, block)
        with select.block_of(part, base, start, step):
            return jax.vmap(lambda v: select.percentile(
                v, 20.0, lead, True))(part)
    (bind,) = _binds(jax.make_jaxpr(fn)(x, jnp.int32(0)).jaxpr)
    assert len(bind.invars) == 1, why


def _double(v):
    return v * 2


# what ``jax.jit(_blocked_run((_double, normalize), 2, ., 24)).lower(
# f32[8, 8, LONG])`` read as at the parent commit (0e07acc, jax 0.9.0,
# under tests/conftest.py)
PARENT_BLOCKED_AFTER_A_MAP = \
    "7c0a5afa6906f65aa3b55940c34008f19414d0af8b30e27a7a5d46431a5a2d0b"


def test_a_selection_of_something_computed_first_lowers_as_it_did():
    import hashlib
    from bolt_tpu.tpu import array as tpu_array
    normalize = ops.series._normalize_fn("percentile", 20.0, 0, 0.0)
    x = jax.ShapeDtypeStruct((8, 8, LONG), np.float32)
    after = lambda d: tpu_array._blocked_run((_double, normalize), 2, d, 24)
    first = lambda d: tpu_array._blocked_run((normalize, _double), 2, d, 24)
    # the operand is the map's result, which XLA writes anyway: one
    # operand, as ever; straight on the block's rows it is three
    (bind,) = _binds(jax.make_jaxpr(after)(x).jaxpr)
    assert len(bind.invars) == 1
    (bind,) = _binds(jax.make_jaxpr(first)(x).jaxpr)
    assert len(bind.invars) == 3
    found = []
    jax.eval_shape(lambda d: tpu_array._blocked_run(
        (_double, normalize), 2, d, 24, found=found), x)
    assert not found
    jax.eval_shape(lambda d: tpu_array._blocked_run(
        (normalize, _double), 2, d, 24, found=found), x)
    assert found
    if jax.__version__ == "0.9.0":
        text = jax.jit(after).lower(x).as_text()
        assert hashlib.sha256(text.encode()).hexdigest() \
            == PARENT_BLOCKED_AFTER_A_MAP


@pytest.fixture(scope="module")
def four_devices():
    return jax.sharding.Mesh(np.asarray(jax.devices()[:4]), ("k",))


@pytest.mark.parametrize("x64", [True, False], ids=["x64", "x32"])
@pytest.mark.parametrize("sharded", [False, True],
                         ids=["one-device", "four-devices"])
def test_through_the_blocked_run_one_device_and_four(four_devices, sharded,
                                                     x64):
    from bolt_tpu.tpu import array as tpu_array
    rng = np.random.default_rng(47)
    x = rng.integers(4000, 4064, (32, 8, LONG)).astype(np.float32)
    x[31, 7, 9] = np.nan                    # in the last block's last tile
    x[31, 6, :4] = [0.0, -0.0, -0.0, 0.0]
    run = (ops.series._normalize_fn("percentile", 20.0, 0, 0.0),)
    # 64 records a shard (256 on one device) in blocks of 24: the last
    # block of each starts early, at 40 (232)
    if sharded:
        blocked = lambda d: tpu_array._sharded_blocked_run(
            run, 2, d, 24, four_devices)
    else:
        blocked = lambda d: tpu_array._blocked_run(run, 2, d, 24)
    with jax.enable_x64(x64):
        (bind,) = _binds(jax.make_jaxpr(blocked)(x).jaxpr)
        rows_, base, _ = bind.invars
        assert rows_.aval.shape == (24, LONG)
        assert base.aval.shape == ((64 if sharded else 256), LONG)
        got = np.asarray(jax.jit(blocked)(x))
        whole = np.asarray(jax.jit(
            lambda d: tpu_array._chain_apply(run, 2, d))(x))

        def parent(v):
            base = jnp.percentile(v, 20.0, axis=0, keepdims=True)
            return (v - base) / jnp.where(base >= 0, base + 0.0, base - 0.0)
        by_sort = np.asarray(jax.jit(jax.vmap(jax.vmap(parent)))(x))
    assert np.array_equal(got, whole, equal_nan=True)
    assert np.array_equal(got, by_sort, equal_nan=True)
    assert np.isnan(got[31, 7]).all() and not np.isnan(got[:31]).any()
