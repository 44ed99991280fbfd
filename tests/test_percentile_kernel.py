"""The selection's second executor (``bolt_tpu/ops/select.py``, PR 40): the
Mosaic kernel that brings a tile of whole records into VMEM once and runs
the image of keys, every counting pass, the neighbour above and the NaN
verdict on the tile, held to ``jnp.percentile`` TO THE BIT in interpret
mode, and the ``percentile_select`` primitive that chooses between it and
the ``jax.numpy`` passes when a program is lowered.

What a chip alone can show (that Mosaic compiles the kernel at the cell's
size, that the program for one v5e device holds the custom call and no
``while`` of passes, that float64 and a record too long for VMEM keep the
passes there) is in ``tests/test_ops_kernels.py``, the one file that loads
the TPU's compiler.  Off the TPU the primitive lowers to the passes; these
tests reach the kernel through it by answering the lowering's question
themselves (``select._takes_kernel``), with Pallas interpreting."""

import functools
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

import bolt_tpu as bolt
from bolt_tpu import analysis, engine, ops
from bolt_tpu.ops import select
from bolt_tpu.tpu import array as tpu_array

from test_percentile_select import (BASE, KINDS, LONG, _base, _binds,
                                    _of_a_block, rows, same_bits)
from test_series_tuning import sessions, tuning

PERCS = [0.0, 20.0, 50.0, 99.9, 100.0]  # 0, 100 and 50 at an odd length:
                                        # low == high, no neighbour
COUNTS = [8, 13, 24, 8 * 3 + 5]         # whole tiles, and tiles with an edge
N = select._KERNEL_FROM                 # the shortest record the rule gives
                                        # the kernel (the kernel itself
                                        # takes any whole lane-groups)


@pytest.fixture
def kernel_everywhere(monkeypatch):
    """Every ``"kernel"`` selection lowered inside the test takes the
    kernel, interpreted: the lowering's question answered as a program
    for one TPU device answers it."""
    from jax.experimental import pallas as pl
    monkeypatch.setattr(select, "_takes_kernel", lambda ctx, tpu: True)
    # Pallas' plain interpreter: the TPU one speaks through ordered
    # callbacks, which a primitive's lowering has no tokens for
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    engine.clear()
    yield
    engine.clear()


@functools.lru_cache(maxsize=None)
def flat(perc, bits=select._KERNEL_BITS):
    """``(reference, kernel)``: each one jitted function of a flat
    ``(records, length)`` batch, answer ``(records, 1)``."""
    return (jax.jit(lambda v: jnp.percentile(v, perc, axis=1, keepdims=True)),
            jax.jit(lambda v: select._select_flat(v, perc, bits)))


def run_flat(x, perc, bits=select._KERNEL_BITS):
    by_sort, by_kernel = flat(perc, bits)
    with pltpu.force_tpu_interpret_mode():
        got = np.asarray(by_kernel(x))
    return got, np.asarray(by_sort(x))


# ---------------------------------------------------------------------
# (a) the kernel against the sort: every family of values, lengths of 2,
# 10 and 80 lane-groups, batches that fill their tiles and that do not,
# percentiles with and without a neighbour above
# ---------------------------------------------------------------------

def _cases():
    out = []
    for kind in KINDS:
        for n, counts, percs in (
                (256, COUNTS, PERCS),
                (1280, (13, 24), (20.0, 100.0)),
                (10240, (8,), (20.0, 99.9))):
            for count in counts:
                for perc in percs:
                    out.append(pytest.param(
                        kind, n, count, perc,
                        id="%s-n%d-r%d-p%g" % (kind, n, count, perc)))
    return out


@pytest.mark.parametrize("kind,n,count,perc", _cases())
def test_the_kernel_is_the_sorts_percentile_to_the_bit(kind, n, count, perc):
    x = rows(kind, n, count=count)
    got, want = run_flat(x, perc)
    assert same_bits(got, want), (got, want)
    if kind == "nan":                   # a NaN in one record, none in others
        assert np.isnan(got[0]) and not np.isnan(got[1:]).any()


@pytest.mark.parametrize("perc", [20.0, 50.0, 100.0])
@pytest.mark.parametrize("bits", [2, 4])
@pytest.mark.parametrize("kind", ["ties14", "zeros", "neginf", "nan"])
def test_more_bits_a_pass_select_the_same(kind, bits, perc):
    # what scripts/select_probe.py times beside the constant
    got, want = run_flat(rows(kind, 384, count=13), perc, bits)
    assert same_bits(got, want), (got, want)


def test_a_long_record_walks_its_lanes_in_spans():
    # more than 128 lane-groups: a loop of unrolled spans, not one unroll
    n = 128 * 130
    x = rows("ties14", n, count=9)
    got, want = run_flat(x, 20.0)
    assert same_bits(got, want)


@pytest.mark.parametrize("count", [1, 5])
def test_a_batch_under_one_vreg_of_records_is_padded(count):
    got, want = run_flat(rows("normal", 256, count=count), 37.3)
    assert got.shape == (count, 1) and same_bits(got, want)


def test_a_tile_is_whole_records_inside_its_budget():
    # the cell's block: 64 records of 40 KB a grid step, one group
    assert select._tile(5352, 10240) == (64, 64)
    # a record of which 64 do not fit keeps the largest group that does
    assert select._tile(5352, 16640) == (32, 32)
    for records in (8, 13, 31, 32, 100, 5352, 26214):
        for length in (256, 1280, 10240, 16640, 98304):
            tile, group = select._tile(records, length)
            assert group in (8, 16, 32, 64) and group <= select._GROUP
            assert tile % group == 0
            assert group <= tile <= max(records, group)
            assert tile * length * 4 <= select._TILE_BYTES


# ---------------------------------------------------------------------
# (b) the rule: a length, a key's width, and a third answer
# ---------------------------------------------------------------------

def test_the_rule_has_a_third_answer():
    f32 = np.float32
    assert select.regime(N, f32) == "kernel"
    assert select.regime(10240, f32) == "kernel"
    assert select.regime(255, f32) == "sort"
    # a record too short to hide a pass's cross-lane sums keeps the passes
    assert select.regime(N - 128, f32) == "select"
    assert select.regime(256, f32) == "select"
    # and so does a length that is not whole groups of 128 lanes
    assert select.regime(10240 + 64, f32) == "select"
    assert select.regime(N + 1000, f32) == "select"
    # so does a record of which 8 do not fit a tile's buffer
    longest = select._TILE_BYTES // 32
    assert select.regime(longest, f32) == "kernel"
    assert select.regime(longest + 128, f32) == "select"
    # and a key Mosaic has no type for, or no tile of 8 sublanes
    assert select.regime(10240, np.float64) == "select"
    assert select.regime(10240, jnp.bfloat16) == "select"
    assert select.regime(10240, np.float16) == "select"


def counters():
    c = engine.counters()
    return tuple(c["percentile_%s_lowerings" % k]
                 for k in ("kernel", "select", "sort"))


@pytest.mark.parametrize("t,dtype,said", [
    (N, "float32", "percentile by selection, one read of a block"),
    (N + 1000, "float32", "percentile by selection: two exact"),
    (256, "float32", "percentile by selection: two exact"),
    (N, "float64", "percentile by selection: two exact"),
    (255, "float32", "percentile by sort")],
    ids=["kernel", "not-lanes", "under-the-kernel's", "float64", "short"])
def test_explain_follows_the_rule(t, dtype, said):
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("k",))
    x = sessions((4, 3, t)).astype(dtype)
    arr = ops.normalize(bolt.array(x, mesh, axis=(0, 1)), "percentile", 20.0)
    text = analysis.explain(arr)
    assert said in text
    assert ("one read of a block" in text) \
        == (select.regime(t, np.dtype(dtype)) == "kernel")
    # off the TPU every selection lowers to the passes: traced as a
    # selection, never counted as a kernel
    before = counters()
    got = arr.toarray()
    after = counters()
    assert after[0] == before[0]
    assert (after[1] > before[1]) == (t >= 256)
    assert (after[2] > before[2]) == (t < 256)
    base = np.percentile(x.astype(np.float64), 20.0, axis=-1, keepdims=True)
    assert np.max(np.abs(got - (x - base) / base)) < 1e-5


# ---------------------------------------------------------------------
# (c) the primitive: any axis of a record, keepdims both ways, one bind
# over the whole batch under one and two vmaps
# ---------------------------------------------------------------------

@pytest.mark.parametrize("keepdims", [False, True], ids=["drop", "keep"])
@pytest.mark.parametrize("perc", PERCS)
@pytest.mark.parametrize("count", COUNTS)
def test_through_the_primitive_keepdims_both_ways(kernel_everywhere, count,
                                                  perc, keepdims):
    x = rows("ties14", N, seed=count, count=count)
    before = counters()
    got = jax.jit(lambda v: select.percentile(v, perc, 1, keepdims))(x)
    want = jax.jit(lambda v: jnp.percentile(v, perc, axis=1,
                                            keepdims=keepdims))(x)
    assert same_bits(got, want)
    after = counters()
    assert after[0] == before[0] + 1 and after[2] == before[2]


@pytest.mark.parametrize("keepdims", [False, True], ids=["drop", "keep"])
@pytest.mark.parametrize("axis", [0, 1, 2, -1, -3])
def test_any_axis_of_a_record_through_the_kernel(kernel_everywhere, axis,
                                                 keepdims):
    shape = [3, 2, 4]
    shape[axis] = N
    x = np.random.default_rng(axis % 3).standard_normal(shape).astype(
        np.float32)
    got = jax.jit(lambda v: select.percentile(v, 20.0, axis, keepdims))(x)
    want = jax.jit(lambda v: jnp.percentile(v, 20.0, axis=axis,
                                            keepdims=keepdims))(x)
    assert same_bits(got, want)


@pytest.mark.parametrize("keepdims", [False, True], ids=["drop", "keep"])
@pytest.mark.parametrize("depth,shape", [(0, (N,)), (1, (13, N)),
                                         (2, (5, 3, N)), (2, (4, 8, N + 256))],
                         ids=["record", "vmap", "vmap-vmap", "vmap-vmap-10"])
def test_under_vmap_one_bind_holds_the_whole_batch(kernel_everywhere, depth,
                                                   shape, keepdims):
    x = np.random.default_rng(depth).integers(
        4000, 4064, shape).astype(np.float32)
    fn = lambda v: select.percentile(v, 20.0, 0, keepdims)
    want = lambda v: jnp.percentile(v, 20.0, axis=0, keepdims=keepdims)
    for _ in range(depth):
        fn, want = jax.vmap(fn), jax.vmap(want)
    jaxpr = jax.make_jaxpr(fn)(x).jaxpr
    (bind,) = _binds(jaxpr)             # ONE, and nothing nested holds more
    assert len(jaxpr.eqns) == 1
    assert bind.invars[0].aval.shape == shape
    assert bind.params["lead"] == depth and bind.params["axis"] == 0
    before = counters()
    assert same_bits(jax.jit(fn)(x), jax.jit(want)(x))
    assert counters()[0] == before[0] + 1


def test_a_mapped_axis_that_is_not_the_first_moves_to_the_front(
        kernel_everywhere):
    x = np.random.default_rng(5).standard_normal((N, 11)).astype(np.float32)
    fn = jax.vmap(lambda v: select.percentile(v, 50.0, 0), in_axes=1)
    (bind,) = _binds(jax.make_jaxpr(fn)(x).jaxpr)
    assert bind.invars[0].aval.shape == (11, N)
    want = jax.jit(lambda v: jnp.percentile(v, 50.0, axis=0))(x)
    assert same_bits(jax.jit(fn)(x), want)


def test_the_fallback_is_select_mapped_over_the_batch():
    # off the TPU (no fixture): the passes, mapped as the nested vmap
    # mapped them, and the same jaxpr whichever way it is reached
    x = sessions((5, 3, N))
    one = lambda v: select._select(v, 20.0, 0, True)
    through = jax.jit(jax.vmap(jax.vmap(
        lambda v: select.percentile(v, 20.0, 0, True))))
    direct = jax.jit(jax.vmap(jax.vmap(one)))
    aval = jax.ShapeDtypeStruct(x.shape, x.dtype)
    assert through.lower(aval).as_text() == direct.lower(aval).as_text()
    assert same_bits(through(x), direct(x))


def test_no_gradient_is_promised_and_none_is_given_silently():
    with pytest.raises(Exception, match="percentile_select|[Dd]ifferentiation"):
        jax.grad(lambda v: select.percentile(v, 20.0, 0))(
            jnp.ones(N, jnp.float32))


# ---------------------------------------------------------------------
# (d) inside the blocked lowering: a forced small block whose count does
# not divide (the last block starts early and rewrites what it holds)
# ---------------------------------------------------------------------

def test_inside_a_blocked_run_whose_last_block_starts_early(
        kernel_everywhere):
    x = sessions((7, 5, N))             # 35 records in blocks of 8
    run = (ops.series._normalize_fn("percentile", 20.0, 0, 0.0),)
    before = counters()
    blocked = jax.jit(lambda d: tpu_array._blocked_run(run, 2, d, 8))(x)
    assert counters()[0] == before[0] + 1
    jaxpr = jax.make_jaxpr(
        lambda d: tpu_array._blocked_run(run, 2, d, 8))(x).jaxpr
    (bind,) = _binds(jaxpr)             # in the loop over blocks
    assert bind.invars[0].aval.shape == (8, N)      # a block, flat
    assert not _binds(jax.make_jaxpr(lambda d: d + 1)(x).jaxpr)
    whole = jax.jit(lambda d: tpu_array._chain_apply(run, 2, d))(x)
    assert np.array_equal(np.asarray(blocked), np.asarray(whole))
    base = np.percentile(x.astype(np.float64), 20.0, axis=-1, keepdims=True)
    assert np.max(np.abs(blocked - (x - base) / base)) < 1e-6


def test_the_tuning_chain_blocked_by_the_rule_takes_the_kernel(
        kernel_everywhere):
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("k",))
    x = sessions((7, 5, N))
    want = tuning(bolt.array(x, mesh, axis=(0, 1)))[0].toarray()
    engine.clear()
    arr = tuning(bolt.array(x, mesh, axis=(0, 1)))[0]
    base = arr._chain[0]
    tpu_array._HBM_LIMIT_OVERRIDE = int(base.nbytes + 7 * 5 * 4 + 200000)
    try:
        assert "blocks of" in analysis.explain(arr)
        before = engine.counters()
        got = arr.toarray()
        moved = engine.counters()
    finally:
        tpu_array._HBM_LIMIT_OVERRIDE = None
    assert moved["map_blocks"] - before["map_blocks"] > 1
    assert moved["percentile_kernel_lowerings"] \
        == before["percentile_kernel_lowerings"] + 1
    # the percentile is the same to the bit; the thin products and the
    # transform after it round by the block's shape on the CPU
    assert np.max(np.abs(got - want)) < 1e-5


# ---------------------------------------------------------------------
# (d') the block read where the array lies (PR 47): the kernel's second
# index map, an offset in rows scalar-prefetched into an element-indexed
# window of the BASE, against the sort and against the kernel over the
# slice; the last tile of a block that does not divide starts early
# ---------------------------------------------------------------------

def based_counters():
    c = engine.counters()
    return (c["percentile_kernel_lowerings"],
            c["percentile_based_lowerings"])


@pytest.mark.parametrize("kind", ["ties14", "zeros", "neginf"])
@pytest.mark.parametrize("perc", [20.0, 100.0])
@pytest.mark.parametrize("block,start", [
    (72, 72), (72, BASE - 72), (136, BASE - 136), (40, 80), (8, 192),
    (BASE, 0)],
    ids=["a-start-between-tiles", "the-last-block-starts-early",
         "three-tiles-the-last-early", "a-block-under-one-tile",
         "one-vreg-of-records", "the-whole-base"])
def test_the_kernel_reads_a_block_of_the_base_to_the_bit(
        kernel_everywhere, block, start, perc, kind):
    x = _base(kind, start, block)
    at = jnp.int32(start)
    before = based_counters()
    got = np.asarray(jax.jit(_of_a_block(perc, block, 8))(x, at))
    assert based_counters() == (before[0] + 1, before[1] + 1)
    # the kernel over the slice counts as a kernel and not as based
    sliced = jax.jit(_of_a_block(perc, block, None))(x, at)
    assert based_counters() == (before[0] + 2, before[1] + 1)
    want = jax.jit(lambda v: jnp.percentile(v, perc, axis=1, keepdims=True))(
        x[start:start + block])
    assert same_bits(got, sliced) and same_bits(got, want)
    assert np.isnan(got[-1, 0]) and not np.isnan(got[:-1]).any()


def test_the_based_kernel_takes_no_row_outside_its_block(kernel_everywhere):
    # rows around the block poisoned: a tile that read past the block's
    # end (or before its start) would answer NaN
    block, start = 72, 64
    x = rows("ties14", LONG, count=BASE)
    want = np.asarray(jax.jit(_of_a_block(20.0, block, None))(
        x, jnp.int32(start)))
    x[:start] = np.nan
    x[start + block:] = np.nan
    got = np.asarray(jax.jit(_of_a_block(20.0, block, 8))(
        x, jnp.int32(start)))
    assert not np.isnan(got).any() and same_bits(got, want)


@pytest.mark.parametrize("first,said", [
    (True, "one read of a block in place"), (False, "one read of a block:")],
    ids=["on-the-block's-rows", "after-a-map"])
def test_a_blocked_chain_selects_in_place_and_explain_says_so(
        kernel_everywhere, first, said):
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("k",))
    x = sessions((16, 8, N))

    def make():
        b = bolt.array(x, mesh, axis=(0, 1))
        b = b if first else b.map(lambda v: v + 0, axis=(0, 1))
        return ops.detrend(ops.normalize(b, "percentile", 20.0), order=3)
    want = make().toarray()
    engine.clear()
    arr = make()
    base = arr._chain[0]
    # room for blocks of 24 of the 128 records
    tpu_array._HBM_LIMIT_OVERRIDE = int(2 * base.nbytes + 12e5)
    try:
        marker = arr._block_plan(*arr._chain)[-1]
        assert marker.block_records % 8 == 0 and marker.blocks > 1
        text = analysis.explain(arr)
        before = based_counters()
        got = arr.toarray()
        moved = based_counters()
    finally:
        tpu_array._HBM_LIMIT_OVERRIDE = None
    assert said in text
    assert moved == (before[0] + 1, before[1] + first)
    assert np.array_equal(got, want)


# ---------------------------------------------------------------------
# (e) the fallback is the parent's program: lowered for the CPU, the
# chains nearest this code have the text they had before the primitive
# stood between ``normalize`` and its passes
# ---------------------------------------------------------------------

def _lowered(run, arg):
    """``sha256`` of the lowered text of each program ``run()`` adds to the
    engine, sorted (``tests/test_shared_parent.py :: _lowered`` holds the
    four-device ``tuning`` chain the same way)."""
    engine.clear()
    run()
    with engine._LOCK:
        entries = dict(engine._CACHE)
    return sorted(hashlib.sha256(entry.lower(arg).as_text().encode())
                  .hexdigest() for entry in entries.values())


def fallback_program(name):
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("k",))
    series = bolt.array(np.zeros((12, 8, N), np.float32), mesh,
                        axis=(0, 1))
    wide = bolt.array(np.zeros((6, 4, N + 256), np.float32), mesh, axis=(0,))
    if name == "tuning-whole":
        return _lowered(lambda: tuning(series)[0].toarray(), series._data)
    if name == "tuning-blocked":
        tpu_array._HBM_LIMIT_OVERRIDE = int(series._data.nbytes
                                            + 12 * 8 * 4 + 200000)
        try:
            return _lowered(lambda: tuning(series)[0].toarray(),
                            series._data)
        finally:
            tpu_array._HBM_LIMIT_OVERRIDE = None
    if name == "normalize-an-inner-axis":
        return _lowered(lambda: ops.normalize(
            wide, "percentile", 20.0, axis=1).sum().toarray(), wide._data)
    raise KeyError(name)


# what they read as at the parent commit (57398e9, jax 0.9.0): printed
# there by ``fallback_program`` under ``tests/conftest.py``.  The two
# ``tuning`` chains are PR 49's text: that PR took ``detrend``'s fit out
# by Horner's rule and the mean's pass out of the ``fourier`` behind it,
# the chain's last two maps (PR 44's text, with the FFT out of
# ``fourier``: d78126b447142f7d... / d3db665c551c4f7b...), and nothing
# out of the selection's passes, which the third case still holds to
# 57398e9
PARENT_PROGRAMS = {
    "tuning-whole": [
        "ebee1c6ea890d60f14dbcd311cb8f64dc891b9257a391c48ee3192081c459c7b"],
    "tuning-blocked": [
        "580333acf5336368850d9fe331be04b9c9ed4042983b0c7f71d6c20fc30901be"],
    "normalize-an-inner-axis": [
        "d29e7810ad7cceb60e30c8c72eab59bec4291ea7a868ca2ab12fa2a429e548b3"],
}


@pytest.mark.skipif(jax.__version__ != "0.9.0",
                    reason="the recorded text is jax 0.9.0's")
@pytest.mark.parametrize("name", sorted(PARENT_PROGRAMS))
def test_off_the_tpu_the_program_is_the_parents(name):
    before = counters()
    got = fallback_program(name)
    assert got and got == PARENT_PROGRAMS[name]
    assert counters()[0] == before[0]
