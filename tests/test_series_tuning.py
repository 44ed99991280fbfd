"""Thunder's per-pixel series analysis (``ops.normalize -> ops.detrend ->
ops.fourier``: dF/F by a percentile baseline, a polynomial detrend, the
coherence and phase at the stimulus bin) against a plain NumPy reference,
and the record-blocked lowering of a deferred map chain
(``bolt_tpu/tpu/blocks.py``) against the whole-array lowering, bit for
bit (PR 36).

A record function that keeps a record-sized temporary (the sort behind
``jnp.percentile``, the FFT) cannot be lowered over a resident array of
HBM size at once; the rule decides, from the function's jaxpr on one
record and what the device has left, to run it over blocks of whole
records inside the same program.  On the CPU there is no limit, so the
tests give the rule a small one (``tight``: a fixture of these tests, not
a knob of the program)."""

import operator

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bolt_tpu as bolt
from bolt_tpu import analysis, engine, ops
from bolt_tpu.parallel.sharding import key_sharding
from bolt_tpu.tpu import array as tpu_array
from bolt_tpu.tpu import blocks
from bolt_tpu.utils import prod

T, FREQ, ORDER, PERC = 256, 8, 5, 20.0


def sessions(shape, seed=36):
    """Seeded series like a two-photon pixel's: a positive resting level,
    a slow drift, a stimulus-locked sinusoid at bin ``FREQ`` on two
    thirds of the pixels, noise; float32."""
    rng = np.random.default_rng(seed)
    keys = shape[:-1]
    t = np.linspace(-1.0, 1.0, shape[-1])
    rest = rng.uniform(100.0, 400.0, keys + (1,))
    drift = sum(rng.uniform(-0.05, 0.05, keys + (1,)) * t ** k
                for k in range(1, 4))
    amp = rng.uniform(0.05, 0.3, keys + (1,)) \
        * (rng.random(keys + (1,)) < 2 / 3)
    phase = rng.uniform(-np.pi, np.pi, keys + (1,))
    wave = amp * np.cos(2 * np.pi * FREQ * np.arange(shape[-1])
                        / shape[-1] + phase)
    noise = 0.02 * rng.standard_normal(shape)
    return (rest * (1.0 + drift + wave + noise)).astype(np.float32)


def tuning_reference(x):
    """The same analysis by NumPy alone, float64: ``np.percentile``, a
    least-squares polynomial residual, ``np.fft.rfft``."""
    x = x.astype(np.float64)
    base = np.percentile(x, PERC, axis=-1, keepdims=True)
    dff = (x - base) / base
    t = np.linspace(-1.0, 1.0, x.shape[-1])
    van = np.vander(t, ORDER + 1, increasing=True)
    rows = dff.reshape(-1, x.shape[-1]).T
    coef = np.linalg.lstsq(van, rows, rcond=None)[0]
    resid = (rows - van @ coef).T.reshape(dff.shape)
    co = np.fft.rfft(resid - resid.mean(axis=-1, keepdims=True), axis=-1)
    coh = np.abs(co[..., FREQ]) / np.sqrt(
        np.sum(np.abs(co[..., 1:]) ** 2, axis=-1))
    return coh, np.angle(co[..., FREQ])


def tuning(b):
    dff = ops.detrend(ops.normalize(b, baseline="percentile", perc=PERC,
                                    axis=0), order=ORDER, axis=0)
    return ops.fourier(dff, freq=FREQ, axis=0)


# ---------------------------------------------------------------------
# (a) the system against the plain reference
# ---------------------------------------------------------------------

@pytest.mark.parametrize("shape,axis", [((16, 8, T), (0,)),
                                        ((16, 8, T), (0, 1)),
                                        ((48, T), (0,))],
                         ids=["split1-of-3d", "split2", "split1"])
def test_tuning_map_matches_the_numpy_reference(mesh, shape, axis):
    x = sessions(shape)
    want_coh, want_ph = tuning_reference(x)
    if len(axis) == 1 and len(shape) == 3:
        # keyed by the first axis alone, a record is (8, T): the series
        # axis is value axis 1
        b = bolt.array(x, mesh, axis=axis)
        dff = ops.detrend(ops.normalize(b, "percentile", PERC, axis=1),
                          order=ORDER, axis=1)
        coh, ph = ops.fourier(dff, freq=FREQ, axis=1)
    else:
        coh, ph = tuning(bolt.array(x, mesh, axis=axis))
    coh, ph = coh.toarray(), ph.toarray()
    assert coh.shape == want_coh.shape and ph.shape == want_ph.shape
    assert coh.dtype == np.float32
    # float32 data and arithmetic against float64: dF/F is O(1) with 24
    # bits, the detrend's two products are pinned to "highest", the FFT
    # sums 256 terms: errors of a few 1e-6 in a coherence in [0, 1]
    assert np.max(np.abs(coh - want_coh)) < 2e-5
    # the phase of a bin is conditioned by the bin's amplitude: compared
    # where the reference coherence says there is a signal, on the circle
    tuned = want_coh > 0.5
    assert tuned.sum() > tuned.size // 3
    turn = np.abs(np.angle(np.exp(1j * (ph - want_ph))))
    assert np.max(turn[tuned]) < 1e-4
    # the planted third without a signal sits at the noise floor
    assert np.median(want_coh[~tuned]) < 0.3


def test_dff_holds_the_percentile_and_the_fit(mesh):
    # coherence and phase do not see a series' scale or offset, so they
    # cannot tell a wrong baseline: dF/F and its detrended residual are
    # held to NumPy's percentile and least squares themselves
    x = sessions((16, 8, T))
    b = bolt.array(x, mesh, axis=(0, 1))
    dff = ops.normalize(b, baseline="percentile", perc=PERC, axis=0)
    x64 = x.astype(np.float64)
    base = np.percentile(x64, PERC, axis=-1, keepdims=True)
    want = (x64 - base) / base
    # dF/F is O(0.1) in float32: a few ulp of 1.0 after the division
    assert np.max(np.abs(dff.toarray() - want)) < 1e-6
    resid = ops.detrend(dff, order=ORDER, axis=0).toarray()
    van = np.vander(np.linspace(-1.0, 1.0, T), ORDER + 1, increasing=True)
    rows = want.reshape(-1, T).T
    fit = van @ np.linalg.lstsq(van, rows, rcond=None)[0]
    # two float32 products of 256 terms at "highest"
    assert np.max(np.abs(resid - (rows - fit).T.reshape(want.shape))) < 5e-6
    wrong = (x64 - np.median(x64, axis=-1, keepdims=True)) / base
    assert np.max(np.abs(dff.toarray() - wrong)) > 1e-2


def test_local_mode_gives_the_same_maps():
    x = sessions((30, T))
    coh, ph = tuning(bolt.array(x))
    want_coh, want_ph = tuning_reference(x)
    assert np.max(np.abs(coh.toarray() - want_coh)) < 2e-5


# ---------------------------------------------------------------------
# (b) blocked against unblocked, bit for bit
# ---------------------------------------------------------------------

def shard_bytes(mesh, shape, dtype, split):
    return prod(key_sharding(mesh, shape, split).shard_shape(tuple(shape))) \
        * np.dtype(dtype).itemsize


@pytest.fixture
def tight():
    """``tight(arr, free)``: tell the rule that the device has ``free``
    bytes left beside the base and the result of the deferred ``arr``
    (the limit that ``memory_stats()`` gives on a TPU)."""
    def squeeze(arr, free):
        base = arr._chain[0] if arr._chain is not None \
            else arr._fpending.base
        aval = arr._aval if arr._chain is not None else \
            jax.ShapeDtypeStruct((arr._fpending.n,)
                                 + tuple(arr._fpending.vshape),
                                 arr._fpending.vdtype)
        split = arr._split if arr._chain is not None \
            else arr._fpending.split
        held = prod(base.sharding.shard_shape(base.shape)) \
            * base.dtype.itemsize
        tpu_array._HBM_LIMIT_OVERRIDE = held + shard_bytes(
            arr._mesh, aval.shape, aval.dtype, split) + free
    yield squeeze
    tpu_array._HBM_LIMIT_OVERRIDE = None


def both_ways(tight, make, take, free=200000):
    """``take(make())`` lowered whole, then lowered over blocks; the
    count of blocks the second ran."""
    whole = take(make())
    arr = make()
    tight(arr, free)
    before = engine.counters()
    blocked = take(arr)
    tpu_array._HBM_LIMIT_OVERRIDE = None
    after = engine.counters()
    return whole, blocked, after["map_blocks"] - before["map_blocks"]


def sorted_rows(v):
    return jnp.sort(v, axis=-1)


def keymap(b, func, **kw):
    """``map`` over the key axes ``b`` has (``map``'s default axis is
    ``(0,)``, which re-splits)."""
    return b.map(func, axis=tuple(range(b.split)), **kw)


def srt(b):
    return keymap(b, sorted_rows)


_SIGNAL = np.cos(np.arange(T) / 7.0)
_SERIES_OPS = [
    ("normalize-percentile", lambda b: ops.normalize(b, "percentile", 20.0)),
    ("normalize-mean", lambda b: ops.normalize(srt(b), "mean")),
    ("detrend", lambda b: ops.detrend(srt(b), order=3)),
    ("zscore", lambda b: ops.zscore(srt(b))),
    ("center", lambda b: ops.center(srt(b))),
    ("crosscorr", lambda b: ops.crosscorr(srt(b), _SIGNAL, lag=2)),
    ("fourier-coherence", lambda b: ops.fourier(srt(b), freq=FREQ)[0]),
    ("fourier-phase", lambda b: ops.fourier(srt(b), freq=FREQ)[1]),
    ("tuning-coherence", lambda b: tuning(b)[0]),
    ("user-sort", srt),
    ("user-cumsum", lambda b: keymap(b, lambda v: jnp.cumsum(v) * 0.5)),
]


@pytest.fixture(scope="module")
def one_device():
    return jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("k",))


@pytest.fixture(scope="module")
def four_devices():
    return jax.sharding.Mesh(np.asarray(jax.devices()[:4]), ("k",))


@pytest.mark.parametrize("name,call", _SERIES_OPS,
                         ids=[c[0] for c in _SERIES_OPS])
def test_every_series_function_is_the_same_blocked(tight, one_device, name,
                                                   call):
    # a function that fuses (detrend, zscore, ...) rides behind a sort,
    # so that its arithmetic runs inside the blocked loop too
    x = sessions((12, 10, T))
    whole, blocked, ran = both_ways(
        tight, lambda: call(bolt.array(x, one_device, axis=(0, 1))),
        lambda a: a.toarray())
    assert ran > 1, name
    assert whole.dtype == blocked.dtype and np.array_equal(whole, blocked)


def test_a_count_that_does_not_divide_leaves_a_tail_block(tight,
                                                          one_device):
    x = sessions((7, 11, T))                # 77 records
    make = lambda: srt(bolt.array(x, one_device, axis=(0, 1)))
    arr = make()
    tight(arr, 200000)
    plan = arr._block_plan(*arr._chain)[-1]
    records, block = plan.runs[0]
    assert records == 77 and records % block and plan.blocks == -(-77 // block)
    tpu_array._HBM_LIMIT_OVERRIDE = None
    whole, blocked, ran = both_ways(tight, make, lambda a: a.toarray())
    assert ran == plan.blocks
    assert np.array_equal(whole, blocked)
    assert np.array_equal(blocked, np.sort(x, axis=-1))


def test_blocks_of_one_record_hold_a_matmul_to_rounding_only(tight,
                                                             one_device):
    # a record's arithmetic does not read its neighbours, but XLA picks
    # the kernel of a record's matrix product by the count of rows it is
    # given: the CPU backend multiplies ONE row (a block of one record)
    # by its matrix-vector kernel, which adds in another order than the
    # matrix-matrix kernel every larger block and the whole array get.
    # Blocks of two records and more are the whole array's to the bit
    # (every other test here); a block of one is held to float32
    # rounding.  The rule gives blocks of one only to a record that
    # fills a quarter of what the device has left by itself
    x = sessions((12, 10, T))
    make = lambda: ops.detrend(ops.normalize(
        bolt.array(x, one_device, axis=(0, 1)), "percentile", PERC),
        order=ORDER)
    arr = make()
    tight(arr, 30000)
    assert arr._block_plan(*arr._chain)[-1].block_records == 1
    tpu_array._HBM_LIMIT_OVERRIDE = None
    whole, blocked, ran = both_ways(tight, make, lambda a: a.toarray(),
                                    free=30000)
    assert ran == 120
    assert np.allclose(whole, blocked, rtol=0, atol=4e-6)   # dF/F is O(1)


def test_with_keys_maps_get_their_own_keys_in_every_block(tight,
                                                          one_device):
    x = sessions((6, 9, T))

    def keyed(kv):
        (i, j), v = kv
        return jnp.sort(v) + (100 * i + j).astype(v.dtype)
    make = lambda: keymap(bolt.array(x, one_device, axis=(0, 1)), keyed,
                          with_keys=True)
    whole, blocked, ran = both_ways(tight, make, lambda a: a.toarray())
    assert ran > 1 and np.array_equal(whole, blocked)
    i, j = np.meshgrid(np.arange(6), np.arange(9), indexing="ij")
    assert np.array_equal(
        blocked, np.sort(x, -1) + (100 * i + j)[..., None].astype(np.float32))


def test_a_window_in_front_slices_first(tight, one_device):
    x = sessions((12, 10, T))
    make = lambda: srt(bolt.array(x, one_device, axis=(0, 1))[2:11, 1:])
    whole, blocked, ran = both_ways(tight, make, lambda a: a.toarray())
    assert ran > 1 and np.array_equal(whole, blocked)
    assert np.array_equal(blocked, np.sort(x[2:11, 1:], axis=-1))


@pytest.mark.parametrize("name,fold", [
    ("sum", lambda a: a.sum()), ("mean", lambda a: a.mean()),
    ("std", lambda a: a.std()), ("max", lambda a: a.max()),
    ("reduce", lambda a: a.reduce(operator.add))])
def test_a_terminal_that_folds_the_chain_in_gets_the_blocked_body(
        tight, one_device, name, fold):
    # integer-valued data: every sum is exact whatever the order, so the
    # blocked program (which writes the mapped records out and folds
    # them as the whole-array program folds them) must agree to the bit
    rng = np.random.default_rng(3)
    x = rng.integers(-2048, 2048, (12, 10, 64)).astype(np.float32)
    make = lambda: srt(bolt.array(x, one_device, axis=(0, 1)))
    whole, blocked, ran = both_ways(
        tight, make, lambda a: np.asarray(fold(a).toarray()), free=40000)
    assert ran > 1, name
    assert np.array_equal(whole, blocked)
    # and on data that rounds, to float32 rounding
    y = sessions((12, 10, 64))
    make = lambda: srt(bolt.array(y, one_device, axis=(0, 1)))
    whole, blocked, ran = both_ways(
        tight, make, lambda a: np.asarray(fold(a).toarray()), free=40000)
    assert ran > 1
    assert np.allclose(whole, blocked, rtol=1e-6, atol=0)


def test_a_deferred_filter_reads_the_blocked_chain(tight, one_device):
    rng = np.random.default_rng(4)
    x = rng.integers(-2048, 2048, (40, 64)).astype(np.float32)

    def make():
        b = bolt.array(x, one_device, axis=(0,)).map(sorted_rows)
        return b.filter(lambda v: v[0] > -2000.0)
    whole = make().sum(axis=(0,)).toarray()
    before = engine.counters()["map_blocks"]
    arr = bolt.array(x, one_device, axis=(0,)).map(sorted_rows)
    tight(arr, 20000)
    blocked = arr.filter(lambda v: v[0] > -2000.0).sum(axis=(0,)).toarray()
    tpu_array._HBM_LIMIT_OVERRIDE = None
    assert engine.counters()["map_blocks"] - before > 1
    assert np.array_equal(whole, blocked)
    kept = np.sort(x, -1)
    assert np.array_equal(blocked, kept[kept[:, 0] > -2000.0].sum(axis=0))


@pytest.mark.parametrize("axis", [(0,), (0, 1)], ids=["split1", "split2"])
def test_on_four_devices_the_blocks_are_taken_inside_each_shard(
        tight, four_devices, axis):
    x = sessions((12, 8, T))

    def keyed(kv):
        k, v = kv
        return jnp.sort(v, axis=-1) + (10 * k[0] + k[-1]).astype(v.dtype)

    def make():
        b = bolt.array(x, four_devices, axis=axis)
        ax = 1 if len(axis) == 1 else 0
        dff = ops.detrend(ops.normalize(b, "percentile", PERC, axis=ax),
                          order=ORDER, axis=ax)
        return ops.fourier(dff, freq=FREQ, axis=ax)[0]
    whole, blocked, ran = both_ways(tight, make, lambda a: a.toarray())
    assert ran > 1 and np.array_equal(whole, blocked)
    make = lambda: keymap(bolt.array(x, four_devices, axis=axis), keyed,
                          with_keys=True)
    arr = make()
    tight(arr, 40000)
    marker = arr._block_plan(*arr._chain)[-1]
    tpu_array._HBM_LIMIT_OVERRIDE = None
    # a shard's records, not the array's
    assert marker.mesh is four_devices
    assert marker.runs[0][0] == prod(x.shape[:len(axis)]) // 4
    assert marker.block_records < marker.runs[0][0]
    whole, blocked, ran = both_ways(tight, make, lambda a: a.toarray(),
                                    free=40000)
    assert ran > 1 and np.array_equal(whole, blocked)
    # the keys a shard's blocks are handed are the array's own
    i, j = np.meshgrid(np.arange(12), np.arange(8), indexing="ij")
    add = (10 * i + j)[..., None] if len(axis) == 2 else \
        (11 * np.arange(12))[:, None, None]
    assert np.array_equal(blocked, np.sort(x, -1) + add.astype(np.float32))


# ---------------------------------------------------------------------
# (c) the rule
# ---------------------------------------------------------------------

def plus_one(v):                        # benchmark/fns/plus_one.py
    return v + 1


def revenue(r):                         # benchmark/steps/tpch_q6.py
    return r[1] * r[2]


def corner_mean(v):                     # benchmark/steps/kept_sum.py
    return v[:2, :2, :2].mean()


@pytest.mark.parametrize("name,func,record", [
    ("plus_one", plus_one, (200, 64, 64)), ("revenue", revenue, (8,)),
    ("corner_mean", corner_mean, (200, 64, 64))])
def test_the_chains_of_the_benchmark_cells_are_light(name, func, record):
    aval = jax.ShapeDtypeStruct(record, np.float32)
    heavy, live, out = blocks.record_live_bytes(func, (aval,))
    assert not heavy
    shape = (64,) + record
    funcs = (func,)
    # whatever the device has left (one byte), a chain that fuses is
    # lowered as ever: the same funcs, so the same engine key
    assert tpu_array._plan_blocks(funcs, 1, shape, np.float32, 1) is funcs
    whole = jax.ShapeDtypeStruct(shape, np.float32)
    now = jax.jit(lambda d: tpu_array._chain_apply(funcs, 1, d)).lower(whole)
    then = jax.jit(lambda d: jax.vmap(func)(d)).lower(whole)
    assert now.as_text() == then.as_text()


@pytest.mark.parametrize("name,func", [
    ("sort", sorted_rows), ("percentile", lambda v: jnp.percentile(v, 20.0)),
    ("fft", lambda v: jnp.abs(jnp.fft.rfft(v))), ("cumsum", jnp.cumsum),
    ("median", jnp.median),
    ("loop", lambda v: jax.lax.fori_loop(0, 3, lambda i, a: a * 0.5, v))])
def test_functions_with_record_sized_temporaries_are_heavy(name, func):
    aval = jax.ShapeDtypeStruct((T,), np.float32)
    heavy, live, out = blocks.record_live_bytes(func, (aval,))
    assert heavy and live >= 2 * T * 4      # the record and a copy of it


def test_a_heavy_chain_that_fits_is_lowered_as_ever(one_device):
    x = sessions((12, 10, T))
    funcs = (sorted_rows,)
    roomy = tpu_array._plan_blocks(funcs, 2, x.shape, np.float32, 1 << 30)
    assert roomy is funcs


def test_the_block_is_the_largest_inside_the_share():
    live, free = 1000, 4_000_000
    assert blocks.block_records(100, live, free) is None        # all fit
    block = blocks.block_records(100_000, live, free)
    assert block * live <= blocks.SHARE * free < (block + 8) * live + 8 * live
    assert block % 8 == 0
    # one record that fits what is left but not the share: blocks of one
    assert blocks.block_records(10, 3_000_000, free) == 1
    with pytest.raises(MemoryError, match="one record of this map chain"):
        blocks.block_records(10, free + 1, free)


# ---------------------------------------------------------------------
# the forecast (analysis.check / explain) reads the same rule
# ---------------------------------------------------------------------

def test_explain_says_blocked_with_the_blocks_the_program_runs(tight,
                                                               one_device):
    x = sessions((12, 10, T))
    arr = srt(bolt.array(x, one_device, axis=(0, 1)))
    assert not analysis.check(arr).has("BLT018")
    tight(arr, 200000)
    rep = analysis.check(arr)
    plan = arr._block_plan(*arr._chain)[-1]
    d, = [d for d in rep.diagnostics if d.code == "BLT018"]
    assert "blocked: %d blocks of %d records" % (
        plan.blocks, plan.block_records) in d.message
    assert "blocked: %d blocks" % plan.blocks in analysis.explain(arr)
    before = engine.counters()["map_blocks"]
    arr.toarray()
    assert engine.counters()["map_blocks"] - before == plan.blocks


def test_a_record_that_cannot_fit_is_refused_in_words_before_xla(
        tight, one_device):
    x = sessions((12, 10, T))
    arr = srt(bolt.array(x, one_device, axis=(0, 1)))
    tight(arr, 500)                     # a record alone is 1 KB
    rep = analysis.check(arr)
    d, = [d for d in rep.diagnostics if d.code == "BLT019"]
    assert d.severity == "error" and "500 bytes left" in d.message
    compiles = engine.counters()["aot_compiles"]
    with pytest.raises(MemoryError, match="one record of this map chain"):
        arr.toarray()
    with pytest.raises(MemoryError, match="one record of this map chain"):
        arr.sum()
    assert engine.counters()["aot_compiles"] == compiles


# ---------------------------------------------------------------------
# spans and counters
# ---------------------------------------------------------------------

def test_the_chain_span_carries_blocks_and_the_counters_reset(tight,
                                                              one_device):
    from bolt_tpu import obs
    x = sessions((12, 10, T))
    arr = srt(bolt.array(x, one_device, axis=(0, 1)))
    tight(arr, 200000)
    plan = arr._block_plan(*arr._chain)[-1]
    obs.enable()
    try:
        obs.clear()
        arr.toarray()
        spans = [s for s in obs.spans() if s.name == "array.chain"]
    finally:
        obs.disable()
    assert spans and spans[-1].attrs["blocks"] == plan.blocks
    assert spans[-1].attrs["block_records"] == plan.block_records
    assert spans[-1].attrs["funcs"] == 1
    c = engine.counters()
    assert c["map_blocks"] >= plan.blocks and c["blocked_chains"] >= 1
    imported = c["import_seconds"]
    assert imported > 0.0
    engine.reset_counters()
    c = engine.counters()
    assert c["map_blocks"] == 0 and c["blocked_chains"] == 0
    # a fact of the process, not a tally since the last reset
    assert c["import_seconds"] == imported


# ---------------------------------------------------------------------
# (d) the percentile by SELECTION (ops/select.py, PR 37) through the
# lowerings that carry it: series long enough to be selected and not
# sorted (T raised over the crossover for these cases only)
# ---------------------------------------------------------------------

from bolt_tpu.ops import select  # noqa: E402

LONG = 2 * select._SELECT_FROM
_LONG_OPS = [
    ("normalize-percentile", lambda b: ops.normalize(b, "percentile", PERC)),
    ("tuning-coherence", lambda b: tuning(b)[0]),
]


def percentile_lowerings():
    c = engine.counters()
    return (c["percentile_select_lowerings"], c["percentile_sort_lowerings"])


def record_live(arr):
    """What the rule says one record of the deferred ``arr``'s chain
    (maps alone) holds live."""
    base, funcs = arr._chain
    rec = (jax.ShapeDtypeStruct(base.shape[arr.split:], base.dtype),) \
        + (jax.ShapeDtypeStruct((), np.int32),) * arr.split
    return blocks.record_live_bytes(tpu_array._record_fn(funcs), rec)[1]


@pytest.mark.parametrize("name,call", _LONG_OPS,
                         ids=[c[0] for c in _LONG_OPS])
@pytest.mark.parametrize("shape,fits,blocks_are", [
    ((6, 4, LONG), 4, "even"),          # 24 records in blocks of 4
    ((7, 5, LONG), 4, "tail"),          # 35 records: a tail block of 3
    ((3, 4, LONG), 1, "ones")],         # blocks of one record
    ids=["even", "tail", "ones"])
def test_a_selected_percentile_is_the_same_blocked(tight, one_device, name,
                                                   call, shape, fits,
                                                   blocks_are):
    x = sessions(shape)
    make = lambda: call(bolt.array(x, one_device, axis=(0, 1)))
    arr = make()
    # what the device has "left": a quarter of it holds ``fits`` records
    free = int((4 * fits + 2) * record_live(arr))
    tight(arr, free)
    records, block = arr._block_plan(*arr._chain)[-1].runs[0]
    tpu_array._HBM_LIMIT_OVERRIDE = None
    assert records == prod(shape[:2]) and block == fits
    assert bool(records % block) == (blocks_are == "tail")
    select_before, sort_before = percentile_lowerings()
    whole, blocked, ran = both_ways(tight, make, lambda a: a.toarray(),
                                    free=free)
    assert ran == -(-records // block) and ran > 1
    # selection on both lowerings, and never the sort
    select_after, sort_after = percentile_lowerings()
    assert select_after > select_before and sort_after == sort_before
    assert whole.dtype == blocked.dtype
    if blocks_are == "ones" and name != "normalize-percentile":
        # one-row matrix products (the detrend) take another kernel on
        # the CPU: float32 rounding, as PR 36's item 6 found
        assert np.allclose(whole, blocked, rtol=0, atol=4e-6)
    else:
        assert np.array_equal(whole, blocked)
    if name == "normalize-percentile":
        x64 = x.astype(np.float64)
        base = np.percentile(x64, PERC, axis=-1, keepdims=True)
        assert np.max(np.abs(blocked - (x64 - base) / base)) < 1e-6


@pytest.mark.parametrize("axis", [(0,), (0, 1)], ids=["split1", "split2"])
def test_a_selected_percentile_on_four_devices(tight, four_devices, axis):
    x = sessions((16, 4, LONG))
    ax = 1 if len(axis) == 1 else 0

    def make():
        b = bolt.array(x, four_devices, axis=axis)
        dff = ops.detrend(ops.normalize(b, "percentile", PERC, axis=ax),
                          order=ORDER, axis=ax)
        return ops.fourier(dff, freq=FREQ, axis=ax)[0]
    # a shard's 16 records (split1: 4) in blocks of two
    free = int(10 * record_live(make()))
    whole, blocked, ran = both_ways(tight, make, lambda a: a.toarray(),
                                    free=free)
    assert ran > 1 and np.array_equal(whole, blocked)
    want_coh, _ = tuning_reference(x)
    assert np.max(np.abs(blocked - want_coh)) < 2e-5


def test_the_selection_is_heavy_and_its_estimate_covers_the_compiler():
    fn = ops.series._normalize_fn("percentile", PERC, 0, 0.0)
    aval = jax.ShapeDtypeStruct((LONG,), np.float32)
    heavy, live, out = blocks.record_live_bytes(fn, (aval,))
    # the loop of passes keeps the chain among those lowered over blocks
    assert heavy and out.shape == (LONG,)
    # the record, its image of keys, and the result at the least
    assert live >= 3 * LONG * 4
    rows = 64
    compiled = jax.jit(jax.vmap(fn)).lower(
        jax.ShapeDtypeStruct((rows, LONG), np.float32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes <= rows * live
    text = compiled.as_text()
    assert "percentile_select" in text and " sort(" not in text


@pytest.mark.parametrize("t,took", [(select._SELECT_FROM - 1, "sort"),
                                    (select._SELECT_FROM, "select")],
                         ids=["under", "at"])
def test_the_counters_and_explain_name_the_regime(one_device, t, took):
    x = sessions((4, 3, t))
    arr = ops.normalize(bolt.array(x, one_device, axis=(0, 1)),
                        "percentile", PERC)
    said = analysis.explain(arr)
    assert ("percentile by selection" in said) == (took == "select")
    assert ("percentile by sort" in said) == (took == "sort")
    select_before, sort_before = percentile_lowerings()
    arr.toarray()
    select_after, sort_after = percentile_lowerings()
    assert (select_after > select_before) == (took == "select")
    assert (sort_after > sort_before) == (took == "sort")
    # a mean baseline takes no percentile at all
    mean = ops.normalize(bolt.array(x, one_device, axis=(0, 1)), "mean")
    assert "percentile" not in analysis.explain(mean)
    mean.toarray()
    assert percentile_lowerings() == (select_after, sort_after)
