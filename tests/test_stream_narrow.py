"""Stored data NARROWER than 32 bits through every streamed consumer
(ISSUE 59): a camera's ``uint16`` words, ``int16`` and ``uint8`` through
``fromcallback`` and ``fromiter``, on one device and on four, into the
resident and the spilled ``swap``, the fold and the collect, each against
``mode='local'`` (NumPy, the oracle).  The element is never widened on the
way: the bytes a pass uploads are the source's stored bytes, the answer
keeps or promotes the dtype as NumPy does, and the lowered place program of
a 16-bit slab holds no float32 of the slab's shape.  Sizes are small and
seeded; a pass is seven slabs, the last one short, which is no multiple of
the resolver's window of three."""

import jax
import numpy as np
import pytest

import bolt_tpu as bolt
from bolt_tpu import analysis, engine, obs, stream
from bolt_tpu.parallel import shuffle

SHAPE = (50, 4, 8)            # 50 records: six slabs of 8 and one of 2;
#                               rows of whole 32-bit words in each dtype
CHUNKS = 8
NSLABS = 7
DTYPES = ["uint16", "int16", "uint8"]
DOORS = ["fromcallback", "fromiter"]
DEVICES = [1, 4]


def _mesh(n):
    return jax.sharding.Mesh(np.array(jax.devices()[:n]), ("k",))


def _data(dtype, seed=0):
    """Seeded values over the element's whole range, the signed one's
    negatives among them."""
    info = np.iinfo(dtype)
    rng = np.random.default_rng(seed)
    return rng.integers(info.min, int(info.max) + 1, size=SHAPE,
                        dtype=np.int64).astype(dtype)


def _source(x, door, ndev):
    mesh = _mesh(ndev)
    if door == "fromcallback":
        return bolt.fromcallback(lambda idx: x[idx], x.shape, mesh,
                                 dtype=x.dtype, chunks=CHUNKS)
    blocks = [x[lo:lo + CHUNKS] for lo in range(0, x.shape[0], CHUNKS)]
    return bolt.fromiter(blocks, x.shape, mesh, dtype=x.dtype)


def _local_swap(x):
    """``swap((0,), (0, 1))`` as ``mode='local'`` has it: the local array
    is NumPy's, whose re-axis is ``transpose``."""
    return bolt.array(x).transpose(1, 2, 0).toarray()


def _widen(v):
    return v.astype(np.float32)


def _plus_one(v):
    return v + 1


everywhere = pytest.mark.parametrize(
    "dtype,door,ndev",
    [(t, d, n) for t in DTYPES for d in DOORS for n in DEVICES],
    ids=["%s-%s-%ddev" % (t, d, n)
         for t in DTYPES for d in DOORS for n in DEVICES])


@everywhere
def test_the_resident_swap_keeps_the_element_and_uploads_its_bytes(
        dtype, door, ndev):
    x = _data(dtype)
    want = _local_swap(x)
    c0 = engine.counters()
    got = _source(x, door, ndev).swap((0,), (0, 1)).cache()
    c1 = engine.counters()
    assert got.dtype == want.dtype == np.dtype(dtype)
    assert got.shape == want.shape == (4, 8, 50) and got.split == 2
    assert np.array_equal(got.toarray(), want)
    assert np.array_equal(want, np.transpose(x, (1, 2, 0)))
    # nothing was widened before the link, and the counters say so
    assert c1["transfer_bytes"] - c0["transfer_bytes"] == x.nbytes
    assert c1["transfer_elements"] - c0["transfer_elements"] == x.size
    assert c1["stream_narrow_slabs"] - c0["stream_narrow_slabs"] == NSLABS
    assert c1["stream_chunks"] - c0["stream_chunks"] == NSLABS
    assert c1["shuffle_bytes"] - c0["shuffle_bytes"] == x.nbytes
    assert c1["spill_bytes"] == c0["spill_bytes"]
    # on ONE device the slabs went up as the words they are and the place
    # program unpacked them; a mesh of four keeps the loader's blocks
    assert c1["stream_thin_slabs"] - c0["stream_thin_slabs"] \
        == (NSLABS if ndev == 1 else 0)


@everywhere
def test_the_spilled_swap_keeps_the_element(dtype, door, ndev, tmp_path):
    x = _data(dtype, 1)
    want = _local_swap(x)
    c0 = engine.counters()
    with stream.spill(dir=str(tmp_path), budget=1):
        got = _source(x, door, ndev).swap((0,), (0, 1))
        out = got.toarray()
    c1 = engine.counters()
    assert got.dtype == out.dtype == want.dtype == np.dtype(dtype)
    assert np.array_equal(out, want)
    assert c1["spill_bytes"] > c0["spill_bytes"]
    assert c1["stream_narrow_slabs"] - c0["stream_narrow_slabs"] >= NSLABS


@pytest.mark.parametrize("stat", ["sum", "mean"])
@everywhere
def test_the_fold_behind_a_widening_map_is_locals(dtype, door, ndev, stat):
    x = _data(dtype, 2)
    want = getattr(bolt.array(x).map(_widen), stat)(axis=(0,))
    c0 = engine.counters()
    got = getattr(_source(x, door, ndev).map(_widen), stat)(axis=(0,))
    want, got = np.asarray(want.toarray()), np.asarray(got.toarray())
    c1 = engine.counters()
    assert got.dtype == want.dtype and got.shape == want.shape
    # sums of integers under 2**24: exact in float32, whatever the order
    assert np.array_equal(got, want) if stat == "sum" \
        else np.allclose(got, want, rtol=1e-6, atol=1e-2)
    assert c1["transfer_bytes"] - c0["transfer_bytes"] == x.nbytes
    assert c1["stream_narrow_slabs"] - c0["stream_narrow_slabs"] == NSLABS


@everywhere
def test_the_fold_of_the_stored_element_promotes_as_numpy_does(
        dtype, door, ndev):
    x = _data(dtype, 3)
    want = np.asarray(bolt.array(x).sum(axis=(0,)).toarray())
    got = np.asarray(_source(x, door, ndev).sum(axis=(0,)).toarray())
    assert got.dtype == want.dtype == x.sum(axis=0).dtype
    assert np.array_equal(got, want)


@pytest.mark.parametrize("func", [_plus_one, _widen],
                         ids=["kept", "widened"])
@everywhere
def test_the_collect_is_locals(dtype, door, ndev, func):
    x = _data(dtype, 4)
    want = bolt.array(x).map(func).toarray()
    c0 = engine.counters()
    got = _source(x, door, ndev).map(func).toarray()
    c1 = engine.counters()
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.array_equal(got, want)
    assert c1["transfer_bytes"] - c0["transfer_bytes"] == x.nbytes
    assert c1["stream_collect_slabs"] - c0["stream_collect_slabs"] \
        == NSLABS


def test_the_fold_and_the_collect_take_the_words_on_one_device():
    x = _data("uint16", 6)
    c0 = engine.counters()
    _source(x, "fromcallback", 1).map(_widen).sum(axis=(0,)).toarray()
    _source(x, "fromiter", 1).map(_plus_one).toarray()
    c1 = engine.counters()
    assert c1["stream_thin_slabs"] - c0["stream_thin_slabs"] == 2 * NSLABS
    assert c1["stream_narrow_slabs"] - c0["stream_narrow_slabs"] \
        == 2 * NSLABS


@pytest.mark.parametrize("shape,dtype,words", [
    ((128, 512, 512), "uint16", True), ((128, 512, 512), "int16", True),
    ((128, 512, 512), "uint8", True), ((1000, 6), "uint16", True),
    ((1000, 7), "uint16", False),       # a row of three and a half words
    ((1000, 6), "uint8", False), ((1000, 8), "uint8", True),
    ((1000,), "uint16", False),         # no row to keep whole
    ((128, 512, 512), "float32", False), ((128, 512, 512), "int32", False),
    ((128, 512, 512), "float16", False), ((128, 512, 512), "bool", False)])
def test_the_rule_reads_the_record_alone(shape, dtype, words):
    assert stream.narrow_words(shape, dtype) is words
    assert not (words and stream.thin_records(shape, dtype))
    src = bolt.fromcallback(lambda idx: None, shape, _mesh(1),
                            dtype=np.dtype(dtype))._stream
    assert bool(stream.dense_route(src)) is (
        words or stream.thin_records(shape, dtype))
    if words:
        # for ONE device, and not under a codec's wire form
        four = bolt.fromcallback(lambda idx: None, shape, _mesh(4),
                                 dtype=np.dtype(dtype))._stream
        assert not stream.dense_route(four)


@pytest.mark.parametrize("dtype", DTYPES)
def test_the_words_are_views_and_unpack_to_the_bit(dtype):
    x = _data(dtype, 7)
    views = stream._dense_views(x[:8])
    assert len(views) == 1 and np.shares_memory(views[0], x)
    assert views[0].dtype == np.uint32 and views[0].nbytes == x[:8].nbytes
    parts = tuple(jax.device_put(v) for v in views)
    assert stream._dense_shape(parts, x.dtype) == (8, 4, 8)
    back = jax.jit(lambda p: stream._reseat(p, x.dtype))(parts)
    assert back.dtype == x.dtype
    assert np.array_equal(np.asarray(back), x[:8])


@pytest.mark.parametrize("dtype", ["uint16", "int16"])
def test_neighbours_exchanged_inside_a_word_are_not_locals(dtype,
                                                           monkeypatch):
    """The unpack's one way to go wrong, made to happen: the two halves of
    every word the other way round.  The re-axis then differs from
    ``mode='local'`` in nearly every element, so the tests above see it."""
    sound = stream._reseat

    def halves_exchanged(parts, dtype=None):
        out = sound(parts, dtype)
        if dtype is None or parts[0].dtype == dtype:
            return out
        pairs = out.reshape(out.shape[:-1] + (out.shape[-1] // 2, 2))
        return pairs[..., ::-1].reshape(out.shape)
    monkeypatch.setattr(stream, "_reseat", halves_exchanged)
    x = _data(dtype, 8)
    # a slab of another size: a program the sound run has not left cached
    src = bolt.fromcallback(lambda idx: x[idx], x.shape, _mesh(1),
                            dtype=x.dtype, chunks=10)
    got = src.swap((0,), (0, 1)).toarray()
    want = _local_swap(x)
    assert (got != want).mean() > 0.9
    assert np.array_equal(np.sort(got, axis=None), np.sort(want, axis=None))


def test_a_block_that_is_no_contiguous_run_keeps_the_loaders_form():
    """A loader that hands out a strided view: nothing to view as words,
    so the block goes up as it is and the answer is the same."""
    wide = np.random.default_rng(9).integers(
        0, 4096, size=(50, 4, 16)).astype(np.uint16)
    x = wide[:, :, ::2]
    assert not x[:8].flags.c_contiguous
    c0 = engine.counters()
    got = bolt.fromiter([x[lo:lo + 8] for lo in range(0, 50, 8)], x.shape,
                        _mesh(1), dtype=x.dtype).swap((0,), (0, 1)).toarray()
    c1 = engine.counters()
    assert np.array_equal(got, np.transpose(x, (1, 2, 0)))
    assert c1["transfer_bytes"] - c0["transfer_bytes"] == x.nbytes
    assert c1["stream_narrow_slabs"] - c0["stream_narrow_slabs"] == NSLABS


@pytest.mark.parametrize("dtype,narrow", [("uint16", 1), ("int16", 1),
                                          ("uint8", 1), ("float32", 0),
                                          ("int32", 0)])
def test_a_narrow_slab_is_one_of_under_four_bytes_an_element(dtype, narrow):
    x = np.arange(np.prod(SHAPE)).astype(dtype).reshape(SHAPE) % 100
    x = x.astype(dtype)
    c0 = engine.counters()
    _source(x, "fromcallback", 1).swap((0,), (0, 1)).cache()
    c1 = engine.counters()
    assert c1["stream_narrow_slabs"] - c0["stream_narrow_slabs"] \
        == narrow * NSLABS
    assert (c1["transfer_bytes"] - c0["transfer_bytes"]) \
        / (c1["transfer_elements"] - c0["transfer_elements"]) \
        == x.dtype.itemsize


@pytest.mark.parametrize("words", [False, True],
                         ids=["as-loaded", "as-words"])
@pytest.mark.parametrize("dtype", DTYPES)
def test_the_place_program_of_a_narrow_slab_holds_no_float32(dtype, words):
    """The lowered text of the place program at ``toseries16``'s shapes in
    miniature, handed the slab as the loader gave it (a mesh of several
    devices, a block that is no contiguous run) and as its 32-bit words
    (one device): the slab, the transposed block and the swapped array in
    the stored element, no float32 anywhere, and nothing 32 bits wide of
    the slab's size but the words themselves."""
    mesh = _mesh(1)
    session, frames = (256, 16, 128), 128
    item = np.dtype(dtype).itemsize
    plan = shuffle.plan_shuffle(session, dtype, 1, (1, 2, 0), 2, mesh,
                                frames, None, None, ring=5)
    slab_shape = (frames,) + session[1:]
    prog = shuffle.place_program(plan, (), mesh, None, np.dtype(dtype),
                                 slab_shape, True, frames, words)
    slab = jax.ShapeDtypeStruct(slab_shape, dtype)
    if words:
        slab = (jax.ShapeDtypeStruct((frames, 16, 128 * item // 4),
                                     np.uint32),)
    text = prog.lower(
        jax.ShapeDtypeStruct(plan.out_shape, dtype), slab,
        jax.ShapeDtypeStruct((), np.uint32)).as_text()
    short = {"uint16": "ui16", "int16": "i16", "uint8": "ui8"}[dtype]
    if words:
        assert "tensor<128x16x%dxui32>" % (128 * item // 4) in text
        assert text.count("stablehlo.bitcast_convert") == 1
        assert "narrow_unpack" in prog.lower(
            jax.ShapeDtypeStruct(plan.out_shape, dtype), slab,
            jax.ShapeDtypeStruct((), np.uint32)).as_text(debug_info=True)
    else:
        assert "stablehlo.bitcast_convert" not in text
    assert "tensor<128x16x128x%s>" % short in text          # the slab
    assert "tensor<16x128x128x%s>" % short in text          # its block
    assert "tensor<16x128x256x%s>" % short in text          # the array
    assert "xf32>" not in text and "xf64>" not in text
    for wide in ("128x16x128x", "16x128x128x", "16x128x256x"):
        assert wide + "i32>" not in text and wide + "ui32>" not in text
    # the plan counts two bytes an element (one for uint8)
    assert plan.total_bytes == int(np.prod(session)) * item
    assert plan.slab_bytes == frames * 16 * 128 * item


def test_the_default_slab_of_16_bit_frames_is_whole_lane_tiles():
    """The caller who sets nothing: 64 MiB of 512 x 512 uint16 frames is
    128 of them, whole lane tiles already, so ``lane_slab`` leaves it; the
    forecast counts the stored width and keeps the session resident where
    the same frames as float32 spill."""
    mesh = _mesh(1)
    src = bolt.fromcallback(lambda idx: None, (20480, 512, 512), mesh,
                            dtype=np.uint16)
    sw = src.swap((0,), (0, 1))._stream
    assert sw.slab == 128 and len(sw.slab_ranges()) == 160
    assert stream._raw_slab_bytes(sw) == 128 * 512 * 512 * 2
    _, perm, new_split = sw.stages[0]
    plans = {
        dt: shuffle.plan_shuffle((20480, 512, 512), dt, 1, perm, new_split,
                                 mesh, 128, int(16.9e9), None,
                                 ring=stream.swap_ring(sw))
        for dt in (np.uint16, np.float32)}
    assert plans[np.uint16].resident and not plans[np.float32].resident
    assert plans[np.uint16].total_bytes == 10737418240
    ring = stream.swap_ring(sw)
    assert plans[np.uint16].resident_bytes \
        == 10737418240 + (ring + 1) * 128 * 512 * 512 * 2


def test_explain_prints_the_stored_and_the_wire_width():
    mesh = _mesh(1)
    x = _data("uint16")
    text = analysis.explain(_source(x, "fromcallback", 1).swap((0,), (0, 1)))
    assert "stored 2 B an element, 2 B on the wire" in text
    f = np.zeros(SHAPE, np.float32)
    text = analysis.explain(bolt.fromcallback(
        lambda idx: f[idx], f.shape, mesh, dtype=np.float32, chunks=CHUNKS)
        .map(_plus_one))
    assert "stored 4 B an element, 4 B on the wire" in text
    # a codec narrows the wire and not what is stored
    i = np.zeros(SHAPE, np.int64)
    text = analysis.explain(bolt.fromcallback(
        lambda idx: i[idx], i.shape, mesh, dtype=np.int64, chunks=CHUNKS,
        codec="dict").map(_plus_one))
    assert "stored 8 B an element, 1 B on the wire" in text


def test_the_spans_carry_the_stored_dtype():
    x = _data("uint16", 5)
    obs.clear()
    obs.enable()
    try:
        _source(x, "fromcallback", 1).swap((0,), (0, 1)).cache()
        spans = obs.spans()
    finally:
        obs.disable()
        obs.clear()
    for name in ("stream.transfer", "stream.compute"):
        mine = [sp for sp in spans if sp.name == name]
        assert len(mine) == NSLABS, name
        assert {sp.attrs["dtype"] for sp in mine} == {"uint16"}, name
