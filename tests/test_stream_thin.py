"""Thin records on the link (ISSUE 51, part B): a slab of a source whose
records are a few 32-bit values goes up as a dense view of the bytes the
loader returned and is given its shape by its slab program's first
operation (``stream.thin_records``, ``_dense_views``, ``_reseat``).  The
rule reads the record's shape and dtype and nothing a caller sets; a fat
record's upload, a codec's wire form and a mesh of several devices keep
what they had.
"""

import numpy as np
import pytest

import jax

import bolt_tpu as bolt
from bolt_tpu import engine, obs, stream


@pytest.fixture
def one():
    """A one-device mesh: the dense form is one device's."""
    return jax.sharding.Mesh(np.array(jax.devices()[:1]), ("k",))


def _table(rows, cols, dtype, seed=11):
    rng = np.random.default_rng(seed)
    return rng.integers(-1000, 1000, size=(rows, cols)).astype(dtype)


def _source(x, mesh, chunks=None, **kw):
    return bolt.fromcallback(lambda idx: x[idx], x.shape, mesh,
                             dtype=x.dtype, chunks=chunks, **kw)


@pytest.mark.parametrize("shape,dtype,thin", [
    ((1000, 1), np.float32, True), ((1000, 7), np.float32, True),
    ((1000, 8), np.float32, True), ((1000, 9), np.float32, True),
    ((1000, 63), np.int32, True), ((1000, 7), np.uint32, True),
    ((1000, 64), np.float32, True), ((1000, 65), np.float32, False),
    ((1000, 128), np.float32, False),
    ((1000, 7), np.float64, False), ((1000, 7), np.int16, False),
    ((1000, 256, 128), np.float32, False), ((1000, 2, 7), np.float32, False),
    # planes of thin rows, whole groups of 128 rows a plane (ISSUE 55)
    ((64, 1048576, 64), np.float32, True), ((5, 256, 16), np.int32, True),
    ((5, 200, 16), np.float32, False), ((5, 256, 65), np.float32, False),
    ((2, 5, 256, 16), np.float32, False),
    ((1000,), np.float32, False)])
def test_the_rule_reads_the_record_alone(shape, dtype, thin):
    assert stream.thin_records(shape, dtype) is thin


@pytest.mark.parametrize("dtype", [np.float32, np.int32],
                         ids=["float32", "int32"])
@pytest.mark.parametrize("cols", [1, 7, 8, 9])
@pytest.mark.parametrize("rows", [128, 1024, 129, 1000, 1151],
                         ids=lambda r: "rows%d" % r)
def test_a_thin_slab_goes_up_dense_and_reads_back_to_the_bit(rows, cols,
                                                             dtype):
    x = _table(rows, cols, dtype)
    views = stream._dense_views(x)
    assert all(np.shares_memory(v, x) for v in views)       # zero-copy
    assert views[0].shape == (rows // 128, 128 * cols)
    assert len(views) == (2 if rows % 128 else 1)
    assert sum(v.size for v in views) == x.size
    back = jax.jit(stream._reseat)(tuple(jax.device_put(v) for v in views))
    assert back.shape == x.shape and back.dtype == x.dtype
    assert np.array_equal(np.asarray(back), x)


@pytest.mark.parametrize("dtype", [np.float32, np.int32],
                         ids=["float32", "int32"])
@pytest.mark.parametrize("chunks", [256, 300, 100],
                         ids=["whole-groups", "ragged", "under-a-group"])
@pytest.mark.parametrize("cols", [1, 7, 9])
def test_a_streamed_fold_of_thin_records_is_the_resident_one(one, cols,
                                                             chunks, dtype):
    x = _table(1000, cols, dtype)
    c0 = engine.counters()
    got = _source(x, one, chunks).map(lambda r: r * 2).sum().toarray()
    c1 = engine.counters()
    nslabs = -(-1000 // chunks)
    assert c1["stream_chunks"] - c0["stream_chunks"] == nslabs
    # every slab of 128 rows and more went up dense (the tail too)
    dense = sum(1 for lo in range(0, 1000, chunks)
                if min(chunks, 1000 - lo) >= 128)
    assert c1["stream_thin_slabs"] - c0["stream_thin_slabs"] == dense
    assert c1["transfer_bytes"] - c0["transfer_bytes"] == x.nbytes
    assert np.array_equal(got, (x * 2).sum(axis=0))
    want = bolt.array(x, one).map(lambda r: r * 2).sum().toarray()
    assert np.array_equal(got, want)
    # the other statistics, through the fused group
    stats = _source(x, one, chunks).stats("mean", "max")
    assert np.allclose(stats["mean"].toarray(), x.mean(axis=0))
    assert np.array_equal(stats["max"].toarray(), x.max(axis=0))


def test_what_is_not_thin_goes_up_as_it_did(one, mesh, monkeypatch):
    seen = []
    real = stream._upload_slab

    def spy(block, mesh_, split, *dense):
        seen.append((block.shape, dense))
        return real(block, mesh_, split, *dense)
    monkeypatch.setattr(stream, "_upload_slab", spy)

    def run(x, m, **kw):
        seen.clear()
        c0 = engine.counters()["stream_thin_slabs"]
        got = _source(x, m, 256, **kw).sum().toarray()
        assert np.allclose(got, x.sum(axis=0))
        return engine.counters()["stream_thin_slabs"] - c0

    fat = _table(512, 128, np.float32)
    assert run(fat, one) == 0                    # a fat record
    assert seen == [((256, 128), ())] * 2        # the parent's call
    assert run(_table(512, 65, np.float32), one) == 0    # pads by under two
    thin = _table(512, 7, np.float32)
    assert run(thin, one) == 2
    assert seen == [((256, 7), (True,))] * 2
    assert run(thin, mesh) == 0                  # eight devices
    assert all(d == () for _, d in seen)
    assert run(thin, one, codec="delta-f32") == 0        # a wire form
    assert run(_table(512, 7, np.float64), one) == 0     # 64-bit


def test_a_collect_and_a_resident_swap_of_thin_records_go_up_dense(one):
    """The resolver's resident leg takes a thin slab as the fold does
    (ISSUE 55): dense up the link, re-seated by the place program, and
    what it assembles is the materialised result to the bit."""
    x = _table(1024, 6, np.float32)
    c0 = engine.counters()["stream_thin_slabs"]
    mapped = _source(x, one, 256).map(lambda r: r + 1)
    assert np.array_equal(mapped.toarray(), x + 1)
    assert engine.counters()["stream_thin_slabs"] - c0 == 4
    swapped = _source(x, one, 256).swap((0,), (0,))
    assert np.array_equal(swapped.toarray(), x.T)
    assert engine.counters()["stream_thin_slabs"] - c0 == 8
    # the re-seat's copies of a slab come off the resident budget
    src = mapped._stream if mapped.streaming else _source(
        x, one, 256).map(lambda r: r + 1)._stream
    with stream.spill(budget=10 ** 6):
        assert stream.place_budget(src) == 10 ** 6 - 2 * 256 * 6 * 4


@pytest.mark.parametrize("planes", [1, 2, 3], ids=lambda k: "slab%d" % k)
@pytest.mark.parametrize("cols", [8, 64])
def test_planes_of_thin_rows_go_up_dense_and_read_back_to_the_bit(
        one, planes, cols):
    rng = np.random.default_rng(5)
    x = rng.integers(-1000, 1000, size=(5, 256, cols)).astype(np.float32)
    views = stream._dense_views(x[:planes])
    assert len(views) == 1 and np.shares_memory(views[0], x)
    assert views[0].shape == (planes, 2, 128 * cols)
    parts = tuple(jax.device_put(v) for v in views)
    assert stream._dense_shape(parts) == (planes, 256, cols)
    back = jax.jit(stream._reseat)(parts)
    assert np.array_equal(np.asarray(back), x[:planes])
    # through the executor, both consumers: a fold and a collect
    src = bolt.fromcallback(lambda idx: x[idx], x.shape, one,
                            dtype=x.dtype, chunks=planes)
    c0 = engine.counters()["stream_thin_slabs"]
    assert np.array_equal(src.sum().toarray(), x.sum(axis=0))
    nslabs = -(-5 // planes)
    assert engine.counters()["stream_thin_slabs"] - c0 == nslabs
    mapped = bolt.fromcallback(lambda idx: x[idx], x.shape, one,
                               dtype=x.dtype, chunks=planes).map(
        lambda v: v[:, :2] * 2)
    assert np.array_equal(mapped.toarray(), x[:, :, :2] * 2)
    assert engine.counters()["stream_thin_slabs"] - c0 == 2 * nslabs


# what ``stack4d-1chip.stream``'s two slab programs read as at the parent
# commit (a712fb1, jax 0.9.0): sha256 of ``lower(...).as_text()``
PARENT_TEXT = {
    "slab-sum":
        "272fe560eb5da2d8c34ba3043691d44bc4e2eda5303febf41a487bda39763ab0",
    "slab-sum-fused":
        "0563f4b4c2fa7a0e650824a2c64e17fb98d92eca2d844f2c85a26a9cb57fab48",
    # the three tupled terminals, read at 06c96b3 before ISSUE 57 touched
    # the file: a fused multi-stat group over the same fat source, Q1's
    # shape (a grouped mean behind a filter over thin rows of seven, dense)
    # and a Gram matrix with its sums over planes of thin rows
    "slab-multi":
        "9abaab6000346c024364bbcda57c769dead02131ed06411e4a6fef4a76337e48",
    "slab-multi-fused":
        "fab9471c51de14e0a2d7e89dbeba463dc55974519904d9bd1334f4f4206d50e2",
    "slab-group":
        "0fc37ff9ee19d7a7a3e180fa90f6d9c015ff7e255b6be69a3d9785bfb09cb44f",
    "slab-group-fused":
        "b444661a8fcd2b5f79a07fcf168fc84012a6fffb0507f822b1410bb645316c37",
    "slab-gram":
        "6ab080d254ca99e79d95e39f694ebe4f41c01f53445334f52b8f04d210af19fc",
    "slab-gram-fused":
        "54a287cbf2cef99a08eacc61ce613151d5870c04101755cc3b34b7908e8ba7f9",
}


def plus_one(v):
    return v + 1


def _keep(r):
    return r[0] > 0


def _label(r):
    return (r[1] > 0).astype(np.int32) * 2 + (r[2] > 0).astype(np.int32)


def _terms(r):
    return (r[3], r[4] * r[5])


def _both(name, src, terminal, wshape, slab, **kw):
    """The lowered text of ``terminal``'s slab program over ``src`` and of
    its acc-fused twin (the partial it takes is the first's result)."""
    first = stream._slab_program(src, terminal, wshape, **kw).lower(slab)
    acc = jax.tree_util.tree_map(
        lambda o: jax.ShapeDtypeStruct(o.shape, o.dtype), first.out_info)
    fused = stream._slab_program(src, terminal, wshape, fused=True,
                                 **kw).lower(slab, acc)
    return {name: first.as_text(), name + "-fused": fused.as_text()}


@pytest.mark.skipif(jax.__version__ != "0.9.0",
                    reason="the recorded text is jax 0.9.0's")
def test_the_fat_slab_program_is_the_parents_to_the_letter(one):
    """``stack4d-1chip.stream``'s programs at its own sizes (records of
    (256, 128), slabs of 512, a map, a sum): the lowered text does not
    know of thin records."""
    import hashlib
    src = bolt.fromcallback(lambda idx: None, (163840, 256, 128), one,
                            dtype=np.float32).map(plus_one)._stream
    assert not stream.thin_records(src.shape, src.dtype)
    slab = jax.ShapeDtypeStruct((512, 256, 128), np.float32)
    acc = jax.ShapeDtypeStruct((256, 128), np.float32)
    texts = {
        "slab-sum": stream._slab_program(
            src, stream._Sum(), slab.shape).lower(slab).as_text(),
        "slab-sum-fused": stream._slab_program(
            src, stream._Sum(), slab.shape, fused=True).lower(
                slab, acc).as_text(),
    }
    specs = (("sum", None), ("mean", None), ("var", 1), ("ptp", None))
    texts.update(_both("slab-multi", src, stream._Multi(specs), slab.shape,
                       slab))
    rows = 1000
    tsrc = bolt.fromcallback(lambda idx: None, (4000, 7), one,
                             dtype=np.float32, chunks=rows).filter(
                                 _keep)._stream
    assert stream.dense_route(tsrc)
    dense = (jax.ShapeDtypeStruct((rows // 128, 128 * 7), np.float32),
             jax.ShapeDtypeStruct((rows % 128, 7), np.float32))
    texts.update(_both(
        "slab-group", tsrc, stream._Group(("mean", _label, _terms, 4), tsrc),
        (rows, 7), dense, thin=True))
    psrc = bolt.fromcallback(lambda idx: None, (8, 256, 16), one,
                             dtype=np.float32, chunks=2).map(
                                 plus_one)._stream
    assert stream.dense_route(psrc)
    dense = (jax.ShapeDtypeStruct((2, 2, 128 * 16), np.float32),)
    texts.update(_both(
        "slab-gram", psrc, stream._Gram((2, "highest", False, True)),
        (2, 256, 16), dense, thin=True))
    assert {k: hashlib.sha256(v.encode()).hexdigest()
            for k, v in texts.items()} == PARENT_TEXT


def test_the_upload_is_counted_once_and_spanned(one):
    x = _table(1000, 7, np.float32)
    obs.enable()
    obs.clear()
    try:
        c0 = engine.counters()
        _source(x, one, 300).sum().toarray()
        c1 = engine.counters()
        spans = obs.totals()
    finally:
        obs.disable()
        obs.clear()
    assert spans["stream.transfer"]["count"] == 4
    assert spans["stream.transfer"]["bytes"] == x.nbytes
    assert c1["stream_upload_parts"] - c0["stream_upload_parts"] == 4
