"""A mapped streamed result collected slab by slab (``stream.collect``),
keyed stages (``with_keys`` on a streamed source) and side operands
(``utils.with_operands``), each against the materialised path to the bit;
what ``analysis.check`` says of them; and that a source with none of it
lowers to the program text it had before."""

import hashlib

import numpy as np
import pytest

import jax

import bolt_tpu as bolt
from bolt_tpu import analysis, engine, obs, stream
from bolt_tpu.parallel import shuffle
from bolt_tpu.tpu import array as tpu_array
from bolt_tpu.utils import prod, with_operands


def rows_since(t0):
    """The compile log's rows stamped at or after ``t0`` (``obs.clock``
    seconds).  The log keeps its newest 512 rows, so in a worker that has
    compiled more its length is no mark."""
    return [r for r in engine.compile_log() if r["t0"] >= t0]


@pytest.fixture(scope="module")
def mesh():
    return jax.sharding.Mesh(np.array(jax.devices()), ("k",))


@pytest.fixture(scope="module")
def mesh4():
    return jax.sharding.Mesh(np.array(jax.devices()[:4]), ("k",))


def data(records=50, vshape=(6, 5), seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(-500, 500, size=(records,) + vshape).astype(
        np.float32)


def callback(x, mesh, chunks, **kw):
    return bolt.fromcallback(lambda i: x[tuple(i)], x.shape, mesh,
                             dtype=x.dtype, chunks=chunks, **kw)


def blocks(x, mesh, sizes):
    cuts = np.cumsum((0,) + tuple(sizes))
    parts = [x[a:b] for a, b in zip(cuts[:-1], cuts[1:])]
    return bolt.fromiter(parts, x.shape, mesh, dtype=x.dtype)


def oracle(src):
    """What ``materialize`` builds: the base uploaded whole, every stage
    replayed on the resident copy."""
    return np.asarray(stream._replay_stages(stream._materialize_base(src),
                                            src.stages).toarray())


def row_stat(v):
    return v.sum(axis=1) * 0.5


def scale_keys(kv):
    (k,), v = kv
    return v * (k % 7 + 1)


CHAINS = {
    "map": lambda b: b.map(row_stat),
    "map-map": lambda b: b.map(row_stat).map(lambda v: v[:3] - 1),
    "chunk": lambda b: b.chunk((3, 5)).map(lambda blk: blk * 2.0).unchunk(),
    "keyed": lambda b: b.map(scale_keys, with_keys=True),
    "keyed-map": lambda b: b.map(scale_keys, with_keys=True).map(row_stat),
}


@pytest.mark.parametrize("chunks", [7, 16, 50, 64])
@pytest.mark.parametrize("chain", sorted(CHAINS))
def test_collect_is_the_materialised_result_to_the_bit(mesh, chain, chunks):
    """Slab counts that do not divide the records among them (50 records
    in slabs of 7 and of 16)."""
    x = data()
    arr = CHAINS[chain](callback(x, mesh, chunks))
    src = arr._stream
    assert stream.collect_refusal(src) is None
    c0 = engine.counters()
    got = np.asarray(arr.toarray())
    c1 = engine.counters()
    assert got.dtype == oracle(src).dtype
    assert np.array_equal(got, oracle(src))
    nslabs = -(-50 // min(chunks, 50))
    assert c1["stream_collect_slabs"] - c0["stream_collect_slabs"] == nslabs
    assert c1["stream_collect_bytes"] - c0["stream_collect_bytes"] \
        == got.nbytes
    assert c1["stream_chunks"] - c0["stream_chunks"] == nslabs
    keyed = nslabs if chain.startswith("keyed") else 0
    assert c1["stream_keyed_slabs"] - c0["stream_keyed_slabs"] == keyed
    assert c1["shuffle_bytes"] == c0["shuffle_bytes"]


@pytest.mark.parametrize("sizes", [(50,), (20, 30), (7, 7, 7, 7, 7, 7, 8)])
@pytest.mark.parametrize("chain", ["map", "keyed"])
def test_collect_over_an_iterators_own_blocks(mesh, chain, sizes):
    x = data(seed=1)
    got = np.asarray(CHAINS[chain](blocks(x, mesh, sizes)).toarray())
    want = oracle(CHAINS[chain](blocks(x, mesh, sizes))._stream)
    assert np.array_equal(got, want)


# the resolver's window of unconfirmed place calls (ISSUE 56): a NEW lazy
# mapped source each call (an iterator is one-shot)
def _thin_rows(mesh):
    one = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("k",))
    x = data(1024, (6,), seed=3)
    return callback(x, one, 128).map(lambda r: r * 2.0 + 1.0)


WINDOW_SOURCES = {
    "callback-map": lambda mesh: CHAINS["map"](callback(data(), mesh, 7)),
    "callback-keyed":
        lambda mesh: CHAINS["keyed-map"](callback(data(), mesh, 7)),
    "iterator-map": lambda mesh: CHAINS["map"](
        blocks(data(), mesh, (7, 7, 7, 7, 7, 7, 8))),
    "iterator-keyed": lambda mesh: CHAINS["keyed"](
        blocks(data(), mesh, (20, 30))),
    "thin-records": _thin_rows,
}


@pytest.mark.parametrize("open_window", [False, True],
                         ids=["done-at-once", "never-done"])
@pytest.mark.parametrize("depth", [1, 2, 4])
@pytest.mark.parametrize("case", sorted(WINDOW_SOURCES))
def test_windowed_collect_is_the_materialised_map_to_the_bit(
        mesh, case, depth, open_window, monkeypatch):
    if open_window:
        # a CPU's programs are done as soon as dispatched: no handle
        # reads done until the resolver blocks for it
        monkeypatch.setattr(stream, "_retired", lambda handle: False)
    want = oracle(WINDOW_SOURCES[case](mesh)._stream)
    seen = []
    record = engine.record_stream
    monkeypatch.setattr(engine, "record_stream", lambda *a, **kw: (
        seen.append(kw), record(*a, **kw))[1])
    with stream.prefetch(depth):
        arr = WINDOW_SOURCES[case](mesh)
        src = arr._stream
        # the window is what the ring holds beyond a slab a worker's
        # hand, and a collect's ring never passes its slab count
        window = max(1, stream.collect_plan(src).ring
                     - stream.pool_size(src))
        c0 = engine.counters()
        got = np.asarray(arr.toarray())
    c1 = engine.counters()
    assert got.dtype == want.dtype and np.array_equal(got, want)
    slabs = c1["stream_collect_slabs"] - c0["stream_collect_slabs"]
    mine, = seen
    assert c1["stream_windowed_slabs"] - c0["stream_windowed_slabs"] \
        == mine["windowed"]
    if open_window or window == 1:
        # every call but the first goes out behind an unconfirmed one,
        # or (a window of one) none does
        assert mine["windowed"] == (slabs - 1 if window > 1 else 0)
        assert mine["inflight"] == min(window, slabs)
    else:
        # how many were done by the time the resolver asked is the
        # device's to say
        assert 0 <= mine["windowed"] <= slabs - 1
        assert 1 <= mine["inflight"] <= min(window, slabs)


def test_collect_records_its_spans_and_no_materialize_span(mesh):
    x = data()
    obs.clear()
    obs.enable()
    try:
        callback(x, mesh, 16).map(row_stat).cache()
        totals = obs.totals()
    finally:
        obs.disable()
        obs.clear()
    assert totals["stream.collect"]["count"] == 1
    assert totals["stream.collect.place"]["count"] == 4
    assert totals["stream.ingest"]["count"] == 4
    assert "stream.materialize" not in totals
    assert "stream.shuffle" not in totals


def test_what_collect_cannot_take_is_refused_in_words_and_materialises(mesh):
    x = data()
    kept = callback(x, mesh, 16).map(row_stat).filter(lambda v: v[0] > 0)
    with pytest.raises(ValueError, match="row count is dynamic"):
        stream.collect(kept._stream)
    c0 = engine.counters()
    want = np.asarray([r for r in x.sum(axis=2) * 0.5 if r[0] > 0])
    assert np.array_equal(np.asarray(kept.toarray()), want)
    assert engine.counters()["stream_collect_slabs"] \
        == c0["stream_collect_slabs"]
    with pytest.raises(ValueError, match="no stage"):
        stream.collect(callback(x, mesh, 16)._stream)
    with pytest.raises(ValueError, match="unresolved swap"):
        stream.collect(callback(x, mesh, 16).swap((0,), (0,))._stream)
    lossy = callback(x, mesh, 16, codec="int8").map(row_stat)
    with pytest.raises(ValueError, match="lossy"):
        stream.collect(lossy._stream)
    # materialised, and therefore not quantised
    assert np.array_equal(np.asarray(lossy.toarray()), x.sum(axis=2) * 0.5)


def test_a_result_past_the_budget_has_no_sink(mesh, monkeypatch):
    """Past ``swap_budget`` the collect refuses in words and the source
    materialises as it always did; what the DEVICE cannot hold whole is
    refused in bolt's words (BLT020) before XLA is asked."""
    x = data()
    arr = callback(x, mesh, 16).map(lambda v: v * 2)
    with stream.spill(budget=1000):
        why = stream.collect_refusal(arr._stream)
        assert "exceed the resident budget" in why
        with pytest.raises(ValueError, match="exceed the resident budget"):
            stream.collect(arr._stream)
        rep = analysis.check(arr)
        note, = [d for d in rep.diagnostics if d.code == "BLT020"]
        assert note.severity == "info" and "materialises" in note.message
        c0 = engine.counters()
        assert np.array_equal(np.asarray(arr.toarray()), x * 2)
        assert engine.counters()["stream_collect_slabs"] \
            == c0["stream_collect_slabs"]
        arr = callback(x, mesh, 16).map(lambda v: v * 2)
        monkeypatch.setattr(tpu_array, "_HBM_LIMIT_OVERRIDE", 1000)
        note, = [d for d in analysis.check(arr).diagnostics
                 if d.code == "BLT020"]
        assert note.severity == "warning" and "will refuse" in note.message
        with pytest.raises(MemoryError, match="BLT020"):
            arr.toarray()
    # with room for the RESULT the same source is collected, however
    # little room there is for the base
    small = callback(x, mesh, 4).map(lambda v: v.sum())
    assert prod(x.shape) * 4 > 4000
    with stream.spill(budget=4000):
        assert stream.collect_refusal(small._stream) is None
    assert np.array_equal(np.asarray(small.toarray()), x.sum(axis=(1, 2)))


@pytest.mark.parametrize("which", ["mesh", "mesh4"])
def test_a_keyed_stage_is_one_program_for_all_slabs(request, which):
    """Streamed ``with_keys`` against the resident ``with_keys`` map to
    the bit; the slab's first key is an operand, so the uniform slabs of a
    pass compile ONE program (the compile log gains one row a family)."""
    m = request.getfixturevalue(which)
    x = data(records=64, seed=3)

    def body(kv):                 # a new function: nothing compiled yet
        (k,), v = kv
        return v + k * 3

    want = np.asarray(bolt.array(x, m).map(body, with_keys=True).toarray())
    assert np.array_equal(want, x + 3 * np.arange(64)[:, None, None])
    t0 = obs.clock()
    got = callback(x, m, 8).map(body, with_keys=True).toarray()
    assert np.array_equal(np.asarray(got), want)
    rows = rows_since(t0)
    assert [r["family"] for r in rows].count("stream-shuffle-place") == 1
    # the same through a statistic's slab programs (plain and fused) and
    # in front of a swap
    t0 = obs.clock()
    total = callback(x, m, 8).map(body, with_keys=True).sum().toarray()
    assert np.array_equal(np.asarray(total), want.sum(axis=0))
    fams = [r["family"] for r in rows_since(t0)]
    assert fams.count("stream-slab") == 1 and fams.count(
        "stream-slab-acc") == 1
    t0 = obs.clock()
    swapped = callback(x, m, 8).map(body, with_keys=True).swap((0,), (0,))
    assert np.array_equal(np.asarray(swapped.toarray()),
                          np.transpose(want, (1, 0, 2)))
    fams = [r["family"] for r in rows_since(t0)]
    assert fams.count("stream-shuffle-place") == 1


def test_keys_of_a_source_with_two_key_axes(mesh):
    x = data(records=24, vshape=(4, 5), seed=4)

    def body(kv):
        (i, j), v = kv
        return v * 0 + i * 10 + j
    src = bolt.fromcallback(lambda i: x[tuple(i)], x.shape, mesh,
                            axis=(0, 1), dtype=np.float32, chunks=5)
    got = np.asarray(src.map(body, axis=(0, 1), with_keys=True).toarray())
    want = (np.arange(24)[:, None] * 10 + np.arange(4)[None, :])[..., None] \
        * np.ones(5, np.float32)
    assert np.array_equal(got, want)


def add_to(v, a, b):
    return v * a + b


def test_side_operands_are_operands_of_every_lowering(mesh):
    """Streamed collect, a streamed statistic, the resident chain and a
    resident statistic: fresh arrays of the same shape run the programs
    the first arrays compiled, and read their own values."""
    x = data(seed=5)

    def run(a, b):
        f = with_operands(add_to, a, b)
        streamed = callback(x, mesh, 16).map(f).toarray()
        total = callback(x, mesh, 16).map(f).sum().toarray()
        resident = bolt.array(x, mesh).map(f)
        rsum = bolt.array(x, mesh).map(f).map(lambda v: v + 1).sum()
        want = x * a + b
        assert np.array_equal(np.asarray(streamed), want)
        assert np.array_equal(np.asarray(resident.toarray()), want)
        assert np.allclose(np.asarray(total), want.sum(axis=0))
        assert np.allclose(np.asarray(rsum.toarray()), (want + 1).sum(0))
        assert np.array_equal(
            np.asarray(bolt.array(x).map(f).toarray()), want)
    rng = np.random.default_rng(0)
    run(rng.integers(1, 4, size=(6, 5)).astype(np.float32),
        np.ones((5,), np.float32))
    t0, c0 = obs.clock(), engine.counters()
    run(rng.integers(1, 4, size=(6, 5)).astype(np.float32),
        np.full((5,), 7, np.float32))
    c1 = engine.counters()
    # the resident statistic fuses the chain with the operands bound (an
    # ordinary closure to it): that one program is new; no other is
    new = [r["family"] for r in rows_since(t0)]
    assert new == ["stat"]
    assert c1["aot_compiles"] - c0["aot_compiles"] == 1
    # another SHAPE is another program
    t0 = obs.clock()
    f = with_operands(add_to, np.float32(2) * np.ones((1, 5), np.float32),
                      np.ones((5,), np.float32))
    assert np.array_equal(
        np.asarray(callback(x, mesh, 16).map(f).toarray()), x * 2 + 1)
    assert "stream-shuffle-place" in [r["family"]
                                      for r in rows_since(t0)]


def test_a_keyed_stage_in_front_of_a_spilled_swap_is_refused(mesh,
                                                             tmp_path):
    x = data()
    arr = callback(x, mesh, 16).map(scale_keys, with_keys=True).swap(
        (0,), (0,))
    with stream.spill(dir=str(tmp_path), budget=1):
        note, = [d for d in analysis.check(arr).diagnostics
                 if d.code == "BLT017"]
        assert note.severity == "warning" and "with_keys" in note.message
        with pytest.raises(RuntimeError, match="with_keys map in front"):
            arr.toarray()
    assert np.array_equal(np.asarray(arr.toarray()), np.transpose(
        x * (np.arange(50) % 7 + 1)[:, None, None], (1, 0, 2)))


def test_the_forecast_says_collected_slab_by_slab(mesh):
    x = data()
    arr = callback(x, mesh, 16).map(scale_keys, with_keys=True).map(row_stat)
    c0 = engine.counters()
    rep = analysis.check(arr)
    text = str(rep)
    assert rep.ok and rep.shape == (50, 6)
    assert "map(scale_keys, with_keys) [streamed]" in text
    note, = [d for d in rep.diagnostics if d.code == "BLT020"]
    assert note.severity == "info" and note.stage == 2
    assert "collected slab by slab: 4 slabs" in note.message
    c1 = engine.counters()
    assert c1["aot_compiles"] == c0["aot_compiles"]
    assert c1["transfer_bytes"] == c0["transfer_bytes"]
    # a source that is not mapped, or whose swap re-axes it, gets no note
    assert not [d for d in analysis.check(callback(x, mesh, 16)).diagnostics
                if d.code == "BLT020"]
    swapped = callback(x, mesh, 16).map(row_stat).swap((0,), (0,))
    assert not [d for d in analysis.check(swapped).diagnostics
                if d.code == "BLT020"]


# what the three programs of a plain source read as at the parent commit
# (dd7f308, jax 0.9.0): sha256 of ``lower(...).as_text()``
PARENT_TEXT = {
    "slab-sum":
        "176c1d30fab2577afdbc9daaa426109418b9fa6d3f9a0a6b5f252205fff1b8e0",
    "slab-sum-fused":
        "9a55e4616e9d80d5ffe970510f522edec61948da5e20e6b0aef1d487fd84c33d",
    "place":
        "bb196d1d6937276501d69cbef96bfea086cc58b3a6bc9632bc46481dac8ae006",
    # the three tupled terminals over the same source on four devices,
    # read at 06c96b3 before ISSUE 57 touched the file
    "slab-multi":
        "8b292d12dc2406203a230de3ef43f2d2570cb55eea32a50255ca20bc27e9fc54",
    "slab-multi-fused":
        "78e5cf4c2074214d8ba55f73f14f4d2736bafc9bf05c76449a4b9039beea0260",
    "slab-group":
        "877d745a636eee5a491ccd68fa1c7adb93edadd5bc15878a973826ce017b46a5",
    "slab-group-fused":
        "c0aa843fe9a894294a9c64404833459a7dc722409b46960ddd45bba50eafef0f",
    "slab-gram":
        "743326962e033467beae7e24253a0424b6728975ddb8ccbdc1906cdf0c18d9b2",
    "slab-gram-fused":
        "7dbd86b2771c752d0b526464c31cf60f633cefe3bc3a099fea2c39d4f713176a",
}


def plus_one(v):
    return v + 1


def _glabel(r):
    return (r[0, 0] > 1).astype(np.int32) + (r[1, 1] > 1).astype(np.int32)


@pytest.mark.skipif(jax.__version__ != "0.9.0",
                    reason="the recorded text is jax 0.9.0's")
def test_a_source_with_no_keyed_stage_lowers_as_it_did(mesh4):
    """The guard of the cells that share this code: no key, no operands,
    no collect, and the slab, fused-slab and place programs are the
    parent's to the letter (and take the arguments they took)."""
    x = np.zeros((48, 4, 6), np.float32)
    src = callback(x, mesh4, 8).map(plus_one)._stream
    slab = jax.ShapeDtypeStruct((8, 4, 6), np.float32)
    acc = jax.ShapeDtypeStruct((4, 6), np.float32)
    texts = {
        "slab-sum": stream._slab_program(
            src, stream._Sum(), (8, 4, 6)).lower(slab).as_text(),
        "slab-sum-fused": stream._slab_program(
            src, stream._Sum(), (8, 4, 6), fused=True).lower(
                slab, acc).as_text(),
    }
    for name, terminal in (
            ("slab-multi", stream._Multi((("sum", None), ("std", 1),
                                          ("min", None), ("max", None)))),
            ("slab-group", stream._Group(("sum", _glabel, None, 3), src)),
            ("slab-gram", stream._Gram((1, "highest", False, True)))):
        first = stream._slab_program(src, terminal, (8, 4, 6)).lower(slab)
        part = jax.tree_util.tree_map(
            lambda o: jax.ShapeDtypeStruct(o.shape, o.dtype), first.out_info)
        texts[name] = first.as_text()
        texts[name + "-fused"] = stream._slab_program(
            src, terminal, (8, 4, 6), fused=True).lower(slab, part).as_text()
    sw = callback(x, mesh4, 8).map(plus_one).swap((0,), (0,))._stream
    _, perm, new_split = sw.stages[1]
    plan = shuffle.plan_shuffle((48, 4, 6), np.float32, 1, perm, new_split,
                                mesh4, 8, None, None, ring=4)
    prog = shuffle.place_program(plan, sw.stages[:1], mesh4, None,
                                 np.dtype(np.float32), (8, 4, 6), True, 8)
    texts["place"] = prog.lower(
        jax.ShapeDtypeStruct(plan.out_shape, np.float32), slab,
        jax.ShapeDtypeStruct((), np.uint32)).as_text()
    got = {k: hashlib.sha256(v.encode()).hexdigest()
           for k, v in texts.items()}
    assert got == PARENT_TEXT
    assert stream.stage_extras(src.stages) == (False, ())
    assert stream.stage_keys(src.stages) == src.stages
