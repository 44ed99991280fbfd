"""The streamed swap on a ONE-PROCESS, FOUR-DEVICE mesh (ISSUE 43): what a
four-chip host does with ``fromcallback(...).swap(...)``.

Four of the eight fake CPU devices as one 1-d mesh, ``uploaders`` left
alone so the pool is ``pool_size``'s 4.  A slab goes up as four sub-blocks
(its frames a quarter a device), the place program exchanges three
quarters of it across devices (GSPMD's all-to-all: the plan is not
``sharded``, which means processes) and writes the block into the swapped
array, sharded over its new leading axis.  ``numpy.transpose`` of the
seeded source is the semantics, held bit for bit; the planner's model of
what crosses devices is held to the counter, and the forecast to the
run's plan.
"""

import threading
import time

import jax
import numpy as np
import pytest

import bolt_tpu as bolt
from bolt_tpu import analysis, engine, obs, stream
from bolt_tpu.parallel import shuffle

V0, V1 = 8, 6                 # V0 divides by four: the output's row blocks


@pytest.fixture(scope="module")
def mesh4():
    return jax.sharding.Mesh(np.array(jax.devices()[:4]), ("k",))


def _data(n, seed=7):
    """Seeded integers of 12 significant bits as float32: every element
    its own value, so a block at another block's place shows."""
    rng = np.random.default_rng(seed)
    return rng.integers(-2048, 2048, size=(n, V0, V1)).astype(np.float32)


def _source(data, mesh, chunks):
    return bolt.fromcallback(lambda idx: data[idx], data.shape, mesh,
                             dtype=data.dtype, chunks=chunks)


def _perm(kaxes, vaxes, ndim=3, split=1):
    """``swap``'s permutation as ``_do_swap`` builds it."""
    keys = [k for k in range(split) if k not in kaxes]
    vals = [v for v in range(ndim - split) if v not in vaxes]
    return tuple(keys + [split + v for v in vaxes] + list(kaxes)
                 + [split + v for v in vals])


def _traced(fn):
    """``fn()`` under the obs tracer: ``(result, spans)``."""
    obs.clear()
    obs.enable()
    try:
        out = fn()
        spans = list(obs.spans())
        assert obs.active_count() == 0
    finally:
        obs.disable()
        obs.clear()
    return out, spans


# frames, chunks, and how many sub-blocks each slab is put as: a slab
# whose frames four devices divide goes up a quarter a device, one they do
# not goes up whole to each (replicated: nothing to exchange, still four)
_GEOMETRY = [
    ("slabs-divide-by-four", 24, 8, [8, 8, 8]),
    ("short-last-slab-does-not", 26, 8, [8, 8, 8, 2]),
    ("frames-no-multiple-of-the-slab", 30, 12, [12, 12, 6]),
    ("one-frame-a-device", 16, 4, [4, 4, 4, 4]),
]
_SWAPS = [
    ("records-land-minor", (0,), (0, 1)),     # (t, x, y) -> (x, y, t)
    ("records-land-in-the-middle", (0,), (0,)),   # -> (x, t, y)
    ("records-land-second", (0,), (1,)),      # -> (y, t, x)
]


@pytest.mark.parametrize("swap", _SWAPS, ids=[s[0] for s in _SWAPS])
@pytest.mark.parametrize("geometry", _GEOMETRY, ids=[g[0] for g in _GEOMETRY])
def test_streamed_swap_on_four_devices_is_numpys_transpose(mesh4, geometry,
                                                           swap):
    _, n, chunks, slabs = geometry
    _, kaxes, vaxes = swap
    data = _data(n)
    perm = _perm(kaxes, vaxes)
    c0 = engine.counters()
    out, spans = _traced(
        lambda: _source(data, mesh4, chunks).swap(kaxes, vaxes)._data)
    c1 = engine.counters()
    want = np.transpose(data, perm)
    assert out.dtype == want.dtype and np.array_equal(np.asarray(out), want)
    # resident over all four, sharded over its new leading axis where four
    # divide it
    assert len(out.sharding.device_set) == 4
    if want.shape[0] % 4 == 0:
        assert {s.data.shape[0] for s in out.addressable_shards} \
            == {want.shape[0] // 4}
    run, = [sp for sp in spans if sp.name == "stream.shuffle"]
    ups = [sp for sp in spans if sp.name == "stream.transfer"]
    assert run.attrs["resident"] and run.attrs["slabs"] == len(slabs)
    assert run.attrs["devices"] == 4
    assert [sp.attrs["parts"] for sp in ups] == [4] * len(slabs)
    assert run.attrs["upload_parts"] == 4 * len(slabs)
    assert c1["stream_upload_parts"] - c0["stream_upload_parts"] \
        == 4 * len(slabs)
    assert c1["stream_chunks"] - c0["stream_chunks"] == len(slabs)
    assert c1["shuffle_bytes"] - c0["shuffle_bytes"] == data.nbytes
    # the planner's model of what crosses devices, held to the counter:
    # three quarters of everything, the record axis leaving the front
    crossed = c1["stream_alltoall_bytes"] - c0["stream_alltoall_bytes"]
    assert crossed == run.attrs["alltoall_bytes"] \
        == run.attrs["crossed_bytes"] == data.nbytes * 3 // 4
    assert c1["spill_bytes"] == c0["spill_bytes"]


# the caller who gives no ``chunks`` (ISSUE 60): the default slab of 64
# frames is drawn a lane tile a DEVICE, 512 frames over the four; frames
# and the sub-blocks each slab is put as (a short last slab that four do
# not divide goes up whole to each, as any such slab does)
_DEFAULT_SLAB = [
    ("the-slab-divides-the-recording", 1024, [512, 512]),
    ("a-short-last-slab", 1100, [512, 512, 76]),
    ("a-short-last-slab-four-do-not-divide", 1101, [512, 512, 77]),
    ("a-recording-of-one-short-slab", 300, [300]),
]


@pytest.fixture
def default_64_frames(monkeypatch):
    monkeypatch.setattr(stream, "_SLAB_BYTES", 64 * V0 * V1 * 4)


@pytest.mark.parametrize("geometry", _DEFAULT_SLAB,
                         ids=[g[0] for g in _DEFAULT_SLAB])
def test_default_slab_on_four_devices_is_numpys_transpose(
        mesh4, default_64_frames, geometry):
    _, n, slabs = geometry
    data = _data(n)
    src = _source(data, mesh4, None)
    assert src._stream.slab == 64
    arr = src.swap((0,), (0, 1))
    assert arr._stream.slab == slabs[0]
    c0 = engine.counters()
    out, spans = _traced(lambda: arr._data)
    c1 = engine.counters()
    want = np.transpose(data, (1, 2, 0))
    assert out.dtype == want.dtype and np.array_equal(np.asarray(out), want)
    assert {s.data.shape[0] for s in out.addressable_shards} == {V0 // 4}
    run, = [sp for sp in spans if sp.name == "stream.shuffle"]
    ups = [sp for sp in spans if sp.name == "stream.transfer"]
    assert run.attrs["resident"] and run.attrs["slabs"] == len(slabs)
    assert run.attrs["devices"] == 4
    assert sorted(sp.attrs["bytes"] for sp in ups) \
        == sorted(k * V0 * V1 * 4 for k in slabs)
    assert [sp.attrs["parts"] for sp in ups] == [4] * len(slabs)
    assert c1["stream_chunks"] - c0["stream_chunks"] == len(slabs)
    assert c1["shuffle_bytes"] - c0["shuffle_bytes"] == data.nbytes
    crossed = c1["stream_alltoall_bytes"] - c0["stream_alltoall_bytes"]
    assert crossed == run.attrs["alltoall_bytes"] == data.nbytes * 3 // 4
    assert c1["spill_bytes"] == c0["spill_bytes"]
    # the uniform slab and the short one are a place program each, and a
    # second pass compiles nothing
    np.asarray(_source(data, mesh4, None).swap((0,), (0, 1))._data)
    assert engine.counters()["aot_compiles"] == c1["aot_compiles"]


def test_nothing_crosses_where_the_record_axis_stays_in_front(mesh4):
    """A swap among the value axes alone: every record keeps its device,
    and the model says so."""
    data = _data(24)
    b = bolt.fromcallback(lambda idx: data[idx], data.shape, mesh4,
                          dtype=data.dtype, chunks=8)
    c0 = engine.counters()
    out, spans = _traced(lambda: b.map(lambda v: v.T)._data)
    assert np.array_equal(np.asarray(out), np.transpose(data, (0, 2, 1)))
    assert engine.counters()["stream_alltoall_bytes"] \
        == c0["stream_alltoall_bytes"]
    plan = shuffle.plan_shuffle(data.shape, data.dtype, 1, (0, 2, 1), 1,
                                mesh4, 8, None, None)
    assert plan.alltoall_bytes == 0 and plan.devices == 4
    assert "across 4 devices of one process" in plan.describe()


def test_one_device_plans_and_says_what_it_did(mesh4):
    one = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("k",))
    plan = shuffle.plan_shuffle((24, V0, V1), np.float32, 1, (1, 2, 0), 2,
                                one, 8, None, None)
    assert plan.devices == 1 and plan.alltoall_bytes == 0
    assert not plan.sharded and "across" not in plan.describe()
    four = shuffle.plan_shuffle((24, V0, V1), np.float32, 1, (1, 2, 0), 2,
                                mesh4, 8, None, None)
    assert four.devices == 4 and not four.sharded
    assert four.alltoall_bytes == 24 * V0 * V1 * 4 * 3 // 4


def test_a_map_stage_in_front_rides_in_the_slabs_program(mesh4):
    data = _data(26)

    def scaled(v):
        return v * 2.0 + 1.0
    c0 = engine.counters()
    s = _source(data, mesh4, 8).map(scaled).swap((0,), (0, 1))
    assert s._stream is not None
    got = np.asarray(s._data)
    c1 = engine.counters()
    assert np.array_equal(got, np.transpose(data * 2.0 + 1.0, (1, 2, 0)))
    assert c1["stream_chunks"] - c0["stream_chunks"] == 4
    # a second pass compiles nothing
    np.asarray(_source(data, mesh4, 8).map(scaled).swap((0,), (0, 1))._data)
    c2 = engine.counters()
    assert c2["aot_compiles"] == c1["aot_compiles"]
    assert c2["misses"] == c1["misses"]


def test_four_workers_finishing_out_of_order_are_re_sequenced(mesh4,
                                                              monkeypatch):
    """The pool is ``pool_size``'s four; the first slabs' uploads are held
    back until later ones have landed, and the place programs still run in
    slab order (the cursor counts slabs)."""
    data = _data(64)
    assert stream.upload_threads() == 0            # nobody set a pool
    sound = stream._upload_slab
    done, lock = [], threading.Lock()

    def held_back(block, mesh, split):
        g = int(np.flatnonzero((data[:, 0, 0] == block[0, 0, 0])
                               & (data[:, 0, 1] == block[0, 0, 1]))[0]) // 4
        if g < 2:
            t0 = time.time()
            while len(done) < 3 and time.time() - t0 < 20:
                time.sleep(0.002)
        out = sound(block, mesh, split)
        with lock:
            done.append(g)
        return out
    monkeypatch.setattr(stream, "_upload_slab", held_back)
    src = _source(data, mesh4, 4)
    assert stream.pool_size(src._stream) == 4
    engine.reset_counters()
    got = np.asarray(src.swap((0,), (0, 1))._data)
    assert np.array_equal(got, np.transpose(data, (1, 2, 0)))
    assert len(done) == 16 and done[:3] != [0, 1, 2]   # 0 and 1 came late
    assert sorted(done) == list(range(16))
    # the resolver reports what its pool observed, as execute does
    assert 2 <= engine.counters()["stream_upload_threads"] <= 4


@pytest.mark.parametrize("n,chunks,slab,nslabs", [
    (26, 8, 8, 4), (1100, None, 512, 3), (1024, None, 512, 2)],
    ids=["the-callers-chunks", "the-default-slab-a-tile-a-device",
         "the-default-slab-divides"])
def test_the_forecast_is_the_runs_plan(mesh4, default_64_frames, n, chunks,
                                       slab, nslabs):
    """BLT017 says, before anything runs, what the run then does: the
    same plan (resident, how much over how many devices, how much across
    them), word for word; at the default slab too, which is drawn when the
    swap is recorded and carried by the source the forecast reads."""
    data = _data(n)
    arr = _source(data, mesh4, chunks).swap((0,), (0, 1))
    rep = analysis.check(arr)
    d, = [x for x in rep.diagnostics if x.code == "BLT017"]
    assert d.severity == "info"
    src = arr._stream
    assert src.slab == slab
    plan = shuffle.plan_shuffle(
        data.shape, data.dtype, 1, (1, 2, 0), 2, mesh4, src.slab,
        stream.swap_budget(mesh4), None, ring=stream.swap_ring(src))
    assert d.message == plan.describe()
    assert "-> resident" in d.message
    assert "across 4 devices of one process" in d.message
    assert "all-to-all ~%.1f MiB" % (data.nbytes * 0.75 / 2**20) \
        in d.message
    assert "one all-to-all per slab across its 4 devices" in d.hint
    assert plan.ring == stream.prefetch_depth() \
        + stream._SWAP_WINDOW_STEP + 4
    # the ring and the place program's temp are slabs of the slab drawn
    assert plan.slab_bytes == slab * V0 * V1 * 4
    assert plan.resident_bytes == data.nbytes \
        + (plan.ring + 1) * plan.slab_bytes
    c0 = engine.counters()
    out, spans = _traced(lambda: arr._data)
    c1 = engine.counters()
    run, = [sp for sp in spans if sp.name == "stream.shuffle"]
    assert run.attrs["alltoall_bytes"] == plan.alltoall_bytes
    assert run.attrs["ring"] == plan.ring
    assert run.attrs["slabs"] == plan.nslabs == nslabs
    assert run.attrs["out_block"] == plan.out_block
    assert c1["stream_alltoall_bytes"] - c0["stream_alltoall_bytes"] \
        == plan.alltoall_bytes
    assert np.array_equal(np.asarray(out), np.transpose(data, (1, 2, 0)))
