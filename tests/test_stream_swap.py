"""The streamed two-phase shuffle (ISSUE 18): parity and contracts.

Parity is the load-bearing half: a ``swap`` recorded on a STREAMED
source resolves through the two-phase shuffle — phase 1 re-buckets each
uploaded slab on device, phase 2 writes each block INTO the one resident
output (ISSUE 32) or re-streams spilled ones — and must equal the
materialise-first in-memory swap BIT for bit (a transpose moves bytes,
it never rounds).  Geometry
edges ride along: uneven last slabs, 1-record slabs, multi-value-axis
permutations, the key↔value round trip, and the budget≈one-bucket
forced-spill path.

Operational contracts: the swap stays LAZY until a consumer arrives,
terminals (sum / map / chunk().map()) consume the swapped stream without
full materialisation, a second identical pass compiles NOTHING new, the
BLT017 forecast agrees with the measured resident/spill decision, chaos
raises are absorbed in place by the ``stream.retries`` fence, and the
dict codec + spill-file layer keep their format contracts.
"""

import contextlib
import glob
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

import bolt_tpu as bolt
from bolt_tpu import (_chaos, analysis, checkpoint, engine, obs, serve,
                      stream)
from bolt_tpu.tpu import codec as codec_mod

N, V0, V1 = 24, 6, 5
SHAPE = (N, V0, V1)


def _data(dtype=np.float32):
    n = int(np.prod(SHAPE))
    if np.issubdtype(np.dtype(dtype), np.integer):
        return ((np.arange(n) % 11) - 5).astype(dtype).reshape(SHAPE)
    return (np.arange(n, dtype=np.float64) * 0.37 - 100.0).astype(
        dtype).reshape(SHAPE)


def _source(data, mesh, chunks, codec=None):
    return bolt.fromcallback(lambda idx: data[idx], data.shape, mesh,
                             dtype=data.dtype, chunks=chunks,
                             codec=codec)


def _mat_swap(data, mesh, kaxes, vaxes):
    """The materialise-first oracle: concrete array, in-memory swap."""
    m = bolt.array(data, mesh)
    return np.asarray(m.swap(kaxes, vaxes)._data)


# ---------------------------------------------------------------------
# streamed vs materialised parity
# ---------------------------------------------------------------------

@pytest.mark.parametrize("chunks", [4, 5, 1])   # even, uneven tail, 1-record
@pytest.mark.parametrize("kaxes,vaxes", [
    ((0,), (0,)),          # the canonical key<->value exchange
    ((0,), (1,)),          # trailing value axis to the keys
    ((0,), (0, 1)),        # one key for BOTH value axes (new_split=2)
])
def test_streamed_swap_parity_bitexact(mesh, chunks, kaxes, vaxes):
    data = _data()
    s = _source(data, mesh, chunks).swap(kaxes, vaxes)
    assert s._stream is not None          # still lazy after the record
    got = np.asarray(s._data)
    assert np.array_equal(got, _mat_swap(data, mesh, kaxes, vaxes))


def test_swap_roundtrip_restores_source_bits(mesh):
    data = _data()
    rt = _source(data, mesh, 4).swap((0,), (0,)).swap((0,), (0,))
    assert np.array_equal(np.asarray(rt._data), data)


def test_swap_stays_lazy_until_consumed(mesh):
    calls = []

    def loader(idx):
        calls.append(idx)
        return _data()[idx]

    s = bolt.fromcallback(loader, SHAPE, mesh, dtype=np.float32,
                          chunks=4).swap((0,), (0,))
    assert calls == []                    # recording is free
    np.asarray(s._data)
    assert calls                          # resolution streamed the source


def test_swap_sum_terminal_consumes_stream(mesh):
    data = _data(np.float64)              # integer-free exactness n/a:
    data = np.round(data)                 # integer-valued f64 sums exact
    got = np.asarray(_source(data, mesh, 4).swap((0,), (0,)).sum())
    assert np.array_equal(got, np.transpose(data, (1, 0, 2)).sum(axis=0))


def test_swap_then_map_parity(mesh):
    data = _data()
    got = np.asarray(_source(data, mesh, 4).swap((0,), (0,))
                     .map(lambda v: v * 2.0)._data)
    assert np.array_equal(got, np.transpose(data, (1, 0, 2)) * 2.0)


def test_swap_then_chunk_map_parity(mesh):
    data = _data()
    got = np.asarray(_source(data, mesh, 4).swap((0,), (0,))
                     .chunk((3, 5)).map(lambda blk: blk + 1.0)
                     .unchunk()._data)
    assert np.array_equal(got, np.transpose(data, (1, 0, 2)) + 1.0)


def test_streamed_swap_under_dict_codec(mesh):
    """A lossless-codec source swaps streamed (phase 1 decodes the wire
    slab on device before the transpose) — still bit-identical."""
    data = _data(np.int32)
    s = _source(data, mesh, 4, codec="dict").swap((0,), (0,))
    assert s._stream is not None
    assert np.array_equal(np.asarray(s._data),
                          np.transpose(data, (1, 0, 2)))


def test_lossy_codec_swap_falls_back_to_materialise(mesh):
    """A LOSSY codec refuses the streamed shuffle (phase 1 would decode
    once and a later lossy terminal would quantise AGAIN — drift) — the
    swap silently takes the materialised path and stays correct."""
    data = _data()
    s = _source(data, mesh, 4, codec="bf16").swap((0,), (0,))
    assert s._stream is None              # materialised at record time
    got = np.asarray(s._data)
    assert got.shape == (V0, N, V1)


# ---------------------------------------------------------------------
# phase 2 in place (ISSUE 32): the swapped array is allocated once and
# every slab's program writes its block into it at the slab's offset
# ---------------------------------------------------------------------

def _double(v):
    return v * 2.0


def _case_j0_last(mesh):
    data = _data()
    return (_source(data, mesh, 4).swap((0,), (0, 1)),
            np.transpose(data, (1, 2, 0)))


def _case_j0_middle(mesh):
    data = _data()
    return (_source(data, mesh, 4).swap((0,), (0,)),
            np.transpose(data, (1, 0, 2)))


def _case_j0_first(mesh):
    # two key axes, the SECOND swapped out: the record axis stays
    # leading (perm (0, 2, 1)), so each block lands at offset lo of axis 0
    data = _data()
    src = bolt.fromcallback(lambda idx: data[idx], data.shape, mesh,
                            axis=(0, 1), dtype=data.dtype, chunks=4)
    return src.swap((1,), (0,)), np.transpose(data, (0, 2, 1))


def _case_short_last_slab(mesh):
    data = _data()                        # 24 records = 4 x 5 + 4
    return (_source(data, mesh, 5).swap((0,), (0, 1)),
            np.transpose(data, (1, 2, 0)))


def _case_map_before(mesh):
    data = _data()
    return (_source(data, mesh, 4).map(_double).swap((0,), (0, 1)),
            np.transpose(data * 2.0, (1, 2, 0)).astype(np.float32))


def _case_map_and_stat_after(mesh):
    data = np.round(_data())              # integer-valued: sums exact
    got = _source(data, mesh, 4).swap((0,), (0,)).map(_double).sum()
    return got, (np.transpose(data, (1, 0, 2)) * 2.0).sum(axis=0)


def _case_dict_codec(mesh):
    data = _data(np.int32)
    return (_source(data, mesh, 4, codec="dict").swap((0,), (0, 1)),
            np.transpose(data, (1, 2, 0)))


_INPLACE_CASES = {
    "j0-last": _case_j0_last, "j0-middle": _case_j0_middle,
    "j0-first": _case_j0_first, "short-last-slab": _case_short_last_slab,
    "map-before": _case_map_before,
    "map-and-stat-after": _case_map_and_stat_after,
    "dict-codec": _case_dict_codec,
}


@pytest.mark.parametrize("case", sorted(_INPLACE_CASES))
def test_inplace_assembly_equals_numpy_transpose(mesh, case):
    """Bit for bit, with the output assembled by the place programs: no
    list of parts, no concatenate (``concat_program`` is gone), nothing
    spilled, and phase 1 counted as the streamed run it is."""
    from bolt_tpu.parallel import shuffle
    assert not hasattr(shuffle, "concat_program")
    obs.clear()
    obs.enable()
    c0 = engine.counters()
    try:
        arr, want = _INPLACE_CASES[case](mesh)
        got = np.asarray(arr._data if hasattr(arr, "_data") else arr)
        runs = [sp for sp in obs.spans() if sp.name == "stream.shuffle"]
        hand = [sp for sp in obs.spans() if sp.name == "stream.handover"]
        slabs = [sp for sp in obs.spans() if sp.name == "stream.compute"]
        assert obs.active_count() == 0
    finally:
        obs.disable()
        obs.clear()
    c1 = engine.counters()
    assert got.dtype == want.dtype and np.array_equal(got, want)
    nslabs = 5 if case == "short-last-slab" else 6
    assert len(runs) == 1 and len(hand) == 1 and len(slabs) == nslabs
    assert runs[0].attrs["inplace"] is True and runs[0].attrs["resident"]
    assert all(sp.attrs["shuffle"] is True for sp in slabs)
    assert c1["spill_bytes"] == c0["spill_bytes"]
    assert c1["shuffle_bytes"] - c0["shuffle_bytes"] == _data().nbytes
    assert c1["stream_chunks"] - c0["stream_chunks"] == nslabs
    assert c1["stream_wall_seconds"] > c0["stream_wall_seconds"]
    assert c1["stream_ingest_seconds"] > c0["stream_ingest_seconds"]


def test_uniform_slabs_share_one_place_program(mesh):
    """The slab's place is an OPERAND (a cursor carried on the device):
    six slabs, one executable; the short last slab is one more; a second
    pass compiles nothing and no slab's dispatch carries a host value."""
    data = _data()

    def run(chunks):
        c0 = engine.counters()
        np.asarray(_source(data, mesh, chunks).swap((0,), (0, 1))._data)
        c1 = engine.counters()
        return c1["aot_compiles"] - c0["aot_compiles"]

    engine.clear()
    assert run(4) == 2                    # the allocation and the place
    assert run(4) == 0
    assert run(5) == 2                    # uniform (5) and the short (4)
    assert run(5) == 0


def test_iterator_blocks_land_at_their_own_offsets(mesh):
    """An iterator's blocks are what it yields: the cursor counts records
    there (unit 1), so uneven blocks still land where they belong."""
    data = _data()
    blocks = [data[0:7], data[7:9], data[9:20], data[20:24]]
    s = bolt.fromiter(blocks, SHAPE, mesh, dtype=np.float32)
    got = np.asarray(s.swap((0,), (0, 1))._data)
    assert np.array_equal(got, np.transpose(data, (1, 2, 0)))


def _slab_mesh(kind):
    """The meshes the default slab is drawn on: one device, the four of a
    one-process host as one axis (``tests/test_stream_swap4.py``'s), and
    the same four as 2 x 2."""
    devs = np.array(jax.devices()[:4])
    if kind == "2x2":
        return jax.sharding.Mesh(devs.reshape(2, 2), ("a", "b"))
    return jax.sharding.Mesh(devs[:kind], ("k",))


# (case, mesh, frames, key axes, default slab, chunks, swap, the slab): a
# frame is 64 float32 (8 x 8, or 2 x 4 x 8 with the 2 a second key axis)
_DEFAULT_SLABS = [
    # one device: the rule as it was before it knew of a mesh (ISSUE 32)
    ("record-axis-minor", 1, 300, 1, 64, None, ((0,), (0, 1)), 128),
    ("already-whole-tiles", 1, 300, 1, 256, None, ((0,), (0, 1)), 256),
    ("rounds-up-not-down", 1, 300, 1, 200, None, ((0,), (0, 1)), 256),
    ("record-axis-in-the-middle", 1, 300, 1, 64, None, ((0,), (0,)), 64),
    ("the-caller-chose", 1, 300, 1, 64, 64, ((0,), (0, 1)), 64),
    ("records-too-fat-for-a-tile", 1, 300, 1, 20, None, ((0,), (0, 1)), 20),
    # four devices shard a slab's records: tiles and ceiling a DEVICE
    ("whole-tiles-a-device", 4, 1100, 1, 64, None, ((0,), (0, 1)), 512),
    ("a-tile-a-mesh-is-no-tile-a-device", 4, 1100, 1, 128, None,
     ((0,), (0, 1)), 512),
    ("already-whole-tiles-a-device", 4, 1100, 1, 512, None,
     ((0,), (0, 1)), 512),
    ("rounds-up-not-down-a-device", 4, 1100, 1, 520, None,
     ((0,), (0, 1)), 1024),
    ("records-too-fat-for-a-tile-a-device", 4, 1100, 1, 20, None,
     ((0,), (0, 1)), 20),
    ("one-record-under-the-ceiling-a-device", 4, 1100, 1, 63, None,
     ((0,), (0, 1)), 63),
    ("a-recording-shorter-than-a-tile-a-device", 4, 300, 1, 64, None,
     ((0,), (0, 1)), 300),
    ("record-axis-in-the-middle-on-four", 4, 1100, 1, 64, None,
     ((0,), (0,)), 64),
    ("the-caller-chose-on-four", 4, 1100, 1, 64, 64, ((0,), (0, 1)), 64),
    # 2 x 2 with two key axes: one mesh axis a key axis, so TWO devices
    # shard the records of a slab and the other two hold the same
    ("half-the-devices-shard-the-records", "2x2", 600, 2, 64, None,
     ((0,), (0, 1)), 256),
    # ... and with one key axis that axis takes the whole mesh
    ("one-key-axis-takes-both-mesh-axes", "2x2", 1100, 1, 64, None,
     ((0,), (0, 1)), 512),
]


@pytest.mark.parametrize(
    "case,devices,frames,split,default,chunks,kv,want_slab",
    _DEFAULT_SLABS, ids=[c[0] for c in _DEFAULT_SLABS])
def test_default_slab_is_whole_lane_tiles_where_records_land_minor(
        monkeypatch, case, devices, frames, split, default, chunks, kv,
        want_slab):
    """A rule, no knob: with no ``chunks`` given, a swap whose record axis
    lands minor re-draws the slab to whole lane tiles (128 records) for
    every device that shards a slab's records (ISSUE 60), unless a tile of
    records is over twice the default slab's bytes, a device."""
    mesh = _slab_mesh(devices)
    shape = (frames, 8, 8) if split == 1 else (frames, 2, 4, 8)
    data = (np.arange(np.prod(shape)) % 977).astype(np.float32).reshape(
        shape)
    monkeypatch.setattr(stream, "_SLAB_BYTES", default * 8 * 8 * 4)
    src = bolt.fromcallback(lambda idx: data[idx], shape, mesh,
                            axis=tuple(range(split)), dtype=np.float32,
                            chunks=chunks)
    assert src._stream.slab == (chunks or default)
    s = src.swap(*kv)
    assert s._stream.slab == want_slab, case
    assert src._stream.slab == (chunks or default)   # the source keeps its
    # ``swap``'s permutation as ``_do_swap`` builds it
    kaxes, vaxes = kv
    perm = tuple([k for k in range(split) if k not in kaxes]
                 + [split + v for v in vaxes] + list(kaxes)
                 + [split + v for v in range(len(shape) - split)
                    if v not in vaxes])
    assert np.array_equal(np.asarray(s._data), np.transpose(data, perm))


@pytest.mark.parametrize("kind,split,want", [
    (None, 1, 1), (1, 1, 1), (4, 1, 4), (4, 2, 4), ("2x2", 1, 4),
    ("2x2", 2, 2), (3, 1, 3),
], ids=["no-mesh", "one-device", "four", "four-two-key-axes",
        "2x2-one-key-axis", "2x2-two-key-axes", "three"])
def test_tile_width_is_the_devices_that_shard_a_slab_of_tiles(kind, split,
                                                              want):
    """``lane_slab``'s width is read off the mesh on a slab every device
    can take a tile of, so it does not depend on how many records the
    default slab happened to hold; a slab drawn at that width is sharded
    by exactly as many."""
    from bolt_tpu.parallel import sharding as sh
    from bolt_tpu.parallel import shuffle
    mesh = None if kind is None else (
        jax.sharding.Mesh(np.array(jax.devices()[:3]), ("k",))
        if kind == 3 else _slab_mesh(kind))
    shape = (1000, 8, 8) if split == 1 else (1000, 2, 4, 8)
    width = shuffle.tile_width(mesh, shape, split)
    assert width == want
    rec = int(np.prod(shape[1:])) * 4
    slab = shuffle.lane_slab(64, 10 ** 6, rec, (1, 2, 0), 128 * rec, width)
    assert slab == shuffle.LANES * want
    assert shuffle._axis0_device_width(mesh, (slab,) + shape[1:],
                                       split) == want
    if mesh is not None:
        _, placed = sh.device_placements(mesh, (slab,) + shape[1:], split)
        rows = {idx[0].indices(slab)[:2] for _, idx in placed}
        assert len(rows) == want
        assert all((hi - lo) == shuffle.LANES for lo, hi in rows)


@pytest.mark.parametrize("slab,want", [
    (64, 128), (128, 128), (200, 256), (256, 256), (20, 20), (63, 63),
    (1, 1), (129, 256)])
def test_lane_slab_at_width_one_is_the_rule_of_one_device(slab, want):
    """``width`` 1 is the function as PR 32 wrote it, before it knew of a
    mesh: these are its answers."""
    from bolt_tpu.parallel import shuffle
    rec = 256
    assert shuffle.lane_slab(slab, 10 ** 6, rec, (1, 2, 0), 2 * slab * rec,
                             1) == want
    # short recordings cap it, a record axis elsewhere leaves it
    assert shuffle.lane_slab(slab, 100, rec, (1, 2, 0), 2 * slab * rec,
                             1) == (want if want == slab else 100)
    assert shuffle.lane_slab(slab, 10 ** 6, rec, (1, 0, 2),
                             2 * slab * rec, 4) == slab


# ---------------------------------------------------------------------
# the plan: true of the program, and aware of the device (ISSUE 32)
# ---------------------------------------------------------------------

def test_resident_rule_counts_what_the_program_holds(mesh):
    """output + (ring + 1) slabs: the ring of uploaded slabs and one place
    program's temp; the planner, BLT017 and the resolver read one rule."""
    from bolt_tpu.parallel import shuffle
    data = _data()
    src = _source(data, mesh, 4)._stream
    ring = stream.swap_ring(src)
    # the resolver's window (the depth and a step) and a slab a worker
    assert ring == stream.prefetch_depth() + stream._SWAP_WINDOW_STEP \
        + stream.pool_size(src)
    slab_bytes = 4 * V0 * V1 * 4
    holds = data.nbytes + (ring + 1) * slab_bytes

    def plan(budget):
        return shuffle.plan_shuffle(SHAPE, np.float32, 1, (1, 2, 0), 2,
                                    mesh, 4, budget, None, ring=ring)
    assert plan(None).resident and plan(None).resident_bytes == holds
    assert plan(holds).resident and not plan(holds - 1).resident
    # the old rule (output + one slab) would have said yes here
    assert data.nbytes + slab_bytes <= holds - 1
    # a deeper ring holds more
    with stream.prefetch(5):
        assert stream.swap_ring(src) == 5 + stream._SWAP_WINDOW_STEP \
            + stream.pool_size(src)
    # one call at a time has no window to deepen
    with stream.prefetch(1):
        assert stream.swap_ring(src) == 1 + stream.pool_size(src)
    # BLT017 and the run agree at the edge, both ways
    for budget, resident in ((holds, True), (holds - 1, False)):
        with stream.spill(budget=budget):
            s = _source(data, mesh, 4).swap((0,), (0, 1))
            d, = [d for d in analysis.check(s).diagnostics
                  if d.code == "BLT017"]
            assert ("resident" in d.message) is resident, d.message
            assert (d.severity == "info") is resident
            if resident:
                assert np.array_equal(np.asarray(s._data),
                                      np.transpose(data, (1, 2, 0)))
            else:
                with pytest.raises(RuntimeError, match="BLT017"):
                    s._data


def test_no_scope_plans_against_the_devices_own_limit(mesh, monkeypatch):
    """With no spill scope and no arbiter the budget is what the device
    has free: a swap that cannot fit and has nowhere to spill refuses in
    BLT017's words BEFORE a thread starts or the loader is called."""
    import threading
    data = _data()
    calls = []

    def loader(idx):
        calls.append(idx)
        return data[idx]

    assert stream.swap_budget(mesh) is None   # the CPU reports no limit
    monkeypatch.setattr(stream, "_device_headroom",
                        lambda mesh=None: data.nbytes)  # output fits,
    assert stream.swap_budget(mesh) == data.nbytes      # ring does not
    s = bolt.fromcallback(loader, SHAPE, mesh, dtype=np.float32,
                          chunks=4).swap((0,), (0, 1))
    d, = [d for d in analysis.check(s).diagnostics if d.code == "BLT017"]
    assert d.severity == "warning" and "NO spill directory" in d.message
    before = threading.active_count()
    with pytest.raises(RuntimeError, match="no spill directory"):
        s._data
    assert calls == [] and threading.active_count() == before
    # an explicit scope still wins over the device, and room is room
    with stream.spill(budget=1 << 30):
        assert stream.swap_budget(mesh) == 1 << 30
    monkeypatch.setattr(stream, "_device_headroom",
                        lambda mesh=None: 1 << 30)
    assert np.array_equal(np.asarray(s._data),
                          np.transpose(data, (1, 2, 0)))


def test_device_headroom_reads_the_tightest_device(monkeypatch):
    class Dev:
        process_index = 0

        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    import jax
    monkeypatch.setattr(jax, "process_index", lambda: 0)
    monkeypatch.setattr(jax, "local_devices", lambda: [
        Dev({"bytes_limit": 100, "bytes_in_use": 30}),
        Dev({"bytes_limit": 100, "bytes_in_use": 45})])
    assert stream._device_headroom() == 2 * 55
    monkeypatch.setattr(jax, "local_devices", lambda: [Dev(None)])
    assert stream._device_headroom() is None


# ---------------------------------------------------------------------
# the forced-spill path (budget ~ one bucket)
# ---------------------------------------------------------------------

@pytest.mark.parametrize("served", [False, True],
                         ids=["alone", "under-a-serving-budget"])
def test_forced_spill_bitexact_and_cleared(mesh, tmp_path, served):
    # served: the swap leases its slabs from a server's arbiter; every
    # byte comes back and no span stays open
    data = _data()
    td = str(tmp_path)
    c0 = engine.counters()
    obs.clear()
    obs.enable()
    try:
        server = (serve.serving(workers=1, budget_bytes=64 << 20)
                  if served else contextlib.nullcontext())
        with server as sv:
            with stream.spill(dir=td, budget=1):
                got = np.asarray(
                    _source(data, mesh, 4).swap((0,), (0,))._data)
            if served:
                assert sv.stats()["arbiter"]["in_use_bytes"] == 0
        assert obs.active_count() == 0
    finally:
        obs.disable()
        obs.clear()
    c1 = engine.counters()
    assert np.array_equal(got, np.transpose(data, (1, 0, 2)))
    assert c1["spill_bytes"] > c0["spill_bytes"]
    assert c1["shuffle_bytes"] > c0["shuffle_bytes"]
    assert checkpoint.spill_pending(td)
    checkpoint.spill_clear(td)
    assert not checkpoint.spill_pending(td)
    assert not glob.glob(os.path.join(td, "bolt-spill-*"))


def test_forced_spill_chunk_map_rides_phase_two(mesh, tmp_path):
    """chunk().map() AFTER the swap streams through the spilled
    phase-2 source — the whole chain completes past the budget without
    full materialisation."""
    data = _data()
    with stream.spill(dir=str(tmp_path), budget=1):
        got = np.asarray(_source(data, mesh, 4).swap((0,), (0,))
                         .chunk((3, 5)).map(lambda blk: blk * 3.0)
                         .unchunk()._data)
    assert np.array_equal(got, np.transpose(data, (1, 0, 2)) * 3.0)


def test_spill_without_dir_refuses_pointedly(mesh):
    data = _data()
    with stream.spill(budget=1):          # budget but NO directory
        s = _source(data, mesh, 4).swap((0,), (0,))
        with pytest.raises(RuntimeError, match="spill"):
            s._data


# ---------------------------------------------------------------------
# compile-once and forecast contracts
# ---------------------------------------------------------------------

def test_zero_second_pass_recompiles(mesh):
    data = _data()

    def run():
        return np.asarray(_source(data, mesh, 4).swap((0,), (0,))._data)

    first = run()
    c0 = engine.counters()
    second = run()
    c1 = engine.counters()
    assert c1["misses"] == c0["misses"], "second pass compiled programs"
    assert np.array_equal(first, second)


def test_blt017_forecast_matches_runtime_decision(mesh, tmp_path):
    data = _data()

    def blt017(arr):
        rep = analysis.check(arr)
        ds = [d for d in rep.diagnostics if d.code == "BLT017"]
        assert len(ds) == 1, rep.diagnostics
        return ds[0]

    # resident forecast -> the run spills nothing
    s = _source(data, mesh, 4).swap((0,), (0,))
    d = blt017(s)
    assert d.severity == "info" and "resident" in d.message
    c0 = engine.counters()
    np.asarray(s._data)
    assert engine.counters()["spill_bytes"] == c0["spill_bytes"]

    # spill forecast (same planner, same budget resolution) -> it spills
    with stream.spill(dir=str(tmp_path), budget=1):
        s2 = _source(data, mesh, 4).swap((0,), (0,))
        d2 = blt017(s2)
        assert d2.severity == "info" and "spill" in d2.message
        np.asarray(s2._data)
    assert engine.counters()["spill_bytes"] > c0["spill_bytes"]

    # spill forecast with NO dir -> warning, and the run refuses
    with stream.spill(budget=1):
        s3 = _source(data, mesh, 4).swap((0,), (0,))
        d3 = blt017(s3)
        assert d3.severity == "warning"


def test_shuffle_chaos_raise_absorbed_in_place(mesh):
    data = _data()
    ref = np.transpose(data, (1, 0, 2))
    for seam in ("stream.shuffle", "stream.spill"):
        _chaos.inject(seam, nth=2)
        c0 = engine.counters()
        try:
            with stream.retries(1), stream.spill(budget=None):
                if seam == "stream.spill":
                    import tempfile
                    td = tempfile.mkdtemp(prefix="bolt-swapchaos-")
                    with stream.spill(dir=td, budget=1):
                        got = np.asarray(
                            _source(data, mesh, 4).swap((0,), (0,))._data)
                    checkpoint.spill_clear(td)
                else:
                    got = np.asarray(
                        _source(data, mesh, 4).swap((0,), (0,))._data)
        finally:
            _chaos.clear()
        c1 = engine.counters()
        assert c1["stream_retries"] - c0["stream_retries"] == 1, seam
        assert np.array_equal(got, ref), seam


# ---------------------------------------------------------------------
# the resolver's window of dispatched, unconfirmed place calls (ISSUE 56)
# ---------------------------------------------------------------------

def _scale_keys(kv):
    (k,), v = kv
    return v * (k % 7 + 1)


def _thin_table():
    rng = np.random.default_rng(11)
    return rng.integers(-1000, 1000, size=(1024, 6)).astype(np.float32)


def _one():
    return jax.sharding.Mesh(np.array(jax.devices()[:1]), ("k",))


# case -> a NEW lazy streamed swap each call (an iterator is one-shot)
_WINDOW_CASES = {
    "callback": lambda mesh: _source(_data(), mesh, 4).swap((0,), (0,)),
    "callback-short-tail":
        lambda mesh: _source(_data(), mesh, 5).swap((0,), (0, 1)),
    "iterator": lambda mesh: bolt.fromiter(
        np.array_split(_data(), 6), SHAPE, mesh,
        dtype=np.float32).swap((0,), (1,)),
    "thin-records": lambda mesh: _source(
        _thin_table(), _one(), 128).swap((0,), (0,)),
    "keyed": lambda mesh: _source(_data(), mesh, 4).map(
        _scale_keys, with_keys=True).swap((0,), (0,)),
}


@pytest.fixture
def never_done(monkeypatch):
    """A CPU's programs are done as soon as dispatched, so the window
    never opens here by itself: no handle reads done until the resolver
    BLOCKS for it (the chip's order of events, with a slow program)."""
    monkeypatch.setattr(stream, "_retired", lambda handle: False)


def _materialised(lazy):
    src = lazy._stream
    return np.asarray(stream._replay_stages(
        stream._materialize_base(src), src.stages).toarray())


@pytest.mark.parametrize("open_window", [False, True],
                         ids=["done-at-once", "never-done"])
@pytest.mark.parametrize("depth", [1, 2, 4])
@pytest.mark.parametrize("case", sorted(_WINDOW_CASES))
def test_windowed_resolver_is_the_materialised_swap_to_the_bit(
        mesh, case, depth, open_window, monkeypatch):
    if open_window:
        monkeypatch.setattr(stream, "_retired", lambda handle: False)
    want = _materialised(_WINDOW_CASES[case](mesh))
    c0 = engine.counters()
    with stream.prefetch(depth):
        got = np.asarray(_WINDOW_CASES[case](mesh)._data)
    c1 = engine.counters()
    assert got.dtype == want.dtype and np.array_equal(got, want)
    windowed = c1["stream_windowed_slabs"] - c0["stream_windowed_slabs"]
    slabs = c1["stream_chunks"] - c0["stream_chunks"]
    if open_window or depth == 1:
        # every call but the first goes out behind an unconfirmed one,
        # or none does: prefetch(1) is one call at a time
        assert windowed == (slabs - 1 if depth > 1 else 0)
    else:
        # how many were done by the time the resolver asked is the
        # device's to say
        assert 0 <= windowed <= slabs - 1


def _spy_record_stream(monkeypatch):
    seen = []
    record = engine.record_stream

    def spy(*args, **kw):
        seen.append(kw)
        return record(*args, **kw)
    monkeypatch.setattr(engine, "record_stream", spy)
    return seen


def _traced(run):
    obs.clear()
    obs.enable()
    try:
        got = run()
        assert obs.active_count() == 0
        return got, obs.spans()
    finally:
        obs.disable()
        obs.clear()


def _by_slab(spans, name):
    return {sp.attrs["slab"]: sp for sp in spans if sp.name == name}


@pytest.mark.parametrize("depth", [1, 2, 3, 4])
def test_the_window_holds_depth_calls_and_the_ring_its_permits(
        mesh, depth, never_done, monkeypatch):
    """With a place program made slow (a confirm that takes a while and
    no handle done before it), slab g + 1's call goes out before slab
    g's confirm ends, never more than the window unconfirmed, never
    more than the ring's permits out, and the counters say so."""
    sync = stream._pod_sync

    def slow(x, pod, phase, slab=None):
        time.sleep(0.004)
        return sync(x, pod, phase, slab=slab)
    monkeypatch.setattr(stream, "_pod_sync", slow)
    seen = _spy_record_stream(monkeypatch)
    data = _data()
    nslabs = N // 2
    c0 = engine.counters()
    with stream.prefetch(depth), stream.uploaders(2):
        src = _source(data, mesh, 2)
        ring = stream.swap_ring(src._stream)
        got, spans = _traced(
            lambda: np.asarray(src.swap((0,), (0,))._data))
    c1 = engine.counters()
    assert np.array_equal(got, np.transpose(data, (1, 0, 2)))
    # the window: the ring less a slab in each of the two workers' hands
    window = ring - 2
    assert window == (depth + stream._SWAP_WINDOW_STEP if depth > 1 else 1)
    calls, blocks = (_by_slab(spans, n) for n in ("stream.dispatch",
                                                  "stream.sync"))
    assert sorted(calls) == sorted(blocks) == list(range(nslabs))
    for g in range(nslabs - 1):
        # confirmed in slab order, each after its own call
        assert calls[g].t1 <= blocks[g].t0 <= blocks[g + 1].t0
        if window > 1:
            assert calls[g + 1].t0 < blocks[g].t1
        else:
            assert blocks[g].t1 <= calls[g + 1].t0
    for g in range(nslabs):
        t = calls[g].t0
        unconfirmed = sum(c.t0 <= t for c in calls.values()) \
            - sum(b.t1 <= t for b in blocks.values())
        assert unconfirmed <= window
    if window > 1:
        # and the window does fill: slab `window - 1`'s call went out
        # with every call before it unconfirmed
        assert blocks[0].t0 >= calls[window - 1].t1
    ingests = [sp for sp in spans if sp.name == "stream.ingest"]
    assert len(ingests) == nslabs
    for sp in ingests:
        out = sum(i.t0 <= sp.t0 for i in ingests) \
            - sum(b.t1 <= sp.t0 for b in blocks.values())
        assert out <= ring
    mine, = seen
    assert mine["inflight"] == min(window, nslabs)
    assert mine["windowed"] == (nslabs - 1 if window > 1 else 0)
    assert c1["stream_windowed_slabs"] - c0["stream_windowed_slabs"] \
        == mine["windowed"]
    assert c1["stream_inflight_high_water"] >= mine["inflight"]


def test_the_spill_leg_keeps_one_block_a_slab(mesh, tmp_path, never_done,
                                              monkeypatch):
    seen = _spy_record_stream(monkeypatch)
    data = _data()
    td = str(tmp_path)
    with stream.prefetch(4), stream.spill(dir=td, budget=1):
        got, spans = _traced(lambda: np.asarray(
            _source(data, mesh, 4).swap((0,), (0,))._data))
    checkpoint.spill_clear(td)
    assert np.array_equal(got, np.transpose(data, (1, 0, 2)))
    calls, blocks = (_by_slab(spans, n) for n in ("stream.dispatch",
                                                  "stream.sync"))
    computes = _by_slab(spans, "stream.compute")
    # the spill's own pass re-streams the buckets through execute: its
    # compute spans carry no shuffle=True
    computes = {g: sp for g, sp in computes.items()
                if sp.attrs.get("shuffle")}
    assert len(computes) == N // 4
    for g, csp in computes.items():
        assert calls[g].pid == blocks[g].pid == csp.sid
        assert calls[g].t1 <= blocks[g].t0
    phase1 = [kw for kw in seen if "windowed" in kw]
    assert [kw["windowed"] for kw in phase1] == [0]
    assert [kw["inflight"] for kw in phase1] == [1]


def test_a_raise_at_the_seam_with_the_window_open_retries_in_place(
        mesh, never_done):
    data = _data()
    _chaos.inject("stream.shuffle", nth=4)
    c0 = engine.counters()
    try:
        with stream.retries(1), stream.prefetch(4):
            got = np.asarray(_source(data, mesh, 2).swap((0,), (0,))._data)
    finally:
        _chaos.clear()
    c1 = engine.counters()
    assert c1["stream_retries"] - c0["stream_retries"] == 1
    # the retried call went out behind the unconfirmed ones all the same
    assert c1["stream_windowed_slabs"] - c0["stream_windowed_slabs"] \
        == N // 2 - 1
    assert np.array_equal(got, np.transpose(data, (1, 0, 2)))


@pytest.mark.parametrize("served", [False, True],
                         ids=["alone", "under-a-serving-budget"])
def test_a_raise_at_a_confirm_ends_the_run_and_names_the_slab(
        mesh, served, never_done, monkeypatch):
    sync = stream._pod_sync

    def failing(x, pod, phase, slab=None):
        if phase == "shuffle re-bucket" and slab == 2:
            raise OSError("device gone")
        return sync(x, pod, phase, slab=slab)
    monkeypatch.setattr(stream, "_pod_sync", failing)
    data = _data()
    c0 = engine.counters()
    obs.clear()
    obs.enable()
    try:
        server = (serve.serving(workers=1, budget_bytes=64 << 20)
                  if served else contextlib.nullcontext())
        with server as sv:
            # a retry budget changes nothing: the confirm is final
            with stream.retries(2), stream.prefetch(3), \
                    pytest.raises(RuntimeError,
                                  match="shuffle slab 2 failed at the "
                                        "confirm") as err:
                _source(data, mesh, 2).swap((0,), (0,))._data
            if served:
                # every permit's lease bytes came back on the way out
                assert sv.stats()["arbiter"]["in_use_bytes"] == 0
        assert obs.active_count() == 0
        syncs = [sp.attrs["slab"] for sp in obs.spans()
                 if sp.name == "stream.sync"]
    finally:
        obs.disable()
        obs.clear()
    assert isinstance(err.value.__cause__, OSError)
    assert syncs == [0, 1, 2]
    assert engine.counters()["stream_retries"] == c0["stream_retries"]
    assert all(not t.is_alive() for t in stream._LAST_POOL)


def test_a_serving_budget_under_the_ring_runs_a_shallower_window(
        mesh, never_done):
    """execute's valve: the feeder waits for budget bytes that only a
    confirm gives back, so the consumer confirms one call per empty
    poll instead of waiting for a slab that cannot come."""
    data = _data()
    slab_bytes = 2 * V0 * V1 * 4
    with serve.serving(workers=1, budget_bytes=slab_bytes) as sv:
        # resident by the scope's own budget; the lease is the arbiter's
        with stream.prefetch(4), stream.spill(budget=1 << 30):
            src = _source(data, mesh, 2)
            assert stream.swap_ring(src._stream) * slab_bytes \
                > sv.stats()["arbiter"]["budget_bytes"]
            got = np.asarray(src.swap((0,), (0,))._data)
        assert sv.stats()["arbiter"]["in_use_bytes"] == 0
    assert np.array_equal(got, np.transpose(data, (1, 0, 2)))


# the window itself (ISSUE 57: one ``stream._Window`` for ``execute`` and
# the resolver alike), on a pool, a lease and handles that only record

class _Recorder:
    """A pool that records what came back, a run whose lease's arbiter
    has ``waiters`` acquires queued, and the order of everything."""

    pod = False
    compute = 0.0

    def __init__(self, monkeypatch, waiters=0, fail_at=None):
        self.log = []
        self.lease = self.arbiter = self
        self.waiting = lambda: waiters

        def sync(handle, pod, phase, slab=None):
            self.log.append(("sync", handle, phase, slab))
            if handle == fail_at:
                raise OSError("device gone")
        monkeypatch.setattr(stream, "_pod_sync", sync)
        # a handle is done when it says so: "done-*"
        monkeypatch.setattr(stream, "_retired",
                            lambda handle: handle.startswith("done"))

    def give_back(self, slabs, nbytes):
        self.log.append(("back", slabs, nbytes))

    def window(self, **kw):
        return stream._Window(self, self, "a phase", **kw)

    def backs(self):
        return [e[1:] for e in self.log if e[0] == "back"]

    def syncs(self):
        return [e[1] for e in self.log if e[0] == "sync"]


def _confirms_in_dispatch_order(rec):
    win = rec.window(attrs={"shuffle": True})
    for g in range(3):
        win.push(1, "call-%d" % g, 100 + g, slab=g)
    assert (win.unconfirmed, win.high_water) == (3, 3)
    obs.clear()
    obs.enable()
    try:
        while win.unconfirmed:
            win.confirm_oldest()
        spans = [sp.attrs for sp in obs.spans() if sp.name == "stream.sync"]
    finally:
        obs.disable()
        obs.clear()
    assert rec.log == [e for g in range(3) for e in (
        ("sync", "call-%d" % g, "a phase", g), ("back", 1, 100 + g))]
    assert spans == [{"slabs": 1, "shuffle": True, "slab": g}
                     for g in range(3)]
    assert win.high_water == 3 and rec.compute >= 0.0


def _retire_lets_a_done_head_go(rec):
    win = rec.window()
    for name in ("done-0", "done-1", "call-2", "done-3"):
        win.push(1, name, 8)
    win.retire(4)               # nothing over: only what is done, in order
    assert rec.syncs() == ["done-0", "done-1"] and win.unconfirmed == 2


def _retire_blocks_for_a_head_over_keep(rec):
    win = rec.window()
    for g in range(4):
        win.push(1, "call-%d" % g, 8)
    win.retire(1)
    assert rec.syncs() == ["call-0", "call-1", "call-2"]
    win.retire(1)               # at keep, head not done: nothing
    assert win.unconfirmed == 1 and len(rec.syncs()) == 3
    win.retire(0)
    assert win.unconfirmed == 0 and rec.backs() == [(1, 8)] * 4


def _starved_needs_a_waiter(rec):
    win = rec.window()
    assert win.starved() is False           # nothing to confirm
    win.push(1, "call-0", 8)
    win.push(1, "call-1", 8)
    if rec.waiting():
        assert win.starved() is True and rec.syncs() == ["call-0"]
        assert win.unconfirmed == 1         # ONE call an empty poll
    else:
        assert win.starved() is False and rec.log == []
        assert win.unconfirmed == 2         # a slow feeder keeps the window


def _release_after_a_failure(rec):
    win = rec.window(failed=lambda slab, exc: RuntimeError(
        "slab %d: %s" % (slab, exc)))
    for g in range(3):
        win.push(1, "call-%d" % g, 10 * (g + 1), slab=g)
    win.confirm_oldest()
    with pytest.raises(RuntimeError, match="slab 1: device gone") as err:
        win.confirm_oldest()
    assert isinstance(err.value.__cause__, OSError)
    # the failed head stays counted, and holds its permit and bytes
    assert win.unconfirmed == 2 and rec.backs() == [(1, 10)]
    win.release()
    win.release()                           # once
    assert rec.backs() == [(1, 10), (1, 20), (1, 30)]
    assert win.unconfirmed == 0 and rec.syncs() == ["call-0", "call-1"]


def _the_spill_hook_runs_between(rec):
    win = rec.window(settle=lambda handle, slab: rec.log.append(
        ("settle", handle, slab)))
    win.push(1, "part-0", 64, slab=5)
    win.confirm_oldest()
    assert [e[0] for e in rec.log] == ["sync", "settle", "back"]
    assert rec.log[1] == ("settle", "part-0", 5)


def _a_cover_of_two_moves_it_by_two(rec):
    win = rec.window()
    win.push(2, "pair-0", 200)
    assert win.unconfirmed == 2
    # execute's even slab counts from its own dispatch, its pair's push
    # covers both
    win.lone()
    assert (win.unconfirmed, win.high_water) == (3, 3)
    win.push(2, "pair-1", 300)
    assert (win.unconfirmed, win.high_water) == (4, 4)
    win.confirm_oldest()
    assert win.unconfirmed == 2 and rec.backs() == [(2, 200)]
    win.push(1, "restored-pair", 50)        # a lone partial of another run
    assert win.unconfirmed == 3
    win.retire(0)
    assert rec.backs() == [(2, 200), (2, 300), (1, 50)]
    assert rec.syncs() == ["pair-0", "pair-1", "restored-pair"]
    assert all(e[3] is None for e in rec.log if e[0] == "sync")


_WINDOW_UNITS = {
    "confirms-in-dispatch-order": (_confirms_in_dispatch_order, {}),
    "retire-lets-a-done-head-go": (_retire_lets_a_done_head_go, {}),
    "retire-blocks-for-a-head-over-keep":
        (_retire_blocks_for_a_head_over_keep, {}),
    "starved-with-no-waiter": (_starved_needs_a_waiter, {}),
    "starved-with-a-waiter": (_starved_needs_a_waiter, {"waiters": 1}),
    "release-after-a-failure":
        (_release_after_a_failure, {"fail_at": "call-1"}),
    "the-spill-hook-runs-between": (_the_spill_hook_runs_between, {}),
    "a-cover-of-two-moves-it-by-two": (_a_cover_of_two_moves_it_by_two, {}),
}


@pytest.mark.parametrize("case", sorted(_WINDOW_UNITS))
def test_the_window_on_a_fake_pool_and_fake_handles(case, monkeypatch):
    check, kw = _WINDOW_UNITS[case]
    check(_Recorder(monkeypatch, **kw))


def test_the_window_probe_runs_at_toy_size():
    """``scripts/swap_window_probe.py``, what PERF.md's window table was
    read from, end to end at ``benchmark/tests``' toy sizes: a JSON line
    a window, in the order asked for."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [sys.executable, os.path.join(root, "scripts",
                                      "swap_window_probe.py"),
         "twophoton512-1chip.toseries", "--seed", "7", "--tiny",
         "--windows", "1", "2", "--passes", "1", "--rounds", "1"],
        capture_output=True, text=True, timeout=300, cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-2000:]
    rows = [json.loads(line) for line in done.stdout.splitlines()
            if line.startswith('{"cell"')]
    assert [r["window"] for r in rows] == [1, 2]
    assert all(r["slabs"] >= 1 and r["windowed"] <= r["slabs"] - 1
               and "sync" in r["share"] for r in rows)
    assert rows[0]["windowed"] == 0


# ---------------------------------------------------------------------
# the dict codec (satellite: ROADMAP item 5 remainder)
# ---------------------------------------------------------------------

def test_dict_codec_registered():
    assert "dict" in codec_mod.names()
    c = codec_mod.get("dict")
    assert c.lossless and c.sidecar


@pytest.mark.parametrize("dtype", [np.int64, np.int32, np.int8, np.bool_])
def test_dict_codec_roundtrip_bitexact(dtype):
    c = codec_mod.get("dict")
    block = (np.arange(60) % 2 if dtype == np.bool_
             else (np.arange(60) % 7) * 3 - 5).astype(dtype).reshape(12, 5)
    wire, side = c.encode(block, delta_ok=False)
    assert wire.dtype == np.uint8 and wire.shape == block.shape
    assert len(side) == 1 and side[0].shape == (256,)
    assert side[0].dtype == block.dtype
    out = np.asarray(c.decode(wire, side, np.dtype(dtype),
                              delta_ok=False))
    assert np.array_equal(out, block)


def test_dict_codec_refuses_floats_pointedly():
    c = codec_mod.get("dict")
    with pytest.raises(ValueError, match="dictionary"):
        c.wire_dtype(np.float32)
    with pytest.raises(ValueError, match="dictionary"):
        c.encode(np.ones((4, 4), np.float64))


def test_dict_codec_cardinality_contract():
    with pytest.raises(ValueError, match="256"):
        codec_mod.get("dict").encode(np.arange(300, dtype=np.int32))


def test_dict_codec_streamed_sum_and_wire_ratio(mesh):
    """End to end through the uploader pool: int64 slabs ship as uint8
    indices (1/8 the wire bytes) and the decoded sum is exact."""
    data = _data(np.int64)
    c0 = engine.counters()
    got = np.asarray(_source(data, mesh, 4, codec="dict").sum())
    c1 = engine.counters()
    assert np.array_equal(got, data.sum(axis=0))
    raw = c1["codec_bytes_raw"] - c0["codec_bytes_raw"]
    wire = c1["codec_bytes_wire"] - c0["codec_bytes_wire"]
    assert raw == 8 * wire


# ---------------------------------------------------------------------
# the spill-file layer (checkpoint.py)
# ---------------------------------------------------------------------

def test_spill_save_load_roundtrip(tmp_path):
    td, fp = str(tmp_path), ("fp-a", 1)
    ints = ((np.arange(40) % 5) - 2).astype(np.int64).reshape(8, 5)
    nb = checkpoint.spill_save(td, fp, 0, 0, ints, 16)
    assert nb > 0
    out, row0 = checkpoint.spill_load(td, fp, 0, 0)
    assert np.array_equal(out, ints) and out.dtype == ints.dtype
    assert row0 == 16

    floats = _data()[:8, :, 0]            # raw path (no dict for floats)
    checkpoint.spill_save(td, fp, 0, 1, floats, 0)
    out2, _ = checkpoint.spill_load(td, fp, 0, 1)
    assert np.array_equal(out2, floats)

    wide = np.arange(300, dtype=np.int32)  # > 256 uniques: raw fallback
    checkpoint.spill_save(td, fp, 1, 0, wide, 0)
    out3, _ = checkpoint.spill_load(td, fp, 1, 0)
    assert np.array_equal(out3, wide)


def test_spill_manifest_and_fingerprint_isolation(tmp_path):
    td, fp = str(tmp_path), ("fp-a",)
    assert checkpoint.spill_manifest(td, fp) == set()
    checkpoint.spill_slab_done(td, fp, 0)
    checkpoint.spill_slab_done(td, fp, 3)
    assert checkpoint.spill_manifest(td, fp) == {0, 3}
    # a different fingerprint hashes to a different directory
    assert checkpoint.spill_manifest(td, ("fp-b",)) == set()
    assert checkpoint.spill_pending(td)
    checkpoint.spill_clear(td)
    assert not checkpoint.spill_pending(td)


def test_spill_load_missing_bucket_refuses_pointedly(tmp_path):
    with pytest.raises(checkpoint.CheckpointCorruptError, match="spill"):
        checkpoint.spill_load(str(tmp_path), ("fp",), 0, 0)
