"""The streamed two-phase shuffle (ISSUE 18): parity and contracts.

Parity is the load-bearing half: a ``swap`` recorded on a STREAMED
source resolves through the two-phase shuffle — phase 1 re-buckets each
uploaded slab on device, phase 2 concatenates resident buckets or
re-streams spilled ones — and must equal the materialise-first in-memory
swap BIT for bit (a transpose moves bytes, it never rounds).  Geometry
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
import os

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
