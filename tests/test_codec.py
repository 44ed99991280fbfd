"""Codec-encoded streaming ingest (ISSUE 14, bolt_tpu/tpu/codec.py).

The parity contract under test: the LOSSLESS ``delta-f32`` codec is
BIT-IDENTICAL to uncompressed streaming; lossy codecs (``bf16``/
``f16``/``int8``) hold their documented envelopes
(``_precision.codec_bound``); order statistics and integer pipelines
REFUSE lossy codecs pointedly; wire bytes shrink by the codec ratio in
the transfer counters and the arbiter/admission floors; checkpoints
fingerprint the codec id (a codec change restarts, never resumes
wrong); the ``stream.encode`` chaos seam rides the existing retry
fence; and the opt-in Pallas decode-and-reduce kernel parity-locks
against the XLA decode path.
"""

import json
import os
import tempfile
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bolt_tpu as bolt
from bolt_tpu import _chaos, _precision, analysis, engine, obs, stream
from bolt_tpu import checkpoint as ckptlib
from bolt_tpu.tpu import codec as codeclib

pytestmark = pytest.mark.codec

SHAPE = (64, 16, 8)


def _intdata(shape=SHAPE, lo=-6, hi=7):
    n = int(np.prod(shape))
    return ((np.arange(n) % (hi - lo)) + lo).astype(np.float32).reshape(
        shape)


def _posdata(shape=SHAPE):
    rs = np.random.RandomState(7)
    return (np.abs(rs.randn(*shape)) + 0.5).astype(np.float32)


def _src(x, mesh, chunks=8, codec=None, ck=None, dtype=None):
    return bolt.fromcallback(lambda idx: x[idx], x.shape, mesh,
                             dtype=dtype or x.dtype, chunks=chunks,
                             codec=codec, checkpoint=ck)


# ---------------------------------------------------------------------
# registry + contract units
# ---------------------------------------------------------------------

def test_registry_names_and_pointed_unknown():
    assert set(codeclib.names()) >= {"bf16", "f16", "int8", "delta-f32"}
    with pytest.raises(ValueError) as ei:
        codeclib.get("zstd")
    assert "unknown codec" in str(ei.value) and "bf16" in str(ei.value)
    # a Codec instance passes through get() (custom-codec door)
    c = codeclib.get("bf16")
    assert codeclib.get(c) is c


def test_wire_dtype_ratios():
    assert codeclib.get("bf16").ratio(np.float32) == 0.5
    assert codeclib.get("f16").ratio(np.float32) == 0.5
    assert codeclib.get("int8").ratio(np.float32) == 0.25
    assert codeclib.get("delta-f32").ratio(np.float32) == 1.0


def test_lossy_refuses_integer_pipeline_pointedly():
    for name in ("bf16", "f16", "int8"):
        with pytest.raises(ValueError) as ei:
            codeclib.get(name).wire_dtype(np.int32)
        assert name in str(ei.value) and "int32" in str(ei.value)
    with pytest.raises(ValueError):
        codeclib.get("delta-f32").wire_dtype(np.float64)


def test_precision_codec_bounds_table():
    assert _precision.codec_bound("delta-f32") == (True, None)
    lossless, env = _precision.codec_bound("bf16")
    assert not lossless and env == 1e-2
    assert _precision.codec_bound("no-such") == (False, None)


def test_delta_roundtrip_bit_exact_incl_nan():
    c = codeclib.get("delta-f32")
    x = np.random.RandomState(0).randn(6, 16).astype(np.float32)
    x[2, 3] = np.nan
    x[4, 0] = np.inf
    wire, side = c.encode(x)
    assert wire.dtype == np.uint32 and side == ()
    back = np.asarray(c.decode(jnp.asarray(wire), (), np.float32))
    assert np.array_equal(back.view(np.uint32), x.view(np.uint32))


def test_delta_all_key_axes_source_skips_the_delta():
    c = codeclib.get("delta-f32")
    x = np.random.RandomState(1).randn(16).astype(np.float32)
    wire, _ = c.encode(x, delta_ok=False)
    assert np.array_equal(wire, x.view(np.uint32))
    back = np.asarray(c.decode(jnp.asarray(wire), (), np.float32,
                               delta_ok=False))
    assert np.array_equal(back, x)


def test_int8_roundtrip_within_half_scale():
    c = codeclib.get("int8")
    x = np.random.RandomState(2).randn(8, 32).astype(np.float32) * 5
    wire, (scale, zp) = c.encode(x)
    assert wire.dtype == np.uint8
    back = np.asarray(c.decode(jnp.asarray(wire),
                               (jnp.float32(scale), jnp.float32(zp)),
                               np.float32))
    assert np.max(np.abs(back - x)) <= float(scale) / 2 + 1e-6


def test_int8_constant_slab_is_exact():
    c = codeclib.get("int8")
    x = np.full((4, 8), 3.25, np.float32)
    wire, (scale, zp) = c.encode(x)
    back = np.asarray(c.decode(jnp.asarray(wire),
                               (jnp.float32(scale), jnp.float32(zp)),
                               np.float32))
    assert np.array_equal(back, x)


# ---------------------------------------------------------------------
# streamed parity
# ---------------------------------------------------------------------

def test_streamed_delta_bit_identical(mesh):
    x = np.random.RandomState(3).randn(*SHAPE).astype(np.float32)
    raw = np.asarray(_src(x, mesh).sum().toarray())
    enc = np.asarray(_src(x, mesh, codec="delta-f32").sum().toarray())
    assert np.array_equal(raw, enc)


def test_streamed_delta_uneven_tail_and_tiny_slabs(mesh):
    x = np.random.RandomState(4).randn(19, 8, 8).astype(np.float32)
    raw = np.asarray(_src(x, mesh, chunks=4).mean().toarray())
    enc = np.asarray(_src(x, mesh, chunks=4,
                          codec="delta-f32").mean().toarray())
    assert np.array_equal(raw, enc)
    raw1 = np.asarray(_src(x, mesh, chunks=1).sum().toarray())
    enc1 = np.asarray(_src(x, mesh, chunks=1,
                           codec="delta-f32").sum().toarray())
    assert np.array_equal(raw1, enc1)


def test_streamed_fromiter_delta_bit_identical(mesh):
    x = _intdata()
    blocks = [x[i:i + 16] for i in range(0, SHAPE[0], 16)]
    raw = np.asarray(bolt.fromiter(
        [b for b in blocks], x.shape, mesh,
        dtype=np.float32).sum().toarray())
    enc = np.asarray(bolt.fromiter(
        [b for b in blocks], x.shape, mesh, dtype=np.float32,
        codec="delta-f32").sum().toarray())
    assert np.array_equal(raw, enc)


def test_streamed_bf16_within_documented_envelope(mesh):
    x = _posdata()
    raw = np.asarray(_src(x, mesh).sum().toarray())
    enc = np.asarray(_src(x, mesh, codec="bf16").sum().toarray())
    _, bound = _precision.codec_bound("bf16")
    assert np.allclose(enc, raw, rtol=bound)
    assert not np.array_equal(enc, raw)     # genuinely lossy opt-in


def test_streamed_f16_within_documented_envelope(mesh):
    x = _posdata()
    raw = np.asarray(_src(x, mesh).mean().toarray())
    enc = np.asarray(_src(x, mesh, codec="f16").mean().toarray())
    _, bound = _precision.codec_bound("f16")
    assert np.allclose(enc, raw, rtol=bound)


def test_streamed_int8_within_slab_scale_bound(mesh):
    x = _posdata()
    raw = np.asarray(_src(x, mesh).sum().toarray())
    enc = np.asarray(_src(x, mesh, codec="int8").sum().toarray())
    # worst case: half a quantisation step per record, summed — derive
    # the concrete bound from the data's range like the docstring says
    step = (x.max() - x.min()) / 255.0
    assert np.max(np.abs(enc - raw)) <= step / 2 * SHAPE[0] + 1e-4


def test_streamed_multi_stat_delta_bit_identical(mesh):
    x = np.random.RandomState(5).randn(*SHAPE).astype(np.float32)
    raw = _src(x, mesh).stats("sum", "var", "min")
    enc = _src(x, mesh, codec="delta-f32").stats("sum", "var", "min")
    for k in raw:
        assert np.array_equal(np.asarray(raw[k].toarray()),
                              np.asarray(enc[k].toarray())), k


def test_streamed_stages_and_filter_ride_the_codec(mesh):
    x = _intdata()
    raw = np.asarray(_src(x, mesh).map(lambda v: v * 2).filter(
        lambda v: v.sum() > 0).sum().toarray())
    enc = np.asarray(_src(x, mesh, codec="delta-f32").map(
        lambda v: v * 2).filter(lambda v: v.sum() > 0).sum().toarray())
    assert np.array_equal(raw, enc)


# ---------------------------------------------------------------------
# refusals
# ---------------------------------------------------------------------

def test_lossy_codec_refuses_order_stats_pointedly(mesh):
    x = _posdata()
    with pytest.raises(ValueError) as ei:
        _src(x, mesh, codec="bf16").stats("min")
    msg = str(ei.value)
    assert "order-statistic" in msg and "delta-f32" in msg
    with pytest.raises(ValueError):
        _src(x, mesh, codec="int8").stats("sum", "max")


def test_lossless_codec_allows_order_stats(mesh):
    x = np.random.RandomState(6).randn(*SHAPE).astype(np.float32)
    raw = _src(x, mesh).stats("min", "max")
    enc = _src(x, mesh, codec="delta-f32").stats("min", "max")
    for k in raw:
        assert np.array_equal(np.asarray(raw[k].toarray()),
                              np.asarray(enc[k].toarray()))


def test_lossy_codec_refuses_integer_stream_pointedly(mesh):
    x = (np.arange(np.prod(SHAPE)) % 7).astype(np.int32).reshape(SHAPE)
    with pytest.raises(ValueError) as ei:
        _src(x, mesh, codec="bf16").sum().toarray()
    assert "int32" in str(ei.value)


def test_sidecar_codec_error_names_the_pod_rule(monkeypatch):
    from bolt_tpu.parallel import multihost
    monkeypatch.setattr(multihost, "mesh_process_count", lambda m: 3)
    msg = multihost.sidecar_codec_error(codeclib.get("int8"), None)
    assert "int8" in msg and "sidecar" in msg and "bf16" in msg
    assert multihost.sidecar_codec_error(codeclib.get("bf16"),
                                         None) is None
    assert multihost.sidecar_codec_error(None, None) is None


def test_unknown_codec_refused_at_scope_and_source(mesh):
    with pytest.raises(ValueError):
        with stream.codec("lz4"):
            pass
    # pointed at the CONSTRUCTION boundary (a typo must not surface as
    # a checker crash or a first-terminal surprise — review finding)
    x = _posdata()
    with pytest.raises(ValueError) as ei:
        _src(x, mesh, codec="lz4")
    assert "unknown codec" in str(ei.value)
    with pytest.raises(ValueError):
        bolt.fromiter([x], x.shape, mesh, dtype=np.float32, codec="lz4")


def test_checker_never_crashes_on_a_hand_built_bad_codec(mesh):
    # the public doors all validate; a hand-built source with a bogus
    # name must degrade to "no forecast", never crash check() — the
    # run itself still refuses at resolve_codec
    src = _src(_posdata(), mesh)
    src._stream.codec = "bogus"
    rep = analysis.check(src)
    assert not rep.has("BLT016")
    assert analysis.admission_floor_bytes(src) is not None
    with pytest.raises(ValueError):
        src.sum().toarray()


def test_serve_propagates_the_submitters_codec_scope(mesh):
    """`with stream.codec(...)` around serve.submit: the scope is
    thread-local, so the server re-enters the SUBMITTER's effective
    codec on the worker thread — the tenant's opt-in is honoured and
    the admission floor (priced on the submit thread) matches what the
    run actually leases (review finding)."""
    from bolt_tpu import serve
    x = _posdata()
    with serve.serving(workers=1, budget_bytes=64 << 20) as sv:
        c0 = engine.counters()
        with stream.codec("bf16"):
            fut = sv.submit(_src(x, mesh).sum(), tenant="scoped")
            out = np.asarray(fut.result(timeout=120).toarray())
        c1 = engine.counters()
        assert sv.stats()["arbiter"]["in_use_bytes"] == 0
    # the worker streamed ENCODED: wire bytes are half the raw bytes
    assert c1["transfer_bytes"] - c0["transfer_bytes"] == x.nbytes // 2
    assert c1["codec_bytes_wire"] - c0["codec_bytes_wire"] \
        == x.nbytes // 2
    raw = np.asarray(_src(x, mesh).sum().toarray())
    _, bound = _precision.codec_bound("bf16")
    assert np.allclose(out, raw, rtol=bound)


# ---------------------------------------------------------------------
# scopes, counters, arbiter
# ---------------------------------------------------------------------

def test_codec_scope_is_thread_local(mesh):
    seen = {}

    def other():
        seen["other"] = stream.current_codec()

    with stream.codec("bf16"):
        assert stream.current_codec() == "bf16"
        with stream.codec(None):
            assert stream.current_codec() is None
        th = threading.Thread(target=other)
        th.start()
        th.join()
    assert seen["other"] is None
    assert stream.current_codec() is None


def test_set_codec_process_default_scopes_override():
    try:
        stream.set_codec("delta-f32")
        assert stream.current_codec() == "delta-f32"
        with stream.codec(None):
            assert stream.current_codec() is None
    finally:
        stream.set_codec(None)
    with pytest.raises(ValueError):
        stream.set_codec("nope")


def test_source_codec_wins_over_scope(mesh):
    x = _posdata()
    src = _src(x, mesh, codec="bf16")
    with stream.codec("delta-f32"):
        assert stream.resolve_codec(src._stream).name == "bf16"
    assert stream.resolve_codec(_src(x, mesh)._stream) is None
    with stream.codec("delta-f32"):
        assert stream.resolve_codec(
            _src(x, mesh)._stream).name == "delta-f32"


def test_wire_bytes_and_codec_counters(mesh):
    x = _posdata()
    c0 = engine.counters()
    first = np.asarray(_src(x, mesh, codec="bf16").sum().toarray())
    c1 = engine.counters()
    wire = c1["transfer_bytes"] - c0["transfer_bytes"]
    # the transfer counters tally the WIRE bytes: half the raw f32
    assert wire == x.nbytes // 2
    assert c1["codec_bytes_raw"] - c0["codec_bytes_raw"] == x.nbytes
    assert c1["codec_bytes_wire"] - c0["codec_bytes_wire"] \
        == x.nbytes // 2
    assert c1["codec_encode_seconds"] > c0["codec_encode_seconds"]
    # a second encoded pass compiles nothing and answers the same bits
    again = np.asarray(_src(x, mesh, codec="bf16").sum().toarray())
    c2 = engine.counters()
    assert c2["misses"] == c1["misses"]
    assert c2["aot_compiles"] == c1["aot_compiles"]
    assert np.array_equal(first, again)


def test_admission_floor_recomputes_via_codec_ratio(mesh):
    x = _posdata()
    raw_floor = analysis.admission_floor_bytes(_src(x, mesh))
    bf16_floor = analysis.admission_floor_bytes(
        _src(x, mesh, codec="bf16"))
    i8_floor = analysis.admission_floor_bytes(
        _src(x, mesh, codec="int8"))
    assert bf16_floor == raw_floor // 2
    assert i8_floor == raw_floor // 4
    # the scope form reshapes the floor too (thread-local at check time)
    with stream.codec("bf16"):
        assert analysis.admission_floor_bytes(
            _src(x, mesh)) == raw_floor // 2


def test_arbiter_leases_wire_bytes_and_returns_them(mesh):
    from bolt_tpu import serve
    x = _posdata()
    with serve.serving(workers=1, budget_bytes=32 << 20) as sv:
        fut = sv.submit(_src(x, mesh, codec="bf16").sum(), tenant="c")
        out = np.asarray(fut.result(timeout=120).toarray())
        assert sv.stats()["arbiter"]["in_use_bytes"] == 0
    raw = np.asarray(_src(x, mesh).sum().toarray())
    _, bound = _precision.codec_bound("bf16")
    assert np.allclose(out, raw, rtol=bound)


def test_codec_span_hygiene_and_names(mesh):
    x = _posdata()
    obs.clear()
    obs.enable()
    try:
        _src(x, mesh, codec="int8").sum().toarray()
        assert obs.active_count() == 0
        names = {s.name for s in obs.spans()}
        assert "stream.encode" in names and "stream.decode" in names
        enc = [s for s in obs.spans() if s.name == "stream.encode"]
        assert all(s.attrs.get("codec") == "int8" for s in enc)
        assert all(s.attrs.get("bytes_wire", 0)
                   < s.attrs.get("bytes_raw", 0) for s in enc)
    finally:
        obs.disable()


# ---------------------------------------------------------------------
# fault paths: chaos seam, retry fence, checkpoint consistency
# ---------------------------------------------------------------------

def test_chaos_encode_raise_retries_in_place(mesh):
    x = _intdata()
    clean = np.asarray(_src(x, mesh, codec="int8").sum().toarray())
    _chaos.inject("stream.encode", nth=3)
    c0 = engine.counters()
    try:
        with stream.retries(1):
            got = np.asarray(_src(x, mesh,
                                  codec="int8").sum().toarray())
    finally:
        _chaos.clear()
    c1 = engine.counters()
    assert np.array_equal(got, clean)
    assert c1["stream_retries"] - c0["stream_retries"] == 1


def test_chaos_encode_exhausted_budget_chains_original(mesh):
    x = _intdata()
    _chaos.inject("stream.encode", nth=2, times=None)
    try:
        with stream.retries(1):
            with pytest.raises(RuntimeError) as ei:
                _src(x, mesh, codec="int8").sum().toarray()
    finally:
        _chaos.clear()
    # the exhausted-budget error chains back to the ORIGINAL ChaosError
    exc = ei.value
    seen = []
    while exc is not None:
        seen.append(type(exc).__name__)
        exc = exc.__cause__
    assert "ChaosError" in seen


def test_chaos_encode_failfast_keeps_original_at_budget_zero(mesh):
    x = _intdata()
    _chaos.inject("stream.encode", nth=2)
    try:
        with pytest.raises(_chaos.ChaosError):
            _src(x, mesh, codec="int8").sum().toarray()
    finally:
        _chaos.clear()


def test_int8_resume_sidecar_scales_checkpoint_consistent(mesh):
    """A killed int8-encoded run resumes BIT-IDENTICALLY to the clean
    int8 run: encode is deterministic per block, so the resumed tail's
    sidecar scales equal the ones the clean run derived — the fold
    state and the re-encoded slabs line up exactly."""
    x = _posdata()
    clean = np.asarray(_src(x, mesh, codec="int8").sum().toarray())
    d = tempfile.mkdtemp(prefix="bolt-codec-resume-")
    _chaos.inject("stream.upload", nth=5)
    try:
        with stream.uploaders(1):
            _src(x, mesh, codec="int8", ck=d).sum().cache()
        raise AssertionError("chaos child was supposed to die")
    except _chaos.ChaosError:
        pass
    finally:
        _chaos.clear()
    assert ckptlib.stream_pending(d)
    meta = json.load(open(os.path.join(d, "stream_meta.json")))
    assert meta.get("codec") == "int8"       # the audit-trail row
    c0 = engine.counters()
    resumed = np.asarray(_src(x, mesh, codec="int8",
                              ck=d).sum().toarray())
    c1 = engine.counters()
    assert c1["stream_resumes"] - c0["stream_resumes"] == 1
    assert np.array_equal(resumed, clean)
    assert not ckptlib.stream_pending(d)


def test_int8_kill9_resume_bit_identical_to_clean_encoded():
    """The subprocess preemption proof over an int8-encoded source:
    kill -9 mid-run, restart, resume — bit-identical to the clean
    encoded child (the satellite's sidecar-consistency gate)."""
    from bolt_tpu.utils import load_script
    cr = load_script("chaos_run")
    wd = tempfile.mkdtemp(prefix="bolt-codec-kill-")
    ck = os.path.join(wd, "ck")
    clean_out = os.path.join(wd, "clean.npy")
    res_out = os.path.join(wd, "resumed.npy")
    proc = cr._run_stream_child(ck, clean_out, codec="int8")
    assert proc.returncode == 0, proc.stderr
    proc = cr._run_stream_child(ck, res_out,
                                arm="stream.upload:6:kill",
                                codec="int8")
    assert proc.returncode == -9, (proc.returncode, proc.stderr)
    assert ckptlib.stream_pending(ck)
    proc = cr._run_stream_child(ck, res_out, codec="int8")
    assert proc.returncode == 0, proc.stderr
    with open(res_out + ".json") as f:
        resumed = json.load(f)
    assert resumed["resumes"] >= 1
    assert np.array_equal(np.load(clean_out), np.load(res_out))
    assert not ckptlib.stream_pending(ck)


def test_codec_change_restarts_instead_of_resuming(mesh):
    x = _posdata()
    d = tempfile.mkdtemp(prefix="bolt-codec-switch-")
    _chaos.inject("stream.upload", nth=5)
    try:
        with stream.uploaders(1):
            _src(x, mesh, codec="int8", ck=d).sum().cache()
    except _chaos.ChaosError:
        pass
    finally:
        _chaos.clear()
    assert ckptlib.stream_pending(d)
    c0 = engine.counters()
    got = np.asarray(_src(x, mesh, codec="delta-f32",
                          ck=d).sum().toarray())
    c1 = engine.counters()
    # fingerprint mismatch: the int8 checkpoint is ignored, the run
    # restarts from slab 0 under the new codec — never resumed wrong
    assert c1["stream_resumes"] - c0["stream_resumes"] == 0
    assert np.array_equal(got, np.asarray(_src(x, mesh).sum().toarray()))


# ---------------------------------------------------------------------
# analysis: BLT016
# ---------------------------------------------------------------------

def test_blt016_forecasts_bytes_saved_zero_compiles(mesh):
    x = _posdata()
    arr = _src(x, mesh, codec="bf16").map(lambda v: v + 1)
    c0 = engine.counters()
    rep = analysis.check(arr)
    c1 = engine.counters()
    assert c1["misses"] - c0["misses"] == 0
    assert c1["aot_compiles"] - c0["aot_compiles"] == 0
    assert rep.has("BLT016")
    d = next(d for d in rep.diagnostics if d.code == "BLT016")
    assert d.severity == "info" and "bf16" in d.message
    assert "0.50x" in d.message


def test_blt016_lossless_notes_bit_identity(mesh):
    rep = analysis.check(_src(_posdata(), mesh, codec="delta-f32"))
    d = next(d for d in rep.diagnostics if d.code == "BLT016")
    assert "bit-identical" in d.message


def test_blt016_warns_lossy_meets_order_member(mesh):
    # the pending-group walk knows the member names: handles created
    # codec-free, then CHECKED under a lossy scope — the checker
    # forecasts the refusal the executor would raise, as a WARNING
    x = np.random.RandomState(10).randn(*SHAPE).astype(np.float32)
    h = _src(x, mesh).stats("sum", "min")["min"]
    with stream.codec("bf16"):
        rep = analysis.check(h)
    d = next(d for d in rep.diagnostics if d.code == "BLT016")
    assert d.severity == "warning" and "min" in d.message
    h.toarray()                             # scope gone: resolves raw


def test_blt016_info_for_lossless_order_member(mesh):
    x = np.random.RandomState(11).randn(*SHAPE).astype(np.float32)
    src = _src(x, mesh, codec="delta-f32")
    h = src.stats("sum", "min")["min"]      # lossless: allowed
    rep = analysis.check(h)
    d = next(d for d in rep.diagnostics if d.code == "BLT016")
    assert d.severity == "info"
    h.toarray()


def test_blt016_warns_unsupported_dtype(mesh):
    x = (np.arange(np.prod(SHAPE)) % 7).astype(np.int32).reshape(SHAPE)
    with stream.codec("bf16"):
        rep = analysis.check(_src(x, mesh))
    d = next(d for d in rep.diagnostics if d.code == "BLT016")
    assert d.severity == "warning" and "refuse" in d.message


def test_no_codec_no_blt016(mesh):
    rep = analysis.check(_src(_posdata(), mesh))
    assert not rep.has("BLT016")
