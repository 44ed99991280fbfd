"""Streaming out-of-core executor (ISSUE 3): the parity suite plus the
pipeline's operational contracts.

Parity is the load-bearing half: streamed ``map/sum/mean/var/std/
filter(...).sum()/reduce`` must agree with BOTH the local (NumPy) oracle
and the materialised TPU path.  Integer-valued float64 data makes
``sum``/``mean`` exact under ANY fold order, so those compare
bit-identically; a crafted equal-slab-mean dataset makes the Welford/
Chan moment merge exact too, so ``mean/var/std`` ALSO compare
bit-identically there; random data covers the general case at f64
tolerance.  Geometry edges ride along: uneven last slabs, 1-record
slabs, ragged value-chunk plans, halo padding.

Operational contracts: laziness (no callback call before a consumer),
engine counters (the per-slab executable compiles EXACTLY once across a
uniform stream; transfer bytes are exact), overlap (ingest demonstrably
hidden behind compute: ``overlap_efficiency > 0``), fault injection (a
mid-stream source failure joins the prefetch thread, releases the ring
and re-raises the original exception), the BLT105 lint rule, and the
abstract checker's streaming-plan support.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bolt_tpu as bolt
from bolt_tpu import analysis, engine, obs, profile, stream
from bolt_tpu.tpu.array import BoltArrayTPU


N, V0, V1 = 16, 6, 4
SHAPE = (N, V0, V1)


def _intdata():
    """Integer-valued float64: sums are exact under any fold order."""
    return ((np.arange(np.prod(SHAPE)) % 13) - 6).astype(
        np.float64).reshape(SHAPE)


def _source(data, mesh, chunks):
    return bolt.fromcallback(lambda idx: data[idx], data.shape, mesh,
                             dtype=data.dtype, chunks=chunks)


ADD1 = lambda v: v + 1.0
DOUBLE = lambda blk: blk * 2.0
POSSUM = lambda v: v.sum() > 0


# ---------------------------------------------------------------------
# the out-of-core parity suite (satellite: streamed vs local vs
# materialised TPU, uneven last chunks, chunk sizes of 1)
# ---------------------------------------------------------------------

@pytest.mark.parametrize("chunks", [1, 4, 5, 16])
def test_stream_sum_mean_parity_bitexact(mesh, chunks):
    data = _intdata()
    src = _source(data, mesh, chunks)
    streamed_sum = np.asarray(src.map(ADD1).sum().toarray())
    streamed_mean = np.asarray(_source(data, mesh, chunks)
                               .map(ADD1).mean().toarray())
    # local oracle
    lo = bolt.array(data).map(ADD1, axis=(0,))
    assert np.array_equal(streamed_sum, np.asarray(lo.sum(axis=0)))
    # materialised TPU path (same chain, standard programs)
    mat = bolt.array(data, mesh).map(ADD1)
    assert np.array_equal(streamed_sum, np.asarray(mat.sum().toarray()))
    want_mean = np.asarray(mat.mean().toarray())
    if N % chunks == 0:
        # even power-of-two slab structure: every Chan-merge denominator
        # is a power of two, so the streamed mean is BIT-identical
        assert np.array_equal(streamed_mean, want_mean)
    else:
        # ragged tail (slabs 5,5,5,1): s/5 rounds — ULP-level agreement
        assert np.allclose(streamed_mean, want_mean, rtol=1e-14,
                           atol=1e-14)


@pytest.mark.parametrize("chunks", [1, 3, 4])
def test_stream_var_std_parity(mesh, chunks):
    rs = np.random.RandomState(3)
    data = rs.randn(*SHAPE)
    for name, kw in (("var", {}), ("std", {}), ("var", {"ddof": 1}),
                     ("std", {"ddof": 1})):
        got = np.asarray(getattr(_source(data, mesh, chunks), name)(
            **kw).toarray())
        want_local = getattr(np, name)(data, axis=0, **kw)
        want_mat = np.asarray(getattr(bolt.array(data, mesh), name)(
            **kw).toarray())
        assert np.allclose(got, want_local, rtol=1e-12, atol=1e-12)
        assert np.allclose(got, want_mat, rtol=1e-12, atol=1e-12)


def test_stream_welford_bitexact_crafted(mesh):
    # every slab holds equal counts of 3.0 and 7.0 per value slot, so
    # slab means are exactly 5.0, Chan deltas are exactly 0, and every
    # moment intermediate is exactly representable — streamed mean/var/
    # std must be BIT-identical to the materialised path
    data = np.where((np.arange(N) % 2 == 0)[:, None, None],
                    3.0, 7.0) * np.ones(SHAPE)
    src_kw = dict(chunks=4)                 # slabs of 4: 2+2 per slab
    mat = bolt.array(data, mesh)
    for name in ("mean", "var", "std"):
        got = np.asarray(getattr(_source(data, mesh, **src_kw),
                                 name)().toarray())
        want = np.asarray(getattr(mat, name)().toarray())
        assert np.array_equal(got, want), name
        assert np.array_equal(got, getattr(np, "mean" if name == "mean"
                                           else name)(data, axis=0)), name


@pytest.mark.parametrize("chunks", [1, 4, 7])
def test_stream_filter_sum_parity(mesh, chunks):
    data = _intdata()
    got = np.asarray(_source(data, mesh, chunks)
                     .filter(POSSUM).sum().toarray())
    keep = data[data.sum(axis=(1, 2)) > 0]
    assert np.array_equal(got, keep.sum(axis=0))
    # materialised twin: the PR-1 fused filter->sum terminal
    mat = np.asarray(bolt.array(data, mesh).filter(POSSUM).sum().toarray())
    assert np.array_equal(got, mat)


def test_stream_filter_all_false_and_empty_mean(mesh):
    data = _intdata()
    never = lambda v: v.sum() > 1e9
    got = np.asarray(_source(data, mesh, 4).filter(never).sum().toarray())
    assert np.array_equal(got, np.zeros((V0, V1)))    # identity fold
    m = np.asarray(_source(data, mesh, 4).filter(never).mean().toarray())
    assert np.all(np.isnan(m))                        # 0/0, like the
    mat = np.asarray(bolt.array(data, mesh).filter(never).mean().toarray())
    assert np.all(np.isnan(mat))                      # fused terminal


def test_stream_filter_mean_parity(mesh):
    data = _intdata()
    got = np.asarray(_source(data, mesh, 4).filter(POSSUM).mean().toarray())
    keep = data[data.sum(axis=(1, 2)) > 0]
    assert np.allclose(got, keep.mean(axis=0), rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("func", [np.maximum, np.minimum])
def test_stream_reduce_parity(mesh, func):
    data = _intdata()
    got = np.asarray(_source(data, mesh, 5).reduce(func).toarray())
    want = func.reduce(data, axis=0)
    assert np.array_equal(got, want)
    mat = np.asarray(bolt.array(data, mesh).reduce(func).toarray())
    assert np.array_equal(got, mat)


@pytest.mark.parametrize("size,axis", [((3,), (0,)), ((4, 3), (0, 1)),
                                       ((5,), (0,))])
def test_stream_chunked_map_parity(mesh, size, axis):
    # (5,) over a 6-long axis is a RAGGED plan: the general (clamp-
    # category) body runs per slab, identically to the materialised one
    data = _intdata()
    got = np.asarray(_source(data, mesh, 4)
                     .chunk(size=size, axis=axis).map(DOUBLE)
                     .sum().toarray())
    mat = bolt.array(data, mesh).chunk(size=size, axis=axis).map(DOUBLE)
    assert np.array_equal(got, np.asarray(mat.sum().toarray()))
    assert np.array_equal(got, (data * 2).sum(axis=0))


def test_stream_chunked_map_padding_parity(mesh):
    # halo padding: shape-preserving func, halos trimmed — the general
    # body per slab must agree with the materialised program
    data = _intdata()
    smooth = lambda blk: blk * 0.5
    got = np.asarray(_source(data, mesh, 4)
                     .chunk(size=(3,), axis=(0,), padding=(1,))
                     .map(smooth).mean().toarray())
    mat = bolt.array(data, mesh).chunk(size=(3,), axis=(0,),
                                       padding=(1,)).map(smooth)
    assert np.array_equal(got, np.asarray(mat.mean().toarray()))


def test_stream_chunked_shape_changing_map(mesh):
    # uniform plans allow per-block shape changes; the streamed view's
    # plan metadata must match the materialised one
    data = _intdata()
    colsum = lambda blk: blk.sum(axis=0, keepdims=True)
    sv = _source(data, mesh, 4).chunk(size=(3, V1), axis=(0, 1)).map(colsum)
    mv = bolt.array(data, mesh).chunk(size=(3, V1), axis=(0, 1)).map(colsum)
    assert sv.plan == mv.plan
    assert np.array_equal(np.asarray(sv.sum().toarray()),
                          np.asarray(mv.sum().toarray()))


def test_stream_stacked_map_parity(mesh):
    data = _intdata()
    zblock = lambda blk: blk - blk.mean(axis=0)    # mixes records IN a block
    # aligned: slab (8) is a multiple of the stack size (4) -> streams
    sv = _source(data, mesh, 8).stacked(4).map(zblock)
    assert sv.unstack().streaming
    mat = bolt.array(data, mesh).stacked(4).map(zblock)
    assert np.array_equal(np.asarray(sv.unstack().sum().toarray()),
                          np.asarray(mat.unstack().sum().toarray()))
    # misaligned (slab 6, size 4): block grouping would differ, so the
    # stage is refused and the map materialises — results still agree
    sv2 = _source(data, mesh, 6).stacked(4).map(zblock)
    assert not sv2.unstack().streaming
    assert np.array_equal(np.asarray(sv2.unstack().sum().toarray()),
                          np.asarray(mat.unstack().sum().toarray()))


def test_fromiter_parity_and_errors(mesh):
    data = _intdata()
    blocks = [data[0:5], data[5:6], data[6:16]]     # ragged block sizes
    it = bolt.fromiter(blocks, SHAPE, mesh, dtype=np.float64)
    assert it.streaming
    assert np.array_equal(np.asarray(it.sum().toarray()),
                          data.sum(axis=0))
    # a list re-streams; materialisation assembles on host
    assert np.array_equal(it.toarray(), data)
    # local twin
    lo = bolt.fromiter(blocks, SHAPE, dtype=np.float64)
    assert lo.mode == "local" and np.array_equal(np.asarray(lo), data)
    with pytest.raises(ValueError, match="explicit dtype"):
        bolt.fromiter(blocks, SHAPE, mesh)
    with pytest.raises(ValueError, match="cover only"):
        bolt.fromiter([data[0:5]], SHAPE, mesh,
                      dtype=np.float64).sum().cache()
    with pytest.raises(ValueError, match="overrun"):
        bolt.fromiter([data, data[:1]], SHAPE, mesh,
                      dtype=np.float64).sum().cache()


def test_stream_map_dtype_and_cast_stage(mesh):
    data = _intdata()
    out = _source(data, mesh, 4).map(ADD1, dtype=np.float32)
    assert out.streaming and out.dtype == np.float32
    got = np.asarray(out.sum().toarray())
    want = (data + 1).astype(np.float32).sum(axis=0, dtype=np.float32)
    assert np.allclose(got, want, rtol=1e-6)


# ---------------------------------------------------------------------
# laziness and materialisation
# ---------------------------------------------------------------------

def test_fromcallback_explicit_dtype_is_lazy(mesh):
    data = _intdata()
    calls = []

    def loader(idx):
        calls.append(idx)
        return data[idx]

    b = bolt.fromcallback(loader, SHAPE, mesh, dtype=np.float64, chunks=4)
    assert b.streaming and calls == []          # nothing produced yet
    assert b.shape == SHAPE and b.dtype == np.float64 and calls == []
    b.sum().cache()                             # the read streams: 4 slabs
    assert len(calls) == 4
    assert all(isinstance(s, slice) for idx in calls for s in idx)
    calls.clear()
    # a non-streaming consumer materialises per device shard
    assert np.array_equal(b.toarray(), data)
    assert len(calls) == len(mesh.devices.ravel())
    assert not b.streaming                      # adopted concrete state
    # dtype=None keeps the eager contract (type inferred from blocks)
    calls.clear()
    e = bolt.fromcallback(loader, SHAPE, mesh)
    assert not e.streaming and len(calls) == len(mesh.devices.ravel())


def test_stream_filtered_shape_materialises(mesh):
    data = _intdata()
    f = _source(data, mesh, 4).filter(POSSUM)
    assert f.streaming and f.dtype == np.float64
    want = data[data.sum(axis=(1, 2)) > 0]
    assert f.shape == want.shape                # materialises + count sync
    assert np.array_equal(f.toarray(), want)


# ---------------------------------------------------------------------
# engine counters: exact transfer accounting, compile-exactly-once
# ---------------------------------------------------------------------

def test_stream_counters_and_compile_once(mesh):
    def add_one(v):                             # stable identity per run
        return v + 1.0

    # geometry UNIQUE to this test, so every engine key is fresh
    data = ((np.arange(12 * 3 * 5) % 11) - 5).astype(
        np.float64).reshape(12, 3, 5)

    c0 = engine.counters()
    src = _source(data, mesh, 3)                # 4 even slabs
    out = src.map(add_one).sum().cache()        # the read streams (lazy)
    c1 = engine.counters()
    d = {k: c1[k] - c0[k] for k in c1}
    assert d["stream_chunks"] == 4
    assert d["transfer_bytes"] == data.nbytes
    assert c1["stream_prefetch_depth"] >= 1
    assert c1["stream_upload_threads"] >= 1
    assert c1["stream_inflight_high_water"] >= 1
    # EXACTLY one executable per program: the per-slab partial (even
    # slabs), its acc-fused twin (odd slabs — the level-0 fold fused
    # into the slab dispatch), and ONE tree merge.  Dispatches are
    # 4 slabs + 1 level-1 merge — the level-0 merges cost nothing,
    # vs 4 + 3 before the fusion (>= 2x fewer fold dispatches).
    assert d["misses"] == 3 and d["aot_compiles"] == 3
    assert d["dispatches"] == 4 + 1
    assert d["stream_ingest_seconds"] > 0
    assert d["stream_wall_seconds"] > 0
    # a second identical run reuses ALL executables: zero new compiles
    c2 = engine.counters()
    out2 = _source(data, mesh, 3).map(add_one).sum().cache()
    c3 = engine.counters()
    d2 = {k: c3[k] - c2[k] for k in c3}
    assert d2["misses"] == 0 and d2["aot_compiles"] == 0
    assert d2["dispatches"] == 4 + 1
    assert np.array_equal(np.asarray(out.toarray()),
                          np.asarray(out2.toarray()))


def test_stream_prefetch_depth_scope():
    before = stream.prefetch_depth()
    assert before >= 1
    with stream.prefetch(5):
        assert stream.prefetch_depth() == 5
    assert stream.prefetch_depth() == before
    stream.set_prefetch_depth(0)            # clamped to >= 1
    assert stream.prefetch_depth() == 1
    stream.set_prefetch_depth(before)


# ---------------------------------------------------------------------
# overlap: transfer demonstrably hidden behind compute
# ---------------------------------------------------------------------

def test_stream_overlap_efficiency_positive(mesh):
    n, d0 = 12, 128
    data = np.arange(n * d0 * d0, dtype=np.float64).reshape(
        (n, d0, d0)) % 7

    def slow_loader(idx):
        time.sleep(0.004)                       # host ingest cost
        return data[idx]

    def heavy(v):                               # real device compute
        for _ in range(6):
            v = jnp.tanh(v @ v.T)
        return v

    src = bolt.fromcallback(slow_loader, data.shape, mesh,
                            dtype=np.float64, chunks=2)
    # the wall-clock overlap is physical but probabilistic under heavy
    # machine load (a saturated host can serialise the prefetch thread
    # behind compute); a couple of retries keep the assertion about the
    # PIPELINE, not the scheduler
    d = None
    for _ in range(3):
        c0 = engine.counters()
        src.map(heavy).sum().cache()
        c1 = engine.counters()
        d = {k: c1[k] - c0[k] for k in c1}
        assert d["stream_chunks"] == 6
        if d["stream_overlap_seconds"] > 0.0:
            break
    # the prefetch thread ingested slab i+1 while the executable ran on
    # slab i: ingest + compute strictly exceeds the wall clock
    assert d["stream_overlap_seconds"] > 0.0
    eff = d["stream_overlap_seconds"] / d["stream_ingest_seconds"]
    assert eff > 0.0
    # the cumulative counter view agrees
    assert profile.overlap_efficiency() > 0.0


# ---------------------------------------------------------------------
# fault injection: mid-stream failures abort cleanly
# ---------------------------------------------------------------------

def test_stream_fault_mid_stream_aborts_cleanly(mesh):
    data = _intdata()
    boom = RuntimeError("storage went away")
    seen = []

    def flaky(idx):
        seen.append(idx)
        if len(seen) == 3:
            raise boom
        return data[idx]

    src = bolt.fromcallback(flaky, SHAPE, mesh, dtype=np.float64,
                            chunks=4)
    threads_before = threading.active_count()
    with pytest.raises(RuntimeError) as ei:
        src.sum().cache()                       # the read streams (lazy)
    assert ei.value is boom                     # the ORIGINAL exception
    # prefetch thread joined, no leak
    assert stream._LAST_THREAD is not None
    assert not stream._LAST_THREAD.is_alive()
    assert threading.active_count() <= threads_before
    # the executor is not poisoned: a healthy stream runs right after
    ok = np.asarray(_source(data, mesh, 4).sum().toarray())
    assert np.array_equal(ok, data.sum(axis=0))


def test_stream_fault_bad_block_shape(mesh):
    bad = bolt.fromcallback(lambda idx: np.zeros((1, 1)), SHAPE, mesh,
                            dtype=np.float64, chunks=4)
    with pytest.raises(ValueError, match="returned shape"):
        bad.sum().cache()
    assert not stream._LAST_THREAD.is_alive()


def test_stream_materialise_failure_is_retryable(mesh):
    # a TRANSIENT source failure during materialisation must leave the
    # array streaming (not half-cleared): the retry re-raises nothing
    # and succeeds, instead of crashing on None state
    data = _intdata()
    calls = []

    def flaky(idx):
        calls.append(idx)
        if len(calls) == 1:
            raise IOError("storage hiccup")
        return data[idx]

    src = bolt.fromcallback(flaky, SHAPE, mesh, dtype=np.float64,
                            chunks=SHAPE[0])
    with pytest.raises(IOError, match="storage hiccup"):
        src.toarray()                           # materialising consumer
    assert src.streaming                        # still a lazy source
    assert np.array_equal(np.asarray(src.toarray()), data)


def test_fromiter_exhausted_restream_raises_pointed_error(mesh):
    # generators are one-shot: a second streamed terminal must say SO,
    # not blame the block count ("cover only 0 of N records")
    data = _intdata()

    def gen():
        yield data[:SHAPE[0] // 2]
        yield data[SHAPE[0] // 2:]

    src = bolt.fromiter(gen(), SHAPE, mesh, dtype=np.float64)
    first = np.asarray(src.sum().toarray())
    assert np.array_equal(first, data.sum(axis=0))
    with pytest.raises(RuntimeError, match="already streamed"):
        src.sum().cache()
    # derived sources share the iterator (with_stage), so the budget is
    # shared too
    src2 = bolt.fromiter(gen(), SHAPE, mesh, dtype=np.float64)
    src2.map(lambda v: v * 2).sum().cache()
    with pytest.raises(RuntimeError, match="already streamed"):
        src2.sum().cache()
    # RE-ITERABLE sources (a list of blocks) stream repeatedly — the
    # guard is for one-shot iterators only
    lst = bolt.fromiter([data], SHAPE, mesh, dtype=np.float64)
    a = np.asarray(lst.sum().toarray())
    b = np.asarray(lst.sum().toarray())
    assert np.array_equal(a, b)


# ---------------------------------------------------------------------
# static analysis: streaming plans + BLT105
# ---------------------------------------------------------------------

def test_analysis_check_streaming_plan_zero_compiles(mesh):
    data = _intdata()
    p = (_source(data, mesh, 4).chunk(size=(3,), axis=(0,))
         .map(DOUBLE).filter(POSSUM))
    c0 = engine.counters()
    rep = analysis.check(p)
    c1 = engine.counters()
    compiled = (c1["misses"] - c0["misses"]
                + c1["aot_compiles"] - c0["aot_compiles"]
                + c1["dispatches"] - c0["dispatches"])
    assert compiled == 0
    assert "streaming" in rep.target
    assert rep.dynamic and rep.has("BLT008")
    assert rep.shape == (None, V0, V1)
    assert np.dtype(rep.dtype) == np.float64
    assert len(rep.stages) == 3                 # source, chunk-map, filter
    # a static streamed plan predicts exactly
    rep2 = analysis.check(_source(data, mesh, 4).map(ADD1))
    assert rep2.shape == SHAPE and not rep2.dynamic


def test_analysis_strict_gates_streamed_terminal(mesh):
    data = _intdata()
    base = _source(data, mesh, 4)
    # hand-append a NON-SCALAR predicate (the public filter() rejects it
    # eagerly): strict must refuse the streamed terminal before any
    # upload or compile
    src2 = base._stream.with_stage(("filter", lambda v: v > 0))
    arr = BoltArrayTPU._streamed(src2)
    c0 = engine.counters()
    with analysis.strict():
        with pytest.raises(analysis.PipelineError, match="BLT007"):
            arr.sum()
    c1 = engine.counters()
    assert c1["strict_rejections"] - c0["strict_rejections"] == 1
    assert c1["misses"] == c0["misses"]
    assert c1["transfer_bytes"] == c0["transfer_bytes"]
    # a healthy streamed terminal passes the gate
    with analysis.strict():
        out = _source(data, mesh, 4).sum()
    assert np.array_equal(np.asarray(out.toarray()), data.sum(axis=0))


def test_clone_preserves_stream_source(mesh):
    # functional forms (np.copy/np.sort/...) go through _clone: the
    # clone must share the lazy source, not become an unreadable husk
    data = _intdata()
    src = _source(data, mesh, 4)
    c = np.copy(src)
    assert np.array_equal(np.asarray(c), data)
    # the original is untouched and still streams
    assert src.streaming
    assert np.array_equal(np.asarray(src.sum().toarray()),
                          data.sum(axis=0))


def test_fromiter_rejects_missing_dtype_only_single_host(mesh):
    # the multihost guard message exists (can't build a multi-process
    # mesh here; the single-host path must NOT trip it)
    data = _intdata()
    out = bolt.fromiter([data], SHAPE, mesh, dtype=np.float64)
    assert out.streaming


@pytest.mark.lint
def test_lint_exemption_is_path_anchored():
    from bolt_tpu.analysis import astlint
    jitbad = "import jax\n\ndef f(g):\n    return jax.jit(g)\n"
    putbad = "import jax\n\ndef f(x):\n    return jax.device_put(x)\n"
    # files merely ENDING in an exempt name must not inherit the pass
    assert any(f.code == "BLT101"
               for f in astlint.lint_source(jitbad, "bolt_tpu/myengine.py"))
    assert any(f.code == "BLT105"
               for f in astlint.lint_source(putbad, "bolt_tpu/upstream.py"))
    # the real exempt files still pass
    assert not astlint.lint_source(jitbad, "bolt_tpu/engine.py")
    assert not astlint.lint_source(putbad, "bolt_tpu/stream.py")


@pytest.mark.lint
def test_blt105_device_put_rule():
    from bolt_tpu.analysis import astlint
    bad = "import jax\n\ndef f(x, s):\n    return jax.device_put(x, s)\n"
    found = astlint.lint_source(bad, "bolt_tpu/tpu/somewhere.py")
    assert any(f.code == "BLT105" for f in found)
    # alias-aware
    bad2 = ("from jax import device_put\n\n"
            "def f(x):\n    return device_put(x)\n")
    assert any(f.code == "BLT105"
               for f in astlint.lint_source(bad2, "bolt_tpu/x.py"))
    # the transfer layer itself is the sanctioned home
    assert not astlint.lint_source(bad, "bolt_tpu/stream.py")
    # and the whole package still lints clean (BLT105 included)
    assert astlint.lint_package() == []


# ---------------------------------------------------------------------
# parallel ingest (ISSUE 5): the uploader pool, slab-order
# re-sequencing, the async in-flight window, and pool fault paths
# ---------------------------------------------------------------------

def _submesh(ndev):
    return jax.sharding.Mesh(np.array(jax.devices()[:ndev]), ("k",))


@pytest.mark.parametrize("ndev", [1, 2, 4, 8])
def test_uploaders_scope_and_pool_size(ndev):
    # the auto rule: a worker a device up to four, and never fewer than
    # the copies ONE device's link wants in flight (stream._LINK_COPIES,
    # measured: PERF.md section 5, PR 35), a one-device mesh included
    mesh = _submesh(ndev)
    data = _intdata()
    src = _source(data, mesh, 4)._stream
    before = stream.upload_threads()
    try:
        stream.set_upload_threads(0)            # auto
        assert 2 <= stream._LINK_COPIES <= 4
        assert stream.pool_size(src) == min(max(ndev, stream._LINK_COPIES),
                                            4)
        # the ring and the resident/spill plan read the same count
        assert stream.swap_ring(src) == (stream.prefetch_depth()
                                         + stream._SWAP_WINDOW_STEP
                                         + stream.pool_size(src))
        with stream.uploaders(7):
            assert stream.upload_threads() == 7
            assert stream.pool_size(src) == 7
        with stream.uploaders(1):               # the override keeps
            assert stream.pool_size(src) == 1   # its meaning
        assert stream.upload_threads() == 0
        stream.set_upload_threads(2)
        assert stream.pool_size(src) == 2
        # sequential sources always stream through ONE prefetch thread
        it = bolt.fromiter([data], SHAPE, mesh, dtype=np.float64)._stream
        with stream.uploaders(6):
            assert stream.pool_size(it) == 1
        stream.set_upload_threads(0)
        assert stream.pool_size(it) == 1
    finally:
        stream.set_upload_threads(before)


def _meeting_loader(data, parties):
    """A loader that returns only once ``parties`` callers are inside it
    together (or 20 s have passed: an odd tail proceeds alone)."""
    bar = threading.Barrier(parties, timeout=20)

    def loader(idx):
        try:
            bar.wait()
        except threading.BrokenBarrierError:
            pass
        return data[idx]
    return loader


@pytest.mark.parametrize("terminal", ["fold", "swap"])
def test_one_device_auto_pool_engages_and_is_bit_identical(terminal):
    # on a ONE-device mesh the auto rule runs _LINK_COPIES workers (the
    # counter says so) and the answer is the one-worker answer bit for
    # bit: slabs are re-sequenced, so the fold order does not change
    mesh = _submesh(1)
    k = stream._LINK_COPIES
    rng = np.random.default_rng(35)
    data = rng.standard_normal((4 * k,) + SHAPE[1:])    # 4k slabs of 1:
    #                                  float sums would tell a reordering

    def run(loader):
        src = bolt.fromcallback(loader, data.shape, mesh,
                                dtype=data.dtype, chunks=1)
        if terminal == "fold":
            return np.asarray(src.map(ADD1).sum().toarray())
        return np.asarray(src.swap((0,), (0,)).toarray())

    before = stream.upload_threads()
    try:
        stream.set_upload_threads(0)
        with stream.uploaders(1):
            engine.reset_counters()
            want = run(lambda idx: data[idx])
            c1 = engine.counters()
        assert c1["stream_upload_threads"] == 1
        assert c1["transfer_copy_seconds"] == pytest.approx(
            c1["transfer_seconds"], rel=1e-9)
        engine.reset_counters()
        got = run(_meeting_loader(data, k))     # k workers must meet
        ck = engine.counters()
    finally:
        stream.set_upload_threads(before)
    assert ck["stream_upload_threads"] == k
    assert ck["stream_chunks"] == c1["stream_chunks"] == 4 * k
    assert ck["transfer_bytes"] == c1["transfer_bytes"]
    assert ck["transfer_seconds"] <= ck["transfer_copy_seconds"]
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()
    if terminal == "fold":
        assert np.allclose(got, (data + 1.0).sum(axis=0))
    else:
        assert np.array_equal(got, np.moveaxis(data, 0, 1))


def test_record_transfer_counts_the_link_once(monkeypatch):
    # transfer_seconds is the time at least one counted copy was in
    # flight (the union of the copies' intervals, each reported as it
    # ends); transfer_copy_seconds is their sum.  Disjoint copies add the
    # same to both, which is every run with one uploader
    now = [0.0]
    monkeypatch.setattr(engine, "_clock", lambda: now[0])
    engine.reset_counters()

    def copy(end, seconds, nbytes=8):
        now[0] = end
        engine.record_transfer(nbytes, seconds)
        c = engine.counters()
        return c["transfer_seconds"], c["transfer_copy_seconds"]

    assert copy(10.0, 4.0) == (4.0, 4.0)            # [6, 10)
    assert copy(12.0, 5.0) == (6.0, 9.0)            # [7, 12): 2 fresh
    assert copy(20.0, 3.0) == (9.0, 12.0)           # [17, 20): disjoint
    assert copy(25.0, 2.0) == (11.0, 14.0)          # [23, 25): disjoint
    # a short copy that ends first, then the long one that held it
    assert copy(31.0, 1.0) == (12.0, 15.0)          # [30, 31)
    assert copy(33.0, 5.0) == (16.0, 20.0)          # [28, 33): 4 fresh
    # one that bridges two counted stretches and the gap between them
    assert copy(34.0, 10.0) == (20.0, 30.0)         # [24, 34): 25-28, 33-34
    assert engine.counters()["transfer_bytes"] == 56
    engine.reset_counters()                         # forgets the stretches
    assert copy(34.0, 10.0) == (10.0, 10.0)
    engine.reset_counters()


def test_ring_keeps_the_whole_pool_at_work():
    # the consumer hands permits back once MORE than the prefetch depth
    # are dispatched and unconfirmed, so every worker finds a slab to
    # take; a window of ring - 1 gave them back two at a time with the
    # ring full, and a pool of any size ran two workers (PR 35).  A
    # count, not a timing: the loader notes how many callers are inside
    # it together, past the first ring's worth of slabs
    mesh = _submesh(1)
    nwork, nslabs = 4, 32
    data = np.arange(nslabs * 8, dtype=np.float64).reshape(nslabs, 4, 2)
    lock = threading.Lock()
    inside = {"n": 0, "late_hw": 0}

    def loader(idx):
        lo = idx[0].start
        with lock:
            inside["n"] += 1
            if lo >= 2 + nwork:                 # past the first ring
                inside["late_hw"] = max(inside["late_hw"], inside["n"])
        time.sleep(0.02)                        # an upload's worth
        with lock:
            inside["n"] -= 1
        return data[idx]

    with stream.prefetch(2), stream.uploaders(nwork):
        got = np.asarray(bolt.fromcallback(
            loader, data.shape, mesh, dtype=data.dtype,
            chunks=1).sum().toarray())
    assert np.array_equal(got, data.sum(axis=0))
    assert inside["late_hw"] >= 3


def test_record_transfer_union_under_many_reporters():
    # more reporters than cores, a short switch interval: the link's busy
    # time can never pass the wall the reports were made in, whatever
    # order they land in, and no copy's bytes or seconds are lost
    import sys
    nthreads, each, span = 32, 200, 0.004
    engine.reset_counters()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        gate = threading.Barrier(nthreads + 1, timeout=20)

        def report():
            gate.wait()
            for _ in range(each):
                engine.record_transfer(3, span)

        pool = [threading.Thread(target=report, daemon=True)
                for _ in range(nthreads)]
        for t in pool:
            t.start()
        t0 = engine._clock() - span         # the first report reaches back
        gate.wait()
        for t in pool:
            t.join(30)
        wall = engine._clock() - t0
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(old)
    c = engine.counters()
    engine.reset_counters()
    assert c["transfer_bytes"] == 3 * nthreads * each
    assert c["transfer_copy_seconds"] == pytest.approx(
        span * nthreads * each, rel=1e-9)
    assert span <= c["transfer_seconds"] <= wall + 1e-6


def test_upload_workers_busy_reads_counters_every_engine_keeps():
    # the benchmark's metric of the pool (PR 35) is plain arithmetic on
    # two counters that were there before it, so a program without the
    # wider pool reads it too (at most 1.0 with one worker)
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "metrics",
        "upload_workers_busy.json")
    with open(path) as f:
        spec = json.load(f)
    assert spec["reader"] == "counter_ratio"
    assert spec["args"] == {"num": ["stream_ingest_seconds"],
                            "den": ["stream_wall_seconds"]}
    mesh = _submesh(1)
    data = _intdata()
    with stream.uploaders(1):
        c0 = engine.counters()
        _source(data, mesh, 2).map(ADD1).sum().toarray()
        c1 = engine.counters()
    busy = ((c1["stream_ingest_seconds"] - c0["stream_ingest_seconds"])
            / (c1["stream_wall_seconds"] - c0["stream_wall_seconds"]))
    assert 0.0 < busy <= 1.0


def test_stream_concurrent_uploaders_counted(mesh):
    # two workers provably ingest AT THE SAME TIME: the loader blocks at
    # a 2-party barrier, so two pool threads must be mid-ingest together
    # before either can finish — the counter records that high-water
    data = _intdata()
    src = bolt.fromcallback(_meeting_loader(data, 2), SHAPE, mesh,
                            dtype=np.float64,
                            chunks=4)           # 4 slabs, pool >= 2
    c0 = engine.counters()
    with stream.uploaders(2):
        got = np.asarray(src.sum().toarray())
    c1 = engine.counters()
    assert np.array_equal(got, data.sum(axis=0))
    assert c1["stream_upload_threads"] >= 2     # > 1 concurrent uploader
    assert c1["stream_inflight_high_water"] >= 1


def test_stream_sharded_multidevice_parity_bitexact(mesh):
    # slabs that REALLY shard: 32 records, slabs of 8 over the 8-way
    # mesh — each device uploads its own sub-block of every slab via the
    # per-device placement path.  Integer-valued data: sum/mean must be
    # BIT-identical to the materialised path; var/std at f64 tolerance.
    n = 32
    data = ((np.arange(n * V0 * V1) % 17) - 8).astype(
        np.float64).reshape(n, V0, V1)
    mat = bolt.array(data, mesh)
    for chunks in (8, 16):                      # power-of-two slab counts
        for name in ("sum", "mean"):
            got = np.asarray(getattr(_source(data, mesh, chunks),
                                     name)().toarray())
            want = np.asarray(getattr(mat, name)().toarray())
            assert np.array_equal(got, want), (name, chunks)
    for chunks in (5, 1):                       # uneven tail + 1-record
        for name, tol in (("sum", 0.0), ("var", 1e-12), ("std", 1e-12)):
            got = np.asarray(getattr(_source(data, mesh, chunks),
                                     name)().toarray())
            want = np.asarray(getattr(mat, name)().toarray())
            if tol:
                assert np.allclose(got, want, rtol=tol, atol=tol), \
                    (name, chunks)
            else:
                assert np.array_equal(got, want), (name, chunks)


# ---------------------------------------------------------------------
# the three consumers of the one ingest pool (ISSUE 41): the fold of
# execute(), a streamed swap's place programs, collect()'s.  The pool's
# ordering and fault contracts hold whoever consumes its slabs.
# ---------------------------------------------------------------------

def _run_fold(b, mesh):
    return np.asarray(b.mean().toarray())


def _run_swap(b, mesh):
    return np.asarray(b.swap((0,), (0,))._data)


def _run_collect(b, mesh):
    return np.asarray(b.map(ADD1).toarray())


def _run_spilled_swap(b, mesh, tmp):
    with stream.spill(dir=str(tmp), budget=1):
        return np.asarray(b.swap((0,), (0,))._data)


CONSUMERS = {
    # name: (drive the source to its result, the result wanted of data)
    "fold": (_run_fold, lambda data, mesh: np.asarray(
        bolt.array(data, mesh).mean().toarray())),
    "swap": (_run_swap, lambda data, mesh: np.transpose(data, (1, 0, 2))),
    "collect": (_run_collect, lambda data, mesh: data + 1.0),
}


@pytest.mark.parametrize("consumer", sorted(CONSUMERS))
def test_stream_out_of_order_upload_folds_in_slab_order(mesh, monkeypatch,
                                                        consumer):
    # slab 0's upload is HELD BACK until another slab has finished: the
    # re-sequencer must still hand slabs to the consumer in slab order,
    # so the result stays bit-identical to the materialised path
    run, want = CONSUMERS[consumer]
    data = _intdata()
    orig = stream._upload_slab
    done = []

    def held_back(block, mesh_, split):
        lo = int(block[0, 0, 0] == data[0, 0, 0] and
                 np.array_equal(block, data[:block.shape[0]]))
        if lo:                                  # slab 0: wait for a peer
            t0 = time.time()
            while not done and time.time() - t0 < 10:
                time.sleep(0.002)
        out = orig(block, mesh_, split)
        done.append(lo)
        return out

    monkeypatch.setattr(stream, "_upload_slab", held_back)
    with stream.uploaders(3):
        got = run(_source(data, mesh, 4), mesh)
    assert done and done[0] == 0                # slab 0 finished LATE
    assert 1 in done
    assert np.array_equal(got, want(data, mesh))    # order unaffected


@pytest.mark.parametrize("consumer", sorted(CONSUMERS))
def test_stream_fault_in_uploader_worker_aborts_cleanly(mesh, monkeypatch,
                                                        consumer):
    # a raise inside ONE pool worker (not the source callback): the
    # whole pool is joined, ring permits are released, and the ORIGINAL
    # exception re-raises in the consumer
    run, want = CONSUMERS[consumer]
    data = _intdata()
    boom = RuntimeError("device link dropped")
    orig = stream._upload_slab
    calls = []

    def flaky_upload(block, mesh_, split):
        calls.append(block.shape)
        if len(calls) == 2:
            raise boom
        return orig(block, mesh_, split)

    monkeypatch.setattr(stream, "_upload_slab", flaky_upload)
    with stream.uploaders(2):
        with pytest.raises(RuntimeError) as ei:
            run(_source(data, mesh, 4), mesh)
    assert ei.value is boom                     # the ORIGINAL exception
    # the WHOLE pool (dispenser + workers) is joined, nothing leaks
    assert stream._LAST_POOL
    assert all(not t.is_alive() for t in stream._LAST_POOL)
    assert obs.thread_census() == {}
    # the executor is not poisoned: a healthy run goes right after
    monkeypatch.setattr(stream, "_upload_slab", orig)
    assert np.array_equal(run(_source(data, mesh, 4), mesh),
                          want(data, mesh))


@pytest.mark.parametrize("consumer", sorted(CONSUMERS))
def test_stream_dead_pool_thread_raises_pointed_error(mesh, monkeypatch,
                                                      consumer):
    # the q.get()-blocks-forever bug: a pool thread that dies WITHOUT
    # enqueueing anything (teardown-killed before its fault handler ran)
    # must surface as a pointed RuntimeError naming the dead thread, not
    # hang the consumer.  Simulated by muting the fault funnel.
    run, _ = CONSUMERS[consumer]
    monkeypatch.setattr(stream._Reseq, "fault",
                        lambda self, exc: None)

    def dying(idx):
        raise RuntimeError("this error is swallowed by the mute")

    def src(chunks):
        return bolt.fromcallback(dying, SHAPE, mesh, dtype=np.float64,
                                 chunks=chunks)

    with pytest.raises(RuntimeError, match="died without delivering"):
        run(src(4), mesh)
    with pytest.raises(RuntimeError, match="bolt-stream"):
        run(src(4), mesh)
    # the harder shape: MORE slabs than the ring, so the dispenser is
    # still alive, blocked on ring permits, when every worker dies —
    # dead workers must trip the guard anyway (nothing can ever arrive)
    with stream.uploaders(2), stream.prefetch(1):   # ring 3 << 16 slabs
        with pytest.raises(RuntimeError, match="died without delivering"):
            run(src(1), mesh)
    assert obs.thread_census() == {}


@pytest.mark.parametrize("consumer", sorted(CONSUMERS) + ["spilled-swap"])
def test_every_pool_is_in_the_thread_census(mesh, monkeypatch, tmp_path,
                                            consumer):
    # a pool held open by a held-back upload is COUNTED by the census
    # the leak gate reads (tests/conftest.py), whoever consumes it, and
    # gone from it afterwards
    if consumer == "spilled-swap":
        def run(b, mesh_):
            return _run_spilled_swap(b, mesh_, tmp_path)
        want = CONSUMERS["swap"][1]
    else:
        run, want = CONSUMERS[consumer]
    data = _intdata()
    orig = stream._upload_slab
    full = {"bolt-stream-prefetch": 1, "bolt-stream-upload": 2}
    seen = []

    def held_back(block, mesh_, split):
        # the run's FIRST upload waits for the whole pool to be up: with
        # 16 slabs and a ring of 4 the dispenser cannot have finished
        t0 = time.time()
        while not seen and obs.thread_census() != full \
                and time.time() - t0 < 10:
            time.sleep(0.002)
        seen.append(obs.thread_census())
        return orig(block, mesh_, split)

    monkeypatch.setattr(stream, "_upload_slab", held_back)
    with stream.uploaders(2):
        got = run(_source(data, mesh, 1), mesh)
    assert np.array_equal(got, want(data, mesh))
    assert seen[0] == full
    assert all(t.name.startswith("bolt-stream-")
               for t in stream._LAST_POOL)
    assert obs.thread_census() == {}


# ---------------------------------------------------------------------
# the ingest pool on its own (ISSUE 41): a fake upload, no device
# ---------------------------------------------------------------------

class _Blocks:
    """A re-iterable of the 4-record blocks ``fn`` produces."""

    def __init__(self, fn):
        self.fn = fn

    def __iter__(self):
        return (self.fn((slice(lo, lo + 4),)) for lo in range(0, N, 4))


def _pool(kind, mesh, ring, data=None, produce=None, uploaders=2,
          retries=0, noun="slab"):
    """A pool of ``ring`` slots over a 4-slab source of ``kind`` that
    reads ``data`` (or calls ``produce``, which raises)."""
    fn = produce or (lambda idx: data[idx])
    jobs = None
    if kind == "callback":
        src = bolt.fromcallback(fn, SHAPE, mesh, dtype=np.float64,
                                chunks=4)._stream
        jobs = [(g, lo, hi) for g, (lo, hi)
                in enumerate(src.slab_ranges())]
    else:
        src = bolt.fromiter(_Blocks(fn), SHAPE, mesh,
                            dtype=np.float64)._stream
    with stream.uploaders(uploaders), stream.retries(retries):
        run = stream._Run(src)          # the scopes are read HERE
    return stream._IngestPool(run, src, ring, jobs=jobs, noun=noun)


def _drain(pool, give_back=True):
    out = []
    while True:
        got = pool.next()
        if got is None:
            return out
        out.append(got)
        if give_back:
            pool.give_back(1, got[2])


@pytest.mark.parametrize("kind", ["callback", "iter"])
def test_pool_hands_slabs_out_in_order(mesh, monkeypatch, kind):
    # whatever order the workers finish in: slab 0's upload waits for
    # a later slab's wherever more than one thread uploads
    data = _intdata()
    finished = []

    def upload(block, mesh_, split):
        lo = int(np.array_equal(block, data[:4]))
        t0 = time.time()
        while lo and kind == "callback" and not finished \
                and time.time() - t0 < 10:
            time.sleep(0.002)
        finished.append(lo)
        return jax.device_put(block)

    monkeypatch.setattr(stream, "_upload_slab", upload)
    pool = _pool(kind, mesh, 4, data, uploaders=3)
    pool.start()
    try:
        got = _drain(pool)
    finally:
        pool.close()
    assert [g for g, *_ in got] == [0, 1, 2, 3]
    assert [hi for *_, hi in got] == [4, 8, 12, 16]
    for g, buf, nbytes, seconds, hi in got:
        assert np.array_equal(buf, data[hi - 4:hi])
        assert nbytes == buf.nbytes and seconds >= 0
    if kind == "callback":
        assert finished[0] == 0                 # slab 0 finished LATE
    assert all(not t.is_alive() for t in pool.threads)


@pytest.mark.parametrize("kind", ["callback", "iter"])
def test_pool_never_exceeds_its_ring(mesh, monkeypatch, kind):
    # never more than `ring` slabs dispensed and not given back: with
    # the ring full nobody uploads, and ONE permit back is ONE more slab
    data = _intdata()
    started = []
    monkeypatch.setattr(stream, "_upload_slab",
                        lambda block, m, s: (started.append(1),
                                             jax.device_put(block))[1])
    pool = _pool(kind, mesh, 2, data)
    pool.start()
    try:
        held = [pool.next(), pool.next()]
        time.sleep(0.3)
        assert len(started) == 2                # ring full: all wait
        pool.give_back(1, held[0][2])
        third = pool.next()
        time.sleep(0.3)
        assert len(started) == 3 and third[0] == 2
        pool.give_back(2, held[1][2] + third[2])
        assert [g for g, *_ in _drain(pool)] == [3]
    finally:
        pool.close()
    assert len(started) == 4


@pytest.mark.parametrize("kind", ["callback", "iter"])
def test_pool_retries_in_place_and_chains_the_attempts(mesh, monkeypatch,
                                                       kind):
    data = _intdata()
    errs = [RuntimeError("flake %d" % k) for k in range(3)]
    fails = []

    def upload(block, mesh_, split):
        if np.array_equal(block, data[4:8]) and len(fails) < len(errs):
            fails.append(errs[len(fails)])      # slab 1 fails 3 times
            raise fails[-1]
        return jax.device_put(block)

    monkeypatch.setattr(stream, "_upload_slab", upload)
    c0 = engine.counters()["stream_retries"]

    def drained(budget):
        del fails[:]
        pool = _pool(kind, mesh, 2, data, uploaders=1, retries=budget,
                     noun="test slab")
        pool.start()
        try:
            return _drain(pool)
        finally:
            pool.close()

    assert [g for g, *_ in drained(3)] == [0, 1, 2, 3]  # the last lands
    with pytest.raises(RuntimeError,
                       match="test slab 1 failed after 2 retries") as ei:
        drained(2)
    # the final error chains every attempt back to the original failure
    assert ei.value.__cause__ is errs[2]
    assert errs[2].__cause__ is errs[1] and errs[1].__cause__ is errs[0]
    assert engine.counters()["stream_retries"] - c0 == 3 + 2


@pytest.mark.parametrize("kind", ["callback", "iter"])
def test_pool_names_a_dead_thread_and_still_joins(mesh, monkeypatch, kind):
    # a thread that dies mute surfaces through next(); close() joins all
    monkeypatch.setattr(stream._Reseq, "fault", lambda self, exc: None)

    def dying(idx):
        raise RuntimeError("swallowed by the mute")

    pool = _pool(kind, mesh, 3, produce=dying)
    pool.start()
    try:
        with pytest.raises(RuntimeError,
                           match="bolt-stream-.*died without delivering"):
            pool.next()
    finally:
        pool.close()
    assert all(not t.is_alive() for t in pool.threads)
    assert len(pool.threads) == (3 if kind == "callback" else 1)
    assert obs.thread_census() == {}


def test_stream_inflight_window_bounds_and_records(mesh):
    # a long stream (16 one-record slabs, depth 1, one uploader) must
    # keep the in-flight window bounded by the ring and record the
    # high-water; the ring permits keep cycling (no deadlock, exact sum)
    data = _intdata()
    c0 = engine.counters()
    with stream.prefetch(1), stream.uploaders(1):
        got = np.asarray(_source(data, mesh, 1).sum().toarray())
    c1 = engine.counters()
    assert np.array_equal(got, data.sum(axis=0))
    assert c1["stream_inflight_high_water"] >= 1
    d = {k: c1[k] - c0[k] for k in c1}
    assert d["stream_chunks"] == 16


# ---------------------------------------------------------------------
# execute's confirm window (PR 58): the prefetch depth and a step deep,
# and a head that is done goes without a block
# ---------------------------------------------------------------------

def _floatdata():
    """float32 values no fold order rounds alike: a merged order that
    moved would show in the last bits of a mean or a variance."""
    rng = np.random.default_rng(58)
    return (rng.standard_normal(SHAPE) * 1e3).astype(np.float32)


@pytest.fixture
def never_done(monkeypatch):
    """No slab program reads done until the consumer BLOCKS for it: the
    chip's order of events with a program that retires late (a CPU's
    are done as soon as dispatched), and the executor before PR 58."""
    monkeypatch.setattr(stream, "_retired", lambda handle: False)


@pytest.fixture
def always_done(monkeypatch):
    monkeypatch.setattr(stream, "_retired", lambda handle: True)


def _spy_record_stream(monkeypatch):
    seen = []
    record = engine.record_stream

    def spy(*args, **kw):
        seen.append(kw)
        return record(*args, **kw)
    monkeypatch.setattr(engine, "record_stream", spy)
    return seen


def _fold_window(depth):
    return depth + stream._FOLD_WINDOW_STEP if depth > 1 else 1


@pytest.mark.parametrize("depth", [1, 2, 3, 4])
def test_executes_window_is_the_depth_and_the_step_and_the_ring_holds(
        mesh, depth, never_done, monkeypatch):
    """With every confirm a real block (no program done before it), the
    consumer blocks only once MORE than the window is unconfirmed: what
    it has dispatched and not confirmed reaches the window and the slab
    just dispatched, never more, each block is for the oldest pair, and
    the permits out never pass ``fold_ring``."""
    sync = stream._pod_sync

    def slow(x, pod, phase, slab=None):
        time.sleep(0.003)
        return sync(x, pod, phase, slab=slab)
    monkeypatch.setattr(stream, "_pod_sync", slow)
    seen = _spy_record_stream(monkeypatch)
    data = _intdata()
    obs.clear()
    obs.enable()
    try:
        with stream.prefetch(depth), stream.uploaders(2):
            src = _source(data, mesh, 1)
            ring = stream.fold_ring(src._stream)
            got = np.asarray(src.sum().toarray())
        assert obs.active_count() == 0
        spans = obs.spans()
    finally:
        obs.disable()
        obs.clear()
    assert np.array_equal(got, data.sum(axis=0))
    window = ring - 2
    assert window == _fold_window(depth)
    mine, = seen
    assert mine["inflight"] == window + 1 and mine["early"] == 0
    calls = sorted((sp for sp in spans if sp.name == "stream.dispatch"),
                   key=lambda sp: sp.t0)
    blocks = sorted((sp for sp in spans if sp.name == "stream.sync"),
                    key=lambda sp: sp.t0)
    # pair partials, all but what the window still held at the end
    assert len(calls) == N and all(b.attrs["slabs"] == 2 for b in blocks)
    assert N - window <= 2 * len(blocks) <= N
    for sp in calls:
        before = sum(c.t0 < sp.t0 for c in calls) \
            - sum(b.attrs["slabs"] for b in blocks if b.t1 <= sp.t0)
        assert before <= window
    # the first block waits until the window is over, and no longer
    assert blocks[0].t0 >= calls[window].t1
    assert depth > 1 or blocks[0].t1 <= calls[2].t0
    if window + 1 < N:
        assert blocks[0].t0 <= calls[window + 1].t0
    ingests = [sp for sp in spans if sp.name == "stream.ingest"]
    assert len(ingests) == N
    for sp in ingests:
        out = sum(i.t0 <= sp.t0 for i in ingests) \
            - sum(b.attrs["slabs"] for b in blocks if b.t1 <= sp.t0)
        assert out <= ring


@pytest.mark.parametrize("done", ["never_done", "always_done"])
def test_prefetch_1_is_one_pair_at_a_time_and_retires_nothing_early(
        mesh, done, request, monkeypatch):
    request.getfixturevalue(done)
    seen = _spy_record_stream(monkeypatch)
    data = _intdata()
    c0 = engine.counters()
    with stream.prefetch(1), stream.uploaders(2):
        src = _source(data, mesh, 1)
        assert stream.fold_ring(src._stream) == 1 + 2     # no step
        got = np.asarray(src.sum().toarray())
    c1 = engine.counters()
    assert np.array_equal(got, data.sum(axis=0))
    mine, = seen
    # an even slab and the odd one its partial is fused into: over the
    # window of 1, so every pair is confirmed by a block
    assert mine["inflight"] == 2 and mine["early"] == 0
    assert c1["stream_early_retired_slabs"] \
        == c0["stream_early_retired_slabs"]


_FOLDS = {
    "sum": lambda b: b.map(ADD1).sum(),
    "mean": lambda b: b.mean(),
    "var": lambda b: b.var(),
    "filtered-sum": lambda b: b.filter(POSSUM).sum(),
    "reduce": lambda b: b.reduce(jnp.maximum),
}


@pytest.mark.parametrize("chunks", [1, 3])        # 16 slabs; 6, odd tail
@pytest.mark.parametrize("fold", sorted(_FOLDS))
def test_a_deeper_window_and_an_early_retirement_change_no_bit(
        mesh, fold, chunks, monkeypatch):
    """The same slabs in the same order through the same pairwise tree,
    whenever a confirm is made: the parent's executor (no step, no head
    let go), the window with nothing ever done, and with everything done
    when asked give one answer, and ``early`` says which it was."""
    data = _floatdata()
    nslabs = -(-N // chunks)
    seen = _spy_record_stream(monkeypatch)

    def run():
        with stream.uploaders(2):
            return np.asarray(_FOLDS[fold](
                _source(data, mesh, chunks)).toarray())

    with monkeypatch.context() as m:
        m.setattr(stream, "_FOLD_WINDOW_STEP", 0)
        m.setattr(stream, "_retired", lambda handle: False)
        parents = run()
    monkeypatch.setattr(stream, "_retired", lambda handle: False)
    blocked = run()
    monkeypatch.setattr(stream, "_retired", lambda handle: True)
    early = run()
    for got in (blocked, early):
        assert got.dtype == parents.dtype and np.array_equal(
            got, parents, equal_nan=True)
    assert [kw["early"] for kw in seen] \
        == [0, 0, nslabs - nslabs % 2]            # every pair, at once
    assert [kw["inflight"] for kw in seen] == [
        3, min(stream._FOLD_WINDOW_STEP + 3, nslabs), 2]


def test_the_forecast_prices_the_ring_the_pool_is_given(mesh, monkeypatch):
    """``analysis.working_set_bytes`` of a streamed source, the pool's
    permits and ``execute``'s window are one number's three readers."""
    data = _intdata()
    rings = []
    init = stream._IngestPool.__init__

    def spy(self, run, source, ring, **kw):
        rings.append(ring)
        return init(self, run, source, ring, **kw)
    monkeypatch.setattr(stream._IngestPool, "__init__", spy)
    slab_bytes = 2 * V0 * V1 * data.dtype.itemsize
    for depth, nwork in ((1, 1), (2, 2), (2, 3), (4, 2)):
        with stream.prefetch(depth), stream.uploaders(nwork):
            src = _source(data, mesh, 2)
            ring = stream.fold_ring(src._stream)
            assert ring == _fold_window(depth) + nwork
            assert analysis.working_set_bytes(src) == slab_bytes * ring
            note = analysis.explain(src)
            src.sum().toarray()
        assert rings.pop() == ring and not rings
        assert "prefetch depth %d, a window of %d unconfirmed slabs, " \
            "uploader pool %d" % (depth, ring - nwork, nwork) in str(note)


def test_a_resumed_run_gives_back_the_permits_it_holds_and_no_more(
        mesh, tmp_path, always_done, monkeypatch):
    """A run resumed behind an UNPAIRED partial (a checkpoint cut at an
    odd slab count): the partial it restored stands for another run's
    slab, so the pair it is fused into covers ONE permit of this run's,
    and early or late every slab's permit comes back once."""
    from bolt_tpu import _chaos as chaos
    data = _intdata()
    ck = str(tmp_path / "ck")

    def source():
        return bolt.fromcallback(lambda idx: data[idx], data.shape, mesh,
                                 dtype=data.dtype, chunks=2, checkpoint=ck)
    chaos.clear()
    chaos.inject("stream.upload", nth=4)          # slabs 0-2 folded: odd
    try:
        with pytest.raises(chaos.ChaosError):
            with stream.uploaders(1):
                source().sum().cache()
    finally:
        chaos.clear()
    backs = []
    give_back = stream._IngestPool.give_back

    def spy(self, slabs, nbytes):
        backs.append(slabs)
        return give_back(self, slabs, nbytes)
    monkeypatch.setattr(stream._IngestPool, "give_back", spy)
    seen = _spy_record_stream(monkeypatch)
    c0 = engine.counters()
    got = np.asarray(source().sum().toarray())
    c1 = engine.counters()
    assert np.array_equal(got, data.sum(axis=0))
    assert c1["stream_resumes"] - c0["stream_resumes"] == 1
    mine, = seen
    left = N // 2 - 3                             # slabs 3-7
    assert c1["stream_chunks"] - c0["stream_chunks"] == left
    # the restored partial's pair first, one permit; then whole pairs
    assert backs == [1, 2, 2] and sum(backs) == left == mine["early"]


def test_the_depth_probe_runs_at_toy_size():
    """``scripts/stream_depth_probe.py``, what PERF.md's table of
    ``execute``'s windows was read from, end to end at
    ``benchmark/tests``' toy sizes: a JSON line a pass, a window's
    high-water one over it, and nothing retired early where no program
    reads done."""
    import json
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [sys.executable, os.path.join(root, "scripts",
                                      "stream_depth_probe.py"),
         "--tiny", "--windows", "2", "3", "--passes", "1", "--seed", "7"],
        capture_output=True, text=True, timeout=300, cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-2000:]
    rows = [json.loads(line) for line in done.stdout.splitlines()
            if line.startswith('{"window"')]
    assert [(r["window"], r["confirm"], r["kind"]) for r in rows] == [
        (w, c, k) for w in (2, 3) for c in ("blocking", "early")
        for k in ("q6", "q1")]
    for r in rows:
        assert r["stream_chunks"] >= 6 and "stream.sync" in r["spans"]
        if r["confirm"] == "blocking":
            assert r["stream_early_retired_slabs"] == 0
            assert r["inflight_hw"] == [r["window"] + 1]
        else:
            assert r["inflight_hw"][0] <= r["window"] + 1


# ---------------------------------------------------------------------
# chunked-view terminals on MATERIALISED arrays (delegation parity)
# ---------------------------------------------------------------------

# ---------------------------------------------------------------------
# a streamed terminal is a VALUE (ISSUE 57): it rides in the engine's
# program keys, so two calls of one door must build equal terminals
# ---------------------------------------------------------------------

def _glabel(v):
    return (v[0, 0] > 0).astype(np.int32)


def _several(b):
    return [o.toarray() for o in bolt.compute(b.sum(), b.var(), b.ptp())]


# kind -> (a door call on a streamed array, what the terminal it hands
# the executor must be)
_TERMINAL_DOORS = {
    "sum": (lambda b: b.sum().toarray(), stream._Sum()),
    "mean": (lambda b: b.mean().toarray(), stream._Moments("mean")),
    "var-ddof": (lambda b: b.var(ddof=1).toarray(),
                 stream._Moments("var", 1)),
    "std": (lambda b: b.std().toarray(), stream._Moments("std", 0)),
    "min": (lambda b: b.min().toarray(), stream._Multi((("min", None),))),
    "max": (lambda b: b.max().toarray(), stream._Multi((("max", None),))),
    # what the array layer hands over for ``func`` is its own to say
    "reduce": (lambda b: b.reduce(np.maximum).toarray(), None),
    "multi": (_several, stream._Multi(
        (("sum", None), ("var", 0), ("ptp", None)))),
    "group": (lambda b: bolt.ops.segment_reduce(b, _glabel, 2, op="mean"),
              None),
    "gram": (lambda b: bolt.ops.cov(b), stream._Gram(
        (1, "highest", True, True))),
}


@pytest.mark.parametrize("kind", sorted(_TERMINAL_DOORS))
def test_two_door_calls_build_one_terminal_and_one_program(
        mesh, kind, monkeypatch):
    door, want = _TERMINAL_DOORS[kind]
    seen = []
    execute = stream.execute

    def spy(arr, terminal, source=None):
        seen.append(terminal)
        return execute(arr, terminal, source)
    monkeypatch.setattr(stream, "execute", spy)
    data = _intdata()
    door(_source(data, mesh, 4).map(ADD1))
    c0 = engine.counters()
    door(_source(data, mesh, 4).map(ADD1))
    c1 = engine.counters()
    first, second = seen
    assert first is not second and first == second
    assert hash(first) == hash(second) and first.key == second.key
    assert len({first, second}) == 1
    if want is not None:
        assert first == want and want.key == first.key
    assert first.name == kind.split("-")[0] or type(first) is stream._Multi
    # the second run found every program the first built
    assert c1["misses"] - c0["misses"] == 0
    assert c1["aot_compiles"] - c0["aot_compiles"] == 0
    assert c1["stream_chunks"] - c0["stream_chunks"] == 4
    with pytest.raises(AttributeError, match="is a value"):
        first.ddof = 3
    others = [t for k, (_, t) in _TERMINAL_DOORS.items()
              if k != kind and t is not None]
    assert first not in others


def test_chunked_terminals_materialised(mesh):
    data = _intdata()
    cv = bolt.array(data, mesh).chunk(size=(3,), axis=(0,))
    b = bolt.array(data, mesh)
    assert np.array_equal(np.asarray(cv.sum().toarray()),
                          np.asarray(b.sum().toarray()))
    assert np.array_equal(np.asarray(cv.mean().toarray()),
                          np.asarray(b.mean().toarray()))
    assert np.array_equal(np.asarray(cv.std(ddof=1).toarray()),
                          np.asarray(b.std(ddof=1).toarray()))
    assert np.array_equal(np.asarray(cv.reduce(np.maximum).toarray()),
                          np.asarray(b.reduce(np.maximum).toarray()))
    f = cv.filter(POSSUM)
    assert f.shape == b.filter(POSSUM).shape


# ---------------------------------------------------------------------
# scope thread-locality (ISSUE 8 regression): concurrent streams on
# different threads must not leak uploaders()/prefetch() scope values
# into each other — under the multi-tenant serving layer every tenant
# runs on its own worker thread
# ---------------------------------------------------------------------

def test_uploaders_and_prefetch_scopes_are_thread_local(mesh):
    default_uploaders = stream.upload_threads()
    default_depth = stream.prefetch_depth()
    barrier = threading.Barrier(2, timeout=10)
    seen = {}
    fail = []

    def run(name, n, k):
        try:
            with stream.uploaders(n), stream.prefetch(k):
                barrier.wait()          # both threads inside their scopes
                seen[name] = (stream.upload_threads(),
                              stream.prefetch_depth())
                barrier.wait()          # hold the scopes open until both
        except Exception as exc:        # sampled under the other's scope
            fail.append(exc)

    t1 = threading.Thread(target=run, args=("a", 7, 5), daemon=True)
    t2 = threading.Thread(target=run, args=("b", 2, 3), daemon=True)
    t1.start()
    t2.start()
    t1.join(20)
    t2.join(20)
    assert not fail
    assert seen["a"] == (7, 5)          # each thread saw ITS scope only
    assert seen["b"] == (2, 3)
    # the main thread (and the process default) never saw either scope
    assert stream.upload_threads() == default_uploaders
    assert stream.prefetch_depth() == default_depth


def test_scoped_pool_size_resolves_per_thread(mesh):
    data = _intdata()
    src = _source(data, mesh, 4)._stream
    got = {}

    def other():
        with stream.uploaders(3):
            got["other"] = stream.pool_size(src)

    with stream.uploaders(1):
        th = threading.Thread(target=other, daemon=True)
        th.start()
        th.join(10)
        got["main"] = stream.pool_size(src)
    assert got == {"other": 3, "main": 1}
