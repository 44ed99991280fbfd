#!/usr/bin/env python
"""Worked examples: the reference's real-world workflows, TPU-native.

Upstream Bolt's primary consumer was the Thunder ecosystem (large-scale
image / time-series analysis); these examples exercise the same jobs
through this framework.  Each section asserts parity against NumPy, so the
file doubles as an integration test: ``python scripts/examples.py``
(runs on whatever devices jax sees — force the 8-device CPU mesh with
``XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu``).

The same code is shown in ``docs/EXAMPLES.md``.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import bolt_tpu as bolt
from bolt_tpu.parallel import default_mesh


def section(title):
    print("==", title, flush=True)


def main():
    mesh = default_mesh()
    rs = np.random.RandomState(7)

    # ------------------------------------------------------------------
    section("1. image-stack statistics (mean/std image over time)")
    # A stack of 512 images of 64x96 pixels; time is the key axis, so the
    # stack is sharded over the mesh and each device holds whole images.
    stack = rs.randn(512, 64, 96).astype(np.float32)
    b = bolt.array(stack, mesh, axis=(0,))
    st = b.stats()                      # one shard_map Welford pass
    assert np.allclose(np.asarray(st.mean()), stack.mean(axis=0), atol=1e-5)
    assert np.allclose(np.asarray(st.stdev()), stack.std(axis=0), atol=1e-4)

    # ------------------------------------------------------------------
    section("2. per-image preprocessing chain (deferred, fused)")
    # Subtract a baseline, clip, square — the chain defers and compiles
    # into ONE program when the reduction forces it.
    baseline = stack.mean()
    mapped = b.map(lambda im: np.clip(im - baseline, 0, None) ** 2)
    total = float(mapped.sum(axis=(0, 1, 2)).toarray())
    expected = (np.clip(stack - baseline, 0, None) ** 2).sum(dtype=np.float64)
    assert np.allclose(total, expected, rtol=1e-5)

    # ------------------------------------------------------------------
    section("3. images -> per-pixel time series (swap re-axis)")
    # Key axis time -> value; pixel rows -> key: afterwards each record is
    # one row's time series, ready for per-pixel temporal analysis.
    series = b.swap((0,), (0,))         # all_to_all under the hood
    assert series.shape == (64, 512, 96) and series.split == 1
    assert np.allclose(series.toarray(), np.transpose(stack, (1, 0, 2)))
    # temporal detrend per pixel row, then back to image layout
    detrended = series.map(lambda ts: ts - ts.mean(axis=0, keepdims=True))
    back = detrended.swap((0,), (0,))
    expect = stack - stack.mean(axis=0, keepdims=True)
    assert np.allclose(back.toarray(), expect, atol=1e-4)

    # ------------------------------------------------------------------
    section("4. halo-padded chunked smoothing of a long series")
    # One long (16, 40000)-sample series bank; chunk the long axis with a
    # 1-sample halo so a 3-tap moving average is exact across block edges.
    bank = rs.randn(16, 40000).astype(np.float32)
    lb = bolt.array(bank, mesh, axis=(0,))

    import jax.numpy as jnp

    def smooth(block):                  # shape-preserving on the padded block
        left = jnp.roll(block, 1, axis=0)
        right = jnp.roll(block, -1, axis=0)
        return (left + block + right) / 3.0

    sm = lb.chunk(size=(5000,), axis=(0,), padding=1).map(smooth).unchunk()
    full = smooth(bank.T).T             # oracle: smooth the whole series
    got = sm.toarray()
    # interior exact (boundaries differ: np.roll wraps on the full array)
    assert np.allclose(got[:, 1:-1], full[:, 1:-1], atol=1e-5)

    # the packaged form: ops.smooth (zero boundary) matches the raw
    # chunk-padding pipeline away from the array edges
    from bolt_tpu.ops import smooth as box_smooth
    got2 = box_smooth(lb, 3, axis=(0,), size=(5000,)).toarray()
    assert np.allclose(got2[:, 1:-1], full[:, 1:-1], atol=1e-5)

    # ------------------------------------------------------------------
    section("5. tall-skinny PCA via per-chunk SVD (BASELINE config 5)")
    npts, nfeat = 32768, 16
    data = rs.randn(npts, nfeat).astype(np.float32)
    pb = bolt.array(data[None], mesh, axis=(0,))  # one record: the matrix
    sv = pb.chunk(size=(4096,), axis=(0,)).map(
        lambda blk: jnp.linalg.svd(blk, compute_uv=False)[None, :]).unchunk()
    expect = np.stack([
        np.linalg.svd(data[i * 4096:(i + 1) * 4096], compute_uv=False)
        for i in range(npts // 4096)])
    assert np.allclose(np.asarray(sv.toarray())[0], expect, rtol=1e-2, atol=1e-2)

    # ------------------------------------------------------------------
    section("5b. whole-array distributed PCA (one SPMD program)")
    from bolt_tpu.ops import pca
    scores, comps, svals = pca(bolt.array(data, mesh, axis=(0,)),
                               k=4, center=True)
    xc = data - data.mean(axis=0)
    expect_sv = np.linalg.svd(xc, compute_uv=False)[:4]
    assert np.allclose(svals, expect_sv, rtol=1e-3)
    assert scores.mode == "tpu" and scores.shape == (npts, 4)

    # ------------------------------------------------------------------
    section("5c. distributed least squares (per-pixel trend fit)")
    # fit a linear trend to every pixel's time series in ONE call: the
    # sharded design matrix stays sharded, GSPMD inserts the all-reduce
    from bolt_tpu.ops import lstsq
    t = np.arange(512, dtype=np.float64)
    design = np.stack([np.ones_like(t), t], axis=1)        # (512, 2)
    targets = bolt.array(stack.reshape(512, -1), mesh, axis=(0,))
    coef = np.asarray(lstsq(design, targets))   # bolt array direct
    ref = np.linalg.lstsq(design, stack.reshape(512, -1), rcond=None)[0]
    assert np.allclose(coef, ref, atol=1e-6)

    # ------------------------------------------------------------------
    section("6. select + mask: keyed filtering")
    means = stack.mean(axis=(1, 2))
    bright = b.filter(lambda im: im.mean() > 0)
    assert bright.shape == ((means > 0).sum(), 64, 96)
    assert np.allclose(bright.toarray(), stack[means > 0])

    # ------------------------------------------------------------------
    section("7. checkpoint / restore")
    import tempfile
    from bolt_tpu import checkpoint
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ckpt")
        checkpoint.save(path, b)
        b2 = checkpoint.load(path, context=mesh)
        assert b2.split == b.split
        assert np.allclose(b2.toarray(), stack)

    # ------------------------------------------------------------------
    section("8. sharded loading + on-device RNG")
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "big.npy")
        disk = rs.randn(64, 32).astype(np.float32)
        np.save(path, disk)
        mm = np.load(path, mmap_mode="r")
        # each device reads ONLY its own slice of the file
        ld = bolt.fromcallback(lambda idx: mm[idx], mm.shape, mesh)
        assert np.array_equal(ld.toarray(), disk)
    rnd = bolt.randn((64, 32), mesh, dtype=np.float32, seed=0)
    assert abs(float(np.asarray(rnd.toarray()).mean())) < 0.1

    # ------------------------------------------------------------------
    section("8c. out-of-core streaming through the uploader pool")
    # an explicit dtype keeps fromcallback LAZY: the reduction streams
    # slab-by-slab through the N-way uploader pool (workers produce and
    # upload concurrently; the re-sequencer keeps the fold in slab
    # order, so the result is bit-identical to single-threaded ingest)
    from bolt_tpu import stream as _stream
    big = rs.randn(96, 16, 8).astype(np.float32)
    src = bolt.fromcallback(lambda idx: big[idx], big.shape, mesh,
                            dtype=np.float32, chunks=16)
    with _stream.uploaders(4), _stream.prefetch(2):
        m = src.map(lambda v: v + 1.0).mean()
    # production numerics (x64 off): compare against the materialised
    # device path at f32 tolerance, and the NumPy oracle likewise
    mat = bolt.array(big, mesh).map(lambda v: v + 1.0).mean()
    assert np.allclose(np.asarray(m.toarray()), np.asarray(mat.toarray()),
                       rtol=1e-5, atol=1e-5)
    assert np.allclose(np.asarray(m.toarray()),
                       (big + 1).mean(axis=0, dtype=np.float64),
                       rtol=1e-4, atol=1e-4)
    ec = bolt.profile.engine_counters()
    assert ec["stream_chunks"] >= 6 and ec["stream_upload_threads"] >= 1

    # ------------------------------------------------------------------
    section("8d. one-pass statistics: bolt.compute fused multi-stat")
    # four lazy stat terminals on one deferred chain fuse into ONE
    # tuple-output program (one read of the data); every fused result
    # is bit-identical to its standalone terminal
    xm = rs.randn(64, 16, 8).astype(np.float32)
    chain = bolt.array(xm, mesh).map(lambda v: v * 2.0)
    c0 = bolt.profile.engine_counters()
    s8, v8, lo8, hi8 = bolt.compute(chain.sum(), chain.var(),
                                    chain.min(), chain.max())
    c1 = bolt.profile.engine_counters()
    assert c1["dispatches"] - c0["dispatches"] == 1     # ONE pass
    assert c1["fused_stat_terminals"] - c0["fused_stat_terminals"] == 4
    sa = bolt.array(xm, mesh).map(lambda v: v * 2.0).sum()
    assert np.array_equal(np.asarray(s8.toarray()),
                          np.asarray(sa.toarray()))     # bit-identical
    # the fluent form works on out-of-core streams too: ONE ingest pass
    st8 = bolt.fromcallback(lambda idx: xm[idx], xm.shape, mesh,
                            dtype=np.float32, chunks=16)
    d8 = st8.stats("sum", "min", "max")
    assert np.array_equal(np.asarray(d8["min"].toarray()),
                          xm.min(axis=0))
    # ptp rides the fused min/max pair; explain() forecasts the fusion
    assert np.allclose(np.asarray(chain.ptp().toarray()),
                       np.ptp(xm * 2.0, axis=0), rtol=1e-6)
    from bolt_tpu import analysis as _analysis
    chain2 = bolt.array(xm, mesh).map(lambda v: v + 1.0)
    h1, h2 = chain2.sum(), chain2.var()
    assert "fusable terminal set" in _analysis.explain(h1)
    bolt.compute(h1, h2)

    # ------------------------------------------------------------------
    section("8e. multi-tenant serving: N pipelines, one engine")
    # N tenants share one process and one mesh: serve.submit queues each
    # pipeline, worker threads drain the per-tenant queues round-robin,
    # the device-memory arbiter keeps every stream inside ONE bytes
    # budget, and identical pipeline shapes compile ONCE across tenants
    from bolt_tpu import serve as _serve
    xs = rs.randn(96, 16, 8).astype(np.float32)
    double = lambda v: v * 2.0          # hoisted: tenants SHARE the
    #                                     callable, so programs coalesce

    def tenant_pipeline():
        src = bolt.fromcallback(lambda idx: xs[idx], xs.shape, mesh,
                                dtype=np.float32, chunks=24)
        return src.map(double).sum()

    expected = np.asarray(tenant_pipeline().toarray())  # single-tenant
    with _serve.serving(workers=3, budget_bytes=64 << 20) as sv:
        futs = [sv.submit(tenant_pipeline(), tenant=t)
                for t in ("ana", "ben", "caro")]
        for f in futs:                  # bit-identical per tenant
            assert np.array_equal(np.asarray(f.result().toarray()),
                                  expected)
        st = sv.stats()
    assert st["totals"]["completed"] >= 3
    # per-tenant accounting: each tenant's scoped engine counters saw
    # exactly its own ingest traffic
    for t in ("ana", "ben", "caro"):
        assert st["tenants"][t]["completed"] == 1
        assert st["tenants"][t]["transfer_bytes"] >= xs.nbytes
    # admission control: a pipeline that could NEVER fit the budget is
    # rejected up front (the checker forecasts it as BLT010)
    with _serve.serving(workers=1, budget_bytes=4096) as sv:
        huge = bolt.fromcallback(lambda idx: xs[idx], xs.shape, mesh,
                                 dtype=np.float32, chunks=96).sum()
        try:
            sv.submit(huge)
            raise AssertionError("BLT010 pipeline was admitted")
        except _serve.AdmissionError:
            pass

    # ------------------------------------------------------------------
    section("8f. survive a preemption: resumable streams + retry")
    # out-of-core runs over hours of data must survive worker failure:
    # checkpoint=dir persists the retired-slab watermark + fold state,
    # stream.retries absorbs flaky ingest in-run, and a killed run
    # restarted over the same source resumes BIT-IDENTICALLY from the
    # last retired slab.  The deterministic fault registry
    # (bolt_tpu._chaos) plays the failures on demand.
    import tempfile
    from bolt_tpu import _chaos as chaos
    from bolt_tpu import checkpoint as _ck
    from bolt_tpu import stream as _stream
    xr = rs.randn(64, 16, 8).astype(np.float32)
    ckd = tempfile.mkdtemp()

    def resumable_pipeline(ck=ckd):
        src = bolt.fromcallback(lambda idx: xr[idx], xr.shape, mesh,
                                dtype=np.float32, chunks=8,  # 8 slabs
                                checkpoint=ck)
        return src.map(lambda v: v + 1.0).sum()

    expected = np.asarray(resumable_pipeline(ck=None).toarray())
    # a flaky upload is absorbed in-run by the retry budget (the slab
    # re-attempts in place, fenced so it can never double-fold)
    chaos.inject("stream.upload", nth=2)
    with _stream.retries(1):
        got = np.asarray(resumable_pipeline().toarray())
    chaos.clear()
    assert np.array_equal(got, expected)
    # a KILLED run leaves a checkpoint; the re-run resumes from the
    # last retired slab and the result is bit-identical
    chaos.inject("stream.upload", nth=5)
    try:
        with _stream.uploaders(1):
            resumable_pipeline().cache()
        raise AssertionError("chaos fault did not fire")
    except chaos.ChaosError:
        pass
    finally:
        chaos.clear()
    assert _ck.stream_pending(ckd)              # the watermark survived
    got2 = np.asarray(resumable_pipeline().toarray())    # resumes
    assert np.array_equal(got2, expected)       # bit-identical
    assert not _ck.stream_pending(ckd)          # success cleared it
    ec = bolt.profile.engine_counters()
    assert ec["stream_resumes"] >= 1 and ec["stream_retries"] >= 1

    # ------------------------------------------------------------------
    section("8g. stream a pod-sized dataset: multi-process ingest")
    # the SAME loader + pipeline, scaled to a mesh spanning PROCESSES:
    # per_process=True makes each host produce and upload only its own
    # shard of every slab, and the slab program folds across hosts with
    # one mesh collective per slab (bolt_tpu.parallel.multihost).  The
    # proof stands up a REAL 2-process jax.distributed CPU cluster on
    # localhost and bit-compares against the single-process run.
    from bolt_tpu.utils import load_script
    _mh = load_script("multihost_harness")
    import shutil as _shutil
    try:
        _res, _out, _ = _mh.run_cluster("stream_parity", nproc=2, devs=1)
        _mh.run_cluster("single_ref", nproc=1, devs=2, out_dir=_out)
        _ref = np.load(os.path.join(_out, "ref_sum.npy"))
        for _pid in (0, 1):
            got_mh = np.load(os.path.join(_out, "sum.%d.npy" % _pid))
            assert np.array_equal(got_mh, _ref)      # bit-identical
        assert all(r["recompiles_second_pass"] == 0 for r in _res)
        assert all(r["blt012_refused"] for r in _res)
        _shutil.rmtree(_out, ignore_errors=True)
        print("  2-process cluster streamed bit-identically to the "
              "single-process run")
    except RuntimeError as exc:
        # an environment without the CPU collective transport skips
        print("  (pod example skipped: %s)" % exc)

    # ------------------------------------------------------------------
    section("8h. survive a pod member loss: shrink-and-resume")
    # the ISSUE-11 outage drill on a REAL 3-process localhost cluster:
    # one member is SIGKILLed mid-stream; every survivor raises the
    # pointed PeerLostError (liveness watchdog, never a hang), reforms
    # onto the 2 survivors (multihost.reform) and RESUMES from the
    # rendezvous-consistent checkpoint — bit-identical to the unkilled
    # 2-process baseline, with recovery bounded against its wall.
    try:
        _r = _mh.run_reform_bench()
        assert _r["peer_lost_everywhere"] and _r["barrier_peerlost"]
        assert _r["victim_rc"] == -9
        assert _r["bit_identical"]
        assert _r["sum_resumes"] >= 2 and _r["stats_resumes"] >= 2
        assert _r["stale_checkpoint_files"] == []
        print("  victim killed (rc %d); survivors raised PeerLostError "
              "in %.2fs (deadline %.1fs), reformed 3->2 in %.2fs and "
              "resumed bit-identically — recovery %.2fx the clean wall"
              % (_r["victim_rc"], _r["detection_s"], _r["pod_timeout"],
                 _r["reform_s"], _r["recovery_over_clean"]))
    except RuntimeError as exc:
        print("  (pod fault example skipped: %s)" % exc)

    # ------------------------------------------------------------------
    section("8i. lose a worker, get it back: the self-healing pod")
    # the ISSUE-12 drill: kill -9 one member of a 3-process pod running
    # Server(supervise=True) — the survivors reform 3->2 AUTOMATICALLY
    # (zero caller intervention; the held retry resumes from the
    # checkpoint) — then a replacement process rings the rejoin door
    # mid-stream and the pod re-expands 2->3 through a slab-boundary
    # quiesce.  Every artifact must be bit-identical to the unkilled
    # 3-process run, and nothing may leak.
    try:
        _e = _mh.run_supervise_bench()
        assert _e["victim_rc"] == -9 and _e["survivors"] == 2
        assert _e["rejoined"] == 1 and _e["nproc_final"] == 3
        assert _e["bit_identical"]
        # the wall ratio against the scenario's OWN clean run is
        # REPORTED, with only a loose sanity bound: examples run
        # alongside anything, and background load must not flake the
        # drill (tests/test_multihost.py holds it to 2.5x)
        assert _e["scenario_over_clean"] < 10
        assert _e["stale_ckpt"] == [] and _e["stale_markers"] == 0
        assert _e["arbiter_bytes"] == 0 and _e["leaked_spans"] == 0
        assert _e["blt014"] and _e["explain_supervised"]
        print("  victim killed (rc %d): auto-reform 3->2 in %.2fs with "
              "zero caller intervention; replacement rejoined and the "
              "pod re-expanded 2->3 in %.2fs — every artifact "
              "bit-identical, scenario %.2fx the clean wall"
              % (_e["victim_rc"], _e["recovery_s"], _e["rejoin_s"],
                 _e["scenario_over_clean"]))
        _p = _mh.run_precollective_probe()
        assert _p["pre_peerlost"]
        assert _p["pre_elapsed"] <= 2 * _p["pod_timeout"]
        print("  pre-collective death surfaced as PeerLostError in "
              "%.2fs (bound %.1fs — not gloo's ~30s connect)"
              % (_p["pre_elapsed"], 2 * _p["pod_timeout"]))
    except RuntimeError as exc:
        print("  (self-healing example skipped: %s)" % exc)

    # ------------------------------------------------------------------
    section("8j. high-QPS small requests: continuous micro-batching")
    # the ISSUE-13 shape: a firehose of SMALL identical-shape pipelines
    # where per-request dispatch overhead, not bytes, is the roofline.
    # Server(batching=...) coalesces queued same-key requests — across
    # tenants — into ONE stacked dispatch (bucketed widths, pad lanes
    # discarded), every lane bit-identical to its standalone dispatch,
    # with zero fresh compiles at steady state once the buckets are
    # warm (batched.warm).
    from bolt_tpu import engine as _engine8j
    from bolt_tpu import serve as _serve8j
    from bolt_tpu.tpu import batched as _batched8j
    _SCALE = lambda v: v * 2.0   # hoisted: same-key requests must share
    #                              stage callables (identity-keyed)
    req8j = [rs.randn(64, 8).astype(np.float32) for _ in range(6)]
    base8j = [bolt.array(x, mesh).cache() for x in req8j]

    def handle8j(i=0):
        return base8j[i % 6].map(_SCALE).sum()

    refs8j = [np.asarray(handle8j(i).toarray()) for i in range(6)]
    with _serve8j.serving(workers=2,
                          batching={"max_batch": 8,
                                    "linger": 0.005}) as sv:
        _batched8j.warm(handle8j, buckets=sv.batching.buckets)
        rep8j = bolt.analysis.check(handle8j())
        assert rep8j.has("BLT015")        # batch eligibility, forecast
        c0 = _engine8j.counters()
        futs = [sv.submit(handle8j(i), tenant="u%d" % (i % 3))
                for i in range(24)]
        outs = [np.asarray(f.result(timeout=120).toarray())
                for f in futs]
        c1 = _engine8j.counters()
        st8j = sv.stats()["batching"]
    assert all(np.array_equal(o, refs8j[i % 6])
               for i, o in enumerate(outs))       # bit-identical lanes
    assert c1["misses"] == c0["misses"]           # steady state: zero
    assert c1["aot_compiles"] == c0["aot_compiles"]   # fresh compiles
    saved = ((c1["batched_requests"] - c0["batched_requests"])
             - (c1["batched_dispatches"] - c0["batched_dispatches"]))
    print("  24 same-shape requests over 3 tenants: %d coalesced "
          "dispatches served %d requests (%d dispatches saved), zero "
          "fresh compiles, every result bit-identical; occupancy %s"
          % (c1["batched_dispatches"] - c0["batched_dispatches"],
             c1["batched_requests"] - c0["batched_requests"], saved,
             st8j["occupancy"].get("mean")))

    # ------------------------------------------------------------------
    section("8k. stream a dataset at half the bytes: codec ingest")
    # the ISSUE-14 lever for the transfer-bound streaming path: the
    # SAME loader, with an ingest codec armed — uploader workers ENCODE
    # each slab on host, half the bytes cross the link (the transfer
    # counters are the proof), and the slab program DECODES on device
    # fused into the fold.  Lossy codecs are an explicit opt-in with
    # documented envelopes; the lossless "delta-f32" codec is
    # BIT-IDENTICAL to uncompressed streaming and allowed everywhere
    # (order statistics included).
    from bolt_tpu import engine as _engine8k
    from bolt_tpu import stream as _stream8k
    big8k = (np.abs(rs.randn(512, 64, 8)) + 0.5).astype(np.float32)

    def load8k(codec=None):
        src = bolt.fromcallback(lambda idx: big8k[idx], big8k.shape,
                                mesh, dtype=np.float32, chunks=128,
                                codec=codec)
        return src.map(lambda v: v + 1).sum()

    rep8k = bolt.analysis.check(load8k("bf16"))
    assert rep8k.has("BLT016")            # bytes-saved forecast
    ref8k = np.asarray(load8k().toarray())
    c0 = _engine8k.counters()
    half8k = np.asarray(load8k("bf16").toarray())      # 0.5x the bytes
    c1 = _engine8k.counters()
    wire8k = c1["transfer_bytes"] - c0["transfer_bytes"]
    assert wire8k == big8k.nbytes // 2    # the wire-bytes proof
    assert np.allclose(half8k, ref8k, rtol=1e-2)       # bf16 envelope
    exact8k = np.asarray(load8k("delta-f32").toarray())
    assert np.array_equal(exact8k, ref8k)              # LOSSLESS
    # the scope form: one thread's opt-in, same stack discipline as
    # stream.uploaders — a per-source codec= always wins over it
    with _stream8k.codec("delta-f32"):
        assert np.array_equal(np.asarray(load8k().toarray()), ref8k)
    print("  streamed %d MB as %d MB on the wire (%.2fx): bf16 within "
          "1e-2, delta-f32 bit-identical, decode fused on device "
          "(codec_bytes_raw/wire: %d/%d)"
          % (big8k.nbytes >> 20, wire8k >> 20,
             wire8k / big8k.nbytes,
             c1["codec_bytes_raw"] - c0["codec_bytes_raw"],
             c1["codec_bytes_wire"] - c0["codec_bytes_wire"]))

    # ------------------------------------------------------------------
    section("8l. swap a dataset larger than HBM: the streamed shuffle")
    # the ISSUE-18 tentpole: a swap RECORDED on a streamed source stays
    # lazy and resolves as a two-phase shuffle — phase 1 re-buckets
    # each slab on device as it lands (overlapping ingest), phase 2
    # concatenates the resident buckets, or — past the budget — spills
    # them through the checkpoint slab files and re-streams them.  The
    # result is bit-identical to materialise-then-swap: a shuffle moves
    # bytes, it never rounds.
    import tempfile as _tf8l
    from bolt_tpu import checkpoint as _ckpt8l
    big8l = rs.randn(512, 64, 8).astype(np.float32)

    def load8l():
        return bolt.fromcallback(lambda idx: big8l[idx], big8l.shape,
                                 mesh, dtype=np.float32, chunks=128)

    swapped = load8l().swap((0,), (0,))   # lazy: nothing streamed yet
    rep8l = bolt.analysis.check(swapped)
    assert rep8l.has("BLT017")            # the shuffle-plan forecast
    got8l = np.asarray(swapped._data)     # resolves the two phases
    ref8l = np.transpose(big8l, (1, 0, 2))
    assert np.array_equal(got8l, ref8l)   # BIT-identical
    # force the out-of-core leg: a one-byte budget spills every
    # re-keyed bucket to disk and phase 2 re-streams them — same bits;
    # post-swap chunk().map() stages ride the re-streamed source
    spill8l = _tf8l.mkdtemp(prefix="bolt-ex8l-")
    with _stream8k.spill(dir=spill8l, budget=1):
        out8l = (load8l().swap((0,), (0,))
                 .chunk((16, 8)).map(lambda blk: blk * 2.0)
                 .unchunk())
        assert np.array_equal(np.asarray(out8l._data), ref8l * 2.0)
    c8l = _engine8k.counters()
    assert c8l["spill_bytes"] > 0         # the buckets really hit disk
    _ckpt8l.spill_clear(spill8l)          # sweep the bolt-spill-* dir
    print("  streamed swap bit-identical resident AND spilled "
          "(shuffle %d KB moved, spill %d KB written, %.3fs)"
          % (c8l["shuffle_bytes"] >> 10, c8l["spill_bytes"] >> 10,
             c8l["shuffle_seconds"]))

    # ------------------------------------------------------------------
    section("9. time-series pipeline: detrend -> zscore -> PCA")
    # per-pixel calcium-imaging-style workflow: remove each pixel's slow
    # drift, standardise, then find the dominant temporal components —
    # the per-record transforms are deferred maps, so they fuse into the
    # PCA program: ONE compiled pass over the data
    import scipy.signal
    from bolt_tpu.ops import detrend, pca, zscore
    npix, T = 128, 40
    drift = np.linspace(0, 3, T)
    sig = np.sin(np.linspace(0, 6 * np.pi, T))
    traces = (rs.randn(npix, T) * 0.2 + drift
              + np.outer(rs.randn(npix), sig)).astype(np.float64)
    tb = bolt.array(traces, mesh, axis=(0,))
    clean = zscore(detrend(tb, order=1), epsilon=1e-9)
    scores, comps, svals = pca(clean, k=2)
    ref = scipy.signal.detrend(traces, axis=1)
    ref = (ref - ref.mean(1, keepdims=True)) / (ref.std(1, keepdims=True) + 1e-9)
    rv = np.linalg.svd(ref, compute_uv=False)
    assert np.allclose(svals, rv[:2], rtol=1e-6)
    # the dominant component tracks the injected oscillation
    c0 = np.asarray(comps[:, 0])
    sig_z = scipy.signal.detrend(sig)
    sig_z /= np.linalg.norm(sig_z)
    assert abs(np.dot(c0, sig_z)) > 0.95

    # ------------------------------------------------------------------
    section("10. event detection: crosscorr + fourier + quantile")
    # which traces carry the oscillation?  crosscorr scores every record
    # against the template; fourier reads coherence at the known bin;
    # quantile gives per-record thresholds — all compiled on-mesh
    from bolt_tpu.ops import crosscorr, fourier
    rs10 = np.random.RandomState(123)
    load10 = rs10.randn(npix)
    tr10 = rs10.randn(npix, T) * 0.3 + np.outer(load10, sig)
    tb10 = bolt.array(tr10, mesh, axis=(0,))
    r = crosscorr(tb10, sig, lag=0).toarray()[:, 0]
    top = np.argsort(np.abs(load10))[-8:]
    bottom = np.argsort(np.abs(load10))[:8]
    assert np.abs(r[top]).mean() > 0.5 > np.abs(r[bottom]).mean()
    coh, phase = fourier(tb10, freq=3)    # sig = 3 cycles over the window
    coh = np.asarray(coh.toarray())
    assert coh.shape == (npix,)
    assert coh[top].mean() > coh[bottom].mean()
    q90 = tb10.quantile(0.9, axis=(1,))   # per-trace 90th percentile
    assert np.allclose(np.asarray(q90.toarray()),
                       np.quantile(tr10, 0.9, axis=1), atol=1e-8)

    # ------------------------------------------------------------------
    section("11. grouped analysis: segment_reduce + topk + histogram")
    # per-condition trial averages (reduceByKey), the strongest responders
    # per condition, and the response distribution — all on-mesh
    from bolt_tpu.ops import histogram, segment_reduce, topk, unique
    rs11 = np.random.RandomState(11)
    ntrial, cond = 64, rs11.randint(0, 4, size=64)
    resp = rs11.randn(ntrial, 32) + cond[:, None] * 0.5   # condition effect
    rb = bolt.array(resp, mesh, axis=(0,))
    means = segment_reduce(rb, cond, num_segments=4, op="mean")
    got = np.asarray(means.toarray())
    for g in range(4):
        assert np.allclose(got[g], resp[cond == g].mean(axis=0), atol=1e-6)
    # group means should be ordered by the injected effect
    assert got.mean(axis=1)[0] < got.mean(axis=1)[3]
    vals, idx = topk(means, 3, axis=1)     # strongest channels per group
    ref_idx = np.argsort(-got, axis=1, kind="stable")[:, :3]
    assert np.array_equal(np.asarray(idx.toarray()), ref_idx)
    assert np.allclose(np.asarray(vals.toarray()),
                       np.take_along_axis(got, ref_idx, axis=1))
    counts, edges = histogram(rb, bins=12)
    cn, en = np.histogram(resp, bins=12)
    assert np.array_equal(counts, cn) and np.allclose(edges, en)
    labels_seen = unique(bolt.array(cond, mesh))
    assert np.array_equal(labels_seen, np.unique(cond))

    print("ALL EXAMPLES OK")


if __name__ == "__main__":
    main()
