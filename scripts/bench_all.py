#!/usr/bin/env python
"""All five BASELINE comparison configs, local (NumPy oracle) vs TPU.

``bench.py`` is the driver-facing single-line harness (config 1 + the 10 GB
north-star, timed INCLUDING the scalar result fetch); this script measures
the full config table from ``BASELINE.json``.

Timing methodology: the TPU column times device-side completion at steady
state — launches are pipelined (dispatch is async), the host syncs once on
the last result via a one-element probe, and the probe's measured pure
round-trip is subtracted (a scheme from a host whose fetch was slow and
on which ``block_until_ready`` did not block; on the chip's own host it
blocks — PERF.md, PR 21 — and ROADMAP S0 replaces this clock).  The
full-array host transfer is likewise excluded; parity
against the oracle is still asserted on the full fetched result, once,
outside the timed region.  Config 4 (filter) dispatches fully async — the
fused mask→compact→count program runs per iteration and only the LAST
result's survivor count is synced (filter results are lazy-count pending
arrays; the reference pays a Spark job per filter at the same spot).
User functions are hoisted so jit caches
hit across iterations (defining a lambda inside the timed closure would
recompile every pass — see README dtype/tracing notes).
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import bolt_tpu as bolt  # noqa: E402
from bolt_tpu.utils import allclose  # noqa: E402


def timed(fn, iters=3):
    out = fn()
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return out, best


def sync(barray):
    """Force device-side completion of a bolt array via a 1-element probe.

    Indexes (never reshapes): an eager reshape of a TPU array is a physical
    relayout copy — doubling HBM for a 10 GB operand."""
    data = barray._data
    return float(np.asarray(jax.device_get(data[(0,) * data.ndim])))


def timed_tpu(launch, iters=40, keep_all=True):
    """Steady-state device time per iteration.

    ``launch()`` must asynchronously dispatch one full iteration and return
    the bolt array to synchronise on.  Launches are pipelined (in-order
    per-device execution: the last result completing implies all ran); the
    closing probe's pure round-trip is measured on an already-materialised
    result and subtracted.  ``keep_all=False`` drops intermediate result
    handles as the loop runs (PJRT frees each buffer once its execution
    retires) — required for multi-GB outputs, where holding every
    iteration's result would overflow HBM (the runtime keeps ~2
    executions in flight, so queue depth never stacks buffers).

    ROUND-3 CORRECTION: the subtracted round-trip was NOISY on the host
    this was built on (drifting between its measurement and its use),
    so the residual error is ~drift/iters per iteration.  ``iters``
    therefore defaults HIGH (40): at 40 launches even an 80 ms drift
    biases a per-iter figure by only 2 ms.  Callers timing sub-50 ms
    ops must not lower it; slow ops (≥0.2 s/iter) may, since the bias
    is relatively tiny there."""
    tail = launch()
    sync(tail)  # compile + warm
    rts = []
    for _ in range(3):
        t0 = time.perf_counter()
        sync(tail)
        rts.append(time.perf_counter() - t0)
    roundtrip = min(rts)
    if not keep_all:
        tail = None  # free the warm result: multi-GB outputs must not
        #              stack up (input + 2 in-flight is the HBM watermark)
    keep = []  # hold references so no buffer is deleted mid-flight
    out = None
    t0 = time.perf_counter()
    for _ in range(iters):
        out = launch()
        if keep_all:
            keep.append(out)
    sync(out)
    per_iter = (time.perf_counter() - t0 - roundtrip) / iters
    return out, per_iter


ADD1 = lambda v: v + 1
SQRT = np.sqrt
MEANPOS = lambda v: v.mean() > 0
SVALS = lambda blk: jnp.linalg.svd(blk, compute_uv=False)[None, :]


# ----------------------------------------------------------------------
# static-analysis twins: every benchmark config's DEFERRED pipeline at
# small geometry, for bolt_tpu.analysis.check — the abstract checker
# must predict each config's result shape/dtype with ZERO XLA compiles
# (engine misses unchanged).  `python scripts/bench_all.py --check`
# runs the gate standalone; tests/test_static_analysis.py runs it in
# tier-1 on the virtual CPU mesh.
# ----------------------------------------------------------------------

def pipelines(mesh=None, nkeys=16):
    """``[(config name, pipeline object)]`` — the pre-terminal deferred
    state of each BASELINE config (map chains, deferred filters, a
    chunked view over a chain, a lazy streaming source), built at toy
    sizes on ``mesh`` (default: the process default mesh)."""
    import bolt_tpu as bolt
    if mesh is None:
        from bolt_tpu.parallel import default_mesh
        mesh = default_mesh()
    rs = np.random.RandomState(7)
    k = nkeys
    x2 = (np.abs(rs.randn(k, 6, 4)) + 0.5).astype(np.float32)
    x4 = rs.randn(k, 6, 4).astype(np.float32)
    # configs 6/7's lazy out-of-core sources: nothing uploads during the
    # check — the streaming plans are interpreted abstractly
    x6 = np.ones((k, 8, 4), np.float32)
    stream6 = bolt.fromcallback(lambda idx: x6[idx], (k, 8, 4), mesh,
                                dtype=np.float32, chunks=max(1, k // 4))
    x7 = (np.arange(k * 8 * 4, dtype=np.int64) % 7).astype(
        np.float32).reshape(k, 8, 4)
    stream7 = bolt.fromcallback(lambda idx: x7[idx], (k, 8, 4), mesh,
                                dtype=np.float32, chunks=max(1, k // 4))
    x8 = rs.randn(k, 6, 4).astype(np.float32)
    x9 = np.ones((k, 8, 4), np.float32)
    stream9 = bolt.fromcallback(lambda idx: x9[idx], (k, 8, 4), mesh,
                                dtype=np.float32, chunks=max(1, k // 4))
    x10 = (np.arange(k * 8 * 4, dtype=np.int64) % 7).astype(
        np.float32).reshape(k, 8, 4)
    stream10 = bolt.fromcallback(lambda idx: x10[idx], (k, 8, 4), mesh,
                                 dtype=np.float32, chunks=max(1, k // 8))
    x11 = (np.arange(k * 8, dtype=np.int64) % 8).astype(
        np.float32).reshape(k, 8)
    stream11 = bolt.fromcallback(lambda idx: x11[idx], (k, 8), mesh,
                                 dtype=np.float32, chunks=max(1, k // 4),
                                 per_process=True)
    x12 = (np.arange(k * 8, dtype=np.int64) % 8).astype(
        np.float32).reshape(k, 8)
    stream12 = bolt.fromcallback(lambda idx: x12[idx], (k, 8), mesh,
                                 dtype=np.float32, chunks=max(1, k // 4),
                                 per_process=True)
    x13 = (np.arange(k * 8, dtype=np.int64) % 8).astype(
        np.float32).reshape(k, 8)
    stream13 = bolt.fromcallback(lambda idx: x13[idx], (k, 8), mesh,
                                 dtype=np.float32, chunks=max(1, k // 4),
                                 per_process=True)
    x15 = (np.arange(k * 8 * 4, dtype=np.int64) % 9).astype(
        np.float32).reshape(k, 8, 4)
    stream15 = bolt.fromcallback(lambda idx: x15[idx], (k, 8, 4), mesh,
                                 dtype=np.float32, chunks=max(1, k // 4),
                                 codec="bf16")
    x16 = (np.arange(k * 8 * 4, dtype=np.int64) % 11).astype(
        np.float32).reshape(k, 8, 4)
    stream16 = bolt.fromcallback(lambda idx: x16[idx], (k, 8, 4), mesh,
                                 dtype=np.float32, chunks=max(1, k // 4))
    return [
        ("1 map->sum", bolt.array(np.ones((k, 8, 4), np.float32),
                                  mesh).map(ADD1)),
        ("2 ufunc+reductions", bolt.array(x2, mesh).map(SQRT)),
        ("3 swap all-to-all", bolt.array(
            rs.randn(k, 4, 6).astype(np.float32), mesh).map(ADD1)),
        ("4 filter mask", bolt.array(x4, mesh).filter(MEANPOS)),
        ("4b filter->sum fused", bolt.array(x4, mesh).filter(MEANPOS)),
        ("5 per-chunk SVD", bolt.array(
            rs.randn(8, 32, 4).astype(np.float32),
            mesh).map(ADD1).chunk(size=(8,), axis=(0,))),
        ("6 stream chunked map->sum",
         stream6.chunk(size=(4,), axis=(0,)).map(ADD1)),
        ("7 stream_sum_parallel", stream7.map(ADD1)),
        ("8 multi_stat_fused", bolt.array(x8, mesh).map(ADD1)),
        ("9 serve_multitenant", stream9.map(ADD1)),
        ("10 stream_resume", stream10.map(ADD1)),
        ("11 multihost_stream", stream11.map(ADD1)),
        ("12 multihost_resume", stream12.map(ADD1)),
        ("13 multihost_elastic", stream13.map(ADD1)),
        ("14 serve_smallreq", bolt.array(
            np.ones((k, 8, 4), np.float32), mesh).map(ADD1)),
        ("15 stream_codec", stream15.map(ADD1)),
        ("16 stream_swap", stream16.swap((0,), (0,))),
    ]


def check_configs(mesh=None):
    """Run :func:`bolt_tpu.analysis.check` over every config pipeline;
    verify zero compiles during checking, that the predicted
    shape/dtype match the materialised result, and — with the obs
    tracer armed for the duration — that no config leaks an open span
    (``obs.active_count()`` back to zero after each).  Returns a
    process exit code (0 ok / 1 any mismatch, compile or leak)."""
    from bolt_tpu import analysis, engine, obs
    failed = False
    obs.clear()
    obs.enable()
    for name, arr in pipelines(mesh=mesh):
        c0 = engine.counters()
        rep = analysis.check(arr)
        c1 = engine.counters()
        compiled = (c1["misses"] - c0["misses"]
                    + c1["aot_compiles"] - c0["aot_compiles"]
                    + c1["dispatches"] - c0["dispatches"])
        print("== %s" % name)
        print(rep)
        target = arr.unchunk() if hasattr(arr, "unchunk") else arr
        got_shape = tuple(target.shape)          # resolves/dispatches NOW
        got_dtype = np.dtype(target.dtype)
        pred = rep.shape
        if rep.dynamic:
            shape_ok = (pred[0] is None and pred[1:] == got_shape[1:])
        else:
            shape_ok = pred == got_shape
        leaked = obs.active_count()
        ok = (shape_ok and np.dtype(rep.dtype) == got_dtype
              and compiled == 0 and leaked == 0)
        print("   predicted %s %s | executed %s %s | compiles during "
              "check: %d | leaked spans: %d -> %s"
              % (pred, rep.dtype, got_shape, got_dtype, compiled, leaked,
                 "OK" if ok else "MISMATCH"))
        failed = failed or not ok
        if name.startswith("7"):
            # the parallel-ingest executor gate (ISSUE 5): stream the
            # terminal through an uploader pool TWICE — the per-slab
            # executable (and its acc-fused level-0 twin) must compile
            # exactly once, so the second pass adds ZERO compiles; and
            # the pool run must leak no spans.  cache() forces each
            # LAZY terminal to actually stream
            from bolt_tpu import stream as _stream
            with _stream.uploaders(2):
                arr.sum().cache()            # first pass compiles
                c0 = engine.counters()
                arr.sum().cache()
                c1 = engine.counters()
            recompiled = (c1["misses"] - c0["misses"]
                          + c1["aot_compiles"] - c0["aot_compiles"])
            leaked7 = obs.active_count()
            ok7 = (recompiled == 0 and leaked7 == 0
                   and c1["stream_upload_threads"] >= 1)
            print("   streamed twice via uploader pool: recompiles on "
                  "2nd pass: %d | leaked spans: %d | uploader "
                  "high-water: %d -> %s"
                  % (recompiled, leaked7, c1["stream_upload_threads"],
                     "OK" if ok7 else "MISMATCH"))
            failed = failed or not ok7
        if name.startswith("8"):
            # the fused multi-stat gate (ISSUE 7): four terminals on
            # one chain must (a) be forecast by the checker (BLT009,
            # zero compiles), (b) fuse into ONE dispatch — the
            # bytes-read model: 1 read of the input vs 4, well under
            # the 1.25x single-pass budget — and (c) compile exactly
            # once: the second fused pass adds ZERO compiles and leaks
            # no spans.
            hs = [arr.sum(), arr.var(), arr.min(), arr.max()]
            rep8 = analysis.check(hs[0])
            s8, v8, mn8, mx8 = bolt.compute(*hs)
            c0 = engine.counters()
            h2 = [arr.sum(), arr.var(), arr.min(), arr.max()]
            bolt.compute(*h2)
            c1 = engine.counters()
            recompiled = (c1["misses"] - c0["misses"]
                          + c1["aot_compiles"] - c0["aot_compiles"])
            fused_disp = c1["dispatches"] - c0["dispatches"]
            leaked8 = obs.active_count()
            bytes_ratio = fused_disp / 1.0     # reads per fused pass
            ok8 = (rep8.has("BLT009") and recompiled == 0
                   and leaked8 == 0 and bytes_ratio <= 1.25
                   and c1["fused_stat_terminals"]
                   - c0["fused_stat_terminals"] == 4)
            print("   fused 4-terminal group: BLT009 forecast %s | "
                  "recompiles on 2nd pass: %d | dispatches (= input "
                  "reads) per fused pass: %d (budget 1.25x of the "
                  "single-pass model) | leaked spans: %d -> %s"
                  % (rep8.has("BLT009"), recompiled, fused_disp,
                     leaked8, "OK" if ok8 else "MISMATCH"))
            failed = failed or not ok8
        if name.startswith("9"):
            # the multi-tenant serving gate (ISSUE 8): N identical
            # tenants submitted concurrently must (a) COMPILE ONCE —
            # cold-cache counters for 4 tenants equal a single cold
            # tenant's (the engine's build/compile coalescing), (b)
            # return bit-identical results to the single-tenant run,
            # (c) keep the admission queue bounded, and (d) leak no
            # spans.
            from bolt_tpu import serve as _serve
            from bolt_tpu.parallel import default_mesh
            mesh9 = mesh if mesh is not None else default_mesh()
            k9 = 16
            x9 = np.ones((k9, 8, 4), np.float32)

            def make9():
                src = bolt.fromcallback(lambda idx: x9[idx],
                                        (k9, 8, 4), mesh9,
                                        dtype=np.float32,
                                        chunks=max(1, k9 // 4))
                return src.map(ADD1).sum()

            ref9 = np.asarray(make9().toarray())   # single-tenant run
            engine.clear()
            c0 = engine.counters()
            with _serve.serving(workers=4, queue_limit=8) as sv:
                futs = [sv.submit(make9(), tenant="t%d" % i)
                        for i in range(4)]
                outs = [np.asarray(f.result(timeout=600).toarray())
                        for f in futs]
                depth_hw = sv.stats()["queue_depth_high_water"]
            c1 = engine.counters()
            four9 = (c1["misses"] - c0["misses"],
                     c1["aot_compiles"] - c0["aot_compiles"])
            engine.clear()
            c0 = engine.counters()
            make9().toarray()
            c1 = engine.counters()
            one9 = (c1["misses"] - c0["misses"],
                    c1["aot_compiles"] - c0["aot_compiles"])
            leaked9 = obs.active_count()
            bit9 = all(np.array_equal(o, ref9) for o in outs)
            ok9 = (four9 == one9 and bit9 and leaked9 == 0
                   and depth_hw <= 8)
            print("   4 identical tenants: builds/compiles %s vs single "
                  "tenant %s (ONE compile across tenants) | bit-identical "
                  "to single-tenant run: %s | queue depth high-water: %d "
                  "(limit 8) | leaked spans: %d -> %s"
                  % (four9, one9, bit9, depth_hw, leaked9,
                     "OK" if ok9 else "MISMATCH"))
            failed = failed or not ok9
        if name.startswith("10"):
            # the resumable-streams gate (ISSUE 9): an uploader death
            # mid-run must leave (a) a checkpoint whose re-run resumes
            # BIT-IDENTICALLY, (b) zero leaked arbiter bytes — the
            # failed run's lease returns everything, (c) zero leaked
            # spans, (d) zero stale checkpoint files once the resumed
            # run succeeds.
            import tempfile
            from bolt_tpu import _chaos as _cha
            from bolt_tpu import checkpoint as _ckpt
            from bolt_tpu import serve as _serve
            from bolt_tpu import stream as _stream
            from bolt_tpu.parallel import default_mesh
            mesh10 = mesh if mesh is not None else default_mesh()
            k10 = 16
            x10 = (np.arange(k10 * 8 * 4, dtype=np.int64) % 7).astype(
                np.float32).reshape(k10, 8, 4)

            def make10(ck=None):
                src = bolt.fromcallback(lambda idx: x10[idx],
                                        (k10, 8, 4), mesh10,
                                        dtype=np.float32, chunks=2,
                                        checkpoint=ck)     # 8 slabs
                return src.map(ADD1).sum()

            ref10 = np.asarray(make10().toarray())
            ckd = tempfile.mkdtemp(prefix="bolt-bench-resume-")
            with _serve.serving(workers=1, budget_bytes=64 << 20) as sv:
                _cha.inject("stream.upload", nth=5)
                died = False
                try:
                    with _stream.uploaders(1):
                        make10(ckd).cache()
                except _cha.ChaosError:
                    died = True
                finally:
                    _cha.clear()
                leaked_fail = sv.stats()["arbiter"]["in_use_bytes"]
                had_ckpt = _ckpt.stream_pending(ckd)
                out10 = np.asarray(make10(ckd).toarray())
                leaked_ok = sv.stats()["arbiter"]["in_use_bytes"]
            ec10 = engine.counters()
            leaked10 = obs.active_count()
            ok10 = (died and had_ckpt and np.array_equal(out10, ref10)
                    and leaked_fail == 0 and leaked_ok == 0
                    and not _ckpt.stream_pending(ckd)
                    and ec10["stream_resumes"] >= 1 and leaked10 == 0)
            print("   uploader death mid-run: died %s | checkpoint "
                  "written %s | resumed bit-identical %s | leaked "
                  "arbiter bytes after fail/success: %d/%d | stale "
                  "checkpoint files %s | leaked spans: %d -> %s"
                  % (died, had_ckpt, np.array_equal(out10, ref10),
                     leaked_fail, leaked_ok, _ckpt.stream_pending(ckd),
                     leaked10, "OK" if ok10 else "MISMATCH"))
            failed = failed or not ok10
        if name.startswith("11"):
            # the pod-scale streaming gate (ISSUE 10): a REAL 2-process
            # jax.distributed localhost cluster streams the per-process
            # fromcallback sum + fused stats and must be (a)
            # BIT-IDENTICAL to the single-process run, (b) compiled
            # exactly once per process (second streamed pass adds zero
            # builds), (c) span-clean in every worker.  Environments
            # WITHOUT the CPU cross-process collective transport skip
            # (capability probe, like tests/test_multihost.py) — a real
            # cluster failure on a capable runtime still fails the gate.
            import shutil
            if "jax_cpu_collectives_implementation" not in getattr(
                    jax.config, "values", {}):
                print("   multihost gate SKIPPED: no CPU cross-process "
                      "collective transport on this jax")
                continue
            mh = _load_mh_harness()
            try:
                res11, out11, _ = mh.run_cluster("stream_parity",
                                                 nproc=2, devs=1)
                mh.run_cluster("single_ref", nproc=1, devs=2,
                               out_dir=out11)
            except RuntimeError as exc:
                print("   multihost cluster FAILED: %s" % exc)
                failed = True
            else:
                ref11 = np.load(os.path.join(out11, "ref_sum.npy"))
                refs = {nm: np.load(os.path.join(
                    out11, "ref_%s.npy" % nm))
                    for nm in ("stats_sum", "stats_var")}
                bit11 = all(
                    np.array_equal(np.load(os.path.join(
                        out11, "sum.%d.npy" % p)), ref11)
                    and all(np.array_equal(np.load(os.path.join(
                        out11, "%s.%d.npy" % (nm, p))), refs[nm])
                        for nm in refs)
                    for p in (0, 1))
                once11 = all(r["aot_first_pass"] > 0
                             and r["recompiles_second_pass"] == 0
                             for r in res11)
                clean11 = all(r["leaked_spans"] == 0 for r in res11)
                ok11 = bit11 and once11 and clean11 \
                    and all(r["blt012_refused"] and r["blt012_forecast"]
                            for r in res11)
                print("   2-process cluster: bit-identical to "
                      "single-process %s | compiles once per process %s "
                      "(first pass %s, second pass %s) | BLT012 "
                      "refusal+forecast %s | leaked spans %s -> %s"
                      % (bit11, once11,
                         [r["aot_first_pass"] for r in res11],
                         [r["recompiles_second_pass"] for r in res11],
                         all(r["blt012_refused"] for r in res11),
                         [r["leaked_spans"] for r in res11],
                         "OK" if ok11 else "MISMATCH"))
                failed = failed or not ok11
                shutil.rmtree(out11, ignore_errors=True)
        if name.startswith("12"):
            # the pod fault-tolerance gate (ISSUE 11): kill -9 of ONE
            # process in a 3-process cluster must (a) raise the
            # pointed PeerLostError on EVERY survivor — watchdog (and
            # barrier conversion) within 2x BOLT_POD_TIMEOUT, (b)
            # reform 3->2 and resume BIT-IDENTICALLY to the unkilled
            # 2-process run (sum AND fused stats via the pod
            # abort-path checkpoint), (c) leave ZERO stale checkpoint
            # files and ZERO leaked spans; and a serving tenant on a
            # pod must fail its in-flight future with PeerLostError,
            # read ZERO leaked arbiter bytes after the abort, and
            # drain/resume admission around the reform.
            import shutil as _sh12
            import tempfile as _tf12
            if "jax_cpu_collectives_implementation" not in getattr(
                    jax.config, "values", {}):
                print("   multihost_resume gate SKIPPED: no CPU "
                      "cross-process collective transport on this jax")
                continue
            mh = _load_mh_harness()
            try:
                r12 = mh.run_reform_bench()
                base12 = _tf12.mkdtemp(prefix="bolt-bench-servepod-")
                res12, out12, rcs12 = mh.run_cluster(
                    "serve_pod", nproc=2, devs=1, timeout=200,
                    tolerate={1},
                    env={"BOLT_POD_TIMEOUT": 2, "BOLT_MH_HARD_EXIT": "1",
                         "BOLT_POD_HB_DIR": os.path.join(base12, "hb")},
                    worker_env={1: {"BOLT_CHAOS":
                                    "stream.upload:5:kill"}})
            except RuntimeError as exc:
                print("   multihost_resume cluster FAILED: %s" % exc)
                failed = True
            else:
                sp12 = res12[0]
                ok12 = (r12["peer_lost_everywhere"]
                        and r12["barrier_peerlost"]
                        and r12["detection_s"] <= 2 * r12["pod_timeout"]
                        and r12["barrier_s"] <= 2 * r12["pod_timeout"]
                        and r12["bit_identical"]
                        and r12["sum_resumes"] >= 2
                        and r12["stats_resumes"] >= 2
                        and r12["stale_checkpoint_files"] == []
                        and r12["leaked_spans"] == 0
                        and sp12["future_error"] == "PeerLostError"
                        and sp12["arbiter_bytes_after_abort"] == 0
                        and sp12["pod_paused"] and sp12["pod_resumed"]
                        and sp12["leaked_spans"] == 0)
                print("   3->2 kill -9: PeerLostError on every survivor "
                      "%s (detection %.2fs, barrier %.4fs, deadline "
                      "%.1fs) | reform %.2fs + resume %.2fs, "
                      "bit-identical %s (sum resumes %d, stats resumes "
                      "%d) | stale ckpt files %s | leaked spans %d | "
                      "serve: future=%s arbiter_bytes=%d "
                      "paused/resumed=%s/%s -> %s"
                      % (r12["peer_lost_everywhere"], r12["detection_s"],
                         r12["barrier_s"], r12["pod_timeout"],
                         r12["reform_s"], r12["resume_s"],
                         r12["bit_identical"], r12["sum_resumes"],
                         r12["stats_resumes"],
                         r12["stale_checkpoint_files"],
                         r12["leaked_spans"], sp12["future_error"],
                         sp12["arbiter_bytes_after_abort"],
                         sp12["pod_paused"], sp12["pod_resumed"],
                         "OK" if ok12 else "MISMATCH"))
                failed = failed or not ok12
                _sh12.rmtree(out12, ignore_errors=True)
                _sh12.rmtree(base12, ignore_errors=True)
        if name.startswith("13"):
            # the self-healing pod gate (ISSUE 12): kill -9 of ONE
            # process under Server(supervise=True) must (a) shrink 3->2
            # and RE-EXPAND 2->3 (a replacement process rejoins
            # mid-stream) with ZERO caller intervention, (b) stay
            # BIT-IDENTICAL to the unkilled 3-process run for every
            # artifact (sums A/B, fused stats C), (c) finish under 2.5x
            # the clean wall with zero leaked arbiter bytes / spans /
            # stale checkpoints / stale transport markers, (d) flag
            # BLT014 and render the SUPERVISED explain() plan on the
            # live pod; and a peer dead BEFORE the first collective
            # must raise PeerLostError within 2x BOLT_POD_TIMEOUT (the
            # pre-collective bound, closed).
            if "jax_cpu_collectives_implementation" not in getattr(
                    jax.config, "values", {}):
                print("   multihost_elastic gate SKIPPED: no CPU "
                      "cross-process collective transport on this jax")
                continue
            mh = _load_mh_harness()
            try:
                r13 = mh.run_supervise_bench()
                p13 = mh.run_precollective_probe()
            except RuntimeError as exc:
                print("   multihost_elastic cluster FAILED: %s" % exc)
                failed = True
            else:
                # resume-count gate vs the SCENARIO'S OWN run, not the
                # committed PERF.json tally (the PR 13 flake): under
                # full-suite load the kill can land before a survivor's
                # first checkpoint, so per-survivor resume counts are
                # timing-dependent — the proof the resume PATH works is
                # >= 1 resume per recovery leg, and correctness is the
                # bit-identity gate either way
                ok13 = (r13["victim_rc"] == -9
                        and r13["survivors"] == 2
                        and r13["rejoined"] == 1
                        and r13["nproc_final"] == 3
                        and r13["detection_s"] <= 2 * r13["pod_timeout"]
                        and r13["scenario_over_clean"] < 2.5
                        and r13["bit_identical"]
                        and r13["a_resumes"] >= 1
                        and r13["b_resumes"] >= 1
                        and r13["arbiter_bytes"] == 0
                        and r13["leaked_spans"] == 0
                        and r13["stale_ckpt"] == []
                        and r13["stale_markers"] == 0
                        and r13["blt014"]
                        and r13["explain_supervised"]
                        and p13["pre_peerlost"]
                        and p13["pre_elapsed"]
                        <= 2 * p13["pod_timeout"])
                print("   3->2->3 supervised: victim rc %s, detection "
                      "%.2fs (deadline %.1fs), reform %.3fs, rejoin "
                      "%.3fs — scenario %.3fs vs clean %.3fs (%.2fx, "
                      "gate < 2.5x), resumes A/B %d/%d, final width %d, "
                      "budget share %.2f->%.2f, bit-identical %s | "
                      "leaks: arbiter %d spans %d stale-ckpt %s "
                      "stale-markers %d | BLT014 %s explain %s | "
                      "pre-collective PeerLost %.2fs (bound %.1fs) -> %s"
                      % (r13["victim_rc"], r13["detection_s"],
                         r13["pod_timeout"], r13["reform_s"],
                         r13["rejoin_s"], r13["scenario_s"],
                         r13["clean_s"], r13["scenario_over_clean"],
                         r13["a_resumes"], r13["b_resumes"],
                         r13["nproc_final"],
                         r13["budget_share_after_a"],
                         r13["budget_share_after_b"],
                         r13["bit_identical"], r13["arbiter_bytes"],
                         r13["leaked_spans"], r13["stale_ckpt"],
                         r13["stale_markers"], r13["blt014"],
                         r13["explain_supervised"],
                         p13["pre_elapsed"] or -1.0,
                         2 * p13["pod_timeout"],
                         "OK" if ok13 else "MISMATCH"))
                failed = failed or not ok13
        if name.startswith("14"):
            # the continuous micro-batching gate (ISSUE 13): queued
            # same-key small requests under Server(batching=...) must
            # (a) coalesce into batched dispatches whose every result is
            # BIT-IDENTICAL to its standalone dispatch, (b) run ZERO
            # fresh XLA compiles at steady state across the bucketed
            # widths (batched.warm pre-compiles them), (c) be forecast
            # by the checker (BLT015, zero compiles), (d) leak no spans
            # and leave zero arbiter bytes in use.
            from bolt_tpu import serve as _serve
            from bolt_tpu.tpu import batched as _batched
            from bolt_tpu.parallel import default_mesh
            mesh14 = mesh if mesh is not None else default_mesh()
            k14 = 16
            xs14 = [np.full((k14, 8, 4), float(i + 1), np.float32)
                    for i in range(6)]
            b14 = [bolt.array(x, mesh14).cache() for x in xs14]

            def make14(i=0):
                return b14[i % 6].map(ADD1).sum()

            refs14 = [np.asarray(make14(i).toarray()) for i in range(6)]
            with _serve.serving(workers=2, queue_limit=64,
                                batching={"max_batch": 8,
                                          "linger": 0.01}) as sv:
                rep14 = analysis.check(make14())      # BLT015 forecast
                c0 = engine.counters()
                _batched.warm(make14, buckets=sv.batching.buckets)
                c1 = engine.counters()
                warm_compiled = (c1["misses"] - c0["misses"]
                                 + c1["aot_compiles"] - c0["aot_compiles"])
                c0 = engine.counters()
                futs = [sv.submit(make14(i), tenant="t%d" % (i % 3))
                        for i in range(24)]
                outs14 = [np.asarray(f.result(timeout=600).toarray())
                          for f in futs]
                c1 = engine.counters()
                leaked_bytes = sv.stats()["arbiter"]["in_use_bytes"]
                occ = sv.stats()["batching"]["occupancy"]
            recompiled = (c1["misses"] - c0["misses"]
                          + c1["aot_compiles"] - c0["aot_compiles"])
            batched_disp = (c1["batched_dispatches"]
                            - c0["batched_dispatches"])
            bit14 = all(np.array_equal(o, refs14[i % 6])
                        for i, o in enumerate(outs14))
            leaked14 = obs.active_count()
            ok14 = (rep14.has("BLT015") and warm_compiled > 0
                    and recompiled == 0 and batched_disp >= 1
                    and bit14 and leaked_bytes == 0 and leaked14 == 0)
            print("   serve micro-batching: BLT015 forecast %s | warm "
                  "compiles %d then steady-state recompiles %d across "
                  "bucketed widths | batched dispatches %d (occupancy "
                  "%s) | bit-identical %s | leaked arbiter bytes %d | "
                  "leaked spans %d -> %s"
                  % (rep14.has("BLT015"), warm_compiled, recompiled,
                     batched_disp, occ, bit14, leaked_bytes, leaked14,
                     "OK" if ok14 else "MISMATCH"))
            failed = failed or not ok14
        if name.startswith("15"):
            # the codec-encoded ingest gate (ISSUE 14): (a) BLT016
            # forecast (zero compiles — already gated above), (b) the
            # bf16-encoded stream moves <= 0.55x the raw f32 bytes
            # through the transfer counters, (c) the LOSSLESS codec is
            # BIT-IDENTICAL to uncompressed streaming, (d) the second
            # encoded pass adds ZERO fresh compiles, (e) zero leaked
            # spans and zero arbiter bytes after streaming under a
            # serving budget.
            from bolt_tpu import serve as _serve
            from bolt_tpu.parallel import default_mesh
            mesh15 = mesh if mesh is not None else default_mesh()
            k15 = 16
            x15g = (np.arange(k15 * 8 * 4, dtype=np.int64) % 9).astype(
                np.float32).reshape(k15, 8, 4)

            def make15(codec=None):
                src = bolt.fromcallback(lambda idx: x15g[idx],
                                        (k15, 8, 4), mesh15,
                                        dtype=np.float32, chunks=4,
                                        codec=codec)
                return src.map(ADD1).sum()

            ref15 = np.asarray(make15().toarray())
            with _serve.serving(workers=1, budget_bytes=64 << 20) as sv:
                c0 = engine.counters()
                out_b = np.asarray(make15("bf16").toarray())
                c1 = engine.counters()
                out_b2 = np.asarray(make15("bf16").toarray())
                c2 = engine.counters()
                out_l = np.asarray(make15("delta-f32").toarray())
                leak_bytes15 = sv.stats()["arbiter"]["in_use_bytes"]
            ratio15 = (c1["transfer_bytes"] - c0["transfer_bytes"]) \
                / float(x15g.nbytes)
            recomp15 = (c2["misses"] - c1["misses"]
                        + c2["aot_compiles"] - c1["aot_compiles"])
            bit15 = np.array_equal(out_l, ref15)
            det15 = np.array_equal(out_b, out_b2)     # deterministic
            close15 = bool(np.allclose(out_b, ref15, rtol=1e-2))
            leaked15 = obs.active_count()
            ok15 = (rep.has("BLT016") and ratio15 <= 0.55 and bit15
                    and det15 and close15 and recomp15 == 0
                    and leaked15 == 0 and leak_bytes15 == 0)
            print("   codec ingest: BLT016 forecast %s | bf16 wire "
                  "bytes %.2fx raw (gate <= 0.55) | lossless "
                  "bit-identical %s | bf16 within envelope %s, "
                  "deterministic %s | recompiles on 2nd encoded pass "
                  "%d | leaked arbiter bytes %d | leaked spans %d -> %s"
                  % (rep.has("BLT016"), ratio15, bit15, close15, det15,
                     recomp15, leak_bytes15, leaked15,
                     "OK" if ok15 else "MISMATCH"))
            failed = failed or not ok15
        if name.startswith("16"):
            # the out-of-core shuffle gate (ISSUE 18): a swap recorded
            # on a streamed source must (a) forecast its shuffle plan
            # (BLT017) in AGREEMENT with the measured resident/spill
            # decision — the checker runs the same planner against the
            # same budget resolution as the dispatcher, so drift here
            # is a real bug, (b) stay bit-identical to the
            # materialise-first transpose on BOTH the resident and the
            # forced-spill legs, (c) add ZERO fresh compiles on a
            # second identical pass, and (d) leave nothing behind: no
            # leaked spans, no arbiter bytes, no spill files after
            # spill_clear.
            import shutil as _sh16
            import tempfile as _tf16
            from bolt_tpu import checkpoint as _ckpt16
            from bolt_tpu import serve as _serve16
            from bolt_tpu import stream as _stream16
            from bolt_tpu.parallel import default_mesh
            mesh16 = mesh if mesh is not None else default_mesh()
            k16 = 16
            x16g = (np.arange(k16 * 8 * 4, dtype=np.int64) % 11).astype(
                np.float32).reshape(k16, 8, 4)

            def make16():
                src = bolt.fromcallback(lambda idx: x16g[idx],
                                        (k16, 8, 4), mesh16,
                                        dtype=np.float32, chunks=4)
                return src.swap((0,), (0,))

            def blt017(a):
                ds = [d for d in analysis.check(a).diagnostics
                      if d.code == "BLT017"]
                return ds[0] if ds else None

            ref16 = np.transpose(x16g, (1, 0, 2))
            td16 = _tf16.mkdtemp(prefix="bolt-gate16-")
            with _serve16.serving(workers=1, budget_bytes=64 << 20) as sv:
                d_res = blt017(make16())
                c0 = engine.counters()
                out_res = np.asarray(make16()._data)
                c1 = engine.counters()
                out_res2 = np.asarray(make16()._data)
                c2 = engine.counters()
                with _stream16.spill(dir=td16, budget=1):
                    d_sp = blt017(make16())
                    out_sp = np.asarray(make16()._data)
                c3 = engine.counters()
                leak_bytes16 = sv.stats()["arbiter"]["in_use_bytes"]
            forecast_res = (d_res is not None and d_res.severity == "info"
                            and "resident" in d_res.message)
            forecast_sp = (d_sp is not None and d_sp.severity == "info"
                           and "spill" in d_sp.message)
            ran_res = (c1["spill_bytes"] == c0["spill_bytes"]
                       and c1["shuffle_bytes"] > 0)
            ran_sp = c3["spill_bytes"] > c1["spill_bytes"]
            recomp16 = (c2["misses"] - c1["misses"]
                        + c2["aot_compiles"] - c1["aot_compiles"])
            spilled_files16 = _ckpt16.spill_pending(td16)
            _ckpt16.spill_clear(td16)
            cleared16 = not _ckpt16.spill_pending(td16)
            _sh16.rmtree(td16, ignore_errors=True)
            bit16 = (np.array_equal(out_res, ref16)
                     and np.array_equal(out_res2, ref16)
                     and np.array_equal(out_sp, ref16))
            leaked16 = obs.active_count()
            ok16 = (forecast_res and forecast_sp and ran_res and ran_sp
                    and spilled_files16 and cleared16 and bit16
                    and recomp16 == 0 and leaked16 == 0
                    and leak_bytes16 == 0)
            print("   stream_swap: BLT017 forecast resident %s / spill "
                  "%s agree with measured %s/%s | bit-identical %s | "
                  "recompiles on 2nd pass %d | leaked arbiter bytes %d "
                  "| leaked spans %d | spill dir cleared %s -> %s"
                  % (forecast_res, forecast_sp, ran_res, ran_sp, bit16,
                     recomp16, leak_bytes16, leaked16, cleared16,
                     "OK" if ok16 else "MISMATCH"))
            failed = failed or not ok16
    obs.disable()
    # thread-census hygiene: every pool/watch/supervisor the configs
    # started must be torn down — a leaked bolt-* thread is an executor
    # that skipped its shutdown path
    census = obs.thread_census()
    print("thread census after all configs: %s -> %s"
          % (census or "{}", "OK" if not census else "LEAKED"))
    failed = failed or bool(census)
    return 1 if failed else 0


def _load_mh_harness():
    """The localhost multi-process cluster harness (shared loader:
    bolt_tpu.utils.load_script)."""
    from bolt_tpu.utils import load_script
    return load_script("multihost_harness")


# ----------------------------------------------------------------------
# Bit-identical pseudo-random data on BOTH sides without moving a byte
# between host and device (set-up then measures no link, and multi-GB
# results need no full fetch).  A u32 LCG + xorshift is exact integer
# arithmetic with identical wraparound in numpy and jnp; the top 24 bits
# convert to float32 exactly, so tpu-generated and host-generated arrays
# are EQUAL, and parity can be asserted on small sampled slices of big
# results.
# ----------------------------------------------------------------------

def lcg_np(shape, salt=0):
    # blockwise + in-place: the naive expression materialises ~6 full-
    # size temporaries, which on a slow host measured 158 s for 4.3 GB;
    # one preallocated output and 64 MB scratch blocks cut that ~4x
    n = int(np.prod(shape))
    out = np.empty(n, np.float32)
    step = 1 << 24
    for s in range(0, n, step):
        e = min(s + step, n)
        v = np.arange(s, e, dtype=np.uint32)
        v += np.uint32(salt)
        v *= np.uint32(2654435761)
        v += np.uint32(12345)
        v ^= v >> np.uint32(13)
        v >>= np.uint32(8)
        blk = v.astype(np.float32)
        blk /= np.float32(1 << 24)
        blk -= np.float32(0.5)
        out[s:e] = blk
    return out.reshape(shape)


def lcg_tpu(shape, axis=(0,), salt=0):
    from bolt_tpu.parallel.sharding import key_sharding
    from bolt_tpu.tpu.array import BoltArrayTPU
    from bolt_tpu.parallel import default_mesh
    mesh = default_mesh()
    split = len(axis)

    def gen():
        n = int(np.prod(shape))
        i = jnp.arange(n, dtype=jnp.uint32) + jnp.uint32(salt)
        v = i * jnp.uint32(2654435761) + jnp.uint32(12345)
        v = v ^ (v >> jnp.uint32(13))
        out = ((v >> jnp.uint32(8)).astype(jnp.float32)
               / jnp.float32(1 << 24) - jnp.float32(0.5))
        return out.reshape(shape)

    data = jax.jit(gen, out_shardings=key_sharding(mesh, shape, split))()
    return BoltArrayTPU(data, split, mesh)


def fetch(barray, index):
    """Small sampled slice of a device result (never the full array)."""
    return np.asarray(barray[index].toarray())


def main():
    def _progress(*row):
        print("done: %s  local=%.3fs tpu=%.4fs %s" % row,
              file=sys.stderr, flush=True)
        return row

    rows = []
    rs = np.random.RandomState(0)

    # ---- config 1: ones((200,200,64,64)).map(x+1).sum() --------------
    shape = (200, 200, 64, 64)
    xl = np.ones(shape, np.float32)
    bt = bolt.ones(shape, mode="tpu", dtype=np.float32).cache()
    axes = tuple(range(4))
    lo, lt = timed(lambda: float((xl + 1).sum(dtype=np.float32)))
    # .cache() forces each LAZY stat terminal to dispatch (async) so
    # every pipelined iteration really runs — stat results are pending
    # fused-group handles since the bolt.compute layer
    to_arr, tt = timed_tpu(lambda: bt.map(ADD1).sum(axis=axes).cache())
    to = float(to_arr.toarray())
    rows.append(_progress("1 map->sum 0.66GB", lt, tt, "bit-exact" if lo == to else "MISMATCH"))

    # ---- config 2: ufuncs + axis reductions over the split axis ------
    # 2.1 GB (round 2): the round-1 268 MB shape ran at about the
    # per-launch floor, so its speedup said little about the chip
    # (VERDICT r1 weak-4)
    shape2 = (8192, 1024, 64)
    x = np.abs(lcg_np(shape2)) + np.float32(0.5)
    bt = lcg_tpu(shape2).map(lambda v: jnp.abs(v) + 0.5).cache()

    def local2():
        m = np.sqrt(x)
        return m.mean(axis=0), m.std(axis=0), m.var(axis=0), m.max(axis=0)

    tpu2_outs = []

    def tpu2():
        m = bt.map(SQRT)
        # cache() per terminal: resolve each standalone (4 sequential
        # passes, the config's historical meaning) instead of letting
        # the four lazy handles fuse into one multi-stat pass —
        # config 8 measures the fused form
        tpu2_outs[:] = [getattr(m, n)().cache()
                        for n in ("mean", "std", "var", "max")]
        return tpu2_outs[-1]

    lo, lt = timed(local2, iters=2)
    _, tt = timed_tpu(tpu2)
    # reduced outputs are small (value-shaped): full-fetch parity
    ok = all(allclose(a, np.asarray(b.toarray()), rtol=1e-4, atol=1e-5)
             for a, b in zip(lo, tpu2_outs))
    rows.append(_progress("2 ufunc+reductions 2.1GB", lt, tt, "allclose" if ok else "MISMATCH"))
    del x

    # ---- config 3: swap() key<->value exchange on a 4D array ---------
    # 4.3 GB (round 2, was 512 MB / 0.7 ms — floor-bound); intermediate
    # swap outputs are dropped as the loop runs (keep_all=False: 24
    # retained 4.3 GB results would overflow HBM many times over — the
    # runtime's ~2 in-flight executions bound the true watermark)
    del bt
    # 4.3 GB: at 2.1 GB the swap measured 6.3 ms — genuinely ~670 GB/s
    # read+write but still within 3x of the dispatch floor; doubling the
    # size puts device time unambiguously in charge.  keep_all=False
    # (plus timed_tpu freeing the warm result) bounds the HBM watermark
    # at input + ~2 in-flight 4.3 GB outputs regardless of iters, so
    # iters=6 amortises closing-sync jitter properly.
    shape3 = (2048, 128, 64, 64)
    x = lcg_np(shape3, salt=3)
    bt = lcg_tpu(shape3, axis=(0, 1), salt=3).cache()
    lo_arr, lt = timed(
        lambda: np.ascontiguousarray(np.transpose(x, (1, 2, 0, 3))), iters=1)

    to, tt = timed_tpu(lambda: bt.swap((0,), (0,)), iters=24, keep_all=False)
    # 4.3 GB output: parity on sampled slices (identical LCG data on both
    # sides), not a full 4.3 GB fetch
    ok = (to.shape == lo_arr.shape
          and allclose(lo_arr[5, 9], fetch(to, np.s_[5, 9]))
          and allclose(lo_arr[127, 63], fetch(to, np.s_[127, 63]))
          and allclose(lo_arr[:, 0, 17], fetch(to, np.s_[:, 0, 17])))
    rows.append(_progress("3 swap all-to-all 4.3GB", lt, tt, "exact*" if ok else "MISMATCH"))
    del x, lo_arr

    # ---- config 4: filter() / boolean mask on the keyed axis ---------
    # 0.94 GB (round 2, was 268 MB): the largest size that keeps the
    # fused lazy-count path (its padded compaction buffer doubles HBM,
    # capped at 1 GB) so iterations still pipeline
    del bt, to
    shape4 = (14336, 256, 64)
    x = lcg_np(shape4, salt=4)
    bt = lcg_tpu(shape4, salt=4).cache()
    lo_arr, lt = timed(lambda: x[x.mean(axis=(1, 2)) > 0], iters=2)

    # filter() now DEFERS (reduction terminals fuse the predicate);
    # materialising configs must dispatch the compaction program
    # explicitly so every pipelined iteration runs.  keep_all=False:
    # at 24 iterations the pending results' padded buffers (0.94 GB
    # each) must retire as the loop runs, not accumulate
    def launch4():
        out = bt.filter(MEANPOS)
        out._resolve_fpending()     # async dispatch, count stays on device
        return out

    to, tt = timed_tpu(launch4, iters=24, keep_all=False)
    # ~0.5 GB of survivors: parity on count + sampled survivor rows
    ok = (to.shape == lo_arr.shape
          and allclose(lo_arr[:2], fetch(to, np.s_[:2]))
          and allclose(lo_arr[-1], fetch(to, np.s_[-1])))
    rows.append(_progress("4 filter mask 0.94GB", lt, tt, "exact*" if ok else "MISMATCH"))

    # ---- config 4b: fused filter→sum terminal (ISSUE 1) --------------
    # the predicate folds into the reduction combine: ONE pass over the
    # input, no compaction buffer — vs config 4's ~3 passes
    lo_sum, lt4b = timed(lambda: x[x.mean(axis=(1, 2)) > 0].sum(axis=0),
                         iters=2)
    to4b, tt4b = timed_tpu(lambda: bt.filter(MEANPOS).sum().cache(),
                           iters=24)
    ok4b = allclose(lo_sum, fetch(to4b, np.s_[:]), rtol=1e-4)
    rows.append(_progress("4b filter->sum fused 0.94GB", lt4b, tt4b,
                          "close*" if ok4b else "MISMATCH"))
    del x, lo_arr

    # ---- config 5: per-chunk SVD (tall-skinny PCA) -------------------
    # 2.1 GB (round 2, was 67 MB): 32768 chunks of (1024, 16)
    del bt, to
    shape5 = (8, 4194304, 16)
    x = lcg_np(shape5, salt=5)
    bt = lcg_tpu(shape5, salt=5).cache()
    nchunk, csize = 4096, 1024

    def local5():
        return np.stack([np.stack([
            np.linalg.svd(x[k, i * csize:(i + 1) * csize], compute_uv=False)
            for i in range(nchunk)]) for k in range(x.shape[0])])

    lo_arr, lt = timed(local5, iters=1)
    to, tt = timed_tpu(
        lambda: bt.chunk(size=(csize,), axis=(0,)).map(SVALS).unchunk(),
        iters=5)
    # output is small ((8, 4096, 16) = 2 MB): full-fetch parity
    ok = allclose(lo_arr, to.toarray().reshape(lo_arr.shape), rtol=1e-2, atol=1e-2)
    rows.append(_progress("5 per-chunk SVD 2.1GB", lt, tt, "allclose" if ok else "MISMATCH"))

    # ---- config 5b: same workload, TPU-first algorithm ---------------
    # singular values via the Gram matrix (MXU matmul + small eigvalsh)
    # instead of QR-iteration SVD — see bolt_tpu/ops svdvals docstring
    from bolt_tpu.ops import svdvals
    GRAM = lambda blk: svdvals(blk)[None, :]
    to, tt = timed_tpu(
        lambda: bt.chunk(size=(csize,), axis=(0,)).map(GRAM).unchunk(),
        iters=5)
    ok = allclose(lo_arr, to.toarray().reshape(lo_arr.shape), rtol=1e-2, atol=1e-2)
    rows.append(_progress("5b gram-SVD (MXU) 2.1GB", lt, tt, "allclose" if ok else "MISMATCH"))

    # ---- config 6: streamed out-of-core map->sum (stream_sum) --------
    # the ISSUE-3 executor: host-resident data streams slab-by-slab
    # through the double-buffered prefetch pipeline into the fused
    # per-slab map+sum, partials merging on device.  The host array here
    # FITS in RAM (it must, to build the oracle), but the device only
    # ever holds prefetch-depth slabs — the timing is the out-of-core
    # ingest path: host->device transfer overlapped with compute, so it
    # gauges the attach link, not HBM.  A streamed run is synchronous
    # end-to-end (the executor blocks per slab), so it is timed directly
    # rather than through the async-launch harness.
    del bt, to, x, lo_arr
    shape6 = (8192, 256, 64)                      # 0.5 GB over the link
    x6 = lcg_np(shape6, salt=6)
    lo6, lt6 = timed(lambda: (x6 + 1).sum(axis=0, dtype=np.float32),
                     iters=2)

    def launch6():
        src = bolt.fromcallback(lambda idx: x6[idx], shape6, mode="tpu",
                                dtype=np.float32, chunks=512)
        return src.chunk(size=(64,), axis=(0,)).map(ADD1).sum()

    from bolt_tpu import profile as _profile
    sync(launch6())                               # compile the slab programs
    c0 = _profile.engine_counters()
    t0 = time.perf_counter()
    to6 = launch6()
    sync(to6)
    tt6 = time.perf_counter() - t0
    c1 = _profile.engine_counters()
    dl = {k: c1[k] - c0[k] for k in c1}
    eff = (dl["stream_overlap_seconds"] / dl["stream_ingest_seconds"]
           if dl["stream_ingest_seconds"] else 0.0)
    print("   stream_sum: %d slabs, %.0f MB shipped, overlap_efficiency "
          "%.2f" % (dl["stream_chunks"], dl["transfer_bytes"] / 1e6, eff),
          file=sys.stderr)
    ok6 = allclose(lo6, np.asarray(to6.toarray()), rtol=1e-4, atol=1e-4)
    rows.append(_progress("6 stream_sum 0.5GB ingest", lt6, tt6,
                          "allclose" if ok6 else "MISMATCH"))

    # ---- config 7: parallel-ingest streamed sum (ISSUE 5) ------------
    # the same out-of-core workload as config 6 through the N-way
    # uploader pool + async dispatch: workers produce AND upload slabs
    # concurrently (per-device sub-blocks), slab programs dispatch into
    # the bounded in-flight window with the level-0 fold fused in.  The
    # counter deltas prove the pipeline: >1 concurrent uploader and
    # ~half the dispatches per slab of the pre-pool executor.
    from bolt_tpu import stream as _stream
    with _stream.uploaders(4):
        sync(launch6())                       # warm the pool-run programs
        c0 = _profile.engine_counters()
        t0 = time.perf_counter()
        to7 = launch6()
        sync(to7)
        tt7 = time.perf_counter() - t0
        c1 = _profile.engine_counters()
    dl = {k: c1[k] - c0[k] for k in c1}
    eff7 = (dl["stream_overlap_seconds"] / dl["stream_ingest_seconds"]
            if dl["stream_ingest_seconds"] else 0.0)
    print("   stream_sum_parallel: %d slabs, %.0f MB shipped, "
          "concurrent uploaders (hw) %d, in-flight hw %d, "
          "dispatches/slab %.2f, overlap_efficiency %.2f"
          % (dl["stream_chunks"], dl["transfer_bytes"] / 1e6,
             c1["stream_upload_threads"],
             c1["stream_inflight_high_water"],
             dl["dispatches"] / max(dl["stream_chunks"], 1), eff7),
          file=sys.stderr)
    ok7 = allclose(lo6, np.asarray(to7.toarray()), rtol=1e-4, atol=1e-4)
    rows.append(_progress("7 stream_sum_parallel", lt6, tt7,
                          "allclose" if ok7 else "MISMATCH"))

    # ---- config 8: fused multi-stat terminal (ISSUE 7) ---------------
    # bolt.compute(m.sum(), m.var(), m.min(), m.max()): four terminals
    # from ONE pass over a >= 1 GB input — vs the sequential form's four
    # passes.  The bytes-read model is dispatch-counted (one fused
    # dispatch over the chain = one read of the input; four standalone
    # dispatches = four reads); the measured ratio is wall-clock.
    # Parity is the acceptance contract: every fused result BIT-equal
    # to its standalone terminal.
    shape8 = (8192, 256, 128)                     # 1.07 GB f32
    x8 = lcg_np(shape8, salt=8)
    bt8 = lcg_tpu(shape8, salt=8).cache()
    lo8, lt8 = timed(lambda: ((x8 + 1).sum(axis=0),
                              (x8 + 1).var(axis=0),
                              (x8 + 1).min(axis=0),
                              (x8 + 1).max(axis=0)), iters=1)

    def fused8():
        m = bt8.map(ADD1)
        s, v, mn, mx = bolt.compute(m.sum(), m.var(), m.min(), m.max())
        return mx                 # all four share the one dispatch

    def seq8():
        m = bt8.map(ADD1)
        # resolve one at a time: each singleton group dispatches its own
        # standalone pass (the pre-fusion cost model)
        m.sum().cache()
        m.var().cache()
        m.min().cache()
        return m.max().cache()

    from bolt_tpu import engine as _engine8
    _, tt8s = timed_tpu(seq8, iters=8)
    c0 = _engine8.counters()
    to8, tt8 = timed_tpu(fused8, iters=8)
    c1 = _engine8.counters()
    per_iter_disp = (c1["dispatches"] - c0["dispatches"]) / float(8 + 1)
    fused_res = fused8()
    seq_last = seq8()
    bit8 = np.array_equal(np.asarray(fused_res.toarray()),
                          np.asarray(seq_last.toarray()))
    ok8 = (bit8 and allclose(lo8[3], np.asarray(fused_res.toarray()),
                             rtol=1e-4, atol=1e-4))
    print("   multi_stat_fused: 4 terminals, dispatches/iter %.2f "
          "(model: 1 fused read vs 4 sequential), measured seq/fused "
          "wall ratio %.2fx, fused-vs-standalone %s"
          % (per_iter_disp, tt8s / tt8,
             "bit-exact" if bit8 else "MISMATCH"), file=sys.stderr)
    rows.append(_progress("8 multi_stat_fused 1.1GB", lt8, tt8,
                          "exact*" if ok8 else "MISMATCH"))

    # ---- config 9: multi-tenant serve (ISSUE 8) ----------------------
    # the load generator: N tenants, each an IDENTICAL streamed
    # reduction over a storage-latency-bound source (the per-slab sleep
    # emulates the object-store/DMA fetch a production loader pays —
    # on this container that wait is what concurrency can recover; the
    # on-device program itself is config 6/7's).  The serialised
    # baseline runs the same four jobs one at a time; the serve row's
    # "speedup" column IS the aggregate-throughput scaling factor the
    # acceptance gate demands (>= 2.5x at 4 tenants).  Engine-counter
    # proof rides along: a COLD 4-tenant round compiles exactly what a
    # cold single tenant does, and every tenant's result is
    # bit-identical to its single-tenant run.
    from bolt_tpu import serve as _serve
    shape9 = (2048, 256, 64)                      # 128 MB per tenant
    x9 = lcg_np(shape9, salt=9)
    lat9 = float(os.environ.get("BOLT_SERVE_BENCH_LATENCY", "0.025"))
    tenants9 = 4

    def read9(idx):
        time.sleep(lat9)                 # emulated storage fetch latency
        return x9[idx]

    def make9():
        src = bolt.fromcallback(read9, shape9, mode="tpu",
                                dtype=np.float32, chunks=128)  # 16 slabs
        return src.map(ADD1).sum()

    sync(make9())                                 # compile slab programs
    ref9 = np.asarray(make9().toarray())          # single-tenant result

    t0 = time.perf_counter()
    for _ in range(tenants9):
        sync(make9())                             # one at a time
    ser9 = time.perf_counter() - t0

    with _serve.serving(workers=tenants9, queue_limit=2 * tenants9) as sv:
        t0 = time.perf_counter()
        futs = [sv.submit(make9(), tenant="t%d" % i)
                for i in range(tenants9)]
        outs9 = [f.result(timeout=600) for f in futs]
        conc9 = time.perf_counter() - t0
        lats = sorted(f.finished_s - f.submitted_s for f in futs)
        depth_hw9 = sv.stats()["queue_depth_high_water"]
    p50 = lats[len(lats) // 2]
    p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))]
    bit9 = all(np.array_equal(np.asarray(o.toarray()), ref9)
               for o in outs9)

    # the ONE-compile proof: cold 4-tenant round vs cold single tenant
    _engine8.clear()
    c0 = _engine8.counters()
    with _serve.serving(workers=tenants9) as sv:
        [f.result(timeout=600) for f in
         [sv.submit(make9(), tenant="t%d" % i) for i in range(tenants9)]]
    c1 = _engine8.counters()
    four9 = (c1["misses"] - c0["misses"],
             c1["aot_compiles"] - c0["aot_compiles"])
    _engine8.clear()
    c0 = _engine8.counters()
    sync(make9())
    c1 = _engine8.counters()
    one9 = (c1["misses"] - c0["misses"],
            c1["aot_compiles"] - c0["aot_compiles"])

    nbytes9 = int(np.prod(shape9)) * 4
    agg_gbps = tenants9 * nbytes9 / conc9 / 1e9
    ser_gbps = tenants9 * nbytes9 / ser9 / 1e9
    ok9 = (bit9 and four9 == one9 and ser9 / conc9 >= 2.5
           and depth_hw9 <= 2 * tenants9)
    print("   serve_multitenant: %d tenants x %d MB, aggregate %.2f GB/s "
          "vs serialised %.2f GB/s (%.2fx, gate >= 2.5x), latency "
          "p50 %.3fs p99 %.3fs, cold compiles 4-tenant %s == 1-tenant "
          "%s, queue depth hw %d, per-slab storage latency %gs"
          % (tenants9, nbytes9 >> 20, agg_gbps, ser_gbps, ser9 / conc9,
             p50, p99, four9, one9, depth_hw9, lat9), file=sys.stderr)
    rows.append(_progress("9 serve_multitenant 4x128MB", ser9, conc9,
                          "exact*" if ok9 else "MISMATCH"))

    # ---- config 10: resumable streams (ISSUE 9) ----------------------
    # the kill -9 proof as a measured row: a child process streams the
    # canonical 8-slab reduction, is SIGKILLed at upload 6 by the
    # BOLT_CHAOS env, and a fresh child resumes from the surviving
    # slab-level checkpoint.  "local s" is the clean child's in-run
    # wall, "tpu s" the resumed child's (it streams only the remaining
    # slabs — the gate is recovery < 1.5x clean, plus bit-identity).
    # This process holds the chip, so the children run on the CPU
    # backend (chaos_run._child_env): the row's seconds are CPU seconds.
    import importlib.util as _ilu
    _spec = _ilu.spec_from_file_location(
        "chaos_run", os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "chaos_run.py"))
    _chaos_run = _ilu.module_from_spec(_spec)
    _spec.loader.exec_module(_chaos_run)
    r10 = _chaos_run.run_resume_bench()
    ok10 = (r10["identical"] and r10["resumes"] >= 1
            and not r10["stale_checkpoint"]
            and r10["recovery_s"] < 1.5 * r10["clean_s"])
    print("   stream_resume (CPU children): killed rc=%s at upload 6/8, "
          "resumed %d of %d slabs, recovery %.3fs vs clean %.3fs "
          "(gate < 1.5x), bit-identical %s"
          % (r10["killed_rc"], r10["slabs_resumed"], r10["slabs_total"],
             r10["recovery_s"], r10["clean_s"], r10["identical"]),
          file=sys.stderr)
    rows.append(_progress("10 stream_resume kill -9 (cpu)", r10["clean_s"],
                          r10["recovery_s"],
                          "exact*" if ok10 else "MISMATCH"))

    # ---- config 11: pod-scale streaming (ISSUE 10) -------------------
    # a REAL 2-process jax.distributed localhost CPU cluster streams the
    # per-process fromcallback sum (each process produces and uploads
    # only its own shard of every slab; the cross-host fold is the slab
    # program's psum).  "local s" is the single-process run of the same
    # workload on the same TOTAL device count; "tpu s" the 2-process
    # cluster wall (max across workers).  The aggregate-vs-single ratio
    # and per-process GB/s land on stderr; parity is bit-identity of
    # the folded result across every process and the single run.
    import shutil as _sh11
    mh = _load_mh_harness()
    env11 = {"BOLT_MH_NKEYS": "4096", "BOLT_MH_VDIM": "256",
             "BOLT_MH_CHUNKS": "512"}
    try:
        res11, out11, _ = mh.run_cluster("bench", nproc=2, devs=1,
                                         env=env11)
        res11s, out11s, _ = mh.run_cluster("bench", nproc=1, devs=2,
                                           env=env11)
    except RuntimeError as exc:
        # an environment without the CPU cross-process collective
        # transport must not lose configs 1-10's results to config 11
        print("   multihost_stream SKIPPED: %s" % exc, file=sys.stderr)
    else:
        wall11 = max(r["wall_s"] for r in res11)
        single11 = res11s[0]["wall_s"]
        nbytes11 = 4096 * 256 * 4
        per_proc = [r["transfer_bytes"] / r["wall_s"] / 1e9
                    for r in res11]
        ref11 = np.load(os.path.join(out11s, "bench_sum.0.npy"))
        bit11 = all(np.array_equal(
            np.load(os.path.join(out11, "bench_sum.%d.npy" % p)), ref11)
            for p in (0, 1))
        ok11 = (bit11 and all(r["recompiles_warm"] == 0 for r in res11)
                and all(r["leaked_spans"] == 0 for r in res11))
        print("   multihost_stream: 2 processes x %d MB/2, per-process "
              "%s GB/s, aggregate-vs-single-process ratio %.2fx, warm "
              "recompiles %s, bit-identical across pod %s"
              % (nbytes11 >> 20,
                 ["%.2f" % g for g in per_proc], single11 / wall11,
                 [r["recompiles_warm"] for r in res11], bit11),
              file=sys.stderr)
        rows.append(_progress("11 multihost_stream 2proc", single11,
                              wall11, "exact*" if ok11 else "MISMATCH"))
        _sh11.rmtree(out11, ignore_errors=True)
        _sh11.rmtree(out11s, ignore_errors=True)

    # ---- config 12: pod fault tolerance (ISSUE 11) -------------------
    # kill -9 of one process in a 3-process cluster: every survivor
    # raises PeerLostError (watchdog within 2x BOLT_POD_TIMEOUT),
    # reforms onto the 2 survivors and resumes from the rendezvous-
    # consistent checkpoint.  "local s" is the clean 2-process run of
    # the same workload, "tpu s" the RECOVERY wall (learn -> barrier
    # probe -> reform -> resume); the gate is recovery < 2.0x clean
    # plus bit-identity to the unkilled run.
    try:
        r12 = mh.run_reform_bench()
    except RuntimeError as exc:
        print("   multihost_resume SKIPPED: %s" % exc, file=sys.stderr)
    else:
        ok12 = (r12["peer_lost_everywhere"] and r12["bit_identical"]
                and r12["detection_s"] <= 2 * r12["pod_timeout"]
                and r12["recovery_over_clean"] < 2.0
                and r12["stale_checkpoint_files"] == []
                and r12["leaked_spans"] == 0)
        print("   multihost_resume: victim rc %s, detection %.2fs "
              "(deadline %.1fs), reform %.2fs, resume %.2fs — recovery "
              "%.3fs vs clean %.3fs (%.2fx, gate < 2.0x), resumes "
              "sum/stats %d/%d, bit-identical %s"
              % (r12["victim_rc"], r12["detection_s"],
                 r12["pod_timeout"], r12["reform_s"], r12["resume_s"],
                 r12["recovery_s"], r12["clean_s"],
                 r12["recovery_over_clean"], r12["sum_resumes"],
                 r12["stats_resumes"], r12["bit_identical"]),
              file=sys.stderr)
        rows.append(_progress("12 multihost_resume 3->2", r12["clean_s"],
                              r12["recovery_s"],
                              "exact*" if ok12 else "MISMATCH"))

    # ---- config 13: self-healing pods (ISSUE 12) ---------------------
    # kill -9 of one process under Server(supervise=True): the pod
    # shrinks 3->2 AUTOMATICALLY (no caller intervention), a restarted
    # replacement rejoins mid-stream and the pod re-expands 2->3.
    # "local s" is the clean 3-process run of the same supervised
    # workload, "tpu s" the elastic scenario wall; the gate is
    # scenario < 2.5x clean plus bit-identity of every artifact to the
    # unkilled run.
    try:
        r13 = mh.run_supervise_bench()
    except RuntimeError as exc:
        print("   multihost_elastic SKIPPED: %s" % exc, file=sys.stderr)
    else:
        ok13 = (r13["bit_identical"] and r13["rejoined"] == 1
                and r13["nproc_final"] == 3
                and r13["detection_s"] <= 2 * r13["pod_timeout"]
                and r13["scenario_over_clean"] < 2.5
                and r13["arbiter_bytes"] == 0
                and r13["leaked_spans"] == 0
                and r13["stale_ckpt"] == []
                and r13["stale_markers"] == 0)
        print("   multihost_elastic: victim rc %s, detection %.2fs "
              "(deadline %.1fs), auto-reform %.3fs, rejoin recovery "
              "%.3fs — scenario %.3fs vs clean %.3fs (%.2fx, gate "
              "< 2.5x), resumes A/B %d/%d, final width %d, "
              "bit-identical %s"
              % (r13["victim_rc"], r13["detection_s"],
                 r13["pod_timeout"], r13["reform_s"], r13["rejoin_s"],
                 r13["scenario_s"], r13["clean_s"],
                 r13["scenario_over_clean"], r13["a_resumes"],
                 r13["b_resumes"], r13["nproc_final"],
                 r13["bit_identical"]),
              file=sys.stderr)
        rows.append(_progress("13 multihost_elastic 3->2->3",
                              r13["clean_s"], r13["scenario_s"],
                              "exact*" if ok13 else "MISMATCH"))

    # ---- config 14: continuous micro-batching (ISSUE 13) -------------
    # the high-QPS small-request firehose: many SAME-SHAPE map->sum
    # requests against ONE serve worker.  The unbatched leg dispatches
    # one 8-device program per request — per-request launch + collective
    # rendezvous, not bytes, is the roofline — while the batched leg
    # coalesces up to 16 requests into one stacked/vmapped dispatch
    # (Server(batching=...), bolt_tpu/tpu/batched.py).  Saturation
    # methodology: the queue is pre-filled behind a parked worker and
    # the measured wall is the DRAIN — aggregate server throughput at
    # high offered QPS; "local s" is the unbatched leg, "tpu s" the
    # batched one, so the speedup column IS the >= 3x acceptance gate.
    # Rides along: bit-identity of every batched result to its
    # standalone dispatch, zero fresh compiles at steady state (bucketed
    # widths pre-warmed via batched.warm), and the sparse single-request
    # p50 with batching ARMED staying < 1.2x of the unbatched server's.
    import threading as _threading
    from bolt_tpu import serve as _serve14
    from bolt_tpu.tpu import batched as _batched14
    shape14 = (128, 32)
    nreq14, nb14 = 256, 8
    xs14 = [lcg_np(shape14, salt=140 + i) for i in range(nb14)]
    b14 = [lcg_tpu(shape14, salt=140 + i).cache() for i in range(nb14)]

    def make14(i=0):
        return b14[i % nb14].map(ADD1).sum()

    refs14 = [np.asarray(make14(i).toarray()) for i in range(nb14)]

    def saturated14(sv):
        # the drain window is SERVER-side: first dispatch opportunity
        # (the gate opening) to the last future's finished_s — the
        # client's result-collection loop stays outside the window,
        # exactly like timed_tpu keeps the host fetch outside
        best = float("inf")
        outs = None
        for _ in range(3):
            gate = _threading.Event()
            blocker = sv.submit(gate.wait)       # parks the ONE worker
            futs = [sv.submit(make14(i), tenant="t%d" % (i % 4))
                    for i in range(nreq14)]
            t0 = time.perf_counter()
            gate.set()
            outs = [f.result(timeout=600) for f in futs]
            best = min(best, max(f.finished_s for f in futs) - t0)
            blocker.result(timeout=30)
        return best, outs

    def sparse14(sv, n=30):
        # min-of-2 medians: a single 30-request window's median is
        # noisy on a loaded 1-core container, and the p50 gate compares
        # two separately-measured windows
        meds = []
        [sv.submit(make14()).result(timeout=60) for _ in range(5)]
        for _ in range(2):
            lats = []
            for _ in range(n):
                f = sv.submit(make14())
                f.result(timeout=60)
                lats.append(f.finished_s - f.submitted_s)
                time.sleep(0.005)
            lats.sort()
            meds.append(lats[len(lats) // 2])
        return min(meds)

    from bolt_tpu import engine as _engine14
    with _serve14.serving(workers=1, queue_limit=2 * nreq14) as sv:
        [f.result(timeout=60) for f in
         [sv.submit(make14(i)) for i in range(16)]]          # warm
        wall14u, _ = saturated14(sv)
        p50_off = sparse14(sv)
    with _serve14.serving(workers=1, queue_limit=2 * nreq14,
                          batching={"max_batch": 16,
                                    "linger": 0.002}) as sv:
        _batched14.warm(make14, buckets=sv.batching.buckets)
        [f.result(timeout=60) for f in
         [sv.submit(make14(i)) for i in range(16)]]          # warm
        c0 = _engine14.counters()
        wall14b, outs14 = saturated14(sv)
        c1 = _engine14.counters()
        p50_on = sparse14(sv)
        st14 = sv.stats()["batching"]
    bit14 = all(np.array_equal(np.asarray(o.toarray()), refs14[i % nb14])
                for i, o in enumerate(outs14))
    recompiled14 = (c1["misses"] - c0["misses"]
                    + c1["aot_compiles"] - c0["aot_compiles"])
    occ14 = ((c1["batched_requests"] - c0["batched_requests"])
             / max(1, c1["batched_dispatches"] - c0["batched_dispatches"]))
    dpr14 = (c1["dispatches"] - c0["dispatches"]) / (3.0 * nreq14)
    ratio14 = wall14u / wall14b
    p50r14 = p50_on / p50_off
    ok14 = (bit14 and ratio14 >= 3.0 and recompiled14 == 0
            and p50r14 < 1.2)
    print("   serve_smallreq: %d x %s requests, 1 worker — aggregate "
          "%.0f req/s batched vs %.0f unbatched (%.2fx, gate >= 3x), "
          "occupancy %.1f, dispatches/request %.3f, steady-state "
          "recompiles %d, sparse p50 %.0f/%.0f us (%.2fx, gate < 1.2x), "
          "bit-identical %s"
          % (nreq14, shape14, nreq14 / wall14b, nreq14 / wall14u,
             ratio14, occ14, dpr14, recompiled14, 1e6 * p50_on,
             1e6 * p50_off, p50r14, bit14), file=sys.stderr)
    print("   batching stats: %s" % (st14,), file=sys.stderr)
    rows.append(_progress("14 serve_smallreq 256x16KB", wall14u, wall14b,
                          "exact*" if ok14 else "MISMATCH"))
    del xs14

    # ---- config 15: codec-encoded ingest (ISSUE 14) ------------------
    # the SAME transfer-bound streamed sum as config 6/7, with the
    # ingest codec armed: uploader workers ENCODE each slab on host,
    # the wire representation crosses the link (transfer counters
    # prove the ratio), and the slab program DECODES on device fused
    # into the fold.  "local s" is the RAW f32 streamed pass, "tpu s"
    # the bf16-encoded one — the speedup column is the wall-clock win
    # of moving half the bytes on this attach; the int8 (0.25x) and
    # lossless delta-f32 (1.0x, bit-exact) legs ride along.  Parity
    # gates: bf16 wire bytes <= 0.55x raw, delta BIT-IDENTICAL to the
    # raw pass, lossy legs inside their documented envelopes.
    shape15 = (8192, 256, 64)                     # 0.5 GB raw
    x15 = lcg_np(shape15, salt=15)

    def launch15(codec=None):
        src = bolt.fromcallback(lambda idx: x15[idx], shape15,
                                mode="tpu", dtype=np.float32,
                                chunks=512, codec=codec)
        return src.sum()

    def run15(codec=None):
        c0 = _profile.engine_counters()
        t0 = time.perf_counter()
        out = launch15(codec)
        sync(out)
        wall = time.perf_counter() - t0
        c1 = _profile.engine_counters()
        return (np.asarray(out.toarray()), wall,
                c1["transfer_bytes"] - c0["transfer_bytes"])

    with _stream.uploaders(4):
        for cdc in (None, "bf16", "int8", "delta-f32"):
            sync(launch15(cdc))                   # compile slab programs
        ref15, traw15, braw15 = run15()
        out15b, tb15, bb15 = run15("bf16")
        out15i, ti15, bi15 = run15("int8")
        out15d, td15, bd15 = run15("delta-f32")
    rb15, ri15, rd15 = (bb15 / braw15, bi15 / braw15, bd15 / braw15)
    bit15 = np.array_equal(out15d, ref15)
    okb15 = allclose(out15b, ref15, rtol=1e-2)
    step15 = (x15.max() - x15.min()) / 255.0
    oki15 = np.max(np.abs(out15i - ref15)) <= step15 / 2 * shape15[0]
    ok15 = (rb15 <= 0.55 and ri15 <= 0.30 and bit15 and okb15
            and bool(oki15))
    print("   stream_codec: raw %.0f MB %.3fs | bf16 %.2fx bytes "
          "%.3fs (%.2fx wall) | int8 %.2fx bytes %.3fs (%.2fx wall) | "
          "delta-f32 %.2fx bytes %.3fs, bit-identical %s | bf16 "
          "envelope ok %s, int8 bound ok %s"
          % (braw15 / 1e6, traw15, rb15, tb15, traw15 / tb15, ri15,
             ti15, traw15 / ti15, rd15, td15, bit15, okb15,
             bool(oki15)), file=sys.stderr)
    rows.append(_progress("15 stream_codec bf16 0.5GB", traw15, tb15,
                          "exact*" if ok15 else "MISMATCH"))
    del x15

    # ---- config 16: out-of-core streamed swap (ISSUE 18) -------------
    # the tentpole leg: a swap RECORDED on a streamed source resolves
    # through the two-phase shuffle (per-slab on-device re-bucket
    # overlapped with ingest, then a resident concat) instead of
    # materialising the whole source first.  "local s" is the
    # materialise-first baseline — cache() the full source into device
    # memory, then the in-memory swap; "tpu s" is the streamed shuffle
    # over the SAME callback source, so the speedup column is what
    # overlapping the re-bucket with ingest buys on this attach.  The
    # forced-spill leg (budget ~ one bucket: every re-keyed bucket
    # rides the checkpoint-slab spill files to disk and phase 2
    # re-streams them) rides along on stderr with its byte gauges.
    import shutil as _sh16m
    import tempfile as _tf16m
    from bolt_tpu import checkpoint as _ckpt16m
    shape16 = (2048, 256, 64)                     # 128 MB raw
    x16 = lcg_np(shape16, salt=16)

    def launch16():
        src = bolt.fromcallback(lambda idx: x16[idx], shape16,
                                mode="tpu", dtype=np.float32,
                                chunks=256)
        return src.swap((0,), (0,))

    def mat16():
        src = bolt.fromcallback(lambda idx: x16[idx], shape16,
                                mode="tpu", dtype=np.float32,
                                chunks=256)
        src.cache()
        return src.swap((0,), (0,))

    with _stream.uploaders(4):
        np.asarray(launch16()._data)              # compile both phases
        t16s, t16m = float("inf"), float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            out16m = np.asarray(mat16()._data)
            t16m = min(t16m, time.perf_counter() - t0)
        for _ in range(2):
            t0 = time.perf_counter()
            out16s = np.asarray(launch16()._data)
            t16s = min(t16s, time.perf_counter() - t0)
        td16m = _tf16m.mkdtemp(prefix="bolt-bench16-")
        try:
            with _stream.spill(dir=td16m, budget=1):
                t0 = time.perf_counter()
                out16sp = np.asarray(launch16()._data)
                t16sp = time.perf_counter() - t0
            c16 = _profile.engine_counters()
            stale16 = _ckpt16m.spill_pending(td16m)
            _ckpt16m.spill_clear(td16m)
        finally:
            _sh16m.rmtree(td16m, ignore_errors=True)
    bit16 = (np.array_equal(out16s, out16m)
             and np.array_equal(out16sp, out16m)
             and np.array_equal(out16m, np.transpose(x16, (1, 0, 2))))
    ok16 = bit16 and stale16                      # the spill leg spilled
    print("   stream_swap: %d MB streamed %.3fs vs materialise-first "
          "%.3fs (%.2fx) | forced-spill %.3fs (spill %.0f MB, shuffle "
          "%.0f MB moved) | all legs bit-identical %s"
          % (x16.nbytes // 2**20, t16s, t16m, t16m / t16s, t16sp,
             c16["spill_bytes"] / 1e6, c16["shuffle_bytes"] / 1e6,
             bit16), file=sys.stderr)
    rows.append(_progress("16 stream_swap 128MB", t16m, t16s,
                          "exact" if ok16 else "MISMATCH"))
    del x16

    print("%-26s %10s %10s %9s  %s" % ("config", "local s", "tpu s", "speedup", "parity"))
    for name, lt, tt, parity in rows:
        print("%-26s %10.4f %10.4f %8.1fx  %s" % (name, lt, tt, lt / tt, parity))
    print("(tpu column: steady-state device time; filter results are "
          "lazy-count, so config 4 pipelines like the rest and pays its "
          "single count sync only at the closing resolution.  exact* = "
          "bit-exact on sampled slices of a multi-GB result, full fetch "
          "skipped — inputs are bit-identical LCG data on both sides)",
          file=sys.stderr)
    if any(r[3] == "MISMATCH" for r in rows):
        sys.exit(1)


if __name__ == "__main__":
    if "--check" in sys.argv:
        sys.exit(check_configs())
    from bolt_tpu import obs
    trace_path = obs.trace_arg(sys.argv)
    if trace_path:
        code = 0
        try:
            with obs.timeline(trace_path):
                main()
        except SystemExit as e:       # a parity MISMATCH exit: the trace
            code = e.code or 0        # of the FAILED run is the point —
        #                               report it before re-exiting
        print(obs.report(), file=sys.stderr)
        print("obs timeline written to %s (load in chrome://tracing or "
              "Perfetto)" % trace_path, file=sys.stderr)
        sys.exit(code)
    main()
