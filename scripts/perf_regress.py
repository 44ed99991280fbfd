#!/usr/bin/env python
"""Per-op-family perf-regression harness (round 2, VERDICT r1 next-2).

Measures steady-state device throughput for each core op family at
device-dominated sizes (every config ≥ ~0.9 GB, so a per-launch floor
of a few ms stays a small part of any timing), prints
one JSON line per family, writes ``PERF.json``, and — when a committed
``PERF_BASELINE.json`` exists — reports any family slower than baseline
by more than ``THRESHOLD`` (exit code 2, so CI can warn without
conflating regressions with failures).

BASELINE CONVENTION: the committed baseline records a conservative
LOW-WATER mark per family — the worst throughput observed across
healthy measurement windows — because the host this was built on
varied 2-4× between windows on some families.  The gate therefore fires
on genuine
collapses, not on drawing an unlucky window against a lucky baseline.
A plain ``--rebaseline`` records the CURRENT window; hand-adjust toward
the low-water mark after collecting a few runs.

Usage::

    python scripts/perf_regress.py              # measure + compare
    python scripts/perf_regress.py --rebaseline # overwrite the baseline
    python scripts/perf_regress.py --trace out.json  # + obs timeline:
        # Chrome trace-event export of the whole run, and each family's
        # PERF.json entry gains a span-derived "phases" breakdown
    python scripts/perf_regress.py --families=a,b    # measure a subset:
        # comma list of family names; a token "platform:cpu" expands to
        # every committed family whose last entry was measured on that
        # backend — so a real-chip window re-measures exactly the
        # container-tagged families without a full sweep
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import bolt_tpu as bolt  # noqa: E402

THRESHOLD = 0.25   # fractional slowdown vs baseline that counts as a regression
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "PERF.json")
BASE = os.path.join(ROOT, "PERF_BASELINE.json")

# Chip resource peaks (v5e, per chip), for the %-of-peak accounting
# (VERDICT r3 next-1: GB/s is the wrong axis for MXU-bound families).
# HBM: 819 GB/s from the v5e spec (16 GB HBM2E); the best one-pass
# number this framework has measured on this chip is 616 GB/s (~75%),
# so treat ~0.75 as the practical per-op ceiling when reading pct_hbm.
# MXU: 197 bf16 TFLOP/s.  f32 matmuls run on the MXU as bf16-pass
# decompositions: precision="default" 1 pass, "high" 3 (error ~f32),
# "highest" 6 (ulp-level) -> the f32-highest peak is 197/6 = 32.8.
HBM_PEAK_GBPS = 819.0
MXU_PEAK_TFLOPS = {"bf16": 197.0, "f32_high": 197.0 / 3, "f32_highest": 197.0 / 6}


# TIMING (reworked round 3, VERDICT r2 #7): built for a host whose
# result fetch was slow and noisy (tens of ms, varying minute to
# minute) and on which ``block_until_ready`` did not block.  A
# measured-and-subtracted probe round-trip left a residual of tens of
# ms whenever the round-trip drifted between its measurement and its
# use, which silently turned sub-5 GB families into LATENCY
# measurements.  Two fetch-proof forms replaced it.  On the chip's own
# host ``block_until_ready`` blocks and a scalar fetch costs under a
# millisecond (PERF.md, PR 21): ROADMAP S0 replaces these clocks.
#
# * ``steady_amortized`` — queue many independent launches, ONE closing
#   fetch; bias <= round-trip/iters (~2.3 ms at the default 48; the
#   pca family accepts ~14 ms at iters=8 against its 0.23 s/iter
#   signal).  For families whose outputs are small (reductions) so
#   queued results can't fill HBM.
# * ``steady_chain`` — each launch consumes the previous result, so at
#   most two buffers are ever alive regardless of queue depth; same
#   single amortized fetch.  For families with input-sized outputs
#   (swap, matmul, halo, filter-via-padded-buffer).

_PROBE = jax.jit(lambda t: t.ravel()[0])


def _tiny(r):
    """Reduce a family result to a one-scalar fetch (families return a
    bolt array, a jax array, or a tuple whose head is one)."""
    if isinstance(r, tuple):
        r = r[0]
    return _PROBE(r.tojax() if hasattr(r, "tojax") else r)


def steady_amortized(launch, iters=48):
    jax.device_get(_tiny(launch()))          # compile + drain
    t0 = time.perf_counter()
    for _ in range(iters):
        r = launch()
    jax.device_get(_tiny(r))
    return (time.perf_counter() - t0) / iters


def steady_chain(x0, step, iters=24, warm=4):
    x = x0
    for _ in range(warm):                    # compile the cycle's programs
        x = step(x)
    jax.device_get(_tiny(x))                 # drain
    t0 = time.perf_counter()
    for _ in range(iters):
        x = step(x)
    jax.device_get(_tiny(x))
    return (time.perf_counter() - t0) / iters


# Every family generates its data ON DEVICE (bolt.randn/ones), so set-up
# measures no host→device link.  ``bytes`` is the logical input
# size — the GB/s figures are per-pass-over-the-input throughput,
# comparable across rounds, not absolute HBM traffic.

MAPSUM_FN = lambda v: v + 1
FILTER_PRED = lambda v: v.mean() > 0


def fam_map_sum():
    shape = (8192, 256, 256)                      # 2.1 GB f32
    b = bolt.ones(shape, mode="tpu", dtype=np.float32).cache()
    # .cache() forces the LAZY stat terminal to dispatch (async) so
    # every queued launch really runs — stat results are pending
    # fused-group handles since the bolt.compute layer
    return int(np.prod(shape)) * 4, steady_amortized(
        lambda: b.map(MAPSUM_FN).sum(axis=(0, 1, 2)).cache()), {
        "bound": "hbm",
        "traffic": (1.0, "one fused read pass; output is a scalar")}


def fam_stats_welford():
    # the shard_map Welford (pallas fused_welford engages — 128-aligned
    # minor dim); times the compiled program via the executable cache,
    # with the same probe-roundtrip subtraction as every other family
    from bolt_tpu.tpu.array import _JIT_CACHE
    shape = (8192, 256, 256)
    nbytes = int(np.prod(shape)) * 4
    b = bolt.ones(shape, mode="tpu", dtype=np.float32).cache()
    b.stats()
    prog = next(v for k, v in _JIT_CACHE.items() if k[0] == "welford")
    data = b._data
    return nbytes, steady_amortized(lambda: prog(data)), {
        "bound": "hbm",
        "traffic": (1.0, "one fused pallas read pass; moments are tiny")}


def fam_swap():
    shape = (1024, 128, 64, 64)                   # 2.1 GB
    b = bolt.randn(shape, mode="tpu", axis=(0, 1), seed=3,
                   dtype=np.float32).cache()
    # NOT a chain: chained swaps rotate through arrangements whose
    # transposes cost wildly different amounts (some move the minor
    # dim), which would measure a layout mix instead of THE exchange.
    # Amortized queueing is safe — the runtime keeps ~2 executions in
    # flight, so 2.1 GB outputs never stack (measured: no OOM at 48).
    return int(np.prod(shape)) * 4, steady_amortized(
        lambda: b.swap((0,), (0,)), iters=48), {
        "bound": "hbm",
        "traffic": (2.0, "read + transposed write per byte (single "
                         "chip; a mesh's all_to_all exchange rides on "
                         "top)")}


def fam_filter_fused():
    from bolt_tpu.tpu.array import BoltArrayTPU
    shape = (14336, 256, 64)                      # 0.94 GB
    b = bolt.randn(shape, mode="tpu", seed=4, dtype=np.float32).cache()

    def step(arr):
        # the padded compaction buffer has the input's shape, so the
        # chain feeds each filter the previous one's buffer (garbage
        # rows are data like any other) — one cached program throughout.
        # filter() now defers; _resolve_fpending dispatches the
        # compaction program without syncing the count
        out = arr.filter(FILTER_PRED)
        out._resolve_fpending()
        return BoltArrayTPU(out._pending[0], 1, arr.mesh)

    return int(np.prod(shape)) * 4, steady_chain(b, step, iters=24), {
        "bound": "hbm",
        "traffic": (3.0, "materialising filter: mask + count + compact "
                         "= ~3 passes over the input (round-3 measured "
                         "~330 GB/s real traffic); reduction terminals "
                         "take the 1-pass filter_sum_fused path instead")}


def fam_filter_sum_fused():
    # the ISSUE-1 fused terminal: filter(...).sum() folds the predicate
    # mask into the reduction combine — ONE pass over the input, no
    # compaction buffer ever materialises (engine.py + _fused_filter_stat)
    shape = (14336, 256, 64)                      # 0.94 GB
    b = bolt.randn(shape, mode="tpu", seed=4, dtype=np.float32).cache()
    return int(np.prod(shape)) * 4, steady_amortized(
        lambda: b.filter(FILTER_PRED).sum().cache(), iters=32), {
        "bound": "hbm",
        "traffic": (1.0, "single fused mask+reduce pass; the (256, 64) "
                         "output is ~0.003% of the input")}


def fam_matmul():
    # the MXU path (highest precision, numpy-parity default); the weight
    # is device-resident — a host ndarray operand would re-upload per call
    n = 8192                                      # 0.8 GB of operands
    # x @ w keeps the shape: chain the product through itself; w is
    # scaled so the chain's magnitude stays ~O(1) per link (a randn
    # product grows ~sqrt(n)x per matmul — 16 links would reach f32 inf)
    w = bolt.randn((n, n), mode="tpu", seed=8, dtype=np.float32).tojax() \
        * np.float32(1.0 / np.sqrt(n))
    b = bolt.randn((n, n), mode="tpu", seed=7, dtype=np.float32).cache()
    sec = steady_chain(b, lambda x: x @ w, iters=16)
    return 2 * n * n * 4, sec, {"bound": "mxu", "flops": 2 * n ** 3,
                                "precision": "f32_highest"}


def fam_matmul_bf16():
    # the MXU's native mode: bf16 operands, precision="default" (one
    # MXU pass — dot(precision=) is the public opt-in, tpu/array.py).
    # This is the family that can approach the chip's 197 TFLOP/s.
    n = 8192
    w = (bolt.randn((n, n), mode="tpu", seed=8, dtype=np.float32).tojax()
         * np.float32(1.0 / np.sqrt(n))).astype(jnp.bfloat16)
    b = bolt.randn((n, n), mode="tpu", seed=7,
                   dtype=np.float32).astype(jnp.bfloat16).cache()
    sec = steady_chain(b, lambda x: x.dot(w, precision="default"), iters=24)
    return 2 * n * n * 2, sec, {"bound": "mxu", "flops": 2 * n ** 3,
                                "precision": "bf16"}


def fam_halo_gaussian():
    from bolt_tpu.ops import gaussian
    shape = (64, 2048, 4096)                      # 2.1 GB
    b = bolt.randn(shape, mode="tpu", seed=6, dtype=np.float32).cache()
    return int(np.prod(shape)) * 4, steady_chain(
        b, lambda x: gaussian(x, sigma=2.0, axis=(0, 1), size="64"),
        iters=12), {
        "bound": "hbm",
        "traffic": (4.0, "two per-axis kernel passes (sublane window + "
                         "lane band matmul), each read + write")}


def fam_segment_reduce():
    from bolt_tpu.ops import segment_reduce
    # few records x big blocks: the public API uploads labels per call,
    # so the label vector is kept tiny (32 KB) — a 131072-label variant
    # measured the label upload, not the scatter combine
    shape = (8192, 1024, 64)                      # 2.1 GB
    b = bolt.randn(shape, mode="tpu", seed=9, dtype=np.float32).cache()
    labels = np.arange(shape[0]) % 256

    return int(np.prod(shape)) * 4, steady_amortized(
        lambda: segment_reduce(b, labels, num_segments=256, op="sum"),
        iters=32), {
        "bound": "hbm",
        "traffic": (1.0, "one matmul read pass (one-hot path); the "
                         "(256, V) output is ~3% of the input")}


def fam_pca():
    from bolt_tpu.ops import pca
    b = bolt.randn((33554432, 16), mode="tpu", seed=5).cache()  # 2.1 GB

    def run_pca():
        # fetch=False: the async path — the default's batched host fetch
        # of comps/svals is one host round-trip per call; the family
        # gates the compiled program
        scores, comps, svals = pca(b, k=4, center=True, fetch=False)
        return svals            # scores stay sharded in HBM; probe the
                                # small vector so queued iterations don't
                                # stack score buffers
    n, d, k = 33554432, 16, 4
    sec = steady_amortized(run_pca, iters=8)
    # Gram 2nd^2 + projection 2ndk (+ the d x d eigh, negligible):
    # arithmetic intensity (d + k)/4 ~ 5 flops/byte << the chip's ~240
    # flops/byte balance point -> HBM-bound by design (the Gram route's
    # whole point is one pass over the data)
    return n * d * 4, sec, {"bound": "hbm",
                            "flops": 2 * n * d * d + 2 * n * d * k,
                            "precision": "f32_highest",
                            "traffic": (3.0, "mean + Gram + projection "
                                             "each read the input once "
                                             "(center=True)")}


def fam_svdvals():
    from bolt_tpu.ops import svdvals
    # batched tall-skinny Gram route (BASELINE config 5b's per-chunk SVD
    # shape): d=64 is the largest dim the jacobi router accepts, batch 64
    # puts it on the jacobi path; intensity d/2 = 32 flops/byte -> still
    # HBM-bound (balance point ~240), reported as such
    batch, n, d = 64, 131072, 64                  # 2.1 GB f32
    x = bolt.randn((batch, n, d), mode="tpu", seed=12,
                   dtype=np.float32).tojax()
    fn = jax.jit(svdvals)
    jax.block_until_ready(fn(x))
    sec = steady_amortized(lambda: fn(x), iters=24)
    return batch * n * d * 4, sec, {"bound": "hbm",
                                    "flops": 2 * batch * n * d * d,
                                    "precision": "f32_highest",
                                    "traffic": (1.0, "one Gram read "
                                                     "pass")}


def fam_jacobi_eigh():
    from bolt_tpu.ops.linalg import jacobi_eigh
    # the batched small-matrix eigensolver (the PCA family's (d, d)
    # kernel, stress-shaped: many matrices).  Neither HBM- nor MXU-bound:
    # the sweep chain is a fixed number of sequential rounds of
    # elementwise rotations (one Mosaic kernel over 128 lane blocks on a
    # TPU, a lax.scan of gather + elementwise rounds elsewhere) — its
    # wall clock is round-count x per-round latency, so the family gates
    # regressions in the schedule/rotation formulation, not a bandwidth
    # number.
    batch, n = 16384, 16                          # 67 MB of matrices
    g = bolt.randn((batch, n, n), mode="tpu", seed=13,
                   dtype=np.float32).tojax()
    g = g + jnp.swapaxes(g, -1, -2)               # symmetric
    fn = jax.jit(jacobi_eigh)
    jax.block_until_ready(fn(g))
    sec = steady_amortized(lambda: fn(g), iters=24)
    # ~12 B m^2 flops per rotation round x sweeps*(m-1) rounds (+trig);
    # the sweep count comes from the solver's own default so a retune
    # there keeps this estimate honest
    from bolt_tpu.ops.linalg import _default_sweeps
    sweeps = _default_sweeps(n, jnp.float32)
    flops = sweeps * (n - 1) * 12 * batch * n * n
    return batch * n * n * 4, sec, {"bound": "latency", "flops": flops,
                                    "precision": "f32"}


def fam_stream_sum():
    # the streaming out-of-core executor, ISSUE-5 form: host-resident
    # data streamed through the N-way UPLOADER POOL (workers produce and
    # upload slabs concurrently as per-device sub-blocks, a re-sequencer
    # keeps the fold in slab order), slab programs dispatched ASYNC into
    # the bounded in-flight window with the level-0 fold fused in (slab
    # buffers donated, the ring recycles).  This family gauges the
    # host->device INGEST link with compute overlapped — transfer-bound
    # by design, so regressions here mean the pipeline stopped hiding
    # the upload (the chip-side program itself is fam_map_sum's).  The
    # s_per_iter is one full streamed pass, not a queued steady-state
    # launch: a streamed run syncs once, on its final result.
    from bolt_tpu import stream as _stream
    shape = (4096, 256, 64)                       # 0.27 GB over the link
    x = (np.arange(np.prod(shape), dtype=np.int64) % 251).astype(
        np.float32).reshape(shape)

    def run():
        src = bolt.fromcallback(lambda idx: x[idx], shape, mode="tpu",
                                dtype=np.float32, chunks=512)
        return src.chunk(size=(64,), axis=(0,)).map(MAPSUM_FN).sum()

    with _stream.uploaders(4):
        jax.device_get(_tiny(run()))              # compile slab programs
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.device_get(_tiny(run()))
            best = min(best, time.perf_counter() - t0)
    eff = bolt.profile.overlap_efficiency()
    ec = bolt.profile.engine_counters()
    return int(np.prod(shape)) * 4, best, {
        "bound": "transfer",
        "overlap_efficiency": round(eff, 3),
        # the parallel-ingest pipeline's shape, recorded with the number
        # (ISSUE 5): configured pool 4, the OBSERVED concurrent-uploader
        # high-water, and the async dispatch window's peak
        "upload_threads": ec["stream_upload_threads"],
        "inflight_high_water": ec["stream_inflight_high_water"],
        "prefetch_depth": ec["stream_prefetch_depth"],
        "traffic": (1.0, "one host->device pass per byte through the "
                         "uploader pool, overlapped with one fused "
                         "on-device map+sum read pass; level-0 fold "
                         "fused into the slab dispatch, pair partials "
                         "merge on device, one value block returns")}


def fam_stream_codec():
    # the ISSUE-14 compressed-ingest family: the SAME transfer-bound
    # streamed reduction as fam_stream_sum with the bf16 ingest codec
    # armed — uploader workers ENCODE each slab on host, HALF the bytes
    # cross the link (the transfer counters are the proof), and the
    # slab program DECODES on device fused into the fold (zero extra
    # HBM passes).  s_per_iter is the ENCODED pass; the family records
    # the raw pass, the coded-over-raw wall speedup (the bytes-win this
    # attach realises), the measured wire-bytes ratio, and the lossless
    # delta-f32 leg's bit-identity — the accuracy contract's anchor.
    from bolt_tpu import stream as _stream
    shape = (4096, 256, 64)                       # 0.27 GB raw
    x = (np.arange(np.prod(shape), dtype=np.int64) % 251).astype(
        np.float32).reshape(shape)

    def run(codec=None):
        src = bolt.fromcallback(lambda idx: x[idx], shape, mode="tpu",
                                dtype=np.float32, chunks=512,
                                codec=codec)
        return src.chunk(size=(64,), axis=(0,)).map(MAPSUM_FN).sum()

    def best_of(codec, n=3):
        best = float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            jax.device_get(_tiny(run(codec)))
            best = min(best, time.perf_counter() - t0)
        return best

    with _stream.uploaders(4):
        for cdc in (None, "bf16", "delta-f32"):
            jax.device_get(_tiny(run(cdc)))       # compile slab programs
        er0 = bolt.profile.engine_counters()
        raw_s = best_of(None)
        ec0 = bolt.profile.engine_counters()
        coded_s = best_of("bf16")
        ec1 = bolt.profile.engine_counters()
        ref = np.asarray(run(None).toarray())
        lossless = np.asarray(run("delta-f32").toarray())
    ratio = ((ec1["codec_bytes_wire"] - ec0["codec_bytes_wire"])
             / max(1, ec1["codec_bytes_raw"] - ec0["codec_bytes_raw"]))
    # the LINK observable: seconds spent inside counted transfers per
    # pass — on a host where produce/encode hide behind a real PCIe/DCN
    # link this is the bound the codec halves; on this container the
    # ratio shows the win even when the wall is produce-bound
    link_raw = (ec0["transfer_seconds"] - er0["transfer_seconds"]) / 3
    link_coded = (ec1["transfer_seconds"] - ec0["transfer_seconds"]) / 3
    eff = bolt.profile.overlap_efficiency()
    return int(np.prod(shape)) * 4, coded_s, {
        "bound": "transfer",
        "codec": "bf16",
        "raw_s": round(raw_s, 5),
        "coded_over_raw": round(raw_s / coded_s, 2),
        "wire_bytes_ratio": round(ratio, 3),
        "link_seconds_raw": round(link_raw, 5),
        "link_seconds_coded": round(link_coded, 5),
        "link_raw_over_coded": round(link_raw / max(link_coded, 1e-9),
                                     2),
        "lossless_bit_identical": bool(np.array_equal(lossless, ref)),
        "overlap_efficiency": round(eff, 3),
        "encode_seconds": round(
            ec1["codec_encode_seconds"] - ec0["codec_encode_seconds"],
            5),
        "traffic": (0.5, "wire bytes = codec ratio x raw bytes: one "
                         "host->device pass per WIRE byte (bf16 = "
                         "0.5x the raw f32), encoded per slab on the "
                         "uploader workers, decoded on device fused "
                         "into the fold — the gbps figure stays "
                         "per-RAW-pass so it is comparable with "
                         "stream_sum's")}


def fam_stream_swap():
    # the ISSUE-18 out-of-core shuffle family: a swap RECORDED on a
    # streamed source resolves through the two-phase shuffle — phase 1
    # re-buckets each uploaded slab on device the moment it lands,
    # phase 2 concatenates the resident buckets — so the re-axis
    # overlaps ingest instead of waiting for full HBM residency.
    # s_per_iter is the STREAMED swap end to end (produce + upload +
    # re-bucket + concat); the family records the materialise-first
    # wall it replaces (cache() everything, then the in-memory swap),
    # the forced-spill leg (budget ~ one bucket: every re-keyed bucket
    # rides the checkpoint-slab spill files and phase 2 re-streams
    # them from disk), the shuffle/spill byte gauges, and bit-identity
    # of EVERY leg against the transpose oracle — a shuffle moves
    # bytes, it never rounds.
    import shutil
    import tempfile
    from bolt_tpu import stream as _stream

    shape = (2048, 256, 64)                       # 128 MB raw
    x = (np.arange(np.prod(shape), dtype=np.int64) % 251).astype(
        np.float32).reshape(shape)

    def streamed():
        src = bolt.fromcallback(lambda idx: x[idx], shape, mode="tpu",
                                dtype=np.float32, chunks=256)
        return src.swap((0,), (0,))

    def materialised():
        src = bolt.fromcallback(lambda idx: x[idx], shape, mode="tpu",
                                dtype=np.float32, chunks=256)
        src.cache()                               # full HBM residency
        return src.swap((0,), (0,))

    def best_of(run, n=3):
        best, out = float("inf"), None
        for _ in range(n):
            t0 = time.perf_counter()
            out = np.asarray(run()._data)
            best = min(best, time.perf_counter() - t0)
        return best, out

    with _stream.uploaders(4):
        np.asarray(streamed()._data)              # compile both phases
        streamed_s, got = best_of(streamed)
        mat_s, ref = best_of(materialised)
        td = tempfile.mkdtemp(prefix="bolt-perf-spill-")
        try:
            with _stream.spill(dir=td, budget=1):
                t0 = time.perf_counter()
                spilled = np.asarray(streamed()._data)
                spill_s = time.perf_counter() - t0
            sc = bolt.profile.engine_counters()
        finally:
            shutil.rmtree(td, ignore_errors=True)
    bit = (np.array_equal(got, ref) and np.array_equal(spilled, ref)
           and np.array_equal(ref, np.transpose(x, (1, 0, 2))))
    eff = bolt.profile.overlap_efficiency()
    return int(np.prod(shape)) * 4, streamed_s, {
        "bound": "transfer",
        "materialised_s": round(mat_s, 5),
        "streamed_over_materialised": round(streamed_s / mat_s, 2),
        "spill_s": round(spill_s, 5),
        "spill_bytes": int(sc["spill_bytes"]),
        "shuffle_bytes": int(sc["shuffle_bytes"]),
        "bit_identical": bool(bit),
        "overlap_efficiency": round(eff, 3),
        "traffic": (2.0, "one host->device pass per input byte plus "
                         "the on-device re-bucket (read + transposed "
                         "write; a mesh's all_to_all exchange rides on "
                         "top); the forced-spill leg adds a disk round "
                         "trip per byte past the budget")}


def fam_multi_stat_fused():
    # the ISSUE-7 fused multi-stat terminal: bolt.compute(m.sum(),
    # m.var(), m.min(), m.max()) — four terminals from ONE read of a
    # >= 1 GB input (the bytes-read model: 1 fused dispatch over the
    # chain = 1 input pass, vs 4 standalone passes).  The family also
    # records per-terminal-count scaling (1/2/4 fused terminals): on
    # HBM-bound hardware the fused time should stay ~flat with N while
    # the sequential cost grows ~Nx.
    shape = (8192, 256, 128)                      # 1.07 GB f32
    b = bolt.ones(shape, mode="tpu", dtype=np.float32).cache()

    def launch_n(n):
        m = b.map(MAPSUM_FN)
        hs = [m.sum(), m.var(), m.min(), m.max()][:n]
        bolt.compute(*hs)
        return hs[-1]

    def launch_seq():
        # the pre-fusion cost model: resolve one terminal at a time,
        # each singleton group dispatching its own standalone pass
        m = b.map(MAPSUM_FN)
        m.sum().cache()
        m.var().cache()
        m.min().cache()
        return m.max().cache()

    scaling = {}
    for n in (1, 2, 4):
        scaling[str(n)] = round(
            steady_amortized(lambda n=n: launch_n(n), iters=8), 5)
    sec = scaling["4"]
    seq4 = steady_amortized(launch_seq, iters=8)
    ec = bolt.profile.engine_counters()
    return int(np.prod(shape)) * 4, sec, {
        "bound": "hbm",
        "terminals": 4,
        "sequential_4_s": round(seq4, 5),
        "seq_over_fused": round(seq4 / sec, 2),
        "terminal_scaling_s": scaling,
        "fused_stat_groups": ec["fused_stat_groups"],
        "fused_stat_terminals": ec["fused_stat_terminals"],
        "traffic": (1.0, "ONE fused read pass serves all 4 terminals "
                         "(sum/var/min/max); the sequential form costs "
                         "4 passes — the bytes-read model the "
                         "multi_stat_fused bench gate enforces")}


def fam_serve_smallreq():
    # the ISSUE-13 continuous micro-batching family: a firehose of
    # SMALL same-shape map->sum requests against ONE serve worker,
    # where per-request dispatch overhead (program launch + the
    # 8-device collective rendezvous), not bytes, is the roofline.
    # s_per_iter is the BATCHED saturated drain wall (queue pre-filled
    # behind a parked worker = high offered QPS; the drain measures
    # aggregate server throughput); the family records the unbatched
    # drain, the batched-over-unbatched scaling factor (the >= 3x
    # acceptance gate), p50/p99 latency at a sweep of offered QPS for
    # BOTH modes (the low-QPS p50 must hold < 1.2x with batching
    # armed), realised batch occupancy, and dispatches-per-request.
    import threading
    from bolt_tpu import serve as _serve
    from bolt_tpu.tpu import batched as _batched
    shape = (128, 32)
    nreq, nb = 256, 8
    bs = [bolt.randn(shape, mode="tpu", seed=140 + i,
                     dtype=np.float32).cache() for i in range(nb)]

    def make(i=0):
        return bs[i % nb].map(MAPSUM_FN).sum()

    for i in range(nb):
        jax.device_get(_tiny(make(i).cache().tojax()))

    def saturated(sv):
        # server-side drain window: gate opening -> last finished_s
        # (the client's result-collection loop stays outside)
        best = float("inf")
        for _ in range(3):
            gate = threading.Event()
            blocker = sv.submit(gate.wait)       # parks the ONE worker
            futs = [sv.submit(make(i), tenant="t%d" % (i % 4))
                    for i in range(nreq)]
            t0 = time.perf_counter()
            gate.set()
            [f.result(timeout=600) for f in futs]
            best = min(best, max(f.finished_s for f in futs) - t0)
            blocker.result(timeout=30)
        return best

    def qps_curve(sv, levels=(100, 1000, 100000), n=24):
        curve = {}
        [sv.submit(make()).result(timeout=60) for _ in range(5)]
        for qps in levels:
            period = 1.0 / qps
            futs = []
            for i in range(n):
                t0 = time.perf_counter()
                futs.append(sv.submit(make(i), tenant="t%d" % (i % 4)))
                dt = period - (time.perf_counter() - t0)
                if dt > 0:
                    time.sleep(dt)
            for f in futs:
                f.result(timeout=120)
            lats = sorted(f.finished_s - f.submitted_s for f in futs)
            curve[str(qps)] = {
                "p50_s": round(lats[len(lats) // 2], 6),
                "p99_s": round(lats[min(len(lats) - 1,
                                        int(len(lats) * 0.99))], 6)}
        return curve

    with _serve.serving(workers=1, queue_limit=2 * nreq) as sv:
        [f.result(timeout=60) for f in
         [sv.submit(make(i)) for i in range(16)]]
        unbatched = saturated(sv)
        curve_off = qps_curve(sv)
    with _serve.serving(workers=1, queue_limit=2 * nreq,
                        batching={"max_batch": 16,
                                  "linger": 0.002}) as sv:
        _batched.warm(make, buckets=sv.batching.buckets)
        [f.result(timeout=60) for f in
         [sv.submit(make(i)) for i in range(16)]]
        # the counter window covers ONLY the saturated drain rounds:
        # warm()'s throwaway bucket dispatches, the warmup submits and
        # the qps-curve traffic must not contaminate the recorded
        # occupancy/dispatch metrics
        ec0 = bolt.profile.engine_counters()
        batched = saturated(sv)
        ec1 = bolt.profile.engine_counters()
        curve_on = qps_curve(sv)
        occ = (sv.stats()["batching"].get("occupancy") or {})
    dreq = max(1, ec1["batched_requests"] - ec0["batched_requests"])
    nbytes = int(np.prod(shape)) * 4
    return nreq * nbytes, batched, {
        "bound": "dispatch",
        "requests": nreq,
        "unbatched_s": round(unbatched, 5),
        "batched_over_unbatched": round(unbatched / batched, 2),
        "batch_occupancy_mean": occ.get("mean"),
        "dispatches_per_request": round(
            (ec1["dispatches"] - ec0["dispatches"]) / float(dreq), 4),
        "batched_dispatches": ec1["batched_dispatches"]
        - ec0["batched_dispatches"],
        "batched_requests": ec1["batched_requests"]
        - ec0["batched_requests"],
        "qps_curve_batched": curve_on,
        "qps_curve_unbatched": curve_off,
        "p50_low_qps_ratio": round(
            curve_on["100"]["p50_s"] / curve_off["100"]["p50_s"], 3),
        "traffic": (1.0, "N tiny same-shape requests; throughput is "
                         "bounded by per-request dispatch overhead, "
                         "which the coalesced stacked dispatch "
                         "amortises across the bucket width — the "
                         "gbps figure is incidental (requests are "
                         "KB-scale)")}


def fam_serve_multitenant():
    # the ISSUE-8 multi-tenant serving layer: N tenants submit
    # IDENTICAL streamed reductions over storage-latency-bound sources
    # (a per-slab sleep emulates the object-store fetch a production
    # loader pays; that wait is what the scheduler's concurrency
    # recovers — the on-device program is fam_map_sum's).  s_per_iter
    # is the CONCURRENT wall for all N tenants; the family records the
    # serialised one-at-a-time wall, the aggregate-over-serialised
    # scaling factor (the >= 2.5x acceptance gate), p50/p99 per-job
    # latency over two rounds, and the admission/arbiter shape.
    from bolt_tpu import serve as _serve
    from bolt_tpu.obs import metrics as _metrics
    tenants = 4
    shape = (1024, 256, 64)                       # 64 MB per tenant
    x = (np.arange(np.prod(shape), dtype=np.int64) % 251).astype(
        np.float32).reshape(shape)
    lat = float(os.environ.get("BOLT_SERVE_BENCH_LATENCY", "0.025"))

    def read(idx):
        time.sleep(lat)                  # emulated storage fetch latency
        return x[idx]

    def make():
        src = bolt.fromcallback(read, shape, mode="tpu",
                                dtype=np.float32, chunks=128)  # 8 slabs
        return src.map(MAPSUM_FN).sum()

    jax.device_get(_tiny(make().cache().tojax()))  # compile slab programs
    t0 = time.perf_counter()
    for _ in range(tenants):
        jax.device_get(_tiny(make().cache().tojax()))
    serialized = time.perf_counter() - t0

    lats = []
    best = float("inf")
    _metrics.registry().gauge("serve.queue_depth_high_water").reset()
    with _serve.serving(workers=tenants, queue_limit=2 * tenants) as sv:
        for _ in range(2):                        # two rounds: 8 jobs
            t0 = time.perf_counter()
            futs = [sv.submit(make(), tenant="t%d" % i)
                    for i in range(tenants)]
            [f.result(timeout=600) for f in futs]
            best = min(best, time.perf_counter() - t0)
            lats += [f.finished_s - f.submitted_s for f in futs]
        # p50/p99-vs-offered-QPS (ISSUE 13 rides along): jobs paced at
        # each offered rate, latency distribution per level — the
        # saturation knee is where p99 detaches from p50
        curve = {}
        for qps in (1, 4, 16):
            period = 1.0 / qps
            cfuts = []
            for i in range(8):
                t0 = time.perf_counter()
                cfuts.append(sv.submit(make(), tenant="t%d" % (i % 4)))
                dt = period - (time.perf_counter() - t0)
                if dt > 0:
                    time.sleep(dt)
            for f in cfuts:
                f.result(timeout=600)
            clats = sorted(f.finished_s - f.submitted_s for f in cfuts)
            curve[str(qps)] = {
                "p50_s": round(clats[len(clats) // 2], 5),
                "p99_s": round(clats[-1], 5)}
        st = sv.stats()
    lats.sort()
    nbytes = int(np.prod(shape)) * 4
    return tenants * nbytes, best, {
        "bound": "transfer",
        "tenants": tenants,
        "qps_curve": curve,
        "p50_s": round(lats[len(lats) // 2], 5),
        "p99_s": round(lats[min(len(lats) - 1,
                                int(len(lats) * 0.99))], 5),
        "serialized_s": round(serialized, 5),
        "aggregate_over_serialized": round(serialized / best, 2),
        "queue_depth_high_water": st["queue_depth_high_water"],
        "arbiter_waits": st["arbiter"]["waits"],
        "traffic": (1.0, "N identical streamed reductions, one "
                         "host->device pass per tenant byte; the "
                         "aggregate GB/s is all tenants' bytes over the "
                         "concurrent wall — scaling over the serialised "
                         "baseline is the multi-tenant win, slab "
                         "ingest latency emulated at %gs" % lat)}


def fam_stream_resume():
    # the ISSUE-9 fault-tolerance family: an injected uploader death
    # kills a resumable streamed reduction mid-run; the re-run resumes
    # from the last retired-slab checkpoint.  s_per_iter is RECOVERY —
    # the resumed run's wall clock (it streams only the remaining
    # slabs, so recovery_over_clean < 1 is the healthy shape; > 1.5
    # means resume stopped saving work).  The retry leg rides along:
    # one injected fault absorbed in-run by stream.retries(1), counted.
    import tempfile
    from bolt_tpu import _chaos as chaos
    from bolt_tpu import checkpoint as ckpt
    from bolt_tpu import stream as _stream
    shape = (2048, 256, 64)                       # 128 MB, 8 slabs
    x = (np.arange(np.prod(shape), dtype=np.int64) % 251).astype(
        np.float32).reshape(shape)

    def make(ck=None):
        src = bolt.fromcallback(lambda idx: x[idx], shape, mode="tpu",
                                dtype=np.float32, chunks=256,
                                checkpoint=ck)
        return src.map(MAPSUM_FN).sum()

    jax.device_get(_tiny(make().cache().tojax()))     # compile
    t0 = time.perf_counter()
    ref = make().cache()
    jax.device_get(_tiny(ref.tojax()))
    clean = time.perf_counter() - t0

    d = tempfile.mkdtemp(prefix="bolt-perf-resume-")
    ec0 = bolt.profile.engine_counters()
    chaos.inject("stream.upload", nth=6)              # die at slab 6/8
    try:
        with _stream.uploaders(1):
            make(d).cache()
    except Exception:
        pass
    finally:
        chaos.clear()
    t0 = time.perf_counter()
    out = make(d).cache()
    jax.device_get(_tiny(out.tojax()))
    recovery = time.perf_counter() - t0
    ec1 = bolt.profile.engine_counters()
    identical = bool(np.array_equal(np.asarray(ref.toarray()),
                                    np.asarray(out.toarray())))

    chaos.inject("stream.upload", nth=3)              # the retry leg
    try:
        with _stream.retries(1):
            jax.device_get(_tiny(make().cache().tojax()))
    finally:
        chaos.clear()
    ec2 = bolt.profile.engine_counters()
    return int(np.prod(shape)) * 4, recovery, {
        "bound": "transfer",
        "recovery_seconds": round(recovery, 5),
        "clean_seconds": round(clean, 5),
        "recovery_over_clean": round(recovery / clean, 2),
        "resumes": ec1["stream_resumes"] - ec0["stream_resumes"],
        "retries": ec2["stream_retries"] - ec1["stream_retries"],
        "checkpoint_bytes": ec1["checkpoint_bytes"],
        "bit_identical": identical,
        "stale_checkpoint": ckpt.stream_pending(d),
        "traffic": (1.0, "recovery pass: only the slabs past the "
                         "retired-slab checkpoint re-stream; the gbps "
                         "figure is input bytes over RECOVERY wall, so "
                         "it exceeds the clean-run link rate when "
                         "resume is doing its job")}


def fam_multihost_stream():
    # the ISSUE-10 pod-scale family: a REAL 2-process jax.distributed
    # localhost CPU cluster streams the per-process fromcallback
    # reduction (each process produces and uploads ONLY its shard of
    # every slab; the cross-host fold is the shard_map slab program's
    # psum).  s_per_iter is the CLUSTER wall (max across workers) for
    # one warmed streamed pass; the family records per-process GB/s
    # (each process's own ingest link) and the aggregate-vs-single-
    # process ratio (the scale-out observable: > 1 means the pod
    # ingests faster than one process feeding the same devices).
    import shutil
    from bolt_tpu.utils import load_script
    mh = load_script("multihost_harness")
    env = {"BOLT_MH_NKEYS": "4096", "BOLT_MH_VDIM": "256",
           "BOLT_MH_CHUNKS": "512"}
    res, out, _ = mh.run_cluster("bench", nproc=2, devs=1, env=env)
    res1, out1, _ = mh.run_cluster("bench", nproc=1, devs=2, env=env)
    ref = np.load(os.path.join(out1, "bench_sum.0.npy"))
    identical = all(np.array_equal(np.load(os.path.join(
        out, "bench_sum.%d.npy" % p)), ref) for p in (0, 1))
    shutil.rmtree(out, ignore_errors=True)
    shutil.rmtree(out1, ignore_errors=True)
    wall = max(r["wall_s"] for r in res)
    single = res1[0]["wall_s"]
    nbytes = 4096 * 256 * 4
    return nbytes, wall, {
        "bound": "transfer",
        "processes": 2,
        "per_process_gbps": [
            round(r["transfer_bytes"] / r["wall_s"] / 1e9, 2)
            for r in res],
        "single_process_s": round(single, 5),
        "aggregate_over_single": round(single / wall, 2),
        "warm_recompiles": sum(r["recompiles_warm"] for r in res),
        "bit_identical": identical,
        "traffic": (1.0, "one host->device pass per byte, SPLIT across "
                         "processes (each ships its own shard); the "
                         "cross-host fold is one psum per slab riding "
                         "the shard_map slab program")}


def fam_multihost_resume():
    # the ISSUE-11 pod fault-tolerance family: kill -9 of ONE process
    # in a REAL 3-process localhost cluster; every survivor raises the
    # watchdog's PeerLostError, reforms onto the 2 survivors
    # (multihost.reform) and resumes from the rendezvous-consistent
    # checkpoint.  s_per_iter is RECOVERY — the survivors' wall from
    # learning of the loss to the resumed bit-identical result
    # (barrier probe + reform + resume); recovery_over_clean < 2.0 is
    # the healthy shape (the clean run is the unkilled 2-process
    # baseline of the same paced workload).  detection_seconds is the
    # heartbeat verdict latency (<= 2x BOLT_POD_TIMEOUT by contract).
    from bolt_tpu.utils import load_script
    mh = load_script("multihost_harness")
    r = mh.run_reform_bench()
    nbytes = 96 * 8 * 4               # the paced workload's input pass
    return nbytes, r["recovery_s"], {
        "bound": "recovery",
        "detection_seconds": round(r["detection_s"], 5),
        "reform_seconds": round(r["reform_s"], 5),
        "resume_seconds": round(r["resume_s"], 5),
        "barrier_seconds": round(r["barrier_s"], 5),
        "clean_seconds": round(r["clean_s"], 5),
        "recovery_over_clean": round(r["recovery_over_clean"], 2),
        "pod_timeout_seconds": r["pod_timeout"],
        "victim_rc": r["victim_rc"],
        "survivors": r["survivors"],
        "resumes_sum": r["sum_resumes"],
        "resumes_stats": r["stats_resumes"],
        "bit_identical": r["bit_identical"],
        "stale_checkpoint_files": len(r["stale_checkpoint_files"]),
        "traffic": (1.0, "recovery leg: the survivors re-stream only "
                         "the slabs past the last rendezvous-"
                         "consistent watermark, on the SHRUNK 2-"
                         "process mesh (topology remap); wall is "
                         "dominated by the paced loader + the reform "
                         "bring-up, not bytes")}


def fam_multihost_elastic():
    # the ISSUE-12 self-healing family: kill -9 of ONE process under
    # Server(supervise=True) in a REAL 3-process localhost cluster —
    # the supervisor shrinks the pod 3->2 automatically (zero caller
    # intervention), a restarted replacement process rejoins
    # mid-stream and the pod re-expands 2->3.  s_per_iter is the whole
    # ELASTIC SCENARIO wall (shrink recovery + rejoin quiesce/grow +
    # the clean fused-stats leg); scenario_over_clean < 2.5 is the
    # healthy shape against the unkilled 3-process run of the same
    # paced workload.  detection_seconds is the heartbeat verdict
    # latency (<= 2x BOLT_POD_TIMEOUT by contract), reform/rejoin/
    # recovery_seconds the auto-reform drive, the rejoin-triggered
    # recovery and the full shrink pause->resume wall,
    # precollective_seconds the CLOSED pre-collective death bound (a
    # peer dead before the first collective raises PeerLostError here,
    # not at gloo's ~30s connect timeout).
    from bolt_tpu.utils import load_script
    mh = load_script("multihost_harness")
    r = mh.run_supervise_bench()
    p = mh.run_precollective_probe()
    nbytes = 96 * 8 * 4               # one paced workload's input pass
    return nbytes, r["scenario_s"], {
        "bound": "recovery",
        "detection_seconds": round(r["detection_s"], 5),
        "reform_seconds": round(r["reform_s"], 5),
        "rejoin_seconds": round(r["rejoin_s"], 5),
        "recovery_seconds": round(r["recovery_s"], 5),
        # None on the degraded paths (no rejoiner result / the kill
        # raced past the rendezvous) — keep the record instead of
        # crashing the family exactly when it would show a regression
        "attach_seconds": (round(r["attach_s"], 5)
                           if r["attach_s"] is not None else None),
        "precollective_seconds": (round(p["pre_elapsed"], 5)
                                  if p["pre_elapsed"] is not None
                                  else None),
        "clean_seconds": round(r["clean_s"], 5),
        "scenario_over_clean": round(r["scenario_over_clean"], 2),
        "pod_timeout_seconds": r["pod_timeout"],
        "victim_rc": r["victim_rc"],
        "survivors": r["survivors"],
        "rejoined": r["rejoined"],
        "nproc_final": r["nproc_final"],
        "resumes_a": r["a_resumes"],
        "resumes_b": r["b_resumes"],
        "bit_identical": r["bit_identical"],
        "stale_markers": r["stale_markers"],
        "traffic": (1.0, "elastic leg: survivors re-stream only the "
                         "slabs past each recovery's checkpoint "
                         "watermark — first on the SHRUNK 2-process "
                         "mesh, then on the re-expanded 3-process one "
                         "(the same psum-replicated topology remap "
                         "both ways); wall is dominated by the paced "
                         "loader + two reform bring-ups, not bytes")}


def fam_pca_default():
    # the SAME pca program under the bolt.precision("default") scope —
    # PERF.json records both policy modes for the precision-bound
    # families (VERDICT r4 weak-3/4; measured 2.47x on chip, sv within
    # 2e-5)
    with bolt.precision("default"):
        return fam_pca()


def fam_halo_gaussian_default():
    with bolt.precision("default"):
        return fam_halo_gaussian()


FAMILIES = [
    ("map_sum", fam_map_sum),
    ("stats_welford", fam_stats_welford),
    ("swap", fam_swap),
    ("filter_fused", fam_filter_fused),
    ("filter_sum_fused", fam_filter_sum_fused),
    ("matmul", fam_matmul),
    ("matmul_bf16", fam_matmul_bf16),
    ("halo_gaussian", fam_halo_gaussian),
    ("halo_gaussian_default", fam_halo_gaussian_default),
    ("segment_reduce", fam_segment_reduce),
    ("pca", fam_pca),
    ("pca_default", fam_pca_default),
    ("svdvals", fam_svdvals),
    ("jacobi_eigh", fam_jacobi_eigh),
    ("stream_sum", fam_stream_sum),
    ("stream_codec", fam_stream_codec),
    ("stream_swap", fam_stream_swap),
    ("multi_stat_fused", fam_multi_stat_fused),
    ("serve_multitenant", fam_serve_multitenant),
    ("serve_smallreq", fam_serve_smallreq),
    ("stream_resume", fam_stream_resume),
    ("multihost_stream", fam_multihost_stream),
    ("multihost_resume", fam_multihost_resume),
    ("multihost_elastic", fam_multihost_elastic),
]


def print_table():
    """Markdown perf table regenerated FROM PERF.json (headline numbers
    come from the artifact, never from memory)."""
    with open(OUT) as f:
        results = json.load(f)
    print("| family | bound | GB/s (per input pass) | eff GB/s "
          "(real traffic) | % of bound | TFLOP/s | % MXU peak |")
    print("|---|---|---|---|---|---|---|")
    for name in sorted(results):
        if name.startswith("_"):
            continue               # metadata entries (_engine), not families
        r = results[name]
        # roofline percentages only mean something on a tpu window; a
        # cpu-container entry shows the platform tag where the % would
        # go (committed pre-fix entries may still carry the keys)
        chip = r.get("platform", "tpu") == "tpu"
        pct = (r.get("pct_of_bound", r.get("pct_mxu_peak", "")) if chip
               else "(%s)" % r.get("platform"))
        print("| %s | %s | %s | %s | %s | %s | %s |" % (
            name, r.get("bound", ""), r.get("gbps", ""),
            r.get("effective_gbps", ""), pct,
            r.get("tflops", ""),
            r.get("pct_mxu_peak", "") if chip else ""))


def _phase_breakdown(spans):
    """Span-derived phase totals for one family: per span name, summed
    wall seconds over the family's spans.  Names nest (engine.dispatch
    runs inside stream.compute), so entries overlap — this is a
    breakdown by PHASE, not a partition of the family's wall clock."""
    tot = {}
    for s in spans:
        d = s.duration
        if d:
            tot[s.name] = tot.get(s.name, 0.0) + d
    return {k: round(v, 5) for k, v in sorted(tot.items())}


def main():
    if "--table" in sys.argv:
        print_table()
        return 0
    from bolt_tpu import obs as _obs
    trace_path = _obs.trace_arg(sys.argv)
    obs = None
    if trace_path:
        obs = _obs
        obs.clear()
        obs.enable(ring=65536)
    # the on-disk XLA cache ($JAX_COMPILATION_CACHE_DIR, else
    # <checkout>/.jax_cache): a warm perf run skips every compile
    # (persistent_hits in the _engine entry confirms it), so short
    # wall-clock budgets go to measurement instead of compilation
    from bolt_tpu import engine
    engine.persistent_cache()
    rebase = "--rebaseline" in sys.argv
    only = None
    for arg in sys.argv[1:]:
        if arg.startswith("--only="):
            only = set(arg.split("=", 1)[1].split(","))
        elif arg.startswith("--families="):
            # the targeted re-measurement door (ISSUE 13 satellite): a
            # comma list of family names, each token either a literal
            # name or "platform:<tag>" — the latter expands to every
            # committed family whose last PERF.json/baseline entry was
            # measured on that backend, so a future real-chip window
            # can re-run exactly the platform-"cpu"-tagged families
            # (`--families=platform:cpu`) without a full sweep
            sel = set()
            committed = {}
            for path in (BASE, OUT):
                if os.path.exists(path):
                    with open(path) as f:
                        committed.update(json.load(f))
            known = {name for name, _ in FAMILIES}
            literal = set()
            for tok in arg.split("=", 1)[1].split(","):
                tok = tok.strip()
                if not tok:
                    continue
                if tok.startswith("platform:"):
                    plat = tok.split(":", 1)[1]
                    # expansion keeps only families that still EXIST —
                    # a stale committed entry must not fail the run
                    sel |= {name for name, entry in committed.items()
                            if name in known and isinstance(entry, dict)
                            and entry.get("platform") == plat}
                else:
                    literal.add(tok)
                    sel.add(tok)
            unknown = sorted(literal - known)
            if unknown:
                print("--families: unknown famil%s %s (known: %s)"
                      % ("y" if len(unknown) == 1 else "ies",
                         ",".join(unknown),
                         ",".join(sorted(known))), file=sys.stderr)
                return 1
            only = sel if only is None else (only | sel)
            if not only:
                print("--families matched nothing (token list: %r)"
                      % arg.split("=", 1)[1], file=sys.stderr)
                return 1
    # start from the committed baseline plus any previous partial
    # measurement (fresher wins), so a run cut short by a wall-clock
    # budget resumes instead of losing
    # everything, and `--rebaseline --only=fam` never wipes the other
    # families' baselines; results are flushed after EVERY family
    results = {}
    for path in (BASE, OUT):
        if os.path.exists(path):
            with open(path) as f:
                results.update(json.load(f))
    failed = []
    measured = set()   # families ACTUALLY run this invocation — the
                       # status report covers only these (seeded baseline
                       # entries would otherwise compare to themselves)
    last_sid = 0       # obs-span watermark: spans above it belong to the
                       # family currently measuring (--trace mode)
    for name, fam in FAMILIES:
        if only is not None and name not in only:
            continue
        try:
            out = fam()
        except Exception as e:   # one broken family must not lose the rest
            print("family %s FAILED: %s" % (name, e), file=sys.stderr)
            failed.append(name)
            # purge any stale number: a broken family must not regression-
            # gate on data from a previous run
            results.pop(name, None)
            if obs is not None:
                # consume the broken family's spans: its compiles and
                # any leaked opens must not land in the NEXT family's
                # "phases" attribution
                last_sid = max((s.sid for s in obs.spans()),
                               default=last_sid)
            continue
        phases = None
        if obs is not None:
            fam_spans = [s for s in obs.spans() if s.sid > last_sid]
            last_sid = max((s.sid for s in fam_spans), default=last_sid)
            phases = _phase_breakdown(fam_spans)
            leaked = obs.active_count()
            if leaked:
                print("family %s leaked %d active span(s)"
                      % (name, leaked), file=sys.stderr)
        nbytes, sec = out[0], out[1]
        meta = out[2] if len(out) > 2 else {"bound": "hbm"}
        gbps = nbytes / sec / 1e9
        entry = {"s_per_iter": round(sec, 5), "bytes": nbytes,
                 "gbps": round(gbps, 1), "bound": meta["bound"],
                 # which backend actually measured this window: chip
                 # numbers and cpu-container numbers must never be
                 # confused when read back (low-water marks are per
                 # platform in spirit)
                 "platform": jax.default_backend()}
        for key in ("upload_threads", "inflight_high_water",
                    "prefetch_depth", "terminals", "terminal_scaling_s",
                    "sequential_4_s", "seq_over_fused",
                    "fused_stat_groups", "fused_stat_terminals",
                    "tenants", "p50_s", "p99_s", "serialized_s",
                    "aggregate_over_serialized",
                    "queue_depth_high_water", "arbiter_waits",
                    "recovery_seconds", "clean_seconds",
                    "recovery_over_clean", "resumes", "retries",
                    "checkpoint_bytes", "bit_identical",
                    "stale_checkpoint", "processes", "per_process_gbps",
                    "single_process_s", "aggregate_over_single",
                    "warm_recompiles",
                    # multihost_resume (ISSUE 11): the pod recovery
                    # phase breakdown and its hygiene observables
                    "detection_seconds", "reform_seconds",
                    "resume_seconds", "barrier_seconds",
                    "pod_timeout_seconds", "victim_rc", "survivors",
                    "resumes_sum", "resumes_stats",
                    "stale_checkpoint_files",
                    # multihost_elastic (ISSUE 12): the self-healing
                    # 3->2->3 phase breakdown — auto-reform, rejoin
                    # re-expansion, the closed pre-collective bound —
                    # and its hygiene observables
                    "rejoin_seconds", "attach_seconds",
                    "precollective_seconds", "scenario_over_clean",
                    "rejoined", "nproc_final", "resumes_a",
                    "resumes_b", "stale_markers",
                    # serve_smallreq (ISSUE 13): continuous
                    # micro-batching observables — aggregate scaling,
                    # occupancy, amortised dispatch count, and the
                    # p50/p99-vs-offered-QPS curves for both modes
                    # (serve_multitenant gains "qps_curve" too)
                    # stream_codec (ISSUE 14): compressed-ingest
                    # observables — the raw-vs-encoded walls, the
                    # measured wire-bytes ratio, the lossless leg's
                    # bit-identity, the host encode cost
                    "codec", "raw_s", "coded_over_raw",
                    "wire_bytes_ratio", "lossless_bit_identical",
                    "encode_seconds", "link_seconds_raw",
                    "link_seconds_coded", "link_raw_over_coded",
                    "requests", "unbatched_s", "batched_over_unbatched",
                    "batch_occupancy_mean", "dispatches_per_request",
                    "batched_dispatches", "batched_requests",
                    "qps_curve", "qps_curve_batched",
                    "qps_curve_unbatched", "p50_low_qps_ratio",
                    # stream_swap (ISSUE 18): out-of-core shuffle
                    # observables — the materialise-first wall it
                    # replaces, the forced-spill leg, and the
                    # shuffle/spill byte gauges
                    "materialised_s", "streamed_over_materialised",
                    "spill_s", "spill_bytes", "shuffle_bytes"):
            if meta.get(key) is not None:
                entry[key] = meta[key]
        if phases:
            # --trace mode: span-derived per-phase wall totals for the
            # family (engine.lower/compile vs dispatch vs stream
            # ingest/compute — where this family's time actually went)
            entry["phases"] = phases
        # %-of-peak on the axis that bounds the family (VERDICT r3
        # next-1): HBM families get pct_hbm_peak, MXU families get
        # TFLOP/s against the per-precision MXU peak; latency-bound
        # families (sequential scan chains) get neither — their gate is
        # s_per_iter.  ROOFLINE percentages exist ONLY for tpu-measured
        # windows: a cpu-container number divided by the v5e HBM peak
        # reads as a 0.1%-of-peak "regression" that never happened, so
        # non-tpu platforms suppress them (the ISSUE 14 reporting fix)
        # and the status line labels the window instead.
        on_chip = entry["platform"] == "tpu"
        if meta["bound"] == "hbm" and on_chip:
            entry["pct_hbm_peak"] = round(100.0 * gbps / HBM_PEAK_GBPS, 1)
        if meta.get("overlap_efficiency") is not None:
            # streaming families: fraction of ingest hidden behind
            # compute (bolt_tpu.profile.overlap_efficiency)
            entry["overlap_efficiency"] = meta["overlap_efficiency"]
        if meta.get("traffic"):
            # HONEST effective-traffic accounting (VERDICT r4 weak-2):
            # gbps above is per-pass-over-the-INPUT; multi-pass families
            # (swap ~2x, filter ~3x, halo ~4x) move more HBM bytes than
            # the input per iteration, and the machine-readable % must
            # say so instead of hiding it in prose
            mult, model = meta["traffic"]
            eff = nbytes * mult
            entry["effective_bytes"] = int(eff)
            entry["effective_gbps"] = round(eff / sec / 1e9, 1)
            if meta["bound"] == "hbm" and on_chip:
                # the %-of-bound denominator is the HBM peak; transfer-
                # bound families (stream_sum) have no meaningful HBM %,
                # and non-tpu windows have no meaningful roofline at all
                entry["pct_of_bound"] = round(
                    100.0 * entry["effective_gbps"] / HBM_PEAK_GBPS, 1)
            entry["traffic_model"] = model
        if meta.get("flops"):
            tf = meta["flops"] / sec / 1e12
            entry["tflops"] = round(tf, 2)
            peak = MXU_PEAK_TFLOPS.get(meta.get("precision"))
            if peak and meta["bound"] == "mxu":
                entry["precision"] = meta["precision"]
                entry["pct_mxu_peak"] = round(100.0 * tf / peak, 1)
        results[name] = entry
        measured.add(name)
        print(json.dumps({"family": name, **entry}), flush=True)
        with open(OUT, "w") as f:
            json.dump(results, f, indent=1, sort_keys=True)

    # executor-layer accounting rides along with the perf numbers: the
    # engine's compile-cache hit rate says whether the run amortised its
    # XLA compiles (a healthy steady-state run is hit-dominated), and
    # compile/lower seconds quantify the one-time cost the persistent
    # cache removes from warm processes.  SKIPPED when this invocation
    # saw no engine activity in-process (an --only= run of a
    # subprocess-only family like multihost_stream) — an all-zeros
    # snapshot must not clobber the committed real one.
    ec = bolt.profile.engine_counters()
    lookups = ec["hits"] + ec["misses"]
    if lookups == 0 and ec["transfer_bytes"] == 0:
        print("(_engine snapshot skipped: no in-process engine "
              "activity this run — an --only= run of a subprocess "
              "family keeps the committed snapshot)", file=sys.stderr)
    else:
        results["_engine"] = {
            "hits": ec["hits"], "misses": ec["misses"],
            "hit_rate": round(ec["hits"] / lookups, 4) if lookups
            else None,
            "aot_compiles": ec["aot_compiles"],
            "compile_seconds": round(ec["compile_seconds"], 3),
            "lower_seconds": round(ec["lower_seconds"], 3),
            "persistent_hits": ec["persistent_hits"],
            "persistent_misses": ec["persistent_misses"],
            "donations": ec["donations"],
            "transfer_bytes": ec["transfer_bytes"],
            "transfer_seconds": round(ec["transfer_seconds"], 3),
            "stream_chunks": ec["stream_chunks"],
            "stream_upload_threads": ec["stream_upload_threads"],
            "stream_inflight_high_water":
                ec["stream_inflight_high_water"],
            "overlap_efficiency": round(
                bolt.profile.overlap_efficiency(ec), 4),
        }
        print(json.dumps({"family": "_engine", **results["_engine"]}),
              flush=True)
    with open(OUT, "w") as f:
        json.dump(results, f, indent=1, sort_keys=True)

    if obs is not None:
        obs.to_chrome(path=trace_path)
        obs.disable()
        print("obs timeline written to %s (load in chrome://tracing or "
              "Perfetto)" % trace_path, file=sys.stderr)

    if rebase or not os.path.exists(BASE):
        with open(BASE, "w") as f:
            json.dump(results, f, indent=1, sort_keys=True)
        print("baseline written to", BASE, file=sys.stderr)
        return 2 if failed else 0

    with open(BASE) as f:
        base = json.load(f)
    # Per-family status against the low-water mark, printed EVERY run
    # (VERDICT r3 weak-2: a below-water family must be visible even when
    # it is inside the 25% regression gate — no more "all above" claims
    # drifting from the committed data).
    regressed, below = [], []
    for name in sorted(measured):
        r = results[name]
        b = base.get(name)
        if not b or "gbps" not in b:
            # covers seeded pending_measurement entries that carry a
            # traffic model but no measured number yet
            print("family %-15s %8.1f GB/s   (no low-water mark yet)"
                  % (name, r["gbps"]), file=sys.stderr)
            continue
        ok = r["gbps"] >= b["gbps"]
        if not ok:
            below.append(name)
            if r["gbps"] < b["gbps"] * (1 - THRESHOLD):
                regressed.append((name, b["gbps"], r["gbps"]))
        # pct_of_bound exists only for hbm-bound TPU-measured families
        # — a recovery-bound family (multihost_elastic) or a cpu
        # container window (every PR 6-14 family until a chip refresh)
        # still reports its effective rate, LABELLED by platform so a
        # cpu number can never read as a %-of-HBM-peak regression
        if "pct_of_bound" in r and r.get("platform") == "tpu":
            eff = ("  [eff %.0f GB/s = %.0f%% of bound]"
                   % (r["effective_gbps"], r["pct_of_bound"]))
        elif "effective_gbps" in r:
            eff = "  [eff %.0f GB/s%s]" % (
                r["effective_gbps"],
                "" if r.get("platform") == "tpu"
                else ", %s window — no roofline %%"
                % r.get("platform", "?"))
        else:
            eff = ""
        print("family %-15s %8.1f GB/s vs low-water %6.1f -> %s%s"
              % (name, r["gbps"], b["gbps"],
                 "above" if ok else "BELOW (%.0f%%)"
                 % (100.0 * r["gbps"] / b["gbps"]), eff), file=sys.stderr)
    for name, was, now in regressed:
        print("REGRESSION %s: %.1f -> %.1f GB/s" % (name, was, now),
              file=sys.stderr)
    n_meas = len([n for n in measured if n in base])
    if below:
        print("%d/%d measured families at-or-above low-water; below: %s"
              % (n_meas - len(below), n_meas, ",".join(below)),
              file=sys.stderr)
    else:
        print("all %d measured families at-or-above low-water" % n_meas,
              file=sys.stderr)
    bad = bool(regressed or failed)
    print("perf_regress:", "FAIL" if bad else "OK", file=sys.stderr)
    return 2 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
