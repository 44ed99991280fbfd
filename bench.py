#!/usr/bin/env python
"""Benchmark harness for the BASELINE north-star.

Two measurements:

1. **Config 1 anchor** (``ones((200,200,64,64)).map(x+1).sum()``, 0.66 GB
   float32): runs on both the ``mode='local'`` NumPy oracle and the TPU
   backend.  This is the parity anchor — the result must be bit-exact
   (integral-valued floats; every partial sum is an exact float32).

2. **North-star scale** (same op at 10 GB float32): the array is built
   directly sharded on device and the deferred ``map`` chain fuses with the
   ``sum``, so the 10 GB intermediate never materialises — the pipeline
   reads HBM once.  NumPy is not run at this size (20+ GB host RSS);
   throughput ratio to the NumPy anchor is computed per-byte, which is
   scale-fair for this bandwidth-bound op.

Throughput is measured at steady state: launches are pipelined (dispatch is
async) and the host syncs once at the end, so the per-iteration figure is
compute time, not the host↔device round-trip latency (logged separately as
``synced``).  Every pipelined iteration still reads the full array from HBM.

The run needs a TPU: without one it exits non-zero before measuring, and a
failed phase or parity mismatch exits non-zero with no result line.

Prints ONE JSON line:
    {"metric": "northstar_10GB_map_sum_throughput_per_chip",
     "value": <GB/s per chip at 10 GB>, "unit": "GB/s",
     "vs_baseline": <per-byte throughput ratio vs NumPy mode='local'>}
"""

import json
import sys
import time

import numpy as np

SHAPE1 = (200, 200, 64, 64)            # BASELINE config 1: 0.655 GB f32
SHAPE10 = (3200, 200, 64, 64)          # north-star scale: 10.49 GB f32
DTYPE = np.float32
ITERS = 5


def _log(msg):
    print(msg, file=sys.stderr, flush=True)


def _gb(shape):
    return int(np.prod(shape)) * np.dtype(DTYPE).itemsize / 1e9


def bench_local_config1():
    x = np.ones(SHAPE1, DTYPE)
    (x + 1).sum()  # warm (page-in)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        out = (x + 1).sum(dtype=DTYPE)
        times.append(time.perf_counter() - t0)
    return float(out), min(times)


def bench_tpu(shape, pipe_iters=50):
    import bolt_tpu as bolt

    b = bolt.ones(shape, mode="tpu", dtype=DTYPE)
    b.cache()  # materialise the input; we time the pipeline, not construction
    mapper = lambda v: v + 1
    axes = tuple(range(len(shape)))

    def launch():
        # map defers; sum fuses the chain into one compiled pass over HBM;
        # dispatch is async — the returned array's buffer is a future.
        # cache() forces the LAZY terminal to dispatch (stat results are
        # pending fused-group handles now); the dispatch itself stays
        # async, so launches still pipeline
        return b.map(mapper, axis=(0,)).sum(axis=axes).cache()

    out = float(launch().toarray())  # compile + warm caches

    # latency including the host round-trip (one fetch per iteration)
    times = []
    for _ in range(ITERS):
        t0 = time.perf_counter()
        out = float(launch().toarray())
        times.append(time.perf_counter() - t0)
    synced = min(times)

    # pure host-fetch round-trip: re-fetch an already-materialised scalar
    # result (no compute), so it can be subtracted from the pipelined window
    done = launch()
    float(done.toarray())
    rts = []
    for _ in range(ITERS):
        t0 = time.perf_counter()
        float(done.toarray())
        rts.append(time.perf_counter() - t0)
    roundtrip = min(rts)

    # steady-state throughput: pipeline the launches, sync once at the end
    # (in-order per-device execution: the last result completing implies all
    # iterations ran; each reads the full array from HBM); the one closing
    # fetch's round-trip is subtracted so the figure is device time only
    t0 = time.perf_counter()
    results = [launch() for _ in range(pipe_iters)]
    out = float(results[-1].toarray())
    steady = (time.perf_counter() - t0 - roundtrip) / pipe_iters
    return out, steady, synced


def _engine_stats():
    """Compile-cache accounting for the result line: hit rate over the
    run, explicit XLA compile seconds, and whether the persistent
    on-disk cache (``engine.persistent_cache``) served them."""
    from bolt_tpu import profile
    c = profile.engine_counters()
    lookups = c["hits"] + c["misses"]
    return {
        "cache_hit_rate": round(c["hits"] / lookups, 4) if lookups else None,
        "aot_compiles": c["aot_compiles"],
        "compile_seconds": round(c["compile_seconds"], 3),
        "persistent_hits": c["persistent_hits"],
    }


def main():
    import jax
    dev = jax.devices()[0]
    _log("device: platform=%s kind=%s count=%d"
         % (dev.platform, dev.device_kind, len(jax.devices())))
    if dev.platform != "tpu":
        _log("bench.py measures the TPU; found platform %r" % dev.platform)
        return 1

    from bolt_tpu import engine
    _log("persistent compile cache: %s" % engine.persistent_cache())

    # ---- config 1: parity anchor ------------------------------------
    _log("config 1 %s (%.2f GB): local baseline..." % (SHAPE1, _gb(SHAPE1)))
    local_out, local_t = bench_local_config1()
    local_gbps = _gb(SHAPE1) / local_t
    _log("local: %.3fs (%.2f GB/s)" % (local_t, local_gbps))

    tpu1_out, tpu1_t, tpu1_sync = bench_tpu(SHAPE1)
    _log("tpu:   %.4fs (%.2f GB/s)  [synced incl. host round-trip: %.4fs]"
         % (tpu1_t, _gb(SHAPE1) / tpu1_t, tpu1_sync))

    expected1 = float(np.prod(SHAPE1, dtype=np.float64) * 2.0)
    exact = (tpu1_out == local_out == expected1)
    _log("parity: tpu=%r local=%r expected=%r bit_exact=%r"
         % (tpu1_out, local_out, expected1, exact))
    if not exact:
        _log("config-1 parity mismatch")
        return 1

    # ---- north-star scale: 10 GB ------------------------------------
    _log("north-star %s (%.2f GB): fused map->sum on device..."
         % (SHAPE10, _gb(SHAPE10)))
    tpu10_out, tpu10_t, tpu10_sync = bench_tpu(SHAPE10)
    gbps10 = _gb(SHAPE10) / tpu10_t
    expected10 = float(np.prod(SHAPE10, dtype=np.float64) * 2.0)
    _log("tpu:   %.4fs (%.2f GB/s)  parity=%r  [synced: %.4fs]"
         % (tpu10_t, gbps10, tpu10_out == expected10, tpu10_sync))
    if tpu10_out != expected10:
        _log("north-star parity mismatch")
        return 1
    print(json.dumps({
        "metric": "northstar_10GB_map_sum_throughput_per_chip",
        "value": round(gbps10, 3),
        "unit": "GB/s",
        "vs_baseline": round(gbps10 / local_gbps, 3),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "engine": _engine_stats(),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
