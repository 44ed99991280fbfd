#!/usr/bin/env python
"""The quickest proof that bolt-tpu still starts, compiles and answers on
the chip: the main path, once, through the entry points a user calls.

    python chip_smoke.py          # on a TPU host; ONE process, x64 off

Three phases over ``default_mesh()`` (every device JAX reports, so the
same file serves one chip and a four-chip host), each answer checked
against NumPy / ``mode='local'``:

* **streamed** — ``fromcallback`` over a 4 GiB float32 source generated
  per slab from ``--seed``, default stream knobs: ``map().sum()`` exact,
  device peak memory bounded by the slab ring, a fused multi-stat
  terminal, the same pass under the lossless ``delta-f32`` codec, a
  streamed ``swap`` under that codec bit-identical to the
  materialise-first raw swap (result resident), and one forced-budget
  spill leg;
* **resident** — the 10.49 GB north-star ``ones.map(v+1).sum()``
  bit-exact, the donation ownership rule on a 64 MiB chain, a
  ``ppermute`` halo exchange, then BASELINE.json configs 2–5 on 1 GiB
  seeded host operands ingested with ``bolt.array``: reductions +
  ``stats()`` (Pallas ``fused_welford``), ``swap``, fused ``filter``,
  ``ops.gaussian`` on the leading-axis and lane-axis kernels, and
  ``chunk().map()`` with a per-chunk SVD;
* **served** — ``serve.serving`` over resident arrays: small requests from
  several tenants with batching off and on plus one streamed job in the
  same queue, every future equal to the direct call.

Streamed runs first because ``peak_bytes_in_use`` is a process-lifetime
high-water mark: the ring bound is only readable before anything big has
been resident.  Within a phase every step runs even after one failed, so
one chip run reports everything that is wrong.

End checks: no engine dispatch fell back from the AOT path, no
``HostFallbackWarning`` (they are errors here), the HBM budget is the
device's own report; every resident array sat ``1/n`` per device (checked
where each is made).

Exit code 0 and, as the LAST line of stdout, one JSON object
``{"ok": true, "device": {...}}`` — only on a TPU and only when every
phase passed.  Everything above that line is a log: the seconds in it are
set-up and compile times for a reader, not metrics.
"""

import argparse
import dataclasses
import gc
import json
import os
import sys
import threading
import time
import traceback
import warnings

import numpy as np


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What each phase runs at.  The defaults are the real sizes; the
    CPU test (tests/test_chip_smoke.py) swaps in tiny ones."""
    northstar: tuple = (3200, 200, 64, 64)     # 10.49 GB f32
    donate: tuple = (1024, 128, 128)           # 64 MiB: the donation floor
    resident: tuple = (2048, 8, 64, 256)       # 1 GiB f32, keys = axis 0
    svd: tuple = (8, 2097152, 16)              # 1 GiB f32
    svd_chunk: int = 1024                      # per-chunk SVD of (1024, 16)
    stream: tuple = (32768, 256, 128)          # 4 GiB f32
    stream_chunks: int = None                  # None = the 64 MB default
    swap_records: int = 16384                  # 2 GiB streamed swap
    spill_records: int = 8192                  # 1 GiB forced-spill leg
    spill_budget: int = 256 << 20
    serve_shape: tuple = (128, 32)             # one small request's operand
    serve_requests: int = 48
    serve_stream_records: int = 2048           # 256 MiB streamed job


def log(msg):
    print(msg, flush=True)


# ---------------------------------------------------------------------
# seeded data and oracles
# ---------------------------------------------------------------------

def lattice(lo, hi, rec_shape, seed):
    """Records ``[lo, hi)`` of the seeded stream: integers in [-8, 8) as
    float32, a pure function of (seed, absolute element index) — any
    slab, device shard or oracle slice regenerates the same bytes, and
    every per-slot sum is exact in float32."""
    rec = int(np.prod(rec_shape))
    x = np.arange(lo * rec, hi * rec, dtype=np.uint32)
    x *= np.uint32((2654435761 + 2 * seed) % (1 << 32))
    x >>= np.uint32(28)
    out = x.astype(np.float32)
    out -= 8.0
    return out.reshape((hi - lo,) + tuple(rec_shape))


def lattice_loader(shape, seed):
    """The ``fromcallback`` loader over :func:`lattice`."""
    rec_shape = tuple(shape[1:])

    def load(index):
        lo, hi, _ = index[0].indices(shape[0])
        return lattice(lo, hi, rec_shape, seed)[
            (slice(None),) + tuple(index[1:])]
    return load


def lattice_oracle(shape, seed, block=512):
    """One host pass over the stream: exact per-slot ``(sum, sum of
    squares, min, max)``.  A block's float32 sums are exact (|v| <= 8,
    512 records: every partial is an integer below 2**24); blocks
    accumulate in float64."""
    rec_shape = tuple(shape[1:])
    s1 = np.zeros(rec_shape, np.float64)
    s2 = np.zeros(rec_shape, np.float64)
    lo_ = np.full(rec_shape, np.inf, np.float32)
    hi_ = np.full(rec_shape, -np.inf, np.float32)
    for lo in range(0, shape[0], block):
        blk = lattice(lo, min(lo + block, shape[0]), rec_shape, seed)
        s1 += blk.sum(axis=0)
        np.minimum(lo_, blk.min(axis=0), out=lo_)
        np.maximum(hi_, blk.max(axis=0), out=hi_)
        blk *= blk
        s2 += blk.sum(axis=0)
    return s1, s2, lo_, hi_


def host(x):
    """Host ndarray of a bolt array / StatCounter field / jax array."""
    return np.asarray(x.toarray() if hasattr(x, "toarray") else x)


def need(cond, what):
    if not cond:
        raise AssertionError(what)


class Steps:
    """A phase's steps, run one by one: a step that raises is logged and
    counted and the next still runs, so one chip run reports everything
    that is wrong instead of the first thing."""

    def __init__(self):
        self.failed = []

    def __call__(self, label, fn):
        try:
            fn()
        except Exception:
            self.failed.append(label)
            log("  !! %s FAILED\n%s" % (label, traceback.format_exc()))

    def finish(self):
        need(not self.failed, "failed steps: %s" % ", ".join(self.failed))


def close(got, want, what, rtol=1e-5, atol=1e-5):
    g, w = host(got), np.asarray(want)
    need(g.shape == w.shape, "%s: shape %s != %s" % (what, g.shape, w.shape))
    need(np.all(np.isfinite(g)), "%s: non-finite values" % what)
    err = float(np.max(np.abs(g.astype(np.float64) - w)
                       / (atol + rtol * np.abs(w)))) if g.size else 0.0
    need(err <= 1.0, "%s: off by %.3g x tolerance" % (what, err))


def same(got, want, what):
    g, w = host(got), np.asarray(want)
    need(g.shape == w.shape, "%s: shape %s != %s" % (what, g.shape, w.shape))
    need(np.array_equal(g, w), "%s: not bit-identical" % what)


def rounding(a, b):
    """How far two float32 answers to the same question sit apart:
    ``"identical"`` or the worst relative difference."""
    a, b = host(a).astype(np.float64), host(b).astype(np.float64)
    if np.array_equal(a, b):
        return "identical"
    scale = np.maximum(np.abs(b), np.finfo(np.float32).tiny)
    return "%d of %d differ, worst %.2g relative" % (
        int((a != b).sum()), a.size, float(np.max(np.abs(a - b) / scale)))


# ---------------------------------------------------------------------
# device-side checks
# ---------------------------------------------------------------------

def device_stat(key):
    """``memory_stats()[key]`` of every device; None off the chip (the
    CPU test mesh reports no memory stats)."""
    import jax
    if jax.default_backend() != "tpu":
        return None
    return [int(d.memory_stats()[key]) for d in jax.devices()]


def check_spread(b, what):
    """Every device holds ``1/n`` of resident array ``b``: by its
    addressable shards, and by what each device says it has in use."""
    import jax
    data = b.tojax()
    n = len(jax.devices())
    shards = data.addressable_shards
    need(len(shards) == n and len({s.device for s in shards}) == n,
         "%s: %d shards on %d devices" % (what, len(shards), n))
    each = data.nbytes // n
    for s in shards:
        need(s.data.nbytes == each,
             "%s: device %s holds %d bytes, expected 1/%d = %d"
             % (what, s.device, s.data.nbytes, n, each))
    used = device_stat("bytes_in_use")
    if used is not None:
        need(min(used) >= each, "%s: bytes_in_use %r < shard %d"
             % (what, used, each))
        need(max(used) - min(used) <= each // 4 + (64 << 20),
             "%s: uneven bytes_in_use %r" % (what, used))
    log("  spread %s: %d x %.3f GB%s"
        % (what, n, each / 1e9,
           "" if used is None else "; in use per device %s"
           % [round(u / 1e9, 3) for u in used]))


def mosaic_ran(tag):
    """On the chip, the engine program(s) keyed ``tag`` must hold a
    Mosaic kernel — a Pallas path that quietly became plain XLA is what
    this file exists to catch.  (Off the chip the kernels interpret.)"""
    import jax
    from bolt_tpu import engine
    if jax.default_backend() != "tpu":
        return
    progs = [fn for key, entry in list(engine._CACHE.items())
             if isinstance(key, tuple) and key and key[0] == tag
             for fn in entry.compiled.values()]
    need(progs, "no compiled %r program in the engine cache" % tag)
    need(all("tpu_custom_call" in p.as_text() for p in progs),
         "%r compiled without its Pallas kernel" % tag)


# ---------------------------------------------------------------------
# phase: streamed
# ---------------------------------------------------------------------

def _plus_one(v):
    return v + 1


def phase_streamed(mesh, sz, seed, out_dir):
    import jax
    import bolt_tpu as bolt
    from bolt_tpu import checkpoint, engine, stream

    shape = tuple(sz.stream)
    n = shape[0]
    slab = stream._slab_records(shape, np.float32, sz.stream_chunks)
    step = Steps()

    def source(codec=None, records=n):
        sub = (records,) + shape[1:]
        return bolt.fromcallback(lattice_loader(sub, seed), sub, mesh,
                                 dtype=np.float32, chunks=sz.stream_chunks,
                                 codec=codec)

    t0 = time.perf_counter()
    s1, s2, mn, mx = lattice_oracle(shape, seed)
    log("  host oracle pass over %.2f GB: %.1fs"
        % (np.prod(shape) * 4 / 1e9, time.perf_counter() - t0))

    def map_sum():
        # exact: integer-valued, every partial below 2**24
        c0 = engine.counters()
        t0 = time.perf_counter()
        got = host(source().map(_plus_one).sum())
        dt = time.perf_counter() - t0
        c1 = engine.counters()
        same(got, (s1 + n).astype(np.float32), "streamed map.sum")
        d = {k: c1[k] - c0[k] for k in (
            "stream_chunks", "transfer_bytes", "transfer_seconds",
            "stream_ingest_seconds", "stream_compute_seconds")}
        log("  map.sum: %d slabs of %.1f MB, %.1fs wall; uploaders hw %d, "
            "in-flight hw %d, depth %d; summed over workers: ingest "
            "(load + upload) %.1fs of which device_put %.1fs (%.2f GB/s "
            "per worker); consumer dispatch + sync %.1fs"
            % (d["stream_chunks"], slab * np.prod(shape[1:]) * 4 / 1e6,
               dt, c1["stream_upload_threads"],
               c1["stream_inflight_high_water"],
               c1["stream_prefetch_depth"], d["stream_ingest_seconds"],
               d["transfer_seconds"], d["transfer_bytes"] / 1e9
               / max(d["transfer_seconds"], 1e-9),
               d["stream_compute_seconds"]))

    def peak():
        # bounded by the slab ring, not by the bytes streamed
        peaks = device_stat("peak_bytes_in_use")
        if peaks is None:
            return
        ndev = len(peaks)
        ring = stream.fold_ring(source()._stream)
        slab_bytes = slab * int(np.prod(shape[1:])) * 4
        # ring slabs + as many again in flight + value-shaped partials;
        # a leak of one slab per slab would be 64 slabs
        bound = (2 * ring + 2) * slab_bytes // ndev + (64 << 20)
        worst = max(peaks)
        log("  peak_bytes_in_use %.1f MB per device (ring %d x %.1f MB "
            "slabs over %d devices; bound %.1f MB)"
            % (worst / 1e6, ring, slab_bytes / 1e6, ndev, bound / 1e6))
        need(worst <= bound, "streamed peak %d bytes exceeds the ring "
             "bound %d: slabs are not being recycled" % (worst, bound))

    names = ("sum", "mean", "var", "min", "max")

    def multi(codec):
        # the fused multi-stat terminal: ONE pass, five answers
        src = source(codec)
        c0 = engine.counters()
        out = [host(o) for o in bolt.compute(
            *[getattr(src, name)() for name in names])]
        c1 = engine.counters()
        need(c1["stream_chunks"] - c0["stream_chunks"] == -(-n // slab),
             "multi-stat streamed the source more than once")
        return out

    raw = []

    def multi_raw():
        raw[:] = multi(None)
        mean = s1 / float(n)
        same(raw[0], s1.astype(np.float32), "streamed multi sum")
        close(raw[1], mean, "streamed multi mean")
        close(raw[2], s2 / float(n) - mean * mean, "streamed multi var",
              rtol=1e-4, atol=1e-4)
        same(raw[3], mn, "streamed multi min")
        same(raw[4], mx, "streamed multi max")

    def multi_coded():
        # one pass under the lossless codec.  What is exact stays exact
        # (sum, min, max); the float-rounded moments agree to float32
        # rounding — XLA may order a reduction differently once the
        # decode is fused into the slab program, and the log says
        # whether it did.  That the DECODE is bit-exact is proved by the
        # swap step, where no arithmetic follows it.
        c0 = engine.counters()
        coded = multi("delta-f32")
        c1 = engine.counters()
        need(c1["codec_bytes_wire"] > c0["codec_bytes_wire"],
             "the delta-f32 pass encoded nothing")
        for k in (0, 3, 4):
            same(coded[k], raw[k], "delta-f32 %s vs raw" % names[k])
        close(coded[1], raw[1], "delta-f32 mean vs raw", rtol=1e-6,
              atol=1e-6)
        close(coded[2], raw[2], "delta-f32 var vs raw")
        log("  multi-stat raw vs delta-f32: sum/min/max identical; mean "
            "%s; var %s; encode %.1fs"
            % (rounding(coded[1], raw[1]), rounding(coded[2], raw[2]),
               c1["codec_encode_seconds"] - c0["codec_encode_seconds"]))

    def swap():
        # streamed under the lossless codec, result resident, vs the
        # materialise-first swap of the raw source: pure data movement
        # after the on-device decode — bit-identical or the decode is
        # wrong
        sw = sz.swap_records
        t0 = time.perf_counter()
        streamed = source("delta-f32", records=sw).swap((0,), (0,))
        need(streamed._stream is not None, "streamed swap resolved eagerly")
        sdata = streamed.tojax()
        need(streamed._stream is None, "streamed swap did not stay resident")
        t1 = time.perf_counter()
        mdata = source(records=sw).cache().swap((0,), (0,)).tojax()
        need(sdata.shape == mdata.shape == (shape[1], sw, shape[2]),
             "swap shapes %s / %s" % (sdata.shape, mdata.shape))
        need(bool(jax.jit(lambda a, b: (a == b).all())(sdata, mdata)),
             "streamed delta-f32 swap != materialise-first raw swap")
        rows = sorted({0, shape[1] // 3, shape[1] - 1})
        want = np.transpose(lattice(0, sw, shape[1:], seed), (1, 0, 2))
        same(np.asarray(sdata[np.asarray(rows)]), want[rows],
             "streamed swap rows")
        check_spread(streamed, "streamed swap result")
        log("  swap %.2f GB: streamed under delta-f32 %.1fs, "
            "materialise-first raw %.1fs, bit-identical"
            % (sdata.nbytes / 1e9, t1 - t0, time.perf_counter() - t1))

    def spill():
        # forced budget: buckets go to disk, phase 2 re-streams them
        sp = sz.spill_records
        spill_dir = os.path.join(out_dir, "smoke_spill")
        os.makedirs(spill_dir, exist_ok=True)
        c0 = engine.counters()
        t0 = time.perf_counter()
        try:
            with stream.spill(dir=spill_dir, budget=sz.spill_budget):
                got = host(source(records=sp).swap((0,), (0,)).sum())
            c1 = engine.counters()
        finally:
            checkpoint.spill_clear(spill_dir)
        need(c1["spill_bytes"] > c0["spill_bytes"],
             "the spill leg kept everything resident")
        same(got, lattice(0, sp, shape[1:], seed).sum(axis=1),
             "spilled swap.sum")
        log("  spill leg: %.2f GB through %s, %.1fs"
            % ((c1["spill_bytes"] - c0["spill_bytes"]) / 1e9, spill_dir,
               time.perf_counter() - t0))

    step("map.sum", map_sum)
    step("peak memory", peak)
    step("multi-stat", multi_raw)
    step("multi-stat under delta-f32", multi_coded)
    step("streamed swap", swap)
    step("spill leg", spill)
    step.finish()


# ---------------------------------------------------------------------
# phase: resident
# ---------------------------------------------------------------------

def _sqrt_abs(v):
    import jax.numpy as jnp
    return jnp.sqrt(jnp.abs(v))


def _mean_positive(v):
    return v.mean() > 0


def _svals(blk):
    import jax.numpy as jnp
    return jnp.linalg.svd(blk, compute_uv=False)[None, :]


def northstar(mesh, shape):
    """``ones.map(v+1).sum()`` at BASELINE.json's north-star shape:
    bit-exact ``2·N``.
    Also answers what ROADMAP S0 waits on: does ``block_until_ready``
    block here?  (A log line, not a metric.)"""
    import jax
    import bolt_tpu as bolt
    b = bolt.ones(shape, context=mesh, dtype=np.float32)
    b.cache()
    check_spread(b, "north-star input")
    axes = tuple(range(len(shape)))
    want = 2.0 * float(np.prod(shape, dtype=np.float64))
    t0 = time.perf_counter()
    got = float(host(b.map(_plus_one).sum(axis=axes)))
    log("  north-star %.2f GB first pass (lower+compile+run+fetch): %.2fs"
        % (b.tojax().nbytes / 1e9, time.perf_counter() - t0))
    need(got == want, "north-star sum %r != %r" % (got, want))
    t0 = time.perf_counter()
    out = b.map(_plus_one).sum(axis=axes).cache().tojax()
    t1 = time.perf_counter()
    jax.block_until_ready(out)
    t2 = time.perf_counter()
    got = float(np.asarray(jax.device_get(out)))
    t3 = time.perf_counter()
    need(got == want, "north-star sum %r != %r" % (got, want))
    log("  warm pass: dispatch %.2f ms, block_until_ready %.2f ms, fetch "
        "after it %.2f ms -> block_until_ready %s"
        % ((t1 - t0) * 1e3, (t2 - t1) * 1e3, (t3 - t2) * 1e3,
           "BLOCKS" if t2 - t1 > t3 - t2 else "does NOT block"))


def donation_rule(mesh, shape):
    """A chain a ``_clone`` still shares must NOT be donated (the clone
    reads it afterwards); a sole-owned one must be, and then reads as
    donated.  On the chip donation really deletes the buffer."""
    import bolt_tpu as bolt
    from bolt_tpu import engine
    want = 2.0 * np.ones(shape[1:], np.float32)
    b = bolt.ones(shape, context=mesh, dtype=np.float32).map(_plus_one)
    c = b._clone()
    n0 = engine.counters()["donations"]
    total = host(b.sum())
    need(engine.counters()["donations"] == n0,
         "a chain shared with a clone was donated")
    same(host(c)[-1], want, "clone read after the terminal")
    same(total, want * shape[0], "shared-chain sum")
    del b, c
    d = bolt.ones(shape, context=mesh, dtype=np.float32).map(_plus_one)
    total = host(d.sum())
    need(engine.counters()["donations"] == n0 + 1,
         "a sole-owned %d-byte chain was not donated"
         % (int(np.prod(shape)) * 4))
    same(total, want * shape[0], "donating sum")
    try:
        d.toarray()
    except RuntimeError as exc:
        need("donated" in str(exc), "unexpected error %r" % (exc,))
    else:
        raise AssertionError("a donated chain was still readable")


def halo_exchange(mesh):
    """``parallel.exchange_halo`` in a hand-written ``shard_map`` kernel:
    a 3-point moving sum across shard boundaries, i.e. ``ppermute`` to
    both neighbours over the real interconnect (with one device, to
    itself)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from bolt_tpu._compat import shard_map
    from bolt_tpu.parallel import exchange_halo
    name = mesh.axis_names[0]
    x = lattice(0, 1024 * mesh.devices.size, (128,), 3)

    def kernel(local):
        padded = exchange_halo(local, 1, 0, name, mode="wrap")
        return padded[:-2] + padded[1:-1] + padded[2:]

    out = jax.jit(shard_map(kernel, mesh, in_specs=P(name),
                            out_specs=P(name)))(
        jax.device_put(x, NamedSharding(mesh, P(name))))
    same(out, np.roll(x, 1, axis=0) + x + np.roll(x, -1, axis=0),
         "halo exchange moving sum")


def gaussian_oracle(x, sigma, axis):
    """ops.gaussian's definition on host: normalised taps at radius
    ``int(4·sigma + 0.5)``, zero-padded 'same' correlation, in f64."""
    radius = int(4.0 * sigma + 0.5)
    taps = np.exp(-0.5 * (np.arange(-radius, radius + 1) / sigma) ** 2)
    taps /= taps.sum()
    pad = [(0, 0)] * x.ndim
    pad[axis] = (radius, radius)
    xp = np.pad(x.astype(np.float64), pad)
    out = np.zeros(x.shape, np.float64)
    for k, t in enumerate(taps):
        sl = [slice(None)] * x.ndim
        sl[axis] = slice(k, k + x.shape[axis])
        out += xp[tuple(sl)] * t
    return out


def phase_resident(mesh, sz, seed):
    import bolt_tpu as bolt
    from bolt_tpu import ops

    step = Steps()
    before = device_stat("bytes_in_use")
    step("north-star", lambda: northstar(mesh, tuple(sz.northstar)))

    def released():
        # a cached program that closed over the input would pin 10 GB of
        # a 16 GB chip: the next large allocation's OOM
        gc.collect()                    # stat group <-> member cycles
        after = device_stat("bytes_in_use")
        held = max(a - b for a, b in zip(after, before))
        need(held < (64 << 20), "%.2f GB still in use after the "
             "north-star's arrays were dropped" % (held / 1e9))

    if before is not None:
        step("north-star input released", released)
    step("donation rule", lambda: donation_rule(mesh, tuple(sz.donate)))
    step("halo exchange", lambda: halo_exchange(mesh))

    # configs 2-4 and the filters share ONE ingested host operand
    shape = tuple(sz.resident)
    t0 = time.perf_counter()
    x = np.random.default_rng(seed).standard_normal(shape, dtype=np.float32)
    t1 = time.perf_counter()
    b = bolt.array(x, mesh)
    b.tojax().block_until_ready()
    log("  operand %s (%.2f GB): generate %.1fs, bolt.array ingest %.2fs"
        % (shape, x.nbytes / 1e9, t1 - t0, time.perf_counter() - t1))
    step("operand spread", lambda: check_spread(b, "resident operand"))
    probe = sorted({0, shape[0] // 2, shape[0] - 1})

    def config2():
        # elementwise + axis reductions over the split axis
        m = b.map(_sqrt_abs)
        y = np.sqrt(np.abs(x))
        y64 = y.astype(np.float64)
        close(m.mean(), y64.mean(axis=0), "config 2 mean", rtol=1e-4)
        close(m.std(), y64.std(axis=0), "config 2 std", rtol=1e-4)
        close(m.var(), y64.var(axis=0), "config 2 var", rtol=1e-4)
        # the chip's sqrt is not numpy's to the last bit
        close(m.max(), y.max(axis=0), "config 2 max", rtol=1e-6, atol=0)

    def stats():
        st = b.stats()                           # shard_map Welford
        x64 = x.astype(np.float64)
        close(st.mean(), x64.mean(axis=0), "stats mean", rtol=1e-4)
        close(st.variance(), x64.var(axis=0), "stats variance", rtol=1e-4)
        same(st.min(), x.min(axis=0), "stats min")
        same(st.max(), x.max(axis=0), "stats max")
        mosaic_ran("welford")

    def config3():
        s = b.swap((0,), (0,))                   # exact, whole result
        check_spread(s, "swap result")
        same(s, np.transpose(x, (1, 0) + tuple(range(2, len(shape)))),
             "config 3 swap")

    def config4():
        # fused filter (pending result), then the fused filter->sum
        keep = x.mean(axis=tuple(range(1, len(shape))),
                      dtype=np.float64) > 0
        same(b.filter(_mean_positive), x[keep], "config 4 filter")
        close(b.filter(_mean_positive).sum(),
              x[keep].sum(axis=0, dtype=np.float64), "config 4 filter.sum",
              rtol=1e-4, atol=1e-3)
        log("  config 4 filter kept %d of %d records"
            % (int(keep.sum()), shape[0]))

    def gaussian(sigma, vaxis):
        # sigma 1 on the leading value axis: the window kernel; sigma 4
        # on the lane axis is past the 9-tap crossover: lane_band_pallas
        def run():
            g = ops.gaussian(b, sigma=sigma, axis=(vaxis,))
            close(g.tojax()[np.asarray(probe)],
                  gaussian_oracle(x[probe], sigma, 1 + vaxis),
                  "gaussian sigma %g on value axis %d" % (sigma, vaxis),
                  rtol=1e-4, atol=1e-4)
            mosaic_ran("sepfilter")
        return run

    step("config 2 reductions", config2)
    step("stats()", stats)
    step("config 3 swap", config3)
    step("config 4 filter", config4)
    step("gaussian, leading axis", gaussian(1.0, 0))
    step("gaussian, lane axis", gaussian(4.0, len(shape) - 2))
    del b, x

    def config5():
        # chunk().map() with a per-chunk SVD
        shape = tuple(sz.svd)
        cs = sz.svd_chunk
        x = np.random.default_rng(seed + 1).standard_normal(
            shape, dtype=np.float32)
        b = bolt.array(x, mesh)
        t0 = time.perf_counter()
        sv = host(b.chunk(size=(cs,), axis=(0,)).map(_svals).unchunk())
        nchunk = shape[1] // cs
        sv = sv.reshape(shape[0], nchunk, shape[2])
        need(np.all(np.isfinite(sv)), "config 5: non-finite values")
        for k, i in [(0, 0), (shape[0] // 2, nchunk // 2),
                     (shape[0] - 1, nchunk - 1)]:
            ref = np.linalg.svd(
                x[k, i * cs:(i + 1) * cs].astype(np.float64),
                compute_uv=False)
            close(sv[k, i], ref, "config 5 svd chunk (%d, %d)" % (k, i),
                  rtol=1e-3, atol=1e-3)
        log("  config 5 per-chunk SVD (%d chunks of %s): %.1fs"
            % (shape[0] * nchunk, (cs, shape[2]),
               time.perf_counter() - t0))

    step("config 5 per-chunk SVD", config5)
    step.finish()


# ---------------------------------------------------------------------
# phase: served
# ---------------------------------------------------------------------

def phase_served(mesh, sz, seed):
    import bolt_tpu as bolt
    from bolt_tpu import engine, serve

    nb = 8
    tenants = ["t%d" % i for i in range(4)]
    bs = [bolt.randn(sz.serve_shape, context=mesh, seed=seed + i,
                     dtype=np.float32).cache() for i in range(nb)]
    xs = [host(b) for b in bs]

    def make(i):
        return bs[i % nb].map(_plus_one).sum()

    direct = [host(make(i)) for i in range(nb)]
    for i in range(nb):
        close(direct[i], (xs[i].astype(np.float64) + 1).sum(axis=0),
              "served operand %d direct" % i, rtol=1e-5, atol=1e-4)

    rec = sz.serve_stream_records
    sshape = (rec,) + tuple(sz.stream[1:])
    load = lattice_loader(sshape, seed + 99)

    def streamed_job():
        return bolt.fromcallback(load, sshape, mesh, dtype=np.float32,
                                 chunks=sz.stream_chunks).map(_plus_one).sum()

    sdirect = host(streamed_job())
    same(sdirect, lattice(0, rec, sshape[1:], seed + 99).sum(axis=0) + rec,
         "served streamed job direct")

    nreq = sz.serve_requests
    workers = 2

    def serve_with(batching):
        def run():
            c0 = engine.counters()
            with serve.serving(workers=workers, queue_limit=2 * nreq,
                               batching=batching) as sv:
                t0 = sv.stats()["totals"]
                # park the workers while the queue fills, so what
                # coalesces does not depend on a race with the submitter
                gate = threading.Event()
                parked = [sv.submit(gate.wait) for _ in range(workers)]
                futs = [sv.submit(make(i), tenant=tenants[i % len(tenants)])
                        for i in range(nreq // 2)]
                sfut = sv.submit(streamed_job(), tenant="bulk")
                futs += [sv.submit(make(i),
                                   tenant=tenants[i % len(tenants)])
                         for i in range(nreq // 2, nreq)]
                gate.set()
                outs = [f.result(timeout=600) for f in futs]
                sout = sfut.result(timeout=600)
                for f in parked:
                    f.result(timeout=60)
            t1 = sv.stats()["totals"]
            c1 = engine.counters()
            # unbatched, a served request IS the direct program; batched,
            # it is a lane of a stacked one, whose reduction XLA may
            # order differently: float32 rounding, and the log says how
            # much
            worst = "identical"
            for i, out in enumerate(outs):
                what = "served request %d (batching=%s)" % (i, batching)
                if batching:
                    close(out, direct[i % nb], what)
                    diff = rounding(out, direct[i % nb])
                    if diff != "identical":
                        worst = diff
                else:
                    same(out, direct[i % nb], what)
            same(sout, sdirect, "served streamed job (batching=%s)"
                 % batching)
            done = t1["completed"] - t0["completed"]
            need(done == nreq + 1 + workers
                 and t1["failed"] == t0["failed"],
                 "serve completed %d of %d jobs, %d failed"
                 % (done, nreq + 1 + workers,
                    t1["failed"] - t0["failed"]))
            batched = c1["batched_requests"] - c0["batched_requests"]
            need(bool(batched) == batching, "batching=%s served %d "
                 "requests through batched dispatches"
                 % (batching, batched))
            log("  %d requests over %d tenants + 1 streamed job, "
                "batching=%s: %d requests in %d batched dispatches; vs "
                "direct: %s"
                % (nreq, len(tenants), batching, batched,
                   c1["batched_dispatches"] - c0["batched_dispatches"],
                   worst))
        return run

    step = Steps()
    step("batching off", serve_with(False))
    step("batching on", serve_with(True))
    step.finish()


# ---------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------

def end_checks(start):
    """``start``: the engine counters when the first phase began."""
    import jax
    from bolt_tpu import engine
    from bolt_tpu.tpu import array as array_mod
    c = engine.counters()
    fell = c["fallbacks"] - start["fallbacks"]
    need(fell == 0, "%d engine dispatches bypassed the AOT path (a failed "
         "lower/compile re-ran through plain jit)" % fell)
    if jax.default_backend() == "tpu":
        limit = array_mod._hbm_limit()
        report = jax.local_devices()[0].memory_stats()["bytes_limit"]
        need(limit == report, "HBM budget %r is not the device's report "
             "%r" % (limit, report))
        log("  HBM budget %.2f GB, from the device" % (limit / 1e9))
    log("  engine: %d programs, %d aot compiles (lower %.1fs, compile "
        "%.1fs), persistent cache %d hits / %d misses, %d donations, "
        "0 fallbacks"
        % (c["misses"], c["aot_compiles"], c["lower_seconds"],
           c["compile_seconds"], c["persistent_hits"],
           c["persistent_misses"], c["donations"]))


def run(sz, seed, out_dir):
    """Run every phase; returns the list of failed phase names."""
    from bolt_tpu import engine
    from bolt_tpu.base import HostFallbackWarning
    from bolt_tpu.parallel.mesh import default_mesh
    warnings.simplefilter("error", HostFallbackWarning)
    mesh = default_mesh()
    failed = []
    start = engine.counters()
    phases = [
        ("streamed", lambda: phase_streamed(mesh, sz, seed, out_dir)),
        ("resident", lambda: phase_resident(mesh, sz, seed)),
        ("served", lambda: phase_served(mesh, sz, seed)),
        ("end checks", lambda: end_checks(start)),
    ]
    for name, fn in phases:
        log("== %s" % name)
        c0 = engine.counters()
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:
            failed.append(name)
            log("!! %s FAILED\n%s" % (name, traceback.format_exc()))
        c1 = engine.counters()
        log("== %s: %s in %.1fs (lower %.1fs + compile %.1fs of it, %d "
            "new programs)"
            % (name, "FAILED" if name in failed else "ok",
               time.perf_counter() - t0,
               c1["lower_seconds"] - c0["lower_seconds"],
               c1["compile_seconds"] - c0["compile_seconds"],
               c1["aot_compiles"] - c0["aot_compiles"]))
    return failed


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out",
                    help="directory for the spill leg's files")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log("device: %s" % json.dumps(device))
    if dev.platform != "tpu":
        print("chip_smoke.py needs a TPU; JAX found platform %r"
              % dev.platform, file=sys.stderr)
        return 2
    need(not jax.config.jax_enable_x64, "the chip runs with x64 off")

    from bolt_tpu import engine
    log("compile cache: %s" % engine.persistent_cache())
    t0 = time.perf_counter()
    failed = run(Sizes(), args.seed, args.out)
    log("total %.1fs" % (time.perf_counter() - t0))
    if failed:
        print("chip_smoke.py: failed phases: %s" % ", ".join(failed),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
