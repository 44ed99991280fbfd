"""Tracing, timing and debug instrumentation.

The reference ships NO in-repo tracing/profiling — users fall back to the
Spark UI and JVM metrics (SURVEY §5).  The TPU stack does better for free:
``jax.profiler`` captures device traces viewable in TensorBoard/Perfetto,
and XLA programs have precise completion semantics, so wall-clock and GB/s
numbers are meaningful.  This module packages that:

* :func:`trace` — context manager writing a device trace to a log dir.
  Every ``bolt_tpu.obs`` span open inside it (the engine's, the streamed
  executor's, the array terminals', a caller's own ``obs.span``) lands
  in that trace as a ``bolt.<name>`` event on the device's clock: this
  module hands the tracer the profiler's side (``obs.trace.set_bridge``).
* :func:`timeit` — wall-clock of a function over device arrays: blocks
  on the result (``block_until_ready`` does block on the chip's own
  host — PERF.md, PR 21) and then fetches it, so the figure includes
  the device→host copy of the result.
* :func:`throughput` — GB/s given bytes touched, the BASELINE "GB/s/chip"
  metric.
* :func:`debug_nans` — toggles jax NaN checking (the race-detector slot in
  SURVEY §5: SPMD is race-free by construction; numeric poison is the
  practical hazard, so that's what debug mode checks).
"""

import contextlib
import time

import numpy as np

import jax

from bolt_tpu.obs import trace as _trace


def _annotate(name, **stats):
    ann = jax.profiler.TraceAnnotation(name, **stats)
    ann.__enter__()
    return ann


# the jax side of the obs bridge: spans record while a profiler session
# is live and open a TraceAnnotation each (obs/trace.py stays stdlib-only)
_trace.set_bridge(jax.profiler.TraceAnnotation.is_enabled, _annotate)


def trace(logdir):
    """Device-trace context manager::

        with bolt_tpu.profile.trace("/tmp/trace"):
            b.map(f).sum().toarray()

    View with TensorBoard's profile plugin or Perfetto; name a region
    of your own with ``bolt_tpu.obs.span("my.region")``."""
    return jax.profiler.trace(logdir)


def timeit(fn, iters=5, warmup=1):
    """``(result, best_seconds)`` for ``fn()`` over ``iters`` timed runs.

    Works on ANY pytree result: each run blocks on the whole output via
    ``jax.block_until_ready`` (tuples/dicts/dataclasses of arrays, and
    non-array leaves, all handled — not just objects exposing a
    ``.block_until_ready`` method), then pulls it to the host
    (``jax.device_get``), so the timing includes the result's
    device→host copy.

    ``iters`` must be >= 1 (a "best of zero runs" has no answer);
    negative ``warmup`` counts as zero.
    """
    if iters < 1:
        raise ValueError(
            "timeit needs iters >= 1 (got %r): best-of is undefined over "
            "zero timed runs" % (iters,))
    result = None
    for _ in range(max(warmup, 0)):
        result = jax.device_get(jax.block_until_ready(fn()))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        result = jax.device_get(jax.block_until_ready(fn()))
        best = min(best, time.perf_counter() - t0)
    return result, best


def throughput(nbytes, seconds):
    """GB/s for ``nbytes`` touched in ``seconds`` (the BASELINE
    "GB/s/chip" metric when run single-chip)."""
    return nbytes / 1e9 / seconds


def array_bytes(barray):
    """Logical payload bytes of a bolt array."""
    return int(np.prod(barray.shape, dtype=np.int64)) * barray.dtype.itemsize


def debug_nans(enable=True):
    """Toggle jax's NaN checking for all subsequently compiled programs."""
    jax.config.update("jax_debug_nans", bool(enable))


@contextlib.contextmanager
def instrument():
    """Context manager recording per-op-family execution counts, compile
    (executable-build) counts and host dispatch time for every bolt
    operation run inside it::

        with bolt_tpu.profile.instrument() as stats:
            b.map(f).sum().toarray()
            b.stats()
        print(bolt_tpu.profile.report(stats))

    ``stats`` maps op family — the executable-cache key prefix:
    ``"chain"`` (materialising a deferred map chain), ``"first"``,
    ``"reduce"``, ``"stat"`` (mean/sum/... family), ``"welford"``,
    ``"filter-fused"``, ``"swap"``, ``"getitem"``, ... — to
    ``{"calls", "builds", "dispatch_s"}``.  ``builds`` counts jit-cache
    misses — the RECOMPILE detector: a pipeline that rebuilds the same
    family every iteration (e.g. a fresh lambda per call) shows
    ``builds == calls`` instead of ``builds == 1``.  ``dispatch_s`` is
    host-side dispatch (launches are async); use :func:`timeit` or
    :func:`trace` for device-completion timing.

    The reference has nothing comparable in-repo (Spark UI fills the
    slot, SURVEY §5); this is the framework-level half of that story.
    """
    import bolt_tpu.stream as _stream
    import bolt_tpu.tpu.array as _arr
    import bolt_tpu.tpu.chunk as _chunk
    import bolt_tpu.tpu.multistat as _mstat
    import bolt_tpu.tpu.stack as _stack
    import bolt_tpu.tpu.stats as _stats
    # every module binds _cached_jit by name at import; snapshot and
    # restore EACH binding so nested/overlapping contexts unwind cleanly
    saved = {m: m._cached_jit for m in (_arr, _chunk, _mstat, _stack,
                                        _stats, _stream)}
    orig = _arr._cached_jit
    stats = {}

    def wrapped(key, builder):
        fam = key[0] if isinstance(key, tuple) and key else str(key)
        e = stats.setdefault(
            fam, {"calls": 0, "builds": 0, "dispatch_s": 0.0})

        def counting_builder():
            e["builds"] += 1
            return builder()

        fn = orig(key, counting_builder)

        def timed(*a, **k):
            t0 = time.perf_counter()
            out = fn(*a, **k)
            e["calls"] += 1
            e["dispatch_s"] += time.perf_counter() - t0
            return out
        return timed

    for m in saved:
        m._cached_jit = wrapped
    try:
        yield stats
    finally:
        for m, fn in saved.items():
            # restore only our own wrapper: if an inner instrument() is
            # still live (contexts should exit LIFO, but generators /
            # ExitStacks can misorder), leave its wrapper counting
            # rather than silently disabling it
            if m._cached_jit is wrapped:
                m._cached_jit = fn


def report(stats):
    """Human-readable table for :func:`instrument` results."""
    lines = ["%-16s %7s %7s %12s" % ("family", "calls", "builds",
                                     "dispatch_s")]
    for fam in sorted(stats):
        e = stats[fam]
        lines.append("%-16s %7d %7d %12.4f"
                     % (fam, e["calls"], e["builds"], e["dispatch_s"]))
    return "\n".join(lines)


def engine_counters():
    """Snapshot of the central dispatch engine's counters (see
    :mod:`bolt_tpu.engine`): executable-cache ``hits``/``misses``,
    ``aot_compiles`` with ``lower_seconds``/``compile_seconds`` split
    (the persistent on-disk cache drives ``compile_seconds`` to ~0 in a
    warm process), ``dispatches``/``dispatch_seconds`` host-side launch
    accounting, ``fallbacks`` (dispatches the AOT path could not serve),
    ``donations`` (terminal buffer donations granted),
    ``persistent_hits``/``persistent_misses`` for the on-disk XLA
    cache, and the static-analysis tallies: ``diagnostics`` (findings
    emitted by ``bolt_tpu.analysis.check``), ``strict_checks`` /
    ``strict_rejections`` (pre-dispatch checks run and dispatches
    refused inside an ``analysis.strict()`` scope).  The snapshot is
    consistent — taken under the same lock every increment holds.

    Since PR 4 the backing store is the ``"engine"`` counter group in
    the :mod:`bolt_tpu.obs.metrics` registry (this function is a thin
    facade over ``engine.counters()``, itself a facade over the group):
    identical keys, types and semantics, now enumerable alongside every
    other metric via ``bolt_tpu.obs.registry().snapshot()``."""
    from bolt_tpu import engine
    return engine.counters()


def reset_engine_counters():
    from bolt_tpu import engine
    engine.reset_counters()


def overlap_efficiency(counters=None):
    """Fraction of streaming ingest time (host production + upload)
    hidden behind device compute, from the engine's ``stream_*``
    counters: ``stream_overlap_seconds / stream_ingest_seconds`` where
    per run ``overlap = max(0, ingest + compute − wall)``.  Ingest is
    summed across the uploader pool's workers (parallel ingest can
    exceed wall time — that surplus IS hidden work), and compute is the
    consumer's dispatch + window/final sync time, so the ratio stays
    meaningful under async dispatch.  ``0.0`` when nothing has streamed
    (or nothing overlapped); values toward ``1.0`` mean transfer is
    fully hidden — the out-of-core pipeline runs at compute speed, not
    ingest speed.

    Well-defined on EVERY input: a fresh process, a CPU-only container
    that never streamed, or a hand-built ``counters`` dict with keys
    missing all return ``0.0`` instead of dividing by zero."""
    c = engine_counters() if counters is None else counters
    ingest = c.get("stream_ingest_seconds", 0.0) or 0.0
    if ingest <= 0.0:
        return 0.0
    return (c.get("stream_overlap_seconds", 0.0) or 0.0) / ingest


def engine_report(counters=None):
    """Human-readable table of the engine counters::

        print(bolt_tpu.profile.engine_report())

    A fresh process (or an empty/all-zero ``counters`` dict) renders a
    "(no engine activity)" note instead of raising or printing a wall
    of zeros as if something ran."""
    c = engine_counters() if counters is None else counters
    lines = ["%-24s %12s" % ("counter", "value")]
    if not c or not any(v for v in c.values()):
        lines.append("(no engine activity)")
        return "\n".join(lines)
    for k in sorted(c):
        v = c[k]
        lines.append("%-24s %12s"
                     % (k, ("%.4f" % v) if isinstance(v, float) else v))
    return "\n".join(lines)


def memory_stats(device=None):
    """Per-device memory counters (HBM on TPU) as a dict.  Keys follow
    the PJRT convention (``bytes_in_use``, ``bytes_limit``,
    ``peak_bytes_in_use``, ...).

    DOCUMENTED DEGRADED SHAPE: returns the empty dict ``{}`` — never
    raises — when the backend lacks ``memory_stats()`` (CPU containers),
    when the query returns nothing, or when no device is visible at
    all; callers can always write ``memory_stats().get("bytes_in_use",
    0)``."""
    try:
        d = device if device is not None else jax.local_devices()[0]
        stats = d.memory_stats()
    except Exception:
        return {}
    return dict(stats) if stats else {}
