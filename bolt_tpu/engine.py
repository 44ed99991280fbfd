"""Central dispatch engine: ONE keyed ahead-of-time executable cache.

Every op family used to hand-roll the same plumbing — a bounded
``OrderedDict`` LRU of jitted callables keyed on (op, funcs, geometry) —
in ``tpu/array.py`` and re-import it everywhere else.  That worked, but a
production executor needs three things the scattered version could not
give:

1. **Ahead-of-time compilation with visibility.**  ``get(key, builder)``
   returns a dispatcher that lowers and compiles the jitted program
   explicitly (``jit(f).lower(*args).compile()``) per argument signature,
   so the engine knows exactly when XLA compilation happens and how long
   it took — exported as the ``aot_compiles`` / ``compile_seconds``
   counters — instead of compilation hiding inside jit's first call.
   Dispatch then goes straight to the compiled executable.

2. **Cross-process persistence.**  :func:`persistent_cache` opts in to
   JAX's on-disk compilation cache (placed by
   ``$JAX_COMPILATION_CACHE_DIR``, else beside the checkout, with the
   min-time/min-size floors dropped to zero), so a warm process
   re-lowers but skips XLA compilation entirely: the second run of an
   identical pipeline in a fresh process shows ``compile_seconds ≈ 0``.

3. **Hit/miss accounting.**  ``hits``/``misses`` count executable-cache
   lookups at the key level, ``dispatches``/``dispatch_seconds`` the
   host-side cost of launching (launches are async; device completion is
   :func:`bolt_tpu.profile.timeit`'s job).  Snapshot via
   :func:`counters`; ``bolt_tpu.profile`` re-exports a formatted report.

The engine also owns the **donation policy** for pipeline terminals:
``reduce``/``_stat``/chained-``map`` materialisation/``chunk().map``
donate a deferred chain's base buffer to XLA when (a) the chain is that
buffer's sole owner (no other live array wraps it) and (b) the buffer is
at least :func:`donation_min_bytes` big — halving peak HBM for one-shot
``ones(10GB).map(f).sum()``-style chains, where input + intermediate
cannot coexist.  A donated parent becomes unreadable (the same guard as
``swap(donate=True)``); the size floor keeps small interactive arrays
reusable.  ``donation(min_bytes)`` scopes the policy; ``donation(None)``
disables it.

Keys follow the established convention: (op-tag, user funcs, shape,
dtype, split, mesh, precision/extras) — hashable, and holding no array
references, so cached entries pin no device memory.
"""

import contextlib
import hashlib
import os
import re
import threading
import warnings
from collections import OrderedDict, deque

import jax

from bolt_tpu import _lockdep
from bolt_tpu.obs import metrics as _metrics
from bolt_tpu.obs import trace as _obs
from bolt_tpu.obs.trace import clock as _clock

# ---------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------

CACHE_MAX = 512                      # keyed entries (same bound as before)

# donation floor: terminals donate sole-owned chain bases at or above
# this size.  The default is deliberately HBM-scale (64 MB): donation's
# win is one-shot multi-GB chains where input + intermediate cannot
# coexist, while its cost — the consumed array can serve only ONE
# terminal — would surprise interactive reuse of modest arrays.  Arrays
# below the floor stay readable after any number of terminals.
# None = off entirely.
_DONATE_MIN_BYTES = int(os.environ.get("BOLT_DONATE_MIN_BYTES",
                                       str(64 << 20)))

_LOCK = _lockdep.rlock("engine.cache")   # guards the executable cache
_CACHE = OrderedDict()               # key -> _Entry
_BUILDING = {}                       # key -> Event: in-flight builds, so
                                     # concurrent same-key misses coalesce

# The engine counters live in the bolt_tpu.obs.metrics registry as the
# counter group named "engine" (PR 4): same keys, same int/float types,
# same lock-consistent snapshots as the module dict they replace —
# profile.engine_counters() is unchanged — but now enumerable (and
# resettable) alongside every other metric via obs.registry().
_SCHEMA = {
    "hits": 0,                # get() found the key
    "misses": 0,              # get() built a new entry (builder ran)
    "aot_compiles": 0,        # explicit lower+compile runs
    "lower_seconds": 0.0,     # wall time tracing/lowering (every process
                              # pays this; it is host work, not XLA)
    "compile_seconds": 0.0,   # wall time inside XLA compilation — the
                              # persistent cache drives this to ~0 in a
                              # warm process
    "dispatches": 0,          # executions dispatched through the engine
    "dispatch_seconds": 0.0,  # host-side dispatch wall time (async)
    "fallbacks": 0,           # dispatches that bypassed the AOT path
    "donations": 0,           # terminal buffer donations granted
    "persistent_hits": 0,     # XLA compiles served from the on-disk cache
    "persistent_misses": 0,   # XLA compiles that had to run for real
    "persistent_warm_hits": 0,  # persistent hits while a warm_start()
                                # fleet-preload is armed (serve.Server
                                # start_warm= — the no-compile-storm proof)
    # start-up accounting, PROCESS-WIDE (jax.monitoring's duration
    # events, so the fallbacks path and every jax.jit outside the engine
    # are in them, which lower_seconds / compile_seconds are not).  Each
    # of the four is SELF time on its thread: an event's seconds less the
    # events nested in it, so no second is in two of them
    "import_seconds": 0.0,    # bolt_tpu/__init__.py, first line to last
    "trace_seconds": 0.0,     # tracing to jaxprs   } the two halves
    "mlir_seconds": 0.0,      # jaxprs to StableHLO } of lowering
    "persistent_read_seconds": 0.0,  # on-disk cache: read, deserialize
    "backend_compile_seconds": 0.0,  # backend compiles less the cache
                                     # reads inside them: XLA's own work
    "compile_requests": 0,    # backend compiles asked for, hit or miss
    "diagnostics": 0,         # findings emitted by bolt_tpu.analysis.check
    "strict_checks": 0,       # pre-dispatch checks forced by analysis.strict
    "strict_rejections": 0,   # dispatches refused on error-severity findings
    # host<->device traffic accounting (fed by bolt_tpu.stream.transfer —
    # the ONE device_put wrapper, enforced by lint rule BLT105)
    "transfer_bytes": 0,      # host bytes shipped to device
    "transfer_seconds": 0.0,  # seconds during which at least ONE counted
                              # transfer was in flight: the union of the
                              # copies' intervals, so bytes over it is
                              # the link's rate however many uploader
                              # workers overlap (never above wall time)
    "transfer_copy_seconds": 0.0,  # each copy's own seconds, summed
                                   # across workers; over the counter
                                   # above it is the mean number of
                                   # copies in flight while any was
    "stream_upload_parts": 0,     # per-device sub-blocks the uploader
                                  # pool put (stream._upload_slab_mh: one
                                  # a local device a slab), so over
                                  # stream_chunks it is the parts a slab
    "transfer_elements": 0,       # elements those bytes were: bytes over
                                  # it is the width of an element ON THE
                                  # WIRE (4.0 for float32 data, 2.0 for a
                                  # camera's uint16 words, a codec's wire
                                  # dtype under a codec)
    "stream_narrow_slabs": 0,     # uploaded slabs whose element on the
                                  # wire was narrower than 32 bits (stored
                                  # so, or encoded so by a codec): the
                                  # device tiles them in packed sublanes
    # streaming-executor accounting (bolt_tpu.stream: the out-of-core
    # double-buffered pipeline).  overlap_seconds is ingest time hidden
    # behind device compute: max(0, ingest + compute - wall) per run;
    # profile.overlap_efficiency() reports it as a fraction of ingest.
    "stream_chunks": 0,           # slabs streamed through the executor
    "stream_ingest_seconds": 0.0,  # uploader-pool produce+upload time
                                   # (summed across workers: parallel
                                   # ingest can exceed wall time)
    "stream_compute_seconds": 0.0,  # main-thread dispatch + sync time
    "stream_wall_seconds": 0.0,    # end-to-end streamed-run wall time
    "stream_compile_seconds": 0.0,  # of that wall, the run's own
                                    # thread inside the start-up phases
                                    # above (a first pass lowers and
                                    # compiles its slab program): wall
                                    # less this is the passes themselves
    "stream_overlap_seconds": 0.0,  # ingest hidden behind compute
    "stream_prefetch_depth": 0,    # high-water configured prefetch depth
    "stream_upload_threads": 0,    # high-water CONCURRENT uploader
                                   # workers observed mid-upload (>1 is
                                   # the parallel-ingest proof)
    "stream_inflight_high_water": 0,  # high-water slab programs
                                      # dispatched but not yet confirmed
                                      # complete (the async window:
                                      # execute's, and the swap / collect
                                      # resolver's own since PR 56)
    "stream_windowed_slabs": 0,   # place calls the swap / collect
                                  # resolver dispatched while an earlier
                                  # one was still unconfirmed
    "stream_early_retired_slabs": 0,  # slabs whose permits execute's
                                      # window handed back without
                                      # blocking, before it was full
    # fault-tolerance accounting (ISSUE 9: resumable streams).  A retry
    # is one re-attempted slab ingest (stream.retries / the serve layer's
    # per-submit retries); a resume is one streamed run that restarted
    # from a slab-level checkpoint instead of from scratch.
    "stream_retries": 0,          # re-attempted slab ingests
    "stream_resumes": 0,          # runs resumed from a checkpoint
    "checkpoint_bytes": 0,        # partial-accumulator bytes persisted
    "checkpoint_seconds": 0.0,    # wall time inside checkpoint writes
                                  # (drain + host pull + atomic rename)
    # fused multi-terminal statistics (bolt.compute / a.stats(...) —
    # bolt_tpu/tpu/multistat.py): groups of N pending stat terminals
    # served by ONE tuple-output dispatch instead of N standalone passes
    "fused_stat_groups": 0,       # multi-terminal fused dispatches
    "fused_stat_terminals": 0,    # terminals served by those dispatches
                                  # (terminals - groups = dispatches saved)
    "one_pass_moment_launches": 0,  # var/std terminals of real floating
                                  # data DISPATCHED in the one-pass form
                                  # (tpu/moments.py): one a standalone
                                  # program, one a var/std member of a
                                  # fused or batched dispatch (counts the
                                  # FORM: over sharded key axes alone its
                                  # pilot is the mean, a second read)
    "getitems_fused": 0,          # deferred getitem windows traced inside
                                  # a consumer's program (no slice program
                                  # or launch of their own)
    "resplit_views": 0,           # re-splits (swap/_align with an identity
                                  # permutation and unchanged sharding)
                                  # served as a view: no program, no buffer
    "filters_fused": 0,           # deferred filters that ended in a
                                  # terminal (a statistic, a reduce, the
                                  # grouped fold) with no buffer built
    "filter_compactions": 0,      # deferred filters whose survivors were
                                  # built as an array (either compaction)
    "gram_kernel_programs": 0,    # programs LOWERED with ops/linalg.py's
                                  # packed_gram kernel in them (the
                                  # executor is chosen at lowering, so
                                  # programs can be counted, not calls;
                                  # 0 on the CPU)
    "gram_sums_programs": 0,      # those of them whose kernel also hands
                                  # back its blocks' row sums: a centred
                                  # pca or cov that takes its mean from
                                  # the Gram pass (0 on the CPU)
    "fold_kernel_programs": 0,    # programs LOWERED with tpu/fold.py's
                                  # thin_fold kernel in them: a filter
                                  # (or a map chain) folded into a
                                  # sum-like terminal over a table of
                                  # thin records, for one TPU device (0
                                  # on the CPU)
    # the record-blocked lowering of a deferred map chain (tpu/blocks.py:
    # a record function with record-sized temporaries, a sort, an FFT,
    # over an array too large to hold them for every record at once)
    "map_blocks": 0,              # blocks of records RUN: every dispatch
                                  # of a blocked program adds its count
    "blocked_chains": 0,          # programs LOWERED with a run of maps
                                  # over blocks (traced, so counted once
                                  # a program, not once a call)
    # a deferred array with more than one deferred consumer (ops.fourier's
    # pair over one map): its chain is run once and the result kept on
    # the node (tpu/array.py :: _lower_from_shared)
    "shared_parent_runs": 0,      # shared parents' chains RUN
    "shared_parent_hits": 0,      # consumers lowered from a kept result
                                  # and not from the base (the one whose
                                  # force ran the parent among them)
    # how a per-record percentile was taken (ops/select.py): by exact
    # selection at and above a length, by jnp.percentile's sort below it.
    # Bumped where the record function is TRACED, so a count says which
    # regime the programs of a chain took, not how often they ran (the
    # blocks rule and the shape inference trace the function too)
    "percentile_select_lowerings": 0,
    "percentile_sort_lowerings": 0,
    # selections LOWERED as the Mosaic kernel (one read of a block: a
    # program for one TPU device); every other selection lowers to the
    # counting passes and counts nothing here
    "percentile_kernel_lowerings": 0,
    # those of them whose kernel reads its block of records where the
    # base lies, by an offset, and not from a slice written out for it
    "percentile_based_lowerings": 0,
    # ``ops.fourier`` record functions TRACED without a pass for the mean,
    # because the stage before them (detrend, center, zscore) left it at
    # zero: one reader of the parent's result, which XLA fuses with it
    "fourier_centred_by_parent": 0,
    # ``ops.register.fit`` calls whose frames take the cross-correlation
    # surface as float32 DFT matrix products (traced float32 frames of
    # 128 to ``register.N_MAX`` a side); every other call keeps XLA's FFT
    # and counts nothing here
    "crosscorr_on_mxu": 0,
    # resident swaps across chips LOWERED as an explicit exchange and the
    # one-pass glue (bolt_tpu/parallel/swapmerge.py: a TPU mesh, the key
    # axis on the lanes in pieces that are no whole lane tiles); every
    # other swap keeps the transpose under a constraint and counts
    # nothing here
    "swap_merge_lowerings": 0,
    # cross-tenant coalescing proof (bolt_tpu.serve: N tenants running
    # the same pipeline shape must compile ONCE) — lookups that WAITED
    # for a concurrent identical build/compile instead of duplicating it
    "coalesced_builds": 0,        # get() calls that joined an in-flight
                                  # build of the same key
    "coalesced_compiles": 0,      # dispatches that joined an in-flight
                                  # lower+compile of the same signature
    # continuous micro-batching (bolt_tpu.serve Server(batching=...) +
    # bolt_tpu/tpu/batched.py): queued same-key small requests coalesced
    # into ONE stacked/vmapped dispatch at bucketed widths.
    # requests - dispatches = dispatches saved; the occupancy
    # distribution lives in the registry histogram
    # "serve.batch_occupancy.hist"
    "batched_dispatches": 0,      # coalesced batched program dispatches
    "batched_requests": 0,        # requests served BY those dispatches
    # codec-encoded streaming ingest (bolt_tpu/tpu/codec.py, ISSUE 14):
    # uploader workers ENCODE slabs on host before shipping, the slab
    # program decodes on device fused into the fold.  raw - wire =
    # host->device bytes SAVED; transfer_bytes tallies the wire bytes
    # (what actually crossed the link).
    "codec_encode_seconds": 0.0,  # host wall inside slab encodes
                                  # (summed across uploader workers)
    "codec_bytes_raw": 0,         # pre-encode logical slab bytes
    "codec_bytes_wire": 0,        # post-encode bytes actually shipped
    # the streaming shuffle (ISSUE 18): bytes moved through phase 1's
    # re-bucket dispatches (all-to-all included), bytes spilled to the
    # fingerprint directory when the plan exceeded the arbiter budget,
    # and the whole phase-1 wall (upload + re-bucket + spill).
    "shuffle_bytes": 0,
    "spill_bytes": 0,
    "shuffle_seconds": 0.0,
    "stream_alltoall_bytes": 0,   # of shuffle_bytes, what the planner's
                                  # model says crossed devices (ShufflePlan
                                  # .alltoall_bytes, in proportion to the
                                  # bytes a run moved; 0 on one device and
                                  # where the record axis stays leading)
    # a mapped result COLLECTED slab by slab (stream.collect: the
    # resolver's resident leg with no re-axis) and keyed stages
    "stream_collect_slabs": 0,    # slabs placed into a collected result
    "stream_collect_bytes": 0,    # bytes of result those slabs placed
    "stream_keyed_slabs": 0,      # slabs whose program took the slab's
                                  # first key as an operand (a with_keys
                                  # map on a streamed source; 0 where it
                                  # fell back to materialising)
    "stream_group_slabs": 0,      # slabs folded by a grouped terminal
                                  # (ops.segment_reduce by a label
                                  # function on a streamed source; 0
                                  # where it materialised)
    "stream_thin_slabs": 0,       # slabs that went up as a dense view of
                                  # their bytes and were re-seated by
                                  # their slab program: thin records
                                  # (stream.thin_records) and elements
                                  # narrower than 32 bits as their words
                                  # (stream.narrow_words)
    # a Gram matrix folded slab by slab (ops.pca / ops.cov on a streamed
    # source: stream.maybe_gram) and pca's second pass
    "stream_gram_slabs": 0,       # slabs folded by the Gram terminal (0
                                  # where the source was materialised)
    "stream_gram_kernel_slabs": 0,  # those of them whose slab program was
                                  # LOWERED with the packed_gram kernel
                                  # (a fall-back to dot_general reads
                                  # fewer; 0 on the CPU)
    "stream_project_slabs": 0,    # slabs a streamed pca's second pass
                                  # projected and placed into the scores
}

_COUNTERS = _metrics.registry().group("engine", _SCHEMA)

# ---------------------------------------------------------------------
# per-tenant counter scoping (bolt_tpu.serve)
# ---------------------------------------------------------------------
#
# A `tenant(name)` scope tags the calling thread; while active, every
# engine-counter increment ALSO lands in the registry group
# "engine/<name>" (same schema, same lock — CounterGroup.set_mirror), so
# a multi-tenant server can attribute transfer bytes, compiles and
# dispatches per tenant without a second accounting seam.  The scope is
# thread-local; bolt_tpu.stream propagates it into its uploader-pool
# threads so a streamed run's ingest traffic is attributed to the tenant
# that submitted it.

_TENANT_TLS = threading.local()


def current_tenant():
    """The calling thread's active tenant tag (``None`` outside any
    :func:`tenant` scope)."""
    return getattr(_TENANT_TLS, "name", None)


@contextlib.contextmanager
def tenant(name):
    """Scope the calling thread's tenant tag::

        with bolt_tpu.engine.tenant("team-a"):
            pipeline.sum().toarray()     # counters also land in
                                         # engine.tenant_counters("team-a")

    ``tenant(None)`` clears the tag inside the scope."""
    old = getattr(_TENANT_TLS, "name", None)
    _TENANT_TLS.name = None if name is None else str(name)
    try:
        yield
    finally:
        _TENANT_TLS.name = old


def _tenant_group():
    name = getattr(_TENANT_TLS, "name", None)
    if name is None:
        return None
    return _metrics.registry().group("engine/%s" % name, _SCHEMA)


_COUNTERS.set_mirror(_tenant_group)


def tenant_counters(name):
    """Consistent snapshot of tenant ``name``'s engine counters (the
    ``"engine/<name>"`` registry group — all zeros until a
    :func:`tenant` scope for that name does counted work)."""
    return _metrics.registry().group("engine/%s" % name, _SCHEMA).snapshot()

# size distribution riding on the same registry lock: the counters above
# give totals, this gives shape (log2 buckets — see
# bolt_tpu.obs.metrics.Histogram).  The ".hist" suffix keeps it off the
# group's flattened "engine.<key>" snapshot namespace.
_TRANSFER_HIST = _metrics.registry().histogram(
    "engine.transfer_bytes.hist", lo=6, hi=36)

# jax.monitoring's duration events, by the start-up counter each feeds
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_seconds",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "mlir_seconds",
    "/jax/compilation_cache/cache_retrieval_time_sec":
        "persistent_read_seconds",
    "/jax/core/compile/backend_compile_duration":
        "backend_compile_seconds",
}
_PHASE_SLACK = 1e-4      # seconds an event may reach its listener late


class _Tally(threading.local):
    """What the calling thread's compiles have done so far: the on-disk
    cache's answers (the hit/miss event fires on the compiling thread,
    inside ``lowered.compile()``, so a reading before and after a compile
    tells which it got) and ``phases``, the newest ``(start, seconds)`` of
    the :data:`_PHASES` events that no later event of the thread holds
    inside it, in :func:`_clock` seconds."""

    def __init__(self):
        self.hits = self.misses = 0
        self.read = 0.0
        self.phases = deque(maxlen=1024)


_TALLY = _Tally()


def _on_event(event, **kwargs):
    if event == "/jax/compilation_cache/cache_hits":
        _TALLY.hits += 1
        if _WARM_ARMED:
            _COUNTERS.update(persistent_hits=1, persistent_warm_hits=1)
        else:
            _COUNTERS.add("persistent_hits")
    elif event == "/jax/compilation_cache/cache_misses":
        _TALLY.misses += 1
        _COUNTERS.add("persistent_misses")


def _on_duration(event, seconds, **kwargs):
    key = _PHASES.get(event)
    if key is None:
        return
    # an event fires as its interval ENDS, the innermost first (a jitted
    # function traced inside another's trace, the cache's read inside the
    # backend compile): whatever this thread recorded since this one began
    # ran inside it, and is taken out so that the counters add up
    start = _clock() - seconds
    phases = _TALLY.phases
    own = seconds
    while phases and phases[-1][0] >= start - _PHASE_SLACK:
        own -= phases.pop()[1]
    phases.append((start, seconds))
    own = max(own, 0.0)
    if key == "backend_compile_seconds":
        _COUNTERS.update(backend_compile_seconds=own, compile_requests=1)
    else:
        if key == "persistent_read_seconds":
            _TALLY.read += seconds
        _COUNTERS.add(key, own)


def _phases_since(start):
    """Seconds the calling thread spent inside the start-up phases since
    ``start`` (whole outermost events; :func:`_clock` seconds)."""
    total = 0.0
    for begun, seconds in reversed(_TALLY.phases):
        if begun < start:
            break
        total += seconds
    return total


def _hook_monitoring():
    """Listen to jax's monitoring events: the only public signal of
    whether ``.compile()`` loaded from disk, and of what tracing, lowering
    and compiling cost OUTSIDE this module's AOT path."""
    try:
        from jax import monitoring
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
    except Exception:
        pass


_hook_monitoring()


def record_import(seconds):
    """The package's own import, first line of ``bolt_tpu/__init__.py`` to
    its last (with whatever it was the first to import: jax, if the
    caller had not)."""
    _COUNTERS.add("import_seconds", seconds)


def counters():
    """A CONSISTENT snapshot dict of the engine counters: the copy is
    taken under the metrics-registry lock — the same lock every
    increment holds — so a snapshot can never interleave with a
    half-applied update (e.g. ``aot_compiles`` bumped but its
    ``compile_seconds`` not yet).  Counters are monotonic within a
    process; :func:`reset_counters` zeroes them.  The backing store is
    the ``"engine"`` counter group in ``bolt_tpu.obs.registry()`` —
    keys, types and semantics are identical to the pre-registry dict."""
    return _COUNTERS.snapshot()


def reset_counters():
    """Zero the counters, and drop the compile log with them (its rows
    are stamped with the ``dispatches`` count).  ``import_seconds`` is a
    fact of the process (the package is imported once, before anything
    can be reset) and not a tally of work since the last reset: it
    stays."""
    _COUNTERS.reset(keep=("import_seconds",))
    with _LOCK:
        _COMPILE_LOG.clear()
        del _LINK_BUSY[:]


# ---------------------------------------------------------------------
# the compile log: which program compiled, and did the cache serve it
# ---------------------------------------------------------------------

_COMPILE_LOG = deque(maxlen=512)     # newest rows; a compile costs
#                                      milliseconds at least, a row nothing


def compile_log():
    """The newest (at most 512) lower+compile runs of the AOT path, oldest
    first, a dict each:

    ``family``      the op family of the program's engine key
    ``program``     a short digest of that key and of the argument
                    signature it was compiled for: the same program reads
                    the same in every process (addresses are left out,
                    so two functions of one qualified name read alike),
                    and a cold run's rows pair with a warm run's
    ``lower_s``     seconds tracing and lowering (host work, every time)
    ``compile_s``   seconds inside ``lowered.compile()``
    ``cache``       ``"hit"``: the on-disk cache served it; ``"miss"``:
                    XLA compiled it (and the cache kept it); ``"off"``:
                    no on-disk cache was asked
    ``read_s``      seconds of ``compile_s`` reading the on-disk cache
    ``t0``          when lowering began, in ``obs.clock`` seconds: the
                    clock of every span, so rows lie on ``obs.to_chrome``'s
                    timeline and on a profiler trace
    ``dispatches``  the ``dispatches`` counter at that moment

    The operator's answer to "which step recompiled": a row stamped past
    warm-up names it.  Compiles outside the AOT path (``fallbacks``, any
    ``jax.jit`` of the caller's own) are in ``compile_requests`` and the
    process-wide seconds, not here."""
    with _LOCK:
        return [dict(row) for row in _COMPILE_LOG]


def _log_compile(key, sig, t0, t1, t2, before):
    """One row for the compile of engine key ``key`` at signature ``sig``
    that ran ``t0 .. t1 .. t2``; ``before`` is the thread's ``(hits,
    misses, read)`` tally as lowering began."""
    hits, misses, read = before
    row = {
        "family": _family(key),
        "program": hashlib.sha256(
            _stable_key((key, sig)).encode()).hexdigest()[:12],
        "lower_s": t1 - t0, "compile_s": t2 - t1,
        "cache": ("hit" if _TALLY.hits > hits
                  else "miss" if _TALLY.misses > misses else "off"),
        "read_s": _TALLY.read - read,
        "t0": t0, "dispatches": _COUNTERS["dispatches"],
    }
    with _LOCK:
        _COMPILE_LOG.append(row)
    return row


def clear():
    """Drop every cached executable (counters are left alone)."""
    with _LOCK:
        _CACHE.clear()


def cache_len():
    with _LOCK:
        return len(_CACHE)


# ---------------------------------------------------------------------
# persistent on-disk compilation cache
# ---------------------------------------------------------------------

_PERSISTENT_DIR = None

# where the cache lives when nobody places it: beside the package, so a
# checkout carries its cache with it (the path is part of jax's cache
# key — a directory that moves never hits)
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def persistent_cache(cache_dir=None, enable=True):
    """Opt in to JAX's persistent on-disk XLA compilation cache.

    ::

        bolt_tpu.engine.persistent_cache()

    Compiled programs persist on disk; a fresh process running the same
    pipeline re-lowers but loads the executable from disk instead of
    invoking XLA — the engine's ``compile_seconds`` counter stays ≈ 0 on
    the warm run.  The min-compile-time and min-entry-size floors are
    dropped to zero so EVERY program persists (this framework's programs
    are many and individually cheap; the default floors would skip most
    of them).

    Where the cache lives: ``$JAX_COMPILATION_CACHE_DIR`` when the
    variable is set — whoever runs the process placed the cache, so it
    wins over ``cache_dir`` and ``jax_compilation_cache_dir`` is left
    exactly as jax read it from the environment; else ``cache_dir``;
    else ``.jax_cache`` beside the package (the checkout).

    ``enable=False`` detaches a directory this function attached
    (in-memory caching only); a cache placed by the environment is not
    this module's to detach.  Returns the resolved directory (or
    ``None`` when disabling).  Any explicit call here also DISARMS a
    prior :func:`warm_start` — hits against a re-attached ordinary
    cache must not keep counting as warm-start hits (``warm_start``
    re-arms after delegating)."""
    global _PERSISTENT_DIR, _WARM_ARMED
    _WARM_ARMED = False
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not enable:
        if not placed:
            jax.config.update("jax_compilation_cache_dir", None)
            _reset_jax_cache_singleton()
        _PERSISTENT_DIR = None
        return None
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if placed:
        _PERSISTENT_DIR = os.path.abspath(placed)
        return _PERSISTENT_DIR
    cache_dir = os.path.abspath(cache_dir or _CHECKOUT_CACHE_DIR)
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    _reset_jax_cache_singleton()
    _PERSISTENT_DIR = cache_dir
    return cache_dir


def _reset_jax_cache_singleton():
    """jax initialises its compilation-cache object once per process;
    flipping the directory afterwards needs an explicit reset or the old
    (absent) cache keeps being consulted."""
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


def persistent_cache_dir():
    """The active on-disk cache directory, or ``None``."""
    return _PERSISTENT_DIR


# fleet-warm start (serve.Server(start_warm=dir)): while armed, every
# persistent-cache hit ALSO tallies persistent_warm_hits — the proof a
# fresh process served its first requests from pre-seeded executables
# instead of paying a compile storm
_WARM_ARMED = False


def warm_start(cache_dir):
    """Arm the fleet-warm start: attach the on-disk XLA cache at
    ``cache_dir`` (pre-seeded by an earlier process running the fleet's
    pipeline shapes; ``$JAX_COMPILATION_CACHE_DIR`` wins when set — see
    :func:`persistent_cache`) and count every compile it serves as a
    ``persistent_warm_hits`` — a warmed process's first request then
    re-lowers but runs ZERO fresh XLA compiles (``persistent_misses``
    stays flat).  Returns the resolved cache directory.
    ``serve.Server(start_warm=dir)`` calls this at startup and
    :func:`disarm_warm_start` when it closes, so the warm tally covers
    the warmed server's lifetime, not every later cache hit."""
    global _WARM_ARMED
    out = persistent_cache(cache_dir)
    _WARM_ARMED = True
    return out


def disarm_warm_start():
    """Stop counting persistent hits as warm-start hits (the cache
    itself stays attached — sharing compiled artifacts is still the
    point; only the METRIC arming ends)."""
    global _WARM_ARMED
    _WARM_ARMED = False


def exported(tag, parts, make, *specs, out_shardings=None):
    """The function ``make()`` returns, jitted (``out_shardings``) and
    exported for the abstract arguments ``specs`` (``jax.export``): read
    from the on-disk cache where one is attached and holds it, else
    exported now and kept there.  ``None`` where no cache is attached.

    For a program whose LOWERING costs more than a read: JAX's
    compilation cache is keyed by the lowered module, so a warm process
    still traces and lowers every program, and one that holds a Pallas
    kernel first imports Pallas, 1.2-1.4 s on the chip's host (PERF.md,
    PR 45).  The exported module is the lowered program itself, the
    kernel inside it as bytes: calling it traces nothing of ``make``'s.
    ``parts`` is everything that decides the program beside ``specs``
    and the jax that lowers it (the caller's own source among it, where
    that can change between runs of one cache)."""
    directory = persistent_cache_dir()
    if directory is None:
        return None
    from jax import export
    import jaxlib
    said = repr((jax.__version__, jaxlib.__version__,
                 bool(jax.config.jax_enable_x64), parts,
                 [(s.shape, str(s.dtype), str(s.sharding)) for s in specs]))
    path = os.path.join(directory, "bolt_exported", "%s-%s.jaxexport" % (
        tag, hashlib.sha256(said.encode()).hexdigest()[:40]))
    try:
        with open(path, "rb") as fh:
            return export.deserialize(bytearray(fh.read()))
    except FileNotFoundError:
        pass
    except Exception as exc:    # noqa: BLE001 - whatever a cut or foreign
        # file makes the reader raise: say so and export again
        warnings.warn("bolt_tpu: %s is unreadable (%r); exporting %s again"
                      % (path, exc, tag), RuntimeWarning, stacklevel=2)
    platform = next(iter(specs[0].sharding.device_set)).platform
    program = export.export(jax.jit(make(), out_shardings=out_shardings),
                            platforms=(platform,))(*specs)
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        scratch = "%s.%d.tmp" % (path, os.getpid())
        with open(scratch, "wb") as fh:
            fh.write(program.serialize())
        os.replace(scratch, path)       # whole or absent, never cut
    except OSError:
        pass                    # a cache that cannot be written is none
    return program


# ---------------------------------------------------------------------
# donation policy
# ---------------------------------------------------------------------

# per-thread scope overrides (a stack; innermost wins) over the
# process-wide default _DONATE_MIN_BYTES
_DONATE_TLS = threading.local()


def donation_min_bytes():
    """Effective donation floor in bytes for the calling thread
    (innermost :func:`donation` scope, else the process default), or
    ``None`` when terminal donation is disabled."""
    st = getattr(_DONATE_TLS, "stack", None)
    if st:
        return st[-1]
    return _DONATE_MIN_BYTES


def set_donation_min_bytes(n):
    """Set the PROCESS-WIDE donation floor (``None`` disables terminal
    donation); per-thread :func:`donation` scopes override it."""
    global _DONATE_MIN_BYTES
    _DONATE_MIN_BYTES = None if n is None else int(n)


@contextlib.contextmanager
def donation(min_bytes):
    """Scope the terminal-donation floor::

        with bolt_tpu.engine.donation(0):      # donate at any size
            out = bolt.ones(shape, mesh).map(f).sum()

    ``donation(None)`` disables donation inside the scope.  The scope is
    THREAD-LOCAL (like ``bolt.precision``): one thread's one-shot-chain
    scope must not flip donation on for a concurrent interactive thread,
    whose arrays would silently become single-terminal."""
    st = getattr(_DONATE_TLS, "stack", None)
    if st is None:
        st = _DONATE_TLS.stack = []
    st.append(None if min_bytes is None else int(min_bytes))
    try:
        yield
    finally:
        st.pop()


def record_batched(n_requests):
    """Tally one coalesced serve dispatch (bolt_tpu/tpu/batched.py)
    serving ``n_requests`` queued same-key requests from one stacked
    program; the timeline carries it as the ``serve.batched_dispatch``
    span."""
    _COUNTERS.update(batched_dispatches=1,
                     batched_requests=int(n_requests))


def record_fused_stats(n_terminals):
    """Tally one fused multi-stat dispatch serving ``n_terminals``
    pending terminals from a single pass (bolt_tpu/tpu/multistat.py);
    the timeline carries it as the ``array.multi_stat`` span."""
    _COUNTERS.update(fused_stat_groups=1,
                     fused_stat_terminals=int(n_terminals))


def record_one_pass_moments(n=1):
    """``n`` ``var``/``std`` terminals of real floating data were
    dispatched in the one-pass shifted-moment form
    (``tpu/moments.py``): the program reads what it reduces once."""
    _COUNTERS.add("one_pass_moment_launches", n)


def record_getitems_fused(n):
    """``n`` deferred ``getitem`` windows were handed to a consumer that
    traces them inside its own program (no slice program, no launch of
    their own); the eager ``getitem`` program is span ``array.getitem``."""
    _COUNTERS.add("getitems_fused", n)


def record_resplit_view():
    """One re-split was served as a view of the buffer it was asked of:
    the permutation was the identity and the sharding did not change, so
    no program ran and no second buffer exists (``tpu/array.py ::
    _do_swap``)."""
    _COUNTERS.add("resplit_views")


def record_filter_fused():
    """One deferred filter was folded into the program of the terminal
    that read it (``tpu/array.py :: _launch_filter_terminal``, span
    ``array.filter_stat``; a streamed filter into its run's slab
    programs, ``stream.execute``): one pass, no survivor buffer."""
    _COUNTERS.add("filters_fused")


def record_filter_compaction():
    """One deferred filter had its survivors built as an array
    (``tpu/array.py :: _resolve_fpending``, span ``array.filter``): the
    padded compaction or, above ``_FILTER_FUSED_MAX_BYTES``, the
    two-phase gather."""
    _COUNTERS.add("filter_compactions")


_LOWERED = threading.local()     # what THIS thread's lowerings placed


def record_gram_kernel_program(sums=False):
    """One program was lowered with the ``packed_gram`` Mosaic kernel in
    it (``ops/linalg.py :: _gram_primitive``): a program for one TPU
    device with a Gram matrix of real float32 that packs; with ``sums``
    in the form that also returns the per-feature sums (a centring
    caller's mean, from the same pass), counted under
    ``gram_sums_programs`` too.  Per call the record is the device trace
    (``packed_gram*`` events; the summing form's are
    ``packed_gram_sums*``)."""
    _COUNTERS.add("gram_kernel_programs")
    if sums:
        _COUNTERS.add("gram_sums_programs")
    _LOWERED.gram = gram_kernel_lowerings() + 1


def gram_kernel_lowerings():
    """How many programs the calling thread has lowered with the
    ``packed_gram`` kernel in them (a program is lowered by the thread
    that first calls it: :class:`_Dispatch` reads this around its own
    lowering and keeps the answer with the cached program, which is what
    ``stream_gram_kernel_slabs`` counts slabs by)."""
    return getattr(_LOWERED, "gram", 0)


def record_fold_kernel_program():
    """One program was lowered with the ``thin_fold`` Mosaic kernel in it
    (``tpu/fold.py :: _fold_primitive``): a program for one TPU device
    that folds a filter or a map chain into a sum-like terminal over a
    stored table of at most eight 32-bit values a record.  Per call the
    record is the device trace (``thin_fold*`` events)."""
    _COUNTERS.add("fold_kernel_programs")


def record_map_blocks(n):
    """A program with a record-blocked run of maps in it is about to be
    dispatched and runs ``n`` blocks (``tpu/array.py ::
    BoltArrayTPU._blocked``)."""
    _COUNTERS.add("map_blocks", n)


def record_shared_parent(ran):
    """A consumer of a shared deferred parent was lowered from the
    parent's kept result (``tpu/array.py ::
    BoltArrayTPU._lower_from_shared``); ``ran``: its force is the one
    that ran the parent's chain."""
    if ran:
        _COUNTERS.add("shared_parent_runs")
    _COUNTERS.add("shared_parent_hits")


def record_blocked_chain():
    """A program was traced with a run of maps lowered over blocks of
    records (``tpu/array.py :: _chain_apply_blocked``)."""
    _COUNTERS.add("blocked_chains")


def record_percentile_lowering(regime):
    """A record function was traced with a percentile taken by
    ``regime`` (``"select"`` or ``"sort"``: ``ops/select.py ::
    percentile``), or a selection was lowered as the Mosaic kernel
    (``"kernel"``: the ``percentile_select`` primitive's TPU rule; and
    ``"based"`` where that kernel reads its block from the base)."""
    _COUNTERS.add("percentile_%s_lowerings" % regime)


def record_fourier_centred_by_parent():
    """``ops.fourier``'s record function was traced without its own
    centring (``ops/series.py :: _fourier_fn``)."""
    _COUNTERS.add("fourier_centred_by_parent")


def record_crosscorr_on_mxu():
    """``ops.register.fit`` was called on frames whose surface is taken
    by matrix products (``ops/register.py :: _by_products``)."""
    _COUNTERS.add("crosscorr_on_mxu")


def record_swap_merge_lowering():
    """A resident swap was lowered with the one-pass glue
    (``parallel/swapmerge.py :: program``)."""
    _COUNTERS.add("swap_merge_lowerings")


def donation_granted():
    """Count a granted terminal donation (called by the op layers); a
    timeline carries it as an instant ``engine.donate`` mark under the
    consuming terminal's span."""
    _COUNTERS.add("donations")
    _obs.event("engine.donate")


# ---------------------------------------------------------------------
# static-analysis integration (bolt_tpu.analysis)
# ---------------------------------------------------------------------
#
# The abstract pipeline checker feeds the ``diagnostics`` counter on
# every check; an ``analysis.strict()`` scope installs a pre-dispatch
# guard here so the engine runs the checker before every compiling
# terminal and refuses to dispatch on error-severity findings.  The
# slot is a plain module global consulted by the op layers right before
# they enter :func:`get` — one attribute read when inactive.

_STRICT_GUARD = None


def set_strict_guard(fn):
    """Install (or clear, with ``None``) the pre-dispatch checker hook —
    owned by :func:`bolt_tpu.analysis.strict`."""
    global _STRICT_GUARD
    _STRICT_GUARD = fn


def strict_guard(arr, op):
    """Run the installed pre-dispatch checker on ``arr`` for terminal
    ``op`` (no-op when no :func:`bolt_tpu.analysis.strict` scope is
    active).  Called by the op layers immediately before a dispatching
    terminal enters :func:`get`."""
    g = _STRICT_GUARD
    if g is not None:
        g(arr, op)


def record_diagnostics(n):
    """Tally ``n`` checker findings (fed by ``bolt_tpu.analysis.check``)."""
    if n:
        _COUNTERS.add("diagnostics", n)


def strict_checked():
    _COUNTERS.add("strict_checks")


def strict_rejected():
    _COUNTERS.add("strict_rejections")
    _obs.event("engine.strict_reject")


# ---------------------------------------------------------------------
# transfer / streaming accounting (fed by bolt_tpu.stream)
# ---------------------------------------------------------------------

# the stretches of _clock time already counted into transfer_seconds:
# disjoint, oldest first, guarded by _LOCK.  A copy reports as it ENDS,
# so a new one reaches back over the newest few only
_LINK_BUSY = []
_LINK_KEEP = 16


def _link_fresh(start, end):
    """Count ``[start, end)`` as link-busy and return the seconds of it
    that no earlier transfer had counted (caller holds ``_LOCK``)."""
    fresh = end - start
    kept = []
    for a, b in _LINK_BUSY:
        if b < start or a > end:
            kept.append((a, b))
            continue
        fresh -= min(b, end) - max(a, start)
        start, end = min(start, a), max(end, b)
    kept.append((start, end))
    kept.sort()
    _LINK_BUSY[:] = kept[-_LINK_KEEP:]
    return max(fresh, 0.0)


def record_transfer(nbytes, seconds, parts=0, elements=0):
    """Tally one counted host->device transfer that took ``seconds`` and
    ended now (bolt_tpu.stream.transfer is the only caller — lint rule
    BLT105 keeps it that way).  ``parts``: the per-device sub-blocks an
    uploaded SLAB was put as (``stream_upload_parts``; 0 for any other
    transfer).  ``elements``: how many elements the bytes were
    (``transfer_elements``); a slab of fewer than four bytes an element
    is a narrow one (``stream_narrow_slabs``).
    ``transfer_copy_seconds`` gets the copy's
    own seconds; ``transfer_seconds`` only the part of them during which
    no other counted copy was in flight, so it is the link's busy time:
    the same number wherever copies never overlap.  The link is the
    process's: a tenant's mirror gets the part its copy added."""
    end = _clock()
    with _LOCK:
        busy = _link_fresh(end - seconds, end)
    _COUNTERS.update(transfer_bytes=int(nbytes),
                     transfer_seconds=busy,
                     transfer_copy_seconds=seconds,
                     stream_upload_parts=int(parts),
                     transfer_elements=int(elements),
                     stream_narrow_slabs=int(
                         bool(parts) and nbytes < 4 * elements))
    _TRANSFER_HIST.observe(int(nbytes))


def record_codec(raw_bytes, wire_bytes, seconds):
    """Tally one slab encode (bolt_tpu.stream's uploader workers — the
    codec-encoded ingest path, bolt_tpu/tpu/codec.py).  Applied
    atomically so a snapshot can never see a slab's raw bytes without
    its wire bytes; the timeline carries it as the ``stream.encode``
    span."""
    _COUNTERS.update(codec_bytes_raw=int(raw_bytes),
                     codec_bytes_wire=int(wire_bytes),
                     codec_encode_seconds=seconds)


def record_shuffle(nbytes, seconds, alltoall=0):
    """Tally one streamed shuffle's phase 1 (bolt_tpu.stream's swap
    resolver): ``nbytes`` moved through the re-bucket programs, of them
    ``alltoall`` across devices by the planner's model, and the phase's
    wall clock.  One update per shuffle, applied at the end — a snapshot
    never sees a half-accounted phase.  The timeline carries it as the
    ``stream.shuffle`` span."""
    _COUNTERS.update(shuffle_bytes=int(nbytes), shuffle_seconds=seconds,
                     stream_alltoall_bytes=int(alltoall))


def record_collect(slabs, nbytes, project=False):
    """Tally one streamed collect (bolt_tpu.stream's resident leg run
    with no re-axis): the slabs it placed and the bytes of result they
    made.  One update a collect, at its end; the timeline carries it as
    the ``stream.collect`` span and a ``stream.collect.place`` span a
    slab.  ``project``: the collect is a streamed pca's second pass
    (``ops/linalg.py``), whose slabs count under ``stream_project_slabs``
    too."""
    _COUNTERS.update(stream_collect_slabs=int(slabs),
                     stream_collect_bytes=int(nbytes),
                     stream_project_slabs=int(slabs) if project else 0)


def record_spill(nbytes):
    """Tally one spilled shuffle bucket's wire bytes
    (checkpoint.spill_save's return — dict-encoded when the slab's
    cardinality allowed, raw otherwise)."""
    _COUNTERS.update(spill_bytes=int(nbytes))


def record_stream_retry():
    """Tally one re-attempted slab ingest (a failed uploader attempt
    that was retried in place instead of poisoning the run)."""
    _COUNTERS.add("stream_retries")


def record_stream_resume():
    """Tally one streamed run resumed from a slab-level checkpoint."""
    _COUNTERS.add("stream_resumes")


def record_checkpoint(nbytes, seconds):
    """Tally one stream-checkpoint write (bolt_tpu.stream's resumable
    path; the timeline carries it as the ``stream.checkpoint`` span)."""
    _COUNTERS.update(checkpoint_bytes=int(nbytes),
                     checkpoint_seconds=seconds)


def record_stream(chunks, ingest_s, compute_s, wall_s, overlap_s, depth,
                  uploaders=1, inflight=1, keyed=0, group=0, thin=0,
                  gram=0, gram_kernel=0, windowed=0, early=0):
    """Tally one completed streamed run (bolt_tpu.stream executor); the
    keys apply atomically — a snapshot can never see a run's wall time
    without its overlap.  Called by the run's own thread as the run ends,
    so what that thread spent lowering and compiling since ``wall_s`` ago
    is the run's (``stream_compile_seconds``).  ``uploaders`` is the run's
    observed concurrent uploader high-water, ``inflight`` its
    dispatched-but-unconfirmed slab-program high-water (``execute``'s
    window of pair partials, and the swap / collect resolver's window of
    place calls: 1 where it confirms every call before the next); both
    (and the depth) keep process maxima.  ``keyed``: of ``chunks``, the
    slabs whose program took the slab's first key as an operand; ``group``:
    those a grouped terminal folded; ``thin``: those that went up dense
    (thin records, or a narrower element's words) and were re-seated on
    the device; ``gram``: those the Gram terminal
    folded, ``gram_kernel`` of them by a program lowered with the
    ``packed_gram`` kernel; ``windowed``: those whose place call the swap
    / collect resolver dispatched while an earlier one was still
    unconfirmed (its window at work: 0 at ``prefetch(1)``); ``early``:
    those whose permits ``execute``'s window handed back before it was
    full, because the head of the window was done when asked (0 at
    ``prefetch(1)``, and wherever no program is done a window later)."""
    _COUNTERS.update(_maxima={"stream_prefetch_depth": int(depth),
                              "stream_upload_threads": int(uploaders),
                              "stream_inflight_high_water": int(inflight)},
                     stream_chunks=int(chunks),
                     stream_keyed_slabs=int(keyed),
                     stream_group_slabs=int(group),
                     stream_thin_slabs=int(thin),
                     stream_gram_slabs=int(gram),
                     stream_gram_kernel_slabs=int(gram_kernel),
                     stream_windowed_slabs=int(windowed),
                     stream_early_retired_slabs=int(early),
                     stream_ingest_seconds=ingest_s,
                     stream_compute_seconds=compute_s,
                     stream_wall_seconds=wall_s,
                     stream_compile_seconds=_phases_since(
                         _clock() - wall_s),
                     stream_overlap_seconds=overlap_s)


# ---------------------------------------------------------------------
# the keyed AOT dispatch path
# ---------------------------------------------------------------------

# ONE blessed enqueue order for executables.  A single process driving a
# multi-device mesh from SEVERAL threads (the multi-tenant serving
# layer) can enqueue two collective programs onto the per-device queues
# in different orders per device — device 0 sees run A then B, device 1
# sees B then A — and the cross-device rendezvous (psum/all_to_all)
# deadlocks with every participant waiting for a different run.  This
# lock serialises only the ENQUEUE (dispatch is async; execution still
# overlaps), so all device queues observe one global program order and
# the rendezvous always completes.  Measured µs-scale per launch; the
# slow paths (lower/compile) run OUTSIDE it.
#
# MULTI-PROCESS scope (bolt_tpu.parallel.multihost): the lock is
# PER-PROCESS — it cannot order enqueues across hosts.  Cross-process
# collective order is instead safe BY CONSTRUCTION for the programs
# that span hosts: the streaming executor's shard_map slab programs
# dispatch in slab order on every process (the re-sequencer delivers
# slabs strictly in order, and the slab schedule is a deterministic
# function of the source geometry), and multihost.barrier() takes this
# lock so a checkpoint rendezvous cannot interleave with a concurrent
# tenant's enqueue within the process.  Running MULTIPLE tenants with
# cross-host collectives concurrently would need a cross-process order
# agreement on top — not provided yet (ROADMAP item 2 remainder).
_ORDER_LOCK = _lockdep.rlock("engine.order")


def order_lock():
    """The process-wide dispatch-order lock, for the few seams outside
    this module that enqueue collective programs of their own
    (``multihost.barrier``'s rendezvous) — taking it keeps every
    per-device queue observing ONE program order per process."""
    return _ORDER_LOCK


# ---------------------------------------------------------------------
# dispatch-schedule digest (the cross-process order verifier's feed)
# ---------------------------------------------------------------------
#
# The order lock serialises enqueues WITHIN a process; across processes
# nothing checks that every pod member enqueued the SAME programs in
# the SAME order — the divergence class behind ROADMAP item 3's
# remaining gap, and it surfaces as a gloo collective hang, the worst
# possible error message.  So the engine keeps a rolling digest of the
# enqueue schedule: under the order lock, every executable enqueue
# folds its program key (address-stabilised repr — `<function f at
# 0x..>` varies per process, the qualified name does not) into a
# sha256 chain.  `multihost.verify_schedule()` exchanges the digest at
# a rendezvous and turns any divergence into a pointed error naming
# the first divergent program instead of a hang.

_SCHED_DIGEST = hashlib.sha256(b"bolt-schedule").hexdigest()
_SCHED_COUNT = 0
_SCHED_RECENT = deque(maxlen=64)      # always-on tail, for error context
_SCHED_LOG = [] if os.environ.get("BOLT_SCHED_LOG", "") == "1" else None


def _stable_key(key):
    """Cross-process-stable rendering of a program key: repr with CPython
    object addresses stripped (function/method/partial reprs embed
    them; everything else in a key — shapes, dtypes, mesh geometry —
    reprs identically on every process running the same program)."""
    return re.sub(r" at 0x[0-9a-fA-F]+", "", repr(key))


def _schedule_note(key):
    """Fold one enqueue into the schedule digest.  Caller holds
    _ORDER_LOCK — the digest order IS the enqueue order."""
    global _SCHED_DIGEST, _SCHED_COUNT
    text = _stable_key(key)
    _SCHED_DIGEST = hashlib.sha256(
        (_SCHED_DIGEST + "|" + text).encode()).hexdigest()
    _SCHED_COUNT += 1
    _SCHED_RECENT.append(text)
    if _SCHED_LOG is not None:
        _SCHED_LOG.append(text)


def schedule_digest():
    """``(count, hexdigest)`` of this process's enqueue schedule so far
    (consistent: read under the order lock)."""
    with _ORDER_LOCK:
        return _SCHED_COUNT, _SCHED_DIGEST


def schedule_recent():
    """The last few (<= 64) stabilised program keys enqueued — the
    always-on context a divergence error quotes."""
    with _ORDER_LOCK:
        return list(_SCHED_RECENT)


def schedule_log():
    """The FULL ordered key log, or ``None`` unless armed
    (:func:`schedule_log_arm` / ``BOLT_SCHED_LOG=1`` — the multihost
    harness arms it so a divergence names the exact first divergent
    key, not just the digest mismatch)."""
    with _ORDER_LOCK:
        return None if _SCHED_LOG is None else list(_SCHED_LOG)


def schedule_log_arm(on=True):
    """Arm (or drop) full schedule-key logging."""
    global _SCHED_LOG
    with _ORDER_LOCK:
        _SCHED_LOG = [] if on else None


def schedule_reset():
    """Reset digest, count and logs (tests; NOT for pod runs — peers
    must reset at the same schedule point or digests diverge)."""
    global _SCHED_DIGEST, _SCHED_COUNT
    with _ORDER_LOCK:
        _SCHED_DIGEST = hashlib.sha256(b"bolt-schedule").hexdigest()
        _SCHED_COUNT = 0
        _SCHED_RECENT.clear()
        if _SCHED_LOG is not None:
            del _SCHED_LOG[:]


def _leaf_sig(x):
    """Signature of one argument leaf: enough to pick a compiled
    executable — aval (shape/dtype) plus sharding for device arrays,
    shape/dtype for host arrays, the Python type for scalars (weak-type
    avals differ by type, and ``0 == 0.0`` would collide under equality
    hashing)."""
    if isinstance(x, jax.Array):
        return ("j", x.shape, str(x.dtype), x.sharding)
    shape = getattr(x, "shape", None)
    if shape is not None:
        return ("h", tuple(shape), str(getattr(x, "dtype", "")))
    return ("s", type(x))


class _Dispatch:
    """The callable ``get`` returns: routes a call to the per-signature
    compiled executable, lowering+compiling (counted) on first sight of a
    signature; falls back to plain jit dispatch for argument structures
    the AOT path cannot serve (and counts the fallback)."""

    __slots__ = ("jitted", "compiled", "key", "_compile_lock",
                 "gram_kernel")

    def __init__(self, jitted, key=None):
        self.jitted = jitted
        self.compiled = {}           # signature -> compiled executable
        self.gram_kernel = False     # a lowering of this entry placed
        #                              the packed_gram kernel
        self.key = key               # engine cache key: what the
        #                              schedule digest folds per enqueue
        # serialises the per-signature lower+compile: N tenants racing
        # the same signature must produce ONE aot compile (the losers
        # wait and count coalesced_compiles), not N identical XLA runs
        self._compile_lock = _lockdep.lock("engine.compile")

    def lower(self, *args, **kwargs):
        """Delegate to the wrapped jitted callable so cached entries stay
        inspectable (``entry.lower(x).compile().as_text()`` — the
        HLO-contract tests read collectives out of cached programs)."""
        return self.jitted.lower(*args, **kwargs)

    def __call__(self, *args):
        _lockdep.note_dispatch()     # armed witness: no ranked lock may
        #                              be held across a dispatch (the
        #                              held-lock-across-collective
        #                              hazard; DISPATCH_SAFE excepted)
        sp = _obs.begin("engine.dispatch")
        if sp is not None:
            sp.set(family=_family(self.key))
        t0 = _clock()
        try:
            out = self._dispatch(args)
        finally:
            dt = _clock() - t0
            _COUNTERS.update(dispatches=1, dispatch_seconds=dt)
            _obs.end(sp)
        return out

    def _enqueue(self, fn, args):
        """Hand ``fn(*args)`` to the device queues, in the process-wide
        program order (the order lock; the schedule digest folds the key
        here)."""
        with _ORDER_LOCK:
            _schedule_note(self.key)
            sp = _obs.begin("engine.enqueue")
            try:
                return fn(*args)
            finally:
                _obs.end(sp)

    def _dispatch(self, args):
        sp = _obs.begin("engine.signature")
        try:
            leaves, treedef = jax.tree_util.tree_flatten(args)
            sig = (treedef, tuple(_leaf_sig(x) for x in leaves))
        except Exception:
            sig = None
        finally:
            _obs.end(sp)
        if sig is not None:
            fn = self.compiled.get(sig)
            if fn is None:
                with self._compile_lock:
                    # a concurrent identical dispatch may have compiled
                    # while this one waited for the lock: join its
                    # executable instead of running XLA again — the
                    # cross-tenant ONE-compile guarantee
                    fn = self.compiled.get(sig)
                    if fn is not None:
                        _COUNTERS.add("coalesced_compiles")
                    else:
                        family = _family(self.key)
                        before = (_TALLY.hits, _TALLY.misses, _TALLY.read)
                        try:
                            lsp = _obs.begin("engine.lower", family=family)
                            try:
                                t0 = _clock()
                                placed = gram_kernel_lowerings()
                                lowered = self.jitted.lower(*args)
                                if gram_kernel_lowerings() != placed:
                                    self.gram_kernel = True
                                t1 = _clock()
                            finally:
                                _obs.end(lsp)
                            csp = _obs.begin("engine.compile",
                                             family=family)
                            try:
                                fn = lowered.compile()
                                t2 = _clock()
                                row = _log_compile(self.key, sig, t0, t1,
                                                   t2, before)
                                if csp is not None:
                                    csp.set(cache=row["cache"])
                            finally:
                                _obs.end(csp)
                            _COUNTERS.update(aot_compiles=1,
                                             lower_seconds=t1 - t0,
                                             compile_seconds=t2 - t1)
                            self.compiled[sig] = fn
                        except Exception:
                            fn = None
            if fn is not None:
                try:
                    return self._enqueue(fn, args)
                except (TypeError, ValueError):
                    # argument-validation drift the leaf model missed
                    # (layouts, committed-device nuances) — raised BEFORE
                    # execution, so inputs (donated ones included) are
                    # intact and the jitted path below is safe.  Genuine
                    # runtime failures (XlaRuntimeError: OOM, nan checks,
                    # asserts) propagate — re-running them would double
                    # work and bury the real error.
                    pass
        _COUNTERS.add("fallbacks")
        # NOTE: a COLD fallback traces+compiles inside jit's first call,
        # i.e. under the order lock — unavoidable here because plain jit
        # dispatch fuses compile and enqueue.  Fallbacks are rare by
        # construction (a leaf with no signature, such as a tracer when
        # the program is called under somebody's trace; argument-
        # validation drift); the hot AOT path above compiles OUTSIDE
        # the lock.
        return self._enqueue(self.jitted, args)


def _family(key):
    """The op family of an engine key (its first element), as the spans'
    ``family=`` attribute."""
    return str(key[0]) if isinstance(key, tuple) and key else str(key)


def _lookup(key):
    """The keyed lookup of :func:`get`: ``(entry, None)`` on a hit (or
    after waiting out another thread's build of the same key), else
    ``(None, event)`` with this thread owning the build that ``event``
    marks.  Each lookup counts exactly ONCE: hit, miss, or coalesced
    wait."""
    waited = False
    while True:
        with _LOCK:
            entry = _CACHE.get(key)
            if entry is not None:
                if not waited:
                    _COUNTERS.add("hits")
                _CACHE.move_to_end(key)
                return entry, None
            ev = _BUILDING.get(key)
            if ev is None:
                ev = _BUILDING[key] = threading.Event()
                if not waited:
                    _COUNTERS.add("misses")
                return None, ev         # this thread owns the build
            if not waited:
                _COUNTERS.add("coalesced_builds")
                waited = True
        ev.wait()
        # the owner either inserted the entry (the re-check above finds
        # it) or failed (loop again: this thread may become the owner)


def get(key, builder):
    """The engine's dispatch lookup — the drop-in replacement for the old
    per-module ``_cached_jit``: returns a callable executing the program
    ``builder`` describes, compiled at most once per (key, argument
    signature) and shared LRU-style across every op family.

    ``builder`` must return a jitted callable (``jax.jit(f, ...)``) whose
    closure captures only geometry — never arrays (cached entries must
    not pin device memory).  ``key`` must be hashable and must determine
    the traced program (op tag, user funcs, shapes, dtypes, split, mesh,
    precision, donation flag, ...).

    Concurrent misses on the SAME key coalesce: the first caller builds,
    the rest wait on its in-flight marker and adopt the winner's entry
    (counted as ``coalesced_builds``) — so N tenants dispatching an
    identical cold pipeline trace and compile it exactly once.  A failed
    build wakes the waiters, which then build for themselves (the
    original exception propagates to the owner alone)."""
    sp = _obs.begin("engine.lookup")
    entry = None
    try:
        entry, ev = _lookup(key)
    finally:
        if sp is not None:
            _obs.end(sp, family=_family(key), hit=entry is not None)
    if entry is not None:
        return entry
    # build OUTSIDE the lock: builders may trace (slow) and re-enter
    sp = _obs.begin("engine.build")
    if sp is not None:
        sp.set(family=_family(key))
    try:
        entry = _Dispatch(builder(), key=key)
    except BaseException:
        with _LOCK:
            _BUILDING.pop(key, None)
        ev.set()                        # waiters retry (and may rebuild)
        raise
    finally:
        _obs.end(sp)
    with _LOCK:
        # an evict/clear may have raced; insert (or adopt) under the lock
        existing = _CACHE.get(key)
        if existing is not None:
            _CACHE.move_to_end(key)
            entry = existing
        else:
            _CACHE[key] = entry
            if len(_CACHE) > CACHE_MAX:
                _CACHE.popitem(last=False)
        _BUILDING.pop(key, None)
    ev.set()
    return entry


def evict(key):
    """Drop one keyed entry (compile-failure fallbacks memoise around a
    poisoned key)."""
    with _LOCK:
        _CACHE.pop(key, None)
