"""Streaming out-of-core executor: host data larger than device memory,
run slab by slab through one ingest pool.

A lazy :class:`StreamSource` describes host-resident data as a sequence
of record *slabs* (consecutive blocks along the first key axis) plus a
chain of device-side stages (per-record maps, chunked maps, stacked
maps, a filter predicate and the record-wise maps called on it since,
a recorded swap).  Every streamed run is the same four pieces::

    StreamSource -> _Run -> _IngestPool -> consumer -> _Window
                                           fold (a _Terminal) | place | spill

* the **run set-up** (:class:`_Run`) resolves once, on the calling
  thread, what the run is held to: prefetch depth and pool size
  (default ``min(max(mesh devices, 2), 4)``: two copies in flight even
  on ONE device's link; ``BOLT_STREAM_UPLOAD_THREADS`` / the
  :func:`uploaders` scope), the ingest codec, the pod slice, the tenant
  and its arbiter lease, the retry budget;
* the **ingest pool** (:class:`_IngestPool`) turns host blocks into
  uploaded slabs.  For random-access ``fromcallback`` sources each of N
  workers produces AND uploads its own slab (per-device sub-blocks via
  ``parallel.sharding.device_placements``; a slab of THIN records for
  one device as a dense view of its bytes, and a slab of elements
  NARROWER than 32 bits as the 32-bit words it already is, which its
  slab program re-seats: :func:`thin_records`, :func:`narrow_words`), so
  one CPU thread is never
  the bottleneck feeding many chips; sequential ``fromiter`` sources
  keep one produce+upload thread.  A **re-sequencer** (:class:`_Reseq`)
  hands slabs to the consumer strictly in slab order whatever order the
  uploads finish in, so every consumer is deterministic.  Slab buffers
  form a **ring** bounded by a consumer's window and the pool's size
  (:func:`fold_ring`, :func:`swap_ring`: the prefetch depth, a measured
  step, a slab in every worker's hand): a permit
  (and, under ``bolt_tpu.serve``, the slab's wire bytes from the
  device-memory arbiter) is taken per slab in slab order and comes back
  when the consumer says the slab's program retired;
* the **consumer** is the calling thread: it takes a slab, builds its
  program, dispatches it **asynchronously** and pushes the handle on
  its **confirm window** (:class:`_Window`) — no per-slab
  ``block_until_ready``; confirming a call gives its ring permits back,
  and when to block for one is the consumer's policy.  :func:`execute`
  FOLDS a reduction terminal, a VALUE that says what a slab's partial is
  and how two merge (:class:`_Terminal`: a sum, a ``reduce``, moments,
  the tuples of a fused multi-stat group, of :func:`maybe_group` and of
  :func:`maybe_gram`): it blocks only on window overflow, for a pair
  partial dispatched two slab periods back and long done, hands a
  permit back as soon as the head of its window is done, and syncs on
  the final result, so compute and ingest overlap.  Each ring buffer is
  **donated** into its slab program, the **level-0 fold is fused into
  the slab program** (odd slabs run ``prog(buf, acc)``: half the fold
  dispatches), and pair-partials above level 0 combine as a pairwise
  tree.  :func:`_resolve_one_swap` PLACES each slab into a resident
  re-keyed array (a recorded swap, or :func:`collect`'s mapped result;
  :func:`swap_ring`), or SPILLS its buckets, a block a slab, to files
  that stream again as a fresh source (``bolt_tpu.parallel.shuffle``).

The per-slab program applies the SAME traced bodies the materialised
paths compile (``tpu/chunk.py :: _uniform_map_body`` /
``_general_map_body``, ``tpu/stack.py :: _stack_map_body``,
``tpu/array.py :: _chain_apply`` / ``_pred_mask``), so streamed and
materialised results cannot drift semantically — the out-of-core parity
suite (``tests/test_stream.py``) bit-compares them.

Who waits for whom is on the tracer (``bolt_tpu.obs``): the consumer
starved of uploads is ``stream.wait.slab`` (inside the pool's ``next``),
an ingesting thread with no ring permit to work under is
``stream.wait.ring`` (a worker with no job to take: the dispenser hands
one out the moment it holds a permit; an iterator's one thread in front
of its pull), the slab program's call alone is ``stream.dispatch`` and
the block for it ``stream.sync``.

Accounting lands in the engine counters: ``transfer_bytes`` /
``transfer_seconds`` for every counted upload (the link's busy time,
counted once where the pool's copies overlap; each copy's own in
``transfer_copy_seconds``) and the ``stream_*`` family for a run, whose
ingest/compute seconds come from the regions the obs spans cover (pool
``stream.ingest``, consumer ``stream.compute`` + ``stream.sync``), NOT
from wall-clock around a per-slab sync.

Fault model (ISSUE 9 made it three-tiered):

* **fail-fast** (the default): a source callback or pool thread that
  raises mid-stream aborts cleanly — the pool is joined, queued ring
  buffers are released, and the ORIGINAL exception is re-raised to the
  caller.  A pool thread that dies WITHOUT delivering is named by the
  consumer's liveness poll instead of blocking it forever;
* **in-run retry** (``stream.retries(n)`` / ``BOLT_STREAM_RETRIES``): a
  failed slab ingest re-runs in place up to *n* times, fenced through
  the re-sequencer so a late duplicate can never be consumed twice; the
  final error chains every attempt back to the original;
* **resume** (``stream.resumable(dir)`` / a source's ``checkpoint=``;
  :func:`execute` only): every ``BOLT_CHECKPOINT_EVERY`` retired slabs
  the fold drains its window and persists the retired-slab watermark
  plus the folded accumulator (``checkpoint.stream_save``).  A killed
  run restarted over the same source skips the retired slabs, reloads
  the exact fold state, and is BIT-IDENTICAL to the uninterrupted run —
  the fold is a deterministic function of (slab order, accumulator
  state).  A finished run clears its checkpoint; a spilled swap resumes
  from its bucket manifest the same way.  Deterministic fault points
  live in ``bolt_tpu._chaos`` (seams ``stream.upload``, ``.dispatch``,
  ``.fold``, ``.checkpoint``, ``.shuffle``, ``.spill``).

POD SCALE (``bolt_tpu.parallel.multihost``): on a mesh spanning
PROCESSES the same run is N peers over one deterministic slab schedule.
Each process produces and uploads ONLY its own contiguous shard of
every slab (``multihost.local_slab_spec`` — the ``fromcallback(...,
per_process=True)`` contract; ``fromiter`` re-iterable sources slice
their shard out of each global block), the global slab is glued from
local parts with zero cross-host motion, and the slab program runs under
``shard_map`` with the cross-host combine as mesh collectives
(``psum``/``pmin``/``pmax`` for a fold, one ``all_to_all`` for a
re-bucket).  Slabs dispatch in slab order on every process, so the
rendezvous can never cross; uneven slabs refuse (BLT012) before any
thread starts; checkpoints become per-process shard files with a
rendezvous-consistent watermark.  On even splits results stay
bit-identical to the single-process run (tests/test_multihost.py, on a
REAL 2-process ``jax.distributed`` localhost cluster).
"""

import contextlib
import functools
import os
import queue
import sys
import threading
import warnings
from collections import deque

import numpy as np

import jax
import jax.numpy as jnp

from bolt_tpu import _chaos
from bolt_tpu import _lockdep
from bolt_tpu import engine as _engine
from bolt_tpu.obs import trace as _obs
from bolt_tpu.obs.trace import clock as _clock
from bolt_tpu.parallel import multihost as _multihost
from bolt_tpu.parallel import podwatch as _podwatch
from bolt_tpu.utils import chain_retry_step, iter_record_blocks, prod

# ---------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------

# prefetch depth k: how many uploaded slabs may wait ahead of the
# consumer beyond the uploader pool's own hands-on slabs (the ring is
# bounded at depth + a measured step + pool size: fold_ring, swap_ring).
# 2 = classic double buffering: one slab in compute, one in flight.
# Deeper rings only help when per-slab ingest time is noisy (0.3 % on
# the chip: PERF.md section 5, PR 35); they cost one slab of HBM each.
_DEPTH = 2

# uploader pool size: concurrent ingest workers.  0 = auto, resolved per
# run as min(max(mesh device count, _LINK_COPIES), 4) — one host thread
# cannot saturate the link feeding many chips, but past ~4 workers the
# host memory bus is the limit, not thread count.  Sequential (fromiter)
# sources always stream through ONE produce+upload prefetch thread
# regardless.
_UPLOADERS = max(0, int(os.environ.get("BOLT_STREAM_UPLOAD_THREADS",
                                       "0")))

# copies in flight ONE device's link wants: the auto rule's floor, so a
# one-device mesh does not run its link one copy at a time, with none in
# flight while its only worker is in Python between two.  MEASURED, not
# assumed (PERF.md section 5, PR 35; scripts/h2d_probe.py on a v5e host,
# raw device_put + block_until_ready of 64 MiB views, GB/s in aggregate,
# medians of six): 1 in flight 9.04, 2: 14.07, 3: 14.09, 4: 14.10 (128
# MiB: 9.44, 14.03, 13.98, 14.05; one thread keeping N puts issued reads
# the same as N threads).  2 is the smallest count within 2 % of the best.
_LINK_COPIES = 2

# place calls the swap / collect resolver keeps dispatched and
# unconfirmed BEYOND the prefetch depth (swap_ring grows by as many, so
# the window and the ring move together and plan_shuffle counts them).
# A place call is done about one slab period after its dispatch, because
# it starts behind the uploads in flight when it was issued (8.3 ms of a
# 9.5 ms period in `toseries`): at a window of 2 the consumer confirms a
# call that is only just finishing, at 3 one long done.  MEASURED
# (PERF.md section 5, PR 56; scripts/swap_window_probe.py on a v5e host,
# GB/s of a request at windows 1 / 2 / 3 / 4, two rounds): `toseries`
# 10.9-11.3 / 13.93-13.96 / 13.97 / 13.97, the link's own rate from 2
# on, with the consumer blocked 0.87 / 0.004 of the pass at 2 / 3;
# `register`, whose `fit` pass has 3.3 ms of device work a slab,
# 8.9-10.6 / 11.8-12.6 / 13.71 / 13.5-13.7; a permit is one slab of HBM
# (134 MB there).  One step is the smallest that leaves no pass short.
_SWAP_WINDOW_STEP = 1

# slabs `execute` keeps dispatched and unconfirmed BEYOND the prefetch
# depth (fold_ring grows by as many: the pool's ring, the window and
# analysis.check's price move together).  The same cause as above: a
# slab program is done about one slab period after its dispatch, so at
# a window of 2 the consumer blocks on a pair it dispatched ONE slab ago
# and at 3 the head is still only just finishing when it is asked.
# MEASURED (PERF.md section 5, PR 58; scripts/stream_depth_probe.py and
# scripts/swap_window_probe.py on a v5e host, seconds a pass at windows
# 2 / 3 / 4 / 5 with a done head let go at once, three passes each, the
# link's own 1.194): `scan_q1q6`'s Q1, whose program of thin records
# retires latest, 1.58-1.63 / 1.249-1.263 / 1.201-1.204 / 1.199-1.200
# (confirmed only by a block: 1.51-1.52 / 1.259-1.266 / 1.205-1.213 /
# 1.235-1.247), its heads found done 124 / 44-60 / 208-236 / 248 of 251
# slabs; Q6 1.194-1.198 at every window; `scan_pca`'s Gram pass 1.46 /
# 1.230 / 1.231 / 1.234 (the link's 1.230); `stack4d-1chip.stream`
# 1.524-1.526 throughout.  Two steps is the smallest that leaves no pass
# more than 2 % short of the link (Q1 is 4.6 % short at one); a permit
# is one slab of HBM (64 MiB there, 268 MB in `scan_pca`).
_FOLD_WINDOW_STEP = 2

# the prefetch()/uploaders() SCOPES are thread-local (like
# engine.donation and bolt.precision): under the multi-tenant serving
# layer (bolt_tpu.serve) concurrent streams run on different threads,
# and one tenant's `with uploaders(8)` must not inflate a neighbour's
# pool mid-run.  set_prefetch_depth/set_upload_threads change the
# PROCESS-WIDE default the scopes override.
_SCOPE_TLS = threading.local()


def _scope_stack(name):
    st = getattr(_SCOPE_TLS, name, None)
    if st is None:
        st = []
        setattr(_SCOPE_TLS, name, st)
    return st

# default slab budget when the caller gives no explicit record count:
# big enough to amortise per-dispatch overhead, small enough that
# a ring of slabs stays far below any device's HBM (64 and 128 MiB read
# alike on the chip: PERF.md section 5, PR 35)
_SLAB_BYTES = 64 << 20


def prefetch_depth():
    """The active prefetch (ring) depth for the CALLING THREAD: the
    innermost :func:`prefetch` scope on this thread, else the
    process-wide default."""
    st = _scope_stack("depth")
    if st:
        return st[-1]
    return _DEPTH


def set_prefetch_depth(k):
    """Set the process-wide DEFAULT prefetch depth (ring size), >= 1;
    per-thread :func:`prefetch` scopes override it."""
    global _DEPTH
    _DEPTH = max(1, int(k))


@contextlib.contextmanager
def prefetch(depth):
    """Scope the prefetch depth::

        with bolt_tpu.stream.prefetch(4):
            big.chunk().map(f).mean()

    The scope is THREAD-LOCAL: a concurrent stream on another thread
    (another serve tenant) keeps its own value — one tenant's deep ring
    must not silently multiply a neighbour's device-memory footprint."""
    st = _scope_stack("depth")
    st.append(max(1, int(depth)))
    try:
        yield
    finally:
        st.pop()


def upload_threads():
    """The configured uploader-pool size for the calling thread
    (innermost :func:`uploaders` scope, else the process default;
    0 = auto: resolved per run by :func:`pool_size`)."""
    st = _scope_stack("uploaders")
    if st:
        return st[-1]
    return _UPLOADERS


def set_upload_threads(n):
    """Set the process-wide DEFAULT uploader-pool size (0 restores
    auto, :func:`pool_size`'s ``min(max(mesh devices, 2), 4)``);
    per-thread :func:`uploaders` scopes override it."""
    global _UPLOADERS
    _UPLOADERS = max(0, int(n))


@contextlib.contextmanager
def uploaders(n):
    """Scope the uploader-pool size (``0`` = auto, like
    :func:`set_upload_threads`)::

        with bolt_tpu.stream.uploaders(8):
            src.map(f).sum()

    THREAD-LOCAL, like :func:`prefetch` — concurrent streams on other
    threads resolve their own scopes (regression-locked in
    tests/test_stream.py)."""
    st = _scope_stack("uploaders")
    st.append(max(0, int(n)))
    try:
        yield
    finally:
        st.pop()


# in-run retry budget per slab: 0 = fail-fast (today's behavior), n = a
# failed slab ingest re-attempts up to n times before poisoning the run
_RETRIES = max(0, int(os.environ.get("BOLT_STREAM_RETRIES", "0")))

# checkpoint cadence under resumable(): persist the fold state every k
# retired slabs.  Each write drains the async window and pulls the
# (value-shaped, small) partials to host — frequent checkpoints buy a
# tighter resume point at a per-write pipeline stall.
_CKPT_EVERY = max(1, int(os.environ.get("BOLT_CHECKPOINT_EVERY", "2")))


def retry_limit():
    """The active per-slab retry budget for the calling thread
    (innermost :func:`retries` scope, else the process default;
    0 = fail-fast)."""
    st = _scope_stack("retries")
    if st:
        return st[-1]
    return _RETRIES


def set_retries(n):
    """Set the process-wide DEFAULT per-slab retry budget; per-thread
    :func:`retries` scopes override it."""
    global _RETRIES
    _RETRIES = max(0, int(n))


@contextlib.contextmanager
def retries(n):
    """Scope the per-slab ingest retry budget::

        with bolt_tpu.stream.retries(2):
            flaky_src.map(f).sum()       # each slab survives 2 failures

    THREAD-LOCAL like :func:`prefetch`/:func:`uploaders`: a serve
    tenant's retry policy must not leak into a neighbour's run."""
    st = _scope_stack("retries")
    st.append(max(0, int(n)))
    try:
        yield
    finally:
        st.pop()


# codec-encoded ingest (ISSUE 14, bolt_tpu/tpu/codec.py): the process
# default codec NAME the thread-local codec() scopes override; None =
# uncompressed.  Lazily validated against the registry so merely
# importing stream never touches the codec module.
_CODEC = os.environ.get("BOLT_STREAM_CODEC") or None


def _codec_registry():
    from bolt_tpu.tpu import codec as m
    return m


def current_codec():
    """The calling thread's effective codec NAME (innermost
    :func:`codec` scope, else the process default; ``None`` =
    uncompressed).  A source's own ``codec=`` always wins over this —
    see :func:`resolve_codec`."""
    st = _scope_stack("codec")
    if st:
        return st[-1]
    return _CODEC


def set_codec(name):
    """Set the process-wide DEFAULT ingest codec (``None`` restores
    uncompressed; ``BOLT_STREAM_CODEC`` seeds it); per-thread
    :func:`codec` scopes override it."""
    global _CODEC
    if name is not None:
        _codec_registry().get(name)     # pointed unknown-codec error NOW
    _CODEC = name


@contextlib.contextmanager
def codec(name):
    """Scope codec-encoded ingest for streamed runs::

        with bolt_tpu.stream.codec("bf16"):
            src.map(f).sum()     # slabs ship at half the bytes; the
                                 # slab program decodes on device

    ``codec(None)`` restores uncompressed ingest inside the scope.
    THREAD-LOCAL with the same stack discipline as :func:`uploaders` /
    :func:`prefetch`: one serve tenant's lossy opt-in must never
    silently quantise a neighbour's stream — and ``serve.submit``
    captures the SUBMITTER's effective codec and re-enters it on the
    worker thread, so a scope wrapped around a submit is honoured by
    the job (and priced by admission) rather than dropped at the
    thread boundary.  A per-source
    ``fromcallback(..., codec=)`` / ``fromiter(..., codec=)`` takes
    precedence over the scope (mirroring ``checkpoint=``).  The
    accuracy contract lives with the registry
    (:mod:`bolt_tpu.tpu.codec`): lossless ``"delta-f32"`` is
    bit-identical to uncompressed streaming; lossy codecs are refused
    for order-statistic terminals and non-float pipelines."""
    if name is not None:
        _codec_registry().get(name)     # validate at scope entry
    st = _scope_stack("codec")
    st.append(name)
    try:
        yield
    finally:
        st.pop()


def resolve_codec(source):
    """The effective :class:`~bolt_tpu.tpu.codec.Codec` for a run over
    ``source`` — the source's own ``codec=`` wins over the calling
    thread's scope/default; ``None`` = uncompressed.  Validates the
    codec against the source dtype (the pointed integer/bool-pipeline
    refusal lives in ``Codec.wire_dtype``)."""
    name = source.codec if source.codec is not None else current_codec()
    if name is None:
        return None
    c = _codec_registry().get(name)
    c.wire_dtype(source.dtype)
    return c


def checkpoint_scope():
    """The calling thread's innermost :func:`resumable` scope as
    ``(dir, every)``, or ``None`` when streaming is not resumable."""
    st = _scope_stack("ckpt")
    return st[-1] if st else None


@contextlib.contextmanager
def resumable(dir, every=None):
    """Scope slab-level checkpointing for streamed runs::

        with bolt_tpu.stream.resumable("/ckpt/run17"):
            src.map(f).sum()     # killed?  re-run resumes from the last
                                 # retired slab, bit-identically

    ``every`` is the checkpoint cadence in retired slabs (default
    ``BOLT_CHECKPOINT_EVERY``, 2).  THREAD-LOCAL; a per-source
    ``checkpoint=dir`` (``fromcallback``/``fromiter``) takes precedence
    over the scope.  One-shot iterator sources cannot be resumed (the
    iterator dies with the process) — ``analysis.check`` flags that
    shape as BLT011."""
    st = _scope_stack("ckpt")
    st.append((os.fspath(dir),
               max(1, int(every)) if every is not None else _CKPT_EVERY))
    try:
        yield
    finally:
        st.pop()


# out-of-core shuffle spill (ISSUE 18): the process default spill
# directory for streamed-swap resolutions whose output exceeds the
# resident budget; None = no spill dir (a spill-forecast resolution
# then refuses pointedly — BLT017 warns ahead of time).
_SPILL_DIR = os.environ.get("BOLT_STREAM_SPILL_DIR") or None


@contextlib.contextmanager
def spill(dir=None, budget=None):
    """Scope the out-of-core shuffle's spill policy::

        with bolt_tpu.stream.spill("/scratch/shuffle", budget=1 << 30):
            big.swap([1], [0]).sum()   # re-keyed buckets larger than
                                       # 1 GiB spill to encoded files

    ``dir`` is where spilled bucket files land (``None`` keeps the
    ``BOLT_STREAM_SPILL_DIR`` default); ``budget`` caps the RESIDENT
    working set in bytes: the swapped array plus the slabs in flight
    (``None`` defers to the serving arbiter's budget, else to the
    device's own free memory: :func:`swap_budget`).  THREAD-LOCAL with
    the same stack
    discipline as :func:`codec`/:func:`resumable`: one serve tenant's
    spill policy must not redirect a neighbour's bucket files."""
    st = _scope_stack("spill")
    st.append((os.fspath(dir) if dir is not None else None,
               int(budget) if budget is not None else None))
    try:
        yield
    finally:
        st.pop()


def spill_scope():
    """The calling thread's innermost :func:`spill` scope as
    ``(dir, budget)`` — dir falling back to ``BOLT_STREAM_SPILL_DIR``,
    budget ``None`` when unset."""
    st = _scope_stack("spill")
    if st:
        d, b = st[-1]
        return (d if d is not None else _SPILL_DIR), b
    return _SPILL_DIR, None


def _device_headroom(mesh=None):
    """Free device memory the platform reports for ``mesh`` (default:
    this process's devices), in bytes over the WHOLE mesh: the tightest
    addressable device's ``bytes_limit - bytes_in_use`` times the
    device count (key sharding spreads a resident array evenly).
    ``None`` where the platform reports no limit (the CPU backend)."""
    devs = list(mesh.devices.flat) if mesh is not None \
        else jax.local_devices()
    free = []
    for d in devs:
        if d.process_index != _multihost.process_index():
            continue
        stats = d.memory_stats() or {}
        if "bytes_limit" not in stats:
            return None
        free.append(int(stats["bytes_limit"])
                    - int(stats.get("bytes_in_use", 0)))
    if not free:
        return None
    return max(0, min(free)) * len(devs)


def swap_budget(mesh=None):
    """The resident-working-set ceiling a streamed-swap resolution
    plans against: the innermost :func:`spill` scope's explicit
    ``budget``, else the ACTIVE serving arbiter's device budget, else
    what the device itself has free (:func:`_device_headroom` over
    ``mesh``), else ``None`` (a platform that reports no limit:
    unbounded — always resident).  The checker's BLT017 forecast calls
    this same function, so the forecast and the measured resident/spill
    decision cannot drift."""
    _, b = spill_scope()
    if b is not None:
        return b
    sv = sys.modules.get("bolt_tpu.serve")
    arb = sv.device_arbiter() if sv is not None else None
    if arb is not None:
        return int(arb.budget)
    return _device_headroom(mesh)


def place_budget(source):
    """:func:`swap_budget` as the resident leg over ``source`` can spend
    it: less the one thing its place program holds that the plan does not
    count, the re-seat's copies of a slab that went up dense
    (:func:`dense_route`; two: rows of 64 are re-seated through two
    copies of the slab, compiled for the v5e, rows of seven through
    one, and the words of narrower elements are unpacked inside the
    update's fusion beside four bytes an element of temporaries: two
    slabs of 16-bit frames, four of 8-bit ones)."""
    budget = swap_budget(source.mesh)
    if budget is not None and dense_route(source):
        budget -= max(2, 4 // source.dtype.itemsize) \
            * _raw_slab_bytes(source)
    return budget


def _ring(source, step):
    """A window of the prefetch depth and ``step`` more, plus one slab
    in the hand of every worker of the uploader pool; ``prefetch(1)``
    takes no step: it stays one call at a time."""
    depth = prefetch_depth()
    if depth > 1:
        depth += step
    return depth + pool_size(source)


def swap_ring(source):
    """Uploaded slabs a streamed-swap resolution over ``source`` keeps
    on the device: the resolver's window of dispatched, unconfirmed
    place calls (a slab's permit goes back when its call is confirmed)
    plus one in the hand of every worker of the uploader pool — the ring
    the resolver's permits bound, and what ``plan_shuffle`` counts beside
    the output.  The window is the prefetch depth and
    ``_SWAP_WINDOW_STEP`` more; ``prefetch(1)`` stays one call at a
    time."""
    return _ring(source, _SWAP_WINDOW_STEP)


def fold_ring(source):
    """Uploaded slabs a folded run (:func:`execute`) over ``source``
    keeps on the device: its window of unconfirmed slab programs plus
    one in the hand of every pool worker — the ring its permits bound,
    and what ``analysis.check`` prices.  The window is the prefetch
    depth and ``_FOLD_WINDOW_STEP`` more; ``prefetch(1)`` stays one
    slab program at a time (a pair's: the even slab's partial is fused
    into the odd slab's program)."""
    return _ring(source, _FOLD_WINDOW_STEP)


def pool_size(source):
    """The uploader-pool size a run over ``source`` will use: the
    calling thread's configured count (scope/env), else ``min(max(mesh
    devices, _LINK_COPIES), 4)``: a worker a device as before, and never
    fewer than the copies one link wants in flight; sequential
    ``fromiter`` sources always use ONE prefetch thread (their iterator
    cannot be consumed concurrently)."""
    if source.kind != "callback":
        return 1
    n = upload_threads()
    if n >= 1:
        return n
    ndev = int(source.mesh.devices.size) if source.mesh is not None else 1
    return min(max(ndev, _LINK_COPIES), 4)


def _cached_jit(key, builder):
    """Engine-routed executable dispatch (same contract as the op
    modules'; ``bolt_tpu.profile.instrument`` patches this name)."""
    return _engine.get(key, builder)


# ---------------------------------------------------------------------
# the counted transfer layer (lint rule BLT105: the only raw
# jax.device_put in the package lives here)
# ---------------------------------------------------------------------

def transfer(x, sharding=None, wait=False):
    """Counted data placement: ``jax.device_put`` with engine accounting.

    Host sources (anything that is not already a ``jax.Array``) tally
    their bytes into the engine's ``transfer_bytes``/``transfer_seconds``
    counters; device-resident inputs (resharding — an ICI exchange, not
    host traffic) pass through uncounted.  EVERY counted upload blocks
    until the buffer lands before its seconds are recorded — otherwise
    ``transfer_seconds`` would tally async-dispatch time against the
    full payload's bytes and report impossible GB/s (``wait`` is kept
    for call-site documentation; the prefetch thread's blocking is the
    point there — it is off the critical path, and host ``device_put``
    is a synchronous copy in practice everywhere else)."""
    host = not isinstance(x, jax.Array)
    sp = _obs.begin("stream.transfer") if host else None
    t0 = _clock()
    try:
        out = jax.device_put(x, sharding) if sharding is not None \
            else jax.device_put(x)
        if host:
            out.block_until_ready()
            if not hasattr(x, "nbytes"):
                x = np.asarray(x)
            nbytes = int(x.nbytes)
            _engine.record_transfer(nbytes, _clock() - t0,
                                    elements=int(x.size))
            if sp is not None:
                sp.set(bytes=nbytes, wait=wait, dtype=str(x.dtype))
    finally:
        _obs.end(sp)
    return out


_LANES = 128        # the lanes of the chip's (8, 128) tile of 32-bit words


def thin_records(shape, dtype):
    """Whether slabs of a source of ``shape``/``dtype`` go up DENSE: the
    last axis of 32-bit elements whose extent pads by two or more under
    the 128 lanes of the chip's tile (``c <= 64``), the records either
    such rows themselves (``(n, c)``, one key axis) or PLANES of them
    (``(K, N, c)`` with ``N`` whole groups of 128 rows).  The device holds
    such a slab with the rows on the lanes (``f32[n,7]{0,1:T(8,128)}``,
    ``f32[1,1048576,64]{1,2,0:T(8,128)}``), and the runtime re-tiles the
    loader's row-major block on the HOST on its way there: at two thirds
    of what the link carries for rows of seven (8.9 against 13.8 GB/s
    with two copies in flight: PERF.md section 6, PR 51), at nine tenths
    for rows of 64 (12.6 against 13.8, PR 55), and under the profiler an
    event a tile, which a traced pass of 17 GB does not survive.  The
    same bytes as rows of whole lanes pad nothing and go up as fat
    records do; the slab program gives them their shape on the device
    (:func:`_reseat`).  The rule reads the record alone: nothing a caller
    sets."""
    if len(shape) not in (2, 3) or np.dtype(dtype).itemsize != 4 \
            or 2 * int(shape[-1]) > _LANES:
        return False
    return len(shape) == 2 or int(shape[1]) % _LANES == 0


def narrow_words(shape, dtype):
    """Whether slabs of a source of ``shape``/``dtype`` go up as the
    32-bit WORDS they already are on the host: integer elements of one or
    two bytes (a camera's ``uint16`` words, ``int16``, ``uint8``) whose
    rows, the last axis, are whole words.  The device tiles such an array
    with PAIRS (or fours) of rows packed into one 32-bit sublane
    (``u16[128,512,512]{2,1,0:T(8,128)(2,1)}``), which no row-major host
    block is, and the runtime interleaves the loader's block on the HOST
    on its way there: 9.5 GB/s with two copies in flight for ``(128, 512,
    512)`` ``uint16`` (5.1 with one; ``uint8`` the same) where the same
    bytes as a ``uint32`` view, or as float32, go up at 13.98
    (``scripts/narrow_h2d_probe.py``: PERF.md section 6, PR 59).  The
    words pad nothing and need no host pass; the slab or place program
    unpacks them on the device (:func:`_reseat`).  The rule reads the
    record alone: nothing a caller sets."""
    dtype = np.dtype(dtype)
    return (dtype.kind in "iu" and dtype.itemsize < 4 and len(shape) >= 2
            and int(shape[-1]) * dtype.itemsize % 4 == 0)


def dense_route(source):
    """Whether ``source``'s slabs go up as :func:`_dense_views` of them
    and are re-seated by their slab or place program: thin records, or
    elements narrower than 32 bits in rows of whole words, for ONE device
    (a codec's wire form and a pod's shards keep what they had)."""
    return ((thin_records(source.shape, source.dtype)
             or narrow_words(source.shape, source.dtype))
            and source.mesh.devices.size == 1
            and _multihost.mesh_process_count(source.mesh) <= 1
            and resolve_codec(source) is None)


def _goes_dense(block):
    """Whether THIS block of a :func:`dense_route` source can be viewed
    dense: C-contiguous, and of thin rows at least one group of 128."""
    if not block.flags.c_contiguous:
        return False
    return (block.dtype.itemsize < 4 or block.ndim == 3
            or block.shape[0] >= _LANES)


def _dense_views(block):
    """A C-contiguous slab that goes up dense as zero-copy views that pad
    nothing.  Elements narrower than 32 bits (:func:`narrow_words`): the
    block as ``uint32`` words, the last axis a half or a quarter as long.
    Thin rows ``(n, c)``: the whole groups of 128 rows as ``(n // 128,
    128 * c)``, then the rows past them as they are (under 128 of them:
    once a slab, a few KB).  Planes ``(k, N, c)``: every plane's groups,
    ``(k, N // 128, 128 * c)``."""
    if block.dtype.itemsize < 4:
        return [block.view(np.uint32)]
    if block.ndim == 3:
        k, n, c = block.shape
        return [block.reshape(k, n // _LANES, _LANES * c)]
    n, c = block.shape
    whole = n // _LANES * _LANES
    views = [block[:whole].reshape(whole // _LANES, _LANES * c)]
    if whole < n:
        views.append(block[whole:])
    return views


def _dense_shape(parts, dtype=None):
    """The shape of the slab of ``dtype`` (default: the parts' own) that
    :func:`_dense_views`' uploads ``parts`` are the bytes of."""
    dense = parts[0]
    if dtype is not None and dense.dtype != dtype:  # a narrower one's words
        return dense.shape[:-1] + (
            dense.shape[-1] * 4 // np.dtype(dtype).itemsize,)
    if dense.ndim == 3:
        return (dense.shape[0], dense.shape[1] * _LANES,
                dense.shape[2] // _LANES)
    return (sum(b.shape[0] for b in parts[1:]) + dense.shape[0] * _LANES,
            dense.shape[1] // _LANES)


def _reseat(parts, dtype=None):
    """The slab of ``dtype`` (default: the parts' own) of
    :func:`_dense_views`' uploads, traced as the slab program's first
    operation: ONE copy of a slab on the device
    (0.7 ms of 64 MiB of rows of seven, 2.5 ms of a 268 MB plane, beside
    4.9 and 21 ms of link; a plain ``reshape`` to the slab's shape goes
    through the row-major tiled form, the row padded to 128 lanes, and
    costs GBs of temp: compiled for the v5e, ISSUEs 51 and 55).  The
    words of narrower elements are unpacked (``bitcast_convert_type``:
    the low bits first, as the host holds them) and given their row back;
    in front of a re-axis XLA transposes the WORDS and unpacks inside the
    update's fusion (compiled for the v5e, ISSUE 59)."""
    dense = parts[0]
    if dtype is not None and dense.dtype != dtype:
        with jax.named_scope("narrow_unpack"):
            return jax.lax.bitcast_convert_type(dense, dtype).reshape(
                _dense_shape(parts, dtype))
    if dense.ndim == 3:
        k, groups, c = dense.shape[0], dense.shape[1], \
            dense.shape[2] // _LANES
        return jnp.swapaxes(
            dense.reshape(k, groups, _LANES, c).transpose(0, 3, 1, 2)
            .reshape(k, c, groups * _LANES), -1, -2)
    groups, c = dense.shape[0], dense.shape[1] // _LANES
    x = dense.reshape(groups, _LANES, c).transpose(2, 0, 1).reshape(
        c, groups * _LANES).T
    if len(parts) > 1:
        x = jnp.concatenate([x, parts[1]], axis=0)
    return x


def _upload_slab(block, mesh, split, dense=False):
    """Upload ONE host slab as its per-device sub-blocks and assemble
    the global sharded array — the uploader-pool hot path.

    Per-device placement (``parallel.sharding.device_placements``) keeps
    each worker's uploads independent: N workers each ``device_put``
    their own slab's sub-blocks concurrently, with no shared whole-slab
    placement call serialising them.  Counted ONCE per slab (logical
    host bytes, like :func:`transfer` — replication is a placement
    detail, not payload), and every sub-block is blocked on before the
    seconds are recorded, so ``transfer_seconds`` stays honest.  The
    degenerate case of :func:`_upload_slab_mh` — the local range is the
    whole slab.  ``dense``: a slab of thin records for ONE device goes up
    as :func:`_dense_views` of it, a tuple the slab program re-seats."""
    return _upload_slab_mh(block, mesh, split, block.shape, 0, dense)


def _upload_slab_mh(block, mesh, split, slab_shape, axis0_off, dense=False):
    """Upload THIS PROCESS's sub-block of one slab and assemble the
    global sharded array — the ONE uploader hot path (single-process
    through :func:`_upload_slab`, pod-scale directly under the
    ``bolt_tpu.parallel.multihost`` per-process contract).

    ``block`` holds this process's contiguous record range of a slab of
    ``slab_shape`` (the whole slab single-process); ``axis0_off`` is
    that range's offset within the slab.  Parts are placed on the
    process's ADDRESSABLE devices only (the index map never names
    remote devices), and the global array is glued with
    ``make_array_from_single_device_arrays`` — no cross-host data
    motion happens at ingest; the cross-host combine is the slab
    program's mesh collective.  Counted at the LOCAL bytes, so
    ``transfer_bytes``/GB-per-second report each process's own link."""
    from bolt_tpu.parallel import sharding as _sh
    _chaos.hit("stream.upload")
    sp = _obs.begin("stream.transfer")
    t0 = _clock()
    try:
        sharding, placements = _sh.device_placements(mesh, slab_shape,
                                                     split)
        if dense:
            (dev, _), = placements          # one device holds the slab
            out = tuple(jax.device_put(v, dev) for v in _dense_views(block))
            jax.block_until_ready(out)
            nparts = 1
        else:
            parts = []
            for dev, index in placements:
                lo0, hi0, _ = index[0].indices(slab_shape[0])
                local = (slice(lo0 - axis0_off, hi0 - axis0_off),) \
                    + tuple(index[1:])
                parts.append(jax.device_put(block[local], dev))
            for p in parts:
                p.block_until_ready()
            out = _sh.assemble_from_parts(slab_shape, sharding, parts)
            nparts = len(parts)
        nbytes = int(block.nbytes)
        _engine.record_transfer(nbytes, _clock() - t0, parts=nparts,
                                elements=int(block.size))
        if sp is not None:
            sp.set(bytes=nbytes, parts=nparts, dtype=str(block.dtype))
    finally:
        _obs.end(sp)
    return out


def _encode_slab(codec_obj, block, delta_ok):
    """Host-side slab ENCODE on an uploader worker (ISSUE 14): the
    ``stream.encode`` chaos seam and obs span (``bytes_raw`` /
    ``bytes_wire`` attrs, nesting under the worker's ``stream.ingest``
    span) plus the ``codec_*`` engine counters all live here.  Encode
    runs per worker, so N workers encode N slabs concurrently — the
    encode cost rides inside the already-overlapped ingest phase."""
    _chaos.hit("stream.encode")
    sp = _obs.begin("stream.encode", codec=codec_obj.name)
    t0 = _clock()
    try:
        wire, side = codec_obj.encode(block, delta_ok)
        _engine.record_codec(int(block.nbytes), int(wire.nbytes),
                            _clock() - t0)
        if sp is not None:
            sp.set(bytes_raw=int(block.nbytes),
                   bytes_wire=int(wire.nbytes))
    finally:
        _obs.end(sp)
    return wire, side


# ---------------------------------------------------------------------
# the lazy source
# ---------------------------------------------------------------------

class StreamSource:
    """A lazy out-of-core operand: host slabs + device-side stages.

    ``kind='callback'`` sources produce any record range on demand
    (``fn(index_slices) -> block``, the ``fromcallback`` contract) and
    can be streamed repeatedly; ``kind='iter'`` sources
    (``fromiter``) yield consecutive blocks and stream in order, once
    per ``iter()`` of the underlying iterable.

    ``stages`` is the device-side chain, applied per slab inside ONE
    compiled program: ``("map", func)`` per-record, ``("chunk", func,
    plan, pad, canon)``, ``("stack", func, size, canon)``, and a
    trailing ``("filter", pred)`` whose mask the reduction terminals
    fold without ever materialising a compaction buffer."""

    __slots__ = ("kind", "produce", "blocks", "shape", "split", "dtype",
                 "mesh", "slab", "stages", "ckpt", "codec", "auto_slab",
                 "_state", "_consumed")

    def __init__(self, kind, produce, blocks, shape, split, dtype, mesh,
                 slab, stages=(), ckpt=None, codec=None, auto_slab=False):
        self.kind = kind
        self.produce = produce          # callback: fn(index_slices)
        self.blocks = blocks            # iter: the iterable of blocks
        self.shape = tuple(int(s) for s in shape)
        self.split = int(split)
        self.dtype = np.dtype(dtype)
        self.mesh = mesh
        self.slab = int(slab)
        self.stages = tuple(stages)
        self.ckpt = ckpt                # resumable checkpoint dir (or None)
        self.codec = codec              # ingest codec NAME (or None);
        #                                 wins over the codec() scope
        self.auto_slab = bool(auto_slab)  # the caller gave no `chunks`:
        #                                 the slab is the default rule's
        #                                 and a swap may re-draw it
        self._state = None
        # iter sources stream ONCE per iter() of a one-shot iterable (a
        # generator cannot rewind); the cell is SHARED across derived
        # sources (with_stage) because they share the iterator itself
        self._consumed = [False]

    # -- construction --------------------------------------------------

    @classmethod
    def from_callback(cls, fn, shape, split, dtype, mesh, chunks=None,
                      checkpoint=None, codec=None):
        if codec is not None:
            # a typo'd codec name must be a pointed error HERE, at the
            # construction boundary — not a crash inside the checker or
            # a first-terminal surprise (dtype fit still resolves per
            # run: the scope form can override a None source codec)
            _codec_registry().get(codec)
        slab = _slab_records(shape, dtype, chunks)
        return cls("callback", fn, None, shape, split, dtype, mesh, slab,
                   ckpt=checkpoint, codec=codec, auto_slab=chunks is None)

    @classmethod
    def from_iter(cls, blocks, shape, split, dtype, mesh,
                  checkpoint=None, codec=None):
        if codec is not None:
            _codec_registry().get(codec)    # pointed at construction
        # slab sizes are whatever the iterator yields; the recorded slab
        # is only the default the shape/dtype imply (for repr/reports)
        slab = _slab_records(shape, dtype, None)
        return cls("iter", None, blocks, shape, split, dtype, mesh, slab,
                   ckpt=checkpoint, codec=codec)

    def with_stage(self, stage, slab=None):
        """A new source sharing the host side, one device stage longer
        (``slab``: re-drawn records a slab, for a swap stage)."""
        out = StreamSource(self.kind, self.produce, self.blocks,
                           self.shape, self.split, self.dtype, self.mesh,
                           self.slab if slab is None else slab,
                           self.stages + (stage,), ckpt=self.ckpt,
                           codec=self.codec, auto_slab=self.auto_slab)
        out._consumed = self._consumed      # same iterator, same budget
        return out

    # -- the host slab iterator ---------------------------------------

    def produce_slab(self, lo, hi):
        """Produce ONE validated host block for records ``[lo, hi)`` —
        the random-access path the uploader-pool workers call
        CONCURRENTLY (callback sources only; the callback must therefore
        be thread-safe, which slicing a memmap/HDF5-style store is)."""
        rest = self.shape[1:]
        index = (slice(lo, hi),) + tuple(slice(0, s) for s in rest)
        block = np.asarray(self.produce(index), dtype=self.dtype)
        if block.shape != (hi - lo,) + rest:
            raise ValueError(
                "fromcallback callback returned shape %s for index "
                "%s (expected %s)"
                % (block.shape, index, (hi - lo,) + rest))
        return block

    def slab_ranges(self):
        """``(lo, hi)`` record ranges of every slab, in key order."""
        n, slab = self.shape[0], self.slab
        return [(lo, min(lo + slab, n)) for lo in range(0, n, slab)]

    def slabs(self):
        """Yield ``(lo, hi, block)`` record slabs in key order; blocks
        are validated and cast to the source dtype.  Callback sources
        slice on demand; iterator sources stream whatever block sizes
        the iterable yields and must cover the shape exactly."""
        if self.kind == "callback":
            for lo, hi in self.slab_ranges():
                yield lo, hi, self.produce_slab(lo, hi)
            return
        # one-shot iterables (iter(x) is x: generators, file readers)
        # cannot stream twice — raise a POINTED error instead of the
        # misleading "blocks cover only 0 of N records" the exhausted
        # iterator would otherwise produce downstream
        if iter(self.blocks) is self.blocks:
            if self._consumed[0]:
                raise RuntimeError(
                    "this fromiter source was already streamed and its "
                    "iterator is exhausted (generators are one-shot); "
                    "materialise once and reuse the result, pass a "
                    "re-iterable (e.g. a list of blocks), or use "
                    "fromcallback for random-access sources")
            self._consumed[0] = True
        yield from iter_record_blocks(self.blocks, self.shape, self.dtype)

    def __repr__(self):
        return ("StreamSource(%s, shape=%s, split=%d, dtype=%s, slab=%d, "
                "stages=%d)" % (self.kind, self.shape, self.split,
                                self.dtype, self.slab, len(self.stages)))


def _slab_records(shape, dtype, chunks):
    n = int(shape[0])
    if chunks is not None:
        slab = int(chunks)
        if slab < 1:
            raise ValueError("chunks (records per slab) must be >= 1, "
                             "got %d" % slab)
        return min(slab, max(n, 1))
    rec = prod(shape[1:]) * np.dtype(dtype).itemsize
    return max(1, min(max(n, 1), _SLAB_BYTES // max(rec, 1)))


# ---------------------------------------------------------------------
# abstract stage interpretation (shared with bolt_tpu.analysis.check)
# ---------------------------------------------------------------------

def _stage_apply(stage, split, x, key0=None, operands=None):
    """Apply ONE device-side stage to traced value ``x`` — the same
    bodies the materialised paths compile, so streamed and materialised
    semantics cannot drift.  ``key0``: the first key of the slab ``x``
    is (an int32 scalar the program takes as an operand), which a KEYED
    stage (a ``with_keys`` map) adds to its slab-local keys.
    ``operands``: an iterator over the program's side operands
    (:func:`stage_extras` order), of which a ``with_operands`` map takes
    its share; without one the map's own arrays are bound."""
    kind = stage[0]
    if kind == "map":
        from bolt_tpu.tpu.array import (_bind_operands, _chain_apply,
                                        _operands_of)
        funcs = (stage[1],)
        if operands is not None:
            funcs = _bind_operands(
                funcs, [next(operands) for _ in _operands_of(funcs)])
        return _chain_apply(funcs, split, x, key0=key0)
    if kind == "chunk":
        from bolt_tpu.tpu.chunk import _general_map_body, _uniform_map_body
        _, func, plan, pad, canon = stage
        vshape = x.shape[split:]
        uniform = not any(pad) and all(
            v % c == 0 for v, c in zip(vshape, plan))
        if uniform:
            return _uniform_map_body(x, func, split, plan, canon)
        return _general_map_body(x, func, split, plan, pad, canon)
    if kind == "stack":
        from bolt_tpu.tpu.stack import _stack_map_body
        _, func, size, canon = stage
        return _stack_map_body(x, func, split, size, canon)
    if kind == "swap":
        # a swap stage is resolved by the two-phase shuffle executor
        # (resolve_swaps) BEFORE any slab program compiles — it can
        # never be applied slab-locally (the transpose crosses slab
        # boundaries), so reaching here is an internal routing bug
        raise RuntimeError(
            "internal: a 'swap' stage reached slab execution without "
            "being resolved — resolve_swaps must run first")
    raise ValueError("unknown stream stage %r" % (kind,))


def stage_label(stage):
    """Human label for one stage (analysis reports)."""
    def _name(f):
        return getattr(f, "__name__", None) or type(f).__name__
    kind = stage[0]
    if kind == "map":
        from bolt_tpu.tpu.array import _WithKeysFunc
        if isinstance(stage[1], _WithKeysFunc):
            return "map(%s, with_keys)" % _name(stage[1].func)
        return "map(%s)" % _name(stage[1])
    if kind == "chunk":
        return "chunk(plan=%s).map(%s)" % (tuple(stage[2]), _name(stage[1]))
    if kind == "stack":
        return "stacked(%d).map(%s)" % (stage[2], _name(stage[1]))
    if kind == "filter":
        return "filter(%s)" % _name(stage[1])
    if kind == "swap":
        return "swap(perm=%s, split=%d)" % (stage[1], stage[2])
    return kind


def stage_aval(stage, split, aval):
    """Abstract result of one stage (``jax.eval_shape`` through the real
    bodies; memoised, ZERO XLA compiles)."""
    from bolt_tpu.tpu.array import _cached_eval_shape
    if stage[0] == "swap":
        # pure axis permutation: the abstract result needs no trace
        return jax.ShapeDtypeStruct(
            tuple(aval.shape[p] for p in stage[1]), aval.dtype)
    key = ("stream-stage", stage_keys((stage,)), split, tuple(aval.shape),
           str(aval.dtype))
    return _cached_eval_shape(
        key, lambda: jax.eval_shape(
            lambda d: _stage_apply(stage, split, d),
            jax.ShapeDtypeStruct(tuple(aval.shape), aval.dtype)))


def stage_keys(stages):
    """``stages`` as a program's engine key takes them: a map of a
    ``utils.with_operands`` by its function and its operands' avals (the
    program takes the arrays as arguments), every other stage itself."""
    from bolt_tpu.tpu.array import _func_key
    return tuple(("map", _func_key(s[1])) if s[0] == "map" else s
                 for s in stages)


def stage_extras(stages):
    """``(keyed, operands)`` of a stage chain: whether a ``with_keys``
    map rides in it (its slab program takes the slab's first key), and
    the arrays its ``with_operands`` maps name, flat, in stage order
    (the program's side operands)."""
    from bolt_tpu.tpu.array import _WithKeysFunc, _operands_of
    maps = tuple(s[1] for s in stages if s[0] == "map")
    return (any(isinstance(f, _WithKeysFunc) for f in maps),
            _operands_of(maps))


class _ResultState:
    """What the stage chain produces: the static result aval (or the
    dynamic pre-filter bound), the result split, and the record count
    ``n``/value shape the terminals fold over."""

    __slots__ = ("shape", "dtype", "split", "dynamic", "n", "vshape",
                 "pred")

    def __init__(self, shape, dtype, split, dynamic, n, vshape, pred):
        self.shape = shape
        self.dtype = dtype
        self.split = split
        self.dynamic = dynamic
        self.n = n
        self.vshape = vshape
        self.pred = pred


def result_state(source):
    """Walk the stage chain abstractly (cached on the source)."""
    if source._state is not None:
        return source._state
    aval = jax.ShapeDtypeStruct(source.shape, source.dtype)
    split = source.split
    pred = None
    dynamic = False
    for stage in source.stages:
        if stage[0] == "filter":
            # behind a filter come record-wise maps alone (post_map_stage):
            # they read the flattened records, one key axis
            pred = stage[1]
            dynamic = True
            aval = jax.ShapeDtypeStruct(
                (prod(aval.shape[:split]),) + tuple(aval.shape[split:]),
                aval.dtype)
            split = 1
            continue
        aval = stage_aval(stage, split, aval)
        if stage[0] == "swap":
            split = stage[2]          # the swap re-draws the key|value cut
    n = prod(aval.shape[:split])
    vshape = tuple(aval.shape[split:])
    if dynamic:
        st = _ResultState(None, np.dtype(aval.dtype), 1, True, n, vshape,
                          pred)
    else:
        st = _ResultState(tuple(aval.shape), np.dtype(aval.dtype), split,
                          False, n, vshape, None)
    source._state = st
    return st


# ---------------------------------------------------------------------
# stage recording (called by the op layers on stream-backed arrays)
# ---------------------------------------------------------------------

def map_stage(arr, func):
    """Record a per-record map on a stream-backed array (lazy).  A
    ``with_keys`` entry or one with side operands makes the slab program
    take more than the slab; on a mesh of several processes, where that
    program runs under ``shard_map`` per shard, such a stage is not
    recorded (NotImplemented: the caller materialises, as before)."""
    from bolt_tpu.tpu.array import BoltArrayTPU
    src = arr._stream
    stage = ("map", func)
    if _multihost.mesh_process_count(src.mesh) > 1 \
            and stage_extras((stage,)) != (False, ()):
        return NotImplemented
    return BoltArrayTPU._streamed(src.with_stage(stage))


def filter_stage(arr, pred):
    """Record a filter predicate (lazy, dynamic shape): the last stage
    but for record-wise maps called on the filter since."""
    from bolt_tpu.tpu.array import BoltArrayTPU
    return BoltArrayTPU._streamed(arr._stream.with_stage(("filter", pred)))


def post_map_stage(arr, func):
    """Record a record-wise map called on a streamed FILTER (lazy): a
    map commutes with the selection, so it becomes a stage behind the
    predicate, as it joins a resident deferred filter's ``post`` maps
    (``BoltArrayTPU._map_filter``), and the terminal folds the mask over
    what it gives.  ``None`` for a callable that does not trace on a
    record (the caller materialises, and takes the host fallback)."""
    from bolt_tpu.tpu.array import _TRACE_ERRORS
    try:
        out = map_stage(arr, func)      # walks the chain abstractly
    except _TRACE_ERRORS:
        return None
    return None if out is NotImplemented else out


def chunked_map_stage(view, func, dtype):
    """Record a chunked per-block map on a streaming chunked view;
    returns the new view, or NotImplemented when the stage cannot be
    planned abstractly (the caller falls back to materialising)."""
    from bolt_tpu.tpu.array import BoltArrayTPU, _TRACE_ERRORS, _canon
    from bolt_tpu.tpu.chunk import ChunkedArray
    b = view._barray
    src = b._stream
    st = result_state(src)
    if st.dynamic:
        return NotImplemented
    plan = tuple(view._plan)
    pad = tuple(view._padding)
    canon = None if dtype is None else _canon(dtype)
    vshape = tuple(st.shape[st.split:])
    uniform = not any(pad) and all(
        v % c == 0 for v, c in zip(vshape, plan))
    stage = ("chunk", func, plan, pad, canon)
    try:
        nxt = stage_aval(stage, st.split,
                         jax.ShapeDtypeStruct(st.shape, st.dtype))
    except _TRACE_ERRORS:
        return NotImplemented       # the materialised path surfaces it
    except ValueError:
        raise                       # rank/block-shape contract violations
    if uniform:
        grid = tuple(v // c for v, c in zip(vshape, plan))
        new_plan = tuple(o // g for o, g in
                         zip(nxt.shape[st.split:], grid))
    else:
        new_plan = plan             # general path preserves blocks
    out = BoltArrayTPU._streamed(src.with_stage(stage))
    return ChunkedArray(out, new_plan, pad)


def stacked_map_stage(view, func, dtype):
    """Record a block-batched map on a streaming stacked view.

    Streams only when every slab holds a whole number of blocks
    (``records_per_slab % size == 0``): a stacked ``func`` may mix
    records WITHIN its block, so slab boundaries must align with block
    boundaries or streamed and materialised results would group records
    differently.  Misaligned geometries (and iterator sources, whose
    block sizes are not known up front) fall back to materialising."""
    from bolt_tpu.tpu.array import BoltArrayTPU, _TRACE_ERRORS, _canon
    from bolt_tpu.tpu.stack import StackedArray
    b = view._barray
    src = b._stream
    st = result_state(src)
    size = int(view._size)
    if st.dynamic or src.kind != "callback" or has_swap(src):
        # a pending swap re-draws the record axis, so the slab/block
        # alignment below would reason about the WRONG geometry —
        # materialise instead (rare: stacked maps over re-keyed streams)
        return NotImplemented
    if _multihost.mesh_process_count(src.mesh) > 1:
        # a stacked func mixes records WITHIN its block; per-process
        # shard boundaries would have to align with block boundaries on
        # every host — fall back to materialising rather than reason
        # about that geometry per process
        return NotImplemented
    recs_per_slab = src.slab * prod(st.shape[1:st.split])
    if recs_per_slab % size != 0:
        return NotImplemented
    canon = None if dtype is None else _canon(dtype)
    stage = ("stack", func, size, canon)
    try:
        stage_aval(stage, st.split,
                   jax.ShapeDtypeStruct(st.shape, st.dtype))
    except _TRACE_ERRORS:
        return NotImplemented
    out = BoltArrayTPU._streamed(src.with_stage(stage))
    return StackedArray(out, size)


def swap_stage(arr, perm, new_split):
    """Record a ``swap`` (axis re-keying) on a stream-backed array —
    LAZILY: the stage is a forecastable marker the two-phase shuffle
    executor (:func:`resolve_swaps`) resolves at consumption, so
    ``swap`` on a streamed source never materialises the input.
    Returns NotImplemented (→ the materialised path) when the swap
    cannot stream: a dynamic (post-filter) row count, a lossy ingest
    codec (phase 1 decodes once; a later terminal would quantise
    AGAIN, drifting from the materialised path), or a pod iterator
    source (per-process bucket ownership needs random access).

    Where the caller gave no ``chunks`` and the record axis lands minor,
    the slab is re-drawn to whole lane tiles a device that shards it
    (``parallel.shuffle.lane_slab``: a rule on the mesh, no knob)."""
    from bolt_tpu.parallel.shuffle import lane_slab, tile_width
    from bolt_tpu.tpu.array import BoltArrayTPU
    src = arr._stream
    st = result_state(src)
    if st.dynamic:
        return NotImplemented
    codec_obj = resolve_codec(src)
    if codec_obj is not None and not codec_obj.lossless:
        return NotImplemented
    if _multihost.mesh_process_count(src.mesh) > 1 \
            and src.kind != "callback":
        return NotImplemented
    slab = None
    if src.auto_slab and src.kind == "callback" and not has_swap(src):
        slab = lane_slab(src.slab, src.shape[0],
                         prod(src.shape[1:]) * src.dtype.itemsize, perm,
                         2 * _SLAB_BYTES,
                         tile_width(src.mesh, src.shape, src.split))
    return BoltArrayTPU._streamed(
        src.with_stage(("swap", tuple(int(p) for p in perm),
                        int(new_split)), slab=slab))


def has_swap(source):
    """Whether ``source`` carries an unresolved ``swap`` stage."""
    return any(s[0] == "swap" for s in source.stages)


def resolve_swaps(source):
    """Resolve every pending ``swap`` stage of ``source`` through the
    two-phase streaming shuffle (:func:`_resolve_one_swap`); returns a
    ``BoltArrayTPU`` — CONCRETE when the last resolution was resident
    (post-swap stages replayed through the normal materialised paths),
    STREAM-BACKED over spilled bucket files when it spilled (post-swap
    stages ride the new source lazily)."""
    b = _resolve_one_swap(source)
    while b._stream is not None and has_swap(b._stream):
        b = _resolve_one_swap(b._stream)
    return b


# ---------------------------------------------------------------------
# terminal routing
# ---------------------------------------------------------------------

_STAT_NAMES = ("sum", "mean", "var", "std")


def _swap_resolved(arr):
    """Resolve ``arr``'s pending swap stages IN PLACE (the adoption
    mirrors ``_data``'s adopt-after-success): returns the post-swap
    stream source to keep streaming over, or ``None`` when resolution
    landed a concrete array — the materialised paths own the rest."""
    res = resolve_swaps(arr._stream)
    arr._adopt_resolved(res)
    return arr._stream


def maybe_stat(arr, axis, name, keepdims, ddof):
    """Stream a reduction terminal when the geometry allows it; returns
    NotImplemented (→ the caller materialises) otherwise."""
    src = arr._stream
    if src is None or keepdims or name not in _STAT_NAMES:
        return NotImplemented
    if has_swap(src):
        # resolve the re-keying FIRST (two-phase shuffle): a resident
        # resolution lands concrete data (the materialised stat path
        # runs on it); a spilled one re-enters here over bucket files
        src = _swap_resolved(arr)
        if src is None:
            return NotImplemented
    st = result_state(src)
    if st.n == 0:
        return NotImplemented           # empty: materialised path's rules
    if axis is not None:
        from bolt_tpu.utils import tupleize
        if tuple(sorted(tupleize(axis))) != tuple(range(st.split)):
            return NotImplemented
    if name in ("mean", "var", "std") and np.issubdtype(
            st.dtype, np.complexfloating):
        return NotImplemented           # mirror the fused-filter gate
    return execute(arr, stat_terminal(name, ddof))


def maybe_reduce(arr, func, axes, keepdims):
    """Stream a ``reduce(func)`` terminal when possible."""
    src = arr._stream
    if src is None or keepdims:
        return NotImplemented
    if has_swap(src):
        src = _swap_resolved(arr)     # see maybe_stat
        if src is None:
            return NotImplemented
    st = result_state(src)
    if st.pred is not None or st.n == 0:
        return NotImplemented
    if _multihost.mesh_process_count(src.mesh) > 1:
        # a user combine function has no mesh collective: the cross-host
        # fold cannot ride psum/pmin/pmax — materialise instead
        return NotImplemented
    if tuple(axes) != tuple(range(st.split)):
        return NotImplemented
    from bolt_tpu.tpu.array import _TRACE_ERRORS, _cached_eval_shape
    vaval = jax.ShapeDtypeStruct(st.vshape, st.dtype)
    try:
        _cached_eval_shape(
            ("reduce", func, st.vshape, str(vaval.dtype)),
            lambda: jax.eval_shape(func, vaval, vaval))
    except _TRACE_ERRORS:
        return NotImplemented           # host-fallback path resolves
    return execute(arr, _Reduce("reduce", rfunc=func))


def maybe_group(arr, label, value, nseg, op):
    """Stream ``ops.segment_reduce`` by a label FUNCTION when the source
    allows it: ``(folded, counts)`` as the resident terminal returns
    them (``BoltArrayTPU._grouped_fold``), or NotImplemented (the caller
    materialises, as it always did).  What streams: one key axis, stages
    that are record-wise maps and at most one filter (its mask is the
    fold's predicate), a fold whose partials merge, one process."""
    from bolt_tpu.tpu import fold as _fold
    from bolt_tpu.tpu.array import _FOLDS
    src = arr._stream
    if src is None or op not in _FOLDS:
        return NotImplemented
    if has_swap(src):
        src = _swap_resolved(arr)     # see maybe_stat
        if src is None:
            return NotImplemented
    if src.split != 1 or _multihost.mesh_process_count(src.mesh) > 1:
        return NotImplemented
    maps = tuple(s for s in src.stages if s[0] != "filter")
    if (any(s[0] != "map" for s in maps)
            or stage_extras(maps) != (False, ())
            or not _fold._plain_callables(tuple(s[1] for s in maps))):
        return NotImplemented
    st = result_state(src)
    if st.n == 0:
        return NotImplemented           # empty: materialised path's rules
    return execute(arr, _Group((op, label, value, int(nseg)), src))


def gram_refusal(source, axes, passes=1):
    """Why the Gram matrix of ``source`` over the sample axes ``axes``
    (``ops.pca`` / ``ops.cov``) cannot be folded slab by slab (the caller
    then materialises, as it always did), or ``None`` where it can.
    ``passes``: how often the caller reads the source (a ``pca`` reads it
    twice: its scores are a second pass).  What streams: record-wise maps
    in front, sample axes that are the leading axes and hold every key
    axis (a slab is then whole samples, and the scores keep the source's
    keys), one process."""
    st = result_state(source)
    if st.dynamic:
        return ("the row count is dynamic (a filter's survivor count is "
                "not known until the predicate has run): the scores' "
                "place in the result cannot be planned")
    if any(s[0] != "map" for s in source.stages):
        return ("a %s stage is in front: record-wise maps alone are "
                "traced into the Gram pass (a swap is resolved first, by "
                "whatever materialises the source)"
                % next(s[0] for s in source.stages if s[0] != "map"))
    m = len(axes)
    if tuple(axes) != tuple(range(m)) or not st.split <= m < len(st.shape):
        return ("the sample axes %s are not the leading axes with every "
                "key axis among them: the samples would have to be "
                "re-axed first" % (tuple(axes),))
    if st.n == 0 or source.shape[0] == 0:
        return "the source is empty"
    if _multihost.mesh_process_count(source.mesh) > 1:
        return ("the mesh spans several processes (the slab program "
                "under shard_map has no Gram partial: psum of it is the "
                "seam)")
    codec_obj = resolve_codec(source)
    if codec_obj is not None and not codec_obj.lossless:
        return ("the ingest codec %r is lossy (the materialised path "
                "uploads the base unencoded)" % (codec_obj.name,))
    if passes > 1 and source.kind != "callback" \
            and iter(source.blocks) is source.blocks:
        return ("the fromiter source is a one-shot iterator and the "
                "caller reads it %d times" % passes)
    return None


def maybe_gram(arr, axes, precision, sums=False, second_conj=False,
               passes=1):
    """Fold the Gram matrix of a stream-backed array over its sample
    axes ``axes`` slab by slab: ``(G,)``, or with ``sums`` ``(G, s)``,
    device arrays ``(d, d)`` and ``(d,)`` with the features every axis
    behind the samples, flattened: the sum over all slabs of
    ``ops.linalg._sample_gram`` of a slab after its recorded stages,
    which is the resident program's own body.  NotImplemented where
    :func:`gram_refusal` says why (the caller materialises)."""
    src = arr._stream
    if src is None or gram_refusal(src, axes, passes) is not None:
        return NotImplemented
    return execute(arr, _Gram((len(axes), precision, bool(second_conj),
                               bool(sums))))


# ---------------------------------------------------------------------
# terminals: what a streamed run folds its slabs into
# ---------------------------------------------------------------------

class _Terminal:
    """A streamed reduction terminal as a VALUE: what one slab's partial
    is (:meth:`partial`), how two partials merge (:meth:`combine`) and
    what the folded partial becomes (:meth:`finalise`, :meth:`wrap`).
    Immutable, and equal and hashable by ``key``, because it rides in the
    engine's program keys: the terminals two calls of ``b.sum()`` build
    hit ONE cached slab program.  A new streamed terminal is one
    subclass; nothing else in this file asks which one it was handed."""

    # min/max/ptp are exact by contract: a lossy ingest codec refuses them
    order_sensitive = False

    def __init__(self, name, ddof=None, rfunc=None, comps=None, params=()):
        # ``name``: the word in spans, errors and the fingerprint;
        # ``comps``: where the partial is a TUPLE, what each component
        # merges as; ``params``: a multi's specs, a group, a gram
        self.__dict__.update(
            name=name, ddof=ddof, rfunc=rfunc, comps=comps, params=params,
            key=(name, ddof, rfunc, comps, params))

    def __setattr__(self, attr, value):
        raise AttributeError("a streamed terminal is a value: %r is set "
                             "where it is built" % (attr,))

    def __eq__(self, other):
        return type(other) is type(self) and other.key == self.key

    def __hash__(self):
        return hash(self.key)

    @property
    def names(self):
        """The terminal(s) a refusal names to the caller."""
        return [self.name]

    @property
    def members(self):
        """What ``params`` add to a run's checkpoint fingerprint."""
        return ""

    def partial(self, x, stages, pred, post, split, apply, axes):
        """One slab's partial: ``x`` is the slab as uploaded (decoded,
        re-seated), ``stages`` / ``pred`` / ``post`` the source's chain
        cut at its filter, ``apply(stage, split, x)`` a stage's traced
        body with the slab's key and side operands bound.  The common
        path: stages, flatten, mask, the maps behind it, :meth:`of_records`.

        ``axes`` is the MULTI-PROCESS hook: inside a shard_map'd slab
        program ``x`` is one device shard's records and ``axes`` names
        the mesh axes the slab's key axes shard over — the reduction
        points then insert the cross-host collective (one per slab for
        sum/min/max, two for moments: the count+sum pair rides ONE fused
        psum, M2 needs the global mean first), so the partial leaves the
        program already combined across the pod.  Sums of sums: equal to
        the one-process run whenever the data keeps the reduction exact
        (even splits; the parity suite's contract)."""
        from bolt_tpu.tpu.array import _pred_mask
        for stg in stages:
            x = apply(stg, split, x)
        vshape = x.shape[split:]
        n = prod(x.shape[:split])
        flat = x.reshape((n,) + vshape)
        mask = mfull = None
        if pred is not None:
            mask = _pred_mask(pred, flat)
            # a map behind the filter reads every record; the mask then
            # folds what it gave for a dropped one away
            for stg in post:
                flat = apply(stg, 1, flat)
            vshape = flat.shape[1:]
            mfull = mask.reshape((n,) + (1,) * len(vshape))
        return self.of_records(flat, mask, mfull, vshape, n, axes)

    def of_records(self, flat, mask, mfull, vshape, n, axes):
        """The partial over the flattened records (``mask`` / ``mfull``:
        the filter's verdicts, ``None`` without one): the expression a
        standalone slab program traces, and a fused group per component."""
        raise NotImplementedError

    def combine(self, a, b):
        """The ONE partial-merge arithmetic, traced by BOTH the merge
        program (the tree above level 0) and the acc-fused slab program
        (level 0), so the two cannot drift.  ``a`` is the EARLIER partial
        (fold order matters for ``reduce``)."""
        raise NotImplementedError

    def fold(self, mesh, sample):
        """A fresh :class:`_PairFold` for one run, its (tiny,
        engine-cached) merge program derived from a sample partial — a
        live device value (the first pushed pair) OR a host array a
        checkpoint restored.  Captures only shape/dtype: a factory
        closing over the live partial would pin its device buffers for
        the whole run."""
        shape, dtype = tuple(sample.shape), str(sample.dtype)

        def factory():
            key = ("stream-merge", self.name, self.rfunc, shape, dtype,
                   mesh, _multihost.topology_token())
            return _cached_jit(key, lambda: jax.jit(
                lambda a, b: self.combine(a, b)))
        return _PairFold(factory)

    def finalise(self, folded, source):
        """The folded partial → the device value(s) the caller asked for."""
        return folded

    def wrap(self, out, mesh):
        """The finalised value(s) as :func:`execute` returns them."""
        from bolt_tpu.tpu.array import BoltArrayTPU
        return BoltArrayTPU(out, 0, mesh)

    def tally(self, nslabs, kernel_slabs):
        """What a run of ``nslabs`` adds to ``engine.record_stream``
        (``kernel_slabs`` of them by a ``packed_gram`` program)."""
        return {}


class _Sum(_Terminal):
    def __init__(self):
        super().__init__("sum")

    def of_records(self, flat, mask, mfull, vshape, n, axes):
        # identity fold, exactly like _fused_filter_stat: dropped
        # records (NaNs included) become inert zeros
        v = flat if mfull is None else jnp.where(
            mfull, flat, jnp.asarray(0, flat.dtype))
        s = jnp.sum(v, axis=0)
        return jax.lax.psum(s, axes) if axes else s

    def combine(self, a, b):
        return jnp.add(a, b)


class _Extremum(_Terminal):
    """``min`` / ``max``: components of a fused multi-stat group and of a
    grouped fold.  Exact order statistics; a filter predicate never
    reaches here (min/max members are ineligible under a filter — zero
    survivors would need the materialised error contract)."""

    order_sensitive = True
    _OPS = {"min": (jnp.min, jax.lax.pmin, jnp.minimum),
            "max": (jnp.max, jax.lax.pmax, jnp.maximum)}

    def of_records(self, flat, mask, mfull, vshape, n, axes):
        op, collective, _ = self._OPS[self.name]
        p = op(flat, axis=0)
        return collective(p, axes) if axes else p

    def combine(self, a, b):
        return self._OPS[self.name][2](a, b)


class _Reduce(_Terminal):
    def of_records(self, flat, mask, mfull, vshape, n, axes):
        if axes:
            raise ValueError(
                "streamed reduce(func) cannot run on a multi-process "
                "mesh: a user combine function has no mesh collective")
        vfunc = jax.vmap(self.rfunc)
        y = flat
        while y.shape[0] > 1:
            half = y.shape[0] // 2
            combined = vfunc(y[:half], y[half:2 * half])
            if combined.shape != y[:half].shape:
                raise ValueError(
                    "reduce produced shape %s, expected value "
                    "shape %s" % (combined.shape[1:], tuple(vshape)))
            rem = y[2 * half:]
            y = jnp.concatenate([combined, rem], axis=0) \
                if rem.shape[0] else combined
        return y[0]

    def combine(self, a, b):
        return self.rfunc(a, b)


class _Moments(_Terminal):
    """``mean`` / ``var`` / ``std``, and ``"moments"``: the statcounter
    triple ``(n, mu, M2)`` per value slot itself, ONE of which serves
    every mean/var/std member of a fused multi-stat group.  Partials
    merge by the Chan et al. parallel recurrence (statcounter's
    ``mergeStats``, vectorised over the value block): in a pairwise tree
    power-of-two slab counts keep its denominators exact."""

    def of_records(self, flat, mask, mfull, vshape, n, axes):
        out_dt = jax.eval_shape(
            lambda t: jnp.mean(t, axis=0),
            jax.ShapeDtypeStruct((1,) + tuple(vshape), flat.dtype)).dtype
        if mfull is None:
            cnt = jnp.asarray(n, out_dt)
            xf = flat.astype(out_dt)
        else:
            cnt = jnp.sum(mask.astype(out_dt))
            xf = jnp.where(mfull, flat,
                           jnp.asarray(0, flat.dtype)).astype(out_dt)
        sums = jnp.sum(xf, axis=0)
        if axes:
            # ONE fused collective for the pre-mean components: the global
            # count and per-slot sum land together
            cnt, sums = jax.lax.psum((cnt, sums), axes)
        safe = jnp.where(cnt > 0, cnt, jnp.asarray(1, out_dt))
        mu = sums / safe
        dev = xf - mu
        if mfull is not None:
            dev = jnp.where(mfull, dev, jnp.asarray(0, out_dt))
        m2 = jnp.sum(dev * dev, axis=0)
        if axes:
            m2 = jax.lax.psum(m2, axes)
        return cnt, mu, m2

    def combine(self, a, b):
        n1, mu1, m21 = a
        n2, mu2, m22 = b
        n = n1 + n2
        safe = jnp.where(n > 0, n, jnp.asarray(1, n.dtype))
        delta = mu2 - mu1
        mu = mu1 + delta * (n2 / safe)
        m2 = m21 + m22 + delta * delta * (n1 * n2 / safe)
        return n, mu, m2

    def fold(self, mesh, sample):
        shape, dtype = tuple(sample[1].shape), str(sample[1].dtype)

        def factory():
            key = ("stream-merge-moments", shape, dtype, mesh,
                   _multihost.topology_token())

            def build():
                def merge(n1, mu1, m21, n2, mu2, m22):
                    return self.combine((n1, mu1, m21), (n2, mu2, m22))
                return jax.jit(merge)
            mp = _cached_jit(key, build)
            return lambda a, b: tuple(mp(*a, *b))
        return _PairFold(factory)

    def finalise(self, folded, source):
        """Moments triple → the requested statistic (engine-cached)."""
        name, ddof, mesh = self.name, self.ddof, source.mesh
        n, mu, m2 = folded
        dtype = mu.dtype
        key = ("stream-final", name, tuple(mu.shape), str(dtype), ddof,
               mesh, _multihost.topology_token())

        def build():
            nan = jnp.asarray(jnp.nan, dtype)
            dd = 0.0 if ddof is None else ddof

            def final(n, mu, m2):
                if name == "mean":
                    return jnp.where(n > 0, mu, nan)
                var = jnp.where(n > 0, m2 / (n - jnp.asarray(dd, n.dtype)),
                                nan)
                if name == "std":
                    return jnp.sqrt(var)
                return var
            return jax.jit(final)
        return _cached_jit(key, build)(n, mu, m2)


def stat_terminal(name, ddof=None):
    """The terminal of ONE streamed ``sum`` / ``mean`` / ``var`` / ``std``
    (:func:`maybe_stat`; a fused group of one: ``tpu/multistat.py``)."""
    return _Sum() if name == "sum" else _Moments(name, ddof)


class _Tupled(_Terminal):
    """A partial that is a TUPLE of components, each merged as ``comps``
    says, all in one dispatch."""

    @property
    def order_sensitive(self):
        return any(c.order_sensitive for c in self.comps)

    def combine(self, a, b):
        return tuple(c.combine(x, y) for c, x, y in zip(self.comps, a, b))

    def fold(self, mesh, sample):
        sig = tuple((tuple(leaf.shape), str(leaf.dtype))
                    for leaf in jax.tree_util.tree_leaves(sample))

        def factory():
            key = ("stream-merge-multi", self.comps, sig, mesh,
                   _multihost.topology_token())
            mp = _cached_jit(key, lambda: jax.jit(
                lambda a, b: self.combine(a, b)))
            return lambda a, b: tuple(mp(a, b))
        return _PairFold(factory)

    def wrap(self, out, mesh):
        return list(out)                  # one jax array per component


class _Multi(_Tupled):
    """A fused multi-stat group (``tpu/multistat.py``): ``specs`` is the
    ordered ``(name, ddof)`` member list, a slab's partial one component
    tuple from a SINGLE read of the slab, and the result one device
    value a member, each finalised from the shared folded components
    exactly as its standalone streamed terminal would be."""

    def __init__(self, specs):
        # ONE moments triple serves every mean/var/std member, min/max
        # serve their members AND both halves of a ``ptp``
        names = [name for name, _ in specs]
        comps = []
        if "sum" in names:
            comps.append(_Sum())
        if any(n in ("mean", "var", "std") for n in names):
            comps.append(_Moments("moments"))
        if "min" in names or "ptp" in names:
            comps.append(_Extremum("min"))
        if "max" in names or "ptp" in names:
            comps.append(_Extremum("max"))
        super().__init__("multi", comps=tuple(comps), params=tuple(specs))

    @property
    def names(self):
        return [name for name, _ in self.params]

    @property
    def members(self):
        return "|".join("%s:%s" % (n, d) for n, d in self.params)

    def of_records(self, *records):
        return tuple(c.of_records(*records) for c in self.comps)

    def finalise(self, folded, source):
        by = dict(zip((c.name for c in self.comps), folded))
        outs = []
        for name, ddof in self.params:
            if name == "ptp":
                # the SAME cached max−min program the in-memory fused
                # groups use (one "multi-stat-sub" key per geometry)
                from bolt_tpu.tpu.multistat import _sub_program
                hi, lo = by["max"], by["min"]
                outs.append(_sub_program(hi.shape, hi.dtype,
                                         source.mesh)(hi, lo))
            elif name in by:
                outs.append(by[name])
            else:
                outs.append(_Moments(name, ddof).finalise(by["moments"],
                                                          source))
        return outs


class _Group(_Tupled):
    """``ops.segment_reduce`` by a label function (:func:`maybe_group`):
    ``group`` is ``(op, label, value, nseg)``, a slab's partial the flat
    tuple of the folded value's leaves (merged by the fold's own
    operator), then the int32 counts (added), and the result the pair
    ``(folded tree, counts)`` of bolt arrays keyed by group, as the
    resident terminal's (``BoltArrayTPU._grouped_fold``).  A ``mean``
    folds SUMS; the quotient is the finalise's."""

    def __init__(self, group, source):
        leaves = jax.tree_util.tree_leaves(self._avals(source, group[2]))
        fop = _Sum() if group[0] in ("sum", "mean") else _Extremum(group[0])
        super().__init__("group", comps=(fop,) * len(leaves) + (_Sum(),),
                         params=tuple(group))

    @staticmethod
    def _avals(source, value):
        """What ``value`` gives for ONE staged record (a tree of avals)."""
        from bolt_tpu.tpu.array import _cached_eval_shape
        st = result_state(source)
        rec = jax.ShapeDtypeStruct(tuple(st.vshape), st.dtype)
        if value is None:
            return rec
        return _cached_eval_shape(
            ("segreduce-value", value, tuple(rec.shape), str(rec.dtype)),
            lambda: jax.eval_shape(value, rec))

    @property
    def members(self):
        from bolt_tpu.utils import code_token
        return "/".join(code_token(x) if callable(x) else repr(x)
                        for x in self.params)

    def partial(self, x, stages, pred, post, split, apply, axes):
        """It IS the resident terminal's fold, through the same entry,
        ``fold.fold_records``, with the slab as its stored table: the
        record-wise maps in front (:func:`maybe_group` admits no other
        stage), the predicate, the maps behind it, the label and the
        value traced into one pass, which over thin records in a program
        for one TPU device is the ``thin_fold`` kernel."""
        from bolt_tpu.tpu import fold as _fold
        from bolt_tpu.tpu.array import _Filter, _chain_apply
        op, label, value, nseg = self.params
        funcs = tuple(s[1] for s in stages)
        post = tuple(s[1] for s in post)
        if op == "mean":
            op, value = "sum", _promoted(value)
        if pred is None:
            src = _fold.Chain(funcs, 1)
        else:
            rec = jax.eval_shape(lambda d: _chain_apply(funcs, 1, d), x)
            one = jax.ShapeDtypeStruct(rec.shape[1:], rec.dtype)
            src = _Filter(None, funcs, pred, 1, tuple(one.shape), x.shape[0],
                          one.dtype, post, jax.eval_shape(
                              lambda r: _chain_apply(post, 0, r), one))
        folded, counts = _fold.fold_records(
            _fold.Fold(src, group=(op, label, value, nseg)), x)
        return tuple(jax.tree_util.tree_leaves(folded)) + (counts,)

    def finalise(self, folded, source):
        from bolt_tpu.tpu.array import _constrain
        op, _, value, nseg = self.params
        tree = jax.tree_util.tree_structure(self._avals(source, value))
        mesh = source.mesh
        sig = tuple((tuple(x.shape), str(x.dtype)) for x in folded)
        key = ("stream-final-group", op, nseg, tree, sig, mesh)

        def build():
            def final(*parts):
                counts = parts[-1]
                outs = []
                for out in parts[:-1]:
                    if op == "mean":
                        out = out / jnp.maximum(counts, 1).astype(
                            out.dtype).reshape(
                                (nseg,) + (1,) * (out.ndim - 1))
                    outs.append(_constrain(out, mesh, 1))
                return (jax.tree_util.tree_unflatten(tree, outs),
                        _constrain(counts, mesh, 1))
            return jax.jit(final)
        return _cached_jit(key, build)(*folded)

    def wrap(self, out, mesh):
        from bolt_tpu.tpu.array import BoltArrayTPU
        wrap = lambda o: BoltArrayTPU(o, 1, mesh)      # noqa: E731
        return jax.tree_util.tree_map(wrap, out[0]), wrap(out[1])

    def tally(self, nslabs, kernel_slabs):
        return {"group": nslabs}


class _Gram(_Tupled):
    """A Gram matrix over the sample axes (:func:`maybe_gram`): ``gram``
    is ``(sample axes, precision, second_conj, sums)``, a slab's partial
    ``(G,)`` or ``(G, s)``, added, and the result those device arrays."""

    def __init__(self, gram):
        super().__init__("gram", comps=(_Sum(),) * (1 + gram[3]),
                         params=tuple(gram))

    @property
    def members(self):
        return repr(self.params)

    def partial(self, x, stages, pred, post, split, apply, axes):
        """The resident program's own body (``ops/linalg.py ::
        _pca_program``) over the slab as its stages leave it (no filter:
        :func:`gram_refusal`): the features merged and widened by
        ``_features_last``, the samples contracted where they lie by
        ``_sample_gram``, in a program for one TPU device the
        ``packed_gram`` kernel."""
        from bolt_tpu.ops import linalg as _linalg
        for stg in stages:
            x = apply(stg, split, x)
        m, precision, second_conj, sums = self.params
        x, widened = _linalg._features_last(x, x.shape[:m],
                                            prod(x.shape[m:]))
        out = _linalg._sample_gram(x, precision, second_conj=second_conj,
                                   widened=widened, sums=sums)
        return tuple(out) if sums else (out,)

    def tally(self, nslabs, kernel_slabs):
        return {"gram": nslabs, "gram_kernel": kernel_slabs}


# ---------------------------------------------------------------------
# per-slab programs and the pairwise fold of their partials
# ---------------------------------------------------------------------

def _split_at_filter(stages):
    """``(head, pred, post)``: the stages in front of the filter, its
    predicate (``None``: no filter) and the record-wise maps behind it."""
    for i, stage in enumerate(stages):
        if stage[0] == "filter":
            return stages[:i], stage[1], stages[i + 1:]
    return stages, None, ()


def _promoted(value):
    """``value`` with every leaf that is not inexact cast to the canonical
    float, as a grouped ``mean`` casts it before it sums
    (``tpu/array.py :: _grouped_fold_expr``): a slab's partial of a mean
    is the SUMS of that.  Made once a slab program's build, which the
    engine keeps; nothing else holds the caller's function."""
    def promoted(r):
        floats = jax.dtypes.canonicalize_dtype(np.float64)
        return jax.tree_util.tree_map(
            lambda lf: lf if jnp.issubdtype(jnp.result_type(lf),
                                            jnp.inexact)
            else jnp.asarray(lf).astype(floats),
            r if value is None else value(r))
    return promoted


def _slab_program(source, terminal, slab_shape, fused=False, sharded=False,
                  codec_obj=None, thin=False):
    """The ONE compiled program each slab runs: device-side stages +
    ``terminal``'s (masked) partial (:meth:`_Terminal.partial`), with the
    slab buffer DONATED so the ring recycles its memory.  ``fused=True``
    is the level-0 fold fusion: the program additionally takes the
    PREVIOUS slab's partial and merges it in the same dispatch
    (``prog(buf, acc)``, :meth:`_Terminal.combine`), halving fold
    dispatches — the acc is donated too, it is consumed.  ``thin``: the
    uploaded buffer is the dense form of a slab of thin records
    (:func:`_dense_views`), given its shape by :func:`_reseat` first.

    ``codec_obj`` (ISSUE 14) is the ingest codec whose device-side
    DECODE is the program's FIRST traced expression: the uploaded buffer
    is the wire representation (plus sidecar leaves, donated like the
    raw slab was), and the decoded values feed the same stage chain and
    partial the uncompressed program traces — no extra HBM pass.

    ``sharded=True`` is the POD form (``parallel.multihost``): the same
    partial body under ``shard_map`` with the terminal's cross-host
    collectives in it, so the program's output is the ALREADY-GLOBAL
    pair partial, replicated on every process (``out_specs=P()``).  The
    level-0 acc merge stays an elementwise combine on replicated values
    outside the shard_map; codec decode happens per shard INSIDE it
    (sidecar codecs are refused on pods before any thread starts).
    Engine-cached per (stages, terminal, slab geometry, fused, codec,
    process topology): uniform slabs compile once a variant A PROCESS."""
    stages, pred, post = _split_at_filter(source.stages)
    split = source.split
    mesh = source.mesh
    raw_dtype = source.dtype
    delta_ok = split < len(source.shape)
    keyed, _ = stage_extras(stages + post)
    key = ("stream-slab-acc" if fused else "stream-slab", terminal,
           stage_keys(stages), pred, slab_shape, str(source.dtype), split,
           mesh, _multihost.topology_token() if sharded else None,
           codec_obj.name if codec_obj is not None else None,
           stage_keys(post), thin)

    def build():
        axes = _multihost.key_collective_axes(mesh, slab_shape, split) \
            if sharded else None

        def partial(data, *extra):
            # under shard_map ``data`` is ONE device shard; standalone it
            # is the whole slab (the body is shape-polymorphic).
            # ``extra``: the slab's first key (a keyed stage chain) then
            # the side operands; empty for every other chain
            key0 = extra[0] if keyed else None
            operands = iter(extra[1:] if keyed else extra)
            if codec_obj is None:
                x = _reseat(data, raw_dtype) if thin else data
            elif codec_obj.sidecar:
                x = codec_obj.decode(data[0], data[1:], raw_dtype, delta_ok)
            else:
                x = codec_obj.decode(data, (), raw_dtype, delta_ok)
            return terminal.partial(
                x, stages, pred, post, split,
                lambda stg, at, v: _stage_apply(stg, at, v, key0, operands),
                axes)

        if sharded:
            from jax.sharding import PartitionSpec
            from bolt_tpu import _compat
            from bolt_tpu.parallel.sharding import key_spec
            # check_vma=False: the outputs ARE replicated (every leaf
            # comes out of a psum/pmin/pmax over the sharding axes, and
            # shards along non-participating axes compute from identical
            # replicated inputs), but older runtimes' replication
            # checker cannot always prove it through the staged bodies
            body = _compat.shard_map(
                partial, mesh, in_specs=key_spec(mesh, slab_shape, split),
                out_specs=PartitionSpec(), check_vma=False)
        else:
            body = partial

        if not fused:
            return jax.jit(body, donate_argnums=(0,))

        def run(data, acc, *extra):
            # level-0 fold fused in: acc (the EVEN slab's partial) merges
            # with this (ODD) slab's partial inside one dispatch
            return terminal.combine(acc, body(data, *extra))
        return jax.jit(run, donate_argnums=(0, 1))

    return _cached_jit(key, build)


class _PairFold:
    """Binary-counter pairwise tree over streamed PAIR partials (level-0
    merges are fused into the odd slab programs): leaf *i* merges at
    tree level ``trailing_zeros(i)``, so the fold depth is log2(nleaves)
    and no more than log2(n) partials are ever alive.  The merge program
    resolves LAZILY on the first actual merge — a 1- or 2-slab stream
    never builds (or counts) it."""

    __slots__ = ("_factory", "_merge", "levels")

    def __init__(self, merge_factory):
        self._factory = merge_factory
        self._merge = None
        self.levels = []

    def merge(self, a, b):
        if self._merge is None:
            self._merge = self._factory()
            self._factory = None        # hold nothing beyond the program
        return self._merge(a, b)

    def push(self, x):
        lvl = 0
        while lvl < len(self.levels) and self.levels[lvl] is not None:
            x = self.merge(self.levels[lvl], x)
            self.levels[lvl] = None
            lvl += 1
        if lvl == len(self.levels):
            self.levels.append(x)
        else:
            self.levels[lvl] = x

    def result(self):
        acc = None
        for x in self.levels:
            if x is None:
                continue
            acc = x if acc is None else self.merge(x, acc)
        return acc


def _stage_token(stage):
    """One stage's fingerprint element: the kind, every callable by its
    BYTECODE token (``utils.code_token`` — two lambdas with different
    bodies differ, unlike ``__name__``), every plain value by repr."""
    from bolt_tpu.utils import code_token
    return "/".join(code_token(x) if callable(x) else repr(x)
                    for x in stage)


def _run_fingerprint(source, terminal, codec=None):
    """Identity of one LOGICAL streamed run for checkpoint matching:
    source geometry + slab plan + stage chain + terminal + ingest
    CODEC, with every user callable (stage funcs, the filter predicate,
    a ``reduce``'s function, a group's label and value, a callback
    source's ``produce``) identified by its bytecode digest — an EDITED
    pipeline over the same dir is refused, never resumed wrong, and a
    resumed run never adopts a checkpoint cut under a DIFFERENT codec
    (the fold partials are decoded values; mixing an uncompressed
    prefix with a quantised tail would be silently wrong, so a codec
    change restarts from scratch).  Closure DATA is not hashable (no
    checkpoint format's is): re-pointing an identical loader at
    different bytes of the same geometry is the caller's contract, as
    with any resume system."""
    from bolt_tpu.utils import code_token
    stages = "|".join(_stage_token(s) for s in source.stages)
    rfunc = terminal.rfunc
    return ("bolt-stream-ckpt-v2", str(terminal.name), str(terminal.ddof),
            code_token(rfunc) if rfunc is not None else "",
            "x".join(str(s) for s in source.shape),
            int(source.split), str(source.dtype), int(source.slab),
            str(source.kind),
            code_token(source.produce) if source.produce is not None
            else "", stages, terminal.members, str(codec or ""))


# ---------------------------------------------------------------------
# the executor
# ---------------------------------------------------------------------

# the most recent prefetch/dispenser thread and full pool
# (introspection for the fault tests)
_LAST_THREAD = None
_LAST_POOL = ()


class _Reseq:
    """Slab-order re-sequencing buffer between the uploader pool and the
    consumer: workers insert completed slabs by index, the consumer pops
    them STRICTLY in slab order — the fold stays deterministic and
    bit-exact no matter which upload finishes first.  Also the fault
    funnel: the first worker exception is recorded and re-raised in the
    consumer, and a liveness poll catches pool threads that died without
    delivering (the ``q.get()``-blocks-forever bug)."""

    __slots__ = ("_cond", "_slots", "_next", "_exc", "_total", "_fenced",
                 "_dead_err")

    def __init__(self):
        self._cond = _lockdep.condition("stream.reseq")
        self._slots = {}
        self._next = 0
        self._exc = None
        self._total = None
        self._fenced = 0
        self._dead_err = None

    def put(self, i, item):
        """Insert slab ``i``; returns False (dropping ``item``) for an
        index already handed to the consumer or already queued — the
        retry FENCE: a late duplicate from a slab's earlier attempt can
        never double-fold, whatever interleaving delivered it."""
        with self._cond:
            if i < self._next or i in self._slots:
                self._fenced += 1
                return False
            self._slots[i] = item
            self._cond.notify_all()
            return True

    @property
    def fenced(self):
        """Duplicate deliveries dropped by the fence."""
        with self._cond:
            return self._fenced

    def fault(self, exc):
        """Record the FIRST failure (later ones are consequences)."""
        with self._cond:
            if self._exc is None:
                self._exc = exc
            self._cond.notify_all()

    def finish(self, total):
        """All slabs dispensed: ``total`` is the slab count."""
        with self._cond:
            self._total = total
            self._cond.notify_all()

    def drain(self):
        """Release every queued ring buffer (abort path)."""
        with self._cond:
            self._slots.clear()

    def _dead(self, threads):
        """Pointed error naming the dead pool threads — the fix for the
        q.get()-blocks-forever bug.  Fires ONCE per dead thread set:
        each dead thread is named exactly once (a pool with 2 dead
        workers must not repeat the list), and repeated polls over the
        same set return the SAME error object, so a chained message
        cannot accumulate duplicates."""
        dead = [t for t in threads if not t.is_alive()] or threads
        key = tuple(sorted(t.ident or id(t) for t in dead))
        cached = self._dead_err
        if cached is not None and cached[0] == key:
            return cached[1]
        names = list(dict.fromkeys(repr(t.name) for t in dead))
        err = RuntimeError(
            "streaming prefetch thread(s) %s died without delivering "
            "slab %d or an error (thread killed before it could enqueue "
            "— e.g. interpreter teardown); the stream cannot complete"
            % (", ".join(names), self._next))
        self._dead_err = (key, err)
        return err

    def next(self, threads, workers=None, timeout=0.1, stall_limit=300,
             idle=None):
        """The next ``(slab_i, item)`` in slab order, or ``None`` at
        end-of-stream.  Re-raises a recorded pool fault; polls with a
        timeout and liveness checks so pool threads that died WITHOUT
        enqueueing (interpreter teardown, a killed thread) surface as a
        pointed error instead of blocking the consumer forever:

        * every INGESTING thread (``workers``, else all of ``threads``)
          dead with the needed slab undelivered → nothing can ever
          arrive, raise immediately (the dispenser alone cannot upload,
          so it blocking on ring permits must not mask dead workers);
        * the lead dispenser dead before announcing the slab count,
          workers alive but starved of jobs → raise after
          ``stall_limit`` polls with no new delivery (~30 s grace so a
          genuinely slow in-hand upload is not mistaken for the hang).

        ``idle`` (when given) runs OUTSIDE the lock after each poll that
        delivered nothing — the arbiter-backed runs' starvation valve:
        the consumer confirms already-retired in-flight windows there,
        releasing budget bytes the (possibly blocked) dispenser is
        waiting on, so a budget smaller than one run's full ring
        degrades to a shallower pipeline instead of a deadlock.
        """
        ingesters = threads if workers is None else workers
        lead = threads[0]
        stalls = 0
        seen = -1
        while True:
            with self._cond:
                # deliverable in-order slabs drain BEFORE a recorded
                # fault raises: they are complete uploads that fold
                # normally, and consuming them advances the resumable
                # checkpoint watermark to the true last retired slab —
                # the fault still re-raises on the first missing slab
                if self._next in self._slots:
                    i = self._next
                    self._next += 1
                    return i, self._slots.pop(i)
                if self._exc is not None:
                    raise self._exc
                if self._total is not None and self._next >= self._total:
                    return None
                if not any(t.is_alive() for t in ingesters):
                    raise self._dead(threads)
                if not lead.is_alive() and self._total is None:
                    # a delivery (even out-of-order) is progress: a
                    # worker finished an in-hand slab — reset the clock
                    if len(self._slots) != seen:
                        seen = len(self._slots)
                        stalls = 0
                    stalls += 1
                    if stalls > stall_limit:
                        raise self._dead(threads)
                self._cond.wait(timeout)
            if idle is not None:
                idle()


def _acquire(sem, stop):
    """Ring-permit acquire that gives up when the run is aborting (a
    pool thread must never deadlock on a dead main loop)."""
    while not stop.is_set():
        if sem.acquire(timeout=0.05):
            return True
    return False


class _Run:
    """What one streamed run is held to, resolved ONCE on the calling
    thread (the scopes are per-thread: a pool thread could not), for
    both consumers of the ingest pool alike."""

    __slots__ = ("depth", "nwork", "codec", "delta_ok", "wire_item",
                 "mspec", "pod", "tenant", "lease", "nretry", "ingest",
                 "compute", "_ready")

    def __init__(self, source):
        # seconds of the regions the obs spans cover: the pool's
        # ``stream.ingest``, the consumer's ``stream.compute`` + ``.sync``
        self.ingest = self.compute = 0.0
        self._ready = False
        self.depth = prefetch_depth()
        self.nwork = pool_size(source)
        # the source's own codec= wins over the scope; integer/bool
        # pipelines refuse lossy codecs pointedly (Codec.wire_dtype)
        codec_obj = self.codec = resolve_codec(source)
        self.delta_ok = source.split < len(source.shape)
        # the arbiter leases WIRE bytes, what occupies the ring and
        # crossed the link (analysis.admission_floor_bytes: same ratio)
        self.wire_item = (codec_obj.wire_dtype(source.dtype).itemsize
                          if codec_obj is not None
                          else source.dtype.itemsize)
        # a mesh spanning processes: this run is one of N peers over the
        # SAME slab schedule, each ingesting its own shard of every slab
        self.mspec = None
        if _multihost.mesh_process_count(source.mesh) > 1:
            err = _multihost.slab_divisibility_error(
                source.mesh, source.shape, source.split,
                source.slab_ranges() if source.kind == "callback" else [])
            if err is not None:
                raise ValueError(err)       # BLT012 — check() forecasts it
            err = _multihost.sidecar_codec_error(codec_obj, source.mesh)
            if err is not None:
                raise ValueError(err)       # per-process sidecars cannot
                #                             feed a shard_map slab program
            self.mspec = _multihost.local_slab_spec(source)
        self.pod = self.mspec is not None
        # the tenant tag rides into the pool threads, so their transfers
        # land in the submitter's counters, and under an ACTIVE serving
        # arbiter the run leases its slab bytes from the process-wide
        # budget instead of assuming sole ownership of device memory
        # (through sys.modules: streaming never imports bolt_tpu.serve)
        self.tenant = _engine.current_tenant()
        sv = sys.modules.get("bolt_tpu.serve")
        arb = sv.device_arbiter() if sv is not None else None
        self.lease = (arb.lease(self.tenant or "default")
                      if arb is not None else None)
        self.nretry = retry_limit()

    def enter(self):
        """The consumer's loop begins.  The supervisor must not reform
        the pod UP under a live collective schedule — this counter is
        what its quiesce drain waits on (parallel.supervisor)."""
        if self.pod:
            _podwatch.pod_enter()

    def ready(self):
        """Before a dispatch; on a pod the FIRST call is the readiness
        rendezvous (ISSUE 12): confirm every peer is alive over the
        heartbeat transport BEFORE a dispatch enters the runtime — a
        peer that died raises the pointed PeerLostError within ~2x
        BOLT_POD_TIMEOUT instead of ~30s in gloo's connect."""
        if self.pod and not self._ready:
            _podwatch.ready_rendezvous()
            self._ready = True

    def leave(self):
        """The consumer's way out: every outstanding budget byte back."""
        if self.pod:
            _podwatch.pod_exit()
        if self.lease is not None:
            self.lease.close()


class _IngestPool:
    """The uploader pool of one streamed run: host blocks in, uploaded
    slabs out STRICTLY in slab order, never more than ``ring`` of them
    dispensed and not yet given back.

    A callback source is driven by ``jobs``, a list of ``(index, lo,
    hi)``: the lead thread takes a ring permit AND the run's arbiter
    bytes per job in list order (a tenant's own slabs can then never
    deadlock each other by acquiring out of order) and ``run.nwork``
    workers produce AND upload their own slabs concurrently.  An
    iterator source (``jobs=None``) is driven by ``blocks``, a callable
    giving the iterator of ``(lo, hi, block)``: ONE thread pulls and
    uploads, its slabs indexed from ``first``.  The consumer calls
    :meth:`start`, takes ``(index, buf, nbytes, seconds, hi)`` from
    :meth:`next` until ``None``, gives slots and bytes back as its
    programs retire, and ALWAYS calls :meth:`close`.  ``noun`` names a
    slab in the retry errors; ``parent`` is the run's span the
    ``stream.ingest`` spans nest under (nesting does not cross
    threads).  ``dense``: the consumer's slab programs take a slab as
    :func:`_dense_views` of it (:func:`dense_route`)."""

    def __init__(self, run, source, ring, jobs=None, blocks=None, first=0,
                 noun="slab", parent=None, dense=False):
        self._run = run
        self._source = source
        self._dense = dense     # the consumer re-seats dense slabs
        self._jobs = jobs
        self._blocks = blocks or source.slabs
        self._first = first
        self._noun = noun
        self._parent = parent
        self._permits = threading.Semaphore(ring)
        self._stop = threading.Event()
        self._rsq = _Reseq()
        self._jobq = queue.Queue()
        # concurrent uploaders at once, and the most there ever were
        self._hw_lock = _lockdep.lock("stream.uploader_hw")
        self._active = 0
        self.high_water = 0
        self.parts = 0          # per-device sub-blocks the slabs were put as
        lead = threading.Thread(
            target=self._prefetch if jobs is None else self._dispense,
            name="bolt-stream-prefetch", daemon=True)
        workers = () if jobs is None else tuple(
            threading.Thread(target=self._work, args=(w,),
                             name="bolt-stream-upload-%d" % w, daemon=True)
            for w in range(run.nwork))
        self.threads = (lead,) + workers
        self._ingesters = workers or (lead,)    # who delivers slabs

    # -- the consumer's surface ---------------------------------------

    def start(self):
        global _LAST_THREAD, _LAST_POOL
        _LAST_THREAD, _LAST_POOL = self.threads[0], self.threads
        for th in self.threads:
            th.start()

    def next(self, idle=None):
        """The next slab in order, ``None`` at end-of-stream; re-raises
        a pool fault and names dead threads (:meth:`_Reseq.next`, which
        says what ``idle`` is for).  The call is the consumer's
        ``stream.wait.slab`` span: the time it is starved of uploads."""
        sp = _obs.begin("stream.wait.slab", parent=self._parent)
        try:
            got = self._rsq.next(self.threads, workers=self._ingesters,
                                 idle=idle)
            if got is None:
                return None
            if sp is not None:
                sp.set(slab=got[1][0])
            return got[1]
        finally:
            _obs.end(sp)

    def form(self, buf):
        """``(thin, shape)`` of a slab :meth:`next` handed over: whether
        it went up as :func:`_dense_views` of it, and the shape of the
        slab its program is built for."""
        if self._dense and isinstance(buf, tuple):
            return True, _dense_shape(buf, self._source.dtype)
        return False, (buf[0].shape if isinstance(buf, tuple)
                       else buf.shape)

    def give_back(self, slabs, nbytes):
        """Return ``slabs`` ring permits and ``nbytes`` lease bytes (the
        ``nbytes`` each slab came with: releases must mirror acquires or
        the serve budget drifts)."""
        if slabs:
            self._permits.release(slabs)
        if self._run.lease is not None:
            self._run.lease.release(nbytes)

    def retry(self, index, attempt, prev, exc, what=None):
        """One failed attempt at slab ``index``: burn a retry (record +
        chain the attempt's exception) or raise the run-poisoning final
        error, by the policy stream AND serve share
        (``utils.chain_retry_step``: at budget 0 the ORIGINAL exception
        propagates untouched)."""
        allowed = attempt < self._run.nretry and not self._stop.is_set()
        if allowed:
            _engine.record_stream_retry()
            _obs.event("stream.retry", slab=index, attempt=attempt + 1,
                       error=type(exc).__name__)
        return chain_retry_step(
            exc, prev, attempt, allowed,
            "%s %d" % (what or self._noun, index),
            "stream.retries / BOLT_STREAM_RETRIES")

    def close(self):
        """Stop, join every thread, release the queued ring buffers."""
        self._stop.set()
        # the consumer's OWN poison pills: a dispenser killed before its
        # finally ran leaves workers blocked in jobq.get(), and the joins
        # below would be the very hang the liveness guard reports
        for _ in self.threads:
            self._jobq.put(None)
        for th in self.threads:
            th.join()
        self._rsq.drain()

    # -- the pool threads ---------------------------------------------

    def _enter(self):
        with self._hw_lock:
            self._active += 1
            if self._active > self.high_water:
                self.high_water = self._active

    def _exit(self, buf=None):
        """One attempt over; ``buf`` is the slab it uploaded, if it did."""
        if isinstance(buf, tuple):
            buf = buf[0]            # a sidecar codec's (wire, *sidecar)
        with self._hw_lock:
            self._active -= 1
            if buf is not None:
                self.parts += len(buf.sharding.addressable_devices)

    def _local(self, lo, hi):
        """The records of slab ``[lo, hi)`` THIS process ingests: all of
        them, or on a pod its own shard, in global coordinates (an
        indivisible slab raises the pointed BLT012 error)."""
        mspec = self._run.mspec
        return (lo, hi) if mspec is None else mspec.local_range(lo, hi)

    def _encode_upload(self, block, lo, hi, llo):
        """Encode (when a codec is armed) + upload the host block that
        starts at record ``llo`` of slab ``[lo, hi)``; returns ``(buf,
        wire_nbytes)``.  ``buf`` is the bare sharded array, or for
        sidecar codecs a ``(wire, *sidecar)`` tuple whose every leaf the
        slab program donates.  Codecs change only the dtype, so the
        per-device placement math is untouched."""
        run, source = self._run, self._source
        side = ()
        if run.codec is None:
            payload = block
        else:
            payload, side = _encode_slab(run.codec, block, run.delta_ok)
        if run.mspec is None and self._dense and _goes_dense(payload):
            buf = _upload_slab(payload, source.mesh, source.split, True)
        elif run.mspec is None:
            # through the module-level name: the tests' patch point
            buf = _upload_slab(payload, source.mesh, source.split)
        else:
            buf = _upload_slab_mh(payload, source.mesh, source.split,
                                  run.mspec.slab_shape(lo, hi), llo - lo)
        if side:
            # int8's scale/zero point, counted through the ONE door
            buf = (buf,) + tuple(transfer(np.asarray(s)) for s in side)
        return buf, int(payload.nbytes)

    def _dispense(self):
        run, stop = self._run, self._stop
        rec_bytes = prod(self._source.shape[1:]) * run.wire_item
        try:
            for j, (g, lo, hi) in enumerate(self._jobs):
                if not _acquire(self._permits, stop):
                    return
                if run.lease is not None:
                    llo, lhi = self._local(lo, hi)
                    if not run.lease.acquire((lhi - llo) * rec_bytes,
                                             stop=stop):
                        return
                self._jobq.put((j, g, lo, hi))
            self._rsq.finish(len(self._jobs))
        except BaseException as exc:        # noqa: BLE001 — re-raised in
            self._rsq.fault(exc)            # the consumer thread
        finally:
            for _ in self._ingesters:
                self._jobq.put(None)        # poison pills: pool drains

    def _work(self, wid):
        run, source, stop = self._run, self._source, self._stop
        try:
            with _engine.tenant(run.tenant):
                while True:
                    # the dispenser hands a job out the moment it holds
                    # a permit, so a worker with none to take waits for
                    # the ring: for the consumer, or the device
                    wsp = _obs.begin("stream.wait.ring",
                                     parent=self._parent, worker=wid)
                    try:
                        job = self._jobq.get()
                        if wsp is not None and job is not None:
                            wsp.set(slab=job[1])
                    finally:
                        _obs.end(wsp)
                    if job is None or stop.is_set():
                        return
                    j, g, lo, hi = job
                    attempt = 0
                    prev = None
                    while True:
                        self._enter()
                        sp = _obs.begin("stream.ingest",
                                        parent=self._parent, slab=g,
                                        worker=wid, attempt=attempt)
                        t0 = _clock()
                        try:
                            llo, lhi = self._local(lo, hi)
                            block = source.produce_slab(llo, lhi)
                            buf, bnb = self._encode_upload(block, lo, hi,
                                                           llo)
                            tsec = _clock() - t0
                            if sp is not None:
                                sp.set(bytes=bnb, lo=lo, hi=hi)
                        except BaseException as exc:  # noqa: BLE001
                            _obs.end(sp, error=type(exc).__name__)
                            self._exit()
                            # retry IN PLACE on this worker (the job
                            # keeps its ring permit and arbiter bytes);
                            # the re-sequencer fences any duplicate
                            prev = self.retry(g, attempt, prev, exc)
                            attempt += 1
                            continue
                        _obs.end(sp)
                        self._exit(buf)
                        break
                    del block          # bnb = the LOCAL WIRE bytes this
                    #                    process acquired and uploaded
                    self._rsq.put(j, (g, buf, bnb, tsec, hi))
        except BaseException as exc:        # noqa: BLE001 — re-raised in
            self._rsq.fault(exc)            # the consumer thread

    def _prefetch(self):
        """The ingest span/time covers produce AND upload, like a
        worker's; the ring permit is taken BEFORE the pull (one host
        block in hand, never two), the arbiter bytes after it (the size
        of an iterator's slab is known only with the block in hand)."""
        run, stop = self._run, self._stop
        j = 0
        try:
            with _engine.tenant(run.tenant):
                it = self._blocks()
                while True:
                    g = self._first + j
                    wsp = _obs.begin("stream.wait.ring",
                                     parent=self._parent, slab=g)
                    try:
                        if not _acquire(self._permits, stop):
                            return      # the run is aborting
                    finally:
                        _obs.end(wsp)
                    buf = None
                    self._enter()
                    sp = _obs.begin("stream.ingest", parent=self._parent,
                                    slab=g)
                    t0 = _clock()
                    try:
                        try:
                            lo, hi, block = next(it)
                        except StopIteration:
                            _obs.cancel(sp)   # probe saw end-of-source
                            sp = None
                            self._permits.release()  # unused hand slot
                            break
                        # on a pod every process walks the SAME
                        # re-iterable block sequence and keeps its slice
                        llo, lhi = self._local(lo, hi)
                        block = block[llo - lo:lhi - lo]
                        if run.lease is not None and not run.lease.acquire(
                                int(block.size) * run.wire_item, stop=stop):
                            return
                        attempt = 0
                        prev = None
                        while True:
                            try:
                                buf, bnb = self._encode_upload(
                                    block, lo, hi, llo)
                                break
                            except BaseException as exc:  # noqa: BLE001
                                # the block is in hand (an iterator
                                # cannot re-produce it), so the retry
                                # budget covers the ENCODE + UPLOAD here
                                prev = self.retry(g, attempt, prev, exc)
                                attempt += 1
                        tsec = _clock() - t0
                        if sp is not None:
                            sp.set(bytes=bnb, lo=lo, hi=hi)
                    finally:
                        _obs.end(sp)
                        self._exit(buf)
                    del block
                    self._rsq.put(j, (g, buf, bnb, tsec, hi))
                    j += 1
                self._rsq.finish(j)
        except BaseException as exc:        # noqa: BLE001
            self._rsq.fault(exc)


def _skip_retired(source, nslabs, nrecords):
    """The block iterator of an iterator source with the ``nslabs`` slabs
    a resume checkpoint already retired drained off it, checking that
    the block layout still cuts at the checkpointed record (a drifted
    iterator would silently corrupt the fold — refuse instead)."""
    it = source.slabs()
    skipped_hi = 0
    for k in range(nslabs):
        try:
            _, skipped_hi, blk = next(it)
        except StopIteration:
            raise RuntimeError(
                "resume checkpoint covers %d slabs but this iterator "
                "ended after %d; the source is not the one the "
                "checkpoint was cut from" % (nslabs, k))
        del blk
    if skipped_hi != nrecords:
        raise RuntimeError(
            "resume checkpoint was cut at record %d but this iterator's "
            "first %d slab(s) cover %d records — the block layout "
            "drifted; delete the checkpoint or restore the original "
            "source" % (nrecords, nslabs, skipped_hi))
    return it


def _pod_sync(x, pod, phase, slab=None):
    """``block_until_ready`` with the pod watchdog armed (ISSUE 11).

    Single-process (``pod=False``) this is a plain block.  On a pod the
    value may depend on a cross-host collective a DEAD peer will never
    complete: the watchdog first polls readiness
    (``podwatch.wait_ready`` — a latched dead peer raises the pointed
    ``PeerLostError`` instead of hanging this survivor in the runtime),
    then blocks for the value, classifying any transport failure
    (gloo connection closed — the fast shape of peer death) into the
    same ``PeerLostError`` via ``podwatch.reraise``."""
    if not pod:
        jax.block_until_ready(x)
        return
    _podwatch.wait_ready(x, phase=phase, slab=slab)
    try:
        jax.block_until_ready(x)
    except _podwatch.PeerLostError:
        raise
    except Exception as exc:          # noqa: BLE001 — classified
        _podwatch.reraise(exc, phase=phase, slab=slab)


@contextlib.contextmanager
def _undonated_ok():
    """Around a slab's dispatch: backends without donation (the CPU dev
    mesh) warn that the donated slab was unusable, and a place call's is
    never aliased (no output has its shape): noise once a geometry."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


def _retired(handle):
    """Whether the slab program that returned ``handle`` (an array, or
    the tuple a tupled partial is) is done, asked without blocking
    (through the module-level name: the tests' patch point, where a
    CPU's programs are done as soon as dispatched)."""
    return all(x.is_ready() for x in jax.tree_util.tree_leaves(handle))


class _Window:
    """The confirm window of one streamed run: the slab programs its
    consumer has dispatched and not yet confirmed done, oldest first.
    Confirming a call hands its slabs' ring permits and lease bytes back
    to the pool; WHEN to block for one is the consumer's policy.

    ``phase`` names the block to the pod watchdog; ``attrs`` are the
    consumer's own attributes of a ``stream.sync`` span; ``failed(slab,
    exc)`` builds the error a failed confirm raises (``None``: the
    failure itself); ``settle(handle, slab)`` runs between the confirm
    and the give-back (the spill leg persists the part it confirmed)."""

    def __init__(self, run, pool, phase, attrs=None, failed=None,
                 settle=None):
        self._run = run
        self._pool = pool
        self._phase = phase
        self._attrs = attrs or {}
        self._failed = failed
        self._settle = settle
        self._calls = deque()   # (slabs covered, handle, lease bytes, slab)
        self._lone = 0
        self.unconfirmed = 0    # slabs dispatched and not confirmed
        self.high_water = 0
        self.early = 0          # slabs retire() confirmed before it had to

    def _grew(self, slabs):
        self.unconfirmed += slabs
        self.high_water = max(self.high_water, self.unconfirmed)

    def lone(self):
        """A slab dispatched whose handle the NEXT call consumes
        (``execute``'s even slab, donated into its pair's program):
        unconfirmed from now, covered by that call's push."""
        self._lone += 1
        self._grew(1)

    def push(self, cover, handle, nbytes, slab=None):
        """A call dispatched: ``handle`` is ready when it is done and
        stands for ``cover`` slabs (the lone ones before it among them)
        and ``nbytes`` lease bytes; ``slab`` names it on its span."""
        self._calls.append((cover, handle, nbytes, slab))
        self._grew(cover - self._lone)
        self._lone = 0

    def confirm_oldest(self):
        """Block for the OLDEST unconfirmed call and hand its permits and
        lease bytes back.  On a pod the block rides the watchdog: a call
        whose collective a dead peer will never complete raises the
        pointed PeerLostError instead of hanging this survivor.  The
        entry leaves only once confirmed: what a failure leaves,
        :meth:`release` gives back."""
        cover, handle, nbytes, slab = self._calls[0]
        named = {} if slab is None else {"slab": slab}
        t0 = _clock()
        ssp = _obs.begin("stream.sync", slabs=cover, **self._attrs, **named)
        try:
            _pod_sync(handle, self._run.pod, self._phase, slab=slab)
        except _podwatch.PeerLostError:
            raise
        except Exception as exc:  # noqa: BLE001
            if self._failed is None:
                raise
            raise self._failed(slab, exc) from exc
        finally:
            _obs.end(ssp)
        if self._settle is not None:
            self._settle(handle, slab)
        self._calls.popleft()
        del handle              # a spilled part goes before its permit
        self.unconfirmed -= cover
        self._run.compute += _clock() - t0
        self._pool.give_back(cover, nbytes)

    def retire(self, keep):
        """Confirm the oldest calls, blocking, until at most ``keep``
        SLABS stay unconfirmed (a place call covers one, a pair partial
        two, and a lone slab counts before any call covers it), and
        WITHOUT blocking every head of the window that is done already:
        its permits go back when the device lets go of the slabs, not a
        slab later (``early`` counts those slabs)."""
        while self._calls:
            if self.unconfirmed <= keep:
                if not _retired(self._calls[0][1]):
                    break
                self.early += self._calls[0][0]
            self.confirm_oldest()

    def starved(self):
        """The arbiter-backed starvation valve (``pool.next``'s
        ``idle``): with the feeder possibly blocked on budget bytes,
        confirm one call per empty poll so its bytes recycle — a budget
        under the full ring then runs a shallower window instead of
        deadlocking.  Opens ONLY under real arbiter contention (some
        acquire is queued): a feeder merely slow on I/O must not collapse
        the window into per-slab syncs.  Says whether it confirmed."""
        if self._calls and self._run.lease.arbiter.waiting():
            self.confirm_oldest()
            return True
        return False

    def release(self):
        """The run's way out, after the pool closed: every call still
        unconfirmed (a run that ended early) gives back what it holds."""
        while self._calls:
            cover, _, nbytes, _ = self._calls.popleft()
            self.unconfirmed -= cover
            self._pool.give_back(cover, nbytes)


def execute(arr, terminal, source=None):
    """Run the streamed reduction ``terminal`` (a :class:`_Terminal`)
    over ``arr``'s source: the parallel-ingest, async-dispatch pipeline
    described in the module docstring.  Returns what the terminal wraps
    its result as (:meth:`_Terminal.wrap`: a value-shaped
    ``BoltArrayTPU``, ``split=0``, for a statistic or a ``reduce``).
    ``source`` overrides ``arr._stream`` for callers resolving
    already-detached pending handles (``arr=None`` skips the strict gate
    — the handle was gated at creation)."""
    if source is None:
        source = arr._stream
    if arr is not None:
        _engine.strict_guard(arr, "stream.%s()" % terminal.name)
    if has_swap(source):
        # every terminal door resolves swaps before entering here; a
        # swap stage reaching the slab pipeline means a door was missed
        raise RuntimeError(
            "internal: execute() received a source with an unresolved "
            "swap stage — the terminal doors resolve swaps first "
            "(stream.resolve_swaps)")
    run = _Run(source)
    mesh, depth, nwork = source.mesh, run.depth, run.nwork
    codec_obj, pod, lease = run.codec, run.pod, run.lease
    # order statistics are bit-exactness-sensitive, so lossy codecs
    # refuse them
    if codec_obj is not None and not codec_obj.lossless \
            and terminal.order_sensitive:
        raise ValueError(
            "lossy codec %r refused for the order-statistic "
            "terminal(s) %s: min/max/ptp are exact by contract and "
            "a quantised extremum is never the answer the caller "
            "meant.  Use the lossless 'delta-f32' codec, or stream "
            "this terminal uncompressed" % (codec_obj.name, terminal.names))
    # resumable checkpointing (ISSUE 9): a per-source checkpoint dir
    # (fromcallback/fromiter checkpoint=) wins over the thread's
    # resumable() scope.  A matching checkpoint from a killed run is
    # loaded BEFORE any thread starts: the dispenser then skips the
    # already-retired slabs and the fold restarts from the persisted
    # accumulator — bit-identical, because the fold is a deterministic
    # function of (slab order, accumulator state) and both are exact.
    scope = checkpoint_scope()
    if source.ckpt is not None:
        ck_dir = source.ckpt
        ck_every = scope[1] if scope is not None else _CKPT_EVERY
    elif scope is not None:
        ck_dir, ck_every = scope
    else:
        ck_dir = ck_every = None
    start_slab = 0
    resume_records = 0
    ck_state = None
    ck_fp = None
    ck_remap = None
    if ck_dir is not None:
        from bolt_tpu import checkpoint as _ckptlib
        if pod and _multihost.mesh_process_count(mesh) \
                != _multihost.process_count():
            # the checkpoint rendezvous (multihost.barrier) is a
            # collective over the WHOLE runtime; a mesh spanning only a
            # subset of the pod's processes would leave non-participants
            # out of the barrier and hang the participants forever —
            # refuse pointedly instead
            raise ValueError(
                "resumable checkpointing on a SUB-POD mesh is not "
                "supported: this mesh spans %d of the runtime's %d "
                "processes, and the checkpoint rendezvous barrier "
                "covers the whole runtime.  Stream the checkpointed "
                "run on a mesh covering every process (or drop "
                "checkpoint=/resumable() for this sub-mesh run)"
                % (_multihost.mesh_process_count(mesh),
                   _multihost.process_count()))
        ck_fp = _run_fingerprint(
            source, terminal,
            codec=codec_obj.name if codec_obj is not None else None)
        # the MESH's multiprocess answer, not the runtime's: a
        # process-local mesh inside a multi-process runtime checkpoints
        # single-process (its peers are elsewhere; a barrier would hang)
        ck_info = {}
        got_ck = _ckptlib.stream_load(ck_dir, ck_fp, multiprocess=pod,
                                      info=ck_info)
        if got_ck is not None:
            start_slab, resume_records, ck_state = got_ck
            # topology remap (shrink-and-resume): the checkpoint was cut
            # by a different pod width; the adopted state is the
            # replicated global fold, and the remap is recorded in every
            # subsequent checkpoint this run writes
            ck_remap = ck_info.get("remapped_from")
            _engine.record_stream_resume()
            _obs.event("stream.resume", slabs=start_slab,
                       records=resume_records,
                       **({"remapped_from": ck_remap}
                          if ck_remap is not None else {}))
    # the retired slabs never reach the pool: a callback's jobs start
    # past them, an iterator's prefix is drained before its first pull
    jobs = blocks = None
    if source.kind == "callback":
        jobs = [(start_slab + k, lo, hi) for k, (lo, hi)
                in enumerate(source.slab_ranges()[start_slab:])]
    else:
        blocks = functools.partial(_skip_retired, source, start_slab,
                                   resume_records)
    total_slabs = len(jobs) if jobs is not None else None
    ring = fold_ring(source)
    # the consumer BLOCKS for a confirm (and hands permits back) once
    # MORE than this many slabs are dispatched and unconfirmed, so a slot
    # stays free for EVERY worker's hand (at `ring - 1` a pool of any
    # size ran two workers, started together: PERF.md section 5, PR 35)
    window = ring - nwork
    run_sp = _obs.begin("stream.run", terminal=terminal.name, depth=depth,
                        uploaders=nwork, kind=source.kind,
                        **({"codec": codec_obj.name}
                           if codec_obj is not None else {}))
    pool = _IngestPool(run, source, ring, jobs=jobs, blocks=blocks,
                       first=start_slab, parent=run_sp,
                       dense=dense_route(source))
    win = _Window(run, pool, "slab-partial sync")

    from bolt_tpu.tpu.array import _place_operands
    keyed, side = stage_extras(source.stages)
    side = _place_operands(side, mesh)      # once a run, not once a slab
    t_start = _clock()
    nslabs = 0
    nthin = 0                   # slabs that went up dense
    ngramk = 0                  # slabs a packed_gram program folded
    fold = None
    pend = None                 # even slab's partial awaiting its pair
    pend_bytes = 0              # that slab's arbiter bytes, still held
    pend_slabs = 0              # and ring permit (0 behind a partial a
    #                             checkpoint restored: another run's slab)
    done_records = resume_records   # records covered by retired slabs
    if ck_state is not None:
        # restore the EXACT fold state the checkpoint captured: the
        # pairwise-tree levels and the unpaired pair partial, as host
        # arrays — the merge/fused programs accept them directly (the
        # arithmetic is placement-independent, so the resumed result
        # stays bit-identical to the uninterrupted run)
        lv, pend = ck_state
        sample = next((x for x in lv if x is not None), pend)
        if sample is not None:
            fold = terminal.fold(mesh, sample)
            fold.levels = list(lv)

    def _starved():
        """The window's valve (``pool.next``'s ``idle``), and behind it
        the lone unpaired partial: once its program retires only a
        value-shaped partial lives, so holding its slab-sized bytes would
        starve the feeder forever on a one-slab-at-a-time budget."""
        nonlocal pend_bytes
        if not win.starved() and pend is not None and pend_bytes \
                and lease.arbiter.waiting():
            _pod_sync(pend, pod, "unpaired-partial sync")
            pool.give_back(0, pend_bytes)
            pend_bytes = 0

    def _fold_push(part):
        # level 0 is fused into the odd slab programs; the pairwise
        # tree of every terminal is level 1 and up
        nonlocal fold
        if fold is None:
            fold = terminal.fold(mesh, part)
        fold.push(part)

    def _write_checkpoint(abort=False):
        """Persist the retired-slab watermark + fold state: drain the
        async window first (permits and arbiter bytes release — the
        persisted state must cover exactly the retired slabs), pull the
        value-shaped partials to host, write atomically.

        ``abort=True`` is the failure-path write.  On a POD it skips
        the rendezvous barriers (peers may be dead or at other
        watermarks) and the meta advances only forward
        (``stream_save(rendezvous=False)``); the drain above still
        runs WATCHDOG-guarded, so the write only lands when every
        retired slab's collective actually completed — i.e. exactly
        when the abort watermark is rendezvous-consistent.  A partial
        hung on the dead peer raises PeerLostError out of the drain
        and the caller falls back to the last periodic checkpoint."""
        win.retire(0)
        state = (list(fold.levels) if fold is not None else [], pend)
        csp = _obs.begin("stream.checkpoint",
                         slabs=start_slab + nslabs)
        t0 = _clock()
        try:
            _pod_sync(state, pod, "checkpoint drain")
            nb = _ckptlib.stream_save(ck_dir, ck_fp, start_slab + nslabs,
                                      done_records, state,
                                      multiprocess=pod,
                                      rendezvous=not (abort and pod),
                                      remap_from=ck_remap,
                                      codec=codec_obj.name
                                      if codec_obj is not None else None)
            _engine.record_checkpoint(nb, _clock() - t0)
            if csp is not None:
                csp.set(bytes=nb)
        finally:
            _obs.end(csp)

    def _abort_checkpoint():
        """The run is failing (uploader death, source error, peer loss,
        a chaos-injected fault): persist the retired-slab watermark
        FIRST, so the next run over this source resumes from here and
        not from the last periodic checkpoint — best effort, never
        masking the original exception.  On a POD the write skips the
        rendezvous (peers may be dead) and lands only when the
        watchdog-guarded drain proves every retired slab's collective
        completed: the watermark is then rendezvous-consistent, and the
        fold partials are replicated values any survivor resumes from."""
        if ck_dir is not None and nslabs:
            try:
                _write_checkpoint(abort=True)
            except Exception:       # noqa: BLE001 — the original
                pass                # failure is the story

    pool.start()
    run.enter()
    try:
        try:
            while True:
                got = pool.next(idle=_starved if lease is not None
                                else None)
                if got is None:
                    break
                run.ready()
                slab_g, buf, slab_bytes, tsec, slab_hi = got
                run.ingest += tsec
                t0 = _clock()
                thin, wshape = pool.form(buf)
                nthin += thin
                csp = _obs.begin("stream.compute",
                                 slab=slab_g,
                                 **({"codec": codec_obj.name}
                                    if codec_obj is not None else {}))
                _chaos.hit("stream.dispatch")
                if pod:
                    # the pod collective seam: this dispatch enqueues a
                    # cross-host rendezvous on every process
                    _chaos.hit("multihost.collective")
                try:
                    with _undonated_ok():
                        try:
                            # with a codec armed the dispatch IS the
                            # fused on-device decode — surfaced on the
                            # timeline as a stream.decode span nested
                            # in this slab's stream.compute (ended in
                            # the finally so a faulting dispatch never
                            # leaks it)
                            dsp = (_obs.begin("stream.decode",
                                              codec=codec_obj.name,
                                              slab=slab_g)
                                   if codec_obj is not None else None)
                            try:
                                # (the slab's first key,) + operands
                                extra = side
                                if keyed:
                                    extra = (np.int32(slab_hi
                                                      - wshape[0]),) + side
                                # an odd slab's program has the
                                # level-0 fold with its pair fused in
                                fused = pend is not None
                                prog = _slab_program(
                                    source, terminal, wshape, fused=fused,
                                    sharded=pod, codec_obj=codec_obj,
                                    thin=thin)
                                xsp = _obs.begin("stream.dispatch",
                                                 slab=slab_g)
                                try:
                                    part = (prog(buf, pend, *extra)
                                            if fused
                                            else prog(buf, *extra))
                                finally:
                                    _obs.end(xsp)
                                # known once the call has lowered it
                                ngramk += getattr(prog, "gram_kernel",
                                                  False)
                            finally:
                                _obs.end(dsp)
                            if fused:
                                pend = None
                                _fold_push(part)
                                win.push(pend_slabs + 1, part,
                                         pend_bytes + slab_bytes)
                                pend_bytes = pend_slabs = 0
                            else:
                                pend, pend_bytes, pend_slabs = \
                                    part, slab_bytes, 1
                                win.lone()
                        except _podwatch.PeerLostError:
                            raise
                        except Exception as exc:  # noqa: BLE001
                            if not pod:
                                raise
                            # a dead peer fails the collective FAST on
                            # localhost TCP (gloo closes the socket) —
                            # classify into the pointed PeerLostError
                            # naming the peer and the in-flight slab
                            _podwatch.reraise(exc, phase="slab program",
                                              slab=slab_g)
                    # counted INSIDE the try, right after the fold state
                    # absorbed the slab: the abort-path checkpoint below
                    # keys its watermark off nslabs, and a watermark
                    # lagging the state would double-fold on resume
                    nslabs += 1
                    done_records = slab_hi
                    del buf, got           # the donated ring slot is free
                finally:
                    _obs.end(csp)
                run.compute += _clock() - t0
                # only once the window fills does the consumer block, and
                # then on the OLDEST pair partial, ~window slabs old; a
                # head that is done goes at once (by its own partial: the
                # slab was donated)
                win.retire(window)
                # resumable(): persist the fold state every ck_every
                # retired slabs (skipping the final slab of a known-size
                # stream — the run is about to finish and clear anyway)
                if ck_dir is not None and nslabs % ck_every == 0 \
                        and not (total_slabs is not None
                                 and nslabs >= total_slabs):
                    if pod:
                        # the slab-boundary QUIESCE gate (ISSUE 12): a
                        # supervisor folding a rejoined process back in
                        # asks running pod streams to stop HERE — the
                        # checkpoint just written is the resume point.
                        # Process 0 publishes its decision BEFORE the
                        # checkpoint, whose own rendezvous barriers
                        # fence the marker read, so every peer abandons
                        # the same watermark (PodQuiesceError,
                        # retryable like a peer loss) with no second
                        # standalone barrier per checkpoint
                        _podwatch.quiesce_pre(start_slab + nslabs)
                    _write_checkpoint()
                    if pod:
                        _podwatch.quiesce_gate(start_slab + nslabs,
                                               fenced=True)
            if pend is not None:
                # odd slab count: the unpaired tail partial joins the
                # tree as its own leaf (deterministic — slab order only)
                _fold_push(pend)
                pend = None
        except BaseException:
            _abort_checkpoint()
            raise
        finally:
            pool.close()
            win.release()

        if fold is None:
            raise RuntimeError(
                "stream produced no slabs (empty source?) — nothing to "
                "reduce; the materialised path owns empty-input rules")
        _chaos.hit("stream.fold")
        fsp = _obs.begin("stream.fold", final=True)
        t0 = _clock()
        try:
            out = terminal.finalise(fold.result(), source)
            # the ONE synchronisation point of the whole run (pod runs
            # sync through the watchdog: a tail collective hung on a
            # dead peer raises PeerLostError, never an infinite wait)
            _pod_sync(out, pod, "final result sync")
        except BaseException:
            # the fold state covers every retired slab, so a failure
            # here still leaves the best possible resume point
            _abort_checkpoint()
            raise
        finally:
            _obs.end(fsp)
        if ck_dir is not None:
            # success: a finished run leaves NO stale checkpoint behind
            _ckptlib.stream_clear(ck_dir, multiprocess=pod)
        run.compute += _clock() - t0
        wall = _clock() - t_start
        overlap = max(0.0, run.ingest + run.compute - wall)
        _engine.record_stream(nslabs, run.ingest, run.compute, wall,
                              overlap, depth,
                              uploaders=max(pool.high_water, 1),
                              inflight=max(win.high_water, 1),
                              early=win.early,
                              keyed=nslabs if keyed else 0, thin=nthin,
                              **terminal.tally(nslabs, ngramk))
        if result_state(source).pred is not None:
            # a filter that ended in this terminal: no buffer was built
            # for it, as for a resident deferred one
            _engine.record_filter_fused()
        if run_sp is not None:
            run_sp.set(slabs=nslabs, ingest_s=round(run.ingest, 6),
                       compute_s=round(run.compute, 6),
                       overlap_s=round(overlap, 6),
                       concurrent_uploaders=max(pool.high_water, 1),
                       inflight_high_water=max(win.high_water, 1))
        return terminal.wrap(out, mesh)
    finally:
        run.leave()
        _obs.end(run_sp)


# ---------------------------------------------------------------------
# materialisation (the fallback for non-streaming consumers)
# ---------------------------------------------------------------------

def materialize(source):
    """Build the CONCRETE array a stream source describes, by the
    standard machinery: the base uploads whole (per device shard for
    callback sources, host-assembled for iterator sources), then every
    recorded stage replays through the normal deferred/chunked/stacked
    paths — so a materialised stream is bit-identical to having never
    streamed at all.  Needs the full array to fit; streaming terminals
    exist so it usually never runs, and a mapped result that fits is
    COLLECTED slab by slab instead (:func:`collect`), as a swap is
    re-axed slab by slab: the ``stream.materialize`` span covers only
    what uploads whole."""
    if has_swap(source):
        # the two-phase shuffle resolves the re-keying SLAB-WISE (the
        # input never lives whole next to the output); a resident
        # resolution is already the concrete replayed array, a spilled
        # one materialises from its bucket files
        b = resolve_swaps(source)
        if b._stream is None:
            return b
        source = b._stream
    if collect_refusal(source) is None:
        # a mapped result: slab by slab through the pool, the stages in
        # the slab program, each slab's records placed into the result —
        # the base never lives whole on the device
        return _resolve_one_swap(source, collect=True)
    with _obs.span("stream.materialize", kind=source.kind,
                   stages=len(source.stages)):
        materialize_check(source)
        b = _materialize_base(source)
        return _replay_stages(b, source.stages)


def materialize_bytes(source):
    """What :func:`materialize` holds on ONE device at its least: its
    shard of the base uploaded whole, and of the staged result beside it
    where stages replay (their temporaries are not counted)."""
    st = result_state(source)
    need = prod(source.shape) * source.dtype.itemsize
    if source.stages:
        need += st.n * prod(st.vshape) * st.dtype.itemsize
    return need // max(int(source.mesh.devices.size), 1)


def materialize_check(source):
    """Refuse in bolt's words (BLT020, ``MemoryError``) a materialisation
    the device cannot hold, before XLA is asked for the memory."""
    from bolt_tpu.tpu.array import hbm_check
    hbm_check("BLT020: materialising this streamed source",
              materialize_bytes(source),
              "the base uploaded whole%s; what cannot be collected slab "
              "by slab has no other sink: reduce it (sum/mean/reduce "
              "stream at any size) or map to smaller records"
              % (" and the staged result beside it"
                 if source.stages else ""))


def collect_refusal(source):
    """Why :func:`collect` cannot take ``source`` (the words it raises
    with), or ``None`` where it can.  What it cannot take materialises:
    a source with no stage (nothing is mapped: the array IS the base), a
    dynamic (post-filter) row count, a lossy ingest codec (the
    materialised path uploads the base unencoded), a mesh of several
    processes, an empty result, a result that does not fit the resident
    budget beside the slabs in flight (:func:`collect_plan`)."""
    if has_swap(source):
        return ("the source carries an unresolved swap: resolve_swaps "
                "re-axes it slab by slab first")
    if not source.stages:
        return "the source has no stage: nothing is mapped"
    st = result_state(source)
    if st.dynamic:
        return ("the result's row count is dynamic (a filter's survivor "
                "count is not known until the predicate has run): a "
                "slab's place in the result cannot be planned; reduce "
                "the filtered source, or materialise it (toarray)")
    if st.n == 0 or source.shape[0] == 0:
        return "the result is empty"
    if _multihost.mesh_process_count(source.mesh) > 1:
        return "the mesh spans several processes"
    codec_obj = resolve_codec(source)
    if codec_obj is not None and not codec_obj.lossless:
        return "the ingest codec %r is lossy" % (codec_obj.name,)
    plan = collect_plan(source)
    if not plan.resident:
        return ("the mapped result and the slabs in flight (%.1f MiB) "
                "exceed the resident budget (%.1f MiB)"
                % (plan.resident_bytes / 2**20,
                   (plan.budget or 0) / 2**20))
    return None


def collect_plan(source):
    """The plan of :func:`collect` over ``source``: the resolver's own
    (``parallel.shuffle.plan_shuffle``) with the identity for a re-axis,
    judged against :func:`swap_budget` — the ONE rule ``analysis.check``
    forecasts by (BLT020) and the run decides by."""
    from bolt_tpu.parallel import shuffle as _shuffle
    st = result_state(source)
    nslabs = max(1, -(-source.shape[0] // max(source.slab, 1)))
    return _shuffle.plan_shuffle(
        st.shape, st.dtype, st.split, tuple(range(len(st.shape))),
        st.split, source.mesh, source.slab, place_budget(source),
        None, ring=min(swap_ring(source), nslabs),
        raw_slab_bytes=_raw_slab_bytes(source))


def _raw_slab_bytes(source):
    """Bytes of one slab of ``source`` as it is uploaded."""
    codec_obj = resolve_codec(source)
    item = (codec_obj.wire_dtype(source.dtype).itemsize
            if codec_obj is not None else source.dtype.itemsize)
    return min(source.slab, source.shape[0]) * prod(source.shape[1:]) * item


def collect(source, project=False):
    """The CONCRETE array of a mapped stream source, assembled slab by
    slab: every slab goes up through the uploader pool, ONE program a
    slab applies the recorded stages and places the slab's records into
    the result, which exists once (the resolver's resident leg, run with
    the identity for a re-axis: no second loop).  The base never lives
    whole on the device, so a map whose RESULT fits is taken however
    large its source.  Bit-identical to :func:`materialize`'s upload and
    replay (the slab program traces the same stage bodies).  Refuses in
    words what it cannot take (:func:`collect_refusal`), a result past
    the resident budget among them (it has no other sink:
    :func:`materialize` then says whether the device can hold the source
    whole, BLT020).  ``project``: the pass is a streamed ``ops.pca``'s
    second, and its slabs count as ``stream_project_slabs`` too."""
    why = collect_refusal(source)
    if why is not None:
        raise ValueError("stream.collect: %s" % why)
    return _resolve_one_swap(source, collect=True, project=project)


def _replay_stages(b, stages):
    """Replay recorded stream stages on a CONCRETE array through the
    normal deferred/chunked/stacked/swap paths — the ONE replay used by
    materialisation AND the resident shuffle's post-swap tail, so both
    are bit-identical to having never streamed at all."""
    for stage in stages:
        kind = stage[0]
        if kind == "map":
            from bolt_tpu.tpu.array import _WithKeysFunc
            keyed = isinstance(stage[1], _WithKeysFunc)
            b = b.map(stage[1].func if keyed else stage[1],
                      axis=tuple(range(b.split)), with_keys=keyed)
        elif kind == "chunk":
            from bolt_tpu.tpu.chunk import ChunkedArray
            _, func, plan, pad, canon = stage
            b = ChunkedArray(b, plan, pad).map(func, dtype=canon).unchunk()
        elif kind == "stack":
            from bolt_tpu.tpu.stack import StackedArray
            _, func, size, canon = stage
            b = StackedArray(b, size).map(func, dtype=canon).unstack()
        elif kind == "filter":
            b = b.filter(stage[1], axis=tuple(range(b.split)))
        elif kind == "swap":
            b = _replay_swap(b, stage[1], stage[2])
        else:
            raise ValueError("unknown stream stage %r" % (kind,))
    return b


def _replay_swap(b, perm, new_split):
    """One recorded swap stage on a CONCRETE array: recover the
    ``(kaxes, vaxes)`` the permutation was built from (``_do_swap``'s
    construction, inverted) and run the standard materialised swap —
    the streamed resolution and this replay therefore compile the SAME
    expression."""
    split = b.split
    kaxes = [p for p in perm[new_split:] if p < split]
    vaxes = [p - split for p in perm[:new_split] if p >= split]
    return b._do_swap(kaxes, vaxes, True)


def _materialize_base(source):
    from bolt_tpu.parallel.sharding import key_sharding
    from bolt_tpu.tpu.array import BoltArrayTPU
    shape = source.shape
    sharding = key_sharding(source.mesh, shape, source.split)
    t0 = _clock()
    if source.kind == "callback":
        def produce(index):
            block = np.asarray(source.produce(index), dtype=source.dtype)
            want = tuple(len(range(*s.indices(nn)))
                         for s, nn in zip(index, shape))
            if block.shape != want:
                raise ValueError(
                    "fromcallback callback returned shape %s for index %s "
                    "(expected %s)" % (block.shape, index, want))
            return block
        data = jax.make_array_from_callback(shape, sharding, produce)
        _engine.record_transfer(
            prod(shape) * source.dtype.itemsize, _clock() - t0,
            elements=prod(shape))
        return BoltArrayTPU(data, source.split, source.mesh)
    host = np.empty(shape, source.dtype)
    for lo, hi, block in source.slabs():
        host[lo:hi] = block
    if _multihost.is_multiprocess(source.mesh):
        # device_put cannot scatter a host array across processes —
        # each process's devices pick their own shards out of the
        # host-assembled copy (every process iterated the re-iterable
        # source itself, so each holds the full array).  Counted at the
        # LOCAL logical bytes (this process's distinct shard regions,
        # replicas deduped), matching the per-process transfer contract
        # of the streaming path.
        t0 = _clock()
        data = jax.make_array_from_callback(shape, sharding,
                                            lambda idx: host[idx])
        seen = set()
        local = 0
        for idx in sharding.addressable_devices_indices_map(
                tuple(shape)).values():
            box = tuple(s.indices(n)[:2] for s, n in zip(idx, shape))
            if box not in seen:
                seen.add(box)
                local += prod([b - a for a, b in box])
        _engine.record_transfer(local * source.dtype.itemsize,
                                _clock() - t0, elements=local)
        return BoltArrayTPU(data, source.split, source.mesh)
    data = transfer(host, sharding)
    return BoltArrayTPU(data, source.split, source.mesh)


# ---------------------------------------------------------------------
# the two-phase shuffle (ISSUE 18): streamed swap resolution
# ---------------------------------------------------------------------

def _shuffle_fingerprint(source, pre_stages, perm, new_split, out_block):
    """Identity of one streamed-swap resolution for spill-manifest
    matching — same discipline as :func:`_run_fingerprint`: geometry +
    slab plan + the PRE-swap stage chain (callables by bytecode) + the
    permutation itself, so a resume never adopts buckets cut by a
    different pipeline."""
    from bolt_tpu.utils import code_token
    stages = "|".join(_stage_token(s) for s in pre_stages)
    return ("bolt-stream-spill-v1",
            "x".join(str(s) for s in source.shape), int(source.split),
            str(source.dtype), int(source.slab), str(source.kind),
            code_token(source.produce) if source.produce is not None
            else "", stages, repr(tuple(perm)), int(new_split),
            int(out_block))


def _bucket_host(part, lo, hi):
    """Host copy of rows ``[lo, hi)`` of a (possibly sharded) device
    array — assembled from ADDRESSABLE shards only, so on a pod this is
    exactly the rows this process owns under the output key sharding
    (the spill files never carry another host's data)."""
    out = np.empty((hi - lo,) + tuple(part.shape[1:]), part.dtype)
    for s in part.addressable_shards:
        idx = s.index
        slo, shi, _ = idx[0].indices(part.shape[0])
        a, b = max(slo, lo), min(shi, hi)
        if a >= b:
            continue
        data = np.asarray(s.data)
        out[(slice(a - lo, b - lo),) + tuple(idx[1:])] = \
            data[a - slo:b - slo]
    return out


def _owned_buckets(part, out_block):
    """Global bucket indices whose rows this process holds in ``part``
    (sorted; single-process: all of them)."""
    owned = set()
    n = part.shape[0]
    for s in part.addressable_shards:
        slo, shi, _ = s.index[0].indices(n)
        owned.update(range(slo // out_block, -(-shi // out_block)))
    return sorted(owned)


def _resolve_one_swap(source, collect=False, project=False):
    """Resolve the FIRST recorded swap of ``source`` via the two-phase
    streaming shuffle (module docstring of
    ``bolt_tpu.parallel.shuffle``): phase 1 streams input slabs through
    the uploader pool and one re-bucket program each (all-to-all on
    pods), phase 2 either happens IN PLACE (resident: the swapped array
    is allocated once and each slab's program writes its block into it,
    post-swap stages replayed concretely) or returns a fresh stream
    source over SPILLED bucket files carrying the post-swap stages
    lazily.  Bit-identical to the materialised swap either way — the
    per-slab program traces the same transpose and the same stage
    bodies.  Phase 1 is a streamed run like any other: its wall, ingest
    and overlap seconds land in the ``stream_*`` counters
    (``engine.record_stream``) beside ``shuffle_bytes/_seconds``.

    ``collect=True`` (:func:`collect`) runs the resident leg over a
    source with NO swap: every stage is a stage before the re-axis, the
    re-axis is the identity, and what the place programs assemble is the
    mapped result itself (``stream_collect_*`` counters in place of the
    shuffle's; the plan is :func:`collect_plan`'s and never spills)."""
    from bolt_tpu import checkpoint as _ckptlib
    from bolt_tpu.parallel import shuffle as _shuffle
    from bolt_tpu.tpu.array import BoltArrayTPU, _place_operands

    if collect:
        pre, post = source.stages, ()
    else:
        cut = next(k for k, s in enumerate(source.stages)
                   if s[0] == "swap")
        pre = source.stages[:cut]
        _, perm, new_split = source.stages[cut]
        post = source.stages[cut + 1:]
    base = StreamSource(source.kind, source.produce, source.blocks,
                        source.shape, source.split, source.dtype,
                        source.mesh, source.slab, pre,
                        ckpt=source.ckpt, codec=source.codec)
    base._consumed = source._consumed
    st = result_state(base)
    mesh = source.mesh
    split = source.split
    spill_dir, _ = spill_scope()
    keyed, side = stage_extras(pre)
    if collect:
        plan = collect_plan(base)         # resident: collect() asked
        perm, new_split = plan.perm, plan.new_split
    else:
        plan = _shuffle.plan_shuffle(st.shape, st.dtype, st.split, perm,
                                     new_split, mesh, base.slab,
                                     place_budget(base), spill_dir,
                                     ring=swap_ring(base),
                                     raw_slab_bytes=_raw_slab_bytes(base))
    if keyed and not plan.resident:
        raise RuntimeError(
            "streamed swap: a with_keys map in front of a SPILLED "
            "re-axis is not supported (the spill leg's program is not "
            "handed a slab's first key) — raise the resident budget so "
            "the re-keyed array stays resident (%.1f MiB needed, %.1f "
            "MiB budget), or materialise first (toarray) and swap in "
            "memory" % (plan.resident_bytes / 2**20,
                        (plan.budget or 0) / 2**20))
    if not plan.resident and spill_dir is None:
        raise RuntimeError(
            "streamed swap: the re-keyed working set (%.1f MiB) "
            "exceeds the resident budget (%.1f MiB) and no spill "
            "directory is configured — wrap the run in "
            "bolt_tpu.stream.spill(dir) (or raise the budget); "
            "analysis.check forecasts this as BLT017"
            % (plan.resident_bytes / 2**20, (plan.budget or 0) / 2**20))

    # the codec is lossless or None (gated when the swap was recorded)
    run = _Run(base)
    pod = run.pod
    if pod and not plan.resident:
        # pod spill is refused, not attempted: phase 1 spills each
        # bucket whole on the one process that owns its rows, but
        # re-streaming those buckets as pod slabs needs every slab
        # SPLIT across processes (the BLT012 divisibility
        # contract) — two ownership models that cannot both hold.
        raise RuntimeError(
            "streamed swap: the re-keyed working set (%.1f MiB) "
            "exceeds the resident budget (%.1f MiB) and disk "
            "spill is single-process only — on a multi-process "
            "mesh raise the arbiter budget so the buckets stay "
            "resident, or materialise first (toarray) and swap "
            "in memory; analysis.check forecasts this as BLT017"
            % (plan.resident_bytes / 2**20,
               (plan.budget or 0) / 2**20))

    # spill-manifest resume (fingerprinted like stream checkpoints):
    # slabs whose every bucket landed are skipped — their files are
    # complete by the atomic-rename + mark-after-buckets discipline.
    # Pod runs re-run phase 1 whole: per-process manifests can disagree
    # after an asymmetric kill, and a disagreeing slab schedule would
    # cross the all-to-all rendezvous (overwrites are atomic, so the
    # re-run is correct, just unskipped).
    fp = _shuffle_fingerprint(base, pre, perm, new_split,
                              plan.out_block)
    done = set()
    if not plan.resident and base.kind == "callback" and not pod:
        done = _ckptlib.spill_manifest(spill_dir, fp)
        if done:
            _engine.record_stream_resume()
            _obs.event("stream.spill_resume", slabs=len(done))

    jobs = None
    if base.kind == "callback":
        jobs = [(g, lo, hi) for g, (lo, hi)
                in enumerate(base.slab_ranges()) if g not in done]
    side = _place_operands(side, mesh)      # once a run, not once a slab
    run_sp = _obs.begin("stream.collect" if collect else "stream.shuffle",
                        resident=plan.resident,
                        inplace=plan.resident, ring=plan.ring,
                        slabs=plan.nslabs, buckets=plan.nbuckets,
                        out_block=plan.out_block,
                        alltoall_bytes=plan.alltoall_bytes,
                        devices=plan.devices)
    # thin records and narrow elements for ONE device go up dense and
    # the place program re-seats them, as execute's slab programs do (the
    # spill leg's program takes the slab as the loader hands it over); a
    # one-shot iterable cannot resume, so `done` is empty without jobs
    pool = _IngestPool(run, base, plan.ring, jobs=jobs,
                       noun="shuffle slab", parent=run_sp,
                       dense=plan.resident and dense_route(base))

    def _spill_part(part, g):
        """Extract and persist every LOCALLY-OWNED bucket of slab
        ``g``'s transposed part (atomic files; the slab is marked
        complete only after its last bucket lands — the kill -9
        resume point)."""
        for bkt in _owned_buckets(part, plan.out_block):
            lo = bkt * plan.out_block
            hi = min(lo + plan.out_block, plan.out_shape[0])
            block = _bucket_host(part, lo, hi)
            attempt = 0
            prev = None
            while True:
                ssp = _obs.begin("stream.spill", slab=g, bucket=bkt)
                try:
                    _chaos.hit("stream.spill")
                    nb = _ckptlib.spill_save(spill_dir, fp, g, bkt,
                                             block, lo)
                    if ssp is not None:
                        ssp.set(bytes=nb)
                    _obs.end(ssp)
                    _engine.record_spill(nb)
                    break
                except BaseException as exc:  # noqa: BLE001
                    _obs.end(ssp, error=type(exc).__name__)
                    prev = pool.retry(g, attempt, prev, exc,
                                      "spill slab")
                    attempt += 1
        _ckptlib.spill_slab_done(spill_dir, fp, g)

    def _unrepeatable(g, exc):
        """A failure at a call's confirm is final: the slab the call was
        handed is donated, and so is the array it wrote into, to the
        calls behind it, so nothing is left to dispatch again."""
        return RuntimeError(
            "shuffle slab %d failed at the confirm of its call "
            "(%s: %s); what the call was handed is donated (the "
            "slab, and the array the calls dispatched behind it "
            "write into), so it cannot be retried in place and the "
            "run ends here" % (g, type(exc).__name__, exc))

    t_start = _clock()
    moved = 0
    placed = 0
    nthin = 0                   # slabs that went up dense
    out = cursor = None
    # the resident leg keeps up to `window` place calls unconfirmed,
    # what the ring holds beyond a slab in every worker's hand: call g's
    # `out` is donated into call g + 1, so what confirms it is the
    # cursor it returned, a fresh scalar ready when the call is done.
    # The spill leg reads its part on the host, one block a slab,
    # between its confirm and the give-back.
    win = _Window(run, pool, "shuffle re-bucket", attrs={"shuffle": True},
                  failed=_unrepeatable,
                  settle=None if plan.resident else _spill_part)
    window = max(1, plan.ring - run.nwork) if plan.resident else 1
    windowed = 0                # calls dispatched behind an unconfirmed one
    if plan.resident:
        # phase 2 in place: the swapped array exists ONCE, from here
        # on; every slab's program is handed it (donated) and hands it
        # back with its block written at the cursor, which counts
        # slabs for a callback source (uniform: slab g starts at
        # g * slab) and records for an iterator's own blocks
        out, cursor = _shuffle.alloc_program(plan, mesh)()
        unit = base.slab if base.kind == "callback" else 1

    pool.start()
    run.enter()
    try:
        while True:
            win.retire(window)
            got = pool.next(idle=win.starved if run.lease is not None
                            else None)
            if got is None:
                break
            run.ready()
            g, buf, bnb, tsec, _ = got
            run.ingest += tsec
            t0 = _clock()
            thin, wshape = pool.form(buf)
            nthin += thin
            csp = _obs.begin("stream.compute", slab=g, shuffle=True,
                             dtype=str(source.dtype))
            attempt = 0
            prev = None
            try:
                while True:
                    try:
                        # the chaos seam fires BEFORE the dispatch, so
                        # an injected raise leaves the donated buffers
                        # intact — the in-place retry (same fence as
                        # ingest retries) re-dispatches them verbatim,
                        # whatever is still unconfirmed in front
                        _chaos.hit("stream.shuffle")
                        if plan.resident:
                            prog = _shuffle.place_program(
                                plan, pre, mesh, run.codec, source.dtype,
                                wshape, run.delta_ok, unit, thin)
                        else:
                            prog = _shuffle.rebucket_program(
                                plan, pre, mesh, run.codec, source.dtype,
                                wshape, run.delta_ok)
                        with _undonated_ok():
                            xsp = _obs.begin("stream.dispatch", slab=g)
                            try:
                                if plan.resident:
                                    # slabs arrive re-sequenced, in key
                                    # order, and a resident run skips
                                    # none
                                    psp = _obs.begin(
                                        "stream.collect.place", slab=g) \
                                        if collect else None
                                    try:
                                        out, cursor = prog(out, buf,
                                                           cursor, *side)
                                    finally:
                                        _obs.end(psp)
                                    handle = cursor
                                else:
                                    handle = prog(buf, *side)
                            finally:
                                _obs.end(xsp)
                        break
                    except _podwatch.PeerLostError:
                        raise
                    except BaseException as exc:  # noqa: BLE001
                        prev = pool.retry(g, attempt, prev, exc,
                                          "shuffle dispatch")
                        attempt += 1
                del buf, got
                moved += wshape[0] * (plan.total_bytes // plan.in_shape[0])
                placed += 1
                windowed += bool(win.unconfirmed)
                win.push(1, handle, bnb, slab=g)
                del handle
                run.compute += _clock() - t0
                win.retire(window - 1)
            finally:
                _obs.end(csp)
        win.retire(0)           # the drain, in slab order
    finally:
        pool.close()
        win.release()
        run.leave()
        wall = _clock() - t_start
        # what crossed devices, by the planner's model, for the bytes
        # this run moved (a resumed spill skips slabs)
        crossed = plan.alltoall_bytes * moved // max(plan.total_bytes, 1)
        if collect:
            _engine.record_collect(placed, moved, project)
        else:
            _engine.record_shuffle(moved, wall, alltoall=crossed)
        if run_sp is not None:
            run_sp.set(bytes=moved, crossed_bytes=crossed,
                       upload_parts=pool.parts)
        _obs.end(run_sp)
    # phase 1 completed: one streamed run, under the counters every
    # streamed run reports (a spilled swap's phase 2 adds its own)
    _engine.record_stream(placed, run.ingest, run.compute, wall,
                          max(0.0, run.ingest + run.compute - wall),
                          run.depth, uploaders=max(pool.high_water, 1),
                          inflight=max(win.high_water, 1),
                          windowed=windowed,
                          keyed=placed if keyed else 0, thin=nthin)

    if plan.resident:
        if not placed:
            raise RuntimeError(
                "streamed swap produced no slabs (empty source?) — "
                "the materialised path owns empty-input rules")
        with _obs.span("stream.handover", bytes=plan.total_bytes,
                       stages=len(post)):
            _pod_sync(out, pod, "shuffle place")
            b = BoltArrayTPU(out, new_split, mesh)
            return _replay_stages(b, post)

    # SPILLED: phase 2 is a fresh callback source over the bucket
    # files — it streams through the SAME slab-program machinery as
    # any other source (execute/materialize/retries/arbiter/resume all
    # inherited), with the post-swap stages riding lazily
    # an iterator's slabs are the blocks it yielded, however many
    nslabs = plan.nslabs if base.kind == "callback" else placed
    out_shape = plan.out_shape
    out_block = plan.out_block
    j0 = plan.j0
    out_n = out_shape[0]

    def produce(index):
        lo, hi, _ = index[0].indices(out_n)
        chunks = []
        for bkt in range(lo // out_block, -(-hi // out_block)):
            pieces = [_ckptlib.spill_load(spill_dir, fp, g, bkt)
                      for g in range(nslabs)]
            blk = np.concatenate([p[0] for p in pieces], axis=j0)
            chunks.append((pieces[0][1], blk))
        full = np.concatenate([c[1] for c in chunks], axis=0)
        row0 = chunks[0][0]
        out = full[lo - row0:hi - row0]
        return out[(slice(None),) + tuple(index[1:])]

    src2 = StreamSource("callback", produce, None, out_shape, new_split,
                        st.dtype, mesh, out_block, post,
                        ckpt=source.ckpt, codec=None)
    return BoltArrayTPU._streamed(src2)
