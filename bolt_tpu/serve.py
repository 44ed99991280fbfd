"""Multi-tenant serving layer: MANY pipelines, ONE engine, shared HBM.

Everything below this module optimises one pipeline at a time; a
process serving heavy traffic runs many of them at once, and two naive
concurrent streams each assume sole ownership of device memory (their
donation rings independently sized to the whole budget) while their
dispatches serialise on ad-hoc locks.  This module is the scheduler
that lets N tenants share one process and one device mesh safely:

* a **device-memory arbiter** (:class:`DeviceArbiter`) generalises the
  streaming executor's donation ring + in-flight window into ONE
  process-wide bytes-weighted budget: streamed slab uploads
  (``bolt_tpu.stream`` acquires per slab, in slab order, releasing on
  confirmed retirement) and terminal dispatches (the worker leases a
  pipeline's estimated working set) draw permits from it, so N tenants
  split HBM instead of each assuming all of it.  Waiters are queued
  per tenant and granted **round-robin across tenants, FIFO within a
  tenant** — fair share across tenants, in-order budget delivery per
  stream (the executor's ``_Reseq`` fencing keeps each tenant's fold
  bit-exact regardless of grant interleaving);
* a **fair-share scheduler** (:class:`Server`): ``submit(pipeline,
  tenant=...)`` returns a :class:`Future`; worker threads pop jobs
  round-robin across per-tenant queues, so one chatty tenant cannot
  starve the rest, while each tenant's own jobs run in submission
  order.  ``Server(weights={tenant: n})`` generalises the rotation to
  a WEIGHTED fair share: the head tenant is served up to *n* queued
  jobs (integer credits) per turn — default 1 keeps the plain
  round-robin bit-for-bit, and any tenant with work is still served
  within one rotation (starvation-free);
* a **fleet-warm start**: ``Server(start_warm=dir)`` attaches a
  pre-seeded ``engine.persistent_cache`` directory before the first
  submit, so a fresh process serves its first request with ZERO fresh
  XLA compiles (executables load from disk, counted as the engine's
  ``persistent_warm_hits``);
* **cross-tenant coalescing of identical executables**: the engine
  cache is keyed on program structure, and ``engine.get`` /
  ``_Dispatch`` now coalesce concurrent identical builds/compiles
  (``coalesced_builds`` / ``coalesced_compiles`` counters), so N
  tenants running the same pipeline shape trace and compile it ONCE —
  provided they share the stage callables (hoist user functions to
  module level, as the tests do; two bytecode-identical lambdas
  are distinct cache keys);
* **admission control with backpressure**: the queue is bounded
  (``queue_limit``); ``policy="queue"`` blocks the submitter until
  room frees (backpressure), ``policy="reject"`` raises
  :class:`AdmissionError` immediately.  A pipeline whose estimated
  working set exceeds the WHOLE budget can never run and is rejected
  at submit time — the ``BLT010`` diagnostic
  (``bolt_tpu.analysis.check`` emits it whenever a serving arbiter is
  active, so ``explain()`` shows the refusal before anything is
  queued);
* **continuous micro-batching** (``Server(batching=...)``, ROADMAP
  item 4): a high-QPS service is mostly a firehose of SMALL
  identical-shape pipelines where per-request dispatch overhead — not
  bytes — is the roofline.  Queued requests sharing a BATCH KEY (same
  pipeline structure, shapes, dtypes, terminal and sharding — see
  ``bolt_tpu.tpu.batched.batch_key``), ACROSS tenants, coalesce into
  ONE stacked dispatch: inputs stack along a new leading axis, the
  standalone terminal body runs vmapped (the ``StackedArray`` batched-
  execution idea applied to the request queue), and each lane's
  results scatter back to its request's ``Future`` — BIT-IDENTICAL to
  the standalone dispatch on XLA's CPU backend, where the tests run;
  on a v5e a float32 reduction inside the stacked program rounded
  differently from the standalone one (1.9e-7 relative; PERF.md,
  PR 21).  Partial batches pad to bucketed widths
  (powers of two up to ``max_batch``) so steady state compiles a small
  fixed executable set and then runs zero fresh XLA compiles
  (``bolt_tpu.tpu.batched.warm`` pre-compiles the buckets for a
  fleet); a worker that found at least one coalescible partner lingers
  up to ``linger`` seconds to fill the bucket, while a lone request
  never waits.  Per-request attribution is preserved: every future
  keeps its own wait/assembly/run seconds and ``batch_width``, every
  tenant its own counters and arbiter leases.  Diagnostics:
  ``BLT015`` forecasts batch eligibility, engine counters
  ``batched_dispatches``/``batched_requests`` and the
  ``serve.batch_occupancy.hist`` histogram record the realised
  coalescing (``stats()["batching"]`` summarises them).

Observability: queue depth (+ high-water), per-job queue-wait and run
seconds (totals per tenant, a log2 histogram overall), arbiter
in-use/high-water bytes and wait counts all land in
``bolt_tpu.obs.registry()`` under ``serve.*`` names; every job runs
inside an ``engine.tenant(<name>)`` scope, so the engine counters —
transfer bytes, compiles, dispatches — are ALSO tallied per tenant
(``engine.tenant_counters(name)``), streamed ingest traffic included
(the executor forwards the tag into its uploader pool).

The blessed entry points::

    with bolt_tpu.serve.serving(workers=4, budget_bytes=2 << 30) as sv:
        futs = [sv.submit(make_pipeline(), tenant=t) for t in tenants]
        outs = [f.result() for f in futs]

or the module-level :func:`submit`, which lazily starts a default
server (env-tunable: ``BOLT_SERVE_WORKERS`` / ``BOLT_SERVE_BUDGET``
/ ``BOLT_SERVE_QUEUE_LIMIT`` / ``BOLT_SERVE_BATCHING`` — with
``BOLT_SERVE_MAX_BATCH`` and ``BOLT_SERVE_LINGER`` tuning the armed
policy).  Lint rule BLT108 keeps this module and
``stream.py`` the ONLY homes of raw thread construction in the
package — every other concurrency need routes through one of them.
"""

import contextlib
import os
import threading
from collections import OrderedDict, deque

from bolt_tpu import _lockdep
from bolt_tpu import engine as _engine
from bolt_tpu.obs import metrics as _metrics
from bolt_tpu.obs import trace as _obs
from bolt_tpu.obs.trace import clock as _clock
from bolt_tpu.parallel import podwatch as _podwatch
from bolt_tpu.parallel.podwatch import PeerLostError  # noqa: F401 — the
#   retryable pod-outage error submit(retries=) honours; re-exported so
#   serving callers need not import the liveness layer

# ---------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------

# process-wide HBM budget for the arbiter.  The default is deliberately
# conservative (1 GB): serving N tenants means N rings + N in-flight
# windows, and the budget is what keeps their SUM bounded; size it to
# the device's usable HBM in production.
_DEF_BUDGET = int(os.environ.get("BOLT_SERVE_BUDGET", str(1 << 30)))
_DEF_WORKERS = max(1, int(os.environ.get("BOLT_SERVE_WORKERS", "4")))
_DEF_QUEUE = max(1, int(os.environ.get("BOLT_SERVE_QUEUE_LIMIT", "64")))
# continuous micro-batching default: OFF unless armed by env (the knob
# is Server(batching=...); BOLT_SERVE_BATCHING=1 arms the default
# server / bare Server() with the default policy)
_DEF_BATCHING = os.environ.get("BOLT_SERVE_BATCHING", "").lower() \
    in ("1", "true", "yes")

# per-tenant + global serve counter schema (obs registry groups
# "serve" and "serve/<tenant>")
_SCHEMA = {
    "submitted": 0,            # jobs accepted into the queue
    "rejected": 0,             # jobs refused (queue full / BLT010)
    "completed": 0,            # jobs finished successfully
    "leased": 0,               # jobs run under an arbiter lease of their
                               # own estimate (a callable has none, a
                               # streamed job leases per slab)
    "failed": 0,               # jobs whose pipeline raised
    "queue_wait_seconds": 0.0,  # total submit->start wait
    "run_seconds": 0.0,        # total start->finish execution time
    "retried": 0,              # per-submit retry attempts consumed
    "expired": 0,              # jobs failed on their deadline= budget
    "peer_losses": 0,          # pod peer deaths observed (ISSUE 11 —
                               # admission drained until the reform)
    "reforms": 0,              # supervised reforms driven (ISSUE 12)
    "rejoins": 0,              # identities folded back in by reform-up
    "supervise_seconds": 0.0,  # total pause -> resume recovery wall
}


class AdmissionError(RuntimeError):
    """A submission the server refused: the bounded queue is full under
    ``policy="reject"``, or the pipeline's estimated device working set
    exceeds the arbiter's whole budget (BLT010 — it could never run)."""


class DeadlineError(RuntimeError):
    """A job's per-submit ``deadline=`` budget (seconds since submit)
    expired before it could start; delivered through
    ``Future.result()``."""


class BatchPolicy:
    """Continuous micro-batching policy (``Server(batching=...)``):

    * ``max_batch`` — widest coalesced dispatch (one batched program
      serves up to this many queued same-key requests; default
      ``BOLT_SERVE_MAX_BATCH`` / 16);
    * ``linger`` — micro-wait in seconds to FILL a forming batch: once
      a worker's gather found at least one coalescible partner it waits
      up to this long for more same-key arrivals before dispatching
      (default ``BOLT_SERVE_LINGER`` / 0.002).  A lone request never
      lingers, so low-QPS single-request latency is untouched;
    * ``buckets`` — the compiled batch widths (default powers of two up
      to ``max_batch``): partial batches PAD to the next bucket, so
      steady state compiles a small fixed executable set and then runs
      zero fresh XLA compiles;
    * ``autotune`` — the width-autotuning scaffold (off by default):
      when True, :meth:`rearm` (called by ``batched.warm(make,
      policy=...)`` on a re-arm) re-derives the bucket set from the
      OBSERVED ``serve.batch_occupancy.hist`` distribution
      (``batched.autotune_buckets``), so the compiled widths track the
      occupancy mix traffic actually realises.  With autotune off the
      static knobs are untouched — today's behaviour exactly.
    """

    __slots__ = ("max_batch", "linger", "buckets", "autotune")

    def __init__(self, max_batch=None, linger=None, buckets=None,
                 autotune=False):
        from bolt_tpu.tpu import batched as _batched
        self.autotune = bool(autotune)
        if buckets:
            buckets = tuple(sorted(int(b) for b in buckets))
            if buckets[0] < 2:
                raise ValueError("batch buckets must be >= 2, got %r"
                                 % (buckets,))
            if max_batch is None:
                max_batch = buckets[-1]
        self.max_batch = int(max_batch if max_batch is not None
                             else _batched.DEFAULT_MAX_BATCH)
        if self.max_batch < 2:
            raise ValueError("max_batch must be >= 2, got %d"
                             % self.max_batch)
        self.linger = float(linger if linger is not None
                            else _batched.DEFAULT_LINGER)
        if self.linger < 0:
            raise ValueError("linger must be >= 0 seconds, got %r"
                             % (linger,))
        self.buckets = buckets or _batched.buckets_for(self.max_batch)
        if self.buckets[-1] != self.max_batch:
            raise ValueError(
                "the largest bucket (%d) must EQUAL max_batch (%d): a "
                "smaller one cannot serve a full batch, a larger one "
                "would pad every dispatch past the promised widest "
                "width" % (self.buckets[-1], self.max_batch))

    def rearm(self, hist_buckets=None):
        """Autotune re-arm: replace :attr:`buckets` with the set
        :func:`bolt_tpu.tpu.batched.autotune_buckets` derives from the
        observed ``serve.batch_occupancy.hist`` (``hist_buckets``
        overrides the registry read, for tests).  Returns True when
        the buckets changed hands; a no-op False when ``autotune`` is
        off (static knobs untouched) or nothing has been observed yet.
        The derived set always ends at ``max_batch``, preserving the
        policy invariant."""
        if not self.autotune:
            return False
        from bolt_tpu.tpu import batched as _batched
        if hist_buckets is None:
            from bolt_tpu.obs import metrics as _metrics
            hist_buckets = _metrics.registry().histogram(
                "serve.batch_occupancy.hist", lo=0, hi=9).buckets()
        derived = _batched.autotune_buckets(hist_buckets, self.max_batch)
        if derived is None:
            return False
        self.buckets = derived
        return True

    def __repr__(self):
        return ("BatchPolicy(max_batch=%d, linger=%g, buckets=%s%s)"
                % (self.max_batch, self.linger, self.buckets,
                   ", autotune" if self.autotune else ""))


# ---------------------------------------------------------------------
# the device-memory arbiter
# ---------------------------------------------------------------------

class _Ticket:
    __slots__ = ("nbytes", "granted", "skipped")

    def __init__(self, nbytes):
        self.nbytes = nbytes
        self.granted = False
        self.skipped = 0      # grants that bypassed this waiting head


# grants that may bypass a waiting head ticket before the arbiter stops
# feeding newer requests and drains toward it (the anti-starvation
# barrier: without it, sustained small-slab traffic keeps _used high
# forever and a large request never sees the budget it needs)
_STARVE_LIMIT = 64


class DeviceArbiter:
    """Process-wide bytes-weighted device-memory budget.

    ``acquire(nbytes, tenant)`` blocks until the bytes fit (or the
    caller's ``stop`` event fires); ``release(nbytes)`` returns them.
    Waiters queue FIFO per tenant and are granted round-robin ACROSS
    tenants — the fair-share rule — with one escape: a request larger
    than the whole budget is granted when nothing else holds bytes
    (it runs alone), so an oversized slab degrades to serial execution
    instead of hanging forever.

    Prefer :meth:`lease` over raw acquire/release: a
    :class:`ArbiterLease` tracks its own outstanding bytes and
    ``close()`` returns whatever an aborted run still held.
    """

    def __init__(self, budget_bytes):
        self.budget = int(budget_bytes)
        if self.budget <= 0:
            raise ValueError("arbiter budget must be positive, got %d"
                             % self.budget)
        self._cond = _lockdep.condition("serve.arbiter")
        self._used = 0
        self._queues = OrderedDict()       # tenant -> deque[_Ticket]
        self._ring = deque()               # tenants with waiters (RR)
        reg = _metrics.registry()
        self._g_used = reg.gauge("serve.arbiter_in_use_bytes")
        self._g_hw = reg.gauge("serve.arbiter_in_use_high_water")
        self._c_waits = reg.counter("serve.arbiter_waits")
        self._c_wait_s = reg.counter("serve.arbiter_wait_seconds", 0.0)

    # -- accounting ----------------------------------------------------

    def in_use(self):
        with self._cond:
            return self._used

    def waiting(self):
        """Queued (ungranted) requests across all tenants."""
        with self._cond:
            return sum(len(q) for q in self._queues.values())

    # -- the grant rule ------------------------------------------------

    def _fits(self, nbytes):
        return self._used + nbytes <= self.budget or self._used == 0

    def _grant_locked(self):
        """Round-robin across tenants with waiters, FIFO within each:
        grant every head ticket that fits, looping until a full cycle
        grants nothing.  The rotation pointer advances only PAST a
        grantee (a full cycle of failed probes returns the ring to its
        origin), so the next grant always starts at the tenant after
        the last one served — fair share, not scan-order luck."""
        made = True
        while made and self._ring:
            made = False
            # anti-starvation barrier: a head ticket bypassed by more
            # than _STARVE_LIMIT grants becomes the ONLY grantable one —
            # releases then drain _used toward it instead of feeding an
            # endless stream of newer, smaller requests (without this, a
            # near-budget request under sustained small-slab traffic
            # would wait forever; with it, starvation is bounded)
            starved = None
            for q in self._queues.values():
                tk = q[0] if q else None
                if tk is not None and tk.skipped >= _STARVE_LIMIT and \
                        (starved is None or tk.skipped > starved.skipped):
                    starved = tk
            for _ in range(len(self._ring)):
                t = self._ring[0]
                q = self._queues.get(t)
                tk = q[0] if q else None
                if tk is not None and self._fits(tk.nbytes) \
                        and (starved is None or tk is starved):
                    q.popleft()
                    tk.granted = True
                    self._used += tk.nbytes
                    made = True
                    for q2 in self._queues.values():  # age bypassed heads
                        if q2 and q2[0] is not tk:
                            q2[0].skipped += 1
                    self._ring.rotate(-1)   # next cycle starts AFTER t
                    break                   # rescan from the new head
                self._ring.rotate(-1)
        for t in [t for t, q in self._queues.items() if not q]:
            del self._queues[t]
            try:
                self._ring.remove(t)
            except ValueError:
                pass
        self._g_used.set(self._used)
        self._g_hw.high_water(self._used)
        self._cond.notify_all()

    # -- the public doors ----------------------------------------------

    def acquire(self, nbytes, tenant="default", stop=None):
        """Block until ``nbytes`` fit in the budget (True), or until
        ``stop`` is set (False — nothing was acquired)."""
        nbytes = int(nbytes)
        if nbytes <= 0:
            return True
        tk = _Ticket(nbytes)
        t0 = _clock()
        with self._cond:
            q = self._queues.get(tenant)
            if q is None:
                q = self._queues[tenant] = deque()
                self._ring.append(tenant)
            q.append(tk)
            self._grant_locked()
            waited = not tk.granted
            while not tk.granted:
                if stop is not None and stop.is_set():
                    # withdraw (grants happen under this lock, so an
                    # ungranted ticket is still safely in its queue)
                    q.remove(tk)
                    self._grant_locked()   # a later head may now fit
                    return False
                self._cond.wait(0.05)
        if waited:
            self._c_waits.inc()
            self._c_wait_s.inc(_clock() - t0)
        return True

    def release(self, nbytes):
        nbytes = int(nbytes)
        if nbytes <= 0:
            return
        with self._cond:
            self._used = max(0, self._used - nbytes)
            self._grant_locked()

    def resize(self, budget_bytes):
        """Re-point the budget (degraded-capacity admission, ISSUE 12:
        a supervised pod that shrank N→M rescales to the surviving
        share, and BLT010 floors recompute against the new value on
        the very next submit).  Growing re-grants queued waiters
        immediately; shrinking never claws back granted bytes — the
        budget simply stays over-committed until releases drain it."""
        budget_bytes = int(budget_bytes)
        if budget_bytes <= 0:
            raise ValueError("arbiter budget must be positive, got %d"
                             % budget_bytes)
        with self._cond:
            self.budget = budget_bytes
            self._grant_locked()

    def lease(self, tenant="default"):
        return ArbiterLease(self, tenant)


class ArbiterLease:
    """One run's handle on the arbiter: tracks outstanding bytes so an
    abort path can return EVERYTHING it still holds with one
    :meth:`close` (idempotent; release of bytes never acquired is
    clamped to the outstanding balance)."""

    __slots__ = ("arbiter", "tenant", "_lock", "_out")

    def __init__(self, arbiter, tenant):
        self.arbiter = arbiter
        self.tenant = tenant
        self._lock = _lockdep.lock("serve.lease")
        self._out = 0

    def outstanding(self):
        with self._lock:
            return self._out

    def acquire(self, nbytes, stop=None):
        ok = self.arbiter.acquire(nbytes, self.tenant, stop=stop)
        if ok:
            with self._lock:
                self._out += int(nbytes)
        return ok

    def release(self, nbytes):
        with self._lock:
            n = min(int(nbytes), self._out)
            self._out -= n
        if n:
            self.arbiter.release(n)

    def close(self):
        with self._lock:
            n = self._out
            self._out = 0
        if n:
            self.arbiter.release(n)


# ---------------------------------------------------------------------
# futures
# ---------------------------------------------------------------------

class Future:
    """The handle :meth:`Server.submit` returns.  ``result(timeout)``
    blocks for the pipeline's value (re-raising its exception);
    ``wait_seconds`` / ``run_seconds`` expose the job's queue and
    execution time once known."""

    __slots__ = ("tenant", "_event", "_result", "_exc", "submitted_s",
                 "started_s", "finished_s", "batch_width",
                 "assembly_seconds")

    def __init__(self, tenant):
        self.tenant = tenant
        self._event = threading.Event()
        self._result = None
        self._exc = None
        self.submitted_s = _clock()
        self.started_s = None
        self.finished_s = None
        # micro-batching attribution (None when the job ran standalone):
        # how many requests this job's coalesced dispatch actually
        # served, and the assembly window — gather scan + linger
        # micro-wait + claim, i.e. pop to dispatch begin (the device
        # execution itself is run_seconds' job)
        self.batch_width = None
        self.assembly_seconds = None

    def done(self):
        return self._event.is_set()

    def _finish(self, result=None, exc=None):
        self._result = result
        self._exc = exc
        self.finished_s = _clock()
        self._event.set()

    def result(self, timeout=None):
        if not self._event.wait(timeout):
            raise TimeoutError("serve job still pending after %ss"
                               % timeout)
        if self._exc is not None:
            raise self._exc
        return self._result

    def exception(self, timeout=None):
        if not self._event.wait(timeout):
            raise TimeoutError("serve job still pending after %ss"
                               % timeout)
        return self._exc

    @property
    def wait_seconds(self):
        """Submit → start queue wait (None until started)."""
        if self.started_s is None:
            return None
        return self.started_s - self.submitted_s

    @property
    def run_seconds(self):
        """Start → finish execution time (None until finished)."""
        if self.started_s is None or self.finished_s is None:
            return None
        return self.finished_s - self.started_s

    def __repr__(self):
        state = ("done" if self.done()
                 else "running" if self.started_s is not None
                 else "queued")
        return "<serve.Future tenant=%r %s>" % (self.tenant, state)


# ---------------------------------------------------------------------
# the scheduler
# ---------------------------------------------------------------------

def _normalise(pipeline):
    """One uniform job shape: a zero-arg callable returning the result.

    Accepted inputs: a zero-arg callable (called as-is); a bolt array
    carrying lazy state (a pending stat handle, a deferred chain, a
    streaming source) — resolved via ``.cache()`` and returned; any
    other object is rejected eagerly (a silent pass-through would hide
    a caller bug until ``result()``)."""
    if callable(pipeline) and not hasattr(pipeline, "cache"):
        return pipeline, None
    cache = getattr(pipeline, "cache", None)
    if callable(cache):
        return (lambda: pipeline.cache()), pipeline
    raise TypeError(
        "serve.submit needs a zero-arg callable or a bolt array "
        "pipeline (got %r)" % type(pipeline).__name__)


def _estimate(arr):
    """The MINIMUM device working set of a bolt-array pipeline (the
    BLT010 admission floor: one slab for streams — the arbiter degrades
    the ring; base + result for in-memory pipelines).  None when
    nothing could be estimated (callables, local arrays).  Streaming
    plans under an ingest codec (ISSUE 14) estimate — and the executor
    leases — the COMPRESSED slab bytes: ``admission_floor_bytes``
    applies the codec's wire ratio, so a bf16-encoded tenant is
    admitted at half the budget footprint its raw twin would claim."""
    try:
        h = getattr(arr, "_spending", None)
        if h is not None and h.group.kind == "chain":
            # fast path for the high-QPS small-request shape: the
            # admission floor of a chain-kind stat group is its one-pass
            # read — exactly analysis.working_set_bytes' answer, without
            # the per-submit import/isinstance walk
            return int(h.group.base.nbytes)
        from bolt_tpu.analysis import admission_floor_bytes
        return admission_floor_bytes(arr)
    except Exception:
        return None


class Server:
    """The multi-tenant scheduler: per-tenant FIFO queues drained
    round-robin by ``workers`` threads, every job leased against the
    shared :class:`DeviceArbiter` and executed inside its tenant's
    ``engine.tenant`` counter scope.  See the module docstring for the
    full contract."""

    def __init__(self, workers=None, budget_bytes=None, queue_limit=None,
                 policy="queue", weights=None, start_warm=None,
                 supervise=False, batching=None):
        if policy not in ("queue", "reject"):
            raise ValueError("policy must be 'queue' or 'reject', got %r"
                             % (policy,))
        # continuous micro-batching (ROADMAP item 4): queued same-key
        # requests — same pipeline structure, shapes, dtypes, terminal
        # and sharding, ACROSS tenants — coalesce into ONE stacked
        # dispatch (bolt_tpu/tpu/batched.py), results scattered back to
        # their futures bit-identically.  batching=True arms the
        # default BatchPolicy, a dict/BatchPolicy tunes max_batch /
        # linger / buckets; None falls back to BOLT_SERVE_BATCHING;
        # False is explicitly off.
        if batching is None:
            batching = _DEF_BATCHING
        self.batching = None
        self._batched = None
        if batching:
            if batching is True:
                self.batching = BatchPolicy()
            elif isinstance(batching, BatchPolicy):
                self.batching = batching
            elif isinstance(batching, dict):
                self.batching = BatchPolicy(**batching)
            else:
                raise ValueError(
                    "batching must be True/False, a dict of BatchPolicy "
                    "kwargs, or a BatchPolicy (got %r)" % (batching,))
            # arm() happens at the END of __init__: a constructor that
            # raises past this point must not leak the armed count
            # (nothing would ever disarm it, leaving the lazy-reduce
            # door open with no batching server alive)
        self.workers = int(workers if workers is not None
                           else _DEF_WORKERS)
        self.queue_limit = int(queue_limit if queue_limit is not None
                               else _DEF_QUEUE)
        self.policy = policy
        # weighted fair share: tenant -> integer credits per rotation.
        # The scheduler serves up to weight(t) queued jobs from tenant t
        # before moving to the next tenant with work; the default weight
        # 1 keeps today's one-job-per-tenant round-robin bit-for-bit.
        # The ring still guarantees starvation freedom: any tenant with
        # queued work is served within one rotation (sum of weights).
        self._weights = {}
        if weights:
            for t, w in dict(weights).items():
                w = int(w)
                if w < 1:
                    raise ValueError(
                        "tenant weight must be a positive integer, got "
                        "%r for tenant %r" % (w, t))
                self._weights[str(t)] = w
        self._credits = {}             # tenant -> credits left this turn
        # fleet-warm start (ROADMAP item 4 remainder): attach the
        # pre-seeded on-disk XLA cache BEFORE the first submit, so a
        # fresh process serves its first request without a compile
        # storm; engine counter persistent_warm_hits is the proof
        self.warm_dir = None
        if start_warm is not None:
            self.warm_dir = _engine.warm_start(start_warm)
        self.arbiter = DeviceArbiter(budget_bytes if budget_bytes
                                     is not None else _DEF_BUDGET)
        self._cond = _lockdep.condition("serve.scheduler")
        self._queues = OrderedDict()       # tenant -> deque of jobs
        self._ring = deque()               # tenants with queued jobs
        self._depth = 0
        self._closing = False
        self._stop = threading.Event()     # workers exit once drained
        self._cancel = threading.Event()   # close(wait=False) ONLY: a
        #                                    leased job's arbiter wait
        #                                    must survive a clean drain
        # pod fault integration (ISSUE 11): a peer death drains
        # admission — in-flight streamed futures fail with the
        # executor's PeerLostError (their arbiter leases return in the
        # worker's finally), workers start nothing new — until
        # multihost.reform notifies the liveness layer and the queue
        # resumes.  Subscriptions are deregistered on close().
        self._pod_ok = threading.Event()
        self._pod_ok.set()
        self._pod_lost = None
        self._pod_reason = None
        self._pause_t0 = None
        self._pw_handles = (
            _podwatch.on_peer_death(self._on_peer_death),
            _podwatch.on_reform(self._on_pod_reform))
        # self-healing pods (ISSUE 12): supervise=True attaches a
        # recovery supervisor — peer death still drains admission, but
        # the reform is now DRIVEN automatically (elect → plan →
        # multihost.reform → resume), rejoined processes re-expand the
        # pod through the quiesce gate, and the arbiter budget is
        # rescaled to the surviving capacity share (BLT010 floors
        # recompute against it).  Pass an existing Supervisor (the
        # rejoiner's attach() handle) to adopt it instead.
        self.supervisor = None
        self._own_supervisor = False
        self._budget0 = None
        self._pod_nproc0 = None
        if supervise:
            from bolt_tpu.parallel import multihost as _multihost
            from bolt_tpu.parallel import supervisor as _supervisor
            self._budget0 = self.arbiter.budget
            n = _multihost.process_count()
            self._pod_nproc0 = n if n > 1 else None
            if supervise is True:
                self.supervisor = _supervisor.Supervisor(
                    on_pause=self._sup_pause, on_resume=self._sup_resume)
                self._own_supervisor = True
            else:
                self.supervisor = supervise
                self.supervisor.on_pause = self._sup_pause
                self.supervisor.on_resume = self._sup_resume
        reg = _metrics.registry()
        self._counters = reg.group("serve", _SCHEMA)
        self._tc_cache = {}            # tenant -> registry group (memo)
        self._g_depth = reg.gauge("serve.queue_depth")
        self._g_depth_hw = reg.gauge("serve.queue_depth_high_water")
        self._h_wait = reg.histogram("serve.queue_wait_seconds.hist")
        # batch-occupancy distribution: one observation per coalesced
        # dispatch, value = requests served (log2 buckets cover 1..256)
        self._h_occ = reg.histogram("serve.batch_occupancy.hist",
                                    lo=0, hi=9)
        self._threads = [
            threading.Thread(target=self._worker,
                             name="bolt-serve-worker-%d" % i, daemon=True)
            for i in range(self.workers)]
        for th in self._threads:
            th.start()
        if self.batching is not None:
            # truly LAST: nothing in __init__ can raise past this point,
            # so the armed count can never leak without a server owning
            # its disarm (workers started above consume nothing until
            # the first submit)
            from bolt_tpu.tpu import batched as _batched
            self._batched = _batched
            _batched.arm()            # opens multistat's lazy-reduce door

    # -- pod fault integration (bolt_tpu.parallel.podwatch) ------------

    def _on_peer_death(self, pid):
        """Liveness-watch callback: a pod peer died — drain admission
        until the pod reforms.  Fired from the watch thread."""
        self._pod_lost = pid
        self._pod_ok.clear()
        self._counters.add("peer_losses")
        _obs.event("serve.peer_lost", peer=pid)
        with self._cond:
            self._cond.notify_all()

    def _on_pod_reform(self):
        """Liveness-watch callback: ``multihost.reform`` rebuilt the
        runtime on the survivors — resume the queue."""
        self._pod_lost = None
        self._pod_ok.set()
        _obs.event("serve.pod_resumed")
        with self._cond:
            self._cond.notify_all()

    def pod_paused(self):
        """Is admission drained behind a pod peer loss (awaiting
        ``multihost.reform``)?"""
        return not self._pod_ok.is_set()

    # -- the supervisor's hooks (Server(supervise=True), ISSUE 12) -----

    def _sup_pause(self, reason):
        """Supervisor hook: a recovery started (death or rejoin
        quiesce) — drain admission exactly like a raw peer loss."""
        if self._pod_nproc0 is None:
            # the server may have been constructed BEFORE
            # multihost.initialize (process_count read 1 then): the
            # pre-loss width is still visible at pause time — capture
            # it now, or the post-shrink resume would record the
            # SHRUNK width as full capacity and skip the rescale
            try:
                from bolt_tpu.parallel import multihost as _multihost
                n = _multihost.process_count()
                self._pod_nproc0 = n if n > 1 else None
            except Exception:         # noqa: BLE001 — best effort
                pass
        self._pod_reason = reason
        self._pause_t0 = _clock()
        self._pod_ok.clear()
        _obs.event("serve.supervise_pause", reason=str(reason))
        with self._cond:
            self._cond.notify_all()

    def _sup_resume(self, info):
        """Supervisor hook: the reform landed — count it, rescale the
        arbiter budget to the surviving capacity share (degraded-
        capacity admission: BLT010 floors recompute against the new
        budget on the next submit), and resume the queue."""
        keys = {"rejoins": len(info.get("rejoined", ()))}
        if not info.get("deferred"):
            # a deferred growth resumed the pod UNTOUCHED (no reform
            # happened — the pod never went idle for the quiesce)
            keys["reforms"] = 1
        if self._pause_t0 is not None:
            keys["supervise_seconds"] = _clock() - self._pause_t0
            self._pause_t0 = None
        self._counters.update(**keys)
        nproc = int(info.get("nproc") or 0)
        if nproc > 1 and self._budget0 is not None:
            if self._pod_nproc0 is None or nproc > self._pod_nproc0:
                self._pod_nproc0 = nproc      # full capacity sighting
            share = nproc / self._pod_nproc0
            self.arbiter.resize(max(1, int(self._budget0 * share)))
        self._pod_reason = None
        self._pod_lost = None
        self._pod_ok.set()
        _obs.event("serve.supervise_resume", nproc=nproc)
        with self._cond:
            self._cond.notify_all()

    # -- submission ----------------------------------------------------

    def _tenant_counters(self, tenant):
        # memoised per server: the registry group lookup (string format
        # + registry lock) measured as a real per-request cost on the
        # high-QPS small-request path (3-4 lookups per job)
        g = self._tc_cache.get(tenant)
        if g is None:
            g = self._tc_cache[tenant] = _metrics.registry().group(
                "serve/%s" % tenant, _SCHEMA)
        return g

    def _reject(self, tenant, why):
        self._counters.add("rejected")
        self._tenant_counters(tenant).add("rejected")
        raise AdmissionError(why)

    def submit(self, pipeline, tenant="default", retries=0,
               deadline=None):
        """Queue ``pipeline`` for tenant ``tenant``; returns a
        :class:`Future`.  Raises :class:`AdmissionError` when the
        pipeline can never fit the arbiter budget (BLT010), or when the
        queue is full under ``policy="reject"``; under
        ``policy="queue"`` a full queue BLOCKS the submitter until a
        worker frees a slot (backpressure, not unbounded memory).

        Per-submit fault policy (ISSUE 9 — tenant failures stay
        isolated): ``retries=n`` re-runs a raising job up to *n* times
        on its worker (each attempt's exception chained to the one
        before; the arbiter lease spans the attempts and is ALWAYS
        returned); ``deadline=s`` bounds seconds-since-submit — a job
        still queued past it fails with :class:`DeadlineError` instead
        of running, and an expired deadline also stops further
        retries.  Neither affects other tenants' futures."""
        tenant = str(tenant)
        sp = _obs.begin("serve.submit", tenant=tenant)
        try:
            return self._admit(pipeline, tenant, retries, deadline)
        finally:
            _obs.end(sp)

    def _admit(self, pipeline, tenant, retries, deadline):
        """:meth:`submit`'s body: the drain gate, the estimate and the
        BLT010 floor, the batch key, the bounded queue."""
        if self._closing:
            raise RuntimeError("serve.Server is closed")
        if not self._pod_ok.is_set():
            # admission is drained behind a pod peer loss: reject-policy
            # servers refuse pointedly, queue-policy servers apply
            # backpressure until multihost.reform resumes the pod
            if self.policy == "reject":
                why = ("pod peer %s was lost" % self._pod_lost
                       if self._pod_lost is not None
                       else "supervised recovery in progress (%s)"
                       % self._pod_reason)
                self._reject(tenant,
                             "admission drained: %s and the pod has "
                             "not reformed yet (multihost.reform "
                             "resumes the queue)" % why)
            while not self._pod_ok.wait(0.05):
                if self._closing:
                    raise RuntimeError("serve.Server is closed")
                sup = self.supervisor
                if sup is not None and sup.failed is not None:
                    self._reject(tenant,
                                 "supervised recovery abandoned (%s); "
                                 "admission stays drained until a "
                                 "manual multihost.reform" % sup.failed)
        retries = max(0, int(retries))
        if deadline is not None:
            deadline = float(deadline)
            if deadline <= 0:
                raise ValueError("deadline must be positive seconds "
                                 "since submit, got %r" % (deadline,))
        job, arr = _normalise(pipeline)
        # the SUBMITTER's effective ingest codec rides into the worker
        # (ISSUE 14): stream scopes are thread-local, so a tenant's
        # `with stream.codec("bf16"): submit(...)` would otherwise be
        # silently dropped on the worker thread — while the admission
        # floor below, computed HERE, already priced the wire bytes.
        # current_codec() collapses scope + process default into one
        # name, so re-entering it on the worker preserves exactly the
        # semantics the submitter saw (a per-source codec= still wins).
        from bolt_tpu import stream as _streamlib
        cname = _streamlib.current_codec()
        if cname is not None:
            inner = job

            def job():
                with _streamlib.codec(cname):
                    return inner()
        est = _estimate(arr) if arr is not None else None
        if est is not None and est > self.arbiter.budget:
            # BLT010: could NEVER run — admitting it would wedge a
            # worker forever (analysis.check emits the same finding)
            self._reject(tenant,
                         "pipeline needs ~%d bytes of device memory but "
                         "the serving budget is %d bytes (BLT010); "
                         "shrink the slabs/operand or raise "
                         "budget_bytes" % (est, self.arbiter.budget))
        fut = Future(tenant)
        # streaming pipelines lease per slab INSIDE the executor — an
        # upfront worker lease on top would double-charge the budget
        # (and deadlock it when budget ~ one slab).  A stream hides in
        # two shapes: a raw stream-backed array, or a pending-stat
        # handle whose GROUP folds a stream source.
        streaming = False
        if arr is not None:
            if getattr(arr, "_stream", None) is not None:
                streaming = True
            else:
                h = getattr(arr, "_spending", None)
                if h is not None and h.group.kind == "stream":
                    streaming = True
        # the batch key (continuous micro-batching): the coalescing
        # identity of an in-memory lazy pipeline — None keeps the job
        # on the standalone path (callables, streams, donating chains,
        # batching off)
        bkey = None
        bt = self._batched            # close() clears it; a submit
        if bt is not None and arr is not None and not streaming:
            bkey = bt.batch_key(arr)  # racing a close must fall to the
            #                           documented closed-server error,
            #                           not an AttributeError
        admitted = False
        with self._cond:
            while self._depth >= self.queue_limit and not self._closing \
                    and self.policy != "reject":
                self._cond.wait(0.05)     # backpressure: block submitter
            if self._closing:
                raise RuntimeError("serve.Server is closed")
            if self._depth < self.queue_limit:
                q = self._queues.get(tenant)
                if q is None:
                    q = self._queues[tenant] = deque()
                    self._ring.append(tenant)
                # streaming pipelines lease per slab inside the
                # executor; in-memory pipelines lease their estimated
                # working set around the dispatch
                q.append((fut, job, None if streaming else est, retries,
                          deadline, bkey,
                          arr if bkey is not None else None))
                self._depth += 1
                self._g_depth.set(self._depth)
                self._g_depth_hw.high_water(self._depth)
                self._cond.notify_all()
                admitted = True
        if not admitted:
            self._reject(tenant,
                         "admission queue is full (%d queued, limit %d, "
                         "policy='reject')" % (self.queue_limit,
                                               self.queue_limit))
        self._counters.add("submitted")
        self._tenant_counters(tenant).add("submitted")
        return fut

    # -- the worker loop -----------------------------------------------

    def _pop(self):
        """Next job, weighted round-robin across tenants (FIFO within
        one); None once the server is draining and every queue is
        empty.  A tenant at the head of the ring is served up to its
        WEIGHT jobs (integer credits, default 1 — bit-for-bit the old
        round-robin) before the rotation advances; credits reset each
        time the tenant returns to the head, and a tenant whose queue
        drains mid-turn forfeits the rest of its credits."""
        with self._cond:
            while True:
                if not self._pod_ok.is_set() and not self._stop.is_set():
                    # peer lost: drain — current jobs finish (or fail
                    # with PeerLostError), nothing new starts until the
                    # reform notification (close() still drains: a
                    # stopping server must terminate, and its jobs fail
                    # fast against the dead pod)
                    self._cond.wait(0.05)
                    continue
                for _ in range(len(self._ring)):
                    t = self._ring[0]
                    q = self._queues.get(t)
                    if not q:
                        self._ring.rotate(-1)
                        continue
                    item = q.popleft()
                    credit = self._credits.pop(
                        t, self._weights.get(t, 1)) - 1
                    if not q:
                        del self._queues[t]
                        self._ring.remove(t)
                    elif credit > 0:
                        # weight left and work left: stay at the head
                        # for the next pop
                        self._credits[t] = credit
                    else:
                        self._ring.rotate(-1)
                    self._depth -= 1
                    self._g_depth.set(self._depth)
                    self._cond.notify_all()
                    return t, item
                if self._stop.is_set():
                    return None
                self._cond.wait(0.05)

    def _run_attempts(self, job, fut, tenant, nretry, deadline):
        """Execute one job with its per-submit retry/deadline policy:
        an expired deadline stops further attempts, and the chaining
        (oldest-first back to the original; pointed error on an
        exhausted budget; the untouched original at budget 0) is the
        shared ``utils.chain_retry_step`` — one policy for serve AND
        the streaming executor's slab retries."""
        from bolt_tpu.utils import chain_retry_step
        attempt = 0
        prev = None
        while True:
            try:
                return job()
            except BaseException as exc:    # noqa: BLE001 — delivered
                expired = deadline is not None and \
                    _clock() - fut.submitted_s > deadline
                allowed = attempt < nretry and not expired \
                    and not self._cancel.is_set()
                poisoned_backend = (
                    isinstance(exc, RuntimeError)
                    and "Unable to initialize backend" in str(exc))
                if allowed and poisoned_backend:
                    # a failed topology exchange leaves this process's
                    # own KV key behind, so a verbatim re-attempt dies
                    # instantly on ALREADY_EXISTS (and starves every
                    # peer waiting on a fresh insert) — purge the stale
                    # keys first so the retry can actually bring the
                    # backend up (multihost.heal_backend_init)
                    from bolt_tpu.parallel import multihost as _mh
                    _mh.heal_backend_init()
                if allowed and (isinstance(exc, PeerLostError)
                                or poisoned_backend):
                    # a pod outage IS retryable (the whole point of
                    # retries= under serving) — but only once the pod
                    # reforms: hold the re-attempt behind the admission
                    # drain instead of burning the budget into a dead
                    # pod.  A latched QUIESCE holds it too — the gate
                    # can trip BEFORE this process's own supervisor
                    # pauses admission (process 0 decides first), and a
                    # re-run in that window would stream into peers
                    # already tearing down for the reform.  Deadline,
                    # cancel AND a closing server cut it off —
                    # close(wait=True) must terminate even when the
                    # reform never comes.
                    while allowed and (
                            not self._pod_ok.wait(0.05)
                            or _podwatch.quiesce_requested()
                            is not None):
                        if _podwatch.quiesce_requested() is not None:
                            self._stop.wait(0.05)
                        if self._cancel.is_set() or self._stop.is_set() \
                                or (deadline is not None
                                    and _clock() - fut.submitted_s
                                    > deadline):
                            allowed = False
                        sup = self.supervisor
                        if sup is not None and sup.failed is not None:
                            # the supervisor gave up (retry budget
                            # exhausted): deliver the loss instead of
                            # holding for a reform that never comes
                            allowed = False
                if allowed:
                    self._counters.add("retried")
                    self._tenant_counters(tenant).add("retried")
                    _obs.event("serve.retry", tenant=tenant,
                               attempt=attempt + 1,
                               error=type(exc).__name__)
                prev = chain_retry_step(exc, prev, attempt, allowed,
                                        "serve job", "submit retries=")
                attempt += 1

    def _worker(self):
        while True:
            got = self._pop()
            if got is None:
                return
            tenant, item = got
            extras = ()
            t_gather = _clock()
            if item[5] is not None and self.batching is not None:
                extras = self._gather_batch(item[5], item[2])
            if extras:
                self._run_batch([(tenant, item)] + extras, t_gather)
            else:
                self._run_one(tenant, item)

    def _run_one(self, tenant, item):
        """Execute one job standalone (the pre-batching worker body)."""
        fut, job, est, nretry, deadline = item[:5]
        fut.started_s = _clock()
        wait = fut.started_s - fut.submitted_s
        self._counters.add("queue_wait_seconds", wait)
        self._tenant_counters(tenant).add("queue_wait_seconds", wait)
        self._h_wait.observe(wait)
        _obs.record("serve.queue", fut.submitted_s, fut.started_s,
                    tenant=tenant)
        sp = _obs.begin("serve.run", tenant=tenant,
                        queued_s=round(wait, 6))
        lease = self.arbiter.lease(tenant) if est else None
        granted = False
        try:
            with _engine.tenant(tenant):
                if deadline is not None and wait > deadline:
                    # expired while queued: fail WITHOUT running —
                    # the tenant's latency budget is already blown
                    self._counters.add("expired")
                    self._tenant_counters(tenant).add("expired")
                    raise DeadlineError(
                        "deadline %.3fs exceeded before the job "
                        "started (queued %.3fs)" % (deadline, wait))
                # stop on CANCEL only: a close(wait=True) drain must
                # let queued leased jobs wait out the arbiter and run
                if lease is not None:
                    lsp = _obs.begin("serve.lease", tenant=tenant)
                    try:
                        granted = lease.acquire(est, stop=self._cancel)
                    finally:
                        _obs.end(lsp)
                    if not granted:
                        raise RuntimeError(
                            "server cancelled before the job's working "
                            "set (%d bytes) was granted" % est)
                out = self._run_attempts(job, fut, tenant, nretry,
                                         deadline)
            fut._finish(result=out)
            key = "completed"
        except BaseException as exc:    # noqa: BLE001 — delivered
            fut._finish(exc=exc)        # through Future.result()
            key = "failed"
        finally:
            if lease is not None:
                lease.close()           # leases are ALWAYS returned
            _obs.end(sp)
        deltas = {key: 1, "run_seconds": fut.finished_s - fut.started_s}
        if granted:
            deltas["leased"] = 1
        self._counters.update(**deltas)
        self._tenant_counters(tenant).update(**deltas)

    # -- continuous micro-batching (bolt_tpu/tpu/batched.py) -----------

    def _gather_batch(self, bkey, head_est):
        """Pull every queued job sharing ``bkey`` — ACROSS tenants,
        FIFO within each — up to the policy's ``max_batch``, lingering
        up to ``linger`` seconds to fill the bucket once at least one
        partner was found.  A gather that finds nothing returns
        immediately (a lone request never waits).  Width is ALSO capped
        by the arbiter budget: the coalesced dispatch's footprint is
        the members' working sets PLUS the bucket-width stacked input
        copy (~2x the sum), and assembling a batch the budget would
        have serialised per-request must not bypass that arbitration.
        Gathered jobs bypass the weighted-rotation credits: coalescing
        is work-conserving — it only accelerates jobs that would
        otherwise each pay their own dispatch, and the batch serves
        multiple tenants at once."""
        pol = self.batching
        limit = pol.max_batch - 1       # the popped head is lane 0
        est = int(head_est or 0)
        if est:
            # equal keys ⇒ equal geometry ⇒ equal per-request estimate:
            # the coalesced lease is (W + bucket_width(W)) x est — the
            # members plus the PADDED stacked copy — so pick the widest
            # W the budget covers (a batch the budget would have
            # serialised per-request must not assemble and then hit the
            # arbiter's runs-alone escape)
            from bolt_tpu.tpu.batched import bucket_width
            w = 1
            for cand in range(pol.max_batch, 1, -1):
                if (cand + bucket_width(cand, pol.buckets)) * est \
                        <= self.arbiter.budget:
                    w = cand
                    break
            limit = min(limit, w - 1)
        out = []
        t0 = None
        while limit > 0:
            with self._cond:
                for t in list(self._queues):
                    if len(out) >= limit:
                        break
                    q = self._queues[t]
                    keep = deque()
                    # stop as soon as the batch fills: examined
                    # non-matching jobs go back to the FRONT in order,
                    # the unexamined tail is never touched — the scan
                    # is O(taken + skipped), not O(queue depth)
                    while q and len(out) < limit:
                        it = q.popleft()
                        if it[5] == bkey:
                            out.append((t, it))
                            self._depth -= 1
                        else:
                            keep.append(it)
                    if keep:
                        q.extendleft(reversed(keep))
                    elif not q:
                        del self._queues[t]
                        self._ring.remove(t)
                        self._credits.pop(t, None)
                if out:
                    self._g_depth.set(self._depth)
                    self._cond.notify_all()   # free blocked submitters
                full = len(out) >= limit
                stopping = (self._closing or self._stop.is_set()
                            or self._cancel.is_set())
            if full or stopping or pol.linger <= 0 or not out:
                return out
            now = _clock()
            if t0 is None:
                t0 = now
            rem = pol.linger - (now - t0)
            if rem <= 0:
                return out
            with self._cond:
                self._cond.wait(rem)    # a submit notifies the cond
        return out                      # budget-capped width < 2: the
        #                                 head runs standalone under its
        #                                 own per-request arbitration

    def _run_batch(self, items, t_gather):
        """One coalesced dispatch serving ``len(items)`` same-key
        requests: per-request wait/deadline/lease accounting first
        (attribution preserved — every future keeps its own wait, run
        and assembly seconds, every tenant its own counters), then ONE
        claimed batched program (``batched.claim``/``dispatch``), then
        per-request adoption through the normal retry machinery.  Any
        claim/dispatch failure degrades every live request to its
        standalone dispatch — batching is an optimisation, never a new
        failure mode.  Note: the coalesced dispatch itself is
        CROSS-TENANT and runs outside any ``engine.tenant`` scope — its
        engine counters (dispatches, transfer bytes) land in the global
        tally only; per-tenant SERVE counters are unaffected."""
        width = len(items)
        bsp = _obs.begin("serve.batch", width=width)
        t_start = _clock()
        live = []
        lease = None
        # per-request attribution is preserved, but the COUNTER totals
        # apply once per (batch, tenant): every locked registry update
        # measured as real per-request cost at small-request QPS, and
        # totals aggregate identically
        agg = {}

        def _acc(tenant, **deltas):
            d = agg.setdefault(tenant, {})
            for k, v in deltas.items():
                d[k] = d.get(k, 0 if isinstance(v, int) else 0.0) + v
        try:
            for t, it in items:
                fut, _, est, _, dl = it[:5]
                fut.started_s = t_start
                wait = t_start - fut.submitted_s
                _acc(t, queue_wait_seconds=wait)
                self._h_wait.observe(wait)
                _obs.record("serve.queue", fut.submitted_s, t_start,
                            tenant=t)
                if dl is not None and wait > dl:
                    _acc(t, expired=1)
                    self._finish_batched(t, fut, None, DeadlineError(
                        "deadline %.3fs exceeded before the job "
                        "started (queued %.3fs)" % (dl, wait)), _acc)
                    continue
                live.append((t, it))
            # ONE summed lease covers the whole coalesced dispatch —
            # the members' working sets PLUS the bucket-width stacked
            # input copy the batched program materialises (pad lanes
            # included); accounted under the head tenant — per-request
            # arbiter round-trips measured as a real cost at
            # small-request QPS
            total_est = sum(it[2] or 0 for _, it in live)
            if len(live) > 1 and total_est:
                total_est += self._batched.bucket_width(
                    len(live), self.batching.buckets) * max(
                    it[2] or 0 for _, it in live)
            if live and total_est:
                lease = self.arbiter.lease(live[0][0])
                lsp = _obs.begin("serve.lease", tenant=live[0][0])
                try:
                    granted = lease.acquire(total_est, stop=self._cancel)
                finally:
                    _obs.end(lsp)
                if granted:
                    for t, it in live:
                        if it[2]:
                            _acc(t, leased=1)
                else:
                    lease.close()
                    lease = None
                    for t, it in live:
                        self._finish_batched(t, it[0], None, RuntimeError(
                            "server cancelled before the batch's "
                            "working set (%d bytes) was granted"
                            % total_est), _acc)
                    live = []
            batch = None
            if len(live) > 1:
                try:
                    batch = self._batched.claim(
                        [it[6] for _, it in live], live[0][1][5])
                    if batch is not None:
                        # assembly = pop -> dispatch begin: the gather
                        # scan, the linger micro-wait and the claim —
                        # the documented gather+linger+stack window,
                        # NOT the device execution (run_seconds covers
                        # that)
                        asm = _clock() - t_gather
                        self._batched.dispatch(batch,
                                               self.batching.buckets)
                        # realised coalescing only: a degraded gather
                        # (failed claim/dispatch, expired members) must
                        # not count as a coalesced dispatch, and only
                        # requests the dispatch actually SERVED carry
                        # the batch attribution (claim may drop raced
                        # members — they dispatch standalone below and
                        # keep the documented None)
                        served = {id(a) for a in batch.arrs}
                        self._h_occ.observe(len(served))
                        for _, it in live:
                            if id(it[6]) in served:
                                it[0].batch_width = len(served)
                                it[0].assembly_seconds = asm
                except BaseException:   # noqa: BLE001 — degrade, the
                    if batch is not None:   # per-request adoption below
                        self._batched.unclaim(batch)   # re-dispatches
                    #                                    standalone
            # adoption (or standalone execution when the claim/dispatch
            # degraded): the normal per-request retry/exception path
            for t, it in live:
                fut, job, _, nretry, dl = it[:5]
                sp = _obs.begin("serve.run", tenant=t, batched=width)
                try:
                    try:
                        with _engine.tenant(t):
                            out = self._run_attempts(job, fut, t,
                                                     nretry, dl)
                        self._finish_batched(t, fut, out, None, _acc)
                    except BaseException as exc:    # noqa: BLE001
                        self._finish_batched(t, fut, None, exc, _acc)
                finally:
                    _obs.end(sp)
        finally:
            if lease is not None:
                lease.close()           # leases are ALWAYS returned
            for t, deltas in agg.items():
                self._counters.update(**deltas)
                self._tenant_counters(t).update(**deltas)
            _obs.end(bsp)

    def _finish_batched(self, tenant, fut, result, exc, acc):
        """Deliver one batched request's outcome: identical future
        delivery to the standalone path's, counters accumulated into
        the batch's per-tenant aggregate instead of N locked registry
        updates."""
        if exc is None:
            fut._finish(result=result)
            key = "completed"
        else:
            fut._finish(exc=exc)
            key = "failed"
        acc(tenant, **{key: 1,
                       "run_seconds": fut.finished_s - fut.started_s})

    # -- lifecycle / introspection -------------------------------------

    def queue_depth(self):
        with self._cond:
            return self._depth

    def stats(self):
        """One consistent-ish status dict: global serve counters, queue
        depth, arbiter state, a ``"batching"`` summary, and a
        per-tenant breakdown (serve counters + LIVE queue depth + that
        tenant's scoped ENGINE counters — transfer bytes, dispatches,
        compiles).

        Documented DEGRADED shapes (like ``profile.memory_stats``):
        ``"batching"`` is ``{}`` — never an AttributeError — when the
        server runs without a batching policy, and its ``"occupancy"``
        sub-dict is ``{}`` until the first coalesced dispatch;
        ``"tenants"`` is ``{}`` before any submit, and a tenant that
        only ever queued (never ran) still appears with zeroed run
        counters and its live ``queue_depth``."""
        reg = _metrics.registry()
        with self._cond:
            depths = {t: len(q) for t, q in self._queues.items()}
        out = {"queue_depth": self.queue_depth(),
               "queue_depth_high_water": self._g_depth_hw.value,
               "arbiter": {"budget_bytes": self.arbiter.budget,
                           "in_use_bytes": self.arbiter.in_use(),
                           "in_use_high_water": reg.gauge(
                               "serve.arbiter_in_use_high_water").value,
                           "waits": reg.counter(
                               "serve.arbiter_waits").value},
               "pod": {"paused": self.pod_paused(),
                       "lost_peer": self._pod_lost,
                       "reason": self._pod_reason,
                       "supervised": self.supervisor is not None,
                       "quarantine": (self.supervisor.quarantined()
                                      if self.supervisor is not None
                                      else []),
                       "budget_share": (
                           self.arbiter.budget / self._budget0
                           if self._budget0 else 1.0)},
               "batching": self._batching_stats(),
               "totals": self._counters.snapshot(),
               "tenants": {}}
        for name in reg.names():
            if name.startswith("serve/"):
                t = name.split("/", 1)[1]
                entry = dict(reg.get(name).snapshot())
                eng = _engine.tenant_counters(t)
                entry["transfer_bytes"] = eng["transfer_bytes"]
                entry["dispatches"] = eng["dispatches"]
                entry["aot_compiles"] = eng["aot_compiles"]
                entry["queue_depth"] = depths.pop(t, 0)
                out["tenants"][t] = entry
        for t, d in depths.items():
            # queued-but-never-counted tenants (a submit can sit queued
            # before its counter group exists under races): still show
            # their live depth
            out["tenants"].setdefault(t, {})["queue_depth"] = d
        return out

    def _batching_stats(self):
        """The ``stats()["batching"]`` block: ``{}`` when batching is
        off; else the policy knobs plus the realised coalescing — the
        engine's ``batched_dispatches``/``batched_requests`` tallies
        and a batch-occupancy summary derived from the
        ``serve.batch_occupancy.hist`` registry histogram (``{}`` until
        the first coalesced dispatch).  Like the engine counters these
        are PROCESS-global tallies — a second batching server in one
        process inherits its predecessor's totals."""
        pol = self.batching
        if pol is None:
            return {}
        ec = _engine.counters()
        occ = {}
        snap = self._h_occ.snapshot()
        if snap["count"]:
            occ = {"dispatches": snap["count"],
                   "mean": round(snap["sum"] / snap["count"], 2),
                   "buckets": [(b, c) for b, c in self._h_occ.buckets()
                               if c]}
        return {"max_batch": pol.max_batch,
                "linger": pol.linger,
                "buckets": pol.buckets,
                "batched_dispatches": ec["batched_dispatches"],
                "batched_requests": ec["batched_requests"],
                "occupancy": occ}

    def close(self, wait=True):
        """Stop the server.  ``wait=True`` drains queued jobs first and
        joins the workers; ``wait=False`` fails every queued job with a
        RuntimeError and returns once workers exit their current job."""
        with self._cond:
            self._closing = True
            if not wait:
                self._cancel.set()
                while self._queues:
                    _, q = self._queues.popitem()
                    for fut, *_ in q:
                        fut._finish(exc=RuntimeError(
                            "serve.Server closed before this job ran"))
                self._ring.clear()
                self._depth = 0
                self._g_depth.set(0)
            self._stop.set()
            self._cond.notify_all()
        for th in self._threads:
            th.join()
        for h in self._pw_handles:
            _podwatch.remove_callback(h)   # a closed server must not
            #                                pause/resume from beyond
        if self.supervisor is not None:
            if self._own_supervisor:
                self.supervisor.close()
            else:                          # adopted: detach our hooks,
                self.supervisor.on_pause = None    # leave it running
                self.supervisor.on_resume = None
        if self.warm_dir is not None:
            # the warm tally covers THIS server's lifetime; the cache
            # stays attached (artifacts keep serving), only the
            # persistent_warm_hits arming ends
            _engine.disarm_warm_start()
        if self._batched is not None:
            self._batched.disarm()     # closes the lazy-reduce door
            self._batched = None       # (idempotent across re-close)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close(wait=exc == (None, None, None))


# ---------------------------------------------------------------------
# the module-level (default-server) doors
# ---------------------------------------------------------------------

_ACTIVE = None
_ACTIVE_LOCK = _lockdep.lock("serve.active")


def start(workers=None, budget_bytes=None, queue_limit=None,
          policy="queue", weights=None, start_warm=None,
          supervise=False, batching=None):
    """Start and install THE process server (at most one may be active
    — the arbiter is only a global budget if there is one of it).
    Returns the :class:`Server`."""
    global _ACTIVE
    with _ACTIVE_LOCK:
        if _ACTIVE is not None:
            raise RuntimeError(
                "a serve.Server is already active; stop() it first "
                "(the device-memory budget must have one owner)")
        _ACTIVE = Server(workers=workers, budget_bytes=budget_bytes,
                         queue_limit=queue_limit, policy=policy,
                         weights=weights, start_warm=start_warm,
                         supervise=supervise, batching=batching)
        return _ACTIVE


def stop(wait=True):
    """Stop and uninstall the active server (no-op when none is)."""
    global _ACTIVE
    with _ACTIVE_LOCK:
        sv, _ACTIVE = _ACTIVE, None
    if sv is not None:
        sv.close(wait=wait)


def active():
    """The installed :class:`Server`, or None."""
    return _ACTIVE


def device_arbiter():
    """The active server's :class:`DeviceArbiter` (None when no server
    is running) — the door ``bolt_tpu.stream`` checks per run."""
    sv = _ACTIVE
    return sv.arbiter if sv is not None else None


def submit(pipeline, tenant="default", retries=0, deadline=None):
    """Submit through the active server, lazily starting the default
    one (env-tuned) when none is running."""
    global _ACTIVE
    sv = _ACTIVE
    if sv is None:
        with _ACTIVE_LOCK:
            if _ACTIVE is None:
                _ACTIVE = Server()
            sv = _ACTIVE
    return sv.submit(pipeline, tenant=tenant, retries=retries,
                     deadline=deadline)


@contextlib.contextmanager
def serving(workers=None, budget_bytes=None, queue_limit=None,
            policy="queue", weights=None, start_warm=None,
            supervise=False, batching=None):
    """Scoped server lifetime::

        with bolt_tpu.serve.serving(workers=4) as sv:
            fut = sv.submit(pipeline, tenant="a")
            out = fut.result()

    Drains and stops on clean exit; cancels queued jobs when the body
    raised.  ``weights={tenant: n}`` generalises the round-robin to a
    weighted fair share (integer credits per rotation; default 1 keeps
    the plain round-robin); ``start_warm=dir`` preloads the engine's
    persistent-cache artifacts so a fresh process serves its first
    request without a compile storm; ``supervise=True`` attaches the
    pod recovery supervisor (``parallel.supervisor``) — peer death and
    rejoin reform the pod automatically, held ``retries=`` re-attempts
    resume from the checkpoint, and the arbiter budget tracks the
    surviving capacity share; ``batching=True`` (or a
    :class:`BatchPolicy` / dict of its kwargs) arms continuous
    micro-batching — queued same-key small requests coalesce into ONE
    stacked dispatch, bit-identical to standalone, at bucketed
    widths."""
    sv = start(workers=workers, budget_bytes=budget_bytes,
               queue_limit=queue_limit, policy=policy, weights=weights,
               start_warm=start_warm, supervise=supervise,
               batching=batching)
    try:
        yield sv
    except BaseException:
        stop(wait=False)
        raise
    else:
        stop(wait=True)
