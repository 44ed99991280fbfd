"""Structured span tracer: the observability layer's timing backbone.

Every earlier PR grew its own wall-clock bookkeeping — ``engine.py``
timed lower/compile/dispatch, ``stream.py`` timed ingest/compute/wall,
``construct.py`` timed uploads — each with a raw ``time.perf_counter()``
pair feeding a counter.  That gives totals but no *structure*: you can
see that a streamed reduction spent 2 s ingesting, but not whether the
ingest was hidden behind compute, which slab stalled, or how much of a
dispatch was XLA compilation.  This module adds the structure:

* :func:`span` — a context manager / decorator recording a named,
  attributed, *nested* time interval (``obs.span("stream.compute",
  slab=3)``); completed spans land in a bounded in-memory ring.
* :func:`begin` / :func:`end` — the allocation-free hot-path form the
  engine and executor call directly: when tracing is disabled,
  ``begin`` is one module-global check returning ``None`` and ``end``
  returns immediately, so instrumented dispatch paths stay counter-only.
* :func:`event` — a zero-duration instant mark (donation grants,
  strict-gate rejections).
* cross-thread nesting by EXPLICIT handoff: the streaming executor
  captures its run span and passes it as ``parent=`` to the spans its
  prefetch thread begins, so a timeline shows ingest *under* the run
  that caused it even though another thread did the work.
* :func:`clock` — the ONE blessed monotonic timer.  Lint rule BLT106
  (``bolt_tpu/analysis/astlint.py``) forbids raw ``time.perf_counter()``
  bookkeeping outside ``obs/``/``profile.py``; timing code elsewhere in
  the package imports this symbol instead, so every duration in the
  system comes from the same clock and can be correlated on one
  timeline.

* the profiler bridge: spans are recorded while the tracer is armed OR
  while a ``jax.profiler`` session is live, and in a live session each
  span also opens a ``TraceAnnotation`` named ``bolt.<span name>``
  carrying its attributes and ``rid``, so bolt's spans lie on the device
  trace's own clock in any profile anyone takes.  The jax side is handed
  in by the package (:func:`set_bridge`, called from ``profile.py``).
* :func:`totals` — running per-name count / seconds / self seconds kept
  beside the ring, so an aggregate survives a ring that wrapped.

Tracing is OFF by default.  :func:`enable` arms it process-wide;
:func:`bolt_tpu.obs.timeline` scopes it around one run and writes a
Chrome trace-event file; a live profiler session arms it for as long as
the session lasts.  This module imports ONLY the standard library.
"""

import functools
import itertools
import os
import sys
import threading
import time
from collections import deque

# THE timing primitive (see module docstring / lint rule BLT106)
clock = time.perf_counter


def _lockdep():
    """bolt_tpu/_lockdep.py (the ranked lock inventory), loaded by path
    under its canonical name when the package is not imported: this
    module stays stdlib-only standalone, and a later ``bolt_tpu``
    import adopts the SAME witness instance."""
    mod = sys.modules.get("bolt_tpu._lockdep")
    if mod is None:
        import importlib.util
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "_lockdep.py")
        spec = importlib.util.spec_from_file_location(
            "bolt_tpu._lockdep", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules["bolt_tpu._lockdep"] = mod
        spec.loader.exec_module(mod)
    return mod


_RING_DEFAULT = 4096

_ON = False                      # the hot-path flag ...
_LIVE = None                     # ... and probe: is a profiler session live?
_ANNOTATE = None                 # (name, **stats) -> an entered annotation
_LOCK = _lockdep().lock("obs.trace")   # guards ring, totals, the leak gate
_RING = deque(maxlen=_RING_DEFAULT)
_TOTALS = {}                     # path -> [count, seconds, self s, bytes]
# the leak gate (active_count) costs begin() nothing: a span's id is drawn
# from _IDS, whose next() needs no lock, and spans open are ids drawn
# since _BASE (the id clear() drew) less the ids active_count() drew
# itself to look (_PEEKS) and the spans closed since (_CLOSED)
_IDS = itertools.count(1)
_BASE = 0
_PEEKS = 0
_CLOSED = 0
_TLS = threading.local()         # per-thread open-span stack


class Span:
    """One recorded interval: ``name``, ``attrs``, ids and timestamps.

    ``sid`` is the span's id, ``pid`` its parent span's id (0 = root),
    ``rid`` the ``sid`` of its root — the request it belongs to,
    inherited through ``parent`` and so across the explicit cross-thread
    hand-off; ``path`` the names from that root down to this span;
    ``tid``/``tname`` identify the recording thread; ``t0``/``t1`` are
    :func:`clock` seconds (``t1`` is ``None`` while open).  ``kind`` is
    ``"S"`` for spans, ``"I"`` for instant events."""

    __slots__ = ("name", "attrs", "sid", "pid", "rid", "path", "tid",
                 "tname", "t0", "t1", "kind", "_kids", "_ann")

    def __init__(self, name, attrs, sid, parent, tid, tname, kind="S"):
        self.name = name
        self.attrs = attrs
        self.sid = sid
        if parent is None:
            self.pid, self.rid, self.path = 0, sid, (name,)
        else:
            self.pid, self.rid = parent.sid, parent.rid
            self.path = parent.path + (name,)
        self.tid = tid
        self.tname = tname
        self.t1 = None
        self.kind = kind
        self._kids = 0.0             # seconds in same-thread children
        self._ann = None             # the profiler's annotation, if live
        self.t0 = clock()

    def set(self, **attrs):
        """Attach attributes to an open span; chainable."""
        self.attrs.update(attrs)
        return self

    @property
    def duration(self):
        """Seconds from begin to end (``None`` while still open)."""
        return None if self.t1 is None else self.t1 - self.t0

    @property
    def self_seconds(self):
        """``duration`` less the direct children that ran on the same
        thread (a child handed to another thread overlaps its parent's
        own work rather than displacing it)."""
        return None if self.t1 is None else self.t1 - self.t0 - self._kids

    def __repr__(self):
        dur = "open" if self.t1 is None else "%.6fs" % (self.t1 - self.t0)
        return "<Span %s sid=%d pid=%d %s>" % (self.name, self.sid,
                                               self.pid, dur)


class _NullSpan:
    """What :class:`span` yields while tracing is disabled: every method
    is a no-op, so ``with obs.span(...) as sp: sp.set(...)`` costs
    nothing when off."""

    __slots__ = ()

    def set(self, **attrs):
        return self

    duration = None


_NULL = _NullSpan()


def _stack():
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


def enabled():
    """Is the tracer armed by :func:`enable`?  (A live profiler session
    records too, armed or not.)"""
    return _ON


def set_bridge(is_live, annotate):
    """Hand the tracer the profiler's side (``profile.py`` does, so this
    module stays standard-library only).  ``is_live()`` says whether a
    profiler session is recording; ``annotate(name, **stats)`` returns
    an ENTERED annotation with ``set_metadata(**stats)`` and
    ``__exit__(None, None, None)``.  ``set_bridge(None, None)`` takes
    the bridge out."""
    global _LIVE, _ANNOTATE
    _LIVE, _ANNOTATE = is_live, annotate


def enable(ring=None):
    """Arm tracing process-wide.  ``ring`` bounds the completed-span
    buffer (oldest spans fall off); ``None`` means the default capacity
    (4096) — every ``enable()`` states its capacity rather than
    inheriting whatever a previous scope set.  Returns the capacity in
    effect."""
    global _ON, _RING
    want = _RING_DEFAULT if ring is None else max(1, int(ring))
    with _LOCK:
        if want != _RING.maxlen:
            _RING = deque(_RING, maxlen=want)
        _ON = True
        return _RING.maxlen


def disable():
    """Disarm tracing (the ring keeps its completed spans for export)."""
    global _ON
    _ON = False


def clear():
    """Drop every completed span, zero the totals and the leak counter
    (open spans begun before ``clear`` still end cleanly — ``end``
    tolerates an already-cleared ring)."""
    global _BASE, _PEEKS, _CLOSED
    with _LOCK:
        _RING.clear()
        _TOTALS.clear()
        _BASE, _PEEKS, _CLOSED = next(_IDS), 0, 0


def spans():
    """A consistent snapshot list of the completed-span ring (oldest
    first)."""
    with _LOCK:
        return list(_RING)


def path_totals():
    """``{path: (count, seconds, self_seconds, bytes)}`` of every span
    ended since :func:`clear`, ``path`` the tuple of names from the
    root span down; a consistent snapshot.  What :func:`report` draws
    its tree from."""
    with _LOCK:
        return {path: tuple(row) for path, row in _TOTALS.items()}


def totals():
    """``{name: {"count", "seconds", "self_seconds", "bytes"}}`` of
    every span ended since :func:`clear`, whatever the ring still
    holds; a consistent snapshot.  ``self_seconds`` leaves out a span's
    direct children on the same thread; ``bytes`` sums the ``bytes``
    attributes."""
    out = {}
    for path, (count, seconds, self_s, nbytes) in path_totals().items():
        row = out.get(path[-1])
        if row is None:
            row = out[path[-1]] = {"count": 0, "seconds": 0.0,
                                   "self_seconds": 0.0, "bytes": 0}
        row["count"] += count
        row["seconds"] += seconds
        row["self_seconds"] += self_s
        row["bytes"] += nbytes
    return out


def active_count():
    """Spans begun but not yet ended — a nonzero value after a run means
    an instrumented path leaked a span (the feature suites under
    ``tests/`` assert zero after a run)."""
    global _PEEKS
    with _LOCK:
        begun = next(_IDS) - _BASE - 1 - _PEEKS
        _PEEKS += 1
        return max(0, begun - _CLOSED)


def begin(name, parent=None, **attrs):
    """Open a span; the hot-path primitive.  Returns ``None`` unless the
    tracer is armed or a profiler session is live — one flag test and
    one probe call, NO allocation — so per-dispatch instrumentation
    costs nothing until someone is looking.  In a live session the span
    also opens the profiler's annotation ``bolt.<name>``.  ``parent``
    overrides the calling thread's current span (the explicit
    cross-thread handoff; see the streaming executor)."""
    live = _LIVE is not None and _LIVE()
    if not (_ON or live):
        return None
    st = _stack()
    if parent is None and st:
        parent = st[-1]
    th = threading.current_thread()
    sp = Span(name, attrs, next(_IDS), parent, th.ident, th.name)
    if live:
        sp._ann = _ANNOTATE("bolt." + name, rid=sp.rid)
    st.append(sp)
    return sp


def _leave(sp):
    """Take ``sp`` off its thread's stack; returns the span then on top
    (its same-thread encloser) or ``None``."""
    st = getattr(_TLS, "stack", None)
    if st and sp in st:
        # pop through: defensive against misordered ends so the stack
        # can never grow without bound
        while st and st[-1] is not sp:
            st.pop()
        st.pop()
        return st[-1] if st else None
    return None


def end(sp, **attrs):
    """Close a span returned by :func:`begin` (no-op on ``None``)."""
    if sp is None:
        return
    sp.t1 = clock()
    ann, sp._ann = sp._ann, None
    if attrs:
        sp.attrs.update(attrs)
    if ann is not None:
        # the attributes go on at the end, so those set while the span
        # was open reach the profiler's event too
        if sp.attrs:
            ann.set_metadata(**sp.attrs)
        ann.__exit__(None, None, None)
    top = _leave(sp)
    if top is not None and top.sid == sp.pid:
        top._kids += sp.t1 - sp.t0
    _land(sp)


def _land(sp):
    """A closed span into the ring, the totals and the leak gate."""
    global _CLOSED
    d = sp.t1 - sp.t0
    nbytes = sp.attrs.get("bytes")
    with _LOCK:
        if sp.sid > _BASE:
            _CLOSED += 1
        _RING.append(sp)
        row = _TOTALS.get(sp.path)
        if row is None:
            row = _TOTALS[sp.path] = [0, 0.0, 0.0, 0]
        row[0] += 1
        row[1] += d
        row[2] += d - sp._kids
        if isinstance(nbytes, (int, float)):
            row[3] += int(nbytes)


def record(name, t0, t1, **attrs):
    """Record an interval whose ends the caller already holds as
    :func:`clock` seconds: one that opens on one thread and closes on
    another (a job's wait in the serve queue, accepted by the
    submitter and popped by a worker), which a span bound to a
    thread's stack cannot be.  A root span on no stack, under the same
    gate as :func:`begin` (``None`` while nobody is looking); it lands
    in the ring and the totals, and opens no profiler annotation (those
    open and close on one thread, now)."""
    if not (_ON or (_LIVE is not None and _LIVE())):
        return None
    th = threading.current_thread()
    sp = Span(name, attrs, next(_IDS), None, th.ident, th.name)
    sp.t0, sp.t1 = t0, t1
    _land(sp)
    return sp


def cancel(sp):
    """Abandon an open span: it leaves the thread stack and the leak
    counter but never lands in the ring or the totals (the profiler's
    annotation, once opened, does close).  For probes that turn out to
    have observed nothing (e.g. the streaming executor's ingest probe
    that hits end-of-source)."""
    global _CLOSED
    if sp is None:
        return
    ann, sp._ann = sp._ann, None
    if ann is not None:
        ann.__exit__(None, None, None)
    _leave(sp)
    with _LOCK:
        if sp.sid > _BASE:
            _CLOSED += 1


def current():
    """The calling thread's innermost open span (``None`` outside any,
    or while disabled).  Capture it before starting a worker thread and
    pass it to ``begin(..., parent=...)`` there to keep the timeline
    nested across threads."""
    st = getattr(_TLS, "stack", None)
    return st[-1] if st else None


def event(name, **attrs):
    """Record a zero-duration instant mark (donation grants, gate
    rejections); parents under the thread's current span.  Tolerates a
    concurrent ``disable()``: ``begin`` re-checks the flag and may
    return ``None``, in which case the mark is silently dropped rather
    than crashing the instrumented operation."""
    sp = begin(name, **attrs)
    if sp is None:
        return None
    sp.kind = "I"
    end(sp)
    return sp


class span:
    """Context manager AND decorator recording one named interval::

        with obs.span("chunk.map", blocks=n) as sp:
            ...
            sp.set(bytes=out.nbytes)

        @obs.span("analysis.check")
        def check(obj): ...

    When tracing is disabled the body runs against a shared no-op span
    (one small object per ``with``; hot per-dispatch paths use
    :func:`begin`/:func:`end` directly, which allocate nothing)."""

    __slots__ = ("_name", "_attrs", "_parent", "_live")

    def __init__(self, name, parent=None, **attrs):
        self._name = name
        self._attrs = attrs
        self._parent = parent
        self._live = None

    def __enter__(self):
        self._live = begin(self._name, parent=self._parent, **self._attrs)
        return self._live if self._live is not None else _NULL

    def __exit__(self, etype, evalue, tb):
        sp, self._live = self._live, None
        if sp is not None and etype is not None:
            sp.attrs["error"] = etype.__name__
        end(sp)
        return False

    def __call__(self, fn):
        name, attrs = self._name, self._attrs

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with span(name, **attrs):
                return fn(*args, **kwargs)
        return wrapper
