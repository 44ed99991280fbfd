"""Exporters for the span ring: Chrome trace-event JSON and a text tree.

* :func:`to_chrome` — the Trace Event Format (``B``/``E`` duration pairs
  + ``i`` instants + thread-name metadata) that ``chrome://tracing`` and
  Perfetto load directly; a streamed reduction exported here SHOWS its
  ingest spans overlapping compute spans on separate thread tracks —
  the visual twin of ``profile.overlap_efficiency()``.
* :func:`report` — an aggregated plain-text tree (span name -> calls,
  total/self seconds, bytes, XLA compiles beneath it) for terminals
  without a trace viewer, drawn from the tracer's running totals.
* :func:`timeline` — the one-shot scope: arm tracing, run, write the
  file::

      with bolt_tpu.obs.timeline("/tmp/run.json"):
          bolt.fromiter(blocks, shape, mesh, dtype="f4").sum()

Standard library only (json/contextlib); spans come from
:mod:`bolt_tpu.obs.trace`.
"""

import contextlib
import json
import os

from bolt_tpu.obs import trace as _trace


def _events(spans):
    """Flatten spans into trace events.  Tie-breaking on equal
    timestamps keeps nesting well-formed: ends sort before begins (a
    span may end exactly where the next begins), child ends before
    parent ends (descending sid — children have larger sids), parent
    begins before child begins (ascending sid)."""
    if not spans:
        return []
    pid = os.getpid()
    origin = min(s.t0 for s in spans)
    evs = []
    threads = {}
    for s in spans:
        threads.setdefault(s.tid, s.tname)
        ts = (s.t0 - origin) * 1e6
        args = {k: v for k, v in s.attrs.items()
                if isinstance(v, (int, float, str, bool))}
        args["rid"] = s.rid
        if s.kind == "I":
            evs.append((ts, 1, s.sid,
                        {"name": s.name, "ph": "i", "s": "t", "ts": ts,
                         "pid": pid, "tid": s.tid, "args": args}))
            continue
        t1 = s.t1 if s.t1 is not None else s.t0
        te = (t1 - origin) * 1e6
        evs.append((ts, 1, s.sid,
                    {"name": s.name, "ph": "B", "ts": ts, "pid": pid,
                     "tid": s.tid, "args": args}))
        evs.append((te, 0, -s.sid,
                    {"name": s.name, "ph": "E", "ts": te, "pid": pid,
                     "tid": s.tid}))
    evs.sort(key=lambda e: e[:3])
    out = [{"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
            "args": {"name": tname}} for tid, tname in threads.items()]
    out.extend(e[3] for e in evs)
    return out


def to_chrome(spans=None, path=None):
    """Chrome trace-event document for ``spans`` (default: the current
    ring).  Returns the document dict; writes JSON to ``path`` when
    given."""
    doc = {"traceEvents": _events(_trace.spans() if spans is None
                                  else spans),
           "displayTimeUnit": "ms"}
    if path is not None:
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(doc, fh)
    return doc


def _human_bytes(n):
    if not n:
        return ""
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024 or unit == "TB":
            return ("%d%s" % (n, unit)) if unit == "B" \
                else ("%.1f%s" % (n, unit))
        n /= 1024.0
    return ""


def report():
    """Aggregated text tree over every span ended since ``obs.clear()``
    (the tracer's running totals, so a ring that wrapped loses nothing):
    per name (within its parent) the call count, total and self wall
    seconds, summed ``bytes`` attrs, and the number of XLA compiles
    (``engine.compile`` spans) at or beneath it."""
    rows = _trace.path_totals()
    if not rows:
        return "(no spans recorded — arm tracing with bolt_tpu.obs." \
               "enable() or the obs.timeline(path) scope, or take a " \
               "jax.profiler trace)"
    lines = ["%-44s %7s %10s %10s %10s %8s"
             % ("span", "calls", "total_s", "self_s", "bytes",
                "compiles")]

    def render(parent):
        depth = len(parent)
        kids = [p for p in rows if len(p) == depth + 1
                and p[:depth] == parent]
        for path in sorted(kids, key=lambda p: -rows[p][1]):
            count, seconds, self_s, nbytes = rows[path]
            compiles = sum(r[0] for p, r in rows.items()
                           if p[:len(path)] == path
                           and p[-1] == "engine.compile")
            label = "  " * depth + path[-1]
            lines.append("%-44s %7d %10.4f %10.4f %10s %8d"
                         % (label[:44], count, seconds, self_s,
                            _human_bytes(nbytes), compiles))
            render(path)

    render(())
    return "\n".join(lines)


@contextlib.contextmanager
def timeline(path, ring=None):
    """Arm tracing, run the body, write a Chrome trace to ``path`` —
    even when the body raises (the timeline of a failed run is usually
    the point).  Restores the tracer's previous armed/disarmed state;
    the ring keeps the run's spans for :func:`report` afterwards."""
    was_on = _trace.enabled()
    _trace.clear()
    if ring is not None:
        _trace.enable(ring=ring)
    else:
        _trace.enable()
    try:
        yield
    finally:
        if not was_on:
            _trace.disable()
        to_chrome(path=path)
