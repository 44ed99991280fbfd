"""bolt_tpu.obs — structured tracing, metrics and timeline export.

The observability subsystem (PR 4): one place to see where a pipeline
spends its time — compile vs dispatch vs transfer vs overlap — without
reading engine internals.

* :mod:`bolt_tpu.obs.trace` — thread-safe span tracer.  ``obs.span``
  is the context-manager/decorator API; ``obs.begin``/``obs.end`` the
  allocation-free hot-path pair the engine and streaming executor use;
  ``obs.event`` instant marks; ``obs.clock`` THE blessed monotonic
  timer (lint rule BLT106 forbids raw ``time.perf_counter()``
  bookkeeping elsewhere in the package).  Off by default; near-zero
  cost while off.  Spans record while ``obs.enable()`` is in force OR a
  ``jax.profiler`` session is live, and in a live session each lands in
  the profiler's trace as ``bolt.<name>`` on the device's clock;
  ``obs.totals()`` keeps per-name count / seconds / self seconds
  whatever the ring has dropped.
* :mod:`bolt_tpu.obs.metrics` — typed registry (counters, gauges,
  log2-bucket histograms, locked counter groups).  The dispatch
  engine's counters are the group named ``"engine"`` here;
  ``profile.engine_counters()`` is a facade over it.
* :mod:`bolt_tpu.obs.export` — ``obs.to_chrome`` (Perfetto/
  ``chrome://tracing`` JSON), ``obs.report`` (text tree), and the
  ``obs.timeline(path)`` scope that arms tracing around one run and
  writes the file.

Quick start::

    import bolt_tpu as bolt
    with bolt.obs.timeline("/tmp/run.json"):
        bolt.fromcallback(load, shape, mesh, dtype="f4").sum()
    print(bolt.obs.report())

The obs modules themselves import ONLY the standard library (no jax,
no numpy — ``trace.py``/``metrics.py`` load standalone by path, the
property the fast CLI gates rely on); reaching them through the
``bolt_tpu`` package of course initialises the package as usual.
"""

from bolt_tpu.obs import metrics
from bolt_tpu.obs.export import report, timeline, to_chrome
from bolt_tpu.obs.metrics import registry, thread_census
from bolt_tpu.obs.trace import (Span, active_count, begin, cancel, clear,
                                clock, current, disable, enable, enabled,
                                end, event, record, span, spans, totals)

__all__ = ["Span", "active_count", "begin", "cancel", "clear", "clock",
           "current", "disable", "enable", "enabled", "end", "event",
           "metrics", "record", "registry", "report", "span", "spans",
           "thread_census", "timeline", "to_chrome", "totals"]
