"""Device time per request while one of the named benchmark spans was open
on the caller's thread: the spans' host-clock length over the window (the
driver's ``span_s``) less the seconds of them in which the device ran
nothing (the idle gaps that ``tracered`` gives to those spans).

For a pass whose operations the trace cannot name: XLA's FFT on the TPU is
some forty fusions called ``fusion.N``, which no ``match`` of
``trace_ops_ms_per_request`` tells from another program's ``fusion.N``; but
the pass runs, whole and alone, inside one ``bench.*`` span.  A gap inside a
loader thread's ``bench.loader`` span is that span's, so the reading is
short by at most the loader's own gaps (a millisecond a window).  Nothing
where the run was not traced or the driver timed no such span."""


def read(ctx, spans):
    t = ctx["trace"]
    held = ctx["result"].get("span_s", {})
    if t is None or not all(s in held for s in spans):
        return None
    gaps = t["idle_gaps_s"]
    busy = sum(held[s] - gaps.get(s, 0.0) for s in spans)
    return busy / len(ctx["result"]["walls_s"]) * 1e3
