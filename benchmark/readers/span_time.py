"""Seconds inside one of the program's own spans (``bolt_tpu.obs``), from
the tracer's running totals: per request of the window (``per:
"request"``) or per span recorded (``per: "span"``), times ``scale``.

The tracer records while a profiler session is live, and in a ``--trace 1``
run one is from ``begin_window`` to ``end_window``: the totals are then the
window's.  Nothing where the span was never recorded (every ``--trace 0``
run) or the program keeps no such totals."""


def read(ctx, span, per, scale=1.0):
    try:
        from bolt_tpu import obs
        row = obs.totals().get(span)
    except (ImportError, AttributeError):
        return None
    if not row or not row["count"]:
        return None
    if per == "request":
        return scale * row["seconds"] / len(ctx["result"]["walls_s"])
    if per == "span":
        return scale * row["seconds"] / row["count"]
    raise ValueError("per is 'request' or 'span', not %r" % (per,))
