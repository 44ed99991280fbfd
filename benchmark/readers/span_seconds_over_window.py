"""Seconds inside one of the program's own spans (``bolt_tpu.obs``), summed
over every thread that recorded it, over the window's seconds: how many
threads the span kept busy at once, as ``upload_workers_busy`` is for the
ingest pool (``serve.run``: how many of the server's workers this mix
keeps).  From the tracer's running totals, which in a ``--trace 1`` run are
the window's (see ``span_time``).  Nothing where the span was never
recorded or the program keeps no such totals."""


def read(ctx, span):
    try:
        from bolt_tpu import obs
        row = obs.totals().get(span)
    except (ImportError, AttributeError):
        return None
    window_s = ctx["result"]["window_s"]
    if not row or not row["count"] or not window_s > 0:
        return None
    return row["seconds"] / window_s
