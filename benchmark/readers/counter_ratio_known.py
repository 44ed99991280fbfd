"""``counter_ratio``'s arithmetic on the window's deltas of
``engine.counters()``, ``scale * sum(num) / sum(den)`` with ``den`` counters
or the word ``requests``, for counters newer than a program this benchmark
may be laid over: nothing where the program lacks one of them (where
``counter_ratio`` raises), and nothing where the denominator is 0."""


def read(ctx, num, den, scale=1.0):
    cell = ctx["cell"]
    try:
        top = sum(cell.counter_delta(n) for n in num)
        bottom = sum(len(ctx["result"]["walls_s"]) if d == "requests"
                     else cell.counter_delta(d) for d in den)
    except KeyError:
        return None
    if bottom == 0:
        return None
    return scale * top / bottom
