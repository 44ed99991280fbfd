"""Plain arithmetic on the window's deltas of ``engine.counters()``:
``scale * sum(num) / sum(den)``.  ``den`` may name counters or the word
``requests`` (the window's request count); with no ``den`` it is the plain
delta.  Nothing where the denominator is 0 (the layer did no work here)."""


def read(ctx, num, den=None, scale=1.0):
    cell = ctx["cell"]
    top = sum(cell.counter_delta(n) for n in num)
    if den is None:
        return scale * top
    bottom = sum(len(ctx["result"]["walls_s"]) if d == "requests"
                 else cell.counter_delta(d) for d in den)
    if bottom == 0:
        return None
    return scale * top / bottom
