"""``{"call": "getitem", "index": [[lo, hi] | null, ...]}``: a static
slice, one entry an axis; ``null`` is the whole axis."""


def _narrow(sizes, step, starts=None):
    for ax, ix in enumerate(step["index"]):
        if ix is not None:
            if starts is not None:
                starts[ax] = int(ix[0])
            sizes[ax] = int(ix[1]) - int(ix[0])


def bind(step, man):
    ix = tuple(slice(None) if s is None else slice(int(s[0]), int(s[1]))
               for s in step["index"])
    return lambda a: a[ix]


def plan(p, step):
    if p.windowed or p.bodies:
        raise ValueError("the reference reads one getitem, before any map")
    p.windowed = True
    _narrow(p.sizes, step, p.starts)


def traffic(step, t):
    """A slice in front of a statistic narrows what is read to the slice."""
    _narrow(t.sizes, step)
