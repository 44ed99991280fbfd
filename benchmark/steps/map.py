"""``{"call": "map", "fn": <name>}``: an elementwise body over every
record, found as ``fns/<name>.py`` (``body`` for the program, ``reference``
and ``REACH`` for the reference)."""


def bind(step, man):
    body = man.module("fns", step["fn"]).body
    return lambda a: a.map(body)


def plan(p, step):
    fn = p.man.module("fns", step["fn"])
    p.bodies.append(fn.reference)
    p.reach += int(fn.REACH)


def traffic(step, t):
    """An elementwise map fuses into its consumer and moves nothing of its
    own: ``map(v + 1).sum()`` reads every element once."""
