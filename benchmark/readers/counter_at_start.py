"""Plain arithmetic on ``engine.counters()`` as they stood at
``begin_window``: ``scale * (sum(num) - sum(less))``.  The counters run from
process start and the window begins where set-up ends, so the snapshot IS
the set-up's total.  Nothing where the program lacks one of the counters
(an older program under a newer benchmark)."""


def read(ctx, num, less=(), scale=1.0):
    at_start = getattr(ctx["cell"], "counters0", None)
    try:
        return scale * (sum(at_start[n] for n in num)
                        - sum(at_start[n] for n in less))
    except (KeyError, TypeError):
        return None
