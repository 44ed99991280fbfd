"""Plain arithmetic on the window's delta of the server's own totals
(``Server.stats()["totals"]``, which a driver that runs a server hands on
as ``result["serve"]``): ``scale * sum(num) / sum(den)``.  Nothing where
the driver ran no server, the program's server lacks one of the counters
(an older program under a newer benchmark), or the denominator is 0."""


def read(ctx, num, den, scale=1.0):
    served = ctx["result"].get("serve")
    try:
        top = sum(served[n] for n in num)
        bottom = sum(served[d] for d in den)
    except (KeyError, TypeError):
        return None
    if bottom == 0:
        return None
    return scale * top / bottom
