"""A percentile of every request's wall time in the window, call to answer
in hand.  Nothing where the window holds too few requests to support it."""
import arith


def read(ctx, q):
    walls = ctx["result"]["walls_s"]
    if not arith.supports_percentile(len(walls), q):
        return None
    return arith.percentile(walls, q) * 1e3
