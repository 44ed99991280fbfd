"""Device time per request: the busy union of the traced window, mean over
the chips used, over the requests completed in it."""


def read(ctx):
    if ctx["trace"] is None:
        return None
    return ctx["trace"]["busy_s"] / len(ctx["result"]["walls_s"]) * 1e3
