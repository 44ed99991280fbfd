"""1 - union of device operations over the traced window, in percent."""


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
