"""All the bytes of the window's completed requests over all its time."""
import arith


def read(ctx):
    r = ctx["result"]
    return arith.rate(r["bytes_done"], r["window_s"]) / 1e9
