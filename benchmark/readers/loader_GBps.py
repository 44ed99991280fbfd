"""Bytes the loader handed over, over the host-clock seconds inside it."""


def read(ctx):
    r = ctx["result"]
    seconds = r["span_s"]["bench.loader"]
    return r["loader_bytes"] / seconds / 1e9 if seconds > 0 else None
