"""Seconds inside the runtime's own start (the first ``jax.devices()``):
not the benchmark's or the program's work, 6.6-14 s from run to run on a
v5e host, so it is left out of ``setup_s`` and shown here instead."""


def read(ctx):
    return ctx["cell"].reach_s
