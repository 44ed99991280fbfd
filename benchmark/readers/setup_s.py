"""Process start to the first timed request (imports, data, compile or
cache load, warm-up), less the seconds inside the runtime's own start (the
first ``jax.devices()``), which are ``runtime_start_s``: they swing by a
factor of two between runs of one machine, more than ``setup_s`` may move."""


def read(ctx):
    return ctx["cell"].setup_s
