"""``peak_bytes_in_use`` of the fullest chip: a process-lifetime high-water
mark, usable because a run is one cell in one process."""


def read(ctx):
    return ctx["cell"].peak_bytes / 1e9
