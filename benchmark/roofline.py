"""Bytes the operation has to move through HBM, per chip: the numerator of
``hbm_roofline_share.scan``.  Counted from shapes, never measured."""


class Traffic:
    """What the steps so far leave (``sizes``) and have to move."""

    def __init__(self, shape):
        self.sizes = [int(s) for s in shape]
        self.read = self.written = 0

    def elements(self):
        n = 1
        for s in self.sizes:
            n *= s
        return n


def hbm_bytes(man, steps, shape, itemsize, chips):
    """Bytes per chip that ``steps`` over an array of ``shape`` cannot
    avoid, with the keys spread evenly over ``chips``.  Each step counts
    its own (``traffic(step, t)`` in ``steps/<call>.py``, with the
    reasoning in its docstring): a map nothing, a slice narrows what is
    read, a statistic one read and its result, a re-axis one read and one
    write.

    A count above what the device really moved would put the share over
    100 %, so every step errs low: only traffic no implementation can
    avoid."""
    t = Traffic(shape)
    for step in steps:
        man.module("steps", step["call"]).traffic(step, t)
    return (t.read + t.written) * itemsize / chips
