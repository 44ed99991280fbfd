"""The least time HBM could take for the window's re-axes over the time the
device took for them, in percent, with the bytes counted at the operand's
OWN item size: ``fold_roofline``'s arithmetic (``rebucket_roofline``) for
data stored narrower than the four bytes a value that reader counts, which
would read twice the share on 16-bit elements and could pass 100 %.

Numerator: per completed request one read and one write of every element
(the steps' own ``traffic()``, ``steps/toseries_narrow.py``) at
``operand.dtype.itemsize`` bytes, over the published HBM bandwidth: the
same work whatever implements it (XLA's transpose and update today, a
kernel tomorrow), and traffic no implementation can avoid, so the share
cannot pass 100 %.  Denominator: the device's busy time in the window.

Nothing in an untraced run, on a device without published peaks, or over
an operand that does not say what it is stored as."""
import numpy as np

import roofline


def read(ctx):
    t, cell = ctx["trace"], ctx["cell"]
    dtype = getattr(cell.operand, "dtype", None)
    if t is None or cell.peaks is None or not t["busy_s"] or dtype is None:
        return None
    itemsize = np.dtype(dtype).itemsize
    need = [roofline.hbm_bytes(cell.manifest, steps, cell.operand.shape,
                               itemsize, cell.chips)
            for _, _, steps in ctx["result"]["requests"]]
    least = sum(need[slot] for slot in ctx["result"]["slots"]) / (
        cell.peaks["hbm_GBps"] * 1e9)
    return 100.0 * least / t["busy_s"]
