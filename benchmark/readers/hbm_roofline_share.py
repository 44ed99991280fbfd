"""The least time HBM could take for a request (``roofline.hbm_bytes`` over
the published bandwidth) over the device time a request took, in percent.
For cells of one request kind: the count is of the cycle's first request."""
import roofline


def read(ctx):
    t, cell = ctx["trace"], ctx["cell"]
    if t is None or cell.peaks is None:
        return None
    per_request = t["busy_s"] / len(ctx["result"]["walls_s"])
    steps = ctx["result"]["requests"][0][2]
    need = roofline.hbm_bytes(cell.manifest, steps, cell.operand.shape, 4, cell.chips)
    return 100.0 * (need / (cell.peaks["hbm_GBps"] * 1e9)) / per_request
