"""The least time HBM could take for the window's requests over the time
the device took for them, in percent: for cells whose cycle holds several
request kinds (``hbm_roofline_share`` counts the cycle's first alone).

Numerator: per completed request the bytes its steps cannot avoid moving,
by the steps' own ``traffic()`` (``steps/tpch_q6.py``: the four columns
the query names; ``steps/tpch_q1.py``: all seven), over the published HBM
bandwidth.  They are counted on the table as the queries see it, four
bytes a value: the device pads a row of seven to eight and reads whole
tiles, so the count errs low and the share cannot pass 100 %.
Denominator: the device's busy time in the window.

Nothing in an untraced run or on a device without published peaks."""
import roofline


def read(ctx):
    t, cell = ctx["trace"], ctx["cell"]
    if t is None or cell.peaks is None or not t["busy_s"]:
        return None
    need = [roofline.hbm_bytes(cell.manifest, steps, cell.operand.shape, 4,
                               cell.chips)
            for _, _, steps in ctx["result"]["requests"]]
    least = sum(need[slot] for slot in ctx["result"]["slots"]) / (
        cell.peaks["hbm_GBps"] * 1e9)
    return 100.0 * least / t["busy_s"]
