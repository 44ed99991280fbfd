"""The Gram kernel's share of its roofline over a streamed source, in
percent: the least time the chip could take for the Gram matrices of the
window's requests over the device time of the kernel's own operations.

Numerator: per completed request ONE read of every slab (the source's
elements, four bytes each) over the published HBM bandwidth, or ``2 n d**2``
operations (``n`` samples of ``d`` features: the last axis) over the
published bfloat16 peak if that is larger; the products are counted as ONE
bfloat16 pass although the program is held to "highest" precision (six), as
``gram_roofline`` counts them.  Denominator: the device time of the
operations whose trace name contains one of ``match`` (the kernel's calls,
``packed_gram_sums``; the merges of the partials and the rows past a
kernel's last block are not in it, so neither are their bytes).  It errs low
and cannot pass 100 %.

Nothing in an untraced run, on a device without published peaks, or where
no such operation ran (a program without the terminal, a slab program that
kept ``dot_general``)."""


def read(ctx, match):
    t, cell = ctx["trace"], ctx["cell"]
    if t is None or cell.peaks is None:
        return None
    kernel = sum(s for name, s in t["ops_s"].items()
                 if any(m in name for m in match))
    if kernel <= 0:
        return None
    shape = cell.operand.shape
    elements = 1
    for s in shape:
        elements *= int(s)
    d = int(shape[-1])
    least = max(elements * 4 / cell.chips / (cell.peaks["hbm_GBps"] * 1e9),
                2 * elements * d / cell.chips
                / (cell.peaks["bf16_TFLOPs"] * 1e12))
    return 100.0 * least * len(ctx["result"]["walls_s"]) / kernel
