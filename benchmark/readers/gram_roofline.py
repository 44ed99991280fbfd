"""The least time the device could take for the matrix passes of the
window's requests, over the time it took for them, in percent.

Numerator: per completed request, the larger of the bytes its steps cannot
avoid moving over the published HBM bandwidth and the operations they
cannot avoid over the published bfloat16 peak, both counted by the steps'
own ``traffic()`` (``steps/chunk_svd.py``: one read, ``2 n d**2``;
``steps/pca.py``: two reads and the scores written, ``2 n d**2 + 2 n d
k``).  The products are counted as ONE bfloat16 pass although the program
is held to "highest" precision (six): what the precision costs is meant to
show.  Denominator: the device's busy time less the eigensolver's (the
operations whose trace name contains one of ``eigh``: the Jacobi ``while``
and XLA's ``eigh`` custom call, each of which covers its own body in
time), so it holds the Gram matrices, the mean, the projection and every
small operation around them.  It errs low and cannot pass 100 %.

Nothing in an untraced run, on a device without published peaks, or where
a step counts no operations."""
import roofline


def read(ctx, eigh):
    t, cell = ctx["trace"], ctx["cell"]
    if t is None or cell.peaks is None:
        return None
    need = {}
    for slot, (_, _, steps) in enumerate(ctx["result"]["requests"]):
        tr = roofline.Traffic(cell.operand.shape)
        for step in steps:
            cell.manifest.module("steps", step["call"]).traffic(step, tr)
        flops = getattr(tr, "flops", 0)
        if not flops:
            return None
        need[slot] = max(
            (tr.read + tr.written) * 4 / cell.chips
            / (cell.peaks["hbm_GBps"] * 1e9),
            flops / cell.chips / (cell.peaks["bf16_TFLOPs"] * 1e12))
    least = sum(need[slot] for slot in ctx["result"]["slots"])
    solver = sum(s for name, s in t["ops_s"].items()
                 if any(m in name for m in eigh))
    passes = t["busy_s"] - solver
    if passes <= 0:
        return None
    return 100.0 * least / passes
