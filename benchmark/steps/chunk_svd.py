"""``{"call": "chunk_svd", "size": "150", "axis": [0], "limits": {..}}``:
BASELINE config 5, the per-chunk SVD of a tall-skinny series matrix:

    b.chunk(size=size, axis=axis).map(
        lambda blk: bolt.ops.svdvals(blk)[None, :]).unchunk()

Every record (a plane of voxels x time) is cut along its voxel axis into
blocks of at most ``size`` megabytes (upstream Bolt's default budget is
"150") and each block gives its singular values: ``(planes, blocks,
times)``.  A terminal; needs an operand whose reference gives exact second
moments (``operands/series.py``).

What is compared, each reading with its own entry in ``limits``:

``spectrum``         ``max |s**2 - s_ref**2| / s_ref[0]**2`` over every block: the
                     distance of the squared singular values (the variances
                     along the block's principal axes) in units of the
                     largest.  The program takes them from the Gram matrix,
                     so this, and not the distance of the roots, is what
                     float32 can hold it to: a root under ``sqrt(eps) *
                     s_ref[0]`` has no digits either way (the Gram route's
                     stated trade, ``bolt.ops.svdvals``)
``energy``           ``|sum s**2 - sum s_ref**2| / sum s_ref**2``, the
                     worst block: the block's total variance, which no
                     eigensolver moves (a rotation keeps the trace), so it
                     reads the Gram matrix alone
"""

import numpy as np

import spectral

ITEMSIZE = 4


def _axis(step):
    (axis,) = step["axis"]
    if int(axis) != 0:
        raise ValueError("chunk_svd cuts the sample axis (value axis 0)")
    return 0


def bind(step, man):
    import bolt_tpu as bolt
    size, axis = str(step["size"]), (_axis(step),)

    def block_spectrum(blk):
        return bolt.ops.svdvals(blk)[None, :]
    return lambda a: a.chunk(size=size, axis=axis).map(
        block_spectrum).unchunk()


def plan(p, step):
    if p.windowed or p.bodies:
        raise ValueError("chunk_svd reads the whole source as it is")
    rows = spectral.block_rows(p.shape[p.split:], ITEMSIZE, step["size"],
                               _axis(step))
    p.terminal = ChunkSvd(rows, step.get("limits", {}))


def traffic(step, t):
    """Every element is read once; the answer is small.  One Gram matrix a
    block: ``2 n d**2`` operations in all."""
    d = t.sizes[-1]
    t.read, t.written = t.elements(), 0
    t.flops = getattr(t, "flops", 0) + 2 * t.elements() * d


class ChunkSvd:
    def __init__(self, rows, limits):
        self.rows, self.limits = rows, limits

    def parts(self, got, want):
        got = np.asarray(got)
        if got.shape != want.shape or not np.all(np.isfinite(got)):
            return {"spectrum": float("inf")}
        got, want = got.astype(np.float64) ** 2, want ** 2
        energy = want.sum(axis=-1)
        return {
            "spectrum": float(np.max(np.abs(got - want) / want[..., :1])),
            "energy": float(np.max(np.abs(got.sum(axis=-1) - energy)
                                   / energy)),
        }

    def number(self, p, got, want):
        return spectral.worst(self.parts(got, want), self.limits)

    def _spectrum(self, ref, lowp):
        gram, _ = ref.moments(self.rows, lowp=lowp)
        return np.sqrt(spectral.eigh_desc(gram)[0])

    def resident_expected(self, ref, p):
        """Exact Gram matrix of every block, then ``numpy.linalg.eigh`` in
        float64: ``(planes, blocks, times)``, descending."""
        return self._spectrum(ref, False)

    def resident_lowp(self, ref, p):
        """The control: the same from the data rounded to bfloat16 and
        nothing else rounded, what one bfloat16 pass of the matrix unit
        with exact accumulation would give: the nearest thing below the
        stated precision that a program could really do.  A spectrum alone
        does not catch it: over half a million rows the rounding averages
        out of a Gram matrix (it adds its own variance, 1e-5 of the energy,
        and turns nothing), which is less than the float32 unit's own
        running-sum bias moves it.  The cell catches it in ``steps/pca.py``,
        where it is not averaged (PERF.md, section 2)."""
        return self._spectrum(ref, True)

    def resident_bf16(self, ref, p):
        """Data and answer held in bfloat16 (the third column of
        ``tools/parts.py``): what the limits are far under."""
        return spectral.bf16(self._spectrum(ref, True))
