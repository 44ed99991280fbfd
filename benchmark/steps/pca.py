"""``{"call": "pca", "k": 8, "center": true, "axis": [0, 1], "patches":
[[plane, voxel], ..], "patch_rows": 128, "limits": {..}}``: whole-data PCA
over time of a plane-keyed series matrix:

    scores, components, singular_values, mean = bolt.ops.pca(
        b, k=k, center=center, axis=axis, return_mean=True)

The three small results come back as host arrays; the scores (samples x k)
stay on the device.  The handle this step returns is the dict the fetch
``pca_parts`` takes: it forces the scores ready, keeps ``patch_rows`` rows
of them at each of ``patches`` (a look at the spatial maps) as a small
device array for the check after the window, and lets the scores go, so
that what is kept of a request is small.  A terminal; needs an operand
whose reference gives exact second moments (``operands/series.py``).

What is compared, each reading with its own entry in ``limits``; nothing
depends on the sign or order the program gives its components:

``spectrum``         ``max |s**2 - s_ref**2| / s_ref[0]**2`` over the ``k``
                     singular values (as ``steps/chunk_svd.py`` reads it)
``subspace``         Frobenius distance of the projectors ``V V^T`` onto
                     the span of the ``k`` components
``mean``             ``max |mu - mu_ref|``, in data units
``scores``           over the rows read, ``max |scores V^T - (x - mu_ref)
                     V_ref V_ref^T|``, in data units: the rows as the
                     program's ``k`` components rebuild them against the
                     reference's
"""

import numpy as np

import spectral

BLOCK = 1 << 19          # rows a block of exact moments is taken over


def _args(step):
    return (int(step["k"]), bool(step["center"]),
            tuple(int(a) for a in step["axis"]))


def bind(step, man):
    import bolt_tpu as bolt
    k, center, axis = _args(step)
    patches = tuple((int(p), int(v)) for p, v in step["patches"])
    rows = int(step["patch_rows"])

    def call(a):
        scores, comps, sv, mean = bolt.ops.pca(
            a, k=k, center=center, axis=axis, return_mean=True)
        return {"scores": scores, "components": comps,
                "singular_values": sv, "mean": mean,
                "patches": patches, "patch_rows": rows}
    return call


def plan(p, step):
    if p.windowed or p.bodies:
        raise ValueError("pca reads the whole source as it is")
    k, center, axis = _args(step)
    if axis != tuple(range(len(p.shape) - 1)):
        raise ValueError("the reference takes every axis but the last as "
                         "samples")
    p.terminal = Pca(k, center, [(int(a), int(b)) for a, b in
                                 step["patches"]], int(step["patch_rows"]),
                     step.get("limits", {}))


def traffic(step, t):
    """The data are read twice at the least (the components are not known
    until every sample has been seen, and the scores need them) and the
    scores are written once; one Gram matrix and one projection, ``2 n d**2
    + 2 n d k`` operations."""
    k = int(step["k"])
    d = t.sizes[-1]
    n = t.elements() // d
    t.read, t.written = 2 * t.elements(), n * k
    t.flops = getattr(t, "flops", 0) + 2 * n * d * d + 2 * n * d * k
    t.sizes = t.sizes[:-1] + [k]


class Pca:
    def __init__(self, k, center, patches, rows, limits):
        self.k, self.center, self.patches = k, center, patches
        self.rows, self.limits = rows, limits

    # -- the comparison --------------------------------------------------

    def parts(self, got, want):
        try:
            vec = np.asarray(got["components"], np.float64)
            sv = np.asarray(got["singular_values"], np.float64)
            mean = np.asarray(got["mean"], np.float64)
            rows = np.asarray(got["rows"], np.float64)
            shapes = (vec.shape == want["components"].shape
                      and sv.shape == want["singular_values"].shape
                      and mean.shape == want["mean"].shape
                      and rows.shape == want["rows"].shape[:1] + (self.k,))
        except (KeyError, TypeError, ValueError):
            shapes = False
        if not shapes or not all(np.all(np.isfinite(a))
                                 for a in (vec, sv, mean, rows)):
            return {"spectrum": float("inf")}
        ref = want["components"]
        return {
            "spectrum": float(np.max(np.abs(
                sv ** 2 - want["singular_values"] ** 2))
                / want["singular_values"][0] ** 2),
            "subspace": float(np.linalg.norm(vec @ vec.T - ref @ ref.T)),
            "mean": float(np.max(np.abs(mean - want["mean"]))),
            "scores": float(np.max(np.abs(rows @ vec.T - want["rows"]))),
        }

    def number(self, p, got, want):
        return spectral.worst(self.parts(got, want), self.limits)

    # -- the reference ----------------------------------------------------

    def _decompose(self, ref, lowp):
        """Mean, centred Gram matrix (float64 from exact integers), its top
        ``k`` eigenpairs."""
        rows = min(BLOCK, ref.shape[1])
        gram, total = ref.moments(rows, lowp=lowp)
        n = ref.shape[0] * ref.shape[1]
        g = gram.sum(axis=(0, 1)).astype(np.float64)
        s = total.sum(axis=(0, 1)).astype(np.float64)
        mean = s / n if self.center else np.zeros_like(s)
        if self.center:
            g = g - np.outer(s, s) / n
        w, v = spectral.eigh_desc(g)
        return mean, v[:, :self.k], np.sqrt(w[:self.k])

    def resident_expected(self, ref, p):
        mean, vec, sv = self._decompose(ref, False)
        x = ref.rows(self.patches, self.rows)
        return {"components": vec, "singular_values": sv, "mean": mean,
                "rows": (x - mean) @ vec @ vec.T}

    def resident_lowp(self, ref, p):
        """The control: an answer as one bfloat16 pass of the matrix unit
        would give it and nothing else rounded (data and components
        rounded where they enter a product, accumulation exact): the
        nearest thing below the stated precision that a program could
        really do.  Its spectrum and span read as the sound answer's do
        (the rounding averages out of a Gram matrix over 42 M samples); it
        is caught where nothing averages, in the rebuilt ``rows``."""
        mean, vec, sv = self._decompose(ref, True)
        x = spectral.bf16(ref.rows(self.patches, self.rows))
        rows = x @ spectral.bf16(vec) - mean @ spectral.bf16(vec)
        return {"components": vec, "singular_values": sv, "mean": mean,
                "rows": rows}

    def resident_bf16(self, ref, p):
        """Data, components and every answer held in bfloat16 (the third
        column of ``tools/parts.py``): what the limits are far under."""
        out = self.resident_lowp(ref, p)
        return {name: spectral.bf16(a) for name, a in out.items()}

