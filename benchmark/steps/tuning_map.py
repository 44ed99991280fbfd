"""``{"call": "tuning_map", "perc": 20.0, "order": 5, "freq": 16, "tuned":
0.3, "sampled": 256, "limits": {..}}``: Thunder's per-pixel stimulus-tuning
analysis of a session held as series keyed by pixel:

    dff = bolt.ops.detrend(bolt.ops.normalize(b, baseline="percentile",
                                              perc=perc, axis=0),
                           order=order, axis=0)
    coherence, phase = bolt.ops.fourier(dff, freq=freq, axis=0)

dF/F against each pixel's own resting level (its ``perc``-th percentile),
a polynomial detrend, and the coherence and phase at the stimulus bin.  The
handle is the pair of deferred maps; the fetch ``toarray_pair`` brings both
``(height, width)`` maps to the host.  A terminal; needs an operand whose
reference gives the device array and sampled pixels (``operands/
pixelseries.py``).

The plain reference (nothing of the program): the same analysis by
``jax.numpy`` alone over EVERY pixel, on the device in blocks of ``BLOCK``
pixels so that it fits beside the array (``jnp.percentile``, a least-squares
polynomial residual through the QR factors of the Vandermonde matrix at
``jax.default_matmul_precision("highest")``, ``jnp.fft.rfft``; float32), and
beside it the same by NumPy in float64 for ``sampled`` pixels on the host.
What is compared, each reading with its own entry in ``limits``:

``coherence``     ``max |got - want|`` over every pixel, against the
                  float32 reference
``phase``         the largest angular distance over the pixels whose
                  reference coherence is at least ``tuned`` (the angle of a
                  bin with no energy in it is ill-conditioned)
``coherence64``,  the same two over the ``sampled`` pixels against the
``phase64``       float64 reading: the program's distance from the truth
"""

import functools

import numpy as np

import spectral

BLOCK = 4096             # pixels the reference takes at once


def _args(step):
    return float(step["perc"]), int(step["order"]), int(step["freq"])


def bind(step, man):
    import bolt_tpu as bolt
    perc, order, freq = _args(step)

    def call(b):
        dff = bolt.ops.detrend(
            bolt.ops.normalize(b, baseline="percentile", perc=perc, axis=0),
            order=order, axis=0)
        return bolt.ops.fourier(dff, freq=freq, axis=0)
    return call


def plan(p, step):
    if p.windowed or p.bodies:
        raise ValueError("tuning_map reads the whole session as it is")
    p.terminal = TuningMap(_args(step), float(step["tuned"]),
                           int(step["sampled"]), step.get("limits", {}))


def traffic(step, t):
    """The session is read once (a percentile, a fit and a transform of a
    series all need the whole series, and it fits no cache) and two values
    a pixel are written: what no implementation can avoid.  The sort, the
    FFT and whatever passes they make are the program's own."""
    pixels = t.elements() // t.sizes[-1]
    t.read, t.written = t.elements(), 2 * pixels
    t.sizes = t.sizes[:-1]


def fit_basis(times, order):
    """Orthonormal columns spanning the polynomials of degree ``order`` on
    ``linspace(-1, 1, times)``: the thin Q of the Vandermonde matrix, by
    NumPy in float64.  ``y - (y Q) Q^T`` is the least-squares residual."""
    t = np.linspace(-1.0, 1.0, times)
    q, _ = np.linalg.qr(np.vander(t, order + 1, increasing=True))
    return q


def analysis64(rows, perc, order, freq):
    """``(coherence, phase)`` of float64 ``rows`` (pixels x times) by NumPy
    alone."""
    base = np.percentile(rows, perc, axis=-1, keepdims=True)
    dff = (rows - base) / base
    q = fit_basis(rows.shape[-1], order)
    resid = dff - (dff @ q) @ q.T
    co = np.fft.rfft(resid - resid.mean(axis=-1, keepdims=True), axis=-1)
    coh = np.abs(co[:, freq]) / np.sqrt(np.sum(np.abs(co[:, 1:]) ** 2,
                                               axis=-1))
    return coh, np.angle(co[:, freq])


@functools.lru_cache(maxsize=None)
def _block_program(times, perc, order, freq, lowp):
    import jax
    import jax.numpy as jnp
    import reference
    q = jnp.asarray(fit_basis(times, order), jnp.float32)

    def prog(rows):
        rows = rows.reshape(-1, times)
        x = reference.bf16(rows) if lowp else rows
        with jax.default_matmul_precision("highest"):
            base = jnp.percentile(x, perc, axis=-1, keepdims=True)
            dff = (x - base) / base
            resid = dff - (dff @ q) @ q.T
        y = resid - jnp.mean(resid, axis=-1, keepdims=True)
        co = jnp.fft.rfft(y, axis=-1)
        energy = jnp.sum(jnp.abs(co[:, 1:]) ** 2, axis=-1)
        return (jnp.abs(co[:, freq]) / jnp.sqrt(energy),
                jnp.angle(co[:, freq]))
    return jax.jit(prog)


def turn(a, b):
    """Angular distance on the circle."""
    return np.abs(np.angle(np.exp(1j * (np.asarray(a, np.float64) - b))))


class TuningMap:
    def __init__(self, args, tuned, sampled, limits):
        self.perc, self.order, self.freq = args
        self.tuned, self.sampled, self.limits = tuned, sampled, limits

    # -- the comparison --------------------------------------------------

    def parts(self, got, want):
        try:
            coh = np.asarray(got["coherence"], np.float64)
            ph = np.asarray(got["phase"], np.float64)
            ok = (coh.shape == want["coherence"].shape == ph.shape
                  and np.all(np.isfinite(coh)) and np.all(np.isfinite(ph)))
        except (KeyError, TypeError, ValueError):
            ok = False
        if not ok:
            return {"coherence": float("inf")}
        tuned = want["coherence"] >= self.tuned
        picks = want["picks"]
        c64, p64 = coh.reshape(-1)[picks], ph.reshape(-1)[picks]
        tuned64 = want["coherence64"] >= self.tuned
        return {
            "coherence": float(np.max(np.abs(coh - want["coherence"]))),
            "phase": float(np.max(turn(ph, want["phase"])[tuned],
                                  initial=0.0)),
            "coherence64": float(np.max(np.abs(c64 - want["coherence64"]))),
            "phase64": float(np.max(turn(p64, want["phase64"])[tuned64],
                                    initial=0.0)),
        }

    def number(self, p, got, want):
        return spectral.worst(self.parts(got, want), self.limits)

    # -- the reference ----------------------------------------------------

    def _maps(self, ref, lowp):
        """Every pixel by the float32 block program; ``lowp``: of the data
        rounded to bfloat16 where it enters."""
        height, width, times = ref.shape
        prog = _block_program(times, self.perc, self.order, self.freq, lowp)
        rows = max(1, BLOCK // width)           # whole rows of pixels
        coh, ph = [], []
        for lo in range(0, height, rows):
            c, a = prog(ref.data[lo:lo + rows])
            coh.append(np.asarray(c))
            ph.append(np.asarray(a))
        return (np.concatenate(coh).astype(np.float64).reshape(height, width),
                np.concatenate(ph).astype(np.float64).reshape(height, width))

    def _picks(self, ref):
        height, width, _ = ref.shape
        rng = np.random.default_rng(ref.seed)
        return np.sort(rng.choice(height * width,
                                  size=min(self.sampled, height * width),
                                  replace=False))

    def resident_expected(self, ref, p):
        coh, ph = self._maps(ref, False)
        picks = self._picks(ref)
        c64, p64 = analysis64(ref.pixels(picks), self.perc, self.order,
                              self.freq)
        return {"coherence": coh, "phase": ph, "picks": picks,
                "coherence64": c64, "phase64": p64}

    def resident_lowp(self, ref, p):
        """The control: the same analysis of the session rounded to
        bfloat16 where it enters (a session STORED one precision lower)
        and nothing else rounded: the nearest thing below the stated
        precision that a program could really do."""
        coh, ph = self._maps(ref, True)
        return {"coherence": coh, "phase": ph}

    def resident_bf16(self, ref, p):
        """The session AND both maps held in bfloat16 (the third column of
        ``tools/parts.py``): what every limit is far under."""
        out = self.resident_lowp(ref, p)
        return {name: spectral.bf16(a) for name, a in out.items()}
