"""Per-record time-series transforms: normalize (dF/F), detrend, z-score,
center, cross-correlation, the Fourier tuning map.

The reference ecosystem's TimeSeries workloads (Thunder: records keyed by
pixel/channel, values = a time axis) detrend and standardise every record
before analysis.  Here each transform is a traceable per-record ``map`` —
it DEFERS like any map and fuses into the next action.  Both backends
compute the same thing (NumPy locally — the oracle).

What that costs on the device depends on what a record's function keeps:

* ``detrend``, ``zscore``, ``center``, ``crosscorr``, ``fourier`` and
  ``normalize(baseline="mean")`` are element-wise work and reductions
  within a record: XLA fuses them, so ``zscore(detrend(b)).stats()`` is
  one compiled pass over HBM with no temporary (compiled for the v5e,
  ``detrend -> sum`` over 10.74 GB takes none, and a bare ``fourier``
  over it is two reads with none: the mean, then five sums in one
  fusion).
  ``fourier`` asks for ONE bin of each record's spectrum and for the
  spectrum's energy, and on the device it computes those and no
  transform: the bin is the centred record's product with a cosine and a
  sine, the energy Parseval's identity on the record itself (PR 44).
  Behind ``detrend``, ``center`` or ``zscore`` along the same axis it is
  ONE read: those stages leave a record's mean at zero and say so
  (``zero_mean_axis`` on their record functions), ``fourier`` reads that
  off its argument's deferred chain and spends no pass on a mean, and a
  ``detrend`` of up to ``_FIT_TERMS_ON_VPU`` terms takes its fit out
  element-wise, so the residual has one reader that XLA fuses with it
  and is never written (PR 49: the five sums of ``normalize -> detrend
  -> fourier`` read the array through the fit; engine counter
  ``fourier_centred_by_parent``, ``analysis.explain``'s "centred by its
  parent").  It takes both: a second reader of the residual (a mean, a
  pilot) makes XLA write it out, and the thin product ``coef @ A.T`` XLA
  lowers as a convolution, whose fusion takes no reduction behind it, or
  expands into the reader at half again Horner's cost, by the shapes
  around it.
* ``normalize(baseline="percentile")`` takes two order statistics of each
  record.  They are SELECTED (``ops/select.py``: the k-th smallest built
  bit by bit by counting passes over the record's image of integer keys,
  exact) from a length on; a shorter record is sorted by
  ``jnp.percentile``, to the same answer to the bit.  In a program for
  one TPU device the passes run on a tile of records held in VMEM, one
  Mosaic kernel that reads a block ONCE (PR 40), and where it lies in the
  array when ``normalize`` is the first map of a blocked chain (PR 47);
  everywhere else they are a loop of ``jax.numpy`` passes, nineteen
  reads of a block.  That loop,
  the kernel and a sort (and a caller's own FFT or scan in a ``map``)
  keep record-sized temporaries that XLA does not fuse away.  Over a
  small array that changes nothing; over an array too large to hold them
  for every record at once (a resident series array of HBM size asks for
  20 GB) the consuming program runs the chain over BLOCKS of whole
  records, chosen by a rule and not by the caller
  (``bolt_tpu/tpu/blocks.py``; ``analysis.explain`` says "blocked: n
  blocks of r records", and of a ``normalize`` stage "percentile by
  selection, one read of a block" ("in place" where the block is read
  from the array), "by selection" or "by sort").  Still
  ONE program an action, but not one pass over HBM (PERF.md, PRs 36, 37
  and 40).
* ``fourier`` returns two deferred arrays over one deferred parent.
  Fetching both runs the parent's chain ONCE: the first force keeps the
  parent's small result and each handle is a slice of it
  (``tpu/array.py :: _lower_from_shared``, PR 39; measured by the
  ``pixelseries512-1chip.tuning`` cell).

Polynomial detrending is ``v - A @ (pinv(A) @ v)`` against the
precomputed Vandermonde ``A`` and its pseudo-inverse, built host-side
once per (length, order): the projection ``pinv(A) @ v`` (K = the
record's length) is a thin matmul, MXU-shaped work; the fit ``A @ coef``
(K = ``order + 1``) is Horner's rule on the vector unit up to
``_FIT_TERMS_ON_VPU`` terms and a second thin matmul above.
"""

from functools import lru_cache

import numpy as np
import jax.numpy as jnp

from bolt_tpu import engine as _engine
from bolt_tpu._precision import resolve as _resolve
from bolt_tpu.ops import select as _select

# A polynomial fit of up to this many terms (``order + 1``) is taken out on
# the vector unit, above it by the thin matrix product (``_detrend_fn``).
# On the v5e Horner's rule reads faster than the product at every order
# swept, 0 to 15, under a consumer that reduces the residual (15.0-20.0 ms
# against 37.0-37.6 over 5.37 GB: the product's residual is written out),
# and within 2 % of it where the residual is written anyway; no order
# above 15 was measured (PERF.md, PR 49: ``scripts/detrend_fit_probe.py``).
_FIT_TERMS_ON_VPU = 16


def _value_axis(b, axis):
    """Resolve ONE value-axis index (relative to the value group)."""
    split = b.split if b.mode == "tpu" else 1
    nv = b.ndim - split
    ax = int(axis)
    if ax < 0:
        ax += nv
    if ax < 0 or ax >= nv:
        raise ValueError(
            "value axis %r out of range for %d value axes" % (axis, nv))
    return ax, split


def _apply_map(b, func):
    """Per-record map on either backend (axis = the array's key axes)."""
    if b.mode == "tpu":
        return b.map(func, axis=tuple(range(b.split)))
    return b.map(func, axis=(0,))


def detrend(b, order=1, axis=0):
    """Remove a least-squares polynomial trend of ``order`` along the
    value axis ``axis`` of every record.

    ``order=0`` removes the mean, ``order=1`` a linear trend, etc.  The
    fit is exact (normal equations via ``pinv``, precomputed host-side):
    one thin matmul along the axis for the coefficients, and on the
    device the polynomial taken out by Horner's rule, ``order`` float32
    multiply-adds, where it has at most ``_FIT_TERMS_ON_VPU`` terms (a
    second thin matmul above that, and locally), inside the fused
    per-record program.  The residual's mean along ``axis`` is zero up
    to rounding at every order (the fit spans the constants), which a
    ``fourier`` behind it reads off the chain.
    """
    order = int(order)
    if order < 0:
        raise ValueError("order must be >= 0, got %d" % order)
    ax, split = _value_axis(b, axis)
    length = b.shape[split + ax]
    if length <= order:
        raise ValueError(
            "axis of length %d cannot fit a degree-%d trend" % (length, order))
    return _apply_map(b, _detrend_fn(length, order, ax))


@lru_cache(maxsize=256)
def _detrend_fn(length, order, ax):
    # residual = v - A @ (pinv(A) @ v): two THIN matmuls (L x (order+1)),
    # O(L * order) per record — never materialise the (L, L) projector,
    # which for a 40k-sample axis would be ~13 GB.  Memoised so repeated
    # detrend calls return the SAME callable and the jit cache (keyed on
    # function identity) hits instead of recompiling.
    t = np.linspace(-1.0, 1.0, length)
    a_mat = np.vander(t, order + 1, increasing=True)
    pinv_a = np.linalg.pinv(a_mat)

    def f(v):
        xp = np if isinstance(v, np.ndarray) else jnp
        # promote to float: casting the fit matrices to an int dtype
        # would truncate them to zeros and silently return zeros
        dt = xp.promote_types(v.dtype, xp.float32)
        p_ = xp.asarray(pinv_a, dtype=dt)
        moved = xp.moveaxis(v.astype(dt), ax, -1)
        if xp is not jnp:
            fit = (moved @ p_.T) @ xp.asarray(a_mat, dtype=dt).T
        else:
            # deliberate pin through the resolver (explicit always wins):
            # the fit matrices are f32/f64 host constants — a bf16 pass
            # here would dominate the detrend residual
            coef = jnp.matmul(moved, p_.T, precision=_resolve("highest"))
            if order + 1 <= _FIT_TERMS_ON_VPU:
                # the polynomial by Horner's rule in ``t``: float32
                # multiply-adds, work that a reader of the residual
                # fuses.  The thin product ``coef @ A.T`` XLA lowers as
                # a convolution, or not, by the shapes around it, and a
                # convolution's result is written out for any reduction
                # behind it
                t_ = jnp.asarray(t, dtype=dt)
                fit = coef[..., order:]
                for k in reversed(range(order)):
                    fit = fit * t_ + coef[..., k:k + 1]
            else:
                fit = jnp.matmul(coef, jnp.asarray(a_mat, dtype=dt).T,
                                 precision=_resolve("highest"))
        return xp.moveaxis(moved - fit, -1, ax)

    # the residual is orthogonal to A's column of ones
    f.zero_mean_axis = ax
    return f


def zscore(b, axis=0, ddof=0, epsilon=0.0):
    """Standardise every record along the value axis ``axis``:
    ``(v - mean) / (std + epsilon)``.

    ``ddof`` selects population (0, default — the reference StatCounter
    convention) or sample (1) standard deviation; ``epsilon`` guards
    constant records (otherwise they divide by zero, matching numpy's
    nan/inf behavior).
    """
    ax, _ = _value_axis(b, axis)
    return _apply_map(b, _zscore_fn(ax, int(ddof), float(epsilon)))


@lru_cache(maxsize=256)
def _zscore_fn(ax, ddof, epsilon):
    def f(v):
        xp = np if isinstance(v, np.ndarray) else jnp
        mu = xp.mean(v, axis=ax, keepdims=True)
        sd = xp.std(v, axis=ax, ddof=ddof, keepdims=True)
        return (v - mu) / (sd + epsilon)
    f.zero_mean_axis = ax
    return f


def center(b, axis=0):
    """Subtract the per-record mean along the value axis ``axis``."""
    ax, _ = _value_axis(b, axis)
    return _apply_map(b, _center_fn(ax))


@lru_cache(maxsize=256)
def _center_fn(ax):
    def f(v):
        xp = np if isinstance(v, np.ndarray) else jnp
        return v - xp.mean(v, axis=ax, keepdims=True)
    f.zero_mean_axis = ax
    return f


def crosscorr(b, signal, lag=0, axis=0, epsilon=0.0):
    """Per-record normalised cross-correlation with a reference
    ``signal`` along the value axis ``axis`` (the Thunder
    ``TimeSeries.crossCorr`` workload).

    For each integer shift ``k`` in ``[-lag, lag]`` the Pearson
    correlation between ``v[t]`` and ``signal[t - k]`` is computed over
    their overlapping window, so the axis of length ``L`` is replaced by
    ``2*lag + 1`` correlation values (``lag=0`` gives each record's
    plain correlation with the signal).  A deferred map on either
    backend; the shift loop is static (``lag`` is small), one fused
    program on TPU.  ``epsilon`` is added to the normaliser to guard
    constant records/windows (otherwise they divide 0/0 to NaN, like
    ``zscore`` without its epsilon).
    """
    lag = int(lag)
    if lag < 0:
        raise ValueError("lag must be >= 0, got %d" % lag)
    ax, split = _value_axis(b, axis)
    length = b.shape[split + ax]
    sig = np.asarray(signal, dtype=np.float64).ravel()
    if sig.shape[0] != length:
        raise ValueError(
            "signal length %d does not match axis length %d"
            % (sig.shape[0], length))
    if lag > length - 2:
        raise ValueError(
            "lag %d needs at least 2 overlapping samples on an axis of "
            "length %d (Pearson r of a single sample is undefined)"
            % (lag, length))
    return _apply_map(
        b, _crosscorr_fn(sig.tobytes(), length, lag, ax, float(epsilon)))


@lru_cache(maxsize=128)
def _crosscorr_fn(sig_bytes, length, lag, ax, epsilon):
    # per-shift signal statistics are pure functions of the host-side
    # signal: centre each window and take its sum-of-squares in float64
    # here, so the traced program only does the record-side math.
    # Memoised by signal CONTENT so repeated calls hit the jit cache.
    sig = np.frombuffer(sig_bytes, dtype=np.float64)
    windows = []
    for k in range(-lag, lag + 1):
        ssub = sig[:length - k] if k >= 0 else sig[-k:]
        sc = ssub - ssub.mean()
        windows.append((k, sc, float(np.sum(sc * sc))))

    def f(v):
        xp = np if isinstance(v, np.ndarray) else jnp
        dt = xp.promote_types(v.dtype, xp.float32)
        moved = xp.moveaxis(v.astype(dt), ax, -1)
        outs = []
        for k, sc_np, sc_ss in windows:
            a = moved[..., k:] if k >= 0 else moved[..., :length + k]
            ac = a - xp.mean(a, axis=-1, keepdims=True)
            sc = xp.asarray(sc_np, dtype=dt)
            denom = xp.sqrt(xp.sum(ac * ac, axis=-1) * sc_ss) + epsilon
            outs.append(xp.sum(ac * sc, axis=-1) / denom)
        return xp.stack(outs, axis=ax)

    return f


def fourier(b, freq, axis=0, epsilon=0.0):
    """Spectral coherence and phase of every record at one frequency
    index along the value axis ``axis`` (the Thunder ``Series.fourier``
    workload; semantics stated explicitly here since the reference
    mount was empty — SURVEY.md §0).

    Each record is mean-centred; with ``co`` its real DFT, at bin
    ``freq`` (1 ≤ freq ≤ L//2, DC excluded):

    * **coherence** = ``|co[freq]| / sqrt(sum_{k>=1} |co[k]|^2)`` — the
      fraction of non-DC spectral energy at that bin (1.0 for a pure
      sinusoid at the bin frequency);
    * **phase** = ``angle(co[freq])`` in radians.

    Returns ``(coherence, phase)`` as bolt arrays with the axis removed —
    both still DEFERRED maps (the selection is itself a per-record map,
    so the contract of this module holds and downstream ops fuse).  On
    the TPU backend the pair SHARES ONE RUN of everything behind it: the
    two are consumers of one deferred map, so whichever is forced first
    runs the chain once, from the base through the bin, its
    ``(..., 2)`` result is kept on the device while the other handle
    lives, and both are slices of that
    (``BoltArrayTPU._lower_from_shared``; engine counters
    ``shared_parent_runs`` / ``shared_parent_hits``).  A caller who
    keeps only one of the two pays for one program, as before.
    ``epsilon`` guards constant records, which otherwise divide 0/0 to
    NaN (same convention as ``zscore``/``crosscorr``).

    On the device a ``b`` whose deferred chain ends in ``detrend``,
    ``center`` or ``zscore`` along ``axis`` is not centred again: its
    mean is zero already, the bin and the energy below leave the DC bin
    out of what they are handed, and with no pass for the mean this
    stage is the ONE reader of its parent's result, which XLA fuses into
    it (engine counter ``fourier_centred_by_parent``).  Anything else
    (stored data, a caller's own map) is centred here: float32 sums over
    a large level would lose the energy.

    Locally ``co`` is ``np.fft.rfft``'s (the oracle).  On the device no
    transform runs: one bin of a DFT is the record's product with a
    cosine and a sine, and ``sum_{k>=1} |co[k]|^2`` is Parseval's
    identity on the record, ``(L sum y^2 - (sum y)^2 + nyquist^2) / 2``
    (``_bin_and_energy``).  Both are exact, so the two routes differ by
    rounding alone, and the work is ``O(L)`` a record with no temporary.
    """
    freq = int(freq)
    ax, split = _value_axis(b, axis)
    length = b.shape[split + ax]
    if not 1 <= freq <= length // 2:
        raise ValueError(
            "freq must be in [1, %d] for an axis of length %d, got %d"
            % (length // 2, length, freq))

    func = _fourier_fn(freq, ax, float(epsilon))
    # the last stage of the argument's chain may have left the mean along
    # this axis at zero (detrend, center, zscore say so on their functions)
    if (b.mode == "tpu" and b.deferred and getattr(
            b._chain[1][-1], "zero_mean_axis", None) == ax):
        func = func.after_zero_mean
    out = _apply_map(b, func)
    return (_apply_map(out, _pick_fn(ax, 0)),
            _apply_map(out, _pick_fn(ax, 1)))


@lru_cache(maxsize=128)
def _bin_rows(freq, length):
    """What a series of ``length`` points is multiplied by, by NumPy in
    float64: ``(cos, -sin)`` of bin ``freq`` of its DFT (the bin is
    ``sum(y * cos) + 1j * sum(y * -sin)``; the angle is reduced in
    integers first) and ``(-1)**t``, the Nyquist bin of an even length.
    Where ``2 * freq == length`` the bin IS the Nyquist bin, real as
    ``rfft`` gives it, and there is no pair; an odd length has no
    Nyquist bin."""
    t = np.arange(length)
    pair = alt = None
    if 2 * freq != length:
        angle = 2.0 * np.pi * ((freq * t) % length) / length
        pair = np.cos(angle), -np.sin(angle)
    if length % 2 == 0:
        alt = 1.0 - 2.0 * (t % 2)
    return pair, alt


@lru_cache(maxsize=128)
def _fourier_fn(freq, ax, epsilon):
    """The record function of ``fourier``, and as its ``after_zero_mean``
    the same for records whose parent stage left their mean at zero
    (``zero_mean_axis``): on the device that one spends no pass on a
    mean, so it is ONE reader of its argument and fuses with the work
    that made it.  ``_bin_and_energy`` leaves the DC bin out of whatever
    it is handed.  A large level it could not take: float32 sums of
    ``1000 +- 10`` lose the energy, which is why a bare ``fourier``
    centres."""
    def build(centred_by_parent):
        def f(v):
            xp = np if isinstance(v, np.ndarray) else jnp
            dt = xp.promote_types(v.dtype, xp.float32)
            y = xp.moveaxis(v.astype(dt), ax, -1)
            if centred_by_parent and xp is jnp:
                _engine.record_fourier_centred_by_parent()
            else:
                y = y - xp.mean(y, axis=-1, keepdims=True)
            if xp is jnp:
                return jnp.stack(_bin_and_energy(y, freq, epsilon), axis=ax)
            co = xp.fft.rfft(y, axis=-1)
            mag2 = xp.abs(co[..., 1:]) ** 2
            coh = (xp.abs(co[..., freq])
                   / (xp.sqrt(xp.sum(mag2, axis=-1)) + epsilon))
            ph = xp.angle(co[..., freq])
            return xp.stack([coh, ph], axis=ax)
        if centred_by_parent:
            # what analysis.explain reads to say the stage takes no mean
            f.centred_by_parent = ax
        return f

    f = build(False)
    f.after_zero_mean = build(True)
    return f


def _bin_and_energy(y, freq, epsilon):
    """``(coherence, phase)`` at bin ``freq`` of the centred series ``y``
    (last axis) with no transform.  The bin is the series' product with
    a cosine and a sine; the half spectrum's non-DC energy is Parseval's
    identity on the series itself: ``sum_k |Y_k|^2 = L sum y^2`` over
    all ``L`` bins, of which ``k`` and ``L - k`` are conjugates, the
    Nyquist bin of an even ``L`` stands once, and ``|Y_0|^2 = (sum y)^2``
    is left out as the definition leaves the DC bin out.  Both are exact;
    what differs from ``rfft`` (the NumPy route, the oracle) is rounding.

    Spelt as multiply-reduces and not as one thin product ``y @ W``:
    compiled for the v5e the five sums are ONE fusion that reads ``y``
    once, where the product and ``sum(y * y)`` read it twice (PERF.md, PR
    44).  float32 products and sums on the vector unit: no pass on the
    MXU, so no precision to pin."""
    length = y.shape[-1]
    pair, alt = _bin_rows(freq, length)

    def dot(row):
        return jnp.sum(y * jnp.asarray(row, y.dtype), axis=-1)

    total = length * jnp.sum(y * y, axis=-1) - jnp.sum(y, axis=-1) ** 2
    if alt is not None:
        nyquist = dot(alt)
        total = total + nyquist ** 2
    if pair is None:
        re, im = nyquist, jnp.zeros_like(nyquist)
    else:
        re, im = dot(pair[0]), dot(pair[1])
    # the bin is one of the bins summed: rounding may not take the
    # energy under it (nor under zero, where sqrt gives NaN)
    energy = jnp.maximum(total / 2, re * re + im * im)
    return (jnp.hypot(re, im) / (jnp.sqrt(energy) + epsilon),
            jnp.arctan2(im, re))


@lru_cache(maxsize=128)
def _pick_fn(ax, i):
    sel = (slice(None),) * ax
    return lambda v: v[sel + (i,)]


def normalize(b, baseline="percentile", perc=20.0, axis=0, epsilon=0.0):
    """Normalise every record to its own baseline along the value axis
    ``axis``: ``(v - base) / denom`` with the sign-aware denominator
    ``denom = base + epsilon`` for ``base >= 0`` and ``base - epsilon``
    otherwise — the ΔF/F transform of the Thunder ``Series.normalize``
    workload, with the guard pushed AWAY from zero so signed baselines
    (e.g. after ``detrend``) cannot land the denominator on it.

    ``baseline``: ``'percentile'`` (the ``perc``-th per-record
    percentile, default 20 — a robust resting level) or ``'mean'``.
    A deferred map on either backend.  The percentile is NumPy's: linear
    interpolation between two exact order statistics.  Locally
    ``np.percentile``; on the device the two are selected without
    sorting the record (``ops/select.py``) where it is long enough for
    that to pay, and ``jnp.percentile`` sorts a shorter one: the same
    value to the bit either way (engine counters
    ``percentile_select_lowerings`` / ``percentile_sort_lowerings``, and
    ``percentile_kernel_lowerings`` where a selection was lowered as the
    kernel that reads a block once, ``percentile_based_lowerings`` where
    that kernel reads the block in the array it is a block of: the first
    map of a chain lowered over blocks).
    """
    if baseline not in ("percentile", "mean"):
        raise ValueError(
            "baseline must be 'percentile' or 'mean', got %r" % (baseline,))
    perc = float(perc)
    if not 0.0 <= perc <= 100.0:
        raise ValueError("perc must be in [0, 100], got %r" % (perc,))
    ax, _ = _value_axis(b, axis)
    return _apply_map(b, _normalize_fn(baseline, perc, ax, float(epsilon)))


@lru_cache(maxsize=128)
def _normalize_fn(baseline, perc, ax, epsilon):
    def f(v):
        xp = np if isinstance(v, np.ndarray) else jnp
        dt = xp.promote_types(v.dtype, xp.float32)
        vf = v.astype(dt)
        if baseline == "percentile":
            # NumPy's own locally (the oracle); selected on the device
            take = np.percentile if xp is np else _select.percentile
            base = take(vf, perc, axis=ax, keepdims=True)
        else:
            base = xp.mean(vf, axis=ax, keepdims=True)
        # sign-aware guard: the baseline is SIGNED (e.g. after detrend),
        # so 'base + epsilon' could move a negative baseline ONTO zero;
        # push it away from zero instead (zero itself goes to +epsilon)
        denom = xp.where(base >= 0, base + epsilon, base - epsilon)
        return (vf - base) / denom
    if baseline == "percentile":
        # what analysis.explain reads to say how the baseline is taken
        f.percentile_axis = ax
    return f
