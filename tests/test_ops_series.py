"""median_filter (scipy.ndimage oracle) and the per-record series
transforms detrend/zscore/center — backend parity + independent oracles
(the reference ecosystem's TimeSeries workloads)."""

import numpy as np
import pytest
import scipy.ndimage as ndi
import scipy.signal

import bolt_tpu as bolt
from bolt_tpu.ops import (center, crosscorr, detrend, gaussian,
                          median_filter, zscore)
from bolt_tpu.utils import allclose


def _x(shape=(3, 20, 6)):
    rs = np.random.RandomState(31)
    return rs.randn(*shape)


def test_median_filter_scipy_parity(mesh):
    x = _x()
    lout = median_filter(bolt.array(x), 3, axis=(0,), size=(6,)).toarray()
    tout = median_filter(bolt.array(x, mesh), 3, axis=(0,),
                         size=(6,)).toarray()
    assert allclose(lout, tout)
    expect = np.stack([ndi.median_filter(r, size=(3, 1), mode="reflect")
                       for r in x])
    assert allclose(lout, expect)


def test_median_filter_2d_window(mesh):
    # joint rectangular window (median is not separable)
    x = _x((2, 12, 10))
    lout = median_filter(bolt.array(x), (3, 5), axis=(0, 1),
                         size=(6, 5)).toarray()
    tout = median_filter(bolt.array(x, mesh), (3, 5), axis=(0, 1),
                         size=(6, 5)).toarray()
    assert allclose(lout, tout)
    expect = np.stack([ndi.median_filter(r, size=(3, 5), mode="reflect")
                       for r in x])
    assert allclose(lout, expect)
    with pytest.raises(ValueError):
        median_filter(bolt.array(x), 2)


def test_gaussian_scipy_parity():
    # scipy is present in this image: gaussian taps match ndimage's.
    # np 'reflect' == scipy 'mirror'; scipy's name is accepted as alias
    x = _x((2, 64, 4))
    out = gaussian(bolt.array(x), 2.0, axis=(0,), mode="reflect").toarray()
    expect = np.stack([ndi.gaussian_filter1d(r, 2.0, axis=0, mode="mirror")
                       for r in x])
    assert allclose(out, expect, rtol=1e-6, atol=1e-8)
    alias = gaussian(bolt.array(x), 2.0, axis=(0,), mode="mirror").toarray()
    assert allclose(alias, expect, rtol=1e-6, atol=1e-8)
    near = gaussian(bolt.array(x), 1.0, axis=(0,), mode="nearest").toarray()
    expect_n = np.stack([ndi.gaussian_filter1d(r, 1.0, axis=0,
                                               mode="nearest") for r in x])
    assert allclose(near, expect_n, rtol=1e-6, atol=1e-8)


def test_detrend_parity(mesh):
    x = _x()
    lout = detrend(bolt.array(x), order=1, axis=0).toarray()
    tout = detrend(bolt.array(x, mesh), order=1, axis=0).toarray()
    assert allclose(lout, tout, rtol=1e-6)
    # scipy.signal.detrend removes the linear least-squares trend
    expect = scipy.signal.detrend(x, axis=1, type="linear")
    assert allclose(lout, expect, rtol=1e-6, atol=1e-8)
    # order=0 == mean removal == scipy type='constant'
    l0 = detrend(bolt.array(x), order=0).toarray()
    assert allclose(l0, scipy.signal.detrend(x, axis=1, type="constant"),
                    rtol=1e-8)
    # quadratic trend is removed exactly
    t = np.linspace(-1, 1, 20)
    quad = 3.0 * t ** 2 + 2.0 * t - 1.0
    y = x + quad[None, :, None]
    l2 = detrend(bolt.array(y), order=2).toarray()
    t2 = detrend(bolt.array(y, mesh), order=2).toarray()
    assert allclose(l2, t2, rtol=1e-6)
    assert allclose(l2, detrend(bolt.array(x), order=2).toarray(), rtol=1e-6)
    # integer input promotes to float instead of truncating the
    # projector to zeros
    xi = (np.arange(40) ** 2).reshape(2, 20)
    di = detrend(bolt.array(xi), order=1).toarray()
    assert np.issubdtype(di.dtype, np.floating)
    assert allclose(di, scipy.signal.detrend(xi.astype(float), axis=1),
                    rtol=1e-8)
    with pytest.raises(ValueError):
        detrend(bolt.array(x), order=-1)
    with pytest.raises(ValueError):
        detrend(bolt.array(x), order=25)   # length 20 axis
    with pytest.raises(ValueError):
        detrend(bolt.array(x), axis=7)


def test_detrend_fuses(mesh):
    # detrend is a deferred map: chaining into an action is one program
    x = _x()
    out = detrend(bolt.array(x, mesh).map(lambda v: v * 2.0)).sum(axis=(0,))
    expect = scipy.signal.detrend(x * 2.0, axis=1).sum(axis=0)
    assert allclose(out.toarray(), expect, rtol=1e-6, atol=1e-7)


def test_zscore_center_parity(mesh):
    x = _x()
    for ddof in (0, 1):
        lz = zscore(bolt.array(x), axis=0, ddof=ddof).toarray()
        tz = zscore(bolt.array(x, mesh), axis=0, ddof=ddof).toarray()
        assert allclose(lz, tz, rtol=1e-6)
        mu = x.mean(axis=1, keepdims=True)
        sd = x.std(axis=1, ddof=ddof, keepdims=True)
        assert allclose(lz, (x - mu) / sd, rtol=1e-8)
    lc = center(bolt.array(x), axis=1).toarray()
    tc = center(bolt.array(x, mesh), axis=1).toarray()
    assert allclose(lc, tc, rtol=1e-8)
    assert allclose(lc, x - x.mean(axis=2, keepdims=True), rtol=1e-8)
    # epsilon guards constant records
    const = np.ones((2, 5))
    z = zscore(bolt.array(const), epsilon=1e-6).toarray()
    assert np.allclose(z, 0.0)


def _pearson(a, b):
    return np.corrcoef(a, b)[0, 1]


def test_crosscorr_parity(mesh):
    rs = np.random.RandomState(5)
    x = rs.randn(6, 30)
    sig = rs.randn(30)
    lout = crosscorr(bolt.array(x), sig, lag=3).toarray()
    tout = crosscorr(bolt.array(x, mesh), sig, lag=3).toarray()
    assert lout.shape == (6, 7)
    assert allclose(lout, tout, rtol=1e-6)
    # independent oracle: pearson r over the overlapping window per lag
    for i in range(6):
        for j, k in enumerate(range(-3, 4)):
            if k >= 0:
                r = _pearson(x[i, k:], sig[:30 - k])
            else:
                r = _pearson(x[i, :30 + k], sig[-k:])
            assert np.isclose(lout[i, j], r, rtol=1e-8), (i, k)
    # lag=0 is each record's plain correlation with the signal
    l0 = crosscorr(bolt.array(x), sig).toarray()
    assert l0.shape == (6, 1)
    assert np.isclose(l0[2, 0], _pearson(x[2], sig), rtol=1e-10)
    # a record equal to the shifted signal peaks at that shift
    y = np.stack([np.r_[sig[2:], np.zeros(2)]])   # y[t] = sig[t+2]
    peak = crosscorr(bolt.array(y), sig, lag=3).toarray()[0]
    assert np.argmax(peak) == 1                   # k = -2 -> index 1
    assert peak[1] > 0.99


def test_crosscorr_epsilon_guard():
    # constant records: 0/0 without the guard; 0 with it
    sig = np.random.RandomState(1).randn(10)
    z = crosscorr(bolt.array(np.ones((2, 10))), sig, epsilon=1e-9).toarray()
    assert np.isfinite(z).all() and np.allclose(z, 0.0)


def test_crosscorr_validation():
    x = np.random.randn(3, 10)
    with pytest.raises(ValueError):
        crosscorr(bolt.array(x), np.zeros(7))     # wrong length
    with pytest.raises(ValueError):
        crosscorr(bolt.array(x), np.zeros(10), lag=-1)
    with pytest.raises(ValueError):
        crosscorr(bolt.array(x), np.zeros(10), lag=10)
    with pytest.raises(ValueError):
        # lag = L-1 leaves a single-sample overlap: Pearson undefined
        crosscorr(bolt.array(x), np.zeros(10), lag=9)


def test_crosscorr_multiaxis(mesh):
    # time on value axis 0, channels on value axis 1: correlation
    # computed per channel, axis replaced by the lag dimension
    rs = np.random.RandomState(9)
    x = rs.randn(4, 20, 3)
    sig = rs.randn(20)
    lout = crosscorr(bolt.array(x), sig, lag=2, axis=0).toarray()
    tout = crosscorr(bolt.array(x, mesh), sig, lag=2, axis=0).toarray()
    assert lout.shape == (4, 5, 3)
    assert allclose(lout, tout, rtol=1e-6)
    assert np.isclose(lout[1, 2, 0], _pearson(x[1, :, 0], sig), rtol=1e-8)


def test_fourier_parity(mesh):
    # records built from known sinusoids: coherence peaks at their bin
    T = 64
    t = np.arange(T)
    rs = np.random.RandomState(17)
    phase_in = 0.7
    x = np.stack([
        np.sin(2 * np.pi * 4 * t / T + phase_in),          # pure bin 4
        np.sin(2 * np.pi * 4 * t / T) + rs.randn(T) * 0.1,  # noisy bin 4
        rs.randn(T),                                        # noise
    ])
    from bolt_tpu.ops import fourier
    lcoh, lph = fourier(bolt.array(x), freq=4)
    tcoh, tph = fourier(bolt.array(x, mesh), freq=4)
    assert lcoh.shape == (3,) and lph.shape == (3,)
    assert allclose(lcoh.toarray(), tcoh.toarray(), rtol=1e-6)
    assert allclose(lph.toarray(), tph.toarray(), rtol=1e-5, atol=1e-6)
    lc = np.asarray(lcoh.toarray())
    assert np.isclose(lc[0], 1.0, atol=1e-9)       # pure tone: all energy
    assert lc[1] > 0.8 > lc[2]
    # phase convention: sin(wt + p) -> rfft angle p - pi/2
    assert np.isclose(np.asarray(lph.toarray())[0],
                      phase_in - np.pi / 2, atol=1e-9)
    # oracle for the noise record
    co = np.fft.rfft(x[2] - x[2].mean())
    expect = np.abs(co[4]) / np.sqrt(np.sum(np.abs(co[1:]) ** 2))
    assert np.isclose(lc[2], expect, rtol=1e-10)
    with pytest.raises(ValueError):
        fourier(bolt.array(x), freq=0)
    with pytest.raises(ValueError):
        fourier(bolt.array(x), freq=T)
    # constant records: epsilon guards the 0/0
    c, p = fourier(bolt.array(np.ones((2, 16))), freq=2, epsilon=1e-9)
    assert np.isfinite(c.toarray()).all()
    # deferral contract: fourier outputs are still deferred maps on the
    # TPU backend (nothing materialised yet) and fuse downstream
    tb2 = bolt.array(x, mesh)
    c2, _ = fourier(tb2, freq=4)
    assert c2.deferred
    assert allclose(c2.map(lambda v: v * 2, axis=(0,)).toarray(),
                    np.asarray(lcoh.toarray()) * 2)


# ---------------------------------------------------------------------
# the device side of ``fourier`` takes ONE bin as a product with a cosine
# and a sine and the energy from Parseval's identity on the series (PR
# 44); the NumPy side is ``np.fft.rfft``.  Two independent routes to one
# definition, compared over what a route could get wrong: the parity of
# the length, the first, an inner and the Nyquist bin, the dtype, where
# the series axis lies
# ---------------------------------------------------------------------

def _turn(a, b):
    """Angular distance on the circle."""
    return np.abs(np.angle(np.exp(1j * (np.asarray(a, np.float64) - b))))


def _planted(shape, freq, seed, dtype):
    """Series along the LAST axis: a third sinusoids at ``freq`` in
    noise, a third sinusoids two bins away on a slope, a third noise,
    all on a level of 40 (the centring matters)."""
    rs = np.random.RandomState(seed)
    length = shape[-1]
    t = np.arange(length)
    x = rs.randn(*shape) * 0.5 + 40.0
    flat = x.reshape(-1, length)
    for i, row in enumerate(flat):
        if i % 3 == 0:
            row += 2.0 * np.cos(2 * np.pi * freq * t / length + 0.1 * i)
        elif i % 3 == 1:
            row += np.sin(2 * np.pi * (freq + 2) * t / length) + 0.01 * t
    return x.astype(dtype)


@pytest.mark.parametrize("dtype", ["float64", "float32"])
@pytest.mark.parametrize("length,freq", [
    (64, 1), (64, 5), (64, 32), (63, 1), (63, 5), (63, 31),
    (250, 7), (250, 125), (1001, 500)])
def test_fourier_on_the_device_is_the_rfft_route(mesh, length, freq, dtype):
    from bolt_tpu.ops import fourier
    x = _planted((24, length), freq, 100 * length + freq, dtype)
    # the oracle reads the same values, in float64
    lcoh, lph = fourier(bolt.array(x.astype(np.float64)), freq=freq)
    tcoh, tph = fourier(bolt.array(x, mesh), freq=freq)
    lcoh, lph = lcoh.toarray(), lph.toarray()
    tcoh, tph = tcoh.toarray(), tph.toarray()
    assert tcoh.dtype == tph.dtype == np.dtype(dtype)
    assert tcoh.shape == tph.shape == (24,)
    f32 = dtype == "float32"
    assert allclose(lcoh, tcoh, rtol=1e-6, atol=2e-7 if f32 else 1e-12)
    if 2 * freq == length:
        # the Nyquist bin is real: 0 or pi, to the bit, with rfft's sign
        assert set(np.unique(tph)) <= {0.0, np.dtype(dtype).type(np.pi)}
        assert np.array_equal(tph > 1, lph > 1)
    else:
        tuned = lcoh > 0.05             # an empty bin has no angle
        assert tuned.sum() >= 8
        assert np.max(_turn(tph, lph)[tuned]) < (1e-5 if f32 else 1e-11)


@pytest.mark.parametrize("length,freq", [(64, 4), (64, 32), (63, 4),
                                         (63, 31), (10240, 16)])
def test_fourier_of_a_pure_sinusoid_at_the_bin_is_all_of_the_energy(
        mesh, length, freq):
    from bolt_tpu.ops import fourier
    t = np.arange(length)
    shifts = (0.0, np.pi) if 2 * freq == length else (0.7, -2.1)
    x = np.stack([3.0 * np.cos(2 * np.pi * freq * t / length + p) + 11.0
                  for p in shifts]).astype(np.float32)
    coh, ph = fourier(bolt.array(x, mesh), freq=freq)
    assert np.max(np.abs(coh.toarray() - 1.0)) < 2e-6
    # cos(wt + p) has the angle p at its bin
    assert np.max(_turn(ph.toarray(), np.asarray(shifts))) < 2e-5


@pytest.mark.parametrize("length", [16, 15], ids=["even", "odd"])
@pytest.mark.parametrize("epsilon", [0.0, 1e-9], ids=["bare", "guarded"])
def test_fourier_of_a_constant_record(mesh, length, epsilon):
    # no energy at all: 0/0 without the guard (NaN, as the rfft route
    # gives), 0 with it; never the NaN of a negative square root, and
    # the angle of an empty bin is 0 on both routes
    from bolt_tpu.ops import fourier
    x = np.full((3, length), 7.25)
    arrays = [bolt.array(x), bolt.array(x, mesh)]
    if length == 16:                    # whose float32 mean is exact
        arrays.append(bolt.array(x.astype(np.float32), mesh))
    for b in arrays:
        coh, ph = fourier(b, freq=2, epsilon=epsilon)
        coh, ph = coh.toarray(), ph.toarray()
        if epsilon:
            assert np.array_equal(coh, np.zeros(3))
        else:
            assert np.isnan(coh).all()
        assert np.array_equal(ph, np.zeros(3))


@pytest.mark.parametrize("length,freq", [(250, 7), (251, 7)],
                         ids=["even", "odd"])
def test_fourier_leaves_out_what_the_centring_left_in_the_dc_bin(
        mesh, length, freq):
    # float32 on a level of 1000: the mean is rounded to 6e-5 and the
    # centred series keeps that much of a level, which is the DC bin's
    # and no other's.  The definition leaves the DC bin out; an energy
    # that kept it would read the coherence 3e-3 low here
    from bolt_tpu.ops import fourier
    rs = np.random.RandomState(3)
    t = np.arange(length)
    x = (1000.3 + 0.002 * np.cos(2 * np.pi * freq * t / length
                                 + 6 * rs.rand(24, 1))
         + 0.001 * rs.randn(24, length)).astype(np.float32)
    lcoh, lph = fourier(bolt.array(x.astype(np.float64)), freq=freq)
    tcoh, tph = fourier(bolt.array(x, mesh), freq=freq)
    assert tcoh.dtype == np.float32
    assert np.max(np.abs(tcoh.toarray() - lcoh.toarray())) < 2e-6
    assert np.max(_turn(tph.toarray(), lph.toarray())) < 2e-6


@pytest.mark.parametrize("dtype", ["int16", "int32", "uint8"])
def test_fourier_promotes_an_integer_record(mesh, dtype):
    from bolt_tpu.ops import fourier
    x = np.round(_planted((12, 96), 6, 5, "float64")).astype(dtype)
    lcoh, lph = fourier(bolt.array(x), freq=6)
    tcoh, tph = fourier(bolt.array(x, mesh), freq=6)
    assert tcoh.dtype == np.float32 == tph.dtype
    assert allclose(lcoh.toarray(), tcoh.toarray(), rtol=1e-6, atol=2e-7)
    tuned = lcoh.toarray() > 0.05
    assert np.max(_turn(tph.toarray(), lph.toarray())[tuned]) < 1e-5


@pytest.mark.parametrize("name,shape,split,axis", [
    ("first-of-two", (6, 48, 3), 1, 0),
    ("last-of-two", (6, 3, 48), 1, 1),
    ("last-of-two-from-the-end", (6, 3, 48), 1, -1),
    ("middle-of-three", (4, 2, 48, 3), 1, 1),
    ("first-of-two-split-2", (3, 4, 48, 2), 2, 0),
], ids=lambda v: v if isinstance(v, str) else None)
def test_fourier_along_any_value_axis(mesh, name, shape, split, axis):
    from bolt_tpu.ops import fourier
    at = split + (axis % (len(shape) - split))
    moved = shape[:at] + shape[at + 1:] + (shape[at],)
    x = np.moveaxis(_planted(moved, 5, 9, "float64"), -1, at)
    assert x.shape == shape
    co = np.fft.rfft(np.moveaxis(x, at, -1)
                     - x.mean(axis=at)[..., None], axis=-1)
    want = np.abs(co[..., 5]) / np.sqrt(np.sum(np.abs(co[..., 1:]) ** 2,
                                               axis=-1))
    tcoh, tph = fourier(bolt.array(x, mesh, axis=tuple(range(split))),
                        freq=5, axis=axis)
    assert tcoh.shape == tph.shape == want.shape
    assert allclose(tcoh.toarray(), want, rtol=1e-9)
    assert np.max(_turn(tph.toarray(), np.angle(co[..., 5]))) < 1e-9
    if split == 1:                      # the local backend keys axis 0
        lcoh, lph = fourier(bolt.array(x), freq=5, axis=axis)
        assert allclose(lcoh.toarray(), want, rtol=1e-12)
        assert allclose(lph.toarray(), np.angle(co[..., 5]), rtol=1e-12)


# ---------------------------------------------------------------------
# a ``fourier`` whose argument's chain ends in ``detrend``, ``center`` or
# ``zscore`` along the same axis spends no pass on a mean its parent took
# out (PR 49): one reader of the parent's result, which XLA fuses with
# it.  Held here to what the centring route (the parent commit's, built
# by hand from the bare record function) reads on the same data, over
# what an uncentred sum could get wrong: a level far above the signal, a
# ramp the fit takes out by cancellation, a spike, no energy at all, the
# parity of the length and the Nyquist bin; and both to NumPy in float64
# ---------------------------------------------------------------------

def _detrend64(order):
    def f(x):
        t = np.linspace(-1.0, 1.0, x.shape[-1])
        q, _ = np.linalg.qr(np.vander(t, order + 1, increasing=True))
        return x - (x @ q) @ q.T
    return f


def _center64(x):
    return x - x.mean(axis=-1, keepdims=True)


def _zscore64(x):
    return _center64(x) / x.std(axis=-1, keepdims=True)


def _dff64(x):
    base = np.percentile(x, 20.0, axis=-1, keepdims=True)
    return (x - base) / base


def _cell_like(n, length, freq, rs):
    """Series made as ``benchmark/operands/pixelseries.py`` makes them:
    whole counts, a resting level of thousands, a drift of order 2, a
    sinusoid at the bin on two pixels in three, uniform noise."""
    t = np.arange(length)
    rest = rs.randint(4000, 8001, (n, 1))
    drift = (np.floor(rs.randint(-400, 401, (n, 1)) * t / length)
             + np.floor(rs.randint(-400, 401, (n, 1)) * (t / length) ** 2))
    tuned = np.arange(n)[:, None] % 3 != 0
    w = 2.0 * np.pi * freq * t / length
    wave = np.floor((tuned * rs.randint(-400, 401, (n, 1))
                     * np.rint(1024 * np.cos(w))
                     + tuned * rs.randint(-400, 401, (n, 1))
                     * np.rint(1024 * np.sin(w))) / 1024)
    return rest + drift + wave + rs.randint(-256, 257, (n, length))


def _wave_in_noise(n, length, freq, rs, level=0.0):
    t = np.arange(length)
    return (level + rs.randn(n, length)
            + 0.7 * np.cos(2 * np.pi * freq * t / length
                           + 6 * rs.rand(n, 1)))


def _zero_mean_case(name):
    """``(x, chain, the same in float64, freq, epsilon)`` of a case."""
    from bolt_tpu.ops import normalize
    rs = np.random.RandomState(49)
    if name == "the-cells-closed-form":
        return (_cell_like(48, 10240, 16, rs),
                lambda b: detrend(normalize(b, perc=20.0), order=5),
                lambda x: _detrend64(5)(_dff64(x)), 16, 0.0)
    if name in ("a-level-of-1e6-center", "a-level-of-1e6-zscore"):
        x = _wave_in_noise(48, 2048, 8, rs, level=1e6)
        if name.endswith("center"):
            return x, center, _center64, 8, 0.0
        return x, zscore, _zscore64, 8, 0.0
    if name == "a-steep-ramp-detrend-1":
        x = (_wave_in_noise(48, 2048, 8, rs)
             + np.linspace(0.0, 1e5, 2048) * rs.uniform(0.5, 2.0, (48, 1)))
        return x, lambda b: detrend(b, order=1), _detrend64(1), 8, 0.0
    if name in ("a-spike-first-detrend-1", "a-spike-first-center"):
        x = _wave_in_noise(48, 2048, 8, rs)
        x[:, 0] += 1e4
        if name.endswith("center"):
            return x, center, _center64, 8, 0.0
        return x, lambda b: detrend(b, order=1), _detrend64(1), 8, 0.0
    if name in ("a-constant-record-center", "a-constant-record-detrend-1"):
        x = np.full((6, 512), 37.3)
        if name.endswith("center"):
            return x, center, _center64, 4, 1e-6
        return x, lambda b: detrend(b, order=1), _detrend64(1), 4, 1e-6
    length = {"odd-length": 2047, "even-length": 2048,
              "odd-length-last-bin": 2047, "the-nyquist-bin": 2048}[name]
    freq = length // 2 if name.endswith("bin") else 8
    x = _wave_in_noise(48, length, freq, rs)
    if 2 * freq == length:
        x = x + np.cos(np.pi * np.arange(length))
    return x, lambda b: detrend(b, order=3), _detrend64(3), freq, 0.0


def _coherence_and_phase64(y, freq, epsilon):
    co = np.fft.rfft(_center64(y), axis=-1)
    energy = np.sum(np.abs(co[..., 1:]) ** 2, axis=-1)
    return (np.abs(co[..., freq]) / (np.sqrt(energy) + epsilon),
            np.angle(co[..., freq]))


def _centring_route(d, freq, epsilon):
    """``fourier`` of the deferred ``d`` with its own pass for the mean,
    whatever ``d``'s chain says: the bare record function, mapped by
    hand."""
    from bolt_tpu.ops import series
    out = series._apply_map(d, series._fourier_fn(freq, 0, float(epsilon)))
    return tuple(series._apply_map(out, series._pick_fn(0, i))
                 for i in (0, 1))


@pytest.mark.parametrize("name", [
    "the-cells-closed-form", "a-level-of-1e6-center",
    "a-level-of-1e6-zscore", "a-steep-ramp-detrend-1",
    "a-spike-first-detrend-1", "a-spike-first-center",
    "a-constant-record-center", "a-constant-record-detrend-1",
    "odd-length", "even-length", "odd-length-last-bin", "the-nyquist-bin"])
def test_fourier_behind_a_zero_mean_parent_reads_as_the_centring_route(
        mesh, name):
    from bolt_tpu.ops import fourier
    x, chain, chain64, freq, epsilon = _zero_mean_case(name)
    x = x.astype(np.float32)
    b = bolt.array(x, mesh)
    fused = fourier(chain(b), freq=freq, epsilon=epsilon)
    centred = _centring_route(chain(b), freq, epsilon)
    # the record functions in front of the pick: without, and with, a mean
    assert [getattr(pair[0]._chain[1][-2], "centred_by_parent", None)
            for pair in (fused, centred)] == [0, None]
    fcoh, fph = (h.toarray() for h in fused)
    ccoh, cph = (h.toarray() for h in centred)
    assert fcoh.dtype == fph.dtype == np.float32
    coh64, ph64 = _coherence_and_phase64(
        chain64(x.astype(np.float64)), freq, epsilon)
    if name.startswith("a-constant-record"):
        # no energy: what the parent's rounding left is a few ulp of the
        # level and far under the guard; an empty bin has no angle
        assert np.max(np.abs(fcoh)) < 1e-3 and np.isfinite(fph).all()
        assert np.max(np.abs(ccoh)) < 1e-3
        return
    # a few ulp of a coherence (at most 1) and of an angle (at most pi)
    assert np.max(np.abs(fcoh - ccoh)) < 6e-7
    # a spike is every bin alike, its own among them: 1 / sqrt(L / 2)
    tuned = coh64 >= (0.02 if name.startswith("a-spike-first") else 0.3)
    assert tuned.sum() >= 8
    assert np.max(_turn(fph, cph.astype(np.float64))[tuned]) < 2e-6
    # and no further from the float64 reading than the centring route is
    # (the steep ramp costs both 1e-3: float32 cancels 1e5 against 1)
    for got, want, far in ((fcoh, coh64, np.abs(ccoh - coh64)),
                           (fph, ph64, _turn(cph, ph64))):
        off = (np.abs(got - want) if want is coh64
               else _turn(got, want))[tuned]
        assert np.max(off) < 1.5 * np.max(far[tuned]) + 5e-7


@pytest.fixture
def matrix_fit(monkeypatch):
    """Every device ``detrend`` made inside takes its fit out by the thin
    matrix product, whatever its order (the spelling above the bound)."""
    from bolt_tpu.ops import series
    monkeypatch.setattr(series, "_FIT_TERMS_ON_VPU", 0)
    series._detrend_fn.cache_clear()
    yield
    series._detrend_fn.cache_clear()


@pytest.mark.parametrize("order", [0, 1, 5, 15])
@pytest.mark.parametrize("name", [
    "the-cells-closed-form", "a-steep-ramp-detrend-1",
    "a-spike-first-detrend-1", "odd-length", "even-length"])
def test_the_fit_by_horners_rule_is_the_matrix_products(mesh, matrix_fit,
                                                        name, order):
    # one polynomial, two ways to evaluate it in float32 from the same
    # coefficients: they part by a few ulp of what they sum, and the
    # bound between them (ops/series.py :: _FIT_TERMS_ON_VPU) is about
    # speed alone
    from bolt_tpu.ops import series
    x = _zero_mean_case(name)[0].astype(np.float32)
    if name == "the-cells-closed-form":
        x = x[:, :2048] / np.float32(4096)
    b = bolt.array(x, mesh)
    by_product = detrend(b, order=order).toarray()
    series._detrend_fn.cache_clear()
    series._FIT_TERMS_ON_VPU = order + 1        # the fixture puts it back
    by_horner = detrend(b, order=order).toarray()
    assert by_horner.dtype == by_product.dtype == np.float32
    # the size of what either sums: the data and the polynomial's terms
    # (at order 15 the monomials' coefficients are thousands of times the
    # data, with signs that cancel: both lose those digits alike)
    t = np.linspace(-1.0, 1.0, x.shape[-1])
    van = np.abs(np.vander(t, order + 1, increasing=True))
    coef = x.astype(np.float64) @ np.linalg.pinv(
        np.vander(t, order + 1, increasing=True)).T
    size = np.max(np.abs(x) + np.abs(coef) @ van.T, axis=-1, keepdims=True)
    ulp = np.finfo(np.float32).eps
    assert np.max(np.abs(by_horner - by_product) / size) < 8 * ulp
    want = _detrend64(order)(x.astype(np.float64))
    far = np.max(np.abs(by_product - want) / size)
    assert np.max(np.abs(by_horner - want) / size) < 1.5 * far + 8 * ulp


def _own_map(v):
    return v * 2.0


@pytest.mark.parametrize("name,chain,axis,engages", [
    ("detrend-0", lambda b: detrend(b, order=0, axis=1), 1, True),
    ("detrend-5", lambda b: detrend(b, order=5, axis=1), 1, True),
    ("detrend-9", lambda b: detrend(b, order=9, axis=1), 1, True),
    ("center", lambda b: center(b, axis=1), 1, True),
    ("zscore", lambda b: zscore(b, axis=-1), 1, True),
    ("center-behind-a-map", lambda b: center(b.map(_own_map), axis=1), 1,
     True),
    ("stored-data", lambda b: b, 1, False),
    ("another-axis", lambda b: center(b, axis=0), 1, False),
    ("a-map-behind-center", lambda b: center(b, axis=1).map(_own_map), 1,
     False),
    ("a-cast-behind-center",
     lambda b: center(b, axis=1).astype(np.float32), 1, False),
    ("the-residual-cached",       # the local backend has nothing to cache
     lambda b: getattr(detrend(b, order=1, axis=1), "cache", lambda: None)()
     or detrend(b, order=1, axis=1), 1, False),
], ids=lambda v: v if isinstance(v, str) else "")
def test_which_fourier_takes_its_parents_word_for_the_mean(mesh, name,
                                                           chain, axis,
                                                           engages):
    # decided from the argument's deferred chain, its LAST stage and the
    # same axis; whatever else comes centres as a bare fourier does, and
    # analysis.explain says which from the function's own attribute
    from bolt_tpu import analysis
    from bolt_tpu.ops import fourier
    rs = np.random.RandomState(7)
    x = (50.0 + _wave_in_noise(12 * 3, 64, 5, rs)).reshape(12, 3, 64)
    coh, ph = fourier(chain(bolt.array(x, mesh)), freq=5, axis=axis)
    took = getattr(coh._chain[1][-2], "centred_by_parent", None)
    assert took == (axis if engages else None)
    said = str(analysis.explain(coh))
    assert ("centred by its parent: no pass for the mean" in said) == engages
    lcoh, lph = fourier(chain(bolt.array(x)), freq=5, axis=axis)
    assert allclose(coh.toarray(), lcoh.toarray(), rtol=1e-6, atol=1e-7)
    assert np.max(_turn(ph.toarray(), lph.toarray())) < 1e-5


def test_the_counter_counts_traces_without_a_mean(mesh):
    from bolt_tpu import engine
    from bolt_tpu.ops import fourier
    x = _wave_in_noise(16, 93, 11, np.random.RandomState(3), level=5.0)
    b = bolt.array(x, mesh)
    c0 = engine.counters()["fourier_centred_by_parent"]
    fourier(b, freq=11)[0].toarray()
    fourier(center(bolt.array(x)), freq=11)[0].toarray()    # local: NumPy
    assert engine.counters()["fourier_centred_by_parent"] == c0
    fourier(center(b), freq=11)[0].toarray()
    assert engine.counters()["fourier_centred_by_parent"] > c0


def test_fourier_of_a_cached_residual_centres_and_reads_the_same(mesh):
    # no chain to read once the residual is an array: it centres, and
    # differs from the fused route by rounding alone
    from bolt_tpu.ops import fourier
    x, chain, _, freq, _ = _zero_mean_case("a-steep-ramp-detrend-1")
    b = bolt.array(x.astype(np.float32), mesh)
    fcoh, fph = (h.toarray() for h in fourier(chain(b), freq=freq))
    kept = chain(b).cache()
    assert not kept.deferred
    kcoh, kph = (h.toarray() for h in fourier(kept, freq=freq))
    assert np.max(np.abs(fcoh - kcoh)) < 6e-7
    assert np.max(_turn(fph, kph.astype(np.float64))) < 2e-6


def test_normalize_parity(mesh):
    from bolt_tpu.ops import normalize
    rs = np.random.RandomState(23)
    x = rs.rand(5, 40) + 0.5                    # positive baselines
    lout = normalize(bolt.array(x), perc=20).toarray()
    tout = normalize(bolt.array(x, mesh), perc=20).toarray()
    assert allclose(lout, tout, rtol=1e-6)
    base = np.percentile(x, 20, axis=1, keepdims=True)
    assert allclose(lout, (x - base) / base, rtol=1e-8)
    lm = normalize(bolt.array(x), baseline="mean").toarray()
    mu = x.mean(axis=1, keepdims=True)
    assert allclose(lm, (x - mu) / mu, rtol=1e-8)
    # epsilon guards zero baselines
    z = normalize(bolt.array(np.zeros((2, 8))), epsilon=1e-9).toarray()
    assert np.isfinite(z).all()
    # ... and NEGATIVE baselines (sign-aware: the guard must push the
    # denominator away from zero, not across it)
    xn = np.array([[-1e-6, -1e-6, -1e-6, 1.0]])
    zn = normalize(bolt.array(xn), perc=20, epsilon=1e-6).toarray()
    assert np.isfinite(zn).all()
    with pytest.raises(ValueError):
        normalize(bolt.array(x), baseline="windowed")
    with pytest.raises(ValueError):
        normalize(bolt.array(x), perc=150)


def test_series_transforms_differentiable():
    # the block functions are pure jnp pipelines: grads flow through them
    # for users embedding these transforms in larger differentiable models
    import jax
    import jax.numpy as jnp
    from bolt_tpu.ops.series import _detrend_fn, _zscore_fn

    x = jnp.asarray(np.random.RandomState(2).randn(20))
    det = _detrend_fn(20, 1, 0)
    g = jax.grad(lambda v: jnp.sum(det(v) ** 2))(x)
    # analytic: d/dv ||R v||^2 = 2 R^T R v = 2 R v (projector: R^T R = R)
    t = np.linspace(-1, 1, 20)
    a = np.vander(t, 2, increasing=True)
    r = np.eye(20) - a @ np.linalg.pinv(a)
    assert np.allclose(np.asarray(g), 2 * r @ np.asarray(x), atol=1e-10)

    zs = _zscore_fn(0, 0, 1e-9)
    gz = jax.grad(lambda v: jnp.sum(zs(v) ** 2))(x)
    assert np.isfinite(np.asarray(gz)).all()
