"""On-chip correctness gate (VERDICT r3 next-3 / missing-4).

Every other suite runs on the virtual 8-device CPU mesh with x64 ON —
exactly the configuration production TPU never sees.  This module is a
``-m chip``-marked parity subset that runs against the REAL device with
production numerics (x64 OFF: f32/bf16, Mosaic geometry, XLA:TPU
lowering): operators, map/reduce, stats (incl. the fused-Welford pallas
geometry), filter both paths, swap, chunked/halo map, the separable
filter on both axis classes, npdispatch, indexing, and the linalg ops.

Run via the one-command driver::

    python scripts/chip_gate.py         # sets BOLT_TEST_CHIP=1, -m chip

Oracle comparisons are against numpy in float32 with f32-appropriate
tolerances — the local backend stays the semantic oracle; only the
precision envelope changes.  Off-gate (normal pytest) the module skips.
"""

import numpy as np
import pytest

import bolt_tpu as bolt
from conftest import CHIP_GATE

pytestmark = [
    pytest.mark.chip,
    pytest.mark.skipif(not CHIP_GATE,
                       reason="on-chip gate only (scripts/chip_gate.py)"),
]


@pytest.fixture(scope="module")
def cmesh():
    import jax
    devs = np.array(jax.devices())
    return jax.sharding.Mesh(devs.reshape(devs.size), ("k",))


def _x(shape=(16, 8, 128), seed=0):
    return np.random.RandomState(seed).randn(*shape).astype(np.float32)


def _close(got, want, rtol=1e-5, atol=1e-5):
    g = np.asarray(got.toarray() if hasattr(got, "toarray") else got)
    w = np.asarray(want)
    assert g.shape == w.shape, (g.shape, w.shape)
    if np.issubdtype(g.dtype, np.floating):
        # x64 must stay off on chip: a float64 result IS the leak this
        # gate exists to catch
        assert g.dtype == np.float32, g.dtype
    assert np.allclose(g, w, rtol=rtol, atol=atol), np.abs(g - w).max()


def test_chip_backend_is_tpu():
    import jax
    assert jax.devices()[0].platform == "tpu", jax.devices()
    assert not jax.config.jax_enable_x64


def test_map_sum_bit_exact_config1(cmesh):
    # BASELINE config 1 on integral-valued floats: bit-exact, the
    # north-star's acceptance condition
    b = bolt.ones((32, 16, 128), context=cmesh, dtype=np.float32)
    out = b.map(lambda v: v + 1).sum(axis=(0, 1, 2))
    assert float(np.asarray(out.toarray())) == 2.0 * 32 * 16 * 128


def test_operators_and_ufuncs(cmesh):
    x = _x()
    b = bolt.array(x, cmesh)
    _close((b + 1) * 2 - b / 2, (x + 1) * 2 - x / 2)
    # TPU's tanh lowering is ~5e-5 off numpy's — production envelope
    _close(np.tanh(b), np.tanh(x), atol=1e-4)
    _close(abs(-b), np.abs(x))
    _close((b > 0).sum(axis=(0, 1, 2)), (x > 0).sum())


def test_stats_welford_fused_geometry(cmesh):
    # minor dim 128-aligned: the pallas fused_welford kernel's geometry;
    # f32 single-pass Welford vs numpy's two-pass in f64, f32 envelope
    x = _x((64, 4, 128), seed=1)
    b = bolt.array(x, cmesh)
    st = b.stats()
    _close(st.mean(), x.mean(axis=0, dtype=np.float64).astype(np.float32),
           rtol=1e-5, atol=1e-5)
    _close(st.variance(), x.var(axis=0, dtype=np.float64).astype(np.float32),
           rtol=1e-4, atol=1e-4)
    _close(b.mean(), x.mean(axis=0, dtype=np.float64), rtol=1e-5)
    _close(b.max(), x.max(axis=0))
    _close(b.min(), x.min(axis=0))
    # unaligned minor dim: the jnp fallback path, same answers
    y = _x((64, 4, 37), seed=2)
    by = bolt.array(y, cmesh)
    _close(by.std(), y.std(axis=0, dtype=np.float64), rtol=1e-4, atol=1e-4)


def test_filter_both_paths(cmesh, monkeypatch):
    import bolt_tpu.tpu.array as mod
    x = _x((32, 4, 8), seed=3)
    keep = np.array([v.mean() > 0 for v in x])
    b = bolt.array(x, cmesh)
    # fused (pending) path
    out = b.filter(lambda v: v.mean() > 0)
    assert out.pending
    _close(out, x[keep])
    # two-phase eager path with the bucketed gather
    monkeypatch.setattr(mod, "_FILTER_FUSED_MAX_BYTES", 0)
    out2 = b.filter(lambda v: v.mean() > 0)
    assert out2.pending             # deferred at every size (ISSUE 30)
    assert out2.shape == x[keep].shape and not out2.pending
    _close(out2, x[keep])


def test_thin_records_fold_by_the_kernel(cmesh):
    # tpu/fold.py (ISSUE 31): on one chip the fold of a filter into a sum,
    # the moments, the extremes and a grouped fold over (rows, c <= 8) is
    # the Mosaic kernel thin_fold; small integers, so every sum is exact
    import jax
    from bolt_tpu import engine, ops
    rows = 3 * 65536 + 4321
    x = np.random.RandomState(31).randint(0, 40, size=(rows, 7)).astype(
        np.float32)
    b = bolt.array(x, cmesh)
    keep = x[:, 0] > 10
    c0 = engine.counters()["fold_kernel_programs"]
    kept = b.filter(lambda r: r[0] > 10)
    _close(kept.map(lambda r: r[2] * r[3]).sum(),
           (x[keep, 2] * x[keep, 3]).sum(dtype=np.float64), rtol=1e-7)
    total, spread, top = bolt.compute(kept.sum(), kept.var(), kept.max())
    _close(total, x[keep].sum(axis=0, dtype=np.float64), rtol=1e-7)
    _close(spread, x[keep].var(axis=0, dtype=np.float64), rtol=1e-3)
    _close(top, x[keep].max(axis=0))
    sums, counts = ops.segment_reduce(
        kept, labels=lambda r: (r[6] % 5).astype(np.int32) - 1,
        num_segments=3, value=lambda r: (r[1], r[1] * r[2] + 1),
        return_counts=True)
    gid = (x[:, 6] % 5).astype(np.int64) - 1
    for g in range(3):
        hit = keep & (gid == g)
        assert int(counts.toarray()[g]) == hit.sum()
        assert float(sums[0].toarray()[g]) == x[hit, 1].sum(dtype=np.float64)
        assert float(sums[1].toarray()[g]) == (
            x[hit, 1] * x[hit, 2] + 1).sum(dtype=np.float64)
    placed = engine.counters()["fold_kernel_programs"] - c0
    # the sum, the fused group (max is a program of its own: it syncs the
    # survivors' count) and the grouped fold, each lowered once
    assert placed == (4 if len(jax.devices()) == 1 else 0), placed


@pytest.mark.parametrize("d", [64, 16], ids=["one-group", "four-groups"])
def test_centred_pca_and_cov_take_the_mean_from_the_gram_pass(cmesh, d):
    # ops/linalg.py (ISSUE 33): on one chip a centred pca or cov over
    # stored float32 of a width that packs runs the summing form of the
    # packed_gram kernel, whose row sums are the mean's numerator; whole
    # steps of 64 // d row groups and a tail, against float64
    import jax
    from bolt_tpu import engine, ops
    planes, rows, k = 4, 2 * (64 // d) * 8192 + 1234, 3
    rs = np.random.RandomState(33 + d)
    x = (rs.randint(-30, 31, size=(planes, rows, d)) * (d - np.arange(d))
         + 7 * np.arange(d) - 100).astype(np.float32)
    b = bolt.array(x, cmesh)
    flat = x.reshape(-1, d).astype(np.float64)
    mean = flat.mean(axis=0)
    gram = (flat - mean).T @ (flat - mean)
    w, v = np.linalg.eigh(gram)
    c0 = engine.counters()["gram_sums_programs"]
    scores, comps, svals, mu = ops.pca(b, k=k, center=True, axis=(0, 1),
                                       return_mean=True)
    cov, cov_mu = ops.cov(b, axis=(0, 1), return_mean=True)
    placed = engine.counters()["gram_sums_programs"] - c0
    assert placed == (2 if len(jax.devices()) == 1 else 0), placed
    for got in (mu, cov_mu):
        assert got.dtype == np.float32
        assert np.abs(got - mean).max() < 2e-4, np.abs(got - mean).max()
    assert np.allclose(svals, np.sqrt(w[::-1][:k]), rtol=1e-4)
    top = v[:, ::-1][:, :k]
    assert np.linalg.norm(comps @ comps.T - top @ top.T) < 1e-2
    assert np.allclose(cov, gram / (len(flat) - 1), rtol=1e-4,
                       atol=1e-5 * np.diag(gram).max() / len(flat))
    _close(scores[:, :512], (flat.reshape(x.shape)[:, :512] - mean) @ comps,
           rtol=1e-3, atol=5e-2)


def test_swap_and_chunked_halo_map(cmesh):
    x = _x((8, 6, 32), seed=4)
    b = bolt.array(x, cmesh)
    s = b.swap((0,), (0,))          # keys (8,) <-> first value axis (6,)
    assert s.shape == (6, 8, 32)
    _close(s, np.transpose(x, (1, 0, 2)))
    out = b.chunk(size=(3, 16), axis=(0, 1), padding=(1, 0)).map(
        lambda blk: blk * 2.0).unchunk()
    _close(out, x * 2.0)


def test_sepfilter_both_axes(cmesh):
    # the separable gaussian on a major (sublane) axis and on the minor
    # (lane) axis — the two Mosaic code paths (ops/kernels.py crossover)
    from bolt_tpu.ops import gaussian
    x = _x((4, 64, 256), seed=5)
    b = bolt.array(x, cmesh)

    def oracle(arr, sigma, axis):
        # the framework's kernel definition: normalised taps at radius
        # int(4*sigma + 0.5), zero-padded full-axis correlation (the
        # convention the CPU-mesh suite pins in test_ops_overlap)
        radius = int(4.0 * sigma + 0.5)
        g = np.exp(-0.5 * (np.arange(-radius, radius + 1) / sigma) ** 2)
        g = (g / g.sum()).astype(np.float32)
        return np.apply_along_axis(
            lambda v: np.convolve(v, g[::-1], "same"), axis, arr)

    # ops.gaussian's axis is relative to the VALUE group: value axis 0
    # is global axis 1 (major/sublane), value axis 1 is global axis 2
    # (minor/lane)
    g1 = gaussian(b, sigma=1.5, axis=(0,), size="64")     # major axis
    _close(g1, oracle(x, 1.5, 1), rtol=1e-4, atol=1e-4)
    g2 = gaussian(b, sigma=1.5, axis=(1,), size="64")     # minor axis
    _close(g2, oracle(x, 1.5, 2), rtol=1e-4, atol=1e-4)
    # sigma above the 9-tap minor crossover: the wide-kernel path
    g3 = gaussian(b, sigma=4.0, axis=(1,), size="64")
    _close(g3, oracle(x, 4.0, 2), rtol=1e-4, atol=1e-4)


def test_npdispatch_sample(cmesh):
    x = _x((16, 8, 16), seed=6)
    b = bolt.array(x, cmesh)
    _close(np.einsum("ijk,kl->ijl", b, np.ones((16, 4), np.float32)),
           np.einsum("ijk,kl->ijl", x, np.ones((16, 4), np.float32)),
           rtol=1e-4, atol=1e-4)
    _close(np.pad(b, ((0, 0), (2, 1), (0, 0)), mode="reflect"),
           np.pad(x, ((0, 0), (2, 1), (0, 0)), mode="reflect"))
    _close(np.stack([b, b], axis=1), np.stack([x, x], axis=1))
    _close(np.sort(b, axis=2), np.sort(x, axis=2))
    _close(np.quantile(b, [0.25, 0.75]),
           np.quantile(x, [0.25, 0.75]).astype(np.float32), rtol=1e-5)
    m = _x((64, 6), seed=7)
    _close(np.cov(bolt.array(m, cmesh)), np.cov(m).astype(np.float32),
           rtol=1e-3, atol=1e-3)


def test_indexing_and_set(cmesh):
    x = _x((16, 8, 16), seed=8)
    b = bolt.array(x, cmesh)
    _close(b[2:9, [0, 5]], x[2:9][:, [0, 5]])
    _close(b[[3, 1], :, [2, 4]],
           x[np.ix_([3, 1], range(8), [2, 4])])     # orthogonal advanced
    _close(b.set(0, -1.0).toarray()[0], np.full((8, 16), -1.0, np.float32))


def test_linalg_ops(cmesh):
    from bolt_tpu.ops import pca, topk, segment_reduce
    x = _x((4096, 8), seed=9)
    b = bolt.array(x, cmesh)
    _, comps, svals = pca(b, k=3, center=True)
    xc = (x - x.mean(0)).astype(np.float64)
    ref = np.linalg.svd(xc, compute_uv=False)[:3]
    assert np.allclose(svals, ref, rtol=1e-3)
    v, i = topk(bolt.array(_x((256,), seed=10), cmesh), 5)
    ref_i = np.argsort(-_x((256,), seed=10))[:5]
    assert np.array_equal(np.asarray(i), ref_i)
    labels = np.arange(64) % 4
    sr = segment_reduce(bolt.array(_x((64, 16), seed=11), cmesh),
                        labels, num_segments=4, op="sum")
    expect = np.zeros((4, 16), np.float32)
    xx = _x((64, 16), seed=11)
    for lab, row in zip(labels, xx):
        expect[lab] += row
    _close(sr, expect, rtol=1e-4, atol=1e-4)


def test_fft_on_chip(cmesh):
    # device-side complex compute, fetched as the complex64 buffer
    x = _x((8, 4, 128), seed=13)
    b = bolt.array(x, cmesh)
    g = np.asarray(np.fft.rfft(b).toarray())
    assert g.dtype == np.complex64, g.dtype
    assert np.allclose(g, np.fft.rfft(x), rtol=1e-3, atol=1e-3)
    # and a complex host array goes up whole
    z = (x + 1j * x[::-1]).astype(np.complex64)
    assert np.array_equal(np.asarray(bolt.array(z, cmesh).toarray()), z)
    back = np.fft.irfft(np.fft.rfft(b), n=128)
    _close(back, x, rtol=1e-4, atol=1e-4)


def test_dtype_policy_x64_off(cmesh):
    # production numerics: float64 requests canonicalise to f32 silently
    b = bolt.array(np.random.RandomState(12).randn(8, 4), cmesh)
    assert b.dtype == np.float32
    assert b.sum().dtype == np.float32
    assert b.astype(np.float64).dtype == np.float32
