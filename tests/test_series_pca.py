"""BASELINE config 5 through the normal path (ISSUE 26): ``pca``/``cov`` of
a ``(K, N, d)`` array keyed on axis 0 with both leading axes as samples,
the re-split that gets it there as a view, and the Gram matrix accumulated
in runs.  Oracle: NumPy in float64 on seeded weights."""

import numpy as np
import pytest

import jax

import bolt_tpu as bolt
from bolt_tpu import engine
from bolt_tpu.ops import cov, linalg, pca, svdvals

K, N, D = 8, 96, 6


def _series(seed):
    """A (K, N, D) array with a planted, well-separated spectrum."""
    rs = np.random.RandomState(seed)
    basis = np.linalg.qr(rs.randn(D, D))[0]
    strengths = 2.0 ** -np.arange(D)
    return (rs.randn(K, N, D) * strengths) @ basis.T + rs.randn(D)


def _one_device_mesh():
    return jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("k",))


@pytest.fixture(params=["one_device", "mesh"])
def where(request, mesh):
    return _one_device_mesh() if request.param == "one_device" else mesh


def _ref(x, k, center):
    x = x.reshape(-1, x.shape[-1]).astype(np.float64)
    mu = x.mean(axis=0) if center else np.zeros(x.shape[1])
    xc = x - mu
    w, v = np.linalg.eigh(xc.T @ xc)
    return mu, v[:, ::-1][:, :k], np.sqrt(w[::-1][:k]), xc


@pytest.mark.parametrize("center", [True, False], ids=["centred", "raw"])
@pytest.mark.parametrize("k", [2, D], ids=["k2", "kall"])
def test_pca_over_both_sample_axes_of_a_plane_keyed_array(where, center, k):
    x = _series(26)
    b = bolt.array(x, where, axis=(0,))
    scores, comps, sv, mean = pca(b, k=k, center=center, axis=(0, 1),
                                  return_mean=True)
    mu, vec, s_ref, xc = _ref(x, k, center)
    assert scores.shape == (K, N, k) and scores.split == 2
    assert comps.shape == (D, k) and sv.shape == (k,) and mean.shape == (D,)
    assert np.allclose(mean, mu, atol=1e-10)
    assert np.allclose(sv, s_ref, rtol=1e-8)
    # sign-free: the projector onto the span, and the rows rebuilt from it
    assert np.allclose(comps @ comps.T, vec @ vec.T, atol=1e-8)
    got = scores.toarray().reshape(-1, k) @ comps.T
    assert np.allclose(got, xc @ vec @ vec.T, atol=1e-8)
    # and what mode='local' returns for the same call
    lscores, lcomps, lsv, lmean = pca(bolt.array(x), k=k, center=center,
                                      axis=(0, 1), return_mean=True)
    assert np.allclose(lsv, sv, rtol=1e-8)
    assert np.allclose(lcomps @ lcomps.T, comps @ comps.T, atol=1e-8)
    assert np.allclose(lscores.toarray() @ lcomps.T, got.reshape(K, N, D),
                       atol=1e-8)


@pytest.mark.parametrize("center", [True, False], ids=["centred", "raw"])
def test_cov_over_both_sample_axes_of_a_plane_keyed_array(where, center):
    x = _series(27)
    b = bolt.array(x, where, axis=(0,))
    c, mean = cov(b, axis=(0, 1), center=center, return_mean=True)
    flat = x.reshape(-1, D)
    mu = flat.mean(axis=0) if center else np.zeros(D)
    want = (flat - mu).T @ (flat - mu) / (flat.shape[0] - 1)
    assert np.allclose(c, want, atol=1e-10)
    assert np.allclose(mean, mu, atol=1e-10)
    assert np.allclose(cov(bolt.array(x), axis=(0, 1), center=center), c,
                       atol=1e-10)


def test_a_deferred_map_fuses_into_the_plane_keyed_pca(mesh):
    x = _series(28)
    b = bolt.array(x, mesh, axis=(0,)).map(lambda v: v * 2.0)
    _, _, sv = pca(b, k=3, center=True, axis=(0, 1))
    assert np.allclose(sv, _ref(2.0 * x, 3, True)[2], rtol=1e-8)


# -- the Gram matrix in runs ---------------------------------------------

@pytest.mark.parametrize("n", [64, 100, 37], ids=["divides", "tail", "short"])
def test_gram_in_runs_equals_one_contraction(monkeypatch, n):
    monkeypatch.setattr(linalg, "_GRAM_RUN", 16)
    rs = np.random.RandomState(n)
    x = rs.randn(3, n, 5)
    want = np.einsum("bni,bnj->ij", x, x)
    got = np.asarray(linalg._sample_gram(jax.numpy.asarray(x), "highest"))
    assert np.allclose(got, want, rtol=1e-12)
    # a block (svdvals' (..., n, d) form) is one contraction, batched and
    # under vmap, as a chunked map hands it over: never cut a second time
    per = np.einsum("bni,bnj->bij", x, x)
    got = np.asarray(linalg._gram(jax.numpy.asarray(x), jax.numpy))
    assert np.allclose(got, per, rtol=1e-12)
    got = np.asarray(jax.vmap(lambda blk: linalg._gram(blk, jax.numpy))(
        jax.numpy.asarray(x)))
    assert np.allclose(got, per, rtol=1e-12)


def test_svdvals_of_a_planted_spectrum_over_six_decades():
    # eight strong components over a noise floor, as a PCA's data have:
    # the batched Jacobi stopped four sweeps early on such a matrix and
    # read 1e-4 of the largest eigenvalue (ISSUE 26, on the chip and off)
    rs = np.random.RandomState(5)
    basis = np.linalg.qr(rs.randn(64, 64))[0]
    strengths = np.concatenate([0.85 ** np.arange(8), np.full(56, 1e-3)])
    x = (rs.randn(40, 4096, 64) * strengths) @ basis.T
    assert linalg._use_jacobi(jax.numpy.zeros((40, 64, 64)))
    got = np.asarray(svdvals(jax.numpy.asarray(x, jax.numpy.float32)),
                     np.float64) ** 2
    want = np.linalg.svd(x, compute_uv=False) ** 2
    assert np.max(np.abs(got - want) / want[:, :1]) < 1e-5


# -- the re-split as a view ------------------------------------------------

def test_identity_resplit_is_a_view(where):
    x = _series(29)
    b = bolt.array(x, where, axis=(0,))
    b.toarray()                                    # nothing pending
    c0 = engine.counters()
    v = b._align([0, 1])                           # value axis 0 to the keys
    c1 = engine.counters()
    assert v.split == 2 and v.shape == b.shape
    assert c1["dispatches"] == c0["dispatches"]    # no program ran
    assert c1["resplit_views"] == c0["resplit_views"] + 1
    assert v._data is b._data                      # one buffer, two arrays
    assert np.array_equal(v.toarray(), x)
    # back again, and through the public swap, the same
    w = v.swap((1,), ())
    assert w.split == 1 and w._data is b._data
    assert engine.counters()["resplit_views"] == c0["resplit_views"] + 2


def test_neither_side_of_a_view_is_donated_while_the_other_lives(where):
    x = _series(30)
    with engine.donation(0):                       # every size may donate
        b = bolt.array(x, where, axis=(0,))
        v = b._align([0, 1])
        n0 = engine.counters()["donations"]
        assert np.allclose(
            v.map(lambda r: r + 1.0, axis=(0, 1)).sum(axis=(0, 1)).toarray(),
            (x + 1.0).sum(axis=(0, 1)))
        assert np.allclose(b.map(lambda r: r + 1.0).sum().toarray(),
                           (x + 1.0).sum(axis=0))
        assert engine.counters()["donations"] == n0
        assert np.array_equal(b.toarray(), x)      # both still readable
        assert np.array_equal(v.toarray(), x)


def test_a_real_permutation_still_launches_the_swap_program(mesh):
    x = _series(31)
    b = bolt.array(x, mesh, axis=(0,))
    b.toarray()
    c0 = engine.counters()
    s = b.swap((0,), (0,))                         # planes <-> voxels
    out = s.toarray()
    c1 = engine.counters()
    assert np.array_equal(out, x.transpose(1, 0, 2))
    assert c1["dispatches"] > c0["dispatches"]
    assert c1["resplit_views"] == c0["resplit_views"]


def test_a_resplit_that_changes_the_sharding_is_a_program(mesh):
    # 6 planes do not divide the 8-device mesh, 96 voxels do: split 1 is
    # replicated, split 2 shards the voxels, so the data has to move
    x = _series(32)[:6]
    b = bolt.array(x, mesh, axis=(0,))
    b.toarray()
    c0 = engine.counters()
    v = b._align([0, 1])
    assert np.array_equal(v.toarray(), x)
    c1 = engine.counters()
    assert c1["resplit_views"] == c0["resplit_views"]
    assert c1["dispatches"] > c0["dispatches"]
    assert v._data is not b._data


def test_pca_spans_and_the_view_counter(mesh):
    from bolt_tpu import obs
    x = _series(33)
    b = bolt.array(x, mesh, axis=(0,))
    c0 = engine.counters()["resplit_views"]
    obs.enable()
    try:
        obs.clear()
        pca(b, k=2, center=True, axis=(0, 1))
        names = [sp.name for sp in obs.spans()]
    finally:
        obs.disable()
    assert engine.counters()["resplit_views"] == c0 + 1
    for name in ("linalg.pca", "linalg.pca.launch", "linalg.pca.fetch"):
        assert names.count(name) == 1, names
