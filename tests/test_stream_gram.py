"""``ops.pca`` and ``ops.cov`` over a STREAMED source (ISSUE 55): the Gram
matrix and the column sums are a terminal of ``stream.execute`` whose
per-slab partial is ``ops.linalg._sample_gram`` of the slab, merged by
``add``; ``pca`` is two passes (the Gram terminal, then the projection
collected slab by slab into the resident scores).  Held to a plain float64
reference written here from the published description (mean over samples,
covariance, ``numpy.linalg.eigh``, descending order, scores ``(x - mu) V``),
which shares nothing with ``_pca_local``, and to the resident call on the
same data, with the source never materialised (a loader that counts);
the slab partials add up to the whole matrix's Gram and sums; pass 2 is
bit-identical to ``collect`` of the explicit map; and what still
materialises says why.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bolt_tpu as bolt
from bolt_tpu import _chaos as chaos
from bolt_tpu import analysis, checkpoint, engine, obs, stream
from bolt_tpu.ops import linalg
from bolt_tpu.utils import with_operands

WIDTHS = (8, 16, 32, 64, 48)        # 48 does not pack into the kernel
K = 3


@pytest.fixture(autouse=True)
def _no_armed_chaos():
    chaos.clear()
    yield
    chaos.clear()


@pytest.fixture
def traced():
    obs.enable()
    obs.clear()
    yield
    obs.disable()
    obs.clear()


def _planted(shape, seed=11):
    """float32 samples x ``d`` features with ``K + 1`` planted directions
    whose strengths fall by a third each over a noise floor 30 times under
    the weakest, on an offset of a few sigma: the leading ``K`` eigenvectors
    are well apart, so a float32 Gram route can be held to them."""
    rng = np.random.default_rng(seed)
    d = shape[-1]
    n = int(np.prod(shape[:-1]))
    basis, _ = np.linalg.qr(rng.standard_normal((d, K + 1)))
    strength = 8.0 * (2.0 / 3.0) ** np.arange(K + 1)
    x = (rng.standard_normal((n, K + 1)) * strength) @ basis.T \
        + 0.05 * rng.standard_normal((n, d)) + rng.uniform(-3, 3, d)
    return x.astype(np.float32).reshape(shape)


def _counted(x, mesh, chunks, **kw):
    """A ``fromcallback`` source of ``x`` and the tally of how often each
    record (index on axis 0) was asked for."""
    asked = np.zeros(x.shape[0], np.int64)

    def load(index):
        lo, hi, _ = index[0].indices(x.shape[0])
        asked[lo:hi] += 1
        return x[index]
    return bolt.fromcallback(load, x.shape, mesh, dtype=x.dtype,
                             chunks=chunks, **kw), asked


def _reference(x, m, k, center):
    """The plain float64 reference over the leading ``m`` sample axes:
    ``(mu, vec (d, k), sv (k,), scores)``."""
    flat = np.asarray(x, np.float64).reshape(
        int(np.prod(x.shape[:m])), -1)
    mu = flat.mean(axis=0) if center else np.zeros(flat.shape[1])
    xc = flat - mu
    w, v = np.linalg.eigh(xc.T @ xc)
    order = np.argsort(w)[::-1][:k]
    vec = v[:, order]
    return mu, vec, np.sqrt(np.maximum(w[order], 0)), xc @ vec


def _layout(name, d):
    """``(shape, sample axes, chunks, slabs)``: plane-keyed ``(K, N, d)``
    over both sample axes in slabs of 2, 2 and 1 planes, or row-keyed
    ``(N, d)`` in slabs of 300, 300, 300 and 100 rows: a short last slab
    either way."""
    if name == "planes":
        return (5, 200, d), (0, 1), 2, 3
    return (1000, d), (0,), 300, 4


def _held(got, want, x, m, tol):
    """``got`` and ``want`` are ``(mu, vec, sv, scores)``; the comparison
    ``steps/pca.py`` makes, which depends on neither sign nor order of the
    components: singular values relative to the largest, the projectors
    onto the span, the mean, and the rows as the components rebuild them.
    ``tol`` is relative to the data's scale (its largest singular value a
    sample, ~8)."""
    (mu, vec, sv, scores), (rmu, rvec, rsv, rscores) = got, want
    k = rvec.shape[1]
    assert vec.shape == rvec.shape and sv.shape == (k,)
    scores = np.asarray(scores, np.float64).reshape(-1, k)
    rscores = np.asarray(rscores, np.float64).reshape(-1, k)
    assert scores.shape == rscores.shape
    assert np.max(np.abs(sv ** 2 - rsv ** 2)) / rsv[0] ** 2 < tol
    assert np.linalg.norm(vec @ vec.T - rvec @ rvec.T) < 30 * tol
    assert np.max(np.abs(mu - rmu)) < 8 * tol
    assert np.max(np.abs(scores @ vec.T - rscores @ rvec.T)) < 100 * tol


# float32 data through a float32 Gram matrix over up to 1,000 samples of
# magnitude ~10: products round at 2**-24 relative and the centring fold
# ``G - n mu mu^T`` cancels a mean of ~2 sigma, so eigenvalues stand within
# ~1e-6 of the largest; 2e-5 leaves room for the CPU's dot_general order.
# Streamed against resident is the same arithmetic in another order of
# addition (slab partials added by a tree): the same tolerance holds it
TOL = 2e-5


@pytest.mark.parametrize("center", [True, False],
                         ids=["centred", "uncentred"])
@pytest.mark.parametrize("d", WIDTHS)
@pytest.mark.parametrize("layout", ["planes", "rows"])
def test_streamed_pca_equals_the_reference_and_the_resident_call(
        mesh, traced, layout, d, center):
    shape, axes, chunks, nslabs = _layout(layout, d)
    x = _planted(shape)
    src, asked = _counted(x, mesh, chunks)
    c0 = engine.counters()
    scores, vec, sv, mu = bolt.ops.pca(src, k=K, center=center, axis=axes,
                                       return_mean=True)
    c1 = engine.counters()
    spans = obs.totals()
    # the source was never materialised: every record was asked for
    # exactly twice, slab by slab, and nothing was uploaded whole
    assert asked.tolist() == [2] * shape[0]
    assert src.streaming and "stream.materialize" not in spans
    for name in ("linalg.pca", "linalg.pca.gram_pass",
                 "linalg.pca.decompose", "linalg.pca.project_pass",
                 "linalg.pca.fetch", "stream.run", "stream.collect"):
        assert spans[name]["count"] == 1, name
    assert "linalg.pca.launch" not in spans
    for name, want in (("stream_gram_slabs", nslabs),
                       ("stream_project_slabs", nslabs),
                       ("stream_collect_slabs", nslabs),
                       ("stream_chunks", 2 * nslabs),
                       ("stream_gram_kernel_slabs", 0)):   # the CPU
        assert c1[name] - c0[name] == want, name
    # what comes back is what the resident call gives back
    assert scores.shape == shape[:-1] + (K,) and scores.split == len(axes)
    assert scores.mode == "tpu" and not scores.streaming
    assert all(isinstance(a, np.ndarray) for a in (vec, sv, mu))
    assert vec.dtype == sv.dtype == mu.dtype == np.float32
    if not center:
        assert not mu.any()
    got = (mu, vec, sv, scores.toarray())
    _held(got, _reference(x, len(axes), K, center), x, len(axes), TOL)
    r = bolt.ops.pca(bolt.array(x, mesh), k=K, center=center, axis=axes,
                     return_mean=True)
    assert r[0].shape == scores.shape and r[0].split == scores.split
    _held(got, (r[3], r[1], r[2], r[0].toarray()), x, len(axes), TOL)


@pytest.mark.parametrize("center", [True, False],
                         ids=["centred", "uncentred"])
@pytest.mark.parametrize("d", WIDTHS)
@pytest.mark.parametrize("layout", ["planes", "rows"])
def test_streamed_cov_equals_the_reference_and_the_resident_call(
        mesh, traced, layout, d, center):
    shape, axes, chunks, nslabs = _layout(layout, d)
    x = _planted(shape, seed=5)
    src, asked = _counted(x, mesh, chunks)
    c0 = engine.counters()
    c, mu = bolt.ops.cov(src, axis=axes, center=center, return_mean=True)
    c1 = engine.counters()
    # one pass: every record asked for exactly once
    assert asked.tolist() == [1] * shape[0]
    assert src.streaming and "stream.materialize" not in obs.totals()
    assert c1["stream_gram_slabs"] - c0["stream_gram_slabs"] == nslabs
    assert c1["stream_chunks"] - c0["stream_chunks"] == nslabs
    assert c1["stream_project_slabs"] == c0["stream_project_slabs"]
    flat = x.astype(np.float64).reshape(-1, d)
    n = flat.shape[0]
    rmu = flat.mean(axis=0) if center else np.zeros(d)
    want = (flat - rmu).T @ (flat - rmu) / (n - 1)
    # entries of a covariance of magnitude up to ~64: the same float32
    # Gram route as above, relative to the largest entry
    scale = np.abs(want).max()
    assert c.shape == (d, d) and np.abs(c - want).max() < TOL * scale
    assert np.abs(mu - rmu).max() < 8 * TOL
    rc, rm = bolt.ops.cov(bolt.array(x, mesh), axis=axes, center=center,
                          return_mean=True)
    assert np.abs(c - rc).max() < TOL * scale
    assert np.abs(mu - rm).max() < 8 * TOL
    if center and layout == "rows":
        # corrcoef rides cov: one more pass of a new source
        again, asked = _counted(x, mesh, chunks)
        r = bolt.ops.corrcoef(again)
        assert asked.tolist() == [1] * shape[0]
        assert np.abs(r - np.corrcoef(flat, rowvar=False)).max() < 1e-4


def _integers(shape, seed=3):
    """Small integers as float32: every product and every sum of them over
    these sizes is below 2**24, so a float32 Gram matrix is EXACT whatever
    order it is added in."""
    rng = np.random.default_rng(seed)
    return rng.integers(-20, 21, size=shape).astype(np.float32)


@pytest.mark.parametrize("sums", [True, False], ids=["sums", "gram-alone"])
@pytest.mark.parametrize("d", WIDTHS)
@pytest.mark.parametrize("layout", ["planes", "rows"])
def test_the_slab_partials_add_up_to_the_whole(mesh, layout, d, sums):
    """Each row once: the partials of every slab, the short last one
    included, ADD UP to the whole matrix's Gram and column sums, exactly;
    and that is what the executor hands back."""
    shape, axes, chunks, nslabs = _layout(layout, d)
    x = _integers(shape)
    m = len(axes)
    gram = stream._Gram((m, "highest", False, sums))
    flat = x.astype(np.float64).reshape(-1, d)
    whole = [flat.T @ flat] + [flat.sum(axis=0)] * sums
    parts = [gram.partial(jnp.asarray(x[lo:lo + chunks]), (), None, (), 1,
                          None, None)
             for lo in range(0, shape[0], chunks)]
    assert len(parts) == nslabs and all(len(p) == 1 + sums for p in parts)
    for comp, want in enumerate(whole):
        added = sum(np.asarray(p[comp], np.float64) for p in parts)
        assert np.array_equal(added, want)
    src, asked = _counted(x, mesh, chunks)
    out = stream.maybe_gram(src, axes, "highest", sums=sums)
    assert asked.tolist() == [1] * shape[0] and len(out) == 1 + sums
    for got, want in zip(out, whole):
        assert isinstance(got, jax.Array)
        assert np.array_equal(np.asarray(got, np.float64), want)


def _shift(v):
    return v * 2 - 1


@pytest.mark.parametrize("layout", ["planes", "rows"])
def test_a_deferred_map_in_front_is_traced_into_both_passes(mesh, traced,
                                                            layout):
    shape, axes, chunks, nslabs = _layout(layout, 16)
    x = _planted(shape, seed=2)
    src, asked = _counted(x, mesh, chunks)
    scores, vec, sv, mu = bolt.ops.pca(src.map(_shift), k=K, center=True,
                                       axis=axes, return_mean=True)
    assert asked.tolist() == [2] * shape[0]
    assert "stream.materialize" not in obs.totals()
    _held((mu, vec, sv, scores.toarray()),
          _reference(_shift(x.astype(np.float64)), len(axes), K, True), x,
          len(axes), 2 * TOL)               # the map doubles the scale


def test_fewer_rows_than_one_kernel_block_and_k_of_every_width(mesh):
    """70 rows of 64 features in slabs of 32, 32 and 6: far under the 8,192
    rows of one kernel block (its tail's path), ``k`` left at all 64."""
    x = _planted((70, 64), seed=9)
    src, asked = _counted(x, mesh, 32)
    scores, vec, sv = bolt.ops.pca(src, center=True)
    assert asked.tolist() == [2] * 70
    assert scores.shape == (70, 64) and vec.shape == (64, 64)
    mu, rvec, rsv, rscores = _reference(x, 1, 64, True)
    assert np.max(np.abs(sv ** 2 - rsv ** 2)) / rsv[0] ** 2 < TOL
    # every component kept: the rows are rebuilt whole
    assert np.abs(scores.toarray() @ vec.T
                  - (x.astype(np.float64) - mu)).max() < 100 * TOL


def test_fetch_false_keeps_the_small_results_on_the_device(mesh, traced):
    x = _planted((1000, 16))
    src, _ = _counted(x, mesh, 300)
    scores, vec, sv, mu = bolt.ops.pca(src, k=K, center=True,
                                       return_mean=True, fetch=False)
    assert all(isinstance(a, jax.Array) for a in (vec, sv, mu))
    assert "linalg.pca.fetch" not in obs.totals()
    _held((np.asarray(mu), np.asarray(vec), np.asarray(sv),
           scores.toarray()), _reference(x, 1, K, True), x, 1, TOL)


@pytest.mark.parametrize("center", [True, False],
                         ids=["centred", "uncentred"])
@pytest.mark.parametrize("layout", ["planes", "rows"])
def test_pass_two_is_collect_of_the_explicit_map_bit_for_bit(mesh, layout,
                                                             center):
    shape, axes, chunks, _ = _layout(layout, 32)
    x = _planted(shape, seed=4)
    scores, vec, sv, mu = bolt.ops.pca(_counted(x, mesh, chunks)[0], k=K,
                                       center=center, axis=axes,
                                       return_mean=True, fetch=False)
    # the explicit spelling: the caller's own projection as a map with
    # side operands, collected slab by slab
    off = jnp.matmul(mu, vec, precision="highest")
    lead = len(axes) - 1
    ops = (vec, off) if center else (vec,)
    explicit = _counted(x, mesh, chunks)[0].map(with_operands(
        linalg._projection(lead, 32, "highest", center), *ops))
    assert explicit.streaming
    want = stream.collect(explicit._stream)
    assert want.shape == scores.shape
    assert np.array_equal(np.asarray(want.toarray()),
                          np.asarray(scores.toarray()))


def test_fromiter_streams_twice_and_a_generator_says_why_not(mesh, traced):
    x = _planted((1000, 16), seed=6)
    blocks = [x[lo:lo + 300] for lo in range(0, 1000, 300)]
    c0 = engine.counters()
    out = bolt.ops.pca(bolt.fromiter(blocks, x.shape, mesh, dtype=x.dtype),
                       k=K, center=True, return_mean=True)
    assert engine.counters()["stream_gram_slabs"] - c0[
        "stream_gram_slabs"] == 4
    assert "stream.materialize" not in obs.totals()
    want = _reference(x, 1, K, True)
    _held((out[3], out[1], out[2], out[0].toarray()), want, x, 1, TOL)
    # a one-shot iterator cannot be read twice: pca materialises it once,
    # as it always did; cov's one pass streams it
    once = bolt.fromiter(iter(blocks), x.shape, mesh, dtype=x.dtype)
    assert "one-shot iterator" in stream.gram_refusal(once._stream, (0,),
                                                      passes=2)
    assert stream.gram_refusal(once._stream, (0,)) is None
    c0 = engine.counters()
    out = bolt.ops.pca(once, k=K, center=True, return_mean=True)
    assert engine.counters()["stream_gram_slabs"] == c0["stream_gram_slabs"]
    _held((out[3], out[1], out[2], out[0].toarray()), want, x, 1, TOL)
    c = bolt.ops.cov(bolt.fromiter(iter(blocks), x.shape, mesh,
                                   dtype=x.dtype))
    assert np.abs(c - np.cov(x.astype(np.float64), rowvar=False)).max() \
        < TOL * 64


def _big(v):
    return v[0] > 0


def test_what_the_executor_does_not_take_says_why_and_materialises(
        mesh, traced, monkeypatch):
    x = _planted((1000, 16), seed=8)
    want = np.cov(x.astype(np.float64)[x[:, 0] > 0], rowvar=False)

    # a filter in front: the row count is dynamic
    src, asked = _counted(x, mesh, 300)
    filtered = src.filter(_big)
    assert "dynamic" in stream.gram_refusal(filtered._stream, (0,))
    assert stream.maybe_gram(filtered, (0,), "highest") is NotImplemented
    c0 = engine.counters()
    c = bolt.ops.cov(filtered)
    assert engine.counters()["stream_gram_slabs"] == c0["stream_gram_slabs"]
    assert np.abs(c - want).max() < TOL * 64

    # sample axes that are not the leading ones, or that leave a key axis
    # among the features
    planes, _ = _counted(_planted((5, 200, 16)), mesh, 2)
    assert "not the leading axes" in stream.gram_refusal(planes._stream,
                                                         (1,))
    keyed = bolt.fromcallback(lambda i: _planted((6, 8, 4))[i], (6, 8, 4),
                              mesh, axis=(0, 1), dtype=np.float32)
    assert "not the leading axes" in stream.gram_refusal(keyed._stream,
                                                         (0,))
    # a chunked stage, a swap
    chunked = _counted(x, mesh, 300)[0].chunk(size=(8,)).map(
        lambda b: b * 2).unchunk()
    assert chunked.streaming and "a chunk stage" in stream.gram_refusal(
        chunked._stream, (0,))
    swapped = planes.swap((0,), (0,))
    assert swapped.streaming and "a swap stage" in stream.gram_refusal(
        swapped._stream, (0,))
    # a lossy ingest codec: the materialised path uploads the base unencoded
    lossy = _counted(x, mesh, 300, codec="int8")[0]
    assert "lossy" in stream.gram_refusal(lossy._stream, (0,))
    # a mesh of several processes: the slab program under shard_map has no
    # Gram partial
    plain, _ = _counted(x, mesh, 300)
    monkeypatch.setattr(stream._multihost, "mesh_process_count",
                        lambda mesh: 2)
    assert "several processes" in stream.gram_refusal(plain._stream, (0,))
    assert stream.maybe_gram(plain, (0,), "highest") is NotImplemented
    monkeypatch.undo()

    # scores past the resident budget have no sink: pca materialises the
    # source whole, as before (or refuses in words, BLT020)
    src, asked = _counted(x, mesh, 300)
    with stream.spill(budget=1000):
        c0 = engine.counters()
        out = bolt.ops.pca(src, k=K, center=True, return_mean=True)
        c1 = engine.counters()
    assert c1["stream_gram_slabs"] == c0["stream_gram_slabs"]
    assert asked.max() == 1                  # no pass was spent first
    _held((out[3], out[1], out[2], out[0].toarray()),
          _reference(x, 1, K, True), x, 1, TOL)


def test_sizes_are_refused_as_the_resident_call_refuses_them(mesh):
    wide, asked = _counted(_planted((40, 64)), mesh, 16)
    with pytest.raises(ValueError, match="#samples >= #features"):
        bolt.ops.pca(wide)
    with pytest.raises(ValueError, match="k=9 out of range"):
        bolt.ops.pca(_counted(_planted((100, 8)), mesh, 30)[0], k=9)
    with pytest.raises(ValueError, match="more than ddof=1000"):
        bolt.ops.cov(_counted(_planted((1000, 8)), mesh, 300)[0], ddof=1000)
    assert not asked.any()                   # before any slab moved


def test_a_killed_gram_pass_resumes_from_its_checkpoint(mesh, tmp_path):
    """Under ``stream.resumable`` the Gram partial is checkpointed as the
    ``multi`` tuple is; its fingerprint holds the terminal's sample axes,
    precision and sums, so another fold over the directory is never
    adopted."""
    x = _integers((1200, 16))
    clean = bolt.ops.cov(_counted(x, mesh, 150)[0])
    ck = str(tmp_path / "ck")
    chaos.inject("stream.upload", nth=5)         # die at slab 5 of 8
    with pytest.raises(chaos.ChaosError):
        with stream.uploaders(1), stream.resumable(ck):
            bolt.ops.cov(_counted(x, mesh, 150)[0])
    chaos.clear()
    assert checkpoint.stream_pending(ck)
    src, asked = _counted(x, mesh, 150)
    c0 = engine.counters()
    with stream.resumable(ck):
        got = bolt.ops.cov(src)
    c1 = engine.counters()
    assert np.array_equal(got, clean)            # BIT-identical
    assert c1["stream_resumes"] - c0["stream_resumes"] == 1
    assert c1["stream_gram_slabs"] - c0["stream_gram_slabs"] < 8
    assert asked.sum() < 1200 and not checkpoint.stream_pending(ck)
    one = stream.StreamSource.from_callback(lambda i: x[i], x.shape, 1,
                                            x.dtype, mesh, chunks=150)
    prints = {stream._run_fingerprint(one, stream._Gram(g))
              for g in ((1, "highest", True, True),
                        (1, "highest", True, False),
                        (1, "highest", False, True),
                        (1, "high", True, True))}
    assert len(prints) == 4


def test_the_forecast_says_two_passes_and_where_the_scores_go(
        mesh, monkeypatch):
    """``analysis.check`` forecasts ``ops.pca`` / ``ops.cov`` of a streamed
    source by the rules the run decides by (BLT021), compiles nothing and
    uploads nothing."""
    from bolt_tpu.tpu import array as tpu_array
    x = _planted((5, 200, 16))
    src, asked = _counted(x, mesh, 2)
    c0 = engine.counters()
    rep = analysis.check(src)
    note, = [d for d in rep.diagnostics if d.code == "BLT021"]
    assert rep.ok and note.severity == "info"
    # the key axis alone leaves 3,200 features for 5 samples: the forecast
    # is over the leading axes whose samples outnumber the features
    assert "axis=(0, 1)" in note.message
    assert "ONE pass: the 16 x 16 Gram matrix" in note.message
    assert "3 slabs" in note.message and "in TWO" in note.message
    assert "4000 B a component (unbounded budget)" in note.message
    c1 = engine.counters()
    assert c1["aot_compiles"] == c0["aot_compiles"]
    assert c1["transfer_bytes"] == c0["transfer_bytes"] and not asked.any()
    # under a resident budget (the chip's, or a caller's): how many
    # components of scores fit beside the slabs in flight
    with stream.spill(budget=10 ** 6):
        note, = [d for d in analysis.check(src).diagnostics
                 if d.code == "BLT021"]
        assert "k up to 16 of 16 fit" in note.message
    # scores past the resident budget: the run would materialise, and says so
    with stream.spill(budget=20000):
        note, = [d for d in analysis.check(src).diagnostics
                 if d.code == "BLT021"]
        assert "k up to 0 of 16" in note.message
        assert "materialises the source whole" in note.message
    # what gram_refusal refuses is forecast with its words
    chunked = src.chunk(size=(100, 8)).map(lambda b: b * 2).unchunk()
    note, = [d for d in analysis.check(chunked).diagnostics
             if d.code == "BLT021"]
    assert "materialise this source" in note.message
    assert "a chunk stage is in front" in note.message
    assert note.severity == "info"
    monkeypatch.setattr(tpu_array, "_HBM_LIMIT_OVERRIDE", 1000)
    note, = [d for d in analysis.check(chunked).diagnostics
             if d.code == "BLT021"]
    assert note.severity == "warning" and "BLT020" in note.message
    monkeypatch.undo()
    # no samples x features reading (more features than samples on every
    # leading cut, or a Gram matrix larger than a slab): no note
    wide = bolt.fromcallback(lambda i: np.zeros((4, 64), np.float32)[i],
                             (4, 64), mesh, dtype=np.float32)
    assert not [d for d in analysis.check(wide).diagnostics
                if d.code == "BLT021"]
    # a filter in front is BLT008's to describe
    rows, _ = _counted(_planted((1000, 16)), mesh, 300)
    assert [d for d in analysis.check(rows).diagnostics
            if d.code == "BLT021"]
    assert not [d for d in analysis.check(rows.filter(_big)).diagnostics
                if d.code == "BLT021"]


@pytest.mark.parametrize("d", [16, 64])
def test_planes_for_one_device_go_up_dense_in_both_passes(d):
    """On ONE device a plane of thin rows goes up as a dense view of its
    bytes and is re-seated by the slab program of pass 1 and by the place
    program of pass 2 (``stream.thin_records``): the answers do not know."""
    one = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("k",))
    x = _planted((5, 256, d), seed=13)
    src, asked = _counted(x, one, 2)
    assert stream.dense_route(src._stream)
    c0 = engine.counters()
    scores, vec, sv, mu = bolt.ops.pca(src, k=K, center=True, axis=(0, 1),
                                       return_mean=True)
    c1 = engine.counters()
    assert asked.tolist() == [2] * 5
    assert c1["stream_thin_slabs"] - c0["stream_thin_slabs"] == 6
    assert c1["stream_gram_slabs"] - c0["stream_gram_slabs"] == 3
    assert c1["stream_project_slabs"] - c0["stream_project_slabs"] == 3
    got = (mu, vec, sv, scores.toarray())
    _held(got, _reference(x, 2, K, True), x, 2, TOL)
    # what the same source gives where nothing goes up dense (a codec's
    # wire form keeps the plain upload), up to the order of the sums: the
    # re-seated slab is the same values in another layout
    plain, _ = _counted(x, one, 2, codec="delta-f32")
    assert not stream.dense_route(plain._stream)
    again = bolt.ops.pca(plain, k=K, center=True, axis=(0, 1),
                         return_mean=True)
    assert engine.counters()["stream_thin_slabs"] == c1["stream_thin_slabs"]
    _held(got, (again[3], again[1], again[2], again[0].toarray()), x, 2,
          TOL)
