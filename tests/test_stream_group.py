"""``ops.segment_reduce`` by a label function over a STREAMED source
(ISSUE 51): the grouped fold is a terminal of ``stream.execute`` whose
per-slab partial is ``(folded leaves, int32 counts)``, merged component by
component as the ``multi`` tuple is.  Held to the resident call and to
``mode='local'`` (counts exactly, sums to the limits of the benchmark's
cell), with the source never materialised; TPC-H's Q1 and Q6 through the
streamed path against exact integer arithmetic in NumPy; an injected slab
fault retried; a killed run resumed from its checkpoint; and what still
materialises.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bolt_tpu as bolt
from bolt_tpu import _chaos as chaos
from bolt_tpu import checkpoint, engine, obs, stream

ROWS, COLS = 1200, 5
OPS = ("sum", "mean", "max", "min")
NSEG = 4            # labels run 0..4: one label falls outside every group


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


def _table(seed=7, rows=ROWS):
    """Small integers as float32: every sum is exact in float32 whatever
    order it is taken in."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, 40, size=(rows, COLS)).astype(np.float32)


# module-level functions: one object a role, so the engine's programs are
# shared by the cases that differ only in what the caller does with them
def _pred(r):
    return r[0] > 9


def _pred_local(r):
    return bool(r[0] > 9)


def _shift(r):
    return r + 1


def _label(r):
    return (r[1] % 5).astype(jnp.int32)


def _label_local(r):
    return int(r[1] % 5)


def _pair(r):
    return (r[2], r[3] * r[4])


def _bare(r):
    return r[2] * r[3]


def _source(x, mesh, chunks, **kw):
    return bolt.fromcallback(lambda idx: x[idx], x.shape, mesh,
                             dtype=x.dtype, chunks=chunks, **kw)


def _chain(b, filtered, mapped, local=False):
    if mapped:
        b = b.map(_shift)
    if filtered:
        b = b.filter(_pred_local if local else _pred)
    return b


def _host(tree):
    return [np.asarray(leaf.toarray()) for leaf in
            jax.tree_util.tree_leaves(
                tree, is_leaf=lambda t: hasattr(t, "toarray"))]


@pytest.mark.parametrize("counts", [True, False],
                         ids=["counts", "no-counts"])
@pytest.mark.parametrize("tupled", [True, False], ids=["tuple", "bare"])
@pytest.mark.parametrize("chunks", [300, 350], ids=["even", "short-tail"])
@pytest.mark.parametrize("mapped", [False, True], ids=["plain", "mapped"])
@pytest.mark.parametrize("filtered", [False, True],
                         ids=["all", "filtered"])
@pytest.mark.parametrize("op", OPS)
def test_streamed_equals_resident_and_local(mesh, traced, op, filtered,
                                            mapped, chunks, tupled, counts):
    x = _table()
    value = _pair if tupled else _bare
    kw = dict(num_segments=NSEG, op=op, value=value, return_counts=counts)
    c0 = engine.counters()
    got = bolt.ops.segment_reduce(
        _chain(_source(x, mesh, chunks), filtered, mapped), labels=_label,
        **kw)
    c1 = engine.counters()
    spans = obs.totals()
    assert "stream.materialize" not in spans
    nslabs = -(-ROWS // chunks)
    assert c1["stream_group_slabs"] - c0["stream_group_slabs"] == nslabs
    assert c1["stream_chunks"] - c0["stream_chunks"] == nslabs
    assert c1["filters_fused"] - c0["filters_fused"] == int(filtered)
    assert c1["filter_compactions"] == c0["filter_compactions"]
    assert spans["group.segment_reduce"]["count"] == 1
    assert [s.attrs.get("streamed") for s in obs.spans()
            if s.name == "group.segment_reduce"] == [True]
    resident = bolt.ops.segment_reduce(
        _chain(bolt.array(x, mesh), filtered, mapped), labels=_label, **kw)
    local = bolt.ops.segment_reduce(
        _chain(bolt.array(x), filtered, mapped, local=True),
        labels=_label_local, **kw)
    if counts:
        got, got_n = got
        resident, res_n = resident
        local, loc_n = local
        assert got_n.shape == (NSEG,) and got_n.dtype == np.int32
        assert np.array_equal(got_n.toarray(), res_n.toarray())
        assert np.array_equal(got_n.toarray(), np.asarray(loc_n))
    assert isinstance(got, tuple) == tupled
    for a, b, c in zip(_host(got), _host(resident),
                       [np.asarray(leaf) for leaf in
                        (local if tupled else [local])]):
        assert a.shape == b.shape == c.shape == (NSEG,)
        assert a.dtype == b.dtype
        assert np.allclose(a, b, rtol=1e-5, atol=0)
        assert np.allclose(a, c, rtol=1e-4, atol=0)


def test_a_mean_of_integers_is_floating_like_the_resident_one(mesh):
    x = _table().astype(np.int32)
    kw = dict(labels=_label, num_segments=NSEG, op="mean", value=_bare,
              return_counts=True)
    got, n = bolt.ops.segment_reduce(_source(x, mesh, 350), **kw)
    want, m = bolt.ops.segment_reduce(bolt.array(x, mesh), **kw)
    assert got.dtype == want.dtype and np.issubdtype(got.dtype, np.floating)
    assert np.allclose(got.toarray(), want.toarray(), rtol=1e-6)
    assert np.array_equal(n.toarray(), m.toarray())


def test_the_record_itself_is_the_value_where_none_is_given(mesh):
    x = _table()
    got = bolt.ops.segment_reduce(
        _source(x, mesh, 350).filter(_pred), labels=_label,
        num_segments=NSEG)
    want = bolt.ops.segment_reduce(bolt.array(x, mesh).filter(_pred),
                                   labels=_label, num_segments=NSEG)
    assert got.shape == (NSEG, COLS)
    assert np.array_equal(got.toarray(), want.toarray())


def test_fromiter_streams_too(mesh, traced):
    x = _table()
    blocks = [x[lo:lo + 250] for lo in range(0, ROWS, 250)]
    src = bolt.fromiter(blocks, x.shape, mesh, dtype=x.dtype)
    got, n = bolt.ops.segment_reduce(
        src.filter(_pred), labels=_label, num_segments=NSEG, value=_pair,
        return_counts=True)
    want, m = bolt.ops.segment_reduce(
        bolt.array(x, mesh).filter(_pred), labels=_label,
        num_segments=NSEG, value=_pair, return_counts=True)
    assert "stream.materialize" not in obs.totals()
    assert np.array_equal(n.toarray(), m.toarray())
    for a, b in zip(got, want):
        assert np.array_equal(a.toarray(), b.toarray())


def test_a_map_behind_a_streamed_filter_stays_streamed(mesh, traced):
    """Q6's shape: ``filter(pred).map(f).sum()`` over a streamed source
    folds the mask over what the map gives; nothing is materialised and
    no survivors are built."""
    x = _table()
    c0 = engine.counters()
    b = _source(x, mesh, 350).filter(_pred).map(_bare)
    assert b.streaming and b.dtype == np.float32
    got = b.sum().toarray()
    c1 = engine.counters()
    assert "stream.materialize" not in obs.totals()
    assert c1["stream_chunks"] - c0["stream_chunks"] == 4
    assert c1["filter_compactions"] == c0["filter_compactions"]
    keep = x[:, 0] > 9
    assert float(got) == float((x[keep, 2] * x[keep, 3]).sum())
    want = bolt.array(x, mesh).filter(_pred).map(_bare).sum().toarray()
    assert np.array_equal(got, want)
    # a second map, a cast, and the other statistics ride the same way
    m = _source(x, mesh, 350).filter(_pred).map(_bare).map(
        _shift, dtype=np.float64).mean().toarray()
    assert np.isclose(float(m), (x[keep, 2] * x[keep, 3] + 1).mean())
    # any other consumer materialises and gives the resident answer
    rows = _source(x, mesh, 350).filter(_pred).map(_bare).toarray()
    assert np.array_equal(rows, x[keep, 2] * x[keep, 3])


# ---------------------------------------------------------------------
# the reference test: TPC-H's Q6 and Q1 over a seeded LINEITEM through the
# streamed path, against exact integer arithmetic in NumPy (nothing of the
# benchmark is imported)
# ---------------------------------------------------------------------

DATE, QTY, PRICE, DISC, TAX, FLAG, STATUS = range(7)


def _lineitem(rows, seed):
    rng = np.random.default_rng(seed)
    ship = rng.integers(1, 2527, rows)
    qty = rng.integers(1, 51, rows)
    price = qty * rng.integers(90000, 209900, rows)
    disc = rng.integers(0, 11, rows)
    tax = rng.integers(0, 9, rows)
    flag = np.where(ship + rng.integers(1, 31, rows) <= 1263,
                    2 * rng.integers(0, 2, rows), 1)
    status = (ship > 1263).astype(np.int64)
    cols = np.stack([ship, qty, price, disc, tax, flag, status], axis=1)
    assert cols.max() < 1 << 24          # exact in float32
    return cols


def _q6_pred(r):
    return ((r[DATE] >= 731) & (r[DATE] < 1096) & (r[DISC] >= 5)
            & (r[DISC] <= 7) & (r[QTY] < 24))


def _q6_revenue(r):
    return r[PRICE] * r[DISC]


def _q1_pred(r):
    return r[DATE] <= 2436


def _q1_group(r):
    return (3 * r[STATUS] + r[FLAG]).astype(jnp.int32)


def _q1_terms(r):
    disc_price = r[PRICE] * (100 - r[DISC])
    return (r[QTY], r[PRICE], disc_price, disc_price * (100 + r[TAX]),
            r[DISC], jnp.ones_like(r[QTY]))


@pytest.mark.parametrize("seed", [3, 2**31 + 17])
def test_q6_and_q1_streamed_against_exact_integers(mesh, traced, seed):
    rows = 100_000
    cols = _lineitem(rows, seed)
    x = cols.astype(np.float32)
    chunks = 13_000                       # seven slabs and a tail of 9,000
    revenue = _source(x, mesh, chunks).filter(_q6_pred).map(
        _q6_revenue).sum().toarray()
    sums, counts = bolt.ops.segment_reduce(
        _source(x, mesh, chunks).filter(_q1_pred), labels=_q1_group,
        num_segments=6, value=_q1_terms, return_counts=True)
    assert "stream.materialize" not in obs.totals()
    c = [cols[:, k].astype(object) for k in range(7)]     # Python integers
    keep6 = _q6_pred(cols.T)
    want6 = int(sum(c[PRICE][keep6] * c[DISC][keep6]))
    assert abs(float(revenue) - want6) / want6 < 1e-5
    keep1 = cols[:, DATE] <= 2436
    gid = 3 * cols[:, STATUS] + cols[:, FLAG]
    dp = c[PRICE] * (100 - c[DISC])
    terms = [c[QTY], c[PRICE], dp, dp * (100 + c[TAX]), c[DISC],
             np.ones(rows, dtype=object)]
    got_counts = np.asarray(counts.toarray())
    assert got_counts.dtype == np.int32
    for g in range(6):
        hit = keep1 & (gid == g)
        assert int(got_counts[g]) == int(hit.sum())       # exact
        for t, leaf in zip(terms, sums):
            want = int(sum(t[hit])) if hit.any() else 0
            got = float(np.asarray(leaf.toarray())[g])
            assert abs(got - want) <= 1e-4 * max(want, 1)
    assert (got_counts > 0).sum() == 4    # four occupied groups of six


# ---------------------------------------------------------------------
# faults: a slab retried, a run resumed
# ---------------------------------------------------------------------

def _grouped(src):
    sums, counts = bolt.ops.segment_reduce(
        src.filter(_pred), labels=_label, num_segments=NSEG, value=_pair,
        return_counts=True)
    return [np.asarray(s.toarray()) for s in sums] + [
        np.asarray(counts.toarray())]


def test_an_injected_slab_fault_is_retried_and_the_answer_unchanged(mesh):
    x = _table()
    clean = _grouped(_source(x, mesh, 150))
    chaos.inject("stream.upload", nth=3)         # one trip, then healthy
    c0 = engine.counters()
    with stream.retries(2):
        got = _grouped(_source(x, mesh, 150))
    c1 = engine.counters()
    assert c1["stream_retries"] - c0["stream_retries"] == 1
    assert c1["stream_group_slabs"] - c0["stream_group_slabs"] == 8
    for a, b in zip(got, clean):
        assert np.array_equal(a, b)


def test_a_killed_grouped_run_resumes_from_its_checkpoint(mesh, tmp_path):
    """Under ``stream.resumable`` the grouped partial is checkpointed as
    the ``multi`` tuple is: a run killed mid-stream and started again over
    the same source skips the retired slabs and gives the uninterrupted
    answer, bit for bit."""
    x = _table()
    clean = _grouped(_source(x, mesh, 150))
    ck = str(tmp_path / "ck")
    chaos.inject("stream.upload", nth=5)         # die at slab 5 of 8
    with pytest.raises(chaos.ChaosError):
        with stream.uploaders(1), stream.resumable(ck):
            _grouped(_source(x, mesh, 150))
    chaos.clear()
    assert checkpoint.stream_pending(ck)         # the watermark survived
    c1 = engine.counters()
    with stream.resumable(ck):
        got = _grouped(_source(x, mesh, 150))
    c2 = engine.counters()
    for a, b in zip(got, clean):
        assert np.array_equal(a, b)              # BIT-identical
    assert c2["stream_resumes"] - c1["stream_resumes"] == 1
    assert c2["stream_group_slabs"] - c1["stream_group_slabs"] < 8
    assert not checkpoint.stream_pending(ck)     # success cleared it
    # another fold over the same directory is another run: never adopted
    chaos.inject("stream.upload", nth=5)
    with pytest.raises(chaos.ChaosError):
        with stream.uploaders(1), stream.resumable(ck):
            _grouped(_source(x, mesh, 150))
    chaos.clear()
    with stream.resumable(ck):
        other = bolt.ops.segment_reduce(
            _source(x, mesh, 150).filter(_pred), labels=_label,
            num_segments=NSEG, value=_bare)
    want = bolt.ops.segment_reduce(
        bolt.array(x, mesh).filter(_pred), labels=_label,
        num_segments=NSEG, value=_bare)
    assert np.array_equal(other.toarray(), want.toarray())


# ---------------------------------------------------------------------
# what behaves as before
# ---------------------------------------------------------------------

def test_what_the_executor_does_not_take_materialises_as_before(mesh,
                                                                traced):
    x = _table()
    want, m = bolt.ops.segment_reduce(
        bolt.array(x, mesh), labels=_label, num_segments=NSEG, value=_bare,
        return_counts=True)

    def check(src):
        obs.clear()
        c0 = engine.counters()
        got, n = bolt.ops.segment_reduce(
            src, labels=_label, num_segments=NSEG, value=_bare,
            return_counts=True)
        c1 = engine.counters()
        # the source was built on the device whole (uploaded, or its
        # mapped result collected slab by slab) and folded resident
        assert not src.streaming
        assert c1["stream_group_slabs"] == c0["stream_group_slabs"]
        assert [s.attrs.get("streamed") for s in obs.spans()
                if s.name == "group.segment_reduce"] == [None]
        assert np.array_equal(got.toarray(), want.toarray())
        assert np.array_equal(n.toarray(), m.toarray())

    # a keyed map in front: the slab program would need the slab's key
    check(_source(x, mesh, 350).map(lambda kv: kv[1], with_keys=True))
    # a label ARRAY is resident-only
    labels = (x[:, 1] % 5).astype(np.int64)
    keep = labels < NSEG
    c0 = engine.counters()
    arr = bolt.ops.segment_reduce(
        _source(x[keep], mesh, 350).map(_bare), labels[keep],
        num_segments=NSEG)
    assert engine.counters()["stream_group_slabs"] == c0["stream_group_slabs"]
    assert np.allclose(arr.toarray(), want.toarray())


def test_a_label_that_is_no_integer_is_refused_before_any_upload(mesh):
    x = _table()
    c0 = engine.counters()
    with pytest.raises(ValueError, match="one integer per record"):
        bolt.ops.segment_reduce(_source(x, mesh, 350).filter(_pred),
                                labels=lambda r: r[1] * 0.5,
                                num_segments=NSEG)
    assert engine.counters()["transfer_bytes"] == c0["transfer_bytes"]


def test_a_lossy_codec_refuses_a_grouped_extremum(mesh):
    x = _table()
    src = _source(x, mesh, 350, codec="int8")
    with pytest.raises(ValueError, match="lossy codec"):
        bolt.ops.segment_reduce(src, labels=_label, num_segments=NSEG,
                                op="max", value=_bare)
