"""Fused multi-terminal statistics (ISSUE 7): ``bolt.compute`` /
``a.stats("sum", ...)`` parity and accounting.

Parity is the load-bearing half: every FUSED result must be
bit-identical to its STANDALONE terminal (the acceptance contract) —
compared across local, materialised, chunked and streamed arrays,
including uneven tails and filter-fused predicates.  Accounting rides
along: a fused group of N terminals costs exactly ONE engine compile
and ONE dispatch (N−1 dispatches saved), ``ptp`` rides the fused
min/max pair, donation fires once for the whole group, and the checker
forecasts the fusion (BLT009) with zero compiles.  The opt-in
reduced-precision accumulation path is parity-locked: default exact,
"f32" bit-identical for f32 pipelines, "bf16" within the documented
~1e-2 relative envelope.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bolt_tpu as bolt
from bolt_tpu import analysis, engine
from bolt_tpu import _precision

STATS = ("sum", "mean", "var", "std", "min", "max", "prod")


def _x(shape=(16, 6, 4), seed=0):
    return np.random.RandomState(seed).randn(*shape)


def _bits(a, b):
    """Bit-compare two results (NaNs equal)."""
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype \
        and np.array_equal(a, b, equal_nan=np.issubdtype(
            a.dtype, np.floating))


# ---------------------------------------------------------------------
# laziness: validation eager, dispatch deferred, reads transparent
# ---------------------------------------------------------------------

def test_stat_terminal_is_lazy_then_transparent(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    s = b.map(lambda v: v * 3).sum()
    assert s._spending is not None            # nothing dispatched yet
    assert s.shape == (6, 4)                  # metadata known abstractly
    assert s.dtype == np.float64
    assert "lazy sum() terminal" in repr(s)
    assert np.allclose(np.asarray(s.toarray()), (x * 3).sum(axis=0))
    assert s._spending is None                # the read resolved it


def test_invalid_axis_still_raises_eagerly(mesh):
    b = bolt.array(_x(), mesh)
    with pytest.raises(ValueError):
        b.sum(axis=(9,))


def test_zero_size_extrema_raise_at_call(mesh):
    b = bolt.array(np.zeros((0, 4)), mesh)
    with pytest.raises(ValueError):
        b.min()


# ---------------------------------------------------------------------
# fused vs standalone parity: materialised arrays
# ---------------------------------------------------------------------

@pytest.mark.parametrize("name", STATS)
def test_fused_bit_identical_to_standalone(mesh, name):
    x = np.abs(_x(seed=1)) * 0.25 + 0.5       # prod-safe magnitudes
    f = lambda v: jnp.sqrt(v) + 1.0           # noqa: E731

    def standalone():
        return getattr(bolt.array(x, mesh).map(f), name)()

    want = np.asarray(standalone().toarray())
    m = bolt.array(x, mesh).map(f)
    handles = {n: getattr(m, n)() for n in STATS}
    bolt.compute(*handles.values())
    assert _bits(handles[name].toarray(), want)


def test_fused_group_costs_one_compile_one_dispatch(mesh):
    # geometry UNIQUE to this test so every engine key is fresh
    x = _x(shape=(12, 5, 3), seed=2)

    def add7(v):
        return v + 7.0

    m = bolt.array(x, mesh).map(add7)
    hs = [m.sum(), m.var(), m.min(), m.max()]
    c0 = engine.counters()
    bolt.compute(*hs)
    c1 = engine.counters()
    d = {k: c1[k] - c0[k] for k in c1}
    # ONE compile + ONE dispatch for N=4 terminals: N-1 = 3 saved
    assert d["misses"] == 1 and d["aot_compiles"] == 1
    assert d["dispatches"] == 1
    assert d["fused_stat_groups"] == 1
    assert d["fused_stat_terminals"] == 4
    # a second identical group hits the cache: zero new compiles
    m2 = bolt.array(x, mesh).map(add7)
    c2 = engine.counters()
    bolt.compute(m2.sum(), m2.var(), m2.min(), m2.max())
    c3 = engine.counters()
    assert c3["misses"] == c2["misses"]
    assert c3["aot_compiles"] == c2["aot_compiles"]
    assert c3["dispatches"] - c2["dispatches"] == 1


def test_read_of_any_member_resolves_whole_group(mesh):
    x = _x(seed=3)
    m = bolt.array(x, mesh).map(lambda v: v - 2)
    s, v = m.sum(), m.var()
    c0 = engine.counters()
    got = np.asarray(s.toarray())             # auto-fuses the siblings
    c1 = engine.counters()
    assert c1["dispatches"] - c0["dispatches"] == 1
    assert np.allclose(got, (x - 2).sum(axis=0))
    assert v._spending.result is not None     # resolved in the same pass
    assert np.allclose(np.asarray(v.toarray()), (x - 2).var(axis=0))


def test_mixed_sources_fall_back_per_group(mesh):
    x, y = _x(seed=4), _x(seed=5)
    ma = bolt.array(x, mesh).map(lambda v: v + 1)
    mb = bolt.array(y, mesh).map(lambda v: v + 1)
    c0 = engine.counters()
    s1, s2, v1 = bolt.compute(ma.sum(), mb.sum(), ma.var())
    c1 = engine.counters()
    # two groups: (ma.sum, ma.var) fused, mb.sum standalone
    assert c1["dispatches"] - c0["dispatches"] == 2
    assert np.allclose(np.asarray(s1.toarray()), (x + 1).sum(axis=0))
    assert np.allclose(np.asarray(s2.toarray()), (y + 1).sum(axis=0))
    assert np.allclose(np.asarray(v1.toarray()), (x + 1).var(axis=0))


def test_compute_passes_through_concrete_and_local():
    x = _x()
    lo = bolt.array(x)                        # local oracle
    out = bolt.compute(lo.sum(axis=0), 3.5)
    assert np.allclose(np.asarray(out[0]), x.sum(axis=0))
    assert out[1] == 3.5
    with pytest.raises(TypeError):
        bolt.compute()


def test_axes_keepdims_ddof_specs_fuse(mesh):
    x = _x(seed=6)
    m = bolt.array(x, mesh).map(lambda v: v * 2)
    a, b, c = bolt.compute(m.sum(axis=(0,), keepdims=True),
                           m.var(ddof=1), m.mean(axis=(0, 1)))
    assert _bits(a.toarray(),
                 bolt.array(x, mesh).map(lambda v: v * 2)
                 .sum(axis=(0,), keepdims=True).toarray())
    assert _bits(b.toarray(),
                 bolt.array(x, mesh).map(lambda v: v * 2)
                 .var(ddof=1).toarray())
    assert _bits(c.toarray(),
                 bolt.array(x, mesh).map(lambda v: v * 2)
                 .mean(axis=(0, 1)).toarray())


# ---------------------------------------------------------------------
# ptp rides the fused min/max pair
# ---------------------------------------------------------------------

def test_ptp_routes_through_min_max_pair(mesh):
    from bolt_tpu.tpu import array as array_mod
    x = _x(shape=(10, 7, 3), seed=7)
    b = bolt.array(x, mesh)

    def ptp_keys():
        # ("stat", "ptp", ...) entries (other paths — e.g. a resolved
        # filter's eager ptp — may legitimately own one; THIS lazy ptp
        # must not add any)
        return sum(1 for k in array_mod._JIT_CACHE
                   if isinstance(k, tuple) and len(k) > 1
                   and k[0] == "stat" and k[1] == "ptp")

    n0 = ptp_keys()
    got = np.asarray(b.ptp().toarray())
    assert np.allclose(got, np.ptp(x, axis=0))
    # one fewer program key: ptp shares the multi-stat pair program
    # instead of adding a ("stat", "ptp", ...) entry
    assert ptp_keys() == n0
    assert any(k[0] == "multi-stat" for k in array_mod._JIT_CACHE
               if isinstance(k, tuple))
    # compute(ptp, min, max) dedups to the same two extrema slots
    b2 = bolt.array(x, mesh)
    p, mn, mx = bolt.compute(b2.ptp(), b2.min(), b2.max())
    assert _bits(p.toarray(), np.asarray(mx.toarray())
                 - np.asarray(mn.toarray()))


def test_ptp_axis_variants_match_numpy(mesh):
    x = _x(seed=8)
    b = bolt.array(x, mesh)
    assert np.allclose(np.asarray(b.ptp(axis=(0, 1, 2)).toarray()),
                       np.ptp(x))
    assert np.allclose(np.asarray(b.ptp(axis=(1,)).toarray()),
                       np.ptp(x, axis=1))


# ---------------------------------------------------------------------
# filter-fused predicates
# ---------------------------------------------------------------------

PRED = lambda v: v.sum() > 0                  # noqa: E731


def _keep(x):
    return x[[v.sum() > 0 for v in x]]


@pytest.mark.parametrize("name", ["sum", "mean", "var", "std", "prod"])
def test_filtered_fused_bit_identical_to_standalone(mesh, name):
    x = _x(seed=9) * 0.5
    keep = _keep(x)
    want = np.asarray(
        getattr(bolt.array(x, mesh).filter(PRED), name)().toarray())
    f = bolt.array(x, mesh).filter(PRED)
    hs = {n: getattr(f, n)() for n in ("sum", "mean", "var", "std",
                                       "prod")}
    c0 = engine.counters()
    bolt.compute(*hs.values())
    c1 = engine.counters()
    assert c1["dispatches"] - c0["dispatches"] == 1   # one masked pass
    assert _bits(hs[name].toarray(), want)
    ref = getattr(keep, name)(axis=0)
    assert np.allclose(np.asarray(hs[name].toarray()), ref, atol=1e-10)


def test_filtered_min_max_stay_eager_with_error_contract(mesh):
    x = _x(seed=10)
    b = bolt.array(x, mesh)
    nothing = lambda v: v.sum() > 1e9         # noqa: E731
    with pytest.raises(ValueError, match="zero-size"):
        b.filter(nothing).max()               # raises AT CALL, as ever
    got = b.filter(PRED).min()                # eager: already concrete
    assert got._spending is None
    assert np.allclose(np.asarray(got.toarray()), _keep(x).min(axis=0))


# ---------------------------------------------------------------------
# chunked views delegate through the same lazy terminals
# ---------------------------------------------------------------------

def test_chunked_view_stats_fuse(mesh):
    x = _x(seed=11)
    cv = bolt.array(x, mesh).map(lambda v: v + 1).chunk(size=(3,),
                                                        axis=(0,))
    s, v = bolt.compute(cv.sum(), cv.var())
    assert np.allclose(np.asarray(s.toarray()), (x + 1).sum(axis=0))
    assert np.allclose(np.asarray(v.toarray()), (x + 1).var(axis=0))


# ---------------------------------------------------------------------
# streamed multi-stat: one ingest pass, bit-exact on power-of-two slabs
# ---------------------------------------------------------------------

SHAPE = (16, 6, 4)


def _intdata(shape=SHAPE):
    return ((np.arange(np.prod(shape)) % 13) - 6).astype(
        np.float64).reshape(shape)


def _source(data, mesh, chunks):
    return bolt.fromcallback(lambda idx: data[idx], data.shape, mesh,
                             dtype=data.dtype, chunks=chunks)


def test_streamed_multi_stat_single_ingest_pass(mesh):
    data = _intdata()
    s = _source(data, mesh, 4)                # 4 power-of-two slabs
    c0 = engine.counters()
    su, va, mn, mx = bolt.compute(s.sum(), s.var(), s.min(), s.max())
    c1 = engine.counters()
    d = {k: c1[k] - c0[k] for k in c1}
    assert d["stream_chunks"] == 4            # ONE pass over the source
    assert d["transfer_bytes"] == data.nbytes
    assert d["fused_stat_terminals"] == 4
    # bit-exact vs the materialised terminals (power-of-two slab count)
    mat = bolt.array(data, mesh)
    assert _bits(su.toarray(), mat.sum().toarray())
    assert _bits(va.toarray(), mat.var().toarray())
    assert _bits(mn.toarray(), mat.min().toarray())
    assert _bits(mx.toarray(), mat.max().toarray())


@pytest.mark.parametrize("chunks", [3, 5, 1])
def test_streamed_multi_stat_uneven_tails(mesh, chunks):
    data = _intdata()
    s = _source(data, mesh, chunks)
    su, me, mn = bolt.compute(s.sum(), s.mean(), s.min())
    # integer-valued data: sum/min exact under any fold order; the
    # mean's Chan denominators are only bit-exact on power-of-two
    # EQUAL slab counts (the documented contract) — uneven tails get
    # ulp-level tolerance
    assert np.array_equal(np.asarray(su.toarray()), data.sum(axis=0))
    assert np.allclose(np.asarray(me.toarray()), data.mean(axis=0),
                       rtol=1e-12, atol=1e-12)
    assert np.array_equal(np.asarray(mn.toarray()), data.min(axis=0))


def test_streamed_standalone_still_bit_exact_and_lazy(mesh):
    data = _intdata()
    s = _source(data, mesh, 4).sum()
    c0 = engine.counters()
    assert c0 is not None and s._spending is not None
    got = np.asarray(s.toarray())
    assert np.array_equal(got, data.sum(axis=0))


def test_streamed_filtered_multi_stat(mesh):
    data = _intdata()
    s = _source(data, mesh, 4).filter(PRED)
    su, me = bolt.compute(s.sum(), s.mean())
    keep = _keep(data)
    assert np.array_equal(np.asarray(su.toarray()), keep.sum(axis=0))
    # the masked per-slab counts merge through the Chan recurrence:
    # ulp-level tolerance off power-of-two survivor splits
    assert np.allclose(np.asarray(me.toarray()), keep.mean(axis=0),
                       rtol=1e-12, atol=1e-12)


def test_streamed_ptp_is_one_pass(mesh):
    data = _intdata()
    c0 = engine.counters()
    p = _source(data, mesh, 4).ptp()
    got = np.asarray(p.toarray())
    c1 = engine.counters()
    assert c1["stream_chunks"] - c0["stream_chunks"] == 4
    assert np.array_equal(got, np.ptp(data, axis=0))


def test_materialised_source_does_not_rejoin_stream_group(mesh):
    data = _intdata()

    def gen():
        yield data[:8]
        yield data[8:]

    it = bolt.fromiter(gen(), SHAPE, mesh, dtype=np.float64)
    h = it.sum()                      # stream group forms
    assert np.array_equal(it.toarray(), data)   # burns the iterator
    h2 = it.mean()                    # computes from the CONCRETE data
    assert np.array_equal(np.asarray(h2.toarray()), data.mean(axis=0))
    # the pre-materialise handle kept its recorded one-shot source: the
    # pointed re-stream error surfaces at ITS read, not a silent wrong
    # answer
    with pytest.raises(RuntimeError, match="already streamed"):
        h.toarray()


def test_one_shot_fromiter_serves_all_members_in_one_pass(mesh):
    data = _intdata()

    def gen():
        yield data[:8]
        yield data[8:]

    it = bolt.fromiter(gen(), SHAPE, mesh, dtype=np.float64)
    su, me, sd = bolt.compute(it.sum(), it.mean(), it.std())
    assert np.array_equal(np.asarray(su.toarray()), data.sum(axis=0))
    assert np.array_equal(np.asarray(me.toarray()), data.mean(axis=0))
    assert np.allclose(np.asarray(sd.toarray()), data.std(axis=0))


# ---------------------------------------------------------------------
# the fluent a.stats("sum", ...) form + the local oracle
# ---------------------------------------------------------------------

def test_fluent_stats_tpu_vs_local_oracle(mesh):
    x = _x(seed=12)
    t = bolt.array(x, mesh).stats("sum", "var", "min", "ptp")
    lo = bolt.array(x).stats("sum", "var", "min", "ptp")
    assert list(t) == ["sum", "var", "min", "ptp"]
    for name in t:
        assert np.allclose(np.asarray(t[name].toarray()),
                           np.asarray(lo[name]), atol=1e-10), name


def test_fluent_stats_is_one_pass(mesh):
    x = _x(seed=13)
    b = bolt.array(x, mesh).map(lambda v: v + 5)
    c0 = engine.counters()
    out = b.stats("sum", "mean", "max")
    c1 = engine.counters()
    assert c1["dispatches"] - c0["dispatches"] == 1
    assert np.allclose(np.asarray(out["max"].toarray()),
                       (x + 5).max(axis=0))


def test_fluent_stats_rejects_unknown_names(mesh):
    b = bolt.array(_x(), mesh)
    with pytest.raises(ValueError, match="unknown statistic"):
        b.stats("sum", "median")
    with pytest.raises(ValueError, match="unknown statistic"):
        bolt.array(_x()).stats("nope")


def test_stats_statcounter_contract_unchanged(mesh):
    x = _x(seed=14)
    st = bolt.array(x, mesh).stats()
    assert np.allclose(np.asarray(st.mean()), x.mean(axis=0))
    st2 = bolt.array(x, mesh).stats(("mean", "var"))
    assert np.allclose(np.asarray(st2.variance()), x.var(axis=0))
    st3 = bolt.array(x, mesh).stats(axis=(1,))
    assert np.allclose(np.asarray(st3.mean()), x.mean(axis=1))
    # the legacy POSITIONAL axis form keeps working on both backends
    st4 = bolt.array(x, mesh).stats(("mean",), (1,))
    assert np.allclose(np.asarray(st4.mean()), x.mean(axis=1))
    st5 = bolt.array(x).stats(("mean",), (1,))
    assert np.allclose(np.asarray(st5.mean()), x.mean(axis=1))
    with pytest.raises(TypeError, match="axis twice"):
        bolt.array(x, mesh).stats(("mean",), (1,), axis=(0,))


def test_fluent_stats_mixed_names_on_one_shot_stream(mesh):
    # a non-streamable name (prod) in the SAME fluent call must not
    # consume a one-shot iterator out from under the streamed siblings:
    # the source materialises once up front and every name computes
    # from the concrete data (order-independent)
    data = _intdata()

    def gen():
        yield data[:8]
        yield data[8:]

    for names in (("sum", "prod"), ("prod", "sum")):
        it = bolt.fromiter(gen(), SHAPE, mesh, dtype=np.float64)
        out = it.stats(*names)
        assert np.array_equal(np.asarray(out["sum"].toarray()),
                              data.sum(axis=0)), names
        assert np.allclose(np.asarray(out["prod"].toarray()),
                           data.prod(axis=0)), names


def test_materialised_chain_source_starts_fresh_group(mesh):
    # after a chain materialises, new terminals must reduce the
    # CONCRETE buffer, not rejoin the old group and re-run the map
    # chain from the base (the one-pass cost model would silently
    # double)
    x = _x(seed=19)
    m = bolt.array(x, mesh).map(lambda v: v * 3)
    s = m.sum()                       # chain group forms
    m.cache()                         # materialises the chain
    v = m.var()
    assert v._spending.group is not s._spending.group
    assert v._spending.group.funcs == ()      # reduces concrete data
    assert np.allclose(np.asarray(v.toarray()), (x * 3).var(axis=0))
    assert np.allclose(np.asarray(s.toarray()), (x * 3).sum(axis=0))


# ---------------------------------------------------------------------
# donation: one donate serves the whole fused group
# ---------------------------------------------------------------------

def test_group_donates_once_and_guards_source(mesh):
    x = _x(seed=15)
    with engine.donation(0):
        d = bolt.array(x, mesh).map(lambda v: v + 1)
        n0 = engine.counters()["donations"]
        s = d.sum()                           # consumes the sole owner
        assert engine.counters()["donations"] == n0 + 1
        v = d.var()                           # joins the SAME group
        assert engine.counters()["donations"] == n0 + 1
        su, va = bolt.compute(s, v)
        assert np.allclose(np.asarray(su.toarray()), (x + 1).sum(axis=0))
        assert np.allclose(np.asarray(va.toarray()), (x + 1).var(axis=0))
        assert engine.counters()["donations"] == n0 + 1   # ONE donate
        with pytest.raises(RuntimeError, match="donated"):
            d.toarray()
        # after the group dispatched, further terminals hit the guard
        with pytest.raises(RuntimeError, match="donated"):
            d.mean()


# ---------------------------------------------------------------------
# reduced-precision accumulation (opt-in; default exact)
# ---------------------------------------------------------------------

def _acc_data(mesh):
    x = (np.random.RandomState(16).rand(32, 8, 4)
         .astype(np.float32) * 3 + 0.5)
    return x, bolt.array(x, mesh)


def test_accumulate_default_is_bit_exact(mesh):
    x, b = _acc_data(mesh)
    s1 = bolt.compute(bolt.array(x, mesh).map(lambda v: v * 1.7).sum())
    m = b.map(lambda v: v * 1.7)
    s2, _v = bolt.compute(m.sum(), m.var())
    assert _bits(s1.toarray(), s2.toarray())


def test_accumulate_f32_exact_for_f32_pipeline(mesh):
    x, b = _acc_data(mesh)
    want = np.asarray(
        bolt.compute(bolt.array(x, mesh).sum()).toarray())
    got = bolt.compute(bolt.array(x, mesh).sum(), accumulate="f32")
    assert _bits(got.toarray(), want)


def test_accumulate_bf16_within_documented_envelope(mesh):
    x, b = _acc_data(mesh)
    exact = np.asarray(bolt.compute(bolt.array(x, mesh).sum(),
                                    bolt.array(x, mesh).var())
                       [0].toarray())
    s, v, mn = bolt.compute(b.sum(), b.var(), b.min(),
                            accumulate="bf16")
    got = np.asarray(s.toarray())
    assert got.dtype == np.float32            # accumulate-in-f32 result
    rel = np.max(np.abs(got - exact) / np.maximum(np.abs(exact), 1e-6))
    assert rel < 1e-2                         # the documented envelope
    # order statistics stay exact regardless of the mode
    assert _bits(mn.toarray(), x.min(axis=0))


def test_accumulate_scope_and_validation(mesh):
    x, _b = _acc_data(mesh)
    with _precision.accumulate("bf16"):
        s = bolt.compute(bolt.array(x, mesh).sum())
        assert np.asarray(s.toarray()).dtype == np.float32
    with pytest.raises(ValueError, match="accumulate mode"):
        bolt.compute(bolt.array(x, mesh).sum(), accumulate="f16")
    # integer pipelines ignore the cast (counts stay exact)
    xi = np.arange(48, dtype=np.int64).reshape(12, 4)
    si = bolt.compute(bolt.array(xi, mesh).sum(), accumulate="bf16")
    assert np.array_equal(np.asarray(si.toarray()), xi.sum(axis=0))


def test_accumulate_rejects_streamed_groups_explicitly(mesh):
    data = _intdata()
    with pytest.raises(ValueError, match="in-memory"):
        bolt.compute(_source(data, mesh, 4).sum(), accumulate="bf16")


# ---------------------------------------------------------------------
# analysis: BLT009 fusion forecast, zero compiles
# ---------------------------------------------------------------------

def test_check_forecasts_fusion_with_zero_compiles(mesh):
    x = _x(seed=17)
    m = bolt.array(x, mesh).map(lambda v: v + 1)
    s, v = m.sum(), m.var()
    c0 = engine.counters()
    rep = analysis.check(s)                   # the pending-stat array
    rep_src = analysis.check(m)               # the source carrying the group
    c1 = engine.counters()
    for k in ("misses", "aot_compiles", "dispatches"):
        assert c1[k] == c0[k], k
    assert rep.ok and rep.has("BLT009")
    assert rep.shape == (6, 4)
    assert np.dtype(rep.dtype) == np.float64
    assert rep_src.has("BLT009")
    txt = analysis.explain(s)
    assert "fusable terminal set" in txt and "ONE" in txt
    # the handles were NOT resolved by the check
    assert s._spending is not None and v._spending is not None
    # forecast on a streamed plan too
    src = _source(_intdata(), mesh, 4)
    h = src.sum()
    rep2 = analysis.check(h)
    assert rep2.has("BLT009")
    assert h._spending is not None


def test_strict_gate_still_fires_at_call(mesh):
    from bolt_tpu.tpu.array import BoltArrayTPU
    base = bolt.array(_x(seed=18), mesh)._data
    bad = BoltArrayTPU._deferred(
        base, (lambda v: v @ jnp.ones((99, 2)),), 1, mesh,
        jax.ShapeDtypeStruct((16, 2), np.float64))
    with analysis.strict():
        with pytest.raises(analysis.PipelineError, match="BLT001"):
            bad.sum()


# ---------------------------------------------------------------------
# int8 accumulate (ISSUE 8 satellite): the integer twin of bf16 —
# int8 values, int32 accumulator, integer additive terminals only
# ---------------------------------------------------------------------

def _xi(shape=(16, 6, 4)):
    # int8-range values (the documented contract)
    return ((np.arange(np.prod(shape)) % 101) - 50).astype(
        np.int32).reshape(shape)


def test_accumulate_int8_parity_locked_for_int_pipeline(mesh):
    xi = _xi()
    got = bolt.compute(bolt.array(xi, mesh).map(lambda v: v).sum(),
                       accumulate="int8")
    # the accumulate-in-i32 contract: int8 values, int32 accumulator —
    # the numpy oracle with the same dtypes is EXACT parity
    oracle = np.sum(xi.astype(np.int8), axis=0, dtype=np.int32)
    out = np.asarray(got.toarray())
    assert out.dtype == np.int32
    assert np.array_equal(out, oracle)


def test_accumulate_int8_fused_group_mixes_exact_order_stats(mesh):
    xi = _xi()
    m = bolt.array(xi, mesh).map(lambda v: v * 2)
    s, mn, mx = bolt.compute(m.sum(), m.min(), m.max(),
                             accumulate="int8")
    vals = xi * 2               # doubled values may exceed int8: wrap,
    #                             exactly like the cast contract says
    oracle = np.sum(vals.astype(np.int8), axis=0, dtype=np.int32)
    assert np.array_equal(np.asarray(s.toarray()), oracle)
    # order statistics are ALWAYS exact, whatever the accumulate mode
    assert np.array_equal(np.asarray(mn.toarray()), vals.min(axis=0))
    assert np.array_equal(np.asarray(mx.toarray()), vals.max(axis=0))


def test_accumulate_int8_leaves_float_pipelines_and_moments_exact(mesh):
    x = _x(seed=21)
    b = bolt.array(x, mesh).map(lambda v: v + 1)
    s, v = bolt.compute(b.sum(), b.var(), accumulate="int8")
    exact = bolt.array(x, mesh).map(lambda v: v + 1)
    assert _bits(s.toarray(), bolt.compute(exact.sum()).toarray())
    # an INT pipeline's moment terminals are float-valued: int8 must
    # not touch them either
    xi = _xi()
    mean8 = bolt.compute(bolt.array(xi, mesh).map(lambda v: v).mean(),
                         accumulate="int8")
    assert _bits(mean8.toarray(),
                 bolt.array(xi, mesh).mean().toarray())


def test_accumulate_int8_scope_and_stream_rejection(mesh):
    xi = _xi()
    with _precision.accumulate("int8"):
        got = bolt.compute(bolt.array(xi, mesh).map(lambda v: v).sum())
    assert np.asarray(got.toarray()).dtype == np.int32
    with pytest.raises(ValueError, match="in-memory"):
        bolt.compute(_source(_intdata(), mesh, 4).sum(),
                     accumulate="int8")


# ---------------------------------------------------------------------
# concurrency (ISSUE 8 satellite): try_join racing resolve, and
# lock-consistent fused-counter snapshots
# ---------------------------------------------------------------------

def test_try_join_racing_resolve_never_strands_a_member(mesh):
    import threading
    x = _x((32, 4), seed=5)
    oracle_sum = (x * 2).sum(axis=0)
    oracle_var = (x * 2).var(axis=0)
    for _ in range(20):                   # many interleavings
        b = bolt.array(x, mesh).map(lambda v: v * 2)
        first = b.sum()
        got = {}

        def reader():
            got["sum"] = np.asarray(first.toarray())   # resolves group

        def joiner():
            h = b.var()                   # try_join may hit a group
            got["var"] = np.asarray(h.toarray())       # mid-resolve

        ts = [threading.Thread(target=reader, daemon=True),
              threading.Thread(target=joiner, daemon=True)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(30)
        # whichever group each member landed in, both values are right
        assert np.allclose(got["sum"], oracle_sum)
        assert np.allclose(got["var"], oracle_var)


def test_fused_counter_snapshots_are_lock_consistent(mesh):
    import threading
    x = _x((8, 3), seed=9)
    c0 = engine.counters()
    stopped = threading.Event()
    bad = []

    def snapshotter():
        while not stopped.is_set():
            c = engine.counters()
            dg = c["fused_stat_groups"] - c0["fused_stat_groups"]
            dt = c["fused_stat_terminals"] - c0["fused_stat_terminals"]
            # every fused dispatch lands groups+terminals in ONE atomic
            # update (2 terminals per group here): a snapshot must never
            # interleave with a half-applied tally
            if dt != 2 * dg:
                bad.append((dg, dt))

    def hammer():
        for _ in range(10):
            m = bolt.array(x, mesh).map(lambda v: v + 3)
            bolt.compute(m.sum(), m.max())

    snap = threading.Thread(target=snapshotter, daemon=True)
    workers = [threading.Thread(target=hammer, daemon=True)
               for _ in range(3)]
    snap.start()
    for w in workers:
        w.start()
    for w in workers:
        w.join(120)
    stopped.set()
    snap.join(10)
    assert not bad
    c1 = engine.counters()
    assert c1["fused_stat_groups"] - c0["fused_stat_groups"] == 30
    assert c1["fused_stat_terminals"] - c0["fused_stat_terminals"] == 60


@pytest.mark.parametrize("terminal", [
    lambda b: b.map(lambda v: v + 1).sum().toarray(),
    lambda b: b.filter(lambda v: v.mean() > 0).sum().toarray(),
    lambda b: b.map(lambda v: v + 1).reduce(np.add).toarray(),
    lambda b: bolt.compute(b.sum(), b.var()),
], ids=["stat", "filter-stat", "reduce", "multi"])
def test_cached_programs_do_not_pin_the_base_buffer(mesh, terminal):
    # engine-cached closures hold geometry only: one that captured the
    # member handle kept group -> base alive in the cache, i.e. a
    # dropped 10 GB input stayed in HBM (seen on the chip, PR 21)
    import gc
    import weakref
    b = bolt.array(np.random.RandomState(7).randn(16, 4, 8), mesh)
    ref = weakref.ref(b._data)
    terminal(b)
    del b
    gc.collect()
    assert ref() is None


# ---------------------------------------------------------------------
# a deferred getitem window in front of the lazy statistics (ISSUE 25):
# the slice is part of the group's chain, traced inside its one program
# ---------------------------------------------------------------------

_WINDOWS = {
    "key_slice": lambda a: a[3:11],
    "value_slice": lambda a: a[:, 1:4, 1:],
    "int_on_value_axis": lambda a: a[:, 2],
    "slice_of_slice_after_map": lambda a: a.map(lambda v: v + 1)[2:14][1:9],
}


@pytest.mark.parametrize("name", STATS + ("ptp",))
@pytest.mark.parametrize("form", sorted(_WINDOWS))
def test_window_fused_bit_identical_to_standalone(mesh, form, name):
    x = np.abs(_x(seed=11)) * 0.25 + 0.5      # prod-safe magnitudes
    index = _WINDOWS[form]
    names = STATS + ("ptp",)
    want = np.asarray(getattr(index(bolt.array(x, mesh)), name)().toarray())
    oracle = np.asarray(getattr(index(bolt.array(x)), name)(axis=0))
    assert np.allclose(want, oracle)
    w = index(bolt.array(x, mesh))
    handles = {n: getattr(w, n)() for n in names}
    c0 = engine.counters()
    bolt.compute(*handles.values())
    c1 = engine.counters()
    # every statistic of the window from ONE program over the base (ptp
    # subtracts its pair in a second tiny one); the window counted when
    # the group took the chain, once
    assert c1["dispatches"] - c0["dispatches"] == 2
    assert c1["fused_stat_groups"] - c0["fused_stat_groups"] == 1
    assert _bits(handles[name].toarray(), want)


def test_compute_of_one_window_is_one_program(mesh):
    # geometry UNIQUE to this test so every engine key is fresh
    x = _x(shape=(24, 5, 7), seed=12)
    b = bolt.array(x, mesh)
    c0 = engine.counters()
    w = b[5:21]
    mean, std, lo, hi = bolt.compute(w.mean(), w.std(), w.min(), w.max())
    c1 = engine.counters()
    d = {k: c1[k] - c0[k] for k in c1}
    assert d["misses"] == 1 and d["aot_compiles"] == 1
    assert d["dispatches"] == 1                 # no getitem program
    assert d["getitems_fused"] == 1
    assert d["fused_stat_terminals"] == 4
    assert d["donations"] == 0
    for got, want in ((mean, x[5:21].mean(axis=0)), (std, x[5:21].std(axis=0)),
                      (lo, x[5:21].min(axis=0)), (hi, x[5:21].max(axis=0))):
        assert np.allclose(np.asarray(got.toarray()), want)
    # the same question at another position: a program of its own (the
    # start is static), still one launch
    c2 = engine.counters()
    bolt.compute(b[6:22].mean(), b[6:22].std())
    c3 = engine.counters()
    assert c3["dispatches"] - c2["dispatches"] == 2   # two windows, two groups
    w2 = b[6:22]
    c4 = engine.counters()
    bolt.compute(w2.mean(), w2.std())
    c5 = engine.counters()
    assert c5["dispatches"] - c4["dispatches"] == 1
    assert np.allclose(b.toarray(), x)          # the base stays readable


def test_fluent_stats_of_a_window_is_one_pass(mesh):
    x = _x(shape=(16, 3, 5), seed=13)
    b, lo = bolt.array(x, mesh), bolt.array(x)
    c0 = engine.counters()
    got = b[:, 1:].stats("sum", "var", "min")
    c1 = engine.counters()
    assert c1["dispatches"] - c0["dispatches"] == 1
    assert c1["getitems_fused"] - c0["getitems_fused"] == 1
    want = lo[:, 1:].stats("sum", "var", "min")
    for n in ("sum", "var", "min"):
        assert np.allclose(np.asarray(got[n].toarray()),
                           np.asarray(want[n]))


def test_check_forecasts_a_windowed_group_with_zero_compiles(mesh):
    x = _x(seed=14)
    w = bolt.array(x, mesh)[2:9].map(lambda v: v * 2)
    s = w.sum()
    c0 = engine.counters()
    rep = analysis.check(s)
    assert not [d for d in rep.diagnostics if d.severity == "error"]
    assert "getitem" in str(analysis.check(w))
    c1 = engine.counters()
    assert c1["aot_compiles"] == c0["aot_compiles"]
    assert c1["dispatches"] == c0["dispatches"]
    with analysis.strict():
        assert np.allclose(np.asarray(w.mean().toarray()),
                           (x[2:9] * 2).mean(axis=0))
