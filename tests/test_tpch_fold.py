"""TPC-H Q6 and Q1 through the normal path (ISSUE 30): ``filter -> map ->
sum`` and ``filter -> group -> aggregates`` over a keyed table of thin
records, each ONE program and one launch, against a NumPy int64 reference
and against ``mode='local'``; the filter deferred at every size, a
record-wise map on it deferred too, and the survivors built as an array
only for a consumer that needs them."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bolt_tpu as bolt
import bolt_tpu.tpu.array as array_mod
from bolt_tpu import engine, ops

DATE, QTY, PRICE, DISC, TAX, FLAG, STATUS = range(7)
GROUPS = 6


def q6_pred(r):
    return ((r[DATE] >= 731) & (r[DATE] < 1096) & (r[DISC] >= 5)
            & (r[DISC] <= 7) & (r[QTY] < 24))


def q6_value(r):
    return r[PRICE] * r[DISC]


def q1_pred(r):
    return r[DATE] <= 2436


def q1_group(r):
    return (3 * r[STATUS] + r[FLAG]).astype(np.int32)


def q1_terms(r):
    disc_price = r[PRICE] * (100 - r[DISC])
    return (r[QTY], r[PRICE], disc_price, disc_price * (100 + r[TAX]),
            r[DISC], r[DATE] * 0 + 1)


def nothing(r):
    return r[DATE] < 0


def table(n, seed=0, dtype=np.float32):
    """``n`` seeded rows of the seven columns, integers held as floats."""
    rng = np.random.default_rng(seed)
    cols = [rng.integers(0, 2600, n), rng.integers(1, 51, n),
            rng.integers(90000, 10_494_951, n), rng.integers(0, 11, n),
            rng.integers(0, 9, n), rng.integers(0, 3, n),
            rng.integers(0, 2, n)]
    return np.stack(cols, axis=1).astype(dtype)


def q6_exact(x):
    r = x.astype(np.int64)
    keep = ((r[:, DATE] >= 731) & (r[:, DATE] < 1096) & (r[:, DISC] >= 5)
            & (r[:, DISC] <= 7) & (r[:, QTY] < 24))
    return int((r[keep, PRICE] * r[keep, DISC]).sum())


def q1_exact(x, pred_date=2436):
    r = x.astype(np.int64)
    sums = np.zeros((GROUPS, 6), np.int64)
    counts = np.zeros(GROUPS, np.int64)
    gid = 3 * r[:, STATUS] + r[:, FLAG]
    for g in range(GROUPS):
        rows = r[(r[:, DATE] <= pred_date) & (gid == g)]
        dp = rows[:, PRICE] * (100 - rows[:, DISC])
        sums[g] = [rows[:, QTY].sum(), rows[:, PRICE].sum(), dp.sum(),
                   (dp * (100 + rows[:, TAX])).sum(), rows[:, DISC].sum(),
                   len(rows)]
        counts[g] = len(rows)
    return sums, counts


def run_q1(b, pred=q1_pred):
    sums, counts = ops.segment_reduce(
        b.filter(pred), labels=q1_group, num_segments=GROUPS,
        value=q1_terms, return_counts=True)
    return (np.stack([np.asarray(s.toarray()) for s in sums], axis=1),
            np.asarray(counts.toarray()))


def close(got, want):
    """Float32 sums of integer terms: within 2e-6 of the exact answer."""
    want = np.asarray(want, np.float64)
    return np.all(np.abs(np.asarray(got, np.float64) - want)
                  <= 2e-6 * np.maximum(np.abs(want), 1.0))


@pytest.fixture(scope="module")
def mesh():
    return jax.sharding.Mesh(np.array(jax.devices()), ("k",))


@pytest.fixture(scope="module")
def mesh1():
    return jax.sharding.Mesh(np.array(jax.devices()[:1]), ("k",))


def delta(c0, *names):
    c1 = engine.counters()
    return tuple(c1[k] - c0[k] for k in names)


COUNTED = ("dispatches", "filters_fused", "filter_compactions")


# n: a multiple of the eight devices (sharded), of 128, and neither
@pytest.mark.parametrize("n", [1024, 1003, 8 * 131])
@pytest.mark.parametrize("which", ["one", "eight"])
def test_q6_is_exact_and_matches_local(mesh, mesh1, which, n):
    x = table(n, seed=n)
    b = bolt.array(x, context=mesh if which == "eight" else mesh1,
                   axis=(0,))
    got = b.filter(q6_pred).map(q6_value).sum().toarray()
    assert close(got, q6_exact(x))
    local = bolt.array(x).filter(q6_pred).map(q6_value).sum()
    assert close(got, np.asarray(local))
    assert np.asarray(got).shape == np.asarray(local).shape == ()


@pytest.mark.parametrize("n", [1024, 1003, 8 * 131])
@pytest.mark.parametrize("which", ["one", "eight"])
def test_q1_is_exact_and_matches_local(mesh, mesh1, which, n):
    x = table(n, seed=n + 1)
    b = bolt.array(x, context=mesh if which == "eight" else mesh1,
                   axis=(0,))
    sums, counts = run_q1(b)
    want_sums, want_counts = q1_exact(x)
    assert close(sums, want_sums)
    assert counts.dtype == np.int32 and np.array_equal(counts, want_counts)
    # the sixth sum is the count again, as a float: exact below 2**24
    assert np.array_equal(sums[:, 5], want_counts)
    lsums, lcounts = run_q1(bolt.array(x))
    assert close(sums, lsums)
    assert lcounts.dtype == np.int32 and np.array_equal(lcounts, counts)


# enough rows for the fold to bind ``thin_fold`` (ISSUE 31, tpu/fold.py):
# on this CPU mesh, one device or eight, the primitive lowers to the same
# expressions as before and places no kernel
@pytest.mark.parametrize("which", ["one", "eight"])
def test_thin_records_bind_the_fold_primitive_and_the_cpu_keeps_the_fusion(
        mesh, mesh1, which):
    from bolt_tpu.tpu import fold as tf
    n = 8 * 1024 + 8
    x = table(n, seed=31)
    b = bolt.array(x, context=mesh if which == "eight" else mesh1,
                   axis=(0,))
    fp = b.filter(q6_pred).map(q6_value)._fpending.geometry()
    spec = tf.Fold(fp, (("sum", (0,), False, None),))
    assert "thin_fold" in str(jax.make_jaxpr(
        lambda data: tf.fold_records(spec, data))(x))
    c0 = engine.counters()["fold_kernel_programs"]
    got = b.filter(q6_pred).map(q6_value).sum().toarray()
    assert close(got, q6_exact(x))
    sums, counts = run_q1(b)
    want_sums, want_counts = q1_exact(x)
    assert close(sums, want_sums)
    assert counts.dtype == np.int32 and np.array_equal(counts, want_counts)
    mean = b.filter(q1_pred).mean().toarray()
    assert close(mean, x[x[:, DATE] <= 2436].mean(axis=0, dtype=np.float64))
    kept = b.filter(q1_pred)
    total, top = bolt.compute(kept.sum(), kept.var())     # one fused group
    rows = x[x[:, DATE] <= 2436].astype(np.float64)
    assert close(total.toarray(), rows.sum(axis=0))
    assert np.allclose(top.toarray(), rows.var(axis=0), rtol=1e-3)
    assert engine.counters()["fold_kernel_programs"] == c0


def test_q1_empty_group_group_of_one_and_a_predicate_that_keeps_nothing(
        mesh):
    x = table(515, seed=7)
    x[:, FLAG] = np.where(x[:, FLAG] == 2, 0, x[:, FLAG])   # groups 2, 5 empty
    x[:, STATUS] = 0                                        # groups 3, 4 too
    x[100, FLAG], x[100, STATUS] = 1, 1                     # group 4: one row
    x[100, DATE] = 5
    b = bolt.array(x, context=mesh, axis=(0,))
    sums, counts = run_q1(b)
    want_sums, want_counts = q1_exact(x)
    assert list(want_counts[[2, 3, 4, 5]]) == [0, 0, 1, 0]
    assert np.array_equal(counts, want_counts) and close(sums, want_sums)
    assert np.all(sums[[2, 3, 5]] == 0)
    assert sums[4, 0] == x[100, QTY] and sums[4, 1] == x[100, PRICE]
    # nothing survives: every sum 0, every count 0, no error
    sums, counts = run_q1(b, nothing)
    assert np.all(sums == 0) and np.all(counts == 0)
    assert counts.dtype == np.int32
    assert b.filter(nothing).map(q6_value).sum().toarray() == 0
    lsums, lcounts = run_q1(bolt.array(x), nothing)
    assert np.all(lsums == 0) and np.all(lcounts == 0)


@pytest.mark.parametrize("cap", [1 << 30, 0], ids=["below", "above"])
def test_each_query_dispatches_one_program_and_builds_no_buffer(
        mesh, monkeypatch, cap):
    """Above and below the size at which a compaction changes form (the
    size test patched down): neither query reaches it."""
    monkeypatch.setattr(array_mod, "_FILTER_FUSED_MAX_BYTES", cap)
    x = table(2048, seed=3)
    b = bolt.array(x, context=mesh, axis=(0,))
    b.filter(q6_pred).map(q6_value).sum().toarray()      # compiled once
    run_q1(b)
    c0 = engine.counters()
    lazy = b.filter(q6_pred).map(q6_value).sum()
    assert delta(c0, *COUNTED) == (0, 0, 0)              # nothing launched
    got = lazy.toarray()
    assert delta(c0, *COUNTED) == (1, 1, 0)
    assert close(got, q6_exact(x))
    c0 = engine.counters()
    sums, counts = ops.segment_reduce(
        b.filter(q1_pred), labels=q1_group, num_segments=GROUPS,
        value=q1_terms, return_counts=True)
    assert delta(c0, *COUNTED) == (1, 1, 0)
    assert delta(c0, "aot_compiles") == (0,)


def test_a_map_on_a_deferred_filter_stays_deferred(mesh):
    x = table(300, seed=5)
    b = bolt.array(x, context=mesh, axis=(0,))
    c0 = engine.counters()
    f = b.filter(q6_pred)
    m = f.map(q6_value)
    m2 = m.map(lambda v: v * 2, dtype=np.float64)
    assert f.pending and m.pending and m2.pending
    assert m.dtype == np.float32 and m2.dtype == np.float64
    assert delta(c0, "dispatches", "filter_compactions") == (0, 0)
    assert "?" in repr(m)
    # every terminal of the family folds the map in
    keep = x[np.asarray([bool(q6_pred(r)) for r in x])]
    vals = keep[:, PRICE].astype(np.float64) * keep[:, DISC]
    assert close(m.mean().toarray(), vals.mean())
    assert close(m2.sum().toarray(), 2 * vals.sum())
    assert m.max().toarray() == vals.max()
    assert close(m.reduce(jnp.add).toarray(), vals.sum())
    assert delta(c0, "filter_compactions") == (0,)
    # a map with keys numbers the SURVIVORS and does not commute: it
    # resolves the filter, as it did
    wk = f.map(lambda kv: kv[1][QTY] + kv[0][0], with_keys=True)
    assert np.allclose(wk.toarray(), keep[:, QTY] + np.arange(len(keep)))
    assert delta(c0, "filter_compactions") == (1,)


@pytest.mark.parametrize("cap", [1 << 30, 0], ids=["padded", "two-phase"])
def test_a_consumer_that_needs_the_survivors_still_gets_them(
        mesh, monkeypatch, cap):
    monkeypatch.setattr(array_mod, "_FILTER_FUSED_MAX_BYTES", cap)
    x = table(257, seed=9)
    b = bolt.array(x, context=mesh, axis=(0,))
    keep = np.asarray([bool(q1_pred(r)) for r in x])
    c0 = engine.counters()
    out = b.map(lambda r: r + 1).filter(q1_pred).map(lambda r: r * 2)
    assert out.pending and delta(c0, "filter_compactions") == (0,)
    # the predicate read the mapped record: dates moved by one
    keep1 = x[:, DATE] + 1 <= 2436
    assert out.shape == (keep1.sum(), 7)
    assert delta(c0, "filter_compactions", "filters_fused") == (1, 0)
    assert np.array_equal(out.toarray(), (x[keep1] + 1) * 2)
    plain = b.filter(q1_pred)
    assert np.array_equal(plain.toarray(), x[keep])
    assert np.array_equal(plain.toarray(), bolt.array(x).filter(q1_pred))


def test_a_label_function_on_a_plain_and_a_mapped_array(mesh):
    x = table(640, seed=11)
    b = bolt.array(x, context=mesh, axis=(0,))
    gid = (3 * x[:, STATUS] + x[:, FLAG]).astype(np.int64)
    # no filter, no value: the records themselves, every op
    for op in ("sum", "mean", "max", "min"):
        got, counts = ops.segment_reduce(b, q1_group, GROUPS, op=op,
                                         return_counts=True)
        want = ops.segment_reduce(b, gid, GROUPS, op=op)
        assert np.allclose(got.toarray(), want.toarray(), rtol=1e-6)
        assert np.array_equal(counts.toarray(), np.bincount(gid,
                                                            minlength=6))
        local = ops.segment_reduce(bolt.array(x), q1_group, GROUPS, op=op)
        assert np.allclose(got.toarray(), np.asarray(local), rtol=1e-6)
    # behind a deferred map chain, an array-valued value, a label outside
    # the groups joining none
    got = ops.segment_reduce(
        b.map(lambda r: r + 1), lambda r: r[FLAG].astype(np.int32) - 1,
        num_segments=2, value=lambda r: r[:3])
    want = np.stack([(x[x[:, FLAG] == g, :3] + 1).sum(axis=0)
                     for g in (0, 1)])
    assert got.shape == (2, 3) and close(got.toarray(), want)
    # counts with a label ARRAY too
    out, counts = ops.segment_reduce(b, gid, GROUPS, return_counts=True)
    assert np.array_equal(counts.toarray(), np.bincount(gid, minlength=6))
    assert counts.dtype == np.int32


def test_what_the_label_path_refuses():
    x = table(64, seed=2)
    for b in (bolt.array(x), bolt.array(x, context=jax.sharding.Mesh(
            np.array(jax.devices()), ("k",)), axis=(0,))):
        with pytest.raises(ValueError, match="num_segments"):
            ops.segment_reduce(b, q1_group)
        with pytest.raises(ValueError, match="label ARRAY"):
            ops.segment_reduce(b, q1_group, GROUPS, method="matmul")
        with pytest.raises(ValueError, match="label function"):
            ops.segment_reduce(b, np.zeros(64, np.int64), 1,
                               value=q1_terms)
    with pytest.raises(ValueError, match="one integer per record"):
        ops.segment_reduce(b, lambda r: r[QTY], GROUPS)


def test_the_fold_launches_under_its_spans(mesh):
    """``array.filter_stat`` around every launch that folds a filter into
    a terminal (``op=``), ``group.segment_reduce`` around the grouped
    fold's with the former beneath it, ``array.filter`` around a
    compaction alone: what ``filter_stat_us`` and ``group_fold_us`` read."""
    from bolt_tpu import obs
    x = table(512, seed=13)
    b = bolt.array(x, context=mesh, axis=(0,))
    obs.enable()
    try:
        obs.clear()
        b.filter(q6_pred).map(q6_value).sum().toarray()
        run_q1(b)
        b.filter(q6_pred).map(q6_value).reduce(jnp.add).toarray()
        bolt.compute(*[getattr(b.filter(q1_pred), n)()
                       for n in ("sum",)] + [b.filter(q6_pred).max()])
        spans = list(obs.spans())
        b.filter(q1_pred).toarray()
        later = list(obs.spans())[len(spans):]
    finally:
        obs.disable()
    folds = [sp for sp in spans if sp.name == "array.filter_stat"]
    assert [sp.attrs["op"] for sp in folds] == ["sum", "sum", "reduce",
                                                 "max", "sum"]
    groups = [sp for sp in spans if sp.name == "group.segment_reduce"]
    assert len(groups) == 1 and groups[0].attrs["segments"] == GROUPS
    assert folds[1].pid == groups[0].sid
    assert not [sp for sp in spans if sp.name == "array.filter"]
    assert [sp.name for sp in later if sp.name.startswith("array.filter")] \
        == ["array.filter"]
