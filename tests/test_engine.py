"""Central dispatch engine tests: AOT executable cache + counters,
persistent on-disk compilation cache, donation-aware pipeline terminals,
and the fused single-pass filter→reduce path (ISSUE 1 tentpole)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bolt_tpu as bolt
from bolt_tpu import engine, profile


def _x():
    x = np.random.RandomState(0).randn(16, 6, 4)
    x[3] = np.nan          # a poison record the filters drop
    return x


PRED = lambda v: ~jnp.isnan(v).any() & (v.sum() > 0)


def _keep(x):
    return x[[bool(not np.isnan(v).any() and v.sum() > 0) for v in x]]


# ----------------------------------------------------------------------
# counters
# ----------------------------------------------------------------------

def test_counters_monotonic_and_hit_miss(mesh):
    b = bolt.array(_x(), mesh)
    f = lambda v: v * 2
    c0 = engine.counters()
    b.map(f).sum().toarray()
    c1 = engine.counters()
    # a fresh pipeline must MISS (new key) and dispatch at least once
    assert c1["misses"] > c0["misses"]
    assert c1["dispatches"] > c0["dispatches"]
    assert c1["dispatch_seconds"] >= c0["dispatch_seconds"]
    b.map(f).sum().toarray()
    c2 = engine.counters()
    # the identical pipeline must HIT (same key, no new build)
    assert c2["hits"] > c1["hits"]
    assert c2["misses"] == c1["misses"]
    # every counter is monotonic
    for k in c2:
        assert c2[k] >= c0[k], k


def test_aot_compiles_once_per_key(mesh):
    b = bolt.array(np.random.RandomState(1).randn(8, 5), mesh)
    f = lambda v: v + 3
    b.map(f).sum().toarray()
    c1 = engine.counters()
    for _ in range(3):
        b.map(f).sum().toarray()
    c2 = engine.counters()
    # three more identical dispatches: zero new XLA compiles
    assert c2["aot_compiles"] == c1["aot_compiles"]
    assert c2["dispatches"] >= c1["dispatches"] + 3


def test_counters_through_profile(mesh):
    bolt.ones((8, 3), mesh).sum().toarray()
    c = profile.engine_counters()
    for key in ("hits", "misses", "aot_compiles", "lower_seconds",
                "compile_seconds", "dispatches", "dispatch_seconds",
                "donations", "persistent_hits"):
        assert key in c
    txt = profile.engine_report()
    assert "aot_compiles" in txt and "compile_seconds" in txt


def test_cached_entries_stay_inspectable(mesh):
    # the HLO-contract tests read collectives out of cached entries:
    # engine entries must answer .lower like the jitted callables they wrap
    from bolt_tpu.tpu import array as array_mod
    b = bolt.array(np.random.RandomState(2).randn(8, 4), mesh)
    b.map(lambda v: v * 5).sum().toarray()
    fns = [v for k, v in array_mod._JIT_CACHE.items() if k[0] == "stat"]
    assert fns
    txt = fns[-1].lower(b._data).compile().as_text()
    assert txt  # lowered+compiled HLO text


# ----------------------------------------------------------------------
# persistent on-disk compilation cache
# ----------------------------------------------------------------------

def test_persistent_cache_roundtrip(tmp_path, mesh, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    d = str(tmp_path / "xla-cache")
    try:
        got = engine.persistent_cache(d)
        assert got == d
        assert engine.persistent_cache_dir() == d
        b = bolt.array(np.random.RandomState(3).randn(16, 8), mesh)
        b.map(lambda v: v * 7 + 1).sum().toarray()
        import os
        entries = os.listdir(d)
        if not entries:
            pytest.skip("backend does not serialize executables")
        # drop the engine's in-memory executables: the SAME program must
        # now load from disk (persistent hit) instead of recompiling
        engine.clear()
        h0 = engine.counters()["persistent_hits"]
        b.map(lambda v: v * 7 + 1).sum().toarray()
        assert engine.counters()["persistent_hits"] > h0
    finally:
        engine.persistent_cache(enable=False)
        assert engine.persistent_cache_dir() is None


# ----------------------------------------------------------------------
# donation-aware terminals
# ----------------------------------------------------------------------

def test_sole_owned_chain_donates_and_guards(mesh):
    x = _x()
    with engine.donation(0):
        d = bolt.array(x, mesh).map(lambda v: v + 1)   # parent is a temp
        n0 = engine.counters()["donations"]
        out = d.sum()
        assert engine.counters()["donations"] == n0 + 1
        assert np.allclose(np.asarray(out.toarray()),
                           (x + 1).sum(axis=0), equal_nan=True)
        # the consumed chain raises the existing donation guard
        with pytest.raises(RuntimeError, match="donated"):
            d.toarray()


def test_referenced_parent_never_donates(mesh):
    x = _x()
    with engine.donation(0):
        src = bolt.array(x, mesh)                      # parent stays live
        d = src.map(lambda v: v * 2)
        n0 = engine.counters()["donations"]
        d.sum().toarray()
        assert engine.counters()["donations"] == n0
        # both the parent and the deferred chain remain readable
        assert np.allclose(src.toarray(), x, equal_nan=True)
        assert np.allclose(d.toarray(), x * 2, equal_nan=True)


def test_clone_shared_chain_blocks_donation(mesh):
    # _clone (np.sort/np.rot90(k=0)/... return paths) shares the CHAIN
    # TUPLE with the original; donation must see the shared tuple and
    # refuse, or the clone would read a deleted buffer
    x = _x()
    with engine.donation(0):
        b = bolt.array(x, mesh).map(lambda v: v + 1)   # sole-owned base
        c = b._clone()
        n0 = engine.counters()["donations"]
        b.sum()
        assert engine.counters()["donations"] == n0
        assert np.allclose(c.toarray(), x + 1, equal_nan=True)


def test_zero_survivor_raise_leaves_donated_guard(mesh):
    # the donating fused program consumes the base BEFORE the
    # zero-survivor error: later reads must hit the guard, not the
    # deleted buffer
    x = _x()
    with engine.donation(0):
        f = bolt.array(x, mesh).filter(lambda v: v.sum() > 1e9)
        with pytest.raises(TypeError, match="empty"):
            f.reduce(np.add)
        with pytest.raises(RuntimeError, match="donated"):
            f.toarray()


def test_donation_floor_defaults_keep_small_arrays_readable(mesh):
    # below the floor nothing donates, so interactive reuse keeps working
    assert engine.donation_min_bytes() >= 1
    d = bolt.array(_x(), mesh).map(lambda v: v + 1)
    d.sum()
    d.mean()                                           # still readable
    assert d.toarray().shape == (16, 6, 4)


def test_donating_reduce_and_chunked_map(mesh):
    x = np.abs(_x())
    x[3] = 1.0                                         # drop the NaNs here
    with engine.donation(0):
        d = bolt.array(x, mesh).map(lambda v: v + 1)
        out = d.reduce(np.maximum)
        assert np.allclose(np.asarray(out.toarray()), (x + 1).max(axis=0))
        with pytest.raises(RuntimeError, match="donated"):
            d.cache()
        d2 = bolt.array(x, mesh).map(lambda v: v * 3)
        got = d2.chunk(size=(3,), axis=(0,)).map(lambda blk: blk * 2)
        assert np.allclose(got.unchunk().toarray(), x * 6)
        with pytest.raises(RuntimeError, match="donated"):
            d2.toarray()


# ----------------------------------------------------------------------
# fused single-pass filter→reduce
# ----------------------------------------------------------------------

def test_filter_stat_fuses_without_compaction(mesh):
    from bolt_tpu.tpu import array as array_mod
    x = _x()
    b = bolt.array(x, mesh)
    keep = _keep(x)
    n_compact = sum(1 for k in array_mod._JIT_CACHE
                    if k[0] == "filter-fused")
    out = b.filter(PRED).sum()
    got = np.asarray(out.toarray())       # first read dispatches (lazy)
    # ONE pass: the mask folded into the reduce — no compaction program
    assert sum(1 for k in array_mod._JIT_CACHE
               if k[0] == "filter-fused") == n_compact
    assert any(k[0] == "filter-stat" for k in array_mod._JIT_CACHE)
    assert np.allclose(got, keep.sum(axis=0))


@pytest.mark.parametrize("name", ["sum", "prod", "any", "all", "mean",
                                  "var", "std", "max", "min"])
def test_fused_filter_stat_parity(mesh, name):
    x = _x()
    b = bolt.array(x, mesh)
    keep = _keep(x)
    got = getattr(b.filter(PRED), name)()
    # the eager 3-pass oracle: resolve the compaction first, then reduce
    eager = b.filter(PRED)
    eager._resolve_fpending()
    want = getattr(eager, name)()
    assert np.allclose(np.asarray(got.toarray()),
                       np.asarray(want.toarray()), atol=1e-10)
    ref = getattr(keep, name)(axis=0) if hasattr(keep, name) else None
    if ref is not None:
        assert np.allclose(np.asarray(got.toarray()), ref, atol=1e-10)


def test_fused_filter_reduce_parity_and_nan_records(mesh):
    x = _x()                       # row 3 is NaN and must stay inert
    b = bolt.array(x, mesh)
    keep = _keep(x)
    got = b.filter(PRED).reduce(np.maximum)
    assert np.allclose(np.asarray(got.toarray()), np.maximum.reduce(keep))
    got2 = b.filter(PRED).reduce(lambda p, q: p + q)
    assert np.allclose(np.asarray(got2.toarray()), keep.sum(axis=0))


def test_fused_filter_all_false_mask(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    nothing = lambda v: v.sum() > 1e9
    assert np.allclose(np.asarray(b.filter(nothing).sum().toarray()),
                       np.zeros((6, 4)))
    assert np.isnan(np.asarray(b.filter(nothing).mean().toarray())).all()
    with pytest.raises(ValueError, match="zero-size"):
        b.filter(nothing).max()
    with pytest.raises(TypeError, match="empty"):
        b.filter(nothing).reduce(np.add)


def test_fused_filter_keepdims_and_ddof(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    keep = _keep(x)
    out = b.filter(PRED).sum(keepdims=True)
    assert np.asarray(out.toarray()).shape == (1, 6, 4)
    v = b.filter(PRED).var(ddof=1)
    assert np.allclose(np.asarray(v.toarray()), keep.var(axis=0, ddof=1),
                       atol=1e-8)


def test_deferred_filter_still_resolves_for_other_consumers(mesh):
    # non-reduction consumers get exactly the old pending semantics
    x = _x()
    b = bolt.array(x, mesh)
    keep = _keep(x)
    f = b.filter(PRED)
    assert f.pending
    assert f.dtype == x.dtype      # known without dispatching
    assert f.shape == keep.shape   # resolves
    assert not f.pending
    assert np.allclose(f.toarray(), keep)
    # toarray straight off the deferred state (batched fetch path)
    f2 = b.filter(PRED)
    assert np.allclose(f2.toarray(), keep)
    # map chains still consume filter output
    f3 = b.filter(PRED).map(lambda v: v * 2)
    assert np.allclose(f3.toarray(), keep * 2)


# ----------------------------------------------------------------------
# counters: consistent snapshots (ISSUE 2 satellite) + diagnostics feed
# ----------------------------------------------------------------------

def test_counters_snapshot_is_consistent_under_concurrent_increments():
    import threading
    n_threads, per_thread = 4, 500
    start = engine.counters()["diagnostics"]
    seen = []
    stop = threading.Event()

    def snapshotter():
        while not stop.is_set():
            seen.append(engine.counters()["diagnostics"])

    def hammer():
        for _ in range(per_thread):
            engine.record_diagnostics(1)

    snap = threading.Thread(target=snapshotter)
    snap.start()
    workers = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    stop.set()
    snap.join()
    # lock-protected increments: nothing lost, snapshots monotonic
    assert engine.counters()["diagnostics"] == start + n_threads * per_thread
    assert seen == sorted(seen)
    # and counters() returns a SNAPSHOT, not a live view
    c = engine.counters()
    c["diagnostics"] += 10 ** 6
    assert engine.counters()["diagnostics"] != c["diagnostics"]


def test_engine_counters_include_analysis_tallies(mesh):
    c = engine.counters()
    for key in ("diagnostics", "strict_checks", "strict_rejections"):
        assert key in c
    txt = profile.engine_report()
    assert "diagnostics" in txt and "strict_rejections" in txt


def test_fused_filter_donates_sole_owned_base(mesh):
    x = _x()
    keep = _keep(x)
    with engine.donation(0):
        d = bolt.array(x, mesh).filter(PRED)
        n0 = engine.counters()["donations"]
        out = d.sum()
        assert engine.counters()["donations"] == n0 + 1
        assert np.allclose(np.asarray(out.toarray()), keep.sum(axis=0))
        with pytest.raises(RuntimeError, match="donated"):
            d.toarray()


# ---------------------------------------------------------------------
# cross-tenant coalescing (ISSUE 8): concurrent identical builds and
# compiles collapse to ONE, counter-proven
# ---------------------------------------------------------------------

def test_concurrent_same_key_builds_coalesce(mesh):
    import threading
    import time as _time
    calls = []

    def builder():
        calls.append(1)
        _time.sleep(0.3)          # widen the race window: every other
        #                           thread must arrive mid-build
        return jax.jit(lambda t: t + 1)

    key = ("test-coalesce-build", object())
    c0 = engine.counters()
    outs = []

    def go():
        outs.append(engine.get(key, builder))

    threads = [threading.Thread(target=go, daemon=True) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    c1 = engine.counters()
    assert len(calls) == 1                    # the builder ran ONCE
    assert all(o is outs[0] for o in outs)    # everyone shares the entry
    assert c1["misses"] - c0["misses"] == 1
    # every lookup is accounted exactly once: 1 miss + 5 waits/hits
    assert (c1["hits"] - c0["hits"]
            + c1["coalesced_builds"] - c0["coalesced_builds"]) == 5


def test_concurrent_same_signature_compiles_once(mesh):
    import threading
    key = ("test-coalesce-compile", object())
    entry = engine.get(key, lambda: jax.jit(lambda t: t * 3))
    x = jnp.arange(8.0)
    c0 = engine.counters()
    outs = []

    def go():
        outs.append(np.asarray(entry(x)))

    threads = [threading.Thread(target=go, daemon=True) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    c1 = engine.counters()
    # ONE aot compile for the signature, however many threads raced it
    assert c1["aot_compiles"] - c0["aot_compiles"] == 1
    assert all(np.array_equal(o, np.arange(8.0) * 3) for o in outs)


def test_failed_build_wakes_waiters_who_rebuild(mesh):
    import threading
    import time as _time
    state = {"n": 0}

    def flaky_builder():
        state["n"] += 1
        if state["n"] == 1:
            _time.sleep(0.2)
            raise RuntimeError("first build fails")
        return jax.jit(lambda t: t - 1)

    key = ("test-coalesce-fail", object())
    results = []

    def go():
        try:
            results.append(engine.get(key, flaky_builder))
        except RuntimeError as exc:
            results.append(exc)

    threads = [threading.Thread(target=go, daemon=True) for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    # exactly one caller saw the failure; the waiters rebuilt and share
    # a live entry (no thread hangs on the dead build's event)
    errs = [r for r in results if isinstance(r, RuntimeError)]
    live = [r for r in results if not isinstance(r, RuntimeError)]
    assert len(errs) == 1 and len(live) == 2
    assert live[0] is live[1]


# ---------------------------------------------------------------------
# per-tenant counter scoping (ISSUE 8)
# ---------------------------------------------------------------------

def test_tenant_scope_mirrors_engine_counters(mesh):
    t0 = engine.tenant_counters("unit-tenant")
    g0 = engine.counters()
    with engine.tenant("unit-tenant"):
        bolt.ones((8, 4), mesh).map(lambda v: v + 1).sum().toarray()
    t1 = engine.tenant_counters("unit-tenant")
    g1 = engine.counters()
    assert t1["dispatches"] > t0["dispatches"]
    # the tenant's tally is a SUBSET of the global one — never more
    assert t1["dispatches"] - t0["dispatches"] \
        <= g1["dispatches"] - g0["dispatches"]
    # outside the scope, nothing mirrors
    t2 = engine.tenant_counters("unit-tenant")
    bolt.ones((8, 4), mesh).sum().toarray()
    assert engine.tenant_counters("unit-tenant") == t2


# ---------------------------------------------------------------------
# var/std of real floating data dispatched in the one-pass form
# (ISSUE 61, bolt_tpu/tpu/moments.py)
# ---------------------------------------------------------------------

def _ask_var(b):
    b.var().toarray()


def _ask_std_of_a_window(b):
    b[2:10].std(axis=(0, 1, 2)).toarray()


def _ask_a_group(b):
    m = b.map(lambda v: v + 1)
    bolt.compute(m.var(), m.std(ddof=1), m.sum(), m.max())


def _ask_mean_and_sum(b):
    bolt.compute(b.mean(), b.sum())


def _ask_var_behind_a_filter(b):
    # the filter-folded terminal keeps its own (unshifted) one-pass fold
    b.filter(lambda v: v.sum() > 0).var().toarray()


@pytest.mark.parametrize("ask,dtype,launches", [
    (_ask_var, np.float32, 1),
    (_ask_var, np.float64, 1),
    (_ask_std_of_a_window, np.float32, 1),
    (_ask_a_group, np.float32, 2),
    (_ask_var, np.int32, 0),
    (_ask_var, np.complex64, 0),
    (_ask_mean_and_sum, np.float32, 0),
    (_ask_var_behind_a_filter, np.float32, 0),
], ids=["var-f32", "var-f64", "window-std", "group-of-two", "var-int32",
        "var-complex64", "no-moment", "behind-a-filter"])
def test_one_pass_moment_launches_counts_dispatched_terminals(
        mesh, ask, dtype, launches):
    x = (np.random.RandomState(5).randn(16, 6, 4) * 8).astype(dtype)
    b = bolt.array(x, mesh)
    assert "one_pass_moment_launches" in engine._SCHEMA
    c0 = engine.counters()["one_pass_moment_launches"]
    ask(b)
    assert engine.counters()["one_pass_moment_launches"] - c0 == launches
    ask(b)                                # a cached program counts again
    assert engine.counters()["one_pass_moment_launches"] - c0 == 2 * launches
