"""Multi-tenant serving layer (ISSUE 8): scheduler, arbiter, admission.

The load-bearing contracts: N tenants submitting identical pipelines
get BIT-IDENTICAL results to their single-tenant runs while compiling
exactly once across all of them (engine build/compile coalescing); the
device-memory arbiter keeps concurrent streams inside ONE process-wide
bytes budget (fair round-robin across tenants, in-order per stream,
degrading to a shallower pipeline — never a deadlock — when the budget
is smaller than a run's full ring); admission control rejects or
queues by policy, with BLT010 refusing pipelines that could never fit;
and every tenant's engine/obs counters are scoped so per-tenant bytes
and wait times are attributable.
"""

import threading
import time

import numpy as np
import pytest

import bolt_tpu as bolt
from bolt_tpu import analysis, engine, serve
from bolt_tpu.obs import metrics as _metrics

pytestmark = pytest.mark.serve


ADD1 = lambda v: v + 1    # hoisted: tenants must SHARE stage callables
#                           for cross-tenant executable coalescing


def _x(shape=(64, 8, 4)):
    return np.arange(np.prod(shape), dtype=np.float32).reshape(shape)


def _pipeline(x, mesh, chunks=16):
    src = bolt.fromcallback(lambda idx: x[idx], x.shape, mesh,
                            dtype=np.float32, chunks=chunks)
    return src.map(ADD1).sum()


@pytest.fixture(autouse=True)
def _no_leaked_server():
    yield
    assert serve.active() is None, "a test leaked an active server"


# ---------------------------------------------------------------------
# the scheduler: submit, futures, results
# ---------------------------------------------------------------------

def test_submit_array_pipeline_and_callable(mesh):
    x = _x()
    ref = (x + 1).sum(axis=0)
    with serve.serving(workers=2) as sv:
        f1 = sv.submit(_pipeline(x, mesh), tenant="a")
        f2 = sv.submit(lambda: 41 + 1, tenant="b")
        out = f1.result(timeout=60)
        assert np.allclose(np.asarray(out.toarray()), ref)
        assert f2.result(timeout=60) == 42
        assert f1.done() and f2.done()
        assert f1.wait_seconds >= 0.0 and f1.run_seconds > 0.0


def test_submit_rejects_non_pipelines(mesh):
    with serve.serving(workers=1) as sv:
        with pytest.raises(TypeError):
            sv.submit(42)


def test_future_delivers_the_pipeline_exception(mesh):
    def boom():
        raise ValueError("tenant bug")
    with serve.serving(workers=1) as sv:
        f = sv.submit(boom, tenant="a")
        with pytest.raises(ValueError, match="tenant bug"):
            f.result(timeout=60)
        assert isinstance(f.exception(), ValueError)


def test_module_level_submit_lazy_default_server(mesh):
    try:
        f = serve.submit(lambda: "ok")
        assert f.result(timeout=60) == "ok"
        assert serve.active() is not None
    finally:
        serve.stop()


def test_start_refuses_a_second_server(mesh):
    with serve.serving(workers=1):
        with pytest.raises(RuntimeError, match="already active"):
            serve.start()


# ---------------------------------------------------------------------
# the acceptance contract: N tenants, bit-identical, ONE compile
# ---------------------------------------------------------------------

def test_tenants_bit_identical_to_single_tenant_run(mesh):
    x = _x()
    ref = np.asarray(_pipeline(x, mesh).toarray())     # single-tenant run
    with serve.serving(workers=4) as sv:
        futs = [sv.submit(_pipeline(x, mesh), tenant="t%d" % i)
                for i in range(4)]
        outs = [f.result(timeout=120) for f in futs]
    for out in outs:
        got = np.asarray(out.toarray())
        assert got.dtype == ref.dtype and np.array_equal(got, ref)


def test_n_identical_tenants_compile_exactly_once(mesh):
    x = _x()
    _pipeline(x, mesh).toarray()          # warm python paths
    engine.clear()
    c0 = engine.counters()
    with serve.serving(workers=4) as sv:
        futs = [sv.submit(_pipeline(x, mesh), tenant="t%d" % i)
                for i in range(4)]
        [f.result(timeout=120) for f in futs]
    c1 = engine.counters()
    four = {k: c1[k] - c0[k] for k in ("misses", "aot_compiles")}
    engine.clear()
    c0 = engine.counters()
    _pipeline(x, mesh).toarray()
    c1 = engine.counters()
    one = {k: c1[k] - c0[k] for k in ("misses", "aot_compiles")}
    # the coalescing proof: 4 concurrent cold tenants build and compile
    # EXACTLY what one cold tenant does
    assert four == one, (four, one)


def test_per_tenant_engine_counters_scoped(mesh):
    x = _x()
    t0 = {t: engine.tenant_counters(t)["transfer_bytes"]
          for t in ("scoped-a", "scoped-b")}
    with serve.serving(workers=2) as sv:
        fa = sv.submit(_pipeline(x, mesh), tenant="scoped-a")
        fb = sv.submit(_pipeline(x, mesh), tenant="scoped-b")
        fa.result(timeout=120)
        fb.result(timeout=120)
        st = sv.stats()
    for t in ("scoped-a", "scoped-b"):
        moved = engine.tenant_counters(t)["transfer_bytes"] - t0[t]
        assert moved == x.nbytes, (t, moved)     # the whole ingest, ONCE
        assert st["tenants"][t]["completed"] == 1
        assert st["tenants"][t]["transfer_bytes"] >= x.nbytes


def test_tenant_scope_nests_and_restores(mesh):
    assert engine.current_tenant() is None
    with engine.tenant("outer"):
        assert engine.current_tenant() == "outer"
        with engine.tenant("inner"):
            assert engine.current_tenant() == "inner"
        assert engine.current_tenant() == "outer"
    assert engine.current_tenant() is None


# ---------------------------------------------------------------------
# the device-memory arbiter
# ---------------------------------------------------------------------

def test_arbiter_grants_fifo_within_round_robin_across_tenants():
    arb = serve.DeviceArbiter(10)
    assert arb.acquire(10, "hold")
    order = []
    threads = []

    def waiter(name, tenant):
        assert arb.acquire(10, tenant)
        order.append(name)
        arb.release(10)

    # enqueue a1, a2 (tenant A) then b1 (tenant B), deterministically
    for name, tenant in (("a1", "A"), ("a2", "A"), ("b1", "B")):
        th = threading.Thread(target=waiter, args=(name, tenant),
                              daemon=True)
        th.start()
        threads.append(th)
        deadline = time.time() + 5
        while arb.waiting() < len(threads) and time.time() < deadline:
            time.sleep(0.005)
    assert arb.waiting() == 3
    arb.release(10)
    for th in threads:
        th.join(timeout=10)
    # round-robin ACROSS tenants: A's head, then B's, then A's second
    assert order == ["a1", "b1", "a2"]
    assert arb.in_use() == 0


def test_arbiter_oversized_request_runs_alone():
    arb = serve.DeviceArbiter(100)
    assert arb.acquire(1000, "big")       # larger than the whole budget
    assert arb.in_use() == 1000
    got = []
    th = threading.Thread(target=lambda: got.append(arb.acquire(10, "s")),
                          daemon=True)
    th.start()
    time.sleep(0.05)
    assert not got                        # blocked while the giant holds
    arb.release(1000)
    th.join(timeout=10)
    assert got == [True]
    arb.release(10)


def test_arbiter_large_request_survives_sustained_small_traffic():
    # the anti-starvation barrier: a near-budget request must be seated
    # even while another tenant streams small acquisitions continuously
    arb = serve.DeviceArbiter(100)
    stop = threading.Event()
    got = []

    def small_traffic():
        while not stop.is_set():
            if arb.acquire(10, "chatty", stop=stop):
                time.sleep(0.001)
                arb.release(10)

    chatty = [threading.Thread(target=small_traffic, daemon=True)
              for _ in range(2)]
    for th in chatty:
        th.start()
    time.sleep(0.05)                       # traffic established

    def big():
        got.append(arb.acquire(90, "big"))
    th = threading.Thread(target=big, daemon=True)
    th.start()
    th.join(30)                            # bounded starvation
    stop.set()
    for c in chatty:
        c.join(10)
    assert got == [True]
    arb.release(90)


def test_close_wait_true_drains_leased_jobs(mesh):
    # a queued IN-MEMORY job blocked on the arbiter while a clean
    # close(wait=True) runs must complete, not fail as "cancelled" —
    # only close(wait=False) may abort a pending lease wait
    x = _x((32, 8, 4))
    pipe = bolt.array(x, mesh).map(ADD1).sum()
    est = analysis.working_set_bytes(pipe)
    assert est and est > 0
    sv = serve.start(workers=2, budget_bytes=est + 40, queue_limit=8)
    try:
        def holder():
            lease = sv.arbiter.lease("holder")
            assert lease.acquire(est + 30)     # leaves < est available
            time.sleep(0.4)
            lease.close()
            return "held"
        f1 = sv.submit(holder, tenant="a")
        time.sleep(0.1)                        # holder owns the budget
        f2 = sv.submit(bolt.array(x, mesh).map(ADD1).sum(), tenant="b")
    finally:
        serve.stop(wait=True)                  # drain DURING f2's wait
    assert f1.result(timeout=10) == "held"
    out = f2.result(timeout=10)                # ran after the drain
    assert np.allclose(np.asarray(out.toarray()), (x + 1).sum(axis=0))
    arb = serve.DeviceArbiter(10)
    assert arb.acquire(10, "hold")
    stop = threading.Event()
    out = []
    th = threading.Thread(
        target=lambda: out.append(arb.acquire(10, "w", stop=stop)),
        daemon=True)
    th.start()
    time.sleep(0.05)
    stop.set()
    th.join(timeout=10)
    assert out == [False] and arb.waiting() == 0
    arb.release(10)


def test_lease_close_returns_outstanding_bytes():
    arb = serve.DeviceArbiter(100)
    lease = arb.lease("t")
    assert lease.acquire(60) and lease.acquire(30)
    lease.release(40)
    assert arb.in_use() == 50 and lease.outstanding() == 50
    lease.close()
    assert arb.in_use() == 0
    lease.close()                          # idempotent
    lease.release(10 ** 9)                 # clamped, never negative
    assert arb.in_use() == 0


def _reset_arbiter_high_water():
    # serve metrics are process-cumulative (registry semantics, like the
    # engine counters); reset the high-water gauge so THIS test's bound
    # is what gets asserted
    g = _metrics.registry().gauge("serve.arbiter_in_use_high_water")
    g.reset()
    return g


def test_streamed_run_respects_budget_smaller_than_ring(mesh):
    # budget below slab x ring: the starvation valve must shallow the
    # pipeline, not deadlock; result stays bit-exact and in-use bytes
    # never pass the budget
    x = _x((64, 8, 4))
    ref = (x + 1).sum(axis=0)
    slab_bytes = 16 * 8 * 4 * 4
    hw = _reset_arbiter_high_water()
    with serve.serving(workers=1, budget_bytes=slab_bytes + 1) as sv:
        out = sv.submit(_pipeline(x, mesh, chunks=16),
                        tenant="tight").result(timeout=120)
    assert np.allclose(np.asarray(out.toarray()), ref)
    assert 0 < hw.value <= slab_bytes + 1


def test_concurrent_streams_share_the_budget(mesh):
    x = _x((64, 8, 4))
    ref = (x + 1).sum(axis=0)
    hw = _reset_arbiter_high_water()
    with serve.serving(workers=3, budget_bytes=x.nbytes) as sv:
        futs = [sv.submit(_pipeline(x, mesh), tenant="t%d" % i)
                for i in range(3)]
        outs = [f.result(timeout=120) for f in futs]
    assert 0 < hw.value <= x.nbytes        # never past the global budget
    for out in outs:
        assert np.allclose(np.asarray(out.toarray()), ref)


# ---------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------

def test_reject_policy_raises_when_queue_full(mesh):
    gate = threading.Event()
    with serve.serving(workers=1, queue_limit=1, policy="reject") as sv:
        r0 = sv.stats()["totals"]["rejected"]   # counters are cumulative
        running = threading.Event()

        def slow():
            running.set()
            gate.wait(30)
            return "slow"
        f1 = sv.submit(slow, tenant="a")
        assert running.wait(10)            # worker busy; queue empty
        f2 = sv.submit(lambda: "queued", tenant="a")   # fills the queue
        with pytest.raises(serve.AdmissionError, match="queue is full"):
            sv.submit(lambda: "over", tenant="a")
        gate.set()
        assert f1.result(timeout=60) == "slow"
        assert f2.result(timeout=60) == "queued"
        st = sv.stats()
        assert st["totals"]["rejected"] - r0 == 1
        assert st["queue_depth"] == 0


def test_queue_policy_blocks_submitter_until_room(mesh):
    gate = threading.Event()
    with serve.serving(workers=1, queue_limit=1, policy="queue") as sv:
        running = threading.Event()

        def slow():
            running.set()
            gate.wait(30)
            return "slow"
        sv.submit(slow, tenant="a")
        assert running.wait(10)
        sv.submit(lambda: 1, tenant="a")   # fills the bounded queue
        done = []

        def blocked_submit():
            done.append(sv.submit(lambda: 2, tenant="a"))
        th = threading.Thread(target=blocked_submit, daemon=True)
        th.start()
        time.sleep(0.1)
        assert not done                    # backpressure: submit blocked
        gate.set()
        th.join(timeout=30)
        assert done and done[0].result(timeout=60) == 2


def test_blt010_rejects_impossible_pipeline_and_check_forecasts(mesh):
    x = _x((64, 8, 4))   # ONE slab of 8 KB: can never degrade into 4 KB
    with serve.serving(workers=1, budget_bytes=4096) as sv:
        r0 = sv.stats()["totals"]["rejected"]
        arr = _pipeline(x, mesh, chunks=64)
        rep = analysis.check(arr)
        assert rep.has("BLT010") and not rep.ok
        with pytest.raises(serve.AdmissionError, match="BLT010"):
            sv.submit(arr, tenant="a")
        assert sv.stats()["totals"]["rejected"] - r0 == 1
        # a slab-shrunk twin of the same pipeline IS admissible: the
        # floor is the slab, not the ring
        small = _pipeline(x, mesh, chunks=8)
        assert not analysis.check(small).has("BLT010")
        out = sv.submit(small, tenant="a").result(timeout=120)
        assert np.allclose(np.asarray(out.toarray()), (x + 1).sum(axis=0))
    # without a serving arbiter the same pipeline checks clean
    rep = analysis.check(_pipeline(x, mesh, chunks=64))
    assert not rep.has("BLT010")


def test_working_set_estimates(mesh):
    from bolt_tpu import stream as _stream
    x = _x((64, 8, 4))
    src = bolt.fromcallback(lambda idx: x[idx], x.shape, mesh,
                            dtype=np.float32, chunks=16)
    ring = _stream.fold_ring(src._stream)
    assert ring == (_stream.prefetch_depth() + _stream._FOLD_WINDOW_STEP
                    + _stream.pool_size(src._stream))
    est = analysis.working_set_bytes(src.map(ADD1))
    assert est == 16 * 8 * 4 * 4 * ring
    b = bolt.array(x, mesh).map(ADD1)
    assert analysis.working_set_bytes(b) == 2 * x.nbytes
    assert analysis.working_set_bytes(np.ones(3)) is None


def test_close_without_wait_fails_pending_jobs(mesh):
    gate = threading.Event()
    sv = serve.start(workers=1, queue_limit=4)
    try:
        running = threading.Event()

        def slow():
            running.set()
            gate.wait(30)
        sv.submit(slow, tenant="a")
        assert running.wait(10)
        f2 = sv.submit(lambda: 2, tenant="a")
        gate.set()
    finally:
        serve.stop(wait=False)
    with pytest.raises(RuntimeError):
        f2.result(timeout=60)
    with pytest.raises(RuntimeError, match="closed"):
        sv.submit(lambda: 3)


# ---------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------

def test_serve_metrics_and_no_leaked_spans(mesh):
    from bolt_tpu import obs
    x = _x()
    obs.clear()
    obs.enable()
    try:
        with serve.serving(workers=2) as sv:
            futs = [sv.submit(_pipeline(x, mesh), tenant="m%d" % i)
                    for i in range(3)]
            [f.result(timeout=120) for f in futs]
        assert obs.active_count() == 0     # every serve.run span closed
        names = [s.name for s in obs.spans()]
        assert "serve.run" in names
        reg = _metrics.registry().snapshot()
        assert reg["serve.completed"] >= 3
        assert reg["serve.queue_wait_seconds.hist"]["count"] >= 3
    finally:
        obs.disable()


def test_concurrent_streamed_runs_overlap(mesh):
    # the load-generator contract at test scale: tenants whose ingest
    # has storage-class latency must OVERLAP under the scheduler.  A
    # count, not a clock: how many RUNS have a read in flight at once
    # (one run's own uploader pool reads several slabs at a time, so
    # reads alone would not tell a serial run from a served one).
    x = _x((48, 8, 4))
    lock = threading.Lock()
    reading = {}                           # run -> its reads in flight
    most = [0]

    def make(run):
        def read(idx):
            with lock:
                reading[run] = reading.get(run, 0) + 1
                most[0] = max(most[0], len(reading))
            time.sleep(0.02)
            with lock:
                reading[run] -= 1
                if not reading[run]:
                    del reading[run]
            return x[idx]
        src = bolt.fromcallback(read, x.shape, mesh, dtype=np.float32,
                                chunks=4)
        return src.map(ADD1).sum()

    ref = (x + 1).sum(axis=0)
    for run in range(3):
        assert np.array_equal(np.asarray(make(run).toarray()), ref)
    assert most[0] == 1                    # one after another
    with serve.serving(workers=3) as sv:
        futs = [sv.submit(make(run), tenant="t%d" % run)
                for run in range(3)]
        for f in futs:
            assert np.array_equal(
                np.asarray(f.result(timeout=120).toarray()), ref)
    assert most[0] >= 2 and not reading    # at least two at once


# ---------------------------------------------------------------------
# fault policy (ISSUE 9): tenant-failure isolation, per-submit
# retries= / deadline=
# ---------------------------------------------------------------------

def test_tenant_stream_failure_returns_lease_and_isolates(mesh):
    # ONE tenant's streamed pipeline dies mid-run: its future carries
    # the original error, its arbiter lease bytes come back, and the
    # OTHER tenants' futures are untouched
    x = _x()
    boom = RuntimeError("tenant-a storage died")
    fired = []

    def flaky(idx):
        fired.append(idx)
        if len(fired) >= 2:
            raise boom
        return x[idx]

    ref = (x + 1).sum(axis=0)
    with serve.serving(workers=2, budget_bytes=64 << 20) as sv:
        bad = bolt.fromcallback(flaky, x.shape, mesh, dtype=np.float32,
                                chunks=16).map(ADD1).sum()
        fa = sv.submit(bad, tenant="iso-a")
        fbs = [sv.submit(_pipeline(x, mesh), tenant="iso-b")
               for _ in range(3)]
        with pytest.raises(RuntimeError, match="storage died"):
            fa.result(timeout=120)
        for f in fbs:                      # neighbours unaffected
            assert np.allclose(np.asarray(f.result(timeout=120)
                                          .toarray()), ref)
        st = sv.stats()
        assert st["arbiter"]["in_use_bytes"] == 0   # lease returned
        assert st["tenants"]["iso-a"]["failed"] == 1
        assert st["tenants"]["iso-b"]["completed"] == 3
        assert st["tenants"]["iso-b"]["failed"] == 0


def test_submit_retries_reruns_and_counts(mesh):
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ValueError("transient %d" % len(calls))
        return "ok"

    with serve.serving(workers=1) as sv:
        f = sv.submit(flaky, tenant="r", retries=2)
        assert f.result(timeout=60) == "ok"
        assert len(calls) == 3
        st = sv.stats()
        assert st["totals"]["retried"] == 2
        assert st["tenants"]["r"]["retried"] == 2
        assert st["tenants"]["r"]["completed"] == 1


def test_submit_retries_exhausted_chains_attempts(mesh):
    def always():
        raise ValueError("still broken")

    with serve.serving(workers=1) as sv:
        f = sv.submit(always, tenant="r", retries=1)
        exc = f.exception(timeout=60)
    assert isinstance(exc, RuntimeError) and "after 1 retries" in str(exc)
    assert isinstance(exc.__cause__, ValueError)          # final attempt
    assert isinstance(exc.__cause__.__cause__, ValueError)  # original


def test_submit_deadline_expires_in_queue(mesh):
    release = threading.Event()

    def blocker():
        release.wait(30)
        return 1

    with serve.serving(workers=1) as sv:
        f1 = sv.submit(blocker, tenant="x")
        f2 = sv.submit(lambda: 2, tenant="x", deadline=0.05)
        time.sleep(0.2)                    # the deadline passes queued
        release.set()
        assert f1.result(timeout=60) == 1
        with pytest.raises(serve.DeadlineError,
                           match="before the job started"):
            f2.result(timeout=60)
        st = sv.stats()
        assert st["totals"]["expired"] == 1
        assert st["totals"]["failed"] >= 1


def test_submit_deadline_stops_retries(mesh):
    calls = []

    def failing():
        calls.append(1)
        time.sleep(0.08)
        raise ValueError("attempt %d" % len(calls))

    with serve.serving(workers=1) as sv:
        f = sv.submit(failing, tenant="d", retries=50, deadline=0.1)
        exc = f.exception(timeout=60)
    assert isinstance(exc, (ValueError, RuntimeError))
    assert len(calls) < 50                 # the deadline cut retries off


def test_submit_deadline_validation(mesh):
    with serve.serving(workers=1) as sv:
        with pytest.raises(ValueError, match="positive"):
            sv.submit(lambda: 1, deadline=0)


# ---------------------------------------------------------------------
# weighted fair share (ISSUE 10 satellite)
# ---------------------------------------------------------------------

def test_weights_validation(mesh):
    with pytest.raises(ValueError, match="positive integer"):
        serve.Server(workers=1, weights={"a": 0}).close()


def _ordered_pops(weights, jobs):
    """Submit ``jobs`` (a list of tenant tags) while ONE worker is held
    on a blocker job, release, and return the order the scheduler ran
    them in — the weighted-round-robin observable."""
    order = []
    gate = threading.Event()

    def blocker():
        gate.wait(30)

    def tagged(t):
        return lambda: order.append(t)

    with serve.serving(workers=1, weights=weights) as sv:
        hold = sv.submit(blocker, tenant="hold")
        time.sleep(0.15)              # the worker is inside blocker now
        futs = [sv.submit(tagged(t), tenant=t) for t in jobs]
        gate.set()
        hold.result(timeout=60)
        for f in futs:
            f.result(timeout=60)
    return order


def test_default_weights_keep_round_robin_order():
    # a then b queued; weight 1 each -> strict alternation (bit-for-bit
    # the pre-weights scheduler)
    order = _ordered_pops(None, ["a"] * 4 + ["b"] * 4)
    assert order == ["a", "b", "a", "b", "a", "b", "a", "b"]


def test_weighted_fair_share_serves_weight_jobs_per_turn():
    # weight 3 vs 1: each rotation serves up to 3 of a's jobs, then one
    # of b's — the integer-credit generalisation
    order = _ordered_pops({"a": 3}, ["a"] * 6 + ["b"] * 2)
    assert order == ["a", "a", "a", "b", "a", "a", "a", "b"]


def test_weighted_fair_share_starvation_freedom():
    # a floods with a big weight; b (weight 1) is still served within
    # ONE rotation — at most weight(a) pops after the turn starts
    order = _ordered_pops({"a": 5}, ["a"] * 12 + ["b"])
    assert "b" in order
    assert order.index("b") <= 5, order


def test_weight_turn_forfeited_when_queue_drains():
    # a has weight 3 but only 2 jobs: its turn ends early, b runs next
    order = _ordered_pops({"a": 3}, ["a", "a", "b", "b"])
    assert order == ["a", "a", "b", "b"]


# ---------------------------------------------------------------------
# fleet-warm start (ROADMAP item 4 remainder)
# ---------------------------------------------------------------------

def test_start_warm_serves_first_request_without_fresh_compiles(
        mesh, tmp_path, monkeypatch):
    """A pre-seeded persistent cache + Server(start_warm=dir): the
    warmed server's first request re-lowers but runs ZERO fresh XLA
    compiles (persistent_misses flat), and every disk-served compile is
    counted as a persistent_warm_hits."""
    import os
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cache = str(tmp_path / "warm-xla")
    x = _x((32, 8, 4))

    def make():
        return bolt.array(x, mesh).map(ADD1).sum()

    try:
        # seed: an earlier process ran the fleet's pipeline shape
        # (clear first — an identical program compiled earlier in THIS
        # suite would otherwise serve from the in-memory cache and
        # never reach the disk layer)
        engine.clear()
        engine.persistent_cache(cache)
        np.asarray(make().toarray())
        if not os.listdir(cache):
            pytest.skip("backend does not serialize executables")
        engine.persistent_cache(enable=False)

        # "fresh process": drop the in-memory executables, then serve
        # with start_warm -- the first request must hit disk only
        engine.clear()
        c0 = engine.counters()
        with serve.serving(workers=1, start_warm=cache) as sv:
            assert sv.warm_dir == cache
            out = sv.submit(make(), tenant="w").result(timeout=120)
        c1 = engine.counters()
        assert np.allclose(np.asarray(out.toarray()),
                           (x + 1).sum(axis=0))
        assert c1["persistent_warm_hits"] > c0["persistent_warm_hits"]
        assert c1["persistent_misses"] == c0["persistent_misses"], \
            "warm start paid a fresh XLA compile"
        assert c1["aot_compiles"] > c0["aot_compiles"]
    finally:
        engine.persistent_cache(enable=False)
