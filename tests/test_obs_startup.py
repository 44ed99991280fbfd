"""Start-up accounting (PR 34): what importing, tracing, lowering, reading
the on-disk cache and compiling cost, process-wide and always on, the
per-program compile log, and the leak gate that no longer costs ``begin``
a lock.

The counters are fed by ``jax.monitoring``'s duration events, each as SELF
time on its thread, so they add up: a second is in one of them or in none.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bolt_tpu as bolt
from bolt_tpu import engine, obs
from bolt_tpu.obs import trace as obs_trace

TRACE = "/jax/core/compile/jaxpr_trace_duration"
MLIR = "/jax/core/compile/jaxpr_to_mlir_module_duration"
READ = "/jax/compilation_cache/cache_retrieval_time_sec"
BACKEND = "/jax/core/compile/backend_compile_duration"

NEW_KEYS = {
    "import_seconds": float, "trace_seconds": float, "mlir_seconds": float,
    "persistent_read_seconds": float, "backend_compile_seconds": float,
    "compile_requests": int, "stream_compile_seconds": float,
}
PHASES = ("trace_seconds", "mlir_seconds", "persistent_read_seconds",
          "backend_compile_seconds", "compile_requests")


def fresh(mesh, salt, width=5):
    """``run()`` launches a program no other test has compiled (the body
    is a new function, so a new engine key) over an array that is there
    already: the only compile a first ``run()`` makes is its own."""
    arr = bolt.ones((8, width), mesh)
    arr.toarray()

    def body(v):
        return v * salt + 1

    return lambda: arr.map(body).sum().toarray()


def delta(before, keys=PHASES):
    after = engine.counters()
    return {k: after[k] - before[k] for k in keys}


def on_a_new_thread(fn):
    """``fn()`` where no earlier event of this process is on the thread's
    tally."""
    out = []
    th = threading.Thread(target=lambda: out.append(fn()))
    th.start()
    th.join(timeout=60)
    assert not th.is_alive() and out
    return out[0]


@pytest.mark.parametrize("key", sorted(NEW_KEYS))
def test_the_key_is_in_the_engine_group_with_its_type(key):
    c = engine.counters()
    assert type(c[key]) is NEW_KEYS[key]
    assert obs.registry().snapshot()["engine.%s" % key] == c[key]


def test_importing_the_package_was_timed():
    assert 0.0 < engine.counters()["import_seconds"] < 120.0


def test_a_cold_compile_is_xla_and_a_warm_one_is_a_read(tmp_path, mesh,
                                                       monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        engine.persistent_cache(str(tmp_path / "cache"))
        run = fresh(mesh, 34.01)
        c0 = engine.counters()
        run()
        cold = delta(c0)
        row = engine.compile_log()[-1]
        if row["cache"] == "off":
            pytest.skip("backend does not serialize executables")
        assert row["cache"] == "miss" and row["read_s"] == 0.0
        assert cold["backend_compile_seconds"] > 0
        assert cold["persistent_read_seconds"] == 0.0
        assert cold["compile_requests"] >= 1
        assert cold["trace_seconds"] > 0 and cold["mlir_seconds"] > 0

        engine.clear()
        jax.clear_caches()
        c1 = engine.counters()
        run()
        warm = delta(c1)
        row = engine.compile_log()[-1]
        assert row["cache"] == "hit" and row["read_s"] > 0
        assert row["read_s"] <= row["compile_s"]
        assert warm["persistent_read_seconds"] == pytest.approx(
            row["read_s"])
        # what a hit leaves of the backend compile beside its read (the
        # cache key's hashing) is small change beside XLA's own work
        assert warm["backend_compile_seconds"] \
            < 0.5 * cold["backend_compile_seconds"]
        assert warm["trace_seconds"] > 0    # every process lowers again
    finally:
        engine.persistent_cache(enable=False)


def test_a_jit_outside_the_engine_is_a_compile_request_and_no_aot_compile():
    x = jnp.ones((3, 7))
    c0 = engine.counters()
    n0 = len(engine.compile_log())
    jax.jit(lambda x: jnp.cos(x) * 34.02)(x).block_until_ready()
    c1 = engine.counters()
    assert c1["compile_requests"] == c0["compile_requests"] + 1
    assert c1["backend_compile_seconds"] > c0["backend_compile_seconds"]
    assert c1["trace_seconds"] > c0["trace_seconds"]
    assert c1["aot_compiles"] == c0["aot_compiles"]
    assert c1["compile_seconds"] == c0["compile_seconds"]
    assert len(engine.compile_log()) == n0


def test_a_row_names_the_program_on_the_spans_clock(mesh):
    run = fresh(mesh, 34.03)
    t_before = obs.clock()
    d_before = engine.counters()["dispatches"]
    run()
    t_after = obs.clock()
    row = engine.compile_log()[-1]
    assert set(row) == {"family", "program", "lower_s", "compile_s",
                        "cache", "read_s", "t0", "dispatches"}
    assert row["family"] == "stat"
    assert len(row["program"]) == 12
    int(row["program"], 16)
    assert t_before < row["t0"] < t_after
    assert row["t0"] + row["lower_s"] + row["compile_s"] < t_after
    assert row["lower_s"] > 0 and row["compile_s"] > 0
    assert row["cache"] in ("hit", "miss", "off")
    assert row["dispatches"] == d_before     # compiled by the next one
    # the rows are the caller's own
    row["family"] = "mine"
    assert engine.compile_log()[-1]["family"] == "stat"


def test_a_program_reads_the_same_again_and_another_reads_otherwise(mesh):
    # two shapes of one function, then the first compiled a second time
    runs = [fresh(mesh, 34.04, width) for width in (6, 9)]
    for run in runs:
        run()
    engine.clear()
    runs[0]()
    a, b, again = engine.compile_log()[-3:]
    assert a["family"] == b["family"] == again["family"] == "stat"
    assert a["program"] != b["program"]
    assert again["program"] == a["program"]
    # another statistic over the same array: one argument signature, two
    # programs (the digest holds the key; two functions of ONE qualified
    # name, as two closures of a factory are, do read alike)
    arr = bolt.ones((8, 6), mesh)
    arr.map(lambda v: v * 34.045).max().toarray()
    assert engine.compile_log()[-1]["program"] not in (a["program"],
                                                       b["program"])


def test_a_warm_dispatch_touches_no_start_up_counter_and_logs_no_row(mesh):
    run = fresh(mesh, 34.05)
    run()
    c0 = engine.counters()
    log0 = engine.compile_log()
    run()
    c1 = engine.counters()
    assert c1["dispatches"] > c0["dispatches"]
    for key in NEW_KEYS:
        assert c1[key] == c0[key], key
    assert engine.compile_log() == log0


def test_the_log_is_bounded_and_keeps_the_newest():
    saved = engine.compile_log()
    size = engine._COMPILE_LOG.maxlen
    try:
        for i in range(size + 7):
            engine._log_compile(("fam%d" % i,), ("sig", i), 1.0, 2.0, 4.0,
                                (engine._TALLY.hits, engine._TALLY.misses,
                                 engine._TALLY.read))
        log = engine.compile_log()
        assert len(log) == size == 512
        assert log[0]["family"] == "fam7"
        assert log[-1]["family"] == "fam%d" % (size + 6)
        assert log[-1]["lower_s"] == 1.0 and log[-1]["compile_s"] == 2.0
        assert log[-1]["cache"] == "off"
    finally:
        engine._COMPILE_LOG.clear()
        engine._COMPILE_LOG.extend(saved)


def test_reset_counters_drops_the_log_with_them(mesh):
    saved_log = engine.compile_log()
    saved = engine.counters()
    try:
        fresh(mesh, 34.06)()
        assert engine.compile_log()
        engine.reset_counters()
        assert engine.compile_log() == []
        assert engine.counters()["compile_requests"] == 0
    finally:
        # later suites read running totals: give them back
        engine._COUNTERS.update(**saved)
        engine._COMPILE_LOG.extend(saved_log)


def test_lower_and_compile_spans_name_the_family_and_the_cache(mesh):
    run = fresh(mesh, 34.07)
    obs.clear()
    obs.enable()
    try:
        run()
    finally:
        obs.disable()
    by_name = {}
    for sp in obs.spans():
        by_name.setdefault(sp.name, []).append(sp)
    lower, = by_name["engine.lower"]
    comp, = by_name["engine.compile"]
    assert lower.attrs == {"family": "stat"}
    assert comp.attrs == {"family": "stat",
                          "cache": engine.compile_log()[-1]["cache"]}
    assert obs.active_count() == 0
    obs.clear()


# -- the listener's arithmetic, on events of known length --------------

def feed(monkeypatch, events):
    """Hand ``engine._on_duration`` ``(clock at arrival, event, seconds)``
    rows on a new thread; returns the counters' deltas."""
    now = [0.0]
    monkeypatch.setattr(engine, "_clock", lambda: now[0])

    def run():
        c0 = engine.counters()
        for at, event, seconds in events:
            now[0] = at
            engine._on_duration(event, seconds, fun_name="f")
        return delta(c0)
    return on_a_new_thread(run)


def test_a_trace_inside_a_trace_is_counted_once(monkeypatch):
    # jnp.sin, jnp.add traced while the caller's function is: three events,
    # the outer one last and holding the two
    got = feed(monkeypatch, [(100.2, TRACE, 0.1), (100.5, TRACE, 0.2),
                             (100.6, TRACE, 0.6)])
    assert got["trace_seconds"] == pytest.approx(0.6)


def test_short_traces_in_a_row_inside_one_are_not_taken_for_each_other(
        monkeypatch):
    # what `jit(lambda x: sin(x) * 2 + 1)` fires: three sub-millisecond
    # traces end to end, then the function's own that holds them
    got = feed(monkeypatch, [(100.00040, TRACE, 0.00038),
                             (100.00082, TRACE, 0.00040),
                             (100.00110, TRACE, 0.00026),
                             (100.00190, TRACE, 0.00185)])
    assert got["trace_seconds"] == pytest.approx(0.00185)


def test_events_side_by_side_are_each_counted(monkeypatch):
    got = feed(monkeypatch, [(100.2, TRACE, 0.1), (100.5, TRACE, 0.2),
                             (100.9, MLIR, 0.3), (101.0, "/jax/other", 9.0)])
    assert got["trace_seconds"] == pytest.approx(0.3)
    assert got["mlir_seconds"] == pytest.approx(0.3)
    assert got["compile_requests"] == 0


def test_a_backend_compile_is_counted_less_the_read_inside_it(monkeypatch):
    got = feed(monkeypatch, [(200.30, READ, 0.25), (200.35, BACKEND, 0.4),
                             (203.0, BACKEND, 2.0)])
    assert got["persistent_read_seconds"] == pytest.approx(0.25)
    assert got["backend_compile_seconds"] == pytest.approx(0.15 + 2.0)
    assert got["compile_requests"] == 2


def test_a_trace_inside_the_lowering_leaves_the_lowering_the_rest(
        monkeypatch):
    # a lowering rule that traces (lower_fun): mlir holds a trace event
    got = feed(monkeypatch, [(300.4, TRACE, 0.1), (300.5, MLIR, 0.5)])
    assert got["trace_seconds"] == pytest.approx(0.1)
    assert got["mlir_seconds"] == pytest.approx(0.4)


def test_phases_since_is_the_threads_own_and_whole_events(monkeypatch):
    now = [0.0]
    monkeypatch.setattr(engine, "_clock", lambda: now[0])

    def run():
        for at, event, seconds in [(10.0, TRACE, 1.0), (20.5, READ, 0.25),
                                   (21.0, BACKEND, 1.0), (22.0, MLIR, 0.5)]:
            now[0] = at
            engine._on_duration(event, seconds)
        return (engine._phases_since(19.0), engine._phases_since(21.2),
                engine._phases_since(0.0), engine._phases_since(30.0))
    assert on_a_new_thread(run) == pytest.approx((1.5, 0.5, 2.5, 0.0))
    assert on_a_new_thread(lambda: engine._phases_since(0.0)) == 0.0


# -- a streamed run's own compile ----------------------------------------

def test_a_first_pass_compiles_inside_its_wall_and_says_how_long(mesh):
    x = np.arange(64 * 6, dtype=np.float64).reshape(64, 6)

    def body(v):
        return v * 34.08

    def one_pass():
        src = bolt.fromcallback(lambda idx: x[idx], x.shape, mesh,
                                dtype=np.float64, chunks=8)
        return src.map(body).sum().toarray()

    keys = ("stream_wall_seconds", "stream_compile_seconds",
            "trace_seconds", "mlir_seconds", "persistent_read_seconds",
            "backend_compile_seconds")
    c0 = engine.counters()
    first = one_pass()
    d1 = delta(c0, keys)
    assert 0 < d1["stream_compile_seconds"] < d1["stream_wall_seconds"]
    # no second of the run's compiles that the process-wide phases lack
    assert d1["stream_compile_seconds"] <= 1e-6 + sum(
        d1[k] for k in keys[2:])
    c1 = engine.counters()
    np.testing.assert_array_equal(one_pass(), first)
    d2 = delta(c1, keys)
    assert d2["stream_wall_seconds"] > 0
    assert d2["stream_compile_seconds"] == 0.0


# -- the leak gate without begin's lock ----------------------------------

class CountingLock:
    def __init__(self, inner):
        self.inner, self.takes = inner, 0

    def __enter__(self):
        self.takes += 1
        return self.inner.__enter__()

    def __exit__(self, *exc):
        return self.inner.__exit__(*exc)


def test_a_span_is_one_lock_take(monkeypatch):
    obs.clear()
    lock = CountingLock(obs_trace._LOCK)
    monkeypatch.setattr(obs_trace, "_LOCK", lock)
    obs.enable()
    try:
        sp = obs.begin("startup.probe")
        assert lock.takes == 1               # enable()'s own
        assert obs.active_count() == 1
        obs.end(sp)
        assert obs.active_count() == 0
        assert lock.takes == 4               # end's one, the two looks
    finally:
        obs.disable()
    obs.clear()


def test_the_leak_gate_counts_across_threads_clears_and_looks():
    obs.clear()
    obs.enable()
    try:
        assert [obs.active_count() for _ in range(3)] == [0, 0, 0]
        held = [obs.begin("startup.a"), obs.begin("startup.b")]
        other = on_a_new_thread(lambda: obs.begin("startup.c"))
        assert [obs.active_count() for _ in range(3)] == [3, 3, 3]
        obs.cancel(other)
        assert obs.active_count() == 2
        obs.clear()                          # open spans are forgotten ...
        assert obs.active_count() == 0
        late = obs.begin("startup.d")
        for sp in held:                      # ... and end cleanly later,
            obs.end(sp)                      # without going below zero
        assert obs.active_count() == 1
        obs.end(late)
        assert obs.active_count() == 0
        assert obs.totals()["startup.d"]["count"] == 1
    finally:
        obs.disable()
    obs.clear()
