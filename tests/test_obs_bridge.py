"""bolt's own spans on the profiler's clock (PR 24).

* the BRIDGE — spans record while ``obs.enable()`` is in force or a
  ``jax.profiler`` session is live, and in a live session each lands in
  the xplane's host plane as ``bolt.<name>`` with its attributes and
  ``rid``, the uploader thread's on that thread's own line; with neither,
  ``begin`` returns ``None`` and allocates nothing;
* what a span RECORDS — ``rid`` across the explicit ``parent=`` hand-off,
  running totals that survive a wrapped ring, self seconds less children;
* the FETCH — ``toarray`` in each of its branches is ``array.fetch`` with
  exactly ``force`` / ``wait`` / ``copy`` beneath it, and leaks no span
  when any of them raises; ``cache()`` is a fetch of the one phase;
* the ENGINE — ``engine.lookup`` on hit and miss, ``engine.dispatch`` with
  ``engine.signature`` and ``engine.enqueue`` beneath it;
* ``scripts/host_gaps.py``'s arithmetic on a hand-made trace.
"""

import glob
import importlib.util
import os
import sys
import threading

import numpy as np
import pytest

import jax

import bolt_tpu as bolt
from bolt_tpu import engine, obs
from bolt_tpu.obs import trace as obs_trace

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _tracer_reset():
    obs.disable()
    obs.clear()
    yield
    obs.disable()
    obs.clear()


def host_lines(logdir):
    """``[(line index, [(name, stats), ...])]`` of the host plane's lines
    that hold ``bolt.*`` events."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for i, line in enumerate(plane.lines):
            evs = [(ev.name, dict(ev.stats)) for ev in line.events
                   if ev.name.startswith("bolt.")]
            if evs:
                out.append((i, evs))
    return out


def children(sp, name):
    """Names of the direct children of the one span called ``name``."""
    parents = [s for s in sp if s.name == name]
    assert len(parents) == 1, [s.name for s in sp]
    return [s.name for s in sorted(sp, key=lambda s: s.t0)
            if s.pid == parents[0].sid]


# ----------------------------------------------------------------------
# the bridge
# ----------------------------------------------------------------------

def test_off_path_returns_none_and_allocates_nothing():
    assert not obs.enabled()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    begin, end = obs.begin, obs.end
    for _ in range(200):                    # warm every cache on the path
        end(begin("engine.dispatch"))
    before = sys.getallocatedblocks()
    for _ in range(5000):
        sp = begin("engine.dispatch")
        end(sp)
    after = sys.getallocatedblocks()
    assert sp is None
    assert after - before < 20              # not one block a call
    assert obs.spans() == [] and obs.totals() == {}


def test_a_profiler_session_arms_the_tracer_and_disarms_it(tmp_path):
    assert obs.begin("before") is None
    with jax.profiler.trace(str(tmp_path)):
        assert not obs.enabled()            # armed by the session alone
        with obs.span("inside", answer=42) as sp:
            sp.set(late="yes")
        obs.event("mark", n=1)
    assert obs.begin("after") is None
    assert [s.name for s in obs.spans()] == ["inside", "mark"]
    assert obs.active_count() == 0
    (_, evs), = host_lines(str(tmp_path))
    got = dict(evs)
    rid = obs.spans()[0].rid
    assert got["bolt.inside"] == {"rid": rid, "answer": 42, "late": "yes"}
    assert got["bolt.mark"]["n"] == 1       # an instant: zero-length event


def test_enable_alone_opens_no_annotation(monkeypatch):
    opened = []
    monkeypatch.setattr(obs_trace, "_ANNOTATE",
                        lambda name, **kw: opened.append(name))
    obs.enable()
    obs.end(obs.begin("quiet"))
    assert [s.name for s in obs.spans()] == ["quiet"] and opened == []


def test_bridge_stand_in_sees_begin_end_and_cancel():
    """``trace.py`` knows the profiler only through ``set_bridge``."""
    log = []

    class Ann:
        def __init__(self, name, **stats):
            log.append(("open", name, stats))

        def set_metadata(self, **stats):
            log.append(("meta", stats))

        def __exit__(self, *exc):
            log.append(("close",))

    saved = obs_trace._LIVE, obs_trace._ANNOTATE
    live = [True]
    obs_trace.set_bridge(lambda: live[0], Ann)
    try:
        sp = obs.begin("a", x=1)
        obs.end(sp, y=2)
        obs.cancel(obs.begin("b"))
        live[0] = False
        assert obs.begin("c") is None
    finally:
        obs_trace.set_bridge(*saved)
    assert log == [("open", "bolt.a", {"rid": sp.rid}),
                   ("meta", {"x": 1, "y": 2}), ("close",),
                   ("open", "bolt.b", {"rid": sp.rid + 1}), ("close",)]
    assert [s.name for s in obs.spans()] == ["a"]      # b was cancelled
    assert obs.active_count() == 0


def test_streamed_run_lands_in_the_trace_on_the_uploaders_line(mesh,
                                                               tmp_path):
    x = np.arange(32 * 4 * 8, dtype=np.float64).reshape(32, 4, 8)
    with jax.profiler.trace(str(tmp_path)):
        src = bolt.fromcallback(lambda idx: x[idx], x.shape, mesh,
                                dtype=np.float64, chunks=8)
        got = src.map(lambda v: v + 1).sum().toarray()
    assert np.allclose(got, (x + 1).sum(axis=0))
    lines = host_lines(str(tmp_path))
    where = {}
    for i, evs in lines:
        for name, stats in evs:
            where.setdefault(name, set()).add(i)
    caller = where["bolt.stream.run"]
    assert len(caller) == 1
    assert where["bolt.array.fetch"] == caller
    assert where["bolt.stream.compute"] == caller
    assert where["bolt.stream.transfer"].isdisjoint(caller)
    transfers = [st for _, evs in lines for name, st in evs
                 if name == "bolt.stream.transfer"]
    assert len(transfers) == 4
    assert sum(st["bytes"] for st in transfers) == x.nbytes
    # the uploader's spans carry their run's request id
    run_rid = [st["rid"] for _, evs in lines for name, st in evs
               if name == "bolt.stream.run"]
    assert {st["rid"] for st in transfers} == set(run_rid)
    assert obs.active_count() == 0


def test_obs_span_names_a_region_in_the_device_trace(mesh, tmp_path):
    """What ``profile.annotate`` was for: ``obs.span`` is the one way."""
    from bolt_tpu import profile
    assert not hasattr(profile, "annotate")
    with profile.trace(str(tmp_path)):
        with obs.span("my.region", note="mine"):
            bolt.ones((8, 2), mesh).sum().toarray()
    names = {name for _, evs in host_lines(str(tmp_path))
             for name, _ in evs}
    assert {"bolt.my.region", "bolt.array.fetch",
            "bolt.engine.dispatch"} <= names


# ----------------------------------------------------------------------
# what a span records
# ----------------------------------------------------------------------

def test_rid_is_the_roots_sid_across_the_parent_handoff():
    obs.enable()
    with obs.span("request") as root:
        with obs.span("inner") as inner:
            pass
        th = threading.Thread(
            target=lambda: obs.end(obs.begin("worker", parent=root)))
        th.start()
        th.join()
    with obs.span("next") as other:
        pass
    by = {s.name: s for s in obs.spans()}
    assert root.rid == root.sid
    assert by["inner"].rid == by["worker"].rid == root.sid
    assert by["worker"].tid != root.tid
    assert by["worker"].path == ("request", "worker")
    assert other.rid == other.sid != root.sid
    doc = obs.to_chrome()
    rids = {e["name"]: e["args"]["rid"] for e in doc["traceEvents"]
            if e.get("ph") == "B"}
    assert rids["worker"] == rids["inner"] == root.sid


def test_totals_survive_a_wrapped_ring_and_clear_zeroes_them():
    obs.enable(ring=4)
    for i in range(50):
        sp = obs.begin("tick")
        obs.end(sp, bytes=10)
    obs.event("mark")
    assert len(obs.spans()) == 4
    t = obs.totals()
    assert t["tick"]["count"] == 50 and t["tick"]["bytes"] == 500
    assert t["tick"]["seconds"] > 0 and t["mark"]["count"] == 1
    txt = obs.report()
    assert "tick" in txt and " 50 " in txt
    obs.clear()
    assert obs.totals() == {} and "no spans recorded" in obs.report()


def test_self_seconds_leave_out_same_thread_children_only():
    obs.enable()
    with obs.span("parent") as parent:
        with obs.span("child"):
            for _ in range(20000):
                pass
        th = threading.Thread(
            target=lambda: obs.end(obs.begin("elsewhere", parent=parent)))
        th.start()
        th.join()
    by = {s.name: s for s in obs.spans()}
    p, c = by["parent"], by["child"]
    assert c.duration > 0
    assert p.self_seconds == pytest.approx(p.duration - c.duration)
    t = obs.totals()
    assert t["parent"]["self_seconds"] == pytest.approx(p.self_seconds)
    assert t["child"]["self_seconds"] == pytest.approx(c.duration)
    # the hand-off's child displaced nothing on the parent's thread
    assert by["elsewhere"].duration is not None


def test_report_tree_nests_by_path_and_counts_compiles(mesh):
    b = bolt.ones((8, 3), mesh)
    obs.enable()
    b.map(lambda v: v * 3).sum().toarray()
    lines = obs.report().splitlines()
    at = {ln.split()[0]: len(ln) - len(ln.lstrip()) for ln in lines[1:]}
    assert at["array.fetch"] == 0
    assert at["array.fetch.force"] == 2 and at["array.stat"] == 4
    assert at["engine.dispatch"] > at["array.stat"]
    fetch = [ln for ln in lines if ln.startswith("array.fetch ")][0]
    assert int(fetch.split()[-1]) >= 1      # the stat compiled beneath it


# ----------------------------------------------------------------------
# the fetch
# ----------------------------------------------------------------------

X = np.arange(16 * 6, dtype=np.float64).reshape(16, 6)


def _not_addressable(monkeypatch):
    """Send ``toarray`` down the multihost gather on one process (every
    region is then local: no broadcast runs)."""
    from jax._src.array import ArrayImpl
    monkeypatch.setattr(ArrayImpl, "is_fully_addressable",
                        property(lambda self: False))


def _plain(mesh, monkeypatch):
    return bolt.array(X, mesh).map(lambda v: v + 1), None, X + 1


def _out(mesh, monkeypatch):
    return bolt.array(X, mesh), np.empty_like(X), X


def _pending(mesh, monkeypatch):
    keep = X[X.mean(axis=1) > 20]
    return bolt.array(X, mesh).filter(lambda v: v.mean() > 20), None, keep


def _multihost(mesh, monkeypatch):
    b = bolt.array(X, mesh)
    b.cache()
    _not_addressable(monkeypatch)
    return b, None, X


BRANCHES = {"plain": _plain, "out": _out, "pending_filter": _pending,
            "multihost_gather": _multihost}


@pytest.mark.parametrize("branch", sorted(BRANCHES))
def test_toarray_is_a_fetch_of_force_wait_copy(mesh, monkeypatch, branch):
    b, out, want = BRANCHES[branch](mesh, monkeypatch)
    if branch == "pending_filter":
        assert b.pending
    obs.enable()
    got = b.toarray(out=out) if out is not None else b.toarray()
    obs.disable()
    assert np.array_equal(np.asarray(got), want)
    sp = obs.spans()
    assert children(sp, "array.fetch") == [
        "array.fetch.force", "array.fetch.wait", "array.fetch.copy"]
    copy = [s for s in sp if s.name == "array.fetch.copy"][0]
    assert copy.attrs["bytes"] == want.nbytes
    root = [s for s in sp if s.name == "array.fetch"][0]
    assert root.pid == 0 and all(s.rid == root.sid for s in sp)
    assert obs.active_count() == 0


def _raise(*a, **k):
    raise RuntimeError("injected")


def _break_plain(mesh, monkeypatch):
    b = bolt.array(X, mesh)
    b._consume_donated("a test")            # force: the donation gate
    return b, None


def _break_out(mesh, monkeypatch):
    return bolt.array(X, mesh), np.empty((3, 3))    # force: out= refused


def _break_wait(mesh, monkeypatch):
    monkeypatch.setattr(jax, "block_until_ready", _raise)
    return bolt.array(X, mesh), None


def _break_pending(mesh, monkeypatch):
    b = bolt.array(X, mesh).filter(lambda v: v.mean() > 20)
    return b, np.empty((1, 6))              # copy: the count is known there


def _break_multihost(mesh, monkeypatch):
    b, _, _ = _multihost(mesh, monkeypatch)
    monkeypatch.setattr(type(b), "_gather_multihost", _raise)
    return b, None                          # copy: the gather


BREAKS = {"plain": (_break_plain, "array.fetch.force"),
          "out": (_break_out, "array.fetch.force"),
          "wait": (_break_wait, "array.fetch.wait"),
          "pending_filter": (_break_pending, "array.fetch.copy"),
          "multihost_gather": (_break_multihost, "array.fetch.copy")}


@pytest.mark.parametrize("branch", sorted(BREAKS))
def test_toarray_leaks_no_span_when_a_phase_raises(mesh, monkeypatch,
                                                   branch, tmp_path):
    make, last_phase = BREAKS[branch]
    b, out = make(mesh, monkeypatch)
    with jax.profiler.trace(str(tmp_path)):     # annotations open too
        with pytest.raises((RuntimeError, ValueError)):
            b.toarray(out=out) if out is not None else b.toarray()
        assert obs.active_count() == 0
        assert obs.current() is None
    monkeypatch.undo()
    kids = children(obs.spans(), "array.fetch")
    assert kids[-1] == last_phase
    assert kids == ["array.fetch.force", "array.fetch.wait",
                    "array.fetch.copy"][:len(kids)]


def test_cache_is_a_fetch_of_the_one_phase(mesh):
    obs.enable()
    b = bolt.array(X, mesh).map(lambda v: v * 2).cache()
    assert children(obs.spans(), "array.fetch") == ["array.fetch.force"]
    assert "array.chain" in children(obs.spans(), "array.fetch.force")
    obs.clear()
    b.tojax()                               # an unwrap: no fetch of its own
    assert obs.spans() == []


def test_a_streamed_operand_runs_whole_inside_force(mesh):
    obs.enable()
    src = bolt.fromcallback(lambda idx: X[idx], X.shape, mesh,
                            dtype=np.float64, chunks=4)
    src.sum().toarray()
    sp = obs.spans()
    force = [s for s in sp if s.name == "array.fetch.force"][0]
    run = [s for s in sp if s.name == "stream.run"][0]
    assert run.pid == force.sid
    assert {s.rid for s in sp if s.name.startswith("stream.")} \
        == {force.rid}                      # uploader threads included


def test_getitem_has_a_span_like_its_sibling_terminals(mesh):
    b = bolt.array(X, mesh)
    obs.enable()
    b[2:9]                  # basic: deferred as a window, nothing launched
    b[::2]
    b[[1, 3], :]
    got = [s for s in obs.spans() if s.name == "array.getitem"]
    assert [s.attrs["advanced"] for s in got] == [0, 1]
    assert "engine.dispatch" in children(
        [s for s in obs.spans() if s.rid == got[0].rid], "array.getitem")


# ----------------------------------------------------------------------
# the engine
# ----------------------------------------------------------------------

def test_engine_lookup_on_miss_and_hit():
    key = ("bridge-test", "lookup", object())
    obs.enable()
    first = engine.get(key, lambda: jax.jit(lambda v: v + 1))
    again = engine.get(key, lambda: pytest.fail("built twice"))
    obs.disable()
    engine.evict(key)
    assert again is first
    looks = [s for s in obs.spans() if s.name == "engine.lookup"]
    assert [(s.attrs["family"], s.attrs["hit"]) for s in looks] == [
        ("bridge-test", False), ("bridge-test", True)]
    builds = [s for s in obs.spans() if s.name == "engine.build"]
    assert len(builds) == 1 and builds[0].t0 >= looks[0].t1


@pytest.mark.parametrize("aot", [True, False])
def test_dispatch_has_signature_and_enqueue_beneath_it(aot):
    """``aot=False`` is the plain-jit fallback, reached the way it is in
    use: the program is called under somebody's trace, and a tracer has
    no signature to pick an executable by."""
    key = ("bridge-test", "dispatch", aot, object())
    fn = engine.get(key, lambda: jax.jit(lambda v: v * 2))
    call = fn if aot else jax.jit(lambda v: fn(v))
    fn(np.ones(4, np.float32))              # compile outside the reading
    before = engine.counters()
    obs.enable()
    call(np.ones(4, np.float32))
    obs.disable()
    engine.evict(key)
    after = engine.counters()
    sp = obs.spans()
    # the signature is tried (and its span closed) on both paths
    assert children(sp, "engine.dispatch") == ["engine.signature",
                                               "engine.enqueue"]
    disp = [s for s in sp if s.name == "engine.dispatch"][0]
    assert disp.attrs["family"] == "bridge-test"
    # the always-on counters are what they were
    assert after["dispatches"] - before["dispatches"] == 1
    assert after["dispatch_seconds"] > before["dispatch_seconds"]
    assert after["fallbacks"] - before["fallbacks"] == (0 if aot else 1)
    assert after["aot_compiles"] == before["aot_compiles"]


# ----------------------------------------------------------------------
# scripts/host_gaps.py on a hand-made trace
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def host_gaps():
    path = os.path.join(REPO, "scripts", "host_gaps.py")
    spec = importlib.util.spec_from_file_location("host_gaps", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _trace(ops, host):
    ms = 1000000
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            [n, int(s * ms), int(d * ms)] for n, s, d in ops]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            [n, int(s * ms), int(d * ms)] for n, s, d in host]}]}]}


def test_host_gaps_gives_the_idle_time_to_the_innermost_span(host_gaps):
    # one 20 ms window, two requests; times in ms
    raw = _trace(
        ops=[("%fusion = f32[] fusion()", 3, 4),
             ("%slice.1 = f32[] slice()", 11, 2),
             ("%fusion = f32[] fusion()", 14, 3)],
        host=[("bench.window", 0, 20),
              ("bench.call", 0, 1), ("bench.fetch", 1, 8),
              ("bolt.array.fetch", 1, 8),
              ("bolt.array.fetch.force", 1, 1),
              ("bolt.array.fetch.wait", 2, 6),
              ("bolt.array.fetch.copy", 8, 1),
              ("bench.call", 10, 1), ("bench.fetch", 11, 9),
              ("bolt.array.fetch", 11.5, 8),
              ("bolt.array.fetch.force", 11.5, 0.5),
              ("bolt.array.fetch.wait", 12, 6),
              ("bolt.array.fetch.copy", 18, 1.5)])
    out = host_gaps.split(raw)
    ms = 1e-3
    assert out["window_s"] == pytest.approx(20 * ms)
    assert out["busy_s"] == pytest.approx(9 * ms)
    assert out["idle_s"] == pytest.approx(11 * ms)
    assert out["bolt_events"] == 8
    bench = out["idle_by_bench"]
    # idle: 0-3, 7-11, 13-14, 17-20
    assert bench["bench.call"] == pytest.approx(2 * ms)
    assert bench["bench.fetch"] == pytest.approx(8 * ms)
    assert bench["_no_span_open_"] == pytest.approx(1 * ms)
    assert out["idle_in_fetch_s"] == pytest.approx(8 * ms)
    inner = out["idle_in_fetch_by_bolt"]
    assert inner["bolt.array.fetch.force"] == pytest.approx(1 * ms)
    assert inner["bolt.array.fetch.wait"] == pytest.approx(
        (1 + 1) * ms + (1 + 1) * ms)
    assert inner["bolt.array.fetch.copy"] == pytest.approx(2.5 * ms)
    assert inner["bolt.array.fetch"] == pytest.approx(0)
    assert inner["_no_span_open_"] == pytest.approx(0.5 * ms)
    assert out["named_share_of_fetch_idle"] == pytest.approx(7.5 / 8)
    wait = out["idle_in_wait"]
    # first wait 2-8: op 3-7; second 12-18: ops 11-13 (running at 12), 14-17
    assert wait["before_first_op"] == pytest.approx(1 * ms)
    assert wait["between_ops"] == pytest.approx(1 * ms)
    assert wait["after_last_op"] == pytest.approx(2 * ms)
    assert wait["none"] == 0


def test_host_gaps_gives_the_consumers_thread_its_own_account(host_gaps):
    """A streamed pass of two slabs, 20 ms: the consumer's thread waits for
    a slab, calls, blocks; a pool thread ingests and waits for the ring.
    Over every thread at once the latest-started span takes a gap, so a
    pool thread's wait takes time from the consumer's; by thread it does
    not."""
    ms = 1000000
    mine = [("bench.window", 0, 20), ("bench.fetch", 0, 20),
            ("bolt.stream.shuffle", 0, 20),
            ("bolt.stream.wait.slab", 0, 6), ("bolt.stream.compute", 6, 4),
            ("bolt.stream.dispatch", 6, 1), ("bolt.stream.sync", 7, 3),
            ("bolt.stream.wait.slab", 10, 2), ("bolt.stream.compute", 12, 8),
            ("bolt.stream.dispatch", 12, 1), ("bolt.stream.sync", 13, 7)]
    pool = [("bolt.stream.ingest", 0, 6), ("bolt.stream.ingest", 6, 5),
            ("bolt.stream.wait.ring", 11, 9)]
    raw = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["%fusion = f32[] fusion()", 8 * ms, 2 * ms],
            ["%fusion = f32[] fusion()", 16 * ms, 2 * ms]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [[n, int(s * ms), int(d * ms)]
                                        for n, s, d in mine]},
            {"name": "bolt-stream-upload-0", "events": [
                [n, int(s * ms), int(d * ms)] for n, s, d in pool]}]}]}
    out = host_gaps.split(raw)
    ms = 1e-3
    # idle: 0-8, 10-16, 18-20
    assert out["idle_s"] == pytest.approx(16 * ms)
    consumer = out["idle_by_consumer"]
    assert consumer["bolt.stream.wait.slab"] == pytest.approx(8 * ms)
    assert consumer["bolt.stream.dispatch"] == pytest.approx(2 * ms)
    assert consumer["bolt.stream.sync"] == pytest.approx(6 * ms)
    assert consumer["bolt.stream.compute"] == pytest.approx(0)
    assert consumer["_no_span_open_"] == pytest.approx(0)
    others = out["idle_by_other_threads"]
    assert others["bolt.stream.ingest"] == pytest.approx(9 * ms)
    assert others["bolt.stream.wait.ring"] == pytest.approx(7 * ms)
    # every thread at once: 11-12 goes to the pool's wait, which started
    # after the consumer's, and the consumer's account loses it
    both = out["idle_by_bolt"]
    assert both["bolt.stream.wait.ring"] == pytest.approx(1 * ms)
    assert both["bolt.stream.wait.slab"] < 8 * ms
    assert sum(consumer.values()) == pytest.approx(out["idle_s"])
    assert sum(others.values()) == pytest.approx(out["idle_s"])
    # the blocks 7-10 and 13-20 hold the programs 8-10 and 16-18: each
    # waited a millisecond and three for its program to start, and the
    # second two more after it
    sync = out["idle_in_sync"]
    assert sync["before_first_op"] == pytest.approx((1 + 3) * ms)
    assert sync["after_last_op"] == pytest.approx(2 * ms)
    assert sync["between_ops"] == pytest.approx(0) and sync["none"] == 0


def test_host_gaps_has_no_consumers_account_without_a_streamed_run(
        host_gaps):
    raw = _trace(ops=[("%a = f32[] a()", 1, 2)],
                 host=[("bench.window", 0, 10), ("bench.fetch", 0, 4),
                       ("bolt.array.fetch", 0, 4)])
    out = host_gaps.split(raw)
    assert "idle_by_consumer" not in out
    assert "idle_by_other_threads" not in out and "idle_in_sync" not in out


def test_host_gaps_takes_the_checks_instants_out(host_gaps):
    raw = _trace(
        ops=[("%a = f32[] a()", 1, 2), ("%check = f32[] c()", 5, 2)],
        host=[("bench.window", 0, 10), ("bench.fetch", 0, 4),
              ("bench.check", 4, 4), ("bench.fetch", 8, 2)])
    out = host_gaps.split(raw)
    assert out["window_s"] == pytest.approx(6e-3)
    assert out["busy_s"] == pytest.approx(2e-3)
    assert out["idle_by_bench"]["bench.fetch"] == pytest.approx(4e-3)
    assert out["bolt_events"] == 0
    assert out["named_share_of_fetch_idle"] == pytest.approx(0.0)


@pytest.mark.parametrize("early_ms", [0.0, 1.0])
def test_host_gaps_bounds_the_device_clocks_offset(host_gaps, early_ms):
    """Five 20 ms requests; each operation truly runs from 0.5 ms after its
    enqueue began to 0.5 ms before its wait ended.  Recorded ``early_ms``
    too early, the feasible shifts move by as much, and at either bound
    the idle time falls where causality allows."""
    ops, host = [], [("bench.window", 0, 100)]
    for k in range(5):
        t = 20 * k
        host += [("bench.call", t, 1), ("bolt.engine.enqueue", t + 0.5, 0.3),
                 ("bench.fetch", t + 1, 18),
                 ("bolt.array.fetch.wait", t + 2, 16)]
        ops.append(("%fusion = f32[] fusion()", t + 1.0 - early_ms, 16.5))
    out = host_gaps.split(_trace(ops, host))
    lo, hi = out["device_clock_offset_s"]
    assert lo == pytest.approx((early_ms - 0.5) * 1e-3, abs=2e-5)
    assert hi == pytest.approx((early_ms + 0.5) * 1e-3, abs=2e-5)
    assert (lo <= 0 <= hi) == (early_ms == 0.0)
    first, last = out["at_offset"]
    assert first["offset_s"] == lo and last["offset_s"] == hi
    # at the low bound the operation starts as its enqueue does: all of the
    # wait's idle time is after it; at the high bound it ends with the wait
    assert first["idle_in_wait"]["before_first_op"] == pytest.approx(0)
    assert first["idle_in_wait"]["after_last_op"] == pytest.approx(
        5 * 1e-3, rel=0.02)
    assert last["idle_in_wait"]["before_first_op"] == pytest.approx(0)
    assert last["idle_in_wait"]["after_last_op"] == pytest.approx(0, abs=1e-4)


def test_host_gaps_gives_no_bound_without_enqueue_spans(host_gaps):
    raw = _trace(ops=[("%a = f32[] a()", 1, 2)],
                 host=[("bench.window", 0, 10), ("bench.fetch", 0, 4)])
    out = host_gaps.split(raw)
    assert out["device_clock_offset_s"] is None and "at_offset" not in out


def test_host_gaps_names_what_compiled_while_the_tracer_looked(host_gaps,
                                                               mesh):
    arr = bolt.ones((8, 5), mesh)
    arr.toarray()

    def warm(v):
        return v * 34.09

    def cold(v):
        return v * 34.10

    arr.map(warm).sum().toarray()
    obs.clear()
    assert host_gaps.window_compiles() == []
    obs.enable()
    try:
        arr.map(warm).sum().toarray()
        assert host_gaps.window_compiles() == []
        arr.map(cold).sum().toarray()
    finally:
        obs.disable()
    row, = host_gaps.window_compiles()
    assert row == engine.compile_log()[-1] and row["family"] == "stat"
    obs.clear()
