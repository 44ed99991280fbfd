"""Concurrency analysis layer (ISSUE 17).

Three coordinated pieces, each tested here:

* the STATIC pass — ``analysis/concurrency.py`` rules BLT111–BLT114
  (inventory-routed lock creation, rank-ordered nesting, no indefinite
  blocking under a lock, order-locked enqueues); zero findings on
  ``bolt_tpu/`` is a tier-1 invariant and every rule has a seeded
  violation below;
* the RUNTIME witness — ``bolt_tpu/_lockdep``: rank inversions,
  self-deadlocks and dispatch-under-lock recorded (or raised) only
  while armed, with edges/cycles/stats inspection;
* the HYGIENE gates that ride along — the diagnostics-table drift gate
  (code tables vs ``docs/API.md`` vs ``lint_bolt.py --codes``), the
  stale-pragma audit, the ``DeviceArbiter.resize`` race hammer under
  the armed witness, and the ``obs.thread_census()`` leak check.

The cross-process schedule-digest exchange is exercised on a real
2-process cluster in ``tests/test_multihost.py`` (``sched_verify``
payload); here only the single-process surface is covered.
"""

import os
import re
import threading

import numpy as np
import pytest

import bolt_tpu as bolt
from bolt_tpu import _lockdep, engine, obs, serve, utils
from bolt_tpu.analysis import astlint, diagnostics
from bolt_tpu.analysis import concurrency as conc
from bolt_tpu.parallel import multihost

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRELUDE = "from bolt_tpu import _lockdep\n"


def _codes(src, path="bolt_tpu/somewhere.py"):
    return [f.code for f in conc.lint_source(src, path)]


# ---------------------------------------------------------------------
# static pass: the tier-1 invariant
# ---------------------------------------------------------------------

@pytest.mark.lint
def test_concurrency_lint_package_zero_findings():
    found = conc.lint_package()
    assert found == [], "\n".join(f.render() for f in found)


# ---------------------------------------------------------------------
# static pass: seeded violations, one (or more) per rule
# ---------------------------------------------------------------------

@pytest.mark.lint
def test_blt111_raw_lock_construction():
    src = "import threading\nL = threading.Lock()\n"
    assert _codes(src) == ["BLT111"]
    # every primitive, any import spelling
    assert _codes("from threading import Condition\nC = Condition()\n") \
        == ["BLT111"]
    assert _codes("import threading as t\nR = t.RLock()\n") == ["BLT111"]
    # the witness itself, tests and scripts build raw primitives freely
    assert conc.lint_source(src, "bolt_tpu/_lockdep.py") == []
    assert conc.lint_source(src, "tests/test_foo.py") == []
    assert conc.lint_source(src, "scripts/chaos_run.py") == []
    # the pragma escape hatch documents a deliberate exception
    ok = ("import threading\n"
          "L = threading.Lock()  # lint: allow(BLT111 scratch harness)\n")
    assert _codes(ok) == []


@pytest.mark.lint
def test_blt111_factory_names_must_be_declared_literals():
    # unknown inventory name: static table and runtime witness agree
    assert _codes(_PRELUDE + "L = _lockdep.lock('no.such.lock')\n") \
        == ["BLT111"]
    # non-literal name: the static pass cannot rank it
    assert _codes(_PRELUDE + "L = _lockdep.lock(name)\n") == ["BLT111"]
    # a declared literal is the blessed form
    assert _codes(_PRELUDE + "L = _lockdep.rlock('engine.cache')\n") == []


@pytest.mark.lint
def test_blt112_static_rank_inversion():
    decl = (_PRELUDE
            + "OUTER = _lockdep.lock('serve.scheduler')\n"   # rank 34
            + "LEAF = _lockdep.lock('engine.cache')\n")      # rank 54
    bad = decl + ("def f():\n"
                  "    with LEAF:\n"
                  "        with OUTER:\n"
                  "            pass\n")
    found = conc.lint_source(bad, "bolt_tpu/x.py")
    assert [f.code for f in found] == ["BLT112"]
    assert "inverts the declared order" in found[0].message
    # the declared order is clean
    good = decl + ("def f():\n"
                   "    with OUTER:\n"
                   "        with LEAF:\n"
                   "            pass\n")
    assert conc.lint_source(good, "bolt_tpu/x.py") == []
    # a nested def's body runs LATER, not under the lock
    closure = decl + ("def f():\n"
                      "    with LEAF:\n"
                      "        def cb():\n"
                      "            with OUTER:\n"
                      "                pass\n"
                      "        return cb\n")
    assert conc.lint_source(closure, "bolt_tpu/x.py") == []
    # instance-attribute bindings resolve too
    attr = (_PRELUDE
            + "class C:\n"
            + "    def __init__(self):\n"
            + "        self.lk = _lockdep.lock('engine.cache')\n"
            + "        self.outer = _lockdep.lock('serve.scheduler')\n"
            + "    def f(self):\n"
            + "        with self.lk:\n"
            + "            with self.outer:\n"
            + "                pass\n")
    assert _codes(attr) == ["BLT112"]


@pytest.mark.lint
def test_blt113_blocking_call_under_ranked_lock():
    decl = _PRELUDE + "L = _lockdep.lock('engine.cache')\n"
    # bare waits with no timeout block indefinitely
    assert _codes(decl + "def f(fut):\n"
                         "    with L:\n"
                         "        fut.result()\n") == ["BLT113"]
    # a bounded wait is fine
    assert _codes(decl + "def f(fut):\n"
                         "    with L:\n"
                         "        fut.result(5)\n") == []
    # a collective under a lock is the classic cross-process deadlock
    found = conc.lint_source(
        decl + "from bolt_tpu.parallel import multihost as mh\n"
               "def f():\n"
               "    with L:\n"
               "        mh.barrier('x')\n", "bolt_tpu/x.py")
    assert [f.code for f in found] == ["BLT113"]
    assert "collective" in found[0].message
    # parking the thread under a lock stalls every contender
    assert _codes(decl + "import time\n"
                         "def f():\n"
                         "    with L:\n"
                         "        time.sleep(1)\n") == ["BLT113"]
    # the same calls OUTSIDE any lock are untouched
    assert _codes(decl + "import time\n"
                         "def f(fut):\n"
                         "    fut.result()\n"
                         "    time.sleep(1)\n") == []


@pytest.mark.lint
def test_blt114_enqueue_outside_order_lock():
    # direct .jitted(...) call
    bad = ("class D:\n"
           "    def run(self, args):\n"
           "        return self.jitted(*args)\n")
    assert _codes(bad) == ["BLT114"]
    # .lower() on the jitted object is NOT a dispatch
    assert _codes("class D:\n"
                  "    def low(self, args):\n"
                  "        return self.jitted.lower(*args)\n") == []
    # under the order lock: the blessed form
    ok = ("from bolt_tpu.engine import order_lock\n"
          "class D:\n"
          "    def run(self, args):\n"
          "        with order_lock():\n"
          "            return self.jitted(*args)\n")
    assert _codes(ok) == []
    # names bound from .compile() / .compiled.get(...) are enqueues too
    bound = ("def run(lowered, args):\n"
             "    fn = lowered.compile()\n"
             "    return fn(*args)\n")
    assert _codes(bound) == ["BLT114"]
    cached = ("from bolt_tpu.engine import order_lock\n"
              "class D:\n"
              "    def run(self, sig, args):\n"
              "        fn = self.compiled.get(sig)\n"
              "        with order_lock():\n"
              "            return fn(*args)\n")
    assert _codes(cached) == []


# ---------------------------------------------------------------------
# satellite: diagnostics-table drift gate
# ---------------------------------------------------------------------

@pytest.mark.lint
def test_all_diagnostic_codes_documented_in_api_md():
    """docs/API.md, the checker table, the (merged) lint registry and
    the CLI must agree on ONE set of BLT codes — a rule added in code
    but not documented (or vice versa) fails here."""
    with open(os.path.join(REPO, "docs", "API.md"),
              encoding="utf-8") as fh:
        api = fh.read()
    # the concurrency rules are merged into the astlint registry: one
    # BLT1xx namespace, one Finding.title resolution, one --codes table
    assert set(conc.RULES) <= set(astlint.RULES)
    known = set(diagnostics.CODES) | set(astlint.RULES)
    documented = set(re.findall(r"BLT\d{3}", api))
    missing = sorted(known - documented)
    assert not missing, "codes missing from docs/API.md: %s" % missing
    phantom = sorted(documented - known)
    assert not phantom, \
        "docs/API.md documents unknown codes: %s" % phantom


@pytest.mark.lint
def test_lint_bolt_codes_table_matches_registry(capsys):
    lint = utils.load_script("lint_bolt")
    assert lint.main(["--codes"]) == 0
    out = capsys.readouterr().out
    listed = set(re.findall(r"^(BLT\d{3})\b", out, re.M))
    assert listed == set(astlint.RULES)
    for code in ("BLT111", "BLT112", "BLT113", "BLT114"):
        assert code in listed


# ---------------------------------------------------------------------
# satellite: stale-pragma audit (lint_bolt.py --check)
# ---------------------------------------------------------------------

@pytest.mark.lint
def test_stale_pragma_audit_fails_the_check_gate(tmp_path, capsys):
    lint = utils.load_script("lint_bolt")
    # a pragma naming an unknown code
    unknown = tmp_path / "unknown.py"
    unknown.write_text("x = 1  # lint: allow(BLT999 never existed)\n")
    assert lint.main(["--check", str(unknown)]) == 1
    assert "unknown code 'BLT999'" in capsys.readouterr().out
    # a pragma that no longer suppresses anything
    stale = tmp_path / "stale.py"
    stale.write_text("x = 1  # lint: allow(BLT104 fixed long ago)\n")
    assert lint.main(["--check", str(stale)]) == 1
    assert "no longer suppresses" in capsys.readouterr().out
    # a live pragma passes: it suppresses a real finding on its line
    live = tmp_path / "live.py"
    live.write_text("import threading\n"
                    "L = threading.Lock()"
                    "  # lint: allow(BLT111 scratch)\n")
    assert lint.main(["--check", str(live)]) == 0


# ---------------------------------------------------------------------
# runtime witness: unit surface
# ---------------------------------------------------------------------
#
# These tests RECORD violations on purpose, so they must not run under
# the suite-wide autouse witness assertion (this module is not in
# conftest._LOCKDEP_SUITES); the local fixture arms, and resets the
# global record on the way out so later tests see a clean slate.

@pytest.fixture
def witness():
    was = _lockdep.enabled()
    _lockdep.reset()
    _lockdep.enable()
    yield _lockdep
    _lockdep.disable()
    _lockdep.reset()
    if was:
        _lockdep.enable()


def test_factory_rejects_undeclared_names():
    with pytest.raises(ValueError, match="not in the declared"):
        _lockdep.lock("no.such.lock")
    with pytest.raises(ValueError, match="BLT111"):
        _lockdep.condition("also.not.a.lock")


def test_witness_records_rank_inversion(witness):
    outer = witness.lock("engine.cache")       # rank 54
    inner = witness.lock("serve.scheduler")    # rank 34
    with outer:
        with inner:
            pass
    v = witness.violations()
    assert len(v) == 1 and "inversion" in v[0]
    assert "'serve.scheduler' (rank 34)" in v[0]
    assert "'engine.cache' (rank 54)" in v[0]
    # the correct order records an EDGE, not a violation
    witness.reset()
    with inner:
        with outer:
            pass
    assert witness.violations() == []
    assert ("serve.scheduler", "engine.cache") in witness.edges()
    assert witness.check() == []               # and no cycle


def test_witness_raise_mode_throws_at_the_acquisition(witness):
    witness.enable(raise_on_violation=True)
    outer = witness.lock("engine.cache")
    inner = witness.lock("serve.scheduler")
    with outer:
        with pytest.raises(witness.LockOrderError, match="inversion"):
            inner.acquire()
    witness.reset()


def test_witness_rlock_reentry_is_exempt(witness):
    rl = witness.rlock("engine.order")
    with rl:
        with rl:
            assert witness.held_names() == ["engine.order"]
    assert witness.violations() == []
    assert witness.held_names() == []


def test_witness_flags_nonreentrant_self_deadlock(witness):
    lk = witness.lock("tpu.lru")
    lk.acquire()
    try:
        # non-blocking, so the test itself cannot deadlock; the
        # witness notes the hazard before touching the primitive
        assert lk.acquire(blocking=False) is False
    finally:
        lk.release()
    assert any("self-deadlock" in x for x in witness.violations())


def test_witness_off_means_no_tracking(witness):
    witness.disable()
    outer = witness.lock("engine.cache")
    inner = witness.lock("serve.scheduler")
    with outer:
        with inner:                       # inverted — but unobserved
            assert witness.held_names() == []
    assert witness.violations() == []


def test_witness_stats_count_acquires(witness):
    base = witness.stats()["acquires"]
    lk = witness.lock("tpu.lru")
    for _ in range(5):
        with lk:
            pass
    st = witness.stats()
    assert st["acquires"] >= base + 5
    assert st["violations"] == 0
    # the flush lands in the obs registry group (flattened keys)
    snap = obs.registry().snapshot()
    assert snap.get("lockdep.acquires", 0) >= 5


def test_note_dispatch_flags_held_locks_except_dispatch_safe(witness):
    lk = witness.lock("serve.arbiter")
    with lk:
        witness.note_dispatch("test.dispatch")
    v = witness.violations()
    assert len(v) == 1 and "dispatch-under-lock" in v[0]
    assert "'serve.arbiter'" in v[0]
    witness.reset()
    # multistat.group holds its lock across resolve() BY DESIGN
    grp = witness.lock("multistat.group")
    with grp:
        witness.note_dispatch("test.dispatch")
    assert witness.violations() == []
    # and with nothing held there is nothing to flag
    witness.note_dispatch("test.dispatch")
    assert witness.violations() == []


# ---------------------------------------------------------------------
# satellite: DeviceArbiter.resize two-thread race under the witness
# ---------------------------------------------------------------------

@pytest.mark.serve
@pytest.mark.lockdep
def test_arbiter_resize_race_is_clean_under_lockdep():
    """One thread oscillates the budget while workers lease through it;
    the autouse lockdep fixture (this test carries the marker) fails
    the test on any recorded inversion, and the end-state assertions
    catch lost grants/releases."""
    arb = serve.DeviceArbiter(1 << 20)
    stop = threading.Event()
    errors = []

    def resizer():
        budgets = [1 << 18, 1 << 20, 1 << 16, 1 << 21]
        i = 0
        while not stop.is_set():
            arb.resize(budgets[i % len(budgets)])
            i += 1

    def worker(tenant):
        try:
            lease = arb.lease(tenant)
            for k in range(200):
                nbytes = 1 << (10 + k % 8)
                arb.acquire(nbytes, tenant=tenant)
                arb.release(nbytes)
                assert lease.acquire(nbytes)
                lease.release(nbytes)
            assert lease.outstanding() == 0
            lease.close()
        except Exception as exc:                # pragma: no cover
            errors.append(exc)

    threads = [threading.Thread(target=worker, args=("t%d" % i,))
               for i in range(4)]
    rs = threading.Thread(target=resizer)
    rs.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    stop.set()
    rs.join(timeout=10)
    assert not errors, errors
    assert not any(t.is_alive() for t in threads)
    assert arb.in_use() == 0                   # conservation: all paid back
    assert arb.waiting() == 0


# ---------------------------------------------------------------------
# satellite: thread-census hygiene
# ---------------------------------------------------------------------

@pytest.mark.obs
def test_thread_census_empty_after_server_teardown(mesh):
    assert obs.thread_census() == {}, \
        "another test leaked worker threads"
    x = np.arange(64, dtype=np.float64).reshape(8, 8)
    with serve.serving(workers=2) as sv:
        census = obs.thread_census()
        assert census.get("bolt-serve-worker") == 2
        src = bolt.fromcallback(lambda idx: x[idx], x.shape, mesh,
                                dtype=np.float64, chunks=4)
        f = sv.submit(src.map(lambda v: v + 1).sum())
        assert np.allclose(np.asarray(f.result(timeout=60).toarray()),
                           (x + 1).sum(axis=0))
    assert obs.thread_census() == {}, "server teardown leaked threads"


# ---------------------------------------------------------------------
# schedule digest: single-process surface (the 2-process exchange and
# the chaos-skew divergence run in tests/test_multihost.py)
# ---------------------------------------------------------------------

def test_schedule_digest_advances_per_enqueue(mesh):
    x = np.arange(48, dtype=np.float64).reshape(8, 6)
    c0, d0 = engine.schedule_digest()
    np.asarray(bolt.array(x, mesh).map(lambda v: v * 2).sum().toarray())
    c1, d1 = engine.schedule_digest()
    assert c1 > c0 and d1 != d0
    assert engine.schedule_recent()            # always-on tail context


def test_stable_key_strips_object_addresses():
    def f():
        pass
    a = engine._stable_key(("sig", f, (8, 6)))
    assert "0x" not in a
    assert "at 0x%x" % id(f) not in a
    assert f.__name__ in a


def test_schedule_log_arm_and_reset(mesh):
    assert engine.schedule_log() is None       # off by default
    engine.schedule_log_arm(True)
    try:
        x = np.arange(16, dtype=np.float64).reshape(8, 2)
        np.asarray(bolt.array(x, mesh).map(lambda v: v + 3).toarray())
        log = engine.schedule_log()
        assert log and all("0x" not in k for k in log)
        count, _ = engine.schedule_digest()
        assert len(log) <= count               # armed after start
    finally:
        engine.schedule_log_arm(False)
    assert engine.schedule_log() is None


def test_verify_schedule_single_process_returns_digest():
    got = multihost.verify_schedule("t17")
    assert got == engine.schedule_digest()[1]
