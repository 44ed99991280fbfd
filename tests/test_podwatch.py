"""Pod fault-tolerance suite, the IN-PROCESS half (ISSUE 11).

Covers the liveness layer (``bolt_tpu.parallel.podwatch``) without a
cluster: transports, the heartbeat watch and its death latch, the
collective watchdog (``wait_ready``/``reraise``/``check``), the
watchdog barrier, the serve-layer integration (admission drain on peer
death, resume on reform, ``PeerLostError``-aware retries), the
checkpoint layer's pod ABORT format (``rendezvous=False``, advance-only
meta, torn-abort atomicity) and topology-remap load, and the BLT013
diagnostic.  "Peers" here are FAKES — the test writes their heartbeat
files — so everything runs single-process; the REAL 3→2 kill -9
scenario lives in tests/test_multihost.py on the localhost cluster.
"""

import os
import threading
import time

import numpy as np
import pytest

import bolt_tpu as bolt
from bolt_tpu import _chaos, checkpoint, obs, serve
from bolt_tpu.parallel import multihost, podwatch
from bolt_tpu.parallel.podwatch import (FileTransport, PeerLostError,
                                        is_transport_error)

pytestmark = pytest.mark.podwatch


@pytest.fixture
def watchdir(tmp_path):
    """A clean watch per test: no stray callbacks, no running watch."""
    with podwatch._CB_LOCK:
        saved_d = dict(podwatch._DEATH_CBS)
        saved_r = dict(podwatch._REFORM_CBS)
        podwatch._DEATH_CBS.clear()
        podwatch._REFORM_CBS.clear()
    yield str(tmp_path)
    podwatch.stop()
    _chaos.clear()
    with podwatch._CB_LOCK:
        podwatch._DEATH_CBS.clear()
        podwatch._REFORM_CBS.clear()
        podwatch._DEATH_CBS.update(saved_d)
        podwatch._REFORM_CBS.update(saved_r)
    # the serve counters are a PROCESS-global registry group and
    # tests/test_serve.py asserts absolute totals — put back the zeros
    # this test's servers consumed
    from bolt_tpu.obs import metrics as _metrics
    reg = _metrics.registry()
    for name in list(reg.names()):
        if name == "serve" or name.startswith("serve/"):
            m = reg.get(name)
            if hasattr(m, "reset"):
                m.reset()


class _FakePeer:
    """A background thread impersonating pod process ``pid`` on the
    file transport: beats until told to die (or to say farewell)."""

    def __init__(self, transport, pid, interval=0.03):
        self.transport = transport
        self.pid = pid
        self.interval = interval
        self.stop_ev = threading.Event()
        self.seq = 0
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def _run(self):
        while not self.stop_ev.is_set():
            self.seq += 1
            self.transport.beat(self.pid, self.seq)
            self.stop_ev.wait(self.interval)

    def kill(self):
        self.stop_ev.set()
        self.thread.join()

    def farewell(self):
        self.transport.farewell(self.pid)
        self.kill()


def _start(watchdir, nproc=2, pid=0, interval=0.05, timeout=0.4):
    assert podwatch.start(nproc, pid, dir=watchdir, interval=interval,
                          timeout=timeout)
    return podwatch._WATCH.transport


# ---------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------

def test_peerlost_error_attrs():
    e = PeerLostError("gone", peer=2, slab=7, phase="slab program")
    assert e.peer == 2 and e.slab == 7 and e.phase == "slab program"
    assert isinstance(e, RuntimeError)


def test_transport_error_classifier():
    assert is_transport_error(ValueError(
        "UNKNOWN: Gloo all-reduce failed: Connection closed by peer"))
    assert is_transport_error(RuntimeError(
        "UNAVAILABLE: failed to send RPC to coordination service"))
    assert not is_transport_error(ValueError("shape mismatch (3, 4)"))


def test_file_transport_roundtrip(tmp_path):
    t = FileTransport(str(tmp_path), epoch=3)
    t.beat(0, 1)
    t.beat(1, 5)
    assert t.read() == {0: 1, 1: 5}
    t.beat(1, 6)
    assert t.read()[1] == 6
    assert t.read_farewells() == set()
    t.farewell(1)
    assert t.read_farewells() == {1}
    # barrier markers
    t.barrier_mark("ck", 0, 0)
    t.barrier_mark("ck", 0, 1)
    assert t.barrier_seen("ck", 0) == {0, 1}
    t.barrier_mark("ck", 2, 0)
    t.barrier_sweep("ck", 2, 0)       # removes own generation-0 marker
    assert t.barrier_seen("ck", 0) == {1}


def test_watch_defaults_off_single_process(watchdir):
    assert podwatch.start(1, 0, dir=watchdir) is False
    assert not podwatch.active()
    assert podwatch.deadline() is None
    assert podwatch.dead_peers() == ()
    podwatch.check()                  # no-op without a watch
    podwatch.wait_ready(object())     # ditto
    assert podwatch.start(4, 0, dir=watchdir, timeout=0) is False


# ---------------------------------------------------------------------
# the death latch
# ---------------------------------------------------------------------

def test_peer_death_detected_and_latched(watchdir):
    deaths = []
    podwatch.on_peer_death(deaths.append)
    t = _start(watchdir)
    peer = _FakePeer(t, 1)
    try:
        deadline = time.monotonic() + 2.0
        while 1 not in {p for p, st in podwatch.peers().items()
                        if st["alive"]} and time.monotonic() < deadline:
            time.sleep(0.02)
        assert podwatch.peers()[1]["alive"]
        peer.kill()                   # the preemption
        t0 = time.monotonic()
        while not podwatch.dead_peers() and \
                time.monotonic() - t0 < 5 * 0.4:
            time.sleep(0.02)
        took = time.monotonic() - t0
        assert podwatch.dead_peers() == (1,)
        # the watchdog bound: verdict within 2x the deadline
        assert took < 2 * 0.4 + 0.2
        assert deaths == [1]
        assert podwatch.alive_peers() == (0,)
        with pytest.raises(PeerLostError) as ei:
            podwatch.check(phase="unit", slab=3)
        assert ei.value.peer == 1 and ei.value.slab == 3
    finally:
        peer.kill()


def test_farewelled_peer_is_not_dead(watchdir):
    deaths = []
    podwatch.on_peer_death(deaths.append)
    t = _start(watchdir)
    peer = _FakePeer(t, 1)
    try:
        time.sleep(0.15)
        peer.farewell()               # leaves for a reform: silent, alive
        time.sleep(1.2)               # >> timeout
        assert podwatch.dead_peers() == ()
        assert deaths == []
        assert 1 in podwatch.alive_peers()
    finally:
        peer.kill()


def test_mark_dead_and_callbacks_once(watchdir):
    deaths = []
    h = podwatch.on_peer_death(deaths.append)
    _start(watchdir, nproc=3)
    podwatch.mark_dead(2)
    podwatch.mark_dead(2)             # latched: fires once
    assert deaths == [2]
    podwatch.remove_callback(h)
    podwatch.mark_dead(1)
    assert deaths == [2]              # deregistered


def test_coordination_error_latch(watchdir):
    """The out-of-band coordination-failure door: a status naming a
    task latches that peer dead; an anonymous one latches coord_error
    (check() raises either way)."""
    deaths = []
    podwatch.on_peer_death(deaths.append)
    _start(watchdir, nproc=3)
    podwatch.coordination_error(
        "UNAVAILABLE: Task /job:jax_worker/replica:0/task:2 heartbeat "
        "timeout.")
    assert deaths == [2]
    with pytest.raises(PeerLostError):
        podwatch.check()


def test_heartbeat_chaos_seam(watchdir):
    _chaos.inject("podwatch.heartbeat", nth=2, times=1)
    _start(watchdir)
    deadline = time.monotonic() + 2.0
    while _chaos.stats("podwatch.heartbeat")[0] < 3 and \
            time.monotonic() < deadline:
        time.sleep(0.02)
    hits, trips = _chaos.stats("podwatch.heartbeat")
    assert hits >= 3 and trips == 1   # the raise was absorbed, the
    w = podwatch._WATCH               # watch kept beating
    assert w.beat_errors == 1


# ---------------------------------------------------------------------
# the collective watchdog
# ---------------------------------------------------------------------

class _NeverReady:
    def is_ready(self):
        return False


class _ReadyAfter:
    def __init__(self, n):
        self.n = n

    def is_ready(self):
        self.n -= 1
        return self.n <= 0


def test_wait_ready_returns_when_ready(watchdir):
    t = _start(watchdir)
    peer = _FakePeer(t, 1)
    try:
        podwatch.wait_ready(_ReadyAfter(3), phase="unit")
        import jax.numpy as jnp
        podwatch.wait_ready(jnp.arange(3.0) + 1)      # real jax leaves
    finally:
        peer.kill()


def test_wait_ready_raises_on_dead_peer(watchdir):
    _start(watchdir, timeout=0.3)
    # peer 1 never beats: latched dead ~one timeout after start
    with pytest.raises(PeerLostError) as ei:
        podwatch.wait_ready(_NeverReady(), phase="slab-partial sync",
                            slab=5)
    assert ei.value.slab == 5
    assert ei.value.peer == 1


def test_reraise_classifies_transport_errors(watchdir):
    _start(watchdir, timeout=0.2)
    podwatch.mark_dead(1)
    gloo = ValueError("UNKNOWN: Gloo all-reduce failed: Connection "
                      "closed by peer [127.0.0.1]:1234")
    with pytest.raises(PeerLostError) as ei:
        podwatch.reraise(gloo, phase="slab program", slab=2)
    assert ei.value.peer == 1
    assert ei.value.__cause__ is gloo
    # an unrelated error passes through untouched
    boom = ValueError("shape mismatch")
    podwatch._WATCH.dead.clear()
    with pytest.raises(ValueError, match="shape mismatch"):
        podwatch.reraise(boom, wait=False)


def test_reraise_classifies_secondary_deleted_array(watchdir):
    """A failed async collective invalidates its output buffers; the
    NEXT dispatch consuming them raises "Array has been deleted" — the
    one-step-removed shape of a dead peer.  It converts to
    PeerLostError only when the heartbeat actually latched someone."""
    _start(watchdir, timeout=0.2)
    deleted = RuntimeError("Array has been deleted with shape=float32[8].")
    assert podwatch.is_secondary_sign(deleted)
    assert not podwatch.is_transport_error(deleted)
    # nobody dead: the genuine deleted-array bug surfaces untouched
    with pytest.raises(RuntimeError, match="has been deleted"):
        podwatch.reraise(deleted, wait=False)
    # a latched dead peer: classified, chained, named
    podwatch.mark_dead(1)
    with pytest.raises(PeerLostError) as ei:
        podwatch.reraise(deleted, phase="slab program", slab=3)
    assert ei.value.peer == 1
    assert ei.value.__cause__ is deleted
    # the grace window: the peer latches dead WHILE reraise waits
    podwatch._WATCH.dead.clear()
    import threading
    t = threading.Timer(0.1, podwatch.mark_dead, args=(1,))
    t.start()
    try:
        with pytest.raises(PeerLostError) as ei:
            podwatch.reraise(deleted, phase="slab program", slab=4)
        assert ei.value.peer == 1
    finally:
        t.cancel()


def test_guard_contextmanager(watchdir):
    _start(watchdir, timeout=0.2)
    with podwatch.guard("unit"):
        pass                          # clean body passes through
    podwatch.mark_dead(1)
    with pytest.raises(PeerLostError):
        with podwatch.guard("unit"):
            raise AssertionError("body must not run on a latched peer")


# ---------------------------------------------------------------------
# the watchdog barrier
# ---------------------------------------------------------------------

def test_barrier_completes_with_live_peer(watchdir):
    t = _start(watchdir)
    peer = _FakePeer(t, 1)
    try:
        done = []

        def arrive_late():
            time.sleep(0.15)
            t.barrier_mark("sync", 0, 1)
            done.append(True)

        th = threading.Thread(target=arrive_late, daemon=True)
        th.start()
        podwatch.barrier("sync")
        th.join()
        assert done == [True]
        # generation counting: a SECOND barrier of the same name waits
        # for generation 1 markers, not the stale generation-0 ones
        t.barrier_mark("sync", 1, 1)
        podwatch.barrier("sync")
    finally:
        peer.kill()


def test_barrier_converts_dead_peer(watchdir):
    t = _start(watchdir, timeout=0.3)
    peer = _FakePeer(t, 1)
    try:
        time.sleep(0.1)
        peer.kill()                   # dies before ever arriving
        t0 = time.monotonic()
        with pytest.raises(PeerLostError) as ei:
            podwatch.barrier("ckpt_w4")
        assert time.monotonic() - t0 < 2 * 0.3 + 0.3
        assert ei.value.peer == 1
        assert "barrier" in (ei.value.phase or "")
    finally:
        peer.kill()


def test_multihost_barrier_routes_through_watch(watchdir, monkeypatch):
    """multihost.barrier hits the chaos seam and the podwatch path when
    a watch is armed (single-process short-circuits first, so the
    process count is faked)."""
    t = _start(watchdir)
    peer = _FakePeer(t, 1)
    try:
        monkeypatch.setattr(multihost, "process_count", lambda: 2)
        _chaos.inject("multihost.barrier", nth=1)
        with pytest.raises(_chaos.ChaosError):
            multihost.barrier("seamcheck")
        _chaos.clear()
        peer.kill()
        with pytest.raises(PeerLostError):
            multihost.barrier("deadcheck")
    finally:
        peer.kill()


# ---------------------------------------------------------------------
# serve integration: drain on death, resume on reform, retryable loss
# ---------------------------------------------------------------------

def test_serve_drains_and_resumes_on_pod_events(watchdir):
    t = _start(watchdir)
    peer = _FakePeer(t, 1)
    try:
        with serve.serving(workers=1, policy="reject") as sv:
            assert not sv.pod_paused()
            peer.kill()
            t0 = time.monotonic()
            while not sv.pod_paused() and time.monotonic() - t0 < 3:
                time.sleep(0.02)
            assert sv.pod_paused()
            assert sv.stats()["pod"]["paused"]
            assert sv.stats()["totals"]["peer_losses"] == 1
            with pytest.raises(serve.AdmissionError,
                               match="pod peer 1 was lost"):
                sv.submit(lambda: 42)
            podwatch.notify_reform()  # the reform completed
            assert not sv.pod_paused()
            assert sv.submit(lambda: 42).result(timeout=30) == 42
    finally:
        peer.kill()


def test_serve_retry_waits_out_the_reform(watchdir):
    t = _start(watchdir)
    peer = _FakePeer(t, 1)
    try:
        with serve.serving(workers=1) as sv:
            attempts = []

            def flaky():
                attempts.append(1)
                if len(attempts) == 1:
                    podwatch.mark_dead(1)     # the pod outage
                    raise PeerLostError("lost", peer=1)
                return "recovered"

            fut = sv.submit(flaky, tenant="t", retries=1)
            time.sleep(0.3)
            assert not fut.done()     # held behind the drain
            podwatch.notify_reform()
            assert fut.result(timeout=30) == "recovered"
            assert len(attempts) == 2
            assert sv.stats()["totals"]["retried"] == 1
    finally:
        peer.kill()


def test_serve_queue_policy_blocks_submit_during_drain(watchdir):
    t = _start(watchdir)
    peer = _FakePeer(t, 1)
    try:
        with serve.serving(workers=1, policy="queue") as sv:
            peer.kill()
            t0 = time.monotonic()
            while not sv.pod_paused() and time.monotonic() - t0 < 3:
                time.sleep(0.02)
            got = []

            def submit_blocked():
                got.append(sv.submit(lambda: "ok").result(timeout=30))

            th = threading.Thread(target=submit_blocked, daemon=True)
            th.start()
            time.sleep(0.3)
            assert got == []          # backpressure while draining
            podwatch.notify_reform()
            th.join(timeout=30)
            assert got == ["ok"]
    finally:
        peer.kill()


def test_serve_close_terminates_during_held_retry(watchdir):
    """close(wait=True) must terminate even while a PeerLostError
    retry is held behind the admission drain and the reform never
    comes — the hold loop yields to a stopping server."""
    t = _start(watchdir)
    peer = _FakePeer(t, 1)
    try:
        sv = serve.start(workers=1)
        try:
            def doomed():
                podwatch.mark_dead(1)
                raise PeerLostError("lost", peer=1)

            fut = sv.submit(doomed, tenant="t", retries=3)
            t0 = time.monotonic()
            while not sv.pod_paused() and time.monotonic() - t0 < 3:
                time.sleep(0.02)
            assert sv.pod_paused()
        finally:
            t0 = time.monotonic()
            serve.stop(wait=True)     # must NOT deadlock
        assert time.monotonic() - t0 < 10
        assert isinstance(fut.exception(timeout=1), RuntimeError)
    finally:
        peer.kill()


def test_sustained_transport_failure_is_a_liveness_verdict(watchdir):
    """A transport that stops answering for a whole deadline (the
    coordinator-death case under the KV transport) latches a
    coordination error, so guarded syncs raise instead of polling a
    silent watch forever."""
    import shutil
    _start(watchdir, timeout=0.3)
    time.sleep(0.1)
    # the store is gone: every beat now fails.  A beat that lands
    # between rmtree's walk and its rmdir leaves the directory "not
    # empty" (it failed so twice under -n 6 at PR 36): remove again
    while os.path.exists(watchdir):
        shutil.rmtree(watchdir, ignore_errors=True)
    t0 = time.monotonic()
    while time.monotonic() - t0 < 5 * 0.3:
        try:
            podwatch.check(phase="unit")
        except PeerLostError as e:
            assert "liveness transport failing" in str(e)
            break
        time.sleep(0.05)
    else:
        raise AssertionError("transport failure never latched")


# ---------------------------------------------------------------------
# span hygiene
# ---------------------------------------------------------------------

def test_watch_leaks_no_spans(watchdir):
    obs.clear()
    obs.enable()
    try:
        t = _start(watchdir)
        peer = _FakePeer(t, 1)
        time.sleep(0.3)
        peer.kill()
        deadline = time.monotonic() + 2.0
        while not podwatch.dead_peers() and time.monotonic() < deadline:
            time.sleep(0.02)
        with pytest.raises(PeerLostError):
            podwatch.barrier("leakcheck")
        podwatch.stop()
        assert obs.active_count() == 0
    finally:
        obs.disable()


# ---------------------------------------------------------------------
# checkpoint: pod abort format + topology remap
# ---------------------------------------------------------------------

@pytest.fixture
def pod3(monkeypatch):
    """Fake a 3-process runtime for the checkpoint-layer units: the
    barriers are no-ops (no real peers) and the process index is a
    settable cell."""
    cell = {"pid": 0}
    monkeypatch.setattr(multihost, "process_count", lambda: 3)
    monkeypatch.setattr(multihost, "process_index",
                        lambda: cell["pid"])
    monkeypatch.setattr(multihost, "barrier", lambda name: None)
    return cell


def _save_all(tmp_path, pod3, fp, slabs, records, val, nproc=3):
    for pid in range(nproc):
        pod3["pid"] = pid
        checkpoint.stream_save(str(tmp_path), fp, slabs, records,
                               ([np.full(3, val, np.float32)], None),
                               multiprocess=True)
    pod3["pid"] = 0


def test_pod_abort_save_meta_advances_only(tmp_path, pod3):
    fp = ("fp-abort",)
    _save_all(tmp_path, pod3, fp, 4, 48, 4.0)
    # an abort at a LOWER watermark must not regress the meta
    checkpoint.stream_save(str(tmp_path), fp, 3, 36,
                           ([np.full(3, 3.0, np.float32)], None),
                           multiprocess=True, rendezvous=False)
    got = checkpoint.stream_load(str(tmp_path), fp, multiprocess=True)
    assert got[0] == 4
    # an abort at a HIGHER watermark advances it (state-first, no
    # barrier, "abort" recorded)
    checkpoint.stream_save(str(tmp_path), fp, 5, 60,
                           ([np.full(3, 5.0, np.float32)], None),
                           multiprocess=True, rendezvous=False)
    got = checkpoint.stream_load(str(tmp_path), fp, multiprocess=True)
    assert got[0] == 5
    assert np.array_equal(got[2][0][0], np.full(3, 5.0, np.float32))
    assert checkpoint._read_meta(str(tmp_path)).get("abort") is True


def test_torn_abort_never_flips_meta(tmp_path, pod3):
    """A fault between the abort's state write and its meta rename
    (the checkpoint.meta chaos seam) leaves the OLD meta intact and
    loadable — meta can never name a watermark whose write tore."""
    fp = ("fp-torn",)
    _save_all(tmp_path, pod3, fp, 4, 48, 4.0)
    _chaos.inject("checkpoint.meta", nth=1)
    try:
        with pytest.raises(_chaos.ChaosError):
            checkpoint.stream_save(
                str(tmp_path), fp, 6, 72,
                ([np.full(3, 6.0, np.float32)], None),
                multiprocess=True, rendezvous=False)
    finally:
        _chaos.clear()
    got = checkpoint.stream_load(str(tmp_path), fp, multiprocess=True)
    assert got[0] == 4                # the old checkpoint still stands
    assert np.array_equal(got[2][0][0], np.full(3, 4.0, np.float32))


def test_topology_remap_load_after_shrink(tmp_path, pod3, monkeypatch):
    """A checkpoint cut by a 3-process pod loads on a 2-process (and a
    1-process) topology: the fold partials are replicated global
    values, so any surviving shard file is a complete resume point —
    and the remap is reported through ``info``."""
    fp = ("fp-remap",)
    _save_all(tmp_path, pod3, fp, 4, 48, 7.0)
    # the shrunk pod: 2 processes; old p1's file may even be missing
    os.remove(os.path.join(str(tmp_path), "stream_state.p1.w4.npz"))
    monkeypatch.setattr(multihost, "process_count", lambda: 2)
    for newpid in (0, 1):
        pod3["pid"] = newpid
        info = {}
        got = checkpoint.stream_load(str(tmp_path), fp,
                                     multiprocess=True, info=info)
        assert got is not None and got[0] == 4 and got[1] == 48
        assert np.array_equal(got[2][0][0],
                              np.full(3, 7.0, np.float32))
        assert info == {"remapped_from": 3}
    # ...and on a single process (multiprocess=False -> nproc 1)
    monkeypatch.setattr(multihost, "process_count", lambda: 1)
    pod3["pid"] = 0
    info = {}
    got = checkpoint.stream_load(str(tmp_path), fp, multiprocess=False,
                                 info=info)
    assert got is not None and got[0] == 4
    assert info == {"remapped_from": 3}
    # a resumed run's next save records the remap for the audit trail
    monkeypatch.setattr(multihost, "process_count", lambda: 2)
    for newpid in (0, 1):
        pod3["pid"] = newpid
        checkpoint.stream_save(str(tmp_path), fp, 6, 72,
                               ([np.full(3, 9.0, np.float32)], None),
                               multiprocess=True, remap_from=3)
    meta = checkpoint._read_meta(str(tmp_path))
    assert meta["nproc"] == 2 and meta["remapped_from"] == 3
    # clearing on the SHRUNK pod sweeps every pid's shard files (pid 0
    # sweeps the dead peers' leftovers too)
    pod3["pid"] = 0
    checkpoint.stream_clear(str(tmp_path), multiprocess=True)
    assert [p for p in os.listdir(str(tmp_path))
            if p.startswith("stream_")] == []


def test_single_process_clear_sweeps_pod_files(tmp_path, pod3,
                                               monkeypatch):
    fp = ("fp-sweep",)
    _save_all(tmp_path, pod3, fp, 2, 24, 1.0)
    monkeypatch.setattr(multihost, "process_count", lambda: 1)
    checkpoint.stream_clear(str(tmp_path), multiprocess=False)
    assert [p for p in os.listdir(str(tmp_path))
            if p.startswith("stream_")] == []


# ---------------------------------------------------------------------
# BLT013: multi-process stream without a recovery path
# ---------------------------------------------------------------------

ADD1 = lambda v: v + 1  # noqa: E731 — module-level: stable fingerprint


def _streamed():
    x = np.zeros((8, 4), np.float32)
    return bolt.fromcallback(lambda i: x[i], (8, 4), mode="tpu",
                             dtype=np.float32, chunks=4).map(ADD1)


def _fake_pod(monkeypatch):
    """Make the CHECKER see a 2-process mesh on this 1-process host —
    applied AFTER the pipeline is built (the factory itself routes
    per_process ingest off the topology, and building under the fake
    would materialise instead of stream).  The BLT012 divisibility
    rule is quieted — it has its own tests."""
    monkeypatch.setattr(multihost, "mesh_process_count", lambda mesh: 2)
    monkeypatch.setattr(multihost, "slab_divisibility_error",
                        lambda *a: None)


def test_blt013_no_checkpoint_dir(monkeypatch):
    from bolt_tpu import analysis
    arr = _streamed()
    _fake_pod(monkeypatch)
    rep = analysis.check(arr)
    assert rep.has("BLT013")
    d = [d for d in rep.diagnostics if d.code == "BLT013"][0]
    assert d.severity == "warning"
    assert "NO checkpoint dir" in d.message
    assert rep.ok                     # warning, not error


def test_blt013_quiet_with_checkpoint_dir(monkeypatch, tmp_path):
    from bolt_tpu import analysis, stream
    arr = _streamed()
    _fake_pod(monkeypatch)
    with stream.resumable(str(tmp_path)):
        rep = analysis.check(arr)
    assert not rep.has("BLT013")


def test_blt013_sub_pod_mesh(monkeypatch, tmp_path):
    from bolt_tpu import analysis, stream
    arr = _streamed()
    _fake_pod(monkeypatch)
    monkeypatch.setattr(multihost, "process_count", lambda: 4)
    with stream.resumable(str(tmp_path)):
        rep = analysis.check(arr)
    assert rep.has("BLT013")
    d = [d for d in rep.diagnostics if d.code == "BLT013"][0]
    assert "SUB-POD" in d.message


def test_explain_shows_recovery_plan(monkeypatch, tmp_path):
    from bolt_tpu import analysis, stream
    arr = _streamed()
    arr2 = _streamed()
    _fake_pod(monkeypatch)
    txt = analysis.explain(arr)
    assert "recovery plan" in txt
    assert "PeerLostError" in txt
    assert "BLT013" in txt            # the no-checkpoint shape
    with stream.resumable(str(tmp_path)):
        txt2 = analysis.explain(arr2)
    assert "resume topology" in txt2 and str(tmp_path) in txt2


def test_config_reports_watchdog(watchdir):
    cfg = podwatch.config()
    assert set(cfg) == {"timeout", "interval", "transport", "nproc"}
    _start(watchdir, nproc=3, interval=0.07, timeout=0.9)
    cfg = podwatch.config()
    assert cfg["timeout"] == 0.9 and cfg["interval"] == 0.07
    assert cfg["transport"] == "file" and cfg["nproc"] == 3


def test_blt108_exempts_podwatch():
    """The heartbeat thread lives in a blessed BLT108 home."""
    from bolt_tpu.analysis import astlint
    assert any(e.endswith(os.path.join("parallel", "podwatch.py"))
               for e in astlint._EXEMPT["BLT108"])
