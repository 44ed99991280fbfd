#!/usr/bin/env python
"""Chaos-injection harness for the resumable streaming executor (ISSUE 9).

Proves the two kill-mid-run contracts end to end:

* **thread-raise variant** (in process): ``_chaos.inject`` fires an
  exception inside an uploader at a chosen slab; with ``stream.retries``
  the run survives it in place, and without retries the run dies having
  checkpointed — the re-run resumes from the last retired slab.  Either
  way the result must be BIT-IDENTICAL to the uninterrupted run.
* **subprocess ``kill -9`` variant**: a child process streams the same
  reduction with ``BOLT_CHAOS=stream.upload:<n>:kill`` in its env and is
  SIGKILLed mid-run — no unwinding, no ``finally`` — then a fresh child
  resumes from the surviving checkpoint.  The harness asserts the
  resumed result is bit-identical AND that recovery wall time stays
  under 1.5x the clean run (the resumed child streams only the
  remaining slabs).

The **matrix** mode (ISSUE 12) sweeps EVERY registered fault seam
(``bolt_tpu._chaos.SEAMS``) × {``raise``, ``kill``} and asserts, for
each cell, either *recovery* (the fault is absorbed in place, or a
re-run resumes bit-identically) or a *pointed error* (the fault
surfaces as a named, actionable exception — never a hang, never silent
corruption).  Seam drivers: the stream/checkpoint seams ride the
subprocess streamed workload; the shuffle seams (ISSUE 18:
``stream.shuffle``/``stream.spill``) ride a forced-spill streamed swap
— raise is absorbed in place by the ``stream.retries`` fence, kill -9
mid-spill resumes from the spill manifest bit-identically; the pod
seams (heartbeat, barrier,
supervisor elect/rejoin) ride a fake-peer pod fixture in a child
process; ``multihost.collective`` rides a REAL 2-process localhost
cluster (skipped without the CPU collective transport).  A seam added
to ``SEAMS`` without a driver here fails its cells loudly.

Usage::

    python scripts/chaos_run.py            # run both variants, assert
    python scripts/chaos_run.py --matrix   # the seam x action sweep
    python scripts/chaos_run.py --child .. # internal: one streamed run

Every child is pinned to the CPU backend (:func:`_child_env`): the
parent may hold the chip (``main`` runs its thread variant in
process), a chip belongs to one process, and a ``kill -9`` must never
land on the process that holds it.  What the children prove —
checkpoint, fence and resume control flow, bit-identity — does not
depend on the backend; their wall seconds are CPU seconds and are
labelled so.

``tests/test_resilience.py`` reuses :func:`run_resume_bench`.
"""

import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

# the child's streamed workload: integer-valued f64 so sums are exact
# under ANY fold order — "bit-identical" is then checkable against both
# the clean child run and the NumPy oracle
N_RECORDS = 64
VSHAPE = (16, 8)
CHUNKS = 8                       # -> 8 slabs
PACE_S = 0.02                    # per-slab storage-fetch pacing: keeps
#                                  the checkpoint cadence ahead of the
#                                  kill (and emulates a real loader)


def _data():
    n = N_RECORDS * int(np.prod(VSHAPE))
    return ((np.arange(n) % 13) - 6).astype(np.float64).reshape(
        (N_RECORDS,) + VSHAPE)


def child_main(argv):
    """One streamed run over the canonical workload: the kill target.
    Writes the result array and a JSON sidecar (in-run wall seconds +
    fault counters) — a SIGKILLed child writes neither, which is the
    point.  ``--arm seam:nth:action[,seam:nth:action...]`` arms fault
    points programmatically (the matrix mode's multi-seam cells; the
    single-seam ``BOLT_CHAOS`` env form still works)."""
    import jax
    import bolt_tpu as bolt
    from bolt_tpu import _chaos, engine
    from bolt_tpu.obs.trace import clock

    args = dict(zip(argv[::2], argv[1::2]))
    ck_dir, out = args["--dir"], args["--out"]
    for spec in filter(None, args.get("--arm", "").split(",")):
        seam, nth, action = spec.split(":")
        _chaos.inject(seam, nth=int(nth), action=action)
    # the stream.encode seam only fires with a codec armed; the matrix
    # cell streams the same integer-valued workload as FLOAT32 under
    # the LOSSLESS delta codec, so the oracle compare stays exact
    codec_name = args.get("--codec") or None
    data = _data()
    if codec_name:
        data = data.astype(np.float32)

    def loader(idx):
        time.sleep(PACE_S)
        return data[idx]

    mesh = jax.make_mesh((jax.device_count(),), ("k",))
    src = bolt.fromcallback(loader, data.shape, mesh, dtype=data.dtype,
                            chunks=CHUNKS, checkpoint=ck_dir,
                            codec=codec_name)
    t0 = clock()
    res = np.asarray(src.sum().toarray())
    wall = clock() - t0
    np.save(out, res)
    ec = engine.counters()
    with open(out + ".json", "w") as f:
        json.dump({"wall": wall, "resumes": ec["stream_resumes"],
                   "retries": ec["stream_retries"],
                   "chunks": ec["stream_chunks"],
                   "checkpoint_bytes": ec["checkpoint_bytes"]}, f)
    return 0


def _child_env(**extra):
    """Environment of a kill-target child: this process's, pinned to
    the CPU backend (see the module docstring), chaos disarmed."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env.pop("BOLT_CHAOS", None)
    return env


def _run_child(ck_dir, out, chaos=None):
    env = _child_env(BOLT_STREAM_UPLOAD_THREADS="1")  # deterministic
    #                                                   watermark
    if chaos:
        env["BOLT_CHAOS"] = chaos
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child",
         "--dir", ck_dir, "--out", out],
        env=env, capture_output=True, text=True, timeout=600)
    return proc


def run_resume_bench(kill_at=6, workdir=None):
    """The subprocess kill -9 proof, packaged for its readers (``main``
    and the tests): clean child run, SIGKILLed child (``BOLT_CHAOS``
    arms the kill at upload ``kill_at`` of 8), resumed child.  Returns
    the measurement dict; raises on a child that failed for any reason
    OTHER than the intended kill."""
    from bolt_tpu import checkpoint as ckpt
    workdir = workdir or tempfile.mkdtemp(prefix="bolt-chaos-")
    ck_dir = os.path.join(workdir, "ckpt")
    clean_out = os.path.join(workdir, "clean.npy")
    resume_out = os.path.join(workdir, "resumed.npy")

    proc = _run_child(ck_dir, clean_out)
    if proc.returncode != 0:
        raise RuntimeError("clean chaos child failed:\n%s" % proc.stderr)
    with open(clean_out + ".json") as f:
        clean = json.load(f)

    proc = _run_child(ck_dir, resume_out,
                      chaos="stream.upload:%d:kill" % kill_at)
    killed_rc = proc.returncode
    if killed_rc == 0:
        raise RuntimeError("chaos child was supposed to die and did not")
    if not ckpt.stream_pending(ck_dir):
        raise RuntimeError(
            "killed child left no checkpoint (rc=%s):\n%s"
            % (killed_rc, proc.stderr))

    proc = _run_child(ck_dir, resume_out)
    if proc.returncode != 0:
        raise RuntimeError("resume chaos child failed:\n%s" % proc.stderr)
    with open(resume_out + ".json") as f:
        resumed = json.load(f)

    res_clean = np.load(clean_out)
    res_resumed = np.load(resume_out)
    oracle = _data().sum(axis=0)
    return {
        "clean_s": clean["wall"],
        "recovery_s": resumed["wall"],
        "killed_rc": killed_rc,
        "resumes": resumed["resumes"],
        "slabs_resumed": resumed["chunks"],
        "slabs_total": clean["chunks"],
        "identical": bool(np.array_equal(res_clean, res_resumed)
                          and np.array_equal(res_resumed, oracle)),
        "stale_checkpoint": ckpt.stream_pending(ck_dir),
    }


def run_thread_variant():
    """The in-process half: an uploader RAISES mid-run.  Covers both
    policies — retries absorb the fault in one run; without retries the
    failed run checkpoints and the re-run resumes.  Returns the
    measurement dict (all booleans must be True)."""
    import jax
    import bolt_tpu as bolt
    from bolt_tpu import _chaos as chaos, checkpoint as ckpt, engine, stream

    data = _data()
    mesh = jax.make_mesh((jax.device_count(),), ("k",))

    def make(ck=None):
        return bolt.fromcallback(lambda idx: data[idx], data.shape, mesh,
                                 dtype=np.float64, chunks=CHUNKS,
                                 checkpoint=ck)

    clean = np.asarray(make().sum().toarray())

    # retry policy: the fault is absorbed in-run
    chaos.inject("stream.upload", nth=3)
    c0 = engine.counters()
    with stream.retries(1):
        retried = np.asarray(make().sum().toarray())
    c1 = engine.counters()
    chaos.clear()
    retry_ok = (np.array_equal(retried, clean)
                and c1["stream_retries"] - c0["stream_retries"] == 1)

    # checkpoint + resume: the fault kills the run
    ck_dir = tempfile.mkdtemp(prefix="bolt-chaos-thread-")
    chaos.inject("stream.upload", nth=5)
    died = False
    try:
        with stream.uploaders(1):
            make(ck_dir).sum().cache()
    except chaos.ChaosError:
        died = True
    chaos.clear()
    c2 = engine.counters()
    resumed = np.asarray(make(ck_dir).sum().toarray())
    c3 = engine.counters()
    return {
        "retry_ok": retry_ok,
        "died": died,
        "checkpointed": c2["checkpoint_bytes"] > c1["checkpoint_bytes"],
        "resumed": c3["stream_resumes"] - c2["stream_resumes"] == 1,
        "identical": bool(np.array_equal(resumed, clean)),
        "stale_checkpoint": ckpt.stream_pending(ck_dir),
    }


# ---------------------------------------------------------------------
# the seam x action matrix (ISSUE 12)
# ---------------------------------------------------------------------

# where each streamed-workload seam trips (of 8 slabs): late enough
# that a checkpoint exists, early enough that slabs remain to resume
_STREAM_NTH = {"stream.encode": 5, "stream.upload": 5,
               "stream.dispatch": 4, "stream.fold": 1,
               "stream.checkpoint": 3, "checkpoint.meta": 3,
               "checkpoint.corrupt": 3}
# the shuffle seams (ISSUE 18) ride the forced-spill streamed swap:
# stream.shuffle hits once per slab re-bucket dispatch (8 total),
# stream.spill once per bucket write — nth=12 lands INSIDE a later
# slab's bucket writes with at least one slab already fenced in the
# manifest, whatever bucket width the planner picked for the local
# device count
_SHUFFLE_NTH = {"stream.shuffle": 4, "stream.spill": 12}
_POD_NTH = {"podwatch.heartbeat": 3, "multihost.barrier": 1,
            "supervisor.elect": 1, "supervisor.rejoin": 1}


def _run_stream_child(ck_dir, out, arm="", codec=None):
    env = _child_env(BOLT_STREAM_UPLOAD_THREADS="1")
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--dir", ck_dir, "--out", out, "--arm", arm]
    if codec:
        cmd += ["--codec", codec]
    return subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=600)


def pod_child_main(argv):
    """One matrix cell of the POD seams, run in a CHILD process (the
    kill cells SIGKILL it): a fake 2-member pod — file-transport watch
    plus a beating fake peer — drives the seam's recovery scenario and
    asserts the recovery semantics in raise mode.  ``BOLT_MATRIX_ARM``
    arms the seam; the re-run (arm off) proves the clean scenario
    completes after a kill."""
    import threading
    from bolt_tpu import _chaos
    from bolt_tpu.parallel import multihost, podwatch, supervisor

    seam, mode = argv[0], argv[1]
    hb = os.environ["BOLT_MATRIX_HB"]
    armed = os.environ.get("BOLT_MATRIX_ARM") == "1"
    if armed:
        _chaos.inject(seam, nth=_POD_NTH[seam], action=mode)
    assert podwatch.start(2, 0, dir=hb, interval=0.05, timeout=0.5)
    tr = podwatch._WATCH.transport
    stop = threading.Event()

    def beat():
        seq = 0
        while not stop.is_set():
            seq += 1
            tr.beat(1, seq)
            for gen in range(8):
                tr.barrier_mark("chaos_probe", gen, 1)
            stop.wait(0.03)

    th = threading.Thread(target=beat, daemon=True)
    th.start()

    def wait_for(pred, what, timeout=10.0):
        deadline = time.monotonic() + timeout
        while not pred():
            if time.monotonic() > deadline:
                raise AssertionError("%s never happened" % what)
            time.sleep(0.02)

    try:
        if seam == "podwatch.heartbeat":
            # the beat absorbs a raise IN PLACE: peers stay alive and
            # the watch keeps beating (a kill lands before this check)
            wait_for(lambda: (not armed)
                     or _chaos.stats(seam)[0] >= _POD_NTH[seam] + 2,
                     "post-fault heartbeats")
            assert podwatch.dead_peers() == ()
            assert podwatch._WATCH.beat_errors == (1 if armed else 0)
        elif seam == "multihost.barrier":
            orig = multihost.process_count
            multihost.process_count = lambda: 2
            try:
                pointed = False
                try:
                    multihost.barrier("chaos_probe")
                except _chaos.ChaosError:
                    pointed = True     # the POINTED, named fault
                multihost.barrier("chaos_probe")   # the retry lands
                assert pointed == armed
            finally:
                multihost.process_count = orig
        elif seam in ("supervisor.elect", "supervisor.rejoin"):
            calls = []

            def reform(addr, num_processes, process_id=None,
                       epoch=None, init_timeout=None):
                calls.append(int(num_processes))
                podwatch.notify_reform()
                return process_id

            multihost.reform = reform
            sup = supervisor.Supervisor(backoff=0.1)
            try:
                if seam == "supervisor.elect":
                    # a peer death: attempt 1 trips the seam, the
                    # backoff retry completes the reform
                    wait_for(lambda: 1 in podwatch.alive_peers(),
                             "fake peer alive")
                    podwatch.mark_dead(1)
                    wait_for(lambda: sup.stats()["reforms"] == 1,
                             "supervised reform")
                    assert sup.stats()["backoffs"] == \
                        (1 if armed else 0)
                    assert calls == [1]
                else:
                    # a rejoin announcement: the tripped handler DROPS
                    # it (no thrash); the next announcement is honored
                    wait_for(lambda: 1 in podwatch.alive_peers(),
                             "fake peer alive")
                    if armed:
                        podwatch.rejoin("wX")
                        wait_for(lambda: _chaos.stats(seam)[1] == 1,
                                 "rejoin handler trip")
                        time.sleep(0.3)
                        assert sup.stats()["reforms"] == 0
                    podwatch.rejoin("wY")
                    wait_for(lambda: sup.stats()["reforms"] == 1,
                             "reform-up")
                    assert calls[-1] == 3   # i0 + i1 + the rejoiner
            finally:
                sup.close()
    finally:
        stop.set()
        th.join()
        podwatch.stop()
    print("POD-CELL OK", flush=True)
    return 0


def _pod_cell(seam, mode, workdir):
    """Run one pod-seam cell: the armed child (raise: asserts the
    absorb/retry semantics in place; kill: dies AT the seam), then for
    kill cells a clean re-run proving the scenario completes."""
    import shutil
    hb = os.path.join(workdir, "hb-%s-%s" % (seam.replace(".", "_"),
                                             mode))

    def run(arm):
        env = _child_env(BOLT_MATRIX_HB=hb,
                         BOLT_MATRIX_ARM="1" if arm else "0")
        return subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--pod-child",
             seam, mode], env=env, capture_output=True, text=True,
            timeout=120)

    os.makedirs(hb, exist_ok=True)
    try:
        proc = run(arm=True)
        if mode == "raise":
            if proc.returncode != 0:
                return ("FAIL", "raise cell rc=%s:\n%s"
                        % (proc.returncode, proc.stderr[-1500:]))
            return ("recovered", "fault absorbed/retried in place")
        if proc.returncode != -9:
            return ("FAIL", "kill cell rc=%s (expected -9):\n%s"
                    % (proc.returncode, proc.stderr[-1500:]))
        shutil.rmtree(hb, ignore_errors=True)
        os.makedirs(hb, exist_ok=True)
        proc = run(arm=False)
        if proc.returncode != 0:
            return ("FAIL", "post-kill re-run rc=%s:\n%s"
                    % (proc.returncode, proc.stderr[-1500:]))
        return ("recovered", "died at the seam; restarted scenario "
                             "completes")
    finally:
        shutil.rmtree(hb, ignore_errors=True)


def _stream_cell(seam, mode, workdir):
    """Run one stream/checkpoint-seam cell through the subprocess
    streamed workload: the armed child dies (or raises out), then a
    re-run must either RESUME bit-identically or refuse POINTEDLY
    (checkpoint.corrupt names the rotted file)."""
    from bolt_tpu import checkpoint as ckpt
    tag = "%s-%s" % (seam.replace(".", "_"), mode)
    ck = os.path.join(workdir, "ck-" + tag)
    out = os.path.join(workdir, "out-" + tag + ".npy")
    nth = _STREAM_NTH[seam]
    arm = "%s:%d:%s" % (seam, nth, mode)
    # the encode seam streams under the lossless codec (the seam never
    # fires uncompressed); resume must re-encode bit-identically
    codec = "delta-f32" if seam == "stream.encode" else None
    if seam == "checkpoint.corrupt" and mode == "raise":
        # the corruption seam's raise form ROTS the just-written state
        # under the atomic rename and lets the run continue — a later
        # kill leaves the rotted checkpoint for the resume to refuse
        arm += ",stream.upload:7:kill"
    proc = _run_stream_child(ck, out, arm=arm, codec=codec)
    if proc.returncode == 0:
        return ("FAIL", "armed child was supposed to die and did not")
    if mode == "kill" or "," in arm:
        if proc.returncode != -9:
            return ("FAIL", "kill child rc=%s (expected -9):\n%s"
                    % (proc.returncode, proc.stderr[-1500:]))
    elif "ChaosError" not in proc.stderr:
        return ("FAIL", "raise child died WITHOUT the pointed "
                        "ChaosError:\n%s" % proc.stderr[-1500:])
    proc = _run_stream_child(ck, out, codec=codec)
    if seam == "checkpoint.corrupt" and mode == "raise":
        # recovery is impossible by design — the contract is the
        # POINTED refusal naming the file, then a clean restart
        if proc.returncode == 0:
            return ("FAIL", "resume accepted a bit-rotted checkpoint")
        if "CheckpointCorruptError" not in proc.stderr \
                or "stream_state" not in proc.stderr:
            return ("FAIL", "corrupt resume died without the pointed "
                            "refusal:\n%s" % proc.stderr[-1500:])
        import shutil
        shutil.rmtree(ck, ignore_errors=True)
        proc = _run_stream_child(ck, out)
        if proc.returncode != 0:
            return ("FAIL", "clean restart after the refusal failed:"
                            "\n%s" % proc.stderr[-1500:])
        return ("pointed", "rotted shard refused by name; clean "
                           "restart recovers")
    if proc.returncode != 0:
        return ("FAIL", "resume child failed:\n%s"
                % proc.stderr[-1500:])
    if not np.array_equal(np.load(out), _data().sum(axis=0)):
        return ("FAIL", "resumed result differs from the oracle")
    if ckpt.stream_pending(ck):
        return ("FAIL", "resumed run left a stale checkpoint")
    return ("recovered", "re-run resumed bit-identically")


def shuffle_child_main(argv):
    """One streamed FORCED-SPILL swap over the canonical workload (the
    shuffle seams' kill target): ``stream.spill(dir, budget=1)`` makes
    every re-keyed bucket spill through the checkpoint slab format, and
    ``stream.retries(1)`` licenses the in-place retry the raise cells
    assert.  Writes the swapped array plus a JSON sidecar of the
    shuffle/spill counters; a SIGKILLed child writes neither — but its
    spill manifest survives, which is the point."""
    import jax
    import bolt_tpu as bolt
    from bolt_tpu import _chaos, checkpoint as ckpt, engine, stream

    args = dict(zip(argv[::2], argv[1::2]))
    spill_dir, out = args["--dir"], args["--out"]
    for spec in filter(None, args.get("--arm", "").split(",")):
        seam, nth, action = spec.split(":")
        _chaos.inject(seam, nth=int(nth), action=action)
    data = _data()

    def loader(idx):
        time.sleep(PACE_S)
        return data[idx]

    mesh = jax.make_mesh((jax.device_count(),), ("k",))
    src = bolt.fromcallback(loader, data.shape, mesh, dtype=data.dtype,
                            chunks=CHUNKS)
    with stream.retries(1), stream.spill(dir=spill_dir, budget=1):
        res = np.asarray(src.swap((0,), (0,))._data)
    np.save(out, res)
    ckpt.spill_clear(spill_dir)
    ec = engine.counters()
    with open(out + ".json", "w") as f:
        json.dump({"retries": ec["stream_retries"],
                   "resumes": ec["stream_resumes"],
                   "spill_bytes": ec["spill_bytes"],
                   "shuffle_bytes": ec["shuffle_bytes"],
                   "stale_spill": ckpt.spill_pending(spill_dir)}, f)
    return 0


def _run_shuffle_child(spill_dir, out, arm=""):
    env = _child_env(BOLT_STREAM_UPLOAD_THREADS="1")
    return subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--shuffle-child",
         "--dir", spill_dir, "--out", out, "--arm", arm],
        env=env, capture_output=True, text=True, timeout=600)


def _shuffle_cell(seam, mode, workdir):
    """One shuffle-seam cell (ISSUE 18): raise is absorbed IN PLACE by
    the armed ``stream.retries`` fence (same-run bit-identity, no
    stale spill); kill -9 mid-spill leaves the fingerprint directory's
    per-slab manifest, and the re-run RESUMES from it — skipping the
    fenced slabs — bit-identically."""
    from bolt_tpu import checkpoint as ckpt
    tag = "%s-%s" % (seam.replace(".", "_"), mode)
    sp = os.path.join(workdir, "spill-" + tag)
    out = os.path.join(workdir, "out-" + tag + ".npy")
    oracle = np.transpose(_data(), (1, 0, 2))
    proc = _run_shuffle_child(
        sp, out, arm="%s:%d:%s" % (seam, _SHUFFLE_NTH[seam], mode))
    if mode == "raise":
        if proc.returncode != 0:
            return ("FAIL", "raise cell rc=%s:\n%s"
                    % (proc.returncode, proc.stderr[-1500:]))
        with open(out + ".json") as f:
            sidecar = json.load(f)
        if sidecar["retries"] < 1:
            return ("FAIL", "fault was not absorbed by a stream retry")
        if not np.array_equal(np.load(out), oracle):
            return ("FAIL", "retried swap differs from the oracle")
        if sidecar["stale_spill"]:
            return ("FAIL", "run left stale spill files after clear")
        return ("recovered", "fault absorbed in place by the "
                             "stream.retries fence")
    if proc.returncode != -9:
        return ("FAIL", "kill child rc=%s (expected -9):\n%s"
                % (proc.returncode, proc.stderr[-1500:]))
    if not ckpt.spill_pending(sp):
        return ("FAIL", "killed child left no spill manifest to resume")
    proc = _run_shuffle_child(sp, out)
    if proc.returncode != 0:
        return ("FAIL", "resume child failed:\n%s" % proc.stderr[-1500:])
    with open(out + ".json") as f:
        sidecar = json.load(f)
    if not np.array_equal(np.load(out), oracle):
        return ("FAIL", "resumed swap differs from the oracle")
    if sidecar["resumes"] < 1:
        return ("FAIL", "re-run did not adopt the spill manifest")
    if sidecar["stale_spill"]:
        return ("FAIL", "resumed run left stale spill files after clear")
    return ("recovered", "killed mid-spill; re-run resumes from the "
                         "spill manifest bit-identically")


def _collective_cell(seam, mode, workdir):
    """multihost.collective rides a REAL 2-process localhost cluster:
    the armed worker dies at a slab dispatch, the harness raises the
    POINTED error naming it, and a restarted cluster RESUMES from the
    shard checkpoint bit-identically."""
    import jax
    if "jax_cpu_collectives_implementation" not in getattr(
            jax.config, "values", {}):
        return ("skipped", "no CPU cross-process collective transport")
    from bolt_tpu.utils import load_script
    mh = load_script("multihost_harness")
    ck = os.path.join(workdir, "ck-coll-" + mode)
    env = {"BOLT_MH_CKPT": ck, "BOLT_CHECKPOINT_EVERY": "1",
           "BOLT_POD_TIMEOUT": "2"}
    try:
        mh.run_cluster("resume", nproc=2, devs=1, timeout=120, env=env,
                       worker_env={1: {"BOLT_CHAOS":
                                       "%s:3:%s" % (seam, mode)}})
        return ("FAIL", "armed cluster was supposed to fail and did "
                        "not")
    except RuntimeError as exc:
        if "process 1 died" not in str(exc):
            return ("FAIL", "cluster failed WITHOUT naming the dead "
                            "process: %s" % exc)
    res, out, _ = mh.run_cluster("resume", nproc=2, devs=1,
                                 timeout=120, env=env)
    if not all(r["resumes"] >= 1 for r in res):
        return ("FAIL", "restarted cluster did not resume: %s" % res)
    return ("pointed", "harness error names the dead process; "
                       "restarted cluster resumes from the shard "
                       "checkpoint")


def run_matrix():
    """Sweep every registered seam x {raise, kill}; assert recovery or
    a pointed error for each cell.  Returns the process exit code."""
    import shutil
    from bolt_tpu import _chaos
    workdir = tempfile.mkdtemp(prefix="bolt-chaos-matrix-")
    cells = []
    try:
        for seam in _chaos.SEAMS:
            for mode in ("raise", "kill"):
                t0 = time.monotonic()
                if seam in _SHUFFLE_NTH:
                    outcome, detail = _shuffle_cell(seam, mode, workdir)
                elif seam in _STREAM_NTH:
                    outcome, detail = _stream_cell(seam, mode, workdir)
                elif seam in _POD_NTH:
                    outcome, detail = _pod_cell(seam, mode, workdir)
                elif seam == "multihost.collective":
                    outcome, detail = _collective_cell(seam, mode,
                                                       workdir)
                else:
                    outcome, detail = (
                        "FAIL", "no matrix driver for this seam — a "
                                "new chaos.hit() site needs a cell "
                                "here")
                cells.append((seam, mode, outcome, detail))
                print("%-22s %-6s %-10s %5.1fs  %s"
                      % (seam, mode, outcome,
                         time.monotonic() - t0, detail.splitlines()[0]),
                      flush=True)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    bad = [c for c in cells if c[2] == "FAIL"]
    print("== matrix: %d cells, %d recovered, %d pointed, %d skipped, "
          "%d FAILED"
          % (len(cells),
             sum(1 for c in cells if c[2] == "recovered"),
             sum(1 for c in cells if c[2] == "pointed"),
             sum(1 for c in cells if c[2] == "skipped"), len(bad)))
    for seam, mode, _, detail in bad:
        print("-- %s x %s:\n%s" % (seam, mode, detail))
    return 1 if bad else 0


def main():
    print("== thread-raise variant (in process)")
    tv = run_thread_variant()
    print("   %s" % json.dumps(tv))
    ok = (tv["retry_ok"] and tv["died"] and tv["checkpointed"]
          and tv["resumed"] and tv["identical"]
          and not tv["stale_checkpoint"])
    print("   -> %s" % ("OK" if ok else "MISMATCH"))

    print("== subprocess kill -9 variant (children on the CPU backend)")
    kv = run_resume_bench()
    print("   %s" % json.dumps(kv))
    bounded = kv["recovery_s"] < 1.5 * kv["clean_s"]
    ok2 = (kv["identical"] and kv["resumes"] >= 1
           and kv["slabs_resumed"] < kv["slabs_total"]
           and not kv["stale_checkpoint"] and bounded)
    print("   recovery %.3fs vs clean %.3fs (gate < 1.5x) -> %s"
          % (kv["recovery_s"], kv["clean_s"],
             "OK" if ok2 else "MISMATCH"))
    return 0 if ok and ok2 else 1


if __name__ == "__main__":
    if "--child" in sys.argv:
        sys.exit(child_main(sys.argv[2:]))
    if "--shuffle-child" in sys.argv:
        sys.exit(shuffle_child_main(sys.argv[2:]))
    if "--pod-child" in sys.argv:
        sys.exit(pod_child_main(sys.argv[2:]))
    if "--matrix" in sys.argv:
        sys.exit(run_matrix())
    sys.exit(main())
