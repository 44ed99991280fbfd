#!/usr/bin/env python
"""Localhost multi-process cluster harness: a REAL ``jax.distributed``
CPU cluster of N OS processes, for the pod-scale streaming suite.

``run_cluster(payload, ...)`` spawns N workers (each owning
``devs`` virtual CPU devices via ``--xla_force_host_platform_device_count``),
joins them through ``bolt_tpu.parallel.multihost.initialize`` (which
arms the gloo cross-process collective transport on CPU), runs the
named payload in every process, and returns the per-process JSON
results plus any ``.npy`` artifacts the payload saved.

The harness is also the pod's FAULT REPORTER: when one worker dies
(``kill -9``, an uncaught error) while its peers still run, the
survivors would block forever inside the next cross-host collective —
so the monitor terminates them and raises a POINTED ``RuntimeError``
naming the dead process and its exit code.  ``expect_dead=True``
(the checkpoint/resume kill tests) instead returns the exit codes.

Used by tests/test_multihost.py and scripts/examples.py; run standalone
as ``python scripts/multihost_harness.py`` for a smoke pass of the
parity payload.
"""

import json
import os
import subprocess
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_port():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _dump_stacks(procs, grace=1.5):
    """Ask every still-running worker to dump all thread stacks into
    its log (faulthandler on SIGUSR1, armed in worker_main) before the
    monitor SIGKILLs it — a wedged survivor's log otherwise says
    nothing about WHERE it wedged."""
    import signal
    alive = [p for p in procs if p.poll() is None]
    for p in alive:
        try:
            p.send_signal(signal.SIGUSR1)
        except OSError:
            pass
    hold = time.time() + grace
    while time.time() < hold and any(p.poll() is None for p in alive):
        time.sleep(0.05)


def free_ports(n):
    """``n`` DISTINCT free ports (all bound simultaneously before any
    is released — sequential ``free_port`` calls tend to hand the same
    just-released port back, and a reform coordinator reusing the old
    cluster's port would connect the survivors to the ORPHANED old
    service instead of the new one)."""
    import socket
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


# ---------------------------------------------------------------------
# the parent side
# ---------------------------------------------------------------------

def run_cluster(payload, nproc=2, devs=1, timeout=300, env=None,
                worker_env=None, expect_dead=False, out_dir=None,
                tolerate=(), extra_workers=None):
    """Stand up an ``nproc``-process cluster and run ``payload`` in
    every process.  Returns ``(results, out_dir, rcs)`` where
    ``results`` is the list of per-process result dicts (``None`` for a
    process that died) and ``rcs`` the exit codes.

    ``env`` adds to every worker's environment; ``worker_env`` is a
    ``{pid: {...}}`` per-worker overlay (how the fault tests arm
    ``BOLT_CHAOS`` on ONE process).  With ``expect_dead=False`` a
    worker death while peers still run raises the pointed
    ``RuntimeError``.  ``tolerate`` names pids whose death is the
    SCENARIO (the reform tests kill one worker and expect the
    survivors to detect it, reform and finish): a tolerated death
    neither terminates the survivors nor fails the run — its result
    slot is ``None`` and its exit code lands in ``rcs``.

    ``extra_workers`` is a ``{wid: {...env}}`` map of ADDITIONAL
    processes spawned OUTSIDE the initial cluster (``wid >= nproc``):
    the rejoiner of the 3→2→3 elastic scenario runs the same payload
    but skips the bootstrap ``multihost.initialize`` (arm
    ``BOLT_MH_REJOINER=1`` in its env) and joins later through
    ``supervisor.attach``.  Extra workers must succeed and their
    results are required before the exit-barrier release."""
    own_dir = out_dir is None
    if own_dir:
        out_dir = tempfile.mkdtemp(prefix="bolt-mh-")
    else:
        os.makedirs(out_dir, exist_ok=True)
    tolerate = set(tolerate)
    extra_workers = dict(extra_workers or {})
    base = dict(os.environ)
    base.pop("BOLT_CHAOS", None)         # never inherit a stale arming
    base.update({
        "BOLT_MH_PAYLOAD": str(payload),
        "BOLT_MH_NPROC": str(nproc),
        "BOLT_MH_DEVS": str(devs),
        "BOLT_MH_PORT": str(free_port()),
        "BOLT_MH_OUT": out_dir,
    })
    if env:
        base.update({k: str(v) for k, v in env.items()})
    wids = list(range(nproc)) + sorted(extra_workers)
    if wids != list(range(len(wids))):
        raise ValueError("extra_workers ids must be contiguous from "
                         "nproc (got %s)" % sorted(extra_workers))
    procs, logs = [], []
    for pid in wids:
        e = dict(base)
        if worker_env and pid in worker_env:
            e.update({k: str(v) for k, v in worker_env[pid].items()})
        if pid in extra_workers:
            e.update({k: str(v) for k, v in extra_workers[pid].items()})
        log = open(os.path.join(out_dir, "worker.%d.log" % pid), "wb")
        logs.append(log)
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker",
             str(pid)],
            env=e, stdout=log, stderr=subprocess.STDOUT))
    total = len(wids)
    rcs = [None] * total
    deadline = time.time() + timeout
    released = False
    try:
        while any(rc is None for rc in rcs):
            for pid, p in enumerate(procs):
                if rcs[pid] is None:
                    rcs[pid] = p.poll()
            if not released:
                # the EXIT BARRIER: a worker that finishes first must
                # not tear the coordination service down under a peer
                # still mid-payload (the peer's error-poll thread
                # aborts the process on "service unavailable").
                # Workers hold their teardown until this parent-side
                # release lands — written once every worker the
                # scenario expects to SURVIVE has durably produced its
                # result (or already exited).
                if all(rcs[pid] is not None
                       or os.path.exists(os.path.join(
                           out_dir, "result.%d.json" % pid))
                       for pid in range(total) if pid not in tolerate):
                    rel = os.path.join(out_dir, "release")
                    with open(rel + ".tmp", "w") as f:
                        f.write("1")
                    os.replace(rel + ".tmp", rel)
                    released = True
            bad = [pid for pid, rc in enumerate(rcs)
                   if rc is not None and rc != 0 and pid not in tolerate]
            if bad and any(rc is None for rc in rcs):
                # a peer is gone: survivors will block in the next
                # cross-host collective forever.  Short grace (they may
                # be dying of the same injected fault), then terminate
                # and report POINTEDLY which process died.
                grace = time.time() + 3.0
                while time.time() < grace and any(
                        p.poll() is None for p in procs):
                    time.sleep(0.05)
                _dump_stacks(procs)
                for p in procs:
                    if p.poll() is None:
                        p.kill()
                for pid, p in enumerate(procs):
                    if rcs[pid] is None:
                        rcs[pid] = p.wait()
                if not expect_dead:
                    dead = bad[0]
                    raise RuntimeError(
                        "multihost cluster: process %d died (exit code "
                        "%s) before the run finished — its peers were "
                        "blocked on the next cross-host collective and "
                        "have been terminated; see %s"
                        % (dead, rcs[dead],
                           os.path.join(out_dir,
                                        "worker.%d.log" % dead)))
                break
            if time.time() > deadline:
                _dump_stacks(procs)
                for p in procs:
                    if p.poll() is None:
                        p.kill()
                raise RuntimeError(
                    "multihost cluster timed out after %ss (logs in %s)"
                    % (timeout, out_dir))
            time.sleep(0.05)
        for pid, p in enumerate(procs):
            if rcs[pid] is None:
                rcs[pid] = p.wait()
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for log in logs:
            log.close()
    results = []
    for pid in range(total):
        path = os.path.join(out_dir, "result.%d.json" % pid)
        if os.path.exists(path):
            with open(path) as f:
                results.append(json.load(f))
        else:
            results.append(None)
    if not expect_dead:
        for pid, rc in enumerate(rcs):
            if pid in tolerate:
                continue              # its death IS the scenario
            if rc != 0 or results[pid] is None:
                with open(os.path.join(out_dir, "worker.%d.log" % pid),
                          "rb") as f:
                    tail = f.read()[-4000:].decode(errors="replace")
                raise RuntimeError(
                    "multihost worker %d failed (rc=%s):\n%s"
                    % (pid, rc, tail))
    return results, out_dir, rcs


# ---------------------------------------------------------------------
# the worker side
# ---------------------------------------------------------------------

def _bootstrap(pid):
    """Per-worker preamble: force the virtual CPU topology BEFORE any
    backend query, then join the cluster through the blessed
    multihost.initialize door (which arms gloo on CPU).  A REJOINER
    (``BOLT_MH_REJOINER=1`` — the replacement process of the elastic
    3→2→3 scenario) skips the initialize: it joins LATER through
    ``supervisor.attach`` once the incumbents publish a plan."""
    devs = int(os.environ["BOLT_MH_DEVS"])
    nproc = int(os.environ["BOLT_MH_NPROC"])
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=%d" % devs)
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, _REPO)
    from bolt_tpu.parallel import multihost
    if nproc > 1 and os.environ.get("BOLT_MH_REJOINER") != "1":
        ok = multihost.initialize(
            coordinator_address="127.0.0.1:%s" % os.environ["BOLT_MH_PORT"],
            num_processes=nproc, process_id=pid)
        assert ok, "multihost.initialize declined"
    return multihost


# user stage funcs at module level: bytecode-identical across processes
# AND across runs, so program keys (and checkpoint fingerprints) match
ADD1 = lambda v: v + 1  # noqa: E731


def _mesh():
    import jax
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()), ("k",))


def _crafted(n, vdim, period=8):
    """Bit-exactness-crafted data: period-``period`` integer pattern
    (+ a half-step per value slot).  Sums are exact in f32, every
    shard of a multiple-of-``period`` record range has the SAME mean,
    so the hierarchical (per-shard + collective) moments equal the
    single-process moments BIT for bit — the same trick the
    crafted-Welford stream suite uses.  ``period=4`` keeps the moments
    exact on shard lengths divisible by 4 (a 96-record key axis split
    3 ways into 8-record slab shards AND 2 ways into 12-record ones —
    the elastic 3→2→3 scenario's geometry)."""
    import numpy as np
    r = np.arange(n, dtype=np.float32) % period
    v = np.arange(vdim, dtype=np.float32) * 0.5
    return (r[:, None] + v[None, :]).astype(np.float32)


def _value(barray):
    """Host value of a (possibly replicated cross-process) result."""
    from bolt_tpu.parallel import multihost
    return multihost.local_value(barray._data)


def payload_stream_parity(pid):
    """The acceptance payload: streamed sum AND fused stats('sum','var')
    over a per-process fromcallback source, with the compile-once,
    zero-leaked-span, BLT012, fromiter and explain() proofs recorded."""
    import numpy as np
    import bolt_tpu as bolt
    from bolt_tpu import analysis, engine, obs
    from bolt_tpu.parallel import multihost
    out = os.environ["BOLT_MH_OUT"]
    n = int(os.environ.get("BOLT_MH_NKEYS", "64"))
    vdim = 8
    chunks = int(os.environ.get("BOLT_MH_CHUNKS", "16"))
    x = _crafted(n, vdim)
    mesh = _mesh()
    obs.clear()
    obs.enable()
    rows = []                       # list.append is thread-safe (the
    #                                 uploader pool calls concurrently)

    def loader(idx):
        rows.append(len(range(*idx[0].indices(n))))
        return x[idx]

    def make():
        return bolt.fromcallback(loader, (n, vdim), mesh,
                                 dtype=np.float32, chunks=chunks,
                                 per_process=True)

    res = {"pid": pid, "nproc": multihost.process_count()}

    # --- streamed sum: compile-once proof across TWO passes -----------
    c0 = engine.counters()
    s1 = make().map(ADD1).sum().cache()
    c1 = engine.counters()
    np.save(os.path.join(out, "sum.%d.npy" % pid), _value(s1))
    make().map(ADD1).sum().cache()
    c2 = engine.counters()
    res["aot_first_pass"] = c1["aot_compiles"] - c0["aot_compiles"]
    res["misses_first_pass"] = c1["misses"] - c0["misses"]
    res["recompiles_second_pass"] = (
        c2["aot_compiles"] - c1["aot_compiles"]
        + c2["misses"] - c1["misses"])
    res["transfer_bytes"] = c2["transfer_bytes"] - c0["transfer_bytes"]

    # --- fused multi-stat: stats("sum", "var") one pass ---------------
    st = make().map(ADD1).stats("sum", "var")
    np.save(os.path.join(out, "stats_sum.%d.npy" % pid),
            _value(st["sum"]))
    np.save(os.path.join(out, "stats_var.%d.npy" % pid),
            _value(st["var"]))

    # --- per-process ingest contract: this process produced ONLY its
    # own shard of every slab (3 passes x its fraction of the records)
    res["rows_produced"] = sum(rows)
    res["rows_expected"] = 3 * (n // multihost.process_count())

    # --- the per-host plan in explain() -------------------------------
    res["explain_multiprocess"] = (
        "MULTI-PROCESS" in analysis.explain(make().map(ADD1))
        if multihost.process_count() > 1 else True)

    # --- BLT012: an indivisible slab refuses, and check() forecasts ---
    bad = bolt.fromcallback(lambda idx: x[idx], (n, vdim), mesh,
                            dtype=np.float32, chunks=3,
                            per_process=True)
    if multihost.process_count() > 1:
        try:
            bad.map(ADD1).sum().cache()
            res["blt012_refused"] = False
        except ValueError as exc:
            res["blt012_refused"] = "BLT012" in str(exc)
        res["blt012_forecast"] = analysis.check(
            bad.map(ADD1)).has("BLT012")
    else:
        res["blt012_refused"] = res["blt012_forecast"] = True

    # --- fromiter: re-iterable streams per process; one-shot refuses --
    blocks = [x[i:i + chunks] for i in range(0, n, chunks)]
    fi = bolt.fromiter(blocks, (n, vdim), mesh, dtype=np.float32)
    np.save(os.path.join(out, "fromiter_sum.%d.npy" % pid),
            _value(fi.map(ADD1).sum().cache()))

    # --- a REPLICATING mesh axis: with >1 device per process, a 2-axis
    # mesh whose second axis does not shard the key replicates each
    # per-process shard across local devices — the local-box dedup and
    # the psum-over-participating-axes-only paths must still fold
    # exactly (key extent 6 keeps axis "b" unabsorbed)
    import jax
    if multihost.process_count() > 1 and len(jax.devices()) >= 4:
        from jax.sharding import Mesh
        dv = np.asarray(jax.devices()).reshape(
            multihost.process_count(), -1)
        mesh2 = Mesh(dv, ("a", "b"))
        xq = (np.arange(6 * 4) % 4).astype(np.float32).reshape(6, 4)
        srcq = bolt.fromcallback(lambda idx: xq[idx], (6, 4), mesh2,
                                 dtype=np.float32, chunks=2,
                                 per_process=True)
        sq = _value(srcq.map(ADD1).sum().cache())
        res["replicated_axis_ok"] = bool(
            np.array_equal(sq, (xq + 1).sum(axis=0)))
    if multihost.process_count() > 1:
        try:
            bolt.fromiter((b for b in blocks), (n, vdim), mesh,
                          dtype=np.float32)
            res["oneshot_refused"] = False
        except ValueError as exc:
            res["oneshot_refused"] = "one-shot" in str(exc).lower() \
                or "RE-ITERABLE" in str(exc)
    else:
        res["oneshot_refused"] = True

    res["leaked_spans"] = obs.active_count()
    obs.disable()
    return res


def payload_single_ref(pid):
    """The single-process reference: identical data and pipelines on a
    one-process mesh of the SAME total device count — the bit-identity
    baseline the 2-process run is compared against."""
    import numpy as np
    import bolt_tpu as bolt
    out = os.environ["BOLT_MH_OUT"]
    n = int(os.environ.get("BOLT_MH_NKEYS", "64"))
    vdim = 8
    chunks = int(os.environ.get("BOLT_MH_CHUNKS", "16"))
    x = _crafted(n, vdim)
    mesh = _mesh()

    def make():
        return bolt.fromcallback(lambda idx: x[idx], (n, vdim), mesh,
                                 dtype=np.float32, chunks=chunks,
                                 per_process=True)

    np.save(os.path.join(out, "ref_sum.npy"),
            _value(make().map(ADD1).sum().cache()))
    st = make().map(ADD1).stats("sum", "var")
    np.save(os.path.join(out, "ref_stats_sum.npy"), _value(st["sum"]))
    np.save(os.path.join(out, "ref_stats_var.npy"), _value(st["var"]))
    blocks = [x[i:i + chunks] for i in range(0, n, chunks)]
    fi = bolt.fromiter(blocks, (n, vdim), mesh, dtype=np.float32)
    np.save(os.path.join(out, "ref_fromiter_sum.npy"),
            _value(fi.map(ADD1).sum().cache()))
    return {"pid": pid, "ok": True}


def payload_resume(pid):
    """Checkpointed streamed sum over 8 slabs; the parent arms
    BOLT_CHAOS to SIGKILL every process mid-run, then re-runs this
    payload clean — the second run must RESUME (stream_resumes >= 1)
    and reproduce the uninterrupted result bit-identically."""
    import numpy as np
    import bolt_tpu as bolt
    from bolt_tpu import engine
    out = os.environ["BOLT_MH_OUT"]
    ck = os.environ["BOLT_MH_CKPT"]
    n, vdim, chunks = 64, 8, 8                      # 8 slabs of 8
    x = _crafted(n, vdim)
    mesh = _mesh()
    c0 = engine.counters()
    src = bolt.fromcallback(lambda idx: x[idx], (n, vdim), mesh,
                            dtype=np.float32, chunks=chunks,
                            checkpoint=ck, per_process=True)
    s = src.map(ADD1).sum().cache()
    c1 = engine.counters()
    np.save(os.path.join(out, "resume_sum.%d.npy" % pid), _value(s))
    return {"pid": pid,
            "resumes": c1["stream_resumes"] - c0["stream_resumes"],
            "slabs": c1["stream_chunks"] - c0["stream_chunks"]}


def payload_reform(pid):
    """The ISSUE-11 acceptance payload: pod fault tolerance end to end.

    With ``BOLT_CHAOS`` armed on ONE worker (the victim), each
    SURVIVOR: (1) catches the watchdog's ``PeerLostError`` from the
    killed checkpointed streamed sum — named dead peer, no hang; (2)
    proves the WATCHDOG BARRIER converts too (``multihost.barrier`` →
    ``PeerLostError`` within 2× the deadline); (3)
    ``multihost.reform``'s onto the survivors (coordinator port from
    ``BOLT_MH_REFORM_PORT``); (4) RESUMES the sum on the shrunk mesh
    from the 3-process checkpoint (topology remap — the fold partials
    are replicated global values); then (5) runs a checkpointed fused
    ``stats("sum","var")`` on the reformed pod through an injected
    abort + resume — the pod ABORT-path checkpoint write
    (``stream_save(rendezvous=False)``) proven end to end.  Run
    without chaos (any nproc) both pipelines stream clean — the
    reference/baseline leg."""
    import time as _time
    import numpy as np
    import bolt_tpu as bolt
    from bolt_tpu import _chaos, engine, obs
    from bolt_tpu import checkpoint as ckptlib
    from bolt_tpu.parallel import multihost, podwatch
    from bolt_tpu.obs.trace import clock

    out = os.environ["BOLT_MH_OUT"]
    ckroot = os.environ["BOLT_MH_CKPT"]
    n = int(os.environ.get("BOLT_MH_NKEYS", "96"))
    chunks = int(os.environ.get("BOLT_MH_CHUNKS", "12"))
    vdim = int(os.environ.get("BOLT_MH_VDIM", "8"))
    pace = float(os.environ.get("BOLT_MH_PACE", "0"))
    x = _crafted(n, vdim)
    ck_sum = os.path.join(ckroot, "sum")
    res = {"pid": pid, "start_nproc": multihost.process_count()}
    obs.clear()
    obs.enable()

    def loader(idx):
        if pace:
            _time.sleep(pace)         # emulated storage-fetch latency
        return x[idx]

    def make_sum():
        src = bolt.fromcallback(loader, (n, vdim), _mesh(),
                                dtype=np.float32, chunks=chunks,
                                checkpoint=ck_sum, per_process=True)
        return src.map(ADD1).sum()

    ec0 = engine.counters()
    t0 = clock()
    try:
        s = make_sum().cache()
        res["peer_lost"] = False
        res["wall_s"] = clock() - t0
    except multihost.PeerLostError as exc:
        t_caught = clock()
        res["peer_lost"] = True
        res["caught_peer"] = exc.peer
        res["caught_slab"] = exc.slab
        res["caught_phase"] = exc.phase
        # how stale was the victim when we learned? ~the heartbeat
        # verdict latency — the detection_seconds observable
        deadline = podwatch.deadline() or 5.0
        td = clock()
        while not podwatch.dead_peers() and clock() - td < 2 * deadline:
            _time.sleep(0.05)
        dead = podwatch.dead_peers()
        res["dead_peers"] = list(dead)
        res["detection_s"] = (
            podwatch.peers().get(dead[0], {}).get("age") if dead
            else None)
        # (2) a hung BARRIER converts on every survivor, within 2x the
        # watchdog deadline (the dead peer can never arrive)
        tb = clock()
        try:
            multihost.barrier("post-loss-probe")
            res["barrier_peerlost"] = False
        except multihost.PeerLostError:
            res["barrier_peerlost"] = True
        res["barrier_s"] = clock() - tb
        res["watchdog_deadline"] = deadline
        # (3) reform onto the survivors (rank mapping from the watch)
        import jax
        survivors = podwatch.alive_peers()
        tr = clock()
        new_pid = multihost.reform(
            "127.0.0.1:%s" % os.environ["BOLT_MH_REFORM_PORT"],
            num_processes=len(survivors) or
            multihost.process_count() - 1)
        res["reform_s"] = clock() - tr
        res["new_pid"] = new_pid
        res["new_nproc"] = multihost.process_count()
        res["new_devices"] = jax.device_count()
        # (4) resume the checkpointed sum on the shrunk mesh
        t4 = clock()
        s = make_sum().cache()
        _value(s)
        res["resume_s"] = clock() - t4
        # recovery = everything AFTER the survivor learned of the loss
        res["recovery_s"] = clock() - t_caught
    np.save(os.path.join(out, "reform_sum.%d.npy" % pid), _value(s))
    ec1 = engine.counters()
    res["sum_resumes"] = ec1["stream_resumes"] - ec0["stream_resumes"]
    res["sum_stale_ckpt"] = ckptlib.stream_pending(ck_sum)
    res["arbiter_leaked"] = 0         # no server in this payload
    # partial observations land NOW (debug breadcrumb for a stats-leg
    # failure) — under a name the parent's exit-barrier release logic
    # does NOT count as a finished worker
    tmp = os.path.join(out, "partial.%d.json.tmp" % pid)
    with open(tmp, "w") as f:
        json.dump(res, f)
    os.replace(tmp, os.path.join(out, "partial.%d.json" % pid))

    # ---- (5) fused stats on the (possibly reformed) pod: injected
    # abort -> pod abort-path checkpoint -> resume, bit-identical ----
    n2, chunks2 = 128, 16             # 8 slabs; per-process shards stay
    x2 = _crafted(n2, vdim)           # period-aligned (Welford-exact)
    ck_st = os.path.join(ckroot, "stats")
    if n2 % (multihost.process_count() * 8):
        # the crafted-Welford exactness needs period-aligned per-
        # process shards; the scenario runs this leg on <=2 processes
        # (the reformed pod / the clean baseline) where they are
        res["stats_skipped"] = multihost.process_count()
        res["leaked_spans"] = obs.active_count()
        obs.disable()
        return res

    def make_stats():
        src = bolt.fromcallback(lambda idx: x2[idx], (n2, vdim),
                                _mesh(), dtype=np.float32,
                                chunks=chunks2, checkpoint=ck_st,
                                per_process=True)
        return src.map(ADD1).stats("sum", "var")

    if multihost.process_count() > 1:
        # every surviving process injects the SAME deterministic
        # mid-run fault: the abort-path write (no rendezvous — the
        # satellite fix) must leave a resumable watermark
        _chaos.inject("stream.upload", nth=5)
        try:
            _value(make_stats()["sum"])
            res["stats_died"] = None
        except Exception as exc:      # noqa: BLE001 — recorded
            res["stats_died"] = type(exc).__name__
        finally:
            _chaos.clear()
        res["stats_ckpt_after_abort"] = ckptlib.stream_pending(ck_st)
    st = make_stats()
    np.save(os.path.join(out, "reform_stats_sum.%d.npy" % pid),
            _value(st["sum"]))
    np.save(os.path.join(out, "reform_stats_var.%d.npy" % pid),
            _value(st["var"]))
    ec2 = engine.counters()
    res["stats_resumes"] = ec2["stream_resumes"] - ec1["stream_resumes"]
    res["stats_stale_ckpt"] = ckptlib.stream_pending(ck_st)
    res["leaked_spans"] = obs.active_count()
    obs.disable()
    return res


def payload_serve_pod(pid):
    """Serve-layer pod degradation (ISSUE 11): a Server per process
    submits a streamed per-process pipeline; the victim is SIGKILLed
    mid-run.  The survivor's in-flight future must FAIL with
    ``PeerLostError`` (never hang), the arbiter must read ZERO bytes
    after the failure (the lease returned everything), and admission
    must drain (``pod_paused``) until a reform notification resumes
    the queue."""
    import time as _time
    import numpy as np
    import bolt_tpu as bolt
    from bolt_tpu import obs, serve
    from bolt_tpu.parallel import multihost, podwatch

    out = os.environ["BOLT_MH_OUT"]
    n, vdim, chunks = 64, 8, 8
    x = _crafted(n, vdim)
    obs.clear()
    obs.enable()

    def make():
        src = bolt.fromcallback(lambda idx: x[idx], (n, vdim), _mesh(),
                                dtype=np.float32, chunks=chunks,
                                per_process=True)
        return src.map(ADD1).sum()

    res = {"pid": pid, "nproc": multihost.process_count()}
    with serve.serving(workers=1, budget_bytes=16 << 20) as sv:
        fut = sv.submit(make(), tenant="podtest")
        exc = fut.exception(timeout=120)
        res["future_error"] = (type(exc).__name__ if exc is not None
                               else None)
        res["future_peer"] = getattr(exc, "peer", None)
        res["arbiter_bytes_after_abort"] = \
            sv.stats()["arbiter"]["in_use_bytes"]
        t0 = _time.monotonic()
        while not sv.pod_paused() and _time.monotonic() - t0 < 30:
            _time.sleep(0.05)
        res["pod_paused"] = sv.pod_paused()
        # the reform notification resumes the queue (the full reform
        # dance is payload_reform's job; here only serve's reaction is
        # under test)
        podwatch.notify_reform()
        res["pod_resumed"] = not sv.pod_paused()
    res["leaked_spans"] = obs.active_count()
    obs.disable()
    return res


def payload_supervise(pid):
    """The ISSUE-12 acceptance payload: SELF-HEALING end to end.

    Every process runs ``Server(supervise=True)`` and submits three
    pipelines in SPMD order:

    * **A** (checkpointed paced sum): the victim is SIGKILLed mid-A —
      survivors' futures succeed with ZERO caller intervention (the
      held ``retries=`` re-attempt resumes once the supervisor's
      automatic 3→2 reform lands);
    * **B** (checkpointed paced sum): a REPLACEMENT process
      (``BOLT_MH_REJOINER=1``, skipped ``multihost.initialize``) rings
      the rejoin door MID-B — incumbents quiesce at a slab-boundary
      checkpoint, the supervisor reforms 2→3, and B's re-attempt
      resumes on the re-expanded pod (the rejoiner submits B too and
      joins the same resumed slab schedule);
    * **C** (fused ``stats("sum","var")``, period-4 crafted data): a
      clean run on the re-expanded 3-wide pod.

    Run without chaos/rejoiner (the reference leg) all three stream
    clean 3-wide; sums are integer-exact under any process grouping
    and C's shards are period-aligned at both widths, so every saved
    artifact must be BIT-IDENTICAL between the legs."""
    import glob as _glob
    import time as _time
    import numpy as np
    import bolt_tpu as bolt
    from bolt_tpu import engine, obs, serve
    from bolt_tpu import checkpoint as ckptlib
    from bolt_tpu.parallel import multihost, podwatch, supervisor
    from bolt_tpu.obs.trace import clock

    out = os.environ["BOLT_MH_OUT"]
    ckroot = os.environ["BOLT_MH_CKPT"]
    hbdir = os.environ["BOLT_POD_HB_DIR"]
    pace = float(os.environ.get("BOLT_MH_PACE", "0.2"))
    rejoiner = os.environ.get("BOLT_MH_REJOINER") == "1"
    n, chunks, vdim = 96, 12, 8           # 8 slabs; 12 % 3 == 12 % 2 == 0
    x = _crafted(n, vdim)                 # integer-exact sums
    x2 = _crafted(n, vdim, period=4)      # moment-exact at widths 2 AND 3
    obs.clear()
    obs.enable()
    res = {"pid": pid, "rejoiner": rejoiner}
    deaths = []
    podwatch.on_peer_death(
        lambda dead: deaths.append(
            (dead, podwatch.peers().get(dead, {}).get("age"), clock())))

    def loader(idx):
        if pace:
            _time.sleep(pace)
        return x[idx]

    # jobs are FACTORIES, not arrays: a retry after a reform must
    # rebuild the pipeline against the CURRENT (reformed) mesh — the
    # checkpoint fingerprint ignores topology, so the re-attempt
    # resumes the same logical run on the new pod width
    def make_sum(name):
        def job():
            src = bolt.fromcallback(
                loader, (n, vdim), _mesh(), dtype=np.float32,
                chunks=chunks, checkpoint=os.path.join(ckroot, name),
                per_process=True)
            return src.map(ADD1).sum().cache()
        return job

    def make_stats():
        def job():
            src = bolt.fromcallback(
                lambda idx: x2[idx], (n, vdim), _mesh(),
                dtype=np.float32, chunks=24,
                checkpoint=os.path.join(ckroot, "statsC"),
                per_process=True)
            return src.map(ADD1).stats("sum", "var")
        return job

    if rejoiner:
        # wait until every incumbent survivor has B in flight, then
        # ring the doorbell and join through the published plan
        want = int(os.environ.get("BOLT_MH_EXPECT_BSTART", "2"))
        hold = _time.monotonic() + 180
        while len(_glob.glob(os.path.join(out, "b_started.*"))) < want:
            if _time.monotonic() > hold:
                raise RuntimeError("rejoiner: b_started gate never "
                                   "opened")
            _time.sleep(0.02)
        t0 = clock()
        sup = supervisor.attach(
            os.environ.get("BOLT_MH_REJOIN_ID", "w%db" % pid), dir=hbdir)
        res["attach_s"] = clock() - t0
        res["new_pid"] = multihost.process_index()
        res["new_nproc"] = multihost.process_count()
        sv = serve.start(workers=1, budget_bytes=64 << 20,
                         supervise=sup)
    else:
        sv = serve.start(workers=1, budget_bytes=64 << 20,
                         supervise=True)

    ec0 = engine.counters()
    try:
        if not rejoiner:
            # ---- A: kill -9 mid-stream -> automatic shrink ----------
            tA = clock()
            futA = sv.submit(make_sum("sumA"), tenant="elastic",
                             retries=3)
            sA = futA.result(timeout=300)
            res["wall_a"] = clock() - tA
            np.save(os.path.join(out, "sup_sumA.%d.npy" % pid),
                    _value(sA))
            ecA = engine.counters()
            res["a_resumes"] = ecA["stream_resumes"] \
                - ec0["stream_resumes"]
            stA = sv.stats()
            res["a_reforms"] = stA["totals"]["reforms"]
            res["a_peer_losses"] = stA["totals"]["peer_losses"]
            res["budget_share_after_a"] = stA["pod"]["budget_share"]
            res["detection_age"] = deaths[0][1] if deaths else None
            supA = (sv.supervisor.stats() if sv.supervisor is not None
                    else {})
            res["reform_s"] = supA.get("last_reform_seconds")
            res["recovery_s"] = supA.get("last_recovery_seconds")

            # ---- B: rejoin arrives mid-stream -> quiesce + grow -----
            tB = clock()
            futB = sv.submit(make_sum("sumB"), tenant="elastic",
                             retries=3)
            gate = os.path.join(out, "b_started.%d" % pid)
            with open(gate + ".tmp", "w") as f:
                f.write("1")
            os.replace(gate + ".tmp", gate)
        else:
            tB = clock()
            futB = sv.submit(make_sum("sumB"), tenant="elastic",
                             retries=3)
        ecB0 = engine.counters()
        sB = futB.result(timeout=300)
        res["wall_b"] = clock() - tB
        np.save(os.path.join(out, "sup_sumB.%d.npy" % pid), _value(sB))
        ecB = engine.counters()
        res["b_resumes"] = ecB["stream_resumes"] - ecB0["stream_resumes"]
        stB = sv.stats()
        res["reforms"] = stB["totals"]["reforms"]
        res["rejoins"] = stB["totals"]["rejoins"]
        res["supervise_seconds"] = stB["totals"]["supervise_seconds"]
        res["budget_share_after_b"] = stB["pod"]["budget_share"]
        res["nproc_after_b"] = multihost.process_count()
        if sv.supervisor is not None:
            sup_st = sv.supervisor.stats()
            res["rejoin_recovery_s"] = sup_st.get(
                "last_recovery_seconds")

        # ---- C: clean fused stats on the re-expanded pod ------------
        tC = clock()
        futC = sv.submit(make_stats(), tenant="elastic", retries=3)
        stats = futC.result(timeout=300)
        res["wall_c"] = clock() - tC
        np.save(os.path.join(out, "sup_statsC_sum.%d.npy" % pid),
                _value(stats["sum"]))
        np.save(os.path.join(out, "sup_statsC_var.%d.npy" % pid),
                _value(stats["var"]))
        res["arbiter_bytes_after"] = \
            sv.stats()["arbiter"]["in_use_bytes"]

        # ---- checker integration on the live re-expanded pod --------
        from bolt_tpu import analysis
        blocks = [x[i:i + chunks] for i in range(0, n, chunks)]
        fi = bolt.fromiter(blocks, (n, vdim), _mesh(),
                           dtype=np.float32)
        res["blt014"] = analysis.check(fi.map(ADD1)).has("BLT014")
        probe = bolt.fromcallback(lambda idx: x[idx], (n, vdim),
                                  _mesh(), dtype=np.float32,
                                  chunks=chunks, per_process=True)
        res["explain_supervised"] = \
            "SUPERVISED" in analysis.explain(probe.map(ADD1))
    finally:
        serve.stop(wait=True)
    # hygiene observables: no stale ckpt, no leaked spans, no stale
    # transport markers beyond the one-epoch grace the sweep keeps
    res["stale_ckpt"] = [name for name in ("sumA", "sumB", "statsC")
                         if ckptlib.stream_pending(
                             os.path.join(ckroot, name))]
    tr = podwatch.transport()
    res["stale_markers"] = (tr.stale_marker_count()
                            if tr is not None else 0)
    res["final_epoch"] = podwatch.epoch()
    res["leaked_spans"] = obs.active_count()
    obs.disable()
    return res


def payload_precollective(pid):
    """The pre-collective death bound (ISSUE 12): the victim dies at
    its FIRST upload — before any collective was ever dispatched — and
    the survivor's readiness rendezvous must convert that into a
    pointed ``PeerLostError`` within ~2x ``BOLT_POD_TIMEOUT``, not
    gloo's ~30s connect timeout."""
    import numpy as np
    import bolt_tpu as bolt
    from bolt_tpu.parallel import multihost, podwatch
    from bolt_tpu.obs.trace import clock

    n, vdim, chunks = 64, 8, 8
    x = _crafted(n, vdim)

    def make():
        src = bolt.fromcallback(lambda idx: x[idx], (n, vdim), _mesh(),
                                dtype=np.float32, chunks=chunks,
                                per_process=True)
        return src.map(ADD1).sum()

    res = {"pid": pid, "deadline": podwatch.deadline()}
    t0 = clock()
    try:
        make().cache()
        res["pre_peerlost"] = False
    except multihost.PeerLostError as exc:
        res["pre_peerlost"] = True
        res["pre_elapsed"] = clock() - t0
        res["pre_phase"] = exc.phase
        res["pre_peer"] = exc.peer
    return res


def payload_codec_pod(pid):
    """The ISSUE-14 pod leg: each process ENCODES its local shard, so
    per-process ingest (DCN/gloo) bytes shrink by the codec's wire
    ratio; the lossless delta-f32 pod sum stays BIT-IDENTICAL to the
    raw pod sum (the shard_map decode is shard-local by construction),
    bf16 lands within its envelope, and sidecar codecs (int8) refuse
    the multi-process mesh pointedly."""
    import numpy as np
    import bolt_tpu as bolt
    from bolt_tpu import engine, obs
    from bolt_tpu.parallel import multihost
    out = os.environ["BOLT_MH_OUT"]
    n = int(os.environ.get("BOLT_MH_NKEYS", "64"))
    vdim = 8
    chunks = int(os.environ.get("BOLT_MH_CHUNKS", "16"))
    x = _crafted(n, vdim)
    mesh = _mesh()
    obs.clear()
    obs.enable()

    def make(codec=None):
        return bolt.fromcallback(lambda idx: x[idx], (n, vdim), mesh,
                                 dtype=np.float32, chunks=chunks,
                                 per_process=True, codec=codec)

    res = {"pid": pid, "nproc": multihost.process_count()}
    c0 = engine.counters()
    raw = make().map(ADD1).sum().cache()
    c1 = engine.counters()
    dl = make("delta-f32").map(ADD1).sum().cache()
    c2 = engine.counters()
    bf = make("bf16").map(ADD1).sum().cache()
    c3 = engine.counters()
    np.save(os.path.join(out, "codec_raw.%d.npy" % pid), _value(raw))
    np.save(os.path.join(out, "codec_delta.%d.npy" % pid), _value(dl))
    np.save(os.path.join(out, "codec_bf16.%d.npy" % pid), _value(bf))
    res["raw_bytes"] = c1["transfer_bytes"] - c0["transfer_bytes"]
    res["delta_bytes"] = c2["transfer_bytes"] - c1["transfer_bytes"]
    res["bf16_bytes"] = c3["transfer_bytes"] - c2["transfer_bytes"]
    if multihost.process_count() > 1:
        try:
            make("int8").map(ADD1).sum().cache()
            res["sidecar_refused"] = False
        except ValueError as exc:
            res["sidecar_refused"] = "sidecar" in str(exc)
    else:
        res["sidecar_refused"] = True
    res["leaked_spans"] = obs.active_count()
    obs.disable()
    return res


def payload_swap(pid):
    """The ISSUE-18 pod leg: a streamed ``swap`` re-buckets every slab
    through ONE ``lax.all_to_all`` per slab inside shard_map (phase 1)
    and concatenates the resident buckets (phase 2) — BIT-IDENTICAL on
    every process to the materialise-first in-memory swap of the same
    per-process source.  Also proves the pointed pod-spill refusal
    (disk spill is single-process only) and zero leaked spans."""
    import numpy as np
    import bolt_tpu as bolt
    from bolt_tpu import engine, obs, stream
    from bolt_tpu.parallel import multihost
    out = os.environ["BOLT_MH_OUT"]
    n = int(os.environ.get("BOLT_MH_NKEYS", "64"))
    vdim = 8
    chunks = int(os.environ.get("BOLT_MH_CHUNKS", "16"))
    x = _crafted(n, vdim)
    mesh = _mesh()
    obs.clear()
    obs.enable()

    def make():
        return bolt.fromcallback(lambda idx: x[idx], (n, vdim), mesh,
                                 dtype=np.float32, chunks=chunks,
                                 per_process=True)

    res = {"pid": pid, "nproc": multihost.process_count()}
    c0 = engine.counters()
    streamed = make().swap((0,), (0,))
    res["lazy_after_swap"] = streamed._stream is not None
    sval = _value(streamed)        # resolves the two-phase shuffle
    c1 = engine.counters()
    mat = make()
    mat.cache()                    # materialise FIRST: the in-memory path
    mval = _value(mat.swap((0,), (0,)))
    np.save(os.path.join(out, "swap_streamed.%d.npy" % pid), sval)
    np.save(os.path.join(out, "swap_materialised.%d.npy" % pid), mval)
    res["shuffle_bytes"] = c1["shuffle_bytes"] - c0["shuffle_bytes"]
    res["spill_bytes"] = c1["spill_bytes"] - c0["spill_bytes"]
    # spill is single-process only: a pod plan past the budget refuses
    # POINTEDLY before any rendezvous (symmetric on every process, so
    # no peer is left hanging at the all-to-all)
    try:
        with stream.spill(dir=out, budget=1):
            make().swap((0,), (0,))._data
        res["pod_spill_refused"] = False
    except RuntimeError as exc:
        res["pod_spill_refused"] = "single-process" in str(exc)
    res["leaked_spans"] = obs.active_count()
    obs.disable()
    return res


def payload_sched_verify(pid):
    """The dispatch-schedule verifier's acceptance payload (ISSUE 17):

    * matched phase — every process runs the SAME streamed pipeline,
      then ``multihost.verify_schedule`` must agree bit-identically on
      the digest;
    * skew phase — ``BOLT_CHAOS=mh.sched.skew:1:raise`` armed on ONE
      process makes it enqueue an extra LOCAL single-device program
      (no cross-process collective, so nothing can hang — only the
      schedules diverge); the next verify must raise a pointed
      :class:`ScheduleDivergenceError` on every process, naming the
      first divergent slot instead of wedging in gloo."""
    import numpy as np
    import bolt_tpu as bolt
    from bolt_tpu import _chaos, engine
    from bolt_tpu.parallel import multihost
    engine.schedule_log_arm(True)
    n, vdim = 32, 4
    x = _crafted(n, vdim)
    mesh = _mesh()
    res = {"pid": pid, "nproc": multihost.process_count()}
    b = bolt.fromcallback(lambda idx: x[idx], (n, vdim), mesh,
                          dtype=np.float32, chunks=4,
                          per_process=True).map(ADD1).sum().cache()
    res["sum"] = float(np.asarray(_value(b)).sum())
    res["digest_matched"] = multihost.verify_schedule("matched")
    res["count_matched"] = engine.schedule_digest()[0]
    try:
        _chaos.hit("mh.sched.skew")
        res["skewed"] = False
    except _chaos.ChaosError:
        res["skewed"] = True
        import jax
        from jax.sharding import Mesh
        lmesh = Mesh(np.asarray(jax.local_devices()[:1]), ("k",))
        bolt.array(_crafted(8, vdim), context=lmesh).map(ADD1) \
            .sum().cache()
    try:
        multihost.verify_schedule("skewed", timeout=30.0)
        res["divergence"] = None
    except multihost.ScheduleDivergenceError as exc:
        res["divergence"] = {"peer": exc.peer, "index": exc.index,
                             "local_key": exc.local_key,
                             "message": str(exc)[:400]}
    return res


PAYLOADS = {
    "stream_parity": payload_stream_parity,
    "single_ref": payload_single_ref,
    "codec_pod": payload_codec_pod,
    "resume": payload_resume,
    "reform": payload_reform,
    "serve_pod": payload_serve_pod,
    "supervise": payload_supervise,
    "precollective": payload_precollective,
    "sched_verify": payload_sched_verify,
    "swap": payload_swap,
}


def worker_main(pid):
    import faulthandler
    import signal
    faulthandler.register(signal.SIGUSR1, all_threads=True)
    _bootstrap(pid)
    payload = PAYLOADS[os.environ["BOLT_MH_PAYLOAD"]]
    res = payload(pid)
    out = os.environ["BOLT_MH_OUT"]
    tmp = os.path.join(out, "result.%d.json.tmp" % pid)
    with open(tmp, "w") as f:
        json.dump(res, f)
    os.replace(tmp, os.path.join(out, "result.%d.json" % pid))
    print("worker %d OK" % pid, flush=True)
    # the EXIT BARRIER (see run_cluster): hold the teardown until the
    # parent has seen every surviving worker's result — the first
    # worker out must not kill the coordination service under a peer
    # still mid-payload (its error-poll thread would abort the process
    # on "service unavailable"; the coordination shutdown barrier alone
    # does not reliably hold it on this runtime)
    release = os.path.join(out, "release")
    hold = time.time() + 60
    while not os.path.exists(release) and time.time() < hold:
        time.sleep(0.02)
    try:
        from bolt_tpu.parallel import multihost
        multihost.shutdown()
    except Exception:
        pass
    if os.environ.get("BOLT_MH_HARD_EXIT") == "1":
        # a reformed worker holds dead-backend threads (the old pod's
        # hung gloo contexts) that can wedge interpreter teardown; the
        # result is durably on disk, so leave without ceremony
        sys.stdout.flush()
        os._exit(0)


# ---------------------------------------------------------------------
# the elastic scenario (tests/test_multihost.py's ``elastic`` fixture,
# scripts/examples.py 8i): the 3→2→3 self-healing pod + the
# pre-collective death bound
# ---------------------------------------------------------------------

def run_supervise_bench(nproc=3, pace=0.2, kill_at=4, pod_timeout=2.0,
                        timeout=420, workdir=None):
    """The ISSUE-12 acceptance scenario, packaged for its readers
    (tests/test_multihost.py, scripts/examples.py): a CLEAN
    ``nproc``-process reference run of the supervised workload
    (pipelines A, B, C — see ``payload_supervise``), then the ELASTIC
    leg — worker 1 SIGKILLed mid-A (automatic 3→2 shrink with zero
    caller intervention), a replacement process rejoining mid-B
    (quiesce + 2→3 re-expansion), C clean on the re-expanded pod.
    Every artifact must be bit-identical between legs; the gate is
    scenario-vs-clean wall < 2.5x plus zero leaked arbiter bytes /
    spans / stale transport markers / stale checkpoints."""
    import shutil
    import numpy as np
    own = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="bolt-mh-elastic-")
    env = {"BOLT_MH_PACE": pace, "BOLT_POD_TIMEOUT": pod_timeout,
           "BOLT_CHECKPOINT_EVERY": "1", "BOLT_MH_HARD_EXIT": "1",
           "BOLT_SUPERVISE_BACKOFF": "0.25"}
    try:
        out_c = os.path.join(workdir, "out-clean")
        out_e = os.path.join(workdir, "out-elastic")
        os.makedirs(out_c, exist_ok=True)
        os.makedirs(out_e, exist_ok=True)
        # -- the clean 3-wide reference -------------------------------
        res_c, out_c, _ = run_cluster(
            "supervise", nproc=nproc, devs=1, timeout=timeout,
            out_dir=out_c,
            env=dict(env, BOLT_MH_CKPT=os.path.join(workdir, "ck-clean"),
                     BOLT_POD_HB_DIR=os.path.join(workdir, "hb-clean")))
        clean_s = max(r["wall_a"] + r["wall_b"] + r["wall_c"]
                      for r in res_c)
        refs = {name: np.load(os.path.join(out_c, "%s.0.npy" % name))
                for name in ("sup_sumA", "sup_sumB", "sup_statsC_sum",
                             "sup_statsC_var")}
        # -- the elastic leg: kill mid-A, rejoin mid-B ----------------
        res, out, rcs = run_cluster(
            "supervise", nproc=nproc, devs=1, timeout=timeout,
            tolerate={1}, out_dir=out_e,
            env=dict(env, BOLT_MH_CKPT=os.path.join(workdir, "ck-el"),
                     BOLT_POD_HB_DIR=os.path.join(workdir, "hb-el"),
                     BOLT_MH_EXPECT_BSTART=str(nproc - 1)),
            worker_env={1: {"BOLT_CHAOS":
                            "stream.upload:%d:kill" % kill_at}},
            extra_workers={nproc: {"BOLT_MH_REJOINER": "1",
                                   "BOLT_MH_REJOIN_ID": "w1b"}})
        done = [r for r in res if r is not None]
        survivors = [r for r in done if not r["rejoiner"]]
        rejoiner = [r for r in done if r["rejoiner"]]
        bit = all(
            np.array_equal(np.load(os.path.join(
                out, "%s.%d.npy" % (name, r["pid"]))), refs[name])
            for r in done
            for name in refs
            if not (r["rejoiner"] and name == "sup_sumA"))
        scenario_s = max(r["wall_a"] + r["wall_b"] + r["wall_c"]
                         for r in survivors)
        return {
            "clean_s": clean_s,
            "scenario_s": scenario_s,
            "scenario_over_clean": scenario_s / clean_s,
            "detection_s": max(r.get("detection_age") or 0.0
                               for r in survivors),
            "reform_s": max(r.get("reform_s") or 0.0
                            for r in survivors),
            "recovery_s": max(r.get("recovery_s") or 0.0
                              for r in survivors),
            "rejoin_s": max(r.get("rejoin_recovery_s") or 0.0
                            for r in survivors),
            "attach_s": (rejoiner[0].get("attach_s")
                         if rejoiner else None),
            "pod_timeout": float(pod_timeout),
            "victim_rc": rcs[1],
            "survivors": len(survivors),
            "rejoined": len(rejoiner),
            "a_resumes": sum(r.get("a_resumes", 0) for r in survivors),
            "b_resumes": sum(r.get("b_resumes", 0) for r in survivors),
            "reforms": max(r.get("reforms", 0) for r in done),
            "rejoins": max(r.get("rejoins", 0) for r in done),
            "nproc_final": max(r.get("nproc_after_b", 0) for r in done),
            "budget_share_after_a": min(
                r.get("budget_share_after_a", 1.0) for r in survivors),
            "budget_share_after_b": max(
                r.get("budget_share_after_b", 0.0) for r in done),
            "bit_identical": bool(bit),
            "arbiter_bytes": max(r.get("arbiter_bytes_after", 0)
                                 for r in done),
            "stale_ckpt": sorted({c for r in done
                                  for c in r.get("stale_ckpt", [])}),
            "stale_markers": max(r.get("stale_markers", 0)
                                 for r in done),
            "leaked_spans": sum(r.get("leaked_spans", 0) for r in done),
            "blt014": all(r.get("blt014") for r in done),
            "explain_supervised": all(r.get("explain_supervised")
                                      for r in done),
        }
    except BaseException:
        own = False      # keep worker logs for post-mortem
        raise
    finally:
        if own:
            shutil.rmtree(workdir, ignore_errors=True)


def run_precollective_probe(pod_timeout=2.0, timeout=180, workdir=None):
    """The closed pre-collective bound, measured: worker 1 dies at its
    FIRST upload (no collective ever dispatched); the survivor must
    catch ``PeerLostError`` within 2x ``pod_timeout`` — not gloo's
    ~30s connect timeout."""
    import shutil
    own = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="bolt-mh-precoll-")
    try:
        res, out, rcs = run_cluster(
            "precollective", nproc=2, devs=1, timeout=timeout,
            tolerate={1}, out_dir=os.path.join(workdir, "out"),
            env={"BOLT_POD_TIMEOUT": pod_timeout,
                 "BOLT_MH_HARD_EXIT": "1",
                 "BOLT_POD_HB_DIR": os.path.join(workdir, "hb")},
            worker_env={1: {"BOLT_CHAOS": "stream.upload:1:kill"}})
        r = res[0]
        return {"victim_rc": rcs[1],
                "pre_peerlost": r.get("pre_peerlost"),
                "pre_elapsed": r.get("pre_elapsed"),
                "pre_phase": r.get("pre_phase"),
                "pod_timeout": float(pod_timeout)}
    except BaseException:
        own = False      # keep worker logs for post-mortem
        raise
    finally:
        if own:
            shutil.rmtree(workdir, ignore_errors=True)


# ---------------------------------------------------------------------
# the reform scenario (tests/test_multihost.py's ``reform`` fixture,
# scripts/examples.py)
# ---------------------------------------------------------------------

def run_reform_bench(nproc=3, nkeys=96, chunks=12, vdim=8, pace=0.25,
                     kill_at=7, pod_timeout=2.0, timeout=420,
                     workdir=None):
    """The ISSUE-11 acceptance scenario, packaged for its readers
    (tests/test_multihost.py, scripts/examples.py): a CLEAN
    ``nproc-1``-process run of the reform workload (the unkilled
    post-shrink baseline), then an ``nproc``-process run with worker 1
    SIGKILLed mid-stream — every survivor must raise
    ``PeerLostError`` (watchdog within 2× ``BOLT_POD_TIMEOUT``),
    ``multihost.reform`` onto the survivors, and resume bit-identically
    to the clean run.  ``recovery_s`` is the max survivor wall from
    the moment it LEARNED of the loss to the resumed result (barrier
    probe + reform + resume) — the gate compares it against the clean
    run's wall (< 2.0x).  ``chunks`` must divide both the ``nproc``-
    and ``(nproc-1)``-wide key-axis assignments.

    ``pace`` (per-slab loader latency) and ``kill_at`` (the victim's
    fatal upload) place the death MID-STREAM: the gloo sockets are
    established by slab 0's collective and several watermarks are
    checkpointed, so peer death surfaces as a fast transport error and
    the resume provably skips retired slabs.  A victim killed before
    the FIRST collective instead costs gloo's own connect timeout
    (~30s) — bounded and converted, but not the fast path this
    scenario exercises."""
    import shutil
    import numpy as np
    own = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="bolt-mh-reform-")
    env = {"BOLT_MH_NKEYS": nkeys, "BOLT_MH_CHUNKS": chunks,
           "BOLT_MH_VDIM": vdim, "BOLT_MH_PACE": pace,
           "BOLT_POD_TIMEOUT": pod_timeout, "BOLT_MH_HARD_EXIT": "1",
           "BOLT_CHECKPOINT_EVERY": "1"}
    try:
        # -- the unkilled baseline on the post-shrink topology --------
        out_c = os.path.join(workdir, "out-clean")
        out_k = os.path.join(workdir, "out-kill")
        os.makedirs(out_c, exist_ok=True)
        os.makedirs(out_k, exist_ok=True)
        res_c, out_c, _ = run_cluster(
            "reform", nproc=nproc - 1, devs=1, timeout=timeout,
            out_dir=out_c,
            env=dict(env, BOLT_MH_CKPT=os.path.join(workdir, "ck-clean"),
                     BOLT_POD_HB_DIR=os.path.join(workdir, "hb-clean")))
        clean_s = max(r["wall_s"] for r in res_c)
        ref = np.load(os.path.join(out_c, "reform_sum.0.npy"))
        ref_ssum = np.load(os.path.join(out_c, "reform_stats_sum.0.npy"))
        ref_svar = np.load(os.path.join(out_c, "reform_stats_var.0.npy"))

        # -- the kill: nproc processes, worker 1 is the victim --------
        port, reform_port = free_ports(2)
        res, out, rcs = run_cluster(
            "reform", nproc=nproc, devs=1, timeout=timeout,
            tolerate={1}, out_dir=out_k,
            env=dict(env, BOLT_MH_CKPT=os.path.join(workdir, "ck-kill"),
                     BOLT_MH_PORT=port, BOLT_MH_REFORM_PORT=reform_port,
                     BOLT_POD_HB_DIR=os.path.join(workdir, "hb-kill")),
            worker_env={1: {"BOLT_CHAOS":
                            "stream.upload:%d:kill" % kill_at}})
        survivors = [r for r in res if r is not None]
        bit = all(
            np.array_equal(np.load(os.path.join(
                out, "reform_sum.%d.npy" % r["pid"])), ref)
            and np.array_equal(np.load(os.path.join(
                out, "reform_stats_sum.%d.npy" % r["pid"])), ref_ssum)
            and np.array_equal(np.load(os.path.join(
                out, "reform_stats_var.%d.npy" % r["pid"])), ref_svar)
            for r in survivors)
        ck_kill = os.path.join(workdir, "ck-kill")
        stale = [p for sub in ("sum", "stats")
                 for p in glob_dir(os.path.join(ck_kill, sub))]
        recovery_s = max(r.get("recovery_s") or 0.0 for r in survivors)
        return {
            "clean_s": clean_s,
            "recovery_s": recovery_s,
            "recovery_over_clean": recovery_s / clean_s,
            "detection_s": max(r.get("detection_s") or 0.0
                               for r in survivors),
            "reform_s": max(r.get("reform_s") or 0.0
                            for r in survivors),
            "resume_s": max(r.get("resume_s") or 0.0
                            for r in survivors),
            "barrier_s": max(r.get("barrier_s") or 0.0
                             for r in survivors),
            "pod_timeout": float(pod_timeout),
            "survivors": len(survivors),
            "victim_rc": rcs[1],
            "peer_lost_everywhere": all(r.get("peer_lost")
                                        for r in survivors),
            "barrier_peerlost": all(r.get("barrier_peerlost")
                                    for r in survivors),
            "sum_resumes": sum(r.get("sum_resumes", 0)
                               for r in survivors),
            "stats_resumes": sum(r.get("stats_resumes", 0)
                                 for r in survivors),
            "bit_identical": bool(bit),
            "stale_checkpoint_files": stale,
            "leaked_spans": sum(r.get("leaked_spans", 0)
                                for r in survivors),
        }
    except BaseException:
        own = False      # keep worker logs for post-mortem
        raise
    finally:
        if own:
            shutil.rmtree(workdir, ignore_errors=True)


def glob_dir(path):
    """Stream-checkpoint files still under ``path`` (the zero-stale
    gate; empty/missing dirs read clean)."""
    import glob as _glob
    return [os.path.basename(p) for p in
            _glob.glob(os.path.join(path, "stream_*"))]


# ---------------------------------------------------------------------
# standalone smoke
# ---------------------------------------------------------------------

def main():
    import shutil
    import numpy as np
    results, out, _ = run_cluster("stream_parity", nproc=2, devs=1)
    _, out1, _ = run_cluster("single_ref", nproc=1, devs=2, out_dir=out)
    ok = all(r and r["recompiles_second_pass"] == 0
             and r["leaked_spans"] == 0 for r in results)
    a = np.load(os.path.join(out, "sum.0.npy"))
    b = np.load(os.path.join(out, "sum.1.npy"))
    ref = np.load(os.path.join(out, "ref_sum.npy"))
    ok = ok and np.array_equal(a, ref) and np.array_equal(b, ref)
    for pid in (0, 1):
        for name in ("stats_sum", "stats_var"):
            got = np.load(os.path.join(out, "%s.%d.npy" % (name, pid)))
            want = np.load(os.path.join(out, "ref_%s.npy" % name))
            ok = ok and np.array_equal(got, want)
    print("multihost harness smoke:", "PASS" if ok else "FAIL")
    print(json.dumps(results, indent=1))
    shutil.rmtree(out, ignore_errors=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[1] == "--worker":
        worker_main(int(sys.argv[2]))
    else:
        main()
