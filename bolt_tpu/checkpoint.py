"""Checkpoint / restore for distributed bolt arrays AND streamed runs.

The reference has NO checkpointing — persistence is ``cache()`` only, and
fault tolerance is inherited from RDD lineage recomputation (SURVEY §5).
On TPU the analog is saving the sharded ``jax.Array`` itself: orbax writes
each shard from the process that owns it (multi-host safe) and restores
onto any compatible mesh, which is strictly more capable than the
reference (a cached RDD dies with the cluster; a checkpoint survives it).

>>> import bolt_tpu as bolt
>>> from bolt_tpu import checkpoint
>>> checkpoint.save("/tmp/ckpt", b)
>>> b2 = checkpoint.load("/tmp/ckpt", context=mesh)

Two degradation rules keep the dependency soft: when orbax is missing,
single-process meshes fall back to a stdlib ``np.save`` of the assembled
array (restore re-shards through the counted transfer layer), and
multi-process meshes raise a POINTED ImportError naming the package to
install — at ``save()`` call time, not as a bare mid-call import crash.

The second half is the **incremental stream-checkpoint path** (ISSUE 9):
:func:`stream_save` / :func:`stream_load` / :func:`stream_clear` persist
a streamed run's retired-slab watermark plus its folded partial
accumulator (the pairwise-tree levels and the unpaired pair partial —
sum/reduce arrays, ``(n, μ, M2)`` moment triples, fused multi-stat
component tuples alike), so a killed run restarted over the same source
resumes from the last retired slab and produces a BIT-IDENTICAL result
(``bolt_tpu.stream`` owns the resume logic; this module owns the
on-disk format).  Writes are atomic-by-rename and ordered state-first /
meta-last, so a ``kill -9`` mid-write can never leave a meta file
pointing at torn state — the interrupted checkpoint simply does not
exist and the previous one still does.
"""

import glob
import hashlib
import json
import os

import numpy as np

from bolt_tpu import _chaos
from bolt_tpu.parallel import multihost as _multihost


class CheckpointCorruptError(RuntimeError):
    """A stream-checkpoint state file failed its integrity digest (bit
    rot, truncation, a torn storage layer) — refusing to resume beats
    silently feeding a corrupt accumulator into the fold.  The message
    names the file; delete it (or the whole checkpoint dir) to restart
    the run from scratch, or restore the file from replicated storage.
    Distinct from the QUIET ``None`` cases of :func:`stream_load`
    (missing checkpoint, fingerprint drift, a kill between the two
    atomic renames): those are expected lifecycle states, corruption
    never is."""


def _array_path(path):
    return os.path.join(path, "array")


def _npy_path(path):
    return os.path.join(path, "array.npy")


def _meta_path(path):
    return os.path.join(path, "bolt_meta.json")


def _orbax():
    """The orbax checkpoint module, or a POINTED ImportError naming the
    package — raised at the call site that needed it, instead of a bare
    ``import orbax.checkpoint`` surfacing mid-call."""
    try:
        import orbax.checkpoint as ocp
        return ocp
    except ImportError as exc:
        raise ImportError(
            "bolt_tpu.checkpoint needs the 'orbax-checkpoint' package "
            "for sharded (multi-process) array checkpoints: pip install "
            "orbax-checkpoint.  Single-process meshes fall back to a "
            "stdlib np.save automatically; this mesh cannot."
        ) from exc


def _have_orbax():
    try:
        import orbax.checkpoint  # noqa: F401
        return True
    except ImportError:
        return False


def save(path, barray, force=True):
    """Write a ``mode='tpu'`` bolt array (data + split/shape/dtype
    metadata) under the directory ``path``.

    Orbax-backed when available (each process writes its own shards);
    without orbax a single-process mesh degrades to ``np.save`` of the
    assembled array, and a multi-process mesh raises the pointed
    ImportError from :func:`_orbax` — at save time, naming the
    package."""
    from bolt_tpu.tpu.array import BoltArrayTPU
    if not isinstance(barray, BoltArrayTPU):
        raise TypeError("checkpoint.save expects a mode='tpu' array; "
                        "got %r" % type(barray).__name__)
    use_orbax = _have_orbax()
    if not use_orbax and _multihost.process_count() > 1:
        _orbax()                    # raises the pointed ImportError
    os.makedirs(path, exist_ok=True)
    if use_orbax:
        import orbax.checkpoint as ocp
        ckptr = ocp.Checkpointer(ocp.ArrayCheckpointHandler())
        ckptr.save(os.path.abspath(_array_path(path)),
                   args=ocp.args.ArraySave(barray._data), force=force)
    else:
        # stdlib fallback (single process): assemble on host, write
        # atomically — the restore path re-shards through the counted
        # transfer layer
        host = np.asarray(barray._data)
        tmp = _npy_path(path) + ".tmp"
        with open(tmp, "wb") as f:       # np.save(path) would append
            np.save(f, host)             # ".npy" to the tmp name
        os.replace(tmp, _npy_path(path))
    if _multihost.process_index() == 0:
        # orbax coordinates per-shard ownership; the metadata file has one
        # writer so a shared checkpoint dir never sees interleaved writes
        meta = {"split": barray.split, "shape": list(barray.shape),
                "dtype": str(barray.dtype),
                "format": "orbax" if use_orbax else "npy"}
        with open(_meta_path(path), "w") as f:
            json.dump(meta, f)
    _multihost.barrier("bolt_checkpoint_save")


def load(path, context=None):
    """Restore a bolt array saved by :func:`save`, placing it with the key
    sharding for ``context`` (default mesh when omitted).  Reads either
    format: an orbax shard directory, or the single-process ``np.save``
    fallback (which any orbax-equipped process can also read)."""
    from bolt_tpu.parallel.sharding import key_sharding
    from bolt_tpu.tpu.array import BoltArrayTPU
    from bolt_tpu.tpu.construct import ConstructTPU

    with open(_meta_path(path)) as f:
        meta = json.load(f)
    mesh = ConstructTPU._resolve(context)
    shape = tuple(meta["shape"])
    split = int(meta["split"])
    sharding = key_sharding(mesh, shape, split)
    if meta.get("format") == "npy" or (
            not os.path.exists(_array_path(path))
            and os.path.exists(_npy_path(path))):
        from bolt_tpu.stream import transfer
        host = np.load(_npy_path(path)).astype(np.dtype(meta["dtype"]),
                                               copy=False)
        return BoltArrayTPU(transfer(host, sharding), split, mesh)
    ocp = _orbax()
    ckptr = ocp.Checkpointer(ocp.ArrayCheckpointHandler())
    data = ckptr.restore(
        os.path.abspath(_array_path(path)),
        args=ocp.args.ArrayRestore(
            restore_args=ocp.ArrayRestoreArgs(
                sharding=sharding, dtype=np.dtype(meta["dtype"]))))
    return BoltArrayTPU(data, split, mesh)


# ---------------------------------------------------------------------
# incremental stream checkpoints (the streamed-run resume format)
# ---------------------------------------------------------------------
#
# On disk: <dir>/stream_state.npz (the partial-accumulator leaves) and
# <dir>/stream_meta.json (fingerprint, watermark, leaf structure).  The
# meta file is the checkpoint's EXISTENCE: state is written and
# replaced first, meta second, both by atomic rename — a kill -9 at any
# instant leaves either the previous complete checkpoint or the new
# complete one, never a meta pointing at torn state.
#
# MULTI-PROCESS runs (bolt_tpu.parallel.multihost) extend the layout to
# PER-PROCESS SHARD FILES with a RENDEZVOUS-CONSISTENT watermark:
# process p writes <dir>/stream_state.p<p>.w<slabs>.npz (the watermark
# is IN the name — old and new checkpoints coexist), every process
# takes a barrier, and only then does process 0 replace the meta to
# point at the new watermark; a second barrier fences the cleanup of
# superseded shard files.  A kill -9 of the whole pod at ANY instant
# therefore leaves a meta whose named watermark has a complete shard
# file for EVERY process — the peers can never resume from different
# watermarks (which would cross the collective fold).  The directory
# must be shared storage (every pod checkpoint system's contract).
#
# Two pod REFINEMENTS ride on one fact (ISSUE 11): the executor's fold
# partials are psum-REPLICATED global values, so every shard file at
# one watermark holds the SAME complete accumulator.  (1) The ABORT
# path (stream_save(rendezvous=False)) lets a survivor persist its
# watermark with no barrier — peers may be dead — under an
# advance-only meta flip; a retired watermark implies every process
# participated in those slabs' collectives, so the point is
# rendezvous-consistent by construction.  (2) The TOPOLOGY REMAP
# (stream_load on a different process count) lets a pod that SHRANK
# (multihost.reform after a peer loss) adopt any surviving shard file
# and resume bit-identically on M<N processes.

_STATE_NAME = "stream_state.npz"
_SMETA_NAME = "stream_meta.json"


def _state_path(path, pid=None, slabs=None):
    if pid is None:
        return os.path.join(path, _STATE_NAME)
    return os.path.join(path, "stream_state.p%d.w%d.npz"
                        % (int(pid), int(slabs)))


def _smeta_path(path):
    return os.path.join(path, _SMETA_NAME)


def _encode(obj, leaves):
    """Structure descriptor for one fold-state node: ``None`` passes
    through, lists/tuples recurse (kind-tagged so decode rebuilds the
    exact container), anything array-like lands in ``leaves`` by
    index.  Covers every accumulator shape the executor folds: bare
    sum/reduce/min/max partials, ``(n, mu, M2)`` moment triples, and
    fused multi-stat component tuples.  Leaves are pulled through
    ``multihost.local_value``: a pod run's fold partials are
    P()-replicated global arrays, whose host copy is the local shard
    (``np.asarray`` refuses the non-fully-addressable global)."""
    if obj is None:
        return None
    if isinstance(obj, list):
        return {"l": [_encode(x, leaves) for x in obj]}
    if isinstance(obj, tuple):
        return {"t": [_encode(x, leaves) for x in obj]}
    leaves.append(_multihost.local_value(obj))
    return {"a": len(leaves) - 1}


def _decode(node, leaves):
    if node is None:
        return None
    if "l" in node:
        return [_decode(x, leaves) for x in node["l"]]
    if "t" in node:
        return tuple(_decode(x, leaves) for x in node["t"])
    return leaves[node["a"]]


def _state_digest(slabs, records, leaves):
    """Content hash of one checkpoint's accumulator state (watermark +
    every leaf's shape/dtype/bytes).  Recorded in the meta by
    :func:`stream_save` and re-verified by :func:`stream_load`, so a
    bit-rotted or truncated shard is REFUSED with a pointed error
    instead of feeding a corrupt accumulator into the fold.  Pod fold
    partials are psum-replicated, so every process's shard file at one
    watermark hashes identically — process 0's meta digest validates
    ANY adopted shard, the topology-remap path included."""
    h = hashlib.sha256()
    h.update(np.asarray([int(slabs), int(records)],
                        dtype=np.int64).tobytes())
    for leaf in leaves:
        arr = np.ascontiguousarray(leaf)
        h.update(repr((arr.shape, str(arr.dtype))).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


def stream_save(path, fingerprint, slabs, records, state,
                multiprocess=None, rendezvous=True, remap_from=None,
                codec=None):
    """Persist one streamed-run checkpoint: ``slabs`` retired slabs
    covering ``records`` records, with ``state`` the executor's folded
    partial accumulator (``(levels, pend)`` — device values are pulled
    to host here).  ``fingerprint`` identifies the logical run (source
    geometry + stage chain + terminal); :func:`stream_load` refuses a
    mismatch so a stale checkpoint can never seed a different pipeline.
    Returns the state's byte count (the ``checkpoint_bytes`` tally).

    On a MULTI-PROCESS run every peer calls this at the SAME watermark
    (the executor checkpoints on a deterministic slab cadence): each
    writes its own watermark-named shard file, a barrier proves all
    landed, process 0 flips the meta, and a second barrier fences the
    cleanup of superseded files — see the section comment above.
    ``multiprocess`` says whether THIS run spans processes — the
    executor passes its MESH's answer, because a process-local mesh
    inside a multi-process runtime streams (and must checkpoint)
    single-process: its peers are not at this watermark, and a barrier
    here would hang them.  ``None`` falls back to the runtime query.

    ``rendezvous=False`` is the POD ABORT path (ISSUE 11): a survivor
    whose run just failed (peer death, injected fault) persists its
    watermark WITHOUT any barrier — peers may be dead or at other
    watermarks.  Safe because a pod run's fold partials are
    psum-replicated GLOBAL values: a retired watermark implies every
    process participated in those slabs' collectives, so ONE process's
    abort state is a complete, rendezvous-consistent resume point.
    The meta advances ONLY forward (an existing same-fingerprint meta
    at a higher-or-equal watermark is left alone), state-first /
    meta-last as always — a torn abort can never flip meta at a
    watermark whose state did not land.

    ``remap_from`` records a topology remap in the meta (the resumed
    run's first checkpoint after a shrink names the pod width the
    loaded checkpoint was cut by) — the audit trail that makes a
    3→2-process resume explainable from the directory alone.
    ``codec`` records the run's ingest codec id the same way (ISSUE
    14): the MATCHING lives in the fingerprint — a codec change names
    a different logical run and the checkpoint is ignored — but the
    meta row makes "this resume point was cut under int8" readable
    from the directory."""
    _chaos.hit("stream.checkpoint")
    os.makedirs(path, exist_ok=True)
    if multiprocess is None:
        multiprocess = _multihost.process_count() > 1
    nproc = _multihost.process_count() if multiprocess else 1
    pid = _multihost.process_index()
    leaves = []
    structure = _encode(state, leaves)
    arrays = {"leaf_%d" % i: leaf for i, leaf in enumerate(leaves)}
    # the watermark rides INSIDE the state file too: a kill between the
    # two renames below leaves the OLD meta next to the NEW state, and
    # without this cross-check a resume would fold the meta's (stale)
    # watermark onto the state's (newer) accumulator — double-counting
    # slabs silently.  stream_load refuses the pair on mismatch.
    # (Multi-process files carry the watermark in their NAME instead:
    # old and new checkpoints coexist and the meta selects one.)
    arrays["watermark"] = np.asarray([int(slabs), int(records)],
                                     dtype=np.int64)
    spath = _state_path(path) if nproc == 1 \
        else _state_path(path, pid, slabs)
    tmp = spath + ".tmp"
    with open(tmp, "wb") as f:
        np.savez(f, **arrays)
    os.replace(tmp, spath)
    try:
        # the bit-rot seam: an armed "checkpoint.corrupt" fault flips
        # bytes in the JUST-WRITTEN state file (simulating storage rot
        # under the atomic rename), which stream_load's digest check
        # must refuse pointedly; action="kill" works unchanged
        _chaos.hit("checkpoint.corrupt")
    except _chaos.ChaosError:
        with open(spath, "r+b") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() // 2))
            f.write(b"\xde\xad\xbe\xef")
    if nproc > 1 and rendezvous:
        # every peer's shard file for THIS watermark exists past here —
        # only then may the meta name it
        _multihost.barrier("bolt_stream_ckpt_w%d" % int(slabs))
    meta = {"fingerprint": list(fingerprint), "slabs": int(slabs),
            "records": int(records), "structure": structure,
            "leaves": len(leaves), "nproc": nproc}
    if remap_from is not None:
        meta["remapped_from"] = int(remap_from)
    if codec is not None:
        meta["codec"] = str(codec)
    if nproc > 1 and not rendezvous:
        meta["abort"] = True
        # advance-only: survivors may abort at different watermarks and
        # each flips the meta for itself — a LOWER watermark must never
        # overwrite a higher one (both are valid resume points; keep
        # the one that loses the least work).  The read-then-rename
        # window is benign: every candidate meta names a complete,
        # rendezvous-consistent state (see the docstring).
        cur = _read_meta(path)
        if cur is not None and \
                list(cur.get("fingerprint", ())) == list(fingerprint) \
                and int(cur.get("slabs", -1)) >= int(slabs):
            return sum(int(leaf.nbytes) for leaf in leaves)
    # single-process checkpoints are written by WHOEVER streams them —
    # a process-local mesh may live on a non-zero runtime process; only
    # the pod format elects process 0 as the one meta writer (abort
    # writes have no rendezvous, so every survivor writes for itself)
    if nproc == 1 or pid == 0 or not rendezvous:
        # the digest hashes every leaf's bytes — pay for it only on
        # the rank that actually writes the meta (pod partials are
        # psum-replicated, so the writer's digest validates any
        # peer's shard), and only past the advance-only abort return
        meta["digest"] = _state_digest(slabs, records, leaves)
        _chaos.hit("checkpoint.meta")
        tmp = _smeta_path(path) + ".tmp"
        with open(tmp, "w") as f:
            json.dump(meta, f)
        os.replace(tmp, _smeta_path(path))
    if nproc > 1 and rendezvous:
        # fence the cleanup: superseded shard files may vanish only
        # once the meta durably points at the new watermark everywhere
        _multihost.barrier("bolt_stream_ckpt_meta_w%d" % int(slabs))
        for old in glob.glob(os.path.join(
                path, "stream_state.p%d.w*.npz" % pid)):
            if old != spath:
                try:
                    os.remove(old)
                except FileNotFoundError:
                    pass
    return sum(int(leaf.nbytes) for leaf in leaves)


def _read_meta(path):
    try:
        with open(_smeta_path(path)) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def stream_load(path, fingerprint, multiprocess=None, info=None):
    """Load a streamed-run checkpoint written by :func:`stream_save`:
    ``(slabs, records, state)`` with host-array leaves, or ``None``
    when no checkpoint exists, its fingerprint names a DIFFERENT
    logical run (shape/stages/terminal drifted — resuming would be
    silently wrong, so the stale checkpoint is ignored), or the meta
    and state files disagree on the watermark (a kill landed between
    the two renames: the torn pair is discarded, never resumed).

    A multi-process run loads the SHARED meta (so every peer agrees on
    the watermark) and this process's own shard file for that
    watermark.  A checkpoint cut by a DIFFERENT process count performs
    a **topology remap** (ISSUE 11 shrink-and-resume): a pod run's
    fold partials are psum-replicated global values — every shard file
    at one watermark holds the same complete accumulator — so a
    resumed M<N-process pod (or a single process) adopts any surviving
    shard file of the meta's watermark (own index preferred, lowest
    index otherwise).  ``info``, when a dict, receives
    ``{"remapped_from": N}`` so the executor can record the remap in
    its next checkpoint write.  ``multiprocess`` mirrors
    :func:`stream_save`'s (the executor passes its mesh's answer;
    ``None`` = the runtime query)."""
    meta = _read_meta(path)           # None on missing OR malformed:
    if meta is None:                  # a torn meta is not a checkpoint
        return None
    if list(meta.get("fingerprint", ())) != list(fingerprint):
        return None
    if multiprocess is None:
        multiprocess = _multihost.process_count() > 1
    nproc = _multihost.process_count() if multiprocess else 1
    meta_nproc = int(meta.get("nproc", 1))
    if meta_nproc == nproc:
        spath = _state_path(path) if nproc == 1 else _state_path(
            path, _multihost.process_index(), int(meta["slabs"]))
        if nproc > 1 and not os.path.exists(spath):
            # this index's file never landed (it was the dead peer's
            # name, or an abort write) — any peer's file is the same
            # replicated global state
            spath = _remap_state_path(path, meta)
    else:
        # topology remap: the checkpoint was cut by a different pod
        # width — adopt a surviving shard file (replicated state)
        spath = _remap_state_path(path, meta)
        if spath is not None and info is not None:
            info["remapped_from"] = meta_nproc
    if spath is None:
        return None
    corrupt = (
        "stream checkpoint state file %r is corrupt (%%s); refusing "
        "to seed the fold with it — delete the file (or the whole "
        "checkpoint dir) to restart from scratch, or restore it from "
        "replicated storage" % spath)
    try:
        z = np.load(spath)
    except FileNotFoundError:
        return None                 # raced cleanup: not a checkpoint
    except Exception as exc:        # noqa: BLE001 — an EXISTING state
        # file that cannot even open is bit rot or truncation, never a
        # torn write (writes are atomic-by-rename)
        raise CheckpointCorruptError(
            corrupt % ("unreadable npz: %s" % exc)) from exc
    try:
        try:
            wm = z["watermark"]
        except Exception as exc:    # noqa: BLE001
            raise CheckpointCorruptError(
                corrupt % ("watermark unreadable: %s" % exc)) from exc
        if int(wm[0]) != int(meta["slabs"]) \
                or int(wm[1]) != int(meta["records"]):
            return None             # meta/state from different writes
        #                             (a kill between the two renames)
        try:
            leaves = [np.asarray(z["leaf_%d" % i])
                      for i in range(int(meta["leaves"]))]
        except Exception as exc:    # noqa: BLE001 — the watermark
            # matched this meta, so the leaves were written by the
            # same atomic write: failing to read them is corruption
            raise CheckpointCorruptError(
                corrupt % ("leaf unreadable: %s" % exc)) from exc
    finally:
        z.close()
    want = meta.get("digest")
    if want is not None and _state_digest(
            meta["slabs"], meta["records"], leaves) != want:
        raise CheckpointCorruptError(
            corrupt % "content digest mismatch vs the meta record")
    state = _decode(meta["structure"], leaves)
    return int(meta["slabs"]), int(meta["records"]), state


def _remap_state_path(path, meta):
    """A usable state file for ``meta``'s watermark, whatever topology
    cut it: this process's own shard file when present, else the
    lowest-index survivor's, else the single-process file.  Valid
    because pod fold partials are replicated global values (see
    :func:`stream_load`)."""
    if int(meta.get("nproc", 1)) == 1:
        sp = _state_path(path)
        return sp if os.path.exists(sp) else None
    slabs = int(meta["slabs"])
    own = _state_path(path, _multihost.process_index(), slabs)
    if os.path.exists(own):
        return own
    cands = glob.glob(os.path.join(path, "stream_state.p*.w%d.npz"
                                   % slabs))
    if not cands:
        return None

    def _pid_of(p):
        try:
            return int(os.path.basename(p).split(".p")[1].split(".w")[0])
        except (IndexError, ValueError):
            return 1 << 30
    return min(cands, key=_pid_of)


def stream_clear(path, multiprocess=None):
    """Remove a directory's stream checkpoint (the success path: a
    finished run must leave NO stale checkpoint behind —
    ``tests/test_resilience.py`` asserts it).  Meta first, then state —
    the reverse of the write order, so an interrupted clear also never
    leaves meta pointing at missing state.  Multi-process (same
    ``multiprocess`` contract as :func:`stream_save` — the executor
    passes its mesh's answer): a barrier proves every peer reached
    success, process 0 removes the meta, a second barrier fences it,
    then each peer removes its own shard files."""
    if multiprocess is None:
        multiprocess = _multihost.process_count() > 1
    if multiprocess:
        _multihost.barrier("bolt_stream_clear")
        if _multihost.process_index() == 0:
            try:
                os.remove(_smeta_path(path))
            except FileNotFoundError:
                pass
        _multihost.barrier("bolt_stream_clear_meta")
        # every peer removes its own shard files; process 0 sweeps the
        # REST too — a pod that shrank (reform) leaves dead peers'
        # stale shard files behind that no surviving index would claim
        pat = ("stream_state.p*.w*.npz"
               if _multihost.process_index() == 0
               else "stream_state.p%d.w*.npz"
               % _multihost.process_index())
        for p in glob.glob(os.path.join(path, pat)):
            try:
                os.remove(p)
            except FileNotFoundError:
                pass
        # dead peers' heartbeat/farewell markers go with their shard
        # files (ISSUE 12 satellite: the shared transport dir must not
        # accumulate a dead pod's droppings)
        from bolt_tpu.parallel import podwatch as _podwatch
        _podwatch.sweep_dead_markers()
        return
    for p in [_smeta_path(path), _state_path(path)] + glob.glob(
            os.path.join(path, "stream_state.p*.w*.npz")):
        # the glob: a single process that resumed a POD checkpoint via
        # the topology remap must not leave the pod's shard files stale
        try:
            os.remove(p)
        except FileNotFoundError:
            pass


def stream_pending(path):
    """Does ``path`` hold a resumable stream checkpoint?"""
    return os.path.exists(_smeta_path(path))


# ---------------------------------------------------------------------------
# shuffle spill slabs (ISSUE 18)
#
# When a streamed `swap` / re-axis shuffle forecasts a working set larger
# than the device arbiter's budget, phase 1 spills each re-keyed bucket
# to disk and phase 2 streams the buckets back as a fresh source.  The
# on-disk format reuses this module's contract: ATOMIC tmp+rename per
# file, self-describing payloads (codec name + dtype + shape + global
# row offset ride inside), and a fingerprint-named working directory so
# a resumed run can only ever adopt ITS OWN spill — a different
# pipeline's leftovers hash to a different directory and are invisible.
#
# Integer/bool buckets are dict-encoded when the slab's cardinality
# allows (codec "dict": uint8 indices + 256-entry dictionary — 1/8 the
# bytes of an int64 key column); anything else is stored raw.  The
# fallback is per-BUCKET and recorded in the file, so mixed-cardinality
# datasets just work and decode never guesses.
#
# Completion is tracked per SLAB (a slab is done only after every one of
# its buckets landed) in a per-process manifest, giving the kill -9
# resume point: a single-process run skips completed slabs; pod runs
# ignore manifests entirely and re-run phase 1 whole (per-process
# manifests can disagree after an asymmetric kill, and a disagreeing
# slab schedule would deadlock the all-to-all rendezvous — atomic
# overwrite keeps the re-run correct).
# ---------------------------------------------------------------------------

def _spill_root(path, fingerprint):
    h = hashlib.sha256(repr(fingerprint).encode()).hexdigest()[:16]
    return os.path.join(path, "bolt-spill-%s" % h)


def _spill_file(path, fingerprint, slab_i, bucket_i):
    return os.path.join(
        _spill_root(path, fingerprint),
        "slab%05d.bucket%05d.p%d.npz"
        % (int(slab_i), int(bucket_i), _multihost.process_index()))


def _spill_manifest_path(path, fingerprint):
    return os.path.join(_spill_root(path, fingerprint),
                        "manifest.p%d.json" % _multihost.process_index())


def spill_save(path, fingerprint, slab_i, bucket_i, block, row0):
    """Persist one re-keyed shuffle bucket (this process's rows of
    bucket ``bucket_i`` produced from input slab ``slab_i``) atomically
    under ``path``'s fingerprint directory.  ``row0`` is the bucket's
    GLOBAL output row offset — phase 2 reassembles buckets by it
    without re-deriving the plan.  Returns the bytes written (the
    ``spill_bytes`` tally).  Integer/bool blocks try the "dict" codec
    first and fall back to raw when the slab's cardinality exceeds the
    dictionary (the fallback is recorded in the file — decode never
    guesses)."""
    block = np.ascontiguousarray(block)
    codec_name = ""
    wire, sides = block, ()
    if np.issubdtype(block.dtype, np.integer) \
            or block.dtype == np.dtype(np.bool_):
        from bolt_tpu.tpu import codec as _codec
        try:
            wire, sides = _codec.get("dict").encode(block, delta_ok=False)
            codec_name = "dict"
        except ValueError:        # > 256 distinct values: store raw
            wire, sides, codec_name = block, (), ""
    root = _spill_root(path, fingerprint)
    os.makedirs(root, exist_ok=True)
    payload = {"wire": wire,
               "row0": np.asarray(int(row0), dtype=np.int64),
               "shape": np.asarray(block.shape, dtype=np.int64),
               "dtype": np.asarray(str(block.dtype)),
               "codec": np.asarray(codec_name),
               "nside": np.asarray(len(sides), dtype=np.int64)}
    for i, s in enumerate(sides):
        payload["side%d" % i] = np.asarray(s)
    fpath = _spill_file(path, fingerprint, slab_i, bucket_i)
    tmp = fpath + ".tmp"
    with open(tmp, "wb") as f:
        np.savez(f, **payload)
    os.replace(tmp, fpath)
    return os.path.getsize(fpath)


def spill_load(path, fingerprint, slab_i, bucket_i):
    """Read one spilled bucket back as ``(host block, row0)`` — the
    inverse of :func:`spill_save`, host-side decode included.  A
    missing or torn file raises :class:`CheckpointCorruptError`
    pointedly (phase 2 only reads slabs the manifest marked done, so a
    hole here is rot or an outside deletion, not a normal resume)."""
    fpath = _spill_file(path, fingerprint, slab_i, bucket_i)
    try:
        with np.load(fpath, allow_pickle=False) as z:
            wire = z["wire"]
            row0 = int(z["row0"])
            dtype = np.dtype(str(z["dtype"]))
            codec_name = str(z["codec"])
            shape = tuple(int(n) for n in z["shape"])
            sides = tuple(z["side%d" % i]
                          for i in range(int(z["nside"])))
    except FileNotFoundError:
        raise CheckpointCorruptError(
            "spill bucket missing: %s — the manifest marked slab %d "
            "done but its bucket %d file is gone (deleted or never "
            "fenced); clear the spill directory "
            "(bolt_tpu.checkpoint.spill_clear) and re-run"
            % (fpath, int(slab_i), int(bucket_i)))
    except (ValueError, OSError, KeyError) as exc:
        raise CheckpointCorruptError(
            "spill bucket unreadable: %s (%s) — torn write or storage "
            "rot; clear the spill directory "
            "(bolt_tpu.checkpoint.spill_clear) and re-run"
            % (fpath, exc))
    if codec_name:
        from bolt_tpu.tpu import codec as _codec
        block = np.asarray(_codec.get(codec_name).decode(
            wire, sides, dtype, delta_ok=False))
    else:
        block = wire.astype(dtype, copy=False)
    return block.reshape(shape), row0


def spill_slab_done(path, fingerprint, slab_i):
    """Mark input slab ``slab_i`` complete in this process's spill
    manifest — called ONLY after every bucket of the slab landed, so
    the manifest's claim is the fence (a kill between bucket writes
    leaves the slab unmarked and the resume re-runs it; the atomic
    per-bucket overwrite makes that idempotent)."""
    done = sorted(spill_manifest(path, fingerprint) | {int(slab_i)})
    mpath = _spill_manifest_path(path, fingerprint)
    os.makedirs(os.path.dirname(mpath), exist_ok=True)
    tmp = mpath + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"done": done}, f)
    os.replace(tmp, mpath)


def spill_manifest(path, fingerprint):
    """The set of input slabs this process has fully spilled for
    ``fingerprint`` under ``path`` — empty when no spill exists (a
    different fingerprint hashes to a different directory, so a stale
    spill can never leak into a changed pipeline)."""
    try:
        with open(_spill_manifest_path(path, fingerprint)) as f:
            return set(int(s) for s in json.load(f)["done"])
    except (FileNotFoundError, ValueError, KeyError):
        return set()


def spill_pending(path):
    """Does ``path`` hold any shuffle spill working directory?"""
    return bool(glob.glob(os.path.join(path, "bolt-spill-*")))


def spill_clear(path):
    """Remove every shuffle spill working directory under ``path`` (the
    success path: a completed shuffle's phase 2 owns its buckets only
    until the output is consumed — ``tests/test_stream_swap.py``
    asserts a cleared directory holds no ``bolt-spill-*`` residue,
    half-written ``.tmp`` droppings included)."""
    import shutil
    for d in glob.glob(os.path.join(path, "bolt-spill-*")):
        shutil.rmtree(d, ignore_errors=True)
