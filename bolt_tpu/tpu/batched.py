"""Batched terminal programs: continuous micro-batching for the serve
queue (ROADMAP item 4, the ``StackedArray`` "batched execution" idea —
SURVEY §2.4 — applied to the request firehose).

Everything below this module optimises ONE pipeline's bytes; a
million-user service is mostly many SMALL identical-shape pipelines
where per-request dispatch overhead, not HBM, is the roofline.  This
module gives the lazy terminals a BATCHED program form the scheduler
(``bolt_tpu.serve``) can dispatch once for N queued requests:

* :func:`batch_key` — the coalescing identity of a submitted pipeline:
  same deferred structure (map chain + terminal slots), same base
  shape/dtype, same split and mesh (⇒ same sharding) hash equal; any
  difference keeps requests apart.  Covers the lazy stat family
  (single terminals AND fused multistat groups), the deferred
  ``reduce(func)`` handle (armed by :func:`bolt_tpu.tpu.multistat.
  defer_reduce` while batching is on), and plain deferred-chain
  materialisation.
* :func:`claim` / :func:`dispatch` / :func:`unclaim` — one batched
  execution: the requests' stat groups are CLAIMED (concurrent readers
  wait on the claim event instead of double-dispatching; new members
  are declined), their bases stacked along a new leading axis inside
  ONE engine-keyed program ``("batched", inner-key, width)`` that
  vmaps the SAME traced terminal body the standalone programs use
  (``multistat._chain_stat_exprs`` / ``array._reduce_tree_expr`` /
  ``_chain_apply`` — the ``_stack_map_body`` one-body-many-programs
  seam), and every lane's results scatter back to its request's
  members — bit-identical to the standalone dispatch, because each
  lane's expressions see only that lane's row.
* **bucketed widths**: partial batches PAD to the next bucket
  (powers of two up to the policy's ``max_batch``; pad lanes replay
  lane 0 and their outputs are discarded), so steady state compiles a
  small fixed set of executables — zero fresh XLA compiles once the
  buckets are warm (:func:`warm` pre-compiles them for a fleet).

Donating pipelines never batch (the stacked program reads all N bases
— consuming them would break the one-donate-per-terminal contract),
and streamed sources batch per slab through their own executor, not
here.  The serve layer records one ``batched_dispatches`` /
``batched_requests`` engine-counter pair per coalesced dispatch plus
the ``serve.batch_occupancy.hist`` registry histogram.
"""

import os
import threading

import jax
import jax.numpy as jnp

from bolt_tpu import _lockdep
from bolt_tpu import engine as _engine
from bolt_tpu.obs import trace as _obs
from bolt_tpu.utils import prod

# ---------------------------------------------------------------------
# policy defaults (the serve layer's BatchPolicy reads these)
# ---------------------------------------------------------------------

# widest coalesced dispatch: one batched program serves up to this many
# queued same-key requests
DEFAULT_MAX_BATCH = max(2, int(os.environ.get("BOLT_SERVE_MAX_BATCH",
                                              "16")))
# micro-wait to FILL a forming batch (seconds): once a gather found at
# least one coalescible partner, the worker lingers up to this long for
# more same-key arrivals before dispatching.  A lone request never
# lingers — low-QPS single-request latency is untouched.
DEFAULT_LINGER = float(os.environ.get("BOLT_SERVE_LINGER", "0.002"))


def buckets_for(max_batch):
    """The bucketed batch widths for ``max_batch``: powers of two up to
    and including it (plus ``max_batch`` itself when it is not one), so
    steady state compiles O(log max_batch) executables per batch key."""
    max_batch = int(max_batch)
    if max_batch < 2:
        raise ValueError("max_batch must be >= 2, got %d" % max_batch)
    out, b = set(), 2
    while b <= max_batch:
        out.add(b)
        b *= 2
    out.add(max_batch)
    return tuple(sorted(out))


def bucket_width(n, buckets):
    """Smallest bucket that fits ``n`` requests (the dispatch width —
    ``bucket - n`` lanes are padding)."""
    for b in buckets:
        if b >= n:
            return b
    return buckets[-1]


def autotune_buckets(hist_buckets, max_batch, min_share=0.05):
    """Derive a bucket set from an OBSERVED batch-occupancy histogram
    (the ``[(upper_bound, count)]`` pairs of
    ``serve.batch_occupancy.hist``).  Each log2 band ``[2^e, 2^(e+1))``
    holding at least ``min_share`` of the observations contributes BOTH
    its edges as widths (clamped to ``[2, max_batch]``): the lower edge
    ``2^e`` serves the band's exact-power occupancies with ZERO padding
    (a steady occupancy of exactly 4 must dispatch at width 4, not pad
    to 8), the upper edge serves the rest of the band minimally.
    ``max_batch`` always closes the set — the
    :class:`~bolt_tpu.serve.BatchPolicy` invariant that a full batch
    never pads.  Returns ``None`` when the histogram holds no
    observations (nothing to tune from — the caller keeps its static
    buckets).

    This is the WIDTH-AUTOTUNING scaffold (ROADMAP item 4 remainder):
    ``BatchPolicy(autotune=True)`` re-derives its buckets from the
    realised occupancy mix on every :func:`warm` re-arm, so a fleet
    that mostly coalesces 3-at-a-time stops compiling (and padding to)
    widths it never fills.  With autotune off — the default — the
    static knobs are untouched."""
    import math
    total = sum(c for _, c in hist_buckets)
    if not total:
        return None
    mb = int(max_batch)
    out = {mb}
    for ub, cnt in hist_buckets:
        if not cnt or cnt / total < min_share:
            continue
        if not math.isfinite(ub):
            out.add(mb)                 # overflow band: max_batch only
            continue
        out.add(min(mb, max(2, int(ub))))        # the band's upper edge
        out.add(min(mb, max(2, int(ub) // 2)))   # ...and its lower edge
    return tuple(sorted(out))


# ---------------------------------------------------------------------
# arming (the lazy-reduce door reads this; serve arms per batching
# server)
# ---------------------------------------------------------------------

_ARMED = 0
_ARM_LOCK = _lockdep.lock("batched.arm")


def arm():
    """Arm the batching doors (called by ``serve.Server`` when a
    batching policy is configured; nests across servers)."""
    global _ARMED
    with _ARM_LOCK:
        _ARMED += 1


def disarm():
    global _ARMED
    with _ARM_LOCK:
        _ARMED = max(0, _ARMED - 1)


def armed():
    """True while at least one batching-enabled server is alive — the
    gate ``multistat.defer_reduce`` consults before deferring
    ``reduce(func)``."""
    return _ARMED > 0


# ---------------------------------------------------------------------
# the batch key
# ---------------------------------------------------------------------

def _group_slots(g):
    """A stat group's program-slot identity (deduped/sorted like the
    fused program's) — or the reduce slot for a deferred-reduce
    group."""
    from bolt_tpu.tpu.multistat import _slot
    if g.rfunc is not None:
        m = g.members[0]
        return (("reduce", m.axes, m.keepdims, None),)
    ms = g.members
    if len(ms) == 1:
        # singleton fast path — THE small-request shape; _slot already
        # returns ptp's pair in the sorted ("max" < "min") order
        return _slot(ms[0])
    return tuple(sorted({s for m in ms for s in _slot(m)},
                        key=repr))


def batch_key(arr):
    """The coalescing identity of a submitted pipeline, or ``None``
    when it cannot batch.  Two requests with equal keys share ONE
    batched dispatch: same terminal slots, same map chain (callable
    identity — hoist stage functions, exactly the cross-tenant
    coalescing contract), same base shape/dtype, same split and mesh
    (the mesh determines the key sharding, so equal keys ⇒ equal
    sharding).  Ineligible: donating chains (donation semantics stay
    standalone), streams (they batch per slab in their own executor),
    deferred filters/compactions, and already-resolved handles."""
    from bolt_tpu.tpu.array import BoltArrayTPU, _chain_donate_ok
    if not isinstance(arr, BoltArrayTPU) or arr._donated:
        return None
    h = arr._spending
    if h is not None:
        if h.result is not None:
            return None
        g = h.group
        if g.kind != "chain" or g.donate or g.dispatched:
            return None
        base = g.base
        if getattr(base, "is_deleted", lambda: False)():
            return None
        return ("stat", _group_slots(g), g.funcs, g.rfunc,
                tuple(base.shape), str(base.dtype), g.split, g.mesh)
    if (arr._chain is not None and arr._fpending is None
            and arr._pending is None and arr._stream is None
            and arr._stat_group is None):
        # a deferred map chain whose submitted terminal is
        # materialisation (serve resolves via .cache())
        if _chain_donate_ok(arr._chain):
            return None
        base, funcs = arr._chain
        if not funcs or getattr(base, "is_deleted", lambda: False)():
            return None
        return ("chain", funcs, tuple(base.shape), str(base.dtype),
                arr._split, arr._mesh)
    return None


# ---------------------------------------------------------------------
# claim / dispatch / unclaim
# ---------------------------------------------------------------------

class _Batch:
    """One claimed batched execution: the per-request sources plus the
    shared geometry the program builder closes over (geometry ONLY —
    the builder must never capture arrays)."""

    __slots__ = ("kind", "key", "arrs", "groups", "slots", "funcs",
                 "rfunc", "split", "mesh", "bases", "in_shape")

    def __init__(self, kind, key, arrs, groups, slots, funcs, rfunc,
                 split, mesh, bases, in_shape):
        self.kind = kind
        self.key = key
        self.arrs = arrs
        self.groups = groups
        self.slots = slots
        self.funcs = funcs
        self.rfunc = rfunc
        self.split = split
        self.mesh = mesh
        self.bases = bases
        self.in_shape = in_shape


def _claim_group(g, slots):
    """Claim one stat group for a batched fill; False when it raced
    away (resolved/claimed concurrently, or its slot set grew past the
    batch key's)."""
    with g.lock:
        if g.dispatched or g.claimed:
            return False
        if _group_slots(g) != slots:
            return False               # a sibling joined since submit
        g.claimed = True
        if g.claim_event is None:
            g.claim_event = threading.Event()
        else:
            g.claim_event.clear()
        return True


def _unclaim_group(g):
    with g.lock:
        g.claimed = False
        ev = g.claim_event
    if ev is not None:
        ev.set()


def claim(arrs, key):
    """Claim the requests in ``arrs`` (all sharing ``key``) for one
    batched dispatch; returns a :class:`_Batch` over the CLAIMABLE
    subset — a member that raced away (its group resolved concurrently,
    a sibling joined since submit, its base was donated) is simply
    DROPPED from the batch and dispatches standalone in the caller's
    adoption loop, so one raced request never costs the healthy
    majority their coalescing.  ``None`` when fewer than two members
    remain claimable (nothing left to coalesce)."""
    kind = key[0]
    if kind == "stat":
        slots = key[1]
        kept, groups = [], []
        for a in arrs:
            h = a._spending
            g = h.group if h is not None else None
            if (g is None or h.result is not None
                    or getattr(g.base, "is_deleted", lambda: False)()
                    or not _claim_group(g, slots)):
                continue               # raced away: standalone path
            kept.append(a)
            groups.append(g)
        if len(kept) < 2:
            for cg in groups:
                _unclaim_group(cg)
            return None
        g0 = groups[0]
        return _Batch("stat", key, kept, groups, slots, g0.funcs,
                      g0.rfunc, g0.split, g0.mesh,
                      [g.base for g in groups],
                      tuple(g0.in_aval.shape))
    kept = [a for a in arrs
            if a._chain is not None and not a._donated
            and not getattr(a._chain[0], "is_deleted", lambda: False)()]
    if len(kept) < 2:
        return None
    base0, funcs = kept[0]._chain
    return _Batch("chain", key, kept, None, None, funcs, None,
                  kept[0]._split, kept[0]._mesh,
                  [a._chain[0] for a in kept], tuple(base0.shape))


def unclaim(batch):
    """Release a claimed batch WITHOUT filling it (the dispatch failed
    or was abandoned): claimed groups un-claim so their handles resolve
    standalone; already-filled groups are left dispatched."""
    if batch.groups is not None:
        for g in batch.groups:
            _unclaim_group(g)


def dispatch(batch, buckets, record=True):
    """Run ONE batched program for every request in ``batch``: stack
    the bases along a new leading axis (padding to the bucket width
    with lane 0), vmap the shared terminal body, and scatter each
    lane's constrained outputs back to its request — stat/reduce
    members filled under their group locks (waiting readers wake),
    chain requests adopt their materialised row.  Engine-keyed as
    ``("batched", inner-key, bucket)`` so steady state re-dispatches
    compiled executables only."""
    from bolt_tpu.tpu.array import _check_live, _constrain
    from bolt_tpu.tpu import multistat as _ms
    n = len(batch.arrs)
    bw = bucket_width(n, buckets)
    kind, slots = batch.kind, batch.slots
    funcs, rfunc = batch.funcs, batch.rfunc
    split, mesh = batch.split, batch.mesh
    in_shape = batch.in_shape
    if kind == "stat" and rfunc is not None:
        from bolt_tpu.tpu.array import _reduce_tree_expr
        (_, axes, keepdims, _), = slots
        nrec = prod(in_shape[:split])
        vshape = in_shape[split:]

        def expr(d):
            return (_reduce_tree_expr(d, rfunc, funcs, split, nrec,
                                      vshape, keepdims),)
        nsplits = (split if keepdims else 0,)
    elif kind == "stat":
        def expr(d):
            return _ms._chain_stat_exprs(d, funcs, split, slots, None,
                                         mesh)
        nsplits = tuple(_ms._new_split(split, s[1], s[2]) for s in slots)
    else:
        from bolt_tpu.tpu.array import _chain_apply

        def expr(d):
            return (_chain_apply(funcs, split, d),)
        nsplits = (split,)

    def build():
        def run(*bases):
            stacked = jnp.stack(bases)
            outs = jax.vmap(expr)(stacked)
            return tuple(
                tuple(_constrain(o[i], mesh, ns)
                      for o, ns in zip(outs, nsplits))
                for i in range(bw))
        return jax.jit(run)

    fn = _engine.get(("batched", batch.key, bw), build)
    bases = [_check_live(b) for b in batch.bases]
    bases = bases + [bases[0]] * (bw - n)     # pad lanes replay lane 0
    sp = _obs.begin("serve.batched_dispatch", width=n, bucket=bw,
                    kind=kind)
    try:
        outs = fn(*bases)
    finally:
        _obs.end(sp)
    if record:
        _engine.record_batched(n)
    if kind == "stat":
        index = {s: j for j, s in enumerate(slots)}
        for i, g in enumerate(batch.groups):
            lane = outs[i]
            with g.lock:
                for m in g.members:
                    if rfunc is not None:
                        m.result = lane[0]
                    elif m.name == "ptp":
                        mx = lane[index[_ms._slot(m)[0]]]
                        mn = lane[index[_ms._slot(m)[1]]]
                        m.result = _ms._sub_program(
                            mx.shape, mx.dtype, mesh)(mx, mn)
                    else:
                        m.result = lane[index[_ms._slot(m)[0]]]
                if rfunc is None:
                    _ms._record_one_pass(g)
                g.dispatched = True
                g.claimed = False
                ev = g.claim_event
            if ev is not None:
                ev.set()                # wake readers parked in resolve
    else:
        for a, lane in zip(batch.arrs, outs):
            a._adopt_materialised(lane[0])
    return n


def warm(make, buckets=None, max_batch=None, policy=None):
    """Pre-compile the batched executables at every bucket width for
    the batch key of ``make()``'s pipeline (the fleet analog of
    ``engine.warm_start``): each width dispatches one throwaway batch
    built from fresh ``make()`` pipelines, so a serving steady state —
    whatever occupancy mix it realises — runs ZERO fresh XLA compiles.
    Returns the warmed widths.

    ``policy=`` is the autotune RE-ARM door: pass the server's live
    :class:`~bolt_tpu.serve.BatchPolicy` and — when it was built with
    ``autotune=True`` and the occupancy histogram has observations —
    its bucket set is re-derived from the realised occupancy mix
    (``policy.rearm()``) before warming, so the freshly compiled
    widths are the ones traffic actually fills.  A static policy
    (autotune off, the default) passes through untouched."""
    if policy is not None:
        policy.rearm()
        bks = tuple(policy.buckets)
    elif buckets:
        bks = tuple(buckets)
    else:
        bks = buckets_for(
            max_batch if max_batch is not None else DEFAULT_MAX_BATCH)
    warmed = []
    for bw in bks:
        arrs = [make() for _ in range(bw)]
        key = batch_key(arrs[0])
        if key is None:
            raise ValueError(
                "warm(): make() built a pipeline that cannot batch "
                "(no batch key — see batched.batch_key)")
        b = claim(arrs, key)
        if b is None:
            raise RuntimeError("warm(): could not claim the throwaway "
                               "warm pipelines")
        # record=False: throwaway warm dispatches must not inflate the
        # batched_dispatches/batched_requests tallies stats() reports
        # as REALISED coalescing
        dispatch(b, (bw,), record=False)
        warmed.append(bw)
    return tuple(warmed)
