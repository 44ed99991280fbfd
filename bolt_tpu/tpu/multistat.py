"""One-pass multi-terminal statistics: the fused ``bolt.compute`` layer.

The single-terminal reductions are at the HBM roofline — a ``map→sum``
pass reads every byte once, and no further single-chip win exists for
ONE statistic.  What Bolt's design has always promised (PAPER.md: every
StatCounter moment from one pass over the values) is doing MORE per byte
read: this module makes ``a.sum()``-family terminals *lazy*
:class:`PendingStat` handles and groups handles that share a source —
the same deferred ``_chain``, the same deferred ``_fpending`` filter, or
the same out-of-core stream — into a :class:`_StatGroup` that dispatches
ONE tuple-output program::

    s, v, lo, hi = bolt.compute(a.sum(), a.var(), a.min(), a.max())
    # map/filter stages applied once, four partials from ONE HBM pass

ONE pass holds for a group with a ``var``/``std`` member of real
floating data since PR 61: they are traced as shifted moments about a
pilot mean known before the pass (``tpu/moments.py``; error scales with
``var + (mean - pilot)**2``), sibling reductions of the other members.
Before it ``jnp.var``'s centred second moment made such a group TWO
reads, which it still is for complex, integer and boolean data (they
keep ``jnp.var``) and, on a mesh of several devices, where ONLY the
sharded key axes are reduced (the pilot takes those whole).  A filtered group's ``var`` is one pass in the
UNSHIFTED form (``array._masked_stat_expr``: error scales with ``var +
mean**2``); a streamed group's is per-slab moments merged by Chan.

Laziness is read-transparent: everything observable at call time stays
at call time (axis validation, the ``analysis.strict`` gate, the
donation decision — a sole-owned chain base is consumed by its FIRST
pending terminal and later siblings join the same group, so N fused
stats cost one donate), and only the engine dispatch moves to the first
read.  A handle read before any sibling exists resolves through the
EXACT standalone program — same engine key, same expressions — so a
lone ``a.sum()`` is byte-for-byte the pre-fusion terminal; a fused
group's outputs are bit-identical to those standalone terminals because
the tuple program traces the same per-terminal expressions over one
shared read (XLA's sibling multi-output fusion serves them from a
single traversal).

Grouping rule: same ``_chain``/``_fpending``/stream source ⇒ same
program; anything else falls back per group ("mixed chains fall back
per group").  ``ptp`` routes through the fused min/max pair — its slots
dedup against sibling ``min``/``max`` members, so
``compute(a.ptp(), a.min(), a.max())`` still emits exactly two extrema
from one pass (and ``a.ptp()`` alone shares the pair program's key
instead of owning a private one).

Reduced-precision accumulation (``compute(..., accumulate="bf16")`` or
the :func:`bolt_tpu._precision.accumulate` scope) is the opt-in fast
path for the additive terminals of an in-memory fused group: values
cast to bf16, accumulated in f32 (the accumulate-in-f32 contract; "f32"
casts values to f32, which for f32 pipelines is exactly the default
arithmetic); ``accumulate="int8"`` is the integer twin — int8 values,
int32 accumulator (accumulate-in-i32), integer additive terminals
(sum/prod) only, exact for values in int8 range.  The default
(``None``) stays bit-exact; order statistics (min/max/any/all, the
pair behind ptp) are always exact.

Streamed groups fold a tuple accumulator through the PR 5 pipeline
(``stream.execute`` of a ``stream._Multi``): one ingest pass feeds every
member, the shared ``(n, mu, M2)`` moments triple serves all of
mean/var/std, and Chan denominators stay exact on power-of-two slab
counts — streamed multi-stat matches materialised bit-exactly there.
"""

from collections import OrderedDict
import threading

import numpy as np

import jax
import jax.numpy as jnp

from bolt_tpu import _lockdep
from bolt_tpu import engine as _engine
from bolt_tpu import _precision
from bolt_tpu import stream as _streamlib
from bolt_tpu.obs import trace as _obs
from bolt_tpu.tpu import fold as _fold
from bolt_tpu.tpu import moments as _onepass
from bolt_tpu.utils import inshape, prod, tupleize


def _cached_jit(key, builder):
    """Engine-routed executable dispatch (same contract as the op
    modules')."""
    return _engine.get(key, builder)


# terminals that defer as PendingStat handles (everything _stat serves)
LAZY_NAMES = ("sum", "mean", "var", "std", "min", "max", "prod", "all",
              "any", "ptp")

# deferred-filter groups: min/max need the survivor-count sync (the
# zero-size error contract) and stay eager; ptp resolves the filter
_FPENDING_LAZY = ("sum", "prod", "any", "all", "mean", "var", "std")

# streamed groups: the accumulator components the slab programs emit
# (prod/all/any have no bit-exact streamed fold and materialise)
_STREAM_LAZY = ("sum", "mean", "var", "std", "min", "max", "ptp")

# accumulate= applies to the additive reductions only; order statistics
# are exact regardless.  The float modes (bf16/f32) serve the whole
# additive family; "int8" serves the INTEGER additive terminals — the
# moment family is float-valued and ignores it
_ADDITIVE = ("sum", "prod", "mean", "var", "std")
_INT_ADDITIVE = ("sum", "prod")

_OPS = {"mean": jnp.mean, "var": _onepass.var, "std": _onepass.std,
        "sum": jnp.sum, "max": jnp.max, "min": jnp.min,
        "prod": jnp.prod, "all": jnp.all, "any": jnp.any,
        "ptp": jnp.ptp}


class PendingStat:
    """One lazy stat terminal: the member record of a
    :class:`_StatGroup`.  Holds the normalised spec, the abstractly
    derived output aval, and (after the group dispatches) the concrete
    result the owning array adopts on first read."""

    __slots__ = ("group", "name", "axes", "keepdims", "ddof", "aval",
                 "new_split", "result")

    def __init__(self, group, name, axes, keepdims, ddof, aval,
                 new_split):
        self.group = group
        self.name = name
        self.axes = axes
        self.keepdims = bool(keepdims)
        self.ddof = ddof
        self.aval = aval
        self.new_split = int(new_split)
        self.result = None

    def __repr__(self):
        return "PendingStat(%s, axes=%s%s)" % (
            self.name, self.axes,
            ", resolved" if self.result is not None else "")


def _slot(member):
    """Program-output slot(s) one member needs — ``ptp`` expands to the
    min/max pair so its slots dedup against sibling extrema members."""
    if member.name == "ptp":
        return (("max", member.axes, member.keepdims, None),
                ("min", member.axes, member.keepdims, None))
    return ((member.name, member.axes, member.keepdims, member.ddof),)


class _StatGroup:
    """A set of pending stat terminals sharing ONE single-pass source.

    ``kind``:

    * ``"chain"``   — a deferred map chain (or a concrete base): the
      fused program applies the chain once and emits one reduction per
      slot.  ``donate`` was decided (with the standalone terminals'
      exact refcount test) when the FIRST handle was created; the
      consumed source keeps a pointer here so later siblings join the
      group — one donate for N stats.
    * ``"fpending"`` — a deferred filter: mapped chain + predicate mask
      traced once, every member folds the same mask.
    * ``"stream"``  — a lazy out-of-core source: one ingest pass through
      ``stream.execute`` of a ``stream._Multi`` feeds a tuple accumulator.
    """

    __slots__ = ("kind", "mesh", "split", "base", "funcs", "fpending",
                 "source", "donate", "in_aval", "members", "dispatched",
                 "lock", "rfunc", "claimed", "claim_event")

    def __init__(self, kind, mesh, split, base=None, funcs=(),
                 fpending=None, source=None, donate=False, in_aval=None):
        self.kind = kind
        self.mesh = mesh
        self.split = split
        self.base = base
        self.funcs = funcs
        self.fpending = fpending
        self.source = source
        self.donate = donate
        self.in_aval = in_aval
        self.members = []
        self.dispatched = False
        self.lock = _lockdep.lock("multistat.group")
        # a chain group carrying a deferred reduce(func) terminal
        # (bolt_tpu/tpu/batched.py's lazy door): singleton, never joined
        # by stat members — its standalone resolution is the EXACT eager
        # reduce program
        self.rfunc = None
        # serve micro-batching claim (bolt_tpu/tpu/batched.py): while a
        # batched dispatch owns this group, resolve() WAITS on the claim
        # event instead of dispatching standalone, and try_join declines
        # new members (they could never ride the already-shaped batch)
        self.claimed = False
        self.claim_event = None

    # -- joining -------------------------------------------------------

    def try_join(self, axis, name, keepdims, ddof):
        """Validate ``(axis, name, ...)`` against this group's kind and
        geometry; returns a new member handle, or NotImplemented when
        the spec cannot ride this group's fused program (the caller
        falls back to the eager path)."""
        if self.rfunc is not None:
            # a deferred-reduce group is singleton by contract: its one
            # slot is the reduce tree, which no stat member can share
            return NotImplemented
        if self.kind == "stream":
            h = _stream_member(self, name, axis, keepdims, ddof)
        elif self.kind == "fpending":
            h = _fpending_member(self, name, axis, keepdims, ddof)
        else:
            h = _chain_member(self, name, axis, keepdims, ddof)
        if h is not NotImplemented:
            with self.lock:
                if self.dispatched or self.claimed:
                    # a concurrent reader resolved the group (or a serve
                    # batched dispatch claimed it) between the caller's
                    # check and this append: the new member would never
                    # be filled — decline, the caller starts a fresh
                    # group / eager path
                    return NotImplemented
                self.members.append(h)
        return h

    # -- resolution ----------------------------------------------------

    def resolve(self, accumulate=None):
        """Dispatch the group's program(s), filling every member's
        ``result``.  Idempotent and thread-safe; ``accumulate`` is the
        per-call reduced-precision override (``bolt.compute``'s
        kwarg).  While a serve batched dispatch holds this group's
        CLAIM (bolt_tpu/tpu/batched.py), a concurrent reader waits for
        the batched fill (or the unclaim, after which it dispatches
        standalone) instead of double-dispatching."""
        while True:
            with self.lock:
                if self.dispatched:
                    return
                ev = self.claim_event if self.claimed else None
                if ev is None:
                    mode = _precision.resolve_accumulate(accumulate)
                    if mode is not None and self.rfunc is not None:
                        # reduce(func) IGNORES accumulate and runs
                        # exact, deferred or not — exactly what the
                        # eager path always did (compute(handle,
                        # accumulate=...) must not start raising just
                        # because a batching server armed the lazy
                        # door)
                        mode = None
                    elif mode is not None and self.kind != "chain":
                        if accumulate is not None:
                            raise ValueError(
                                "accumulate=%r applies to in-memory "
                                "fused reductions only; this group "
                                "streams/filters (%s) and runs exact"
                                % (accumulate, self.kind))
                        mode = None     # ambient scope: exact fallback
                    if self.rfunc is not None:
                        self._resolve_reduce()
                    elif self.kind == "chain":
                        self._resolve_chain(mode)
                    elif self.kind == "fpending":
                        self._resolve_fpending()
                    else:
                        self._resolve_stream()
                    self.dispatched = True
                    return
            # claimed by a serve batched dispatch on a worker thread:
            # wait for the fill/unclaim and re-check (the timeout only
            # bounds a claim owner dying without its unclaim finally)
            ev.wait(1.0)

    def _resolve_reduce(self):
        """Standalone resolution of a deferred ``reduce(func)`` handle:
        the EXACT eager reduce program — same engine key (donate=False,
        the lazy door refuses donating chains), same traced pairwise
        tree (`array._reduce_tree_expr`)."""
        from bolt_tpu.tpu.array import _check_live, _constrain, \
            _reduce_tree_expr, _span_funcs
        m = self.members[0]
        func = self.rfunc
        base, funcs, split, mesh = (self.base, self.funcs, self.split,
                                    self.mesh)
        shape = tuple(self.in_aval.shape)
        n = prod(shape[:split])
        vshape = shape[split:]
        # cached programs close over GEOMETRY only: a closure holding
        # the member handle would pin group -> base, i.e. the engine
        # cache would keep the device buffer alive
        keepdims, new_split = m.keepdims, m.new_split

        def build():
            def reducer(data):
                out = _reduce_tree_expr(data, func, funcs, split, n,
                                        vshape, keepdims)
                return _constrain(out, mesh, new_split)
            return jax.jit(reducer)

        fn = _cached_jit(("reduce", func, funcs, base.shape,
                          str(base.dtype), split, keepdims, False, mesh),
                         build)
        with _obs.span("array.reduce", donate=False, **_span_funcs(funcs)):
            m.result = fn(_check_live(base))

    def _resolve_chain(self, mode):
        from bolt_tpu.tpu.array import _check_live, _chain_apply, \
            _constrain, _span_funcs
        members = self.members
        base, funcs, split, mesh = (self.base, self.funcs, self.split,
                                    self.mesh)
        donate = self.donate
        if len(members) == 1 and members[0].name != "ptp" and mode is None:
            # standalone resolution: the EXACT pre-fusion terminal —
            # same engine key, same traced expressions
            m = members[0]
            # geometry only in the cached closure (see _resolve_reduce)
            name, ddof = m.name, m.ddof
            axes, keepdims, new_split = m.axes, m.keepdims, m.new_split

            def build():
                def stat(data):
                    mapped = _chain_apply(funcs, split, data)
                    out = _stat_expr(mapped, name, axes, keepdims, ddof,
                                     None, mesh, split)
                    return _constrain(out, mesh, new_split)
                return jax.jit(stat,
                               donate_argnums=(0,) if donate else ())

            fn = _cached_jit(("stat", m.name, funcs, base.shape,
                              str(base.dtype), split, m.axes, m.keepdims,
                              m.ddof, donate, mesh), build)
            with _obs.span("array.stat", op=m.name, donate=donate,
                           **_span_funcs(funcs)):
                m.result = fn(_check_live(base))
            _record_one_pass(self)
            return

        # the fused multi-terminal program: one read, one slot per
        # distinct (name, axes, keepdims, ddof) — sorted for an
        # order-insensitive key, deduped so compute(ptp, min, max)
        # still emits exactly two extrema
        slots = sorted({s for m in members for s in _slot(m)}, key=repr)
        slots = tuple(slots)
        nsplit = {s: _new_split(split, s[1], s[2]) for s in slots}

        def build():
            def stat(data):
                outs = _chain_stat_exprs(data, funcs, split, slots, mode,
                                         mesh)
                return tuple(_constrain(o, mesh, nsplit[s])
                             for o, s in zip(outs, slots))
            return jax.jit(stat, donate_argnums=(0,) if donate else ())

        fn = _cached_jit(("multi-stat", slots, funcs, base.shape,
                          str(base.dtype), split, donate, mode, mesh),
                         build)
        with _obs.span("array.multi_stat", terminals=len(members),
                       slots=len(slots), donate=donate,
                       accumulate=mode or "exact", **_span_funcs(funcs)):
            outs = fn(_check_live(base))
        if len(members) > 1:
            _engine.record_fused_stats(len(members))
        _record_one_pass(self)
        index = {s: i for i, s in enumerate(slots)}
        for m in members:
            if m.name == "ptp":
                mx = outs[index[_slot(m)[0]]]
                mn = outs[index[_slot(m)[1]]]
                m.result = _sub_program(mx.shape, mx.dtype, mesh)(mx, mn)
            else:
                m.result = outs[index[_slot(m)[0]]]

    def _resolve_fpending(self):
        from bolt_tpu.tpu.array import _constrain, _launch_filter_terminal
        members = self.members
        fp = self.fpending
        base = fp.base
        # geometry only in the cached closures (see _resolve_reduce)
        geo = fp.geometry()
        mesh = self.mesh
        donate = self.donate
        if len(members) == 1:
            # standalone resolution: the exact filter-stat terminal of
            # the eager path (same key, same fold; never needs_count —
            # min/max handles are not lazy here)
            m = members[0]
            slot, new_split = _slot(m)[0], m.new_split

            def build():
                fold = _fold.Fold(geo, (slot,))

                def stat(data):
                    out, = _fold.fold_records(fold, data)
                    return _constrain(out, mesh, new_split)
                return jax.jit(stat,
                               donate_argnums=(0,) if donate else ())

            fn = _cached_jit(("filter-stat", m.name) + fp.key()
                             + (m.axes, m.keepdims, m.ddof, donate, mesh),
                             build)
            m.result = _launch_filter_terminal(fn, base, m.name, donate)
            return

        slots = sorted({s for m in members for s in _slot(m)}, key=repr)
        slots = tuple(slots)

        def build():
            fold = _fold.Fold(geo, slots)

            def stat(data):
                return tuple(
                    _constrain(out, mesh, 1 if keepdims else 0)
                    for out, (_, _, keepdims, _) in zip(
                        _fold.fold_records(fold, data), slots))
            return jax.jit(stat, donate_argnums=(0,) if donate else ())

        fn = _cached_jit(("multi-filter-stat", slots) + fp.key()
                         + (donate, mesh), build)
        with _obs.span("array.multi_stat", terminals=len(members),
                       slots=len(slots), filtered=True, donate=donate):
            outs = _launch_filter_terminal(fn, base, "multi", donate)
        _engine.record_fused_stats(len(members))
        index = {s: i for i, s in enumerate(slots)}
        for m in members:
            m.result = outs[index[_slot(m)[0]]]

    def _resolve_stream(self):
        members = self.members
        if (len(members) == 1
                and members[0].name in _streamlib._STAT_NAMES):
            # standalone resolution: the exact pre-fusion streamed
            # terminal (same slab/merge/finalise programs and keys)
            m = members[0]
            out = _streamlib.execute(
                None, _streamlib.stat_terminal(m.name, m.ddof),
                source=self.source)
            m.result = out.tojax()
            return
        specs = tuple((m.name, m.ddof) for m in members)
        outs = _streamlib.execute(None, _streamlib._Multi(specs),
                                  source=self.source)
        if len(members) > 1:
            _engine.record_fused_stats(len(members))
        for m, out in zip(members, outs):
            m.result = out


def _record_one_pass(group):
    """Count a dispatched chain group's ``var``/``std`` members that
    were traced in the one-pass form (``tpu/moments.py``: real floating
    values; the ``accumulate=`` float modes cast such values and keep
    the form)."""
    n = sum(m.name in ("var", "std") for m in group.members)
    if n and _onepass.one_pass(group.in_aval.dtype):
        _engine.record_one_pass_moments(n)


def _new_split(split, axes, keepdims):
    nkeys = sum(1 for a in axes if a < split)
    return split if keepdims else split - nkeys


def _chain_stat_exprs(data, funcs, split, slots, mode, mesh):
    """The UNCONSTRAINED per-slot reduction expressions over one chain
    input — the shared body of the fused multi-stat program above AND
    the serve layer's batched (vmapped) program
    (``bolt_tpu/tpu/batched.py``): one traced arithmetic, so a batched
    lane computes bit-identically to its standalone dispatch.  The
    caller applies the per-slot sharding constraint."""
    from bolt_tpu.tpu.array import _chain_apply
    mapped = _chain_apply(funcs, split, data)
    return tuple(_stat_expr(mapped, name, axes, keepdims, ddof, mode, mesh,
                            split)
                 for (name, axes, keepdims, ddof) in slots)


def _stat_expr(mapped, name, axes, keepdims, ddof, mode, mesh, split):
    """The reduction expression of ONE chain terminal, standalone
    (``mode=None``), fused or a batched lane: one traced arithmetic, so
    bit-identity of fused vs standalone holds (parity-locked in
    tests/test_multistat.py); ``mode`` casts the ADDITIVE terminals'
    values ("bf16" accumulates in f32 — the accumulate-in-f32 contract;
    "f32" is exact for f32 pipelines) and leaves order statistics
    untouched.  On a ``mesh`` of several devices the key axes (below
    ``split``) are sharded, and a ``var``/``std`` is told to take them
    whole in its pilot (``tpu/moments.py`` says why)."""
    op = _OPS[name]
    kwargs = {} if ddof is None else {"ddof": ddof}
    if name in ("var", "std") and mesh is not None and mesh.size > 1:
        kwargs["whole"] = tuple(a for a in axes if a < split)
    if mode == "int8":
        # the integer twin of bf16: int8 values, int32 accumulator (the
        # accumulate-in-i32 contract) — integer additive terminals of
        # integer pipelines only; everything else stays exact
        if name in _INT_ADDITIVE \
                and jnp.issubdtype(mapped.dtype, jnp.integer):
            return op(mapped.astype(jnp.int8), axis=axes,
                      dtype=jnp.int32, keepdims=keepdims, **kwargs)
    elif mode is not None and name in _ADDITIVE \
            and jnp.issubdtype(mapped.dtype, jnp.floating):
        if mode == "bf16":
            return op(mapped.astype(jnp.bfloat16), axis=axes,
                      dtype=jnp.float32, keepdims=keepdims, **kwargs)
        return op(mapped.astype(jnp.float32), axis=axes,
                  keepdims=keepdims, **kwargs)
    return op(mapped, axis=axes, keepdims=keepdims, **kwargs)


def _sub_program(shape, dtype, mesh):
    """``max − min`` for a ``ptp`` member — exactly ``jnp.ptp``'s own
    arithmetic, as one tiny cached program shared by every ptp of this
    geometry."""
    key = ("multi-stat-sub", tuple(shape), str(dtype), mesh)

    def build():
        return jax.jit(jnp.subtract)
    return _cached_jit(key, build)


# ---------------------------------------------------------------------
# handle creation (the lazy door _stat calls first)
# ---------------------------------------------------------------------

def defer_stat(arr, axis, name, keepdims, ddof):
    """Create (or join) a lazy :class:`PendingStat` for ``arr``'s
    ``name`` terminal; returns the pending result array, or
    NotImplemented when this spec must take the eager path (non-lazy
    name, consumed source without a live group, a geometry the fused
    machinery does not serve)."""
    if name not in LAZY_NAMES:
        return NotImplemented
    g = arr._stat_group
    if g is not None and g.dispatched:
        g = arr._stat_group = None
    if g is not None and not arr._donated and (
            (g.kind == "stream" and arr._stream is None)
            or (g.kind == "fpending" and arr._fpending is None)
            or (g.kind == "chain" and g.funcs and arr._chain is None)):
        # the source materialised since the group formed: new terminals
        # must compute from the CONCRETE data, not re-run the recorded
        # chain/filter/stream (a one-shot iterator could not stream
        # again anyway, and re-applying a map chain would silently
        # double the one-pass cost model); the old group's own members
        # still resolve from their recorded source.  Donated sources
        # have no other state — they keep joining their group.
        g = None
    if g is not None:
        h = g.try_join(axis, name, keepdims, ddof)
        if h is not NotImplemented:
            return _wrap(arr, g, h)
        if arr._donated:
            return NotImplemented     # consumed; eager path raises guard
        # live source, spec ineligible for the existing group: eager
        return NotImplemented
    if arr._donated:
        return NotImplemented
    g = _new_group(arr, axis, name, keepdims, ddof)
    if g is NotImplemented:
        return NotImplemented
    arr._stat_group = g
    return _wrap(arr, g, g.members[0])


def _wrap(arr, group, handle):
    from bolt_tpu.tpu.array import BoltArrayTPU
    out = BoltArrayTPU(None, handle.new_split, group.mesh)
    out._aval = handle.aval
    out._spending = handle
    return out


def _new_group(arr, axis, name, keepdims, ddof):
    from bolt_tpu.tpu.array import _chain_donate_ok
    mesh = arr._mesh
    if arr._stream is not None and _streamlib.has_swap(arr._stream):
        # a recorded swap resolves BEFORE the group forms (ISSUE 18):
        # the two-phase shuffle re-seats the array on a swap-free
        # source (or on concrete data if the shuffle fell back to
        # materialise), and the group machinery below sees only
        # geometry it already serves
        _streamlib._swap_resolved(arr)
    if arr._stream is not None:
        g = _StatGroup("stream", mesh, arr._stream.split,
                       source=arr._stream)
        if g.try_join(axis, name, keepdims, ddof) is NotImplemented:
            return NotImplemented
        return g
    if arr._fpending is not None:
        donate = _chain_donate_ok(arr._fpending)     # [0] is the base
        g = _StatGroup("fpending", mesh, 1, fpending=arr._fpending,
                       donate=donate)
        if g.try_join(axis, name, keepdims, ddof) is NotImplemented:
            return NotImplemented
        if donate:
            # today's semantics, kept eager: the first donating
            # terminal consumes the source; siblings join THIS group
            # (one donate serves every member)
            arr._consume_donated("filter().%s()" % name)
        return g
    # standard chain / concrete base.  The donation decision runs with
    # the standalone terminals' exact reference pattern (attribute
    # access straight into the call — the ownership test is
    # refcount-based)
    donate = arr.deferred and arr._donatable()
    base, funcs = arr._chain_parts()
    g = _StatGroup("chain", mesh, arr._split, base=base, funcs=funcs,
                   donate=donate,
                   in_aval=jax.ShapeDtypeStruct(tuple(arr._aval.shape),
                                                arr._aval.dtype))
    if g.try_join(axis, name, keepdims, ddof) is NotImplemented:
        return NotImplemented
    if donate:
        arr._consume_donated("%s()" % name)
    return g


def _chain_member(g, name, axis, keepdims, ddof):
    from bolt_tpu.tpu.array import _cached_eval_shape
    shape = tuple(g.in_aval.shape)
    split = g.split
    if axis is None:
        axes = tuple(range(split)) if split else tuple(range(len(shape)))
    else:
        axes = tuple(sorted(tupleize(axis)))
        inshape(shape, axes)
    if name in ("min", "max", "ptp") \
            and prod([shape[a] for a in axes]) == 0:
        return NotImplemented          # zero-size: eager raise contract
    kwargs = {} if ddof is None else {"ddof": ddof}
    aval = _cached_eval_shape(
        ("stat-aval", name, shape, str(g.in_aval.dtype), axes, keepdims,
         ddof),
        lambda: jax.eval_shape(
            lambda x: _OPS[name](x, axis=axes, keepdims=keepdims,
                                 **kwargs), g.in_aval))
    return PendingStat(g, name, axes, keepdims, ddof, aval,
                       _new_split(split, axes, keepdims))


def _fpending_member(g, name, axis, keepdims, ddof):
    n, vshape, vdtype = g.fpending.n, g.fpending.out.shape, \
        g.fpending.out.dtype
    if name not in _FPENDING_LAZY:
        return NotImplemented
    ndim = 1 + len(vshape)
    if axis is None:
        axes = (0,)                    # the flat key axis (split=1)
    else:
        axes = tuple(sorted(tupleize(axis)))
        for a in axes:
            if not 0 <= a < ndim:
                return NotImplemented  # let the eager path reject
    if 0 not in axes:
        return NotImplemented
    vdtype = np.dtype(vdtype)
    if name in ("var", "std") and np.issubdtype(vdtype,
                                                np.complexfloating):
        return NotImplemented
    ref = _OPS[name]
    kwargs = {} if ddof is None else {"ddof": ddof}
    aval = jax.eval_shape(
        lambda x: ref(x, axis=axes, keepdims=keepdims, **kwargs),
        jax.ShapeDtypeStruct((n,) + tuple(vshape), vdtype))
    return PendingStat(g, name, axes, keepdims, ddof, aval,
                       1 if keepdims else 0)


def _stream_member(g, name, axis, keepdims, ddof):
    st = _streamlib.result_state(g.source)
    if name not in _STREAM_LAZY or keepdims or st.n == 0:
        return NotImplemented
    if axis is not None:
        if tuple(sorted(tupleize(axis))) != tuple(range(st.split)):
            return NotImplemented
    if st.pred is not None and name in ("min", "max", "ptp"):
        # zero survivors would need the materialised error contract
        return NotImplemented
    if name in ("mean", "var", "std") and np.issubdtype(
            st.dtype, np.complexfloating):
        return NotImplemented          # mirror the fused-filter gate
    probe = jax.ShapeDtypeStruct((max(st.n, 1),) + tuple(st.vshape),
                                 st.dtype)
    kwargs = {} if ddof is None else {"ddof": ddof}
    aval = jax.eval_shape(
        lambda x: _OPS[name](x, axis=0, **kwargs), probe)
    return PendingStat(g, name, tuple(range(st.split)), False, ddof,
                       aval, 0)


def defer_reduce(arr, func, axes, keepdims):
    """Lazy door for ``reduce(func)`` — armed ONLY while a
    batching-enabled serving layer is active (``bolt_tpu.serve``
    ``Server(batching=...)`` arms ``bolt_tpu/tpu/batched.py``): a
    full-key-axis reduce over a plain chain/concrete source defers as a
    singleton pending-handle group so the serve scheduler can coalesce
    same-shape requests into ONE batched dispatch.  Standalone
    resolution is the EXACT eager program (same key, same traced tree),
    so a deferred handle read outside any batch is byte-for-byte the
    eager terminal.  Returns ``NotImplemented`` (→ the eager path) when
    the door is unarmed or the geometry does not fit: misaligned axes,
    streams/filters/pending compactions, donating chains (donation
    semantics stay eager), non-traceable reducers, or a reducer whose
    output drifts from the value shape (the eager call-time error
    contract is preserved)."""
    import sys as _sys
    bt = _sys.modules.get("bolt_tpu.tpu.batched")
    if bt is None or not bt.armed():
        return NotImplemented
    if (arr._donated or arr._stream is not None
            or arr._fpending is not None or arr._pending is not None
            or arr._stat_group is not None):
        return NotImplemented
    split = arr._split
    if split == 0 or tuple(axes) != tuple(range(split)):
        return NotImplemented
    shape = tuple(arr._aval.shape)
    n = prod(shape[:split])
    if n == 0:
        return NotImplemented          # eager empty-reduce raise contract
    vshape = shape[split:]
    dtype = arr._aval.dtype
    from bolt_tpu.tpu.array import _TRACE_ERRORS, _cached_eval_shape
    vaval = jax.ShapeDtypeStruct(vshape, dtype)
    try:
        oav = _cached_eval_shape(
            ("reduce", func, vshape, str(vaval.dtype)),
            lambda: jax.eval_shape(func, vaval, vaval))
    except _TRACE_ERRORS:
        return NotImplemented          # host-fallback path resolves
    if tuple(oav.shape) != tuple(vshape):
        return NotImplemented          # eager call-time ValueError
    if arr.deferred and arr._donatable():
        return NotImplemented          # keep the donating eager terminal
    base, funcs = arr._chain_parts()
    g = _StatGroup("chain", arr._mesh, split, base=base, funcs=funcs,
                   donate=False,
                   in_aval=jax.ShapeDtypeStruct(shape, dtype))
    g.rfunc = func
    new_split = split if keepdims else 0
    aval = jax.ShapeDtypeStruct(
        ((1,) * split + tuple(vshape)) if keepdims else tuple(vshape),
        oav.dtype)
    m = PendingStat(g, "reduce", tuple(axes), keepdims, None, aval,
                    new_split)
    g.members.append(m)
    return _wrap(arr, g, m)


# ---------------------------------------------------------------------
# the public multi-output terminal
# ---------------------------------------------------------------------

def compute(*stats, accumulate=None):
    """Resolve pending statistics with as few passes as possible::

        s, v, lo, hi = bolt.compute(a.sum(), a.var(), a.min(), a.max())

    Handles sharing one source (the same deferred chain, deferred
    filter, or out-of-core stream) dispatch ONE fused tuple program —
    map/filter stages applied once, one read of the data for the whole
    group, each result bit-identical to its standalone terminal.
    Mixed sources fall back per group; already-concrete inputs (any
    backend) pass through untouched.  Returns the inputs in argument
    order (a single input comes back bare).

    ``accumulate`` opts the group's additive reductions into the
    reduced-precision path ("bf16" values with f32 accumulation, or
    "f32"); default ``None`` is bit-exact.  See
    :func:`bolt_tpu._precision.accumulate` for the scoped form."""
    if not stats:
        raise TypeError("compute() needs at least one statistic")
    seen, groups = set(), []
    for s in stats:
        h = getattr(s, "_spending", None)
        if h is not None and h.result is None:
            if id(h.group) not in seen:
                seen.add(id(h.group))
                groups.append(h.group)
    for g in groups:
        g.resolve(accumulate)
    if accumulate is not None and not groups:
        _precision._check_accumulate(accumulate)   # validate even if moot
    return stats[0] if len(stats) == 1 else tuple(stats)


def fluent_stats(arr, names, axis=None, accumulate=None):
    """``a.stats("sum", "var", "min")`` — the fluent fused multi-stat:
    one pending handle per name (each exactly the standalone method's
    spec), resolved together through :func:`compute`, returned as an
    ordered ``{name: value-shaped array}`` dict."""
    for n in names:
        if n not in LAZY_NAMES:
            raise ValueError(
                "unknown statistic %r; choose from %s"
                % (n, ", ".join(LAZY_NAMES)))
    if arr._stream is not None and any(n not in _STREAM_LAZY
                                       for n in names):
        # a name with no streamed fold (prod/all/any) would materialise
        # the source MID-LIST — consuming a one-shot iterator out from
        # under the streamed siblings (and double-ingesting re-iterable
        # sources).  Materialise ONCE up front instead: every name then
        # computes from the concrete data as one fused chain group.
        arr.cache()
    handles = [getattr(arr, n)(axis=axis) for n in names]
    compute(*handles, accumulate=accumulate)
    return OrderedDict(zip(names, handles))
