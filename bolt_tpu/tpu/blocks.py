"""The rule behind the record-blocked lowering of a deferred map chain.

``map`` lowers a chain as a nested ``vmap`` over the whole array in ONE
program.  A record function that XLA fuses into an element-wise loop (every
``v + 1``, a product of two columns, a corner mean) then costs no
temporaries at all; one with a record-sized temporary that is NOT fused away
(a sort, an FFT, a cumulative sum, the loop of counting passes by which
``ops.normalize`` selects a percentile) costs temporaries as large as the
ARRAY, and a resident array over a third of HBM can take no such function.
Compiled for the v5e at ``(512, 512, 10240)`` float32 (10.74 GB): a
percentile by ``jnp.percentile``'s sort asks for 20.00 G of 15.75 G,
``jnp.fft.rfft`` of every record, as ``ops.fourier`` took it until PR 44,
for 40.00 G (PERF.md, PR 36; since PR 37 the percentile of a
record that long is selected, and held the record's image of keys where
the sort held its copy; since PR 40 the selection is the primitive
``percentile_select``, whose Mosaic kernel holds a tile's image in VMEM
(since PR 47 it reads the block from the array where the selection is a
run's first map), and whose fallback is the loop of passes with the
image as before: the record's jaxpr shows neither, only the primitive
and its 4 bytes a record, so the rule walks the fallback the primitive
names, as a call of it).

The rule, static and with no knob.  The runs of maps of a chain (between
its getitem windows) are traced ONCE, on ONE record's aval, to a jaxpr:

* a run is **heavy** when that jaxpr holds a primitive of :data:`HEAVY`
  (anywhere, nested calls included): primitives that XLA's loop fusion
  stops at and whose result, with the scratch beside it, is as large as
  their operand.  ``dot_general`` is not among them: ``detrend -> sum``
  compiles to no temporary at all at the size above (the thin product is a
  fusion's operand), and the Gram-shaped programs of ``ops/linalg.py`` are
  not per-record maps;
* a heavy run holds live, per record, the peak of its jaxpr's intermediates
  (:func:`record_live_bytes`: every intermediate counted as written out,
  which errs high for what fuses and LOW for what the jaxpr does not show:
  compiled for the v5e the ``tuning`` cell's whole chain took 0.6 of the
  estimate while its last map was an FFT, and that FFT alone 1.55 of its
  own, XLA's scratch; since PR 44 ``ops.fourier`` holds no heavy
  primitive, and the chain, heavy by its selection, takes 0.14, and
  nothing since PR 49, whose ``detrend -> fourier`` writes no residual),
  and all its records at once hold ``records x`` that;
* when that passes :data:`SHARE` of what the device has left after the
  chain's base and its result (``memory_stats()["bytes_limit"]`` less
  both), the run is lowered over blocks of whole records, the largest block
  (a multiple of 8 records, evened out over the count of blocks) whose live
  bytes stay inside that share.  A record that cannot fit alone is refused
  in words (:class:`MemoryError`) before XLA is asked.

A light run, and a heavy one that fits (any small array), lowers exactly as
it always did: same HLO, same engine keys.  Off the TPU there is no limit
(:func:`bolt_tpu.tpu.array._hbm_limit` is ``None``) and nothing is blocked.
"""

from functools import partial

import numpy as np

import jax
from jax.extend.core import Literal

# primitives that end a loop fusion and keep a result (and scratch) as
# large as their operand: sorts (jnp.sort/argsort/percentile/median),
# FFTs, cumulative scans, top-k, loops that carry per-record state, the
# selection of ops/select.py's percentile (a custom call that XLA fuses
# nothing into, or off the TPU the ``while`` of passes it stands for),
# and the dense decompositions
HEAVY = frozenset([
    "sort", "fft", "cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp",
    "top_k", "approx_top_k", "while", "scan", "percentile_select",
    "cholesky", "lu", "qr", "eigh", "eig", "svd", "schur",
    "triangular_solve", "tridiagonal", "tridiagonal_solve"])

# the share of what the device has left (after the chain's base and its
# result) that one program's blocked temporaries may take; the rest is
# for what else the caller holds, for XLA's own scratch (a sort's is not
# in the jaxpr) and for the estimate's error
SHARE = 0.25


def _nbytes(var):
    aval = getattr(var, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    try:
        item = np.dtype(dtype).itemsize
    except TypeError:                   # an extended dtype (a PRNG key)
        item = 4
    return int(np.prod(shape, dtype=np.int64)) * item


def _sub_jaxprs(eqn):
    for val in eqn.params.values():
        for v in (val if isinstance(val, (tuple, list)) else (val,)):
            inner = getattr(v, "jaxpr", v)      # ClosedJaxpr or Jaxpr
            if hasattr(inner, "eqns"):
                yield inner
    # a primitive whose executor is chosen at lowering shows nothing of
    # what it holds; one that names its ``fallback`` (ops/select.py's
    # ``percentile_select``) is counted as a call of it
    fallback = getattr(eqn.primitive, "fallback", None)
    if fallback is not None:
        yield jax.make_jaxpr(partial(fallback, **eqn.params))(
            *(v.aval for v in eqn.invars)).jaxpr


def _walk(jaxpr):
    """``(heavy, peak)`` of one jaxpr: whether it holds a primitive of
    :data:`HEAVY`, and the most bytes live at once if every intermediate
    were written out (its inputs included)."""
    last = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not isinstance(v, Literal):
                last[v] = i
    for v in jaxpr.outvars:
        if not isinstance(v, Literal):
            last[v] = len(jaxpr.eqns)
    live = sum(_nbytes(v) for v in jaxpr.invars)
    heavy, peak = False, live
    for i, eqn in enumerate(jaxpr.eqns):
        heavy = heavy or eqn.primitive.name in HEAVY
        inside = 0
        for sub in _sub_jaxprs(eqn):
            h, p = _walk(sub)
            heavy = heavy or h
            # the callee's inputs are the caller's live values already
            inside = max(inside, p - sum(_nbytes(v) for v in sub.invars))
        out = sum(_nbytes(v) for v in eqn.outvars)
        peak = max(peak, live + max(out, inside))
        live += out
        for v in set(v for v in eqn.invars
                     if not isinstance(v, Literal)):
            if last.get(v) == i:
                live -= _nbytes(v)
        for v in eqn.outvars:
            if v not in last:           # never read: dead at once
                live -= _nbytes(v)
    return heavy, peak


def record_live_bytes(fn, avals):
    """``(heavy, bytes, result aval)`` for the record function ``fn``
    traced on the avals of ONE record (and of its key indices, which a
    ``with_keys`` map reads)."""
    closed, out = jax.make_jaxpr(fn, return_shape=True)(*avals)
    return _walk(closed.jaxpr) + (out,)


def _fmt(nbytes):
    for unit, size in (("GB", 1e9), ("MB", 1e6), ("KB", 1e3)):
        if nbytes >= size:
            return "%.3g %s" % (nbytes / size, unit)
    return "%d bytes" % nbytes


def block_records(records, live, free):
    """Records a block of a heavy run whose ``records`` records hold
    ``live`` bytes each, on a device with ``free`` bytes left: ``None``
    where all of them fit :data:`SHARE` of it at once (today's lowering),
    else the block.  :class:`MemoryError` where one record does not fit
    what is left."""
    if live > free:
        raise MemoryError(
            "one record of this map chain holds ~%s live (a sort, an FFT "
            "or a scan keeps record-sized temporaries, and a run lowered "
            "over blocks writes its result out whole) but the device has "
            "%s left beside the chain's base and that result"
            % (_fmt(live), _fmt(max(free, 0))))
    budget = int(SHARE * free)
    if records * live <= budget:
        return None
    block = max(1, budget // live)
    if block >= records:
        return None
    if block >= 8:
        block -= block % 8
    count = -(-records // block)
    even = -(-records // count)         # the same count of blocks, evened
    if even >= 8:
        even = -(-even // 8) * 8
    return min(block, even)
