"""The ``mode='tpu'`` backend: a sharded ``jax.Array`` over a device mesh.

Structural replacement for ``bolt/spark/array.py :: BoltArraySpark``
(symbol-level citations throughout; the reference mount was empty — see
SURVEY.md §0).  Where the reference holds an RDD of
``(key-tuple, value-ndarray)`` records plus ``(shape, split, dtype,
ordered)``, this backend holds ONE global ``jax.Array`` carrying the full
logical shape (key axes leading) whose ``NamedSharding`` maps key axes onto
mesh axes — the key/value split IS the sharding spec, and the reference's
per-record Python hot loops, tree reductions and shuffles lower to a single
compiled XLA program per op:

=====================  ==========================================  =============================
reference call site    Spark mechanism                             lowering here
=====================  ==========================================  =============================
``map``                ``rdd.mapValues`` per-record Python loop    ``jit(vmap(func))`` w/ sharding
``reduce``             ``rdd.treeReduce``                          fixed-order pairwise tree, compiled
``mean/var/std``       ``rdd.aggregate(StatCounter...)``           ``jnp`` reductions / psum-Welford
``swap``               chunk → shuffle → unchunk                   transpose + reshard (all_to_all)
``toarray``            ``sortByKey().collect()``                   ``jax.device_get`` (ICI gather)
``cache``              RDD persistence                             arrays are device-resident already
=====================  ==========================================  =============================

**Laziness and fusion.**  Like the reference's RDDs (transformations are
lazy, actions execute), a traceable ``map`` is deferred: the array records a
chain of per-record functions over its parent and materialises on demand.
When an action (``reduce``, ``sum``/``mean``/…, ``toarray``) consumes a
deferred chain, the whole pipeline compiles to ONE fused XLA program —
``ones(10GB).map(f).sum()`` reads HBM once and never materialises the mapped
intermediate, which is what lets the 10 GB north-star workload fit and run
at HBM bandwidth.  ``cache()`` forces materialisation, exactly like the
reference pinning an RDD.

Arrays are always ordered (a global ``jax.Array`` has no record ordering to
lose — ``toarray`` is key-ordered by construction, matching the reference's
sorted collect).
"""

import sys
import threading
import warnings
from collections import OrderedDict
from functools import lru_cache, partial
from math import gcd
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from bolt_tpu import _lockdep
from bolt_tpu import engine as _engine
from bolt_tpu import stream as _streamlib
from bolt_tpu.base import BoltArray, HostFallbackWarning
from bolt_tpu.obs import trace as _obs
from bolt_tpu._compat import shard_map as _shard_map
from bolt_tpu.parallel import swapmerge as _swapmerge
from bolt_tpu.parallel.sharding import key_sharding, key_spec, spec_names
from bolt_tpu.tpu import blocks as _blocks
from bolt_tpu.tpu import fold as _fold
from bolt_tpu.tpu import moments as _onepass
from bolt_tpu.utils import (argpack, check_value_shape as _check_value_shape,
                            inshape, isreshapeable, istransposeable, prod,
                            tupleize, with_operands)

# Compiled-executable cache keyed on (operation, user function, static
# geometry): repeated calls with the same func/shape reuse the executable
# (the analog of Spark reusing a cached stage).  The table itself now
# lives in the central dispatch engine (bolt_tpu/engine.py) — one keyed
# AOT compile cache for every op family, with hit/miss/compile-time
# counters and optional on-disk persistence — and is aliased here for
# introspection: tests and tools scan its keys, and entries answer
# ``.lower`` like the jitted callables they wrap.  Closures in the cache
# deliberately capture only (mesh, geometry) — never an array — so
# cached entries pin no device memory.
_JIT_CACHE = _engine._CACHE
_JIT_CACHE_MAX = _engine.CACHE_MAX

# stable callables for scalar operator operands (see _scalar_fn)
_SCALAR_FN_CACHE = OrderedDict()

# binary ufuncs whose reduce/reduceat fold order provably matches numpy's
# (verified empirically np-vs-jnp over float/int operands).  numpy's
# generic non-reorderable reduce uses a buffer-striding order that is
# NEITHER a left nor right fold (np.power.reduce([2,3,2,1.5]) == 2**1.5,
# yet power.accumulate IS the left fold) — power/arctan2 and anything
# unverified reject loudly instead of returning silently different
# numbers.  accumulate (sequential by definition) and outer
# (order-free broadcast) need no gate.
_UFUNC_FOLD_SAFE = frozenset([
    "add", "subtract", "multiply", "divide", "true_divide",
    "floor_divide", "maximum", "minimum", "fmax", "fmin", "hypot",
    "logaddexp", "logaddexp2", "copysign", "nextafter", "heaviside",
    "fmod", "mod", "remainder", "float_power", "logical_and",
    "logical_or", "logical_xor", "bitwise_and", "bitwise_or",
    "bitwise_xor", "left_shift", "right_shift", "gcd", "lcm"])


@lru_cache(maxsize=256)
def _round_fn(decimals):
    def f(v):
        return jnp.round(v, decimals)
    f.__name__ = "round_%d" % decimals
    return f


@lru_cache(maxsize=64)
def _cast_fn(dtype):
    """Stable per-dtype cast callable (streamed ``map(dtype=...)``
    records it as a stage; a fresh lambda per call would defeat the
    per-slab executable cache)."""
    dt = np.dtype(dtype)

    def f(v):
        return v.astype(dt)
    f.__name__ = "astype_%s" % dt
    return f

# toarray's batched pending-filter fetch ships the FULL padded buffer to
# save one round-trip; above this size the worst case (few survivors) costs
# more in transfer than the round-trip saves, so resolve first instead
_PENDING_FETCH_MAX_BYTES = 32 << 20

# a filter whose survivors somebody needs as an ARRAY compacts them: the
# fused compaction program writes an n-row padded buffer — a full-size
# transient copy.  Above this input size that copy threatens HBM (a 10 GB
# filter would need 20 GB) and the two-phase path runs instead, whose
# gather output is only survivor-count rows.  Read where the buffer is
# built (_resolve_fpending); a terminal that folds the filter builds none
_FILTER_FUSED_MAX_BYTES = 1 << 30

# HBM-scale guards (VERDICT r2 weak-4).  Ops whose TRANSIENT working set
# is a multiple of the input (unique's sorted copy, topk's transposed
# copy, argsort's sort scratch) switch to bounded chunked paths above
# this size — the _FILTER_FUSED_MAX_BYTES pattern; ops whose OUTPUT is
# inherently input-sized (sort, cumsum, argsort) additionally check the
# total demand up front so a doomed program fails with a clear error
# before dispatch instead of an opaque XLA OOM.
_CHUNK_MAX_BYTES = 1 << 30

# device-memory limit resolution: explicit override > BOLT_HBM_BYTES env
# > the device's own report (memory_stats()["bytes_limit"])
_HBM_LIMIT_OVERRIDE = None


@lru_cache(maxsize=None)
def _device_hbm_bytes():
    """What the first local device reports as its memory limit, once per
    process: ``None`` off the TPU (host RAM is not budgeted).  A TPU that
    reports no ``bytes_limit`` is an error — guessing a capacity would
    arm the guards against the wrong chip."""
    dev = jax.local_devices()[0]
    if dev.platform != "tpu":
        return None
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    if not limit:
        raise RuntimeError(
            "%r reports no memory_stats()['bytes_limit']; set "
            "BOLT_HBM_BYTES to the chip's HBM size" % (dev,))
    return int(limit)


def _hbm_limit():
    """The device memory budget in bytes, or ``None`` where there is
    none to enforce.  The override and env var stay dynamic (tests flip
    them); the device's own report is the default."""
    import os
    if _HBM_LIMIT_OVERRIDE is not None:
        return int(_HBM_LIMIT_OVERRIDE)
    env = os.environ.get("BOLT_HBM_BYTES")
    if env:
        return int(env)
    return _device_hbm_bytes()


def slab_plan(shape, axis, in_bytes):
    """``(carry_axis, bounds)`` for slabbing an HBM-scale op along an
    axis other than its target ``axis`` — slabs of at most
    ``_CHUNK_MAX_BYTES`` with a shared recipe so the chunked paths
    (argsort, topk) cannot drift.  ``None`` when no other axis can
    carry the slabbing.  The LARGEST other axis carries it — a small
    first axis could not cut slabs fine enough to honour the bound."""
    cands = [a for a in range(len(shape)) if a != axis and shape[a] > 1]
    if not cands:
        return None
    cax = max(cands, key=lambda a: shape[a])
    nslabs = min(shape[cax], max(2, -(-in_bytes // _CHUNK_MAX_BYTES)))
    bounds = np.linspace(0, shape[cax], nslabs + 1).astype(int)
    pairs = [(int(s0), int(s1))
             for s0, s1 in zip(bounds[:-1], bounds[1:]) if s0 != s1]
    return cax, pairs


def _gather_bucket(count, cap):
    """Next power of two ≥ ``count`` (≥1, capped at ``cap``): the size
    band a dynamic survivor gather pads to so its executable is reused
    across calls whose counts drift within the band (VERDICT r3
    weak-5)."""
    b = 1
    while b < count:
        b <<= 1
    return min(b, cap)


def hbm_check(op, need_bytes, model):
    """Fail fast when ``op``'s estimated device demand ``need_bytes``
    cannot fit.  ``model`` is the human-readable memory model ("input +
    output + sort scratch") shown in the message — the documented
    per-op accounting."""
    limit = _hbm_limit()
    if limit is None or need_bytes <= limit:
        return
    raise MemoryError(
        "%s needs ~%.1f GB of device memory (%s) but the device holds "
        "%.1f GB" % (op, need_bytes / float(1 << 30), model,
                     limit / float(1 << 30)))


# multi-host toarray broadcasts each remote shard region in pieces of at
# most this many bytes, bounding the per-device HBM overhead of the
# cross-host collect at any array size (the full-array replication a
# plain allgather would do); pieces are host-sliced, so compiled-program
# count scales with distinct piece shapes, not array size
_GATHER_SLAB_BYTES = 256 << 20

# introspection for tests/smoke: piece accounting of the last
# _gather_multihost call ({"regions", "broadcasts", "max_piece_bytes"})
_LAST_GATHER_STATS = None


_LRU_LOCK = _lockdep.rlock("tpu.lru")


def _lru_get(cache, key, build):
    """Shared bounded-LRU policy for the aval/scalar-callable caches.
    NOTE: keys hold strong references to user callables, so a closure
    capturing a large array stays alive until its entry evicts — the
    values are the cheap part (executables/avals), the keys are what can
    pin memory in pathological many-distinct-closures sessions.
    Locked: concurrent tenants (bolt_tpu.serve) walk these OrderedDicts
    from many threads, and an unguarded move_to_end/popitem pair can
    corrupt the linkage; ``build`` runs under the lock — it is
    eval_shape-class host work, never an XLA compile."""
    with _LRU_LOCK:
        out = cache.get(key)
        if out is None:
            out = build()
            cache[key] = out
            if len(cache) > _JIT_CACHE_MAX:
                cache.popitem(last=False)
        else:
            cache.move_to_end(key)
        return out


def _cached_jit(key, builder):
    """Keyed executable dispatch through the central engine: compiled at
    most once per (key, argument signature), AOT, counted, and shared
    across every op family (``bolt_tpu.profile.instrument`` patches this
    name per module to count calls/builds)."""
    return _engine.get(key, builder)


def _chain_refs(chain):
    """``(references to the chain tuple, references to its base)`` as
    seen two calls below an ``owner.attr`` argument load — the shape
    both :func:`_chain_donate_ok` and its calibration share."""
    return sys.getrefcount(chain), sys.getrefcount(chain[0])


def _sole_owner_refs():
    """What :func:`_chain_refs` reads for a chain held by exactly ONE
    attribute whose base nothing else references.  Measured, not
    assumed: how many temporaries a call adds is the interpreter's
    business (CPython 3.12 moves argument references into the callee's
    frame where 3.10 copied them), and a constant calibrated for another
    interpreter either never donates or donates a shared buffer."""
    class _Owner:
        pass
    owner = _Owner()
    owner.chain = (object(), ())

    def ask(chain):                     # the _chain_donate_ok call shape
        return _chain_refs(chain)
    return ask(owner.chain)


_SOLE_CHAIN_REFS, _SOLE_BASE_REFS = _sole_owner_refs()


def _chain_donate_ok(chain):
    """True when a deferred chain's base buffer may be DONATED to the
    compiled program of a consuming terminal (reduce/_stat/chain
    materialisation/chunked map): the chain tuple must be the buffer's
    sole owner — no other live bolt array wraps it and no other chain
    shares it — and the buffer must be at least
    ``engine.donation_min_bytes()`` big (small interactive arrays stay
    readable after a terminal; HBM-scale one-shot chains get input+output
    overlap, halving their peak footprint).

    Ownership is decided by Python refcounts, twice over, against the
    counts :func:`_sole_owner_refs` measured at import: the chain TUPLE
    must be owned by exactly one wrapper (``_clone`` copies share the
    tuple — a shared tuple means another live array can still
    re-materialise from the base, so donation must not fire), and the
    BASE by that tuple alone.  Callers MUST pass ``owner._chain`` /
    ``owner._fpending`` directly as the argument (the calibrated shape)
    and before binding their own local to the base — an extra reference
    fails safe: no donation.  tests/test_engine.py pins both the shared
    and the unshared side.

    A chain that holds a :class:`_Window` is never donated, whatever the
    counts say: it is a view of a base that the array it was sliced from
    still owns (``chain[1]`` is the ``funcs`` of a ``_chain`` and of an
    ``_fpending`` alike)."""
    if _windows(chain[1]):
        return False            # a view of a base somebody else holds
    base = chain[0]
    floor = _engine.donation_min_bytes()
    if floor is None or base.nbytes < floor:
        return False
    if getattr(base, "is_deleted", lambda: False)():
        return False
    del base
    chain_refs, base_refs = _chain_refs(chain)
    return chain_refs <= _SOLE_CHAIN_REFS and base_refs <= _SOLE_BASE_REFS


# abstract-shape inference results, keyed on (func identity, input aval):
# jax.eval_shape re-traces the callable each call (~ms of host work),
# which at steady state was measured as the dominant per-dispatch
# framework overhead vs raw jax on small-array pipelines
_EVAL_CACHE = OrderedDict()


def _cached_eval_shape(key, thunk):
    return _lru_get(_EVAL_CACHE, key, thunk)


def _constrain(out, mesh, split):
    """Key-sharding constraint on a traced intermediate (shapes are static
    at trace time, so the spec is computable inside jit)."""
    return jax.lax.with_sharding_constraint(
        out, key_sharding(mesh, out.shape, split))


def _swap_glue(mesh, data, split, perm, new_split):
    """What to jit for the swap ``transpose(perm)`` of the resident
    ``data`` where it is an explicit exchange and a one-pass glue (a
    function of no arguments that makes it), or ``None`` where the swap
    keeps the transpose under a constraint (``parallel/swapmerge.py``
    says which and why).  Holds the array's shape, not the array."""
    p = _swapmerge.plan(mesh, data.shape, data.dtype, split, perm, new_split)
    if p is None or not _swapmerge.takes(p, mesh, data):
        return None
    return partial(_swapmerge.swapper, p, mesh, perm, data.shape,
                   data.dtype)


def _traceable(func):
    """Translate a NumPy ufunc to its jnp twin so reference user code
    (``b.reduce(np.maximum)``) traces on TPU; other callables pass through
    (``mode='tpu'`` requires jax-compatible callables — SURVEY §7 hard
    part 4 — with a host fallback as the escape hatch)."""
    if isinstance(func, np.ufunc):
        jf = getattr(jnp, func.__name__, None)
        if jf is not None:
            return jf
    return func




# Exceptions that mean "this callable cannot be traced by jax" — every
# tracer-concreteness failure derives from JAXTypeError (Concretization,
# TracerArray/Bool/IntegerConversion); NonConcreteBooleanIndexError is the
# one traceability failure raised under JAXIndexError instead.  Anything
# else out of eval_shape (plain TypeError from a shape mismatch,
# AttributeError from a typo, ValueError from user asserts) is a genuine
# bug in the user's callable and must surface, not silently reroute a
# 100×-slower host round-trip (VERDICT r1 weak-1).
_TRACE_ERRORS = (jax.errors.JAXTypeError, jax.errors.NonConcreteBooleanIndexError)


def _warn_fallback(op, func, exc):
    name = getattr(func, "__name__", repr(func))
    warnings.warn(
        "%s: callable %r is not jax-traceable (%s: %s); falling back to the "
        "local oracle via a device->host->device round-trip. Rewrite with "
        "the jax-compatible numpy-API subset to stay on device."
        % (op, name, type(exc).__name__,
           str(exc).splitlines()[0] if str(exc) else ""),
        HostFallbackWarning, stacklevel=3)


def _canon(dtype):
    """Canonicalise a dtype to what the backend can hold (f64→f32 unless
    x64 is enabled) — explicit and silent rather than warn-and-truncate."""
    return jax.dtypes.canonicalize_dtype(np.dtype(dtype))


def _check_live(arr):
    """Guard reads of a buffer that a ``swap(..., donate=True)`` may have
    consumed — deferred children can hold the donated parent's buffer."""
    if getattr(arr, "is_deleted", lambda: False)():
        raise RuntimeError(
            "the underlying device buffer was donated to a "
            "swap(..., donate=True) and is no longer readable")
    return arr


def _check_sort_kind(kind):
    """Shared ``kind`` validation for sort/argsort (numpy's exact
    rejection wording); returns True when numpy-identical tie order is
    guaranteed."""
    if kind not in (None, "quicksort", "heapsort", "mergesort", "stable"):
        raise ValueError("sort kind must be one of 'quick', 'heap', "
                         "or 'stable' (got %r)" % (kind,))
    return kind in ("stable", "mergesort")


class _WithKeysFunc:
    """Deferred-chain entry for ``map(func, with_keys=True)``: ``func``
    takes ``((k0, ..., kn-1), value)`` and needs the key indices
    alongside each block, so :func:`_chain_apply` expands it with traced
    ``unravel_index`` keys instead of a plain nested vmap.  Hash/eq
    delegate to the wrapped callable so two maps of the same func share
    compiled programs (the executable cache keys on chain tuples)."""

    __slots__ = ("func",)

    def __init__(self, func):
        self.func = func

    def __hash__(self):
        return hash((_WithKeysFunc, self.func))

    def __eq__(self, other):
        return type(other) is _WithKeysFunc and self.func == other.func


def _func_key(func):
    """What a program is keyed by for one chain entry: the entry itself,
    but a :class:`~bolt_tpu.utils.with_operands` by its function and its
    operands' avals (the program takes the arrays as arguments, so their
    values are no part of it)."""
    if isinstance(func, _WithKeysFunc):
        inner = _func_key(func.func)
        return func if inner is func.func else ("with_keys", inner)
    if isinstance(func, with_operands):
        return func.key()
    return func


def _funcs_key(funcs):
    return tuple(_func_key(f) for f in funcs)


def _operands_of(funcs):
    """Every array the ``with_operands`` entries of ``funcs`` name, flat,
    in chain order: the extra arguments of a program that lowers them."""
    out = []
    for func in funcs:
        if isinstance(func, _WithKeysFunc):
            func = func.func
        if isinstance(func, with_operands):
            out.extend(func.operands)
    return tuple(out)


def _bind_operands(funcs, flat):
    """``funcs`` with each ``with_operands`` entry replaced by a plain
    function over ITS share of ``flat`` (the program's traced arguments,
    in :func:`_operands_of` order) — called while the program is traced,
    so nothing of the entry's own arrays enters it."""
    flat = iter(flat)

    def bind(func):
        if isinstance(func, _WithKeysFunc):
            inner = bind(func.func)
            return func if inner is func.func else _WithKeysFunc(inner)
        if not isinstance(func, with_operands):
            return func
        mine = tuple(next(flat) for _ in func.operands)
        return lambda v, _f=func.func, _ops=mine: _f(v, *_ops)
    return tuple(bind(f) for f in funcs)


def _place_operands(flat, mesh):
    """The operands as the program is handed them: on the device,
    replicated over ``mesh`` (one placement a session whatever the count
    of slabs or blocks; an array that is there already passes through)."""
    if not flat:
        return ()
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    return tuple(
        x if isinstance(x, jax.Array) and x.sharding == rep
        else _streamlib.transfer(np.asarray(x), rep) for x in flat)


class _Window(NamedTuple):
    """Deferred-chain entry for a basic-slice ``getitem`` (slices of step
    1 and integers): ``starts``/``sizes`` cover the leading axes up to
    the last one the index touches (the axes after it pass whole),
    ``squeezed`` lists the axes an integer removed, and ``split`` is the
    number of key axes of what the window is applied TO — an integer on
    a key axis lowers the split for every entry after it (``kdrop``).
    :func:`_chain_apply` applies it as ``lax.slice`` plus the reshape
    for squeezed axes, with no sharding constraint, so whatever reads
    the chain traces the slice inside its own program.

    All static (tuples of ints) and hashed by value: the entry is part
    of every engine key that holds the chain's ``funcs``.  A chain that
    holds a window is a VIEW of a base somebody else holds and is never
    donated (:func:`_chain_donate_ok`)."""

    starts: tuple
    sizes: tuple
    squeezed: tuple
    split: int

    @property
    def kdrop(self):
        """Key axes this window removes."""
        return sum(1 for a in self.squeezed if a < self.split)

    def out_shape(self, shape):
        return tuple(s for a, s in enumerate(
            self.sizes + tuple(shape[len(self.sizes):]))
            if a not in self.squeezed)

    def apply(self, x):
        m = len(self.starts)
        out = jax.lax.slice(
            x, self.starts + (0,) * (x.ndim - m),
            tuple(a + z for a, z in zip(self.starts, self.sizes))
            + tuple(x.shape[m:]))
        if self.squeezed:
            out = out.reshape(self.out_shape(x.shape))
        return out

    def then(self, nxt):
        """This window followed by ``nxt`` (which indexes this one's
        output), as ONE window of this one's input."""
        starts, sizes, squeezed = [], [], list(self.squeezed)
        m1, m2 = len(self.starts), len(nxt.starts)
        a = out = 0
        while a < m1 or out < m2:
            s1, z1 = (self.starts[a], self.sizes[a]) if a < m1 else (0, None)
            if a in self.squeezed:
                starts.append(s1)
                sizes.append(1)
            else:
                if out < m2:
                    s1, z1 = s1 + nxt.starts[out], nxt.sizes[out]
                    if out in nxt.squeezed:
                        squeezed.append(a)
                starts.append(s1)
                sizes.append(z1)
                out += 1
            a += 1
        return _Window(tuple(starts), tuple(sizes), tuple(sorted(squeezed)),
                       self.split)


def _windows(funcs):
    """The ``getitem`` windows among a chain's entries."""
    return [f for f in funcs if type(f) is _Window]


def _reduce_tree_expr(data, func, funcs, split, n, vshape, keepdims):
    """The fixed-order pairwise-tree reduction expression — ONE traced
    body shared by the eager ``reduce`` program, the lazy reduce
    handle's standalone resolution (``bolt_tpu/tpu/multistat.py``) and
    the serve layer's batched (vmapped) program
    (``bolt_tpu/tpu/batched.py``), so every form computes bit-identical
    results.  Applies the deferred chain, folds the flattened records
    pairwise, validates the reducer's value shape, and restores
    ``keepdims`` key axes; the caller applies the sharding constraint."""
    mapped = _chain_apply(funcs, split, data)
    x = mapped.reshape((n,) + mapped.shape[split:])
    vfunc = jax.vmap(func)
    while x.shape[0] > 1:
        half = x.shape[0] // 2
        combined = vfunc(x[:half], x[half:2 * half])
        rem = x[2 * half:]
        x = jnp.concatenate([combined, rem], axis=0) if rem.shape[0] \
            else combined
    out = x[0]
    if out.shape != tuple(vshape):
        raise ValueError(
            "reduce produced shape %s, expected value shape %s"
            % (out.shape, tuple(vshape)))
    if keepdims:
        out = out.reshape((1,) * split + tuple(vshape))
    return out


class _Blocked(NamedTuple):
    """The LAST entry of a chain's ``funcs`` as a consumer holds them
    (:meth:`BoltArrayTPU._chain_parts`), when the rule of
    ``bolt_tpu/tpu/blocks.py`` says that a run of its maps cannot be
    lowered over the whole array at once: ``runs`` has one entry for
    each run of maps (the stretches between getitem windows), ``None``
    for a run that lowers as ever and ``(records, block)`` for one that
    :func:`_chain_apply` lowers over blocks of ``block`` whole records,
    inside the consumer's own program.  Static and hashed by value, so
    every engine key that holds the ``funcs`` tells a blocked program
    from an unblocked one; a chain whose record functions fuse carries
    no such entry and its keys and programs are what they always were.

    On a mesh of several devices ``mesh`` is that mesh: the blocks are
    taken inside each device's shard of the keys (the loop under
    ``shard_map``, no communication), and ``records`` counts the records
    of ONE shard.  ``None`` on one device."""

    runs: tuple
    mesh: object = None

    @property
    def blocks(self):
        """Blocks one dispatch runs."""
        return sum(-(-r[0] // r[1]) for r in self.runs if r)

    @property
    def block_records(self):
        """Records of the largest block."""
        return max(r[1] for r in self.runs if r)


def _chain_runs(funcs):
    """``funcs`` (no :class:`_Blocked` among them) as the sequence
    :func:`_chain_apply` walks: each :class:`_Window` alone, each stretch
    of maps between windows as one tuple."""
    out, run = [], []
    for func in funcs:
        if type(func) is _Window:
            if run:
                out.append(tuple(run))
                run = []
            out.append(func)
        else:
            run.append(func)
    if run:
        out.append(tuple(run))
    return out


def _record_fn(run):
    """The maps of one run as ONE function of a record (and its key
    indices, which only ``with_keys`` entries read)."""
    def one(v, *k):
        for func in run:
            if isinstance(func, _WithKeysFunc):
                v = func.func((tuple(k), v))
            else:
                v = func(v)
        return v
    return one


def _key_shards(mesh, shape, split):
    """Into how many shards the key sharding cuts each key axis of an
    array of ``shape``, and the mesh axes that do it: ``[(count,
    names)]``."""
    out = []
    for entry in tuple(key_spec(mesh, shape, split))[:split]:
        names = spec_names(entry)
        out.append((prod([mesh.shape[n] for n in names]), names))
    return out


def _sharded_blocked_run(run, split, x, block, mesh):
    """:func:`_blocked_run` inside each device's shard of the keys: the
    loop under ``shard_map`` over the key sharding, so a block is a
    block of one shard and nothing crosses between devices.  A
    ``with_keys`` map is handed the GLOBAL key indices (the shard's
    place along each mesh axis, times the shard's extent)."""
    shards = _key_shards(mesh, x.shape, split)
    local = tuple(k // c for k, (c, _) in zip(x.shape[:split], shards))

    def per_shard(part):
        offsets = []
        for extent, (_, names) in zip(local, shards):
            at = jnp.int32(0)
            for name in names:
                at = at * mesh.shape[name] + jax.lax.axis_index(name)
            offsets.append(at * extent)
        return _blocked_run(run, split, part, block, tuple(offsets))

    spec = key_spec(mesh, x.shape, split)
    keys = jax.sharding.PartitionSpec(*tuple(spec)[:split])
    return _shard_map(per_shard, mesh, in_specs=spec, out_specs=keys,
                      check_vma=False)(x)


def _blocked_run(run, split, x, block, offsets=None, found=None):
    """The maps of ``run`` over ``x`` in blocks of ``block`` whole
    records: a loop inside the program, whose temporaries are a block's
    and not the array's.  Each record's value is what the nested ``vmap``
    gives it (a record's arithmetic does not read its neighbours); where
    the count does not divide, the last block starts early and rewrites a
    few records with the values they already have.  ``offsets``: what to
    add to each key index a ``with_keys`` map is handed (a shard's place
    in the whole array).  ``found``: a list that takes an entry for every
    percentile the run selects on a block's rows themselves, which a
    program for one TPU device reads where the array lies
    (``ops/select.py :: block_of``; ``analysis.explain`` asks)."""
    from bolt_tpu.ops.select import block_of
    kshape = x.shape[:split]
    n = prod(kshape)
    block = min(block, n)
    flat = x.reshape((n,) + x.shape[split:])
    one = _record_fn(run)
    keyed = any(isinstance(f, _WithKeysFunc) for f in run)

    def piece(start):
        rows = jax.lax.dynamic_slice_in_dim(flat, start, block, axis=0)
        # every block starts at a multiple of the block or ends with the
        # array
        with block_of(rows, flat, start, gcd(block, n), found):
            if not keyed:
                return jax.vmap(one)(rows)
            keys = jnp.unravel_index(
                start + jnp.arange(block, dtype=jnp.int32), kshape)
            if offsets is not None:
                keys = tuple(k + o for k, o in zip(keys, offsets))
            return jax.vmap(one)(rows, *keys)

    aval = jax.eval_shape(piece, jax.ShapeDtypeStruct((), jnp.int32))

    def body(i, out):
        start = jnp.minimum(i.astype(jnp.int32) * block, n - block)
        return jax.lax.dynamic_update_slice_in_dim(out, piece(start), start,
                                                   axis=0)

    out = jax.lax.fori_loop(0, -(-n // block), body,
                            jnp.zeros((n,) + aval.shape[1:], aval.dtype))
    return out.reshape(kshape + out.shape[1:])


def _chain_apply(funcs, split, data, key0=None):
    """Apply a deferred map chain: each func nested-vmapped over the
    ``split`` leading key axes, in order; ``with_keys`` entries vmap
    over flattened records zipped with their (traced, int32 — matching
    the shape-inference avals) key tuples; :class:`_Window` entries
    slice in place.  ``split`` is the split of the chain's RESULT: a
    window that takes an integer on a key axis lowers it on the way.
    A trailing :class:`_Blocked` names the runs of maps that are lowered
    over blocks of records instead (:func:`_blocked_run`).  ``key0``:
    where ``data`` is a slab of a longer array, the first key of the slab
    on axis 0 (an int32 scalar, traced: one program for every slab),
    added to the keys a ``with_keys`` entry is handed."""
    if funcs and type(funcs[-1]) is _Blocked:
        return _chain_apply_blocked(funcs[:-1], split, data, funcs[-1])
    out = data
    split += sum(w.kdrop for w in _windows(funcs))
    for func in funcs:
        if type(func) is _Window:
            out = func.apply(out)
            split -= func.kdrop
            continue
        if isinstance(func, _WithKeysFunc):
            kshape = out.shape[:split]
            n = prod(kshape)
            flat = out.reshape((n,) + out.shape[split:])
            keys = jnp.unravel_index(jnp.arange(n, dtype=jnp.int32),
                                     kshape)
            if key0 is not None:
                keys = (keys[0] + key0,) + tuple(keys[1:])

            def one(v, *k, _f=func.func):
                return _f((tuple(k), v))

            res = jax.vmap(one)(flat, *keys)
            out = res.reshape(kshape + res.shape[1:])
            continue
        f = func
        for _ in range(split):
            f = jax.vmap(f)
        out = f(out)
    return out


def _chain_apply_blocked(funcs, split, data, marker):
    """:func:`_chain_apply` for a chain some of whose runs of maps are
    lowered over blocks, as its :class:`_Blocked` ``marker`` says."""
    _engine.record_blocked_chain()
    out = data
    split += sum(w.kdrop for w in _windows(funcs))
    plan = iter(marker.runs)
    for part in _chain_runs(funcs):
        if type(part) is _Window:
            out = part.apply(out)
            split -= part.kdrop
            continue
        blocked = next(plan)
        if blocked is None:
            out = _chain_apply(part, split, out)
        elif marker.mesh is None:
            out = _blocked_run(part, split, out, blocked[1])
        else:
            out = _sharded_blocked_run(part, split, out, blocked[1],
                                       marker.mesh)
    return out


def _plan_blocks(funcs, split, shape, dtype, free, mesh=None):
    """The rule of ``bolt_tpu/tpu/blocks.py`` over a chain: ``funcs``
    with a trailing :class:`_Blocked` where a run of its maps has to be
    lowered over blocks on a device with ``free`` bytes left beside (its
    shard of) the base and the result, else ``funcs`` as they are.
    ``mesh``: the mesh of several devices the keys are sharded over (a
    device then holds, and blocks, its shard's records).  Raises
    :class:`MemoryError` where one record's temporaries do not fit."""
    runs = []
    aval = jax.ShapeDtypeStruct(tuple(shape), dtype)
    at = split + sum(w.kdrop for w in _windows(funcs))
    for part in _chain_runs(funcs):
        if type(part) is _Window:
            aval = jax.eval_shape(part.apply, aval)
            at -= part.kdrop
            continue
        records = prod(aval.shape[:at])
        if mesh is not None:
            records //= prod([c for c, _ in
                              _key_shards(mesh, aval.shape, at)])
        rec = (jax.ShapeDtypeStruct(aval.shape[at:], aval.dtype),) \
            + (jax.ShapeDtypeStruct((), jnp.int32),) * at
        is_heavy, live, out = _blocks.record_live_bytes(_record_fn(part),
                                                        rec)
        block = _blocks.block_records(records, live, free) \
            if is_heavy else None
        runs.append(None if block is None else (records, block))
        aval = jax.ShapeDtypeStruct(aval.shape[:at] + out.shape, out.dtype)
    return funcs + (_Blocked(tuple(runs), mesh),) if any(runs) else funcs


def _span_funcs(funcs):
    """What a span says of the chain its program lowers: the count of
    its maps and windows and, where :class:`_Blocked` rides with them,
    the blocks a dispatch runs and the records of a block."""
    if funcs and type(funcs[-1]) is _Blocked:
        return {"funcs": len(funcs) - 1, "blocks": funcs[-1].blocks,
                "block_records": funcs[-1].block_records}
    return {"funcs": len(funcs)}


class _SharedNode:
    """What the deferred consumers of ONE deferred array hold in common.

    ``map`` on a deferred array copies its chain and appends, so two
    consumers of one deferred parent (``ops.fourier``'s coherence and
    phase over one map) are two chains from the same base, and forcing
    both would run the parent's maps twice.  The parent gets one of
    these where its first consumer is hung on it: ``nfuncs`` is how
    many of a consumer's ``funcs`` are the parent's own, ``split`` and
    ``aval`` are the parent's, ``consumers`` the live
    :class:`_Consumer` edges (their ids: a set, so that an edge coming
    or going on another thread is one atomic step), and ``kept`` the
    parent's result once a consumer's force has run it
    (:meth:`BoltArrayTPU._lower_from_shared`).  Nothing here holds the
    base, so the refcounts :func:`_chain_donate_ok` reads are what they
    were, and ``kept`` dies with the last handle that can reach the
    node: the parent array and its consumers."""

    __slots__ = ("nfuncs", "split", "aval", "consumers", "kept")

    def __init__(self, nfuncs, split, aval):
        self.nfuncs, self.split, self.aval = nfuncs, split, aval
        self.consumers = set()
        self.kept = None

    @property
    def nbytes(self):
        return prod(self.aval.shape) * np.dtype(self.aval.dtype).itemsize


class _Consumer:
    """One consumer hung on a deferred parent: held by the consumer and
    by every chain extended from it (``_links``), and counted by the
    parent's :class:`_SharedNode` while any of them lives, so the node
    counts the consumers that can still be forced and no others."""

    __slots__ = ("node",)

    def __init__(self, node):
        self.node = node
        node.consumers.add(id(self))

    def __del__(self):
        self.node.consumers.discard(id(self))


def _pred_mask(pred, flat):
    """Filter predicate as a bool mask over flattened records — the ONE
    coercion rule (`asarray(...,bool).reshape(())` per record) shared by
    the compaction program and both fused filter terminals, so the
    paths' semantics cannot diverge."""
    return jax.vmap(
        lambda v: jnp.asarray(pred(v), dtype=bool).reshape(()))(flat)


class _Filter(NamedTuple):
    """A deferred ``filter`` (the ``_fpending`` state): nothing has been
    dispatched, so a terminal can fold the predicate into its own pass.

    ``base``/``funcs``/``split`` are the map chain the filter was called
    on; ``pred`` reads its records, ``n`` of them of ``vshape``/``vdtype``;
    ``post`` are the record-wise maps called on the filter since (a map
    commutes with the selection, so it stays deferred) and ``out`` the
    aval of ONE record after them.  A tuple whose first two fields are a
    chain's, so :func:`_chain_donate_ok` reads it like one."""

    base: object
    funcs: tuple
    pred: object
    split: int
    vshape: tuple
    n: int
    vdtype: object
    post: tuple
    out: object

    def key(self):
        """What an engine key holds of this filter: everything but the
        buffer."""
        return (self.pred, self.funcs, self.post, self.base.shape,
                str(self.base.dtype), self.split)

    def records(self, data):
        """``(records, mask)`` traced over the base ``data``: the ``n``
        flattened records after ``post`` and the predicate's verdict on
        each — the ONE expression every consumer of a deferred filter
        starts from (the compaction, the fused stat and reduce terminals,
        the multi-stat group, the grouped fold)."""
        mapped = _chain_apply(self.funcs, self.split, data)
        mask = _pred_mask(self.pred, mapped.reshape(
            (self.n,) + mapped.shape[self.split:]))
        # the predicate read the maps in FRONT of the filter: only those
        # need a second application for the survivors' values
        return self.mapped(data, own=bool(self.funcs)), mask

    def mapped(self, data, own=True):
        """The ``n`` flattened records after ``post``; with ``own`` from
        an application of the maps that is this reader's own: every
        reader of the mapped records (the predicate, the survivors'
        values, a label function) applies the maps for itself behind a
        barrier that keeps XLA from merging the applications again.  ONE
        mapped array with two readers is written out whole (compiled for
        the v5e, ``map(v + 1).filter(p).sum(0)`` over 10.49 GB took a
        second 10.49 GB and did not fit), where a predicate that reads a
        corner of a record now maps that corner alone.  Without maps
        there is nothing to apply twice and no barrier: behind one, a
        column the predicate and the values both read is streamed from
        HBM twice (on the chip every column cut out of a table of thin
        records is a pass over the whole table, 13.4 ms of 9.6 GB:
        PERF.md, PR 30)."""
        if own and (self.funcs or self.post):
            data = jax.lax.optimization_barrier(data)
        mapped = _chain_apply(self.funcs, self.split, data)
        return _chain_apply(self.post, 1, mapped.reshape(
            (self.n,) + mapped.shape[self.split:]))

    def geometry(self):
        """This filter without its buffer, for a cached program's closure
        (a closure that held the base would pin it in the engine)."""
        return self._replace(base=None)


def _fold_identity_value(name, dtype):
    """The identity of the reduction ``name`` in ``dtype`` as a NumPy
    scalar (what a Mosaic kernel, which holds no array constants, folds
    a row onto: ``tpu/fold.py``)."""
    dtype = np.dtype(dtype)
    if name in ("sum", "prod", "any", "all"):
        ident = {"sum": 0, "prod": 1, "any": False, "all": True}[name]
    elif np.issubdtype(dtype, np.inexact):
        ident = -np.inf if name == "max" else np.inf
    elif dtype == np.bool_:
        ident = name == "min"
    else:
        info = np.iinfo(dtype)
        ident = info.min if name == "max" else info.max
    return dtype.type(ident)


def _fold_identity(name, dtype):
    """What a record that takes no part is folded onto: the identity of
    the reduction ``name`` in ``dtype`` (``where(kept, v, identity)``
    makes a dropped record, NaNs included, inert)."""
    return jnp.asarray(_fold_identity_value(name, dtype), dtype)


def _stat_dtype(name, axes, vshape, vdtype):
    """The dtype of the statistic ``name`` over ``axes`` of records
    ``vshape``/``vdtype``: jnp's own promotion rule on a 1-record probe,
    so fused and eager results always agree on dtype."""
    ref = {"sum": jnp.sum, "prod": jnp.prod, "any": jnp.any,
           "all": jnp.all, "max": jnp.max, "min": jnp.min,
           "mean": jnp.mean, "var": jnp.var, "std": jnp.std}[name]
    return jax.eval_shape(
        lambda x: ref(x, axis=axes), jax.ShapeDtypeStruct(
            (1,) + tuple(vshape), np.dtype(vdtype))).dtype


def _masked_stat_expr(name, flat, mask, mfull, axes, keepdims, ddof,
                      vshape, vdtype):
    """ONE masked reduction over the flattened filtered records — the
    arithmetic of the fused ``filter(...).sum()``-family terminals,
    factored out so the standalone filter-stat program and the fused
    multi-terminal program (bolt_tpu/tpu/multistat.py) trace the SAME
    expressions and cannot drift.  ``mean/var/std`` divide by the
    masked COUNT computed in the same pass (var as the one-pass moment
    form ``(Σx² − (Σx)²/n)/(n−ddof)``); the rest fold dropped records
    onto their identity."""
    op = {"sum": jnp.sum, "prod": jnp.prod, "any": jnp.any,
          "all": jnp.all, "max": jnp.max, "min": jnp.min}.get(name)
    out_dt = _stat_dtype(name, axes, vshape, vdtype)
    if name in ("sum", "prod", "any", "all", "max", "min"):
        v = jnp.where(mfull, flat, _fold_identity(name, flat.dtype))
        out = op(v, axis=axes, keepdims=keepdims)
        if out.dtype != out_dt:
            out = out.astype(out_dt)
        return out
    # element count each output slot divides by beyond the mask: the
    # reduced VALUE axes are dense (the mask only thins records)
    prodv = prod([vshape[a - 1] for a in axes if a > 0])
    cnt = jnp.sum(mask, dtype=jnp.int32)
    den = (cnt * prodv).astype(out_dt)
    xf = jnp.where(mfull, flat, jnp.zeros((), flat.dtype)).astype(out_dt)
    s1 = jnp.sum(xf, axis=axes, keepdims=keepdims)
    s2 = None if name == "mean" else jnp.sum(xf * xf, axis=axes,
                                             keepdims=keepdims)
    return _moments(name, s1, s2, den, ddof)


def _moments(name, s1, s2, den, ddof):
    """``mean``/``var``/``std`` from the survivors' sum ``s1``, their sum
    of squares ``s2`` and what each slot divides by, ``den``: the finish
    of :func:`_masked_stat_expr`, and of the same fold by the kernel
    (``tpu/fold.py``)."""
    if name == "mean":
        return s1 / den
    dd = 0.0 if ddof is None else ddof
    out = (s2 - s1 * s1 / den) / (den - dd)
    if name == "std":
        out = jnp.sqrt(out)
    return out


def _check_label(label, rec):
    """A label function on one record ``rec`` (an aval) gives one
    integer, or this says what it gave."""
    lab = _cached_eval_shape(
        ("segreduce-label", label, tuple(rec.shape), str(rec.dtype)),
        lambda: jax.eval_shape(label, rec))
    if prod(lab.shape) != 1 or not (
            np.issubdtype(lab.dtype, np.integer)
            or lab.dtype == np.bool_):
        raise ValueError(
            "a label function must return one integer per record; got "
            "shape %s dtype %s for value shape %s"
            % (tuple(lab.shape), lab.dtype, tuple(rec.shape)))


def _launch_filter_terminal(fn, base, op, donate):
    """Launch a program that folds a deferred filter into a terminal (a
    statistic, a reduce, the grouped fold): span ``array.filter_stat``
    around the launch, one more of ``filters_fused`` — a filter that
    ended with no buffer built for it."""
    with _obs.span("array.filter_stat", op=op, donate=donate):
        out = fn(_check_live(base))
    _engine.record_filter_fused()
    return out


_FOLDS = {"sum": jnp.add, "mean": jnp.add, "max": jnp.maximum,
          "min": jnp.minimum}


def _grouped_fold_expr(op, recs, mask, label, value, nseg, keyed=None):
    """ONE reduction of the records ``recs`` (``(n, ...)``, each kept
    where ``mask`` says, every one where it is ``None``) into ``nseg``
    groups: ``label(record)`` is the group a record joins (read from
    ``keyed``, the same records from an application of their maps that
    is the label's own: :meth:`_Filter.mapped`; ``recs`` where ``None``),
    ``value(record)`` (an array or a tuple of them; the record itself
    where ``None``) what it adds there.  Returns ``(folded, counts)``:
    for every leaf of the value its ``op`` a group, ``(nseg, ...)``, and
    the int32 count of records a group.

    The arithmetic of ``ops.segment_reduce`` by a label function,
    beside :func:`_masked_stat_expr`, which it is the grouped form of:
    a record outside a group (dropped by the predicate, or labelled
    outside ``[0, nseg)``) is folded onto the op's identity, so nothing
    record-sized is stored.  All groups of all leaves of one shape are
    ONE variadic reduce, and a leaf that is a scalar a record keeps a
    unit axis beside the record axis: that is the form XLA fuses into
    one pass over a table of thin records, which the chip lays out with
    the rows on the lanes (``f32[n,7]{0,1:T(8,128)}``) — a column cut
    out of it is ``(n, 1)``, and a value built by ``jnp.stack`` is
    written out first (compiled for the v5e: PERF.md, PR 30)."""
    n = recs.shape[0]
    gid = jax.vmap(lambda r: jnp.asarray(label(r)).astype(
        jnp.int32).reshape(()))(recs if keyed is None else keyed)
    if mask is not None:
        gid = jnp.where(mask, gid, nseg)
    vals = recs if value is None else jax.vmap(value)(recs)
    leaves, tree = jax.tree_util.tree_flatten(vals)
    if op == "mean":
        leaves = [lf if jnp.issubdtype(lf.dtype, jnp.inexact) else
                  lf.astype(jax.dtypes.canonicalize_dtype(np.float64))
                  for lf in leaves]
    shapes = [lf.shape[1:] for lf in leaves]
    leaves = [lf.reshape((n, 1)) if lf.ndim == 1 else lf for lf in leaves]
    hits = [gid == k for k in range(nseg)]
    # operands by shape; the counts ride with the scalar leaves
    plan = {}
    for i, lf in enumerate(leaves):
        ident = _fold_identity("sum" if op == "mean" else op, lf.dtype)
        for k, hit in enumerate(hits):
            plan.setdefault(lf.shape, []).append((
                (i, k), jnp.where(hit.reshape((n,) + (1,) * (lf.ndim - 1)),
                                  lf, ident), ident, _FOLDS[op]))
    for k, hit in enumerate(hits):
        plan.setdefault((n, 1), []).append((
            (None, k), hit.reshape((n, 1)).astype(jnp.int32),
            jnp.zeros((), jnp.int32), jnp.add))
    got = {}
    for rows in plan.values():
        folds = [r[3] for r in rows]

        def combine(a, b, folds=folds):
            return tuple(f(x, y) for f, x, y in zip(folds, a, b))
        outs = jax.lax.reduce(tuple(r[1] for r in rows),
                              tuple(r[2] for r in rows), combine, (0,))
        got.update((r[0], o) for r, o in zip(rows, outs))

    def groups(i, shape, dtype):
        if not nseg:
            return jnp.zeros((0,) + tuple(shape), dtype)
        return jnp.stack([got[i, k] for k in range(nseg)]).reshape(
            (nseg,) + tuple(shape))
    counts = groups(None, (), jnp.int32)
    folded = []
    for i, lf in enumerate(leaves):
        out = groups(i, shapes[i], lf.dtype)
        if op == "mean":
            out = out / jnp.maximum(counts, 1).astype(out.dtype).reshape(
                (nseg,) + (1,) * len(shapes[i]))
        folded.append(out)
    return jax.tree_util.tree_unflatten(tree, folded), counts


class BoltArrayTPU(BoltArray):
    """Distributed n-d array: key axes sharded over a TPU mesh, value axes
    local to each device."""

    _mode = "tpu"

    def __init__(self, data, split, mesh):
        if data is not None and (split < 0 or split > data.ndim):
            raise ValueError("split %d out of range for %d-d array" % (split, data.ndim))
        self._concrete = data
        self._split = int(split)
        self._mesh = mesh
        # deferred map chain: (base jax.Array, (func, ...)) or None
        self._chain = None
        # pending dynamic-shape result: (padded jax.Array, count device
        # scalar) from filter() — the survivor count has not been read on
        # host yet, so the logical shape is not known (see filter())
        self._pending = None
        # deferred filter, a :class:`_Filter` — no program has been
        # DISPATCHED yet, so a terminal (a statistic, a reduce, the
        # grouped fold) folds the predicate into its own pass and a
        # record-wise map joins it (see filter / map); any other
        # consumer resolves it into the _pending compaction form first
        self._fpending = None
        # lazy out-of-core stream source (bolt_tpu/stream.py): no device
        # data exists yet; reduction terminals run the double-buffered
        # streaming executor, everything else materialises via ._data
        self._stream = None
        # lazy stat terminal (bolt_tpu/tpu/multistat.py): this array IS
        # the not-yet-dispatched result of a sum()/var()/... terminal —
        # a PendingStat handle into a shared single-pass group; the
        # first read resolves the group (fused with any siblings)
        self._spending = None
        # the live (undispatched) stat group reading THIS array's
        # terminals — later sum()/var()/... calls join it, so N stats
        # on one source fuse into one pass (and one donate)
        self._stat_group = None
        self._donated = False
        # a deferred chain's place among the deferred arrays it was
        # mapped from: the :class:`_Consumer` edges up to each deferred
        # ancestor (outermost first), and the :class:`_SharedNode` that
        # this array's own consumers hang on (see map)
        self._links = ()
        self._node = None
        self._aval = None if data is None else jax.ShapeDtypeStruct(
            data.shape, data.dtype)

    @classmethod
    def _deferred(cls, base, funcs, split, mesh, aval):
        b = cls(None, split, mesh)
        b._chain = (base, tuple(funcs))
        b._aval = aval
        return b

    @classmethod
    def _streamed(cls, source):
        """Wrap a lazy out-of-core :class:`bolt_tpu.stream.StreamSource`:
        shape/dtype answer abstractly from the recorded stage chain, the
        streaming terminals (``sum``/``mean``/``var``/``std``/``reduce``)
        run the double-buffered executor, and any other consumer
        materialises transparently through ``._data`` (per-shard callback
        upload + the standard deferred/chunked/stacked programs)."""
        st = _streamlib.result_state(source)
        b = cls(None, st.split, source.mesh)
        b._stream = source
        b._aval = None if st.dynamic else jax.ShapeDtypeStruct(
            tuple(st.shape), st.dtype)
        return b

    # ------------------------------------------------------------------
    # properties (reference: ``BoltArraySpark`` properties, SURVEY §2.2)
    # ------------------------------------------------------------------

    @property
    def shape(self):
        if self._stream is not None and self._aval is None:
            # a streamed filter: the survivor count is unknowable
            # without running the pipeline — materialise (mirrors the
            # pending-filter count sync)
            self._data
        if self._fpending is not None:
            self._resolve_fpending()
        if self._pending is not None:
            self._resolve_pending()
        if self._aval is None:
            # a filter array consumed by a donating terminal: its count
            # was never synced, so the metadata is unknowable — raise
            # the named donation guard, not AttributeError (chain-
            # donated arrays keep answering from their recorded aval)
            self._guard_donated()
        return tuple(self._aval.shape)

    @property
    def dtype(self):
        if self._stream is not None and self._aval is None:
            # dtype is known abstractly even for a streamed filter
            return np.dtype(_streamlib.result_state(self._stream).dtype)
        if self._fpending is not None:
            # dtype is known without dispatching the filter program
            return np.dtype(self._fpending.out.dtype)
        if self._pending is not None:
            # dtype is known without syncing the survivor count
            return np.dtype(self._pending[0].dtype)
        if self._aval is None:
            self._guard_donated()   # consumed filter (see shape)
        return np.dtype(self._aval.dtype)

    @property
    def split(self):
        """Number of leading key axes (reference: ``BoltArraySpark.split``)."""
        return self._split

    @property
    def mesh(self):
        return self._mesh

    @property
    def deferred(self):
        """True while this array is an unmaterialised map chain (the
        analog of an RDD transformation not yet executed)."""
        return self._concrete is None and self._chain is not None

    @property
    def streaming(self):
        """True while this array is a lazy out-of-core stream source
        (``fromcallback``/``fromiter``): nothing is resident on device;
        reduction terminals stream it slab-by-slab, other consumers
        materialise it (which requires the full array to fit)."""
        return self._stream is not None

    @property
    def pending(self):
        """True while this array is an unresolved dynamic-shape result (a
        ``filter`` whose survivor count has not been synced to host): the
        compacted data lives on device, but the logical shape is unknown
        until one scalar fetch.  Reading ``shape`` (or any consumer)
        resolves it; ``toarray`` resolves it with a single batched
        transfer.  A still-DEFERRED filter (no program dispatched yet —
        reductions fuse the predicate into their own pass) reports
        pending too: its survivor count is equally unknown."""
        return self._pending is not None or self._fpending is not None

    def _consume_donated(self, op="a donating pipeline terminal",
                         granted=True):
        """Mark this array consumed by the donating operation ``op``: its
        chain base buffer was handed to XLA, so the chain can never be
        re-materialised — reads now raise the :meth:`_guard_donated`
        gate, whose message names ``op`` (so a use-after-donate error
        says WHICH terminal consumed the buffer).  ``granted=False``
        records the donation without counting it as an engine-policy
        grant (``swap(donate=True)`` is user-explicit, not granted)."""
        self._retire_chain()
        self._concrete = None
        self._fpending = None
        self._donated = op
        if granted:
            _engine.donation_granted()

    def _guard_donated(self):
        """THE donation gate: every read of this array's device state
        goes through here (via ``._data``); a buffer consumed by a
        donating terminal raises, naming the consuming operation.  The
        repo linter (BLT104) forbids ``._concrete`` reads that would
        skip this gate."""
        if self._donated:
            op = self._donated if isinstance(self._donated, str) \
                else "a donating pipeline terminal"
            raise RuntimeError(
                "this array's device buffer was donated to %s and can no "
                "longer be read (donation-aware terminals consume a "
                "sole-owned array; scope bolt_tpu.engine.donation(None) "
                "to keep sources readable, and bolt_tpu.analysis.check "
                "flags this before dispatch)" % op)

    def _resolve_fpending(self):
        """Build the deferred filter's survivors as an array: the only
        place a filter takes a buffer, and so the only place its size is
        asked.  Up to ``_FILTER_FUSED_MAX_BYTES`` of records ONE compiled
        pass (map chain + predicate + stable compaction + count) leaves a
        *pending* ``(padded, count)`` pair and the survivor count stays on
        the device until the shape is read; a sole-owned base donates its
        buffer to the program (the compaction buffer is input-sized).
        Above it that padded copy would not fit beside its input, and
        :meth:`_compact_two_phase` gathers the survivors alone."""
        fp = self._fpending
        if fp is None:
            return
        _engine.strict_guard(self, "filter() compaction")
        _engine.record_filter_compaction()
        nbytes = fp.n * prod(fp.vshape) * np.dtype(fp.vdtype).itemsize
        if nbytes > _FILTER_FUSED_MAX_BYTES:
            return self._compact_two_phase()
        del fp
        donate = _chain_donate_ok(self._fpending)   # [0] is the base
        fp = self._fpending
        base, geo, n, mesh = fp.base, fp.geometry(), fp.n, self._mesh

        def build():
            def fused(data):
                flat, mask = geo.records(data)
                # survivor indices in increasing (key) order, padded with 0s
                # beyond the count — rows past the count are garbage and are
                # sliced away at resolution
                perm = jnp.nonzero(mask, size=n, fill_value=0)[0]
                padded = jnp.take(flat, perm, axis=0)
                return (_constrain(padded, mesh, 1),
                        jnp.sum(mask, dtype=jnp.int32))
            return jax.jit(fused, donate_argnums=(0,) if donate else ())

        fn = _cached_jit(("filter-fused",) + fp.key() + (donate, mesh),
                         build)
        with _obs.span("array.filter", funcs=len(fp.funcs), donate=donate):
            padded, cnt = fn(_check_live(base))
        self._fpending = None
        self._pending = (padded, cnt)
        if donate:
            _engine.donation_granted()

    def _compact_two_phase(self):
        """The survivors of a filter too large for a padded compaction
        copy: compiled mask → host count sync → compiled gather into a
        BUCKET-sized buffer (next power of two ≥ count) — peak HBM is
        input + <2× survivors, never 2× input.  A map chain in front of
        the predicate is materialised first (the gather reads rows of an
        array that exists); the maps after it run on the survivors.

        Bucketing (VERDICT r3 weak-5): the gather executable is cached on
        the bucket, not the exact survivor count, so repeated HBM-scale
        filters with drifting counts reuse ONE compiled gather per
        power-of-two band instead of paying a fresh XLA compile each
        call.  The count is on the host already, so the count-exact slice
        (the only per-count program left, a trivial compile) runs here
        and the array comes out concrete."""
        fp = self._fpending
        mesh, n, pred, post = self._mesh, fp.n, fp.pred, fp.post
        data = fp.base if not fp.funcs else BoltArrayTPU._deferred(
            fp.base, fp.funcs, fp.split, mesh, None)._data
        rec = (n,) + tuple(fp.vshape)

        def build():
            def masker(data):
                return _pred_mask(pred, data.reshape(rec))
            return jax.jit(masker)

        with _obs.span("array.filter", funcs=len(fp.funcs), donate=False,
                       phases=2):
            mask = _cached_jit(("filter-mask", pred, data.shape,
                                str(data.dtype), mesh), build)(
                _check_live(data))
            idx = np.nonzero(np.asarray(jax.device_get(mask)))[0]
            cnt = len(idx)
            bucket = _gather_bucket(cnt, n)
            ids = np.zeros(bucket, dtype=np.int32)
            ids[:cnt] = idx                   # pad rows re-gather record 0;
                                              # they are sliced away below

            def gather_build():
                def gather(data, ids):
                    out = jnp.take(data.reshape(rec), ids, axis=0)
                    return _constrain(_chain_apply(post, 1, out), mesh, 1)
                return jax.jit(gather)

            out = _cached_jit(("filter-gather", post, data.shape,
                               str(data.dtype), bucket, mesh),
                              gather_build)(data, jnp.asarray(ids))
        self._fpending = None
        self._pending = (out, cnt)
        self._resolve_pending(count=cnt)      # count already synced: the
                                              # slice is eager, no fetch

    def _resolve_pending(self, count=None):
        """Slice the padded on-device buffer down to the true
        ``(n, *value_shape)``; syncs the survivor count (one scalar host
        fetch) unless the caller already knows it.  A still-deferred
        filter dispatches its compaction program first."""
        if self._fpending is not None:
            self._resolve_fpending()
        if self._pending is None:
            return
        padded, cnt = self._pending
        if count is None:
            count = int(jax.device_get(cnt))
        mesh = self._mesh

        def build():
            def sl(p):
                out = jax.lax.slice_in_dim(p, 0, count, axis=0)
                return _constrain(out, mesh, 1)
            return jax.jit(sl)

        fn = _cached_jit(("filter-slice", padded.shape, str(padded.dtype),
                          count, mesh), build)
        self._concrete = fn(padded)
        self._aval = jax.ShapeDtypeStruct(self._concrete.shape,
                                          self._concrete.dtype)
        self._pending = None

    def _resolve_spending(self):
        """Adopt the result of this array's lazy stat terminal,
        dispatching its group's single-pass program on first need (any
        pending siblings of the group resolve in the same dispatch —
        the read-side half of ``bolt.compute``)."""
        h = self._spending
        if h is None:
            return
        if h.result is None:
            h.group.resolve()
        self._concrete = h.result
        self._aval = jax.ShapeDtypeStruct(h.result.shape, h.result.dtype)
        self._spending = None

    @property
    def _data(self):
        """The concrete sharded ``jax.Array``; materialises a deferred
        chain on first access (one fused compiled program)."""
        self._guard_donated()
        if self._spending is not None:
            self._resolve_spending()
        if self._stream is not None:
            # materialise the lazy out-of-core source through the
            # STANDARD machinery (stream.materialize replays every
            # recorded stage via the normal deferred/chunked/stacked
            # programs), then adopt the result
            source = self._stream
            out = _streamlib.materialize(source)
            data = out._data            # resolves deferred/pending state
            # adopt only AFTER materialisation succeeded: a transient
            # source failure (an IOError mid-callback) must leave the
            # array still streaming so a retry re-raises the REAL error
            # instead of crashing on half-cleared state
            self._stream = None
            self._concrete = data
            self._split = out._split
            self._aval = jax.ShapeDtypeStruct(data.shape, data.dtype)
            return _check_live(self._concrete)
        if self._fpending is not None:
            self._resolve_fpending()
        if self._pending is not None:
            self._resolve_pending()
        if self._concrete is None:
            _engine.strict_guard(self, "map-chain materialisation")
            node = self._node
            if node is not None and node.kept is not None:
                # a consumer's force ran this chain already
                self._concrete = node.kept
                self._retire_chain()
                return _check_live(self._concrete)
            # chained-map terminal: a sole-owned base donates its buffer
            # to the materialising program (the output is input-sized, so
            # XLA aliases them — one buffer instead of two)
            donate = self._donatable()
            base, funcs = self._chain
            funcs = self._blocked(base, funcs)
            mesh, split = self._mesh, self._split

            def build():
                def run(d, *operands):
                    bound = _bind_operands(funcs, operands)
                    return _constrain(_chain_apply(bound, split, d), mesh, split)
                return jax.jit(run, donate_argnums=(0,) if donate else ())

            fn = _cached_jit(("chain", _funcs_key(funcs), base.shape,
                              str(base.dtype), split, donate, mesh), build)
            with _obs.span("array.chain", donate=donate,
                           bytes=int(base.nbytes), **_span_funcs(funcs)):
                self._concrete = fn(
                    _check_live(base),
                    *_place_operands(_operands_of(funcs), mesh))
            if node is not None and node.nbytes < base.nbytes:
                node.kept = self._concrete      # its consumers' base now
            self._retire_chain()
            if donate:
                _engine.donation_granted()
        return _check_live(self._concrete)

    def _retire_chain(self):
        """This array is a deferred chain no longer (it was materialised,
        consumed, or re-seated on another representation): it lets go of
        its place among the deferred arrays with the chain."""
        self._chain = None
        self._links = ()
        self._node = None

    def _shared_parent(self):
        """``(index into _links, node, consumers)`` of the deferred
        ancestor this chain is lowered FROM rather than through, or
        ``None``: the nearest one whose result is kept already, or that
        has more than one live consumer and a result smaller than the
        base (kept beside the base it never raises the peak past what a
        consumer's own result would; a map whose result is as large as
        its input is cheap to run again and dear to keep).  The ONE rule:
        the lowering asks it (:meth:`_lower_from_shared`) and
        ``analysis.explain`` says what it answers."""
        base = self._chain[0]
        for at in range(len(self._links) - 1, -1, -1):
            node = self._links[at].node
            kept = node.kept
            if kept is base:
                return None             # lowered from it already
            if kept is not None and kept.is_deleted():
                # the ancestor was forced and then gave its buffer away
                # (swap(donate=True)): the base still has everything
                kept = node.kept = None
            live = len(node.consumers)
            if kept is not None or (live > 1
                                    and node.nbytes < base.nbytes):
                return at, node, live
        return None

    def _parent_of(self, at):
        """The deferred ancestor behind ``_links[at]`` as an array of its
        own (the ancestor itself may be gone: ``ops.fourier`` drops the
        map its pair is picked from)."""
        node = self._links[at].node
        base, funcs = self._chain
        parent = BoltArrayTPU._deferred(base, funcs[:node.nfuncs],
                                        node.split, self._mesh, node.aval)
        parent._links = self._links[:at]
        return parent

    def _lower_from_shared(self):
        """Re-seat this deferred chain on the kept result of a shared
        ancestor (:meth:`_shared_parent`), running the ancestor's chain
        first where no consumer has yet: ONE program over the base for
        all of them, and this chain's own program reads the result.  The
        ancestor's program is the ``("chain", ...)`` program its own
        materialisation would run, never donating (its consumers hold
        the base); the kept result is never donated either, because this
        array keeps the edge to the node that holds it.  Called BEFORE a
        terminal decides whether to donate (:meth:`_donatable`): a chain
        that is the base's sole owner by then has no shared ancestor
        still to run (every other live consumer holds the base too), so
        it is lowered whole and donates, as ever, unless an ancestor's
        result is kept, and then it reads that and lets the base go."""
        found = self._links and self._shared_parent()
        if not found:
            return
        at, node, _ = found
        ran = node.kept is None
        if ran:
            node.kept = self._parent_of(at)._data
        _engine.record_shared_parent(ran)
        self._chain = (node.kept, self._chain[1][node.nfuncs:])
        # the edge to the node stays (it holds the kept result); a
        # consumer hung on this array from now on extends the new chain
        self._links = self._links[at:at + 1]
        self._node = None

    def _donatable(self):
        """:func:`_chain_donate_ok` of this deferred chain as the terminal
        about to consume it will find it: re-seated first on a shared
        ancestor's kept result where there is one
        (:meth:`_lower_from_shared`)."""
        self._lower_from_shared()
        return _chain_donate_ok(self._chain)

    def _chain_parts(self, consume=True):
        """``(base jax.Array, funcs)`` for fusing this array into a bigger
        program: the unmaterialised chain if deferred, else the concrete
        data with an empty chain.  A consumer that takes a chain with
        windows in it traces those slices inside its own program: they
        count as ``getitems_fused`` (``consume=False``: the chain is
        only being extended)."""
        if not self.deferred:
            return self._data, ()
        if consume:
            self._lower_from_shared()
        wins = consume and _windows(self._chain[1])
        if wins:
            _engine.record_getitems_fused(len(wins))
        if not consume:
            return self._chain
        base, funcs = self._chain
        return base, self._blocked(base, funcs)

    def _blocked(self, base, funcs):
        """:meth:`_block_plan` for a program that is about to be
        dispatched: its blocks are counted (``map_blocks``)."""
        marked = self._block_plan(base, funcs)
        if marked is not funcs:
            _engine.record_map_blocks(marked[-1].blocks)
        return marked

    def _block_plan(self, base, funcs):
        """``funcs`` as the program that lowers them takes them: with a
        trailing :class:`_Blocked` where the rule of ``tpu/blocks.py``
        says that a run of these maps holds more live than the device
        has left beside ``base`` and the chain's result (each counted
        whole: a blocked run writes its result out).  Where there is no
        limit (off the TPU) and for a chain that fuses it is ``funcs``
        itself, and nothing about the program changes.  On a mesh of
        several devices the bytes are a device's shard's.  Judged once
        per chain, geometry and limit (the eval cache, whose lock makes
        it safe under ``bolt_tpu.serve``'s threads): a dispatch pays a
        dictionary look-up."""
        limit = _hbm_limit()
        if limit is None or not funcs:
            return funcs
        aval, mesh, split = self._aval, self._mesh, self._split

        def judge():
            held = prod(base.sharding.shard_shape(base.shape)) \
                * base.dtype.itemsize
            made = prod(key_sharding(mesh, aval.shape, split).shard_shape(
                tuple(aval.shape))) * np.dtype(aval.dtype).itemsize
            planned = _plan_blocks(funcs, split, base.shape, base.dtype,
                                   int(limit - held - made),
                                   mesh if mesh.size > 1 else None)
            return planned[-1] if planned is not funcs else False
        marker = _cached_eval_shape(
            ("blocks", _funcs_key(funcs), split, base.shape, base.dtype,
             base.sharding,
             aval.shape, aval.dtype, limit, mesh), judge)
        return funcs + (marker,) if marker else funcs

    def _adopt_materialised(self, data):
        """Adopt ``data`` as this deferred chain's materialised result —
        the scatter half of a serve BATCHED dispatch
        (``bolt_tpu/tpu/batched.py``): the lane's output is exactly what
        the standalone ``("chain", ...)`` program would have produced,
        so the chain is simply retired."""
        self._concrete = data
        self._aval = jax.ShapeDtypeStruct(tuple(data.shape), data.dtype)
        self._retire_chain()

    def _adopt_resolved(self, res):
        """Adopt the result of resolving this array's swap stages
        (``stream.resolve_swaps`` — ISSUE 18): ``res`` is either still
        streaming (a resident shuffle re-streams its buckets, a spilled
        one streams them from disk) or concrete (the materialise
        fallback).  Either way it IS this array's value — same shape,
        dtype, split — so the identity simply re-seats on the resolved
        representation and every later terminal sees a swap-free
        source."""
        if res._stream is not None:
            self._stream = res._stream
            self._concrete = None
        else:
            self._stream = None
            self._concrete = res._concrete
            self._chain = res._chain
            self._links, self._node = res._links, res._node
        self._split = res._split
        self._aval = res._aval

    @property
    def keys(self):
        """Key-axis shape view (reference: ``bolt/spark/shapes.py :: Keys``)."""
        from bolt_tpu.tpu.shapes import Keys
        return Keys(self)

    @property
    def values(self):
        """Value-axis shape view (reference: ``bolt/spark/shapes.py :: Values``)."""
        from bolt_tpu.tpu.shapes import Values
        return Values(self)

    @property
    def _constructor(self):
        from bolt_tpu.tpu.construct import ConstructTPU
        return ConstructTPU

    def _wrap(self, data, split):
        return BoltArrayTPU(data, split, self._mesh)

    # ------------------------------------------------------------------
    # alignment (reference: ``bolt/spark/array.py :: BoltArraySpark._align``)
    # ------------------------------------------------------------------

    def _align(self, axes):
        """Ensure the requested ``axes`` are exactly the key axes, swapping
        if they are not — same algorithm as the reference: value axes named
        in ``axes`` move to keys, key axes missing from ``axes`` move to
        values."""
        inshape(self.shape, axes)
        tokeys = [a - self._split for a in axes if a >= self._split]
        tovalues = [a for a in range(self._split) if a not in axes]
        if tokeys or tovalues:
            return self.swap(tovalues, tokeys)
        return self

    # ------------------------------------------------------------------
    # functional operators
    # ------------------------------------------------------------------

    def map(self, func, axis=(0,), value_shape=None, dtype=None, with_keys=False):
        """Apply ``func`` to every key's value block as ONE compiled SPMD
        program: nested ``vmap`` over the key axes under ``jit`` with a key
        sharding on the output, so each device maps only its local blocks
        and no data moves (reference: ``BoltArraySpark.map`` →
        ``rdd.mapValues`` with a one-record job for shape inference; here
        shape inference is ``jax.eval_shape`` — SURVEY §3.2).

        Traceable maps are DEFERRED (lazy, like the reference's RDD
        transformations) and fuse with downstream maps/reductions; any
        materialising consumer compiles the whole chain at once.

        ``func`` must be jax-traceable in this mode (numpy-API subset);
        non-traceable callables fall back to a host round-trip through the
        local oracle, preserving semantics at the cost of a transfer.
        ``value_shape``/``dtype`` are accepted for signature parity and
        validated when given.
        """
        func = _traceable(func)
        axes = sorted(tupleize(axis))
        if self._fpending is not None and axes == [0] and not with_keys:
            out = self._map_filter(func, value_shape, dtype)
            if out is not None:
                return out
        if self._stream is not None and self._aval is None \
                and axes == [0] and not with_keys:
            # a streamed filter: the map joins the stages behind the
            # predicate, as it joins a resident filter's (no count
            # sync, nothing materialised)
            out = self._map_streamed_filter(func, value_shape, dtype)
            if out is not None:
                return out
        aligned = self._align(axes)
        split = aligned._split
        kshape = aligned.shape[:split]
        vshape = aligned.shape[split:]

        try:
            if with_keys:
                def infer_wk():
                    kavals = tuple(jax.ShapeDtypeStruct((), jnp.int32)
                                   for _ in range(split))
                    return jax.eval_shape(
                        lambda k, v: func((k, v)), kavals,
                        jax.ShapeDtypeStruct(vshape, aligned._aval.dtype))
                out_aval = _cached_eval_shape(
                    ("map-wk", _func_key(func), split, vshape,
                     str(aligned._aval.dtype)), infer_wk)
            else:
                out_aval = _cached_eval_shape(
                    ("map", _func_key(func), vshape,
                     str(aligned._aval.dtype)),
                    lambda: jax.eval_shape(
                        func,
                        jax.ShapeDtypeStruct(vshape, aligned._aval.dtype)))
        except _TRACE_ERRORS as exc:
            # non-traceable func: host fallback through the local oracle
            _warn_fallback("map", func, exc)
            local = aligned.tolocal().map(
                func, axis=tuple(range(split)), value_shape=value_shape,
                dtype=dtype, with_keys=with_keys)
            return self._constructor.array(
                local.toarray(), context=self._mesh, axis=tuple(range(split)))

        _check_value_shape(value_shape, tuple(out_aval.shape))

        mesh = self._mesh
        full_aval = jax.ShapeDtypeStruct(kshape + tuple(out_aval.shape),
                                         out_aval.dtype)

        if aligned._stream is not None:
            # streaming source (out-of-core): record the map as a
            # device-side stage — it fuses into the per-slab program.
            # A with_keys map is a KEYED stage: the slab program is
            # handed the slab's first key as an operand and adds it to
            # the slab-local keys.  (Where that cannot be, on a mesh of
            # several processes, it materialises below.)
            out = _streamlib.map_stage(
                aligned, _WithKeysFunc(func) if with_keys else func)
            if out is not NotImplemented:
                if dtype is not None and np.dtype(dtype) != np.dtype(
                        full_aval.dtype):
                    out = _streamlib.map_stage(out,
                                               _cast_fn(_canon(dtype)))
                return out

        # defer: extend the chain (or start one) without executing —
        # with_keys maps defer too (as _WithKeysFunc entries), so
        # map(f, with_keys=True).sum() is ONE fused program like any
        # other chain (VERDICT r2 weak-5)
        entry = _WithKeysFunc(func) if with_keys else func
        if aligned.deferred:
            base, funcs = aligned._chain
            out = BoltArrayTPU._deferred(base, funcs + (entry,), split,
                                         mesh, full_aval)
            # one more consumer of a deferred parent: counted on the
            # parent's node, which a force asks (_shared_parent)
            node = aligned._node
            if node is None:
                node = aligned._node = _SharedNode(len(funcs), split,
                                                   aligned._aval)
            out._links = aligned._links + (_Consumer(node),)
        else:
            out = BoltArrayTPU._deferred(aligned._data, (entry,), split,
                                         mesh, full_aval)
        if dtype is not None and np.dtype(dtype) != np.dtype(full_aval.dtype):
            return out.astype(dtype)
        return out

    def filter(self, func, axis=(0,), sort=False):
        """Dynamic-shape filter, fully on device: ONE fused compiled program
        applies any deferred map chain, evaluates the vmapped predicate,
        stably compacts the surviving records to the front of a padded
        ``(nkeys, *value_shape)`` buffer, and counts them — all without
        leaving the device.  The result is returned immediately in a
        *pending* state: the survivor count (the only thing XLA's static
        shapes cannot express) is synced lazily — one scalar fetch when the
        shape is first needed, or batched into ``toarray``'s transfer so a
        ``filter(...).toarray()`` pipeline pays a single host round-trip.

        Output records are re-keyed to a flat ``(n,)`` key space with
        ``split=1`` in original key order — the reference's re-key-to-linear
        semantics (``BoltArraySpark.filter``); the reference pays a Spark
        job at the same spot for shape inference (SURVEY §7 hard part 1).
        ``sort`` is accepted for parity; output is always ordered.

        NOTHING is dispatched here, at any size: the filter is recorded
        (``_fpending``).  A terminal that reduces the survivors away
        (``sum``/``mean``/…, ``reduce``, ``ops.segment_reduce`` by a label
        function) folds the predicate into its own ONE pass and builds no
        buffer, and a record-wise ``map`` in between stays recorded too,
        so ``b.filter(p).map(f).sum()`` reads the input once.  Over a
        stored table of thin records (``(rows, c)`` float32 or int32,
        ``c <= 8``) with element-wise functions that pass is the Mosaic
        kernel ``thin_fold`` in a program for one TPU device
        (``tpu/fold.py``, engine counter ``fold_kernel_programs``): it
        keeps more running sums than XLA's fusion, so a float answer's
        last digits differ from the fusion's.  Only a
        consumer that needs the survivors as an array compacts them
        (:meth:`_resolve_fpending`), and only there is the size asked:
        the padded compaction buffer is a full-size transient copy, so
        above ``_FILTER_FUSED_MAX_BYTES`` the two-phase
        mask→count→gather path runs instead, whose output is
        survivor-count rows only.
        """
        func = _traceable(func)
        axes = sorted(tupleize(axis))
        aligned = self._align(axes)
        split = aligned._split
        kshape = aligned.shape[:split]
        vshape = aligned.shape[split:]
        n = prod(kshape)
        mesh = self._mesh

        try:
            pred_aval = _cached_eval_shape(
                ("filter", func, vshape, str(aligned._aval.dtype)),
                lambda: jax.eval_shape(
                    func, jax.ShapeDtypeStruct(vshape, aligned._aval.dtype)))
        except _TRACE_ERRORS as exc:
            # non-traceable predicate: host fallback through the local oracle
            _warn_fallback("filter", func, exc)
            out = aligned.tolocal().filter(func, axis=tuple(range(split)))
            data = _streamlib.transfer(
                np.asarray(out), key_sharding(mesh, out.shape, 1))
            return self._wrap(data, 1)
        if prod(getattr(pred_aval, "shape", ())) != 1:
            raise ValueError(
                "filter predicate must return a scalar truth value per "
                "record; got shape %s for value shape %s"
                % (tuple(pred_aval.shape), vshape))

        if aligned._stream is not None:
            # streaming source: the predicate stays lazy (a trailing
            # stream stage); reduction terminals fold its mask into the
            # per-slab pass — out-of-core filter(...).sum() never
            # materialises anything input-sized
            return _streamlib.filter_stage(aligned, func)

        # DEFER: no program dispatches here.  A reduction terminal
        # (sum/mean/reduce/the grouped fold) folds the predicate into its
        # own pass — ONE read of HBM, no compaction buffer; any other
        # consumer builds the survivors (see _resolve_fpending).
        base, funcs = aligned._chain_parts()
        vdtype = np.dtype(aligned._aval.dtype)
        out = BoltArrayTPU(None, 1, mesh)
        out._fpending = _Filter(base, funcs, func, split, tuple(vshape), n,
                                vdtype, (),
                                jax.ShapeDtypeStruct(tuple(vshape), vdtype))
        return out

    def _map_filter(self, func, value_shape, dtype):
        """``map`` on a deferred filter: a record-wise map commutes with
        the selection, so it joins the filter's ``post`` maps and nothing
        runs (no compaction, no count sync).  ``None`` for a callable
        that does not trace: the caller resolves the filter and takes
        the host fallback, as before."""
        fp = self._fpending
        try:
            aval = _cached_eval_shape(
                ("map", func, tuple(fp.out.shape), str(fp.out.dtype)),
                lambda: jax.eval_shape(func, fp.out))
        except _TRACE_ERRORS:
            return None
        _check_value_shape(value_shape, tuple(aval.shape))
        post = fp.post + (func,)
        if dtype is not None and np.dtype(dtype) != np.dtype(aval.dtype):
            target = _canon(dtype)
            post += (_cast_fn(target),)
            aval = jax.ShapeDtypeStruct(aval.shape, target)
        out = BoltArrayTPU(None, 1, self._mesh)
        out._fpending = fp._replace(post=post, out=jax.ShapeDtypeStruct(
            tuple(aval.shape), aval.dtype))
        return out

    def _map_streamed_filter(self, func, value_shape, dtype):
        """``map`` on a streamed filter (``stream.post_map_stage``):
        :meth:`_map_filter` for a lazy out-of-core source.  ``None`` for
        a callable that does not trace."""
        out = _streamlib.post_map_stage(self, func)
        if out is None:
            return None
        st = _streamlib.result_state(out._stream)
        _check_value_shape(value_shape, tuple(st.vshape))
        if dtype is not None and np.dtype(dtype) != np.dtype(st.dtype):
            out = _streamlib.post_map_stage(out, _cast_fn(_canon(dtype)))
        return out

    def reduce(self, func, axis=(0,), keepdims=False):
        """Fixed-order pairwise tree reduction over the key axes, compiled:
        each round vmaps the binary ``func`` over half the records
        (log2(n) rounds, deterministic order — the reference's
        ``rdd.treeReduce`` has *unspecified* combine order, so this is
        stricter; SURVEY §7 hard part 2).  A deferred map chain on the
        input fuses into the same program (map→reduce reads HBM once).
        """
        func = _traceable(func)
        _engine.strict_guard(self, "reduce()")
        if self._fpending is not None:
            # deferred filter feeding the reduce: fold the predicate into
            # the pairwise tree — one fused HBM pass (see
            # _fused_filter_reduce; NotImplemented geometries resolve)
            out = self._fused_filter_reduce(func, axis, keepdims)
            if out is not NotImplemented:
                return out
        axes = sorted(tupleize(axis))
        if self._stream is not None:
            # lazy out-of-core source: stream the pairwise tree (per-slab
            # trees, cross-slab pairwise merges — fold order follows slab
            # boundaries, like the reference's treeReduce)
            out = _streamlib.maybe_reduce(self, func, tuple(axes), keepdims)
            if out is not NotImplemented:
                return out
        # lazy door while a batching-enabled serving layer is armed
        # (bolt_tpu/tpu/multistat.py): a full-key-axis reduce over a
        # plain chain defers as a pending handle so the serve scheduler
        # can coalesce same-shape requests into ONE batched dispatch;
        # standalone resolution reuses the EXACT eager program (same
        # engine key, same traced tree), so results and caching are
        # unchanged.  NotImplemented falls through to the eager path.
        from bolt_tpu.tpu import multistat as _ms
        out = _ms.defer_reduce(self, func, tuple(axes), keepdims)
        if out is not NotImplemented:
            return out
        aligned = self._align(axes)
        split = aligned._split
        kshape = aligned.shape[:split]
        vshape = aligned.shape[split:]
        n = prod(kshape)
        if n == 0:
            # same error contract as the local oracle (and functools.reduce)
            raise TypeError("reduce of an empty array with no initial value")
        mesh = self._mesh
        new_split = split if keepdims else 0

        vaval = jax.ShapeDtypeStruct(vshape, aligned._aval.dtype)
        try:
            _cached_eval_shape(
                ("reduce", func, vshape, str(vaval.dtype)),
                lambda: jax.eval_shape(func, vaval, vaval))
        except _TRACE_ERRORS as exc:
            # non-traceable reducer: host fallback through the local oracle
            _warn_fallback("reduce", func, exc)
            out = aligned.tolocal().reduce(
                func, axis=tuple(range(split)), keepdims=keepdims)
            data = _streamlib.transfer(
                np.asarray(out), key_sharding(mesh, out.shape, new_split))
            return self._wrap(data, new_split)

        # donation-aware terminal: consuming a sole-owned deferred chain
        # frees the parent buffer inside the reduction program (checked
        # BEFORE binding the base local — see _chain_donate_ok)
        donate = aligned.deferred and aligned._donatable()
        base, funcs = aligned._chain_parts()

        def build():
            def reducer(data):
                out = _reduce_tree_expr(data, func, funcs, split, n,
                                        vshape, keepdims)
                return _constrain(out, mesh, new_split)
            return jax.jit(reducer, donate_argnums=(0,) if donate else ())

        fn = _cached_jit(("reduce", func, funcs, base.shape, str(base.dtype),
                          split, keepdims, donate, mesh), build)
        with _obs.span("array.reduce", donate=donate, **_span_funcs(funcs)):
            out = self._wrap(fn(_check_live(base)), new_split)
        if donate:
            aligned._consume_donated("reduce()")
        return out

    # ------------------------------------------------------------------
    # statistics (reference: ``BoltArraySpark._stat/stats`` + StatCounter
    # aggregation — SURVEY §3.4; here they are single compiled XLA
    # reductions whose cross-device combine is the psum tree GSPMD inserts)
    # ------------------------------------------------------------------

    def _stat(self, axis, name, keepdims=False, ddof=None):
        _engine.strict_guard(self, "%s()" % name)
        # lazy door (bolt_tpu/tpu/multistat.py): the stat family defers
        # as a PendingStat handle — validation/strict/donation stay
        # eager here, only the dispatch moves to the first read, and
        # handles sharing this source fuse into ONE single-pass program
        # (bolt.compute / a.stats(...); single-pass with a var/std of
        # real floating data in it too: tpu/moments.py's shifted
        # moments, where jnp.var read twice).  NotImplemented falls through
        # to the eager paths (consumed sources, zero-size extrema,
        # geometries the fused machinery does not serve).
        from bolt_tpu.tpu import multistat as _ms
        out = _ms.defer_stat(self, axis, name, keepdims, ddof)
        if out is not NotImplemented:
            return out
        if self._stream is not None:
            # lazy out-of-core source: run the reduction as a streamed
            # double-buffered pipeline when the geometry allows (all key
            # axes, no keepdims); anything else materialises below
            out = _streamlib.maybe_stat(self, axis, name, keepdims, ddof)
            if out is not NotImplemented:
                return out
        if self._fpending is not None:
            # an unmaterialised filter feeding a reduction: fold the
            # predicate mask straight into the reduce — ONE fused HBM
            # pass, no compaction buffer (falls through to the resolving
            # path for geometries the fused program does not serve)
            out = self._fused_filter_stat(axis, name, keepdims, ddof)
            if out is not NotImplemented:
                return out
        if axis is None:
            axes = tuple(range(self._split)) if self._split else tuple(range(self.ndim))
        else:
            axes = tuple(sorted(tupleize(axis)))
            inshape(self.shape, axes)
        mesh = self._mesh
        split = self._split
        nkeys_reduced = sum(1 for a in axes if a < split)
        new_split = split if keepdims else split - nkeys_reduced

        # donation-aware terminal (see _chain_donate_ok: checked before
        # the base local exists)
        donate = self.deferred and self._donatable()
        base, funcs = self._chain_parts()

        def build():
            def stat(data):
                mapped = _chain_apply(funcs, split, data)
                out = _ms._stat_expr(mapped, name, axes, keepdims, ddof,
                                     None, mesh, split)
                return _constrain(out, mesh, new_split)
            return jax.jit(stat, donate_argnums=(0,) if donate else ())

        fn = _cached_jit(("stat", name, funcs, base.shape, str(base.dtype),
                          split, axes, keepdims, ddof, donate, mesh), build)
        with _obs.span("array.stat", op=name, donate=donate,
                       **_span_funcs(funcs)):
            out = self._wrap(fn(_check_live(base)), new_split)
        if name in ("var", "std") and _onepass.one_pass(self.dtype):
            _engine.record_one_pass_moments()
        if donate:
            self._consume_donated("%s()" % name)
        return out

    # identity each fusable reduction folds non-surviving records onto:
    # where(mask, v, identity) makes dropped rows (NaNs included) inert,
    # collapsing filter→reduce to ONE pass over the input
    _FUSED_STAT_NAMES = ("sum", "prod", "any", "all", "mean", "var",
                         "std", "max", "min")

    def _fused_filter_stat(self, axis, name, keepdims, ddof):
        """Single-pass ``filter(...).sum()``-family terminal: the
        predicate mask folds into the reduction combine, so the 3-pass
        mask+count+compact pipeline (and its input-sized compaction
        buffer) never runs.  Returns NotImplemented for geometries the
        fused program does not serve (the caller resolves and takes the
        materialising path):

        * reductions that keep the (dynamic) key axis — the output shape
          would need the survivor count;
        * ``ptp`` (needs both extrema identities at once) and
          complex-var/std (resolve instead of reimplementing numpy's
          abs²-moment rules);
        * ``max``/``min`` ARE fused but sync the survivor count (one
          scalar fetch, same price the eager path pays) to preserve the
          zero-size reduction error.

        ``mean``/``var``/``std`` divide by the masked COUNT (computed in
        the same pass); var uses the one-pass moment form
        ``(Σx² − (Σx)²/n)/(n−ddof)`` — single HBM read, UNSHIFTED, so
        less cancellation-robust than the unfiltered terminal's form
        (``tpu/moments.py``: also one read, about a pilot mean): its
        error scales with ``var + mean²``."""
        vshape = tuple(self._fpending.out.shape)
        ndim = 1 + len(vshape)
        if axis is None:
            axes = (0,)                      # the flat key axis (split=1)
        else:
            axes = tuple(sorted(tupleize(axis)))
            for a in axes:
                if not 0 <= a < ndim:
                    return NotImplemented    # let the eager path reject
        if 0 not in axes or name not in self._FUSED_STAT_NAMES:
            return NotImplemented
        vdtype = np.dtype(self._fpending.out.dtype)
        if name in ("var", "std") and np.issubdtype(vdtype,
                                                    np.complexfloating):
            return NotImplemented
        donate = _chain_donate_ok(self._fpending)    # [0] is the base
        fp = self._fpending
        base, geo, mesh = fp.base, fp.geometry(), self._mesh
        new_split = 1 if keepdims else 0
        needs_count = name in ("max", "min")

        def build():
            # the fold lives in ONE module function, shared with the
            # fused multi-terminal program (bolt_tpu/tpu/multistat.py)
            # and the grouped fold: single and fused filter-stats trace
            # identical arithmetic, by the same executor
            fold = _fold.Fold(geo, ((name, axes, keepdims, ddof),),
                              needs_count)

            def stat(data):
                out, *cnt = _fold.fold_records(fold, data)
                out = _constrain(out, mesh, new_split)
                return (out, cnt[0]) if needs_count else out
            return jax.jit(stat, donate_argnums=(0,) if donate else ())

        fn = _cached_jit(("filter-stat", name) + fp.key()
                         + (axes, keepdims, ddof, donate, mesh), build)
        out = _launch_filter_terminal(fn, base, name, donate)
        if donate:
            # mark consumption BEFORE any error path below: the program
            # already took the buffer, and a zero-survivor raise must
            # leave this array guarded, not pointing at a deleted base
            self._consume_donated("filter().%s()" % name)
        if needs_count:
            out, cnt = out
            if int(jax.device_get(cnt)) == 0:
                # match the eager path's zero-size reduction rejection
                raise ValueError(
                    "zero-size array to reduction operation %s which has "
                    "no identity" % name)
        return self._wrap(out, new_split)

    def _fused_filter_reduce(self, func, axis, keepdims):
        """Single-pass ``filter(...).reduce(func)``: the pairwise tree
        carries a VALIDITY bit per slot — combining a valid with an
        invalid slot selects the valid operand unchanged (no identity
        element needed for arbitrary ``func``; garbage from combining
        dropped records, NaNs included, is discarded by the select).  One
        scalar sync of the survivor count afterwards preserves the
        empty-reduce error contract.  NotImplemented (→ resolve-and-
        materialise path) off the flat key axis or for non-traceable
        reducers."""
        axes = tuple(sorted(tupleize(axis)))
        if axes != (0,):
            return NotImplemented
        donate = _chain_donate_ok(self._fpending)    # [0] is the base
        fp = self._fpending
        base, geo, n = fp.base, fp.geometry(), fp.n
        vshape, vdtype = tuple(fp.out.shape), fp.out.dtype
        if n == 0:
            raise TypeError("reduce of an empty array with no initial value")
        vaval = fp.out
        try:
            _cached_eval_shape(
                ("reduce", func, tuple(vshape), str(vdtype)),
                lambda: jax.eval_shape(func, vaval, vaval))
        except _TRACE_ERRORS:
            return NotImplemented            # host fallback path resolves
        mesh = self._mesh
        new_split = 1 if keepdims else 0

        def build():
            def reducer(data):
                flat, mask = geo.records(data)
                cnt = jnp.sum(mask, dtype=jnp.int32)
                vfunc = jax.vmap(func)

                def bc(m, like):
                    return m.reshape(m.shape + (1,) * (like.ndim - 1))

                x, valid = flat, mask
                while x.shape[0] > 1:
                    half = x.shape[0] // 2
                    a, b = x[:half], x[half:2 * half]
                    va, vb = valid[:half], valid[half:2 * half]
                    comb = vfunc(a, b)
                    if comb.shape != a.shape:
                        raise ValueError(
                            "reduce produced shape %s, expected value "
                            "shape %s" % (comb.shape[1:], tuple(vshape)))
                    # both valid → combined; one valid → that operand
                    # (combined may be garbage and is discarded)
                    sel = jnp.where(bc(va & vb, comb), comb,
                                    jnp.where(bc(va, comb), a, b))
                    vsel = va | vb
                    rem, vrem = x[2 * half:], valid[2 * half:]
                    if rem.shape[0]:
                        x = jnp.concatenate([sel, rem], axis=0)
                        valid = jnp.concatenate([vsel, vrem], axis=0)
                    else:
                        x, valid = sel, vsel
                out = x[0]
                if out.shape != tuple(vshape):
                    raise ValueError(
                        "reduce produced shape %s, expected value shape %s"
                        % (out.shape, tuple(vshape)))
                if keepdims:
                    out = out.reshape((1,) + tuple(vshape))
                return _constrain(out, mesh, new_split), cnt
            return jax.jit(reducer, donate_argnums=(0,) if donate else ())

        fn = _cached_jit(("filter-reduce", func) + fp.key()
                         + (keepdims, donate, mesh), build)
        out, cnt = _launch_filter_terminal(fn, base, "reduce", donate)
        if donate:
            # before the zero-survivor raise: the buffer is already gone,
            # so the array must carry the guard, not the deleted base
            self._consume_donated("filter().reduce()")
        if int(jax.device_get(cnt)) == 0:
            # every record was filtered out: same contract as reducing an
            # (0, ...)-shaped resolved result
            raise TypeError("reduce of an empty array with no initial value")
        return self._wrap(out, new_split)

    def _grouped_fold(self, label, value, nseg, op):
        """The terminal behind ``ops.segment_reduce`` by a label
        FUNCTION: one compiled program and one launch folds this array's
        records (axis 0) into ``nseg`` groups (:func:`_grouped_fold_expr`)
        and returns ``(folded, counts)`` as bolt arrays keyed by group.
        A deferred filter is folded in with its predicate and its maps —
        the survivors are never built — and a deferred map chain with its
        maps; the label and the value are traced into the same program as
        they are."""
        _engine.strict_guard(self, "segment_reduce()")
        if self._stream is not None:
            # a lazy out-of-core source: the fold is a terminal of the
            # streamed executor where the stage chain allows (one key
            # axis, record-wise maps, a filter); anything else
            # materialises below, as every other consumer does
            out = self._grouped_fold_streamed(label, value, nseg, op)
            if out is not NotImplemented:
                return out
        fp = self._fpending
        if fp is not None:
            base, source, key, rec = fp.base, fp.geometry(), fp.key(), fp.out
        else:
            base, funcs = self._chain_parts()
            source = _fold.Chain(funcs, self._split)
            key = (funcs, base.shape, str(base.dtype), self._split)
            rec = jax.ShapeDtypeStruct(self.shape[1:], self.dtype)
        _check_label(label, rec)
        mesh = self._mesh

        def build():
            spec = _fold.Fold(source, group=(op, label, value, nseg))

            def fold(data):
                folded, counts = _fold.fold_records(spec, data)
                return (jax.tree_util.tree_map(
                    lambda o: _constrain(o, mesh, 1), folded),
                    _constrain(counts, mesh, 1))
            return jax.jit(fold)

        fn = _cached_jit(("grouped-fold", op, label, value, nseg) + key
                         + (mesh,), build)
        with _obs.span("group.segment_reduce", op=op, segments=nseg,
                       filtered=fp is not None):
            if fp is not None:
                folded, counts = _launch_filter_terminal(fn, base, op,
                                                         False)
            else:
                folded, counts = fn(_check_live(base))
        wrap = lambda o: BoltArrayTPU(o, 1, mesh)      # noqa: E731
        return jax.tree_util.tree_map(wrap, folded), wrap(counts)

    def _grouped_fold_streamed(self, label, value, nseg, op):
        """:meth:`_grouped_fold` over a stream-backed array, slab by slab
        (``stream.maybe_group``): nothing is materialised, and the span
        ``group.segment_reduce`` says ``streamed=True``.  NotImplemented
        where the executor does not take the chain."""
        st = _streamlib.result_state(self._stream)
        if st.split == 1:       # the records the executor would fold
            _check_label(label, jax.ShapeDtypeStruct(tuple(st.vshape),
                                                     st.dtype))
        sp = _obs.begin("group.segment_reduce", op=op, segments=nseg,
                        streamed=True)
        try:
            out = _streamlib.maybe_group(self, label, value, nseg, op)
        except BaseException as exc:
            _obs.end(sp, error=type(exc).__name__)
            raise
        if out is NotImplemented:
            _obs.cancel(sp)         # not taken: the resident span follows
        else:
            _obs.end(sp)
        return out

    def mean(self, axis=None, keepdims=False):
        """Mean over ``axis`` (default: all key axes)."""
        return self._stat(axis, "mean", keepdims)

    def var(self, axis=None, keepdims=False, ddof=0):
        """Variance over ``axis`` (``ddof=0`` population default, matching
        the reference StatCounter; ``ddof=1`` for the sample variance,
        like the ndarray method the local backend inherits; fractional
        ddof passes through like numpy's)."""
        return self._stat(axis, "var", keepdims, ddof=ddof)

    def std(self, axis=None, keepdims=False, ddof=0):
        """Standard deviation over ``axis`` (``ddof`` like :meth:`var`)."""
        return self._stat(axis, "std", keepdims, ddof=ddof)

    def ptp(self, axis=None, keepdims=False):
        """Peak-to-peak (max − min) over ``axis`` — the ndarray method
        (numpy ≥2 spells it ``np.ptp``); one compiled program."""
        return self._stat(axis, "ptp", keepdims)

    def sum(self, axis=None, keepdims=False):
        return self._stat(axis, "sum", keepdims)

    def max(self, axis=None, keepdims=False):
        return self._stat(axis, "max", keepdims)

    def min(self, axis=None, keepdims=False):
        return self._stat(axis, "min", keepdims)

    def prod(self, axis=None, keepdims=False):
        """Product over ``axis`` — this backend's mean-family convention
        (default: all KEY axes, unlike bare ``ndarray.prod()`` which
        reduces everything; pass ``axis=tuple(range(b.ndim))`` for the
        full reduction), as one compiled program."""
        return self._stat(axis, "prod", keepdims)

    def all(self, axis=None, keepdims=False):
        """Truth-reduction AND over ``axis`` (mean-family convention:
        default reduces the key axes — see :meth:`prod`)."""
        return self._stat(axis, "all", keepdims)

    def any(self, axis=None, keepdims=False):
        """Truth-reduction OR over ``axis`` (mean-family convention:
        default reduces the key axes — see :meth:`prod`)."""
        return self._stat(axis, "any", keepdims)

    def cumsum(self, axis=None):
        """Cumulative sum (ndarray semantics: int axis, negative wrap, or
        ``None`` for the cumsum of the FLATTENED array, returned with a
        single flat key axis like ``filter``'s output convention)."""
        return self._cum("cumsum", axis)

    def cumprod(self, axis=None):
        """Cumulative product (ndarray semantics, see :meth:`cumsum`)."""
        return self._cum("cumprod", axis)

    def _one_axis(self, axis):
        """Normalise a single-int axis (Integral check, negative wrap,
        range check) — shared by argmax/argmin/cumsum/cumprod."""
        from numbers import Integral
        if not isinstance(axis, Integral):
            # TypeError matches the inherited ndarray methods on the local
            # backend, so portable error handling sees one exception type
            raise TypeError("axis %r is not an integer" % (axis,))
        axis = int(axis)
        if axis < 0:
            axis += self.ndim
        inshape(self.shape, (axis,))
        return axis

    def _cum(self, name, axis):
        if axis is not None:
            axis = self._one_axis(axis)
        mesh = self._mesh
        split = self._split
        new_split = (1 if split else 0) if axis is None else split
        # memory model: input + full-size output (dtype may widen: bool
        # cumsum counts in the canonical int) — inherent to the op, so
        # the guard is the up-front demand check, not a bounded path
        out_item = np.dtype(_canon(np.cumsum(
            np.zeros(1, self.dtype)).dtype)).itemsize
        hbm_check(name, self.size * (self.dtype.itemsize + out_item),
                  "input + full-size output")
        base, funcs = self._chain_parts()

        def build():
            op = {"cumsum": jnp.cumsum, "cumprod": jnp.cumprod}[name]

            def run(data):
                mapped = _chain_apply(funcs, split, data)
                out = op(mapped, axis=axis)
                return _constrain(out, mesh, new_split)
            return jax.jit(run)

        fn = _cached_jit(("cum", name, funcs, base.shape, str(base.dtype),
                          split, axis, mesh), build)
        return self._wrap(fn(_check_live(base)), new_split)

    def stats(self, *requested, axis=None, accumulate=None, **kwargs):
        """Statistics in one pass, two forms:

        * ``stats()`` / ``stats(("mean", "var"))`` /
          ``stats(requested=..., axis=...)`` — the reference contract: a
          :class:`~bolt_tpu.statcounter.StatCounter` of Welford moments
          via the explicit shard_map combine
          (``bolt_tpu/tpu/stats.py :: welford``).
        * ``stats("sum", "var", "min", ...)`` — the fluent FUSED
          multi-stat (bolt_tpu/tpu/multistat.py): every requested
          terminal (any of sum/mean/var/std/min/max/prod/all/any/ptp)
          from ONE single-pass program over this array — deferred
          chains applied once, streamed sources ingested once — each
          result bit-identical to its standalone terminal; returns an
          ordered ``{name: value-shaped array}`` dict.  ``accumulate``
          opts the additive terminals into the reduced-precision path
          (see :func:`bolt_tpu.tpu.multistat.compute`).
        """
        if requested and all(isinstance(r, str) for r in requested):
            from bolt_tpu.tpu.multistat import fluent_stats
            return fluent_stats(self, requested, axis=axis,
                                accumulate=accumulate)
        from bolt_tpu.tpu.stats import welford
        if requested:
            # legacy positional form: stats(requested_tuple[, axis])
            if len(requested) > 2:
                raise TypeError("stats() takes at most 2 positional "
                                "arguments (requested, axis)")
            kwargs.setdefault("requested", requested[0])
            if len(requested) == 2:
                if axis is not None:
                    raise TypeError("stats() got axis twice")
                axis = requested[1]
        return welford(self, axis=axis, **kwargs)

    def quantile(self, q, axis=None, keepdims=False, method="linear"):
        """The ``q``-th quantile over ``axis`` (default: all key axes) —
        one compiled program (XLA sorts on device; GSPMD gathers the
        reduced axes as needed).  ``q``: a scalar or a 1-d array of values
        in [0, 1]; a 1-d ``q`` prepends a q axis to the result, exactly
        like ``np.quantile`` — that new axis is a flat KEY axis (the same
        convention as ``filter``'s flat output key), so the remaining key
        axes stay leading.  Superset of the reference (no quantiles in
        Bolt/StatCounter)."""
        from bolt_tpu.utils import check_q
        qarr = check_q(q)
        vector_q = qarr.ndim == 1
        if axis is None:
            axes = tuple(range(self._split)) if self._split \
                else tuple(range(self.ndim))
        else:
            axes = tuple(sorted(tupleize(axis)))
            inshape(self.shape, axes)
        mesh = self._mesh
        split = self._split
        nkeys_reduced = sum(1 for a in axes if a < split)
        new_split = (split if keepdims else split - nkeys_reduced) \
            + (1 if vector_q else 0)
        base, funcs = self._chain_parts()

        def build():
            # q is a traced ARGUMENT, not a trace constant: sweeping many
            # quantiles reuses one compiled program instead of recompiling
            # (and re-caching) per q (per q-LENGTH for vector q — jit
            # retraces per aval internally)
            def stat(data, qv):
                mapped = _chain_apply(funcs, split, data)
                xf = mapped.astype(jnp.promote_types(mapped.dtype,
                                                     jnp.float32))
                out = jnp.quantile(xf, jnp.asarray(qv, xf.dtype), axis=axes,
                                   keepdims=keepdims, method=method)
                return _constrain(out, mesh, new_split)
            return jax.jit(stat)

        fn = _cached_jit(("quantile", method, funcs, base.shape,
                          str(base.dtype), split, axes, keepdims, vector_q,
                          mesh), build)
        return self._wrap(fn(_check_live(base),
                             qarr if vector_q else float(q)), new_split)

    def median(self, axis=None, keepdims=False):
        """Median over ``axis`` (default: all key axes)."""
        return self.quantile(0.5, axis=axis, keepdims=keepdims)

    def argmax(self, axis=None, keepdims=False):
        """Index of the maximum along ONE axis (numpy semantics: an int
        axis, or ``None`` for the index into the flattened array) — the
        local backend inherits exactly this from ``ndarray``.  One
        compiled program; ties resolve to the first occurrence, like
        numpy."""
        return self._arg_stat("argmax", axis, keepdims)

    def argmin(self, axis=None, keepdims=False):
        """Index of the minimum along ONE axis (numpy semantics)."""
        return self._arg_stat("argmin", axis, keepdims)

    def _arg_stat(self, name, axis, keepdims):
        if axis is not None:
            axis = self._one_axis(axis)
        mesh = self._mesh
        split = self._split
        if axis is None:
            new_split = 0
        else:
            new_split = split - (1 if axis < split and not keepdims else 0)
        base, funcs = self._chain_parts()

        def build():
            op = {"argmax": jnp.argmax, "argmin": jnp.argmin}[name]

            def stat(data):
                mapped = _chain_apply(funcs, split, data)
                out = op(mapped, axis=axis, keepdims=keepdims)
                return _constrain(out, mesh, new_split)
            return jax.jit(stat)

        fn = _cached_jit(("argstat", name, funcs, base.shape,
                          str(base.dtype), split, axis, keepdims, mesh),
                         build)
        return self._wrap(fn(_check_live(base)), new_split)

    # ------------------------------------------------------------------
    # elementwise operators
    #
    # The reference's Spark array has NO operator overloads — elementwise
    # math goes through ``map`` (SURVEY §2.2) and only the local ndarray
    # subclass gets them from numpy.  Providing them here is a deliberate
    # superset: the same expressions now run on both backends.  Scalar
    # operands defer (fuse into the map chain); array operands broadcast
    # against the full logical shape in one compiled program.
    # ------------------------------------------------------------------

    def __array_ufunc__(self, ufunc, method, *inputs, **kwargs):
        """Route numpy ufunc calls into the deferred map chain, so
        ``np.sin(b)`` / ``np.add(x, b)`` work identically on both backends
        (the local backend inherits this from ndarray — VERDICT r1 weak-3).
        Plain ``__call__`` with a jnp twin is served, and so are the
        binary-ufunc METHODS ``reduce``/``accumulate``/``outer``/
        ``reduceat`` (the local backend answers those natively through
        ndarray — VERDICT r4 missing-3); ``out=``/``where=``/``at`` and
        multi-output ufuncs return NotImplemented rather than silently
        gathering the distributed array to host through ``__array__``."""
        if method in ("reduce", "accumulate", "outer", "reduceat"):
            return self._ufunc_method(ufunc, method, inputs, kwargs)
        if method != "__call__" or kwargs or ufunc.nout != 1:
            return NotImplemented
        jf = getattr(jnp, ufunc.__name__, None)
        if jf is None or len(inputs) not in (1, 2):
            return NotImplemented
        if len(inputs) == 1:
            return self._unary(jf)
        a, b = inputs
        if ufunc.__name__ == "matmul":
            # contraction, not elementwise: route around the broadcast check
            return self._matmul(b if a is self else a, reverse=a is not self)
        if a is self:
            return self._elementwise(b, jf)
        return self._elementwise(a, jf, reverse=True)

    def _ufunc_method(self, ufunc, method, inputs, kwargs):
        """Device lowerings for the ufunc *methods* — ``np.add.reduce(b)``,
        ``np.multiply.accumulate(b)``, ``np.subtract.outer(b, w)``,
        ``np.add.reduceat(b, idx)`` — ONE fused program each through the
        ``jnp.ufunc`` twins, so the method surface answers identically on
        both backends (reference: the ndarray-native methods of
        ``bolt/local/array.py`` — SURVEY §2.3; VERDICT r4 missing-3 named
        this the one known cross-backend divergence).  Binary ufuncs with
        callable-but-unwrapped jnp twins (e.g. ``np.hypot``) are wrapped
        via ``jnp.frompyfunc`` with the numpy identity.  ``out=`` /
        non-default ``where=`` / ``at`` stay NotImplemented → TypeError,
        never a silent host gather."""
        from bolt_tpu.tpu.npdispatch import _device_fused
        if ufunc.nin != 2 or ufunc.nout != 1:
            return NotImplemented
        jf = getattr(jnp, ufunc.__name__, None)
        if jf is None:
            return NotImplemented
        if not isinstance(jf, jnp.ufunc):
            if not callable(jf):
                return NotImplemented
            jf = jnp.frompyfunc(jf, 2, 1, identity=ufunc.identity)
        kwargs = dict(kwargs)
        if kwargs.pop("out", None) is not None:
            return NotImplemented          # in-place target: explicit no
        where = kwargs.pop("where", True)
        if where is not True and not (np.ndim(where) == 0
                                      and bool(np.asarray(where))):
            return NotImplemented          # masked reduce: explicit no
        name = ufunc.__name__

        if method == "reduce":
            if len(inputs) != 1 or inputs[0] is not self:
                return NotImplemented
            axis = kwargs.pop("axis", 0)
            dtype = kwargs.pop("dtype", None)
            keepdims = kwargs.pop("keepdims", False)
            initial = kwargs.pop("initial", None)
            if kwargs:
                return NotImplemented
            if initial is not None and not isinstance(initial, (int, float,
                                                                complex)):
                if np.ndim(initial) == 0:
                    initial = np.asarray(initial).item()
                else:
                    return NotImplemented
            if name not in _UFUNC_FOLD_SAFE:
                return NotImplemented      # see _UFUNC_FOLD_SAFE
            if axis is None:
                axes = tuple(range(self.ndim))
            else:
                axes = tuple(sorted(self._one_axis(a)
                                    for a in tupleize(axis)))
                if len(set(axes)) != len(axes):
                    raise ValueError("duplicate value in 'axis'")
            if len(axes) > 1:
                # let numpy itself validate multi-axis reducibility on a
                # one-element dummy: non-reorderable ufuncs (subtract,
                # divide) must raise its exact ValueError here, not take
                # the sequential device path to an order-dependent value
                ufunc.reduce(np.zeros((1,) * self.ndim, self.dtype),
                             axis=axes)
            split = self._split
            nkeys = sum(1 for a in axes if a < split)
            new_split = split if (keepdims or not axes) else split - nkeys
            dt = None if dtype is None else _canon(dtype)

            # XLA rejects a cross-partition xor reduce computation
            # (UNIMPLEMENTED: Unsupported reduction computation), so a
            # key-axis xor cannot ride the GSPMD all-reduce.  Logical
            # parity is exactly a mod-2 sum — served below; the per-bit
            # bitwise form has no cheap collective and rejects loudly.
            if name == "bitwise_xor" and any(a < split for a in axes):
                return NotImplemented
            if name == "logical_xor" and axes:
                def body(v):
                    ax = axes if len(axes) > 1 else axes[0]
                    out = (jnp.sum(v.astype(bool).astype(jnp.int32),
                                   axis=ax, keepdims=keepdims) % 2
                           ).astype(bool)
                    if initial is not None:
                        out = jnp.logical_xor(out, bool(initial))
                    return out if dt is None else out.astype(dt)
                return _device_fused(
                    "ufunc_reduce", [self], self, new_split, body,
                    (name, axes, str(dt), keepdims,
                     type(initial).__name__, initial))

            def body(v):
                if not axes:
                    # numpy's axis=() applies op(initial, elem) per element
                    out = v.astype(dt) if dt is not None else v
                    return out if initial is None else jf(initial, out)
                if len(axes) == 1:
                    return jf.reduce(v, axis=axes[0], dtype=dt,
                                     keepdims=keepdims, initial=initial)
                try:
                    return jf.reduce(v, axis=axes, dtype=dt,
                                     keepdims=keepdims, initial=initial)
                except NotImplementedError:
                    # frompyfunc-wrapped twins reduce one axis per pass
                    # (scan lowering); ``initial`` joins only the LAST
                    # pass so each output element folds it exactly once
                    out = v
                    for i, ax in enumerate(reversed(axes)):
                        last = i == len(axes) - 1
                        out = jf.reduce(
                            out, axis=ax, dtype=dt, keepdims=keepdims,
                            initial=initial if last else None)
                    return out
            return _device_fused(
                "ufunc_reduce", [self], self, new_split, body,
                (name, axes, str(dt), keepdims,
                 type(initial).__name__, initial))

        if method == "accumulate":
            if len(inputs) != 1 or inputs[0] is not self:
                return NotImplemented
            axis = kwargs.pop("axis", 0)
            dtype = kwargs.pop("dtype", None)
            if kwargs:
                return NotImplemented
            if axis is None:               # numpy's exact rejection
                raise ValueError("accumulate does not allow multiple axes")
            axis = self._one_axis(axis)
            dt = None if dtype is None else _canon(dtype)
            # memory model mirrors _cum: input + full-size output, with
            # the output dtype taken from numpy's own promotion rule
            try:
                out_dt = ufunc.accumulate(np.zeros(1, self.dtype)).dtype
            except Exception:
                out_dt = self.dtype
            out_item = np.dtype(_canon(dt or out_dt)).itemsize
            hbm_check("%s.accumulate" % name,
                      self.size * (self.dtype.itemsize + out_item),
                      "input + full-size output")

            def body(v):
                return jf.accumulate(v, axis=axis, dtype=dt)
            return _device_fused(
                "ufunc_accumulate", [self], self, self._split, body,
                (name, axis, str(dt)))

        if method == "outer":
            dtype = kwargs.pop("dtype", None)
            if kwargs or len(inputs) != 2:
                return NotImplemented
            dt = None if dtype is None else _canon(dtype)
            a, b = inputs
            # keys survive only when the LEADING operand carries them (its
            # axes lead the outer's result); otherwise the result is
            # replicated — correct, and guarded by the demand check below
            new_split = a.split if isinstance(a, BoltArrayTPU) else 0
            out_dt = dt if dt is not None else np.result_type(
                getattr(a, "dtype", type(a)), getattr(b, "dtype", type(b)))
            in_bytes = sum(
                int(np.size(op)) * np.dtype(
                    _canon(getattr(op, "dtype", out_dt))).itemsize
                for op in (a, b))
            hbm_check("%s.outer" % name,
                      int(np.size(a)) * int(np.size(b))
                      * np.dtype(_canon(out_dt)).itemsize + in_bytes,
                      "both inputs + full outer product")

            def body(x, y):
                out = jf.outer(x, y)
                return out if dt is None else out.astype(dt)
            return _device_fused("ufunc_outer", [a, b], self, new_split,
                                 body, (name, str(dt)))

        if method == "reduceat":
            if len(inputs) != 2 or inputs[0] is not self:
                return NotImplemented
            axis = kwargs.pop("axis", 0)
            dtype = kwargs.pop("dtype", None)
            if kwargs or name not in _UFUNC_FOLD_SAFE:
                return NotImplemented
            if axis is None:               # numpy's exact rejection
                raise ValueError("reduceat does not allow multiple axes")
            axis = self._one_axis(axis)
            dt = None if dtype is None else _canon(dtype)
            # the indices ride through _device_fused as a runtime operand
            # (bolt arrays fuse on device — no silent host gather; host
            # lists are device-coerced once); executables cache by shape
            indices = inputs[1]
            if np.ndim(indices) != 1:
                return NotImplemented
            if not isinstance(indices, BoltArrayTPU):
                # host-visible indices validate up front (numpy raises
                # IndexError where jax's gather would silently clamp);
                # distributed index arrays are exempt — checking them
                # would be the silent gather this method forbids
                n_ax = self.shape[axis]
                host_idx = np.asarray(indices)
                bad = (host_idx < 0) | (host_idx >= n_ax)
                if host_idx.size and bad.any():
                    raise IndexError(
                        "index %d out-of-bounds in %s.reduceat [0, %d)"
                        % (int(host_idx[bad][0]), name, n_ax))
            nidx = int(np.shape(indices)[0])
            out_elems = (self.size // max(self.shape[axis], 1)) * nidx
            hbm_check("%s.reduceat" % name,
                      self.size * self.dtype.itemsize
                      + out_elems * np.dtype(_canon(dt or self.dtype)
                                             ).itemsize,
                      "input + one output slot per index")

            def body(v, idx):
                return jf.reduceat(v, idx, axis=axis, dtype=dt)
            return _device_fused(
                "ufunc_reduceat", [self, indices], self, self._split,
                body, (name, axis, str(dt)))

        return NotImplemented

    def _scalar_fn(self, op, other, reverse):
        """A per-(op, scalar) callable with a STABLE identity, so deferred
        chains built from repeated scalar expressions hit the jit cache
        instead of recompiling per fresh lambda.

        The key includes the scalar's TYPE: dict lookup hashes by
        equality and ``0 == 0.0 == False``, so without it ``b * 2.0``
        after ``b * 2`` would reuse the int-closing callable and silently
        change an integer array's result dtype."""
        key = (op.__name__, type(other).__name__, other, reverse)
        fn = _SCALAR_FN_CACHE.get(key)
        if fn is None:
            if reverse:
                def fn(v, _op=op, _o=other):
                    return _op(_o, v)
            else:
                def fn(v, _op=op, _o=other):
                    return _op(v, _o)
            _SCALAR_FN_CACHE[key] = fn
            if len(_SCALAR_FN_CACHE) > _JIT_CACHE_MAX:
                _SCALAR_FN_CACHE.popitem(last=False)
        else:
            _SCALAR_FN_CACHE.move_to_end(key)
        return fn

    def _coerce_operand(self, other):
        """Device-side coercion of a non-bolt operand.  A ``jax.Array``
        already on this mesh's devices feeds the compiled op directly
        (bouncing it through ``np.asarray`` would round-trip device→host→
        device per call, and outright fails for non-addressable arrays); an
        array committed elsewhere (another backend/device) takes the host
        path so mixed-device code keeps working."""
        if isinstance(other, jax.Array):
            try:
                if set(other.devices()).issubset(
                        set(self._mesh.devices.flat)):
                    return other
            except Exception:
                pass
        return jnp.asarray(np.asarray(other))

    def _coerce_bolt_operand(self, value, what):
        """Unwrap a possibly-bolt operand for a compiled program: a
        same-mesh TPU array passes through as its device data (foreign
        meshes get :meth:`_check_mesh`'s loud rejection), a local array
        gathers to host; anything else returns unchanged.  ONE home for
        the contract shared by ``set``/``searchsorted``/
        ``segment_reduce`` labels."""
        from bolt_tpu.base import BoltArray
        if isinstance(value, BoltArray):
            if value.mode == "tpu":
                self._check_mesh(value, what)
                return value.tojax()
            return np.asarray(value)
        return value

    def _check_mesh(self, other, what):
        """Binary ops take same-mesh operands only: silently constraining a
        foreign-mesh array to ``self``'s mesh would hide a (potentially
        DCN-wide) data move, or die later in XLA with an opaque error
        (VERDICT r1 weak-5)."""
        if other._mesh != self._mesh:
            raise ValueError(
                "%s operands live on different meshes (%s vs %s); move one "
                "explicitly first, e.g. other.tolocal().totpu(context=self."
                "mesh) or bolt_tpu.parallel.reshard" % (
                    what, getattr(self._mesh, "shape_tuple", self._mesh),
                    getattr(other._mesh, "shape_tuple", other._mesh)))

    def _elementwise(self, other, op, reverse=False):
        opname = op.__name__
        if isinstance(other, (int, float, complex, np.number)):
            fn = self._scalar_fn(op, other, reverse)
            if self._split == 0:
                out = _cached_jit(
                    ("ew0", opname, type(other).__name__, other, self.shape,
                     str(self.dtype), reverse, self._mesh),
                    lambda: jax.jit(fn))(self._data)
                return self._wrap(out, 0)
            return self.map(fn, axis=tuple(range(self._split)))
        if isinstance(other, BoltArrayTPU):
            self._check_mesh(other, "elementwise")
            odata = other._data
        elif isinstance(other, BoltArray):
            odata = jnp.asarray(other.toarray())
        else:
            odata = self._coerce_operand(other)
        # numpy broadcasting is symmetric: the result may OUTGROW self
        # (np.ones(8) * b_scalar).  Keys survive while they remain the
        # leading axes with unchanged lengths; a result that gains
        # leading dims is replicated.  (The shape-mismatch ValueError
        # for incompatible operands comes from broadcast_shapes itself.)
        out_shape = np.broadcast_shapes(self.shape, odata.shape)
        mesh, split = self._mesh, self._split
        if out_shape != self.shape:
            if len(out_shape) != self.ndim or \
                    out_shape[:split] != self.shape[:split]:
                split = 0
            out_item = np.dtype(_canon(np.result_type(
                self.dtype, odata.dtype))).itemsize
            need = int(np.prod(out_shape)) * out_item \
                + self.size * self.dtype.itemsize \
                + int(odata.size) * odata.dtype.itemsize
            hbm_check(opname, need, "both inputs + broadcast output")

        def build():
            def run(a, b):
                out = op(b, a) if reverse else op(a, b)
                return _constrain(out, mesh, split)
            return jax.jit(run)

        fn = _cached_jit(("ew", opname, self.shape, tuple(odata.shape),
                          str(self.dtype), str(odata.dtype), split, reverse,
                          mesh), build)
        return self._wrap(fn(self._data, odata), split)

    def __add__(self, other):
        return self._elementwise(other, jnp.add)

    __radd__ = __add__

    def __sub__(self, other):
        return self._elementwise(other, jnp.subtract)

    def __rsub__(self, other):
        return self._elementwise(other, jnp.subtract, reverse=True)

    def __mul__(self, other):
        return self._elementwise(other, jnp.multiply)

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self._elementwise(other, jnp.divide)

    def __rtruediv__(self, other):
        return self._elementwise(other, jnp.divide, reverse=True)

    def __pow__(self, other):
        return self._elementwise(other, jnp.power)

    def __mod__(self, other):
        return self._elementwise(other, jnp.mod)

    def __rmod__(self, other):
        return self._elementwise(other, jnp.mod, reverse=True)

    def __rpow__(self, other):
        return self._elementwise(other, jnp.power, reverse=True)

    def __floordiv__(self, other):
        return self._elementwise(other, jnp.floor_divide)

    def __rfloordiv__(self, other):
        return self._elementwise(other, jnp.floor_divide, reverse=True)

    def _matmul(self, other, reverse=False, op=jnp.matmul,
                precision=None):
        """Contraction with ndarray semantics (``op`` = ``jnp.matmul`` for
        ``@``, ``jnp.dot`` for :meth:`dot`), batched over the key axes:
        ONE compiled program on the full logical array — the MXU-shaped
        path, far better than a per-record map.  The key axes stay
        key-sharded whenever they survive as leading output axes;
        otherwise (contracted or displaced by broadcasting) the result is
        re-keyed to ``split=0``.  ``precision=None`` resolves through the
        scoped policy (``bolt.precision``), pinned at "highest"."""
        from bolt_tpu._precision import resolve
        precision = resolve(precision)
        if isinstance(other, BoltArrayTPU):
            self._check_mesh(other, op.__name__)
            odata = other._data
        elif isinstance(other, BoltArray):
            odata = jnp.asarray(other.toarray())
        else:
            odata = self._coerce_operand(other)
        # self.shape (not _aval, which is None on a pending filter result)
        # resolves the lazy survivor count first
        self_aval = jax.ShapeDtypeStruct(self.shape, self.dtype)
        a_aval = jax.ShapeDtypeStruct(odata.shape, odata.dtype) if reverse \
            else self_aval
        b_aval = self_aval if reverse \
            else jax.ShapeDtypeStruct(odata.shape, odata.dtype)
        # shape/dtype validation without execution; numpy raises
        # ValueError for contraction mismatches where jax raises
        # TypeError — normalise so portable error handling sees one type
        try:
            out_aval = jax.eval_shape(op, a_aval, b_aval)
        except TypeError as e:
            raise ValueError(str(e)) from None
        out_shape = tuple(out_aval.shape)
        split = self._split
        # keys survive when they still lead the output: self contributes
        # its batch dims plus (non-reverse) its row axis, so key axes past
        # `cap` are contracted; extra broadcast batch dims from a
        # higher-rank operand can displace the keys (matmul prepends them;
        # dot appends, but the conservative re-key is merely suboptimal)
        cap = self.ndim - (2 if reverse else 1)
        new_split = min(split, max(cap, 0))
        if (len(odata.shape) > self.ndim
                or out_shape[:new_split] != self.shape[:new_split]):
            new_split = 0
        mesh = self._mesh

        def build():
            def run(a, b):
                # default "highest": f32 accumulation on the MXU, matching
                # the numpy oracle to ulp level — TPU's native bf16 passes
                # diverge at ~1e-2 but run ~2.8x faster (measured 45 vs
                # 16 ms on 8192^2; dot(precision=) opts in)
                out = op(b, a, precision=precision) if reverse \
                    else op(a, b, precision=precision)
                return _constrain(out, mesh, new_split)
            return jax.jit(run)

        fn = _cached_jit((op.__name__, self.shape, tuple(odata.shape),
                          str(self.dtype), str(odata.dtype), split, reverse,
                          str(precision), mesh), build)
        return self._wrap(fn(self._data, odata), new_split)

    def __matmul__(self, other):
        return self._matmul(other)

    def __rmatmul__(self, other):
        return self._matmul(other, reverse=True)

    def dot(self, other, *, precision=None):
        """``numpy.dot`` semantics (the ndarray method the local backend
        inherits): matrix product for 2-d, inner product for 1-d, and for
        higher ranks the sum-product over self's LAST axis and ``other``'s
        second-to-last — which differs from ``@``'s stacked matmul.  One
        compiled MXU program.

        ``precision`` (keyword-only — ndarray.dot's second POSITIONAL is
        ``out``, which this backend does not take): ``None`` resolves
        through the scoped policy (``bolt.precision``), pinned at
        ``"highest"`` — f32 MXU accumulation, ulp-level numpy parity;
        ``"default"`` (bf16 passes) measured 2.8x faster on an 8192x8192
        product at ~1e-2 relative error.  ``@`` follows the SCOPE (the
        operator spelling cannot carry options) and stays "highest"
        outside one."""
        return self._matmul(other, op=jnp.dot, precision=precision)

    def take(self, indices, axis=None, mode="raise"):
        """Select elements by index (the ndarray method the local backend
        inherits): ``axis=None`` indexes the flattened array (result
        re-keyed to a flat key axis), an int axis gathers along it —
        numpy semantics, one compiled program.  ``mode``: ``'raise'``
        (default — negative wrap, out-of-bounds rejected), ``'wrap'``
        (modular), ``'clip'``.  Index-dtype rules follow numpy exactly:
        float NDARRAYS are rejected, float sequences/scalars truncate,
        booleans cast to 0/1 (not masks)."""
        if mode not in ("raise", "wrap", "clip"):
            raise ValueError("mode must be 'raise', 'wrap' or 'clip', "
                             "got %r" % (mode,))
        arraylike = isinstance(indices, np.ndarray) or (
            hasattr(indices, "__array__")
            and not isinstance(indices, (list, tuple)))
        idx = np.asarray(indices)
        if idx.dtype == bool:
            idx = idx.astype(np.intp)
        elif not np.issubdtype(idx.dtype, np.integer):
            if arraylike:
                raise TypeError(
                    "Cannot cast take indices from %s to integer"
                    % (idx.dtype,))
            idx = np.trunc(idx).astype(np.intp)   # numpy truncates sequences
        if axis is not None:
            axis = self._one_axis(axis)
        dim = prod(self.shape) if axis is None else self.shape[axis]
        if mode == "wrap":
            wrapped = idx % dim
        elif mode == "clip":
            wrapped = np.clip(idx, 0, dim - 1)
        else:
            wrapped = np.where(idx < 0, idx + dim, idx)
            if idx.size and (wrapped.min() < 0 or wrapped.max() >= dim):
                raise IndexError(
                    "take index out of bounds for size %d" % dim)
        mesh = self._mesh
        split = self._split
        new_split = (1 if split and idx.ndim else 0) if axis is None \
            else (split if axis >= split or idx.ndim == 1
                  else split + idx.ndim - 1)
        base, funcs = self._chain_parts()

        def build():
            def run(data, ids):
                mapped = _chain_apply(funcs, split, data)
                if axis is None:
                    out = jnp.take(mapped.reshape(-1), ids, axis=0)
                else:
                    out = jnp.take(mapped, ids, axis=axis)
                return _constrain(out, mesh, new_split)
            return jax.jit(run)

        fn = _cached_jit(("take", funcs, base.shape, str(base.dtype),
                          split, axis, idx.shape, mesh), build)
        out = fn(_check_live(base), jnp.asarray(wrapped, dtype=jnp.int32))
        return self._wrap(out, new_split)

    def argsort(self, axis=-1, kind=None):
        """Indices that would sort along ``axis`` (ndarray semantics:
        default LAST axis; ``None`` flattens to a 1-d result, re-keyed to
        a flat key axis like ``cumsum``).  ``kind='stable'`` (or numpy's
        synonym ``'mergesort'``) guarantees numpy-identical tie order;
        other kinds sort equal elements in an unspecified (numpy:
        quicksort's, here XLA's) order."""
        stable = _check_sort_kind(kind)
        if axis is not None:
            axis = self._one_axis(axis)
        mesh = self._mesh
        split = self._split
        new_split = (1 if split else 0) if axis is None else split
        in_bytes = self.size * self.dtype.itemsize
        out_bytes = self.size * np.dtype(
            jax.dtypes.canonicalize_dtype(np.int64)).itemsize
        if axis is not None and in_bytes > _CHUNK_MAX_BYTES:
            chunked = self._argsort_chunked(axis, stable, in_bytes,
                                            out_bytes)
            if chunked is not None:
                return chunked
        # memory model: input + index output + the variadic sort's
        # (value, iota) scratch of the same again
        hbm_check("argsort", 2 * (in_bytes + out_bytes),
                  "input + index output + variadic-sort scratch of both")
        base, funcs = self._chain_parts()

        def build():
            def run(data):
                mapped = _chain_apply(funcs, split, data)
                if axis is None:
                    out = jnp.argsort(mapped.reshape(-1), stable=stable)
                else:
                    out = jnp.argsort(mapped, axis=axis, stable=stable)
                return _constrain(out, mesh, new_split)
            return jax.jit(run)

        fn = _cached_jit(("argsort", funcs, base.shape, str(base.dtype),
                          split, axis, stable, mesh), build)
        return self._wrap(fn(_check_live(base)), new_split)

    def _argsort_chunked(self, axis, stable, in_bytes, out_bytes):
        """Bounded-workspace argsort along ``axis`` for HBM-scale inputs
        (VERDICT r2 weak-4): rows are independent, so slabs along another
        axis argsort separately and write into ONE donated output buffer
        (`.at[slab].set` with buffer donation — XLA updates in place, no
        copy-per-slab accumulation).  Peak = input + output + two
        slab-sized sort transients, instead of 2×(input+output).
        Returns None when no other axis can carry the slabbing."""
        plan = slab_plan(self.shape, axis, in_bytes)
        if plan is None:
            return None
        cax, pairs = plan
        mesh, split = self._mesh, self._split
        slab_bytes = in_bytes // len(pairs)
        hbm_check("argsort", in_bytes + out_bytes + 2 * slab_bytes,
                  "input + index output + per-slab sort transients")
        data = self._data                   # chain materialises once
        idx_dtype = jax.dtypes.canonicalize_dtype(np.int64)

        def zeros_build():
            def z():
                return _constrain(jnp.zeros(data.shape, idx_dtype),
                                  mesh, split)
            return jax.jit(z)

        buf = _cached_jit(("argsort-buf", data.shape, str(idx_dtype),
                           split, mesh), zeros_build)()
        for s0, s1 in pairs:

            def upd_build(s0=s0, s1=s1):
                def upd(b, d):
                    slab = jax.lax.slice_in_dim(d, s0, s1, axis=cax)
                    idx = jnp.argsort(slab, axis=axis, stable=stable)
                    sl = tuple(slice(s0, s1) if a == cax else slice(None)
                               for a in range(d.ndim))
                    return _constrain(b.at[sl].set(idx), mesh, split)
                return jax.jit(upd, donate_argnums=(0,))

            buf = _cached_jit(("argsort-slab", data.shape,
                               str(data.dtype), split, axis, stable,
                               s0, s1, cax, mesh),
                              upd_build)(buf, data)
        return self._wrap(buf, split)

    # ------------------------------------------------------------------
    # inherited-ndarray method surface (the local backend gets all of
    # these from ``numpy.ndarray``; providing them here keeps
    # mode-agnostic code running on both backends — VERDICT r2 missing-2.
    # Reference: ``bolt/local/array.py`` — the ndarray subclass)
    # ------------------------------------------------------------------

    def sort(self, axis=-1, kind=None):
        """Sort along ``axis`` IN PLACE and return ``None`` — the ndarray
        calling convention the local backend inherits.  Device buffers
        are immutable, so "in place" is at the wrapper level: this handle
        rebinds to the sorted array (other handles, and the numpy views
        the local backend can alias, are unaffected — this backend has no
        views).  ``kind`` accepts ndarray.sort's names; values are
        identical under any of them."""
        _check_sort_kind(kind)
        axis = self._one_axis(axis)
        # memory model: input + sorted output + XLA sort scratch
        hbm_check("sort", 3 * self.size * self.dtype.itemsize,
                  "input + sorted output + sort scratch")
        mesh, split = self._mesh, self._split
        base, funcs = self._chain_parts()

        def build():
            def run(data):
                mapped = _chain_apply(funcs, split, data)
                return _constrain(jnp.sort(mapped, axis=axis), mesh, split)
            return jax.jit(run)

        out = _cached_jit(("sort", funcs, base.shape, str(base.dtype),
                           split, axis, mesh), build)(_check_live(base))
        self._concrete = out
        self._retire_chain()
        self._aval = jax.ShapeDtypeStruct(out.shape, out.dtype)
        return None

    def ravel(self, order="C"):
        """Flatten to 1-d, the result keyed by a single flat key axis
        (``filter``'s output convention; a ``split=0`` input stays
        value-only).  ``order='F'`` flattens column-major (a reversed
        transpose on device); ``'A'``/``'K'`` follow the LOGICAL C
        layout — device arrays have no host memory order for them to
        inspect (the only divergence from numpy: a non-contiguous local
        oracle view could answer 'A'/'K' in F order)."""
        if order not in ("C", "F", "A", "K"):
            raise ValueError(
                "order must be one of 'C', 'F', 'A', or 'K' (got %r)"
                % (order,))
        fortran = order == "F"
        mesh, split = self._mesh, self._split
        new_split = 1 if split else 0
        base, funcs = self._chain_parts()

        def build():
            def run(data):
                mapped = _chain_apply(funcs, split, data)
                if fortran:
                    mapped = mapped.transpose(range(mapped.ndim)[::-1])
                return _constrain(mapped.reshape(-1), mesh, new_split)
            return jax.jit(run)

        fn = _cached_jit(("ravel", funcs, base.shape, str(base.dtype),
                          split, fortran, mesh), build)
        return self._wrap(fn(_check_live(base)), new_split)

    def flatten(self, order="C"):
        """Flattened copy (``ndarray.flatten``); identical to
        :meth:`ravel` here — both produce a fresh device array."""
        return self.ravel(order=order)

    def repeat(self, repeats, axis=None):
        """Repeat elements (ndarray semantics: ``axis=None`` flattens
        first; ``repeats`` a scalar, or a 1-d array matching the axis
        length — floats truncate like numpy).  The output length is
        computed on host, so the compiled program has a static shape;
        an array ``repeats`` is a traced argument (distinct repeat
        vectors of one total length reuse a program)."""
        rep = np.asarray(repeats)
        if rep.ndim > 1:
            raise ValueError("object too deep for desired array")
        if rep.dtype == bool or not np.issubdtype(rep.dtype, np.integer):
            rep = np.trunc(rep).astype(np.int64)   # numpy truncates floats
        if rep.size and rep.min() < 0:
            raise ValueError("negative dimensions are not allowed")
        if axis is not None:
            axis = self._one_axis(axis)
        dim = prod(self.shape) if axis is None else self.shape[axis]
        if rep.ndim == 1 and rep.size not in (1, dim):
            raise ValueError(
                "operands could not be broadcast together with shape "
                "(%d,) (%d,)" % (dim, rep.size))
        if rep.ndim == 1 and rep.size == 1:
            rep = np.full(dim, rep[0])      # numpy broadcasts size-1 repeats
        total = int(rep.sum()) if rep.ndim else int(rep) * dim
        mesh, split = self._mesh, self._split
        new_split = split if axis is not None else (1 if split else 0)
        base, funcs = self._chain_parts()

        def build():
            def run(data, r):
                mapped = _chain_apply(funcs, split, data)
                out = jnp.repeat(mapped, r, axis=axis,
                                 total_repeat_length=total)
                return _constrain(out, mesh, new_split)
            return jax.jit(run)

        fn = _cached_jit(("repeat", funcs, base.shape, str(base.dtype),
                          split, axis, rep.shape, total, mesh), build)
        return self._wrap(fn(_check_live(base), jnp.asarray(rep)), new_split)

    def _diag_axes(self, axis1, axis2):
        axis1 = self._one_axis(axis1)
        axis2 = self._one_axis(axis2)
        if axis1 == axis2:
            raise ValueError("axis1 and axis2 cannot be the same")
        return axis1, axis2

    def diagonal(self, offset=0, axis1=0, axis2=1):
        """Diagonal over the (``axis1``, ``axis2``) planes (ndarray
        semantics: both axes are removed and the diagonal appears as the
        LAST axis — a value axis; remaining key axes stay leading)."""
        axis1, axis2 = self._diag_axes(axis1, axis2)
        offset = int(offset)
        mesh, split = self._mesh, self._split
        new_split = split - sum(1 for a in (axis1, axis2) if a < split)
        base, funcs = self._chain_parts()

        def build():
            def run(data):
                mapped = _chain_apply(funcs, split, data)
                out = jnp.diagonal(mapped, offset, axis1, axis2)
                return _constrain(out, mesh, new_split)
            return jax.jit(run)

        fn = _cached_jit(("diagonal", funcs, base.shape, str(base.dtype),
                          split, offset, axis1, axis2, mesh), build)
        return self._wrap(fn(_check_live(base)), new_split)

    def trace(self, offset=0, axis1=0, axis2=1, dtype=None):
        """Sum of the (``axis1``, ``axis2``) diagonal.  The accumulator
        dtype is whatever numpy's ``ndarray.trace`` would produce for
        this input (asked of numpy directly, then canonicalised), so the
        backends agree — e.g. int8/bool promote to the canonical int."""
        axis1, axis2 = self._diag_axes(axis1, axis2)
        offset = int(offset)
        # numpy decides the output dtype (probe on an empty 2-d); the
        # backend canonicalises it (int64→int32 when x64 is off)
        target = _canon(np.empty((1, 1), dtype=self.dtype)
                        .trace(dtype=dtype).dtype)
        mesh, split = self._mesh, self._split
        new_split = split - sum(1 for a in (axis1, axis2) if a < split)
        base, funcs = self._chain_parts()

        def build():
            def run(data):
                mapped = _chain_apply(funcs, split, data)
                out = jnp.diagonal(mapped, offset, axis1, axis2)
                out = jnp.sum(out.astype(target), axis=-1)
                return _constrain(out, mesh, new_split)
            return jax.jit(run)

        fn = _cached_jit(("trace", funcs, base.shape, str(base.dtype),
                          split, offset, axis1, axis2, str(target), mesh),
                         build)
        return self._wrap(fn(_check_live(base)), new_split)

    def nonzero(self):
        """Indices of non-zero elements as a tuple of host int64 arrays,
        one per dimension — the plain-ndarray return the local backend
        inherits.  Dynamic count → the two-phase pattern (SURVEY §7 hard
        part 1): one compiled mask+count program, one scalar sync, then a
        count-shaped gather; the host receives only the indices."""
        mesh, split = self._mesh, self._split
        base, funcs = self._chain_parts()

        def count_build():
            def run(data):
                mapped = _chain_apply(funcs, split, data)
                # canonical int: int64 under x64, so a >2**31 match
                # count cannot wrap (x64-off cannot index past 2**31
                # anyway — int32 indices are platform-wide there)
                return jnp.sum(mapped != 0,
                               dtype=jax.dtypes.canonicalize_dtype(np.int64))
            return jax.jit(run)

        k = int(jax.device_get(_cached_jit(
            ("nonzero-count", funcs, base.shape, str(base.dtype), split,
             mesh), count_build)(_check_live(base))))

        def gather_build():
            def run(data):
                mapped = _chain_apply(funcs, split, data)
                return jnp.nonzero(mapped, size=k)
            return jax.jit(run)

        out = jax.device_get(_cached_jit(
            ("nonzero-gather", funcs, base.shape, str(base.dtype), split,
             k, mesh), gather_build)(_check_live(base)))
        return tuple(np.asarray(i).astype(np.int64) for i in out)

    def searchsorted(self, v, side="left", sorter=None):
        """Insertion points keeping this (1-d, sorted) array sorted —
        computed on device, returned as host indices (the plain-ndarray
        return the local backend inherits): a numpy int for scalar ``v``,
        an int64 ndarray shaped like ``v`` otherwise."""
        if self.ndim != 1:
            raise ValueError("object too deep for desired array")
        if side not in ("left", "right"):
            raise ValueError(
                "'%s' is an invalid value for keyword 'side'" % (side,))
        v = self._coerce_bolt_operand(v, "searchsorted values")
        varr = v if isinstance(v, jax.Array) else np.asarray(v)
        scalar = np.ndim(varr) == 0
        if sorter is not None:
            sorter = np.asarray(sorter)
            if not np.issubdtype(sorter.dtype, np.integer):
                # numpy's exact rejection — silent truncation would
                # search a wrongly-permuted array
                raise TypeError("sorter must only contain integers")
            if sorter.shape != self.shape:
                raise ValueError("sorter.size must equal a.size")
        mesh, split = self._mesh, self._split
        base, funcs = self._chain_parts()

        def build():
            def run(data, vv, srt):
                mapped = _chain_apply(funcs, split, data)
                if srt is not None:
                    mapped = jnp.take(mapped, srt, axis=0)
                return jnp.searchsorted(mapped, vv, side=side)
            return jax.jit(run)

        fn = _cached_jit(("searchsorted", funcs, base.shape,
                          str(base.dtype), split, side,
                          sorter is not None, mesh), build)
        srt = None if sorter is None else jnp.asarray(sorter, jnp.int32)
        out = np.asarray(jax.device_get(fn(_check_live(base), varr, srt)))
        out = out.astype(np.int64)
        return out[()] if scalar else out

    @property
    def real(self):
        """Real part (elementwise; defers and fuses like a map)."""
        return self._unary(jnp.real)

    @property
    def imag(self):
        """Imaginary part — zeros of the same dtype for real input, like
        numpy (elementwise; defers and fuses like a map)."""
        return self._unary(jnp.imag)

    def conj(self):
        """Elementwise complex conjugate (identity for real dtypes)."""
        return self._unary(jnp.conj)

    conjugate = conj

    def set(self, index, value):
        """Functional indexed update: a NEW array equal to this one with
        ``self[index] = value`` applied — the cross-backend mutation
        story (device arrays are immutable; ``__setitem__`` raises and
        points here, and the local backend offers the same method).

        Supports the same per-axis index forms as ``__getitem__``
        (ints / slices / lists / 1-d int or bool arrays / one Ellipsis);
        two or more advanced indices select ORTHOGONALLY, matching
        ``__getitem__``.  ``value`` broadcasts against the selected
        region and casts to this array's dtype (numpy assignment
        semantics).  One compiled scatter program per index geometry."""
        from bolt_tpu.utils import assignment_index, normalize_index
        norm, squeezed = normalize_index(index, self.shape)
        idx = assignment_index(norm, self.shape, squeezed)
        value = self._coerce_bolt_operand(value, "set value")
        val = value if isinstance(value, jax.Array) else np.asarray(value)
        # numpy assignment tolerates EXTRA leading length-1 dims on the
        # value (relative to the region, which drops scalar-indexed
        # axes); jax's scatter does not — squeeze them for parity
        region_ndim = self.ndim - len(squeezed)
        while val.ndim > region_ndim and val.shape[0] == 1:
            val = val.reshape(val.shape[1:])
        arrays = {ax: jnp.asarray(a) for ax, a in enumerate(idx)
                  if isinstance(a, np.ndarray)}
        static = tuple(None if isinstance(s, np.ndarray) else s
                       for s in idx)
        mesh, split = self._mesh, self._split
        base, funcs = self._chain_parts()

        def build():
            def run(data, v, iarrs):
                mapped = _chain_apply(funcs, split, data)
                full = tuple(iarrs[ax] if ax in iarrs else s
                             for ax, s in enumerate(static))
                out = mapped.at[full].set(v.astype(mapped.dtype))
                return _constrain(out, mesh, split)
            return jax.jit(run)

        key = ("set", funcs, base.shape, str(base.dtype), split,
               tuple((s.start, s.stop, s.step) if isinstance(s, slice)
                     else s for s in static),
               tuple((ax, a.shape) for ax, a in sorted(arrays.items())),
               tuple(val.shape), str(val.dtype), mesh)
        out = _cached_jit(key, build)(_check_live(base), val, arrays)
        return self._wrap(out, split)

    def __setitem__(self, index, value):
        raise TypeError(
            "'%s' does not support item assignment: device arrays are "
            "immutable.  Use b = b.set(index, value) for a functional "
            "update with the same indexing semantics (the local backend "
            "offers the same method)" % type(self).__name__)

    def item(self, *args):
        """Copy the selected element to a Python scalar (ndarray
        semantics: no args require size 1, one int is a flat index,
        ``ndim`` ints are per-axis — negatives wrap).  ONE element is
        gathered on device and fetched — never the array (one tiny
        compiled program per distinct index; a static index keeps GSPMD
        from all-gathering the sharded operand)."""
        from numbers import Integral
        if len(args) == 1 and isinstance(args[0], tuple):
            args = args[0]
        if not all(isinstance(a, Integral) for a in args):
            raise TypeError("item() takes integer arguments")
        if not args:
            if prod(self.shape) != 1:
                raise ValueError(
                    "can only convert an array of size 1 to a Python "
                    "scalar")
            multi = (0,) * self.ndim
        elif len(args) == 1:
            flat = int(args[0])
            size = prod(self.shape)
            if flat < 0:
                flat += size
            if not 0 <= flat < size:
                raise IndexError(
                    "index %d is out of bounds for size %d"
                    % (int(args[0]), size))
            multi = tuple(int(i) for i in
                          np.unravel_index(flat, self.shape)) \
                if self.ndim else ()
        else:
            if len(args) != self.ndim:
                raise ValueError("incorrect number of indices for array")
            multi = []
            for a, dim in zip(args, self.shape):
                i = int(a)
                if i < 0:
                    i += dim
                if not 0 <= i < dim:
                    raise IndexError(
                        "index %d is out of bounds for axis of size %d"
                        % (int(a), dim))
                multi.append(i)
            multi = tuple(multi)
        mesh, split = self._mesh, self._split
        base, funcs = self._chain_parts()

        def build():
            def run(data):
                mapped = _chain_apply(funcs, split, data)
                return mapped[multi]
            return jax.jit(run)

        out = _cached_jit(("item", funcs, base.shape, str(base.dtype),
                           split, multi, mesh), build)(_check_live(base))
        return np.asarray(jax.device_get(out)).item()

    def tolist(self):
        """Nested Python lists of the gathered array (ndarray
        semantics: a FULL host gather — size-bound like toarray)."""
        return self.toarray().tolist()

    # In-place operators: jax arrays are immutable, so these are the
    # functional rebinding form (``b += 1`` rebinds ``b`` to a new array;
    # other references to the old array are unchanged — jax's own
    # convention; true aliasing mutation is impossible on device).
    __iadd__ = __add__
    __isub__ = __sub__
    __imul__ = __mul__
    __itruediv__ = __truediv__
    __ifloordiv__ = __floordiv__
    __ipow__ = __pow__
    __imod__ = __mod__
    __imatmul__ = __matmul__

    def _unary(self, op):
        if self._split:
            return self.map(op, axis=tuple(range(self._split)))
        return self._wrap(
            _cached_jit((op.__name__ + "0", self.shape, str(self.dtype),
                         self._mesh),
                        lambda: jax.jit(op))(self._data), 0)

    def __neg__(self):
        # jnp.negative matches numpy in rejecting boolean negate, keeping
        # the two backends' semantics identical
        return self._unary(jnp.negative)

    def __abs__(self):
        return self._unary(jnp.abs)

    def clip(self, min=None, max=None, a_min=None, a_max=None):
        """Bound values to ``[min, max]`` — the ndarray method (and
        keyword names) the local backend inherits; ``a_min``/``a_max``
        accepted as np.clip-style aliases.

        Composed from the elementwise machinery — ``maximum(min)`` then
        ``minimum(max)``, numpy's ordering (the upper bound wins when
        ``min > max``) — so scalar bounds defer/fuse through the cached
        per-scalar callables and array bounds broadcast-validate against
        the FULL logical shape (key axes included) in one compiled
        program, exactly like operators."""
        if a_min is not None:
            if min is not None:
                raise ValueError("pass min= or a_min=, not both")
            min = a_min
        if a_max is not None:
            if max is not None:
                raise ValueError("pass max= or a_max=, not both")
            max = a_max
        if min is None and max is None:
            raise ValueError("clip needs at least one of min/max")
        out = self
        if min is not None:
            out = out._elementwise(min, jnp.maximum)
        if max is not None:
            out = out._elementwise(max, jnp.minimum)
        return out

    def round(self, decimals=0):
        """Round to ``decimals`` places (ndarray semantics; banker's
        rounding at .5, identical on both backends)."""
        from numbers import Integral
        if not isinstance(decimals, Integral):
            # ndarray.round raises TypeError here; silent int() truncation
            # would mask a caller bug only on this backend
            raise TypeError("decimals must be an integer, got %r"
                            % (decimals,))
        return self._unary(_round_fn(int(decimals)))

    def __lt__(self, other):
        return self._elementwise(other, jnp.less)

    def __le__(self, other):
        return self._elementwise(other, jnp.less_equal)

    def __gt__(self, other):
        return self._elementwise(other, jnp.greater)

    def __ge__(self, other):
        return self._elementwise(other, jnp.greater_equal)

    def __eq__(self, other):
        try:
            return self._elementwise(other, jnp.equal)
        except Exception:
            # non-comparable operand (None, sentinels): let Python fall
            # back to identity comparison
            return NotImplemented

    def __ne__(self, other):
        try:
            return self._elementwise(other, jnp.not_equal)
        except Exception:
            return NotImplemented

    __hash__ = None

    # ------------------------------------------------------------------
    # re-axis: THE signature operation
    # ------------------------------------------------------------------

    def swap(self, kaxes, vaxes, size="150", donate=False):
        """Move key axes ``kaxes`` into the values and value axes ``vaxes``
        into the keys.

        ``donate=True`` hands this array's device buffer to XLA for reuse —
        essential at HBM-filling sizes, where input + output of a re-axis
        cannot coexist (a 10 GB swap needs 20 GB without donation).  The
        donated array becomes unreadable afterwards, like the reference's
        consumed RDD lineage stage.

        New keys = (remaining keys) + (moved-in value axes); new values =
        (moved-out key axes) + (remaining value axes) — the reference's
        composite-key algebra (``BoltArraySpark.swap`` → ``ChunkedArray.
        keys_to_values/values_to_keys`` → shuffle → unchunk, SURVEY §3.3).

        Here the whole pipeline is one compiled transpose whose output
        carries the *new* key sharding: GSPMD lowers the sharding change to
        an ``all_to_all`` over ICI — the TPU-native form of the reference's
        cluster-wide shuffle.  ``size`` (the reference's chunk-size budget
        for the shuffle) is accepted and ignored: XLA chooses its own
        collective tiling.
        """
        kaxes = tuple(tupleize(kaxes) or ())
        vaxes = tuple(tupleize(vaxes) or ())
        split = self._split
        nvalue = self.ndim - split
        for a in kaxes:
            if a < 0 or a >= split:
                raise ValueError("key axis %d out of range for split %d" % (a, split))
        for a in vaxes:
            if a < 0 or a >= nvalue:
                raise ValueError("value axis %d out of range for %d value axes" % (a, nvalue))
        if len(set(kaxes)) != len(kaxes) or len(set(vaxes)) != len(vaxes):
            raise ValueError("swap axes must be unique")
        if len(kaxes) == split and len(vaxes) == 0:
            raise ValueError("cannot perform a swap that would leave the "
                             "array with no key axes")
        return self._do_swap(kaxes, vaxes, donate=donate)

    def _do_swap(self, kaxes, vaxes, donate=False):
        """The swap lowering without the no-key-axes guard — the chunk
        primitives (``keys_to_values`` over every key axis) legitimately
        produce key-less intermediates, which this representation supports
        as ``split=0``."""
        split = self._split
        nvalue = self.ndim - split
        keys_rest = [k for k in range(split) if k not in kaxes]
        values_rest = [v for v in range(nvalue) if v not in vaxes]
        perm = (keys_rest + [split + v for v in vaxes]
                + list(kaxes) + [split + v for v in values_rest])
        new_split = len(keys_rest) + len(vaxes)
        identity = perm == list(range(self.ndim))
        if identity and new_split == split:
            return self
        if self._stream is not None:
            # a STREAMED source records the swap as a lazy stage instead
            # of materialising (ISSUE 18): the terminal that eventually
            # consumes the chain resolves it through the two-phase
            # shuffle (stream.resolve_swaps) — all-to-all re-bucketing
            # slab by slab, spilling past the arbiter budget.
            # NotImplemented = this swap is outside the streamed story
            # (dynamic chain, lossy codec, pod iter source) and the
            # materialise-first path below serves it bit-identically.
            out = _streamlib.swap_stage(self, tuple(perm), new_split)
            if out is not NotImplemented:
                return out
        mesh = self._mesh
        if identity and not self.deferred:
            # only ``split`` changes.  Where the key sharding of the new
            # split places the data exactly as it lies, the re-split is a
            # VIEW: no program, no second buffer (at HBM-filling sizes
            # the copy would not fit beside its source).  Both wrappers
            # then hold the one jax.Array, which is what the donation
            # rule counts (:func:`_chain_donate_ok`: a base with two
            # owners is never donated), so neither can be consumed while
            # the other lives.  ``donate=True`` has nothing to hand over.
            # (A deferred chain has a program to run anyway and fuses
            # into the one below, as it always did.)
            data = self._data
            if data.sharding.is_equivalent_to(
                    key_sharding(mesh, data.shape, new_split), data.ndim):
                _engine.record_resplit_view()
                return self._wrap(data, new_split)

        if not donate:
            # a deferred chain fuses into the transpose program (donation
            # keeps materialise-first semantics: the chain's BASE buffer
            # may be aliased by other arrays, so it must not be donated)
            base, funcs = self._chain_parts()
            # a materialised base whose sharded axis moves across chips
            # is exchanged and glued in one pass where GSPMD's program
            # takes two (parallel/swapmerge.py); a chain keeps the
            # transpose it fuses into
            glue = (None if funcs else
                    _swap_glue(mesh, base, split, perm, new_split))

            def build():
                if glue is not None:
                    return jax.jit(glue())

                def swapper(data):
                    mapped = _chain_apply(funcs, split, data)
                    return _constrain(jnp.transpose(mapped, perm), mesh,
                                      new_split)
                return jax.jit(swapper)

            fn = _cached_jit(("swap", funcs, base.shape, str(base.dtype),
                              tuple(perm), split, new_split, False, mesh)
                             + (("merge",) if glue is not None else ()),
                             build)
            return self._wrap(fn(_check_live(base)), new_split)

        data = self._data
        glue = _swap_glue(mesh, data, split, perm, new_split)

        def build():
            if glue is not None:
                return jax.jit(glue(), donate_argnums=(0,))

            def swapper(data):
                return _constrain(jnp.transpose(data, perm), mesh, new_split)
            return jax.jit(swapper, donate_argnums=(0,))

        fn = _cached_jit(("swap", self.shape, str(self.dtype), tuple(perm),
                          split, new_split, True, mesh)
                         + (("merge",) if glue is not None else ()), build)
        out = fn(data)
        # only after a successful dispatch: a compile failure must not
        # brick an array whose buffer was never consumed (granted=False:
        # user-explicit donation, not an engine-policy grant)
        self._consume_donated("swap(..., donate=True)", granted=False)
        return self._wrap(out, new_split)

    def chunk(self, size="150", axis=None, padding=None):
        """Decompose the value axes into chunks; returns a
        :class:`~bolt_tpu.tpu.chunk.ChunkedArray` *view* — no data moves
        (reference: ``BoltArraySpark.chunk`` → ``ChunkedArray._chunk``;
        here chunking is bookkeeping over the already-mesh-resident array,
        the BASELINE north-star's "thin view over the mesh partition")."""
        from bolt_tpu.tpu.chunk import ChunkedArray
        return ChunkedArray.chunk(self, size=size, axis=axis, padding=padding)

    def stacked(self, size=1000):
        """Batch flat key records into blocks (reference:
        ``BoltArraySpark.stacked`` → ``StackedArray``).  On TPU batching is
        native — this view exists for API compatibility."""
        from bolt_tpu.tpu.stack import StackedArray
        return StackedArray.stack(self, size=size)

    # ------------------------------------------------------------------
    # shaping (within-group only, no data shuffle — reference:
    # ``BoltArraySpark.transpose/swapaxes/reshape/squeeze`` with
    # istransposeable/isreshapeable guards)
    # ------------------------------------------------------------------

    def transpose(self, *axes):
        axes = argpack(axes)
        if len(axes) == 0:
            axes = tuple(reversed(range(self.ndim)))
        if not istransposeable(axes, range(self.ndim)):
            raise ValueError("axes %s is not a permutation of %d axes"
                             % (str(axes), self.ndim))
        split = self._split
        if sorted(axes[:split]) != list(range(split)):
            raise ValueError(
                "transpose may not move axes between keys and values; "
                "use swap (key axes: %s)" % str(tuple(range(split))))
        if tuple(axes) == tuple(range(self.ndim)):
            return self
        mesh = self._mesh

        def build():
            def t(data):
                return _constrain(jnp.transpose(data, axes), mesh, split)
            return jax.jit(t)

        fn = _cached_jit(("transpose", self.shape, str(self.dtype),
                          split, tuple(axes), mesh), build)
        return self._wrap(fn(self._data), split)

    @property
    def T(self):
        """Reverse keys among themselves and values among themselves (the
        group-respecting transpose)."""
        split = self._split
        perm = tuple(reversed(range(split))) + tuple(
            reversed(range(split, self.ndim)))
        return self.transpose(*perm)

    def swapaxes(self, axis1, axis2):
        perm = list(range(self.ndim))
        perm[axis1], perm[axis2] = perm[axis2], perm[axis1]
        return self.transpose(*perm)

    def reshape(self, *shape):
        shape = argpack(shape)
        if not isreshapeable(shape, self.shape):
            raise ValueError("cannot reshape %s to %s" % (str(self.shape), str(shape)))
        ksize = prod(self.shape[:self._split])
        # infer the boundary: the smallest non-empty key prefix whose
        # product matches.  Ambiguous cases (trailing size-1 axes) should
        # use the keys/values views, which state the boundary explicitly.
        start = 1 if self._split > 0 else 0
        new_split = None
        for k in range(start, len(shape) + 1):
            if prod(shape[:k]) == ksize:
                new_split = k
                break
        if new_split is None:
            raise ValueError(
                "new shape %s does not preserve the key/value boundary "
                "(key size %d)" % (str(shape), ksize))
        return self._reshape_with_split(shape, new_split)

    def _reshape_with_split(self, shape, new_split):
        """Reshape to ``shape`` with an explicitly stated key-axis count
        (used by the ``keys``/``values`` views, which know the boundary)."""
        shape = tuple(shape)
        if prod(shape[:new_split]) != prod(self.shape[:self._split]):
            raise ValueError(
                "new key shape %s does not match key size %d"
                % (str(shape[:new_split]), prod(self.shape[:self._split])))
        if shape == self.shape and new_split == self._split:
            return self
        mesh = self._mesh
        ns = new_split

        def build():
            def r(data):
                return _constrain(data.reshape(shape), mesh, ns)
            return jax.jit(r)

        fn = _cached_jit(("reshape", self.shape, str(self.dtype),
                          self._split, shape, ns, mesh), build)
        return self._wrap(fn(self._data), ns)

    def squeeze(self, axis=None):
        if axis is None:
            axes = tuple(i for i, s in enumerate(self.shape) if s == 1)
        else:
            axes = tupleize(axis)
            inshape(self.shape, axes)
            for a in axes:
                if self.shape[a] != 1:
                    raise ValueError("cannot squeeze axis %d of size %d"
                                     % (a, self.shape[a]))
        new_shape = tuple(s for i, s in enumerate(self.shape) if i not in axes)
        new_split = self._split - sum(1 for a in axes if a < self._split)
        if new_shape == self.shape:
            return self
        mesh = self._mesh

        def build():
            def s(data):
                return _constrain(data.reshape(new_shape), mesh, new_split)
            return jax.jit(s)

        fn = _cached_jit(("squeeze", self.shape, str(self.dtype),
                          self._split, axes, mesh), build)
        return self._wrap(fn(self._data), new_split)

    # ------------------------------------------------------------------
    # indexing (reference: ``BoltArraySpark.__getitem__`` — per-axis
    # int/slice/list/bool, key-axis selection as record filtering, value-axis
    # as block slicing; advanced indices apply orthogonally per axis)
    # ------------------------------------------------------------------

    def __getitem__(self, index):
        """Index per axis with an integer, a slice, a list or a boolean
        mask (advanced indices apply orthogonally, axis by axis).

        A BASIC index — slices of step 1 and integers only — launches
        nothing: it is recorded as a window on the deferred chain
        (:class:`_Window`) and traced inside the program of whatever
        reads it, so ``b[t:t+16].mean()`` is one program and one launch.
        Like a NumPy view the result keeps the array it was cut from
        alive (``small = big[:16]; del big`` holds ``big``'s HBM until
        ``small`` is ``.cache()``d or otherwise materialised); unlike a
        chain of maps it is never donated.  A window of a window is one
        window; a window over key axes alone moves in front of the
        per-record maps before it, which it commutes with.

        Today's eager program (one launch, the slice materialised) still
        serves what a window in the chain cannot mean the same for: an
        array or boolean index, a step other than 1; a filtered, streamed
        or pending-statistic source; and a window that touches a value
        axis after maps that changed the value shape."""
        from bolt_tpu.utils import normalize_index
        # decided on what the array is when it is indexed: reading the
        # shape below resolves a filter
        plain = not (self._donated or self._stream is not None
                     or self._fpending is not None
                     or self._pending is not None
                     or self._spending is not None)
        norm, squeezed = normalize_index(index, self.shape)
        if plain and all(isinstance(s, slice) and s.step == 1
                         for s in norm):
            out = self._defer_window(norm, squeezed)
            if out is not None:
                return out

        mesh = self._mesh
        adv = tuple(ax for ax, s in enumerate(norm) if isinstance(s, np.ndarray))
        arrays = {ax: jnp.asarray(norm[ax]) for ax in adv}
        slices = tuple(s if isinstance(s, slice) else slice(None) for s in norm)
        key = ("getitem", self.shape, str(self.dtype), self._split,
               tuple((s.start, s.stop, s.step) for s in slices),
               tuple((ax, arrays[ax].shape) for ax in adv),
               tuple(squeezed), mesh)
        new_split = self._split - sum(1 for a in squeezed if a < self._split)

        def build():
            def get(data, idx_arrays):
                out = data[slices]
                for ax in adv:
                    out = jnp.take(out, idx_arrays[ax], axis=ax)
                if squeezed:
                    out = out.reshape(tuple(
                        s for i, s in enumerate(out.shape) if i not in squeezed))
                return _constrain(out, mesh, new_split)
            return jax.jit(get)

        with _obs.span("array.getitem", advanced=len(adv)):
            out = _cached_jit(key, build)(self._data, arrays)
        return self._wrap(out, new_split)

    def _defer_window(self, norm, squeezed):
        """``self[index]`` as a deferred chain with the index as a
        :class:`_Window` entry, or None where only the eager program
        will do (see :meth:`__getitem__`).  ``norm`` holds step-1 slices
        only, already clipped to the shape."""
        shape, split = self.shape, self._split
        starts = [s.start for s in norm]
        sizes = [max(0, s.stop - s.start) for s in norm]
        m = len(shape)
        while m and sizes[m - 1] == shape[m - 1] and m - 1 not in squeezed:
            m -= 1                      # trailing whole axes pass untouched
        win = _Window(tuple(starts[:m]), tuple(sizes[:m]), tuple(squeezed),
                      split)
        base, funcs = self._chain_parts(consume=False)
        at = len(funcs)
        if m <= split:
            # key axes alone: commutes with the per-record maps before
            # it (not with a with_keys map, whose keys it would shift)
            while at and type(funcs[at - 1]) is not _Window \
                    and not isinstance(funcs[at - 1], _WithKeysFunc):
                at -= 1
        elif at and type(funcs[at - 1]) is not _Window:
            # a value axis, after maps: in place while the value shape
            # is still the one the maps were given
            before = base.shape
            for w in _windows(funcs):
                before = w.out_shape(before)
            if tuple(before[split:]) != tuple(shape[split:]):
                return None
        if at and type(funcs[at - 1]) is _Window:
            funcs = funcs[:at - 1] + (funcs[at - 1].then(win),) + funcs[at:]
        else:
            funcs = funcs[:at] + (win,) + funcs[at:]
        aval = jax.ShapeDtypeStruct(win.out_shape(shape), self._aval.dtype)
        return BoltArrayTPU._deferred(base, funcs, split - win.kdrop,
                                      self._mesh, aval)

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __iter__(self):
        """Iterate over the leading axis, like numpy (each item is a bolt
        array with one fewer dimension).  One compiled take program serves
        every index (the index is a traced argument, not a cache key)."""
        n = len(self)
        mesh = self._mesh
        new_split = self._split - 1 if self._split > 0 else 0

        def build():
            def take(data, i):
                return _constrain(jnp.take(data, i, axis=0), mesh, new_split)
            return jax.jit(take)

        fn = _cached_jit(("iter-take", self.shape, str(self.dtype),
                          self._split, mesh), build)
        data = self._data
        for i in range(n):
            yield self._wrap(fn(data, jnp.asarray(i, dtype=jnp.int32)),
                             new_split)

    # ------------------------------------------------------------------
    # conversions / persistence
    # ------------------------------------------------------------------

    def toarray(self, out=None):
        """Gather to a host ``numpy.ndarray`` in key order (reference:
        ``BoltArraySpark.toarray`` = sortByKey → collect → reshape; here a
        single ``device_get`` — ordering is intrinsic, SURVEY §3.5).  On a
        multi-host mesh, shards the local process cannot address are
        all-gathered over DCN first.

        HOST-RAM MODEL: every process receives the FULL logical array —
        that is ``toarray``'s contract (device memory stays bounded; see
        ``_gather_multihost``), so the host must hold ``size × itemsize``
        bytes per process.  For arrays bigger than host RAM pass ``out=``
        (any writable shape/dtype-matching array, e.g. an
        ``np.lib.format.open_memmap`` / ``np.memmap``) and the gather
        writes into it shard by shard; or skip assembly entirely with
        :meth:`iter_shards`.

        A small pending ``filter`` result is fetched in ONE batched
        transfer (padded buffer + survivor count together) and sliced on
        host, so ``filter(...).toarray()`` pays a single round-trip instead
        of a count sync followed by a data fetch; the fetched count then
        resolves the device side for free.  Large padded buffers skip the
        fast path — when few records survive, shipping the full buffer
        would cost more than the extra count round-trip saves.

        Three phases, each a span under the root ``array.fetch`` (the
        same calls whether or not anyone records them):
        ``array.fetch.force`` until the last program this fetch needs
        and the copy back are enqueued (a streamed operand runs whole in
        here), ``array.fetch.wait`` until the device has the answer, and
        ``array.fetch.copy`` for the rest of the way to a host ndarray
        (``bytes=``)."""
        root = _obs.begin("array.fetch")
        sp = _obs.begin("array.fetch.force")
        try:
            arrays, finish = self._fetch_plan(out)
            for a in arrays:
                # asked for now, so that the copy follows the program
                # down the device's queue and not the host's wake-up
                a.copy_to_host_async()
            _obs.end(sp)
            sp = _obs.begin("array.fetch.wait")
            # the device_get below would wait here anyway: this only
            # tells the wait from the copy
            jax.block_until_ready(arrays)  # lint: allow(BLT107 toarray is the sync point)
            _obs.end(sp)
            sp = _obs.begin("array.fetch.copy")
            res = finish()
            if sp is not None:
                sp.set(bytes=int(res.nbytes))
            return res
        finally:
            _obs.end(sp)
            _obs.end(root)

    def _fetch_plan(self, out):
        """The force phase of :meth:`toarray`: ``(arrays, finish)`` with
        every program enqueued, ``arrays`` what is to be waited for and
        copied back, ``finish()`` the copy and the host assembly."""
        if self._fpending is not None:
            self._resolve_fpending()   # one fused pass → (padded, count)
        if self._pending is not None:
            padded, cnt = self._pending
            if (padded.is_fully_addressable
                    and padded.size * padded.dtype.itemsize
                    <= _PENDING_FETCH_MAX_BYTES):
                def finish_pending():
                    p, c = jax.device_get((padded, cnt))
                    c = int(c)
                    # the count is on host now: resolve device-side
                    # without a second sync, releasing the padded buffer
                    self._resolve_pending(count=c)
                    if out is None:
                        return np.asarray(p)[:c].copy()
                    # out= keeps the single batched round-trip: validate
                    # against the now-known filtered shape, copy the
                    # survivor slice in
                    BoltArray._check_out(
                        out, (c,) + tuple(padded.shape[1:]), padded.dtype)
                    out[...] = np.asarray(p)[:c]
                    return out
                return [padded, cnt], finish_pending
        data = self._data
        if out is not None:
            BoltArray._check_out(out, data.shape, data.dtype)
        if not data.is_fully_addressable:
            return ([sh.data for sh in data.addressable_shards],
                    lambda: self._gather_multihost(data, out=out))
        if out is None:
            return [data], lambda: np.asarray(jax.device_get(data))
        # shard-wise writes into the caller's target (which may be a
        # memmap) — fetched in ONE batched device_get (per-shard gets
        # would pay a host round-trip EACH)
        shards = data.addressable_shards
        blocks = [sh.data for sh in shards]

        def finish_out():
            for sh, blk in zip(shards, jax.device_get(blocks)):
                out[sh.index] = np.asarray(blk)
            return out
        return blocks, finish_out

    def iter_shards(self):
        """Yield ``(index, block)`` for every shard THIS process can
        address — ``index`` the tuple of slices locating the block in the
        logical array, ``block`` its host ndarray.  The zero-assembly
        collect: per-shard host RAM instead of ``toarray``'s full-array
        buffer, and on a multi-host mesh no DCN traffic at all (each
        process walks its own shards; a replicated array yields every
        shard from every process).  Blocks are WRITABLE host copies on
        both backends (a bare device_get view is read-only), so shard-
        walking code can scribble without mode-dependent aliasing."""
        data = self._data
        for sh in data.addressable_shards:
            yield sh.index, np.array(jax.device_get(sh.data))

    def _gather_multihost(self, data, out=None):
        """Shard-wise cross-host gather with bounded device memory at ANY
        array size (VERDICT r1 missing-2: ``process_allgather(tiled)``
        replicates the FULL logical array on every device, OOMing every
        host at once at TB scale).  Three steps:

        1. each process ``device_get``s its own addressable shards straight
           into the host result — most of the data, zero collectives;
        2. the global shard layout (``devices_indices_map`` — identical on
           every process) assigns each remaining region one owner;
        3. each remote region is broadcast from its owner in
           ``<= _GATHER_SLAB_BYTES`` pieces (host-sliced, so the compiled
           psum-broadcast program count is the number of distinct piece
           SHAPES, not piece count — device memory per step is one piece).

        Every process still receives the full host ndarray: all processes
        run the same SPMD program, so a one-driver collect (the
        reference's ``sortByKey().collect()``) has no analog —
        collectives need every process participating."""
        from jax.experimental import multihost_utils
        from bolt_tpu.parallel import multihost as _mh
        shape = tuple(data.shape)
        dtype = np.dtype(data.dtype)
        if out is None:
            # the full-array host buffer toarray's contract requires;
            # callers with less host RAM pass out= (e.g. a memmap) or
            # use iter_shards
            out = np.empty(shape, dtype)
        pid = _mh.process_index()

        def norm(idx):
            return tuple(s.indices(d)[:2] for s, d in zip(idx, shape))

        # step 1: local shards, no communication
        for sh in data.addressable_shards:
            out[sh.index] = np.asarray(jax.device_get(sh.data))

        # step 2: deterministic region -> owner map (lowest device id)
        owners, procs = {}, {}
        for dev, idx in data.sharding.devices_indices_map(shape).items():
            key = norm(idx)
            if key not in owners or dev.id < owners[key].id:
                owners[key] = dev
            procs.setdefault(key, set()).add(dev.process_index)
        nproc = _mh.process_count()
        stats = {"regions": 0, "broadcasts": 0, "max_piece_bytes": 0}

        # step 3: broadcast each non-universal region in bounded pieces
        for key in sorted(owners):
            if len(procs[key]) == nproc:
                continue  # replicated region: every process has it already
            stats["regions"] += 1
            src = owners[key].process_index
            rshape = tuple(b - a for a, b in key)
            rbytes = prod(rshape) * dtype.itemsize
            if not rshape or rbytes <= _GATHER_SLAB_BYTES:
                pieces = [tuple(slice(a, b) for a, b in key)]
            else:
                # split the largest extent so each piece fits the budget
                ax = int(np.argmax(rshape))
                step = max(1, int(rshape[ax] * _GATHER_SLAB_BYTES // rbytes))
                a0 = key[ax][0]
                pieces = []
                for p0 in range(0, rshape[ax], step):
                    pb = [slice(a, b) for a, b in key]
                    pb[ax] = slice(a0 + p0, min(a0 + p0 + step, key[ax][1]))
                    pieces.append(tuple(pb))
            for pb in pieces:
                pshape = tuple(s.stop - s.start for s in pb)
                piece = out[pb] if src == pid else np.zeros(pshape, dtype)
                got = multihost_utils.broadcast_one_to_all(
                    np.ascontiguousarray(piece), is_source=(src == pid))
                if src != pid:
                    out[pb] = got
                stats["broadcasts"] += 1
                stats["max_piece_bytes"] = max(
                    stats["max_piece_bytes"], prod(pshape) * dtype.itemsize)
        global _LAST_GATHER_STATS
        _LAST_GATHER_STATS = stats
        return out

    def __array__(self, dtype=None):
        from bolt_tpu.tpu.npdispatch import implicit_gather_warning
        implicit_gather_warning(self.size * self.dtype.itemsize)
        a = self.toarray()
        return a.astype(dtype) if dtype is not None else a

    def __array_function__(self, func, types, args, kwargs):
        """Non-ufunc numpy API (``np.sum(b)``, ``np.concatenate``, …)
        with NUMPY semantics, served on device by
        :mod:`bolt_tpu.tpu.npdispatch` where the table covers it (result
        comes back as a bolt array, zero host transfer) and by an
        explicit host fallback — which warns above a size threshold —
        otherwise.  The local backend gets the same API natively from
        ndarray (VERDICT r2 missing-3)."""
        from bolt_tpu.tpu import npdispatch
        return npdispatch.dispatch(self, func, types, args, kwargs)

    def _clone(self):
        """A new wrapper over the same (immutable) device state — the
        cheap copy behind functional forms of the in-place methods
        (``np.sort``)."""
        b = BoltArrayTPU(self._concrete, self._split, self._mesh)
        b._chain = self._chain
        b._links, b._node = self._links, self._node
        b._pending = self._pending
        b._fpending = self._fpending
        # a lazy stream source is shared, not forked: callback sources
        # re-stream on demand, and either wrapper materialising adopts
        # its own concrete state without touching the other
        b._stream = self._stream
        # a pending stat handle is shared too: either wrapper's first
        # read resolves the group once and both adopt the same result
        b._spending = self._spending
        b._stat_group = self._stat_group
        b._donated = self._donated
        b._aval = self._aval
        return b

    def tolocal(self):
        from bolt_tpu.local.array import BoltArrayLocal
        return BoltArrayLocal(self.toarray())

    def totpu(self, context=None, axis=(0,)):
        if context is None or context is self._mesh:
            return self
        return BoltArray.totpu(self, context=context, axis=axis)

    def tojax(self):
        """Unwrap to the engine-native object: the underlying sharded
        ``jax.Array`` (materialises a deferred chain first).  Fills the
        structural slot of the reference's ``BoltArraySpark.tordd`` —
        unwrap to the RDD of ``(key, value)`` records."""
        return self._data

    def first(self):
        """The value block at the first key tuple (reference:
        ``BoltArraySpark.first`` — a one-record job; here one block
        transfer).  On a DEFERRED chain this compiles a one-record
        program — the chain runs on the first block only, never
        materialising the full mapped array (the reference's
        one-record-job economy, VERDICT r2 weak-5)."""
        if self.deferred and _windows(self._chain[1]):
            # the one-record slice below is a slice of the BASE: over a
            # windowed chain ask for the first record as one more window
            return self[(0,) * self._split].toarray()
        if self.deferred:
            base, funcs = self._chain
            mesh, split = self._mesh, self._split

            def build():
                def run(d):
                    # static size-1 key slice, then the SAME chain
                    # application as materialisation (size-1 key axes
                    # make with_keys entries see exactly the all-zero
                    # first key) — one code path, one-record economy
                    rec = d[(slice(0, 1),) * split]
                    return _chain_apply(funcs, split, rec)[(0,) * split]
                return jax.jit(run)

            fn = _cached_jit(("first", funcs, base.shape, str(base.dtype),
                              split, mesh), build)
            return np.asarray(jax.device_get(fn(_check_live(base))))
        return np.asarray(jax.device_get(self._data[(0,) * self._split]))

    def _concat_many(self, others, axis):
        """Concatenate with any number of operands in ONE compiled
        program (``np.concatenate``'s dispatch target — the pairwise
        method would materialise n−1 intermediates).  ``axis=None``
        ravels every operand first, like numpy (result gets the flat
        key axis).  Built on the shared fused-program machinery
        (:func:`bolt_tpu.tpu.npdispatch._device_fused`): deferred chains
        on bolt operands fuse in, host operands upload once."""
        from bolt_tpu.tpu.npdispatch import _device_fused
        parts = [self] + list(others)
        if axis is not None:
            axis = int(axis)
            for p in parts:
                if np.ndim(p) != self.ndim:
                    raise ValueError(
                        "cannot concatenate %d-d with %d-d array"
                        % (self.ndim, np.ndim(p)))
        new_split = self._split if axis is not None \
            else (1 if self._split else 0)

        def body(*mapped):
            if axis is None:
                mapped = [m.reshape(-1) for m in mapped]
            return jnp.concatenate(mapped, axis=0 if axis is None else axis)

        return _device_fused("concat", parts, self, new_split, body, (axis,))

    def concatenate(self, arry, axis=0):
        """Concatenate along ``axis`` with another bolt array or ndarray
        (reference: ``BoltArraySpark.concatenate``).  A distributed other
        stays on device — the reshard rides ICI, no host round-trip."""
        return self._concat_many([arry], int(axis))

    def astype(self, dtype, casting="unsafe"):
        """Cast elements (reference: ``BoltArraySpark.astype`` via
        ``mapValues``; deferred like a map, so it fuses).  ``casting`` is
        validated against numpy's rules; the target dtype is canonicalised
        to what the backend holds (f64→f32 unless x64 is enabled)."""
        np.empty(0, dtype=self.dtype).astype(dtype, casting=casting)
        target = _canon(dtype)
        if self._split == 0:
            # value-shaped result of a reduction: no key axes to map over
            out = _cached_jit(
                ("astype0", self.shape, str(self.dtype), str(target), self._mesh),
                lambda: jax.jit(lambda d: d.astype(target)))(self._data)
            return self._wrap(out, 0)
        return self.map(lambda v: v.astype(target),
                        axis=tuple(range(self._split)))

    def cache(self):
        """Force materialisation of a deferred chain and keep the result
        resident (reference: ``BoltArraySpark.cache`` pins the
        lazily-computed RDD).  As a synchronous exit it is a fetch of
        one phase: ``array.fetch`` with ``array.fetch.force`` beneath
        (every program enqueued; the caller waits on the jax.Array)."""
        root = _obs.begin("array.fetch")
        sp = _obs.begin("array.fetch.force")
        try:
            self._data
        finally:
            _obs.end(sp)
            _obs.end(root)
        return self

    def unpersist(self):
        """Counterpart of :meth:`cache`; device residency is managed by
        jax, so this is a no-op for parity."""
        return self

    def repartition(self, npartitions):
        """Accepted for parity; the partition layout is the mesh and does
        not change per-array (reference: ``BoltArraySpark.repartition``)."""
        return self

    def __repr__(self):
        s = "BoltArray\n"
        s += "mode: %s\n" % self.mode
        if self._donated:
            # repr must never raise: a donated FILTER array has no aval,
            # so the shape/dtype properties below would hit the guard —
            # and printing an array is how users diagnose exactly that
            if self._aval is not None:
                s += "shape: %s\n" % str(tuple(self._aval.shape))
                s += "dtype: %s\n" % str(np.dtype(self._aval.dtype))
            s += "split: %d\n" % self._split
            s += "donated: buffer consumed by %s\n" % (
                self._donated if isinstance(self._donated, str)
                else "a donating swap or terminal")
            return s
        if self._fpending is not None:
            # don't dispatch the filter just to print; show what is known
            s += "shape: (%s)\n" % ", ".join(
                ["?"] + [str(d) for d in self._fpending.out.shape])
        elif self._pending is not None:
            # don't force the count sync just to print; show what is known
            s += "shape: (%s)\n" % ", ".join(
                ["?"] + [str(d) for d in self._pending[0].shape[1:]])
        else:
            s += "shape: %s\n" % str(self.shape)
        s += "split: %d\n" % self._split
        s += "dtype: %s\n" % str(self.dtype)
        if self.deferred:
            s += "deferred: %d-op map chain\n" % len(self._chain[1])
        elif self._spending is not None:
            # don't dispatch the fused group just to print
            s += "pending: lazy %s() terminal (fused group not yet " \
                 "dispatched)\n" % self._spending.name
        elif self._fpending is not None:
            s += "pending: deferred filter (predicate not yet dispatched)\n"
        elif self._pending is not None:
            s += "pending: filter count not yet synced\n"
        else:
            try:
                s += "sharding: %s\n" % str(self._concrete.sharding.spec)
            except Exception:
                pass
        return s
