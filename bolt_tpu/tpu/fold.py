"""The fold of a deferred filter (or a map chain) into a sum-like terminal:
ONE entry, :func:`fold_records`, that the four places which used to put
"records, mask, fold" together call (``tpu/array.py ::
_fused_filter_stat``, ``_grouped_fold``; ``tpu/multistat.py ::
_resolve_fpending``, alone and as a group), so what executes the fold is
chosen in one place.

There are two executors and nothing a caller sets picks between them.
Everywhere, today's expressions: ``_masked_stat_expr`` and
``_grouped_fold_expr`` over :meth:`_Filter.records`, which XLA fuses into
one pass.  On a table of THIN records (at most eight 32-bit values a
record) the chip lays the rows on the lanes and the columns on the
sublanes (``f32[300018951,7]{0,1:T(8,128)}``), and that one fusion streams
the whole table once for every column it reads: TPC-H Q6 names four
columns and took 53 ms where one read of the table is 13.4, Q1 names seven
and took 151 (PERF.md section 5, PR 30).  There the fold is the primitive
``thin_fold``, whose TPU lowering for one device is a Mosaic kernel that
streams the ``(c, rows)`` view of the table once (:func:`_thin_kernel`);
its lowering anywhere else (the CPU, a program for several chips outside
``shard_map``) is the same expressions as before.
"""

from functools import partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from bolt_tpu.utils import prod

_LANES = 128
_SUBLANES = 8
# XLA names the kernel's instruction, and so its event on the device
# trace, after this: per call the record that the kernel ran is the trace
_FOLD_KERNEL_NAME = "thin_fold"
# rows a grid step streams, and rows re-seated and folded in one go
# inside a step (each column read is ``_FOLD_CHUNK / 1024`` dense vregs,
# folded to one before it meets its accumulator).  From two sweeps on the
# chip over the benchmark's table (PERF.md section 6, PR 31; host clock
# around one call): (8, 65536) float32 is packed_gram's 2 MB step; Q6
# reads 13.4-13.6 ms at every size tried (the stream alone is 13.4); Q1
# 13.97 here, 15.2 at chunks of 1,024, 14.9 at 4,096, 17.6 at 16,384,
# 16.7 at blocks of 32,768, 14.0 at 131,072, and at 262,144 its blocks
# leave the scoped VMEM no room for the accumulators
_FOLD_BLOCK = 65536
_FOLD_CHUNK = 2048
# accumulators of (8, 128) a kernel may hold (Q1 has 36 sums and 6
# counts); past it the fold is many groups of few rows each, which is
# another algorithm's work
_FOLD_MAX_ACCUMULATORS = 64

_KERNEL_STATS = ("sum", "mean", "var", "std", "max", "min")


class Chain(NamedTuple):
    """What a grouped fold reads where no filter is deferred: the map
    chain ``funcs`` over the base, ``split`` key axes.  It answers what a
    fold asks of a :class:`_Filter`, with no predicate and no maps behind
    one."""

    funcs: tuple
    split: int
    pred = None
    post = ()

    def records(self, data):
        from bolt_tpu.tpu.array import _chain_apply
        return _chain_apply(self.funcs, self.split, data), None

    def mapped(self, data):
        """The records again, from an application of the maps that is the
        label's own, as a filter's readers have theirs
        (:meth:`_Filter.mapped`)."""
        from bolt_tpu.tpu.array import _chain_apply
        if self.funcs:
            data = jax.lax.optimization_barrier(data)
        return _chain_apply(self.funcs, self.split, data)


class Fold(NamedTuple):
    """One fold, everything but the buffer (hashable: a primitive's
    parameter).  ``source`` is a :class:`_Filter` without its base or a
    :class:`Chain`.  ``stats`` are slots ``(name, axes, keepdims, ddof)``
    of ``_masked_stat_expr`` (``count``: the survivors' count after
    them); or ``group`` is ``(op, label, value, nseg)`` of
    ``_grouped_fold_expr``."""

    source: object
    stats: tuple = ()
    count: bool = False
    group: tuple = None


def _plain(fold, data):
    """The fold by the expressions XLA fuses, as the call sites traced
    them before there was a kernel."""
    from bolt_tpu.tpu.array import _grouped_fold_expr, _masked_stat_expr
    src = fold.source
    flat, mask = src.records(data)
    if fold.group is not None:
        op, label, value, nseg = fold.group
        return _grouped_fold_expr(op, flat, mask, label, value, nseg,
                                  src.mapped(data))
    vshape = tuple(src.out.shape)
    mfull = mask.reshape((src.n,) + (1,) * len(vshape))
    cnt = jnp.sum(mask, dtype=jnp.int32) if fold.count else None
    outs = tuple(_masked_stat_expr(name, flat, mask, mfull, axes, keepdims,
                                   ddof, vshape, src.out.dtype)
                 for name, axes, keepdims, ddof in fold.stats)
    return outs + (cnt,) if fold.count else outs


def fold_records(fold, data):
    """The fold ``fold`` over the base ``data``, traced into the caller's
    program.  For ``stats`` the tuple of their results (and the int32
    count of survivors last, with ``count``); for ``group`` the pair
    ``(folded, counts)``.

    Where the records are thin and the fold is one the kernel makes
    (:func:`_kernel_serves`) this binds ``thin_fold`` and the program's
    target decides at lowering; everything else is today's expressions,
    traced here as they always were."""
    if not _kernel_serves(fold, jax.typeof(data)):
        return _plain(fold, data)
    out = _thin_fold_p.bind(data, fold=fold)
    tree = jax.tree.structure(jax.eval_shape(partial(_plain, fold), data))
    return jax.tree.unflatten(tree, out)


# ---------------------------------------------------------------------
# which folds the kernel makes: decided by what the code can see in its
# input (the stored base's shape and dtype, the fold's own geometry, the
# jaxpr of the caller's functions on a block)
# ---------------------------------------------------------------------

# the closed list: what a caller's function may be made of on ONE record
# for the kernel to run it over dense columns.  Element-wise arithmetic,
# compares and selects ...
_ELEMENTWISE = frozenset([
    "abs", "add", "and", "ceil", "clamp", "convert_element_type", "copy",
    "div", "eq", "exp", "exp2", "expm1", "floor", "ge", "gt", "integer_pow",
    "is_finite", "le", "log", "log1p", "logistic", "lt", "max", "min",
    "mul", "ne", "neg", "not", "or", "pow", "rem", "round", "rsqrt",
    "select_n", "sign", "sqrt", "square", "stop_gradient", "sub", "tanh",
    "xor"])
# ... over statically indexed columns: ways to take a record apart and to
# put a value together, which in the kernel are bookkeeping of whole vregs
_STRUCTURAL = frozenset(["slice", "dynamic_slice", "squeeze", "reshape",
                         "broadcast_in_dim", "concatenate"])
_CALLS = frozenset(["pjit", "jit", "closed_call", "core_call",
                    "custom_jvp_call"])


def _inner_jaxpr(eqn):
    inner = eqn.params.get("jaxpr", eqn.params.get("call_jaxpr"))
    return getattr(inner, "jaxpr", inner), getattr(inner, "consts", ())


def _record_jaxpr_fits(jaxpr, consts=()):
    """Whether a function's jaxpr on ONE record is made of the closed list
    alone (a gather, a sort, a reduction inside the record, a callback, a
    constant array or a traced index are not)."""
    from jax.extend.core import Literal
    if any(np.ndim(c) for c in consts):
        return False
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in _CALLS:
            if not _record_jaxpr_fits(*_inner_jaxpr(eqn)):
                return False
        elif name == "dynamic_slice":
            if not all(isinstance(v, Literal) for v in eqn.invars[1:]):
                return False
        elif name not in _ELEMENTWISE and name not in _STRUCTURAL:
            return False
    return True


def _dense(x, lifted, tail, shape=None):
    """``x`` of shape ``vs`` a record as an array ``shape + tail``
    (``shape``: ``vs`` or what it broadcasts to); as it is where it is one
    already (``lifted``)."""
    if lifted:
        return x
    x = jnp.asarray(x)
    shape = x.shape if shape is None else tuple(shape)
    return jnp.broadcast_to(x.reshape(x.shape + (1,) * len(tail)),
                            shape + tail)


def _dense_eval(jaxpr, consts, args, tail):
    """Evaluate a one-record ``jaxpr`` that :func:`_record_jaxpr_fits`
    over DENSE arguments: a value of shape ``vs`` a record is an array
    ``vs + tail`` (``tail`` the rows of a chunk as ``(rows / 128, 128)``),
    so every element-wise equation works on full vregs and every
    structural one touches the axes in front of ``tail`` alone; nothing is
    transposed.  A value no record reaches (a literal, ``ones_like``)
    stays as it is until it meets one: the results are pairs ``(value,
    whether it is dense)``."""
    from jax.extend.core import Literal
    nt = len(tail)
    env = {}

    def read(v):
        if isinstance(v, Literal):
            return np.asarray(v.val, v.aval.dtype), False
        return env[v]

    for var, c in zip(jaxpr.constvars, consts):
        env[var] = (c, False)
    for var, a in zip(jaxpr.invars, args):
        env[var] = a
    for eqn in jaxpr.eqns:
        name, params = eqn.primitive.name, dict(eqn.params)
        ins = [read(v) for v in eqn.invars]
        lifted = any(l for _, l in ins)
        if name in _CALLS:
            inner, inner_consts = _inner_jaxpr(eqn)
            outs = _dense_eval(inner, inner_consts, ins, tail)
        elif not lifted:
            out = eqn.primitive.bind(*[x for x, _ in ins], **params)
            outs = [(o, False) for o in
                    (out if eqn.primitive.multiple_results else [out])]
        elif name in _ELEMENTWISE:
            shape = eqn.outvars[0].aval.shape
            out = eqn.primitive.bind(
                *[_dense(x, l, tail, shape) for x, l in ins], **params)
            outs = [(out, True)]
        else:
            x = ins[0][0]
            rank = x.ndim - nt
            if name == "slice":
                strides = params["strides"]
                out = jax.lax.slice(
                    x, tuple(params["start_indices"]) + (0,) * nt,
                    tuple(params["limit_indices"]) + tail,
                    None if strides is None else tuple(strides) + (1,) * nt)
            elif name == "dynamic_slice":
                start = [int(i) for i, _ in ins[1:]]
                start = [max(0, min(i, x.shape[d] - size)) for d, (i, size)
                         in enumerate(zip(start, params["slice_sizes"]))]
                out = jax.lax.slice(
                    x, tuple(start) + (0,) * nt,
                    tuple(i + size for i, size in zip(
                        start, params["slice_sizes"])) + tail)
            elif name == "squeeze":
                out = jax.lax.squeeze(x, [d % rank for d in
                                          params["dimensions"]])
            elif name == "reshape":
                out = x.reshape(tuple(params["new_sizes"]) + tail)
            elif name == "broadcast_in_dim":
                out = jax.lax.broadcast_in_dim(
                    x, tuple(params["shape"]) + tail,
                    tuple(params["broadcast_dimensions"])
                    + tuple(range(len(params["shape"]),
                                  len(params["shape"]) + nt)))
            else:                                   # concatenate
                out = jax.lax.concatenate(
                    [_dense(x, l, tail) for x, l in ins],
                    params["dimension"])
            outs = [(out, True)]
        for var, o in zip(eqn.outvars, outs):
            env[var] = o
    return [read(v) for v in jaxpr.outvars]


def _plain_callables(funcs):
    from bolt_tpu.tpu.array import _Window, _WithKeysFunc
    return all(callable(f) and not isinstance(f, (_Window, _WithKeysFunc))
               for f in funcs)


def _kernel_serves(fold, aval):
    """Whether ``thin_fold`` takes this fold (what it lowers to is decided
    later, by the program's target).  A stored base ``(rows, c)`` of
    float32 or int32 with ``c <= 8``: one tile of sublanes, the layout
    that puts the rows on the lanes; one key axis, alone reduced; a
    statistic or a grouped op the kernel accumulates, with 32-bit results;
    and functions that on a block are element-wise arithmetic, compares
    and selects over statically indexed columns.  Fat records, other
    dtypes, a predicate that gathers, sorts, reduces inside the record or
    calls back: the fusion."""
    from bolt_tpu.tpu.array import _FOLDS
    src = fold.source
    if (len(aval.shape) != 2 or aval.shape[1] > _SUBLANES
            or aval.dtype not in (jnp.float32, jnp.int32)
            or aval.shape[0] < _FOLD_CHUNK or src.split != 1):
        return False
    if not _plain_callables(src.funcs + src.post):
        return False
    if fold.group is not None:
        if fold.group[0] not in _FOLDS:
            return False
    elif not fold.stats or any(
            name not in _KERNEL_STATS or tuple(axes) != (0,)
            for name, axes, _, _ in fold.stats):
        return False
    try:
        plan = _block_plan(fold, aval)
    except Exception:       # the caller's functions do not trace so:
        return False        # today's expressions say what is wrong
    if (plan is None
            or sum(k for _, _, k in plan.accs) > _FOLD_MAX_ACCUMULATORS
            or not all(dt in (jnp.float32, jnp.int32)
                       for _, dt, _ in plan.accs)):
        return False
    outs = jax.tree.leaves(jax.eval_shape(partial(_plain, fold), aval))
    return all(o.dtype in (jnp.float32, jnp.int32) for o in outs)


# ---------------------------------------------------------------------
# the fold on a block: the caller's functions traced over dense columns
# ---------------------------------------------------------------------

class _Plan(NamedTuple):
    """A fold as the kernel runs it.  ``terms(block, live)`` takes the
    columns of a chunk re-seated, ``(c, s, 128)``, and which of its rows
    are rows of the table, ``(s, 128)``; it gives one array a part, each
    ``(k, s, 128)`` with every row that takes no part (dropped by the
    predicate, outside every group, not live) folded onto the part's
    identity; ``accs`` are the parts' ``(fold, dtype,
    k)`` with fold one of ``"sum"``, ``"max"``, ``"min"``;
    ``finish(sums)`` takes the parts reduced to ``(k,)`` each and gives
    :func:`_plain`'s results."""

    terms: object
    accs: tuple
    finish: object


def _per_record(fold):
    """``record -> (keep, gid, leaves)`` on ONE stored record: the maps in
    front, the predicate's verdict (``None``: a chain), the group (``None``:
    a statistic) and the value."""
    from bolt_tpu.tpu.array import _chain_apply
    src = fold.source

    def one(r):
        m = _chain_apply(src.funcs, 0, r)
        keep = None if src.pred is None else jnp.asarray(
            src.pred(m), bool).reshape(())
        v = _chain_apply(src.post, 0, m)
        if fold.group is None:
            return keep, None, v
        _, label, value, _ = fold.group
        gid = jnp.asarray(label(v)).astype(jnp.int32).reshape(())
        return keep, gid, v if value is None else value(v)
    return one


def _block_plan(fold, aval):
    """The :class:`_Plan` of ``fold`` over a base of ``aval``; ``None``
    where the caller's functions on one record are not of the closed
    list."""
    from bolt_tpu.tpu.array import (_fold_identity_value as identity,
                                    _moments, _stat_dtype)
    one = _per_record(fold)
    rec = jax.ShapeDtypeStruct(aval.shape[1:], aval.dtype)
    closed, out_a = jax.make_jaxpr(one, return_shape=True)(rec)
    if not _record_jaxpr_fits(closed.jaxpr, closed.consts):
        return None
    keep_a, leaf_a = out_a[0], jax.tree.leaves(out_a[2])
    shapes = [tuple(lf.shape) for lf in leaf_a]
    filtered = keep_a is not None

    def columns(block):
        """Leaves as ``(k, s, 128)``, the verdict and group ``(s, 128)``."""
        tail = block.shape[1:]
        outs = _dense_eval(closed.jaxpr, closed.consts, [(block, True)],
                           tail)
        keep, gid, value = jax.tree.unflatten(
            jax.tree.structure(out_a),
            [_dense(x, lifted, tail) for x, lifted in outs])
        return keep, gid, [lf.reshape((-1,) + tail)
                           for lf in jax.tree.leaves(value)]

    if fold.group is not None:
        op, _, _, nseg = fold.group
        fop = "sum" if op == "mean" else op
        dts = [lf.dtype for lf in leaf_a]
        if op == "mean":
            dts = [dt if jnp.issubdtype(dt, jnp.inexact)
                   else jax.dtypes.canonicalize_dtype(np.float64)
                   for dt in dts]
        sizes = [prod(s) for s in shapes]

        def terms(block, live):
            keep, gid, leaves = columns(block)
            gid = jnp.where(keep & live if filtered else live, gid, nseg)
            hits = [gid == k for k in range(nseg)]
            parts = []
            for lf, dt in zip(leaves, dts):
                lf = lf.astype(dt)
                parts.append(jnp.concatenate(
                    [jnp.where(hit[None], lf, identity(fop, dt))
                     for hit in hits]))
            parts.append(jnp.stack([hit.astype(jnp.int32) for hit in hits]))
            return parts

        def finish(sums):
            counts = sums[-1]
            folded = []
            for out, shape in zip(sums[:-1], shapes):
                out = out.reshape((nseg,) + shape)
                if op == "mean":
                    out = out / jnp.maximum(counts, 1).astype(
                        out.dtype).reshape((nseg,) + (1,) * len(shape))
                folded.append(out)
            return jax.tree.unflatten(jax.tree.structure(out_a[2]),
                                      folded), counts

        accs = tuple((fop, dt, nseg * k) for dt, k in zip(dts, sizes))
        accs += (("sum", jnp.dtype(jnp.int32), nseg),)
        return _Plan(terms, accs, finish)

    (shape,), (leaf,) = shapes, leaf_a
    # the parts the slots need, each made once: the survivors' sum in the
    # value's own dtype ("sum"), in the result's ("mean", the moments),
    # of squares, the extremes, the count
    wanted, outs, count = [], [], ("sum", "count", jnp.int32)
    for name, _, keepdims, ddof in fold.stats:
        out_dt = _stat_dtype(name, (0,), shape, leaf.dtype)
        if name in ("sum", "max", "min"):
            keys = [(name, "value", leaf.dtype)]
        else:
            keys = [("sum", "cast", out_dt), count]
            if name != "mean":
                keys.append(("sum", "square", out_dt))
        for key in keys:
            if key not in wanted:
                wanted.append(key)
        outs.append((name, keepdims, ddof, out_dt, keys))
    if fold.count and count not in wanted:
        wanted.append(count)

    def terms(block, live):
        keep, _, (v,) = columns(block)
        keep = keep & live
        parts = []
        for fop, what, dt in wanted:
            if what == "count":
                parts.append(keep.astype(jnp.int32)[None])
                continue
            x = jnp.where(keep[None], v, identity(fop, v.dtype))
            if what != "value":
                x = x.astype(dt)
            parts.append(x * x if what == "square" else x)
        return parts

    def finish(sums):
        got = dict(zip(wanted, sums))
        res = []
        for name, keepdims, ddof, out_dt, keys in outs:
            first = got[keys[0]].reshape(((1,) if keepdims else ()) + shape)
            if name in ("sum", "max", "min"):
                res.append(first.astype(out_dt))
                continue
            den = got[keys[1]].reshape(()).astype(out_dt)
            second = got[keys[2]].reshape(first.shape) \
                if name != "mean" else None
            res.append(_moments(name, first, second, den, ddof))
        if fold.count:
            res.append(got[count].reshape(()))
        return tuple(res)

    accs = tuple((fop, jnp.dtype(dt), 1 if what == "count" else prod(shape))
                 for fop, what, dt in wanted)
    return _Plan(terms, accs, finish)


# ---------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------

_REDUCE = {"sum": jnp.sum, "max": jnp.max, "min": jnp.min}


def _thin_kernel(x_ref, *acc_refs, terms, accs, rows, block, chunk):
    """One grid step: ``block`` rows of every column, ``x_ref`` ``(c,
    block)`` with column ``j`` on sublane ``j`` of every tile, folded into
    the accumulators ``acc_refs`` (``(8 k, 128)`` a part: one running
    value a lane position of a part's every row), which stay in VMEM over
    the grid.

    A chunk at a time each column is RE-SEATED to dense vregs (sublane
    ``j`` of eight consecutive tiles into one ``(8, 128)``), so the
    caller's compares and the accumulations work on eight sublanes and
    not one; ``terms`` is traced on that.  Rows past the table's end (the
    last step's ragged tail, whose tiles hold whatever was there) take no
    part, by row index, like rows the predicate drops."""
    from jax.experimental import pallas as pl
    from bolt_tpu.tpu.array import _FOLDS, _fold_identity_value
    step = pl.program_id(0)
    c = x_ref.shape[0]
    s = chunk // _LANES

    @pl.when(step == 0)
    def _():
        for ref, (op, dt, _) in zip(acc_refs, accs):
            ref[...] = jnp.full(ref.shape, _fold_identity_value(op, dt), dt)

    at = jax.lax.broadcasted_iota(jnp.int32, (s, _LANES), 0) * _LANES \
        + jax.lax.broadcasted_iota(jnp.int32, (s, _LANES), 1)

    def fold_chunk(i, carry):
        start = pl.multiple_of(i * chunk, chunk)
        cols = jnp.stack([
            x_ref[j, pl.ds(start, chunk)].reshape(s, _LANES)
            for j in range(c)])
        live = at < rows - step * block - start
        for ref, part, (op, _, k) in zip(acc_refs, terms(cols, live), accs):
            part = _REDUCE[op](part.reshape(k, s // _SUBLANES, _SUBLANES,
                                            _LANES), axis=1)
            ref[...] = _FOLDS[op](ref[...],
                                  part.reshape(k * _SUBLANES, _LANES))
        return carry

    jax.lax.fori_loop(0, block // chunk, fold_chunk, None)


def _kernel_fold(fold, data):
    """:func:`_plain` where the kernel can be placed: the ``(c, rows)``
    view of the table (a bitcast on the chip's layout, rows on the lanes)
    streamed once on a one-axis grid, then the small finish (the
    cross-lane reductions, the quotients, the stacking) as XLA ops of the
    same program."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    rows, c = data.shape
    chunk = _FOLD_CHUNK
    block = min(_FOLD_BLOCK, rows // chunk * chunk)
    plan = _block_plan(fold, jax.typeof(data))
    sums = pl.pallas_call(
        partial(_thin_kernel, terms=plan.terms, accs=plan.accs, rows=rows,
                block=block, chunk=chunk),
        out_shape=[jax.ShapeDtypeStruct((k * _SUBLANES, _LANES), dt)
                   for _, dt, k in plan.accs],
        grid=(-(-rows // block),),
        in_specs=[pl.BlockSpec((c, block), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((k * _SUBLANES, _LANES), lambda i: (0, 0))
                   for _, _, k in plan.accs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=_FOLD_KERNEL_NAME,
    )(data.T)
    sums = [_REDUCE[op](acc.reshape(k, _SUBLANES * _LANES), axis=1)
            for acc, (op, _, k) in zip(sums, plan.accs)]
    return plan.finish(sums)


def _fold_primitive():
    """``thin_fold``: a :class:`Fold` over a stored table of thin records,
    its results as a flat tuple.  A primitive because its executor is
    chosen when a program is LOWERED, as ``gram_products``' and
    ``jacobi_sweeps``' are (``ops/linalg.py``): in a program for one TPU
    device, or inside a fully manual ``shard_map``, :func:`_kernel_fold`;
    on the CPU and in a program for several chips outside ``shard_map``
    :func:`_plain`, the expressions the call sites traced before."""
    from jax._src import dispatch       # eager calls: jax's own cache
    from jax.extend.core import Primitive
    from jax.interpreters import mlir
    prim = Primitive("thin_fold")
    prim.multiple_results = True
    prim.def_impl(partial(dispatch.apply_primitive, prim))

    @prim.def_abstract_eval
    def _(data, *, fold):
        return [jax.core.ShapedArray(o.shape, o.dtype) for o in
                jax.tree.leaves(jax.eval_shape(partial(_plain, fold), data))]

    def lower(fits):
        def rule(ctx, data, *, fold):
            kernel = fits(ctx)
            if kernel:
                from bolt_tpu import engine
                engine.record_fold_kernel_program()
            fn = _kernel_fold if kernel else _plain
            # Mosaic has no 64-bit types (see ``jacobi_sweeps``)
            with jax.enable_x64(jax.config.jax_enable_x64 and not kernel):
                return mlir.lower_fun(
                    lambda x: jax.tree.leaves(fn(fold, x)),
                    multiple_results=True)(ctx, data)
        return rule

    mlir.register_lowering(prim, lower(lambda ctx: False))

    def fits(ctx):
        from bolt_tpu.ops.linalg import _mosaic_fits
        return _mosaic_fits(ctx)

    mlir.register_lowering(prim, lower(fits), platform="tpu")
    return prim


_thin_fold_p = _fold_primitive()
