"""Segmented (grouped) reductions over the key axis.

The Spark ecosystem around the reference does this with
``reduceByKey``/``aggregateByKey`` — re-key records by a label, shuffle,
combine per group.  On TPU the whole thing is ONE compiled program:
``jax.ops.segment_*`` lowers to scatter-add/min/max, GSPMD inserts the
cross-shard combine, and the result comes back as a bolt array keyed by
group id.  Extension beyond the reference (``bolt/spark/array.py``
exposes no grouped reduction; symbol-level cite, SURVEY §0).
"""

import numpy as np

import jax
import jax.numpy as jnp

from bolt_tpu._compat import shard_map as _shard_map

_OPS = ("sum", "mean", "max", "min")


@jax.jit  # lint: allow(BLT101 one module-level program, keyed on ONE aval)
def _minmax_program(lab):
    # module-level jit: ONE compiled program per label aval (a per-call
    # inner @jax.jit would recompile every call — jit keys on function
    # identity; measured 1.09 s vs 0.11 s per segment_reduce on chip).
    # Deliberately NOT engine-routed: the engine key would have to carry
    # the aval this jit already keys on, for a two-scalar program with
    # nothing to donate or persist.
    return jnp.min(lab), jnp.max(lab)


def _label_minmax(labels):
    """``(min, max)`` of a device labels array as Python ints — ONE host
    sync of two scalars (the data itself never leaves the device)."""
    mn, mx = jax.device_get(_minmax_program(labels))
    return int(mn), int(mx)


def segment_reduce(b, labels, num_segments=None, op="sum", method=None,
                   precision=None, value=None, return_counts=False):
    """Reduce the records of ``b`` (leading key axis) into groups given by
    ``labels``: record ``i`` joins group ``labels[i]``, and group ``g``'s
    result is the ``op``-combine of its records — the ``reduceByKey``
    analog, one compiled program.

    ``labels`` is either the group of every record, as an array, or a
    FUNCTION of the record that gives it.

    **A label function** (``record -> integer``, jax-traceable like
    ``filter``'s predicate; ``num_segments`` is then required) is traced
    into the same program as everything else, so nothing record-sized is
    made for it: ``b`` may be a deferred ``filter`` (with record-wise
    maps behind it), whose predicate, maps, labels and fold are then ONE
    pass that never builds the survivors — SQL's ``WHERE .. GROUP BY ..``
    with its aggregates::

        sums, counts = segment_reduce(
            b.filter(shipped_by), labels=flag_and_status, num_segments=6,
            value=lambda r: (r[QTY], r[PRICE], r[PRICE] * (100 - r[DISC])),
            return_counts=True)

    ``value`` (label functions only) is what a record adds to its group:
    a function of the record returning an array or a TUPLE of arrays
    (one aggregate each; the result is then a tuple of bolt arrays in
    the same order); the record itself where ``None``.  On a table of
    thin records give the aggregates as a tuple of scalars and not as
    one stacked vector: the chip holds such a table with the rows on the
    lanes, where a stacked value is written out row-sized before it is
    folded and a tuple of scalars is not (PERF.md, PR 30).  A record
    whose label falls outside ``[0, num_segments)`` joins no group.
    Over a stored table of thin records (``(rows, c)`` float32 or int32,
    ``c <= 8``) with an element-wise predicate, label and value the fold
    is the Mosaic kernel ``thin_fold`` in a program for one TPU device
    (``tpu/fold.py``, engine counter ``fold_kernel_programs``): one
    stream of the table, float sums whose last digits differ from the
    fusion's (more running sums), every count exact.

    **A label array**: 1-d integers of length ``b.shape[0]``.  A host
    sequence / ndarray ships to the device once; a ``jax.Array`` (or a
    bolt TPU array) STAYS on device — range validation is one two-scalar
    sync, the label data itself never round-trips through the host.
    ``num_segments``: static group count (defaults to ``labels.max() + 1``
    — free on host labels, part of the same two-scalar sync on device
    labels).

    Groups with no records get ``0`` for sum/mean and the dtype's
    identity (∓inf → the op's init) for max/min, matching
    ``jax.ops.segment_max/min``.  ``op='mean'`` on integer input promotes
    through the canonical float (float64 under x64, float32 on a
    production x64-off TPU) on BOTH backends, so the backends agree under
    either x64 setting.
    Returns a bolt array shaped ``(num_segments, *value_shape)`` with
    ``split=1`` (``mode='local'`` computes the same thing in NumPy);
    with ``return_counts=True`` a pair of it and the exact int32 count of
    records in every group, ``(num_segments,)`` — what a mean divides by,
    and ``COUNT(*)``.

    ``method`` (label arrays only): ``None``/``"auto"`` (default) picks
    per a cost model; ``"scatter"`` forces the ``jax.ops.segment_*``
    scatter combine; ``"matmul"`` forces the one-hot MXU form (sum/mean
    of floating data only).  The matmul form computes ``onehot(labels) @
    X`` — small segment counts turn the memory-latency-bound scatter
    into one MXU matmul; the (nseg, n) one-hot is a tensor of its own,
    so the form is capped at the data's size.  Neither form has a cell
    in the benchmark and no speed is stated for them; the label-function
    fold is what ``lineitem-1chip.q1q6`` measures (PERF.md).  Products
    against a 0/1 matrix are exact, so "highest" matches the scatter
    combine to f32 round-off.  Non-finite records would
    poison whole value columns through ``0 x NaN``, so the program
    guards with one fused ``isfinite`` test and falls back to the
    scatter combine at runtime when any record is non-finite —
    numpy/scatter semantics always.  ``precision=None`` resolves
    through the scoped policy (``bolt.precision``), pinned "highest".
    """
    if op not in _OPS:
        raise ValueError("op must be one of %s, got %r" % (_OPS, op))
    if method not in (None, "auto", "scatter", "matmul"):
        raise ValueError("method must be 'auto', 'scatter' or 'matmul', "
                         "got %r" % (method,))
    # op/dtype eligibility for the forced matmul form validates up front
    # — BEFORE the backend split, so both backends reject identically
    _float_in = np.issubdtype(np.dtype(b.dtype), np.floating) or (
        op == "mean" and np.issubdtype(np.dtype(b.dtype), np.integer))
    if method == "matmul" and (op not in ("sum", "mean") or not _float_in):
        raise ValueError(
            "method='matmul' serves sum/mean of real floating (or "
            "int-mean) data only, got op=%r dtype=%s" % (op, b.dtype))
    if callable(labels):
        if method not in (None, "auto"):
            raise ValueError("method=%r chooses between the forms that take "
                             "a label ARRAY; a label function has one form"
                             % (method,))
        if num_segments is None:
            raise ValueError("a label function needs num_segments: the "
                             "group count is static")
        out = _fold_by_function(b, labels, value, int(num_segments), op)
        return out if return_counts else out[0]
    if value is not None:
        raise ValueError("value= goes with a label function; with a label "
                         "array, map the records first (b.map(value))")
    from bolt_tpu._precision import resolve
    pr = resolve(precision)
    from bolt_tpu.base import BoltArray
    if b.mode == "tpu":
        labels = b._coerce_bolt_operand(labels, "segment_reduce labels")
    elif isinstance(labels, BoltArray):
        labels = np.asarray(labels)
    device_labels = isinstance(labels, jax.Array) and b.mode == "tpu"
    if not device_labels:
        labels = np.asarray(labels)
    if labels.ndim != 1 or not np.issubdtype(
            np.dtype(labels.dtype), np.integer):
        raise ValueError("labels must be 1-d integers, got shape %s dtype %s"
                         % (labels.shape, labels.dtype))
    n = b.shape[0]
    if labels.shape[0] != n:
        raise ValueError("labels length %d != leading axis %d"
                         % (labels.shape[0], n))
    if device_labels:
        lmin, lmax = _label_minmax(labels) if labels.size else (0, -1)
    else:
        lmin = int(labels.min()) if labels.size else 0
        lmax = int(labels.max()) if labels.size else -1
    if labels.size and lmin < 0:
        raise ValueError("labels must be non-negative")
    if num_segments is None:
        num_segments = lmax + 1 if labels.size else 0
    num_segments = int(num_segments)
    if labels.size and lmax >= num_segments:
        raise ValueError("label %d out of range for num_segments=%d"
                         % (lmax, num_segments))

    if b.mode == "local":
        x = np.asarray(b)
        vshape = x.shape[1:]
        if op in ("sum", "mean"):
            if op == "mean" and not np.issubdtype(x.dtype, np.floating):
                # mean of ints is floating — promote through the CANONICAL
                # float (f64 under x64, f32 otherwise) so this oracle and
                # the TPU path return the same dtype under either setting
                x = x.astype(jax.dtypes.canonicalize_dtype(np.float64))
            out = np.zeros((num_segments,) + vshape, x.dtype)
            np.add.at(out, labels, x)
            if op == "mean":
                cnt = np.bincount(labels, minlength=num_segments)
                out = out / np.maximum(cnt, 1).reshape(
                    (num_segments,) + (1,) * len(vshape)).astype(x.dtype)
        else:
            if np.issubdtype(x.dtype, np.floating):
                init = -np.inf if op == "max" else np.inf
            else:                           # empty-group identity for ints
                info = np.iinfo(x.dtype)
                init = info.min if op == "max" else info.max
            out = np.full((num_segments,) + vshape, init, x.dtype)
            ufunc = np.maximum if op == "max" else np.minimum
            ufunc.at(out, labels, x)
        from bolt_tpu.local.array import BoltArrayLocal
        if return_counts:
            return BoltArrayLocal(out), BoltArrayLocal(np.bincount(
                labels, minlength=num_segments).astype(np.int32))
        return BoltArrayLocal(out)

    from bolt_tpu.tpu.array import (BoltArrayTPU, _cached_jit, _chain_apply,
                                    _check_live, _constrain)
    base, funcs = b._chain_parts()
    split = b.split
    mesh = b.mesh

    # cost-model gate for the one-hot MXU form (docstring numbers):
    #   matmul ~ 2 * nseg * size flops at the MXU's effective rate per
    #   precision mode, PLUS the materialised (nseg, n) one-hot's own
    #   HBM traffic; scatter ~ bytes at its measured ~150 GB/s upper
    #   band.  Only sum/mean of real floating data qualify (ints must
    #   stay exact, complex has no bf16 path, max/min cannot matmul).
    #   Thin-value/many-record inputs make the one-hot the dominant
    #   tensor, so it is capped at the data's own size (and demand-
    #   checked) before the flop model even gets a vote.
    item = np.dtype(b.dtype).itemsize
    oh_item = 2 if np.dtype(b.dtype) == np.float32 else item
    oh_bytes = float(num_segments) * n * oh_item
    data_bytes = float(b.size) * item
    mxu_eff = {"default": 1.0e14, "high": 6.0e13, "highest": 3.0e13}[pr]
    est_matmul = (2.0 * num_segments * b.size / mxu_eff
                  + oh_bytes / 6.0e11)
    est_scatter = data_bytes / 1.5e11
    if method == "matmul" and n > 0:
        from bolt_tpu.tpu.array import hbm_check
        hbm_check("segment_reduce matmul",
                  int(data_bytes + oh_bytes
                      + num_segments * (b.size // max(n, 1)) * item),
                  "input + one-hot + output")
    use_matmul = (method == "matmul" or (
        method in (None, "auto") and op in ("sum", "mean") and _float_in
        and num_segments > 0 and oh_bytes <= data_bytes
        and est_matmul < est_scatter)) and n > 0

    def build():
        seg = {"sum": jax.ops.segment_sum, "mean": jax.ops.segment_sum,
               "max": jax.ops.segment_max, "min": jax.ops.segment_min}[op]

        def promote(flat):
            if op == "mean" and not jnp.issubdtype(flat.dtype,
                                                   jnp.floating):
                # mean of ints is floating (f64 under x64, like numpy)
                return flat.astype(
                    jax.dtypes.canonicalize_dtype(np.float64))
            return flat

        def scatter_out(flat, lab):
            out = seg(flat, lab, num_segments=num_segments)
            return mean_divide(out, lab) if op == "mean" else out

        def matmul_sum(flat, lab):
            # onehot(labels) @ X: 0/1 products are exact, so "highest"
            # matches the scatter combine to f32 round-off; GSPMD
            # shards the contraction over the key axis and all-reduces
            # the (nseg, V) partials over ICI.  The one-hot rides bf16
            # against f32 data (0/1 is exact in bf16, and the narrow
            # operand halves its MXU passes — the measured-321-GB/s
            # configuration); other dtypes keep their own width.
            oh_dt = jnp.bfloat16 if flat.dtype == jnp.float32 \
                else flat.dtype
            oh = (lab[None, :] ==
                  jnp.arange(num_segments, dtype=jnp.int32)[:, None]
                  ).astype(oh_dt)
            v2d = flat.reshape((n, -1))
            out = jax.lax.dot_general(
                oh, v2d, (((1,), (0,)), ((), ())), precision=pr,
                preferred_element_type=flat.dtype)
            return out.reshape((num_segments,) + flat.shape[1:])

        def mean_divide(out, lab):
            cnt = jax.ops.segment_sum(
                jnp.ones((n,), out.dtype), lab,
                num_segments=num_segments)
            return out / jnp.maximum(cnt, 1).reshape(
                (num_segments,) + (1,) * (out.ndim - 1))

        def run(data, lab):
            # records = axis-0 groups, like the labels contract; further
            # key axes just ride along in the value block (the local
            # oracle path flattens identically)
            lab = lab.astype(jnp.int32)
            flat = promote(_chain_apply(funcs, split, data))
            if use_matmul:
                # 0 x NaN poisons whole value columns through the
                # one-hot, so a non-finite RECORD always surfaces as a
                # non-finite OUTPUT entry (and a finite-input partial-
                # sum overflow surfaces as Inf/NaN) — checking the
                # small (nseg, V) RESULT costs ~nothing where a
                # pre-pass over the input would re-read all of HBM
                # serially (measured 9.0 -> 6.9 ms on the perf family).
                # Any hit recomputes with the exact scatter combine
                # (numpy non-finite semantics) at runtime.
                s = matmul_sum(flat, lab)
                ok = jnp.all(jnp.isfinite(s))
                out = jax.lax.cond(
                    ok, lambda f, l, sm: sm,
                    lambda f, l, sm: seg(f, l,
                                         num_segments=num_segments),
                    flat, lab, s)
                if op == "mean":
                    out = mean_divide(out, lab)
            else:
                out = scatter_out(flat, lab)
            out = _constrain(out, mesh, 1)
            if return_counts:
                return out, _constrain(jax.ops.segment_sum(
                    jnp.ones((n,), jnp.int32), lab,
                    num_segments=num_segments), mesh, 1)
            return out
        return jax.jit(run)

    # labels is a traced argument (its length is pinned by base.shape), so
    # distinct label vectors REUSE one compiled program — never key on
    # label content; device labels pass through untouched (the int32 cast
    # happens inside the program — no host round-trip)
    fn = _cached_jit(("segreduce", op, funcs, base.shape, str(base.dtype),
                      split, num_segments, mesh, use_matmul,
                      pr if use_matmul else None, return_counts), build)
    lab = labels if device_labels else jnp.asarray(labels, dtype=jnp.int32)
    out = fn(_check_live(base), lab)
    if return_counts:
        return BoltArrayTPU(out[0], 1, mesh), BoltArrayTPU(out[1], 1, mesh)
    return BoltArrayTPU(out, 1, mesh)


def _fold_by_function(b, label, value, num_segments, op):
    """``segment_reduce`` by a label function: ``(folded, counts)``.  On
    the TPU backend the terminal ``BoltArrayTPU._grouped_fold`` (one
    program; a deferred filter folded in); on the local backend the same
    semantics record by record in NumPy, the oracle."""
    if num_segments < 0:
        raise ValueError("num_segments must not be negative")
    if b.mode == "tpu":
        from bolt_tpu.tpu.array import _TRACE_ERRORS, _warn_fallback
        try:
            return b._grouped_fold(label, value, num_segments, op)
        except _TRACE_ERRORS as exc:
            # a label or value function that does not trace: the local
            # oracle answers, as for a predicate that does not
            _warn_fallback("segment_reduce", label, exc)
            folded, counts = _fold_by_function(
                b.tolocal(), label, value, num_segments, op)
            totpu = lambda a: b._constructor.array(      # noqa: E731
                np.asarray(a), context=b.mesh, axis=(0,))
            return jax.tree_util.tree_map(totpu, folded), totpu(counts)
    from bolt_tpu.local.array import BoltArrayLocal
    x = np.asarray(b)
    recs = list(x)
    labs = np.asarray([int(np.asarray(label(r)).reshape(())) for r in recs],
                      dtype=np.int64).reshape((-1,))
    vals = recs if value is None else [value(r) for r in recs]
    # the value's structure: from a record, or where there is none from
    # the function over zeros
    probe = vals[0] if vals else np.zeros(x.shape[1:], x.dtype) \
        if value is None else value(np.zeros(x.shape[1:], x.dtype))
    zeros, tree = jax.tree_util.tree_flatten(probe)
    keep = (labs >= 0) & (labs < num_segments)
    # every leaf is a label-ARRAY reduction of the records that have a
    # group: the oracle above answers it
    folded = [segment_reduce(BoltArrayLocal(np.asarray(
        [np.asarray(jax.tree_util.tree_leaves(v)[i]) for v in vals]).reshape(
            (len(recs),) + np.shape(z))[keep]), labs[keep], num_segments, op)
        for i, z in enumerate(zeros)]
    counts = np.bincount(labs[keep], minlength=num_segments).astype(np.int32)
    return (jax.tree_util.tree_unflatten(tree, folded),
            BoltArrayLocal(counts))


def _topk_desc(xp, moved, k):
    """Largest ``k`` along the LAST axis, descending, with
    ``lax.top_k``'s exact tie/NaN semantics, for either array module
    (``np`` on the oracle, ``jnp`` on device) — ONE algorithm on both
    backends, and the formulation GSPMD partitions without gathering
    (``lax.top_k`` itself all-gathers a sharded operand; a stable
    argsort along an unsharded last axis is collective-free, and along
    a sharded axis lowers to all-to-all — see tests/test_lowering.py).

    Descending order WITHOUT negating (negation wraps unsigned/INT_MIN
    and rejects bools): stable-ascending-argsort the index-reversed
    array (ties there resolve to the HIGHER original index), map back,
    reverse — descending, ties to the LOWER index, NaNs first
    (largest)."""
    L = moved.shape[-1]
    if xp is np:
        idx_rev = np.argsort(moved[..., ::-1], axis=-1, kind="stable")
    else:
        idx_rev = xp.argsort(moved[..., ::-1], axis=-1, stable=True)
    desc = (L - 1 - idx_rev)[..., ::-1]
    idx = desc[..., :k]
    return xp.take_along_axis(moved, idx, axis=-1), idx


def topk(b, k, axis=-1):
    """Largest ``k`` values (descending) and their indices along ``axis``
    — ``jax.lax.top_k`` semantics, one compiled program; returns
    ``(values, indices)`` bolt arrays whose ``axis`` dimension becomes
    ``k``.  Ties keep the lower index first, like ``lax.top_k`` (numpy
    has no direct analog; ``argpartition`` leaves ties unordered).
    ``mode='local'`` computes the same thing in NumPy (including
    ``lax.top_k``'s NaN-is-largest ordering)."""
    from numbers import Integral
    if not isinstance(k, Integral):
        raise TypeError("k must be an integer, got %r" % (k,))
    k = int(k)
    ndim = b.ndim
    if not isinstance(axis, (int, np.integer)):
        raise TypeError("axis must be an integer, got %r" % (axis,))
    axis = int(axis)
    if axis < 0:
        axis += ndim
    if axis < 0 or axis >= ndim:
        raise ValueError("axis out of range for %d-d array" % ndim)
    if not 1 <= k <= b.shape[axis]:
        raise ValueError("k=%d out of range for axis of size %d"
                         % (k, b.shape[axis]))

    if b.mode == "local":
        x = np.asarray(b)
        moved = np.moveaxis(x, axis, -1)
        vals, idx = _topk_desc(np, moved, k)
        from bolt_tpu.local.array import BoltArrayLocal
        return (BoltArrayLocal(np.moveaxis(vals, -1, axis)),
                BoltArrayLocal(np.moveaxis(idx, -1, axis)))

    from bolt_tpu.tpu.array import (_CHUNK_MAX_BYTES, BoltArrayTPU,
                                    _cached_jit, _chain_apply, _check_live,
                                    _constrain, hbm_check)
    base, funcs = b._chain_parts()
    split = b.split
    mesh = b.mesh
    # the axis keeps its key/value role (its size becomes k; a
    # non-dividing key size just falls back to replication in the spec)

    # memory model: _topk_desc materialises the (possibly transposed)
    # operand, its reversed view, and an input-sized argsort index
    # array; at HBM scale a non-last ``axis`` is bounded by slabbing
    # along another axis (outputs are k-sized — small — so the
    # reassembly concatenate is cheap).  VERDICT r2 weak-4.
    idx_item = np.dtype(jax.dtypes.canonicalize_dtype(np.int64)).itemsize
    in_bytes = int(np.prod(b.shape)) * np.dtype(b.dtype).itemsize
    idx_bytes = int(np.prod(b.shape)) * idx_item
    if axis != ndim - 1 and in_bytes > _CHUNK_MAX_BYTES:
        out = _topk_chunked(b, k, axis, in_bytes)
        if out is not None:
            return out
    hbm_check("topk", 2 * in_bytes + idx_bytes,
              "input + reversed/transposed copy + argsort index array")

    def build():
        def run(data):
            x = _chain_apply(funcs, split, data)
            moved = jnp.moveaxis(x, axis, -1)
            vals, idx = _topk_desc(jnp, moved, k)
            return (_constrain(jnp.moveaxis(vals, -1, axis), mesh, split),
                    _constrain(jnp.moveaxis(idx, -1, axis), mesh, split))
        return jax.jit(run)

    vals, idx = _cached_jit(
        ("topk", funcs, base.shape, str(base.dtype), split, axis, k, mesh),
        build)(_check_live(base))
    return (BoltArrayTPU(vals, split, mesh),
            BoltArrayTPU(idx, split, mesh))


def _topk_chunked(b, k, axis, in_bytes):
    """HBM-bounded topk over a non-last axis: slab along another axis so
    the transposed copy lax.top_k needs never exceeds a slab; per-slab
    (k-sized) results concatenate back along the slab axis.  Returns
    None when no other axis can carry the slabbing."""
    import jax
    import jax.numpy as jnp
    from bolt_tpu.tpu.array import (BoltArrayTPU, _cached_jit, _constrain,
                                    hbm_check, slab_plan)
    plan = slab_plan(b.shape, axis, in_bytes)
    if plan is None:
        return None
    cax, pairs = plan
    slab_bytes = in_bytes // len(pairs)
    idx_item = np.dtype(jax.dtypes.canonicalize_dtype(np.int64)).itemsize
    hbm_check("topk", in_bytes + 2 * slab_bytes
              + (slab_bytes // np.dtype(b.dtype).itemsize) * idx_item,
              "input + per-slab transposed copy + per-slab argsort index")
    data = b._data                          # chain materialises once
    mesh, split = b.mesh, b.split
    parts = []
    for s0, s1 in pairs:

        def slab_build(s0=s0, s1=s1):
            def run(d):
                slab = jax.lax.slice_in_dim(d, s0, s1, axis=cax)
                moved = jnp.moveaxis(slab, axis, -1)
                vals, idx = _topk_desc(jnp, moved, k)
                return (jnp.moveaxis(vals, -1, axis),
                        jnp.moveaxis(idx, -1, axis))
            return jax.jit(run)

        parts.append(_cached_jit(
            ("topk-slab", data.shape, str(data.dtype), split, axis, k,
             s0, s1, cax, mesh), slab_build)(data))

    def cat_build():
        def run(vs, ids):
            return (_constrain(jnp.concatenate(vs, axis=cax), mesh, split),
                    _constrain(jnp.concatenate(ids, axis=cax), mesh, split))
        return jax.jit(run)

    vals, idx = _cached_jit(
        ("topk-cat", data.shape, str(data.dtype), split, axis, k, cax,
         tuple(pairs), mesh), cat_build)(
        [p[0] for p in parts], [p[1] for p in parts])
    return (BoltArrayTPU(vals, split, mesh),
            BoltArrayTPU(idx, split, mesh))


def unique(b, return_counts=False):
    """``numpy.unique`` over ALL elements (flattened): sorted unique
    values as a host ndarray, optionally with per-value counts.

    XLA needs static shapes, so the device work is two programs (the
    filter two-phase pattern, SURVEY §7 hard part 1): sort + first-
    occurrence mask + count, one scalar sync, then a ``k``-shaped gather
    of the unique values (and counts as index differences) — the host
    never receives more than the ``k`` uniques.  Like modern numpy, all
    NaNs collapse to a single entry (they sort together at the end).

    Memory model: the sorted copy + mask is a ~1.25× input transient; at
    HBM scale (input > ``_CHUNK_MAX_BYTES``) the op switches to a
    CHUNKED path — per-chunk sort/mask/gather (transients bounded by the
    chunk size) with an exact host-side merge of the per-chunk uniques
    and counts — so a 10 GB ``unique`` never doubles HBM (VERDICT r2
    weak-4).
    """
    if b.mode == "local":
        return np.unique(np.asarray(b), return_counts=return_counts)

    from bolt_tpu.tpu.array import (_CHUNK_MAX_BYTES, _cached_jit,
                                    _chain_apply, _check_live)
    n = int(np.prod(b.shape))
    if n == 0:
        empty = np.empty(0, np.dtype(b.dtype))
        return (empty, np.empty(0, np.int64)) if return_counts else empty
    # the sharded attempt runs BEFORE the chain parts are captured: it
    # may materialise the chain (its gates need the concrete sharding),
    # and capturing first would make the fallback re-run the chain
    sharded = _unique_sharded(b, return_counts)
    if sharded is not None:
        return sharded
    if n * np.dtype(b.dtype).itemsize > _CHUNK_MAX_BYTES:
        return _unique_chunked(b, return_counts)
    base, funcs = b._chain_parts()
    split = b.split
    mesh = b.mesh

    sorted_, mask, cnt = _cached_jit(
        ("unique-sort", funcs, base.shape, str(base.dtype), split, mesh),
        lambda: jax.jit(_unique_phase1(funcs, split, None,
                                       None)))(_check_live(base))
    k = int(jax.device_get(cnt))               # the one unavoidable sync

    # n is the chain-OUTPUT element count (a shape-changing map can alter
    # it), so the key carries funcs and n like every other chain consumer
    out = jax.device_get(_cached_jit(
        ("unique-gather", funcs, base.shape, str(base.dtype), split, n, k,
         return_counts, mesh),
        lambda: jax.jit(_unique_phase2(n, k, return_counts)))(sorted_, mask))
    uniq = np.asarray(out[0])
    if return_counts:
        return uniq, np.asarray(out[1]).astype(np.int64)
    return uniq


def _sort_mask(flat):
    """Sorted values, first-occurrence mask — with numpy's NaN collapse:
    sorted NaNs are contiguous at the end, so "both NaN" marks
    duplicates — and the mask count.  The ONE mask semantics shared by
    the whole-array, chunked, and shard-local unique paths."""
    flat = jnp.sort(flat)
    neq = flat[1:] != flat[:-1]
    if jnp.issubdtype(flat.dtype, jnp.floating):
        neq &= ~(jnp.isnan(flat[1:]) & jnp.isnan(flat[:-1]))
    mask = jnp.concatenate([jnp.ones(1, bool), neq])
    return flat, mask, jnp.sum(mask, dtype=jnp.int32)


def _gather_uniques(s, msk, m, size, return_counts):
    """Gather ``size`` unique values (first-occurrence indices) out of
    an ``m``-element sorted piece, with counts as index differences;
    pad gathers clip to the last element and the host trims.  Counts
    use the canonical int on device (int32 when x64 is off — no
    warning); the host widens to int64 after the fetch.  Shared by
    every unique path."""
    idx = jnp.nonzero(msk, size=size, fill_value=m)[0]
    uniq = jnp.take(s, idx, axis=0, mode="clip")
    if not return_counts:
        return (uniq,)       # skip the counts work and their transfer
    ends = jnp.concatenate([idx[1:], jnp.asarray([m], idx.dtype)])
    return uniq, (ends - idx).astype(
        jax.dtypes.canonicalize_dtype(np.int64))


def _merge_unique_parts(vals_parts, cnt_parts, return_counts):
    """Exact host merge of per-piece uniques (+counts): the union of
    piece uniques is the global unique set and counts add (np.unique's
    NaN collapse maps every piece's NaN to one slot).  Shared by the
    chunked and shard-local paths."""
    allv = np.concatenate(vals_parts)
    if not return_counts:
        return np.unique(allv)
    uniq, inv = np.unique(allv, return_inverse=True)
    tot = np.zeros(len(uniq), np.int64)
    np.add.at(tot, inv, np.concatenate(cnt_parts))
    return uniq, tot


def _unique_phase1(funcs, split, start, stop):
    """Phase-1 traced body: :func:`_sort_mask` over (a ``[start:stop)``
    slice of) the flattened chain output.  Returns the UNJITTED
    callable — the engine builder at the call site jits it, so
    compilation stays on the engine's counted AOT path (lint BLT101)."""
    from bolt_tpu.tpu.array import _chain_apply

    def run(d):
        flat = _chain_apply(funcs, split, d).reshape(-1)
        if start is not None:
            flat = jax.lax.slice_in_dim(flat, start, stop)
        return _sort_mask(flat)
    return run


def _unique_phase2(m, size, return_counts):
    """Phase-2 traced body: :func:`_gather_uniques` (unjitted — the
    engine builder at the call site jits it)."""
    def run(s, msk):
        return _gather_uniques(s, msk, m, size, return_counts)
    return run


# bincount accumulates per-chunk below this element count when the
# canonical int is int32 (x64 off), so no bin can reach 2**31 inside one
# device program; chunk partials combine in host int64.  None = automatic
# (engages only when x64 is off AND the array is big enough to wrap);
# tests set it small to force the chunked path.
_BINCOUNT_CHUNK = None


def _unique_sharded(b, return_counts):
    """Shard-local ``unique`` for a multi-device array: ``shard_map``
    sorts and masks each shard's OWN block (a global sort order is not
    needed — any partition of the elements works for unique), per-shard
    counts sync in one fetch, a second shard-local program gathers each
    shard's uniques padded to a power of two, and the host merges
    exactly — ZERO device collectives, where GSPMD's global 1-d sort
    would all-gather the whole operand onto every device (the round-3
    lowering probe).

    Returns None (caller keeps the single-program / chunked paths) for
    the layouts the simple formulation doesn't cover: single device,
    multi-process (the per-shard outputs must be addressable), a
    replicated dimension (per-shard counts would multiply), a
    non-NamedSharding, or shards too big for their local sort transient.
    """
    from jax.sharding import NamedSharding, PartitionSpec
    from bolt_tpu.parallel import multihost as _mh
    from bolt_tpu.tpu.array import _CHUNK_MAX_BYTES, _cached_jit
    # cheap gates FIRST — they must not materialise a deferred chain
    # just to decline (single-device / multi-process layouts)
    if b.mesh is None or b.mesh.size <= 1 or _mh.process_count() > 1:
        return None
    data = b._data                          # chain materialises once
    sharding = data.sharding
    if not isinstance(sharding, NamedSharding):
        return None
    mesh = sharding.mesh
    if not data.is_fully_addressable:
        return None
    used = []
    for dim, entry in enumerate(sharding.spec):
        if entry is None:
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        ways = int(np.prod([mesh.shape[u] for u in names]))
        if data.shape[dim] % ways != 0:
            return None                      # shard_map needs even splits
        used.extend(names)
    nshards = int(np.prod([mesh.shape[u] for u in used])) if used else 1
    if nshards != mesh.size or nshards <= 1:
        return None                          # replicated somewhere
    local_elems = data.size // nshards
    if local_elems == 0 \
            or local_elems * data.dtype.itemsize > _CHUNK_MAX_BYTES:
        return None
    spec = sharding.spec
    out_spec = PartitionSpec(tuple(used))

    def p1_build():
        def local(blk):
            flat, mask, cnt = _sort_mask(blk.reshape(-1))
            return flat[None], mask[None], cnt[None]
        return jax.jit(_shard_map(
            local, mesh=mesh, in_specs=spec,
            out_specs=(out_spec, out_spec, out_spec)))

    sorted_, mask, cnt = _cached_jit(
        ("unique-shard-sort", data.shape, str(data.dtype), spec, mesh),
        p1_build)(data)
    counts = np.asarray(jax.device_get(cnt))   # the one sync
    kpad = 1 << max(0, (int(counts.max()) - 1).bit_length())

    def p2_build():
        def gather(s_ref, m_ref):
            out = _gather_uniques(s_ref[0], m_ref[0], s_ref.shape[1],
                                  kpad, return_counts)
            return tuple(o[None] for o in out)
        return jax.jit(_shard_map(
            gather, mesh=mesh, in_specs=(out_spec, out_spec),
            out_specs=(out_spec,) * (2 if return_counts else 1)))

    out = jax.device_get(_cached_jit(
        ("unique-shard-gather", data.shape, str(data.dtype), spec, kpad,
         return_counts, mesh), p2_build)(sorted_, mask))
    vals_parts = [np.asarray(out[0][i][:int(counts[i])])
                  for i in range(nshards)]
    cnt_parts = [np.asarray(out[1][i][:int(counts[i])]).astype(np.int64)
                 for i in range(nshards)] if return_counts else None
    return _merge_unique_parts(vals_parts, cnt_parts, return_counts)


def _unique_chunked(b, return_counts):
    """HBM-bounded ``unique``: sort/mask/count/gather one
    ``_CHUNK_MAX_BYTES`` slice of the flattened array at a time (device
    transients never exceed ~2.25× one chunk), then merge the per-chunk
    uniques and counts EXACTLY on host — the union of per-chunk uniques
    is the global unique set, and counts add.  The per-chunk gather pads
    its size to the next power of two so the compiled-program count
    stays logarithmic in the unique count, not linear in chunks."""
    import jax
    from bolt_tpu.tpu.array import _CHUNK_MAX_BYTES, _cached_jit
    data = b._data                          # chain materialises once
    mesh = b.mesh
    n = int(np.prod(data.shape))
    itemsize = np.dtype(data.dtype).itemsize
    chunk = max(1, _CHUNK_MAX_BYTES // itemsize)
    vals_parts, cnt_parts = [], []
    for start in range(0, n, chunk):
        stop = min(start + chunk, n)
        m = stop - start

        sorted_, mask, cnt = _cached_jit(
            ("unique-chunk-sort", data.shape, str(data.dtype), start,
             stop, mesh),
            lambda start=start, stop=stop: jax.jit(_unique_phase1(
                (), 0, start, stop)))(data)
        k = int(jax.device_get(cnt))
        kpad = 1 << max(0, (k - 1).bit_length())

        out = jax.device_get(_cached_jit(
            ("unique-chunk-gather", str(data.dtype), m, kpad,
             return_counts, mesh),
            lambda m=m, kpad=kpad: jax.jit(_unique_phase2(
                m, kpad, return_counts)))(sorted_, mask))
        vals_parts.append(np.asarray(out[0])[:k])
        if return_counts:
            cnt_parts.append(np.asarray(out[1])[:k].astype(np.int64))
    return _merge_unique_parts(vals_parts,
                               cnt_parts if return_counts else None,
                               return_counts)


def bincount(b, minlength=0):
    """``numpy.bincount`` over ALL elements of an integer bolt array
    (flattened, like numpy), as one compiled program; returns a host
    int64 ndarray of length ``max(minlength, max(b) + 1)``.  The length
    must be static for XLA, so a device-side max costs one scalar sync
    when ``minlength`` doesn't already cover it.  Counts accumulate in
    the canonical int; when that is int32 (x64 off, the production-TPU
    default) arrays big enough for a single bin to pass 2**31−1 are
    counted in chunks whose int32 partials combine in host int64 — the
    result is exact at any size, matching the local backend."""
    if not np.issubdtype(np.dtype(b.dtype), np.integer):
        raise TypeError("bincount requires an integer array, got %s"
                        % (b.dtype,))
    minlength = int(minlength)
    if minlength < 0:
        raise ValueError("'minlength' must not be negative")
    if b.size == 0:
        return np.zeros(minlength, np.int64)   # numpy's empty contract
    if b.mode == "local":
        return np.bincount(np.asarray(b).reshape(-1), minlength=minlength)

    from bolt_tpu.tpu.array import _cached_jit, _chain_apply, _check_live
    base, funcs = b._chain_parts()
    split = b.split
    mesh = b.mesh

    def minmax_build():
        def mm(data):
            x = _chain_apply(funcs, split, data).reshape(-1)
            return jnp.min(x), jnp.max(x)
        return jax.jit(mm)

    mn, mx = jax.device_get(_cached_jit(
        ("bincount-minmax", funcs, base.shape, str(base.dtype), split, mesh),
        minmax_build)(_check_live(base)))
    if int(mn) < 0:
        raise ValueError("bincount requires non-negative values")
    length = max(minlength, int(mx) + 1)

    n_elems = int(np.prod(b.shape))
    chunk = _BINCOUNT_CHUNK
    if chunk is None and jax.dtypes.canonicalize_dtype(np.int64) != np.int64:
        chunk = (1 << 31) - (1 << 20)
    if chunk is not None and n_elems > chunk:
        # x32 wraparound guard: each device program counts < 2**31
        # elements (its int32 per-bin partial cannot wrap); partials
        # combine exactly in host int64.  Chunk starts stay STATIC —
        # dynamic-start slices of sharded operands make GSPMD all-gather
        # the whole array — so it is one program per chunk;
        # at the default ~2**31 chunk a 16 GB chip holds at most a
        # handful of chunks.
        total = np.zeros(length, np.int64)
        # materialise any deferred chain ONCE (a per-chunk program would
        # re-run the whole chain before slicing its window)
        data = b._data
        for start in range(0, n_elems, chunk):
            stop = min(start + chunk, n_elems)

            def chunk_build(start=start, stop=stop):
                def run(d):
                    x = d.reshape(-1)
                    return jax.ops.segment_sum(
                        jnp.ones(stop - start,
                                 jax.dtypes.canonicalize_dtype(np.int64)),
                        jax.lax.slice_in_dim(x, start, stop),
                        num_segments=length)
                return jax.jit(run)

            part = _cached_jit(
                ("bincount-chunk", data.shape, str(data.dtype),
                 length, start, stop, mesh),
                chunk_build)(data)
            total += np.asarray(jax.device_get(part)).astype(np.int64)
        return total

    def build():
        def run(data):
            x = _chain_apply(funcs, split, data).reshape(-1)
            return jax.ops.segment_sum(
                jnp.ones_like(x, dtype=jax.dtypes.canonicalize_dtype(
                    np.int64)), x, num_segments=length)
        return jax.jit(run)

    counts = _cached_jit(("bincount", funcs, base.shape, str(base.dtype),
                          split, length, mesh), build)(_check_live(base))
    return np.asarray(jax.device_get(counts)).astype(np.int64)
