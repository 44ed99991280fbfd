"""Abstract pipeline checker: interpret a deferred pipeline without
compiling or dispatching anything.

PR 1 made pipelines deferred, fused and donation-aware — a
:class:`~bolt_tpu.tpu.array.BoltArrayTPU` can be an opaque
``(base, funcs)`` program whose shape/dtype/sharding errors and
use-after-donate crashes only surface at XLA compile or dispatch time.
:func:`check` walks that recorded state — the ``_chain`` map chain, a
deferred ``_fpending`` filter, a ``_pending`` compaction — and abstractly
interprets it stage by stage with ``jax.eval_shape`` (abstract
interpretation only: ZERO XLA compiles, proven by the engine counters
staying flat), inferring the result shape, dtype and key sharding per
stage and emitting structured ``BLT0xx`` diagnostics
(:mod:`bolt_tpu.analysis.diagnostics`) for:

* stages that fail abstract tracing (``BLT001``);
* a recorded result aval that lies about what the chain produces
  (``BLT002`` — the ``value_shape``-lie class);
* silent dtype widening along the chain (``BLT003`` — an f32 pipeline
  that materialises f64 doubles its HBM footprint);
* key axes that do not divide the mesh, leaving devices idle
  (``BLT004``);
* donation-safety violations: any read path that hits a ``_donated``
  buffer (``BLT005``), and a forecast of the terminal donation the
  engine's policy WILL grant (``BLT006``);
* a filter predicate that is not scalar-per-record (``BLT007``) and
  dynamic shapes pending a survivor-count sync (``BLT008``).

The interpretation applies each stage through the SAME
``_chain_apply`` the compiled program uses, so predicted and executed
shape/dtype cannot drift (``tests/test_pipeline_fuzz.py`` asserts this
parity on every fuzzed pipeline).
"""

import sys

import numpy as np

import jax

from bolt_tpu.obs import trace as _obs
from bolt_tpu.analysis.diagnostics import Diagnostic, Report, Stage
from bolt_tpu.parallel.sharding import key_spec, spec_names
from bolt_tpu.utils import prod


def _name(func):
    return getattr(func, "__name__", None) or type(func).__name__


def _func_label(func):
    from bolt_tpu.tpu.array import _Window, _WithKeysFunc
    if type(func) is _Window:
        return "getitem(starts=%s, sizes=%s%s)" % (
            func.starts, func.sizes,
            ", squeezed=%s" % (func.squeezed,) if func.squeezed else "")
    if isinstance(func, _WithKeysFunc):
        return "map(%s, with_keys)" % _name(func.func)
    return "map(%s)" % _name(func)


def _percentile_note(func, split, aval, blocked=None):
    """How an ``ops.normalize(baseline="percentile")`` stage takes its
    baseline over records of ``aval``: from the SAME function the
    lowering asks (``ops/select.py :: regime``), so the forecast and the
    program cannot disagree.  ``blocked``: ``(records, block)`` where
    the stage is the first map of a run lowered over blocks, whose
    selection may read its block where the array lies: asked of the
    trace of the lowering's own loop (``tpu/array.py :: _blocked_run``).
    Empty for any other stage."""
    ax = getattr(func, "percentile_axis", None)
    if ax is None:
        return ""
    from bolt_tpu.ops import select
    length = aval.shape[split + ax]
    # the dtype the stage itself promotes to
    dtype = jax.numpy.promote_types(aval.dtype, np.float32)
    how = select.regime(length, dtype)
    if how == "kernel":
        found = []
        if blocked is not None:
            from bolt_tpu.tpu.array import _blocked_run
            jax.eval_shape(
                lambda x: _blocked_run((func,), 1, x, blocked[1],
                                       found=found),
                jax.ShapeDtypeStruct((blocked[0],) + aval.shape[split:],
                                     aval.dtype))
        return ("percentile by selection, one read of a block%s: two exact "
                "order statistics of %d values found bit by bit on a tile "
                "held in VMEM (a program for one TPU device; counting "
                "passes over the block elsewhere), no sort"
                % (" in place" if found else "", length))
    if how == "select":
        return ("percentile by selection: two exact order statistics of "
                "%d values found bit by bit, no sort" % length)
    return "percentile by sort: %d values a record is under the %d " \
        "from which it is selected" % (length, select.select_from(dtype))


def _centring_note(func):
    """What an ``ops.fourier`` stage built behind ``detrend``, ``center``
    or ``zscore`` says of its mean: from the attribute of the record
    function that ``ops/series.py :: _fourier_fn`` builds without its own
    centring.  Empty for any other stage."""
    if getattr(func, "centred_by_parent", None) is None:
        return ""
    return ("centred by its parent: no pass for the mean, one reader of "
            "the parent's result")


def _block_heads(arr, base, funcs):
    """``{i: (records, block)}``: the ``funcs[i]`` that are the first map
    of a run which the rule of ``tpu/blocks.py`` lowers over blocks (the
    one map of such a run whose operand is a block of the array
    itself)."""
    from bolt_tpu.tpu.array import _Window, _chain_runs
    try:
        marked = arr._block_plan(base, funcs)
    except Exception:       # a record too large (BLT019: _note_blocks)
        return {}           # or a later stage that does not trace (BLT001)
    if marked is funcs:
        return {}
    heads, at, plan = {}, 0, iter(marked[-1].runs)
    for part in _chain_runs(funcs):
        if type(part) is _Window:
            at += 1
            continue
        run = next(plan)
        if run:
            heads[at] = run
        at += len(part)
    return heads


def _shared_note(shared):
    """What :meth:`BoltArrayTPU._shared_parent` answered, on the stage
    that is the shared parent's last: its chain is one program for all
    of its consumers, and the stages after it read the kept result."""
    _, node, live = shared
    if node.kept is None:
        return "shared parent: materialised once for %d consumers" % live
    return "shared parent: materialised once, its result kept for the " \
        "%d still deferred" % live


def _kdrop(funcs):
    """Key axes the getitem windows among ``funcs`` remove."""
    from bolt_tpu.tpu.array import _windows
    return sum(w.kdrop for w in _windows(funcs))


def _stage_eval(func, split, aval):
    """Abstractly apply ONE chain stage — through the same
    ``_chain_apply`` the compiled program runs, so the prediction cannot
    drift from execution.  Results are memoised in the array module's
    eval cache (keyed on func identity + input aval)."""
    from bolt_tpu.tpu.array import (_cached_eval_shape, _chain_apply,
                                    _func_key)
    key = ("analysis-stage", _func_key(func), split, tuple(aval.shape),
           str(aval.dtype))
    return _cached_eval_shape(
        key, lambda: jax.eval_shape(
            lambda d: _chain_apply((func,), split, d),
            jax.ShapeDtypeStruct(tuple(aval.shape), aval.dtype)))


def _untraceable(idx, label, aval, exc):
    """``BLT001`` for the chain stage ``label`` that failed abstract
    tracing on ``aval``."""
    first = str(exc).splitlines()[0] if str(exc) else ""
    return Diagnostic(
        "BLT001", idx,
        "%s fails abstract tracing on input %s %s: %s%s"
        % (label, tuple(aval.shape), np.dtype(aval.dtype),
           type(exc).__name__, ": " + first if first else ""),
        hint="the stage would fail identically at compile time; "
             "fix the callable's shape/dtype contract")


def _would_donate(arr):
    """Would the NEXT terminal donate this array's chain base?  Mirrors
    the terminals exactly by delegating to ``_chain_donate_ok`` with the
    same reference pattern (attribute access straight into the call, no
    extra locals — the ownership test is refcount-based)."""
    from bolt_tpu.tpu.array import _chain_donate_ok
    if arr._fpending is not None:
        return _chain_donate_ok(arr._fpending)
    if arr._chain is not None:
        return _chain_donate_ok(arr._chain)
    return False


def _idle_device_check(mesh, shape, split, stage_idx, diags, seen):
    """``BLT004`` once per report: the derived key sharding leaves mesh
    devices idle because the key extents do not divide the mesh.
    Malformed state (split beyond the rank — exactly what hand-built
    deferred arrays can carry) must not crash the checker: the shape
    contradiction gets its own BLT002, so sharding is simply skipped."""
    if seen or mesh is None or not split:
        return seen
    try:
        spec = key_spec(mesh, shape, split)
        names = [n for e in spec for n in spec_names(e)]
        assigned = prod([mesh.shape[n] for n in names]) if names else 1
        full = prod([mesh.shape[n] for n in mesh.axis_names
                     if mesh.shape[n] > 1])
    except Exception:
        return seen
    if assigned < full:
        diags.append(Diagnostic(
            "BLT004", stage_idx,
            "key axes %s assign only %d of %d mesh devices (extents do "
            "not divide the mesh %s)"
            % (tuple(shape[:split]), assigned, full, dict(mesh.shape)),
            hint="reshape the key axes (keys.reshape) or choose key "
                 "extents divisible by the mesh axis sizes"))
        return True
    return seen


def _spec(mesh, shape, split):
    try:
        return key_spec(mesh, shape, split)
    except Exception:
        return None


def _fmt_bytes(n):
    if n >= 1 << 30:
        return "%.1f GB" % (n / float(1 << 30))
    if n >= 1 << 20:
        return "%.1f MB" % (n / float(1 << 20))
    return "%d B" % n


def _group_bytes(g):
    """Bytes ONE pass of a stat group reads (the fusion forecast's
    bytes-read model)."""
    if g.kind == "chain":
        return int(g.base.nbytes)
    if g.kind == "fpending":
        return int(g.fpending[0].nbytes)
    return prod(g.source.shape) * np.dtype(g.source.dtype).itemsize


# ---------------------------------------------------------------------
# admission budget (the serving layer's BLT010 contract)
# ---------------------------------------------------------------------

def _effective_codec(src):
    """The codec a run over ``src`` would resolve (source ``codec=``
    wins over the caller's ``stream.codec()`` scope), WITHOUT the dtype
    validation ``stream.resolve_codec`` performs — the checker wants to
    FORECAST the refusal (BLT016 warning), not raise it.  Unknown names
    cannot arm through any public door (``fromcallback``/``fromiter``,
    the scope and ``set_codec`` all validate pointedly), but a
    hand-built source must degrade to "no forecast", never crash the
    checker — the run itself still refuses at ``resolve_codec``."""
    from bolt_tpu import stream as _stream
    name = src.codec if src.codec is not None else _stream.current_codec()
    if name is None:
        return None
    from bolt_tpu.tpu import codec as _codeclib
    try:
        return _codeclib.get(name)
    except ValueError:
        return None


def _wire_itemsize(src):
    """Bytes an element of ``src`` takes on the link and in the ring:
    the stored dtype's, or the WIRE dtype's when a codec is armed."""
    c = _effective_codec(src)
    if c is not None:
        try:
            return c.wire_dtype(src.dtype).itemsize
        except ValueError:
            pass          # refused combination: the run never streams
    return src.dtype.itemsize


def _stream_slab_bytes(src):
    """One slab's DEVICE bytes — the WIRE representation when a codec
    is armed (the ring holds and the arbiter leases compressed slabs;
    the admission floor recomputes through the codec ratio)."""
    return int(src.slab * prod(src.shape[1:]) * _wire_itemsize(src))


def _stream_ring_bytes(src):
    """A streaming plan's peak device footprint: slab bytes times the
    donated-ring bound (``stream.fold_ring``) — exactly the budget one
    run's slabs can hold at once in ``stream.execute``."""
    from bolt_tpu import stream as _stream
    return _stream_slab_bytes(src) * _stream.fold_ring(src)


def _admission_budget():
    """The ACTIVE serving arbiter's byte budget, or None when
    ``bolt_tpu.serve`` is not running (consulted via ``sys.modules`` so
    checking never imports the serving layer)."""
    sv = sys.modules.get("bolt_tpu.serve")
    if sv is None:
        return None
    arb = sv.device_arbiter()
    return arb.budget if arb is not None else None


def _note_blocks(arr, base, funcs, idx, diags):
    """``BLT018`` / ``BLT019``: what the rule of ``tpu/blocks.py`` says
    of this chain on this device, from the SAME function the lowering
    asks (``BoltArrayTPU._block_plan``), so the forecast and the program
    cannot disagree: a run of maps with record-sized temporaries (a
    sort, an FFT, a scan) that the device cannot hold for every record
    at once is lowered over blocks of whole records; one whose single
    record does not fit is refused here, in words, before XLA is asked."""
    try:
        marked = arr._block_plan(base, funcs)
    except MemoryError as exc:
        diags.append(Diagnostic(
            "BLT019", idx, str(exc),
            hint="shorten the records (chunk the value axis) or free "
                 "device memory; no block of whole records can fit"))
        return
    if marked is not funcs:
        plan = marked[-1]
        diags.append(Diagnostic(
            "BLT018", idx,
            "blocked: %d blocks of %d records (a record of this chain "
            "keeps record-sized temporaries, and all %s records at once "
            "would not fit what the device has left)"
            % (plan.blocks, plan.block_records,
               " + ".join(str(r[0]) for r in plan.runs if r))))


def _note_admission(est, idx, diags):
    """``BLT010``: the pipeline's MINIMUM device working set — the
    floor it can degrade to under budget pressure (one slab for
    streams; the whole base + result for in-memory pipelines) — exceeds
    the serving budget: ``serve.submit`` rejects it, because a worker
    that admitted it would hog or wedge the arbiter forever."""
    budget = _admission_budget()
    if budget is None or est is None or est <= budget:
        return
    diags.append(Diagnostic(
        "BLT010", idx,
        "minimum device working set ~%s exceeds the serving admission "
        "budget %s even fully degraded; serve.submit will reject this "
        "pipeline" % (_fmt_bytes(int(est)), _fmt_bytes(int(budget))),
        hint="shrink the operand or streaming slabs "
             "(fromcallback(chunks=...)), or start the server with a "
             "larger budget_bytes"))


def admission_floor_bytes(obj):
    """The MINIMUM device bytes ``obj``'s pipeline needs at once — the
    number admission control (``serve.submit`` / BLT010) compares
    against the serving budget.  Streaming plans degrade to ONE slab in
    flight (the arbiter's starvation valve shallows the ring), so their
    floor is the slab size; in-memory pipelines cannot shrink, so their
    floor is :func:`working_set_bytes`.  None when nothing can be
    estimated."""
    from bolt_tpu.tpu.array import BoltArrayTPU
    from bolt_tpu.tpu.chunk import ChunkedArray
    from bolt_tpu.tpu.stack import StackedArray
    arr = obj
    if isinstance(arr, (ChunkedArray, StackedArray)):
        arr = arr._barray
    if not isinstance(arr, BoltArrayTPU):
        return None
    if arr._spending is not None and arr._spending.group.kind == "stream":
        return _stream_slab_bytes(arr._spending.group.source)
    if arr._stream is not None:
        return _stream_slab_bytes(arr._stream)
    return working_set_bytes(arr)


def working_set_bytes(obj):
    """Estimated PEAK device bytes ``obj``'s pipeline needs at once —
    the number admission control compares against the serving budget:

    * streaming plan → slab bytes x ``stream.fold_ring`` (the window
      of unconfirmed slab programs + the uploader pool), the
      donated-ring bound;
    * pending stat group → the group's one-pass read (stream groups use
      the ring bound);
    * deferred chain / filter / concrete array → source bytes + result
      bytes (input and output coexist during the dispatch).

    Returns ``None`` for objects with nothing to estimate (local
    arrays)."""
    from bolt_tpu.tpu.array import BoltArrayTPU
    from bolt_tpu.tpu.chunk import ChunkedArray
    from bolt_tpu.tpu.stack import StackedArray
    arr = obj
    if isinstance(arr, (ChunkedArray, StackedArray)):
        arr = arr._barray
    if not isinstance(arr, BoltArrayTPU):
        return None
    if arr._spending is not None:
        g = arr._spending.group
        if g.kind == "stream":
            return _stream_ring_bytes(g.source)
        return int(_group_bytes(g))
    if arr._stream is not None:
        return _stream_ring_bytes(arr._stream)
    aval = arr._aval
    out_bytes = (prod(tuple(aval.shape)) * np.dtype(aval.dtype).itemsize
                 if aval is not None else 0)
    if arr._fpending is not None:
        return int(arr._fpending[0].nbytes) + int(out_bytes)
    if arr._chain is not None:
        return int(arr._chain[0].nbytes) + int(out_bytes)
    return int(out_bytes)


def _batching_policy():
    """The ACTIVE server's batching policy, or ``None`` when no
    batching-enabled server is running (consulted via ``sys.modules``
    like the BLT010 budget — checking never imports the serving
    layer)."""
    sv = sys.modules.get("bolt_tpu.serve")
    if sv is None:
        return None
    srv = sv.active()
    return getattr(srv, "batching", None) if srv is not None else None


def _note_batchable(arr, idx, diags):
    """``BLT015``: forecast serve micro-batching — a batching-enabled
    server is active and this pipeline carries a batch key
    (``bolt_tpu.tpu.batched.batch_key``), so queued same-key requests
    (same structure, shapes, dtypes, terminal and sharding — across
    tenants) will coalesce into ONE stacked dispatch at bucketed
    widths, bit-identical to the standalone dispatch."""
    pol = _batching_policy()
    if pol is None:
        return
    bt = sys.modules.get("bolt_tpu.tpu.batched")
    if bt is None:
        return
    try:
        key = bt.batch_key(arr)
    except Exception:
        return
    if key is None:
        return
    diags.append(Diagnostic(
        "BLT015", idx,
        "terminal is batch-eligible (%s form): the active batching "
        "server coalesces up to %d queued same-key requests — same "
        "pipeline structure/shape/dtype/terminal/sharding, across "
        "tenants — into ONE stacked dispatch at bucketed widths %s, "
        "each lane bit-identical to its standalone dispatch"
        % (key[0], pol.max_batch, tuple(pol.buckets)),
        hint="submit same-shape pipelines concurrently to share one "
             "batched executable; serve.stats()['batching'] shows the "
             "realised occupancy, batched.warm() pre-compiles the "
             "buckets"))


def _note_fusable(arr, idx, diags):
    """``BLT009``: forecast the single-pass fusion — this array's
    source carries a live fused stat group (bolt_tpu/tpu/multistat.py),
    so its pending terminals will resolve from ONE read instead of one
    pass each.  ``explain()`` thereby shows the single-pass plan and
    the bytes-read estimate before anything dispatches."""
    g = getattr(arr, "_stat_group", None)
    if g is not None:
        _note_fusable_group(g, idx, diags)


def _check_spending(arr, target, stages, diags):
    """Abstractly interpret a PENDING STAT array (the lazy result of a
    ``sum()``-family terminal): nothing dispatches — the group's source
    and the terminal's derived aval are reported, plus the ``BLT009``
    fusion forecast."""
    h = arr._spending
    g = h.group
    if g.kind == "stream":
        src_shape = tuple(g.source.shape)
        src_dtype = np.dtype(g.source.dtype)
        label = "stream source (%s)" % g.source.kind
    elif g.kind == "fpending":
        base = g.fpending[0]
        src_shape = tuple(base.shape)
        src_dtype = np.dtype(base.dtype)
        label = "filtered chain base"
    else:
        src_shape = tuple(g.base.shape)
        src_dtype = np.dtype(g.base.dtype)
        label = "chain base" if g.funcs else "base (concrete)"
    stages.append(Stage(0, label, src_shape, src_dtype, g.split,
                        _spec(arr._mesh, src_shape, g.split)))
    stages.append(Stage(
        1, "%s() [pending stat]" % h.name, tuple(h.aval.shape),
        np.dtype(h.aval.dtype), h.new_split,
        _spec(arr._mesh, tuple(h.aval.shape), h.new_split),
        note="terminal of a %d-member fused group, not yet dispatched"
             % len(g.members)))
    _note_fusable_group(g, 1, diags)
    _note_batchable(arr, 1, diags)
    _note_admission(_stream_slab_bytes(g.source) if g.kind == "stream"
                    else _group_bytes(g), 1, diags)
    if g.kind == "stream":
        _note_codec(g.source, 1, diags,
                    members=[m.name for m in g.members])
    return Report(target + ", pending stat", stages, diags)


def _note_fusable_group(g, idx, diags):
    pend = [m for m in g.members if m.result is None]
    if g.dispatched or not pend:
        return
    names = ", ".join(m.name for m in pend)
    nbytes = _group_bytes(g)
    diags.append(Diagnostic(
        "BLT009", idx,
        "fusable terminal set: %d pending stat terminal(s) [%s] resolve "
        "from ONE %s pass reading ~%s (instead of %d passes / ~%s); "
        "results are bit-identical to the standalone terminals"
        % (len(pend), names, g.kind, _fmt_bytes(nbytes), len(pend),
           _fmt_bytes(nbytes * len(pend))),
        hint="read any member (or bolt.compute(...)) to dispatch the "
             "group; terminals on other sources fall back per group"))


def _note_codec(src, idx, diags, members=()):
    """``BLT016``: forecast codec-encoded ingest (ISSUE 14) — the bytes
    this streaming plan will NOT move over the host→device link, plus a
    WARNING when a lossy codec meets a bit-exactness-sensitive terminal
    (order statistics — the executor will refuse) or a dtype the codec
    cannot encode."""
    c = _effective_codec(src)
    if c is None:
        return
    raw = int(prod(src.shape) * src.dtype.itemsize)
    try:
        wire = int(prod(src.shape) * c.wire_dtype(src.dtype).itemsize)
    except ValueError as exc:
        diags.append(Diagnostic(
            "BLT016", idx,
            "codec %r cannot encode this %s pipeline — the streamed "
            "run will refuse pointedly: %s"
            % (c.name, np.dtype(src.dtype), str(exc).splitlines()[0]),
            severity="warning",
            hint="pick a codec that supports the dtype, or stream "
                 "uncompressed"))
        return
    sensitive = sorted({m for m in members if m in ("min", "max",
                                                    "ptp")})
    if not c.lossless and sensitive:
        diags.append(Diagnostic(
            "BLT016", idx,
            "lossy codec %r meets the bit-exactness-sensitive order "
            "statistic(s) %s — the streamed run will refuse them "
            "(a quantised extremum is never the intended answer)"
            % (c.name, sensitive), severity="warning",
            hint="use the lossless 'delta-f32' codec for order stats, "
                 "or resolve them over an uncompressed source"))
        return
    diags.append(Diagnostic(
        "BLT016", idx,
        "codec-encoded ingest (%s%s): one full pass ships ~%s on the "
        "wire instead of ~%s (%.2fx)%s"
        % (c.name, "" if c.lossless else ", LOSSY opt-in",
           _fmt_bytes(wire), _fmt_bytes(raw),
           (wire / raw) if raw else 1.0,
           " — lossless: bit-identical to uncompressed streaming"
           if c.lossless else ""),
        hint="uploader workers encode per slab (codec_bytes_raw/"
             "codec_bytes_wire engine counters); the slab program "
             "decodes on device fused into the fold — zero extra HBM "
             "passes, and the arbiter leases the wire bytes"))


def _whole_need(src):
    """``(bytes a device, the device's limit, refused)`` of materialising
    ``src`` whole: what BLT020 and BLT021 say where nothing streams."""
    from bolt_tpu import stream as _stream
    from bolt_tpu.tpu.array import _hbm_limit
    need, limit = _stream.materialize_bytes(src), _hbm_limit()
    return need, limit, limit is not None and need > limit


def _note_collect(src, idx, diags):
    """``BLT020``: what taking this mapped streamed result WHOLE
    (``toarray`` / ``tojax`` / ``cache``) will do, by the rules the run
    itself decides by (``stream.collect_refusal``, then
    ``stream.materialize_bytes`` against the device's memory): collected
    slab by slab, materialised with the base uploaded whole, or refused.
    Reduction terminals stream whatever this says."""
    from bolt_tpu import stream as _stream
    why = _stream.collect_refusal(src)
    if why is None:
        plan = _stream.collect_plan(src)
        diags.append(Diagnostic(
            "BLT020", idx,
            "taken whole, this result is collected slab by slab: %d "
            "slab%s through the uploader pool, the stages in the slab "
            "program, each slab's records placed into the %s result (%s "
            "working set with %d slabs in flight, budget %s)"
            % (plan.nslabs, "s" if plan.nslabs != 1 else "",
               _fmt_bytes(plan.total_bytes),
               _fmt_bytes(plan.resident_bytes), plan.ring,
               _fmt_bytes(plan.budget) if plan.budget is not None
               else "unbounded"),
            hint="the base never lives whole on the device; bit-identical "
                 "to materialising it (stream_collect_slabs / "
                 "stream_collect_bytes engine counters)"))
        return
    need, limit, refused = _whole_need(src)
    diags.append(Diagnostic(
        "BLT020", idx,
        "taken whole, this result materialises, %s a device: the base "
        "uploads whole, outside the uploader pool, and the stages replay "
        "on the resident copy (not collected slab by slab: %s)%s"
        % (_fmt_bytes(need), why,
           " — the device holds %s: toarray / cache will refuse it at "
           "dispatch with these words" % _fmt_bytes(limit)
           if refused else ""),
        severity="warning" if refused else "info",
        hint="reduction terminals (sum/mean/var/std/reduce, and "
             "ops.pca / ops.cov where BLT021 says so) stream it slab by "
             "slab whatever its size"))


def _note_gram(src, idx, diags):
    """``BLT021``: what ``ops.cov`` and ``ops.pca`` of this streamed
    source will do, by the rules the run itself decides by
    (``stream.gram_refusal``; for pca's scores ``stream.collect_plan`` of
    the source with the projection as its last stage, as
    ``ops/linalg.py :: _pca_streamed`` asks it): one pass that folds the
    Gram matrix slab by slab, a second that collects the scores, or the
    source materialised whole with the reason.  Over the sample axes a
    caller would name: the key axes and the value axes behind them until
    the samples outnumber the features.  Nothing where no such reading
    exists or the features' Gram matrix would outgrow a slab."""
    from bolt_tpu import stream as _stream
    from bolt_tpu.ops import linalg as _linalg
    st = _stream.result_state(src)
    shape = tuple(st.shape)
    m = max(st.split, 1)
    while m < len(shape) - 1 and prod(shape[:m]) < prod(shape[m:]):
        m += 1
    n, d = prod(shape[:m]), prod(shape[m:])
    item = np.dtype(st.dtype).itemsize
    if m >= len(shape) or n < d or d * d * item > _stream_slab_bytes(src):
        return
    axes = tuple(range(m))
    try:
        why = _stream.gram_refusal(src, axes, passes=2)
    except ValueError:
        return          # a codec that does not resolve is BLT016's to say
    if why is not None:
        need, limit, refused = _whole_need(src)
        diags.append(Diagnostic(
            "BLT021", idx,
            "ops.pca / ops.cov over axis=%s materialise this source, %s "
            "a device (not folded slab by slab: %s)%s"
            % (axes, _fmt_bytes(need), why,
               " — the device holds %s: the call is refused at dispatch "
               "(BLT020)" % _fmt_bytes(limit) if refused else ""),
            severity="warning" if refused else "info",
            hint="record-wise maps in front, the leading axes as samples "
                 "and one process stream at any size"))
        return
    # the scores' place, by the plan of ONE component: a component more
    # is a result that much larger beside the same slabs in flight
    plan = _stream.collect_plan(_linalg._scores_plan_source(
        src, m, d, 1, False, "highest"))
    room = None if plan.budget is None else max(
        0, (plan.budget - (plan.resident_bytes - plan.total_bytes))
        // plan.total_bytes)
    diags.append(Diagnostic(
        "BLT021", idx,
        "ops.cov over axis=%s streams this source in ONE pass: the %d x "
        "%d Gram matrix and the column sums folded slab by slab, %d "
        "slab%s; ops.pca in TWO: that pass, then every slab projected "
        "and its scores collected into the resident result, %s a "
        "component (%s)"
        % (axes, d, d, plan.nslabs, "s" if plan.nslabs != 1 else "",
           _fmt_bytes(plan.total_bytes),
           "unbounded budget" if room is None else
           "k up to %d of %d fit the %s budget beside %d slabs in flight%s"
           % (min(room, d), d, _fmt_bytes(plan.budget), plan.ring,
              "" if room else ": pca materialises the source whole, or "
              "is refused (BLT020)")),
        hint="the source never lives whole on the device "
             "(stream_gram_slabs / stream_project_slabs engine counters)"))


def _note_shuffle(src, stage, aval, split, mesh, idx, diags, keyed=False):
    """``BLT017``: forecast the streamed shuffle (ISSUE 18) — the SAME
    planner the executor runs (``parallel.shuffle.plan_shuffle`` fed by
    ``stream.swap_budget()``/``spill_scope()``), so the forecast and
    the dispatch-time resident/spill decision cannot drift.  INFO for a
    servable plan; WARNING when the plan forecasts spill with no spill
    directory configured (the executor will refuse pointedly) or when
    the pod geometry refuses the collective outright."""
    from bolt_tpu import stream as _stream
    from bolt_tpu.parallel import shuffle as _shuffle
    perm, new_split = stage[1], stage[2]
    spill_dir, _ = _stream.spill_scope()
    try:
        plan = _shuffle.plan_shuffle(
            tuple(aval.shape), np.dtype(aval.dtype), split, perm,
            new_split, mesh, src.slab, _stream.place_budget(src),
            spill_dir, ring=_stream.swap_ring(src),
            raw_slab_bytes=_stream._raw_slab_bytes(src))
    except ValueError as exc:
        diags.append(Diagnostic(
            "BLT017", idx,
            "the streamed shuffle refuses this swap — the run will "
            "raise identically at dispatch: %s"
            % str(exc).splitlines()[0], severity="warning",
            hint="reshape the pipeline so the swap satisfies the pod "
                 "geometry, or materialise first (toarray) and swap "
                 "in memory"))
        return
    if not plan.resident and plan.sharded:
        diags.append(Diagnostic(
            "BLT017", idx,
            plan.describe() + " — but disk spill is single-process "
            "only: the multi-process executor will refuse this swap "
            "at dispatch",
            severity="warning",
            hint="raise the arbiter budget so the re-keyed buckets "
                 "stay resident, or materialise first (toarray) and "
                 "swap in memory"))
        return
    if not plan.resident and keyed:
        diags.append(Diagnostic(
            "BLT017", idx,
            plan.describe() + " — but a with_keys map rides in front of "
            "the swap, and the spill leg's program is not handed a "
            "slab's first key: the executor will refuse this swap at "
            "dispatch",
            severity="warning",
            hint="raise the budget so the re-keyed array stays "
                 "resident, or materialise first (toarray) and swap in "
                 "memory"))
        return
    if not plan.resident and plan.spill_dir is None:
        diags.append(Diagnostic(
            "BLT017", idx,
            plan.describe() + " — but NO spill directory is "
            "configured: the executor will refuse this swap at "
            "dispatch rather than materialise silently",
            severity="warning",
            hint="wrap the run in bolt_tpu.stream.spill(dir=...) to "
                 "license disk spill, or raise the arbiter budget so "
                 "the re-keyed buckets stay resident"))
        return
    exchange = ("one all-to-all per slab across its %d devices"
                % plan.devices if plan.alltoall_bytes
                else "a local permute: nothing crosses devices")
    diags.append(Diagnostic(
        "BLT017", idx, plan.describe(),
        hint="phase 1 re-buckets each uploaded slab on device (%s) "
             "and %s; phase 2 streams "
             "the buckets through the standard slab machinery — "
             "bit-identical to the materialised swap "
             "(shuffle_bytes/spill_bytes/stream_alltoall_bytes engine "
             "counters)"
             % (exchange,
                "keeps them resident in HBM under the arbiter lease"
                if plan.resident
                else "spills them codec-encoded to the fingerprint "
                     "directory")))


def _check_predicate(pred, vshape, vdtype, idx, diags):
    """Abstractly trace a filter predicate over one value block and emit
    BLT001 (trace failure) / BLT007 (non-scalar per record) — the ONE
    predicate contract, shared by the deferred-filter and streaming-plan
    walks so their diagnostics cannot drift."""
    try:
        from bolt_tpu.tpu.array import _cached_eval_shape
        paval = _cached_eval_shape(
            ("filter", pred, tuple(vshape), str(np.dtype(vdtype))),
            lambda: jax.eval_shape(
                pred, jax.ShapeDtypeStruct(tuple(vshape),
                                           np.dtype(vdtype))))
    except Exception as exc:
        first = str(exc).splitlines()[0] if str(exc) else ""
        diags.append(Diagnostic(
            "BLT001", idx,
            "filter predicate %s fails abstract tracing: %s%s"
            % (_name(pred), type(exc).__name__,
               ": " + first if first else ""),
            hint="the predicate must trace over one value block"))
    else:
        if prod(tuple(getattr(paval, "shape", ()))) != 1:
            diags.append(Diagnostic(
                "BLT007", idx,
                "filter predicate %s returns shape %s per record; it "
                "must reduce each value block to ONE truth value"
                % (_name(pred), tuple(paval.shape)),
                hint="reduce inside the predicate, e.g. "
                     "lambda v: (v > 0).all()"))


def check(obj):
    """Abstractly interpret ``obj``'s recorded pipeline; returns a
    :class:`~bolt_tpu.analysis.diagnostics.Report`.

    Accepts a ``BoltArrayTPU``, a ``ChunkedArray``/``StackedArray`` view
    (checked through its underlying array), or a local array (trivial
    report).  Never compiles, dispatches, syncs a survivor count or
    resolves deferred state — ``engine.counters()`` is unchanged except
    for the ``diagnostics`` tally this check feeds.  Each check records
    an ``analysis.check`` span on the obs timeline (attributes: finding
    count, dynamic flag) — under ``analysis.strict()`` those spans sit
    inside the terminal's dispatch span, making the gate's cost
    visible."""
    with _obs.span("analysis.check") as sp:
        rep = _check_impl(obj)
        sp.set(diagnostics=len(rep.diagnostics),
               dynamic=bool(getattr(rep, "dynamic", False)))
        return rep


def _check_impl(obj):
    from bolt_tpu import engine
    from bolt_tpu.tpu.array import BoltArrayTPU

    target = "tpu"
    arr = obj
    # unwrap the thin views — their pipeline state IS the wrapped array's
    from bolt_tpu.tpu.chunk import ChunkedArray
    from bolt_tpu.tpu.stack import StackedArray
    if isinstance(arr, ChunkedArray):
        target = "tpu, chunked view plan=%s" % (arr.plan,)
        arr = arr._barray
    elif isinstance(arr, StackedArray):
        target = "tpu, stacked view size=%d" % arr.size
        arr = arr._barray

    if not isinstance(arr, BoltArrayTPU):
        # local oracle (or anything array-like): nothing deferred to check
        shape = tuple(np.shape(np.asarray(arr))) \
            if not hasattr(arr, "shape") else tuple(arr.shape)
        dtype = np.dtype(getattr(arr, "dtype", np.asarray(arr).dtype))
        rep = Report("local", [Stage(0, "base", shape, dtype,
                                     getattr(arr, "split", 0) or 0)], [])
        return rep

    diags = []
    stages = []

    if arr._donated:
        op = arr._donated if isinstance(arr._donated, str) \
            else "a donating terminal"
        diags.append(Diagnostic(
            "BLT005", -1,
            "this array's device buffer was donated to %s; every read "
            "path (toarray, reduce, map, ...) will raise" % op,
            hint="re-materialise from the source array, or disable the "
                 "policy with engine.donation(None) before the "
                 "consuming terminal"))
        # a donating PENDING terminal may still be joinable: further
        # stat calls ride the same group (one donate for N stats)
        _note_fusable(arr, -1, diags)
        rep = Report(target, stages, diags)
        engine.record_diagnostics(len(diags))
        return rep

    if arr._spending is not None:
        # a lazy stat result (bolt_tpu/tpu/multistat.py): report the
        # group's single-pass plan without dispatching anything
        rep = _check_spending(arr, target, stages, diags)
        engine.record_diagnostics(len(diags))
        return rep

    if arr._stream is not None:
        # streaming plan (bolt_tpu.stream): walk the recorded stage
        # chain abstractly — same _stage_apply bodies the per-slab
        # program traces, eval_shape only, ZERO XLA compiles
        _note_fusable(arr, 0, diags)
        rep = _check_stream(arr, target, stages, diags)
        engine.record_diagnostics(len(diags))
        return rep

    # as the lowering asks it (BoltArrayTPU._lower_from_shared), and
    # before it decides donation: a chain that will read a kept result
    # leaves the base alone
    shared = arr._links and arr._shared_parent()
    # donation forecast BEFORE binding any base/chain local (the
    # ownership test is refcount-based; an extra local would mask it)
    will_donate = not shared and _would_donate(arr)

    mesh = arr._mesh
    fp = arr._fpending
    pend = arr._pending
    idle_seen = False
    dynamic = False

    if fp is not None:
        base, funcs, pred, walk_split, vshape, n, vdtype = fp[:7]
    elif arr._chain is not None:
        base, funcs = arr._chain
        walk_split = arr._split
    elif pend is not None:
        padded, _cnt = pend
        shape = tuple(padded.shape)
        stages.append(Stage(0, "filter compaction (pending)", shape,
                            np.dtype(padded.dtype), 1,
                            _spec(mesh, shape, 1), dynamic=True,
                            note="survivor count not yet synced"))
        diags.append(Diagnostic(
            "BLT008", 0,
            "the result shape is dynamic: at most %d records survive; "
            "reading .shape syncs one scalar from device" % shape[0]))
        rep = Report(target, stages, diags, dynamic=True)
        engine.record_diagnostics(len(diags))
        return rep
    else:
        aval = arr._aval
        shape = tuple(aval.shape)
        stages.append(Stage(0, "base (concrete)", shape,
                            np.dtype(aval.dtype), arr._split,
                            _spec(mesh, shape, arr._split)))
        _idle_device_check(mesh, shape, arr._split, 0, diags, idle_seen)
        _note_fusable(arr, 0, diags)
        rep = Report(target, stages, diags)
        engine.record_diagnostics(len(diags))
        return rep

    # ---- stage 0: the chain base ------------------------------------
    if getattr(base, "is_deleted", lambda: False)():
        diags.append(Diagnostic(
            "BLT005", 0,
            "the chain base buffer has been deleted (donated to a "
            "swap(donate=True) or consumed by a donating terminal); "
            "materialising this pipeline will raise",
            hint="rebuild the pipeline from a live source array"))
        rep = Report(target, stages, diags)
        engine.record_diagnostics(len(diags))
        return rep

    # the recorded split is that of the chain's RESULT: a getitem window
    # that took an integer on a key axis lowers it on the way
    walk_split += _kdrop(funcs)
    aval = jax.ShapeDtypeStruct(tuple(base.shape), base.dtype)
    stages.append(Stage(0, "base", aval.shape, np.dtype(aval.dtype),
                        walk_split, _spec(mesh, aval.shape, walk_split)))
    idle_seen = _idle_device_check(mesh, aval.shape, walk_split, 0,
                                   diags, idle_seen)

    # the chain whose program runs over the base, the array it is planned
    # as and where its notes go: this one or, where a shared parent has
    # still to run, the parent's (this chain's own reads its result)
    planned = None
    if not shared:
        planned = (arr, funcs, len(funcs))
    elif shared[1].kept is None:
        at, node, _ = shared
        planned = (arr._parent_of(at), funcs[:node.nfuncs], node.nfuncs)
    heads = _block_heads(planned[0], base, planned[1]) if planned else {}

    # ---- the deferred map chain, one abstract stage per func --------
    failed = False
    for i, func in enumerate(funcs):
        label = _func_label(func)
        walk_split -= _kdrop((func,))
        try:
            nxt = _stage_eval(func, walk_split, aval)
        except Exception as exc:
            diags.append(_untraceable(i + 1, label, aval, exc))
            failed = True
            break
        old, new = np.dtype(aval.dtype), np.dtype(nxt.dtype)
        if new.itemsize > old.itemsize:
            diags.append(Diagnostic(
                "BLT003", i + 1,
                "%s widens the pipeline dtype %s -> %s (the materialised "
                "result costs %dx the base's HBM)"
                % (label, old, new, new.itemsize // old.itemsize),
                hint="keep constants in the input dtype or cast back "
                     "with astype/map(dtype=...) if the widening is "
                     "unintended"))
        note = (_percentile_note(func, walk_split, aval, heads.get(i))
                or _centring_note(func))
        if shared and i + 1 == shared[1].nfuncs:
            note = "; ".join(filter(None, (note, _shared_note(shared))))
        aval = nxt
        stages.append(Stage(i + 1, label, aval.shape, np.dtype(aval.dtype),
                            walk_split, _spec(mesh, aval.shape,
                                              walk_split), note=note))
        idle_seen = _idle_device_check(mesh, aval.shape, walk_split,
                                       i + 1, diags, idle_seen)

    if not failed and fp is None:
        # the chain's recorded result aval must agree with the derived one
        rec = arr._aval
        if rec is not None and (tuple(rec.shape) != tuple(aval.shape)
                                or np.dtype(rec.dtype)
                                != np.dtype(aval.dtype)):
            diags.append(Diagnostic(
                "BLT002", len(funcs),
                "the recorded result aval %s %s contradicts what the "
                "chain actually produces (%s %s)"
                % (tuple(rec.shape), np.dtype(rec.dtype),
                   tuple(aval.shape), np.dtype(aval.dtype)),
                hint="a value_shape/dtype hint lied, or deferred state "
                     "was constructed by hand; trust the derived aval"))

    if not failed and fp is not None:
        # ---- the deferred filter: predicate + dynamic compaction ----
        pidx = len(funcs) + 1
        mapped_ok = (prod(aval.shape[:walk_split]) == n
                     and tuple(aval.shape[walk_split:]) == tuple(vshape)
                     and np.dtype(aval.dtype) == np.dtype(vdtype))
        if not mapped_ok:
            diags.append(Diagnostic(
                "BLT002", pidx,
                "the recorded filter state (n=%d, value shape %s, dtype "
                "%s) contradicts the mapped chain result %s %s"
                % (n, tuple(vshape), np.dtype(vdtype),
                   tuple(aval.shape), np.dtype(aval.dtype)),
                hint="deferred filter state was constructed by hand or "
                     "the chain drifted; rebuild via filter()"))
        _check_predicate(pred, vshape, vdtype, pidx, diags)
        out_shape = (n,) + tuple(vshape)
        stages.append(Stage(pidx, "filter(%s)" % _name(pred), out_shape,
                            np.dtype(vdtype), 1, _spec(mesh, out_shape, 1),
                            dynamic=True,
                            note="survivor count pending (<= %d)" % n))
        diags.append(Diagnostic(
            "BLT008", pidx,
            "the result shape is dynamic: at most %d records survive the "
            "predicate; reading .shape dispatches the fused compaction "
            "and syncs one scalar" % n))
        dynamic = True
        # the record-wise maps called on the filter since: they stay
        # deferred with it and run on the survivors
        aval = jax.ShapeDtypeStruct(out_shape, vdtype)
        for j, func in enumerate(fp.post):
            try:
                aval = _stage_eval(func, 1, aval)
            except Exception as exc:
                diags.append(_untraceable(pidx + j + 1, _func_label(func),
                                          aval, exc))
                failed = True
                break
            stages.append(Stage(pidx + j + 1, _func_label(func),
                                aval.shape, np.dtype(aval.dtype), 1,
                                _spec(mesh, aval.shape, 1), dynamic=True))

    if not failed:
        if planned:
            _note_blocks(planned[0], base, planned[1], planned[2], diags)
        _note_admission(
            int(base.nbytes)
            + prod(tuple(stages[-1].shape))
            * np.dtype(stages[-1].dtype).itemsize,
            len(stages) - 1, diags)

    if will_donate and not failed:
        nbytes = int(base.nbytes)
        diags.append(Diagnostic(
            "BLT006", len(stages) - 1,
            "the next dispatching terminal will DONATE the %d-byte chain "
            "base to XLA (sole owner, >= engine.donation_min_bytes()); "
            "this array serves exactly ONE terminal and then becomes "
            "unreadable" % nbytes,
            hint="hold another reference to the source array or scope "
                 "engine.donation(None) to keep it readable"))

    _note_fusable(arr, len(stages) - 1, diags)
    _note_batchable(arr, len(stages) - 1, diags)
    rep = Report(target, stages, diags, dynamic=dynamic)
    engine.record_diagnostics(len(diags))
    return rep


def _note_resumable(src, idx, diags):
    """``BLT011``: this streaming plan is checkpointed (a per-source
    ``checkpoint=`` dir or an active ``stream.resumable()`` scope) but
    its source is a ONE-SHOT iterator — the iterator dies with the
    process, so a killed run can never re-stream the surviving slabs:
    resume is impossible and every checkpoint write is wasted."""
    from bolt_tpu import stream as _stream
    scope = _stream.checkpoint_scope()
    ck_dir = src.ckpt if src.ckpt is not None else (
        scope[0] if scope is not None else None)
    if ck_dir is None or src.kind != "iter" or src.blocks is None:
        return
    if iter(src.blocks) is not src.blocks:
        return                      # re-iterable (a list of blocks): fine
    diags.append(Diagnostic(
        "BLT011", idx,
        "resumable checkpointing is armed (dir %r) but this fromiter "
        "source is a one-shot iterator: a killed run cannot re-stream "
        "it, so resume is impossible and the checkpoint is wasted"
        % ck_dir,
        hint="use fromcallback (random access) or pass a re-iterable "
             "block list so a restarted run can skip the already-"
             "retired slabs"))


def _stream_ckpt_dir(src):
    """The checkpoint dir a run over ``src`` would use (per-source
    ``checkpoint=`` wins over the thread's ``resumable()`` scope), or
    ``None``."""
    from bolt_tpu import stream as _stream
    if src.ckpt is not None:
        return src.ckpt
    scope = _stream.checkpoint_scope()
    return scope[0] if scope is not None else None


def _active_supervisor():
    """The installed recovery supervisor, probed through
    ``sys.modules`` so merely checking a pipeline never imports (or
    spins up) the supervision layer."""
    import sys
    sup = sys.modules.get("bolt_tpu.parallel.supervisor")
    if sup is None:
        return None
    return sup.active()


def _recovery_plan(src, nproc):
    """The pod fault-tolerance plan ``explain()`` renders for a
    multi-process stream: heartbeat cadence, watchdog deadline, the
    resume topology a peer loss would lead to (ISSUE 11), and — when a
    recovery supervisor is installed — the SUPERVISED contract: the
    backoff budget, the quarantine state, and the rejoin door
    (ISSUE 12)."""
    from bolt_tpu.parallel import podwatch as _pw
    cfg = _pw.config()
    if cfg.get("timeout"):
        hb = ("peer loss -> PeerLostError (heartbeat %.3gs, watchdog "
              "deadline %.3gs, %s transport)"
              % (cfg["interval"], cfg["timeout"], cfg["transport"]))
    else:
        hb = "watchdog OFF (BOLT_POD_TIMEOUT=0): peer loss may hang"
    ck_dir = _stream_ckpt_dir(src)
    if ck_dir is not None:
        resume = ("resume topology: reform to the survivors (<= %d "
                  "processes) and resume from %r" % (nproc - 1, ck_dir))
    else:
        resume = ("NO checkpoint dir: peer loss discards all partials "
                  "(BLT013)")
    plan = "recovery plan: %s; %s" % (hb, resume)
    sup = _active_supervisor()
    if sup is not None:
        scfg = sup.config()
        q = scfg.get("quarantine") or []
        plan += ("; SUPERVISED: auto-reform (%d retries, %.3gs "
                 "exponential backoff), rejoin door open via the %s "
                 "transport (quiesce at a slab-boundary checkpoint, "
                 "reform UP, resume bit-identically), quarantine %s"
                 % (scfg["retries"], scfg["backoff"], cfg["transport"],
                    sorted(q) if q else "empty"))
    return plan


def _note_pod_recovery(src, nproc, idx, diags):
    """``BLT013``: this pipeline streams across processes but has no
    recovery path — either no checkpoint dir is armed (a single peer
    loss discards every fold partial) or the mesh is SUB-POD (the
    checkpoint rendezvous covers the whole runtime, so resumable
    checkpointing is refused there)."""
    if nproc <= 1:
        return
    from bolt_tpu.parallel import multihost as _mh
    ck_dir = _stream_ckpt_dir(src)
    if ck_dir is None:
        diags.append(Diagnostic(
            "BLT013", idx,
            "this pipeline streams across %d processes with NO "
            "checkpoint dir: a single peer loss discards every fold "
            "partial and the whole run restarts from scratch "
            "(recovery impossible)" % nproc,
            hint="arm stream.resumable(dir) or fromcallback/fromiter "
                 "checkpoint=dir so the survivors can "
                 "multihost.reform() and resume from the last "
                 "rendezvous-consistent watermark"))
        return
    runtime = _mh.process_count()
    if runtime > 1 and nproc != runtime:
        diags.append(Diagnostic(
            "BLT013", idx,
            "this stream's mesh spans %d of the runtime's %d "
            "processes (a SUB-POD mesh): the checkpoint rendezvous "
            "barrier covers the whole runtime, so resumable "
            "checkpointing is refused and peer loss discards all "
            "partials" % (nproc, runtime),
            hint="stream the checkpointed run on a mesh covering "
                 "every process, or drop checkpoint=/resumable() for "
                 "this sub-mesh run"))


def _note_supervised_source(src, nproc, idx, diags):
    """``BLT014``: a recovery supervisor is installed (automatic
    re-expansion is armed — ``Server(supervise=True)`` or a standalone
    ``parallel.supervisor.Supervisor``), this pipeline streams across
    processes, but its source is a ``fromiter`` block iterable: only a
    per-process ``fromcallback`` loader (shared storage, global
    coordinates) lets a REJOINED replacement process re-ingest its
    shard of the remaining slabs, so the supervisor cannot grow the
    pod during this run — re-expansion waits for the next
    per-process-sourced stream."""
    if nproc <= 1 or src.kind != "iter":
        return
    if _active_supervisor() is None:
        return
    diags.append(Diagnostic(
        "BLT014", idx,
        "automatic re-expansion is armed (a recovery supervisor is "
        "installed) but this %d-process stream reads a fromiter block "
        "iterable: a REJOINED replacement process has no way to "
        "re-ingest its shard mid-run, so the supervisor cannot grow "
        "the pod during this stream" % nproc,
        hint="use fromcallback(..., per_process=True) with a shared-"
             "storage loader (any process can then produce any shard "
             "range), or accept that re-expansion defers to the next "
             "per-process-sourced run"))


def _check_stream(arr, target, stages, diags):
    """Abstractly interpret a STREAMING plan (a lazy ``fromcallback``/
    ``fromiter`` source plus its recorded device-side stages).  Nothing
    uploads, compiles or streams — each stage evaluates through the SAME
    ``stream._stage_apply`` body the per-slab executable traces."""
    from bolt_tpu import stream as _stream
    from bolt_tpu.parallel import multihost as _mh
    src = arr._stream
    mesh = arr._mesh
    walk_split = src.split
    nslabs = -(-src.shape[0] // src.slab) if src.shape[0] else 0
    aval = jax.ShapeDtypeStruct(tuple(src.shape), src.dtype)
    nproc = _mh.mesh_process_count(mesh)
    pool = _stream.pool_size(src)
    note = ("out-of-core: ~%d slabs of %d records, prefetch depth %d, "
            "a window of %d unconfirmed slabs, uploader pool %d"
            % (nslabs, src.slab, _stream.prefetch_depth(),
               _stream.fold_ring(src) - pool, pool))
    note += ("; stored %d B an element, %d B on the wire"
             % (src.dtype.itemsize, _wire_itemsize(src)))
    if nproc > 1:
        # the per-host plan (explain() shows it): each process produces
        # and uploads only its shard of every slab; the cross-host fold
        # is the slab program's mesh collective
        note += ("; MULTI-PROCESS: %d hosts x ~%d records/slab each "
                 "(per-process ingest, shard_map cross-host fold over "
                 "axes %s)"
                 % (nproc, src.slab // nproc,
                    _mh.key_collective_axes(mesh, src.shape,
                                            walk_split) or ("?",)))
        # the RECOVERY PLAN (ISSUE 11): what happens to this run when a
        # peer dies — heartbeat cadence, watchdog deadline, and the
        # topology a reform would resume on
        note += "; " + _recovery_plan(src, nproc)
    stages.append(Stage(
        0, "stream source (%s)" % src.kind, aval.shape,
        np.dtype(aval.dtype), walk_split,
        _spec(mesh, aval.shape, walk_split), note=note))
    if nproc > 1:
        # BLT012: a slab whose record extent does not divide the
        # key-axis device assignment has no per-process split — the
        # executor refuses it with this same message
        mh_err = _mh.slab_divisibility_error(
            mesh, src.shape, walk_split,
            src.slab_ranges() if src.kind == "callback" else [])
        if mh_err is not None:
            if mh_err.startswith("BLT012: "):
                mh_err = mh_err[len("BLT012: "):]
            diags.append(Diagnostic(
                "BLT012", 0, mh_err,
                hint="pick chunks= and key extents that are multiples "
                     "of the key-axis device assignment; uneven tails "
                     "cannot stream across processes"))
    _note_admission(_stream_slab_bytes(src), 0, diags)
    _note_codec(src, 0, diags)
    _note_resumable(src, 0, diags)
    _note_pod_recovery(src, nproc, 0, diags)
    _note_supervised_source(src, nproc, 0, diags)
    idle_seen = _idle_device_check(mesh, aval.shape, walk_split, 0, diags,
                                   False)
    dynamic = False
    for i, stage in enumerate(src.stages):
        idx = i + 1
        if stage[0] == "filter":
            pred = stage[1]
            n = prod(aval.shape[:walk_split])
            vshape = tuple(aval.shape[walk_split:])
            _check_predicate(pred, vshape, aval.dtype, idx, diags)
            out_shape = (n,) + vshape
            stages.append(Stage(idx, "filter(%s) [streamed]" % _name(pred),
                                out_shape, np.dtype(aval.dtype), 1,
                                _spec(mesh, out_shape, 1), dynamic=True,
                                note="survivor count pending (<= %d); "
                                     "streamed reductions fold the mask "
                                     "per slab" % n))
            diags.append(Diagnostic(
                "BLT008", idx,
                "the result shape is dynamic: at most %d records survive "
                "the predicate; streamed reduction terminals fold the "
                "mask without materialising, any other consumer "
                "materialises the whole source" % n))
            dynamic = True
            # behind a filter come record-wise maps alone: they read
            # the flattened records and keep the dynamic row count
            aval = jax.ShapeDtypeStruct(out_shape, aval.dtype)
            walk_split = 1
            continue
        label = "%s [streamed]" % _stream.stage_label(stage)
        try:
            nxt = _stream.stage_aval(stage, walk_split, aval)
        except Exception as exc:
            first = str(exc).splitlines()[0] if str(exc) else ""
            diags.append(Diagnostic(
                "BLT001", idx,
                "%s fails abstract tracing on input %s %s: %s%s"
                % (label, tuple(aval.shape), np.dtype(aval.dtype),
                   type(exc).__name__, ": " + first if first else ""),
                hint="the stage would fail identically inside the "
                     "per-slab program; fix the callable's shape/dtype "
                     "contract"))
            break
        if stage[0] == "swap":
            # the shuffle forecast anchors on the PRE-swap geometry
            # (the planner's input), then the walk adopts the swapped
            # split for every later stage
            _note_shuffle(src, stage, aval, walk_split, mesh, idx, diags,
                          keyed=_stream.stage_extras(src.stages[:i])[0])
            walk_split = stage[2]
        old, new = np.dtype(aval.dtype), np.dtype(nxt.dtype)
        if new.itemsize > old.itemsize:
            diags.append(Diagnostic(
                "BLT003", idx,
                "%s widens the pipeline dtype %s -> %s (every streamed "
                "slab costs %dx its upload size on device)"
                % (label, old, new, new.itemsize // old.itemsize),
                hint="keep constants in the input dtype or cast back "
                     "with map(dtype=...) if the widening is unintended"))
        aval = nxt
        stages.append(Stage(idx, label, aval.shape, np.dtype(aval.dtype),
                            walk_split, _spec(mesh, aval.shape,
                                              walk_split), dynamic=dynamic))
        idle_seen = _idle_device_check(mesh, aval.shape, walk_split, idx,
                                       diags, idle_seen)
    else:
        # every stage traced: what taking the mapped result whole does
        if src.stages and not dynamic and not _stream.has_swap(src):
            _note_collect(src, len(src.stages), diags)
        if not dynamic and not _stream.has_swap(src):
            _note_gram(src, len(src.stages), diags)
    return Report(target + ", streaming (out-of-core)", stages, diags,
                  dynamic=dynamic)


def explain(obj):
    """Human-readable per-stage rendering of :func:`check`'s report."""
    return str(check(obj))
