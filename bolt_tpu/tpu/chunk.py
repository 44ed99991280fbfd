"""Chunking: block decomposition of the value axes.

Reference: ``bolt/spark/chunk.py :: ChunkedArray`` — records re-keyed to
``((key-tuple, chunk-id-tuple), block)`` with a per-value-axis ``plan`` of
chunk sizes (MB budget or explicit), optional halo ``padding``, per-block
``map``, shuffle-based ``unchunk``, and the ``keys_to_values`` /
``values_to_keys`` axis-exchange primitives behind ``swap`` (symbol-level
citations, SURVEY.md §0).

TPU-native design: the underlying array already lives sharded on the mesh,
so a ``ChunkedArray`` is a **thin view** (the BASELINE north-star's words) —
``chunk()`` records a plan without moving a byte, ``unchunk()`` returns the
wrapped array, and only ``map`` launches a compiled program: the uniform
no-padding path reshapes value axes into (grid, block) pairs and nested-
``vmap``s the function over keys+grid (one fused SPMD launch); the general
path (ragged tails, halo padding) groups blocks by static clamp category
(≤4 per chunked axis), vmaps each category's dynamic-sliced padded blocks
through ``func`` per record, trims the halo, and reassembles with the same
recursive concatenate tree the reference's ``unchunk`` uses — all inside
one jit whose trace cost is independent of the grid size.
"""

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from bolt_tpu import engine as _engine
from bolt_tpu import stream as _streamlib
from bolt_tpu.obs import trace as _obs
from bolt_tpu.parallel.sharding import combined_spec
from bolt_tpu.tpu.array import (BoltArrayTPU, _TRACE_ERRORS, _cached_jit,
                                _canon, _chain_apply, _check_live,
                                _check_value_shape, _constrain, _traceable)
from bolt_tpu.utils import (chunk_align, chunk_pad, chunk_plan, iterexpand,
                            tupleize)


def _constrain_chunked(out, mesh, split, vshard):
    """Sharding constraint preserving explicit value-axis shards where the
    output shape still divides; key-only sharding otherwise."""
    if vshard:
        try:
            spec = combined_spec(mesh, out.shape, split, vshard)
            return jax.lax.with_sharding_constraint(
                out, NamedSharding(mesh, spec))
        except ValueError:
            pass
    return _constrain(out, mesh, split)


def _axis_categories(v, c, p, g):
    """Static clamp categories for a chunked axis of length ``v`` with
    chunk size ``c``, halo ``p`` and ``g`` blocks.  Every block in a
    category shares the same padded-slice size and trim, so a whole
    category maps under one vmap.  Categories (block indices):

    - ``g == 1``: the lone block (no halo possible beyond the edges);
    - otherwise: first (0), interior (1..g-3, halo never clips since
      ``p < c``), penultimate (g-2, its upper halo may clip into a short
      ragged tail), last (g-1, ragged tail, upper halo clipped at ``v``).

    Each dict: ``count`` blocks, padded slice start ``start0 + i*stride``
    of length ``size``, core region ``[t0, t1)`` within the slice.
    """
    if g == 1:
        return [dict(count=1, start0=0, stride=0, size=v, t0=0, t1=v)]
    cats = [dict(count=1, start0=0, stride=0, size=min(v, c + p),
                 t0=0, t1=c)]
    if g >= 3:
        if g > 3:
            cats.append(dict(count=g - 3, start0=c - p, stride=c,
                             size=c + 2 * p, t0=p, t1=p + c))
        pen0 = (g - 2) * c - p
        cats.append(dict(count=1, start0=pen0, stride=0,
                         size=min(v, (g - 1) * c + p) - pen0, t0=p, t1=p + c))
    hi0 = (g - 1) * c - p
    tail = v - (g - 1) * c
    cats.append(dict(count=1, start0=hi0, stride=0, size=v - hi0,
                     t0=p, t1=p + tail))
    return cats


@dataclasses.dataclass(frozen=True)
class MappedAxis:
    """The ``vmap`` ``axis_name`` of an axis the chunked map maps.  Only
    the code that cut an axis knows how the pieces lie in memory, and a
    kernel that reads them where they lie has to be told: ``rows`` 0 is a
    key axis, stored outside the block; otherwise the axis cuts the
    block's leading (row) axis into pieces of ``rows`` rows, which on the
    chip's tiled layout lie BETWEEN the two halves of the tiled last
    axis.  ``ops/linalg.py :: gram_products`` folds both into one call
    over a view of ``data``; a mapped axis without this name, a user's own
    ``vmap`` among them, keeps its ``dot_general``."""
    rows: int = 0


def _uniform_map_body(data, func, split, plan, canon=None):
    """The uniform no-padding chunked-map program body: reshape the
    value axes into (grid, block) pairs, nested-vmap ``func`` over
    keys+grid, reassemble, optionally cast.  Geometry derives from
    ``data.shape``, so the SAME traced body serves the materialised
    whole-array program below AND the streaming executor's per-slab
    program (``bolt_tpu/stream.py``) — parity by construction."""
    kshape = data.shape[:split]
    vshape = data.shape[split:]
    nv = len(vshape)
    grid = tuple(v // c for v, c in zip(vshape, plan))
    # only an axis that is really cut (more than one chunk) is split into
    # a (grid, block) pair.  An uncut axis keeps its place: a size-1 grid
    # axis beside it says nothing, and on the chip's tiled layout it made
    # the reshape a relayout copy of the whole array (a 10.74 GB
    # temporary for f32[40,1048576,64] cut to (524288, 64) blocks, which
    # does not fit beside its source; without it the reshape stays
    # inside the consumer's fusion)
    cut = [i for i in range(nv) if grid[i] > 1]
    newshape = kshape + tuple(
        x for i in range(nv)
        for x in ((grid[i], plan[i]) if i in cut else (vshape[i],)))
    r = data.reshape(newshape)
    g_axes, c_axes, pos = [], [], split
    for i in range(nv):
        if i in cut:
            g_axes.append(pos)
            pos += 1
        c_axes.append(pos)
        pos += 1
    r = jnp.transpose(
        r, tuple(range(split)) + tuple(g_axes) + tuple(c_axes))
    ncut = len(cut)
    # the map names the axes it maps (see ``MappedAxis``); a cut of a
    # value axis other than the block's leading one stays anonymous
    names = [MappedAxis()] * split + [
        MappedAxis(plan[0]) if i == 0 and nv > 1 else None for i in cut]
    f = func
    for name in reversed(names):
        f = jax.vmap(f, axis_name=name)
    out = f(r)
    ob = out.shape[split + ncut:]
    if len(ob) != nv:
        raise ValueError(
            "chunked map must preserve block rank: block %s "
            "-> %s" % (str(tuple(plan)), str(tuple(ob))))
    # each cut axis' grid goes back in front of its own block axis
    perm, g = list(range(split)), 0
    for i in range(nv):
        if i in cut:
            perm.append(split + g)
            g += 1
        perm.append(split + ncut + i)
    out = jnp.transpose(out, tuple(perm))
    merged = kshape + tuple(g * o for g, o in zip(grid, ob))
    out = out.reshape(merged)
    if canon is not None:
        out = out.astype(canon)
    return out


def _general_map_body(data, func, split, plan, pad, canon=None):
    """The general (ragged-tail / halo-padding) chunked-map program
    body — the ≤4-clamp-category dynamic-slice scheme described on
    :meth:`ChunkedArray.map`.  Like :func:`_uniform_map_body`, geometry
    derives from ``data.shape`` so the streaming per-slab program runs
    the identical trace."""
    kshape = data.shape[:split]
    vshape = data.shape[split:]
    nv = len(vshape)
    grid = tuple(-(-v // c) for v, c in zip(vshape, plan))
    axes_cats = [_axis_categories(vshape[i], plan[i], pad[i], grid[i])
                 for i in range(nv)]

    def group(sig):
        sizes = tuple(c["size"] for c in sig)

        def one(*idx):
            starts = [jnp.int32(0)] * split + [
                c["start0"] + idx[i] * c["stride"]
                for i, c in enumerate(sig)]
            blk = jax.lax.dynamic_slice(
                data, starts, kshape + sizes)
            f = func
            for _ in range(split):
                f = jax.vmap(f)
            out = f(blk)
            if out.shape != blk.shape:
                raise ValueError(
                    "with padding or a ragged chunk plan, the "
                    "mapped function must preserve the block "
                    "shape; got %s -> %s"
                    % (str(sizes), str(out.shape[split:])))
            trim = (slice(None),) * split + tuple(
                slice(c["t0"], c["t1"]) for c in sig)
            return out[trim]

        g_fn = one
        for i in reversed(range(nv)):
            in_axes = [None] * nv
            in_axes[i] = 0
            g_fn = jax.vmap(g_fn, in_axes=tuple(in_axes))
        res = g_fn(*(jnp.arange(c["count"], dtype=jnp.int32)
                     for c in sig))
        # (count_0..count_{nv-1}, *kshape, *trims) →
        # (*kshape, count_0*trim_0, ...)
        perm = tuple(range(nv, nv + split)) + tuple(
            x for i in range(nv) for x in (i, nv + split + i))
        res = jnp.transpose(res, perm)
        return res.reshape(kshape + tuple(
            c["count"] * (c["t1"] - c["t0"]) for c in sig))

    def assemble(prefix, level):
        if level == nv:
            return group(tuple(prefix))
        parts = [assemble(prefix + [c], level + 1)
                 for c in axes_cats[level] if c["count"] > 0]
        if len(parts) == 1:
            return parts[0]
        return jnp.concatenate(parts, axis=split + level)

    out = assemble([], 0)
    if canon is not None:
        out = out.astype(canon)
    return out


class ChunkedArray:
    """A chunk-plan view over a :class:`BoltArrayTPU`."""

    def __init__(self, barray, plan, padding, vshard=None):
        self._barray = barray
        self._plan = tuple(int(p) for p in plan)
        self._padding = tuple(int(p) for p in padding)
        # value-axis -> mesh-axis shards (sequence-parallel analog)
        self._vshard = dict(vshard) if vshard else {}

    # ------------------------------------------------------------------
    # construction (reference: ``ChunkedArray._chunk``)
    # ------------------------------------------------------------------

    @classmethod
    def chunk(cls, barray, size="150", axis=None, padding=None):
        """Compute the chunk ``plan``.

        ``size``: a string is a per-block megabyte budget (the reference's
        ``size='150'`` default) — the largest chunkable axis is halved until
        the block fits; an int/tuple gives explicit chunk sizes for the
        chosen ``axis`` set.  ``padding`` adds a halo (elements borrowed
        from neighbouring chunks, clipped at the array edge) on the chunked
        axes.
        """
        split = barray.split
        vshape = barray.shape[split:]
        axes, size, padding = chunk_align(vshape, axis, size, padding)
        plan = chunk_plan(vshape, barray.dtype.itemsize, size, axes,
                          padding=padding)
        pad = chunk_pad(plan, axes, padding, vshape)
        return cls(barray, plan, pad)

    # ------------------------------------------------------------------
    # properties (reference: ``ChunkedArray.plan/padding/kshape/vshape/
    # uniform``)
    # ------------------------------------------------------------------

    @property
    def plan(self):
        return self._plan

    @property
    def padding(self):
        return self._padding

    @property
    def kshape(self):
        b = self._barray
        return b.shape[:b.split]

    @property
    def vshape(self):
        b = self._barray
        return b.shape[b.split:]

    @property
    def shape(self):
        return self._barray.shape

    @property
    def split(self):
        return self._barray.split

    @property
    def dtype(self):
        return self._barray.dtype

    @property
    def mode(self):
        return "tpu"

    @property
    def grid(self):
        """Number of chunks along each value axis."""
        return tuple(-(-v // c) for v, c in zip(self.vshape, self._plan))

    @property
    def uniform(self):
        """True when every chunk has the same shape (no ragged tail)."""
        return all(v % c == 0 for v, c in zip(self.vshape, self._plan))

    @property
    def vshard(self):
        """Value-axis → mesh-axis shards (empty unless :meth:`shard`-ed)."""
        return dict(self._vshard)

    # ------------------------------------------------------------------
    # value-axis sharding: the sequence/context-parallel analog.  The
    # reference scales a too-long contiguous axis by chunking it over
    # workers (SURVEY §2.4 "block/chunk decomposition ... closest analog to
    # sequence parallelism"); here the axis is split across the mesh
    # itself, and padded per-block maps get their halos from GSPMD's
    # inserted neighbour collectives.
    # ------------------------------------------------------------------

    def shard(self, mesh_axis, axis=None):
        """Shard a chunked value axis across the (unused) mesh axis
        ``mesh_axis``.  ``axis`` defaults to the first chunked value axis.
        Returns a new :class:`ChunkedArray` whose underlying data is
        resharded (an ICI scatter, no host round-trip)."""
        b = self._barray
        if axis is None:
            chunked = [i for i, (v, c) in enumerate(zip(self.vshape, self._plan))
                       if c < v]
            axis = chunked[0] if chunked else 0
        vshard = dict(self._vshard)
        vshard[axis] = mesh_axis
        spec = combined_spec(b.mesh, b.shape, b.split, vshard)  # validates
        data = _streamlib.transfer(b._data, NamedSharding(b.mesh, spec))
        return ChunkedArray(BoltArrayTPU(data, b.split, b.mesh),
                            self._plan, self._padding, vshard)

    # ------------------------------------------------------------------
    # per-block map (reference: ``ChunkedArray.map`` with padding trim)
    # ------------------------------------------------------------------

    def map(self, func, value_shape=None, dtype=None):
        """Apply ``func`` to every chunk of every record; returns a new
        :class:`ChunkedArray`.

        With no padding and a uniform plan, ``func`` may change the block
        shape (rank-preserving — e.g. the per-chunk SVD of BASELINE config
        5); with padding or a ragged tail, ``func`` must preserve the block
        shape so the halo can be trimmed and the tiles reassembled.
        """
        func = _traceable(func)
        _engine.strict_guard(self._barray, "chunk().map()")
        hint_ob = None
        if value_shape is not None:
            # reference-parity hint: validate the per-block output shape
            # (reference ChunkedArray.map accepts the same hint to skip
            # its run-one-block inference)
            try:
                hint_ob = jax.eval_shape(func, jax.ShapeDtypeStruct(
                    tuple(self._plan), self._barray._aval.dtype))
            except _TRACE_ERRORS:
                # non-traceable func: skip hint validation (errors surface
                # at the real trace)
                hint_ob = None
            _check_value_shape(
                value_shape, None if hint_ob is None else tuple(hint_ob.shape))
        b = self._barray
        if b._stream is not None and not self._vshard:
            # streaming source (out-of-core): record the per-block map as
            # a device-side stage — nothing uploads or compiles until a
            # reduction terminal drives the double-buffered pipeline
            out = _streamlib.chunked_map_stage(self, func, dtype)
            if out is not NotImplemented:
                return out
        split = b.split
        mesh = b.mesh
        kshape = self.kshape
        vshape = self.vshape
        nv = len(vshape)
        plan = self._plan
        pad = self._padding
        grid = self.grid
        padded = any(p > 0 for p in pad)
        vshard = dict(self._vshard)
        vs_key = tuple(sorted(vshard.items()))
        # a deferred chain on the underlying array fuses INTO the chunked
        # program — no materialised intermediate between map and chunk.map;
        # a sole-owned chain base additionally DONATES its buffer to the
        # program (the chunked output is input-sized, so XLA aliases the
        # two — the chunk→map→unchunk pipeline's donation-aware terminal)
        donate = b.deferred and b._donatable()
        base, funcs = b._chain_parts()
        canon = None if dtype is None else _canon(dtype)

        if self.uniform and not padded:
            # decide the OUTPUT's value sharding up front so the returned
            # metadata matches what the constraint actually applies: a
            # shape-changing block func can break divisibility, in which
            # case the axis really is re-replicated and we say so
            if vshard:
                keep = False
                try:
                    ob_shape = tuple(hint_ob.shape) if hint_ob is not None \
                        else tuple(jax.eval_shape(
                            func, jax.ShapeDtypeStruct(
                                tuple(plan), b._aval.dtype)).shape)
                except _TRACE_ERRORS:
                    ob_shape = None
                if ob_shape is not None and len(ob_shape) == nv:
                    out_full = kshape + tuple(
                        g * o for g, o in zip(grid, ob_shape))
                    try:
                        combined_spec(mesh, out_full, split, vshard)
                        keep = True
                    except ValueError:
                        pass
                if not keep:
                    # unverifiable or indivisible output: the constraint
                    # would fall back to key-only sharding, so the metadata
                    # must not claim otherwise
                    import warnings
                    warnings.warn(
                        "chunked map output does not (verifiably) divide the "
                        "mesh for value shard %s; the axis is now replicated"
                        % (vshard,))
                    vshard = {}
                    vs_key = ()

            def build():
                def run(data):
                    data = _chain_apply(funcs, split, data)
                    out = _uniform_map_body(data, func, split, plan, canon)
                    return _constrain_chunked(out, mesh, split, vshard)
                return jax.jit(run, donate_argnums=(0,) if donate else ())

            fn = _cached_jit(("chunk-map-u", func, funcs, base.shape,
                              str(base.dtype), split, plan, vs_key, canon,
                              donate, mesh), build)
            with _obs.span("chunk.map", path="uniform", donate=donate):
                out = fn(_check_live(base))
            if donate:
                b._consume_donated("chunk().map()")
            new_plan = tuple(o // g for o, g in zip(out.shape[split:], grid))
            return ChunkedArray(BoltArrayTPU(out, split, mesh), new_plan, pad,
                                vshard)

        # general path: ragged tails and/or halo padding.  Blocks along a
        # chunked axis fall into at most FOUR static clamp categories —
        # first (halo clipped below), interior, penultimate (halo may clip
        # into a short tail), last (ragged tail, halo clipped above) — so
        # each category product is one nested-vmapped dynamic_slice +
        # per-record func + static trim.  Trace cost is O(4^chunked_axes),
        # independent of the grid size (a 10k-chunk axis traces func the
        # same ≤4 times a 3-chunk axis does); the reference pays a record
        # per block here, we pay one compiled program.
        def build():
            def run(data):
                data = _chain_apply(funcs, split, data)
                out = _general_map_body(data, func, split, plan, pad, canon)
                return _constrain_chunked(out, mesh, split, vshard)
            return jax.jit(run, donate_argnums=(0,) if donate else ())

        fn = _cached_jit(("chunk-map-g", func, funcs, base.shape,
                          str(base.dtype), split, plan, pad, vs_key, canon,
                          donate, mesh), build)
        with _obs.span("chunk.map", path="general", donate=donate):
            out = fn(_check_live(base))
        if donate:
            b._consume_donated("chunk().map()")
        return ChunkedArray(BoltArrayTPU(out, split, mesh), plan, pad, vshard)

    # ------------------------------------------------------------------
    # axis exchange (reference: ``ChunkedArray.keys_to_values`` /
    # ``values_to_keys`` — the primitives behind ``swap``)
    # ------------------------------------------------------------------

    def keys_to_values(self, axes, size=None):
        """Move key axes into the values (they land at the FRONT of the
        value group in the order given, matching the swap algebra).  The
        data movement is the resharding inside ``swap`` — an ``all_to_all``
        over the mesh.  Moving EVERY key axis is allowed (the reference
        keeps blocks keyed by chunk ids); the result has ``split=0`` until
        ``values_to_keys`` restores key axes."""
        axes = tuple(tupleize(axes))
        split = self._barray.split
        for a in axes:
            if a < 0 or a >= split:
                raise ValueError(
                    "key axis %d out of range for split %d" % (a, split))
        if len(set(axes)) != len(axes):
            raise ValueError("keys_to_values axes must be unique")
        swapped = self._barray._do_swap(axes, ())
        moved = [self._barray.shape[a] for a in axes]
        if size is not None:
            sizes = iterexpand(size, len(moved))
            for s in sizes:
                if int(s) < 1:
                    raise ValueError(
                        "chunk size must be >= 1, got %d" % int(s))
            moved = [min(int(s), m) for s, m in zip(sizes, moved)]
        new_plan = tuple(moved) + self._plan
        new_pad = (0,) * len(moved) + self._padding
        # surviving value axes shift right by the number moved in
        new_vshard = {va + len(moved): name
                      for va, name in self._vshard.items()}
        return self._rewrap(swapped, new_plan, new_pad, new_vshard)

    def values_to_keys(self, axes):
        """Move value axes into the keys (appended after the existing key
        axes, matching the swap algebra)."""
        axes = tuple(tupleize(axes))
        nv = len(self.vshape)
        for a in axes:
            if a < 0 or a >= nv:
                raise ValueError(
                    "value axis %d out of range for %d value axes" % (a, nv))
        swapped = self._barray.swap((), axes)
        keep = [i for i in range(nv) if i not in axes]
        new_plan = tuple(self._plan[i] for i in keep)
        new_pad = tuple(self._padding[i] for i in keep)
        new_vshard = {pos: self._vshard[old]
                      for pos, old in enumerate(keep) if old in self._vshard}
        return self._rewrap(swapped, new_plan, new_pad, new_vshard)

    def _rewrap(self, barray, plan, padding, vshard):
        """Wrap a swapped underlying array, re-applying value-axis shards
        that survived the swap (the swap itself constrains to key-only
        sharding, which would silently re-replicate a long axis the user
        sharded to fit memory)."""
        if vshard:
            try:
                spec = combined_spec(barray.mesh, barray.shape, barray.split,
                                     vshard)
            except ValueError:
                import warnings
                warnings.warn(
                    "value-axis shard %s no longer divides after the axis "
                    "exchange; the axis is now replicated" % (vshard,))
                vshard = {}
            else:
                data = _streamlib.transfer(
                    barray._data, NamedSharding(barray.mesh, spec))
                barray = BoltArrayTPU(data, barray.split, barray.mesh)
        return ChunkedArray(barray, plan, padding, vshard)

    # ------------------------------------------------------------------

    def unchunk(self):
        """Back to a :class:`BoltArrayTPU` — a no-op unwrap: the data never
        left its assembled, mesh-resident layout (reference:
        ``ChunkedArray.unchunk`` pays a full shuffle here)."""
        return self._barray

    # ------------------------------------------------------------------
    # reduction terminals (ISSUE 3): the chunked view is thin, so these
    # delegate to the wrapped array's terminals — which means a chunked
    # view over a STREAMING source (a lazy ``fromcallback``/``fromiter``)
    # runs the out-of-core double-buffered executor
    # (``bolt_tpu/stream.py``), while a materialised view compiles the
    # standard fused programs.  One code path, two execution engines.
    # ------------------------------------------------------------------

    def sum(self, axis=None, keepdims=False):
        """Sum over ``axis`` (default: all key axes); streams when the
        underlying array is an out-of-core source."""
        return self._barray.sum(axis=axis, keepdims=keepdims)

    def mean(self, axis=None, keepdims=False):
        """Mean over ``axis`` (default: all key axes); streamed means
        merge per-chunk Welford/statcounter moments on device."""
        return self._barray.mean(axis=axis, keepdims=keepdims)

    def var(self, axis=None, keepdims=False, ddof=0):
        """Variance over ``axis`` (``ddof`` like the array method)."""
        return self._barray.var(axis=axis, keepdims=keepdims, ddof=ddof)

    def std(self, axis=None, keepdims=False, ddof=0):
        """Standard deviation over ``axis``."""
        return self._barray.std(axis=axis, keepdims=keepdims, ddof=ddof)

    def reduce(self, func, axis=(0,), keepdims=False):
        """Pairwise-tree reduction over the key axes; streamed sources
        fold per-chunk partials with ``func`` on device."""
        return self._barray.reduce(func, axis=axis, keepdims=keepdims)

    def filter(self, func, axis=(0,), sort=False):
        """Filter records by a predicate — leaves the chunked view (the
        result is re-keyed flat, like the array method).  On a streaming
        source the predicate stays lazy and reduction terminals fold its
        mask into the per-chunk pass."""
        return self._barray.filter(func, axis=axis, sort=sort)

    def __repr__(self):
        s = "ChunkedArray\n"
        s += "mode: tpu\n"
        s += "shape: %s\n" % str(self.shape)
        s += "split: %d\n" % self.split
        s += "plan: %s\n" % str(self._plan)
        s += "padding: %s\n" % str(self._padding)
        s += "grid: %s\n" % str(self.grid)
        return s
