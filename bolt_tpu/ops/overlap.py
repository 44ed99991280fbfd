"""Halo-overlap mapping and separable smoothing over the value axes.

The reference's chunk ``padding`` exists for exactly this workload: its
ecosystem (Thunder) ran spatial filters over image stacks by chunking the
spatial axes with a halo so each block sees its neighbours' boundary rows
(``bolt/spark/chunk.py :: ChunkedArray`` padding — symbol-level citation,
SURVEY.md §0).  :func:`map_overlap` packages that pattern (dask names the
same idiom ``map_overlap``); :func:`smooth` builds the canonical consumer —
a separable boxcar filter — on top of it.

Both work on either backend: on TPU the chunked map is one compiled SPMD
program and halos ride GSPMD's neighbour collectives; locally the same
contract runs on NumPy (the oracle).
"""

from functools import lru_cache

import numpy as np
import jax
import jax.numpy as jnp

from bolt_tpu.utils import chunk_axes, iterexpand, tupleize

# boundary-mode names follow numpy.pad; scipy.ndimage's names are accepted
# as aliases (scipy 'reflect' repeats the edge sample = np 'symmetric';
# scipy 'mirror' excludes it = np 'reflect'; scipy 'nearest' = np 'edge')
_PAD_MODES = ("constant", "reflect", "edge", "symmetric")
_MODE_ALIASES = {"mirror": "reflect", "nearest": "edge"}


def _canon_mode(mode):
    mode = _MODE_ALIASES.get(mode, mode)
    if mode not in _PAD_MODES:
        raise ValueError("mode must be one of %s (or scipy aliases %s), "
                         "got %r" % (_PAD_MODES, tuple(_MODE_ALIASES), mode))
    return mode


def map_overlap(b, func, depth, axis=None, size="150", value_shape=None,
                dtype=None, shard=None):
    """Apply ``func`` to halo-padded blocks of the value axes and
    reassemble: ``b.chunk(size, axis, padding=depth).map(func).unchunk()``.

    ``depth`` is the halo width (scalar, or per-axis paired with ``axis``
    in the order given); ``func`` must
    preserve the block shape (the padded-map contract — the halo is
    trimmed after).  Each block sees ``depth`` extra elements from its
    neighbours on the chunked axes, clipped at the array edges, so
    stencil/filter funcs compute correct values at interior block
    boundaries without any global pass.

    ``shard`` (TPU backend only) splits chunked VALUE axes across mesh
    axes — the sequence-parallel regime, for contiguous axes too long
    for one device: a mesh-axis name (applied to the first chunked
    axis) or a ``{value_axis: mesh_axis}`` dict.  Halos then ride
    GSPMD's inserted neighbour collectives (ICI/DCN).
    """
    if shard is not None and b.mode != "tpu":
        raise ValueError("shard= needs the tpu backend (a mesh); "
                         "mode=%r has no mesh axes" % (b.mode,))
    c = b.chunk(size=size, axis=axis, padding=depth)
    if shard is not None:
        if isinstance(shard, dict):
            for va, name in sorted(shard.items()):
                c = c.shard(name, axis=va)
        else:
            c = c.shard(shard)
    return c.map(func, value_shape=value_shape, dtype=dtype).unchunk()


def _odd_widths(width, n):
    """Validate per-axis window widths: odd and >= 1 (shared by the whole
    filter family — a symmetric window needs an integer radius)."""
    widths = [int(w) for w in iterexpand(width, n)]
    for w in widths:
        if w < 1 or w % 2 == 0:
            raise ValueError("filter width must be odd and >= 1, got %d" % w)
    return widths


def _halo_pad(x, axes, widths, mode, xp):
    """Pad ``x`` by each window's radius on its axis with boundary
    ``mode`` (the shared pad step before any shifted-slice window)."""
    pad = [(0, 0)] * x.ndim
    for ax, w in zip(axes, widths):
        pad[ax] = (w // 2, w // 2)
    return xp.pad(x, pad, mode=mode)


def _filter1d(x, ax, taps, mode, xp):
    """Correlation of ``x`` with the 1-d ``taps`` along ``ax`` ('same'
    size, boundary per ``mode``) — the weighted sum of ``len(taps)``
    shifted slices of the padded array, which is exact (no cumsum
    cancellation) for the small widths filters use."""
    w = len(taps)
    length = x.shape[ax]
    xpad = _halo_pad(x, [ax], [w], mode, xp)
    acc = None
    for off in range(w):
        sl = [slice(None)] * x.ndim
        sl[ax] = slice(off, off + length)
        piece = xpad[tuple(sl)] * taps[off]
        acc = piece if acc is None else acc + piece
    return acc


@lru_cache(maxsize=256)
def _sepfilter_fn(taps_key, axes, mode):
    """Memoised block function for the separable filters: identical
    (taps, axes, mode) return the SAME callable object, so the chunked
    map's jit cache (keyed on function identity) hits and repeated
    filter calls dispatch in milliseconds instead of recompiling."""
    def sepfilter(blk):
        xp = np if isinstance(blk, np.ndarray) else jnp
        out = blk
        for ax, taps in zip(axes, taps_key):
            if len(taps) > 1 or taps[0] != 1.0:  # skip only the identity
                out = _filter1d(out, ax, taps, mode, xp)
        return out
    return sepfilter


def _separable_filter(b, taps_list, axes, size, mode, shard=None,
                      precision=None):
    """Shared core of :func:`smooth`/:func:`convolve`/:func:`gaussian`:
    one program applying a 1-d tap filter per axis.

    On the TPU backend (no ``shard=``) the filter runs as ONE
    whole-array program whose per-axis correlations are Pallas window
    kernels where the plan allows — each block reads HBM once and
    windows in VMEM, where the XLA shifted-slice form re-reads the
    operand once per tap.  Anything the kernel can't serve (unplannable
    geometry, non-float dtype) takes the halo-chunked machinery, which
    also serves ``shard=`` (sequence-parallel) and the local oracle."""
    from bolt_tpu._precision import resolve
    pr = resolve(precision)
    mode = _canon_mode(mode)
    depth = tuple(len(t) // 2 for t in taps_list)
    taps_key = tuple(tuple(float(t) for t in taps) for taps in taps_list)
    if b.mode == "tpu" and shard is None:
        out = _whole_array_sepfilter(b, taps_key, tuple(axes), mode, pr)
        if out is not None:
            return out
    sepfilter = _sepfilter_fn(taps_key, tuple(axes), mode)
    return map_overlap(b, sepfilter, depth, axis=axes, size=size,
                       shard=shard)


def _whole_array_sepfilter(b, taps_key, axes, mode, precision="highest"):
    """ONE compiled program filtering every requested axis of the full
    (sharded) array — Pallas window kernel per axis, shifted-slice for
    any axis the plan can't serve.  The filtered axes are VALUE axes,
    whole on every device, so the kernels run per shard under
    ``shard_map`` with no communication (Mosaic kernels cannot be
    partitioned by GSPMD).  Returns None (caller takes the chunked
    path) when no axis can use the kernel.  A geometry the plan admits
    and Mosaic refuses is a bug to see: the compile error propagates."""
    import numpy as _np
    from bolt_tpu._compat import shard_map
    from bolt_tpu.ops import kernels
    from bolt_tpu.parallel.sharding import key_sharding
    from bolt_tpu.tpu.array import (_cached_jit, _chain_apply, _check_live,
                                    _constrain)
    split = b.split
    active = [(split + a, taps) for a, taps in zip(axes, taps_key)
              if len(taps) > 1 or taps[0] != 1.0]
    if not active:
        # identity filter: a NEW wrapper, never the input itself (the
        # in-place surface — sort, wrapper rebinds — must not alias)
        return b._clone()
    itemsize = _np.dtype(b.dtype).itemsize
    if not _np.issubdtype(_np.dtype(b.dtype), _np.floating):
        return None
    mesh = b.mesh
    sharding = key_sharding(mesh, b.shape, split)
    local = sharding.shard_shape(tuple(b.shape))   # what a kernel sees
    if not any(kernels.sepfilter_capable(local, itemsize, g, len(t),
                                         mode=mode)
               for g, t in active):
        return None
    base, funcs = b._chain_parts()
    key = ("sepfilter", taps_key, axes, mode, funcs, base.shape,
           str(base.dtype), split, mesh, precision)

    def build():
        def per_shard(x):
            for g, taps in active:
                y = kernels.sepfilter1d(x, taps, g, mode=mode,
                                        precision=precision)
                x = y if y is not None else _filter1d(x, g, taps, mode, jnp)
            return x

        # check_vma=False: the pallas out_shape carries no vma annotation
        filt = shard_map(per_shard, mesh, in_specs=sharding.spec,
                         out_specs=sharding.spec, check_vma=False)

        def run(d):
            x = _chain_apply(funcs, split, d)
            return _constrain(filt(x), mesh, split)
        return jax.jit(run)

    out = _cached_jit(key, build)(_check_live(base))
    return b._wrap(out, split)


def _filter_axes(b, axis):
    """Value axes for a filtering op, in the caller's order (widths/taps
    bind to the axes as given; the chunk layer re-sorts (axis, depth)
    pairs together via ``chunk_align``)."""
    split = b.split if b.mode == "tpu" else 1
    vshape = b.shape[split:]
    axes = (chunk_axes(vshape, None) if axis is None
            else tuple(tupleize(axis)))
    chunk_axes(vshape, axes)  # validate (range, uniqueness)
    return axes


def smooth(b, width, axis=None, size="150", mode="constant", shard=None,
           precision=None):
    """Separable moving-average (boxcar) filter along value axes — the
    Thunder-style spatial smoothing workload, one halo-padded blockwise
    program per backend.

    ``width``: odd window (scalar or per-``axis``, paired in the order
    given); ``axis``: the value axes to filter (default: all); ``size``:
    chunk plan for the blockwise execution; ``mode``: boundary handling
    at the ARRAY edges — ``'constant'`` (zeros, numpy ``convolve 'same'``
    semantics), ``'reflect'``, ``'edge'`` or ``'symmetric'`` (numpy.pad
    names; scipy's ``'mirror'``/``'nearest'`` accepted as aliases —
    see ``_canon_mode``).  Boundary modes stay exact
    under chunking because an edge block's clipped halo ends exactly at
    the array boundary.  Floating inputs keep their dtype; integers
    promote through the mean's true division.
    """
    axes = _filter_axes(b, axis)
    widths = _odd_widths(width, len(axes))
    taps_list = [[1.0 / w] * w for w in widths]
    return _separable_filter(b, taps_list, axes, size, mode, shard=shard,
                             precision=precision)


def convolve(b, kernel, axis=None, size="150", mode="constant",
             shard=None, precision=None):
    """Separable correlation with explicit 1-d kernels along value axes.

    ``kernel``: a 1-d sequence of odd length, or one such sequence per
    ``axis`` (paired in the order given).  Orientation is correlation
    (the filter is not flipped), matching ``scipy.ndimage``; symmetric
    kernels — the usual case — make the distinction moot.  Same
    boundary/chunking semantics as :func:`smooth`.
    """
    axes = _filter_axes(b, axis)
    kern = list(kernel)
    if kern and np.isscalar(kern[0]):
        taps_list = [[float(t) for t in kern]] * len(axes)
    else:
        if len(kern) != len(axes):
            raise ValueError("expected %d kernels for %d axes, got %d"
                             % (len(axes), len(axes), len(kern)))
        taps_list = [[float(t) for t in k] for k in kern]
    _odd_widths([len(taps) for taps in taps_list], len(taps_list))
    return _separable_filter(b, taps_list, axes, size, mode, shard=shard,
                             precision=precision)


def gaussian(b, sigma, axis=None, size="150", mode="constant", truncate=4.0,
             shard=None, precision=None):
    """Separable Gaussian filter along value axes (``scipy.ndimage.
    gaussian_filter`` tap construction: radius ``truncate * sigma``,
    normalised).  ``sigma``: scalar or per-``axis``."""
    axes = _filter_axes(b, axis)
    sigmas = [float(s) for s in iterexpand(sigma, len(axes))]
    taps_list = []
    for s in sigmas:
        if s < 0:
            raise ValueError("sigma must be >= 0, got %r" % (s,))
        radius = int(truncate * s + 0.5)
        grid = np.arange(-radius, radius + 1, dtype=np.float64)
        taps = np.exp(-0.5 * (grid / s) ** 2) if s > 0 else np.ones(1)
        taps_list.append([float(t) for t in taps / taps.sum()])
    return _separable_filter(b, taps_list, axes, size, mode, shard=shard,
                             precision=precision)


def median_filter(b, width, axis=None, size="150", mode="symmetric",
                  shard=None):
    """Windowed median filter along value axes — the joint (rectangular)
    window over ALL named axes, matching ``scipy.ndimage.median_filter``
    (a median is not separable, so multi-axis requests stack every
    offset in the window product).  ``width``: odd window per axis; the
    default boundary (np ``'symmetric'``) is scipy's default
    (``'reflect'`` in scipy's vocabulary).  Same halo/chunking machinery
    as the linear filters: exact at block boundaries, one compiled
    program on TPU, `shard=` for mesh-split axes."""
    mode = _canon_mode(mode)
    axes = _filter_axes(b, axis)
    widths = _odd_widths(width, len(axes))
    depth = tuple(w // 2 for w in widths)
    medfilt = _medfilt_fn(tuple(axes), tuple(widths), mode)
    return map_overlap(b, medfilt, depth, axis=axes, size=size, shard=shard)


@lru_cache(maxsize=256)
def _medfilt_fn(axes, widths, mode):
    """Memoised median block function (same rationale as
    :func:`_sepfilter_fn`)."""
    from itertools import product as _product
    offsets = list(_product(*[range(w) for w in widths]))

    def medfilt(blk):
        xp = np if isinstance(blk, np.ndarray) else jnp
        xpad = _halo_pad(blk, axes, widths, mode, xp)
        pieces = []
        for off in offsets:
            sl = [slice(None)] * blk.ndim
            for ax, o in zip(axes, off):
                sl[ax] = slice(o, o + blk.shape[ax])
            pieces.append(xpad[tuple(sl)])
        return xp.median(xp.stack(pieces, axis=0), axis=0)

    return medfilt
