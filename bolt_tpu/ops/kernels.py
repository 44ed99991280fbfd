"""Pallas TPU kernels for the passes XLA's own fusion cannot make in one.

A plain ``sum(fn(x))`` is XLA's fused reduction (the ``reduce`` cell of the
benchmark measures it); the kernels here are the ones something calls:

* :func:`fused_welford` — mean / centred second moment / min / max over
  axis 0 in ONE pass over HBM (the centred moment needs the finished mean,
  so XLA reads twice); ``tpu/stats.py`` calls it for ``stats()``.
* :func:`sepfilter1d` — a separable filter's 1-d pass with every block read
  once, and its wide-window lane form :func:`lane_band_pallas` (with the
  XLA twin :func:`lane_band_conv`); ``ops/overlap.py`` calls them.

Blocks are carved from the array's ORIGINAL shape — no reshape, because on
TPU a reshape that merges the minor (tiled) dims is a physical relayout
copy, which would double HBM for a 10 GB input.  A kernel whose plan does
not apply returns ``None`` and its caller keeps the XLA path.  Off-TPU the
kernels run in interpret mode, so the same code paths are testable on the
CPU mesh.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from bolt_tpu.utils import prod


def _interpret_default():
    return jax.default_backend() != "tpu"


def _padded_bytes(block, itemsize):
    """VMEM footprint of a block after TPU tiling pads the last dim to 128
    lanes and the second-to-last to 8 sublanes."""
    if len(block) == 0:
        return itemsize
    dims = list(block)
    dims[-1] = -(-dims[-1] // 128) * 128
    if len(dims) >= 2:
        dims[-2] = -(-dims[-2] // 8) * 8
    return prod(dims) * itemsize


def _largest_divisor_fitting(n, unit_bytes, budget):
    """Largest divisor d of n with d * unit_bytes <= budget (or None)."""
    best = None
    d = 1
    while d * d <= n:
        if n % d == 0:
            for cand in (d, n // d):
                if cand * unit_bytes <= budget and (best is None or cand > best):
                    best = cand
        d += 1
    return best


def _welford_kernel(x_ref, mu_ref, m2_ref, mn_ref, mx_ref, *, t0):
    """Chan parallel-combine over leading-axis blocks, elementwise in the
    value shape.  The whole point: the centred second moment needs the
    finished mean, so XLA computes mean/m2 in TWO passes over HBM; here
    each block's two "passes" happen on the VMEM-resident tile and the
    combine is O(value tile), making the welford moments ONE HBM pass."""
    i = pl.program_id(1)
    blk = x_ref[...].astype(mu_ref.dtype)   # sub-f32 inputs widen in VMEM
    bmu = jnp.mean(blk, axis=0)
    bm2 = jnp.sum((blk - bmu[None]) ** 2, axis=0)
    bmn = jnp.min(blk, axis=0)
    bmx = jnp.max(blk, axis=0)

    @pl.when(i == 0)
    def _init():
        mu_ref[...] = bmu
        m2_ref[...] = bm2
        mn_ref[...] = bmn
        mx_ref[...] = bmx

    @pl.when(i > 0)
    def _combine():
        n_a = (i * t0).astype(bmu.dtype)
        n_b = jnp.asarray(t0, bmu.dtype)
        delta = bmu - mu_ref[...]
        tot = n_a + n_b
        mu_ref[...] += delta * (n_b / tot)
        m2_ref[...] += bm2 + delta * delta * (n_a * n_b / tot)
        mn_ref[...] = jnp.minimum(mn_ref[...], bmn)
        mx_ref[...] = jnp.maximum(mx_ref[...], bmx)


def welford_plan(shape, itemsize):
    """Pick ``(t0, v0)`` for :func:`fused_welford` on ``shape`` =
    ``(n, *vshape)``: leading-axis block ``t0`` rows × a value tile that
    splits ``vshape[0]`` into ``v0``-sized pieces.  None when the kernel
    shouldn't engage (non-128-aligned minor dim — feeding one to a TPU
    pallas kernel relayout-copies the whole operand — or nothing tiles
    into VMEM)."""
    if len(shape) < 2 or shape[-1] % 128 != 0 or shape[0] < 2:
        return None
    vshape = shape[1:]
    inner = _padded_bytes(vshape[1:], itemsize) if len(vshape) > 1 else itemsize
    # VMEM holds: input block ×2 (double buffering), a block-sized
    # centred-deviation temporary, and 4 resident accumulator tiles —
    # budget each piece well under the ~16 MB/core limit (an 18.4 MB
    # stack OOM was measured with looser budgets)
    v0 = _largest_divisor_fitting(vshape[0], inner, 256 << 10)
    if v0 is None:
        return None
    tile_bytes = _padded_bytes((v0,) + vshape[1:], itemsize)
    t0 = _largest_divisor_fitting(shape[0], tile_bytes, 2 << 20)
    if t0 is None or t0 < 2:
        return None
    return t0, v0


def fused_welford(x, interpret=None):
    """Single-HBM-pass ``(mean, m2, min, max)`` over axis 0 of ``x``,
    each shaped ``x.shape[1:]`` (``m2`` = sum of squared deviations, the
    StatCounter field).  Returns None when the plan doesn't apply — the
    caller keeps its jnp two-pass path.

    XLA cannot fuse the mean and the centred second moment (sequential
    dependence → two HBM reads), while this kernel reads HBM once.
    """
    plan = welford_plan(x.shape, x.dtype.itemsize)
    if plan is None or not jnp.issubdtype(x.dtype, jnp.floating):
        return None
    t0, v0 = plan
    if interpret is None:
        interpret = _interpret_default()
    n = x.shape[0]
    vshape = x.shape[1:]
    grid = (vshape[0] // v0, n // t0)   # n innermost: accumulators stay put
    block = (t0, v0) + tuple(vshape[1:])
    out_block = (v0,) + tuple(vshape[1:])
    acc = jnp.float32 if x.dtype in (jnp.bfloat16, jnp.float16) else x.dtype
    out_shape = jax.ShapeDtypeStruct(vshape, acc)

    def in_map(j, i):
        return (i, j) + (0,) * (len(vshape) - 1)

    def out_map(j, i):
        return (j,) + (0,) * (len(vshape) - 1)

    mu, m2, mn, mx = pl.pallas_call(
        partial(_welford_kernel, t0=t0),
        grid=grid,
        in_specs=[pl.BlockSpec(block, in_map)],
        out_specs=[pl.BlockSpec(out_block, out_map)] * 4,
        out_shape=[out_shape] * 4,
        interpret=interpret,
    )(x)
    # match the jnp fallback's dtype exactly, so the SAME stats() call
    # returns the same dtype/precision whether or not the kernel engaged
    # (sub-f32 inputs accumulate in f32 in VMEM, then narrow once here)
    return tuple(v.astype(x.dtype) for v in (mu, m2, mn, mx))


# windowing ALONG the minor (lane) axis: the lane-shift chain COMPILES
# up to 13 taps (bisected: 11/13 OK, 15/17 crash the Mosaic subprocess
# — toolchain-specific) but its throughput degrades with width; past 9
# taps the banded-matmul formulation below (round 4) or, for
# non-constant boundary modes, the swap-inland transpose detour serves
# instead, so the DIRECT minor path is capped at the performance
# crossover, not the crash limit
_MINOR_MAX_TAPS = 9


def _band_weights(taps, dtype):
    """The (3·128, 128) channel-mixing weight stack of the banded-matmul
    lane filter: out tile ``t`` = ``[X[t-1]; X[t]; X[t+1]] @ W``.  Row
    block ``kw`` holds the taps that reach from neighbor ``kw-1``."""
    w = len(taps)
    r = w // 2
    wt = np.zeros((3, 128, 128), dtype=np.float64)
    for c in range(128):
        for k in range(w):
            off = c + k - r
            wt[off // 128 + 1, off % 128, c] = taps[k]
    return wt.astype(dtype)


def _band_kernel(x_ref, w_ref, o_ref, *, precision="highest"):
    blk = x_ref[...]                              # (1, S, T, 128)
    zero = jnp.zeros(blk.shape[:-2] + (1, 128), blk.dtype)
    if blk.shape[-2] == 1:
        # one lane tile has no neighbours (and Mosaic no zero-size slice)
        xl = xr = zero
    else:
        xl = jnp.concatenate([zero, blk[..., :-1, :]], axis=-2)
        xr = jnp.concatenate([blk[..., 1:, :], zero], axis=-2)
    big = jnp.concatenate([xl, blk, xr], axis=-1)  # (1, S, T, 384)
    o_ref[...] = jnp.einsum("bstk,ko->bsto", big, w_ref[...],
                            precision=precision)


# block budget for the band kernel: S·L·itemsize ≤ 2 MB measured safe
# (the kernel holds ~7 block-sized tensors; a 4 MB block crashed the
# Mosaic subprocess with VMEM overflow)
_BAND_BLOCK_BYTES = 2 << 20


def lane_band_pallas(x, taps, interpret=None, precision="highest"):
    """Pallas form of the banded-matmul lane filter: each block reads
    HBM once, builds its 384-channel shifted operand in VMEM, and runs
    ONE MXU matmul.  Returns None when the geometry does not fit (caller
    falls back to :func:`lane_band_conv`, then to the transpose
    detour)."""
    w = len(taps)
    L = x.shape[-1]
    if x.ndim < 2 or L % 128 != 0 or w // 2 > 128 \
            or not jnp.issubdtype(x.dtype, jnp.floating):
        return None
    s1 = x.shape[-2]
    T = L // 128
    S = _largest_divisor_fitting(
        s1, L * x.dtype.itemsize, _BAND_BLOCK_BYTES)
    if S is None:
        return None
    B = prod(x.shape[:-2]) if x.ndim > 2 else 1
    X = x.reshape((B, s1, T, 128))
    if interpret is None:
        interpret = _interpret_default()
    out = pl.pallas_call(
        partial(_band_kernel, precision=precision),
        grid=(B, s1 // S),
        in_specs=[pl.BlockSpec((1, S, T, 128), lambda i, j: (i, j, 0, 0)),
                  pl.BlockSpec((384, 128), lambda i, j: (0, 0))],
        out_specs=pl.BlockSpec((1, S, T, 128), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(X.shape, x.dtype),
        interpret=interpret,
    )(X, jnp.asarray(_band_weights(taps, x.dtype).reshape(384, 128)))
    return out.reshape(x.shape)


def lane_band_conv(x, taps, precision="highest"):
    """Wide 1-d correlation ALONG the minor (lane) axis as a banded
    matmul on the MXU (VERDICT r3 next-5 — the round-3 path paid a
    6-pass transpose detour here).

    The lane axis splits into 128-wide tiles ``(..., T, 128)`` — a
    re-tiling XLA performs for free — and the correlation becomes a
    3-tap, 128→128-channel ``conv_general_dilated`` over the tile axis:
    each output tile is ``X[t-1] @ Wl + X[t] @ Wm + X[t+1] @ Wr`` with
    the three (128, 128) bands of the tap matrix as channel-mixing
    weights.  ONE read + ONE write of HBM (the detour pays ~6 passes,
    two of them relayout transposes), with the tap arithmetic moved
    onto the MXU where it is ~free.  Zero-padding of the tile axis IS
    'constant' boundary semantics (the window never reaches past the
    adjacent tile while ``radius <= 128``).  Returns None when the
    geometry does not apply: lane extent not 128-aligned, radius > 128,
    or non-floating dtype."""
    w = len(taps)
    r = w // 2
    L = x.shape[-1]
    if L % 128 != 0 or r > 128 or not jnp.issubdtype(x.dtype, jnp.floating):
        return None
    T = L // 128
    lead = x.shape[:-1]
    rows = prod(lead) if lead else 1
    kernel = jnp.asarray(_band_weights(taps, x.dtype))
    out = jax.lax.conv_general_dilated(
        x.reshape((rows, T, 128)), kernel,
        window_strides=(1,), padding=((1, 1),),
        dimension_numbers=("NWC", "WIO", "NWC"),
        precision=precision)
    return out.reshape(x.shape)


def sepfilter_plan(shape, itemsize, ax, w=1):
    """``(block, grid_axes, grid)`` for :func:`sepfilter1d` on ``shape``
    filtering along ``ax`` with ``w`` taps: blocks keep the FULL ``ax``
    extent (so each block pads and windows itself in VMEM — no
    inter-block halo, no global pad copy) and tile the other axes,
    shrinking greedily left to right (the minor axis in 128-lane units,
    the second-minor in 8s — Mosaic's block rule) until ~1 MB holds the
    block.  ``None`` when nothing fits, the minor dim isn't 128-aligned,
    the grid would exceed TPU's 3 dims, or ``ax`` is the minor axis with
    more than :data:`_MINOR_MAX_TAPS` taps."""
    nd = len(shape)
    if nd == 0 or shape[-1] % 128 != 0:
        return None
    if ax == nd - 1 and w > _MINOR_MAX_TAPS:
        return None
    # ~6 live block-sized tensors (input, padded copy, accumulator,
    # output, double buffering): 1 MB blocks ≈ 6 MB live — measured
    # safe; a 2 MB pad-along-minor block (~13 MB live after lane
    # padding) crashed the Mosaic subprocess with VMEM overflow
    budget = 1 << 20
    block = list(shape)
    for t in [a for a in range(nd) if a != ax]:
        if _padded_bytes(tuple(block), itemsize) <= budget:
            break
        # Mosaic block rule: the last two block dims must be multiples
        # of (8, 128) — or equal to the full array dims
        unit = 128 if t == nd - 1 else (8 if t == nd - 2 else 1)
        if shape[t] % unit != 0:
            continue                      # can't shrink this axis legally
        probe = list(block)
        probe[t] = unit
        unit_bytes = _padded_bytes(tuple(probe), itemsize)
        d = _largest_divisor_fitting(shape[t] // unit, unit_bytes, budget)
        block[t] = d * unit if d else unit
    if _padded_bytes(tuple(block), itemsize) > budget:
        return None
    grid_axes = tuple(a for a in range(nd) if block[a] != shape[a])
    if len(grid_axes) > 3:
        return None
    grid = tuple(shape[a] // block[a] for a in grid_axes) or (1,)
    return tuple(block), grid_axes, grid


def sepfilter_capable(shape, itemsize, ax, w, mode="constant"):
    """True when :func:`sepfilter1d` can serve this geometry and
    boundary ``mode`` — a direct plan, the banded-matmul lane path, or
    the wide-minor-window transpose detour.  Only ``'constant'`` is
    served: Mosaic lowers neither the ``rev`` behind numpy-pad's
    reflect/symmetric nor the edge-mode pad (jax 0.9.0), so those modes
    take the halo-chunked XLA path.  The whole-array fast-path gate in
    ``overlap._whole_array_sepfilter`` uses this so it cannot disagree
    with what the kernel actually accepts."""
    if mode != "constant":
        return False
    if sepfilter_plan(shape, itemsize, ax, w) is not None:
        return True
    nd = len(shape)
    if ax == nd - 1 and w > _MINOR_MAX_TAPS:
        if shape[-1] % 128 == 0 and w // 2 <= 128:
            return True                    # banded-matmul lane path
        if nd >= 2 and shape[nd - 2] % 128 == 0:
            swapped = shape[:nd - 2] + (shape[nd - 1], shape[nd - 2])
            return sepfilter_plan(swapped, itemsize, nd - 2, w) is not None
    return False


def _sep1d_kernel(x_ref, o_ref, *, taps, ax):
    # the SAME pad-and-shifted-slice correlation as overlap._filter1d —
    # one algorithm, so the kernel and its chunked/shifted fallback are
    # each other's oracle by construction (import at call time; overlap
    # only imports kernels inside functions, so no cycle)
    from bolt_tpu.ops.overlap import _filter1d
    o_ref[...] = _filter1d(x_ref[...], ax, taps, "constant", jnp)


def sepfilter1d(x, taps, ax, mode="constant", interpret=None,
                precision="highest"):
    """1-d correlation of ``x`` with ``taps`` along ``ax`` ('same' size,
    zero ``'constant'`` boundary) in ONE HBM pass.

    The XLA shifted-slice formulation re-reads the operand once per tap;
    here every block is read into VMEM once, pads itself (the block
    holds the full ``ax`` extent, so array-edge semantics are exact with
    no inter-block halo), and the windowed sum runs on registers.
    Returns ``None`` when the plan doesn't apply (caller keeps its
    shifted-slice path): a boundary ``mode`` other than ``'constant'``
    (see :func:`sepfilter_capable`), non-floating dtype, unaligned minor
    dim, or nothing tiles."""
    taps = tuple(float(t) for t in taps)
    if mode != "constant" or not jnp.issubdtype(x.dtype, jnp.floating):
        return None
    nd = x.ndim
    if ax == nd - 1 and len(taps) > _MINOR_MAX_TAPS:
        # wide window on the lane axis: banded matmul on the MXU, one
        # read + one write (round 4) — pallas form first, XLA conv form
        # when the block plan doesn't fit
        out = lane_band_pallas(x, taps, interpret=interpret,
                               precision=precision)
        if out is None:
            out = lane_band_conv(x, taps, precision=precision)
        if out is not None:
            return out
        if nd >= 2 and x.shape[nd - 2] % 128 == 0:
            # radius > 128: swap the lane axis inland (both dims stay
            # 128-aligned), window there, swap back — two relayout
            # passes (~4x traffic) still beat a shifted-slice re-read
            # per tap
            y = jnp.swapaxes(x, nd - 2, nd - 1)
            out = sepfilter1d(y, taps, nd - 2, interpret=interpret,
                              precision=precision)
            return None if out is None else jnp.swapaxes(out, nd - 2, nd - 1)
    plan = sepfilter_plan(x.shape, x.dtype.itemsize, ax, len(taps))
    if plan is None:
        return None
    block, grid_axes, grid = plan
    if interpret is None:
        interpret = _interpret_default()
    nd = x.ndim

    def im(*gids):
        pos = [0] * nd
        for g, a in zip(gids, grid_axes):
            pos[a] = g
        return tuple(pos)

    return pl.pallas_call(
        partial(_sep1d_kernel, taps=taps, ax=ax),
        grid=grid,
        in_specs=[pl.BlockSpec(block, im)],
        out_specs=pl.BlockSpec(block, im),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x)


# svdvals / tallskinny_pca / jacobi_eigh live in bolt_tpu.ops.linalg
