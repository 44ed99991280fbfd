"""The percentile of a record as a SELECTION: two exact order statistics
found by bisection over the bits of the values, and NumPy's linear
interpolation between them.

``jnp.percentile`` sorts: for a series of 10,240 points XLA's sort is a
network of about a hundred compare-exchange stages over the record, to
read back two of its elements.  The k-th smallest of a record needs no
order among the others.  Floats map onto unsigned integers of their own
width in an order-preserving way (the image ``lax.sort``'s comparator
compares by: −0.0 counted as +0.0, negative values with their magnitude
bits flipped), and the k-th smallest KEY is built from its top bit down:
a candidate stays when at most k of the record's keys lie below it, which
is one compare and one sum over the record a candidate, fused by XLA into
one read.  Its neighbour above (the (k+1)-th) is one more such read.
Nothing is estimated, sampled or narrowed: the two values are elements of
the record, the same two the sort would put at those indices, and the
interpolation is ``jnp.quantile``'s own arithmetic (jax 0.9.0,
``jax/_src/numpy/reductions.py::_quantile``), so the result is
``jnp.percentile``'s to the bit (a zero's sign apart, where a record
holds both).

Plain ``jax.numpy``: it traces under ``vmap``, inside the blocked loop of
``tpu/array.py::_chain_apply`` and under ``shard_map``.  A float of any
width selects over keys of that width (float64 with x64 on: 64-bit keys,
twice the passes); ``ops.normalize`` promotes everything else to float32
first, and the helper takes floats alone.

Which of the two a length takes is a rule on the length and the key's
width alone (:func:`regime`): a short record sorts inside one fusion and
the passes of a selection would only slow it down.

The selection has TWO executors of the one algorithm (PR 40).  Spelled in
``jax.numpy`` every pass is a trip to HBM: a block of records is read
nineteen times (sixteen counting passes, the image's read and its write,
the neighbour, the NaN verdict) for 4 bytes a record.  The Mosaic kernel
(:func:`_select_kernel`) brings a tile of whole records into VMEM ONCE
and runs the image, every pass, the neighbour and the NaN verdict on the
tile where it lies: records on the sublanes, time on the lanes, a count
an element-wise accumulate over a record's groups of 128 lanes and one
cross-lane sum a record a pass; it writes two keys and the verdict a
record, and the interpolation after it is the same ``jax.numpy`` lines.
What the kernel holds: float32 records of a length :func:`regime` calls
``"kernel"`` (whole groups of 128 lanes, at least ``_KERNEL_FROM``, a
tile of 8 of them inside ``_TILE_BYTES``), in a program lowered for ONE
TPU device (or the inside of a fully manual ``shard_map``).  What the
fallback holds is everything else, by the ``jax.numpy`` passes as they
were: the CPU, a program GSPMD partitions, float64 and 16-bit keys, a
length that is not whole lane-groups, a record too long for VMEM.  The
choice is made when the program is LOWERED (the ``percentile_select``
primitive, as ``ops/linalg.py``'s ``jacobi_sweeps``): by then the target
is known, at trace time it is not.  Under ``vmap`` the primitive's rule
takes the mapped axis as one more leading axis of ONE bind over the
whole batch (``pallas_call``'s own rule would make each record a grid
step of one sublane); the kernel's lowering flattens the batch, a
bitcast, and the fallback's maps ``_select`` over it again, so that off
the TPU the program's text is what it was before the kernel came.

The kernel's batch arrives by ONE OF TWO index maps (PR 47).  A custom
call's operand is a buffer: where the batch is a block of a resident
array (``tpu/array.py :: _blocked_run``'s ``dynamic_slice_in_dim``), XLA
wrote the block out in front of every call, a read and a write of it to
put the same bytes further along in the same memory.  So the blocked run
says where its rows lie (:func:`block_of`), and a selection bound on
those very rows (through nothing but a same-dtype ``astype``, which
leaves no operation) takes the base and the offset as two more operands.
The kernel's lowering then windows the BASE by an offset in rows, scalar-
prefetched, where a slice is windowed by tile number; the body is the
same.  The fallback reads the rows as ever and not the two operands, so
its program is unchanged to the letter; a selection of anything computed
first has an operand XLA writes anyway and keeps one.

The constants below were read on the chip with
``scripts/select_probe.py``; PERF.md (section 6, PR 37 and PR 40) has the
tables.
"""

import threading
from contextlib import contextmanager
from functools import partial, reduce

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from bolt_tpu import engine as _engine

# bits of the key decided by one pass over the record: a pass counts the
# keys below each of ``2**_BITS_A_PASS - 1`` candidates in one fusion (one
# read, that many compares an element).  On the v5e, 1 GiB of rows of
# 10,240 float32 (scripts/select_probe.py, PR 37): one bit 53.9 ms, two
# 31.3, four 32.2 (at four the fifteen compares an element bound the pass,
# not its read), XLA's sort 252.6; two is the fastest at every length
# from 256 up, and in the pixelseries512-1chip.tuning cell (scan_GBps 5.18
# / 6.63 / 6.55 at one, two, four)
_BITS_A_PASS = 2

# the shortest record (32-bit keys) whose percentile is selected; below
# it ``jnp.percentile`` sorts.  The same probe, seconds for 1 GiB of rows,
# sort / selection: 64 points 0.034 / 0.182, 128 0.050 / 0.095, 192 0.072
# / 0.089, 256 0.055 / 0.047, 512 0.073 / 0.032, 1,024 0.094 / 0.031,
# 4,096 0.163 / 0.028, 10,240 0.253 / 0.031.  A key of another width moves
# it in proportion (the passes are the width's)
_SELECT_FROM = 256

# the kernel's side of the rule (PR 40; scripts/select_probe.py, the
# kernel's columns, 1 GiB of rows on the v5e).  Bits a pass in VMEM: a
# pass costs compares and no read, and a compare-and-count is three
# vector operations an element (compare, select, add), so one bit is 96
# of them an element, two 144, four 360.  At 10,240 values a record the
# kernel read 7.4 / 8.9 ms at one and two bits (19.0 at four, groups of
# 32), the passes over HBM 29.8; one bit is the fastest from 4,096 up
# (9.2 / 9.9), two bits under it (2,048: 13.7 / 12.9; 1,024: 22.4 / 18.4)
_KERNEL_BITS = 1
# the shortest record the kernel takes (whole groups of 128 lanes from
# here up).  A pass ends in a cross-lane sum a record and the broadcast
# of the next candidate, which a short record does not hide.  The same
# probe, ms a GiB, kernel / passes over HBM: 512 values 39.1 / 30.4, 768
# 28.0 / 30.3, 1,024 22.4 / 29.9, 1,536 15.7 / 28.6, 2,048 13.7 / 29.9,
# 4,096 9.2 / 28.3, 10,240 7.4 / 29.8
_KERNEL_FROM = 1024
# what one buffer of a tile of records may hold in VMEM: the pipeline
# keeps two, and a group's image of keys lies beside them, all inside
# Mosaic's 16 MiB scoped default.  A tile is at least 8 records (the
# sublanes of one vreg), so a record of more than _TILE_BYTES / 32
# values (98,304) keeps the passes over HBM
_TILE_BYTES = 3 << 20
_LANES = 128
# copies of a walk's running sums, taken in turn by the lane-groups, so
# that an add does not wait for the one before it (ms a GiB at 10,240:
# one copy 7.7, two 7.3, four 7.3)
_WAYS = 2
# records a group, the rows one pass runs over at once: the wait for a
# pass's cross-lane sums is shared by that many records (ms a GiB at
# 10,240: 8 records 18.5, 16 11.6, 32 8.8, 64 7.3; 128 do not fit VMEM
# beside two buffers of a tile)
_GROUP = 64


def select_from(dtype):
    """The shortest record of the float ``dtype`` whose percentile is
    selected."""
    return _SELECT_FROM * np.dtype(dtype).itemsize * 8 // 32


def regime(length, dtype):
    """``"kernel"``, ``"select"`` or ``"sort"``: how the percentile of
    records of ``length`` values of the float ``dtype`` is taken.
    ``"kernel"`` is a selection too, and says that a program lowered for
    one TPU device runs it as the Mosaic kernel (one read of a block);
    everywhere else it lowers to ``"select"``'s passes.  The lowering
    and ``analysis.explain`` both ask this."""
    if length < select_from(dtype):
        return "sort"
    tiles = np.dtype(dtype) == np.float32 and length % _LANES == 0 \
        and _KERNEL_FROM <= length <= _TILE_BYTES // 32
    return "kernel" if tiles else "select"


def _keys(x):
    """The order-preserving unsigned image of the floats ``x``: a
    negative value's bits are flipped, a positive one's get the top bit,
    and the two zeros are ONE key (+0.0's), as in ``lax.sort``'s
    comparator.  Integer work on the bits alone."""
    uint = np.dtype("u%d" % x.dtype.itemsize).type
    top = uint(1 << (x.dtype.itemsize * 8 - 1))
    bits = lax.bitcast_convert_type(x, uint)
    keys = jnp.where(bits >= top, ~bits, bits | top)
    return jnp.where(keys == top - uint(1), top, keys)


def _values(keys, dtype):
    """Back from keys to the floats they are the image of."""
    uint = keys.dtype.type
    top = uint(1 << (keys.dtype.itemsize * 8 - 1))
    bits = jnp.where(keys >= top, keys ^ top, ~keys)
    return lax.bitcast_convert_type(bits, dtype)


def _kth_key(keys, k, axis, bits):
    """The ``k``-th smallest (from 0) of ``keys`` along ``axis`` (kept,
    length 1): the largest key with at most ``k`` of the record's keys
    below it, built from the top, ``bits`` bits a pass.  A pass counts
    the keys below each value the next ``bits`` bits could take; the
    counts rise with the candidate, so the digit is how many of them are
    still at most ``k``."""
    uint = keys.dtype.type
    width = keys.dtype.itemsize * 8

    def step(i, found):
        shift = (width - bits * (i + 1)).astype(uint)
        digit = jnp.zeros_like(found)
        for j in range(1, 2 ** bits):
            cand = found | lax.shift_left(uint(j), shift)
            below = jnp.sum(keys < cand, axis=axis, keepdims=True,
                            dtype=jnp.int32)
            digit += (below <= k).astype(uint)
        return found | lax.shift_left(digit, shift)

    shape = keys.shape[:axis] + (1,) + keys.shape[axis + 1:]
    with jax.named_scope("percentile_select"):
        return lax.fori_loop(0, width // bits, step, jnp.zeros(shape, uint))


def _next_key(keys, key, k, axis):
    """The ``k + 1``-th smallest of ``keys`` given the ``k``-th: the same
    key where the record's ties reach that index, else the least key
    above it.  The count of keys up to ``key`` and the least above it are
    ONE reduction of two results, so one pass."""
    most = ~keys.dtype.type(0)
    upto, above = lax.reduce(
        ((keys <= key).astype(jnp.int32), jnp.where(keys > key, keys, most)),
        (jnp.int32(0), most),
        lambda a, b: (a[0] + b[0], jnp.minimum(a[1], b[1])), (axis,))
    upto, above = (jnp.expand_dims(r, axis) for r in (upto, above))
    return jnp.where(upto > k + 1, key, above)


def percentile(a, perc, axis, keepdims=False):
    """``jnp.percentile(a, perc, axis=axis, keepdims=keepdims)`` of a
    float array (one static ``perc`` in [0, 100], one axis, the linear
    method), taken by selection at and above the length :func:`regime`
    says and by ``jnp.percentile`` itself below it.  Equal to
    ``jnp.percentile`` to the bit either way (but for a zero's sign where
    a record holds both zeros); a record that holds a NaN answers NaN."""
    perc = float(perc)
    a = jnp.asarray(a)
    if not jnp.issubdtype(a.dtype, jnp.floating):
        raise TypeError("percentile selects over the bits of floats, got %s"
                        % a.dtype)
    axis = axis % a.ndim
    how = regime(a.shape[axis], a.dtype)
    # which executor a selection gets is the lowering's to say (and to
    # count: percentile_kernel_lowerings); traced, it is a selection
    _engine.record_percentile_lowering("sort" if how == "sort" else "select")
    if how == "sort":
        return jnp.percentile(a, perc, axis=axis, keepdims=keepdims)
    if how == "select":
        return _select(a, perc, axis, keepdims)
    return _select_p.bind(a, perc=perc, axis=axis, keepdims=keepdims, lead=0)


def _ranks(n, perc):
    """The two indices ``jnp.quantile`` interpolates between in a record
    of ``n`` values and their weights: its own arithmetic, in the dtype
    it gives a Python float (``q * (n - 1)``, floor and ceil, the
    weights, the clamp).  On the host: XLA folds the same operations on
    the same constants."""
    real = np.dtype(jnp.result_type(float)).type
    at = real(perc) / real(100) * (real(n) - real(1))
    low, high = np.floor(at), np.ceil(at)
    high_weight = at - low
    low_weight = real(1) - high_weight
    low = int(np.clip(low, 0, n - 1))
    high = int(np.clip(high, 0, n - 1))
    return low, high, low_weight, high_weight


def _blend(low_key, high_key, nan, low_weight, high_weight, dtype):
    """``jnp.quantile``'s answer from the two keys of each record and its
    NaN verdict: two products and a sum, as ``_quantile`` writes them."""
    low_value = jnp.where(nan, np.nan, _values(low_key, dtype))
    high_value = jnp.where(nan, np.nan, _values(high_key, dtype))
    # the weights are kept from XLA's sight as literals: it rewrites
    # a * c + b * c with a literal c = 0.5 (a median between two
    # elements) into (a + b) * c, which overflows where the products do
    # not (values past half the largest float); jnp.quantile's weights
    # reach it as expressions folded later, and its products stay
    low_weight, high_weight = lax.optimization_barrier(
        (jnp.asarray(low_weight), jnp.asarray(high_weight)))
    real = low_weight.dtype
    return (low_value.astype(real) * low_weight
            + high_value.astype(real) * high_weight).astype(dtype)


def _select(a, perc, axis, keepdims, bits=_BITS_A_PASS):
    """:func:`percentile` by selection, whatever the length (``a`` a
    float array, ``axis`` not negative), by passes of ``jax.numpy``: the
    fallback of the ``percentile_select`` primitive, and the selection of
    every record :func:`regime` does not give the kernel."""
    low, high, low_weight, high_weight = _ranks(a.shape[axis], perc)
    # the image is taken ONCE, ahead of the passes, and held: written
    # inside a pass, XLA's loop-invariant code motion lifts half of it
    # out (the flipped bits) and every pass then reads the record twice
    keys = _keys(a)
    low_key = _kth_key(keys, low, axis, bits)
    high_key = low_key if high == low \
        else _next_key(keys, low_key, low, axis)
    nan = jnp.any(jnp.isnan(a), axis=axis, keepdims=True)
    out = _blend(low_key, high_key, nan, low_weight, high_weight, a.dtype)
    return out if keepdims else jnp.squeeze(out, axis)


# ---------------------------------------------------------------------
# the same selection on a tile of records held in VMEM
# ---------------------------------------------------------------------

_TOP = np.int32(-2 ** 31)
_MOST = np.int32(2 ** 31 - 1)


def _select_kernel(x_ref, out_ref, keys_ref, *, records, low, high, bits):
    """Both order statistics of every record of one tile.

    ``x_ref`` ``(tile, length)`` float32, a record a sublane; ``keys_ref``
    ``(group, length)`` int32, the image of the group of records at work;
    ``out_ref`` ``(tile, 128)`` int32: lane 0 the ``low``-th smallest key
    of the record, lane 1 the ``high``-th, lane 2 whether it holds a NaN.

    Keys are :func:`_keys`' with the top bit flipped, so that SIGNED
    compares order them (Mosaic's unsigned compares are not relied on):
    a negative value's low 31 bits flipped, a positive one's bits as
    they are, -0.0's key (-1) made +0.0's (0).  ``found`` is built as
    the unsigned key's bit pattern, as :func:`_kth_key` builds it, and a
    candidate crosses to the signed image by the same flip."""
    from jax.experimental import pallas as pl
    tile, length = x_ref.shape
    group = keys_ref.shape[0]
    chunks = length // _LANES
    # the last tile of a batch that does not divide: its rows past the
    # batch hold whatever the buffer held, and groups of them are skipped
    # (rows of a group that straddles the end are selected and dropped
    # by the masked write-back)
    valid = jnp.minimum(tile, records - pl.program_id(0) * tile)
    lane = lax.broadcasted_iota(jnp.int32, (group, _LANES), 1)
    zeros = jnp.zeros((group, _LANES), jnp.int32)

    # a walk over a record's lane-groups: unrolled as far as 128 of them
    # (the cell's 80 are straight-line code), a loop of unrolled spans
    # beyond (Mosaic unrolls a loop whole or not at all)
    span = max(d for d in range(1, 129) if chunks % d == 0)

    def over_lanes(body, init, merge):
        # _WAYS copies of the carry take the lane-groups in turn, so that
        # a step's add does not wait for the step before it; ``merge``
        # folds them at the end
        def spans(i, turn):
            for j in range(span):
                turn = turn[1:] + (body(pl.ds(pl.multiple_of(
                    (i * span + j) * _LANES, _LANES), _LANES), turn[0]),)
            return turn
        turn = (init,) * _WAYS
        turn = spans(0, turn) if span == chunks \
            else lax.fori_loop(0, chunks // span, spans, turn)
        return reduce(merge, turn)

    def one_group(g, carry):
        rows = pl.ds(pl.multiple_of(g * group, group), group)

        def image(lanes, nan):
            x = x_ref[rows, lanes]
            bits_ = lax.bitcast_convert_type(x, jnp.int32)
            keys = jnp.where(bits_ < 0, bits_ ^ _MOST, bits_)
            keys_ref[:, lanes] = jnp.where(keys == -1, 0, keys)
            return jnp.where(x != x, 1, nan)

        nan = jnp.max(over_lanes(image, zeros, jnp.maximum), axis=1,
                      keepdims=True)

        def one_pass(i, found):
            shift = 32 - bits * (i + 1)
            cands = [jnp.broadcast_to(
                (found | lax.shift_left(jnp.int32(j), shift)) ^ _TOP,
                (group, _LANES)) for j in range(1, 2 ** bits)]

            def count(lanes, below):
                keys = keys_ref[:, lanes]
                return tuple(b + jnp.where(keys < c, 1, 0)
                             for b, c in zip(below, cands))

            digit = jnp.zeros((group, 1), jnp.int32)
            for below in over_lanes(
                    count, (zeros,) * len(cands),
                    lambda a, b: tuple(x + y for x, y in zip(a, b))):
                below = jnp.sum(below, axis=1, keepdims=True)
                digit += jnp.where(below <= low, 1, 0)
            return found | lax.shift_left(digit, shift)

        found = lax.fori_loop(0, 32 // bits, one_pass,
                              jnp.zeros((group, 1), jnp.int32))
        above = found
        if high != low:
            # _next_key: the count of keys up to the k-th and the least
            # key above it, one walk over the image
            key = jnp.broadcast_to(found ^ _TOP, (group, _LANES))

            def neighbour(lanes, carry):
                upto, least = carry
                keys = keys_ref[:, lanes]
                within = keys <= key
                return (upto + jnp.where(within, 1, 0),
                        jnp.minimum(least, jnp.where(within, _MOST, keys)))

            upto, least = over_lanes(
                neighbour, (zeros, jnp.full((group, _LANES), _MOST)),
                lambda a, b: (a[0] + b[0], jnp.minimum(a[1], b[1])))
            upto = jnp.sum(upto, axis=1, keepdims=True)
            least = jnp.min(least, axis=1, keepdims=True) ^ _TOP
            above = jnp.where(upto > low + 1, found, least)
        out_ref[rows, :] = jnp.where(
            lane == 0, found, jnp.where(
                lane == 1, above, jnp.where(lane == 2, nan, 0)))
        return carry

    lax.fori_loop(0, pl.cdiv(valid, group), one_group, 0)


def _tile(records, length):
    """``(tile, group)``: records a grid step brings into VMEM and
    records a pass runs over at once (a power of two from 8 to
    ``_GROUP``, as many as the tile's budget and the batch hold);
    ``records`` at least 8."""
    fit = _TILE_BYTES // (4 * length)
    group = 8
    while group * 2 <= min(_GROUP, fit, records):
        group *= 2
    return max(group, min(fit, records) // group * group), group


def _kernel_keys(x, low, high, bits=_KERNEL_BITS, start=None, block=None):
    """``(low key, high key, NaN verdict)`` of every record of a flat
    float32 batch, each ``(records, 1)`` (the keys uint32 as
    :func:`_keys` has them), by :func:`_select_kernel`.  The batch is
    ``x (records, length)`` itself or, with ``start`` (an int32 scalar),
    the ``block`` rows of the base ``x`` from there, both whole vregs of
    sublanes (multiples of 8): the kernel's tiles are then read where the
    array lies, and XLA writes no slice out for it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    length = x.shape[1]
    records = x.shape[0] if start is None else block
    held = max(records, 8)              # rows of the kernel's result
    if start is None and records < 8:
        # one vreg's sublanes at the least; a batch this small is a copy
        # of a few records
        x = jnp.pad(x, ((0, 8 - records), (0, 0)))
    tile, group = _tile(held, length)
    steps = pl.cdiv(held, tile)
    if start is None:
        # tile ``g`` of the batch by its number; the rows of the last
        # tile past the batch are skipped (``held``)
        operands = (x,)
        reads = pl.BlockSpec((tile, length), lambda g: (g, 0))
    else:
        # tile ``g`` from row ``start + g * tile`` of the base: an offset
        # in rows, since a block starts anywhere and not at a tile's
        # number.  Where the block does not divide, its last tile starts
        # early and hands some rows back a second time: no tile reads
        # past the block, whose last ends where the array does
        held, early = steps * tile, records - tile
        operands = (start.astype(jnp.int32).reshape(1), x)
        reads = pl.BlockSpec(
            (pl.Element(tile), pl.Element(length)),
            lambda g, at: (pl.multiple_of(
                at[0] + jnp.minimum(g * tile, early), 8), 0))
    # Mosaic has no 64-bit types: whatever the session's x64 says, the
    # kernel's side traces int32 and float32
    with jax.enable_x64(False):
        out = pl.pallas_call(
            # the body sees the tile and not where it came from
            lambda *refs: _select_kernel(*refs[-3:], records=held, low=low,
                                         high=high, bits=bits),
            out_shape=jax.ShapeDtypeStruct((held, _LANES), jnp.int32),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(operands) - 1, grid=(steps,),
                in_specs=[reads],
                out_specs=pl.BlockSpec((tile, _LANES), lambda g, *_: (g, 0)),
                scratch_shapes=[pltpu.VMEM((group, length), jnp.int32)]),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            name="percentile_select",
        )(*operands)
    if start is not None:
        out = jnp.concatenate([out[:early], out[(steps - 1) * tile:]])
    out = lax.bitcast_convert_type(out[:records, :3], jnp.uint32)
    return out[:, 0:1], out[:, 1:2], out[:, 2:3] != 0


def _select_flat(x, perc, bits=_KERNEL_BITS, start=None, block=None):
    """``_select(x, perc, 1, True)`` of a flat float32 batch ``(records,
    length)`` by the kernel; with ``start``, of the ``block`` rows of
    ``x`` from there (:func:`_kernel_keys`)."""
    low, high, low_weight, high_weight = _ranks(x.shape[1], perc)
    with jax.named_scope("percentile_select"):
        low_key, high_key, nan = _kernel_keys(x, low, high, bits, start,
                                              block)
    return _blend(low_key, high_key, nan, low_weight, high_weight, x.dtype)


def _by_passes(a, *where, perc, axis, keepdims, lead):
    """The primitive's fallback: :func:`_select` mapped over the ``lead``
    batch axes of ``a``, which is what the nested ``vmap`` traced before
    the primitive stood in its way.  ``where`` (the base and the offset
    ``a`` was sliced at) is the kernel's to use: the passes read the
    slice, and their program is what it was without it."""
    fn = partial(_select, perc=perc, axis=axis, keepdims=keepdims)
    for _ in range(lead):
        fn = jax.vmap(fn)
    return fn(a)


def _by_kernel(a, *where, perc, axis, keepdims, lead):
    """The same by the kernel: the batch flattened to ``(records,
    length)``, a bitcast where ``axis`` is the record's last (a series).
    With ``where``, a base and an offset (:func:`_lies_in`), the
    batch is read from the base and ``a``, its slice, is not read."""
    if where:
        base, start = where
        out = _select_flat(base, perc, start=start, block=a.shape[0])
        return out if keepdims else jnp.squeeze(out, 1)
    at = lead + axis
    rows = jnp.moveaxis(a, at, -1)
    out = _select_flat(rows.reshape(-1, a.shape[at]), perc)
    out = jnp.moveaxis(out.reshape(rows.shape[:-1] + (1,)), -1, at)
    return out if keepdims else jnp.squeeze(out, at)


def _takes_kernel(ctx, tpu):
    """Whether the program being lowered runs a ``"kernel"`` selection as
    the kernel: lowered for a TPU (``tpu``: which rule asks), and for one
    device of it or the inside of a fully manual ``shard_map``
    (``ops/linalg.py :: _mosaic_fits``: GSPMD partitions no kernel)."""
    from bolt_tpu.ops.linalg import _mosaic_fits
    return tpu and _mosaic_fits(ctx)


_BLOCK = threading.local()


@contextmanager
def block_of(rows, base, start, step, found=None):
    """While the function of a block of records is traced (``tpu/array.py
    :: _blocked_run``'s ``vmap`` over ``rows``, which is
    ``dynamic_slice_in_dim(base, start, len(rows))``; ``step`` divides
    every ``start`` and the block): a selection whose operand IS ``rows``
    (the very tracer: nothing computed from it, which XLA would have to
    write anyway) is bound with the base and the offset beside it, where
    the kernel can read them in place.  ``found``: a list that takes an
    entry for every such selection."""
    kept = getattr(_BLOCK, "at", None)
    _BLOCK.at = (rows, base, start, step, [] if found is None else found)
    try:
        yield
    finally:
        _BLOCK.at = kept


def _lies_in(a, lead):
    """``(base, start)`` where the batch ``a`` is the block
    :func:`block_of` names and the kernel can read it from the base:
    a flat batch of series (the base is then ``(rows, length)`` as it
    lies, no reshape of it a copy) whose blocks start and end on whole
    vregs of 8 sublanes, which is what Mosaic asks of a window's offset
    into a tiled array.  Else ``()``."""
    rows, base, start, step, found = getattr(_BLOCK, "at", None) \
        or (None,) * 5
    if a is not rows or lead != 1 or a.ndim != 2 or step % 8:
        return ()
    found.append(a.shape)
    return base, start


def _select_primitive():
    """``percentile_select``: :func:`_select` of every record of a batch,
    as a primitive because its executor is chosen when a program is
    LOWERED (see the module's text, and ``ops/linalg.py``'s
    ``jacobi_sweeps``).  The operand is ``lead`` batch axes in front of
    one record's axes, ``axis`` the record's; the result keeps the batch.

    Under ``vmap`` (``tpu/array.py :: _blocked_run`` maps the record
    function over a block, an unblocked chain over each key axis in
    turn) the rule moves the mapped axis to the front and binds again
    with one more leading axis: ONE bind over the whole batch, however
    deep the nesting.  The kernel's lowering flattens the batch to
    ``(records, length)``; the fallback maps :func:`_select` over the
    leading axes again (a reshape of key axes that GSPMD shards is not a
    bitcast, so the bind itself folds nothing).

    Two more operands say where the batch lies (the ``vmap`` rule binds
    them, inside :func:`block_of`): the base it is
    ``dynamic_slice_in_dim(base, start, records)`` of, and ``start``.
    The answer is the same; the kernel's lowering reads the base and
    leaves the slice to whoever else wants it."""
    from jax._src import dispatch       # eager calls: jax's own cache
    from jax.extend.core import Primitive
    from jax.interpreters import batching, mlir
    prim = Primitive("percentile_select")
    prim.def_impl(partial(dispatch.apply_primitive, prim))

    @prim.def_abstract_eval
    def _(a, *where, perc, axis, keepdims, lead):
        at = lead + axis
        return a.update(shape=a.shape[:at] + (1,) * keepdims
                        + a.shape[at + 1:])

    def lower(tpu):
        def rule(ctx, a, *where, **params):
            fn = _by_passes
            if _takes_kernel(ctx, tpu):
                _engine.record_percentile_lowering("kernel")
                if where:
                    _engine.record_percentile_lowering("based")
                fn = _by_kernel
            return mlir.lower_fun(partial(fn, **params),
                                  multiple_results=False)(ctx, a, *where)
        return rule

    mlir.register_lowering(prim, lower(False))
    mlir.register_lowering(prim, lower(True), platform="tpu")

    def fold(args, dims, *, lead, **params):
        a = jnp.moveaxis(args[0], dims[0], 0)
        return prim.bind(a, *_lies_in(a, lead + 1), lead=lead + 1,
                         **params), 0

    batching.primitive_batchers[prim] = fold
    # what the blocks rule counts a record's selection as holding
    # (tpu/blocks.py :: _sub_jaxprs): the passes and their image of keys
    prim.fallback = _by_passes
    return prim


_select_p = _select_primitive()
