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
the passes of a selection would only slow it down.  The two constants
below were read on the chip with ``scripts/select_probe.py``; PERF.md
(section 6, PR 37) has the table.
"""

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

def select_from(dtype):
    """The shortest record of the float ``dtype`` whose percentile is
    selected."""
    return _SELECT_FROM * np.dtype(dtype).itemsize * 8 // 32


def regime(length, dtype):
    """``"select"`` or ``"sort"``: how the percentile of records of
    ``length`` values of the float ``dtype`` is taken.  The lowering and
    ``analysis.explain`` both ask this."""
    return "select" if length >= select_from(dtype) else "sort"


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
    _engine.record_percentile_lowering(how)
    if how == "sort":
        return jnp.percentile(a, perc, axis=axis, keepdims=keepdims)
    return _select(a, perc, axis, keepdims)


def _select(a, perc, axis, keepdims, bits=_BITS_A_PASS):
    """:func:`percentile` by selection, whatever the length (``a`` a
    float array, ``axis`` not negative)."""
    n = a.shape[axis]
    # jnp.quantile's own arithmetic, in the dtype it gives a Python
    # float: q * (n - 1), floor and ceil, the weights, the clamp.  On
    # the host: XLA folds the same operations on the same constants
    real = np.dtype(jnp.result_type(float)).type
    at = real(perc) / real(100) * (real(n) - real(1))
    low, high = np.floor(at), np.ceil(at)
    high_weight = at - low
    low_weight = real(1) - high_weight
    low = int(np.clip(low, 0, n - 1))
    high = int(np.clip(high, 0, n - 1))

    # the image is taken ONCE, ahead of the passes, and held: written
    # inside a pass, XLA's loop-invariant code motion lifts half of it
    # out (the flipped bits) and every pass then reads the record twice
    keys = _keys(a)
    low_key = _kth_key(keys, low, axis, bits)
    high_key = low_key if high == low \
        else _next_key(keys, low_key, low, axis)
    nan = jnp.any(jnp.isnan(a), axis=axis, keepdims=True)
    low_value = jnp.where(nan, np.nan, _values(low_key, a.dtype))
    high_value = jnp.where(nan, np.nan, _values(high_key, a.dtype))
    # two products and a sum, as _quantile writes them.  The weights are
    # kept from XLA's sight as literals: it rewrites a * c + b * c with a
    # literal c = 0.5 (a median between two elements) into (a + b) * c,
    # which overflows where the products do not (values past half the
    # largest float); jnp.quantile's weights reach it as expressions
    # folded later, and its products stay
    low_weight, high_weight = lax.optimization_barrier(
        (jnp.asarray(low_weight), jnp.asarray(high_weight)))
    out = (low_value.astype(real) * low_weight
           + high_value.astype(real) * high_weight).astype(a.dtype)
    return out if keepdims else jnp.squeeze(out, axis)
