"""``var`` / ``std`` of real floating data as ONE pass of shifted moments.

``jnp.var`` is the two-pass form: a mean, then the sum of squared
deviations from it.  XLA cannot fuse a reduction with a second one that
depends on its result, so the input is read twice (a 16-record window of
the resident stack is two 420 MB reads, a whole-stack ``std()`` 20.97
GB).  Here the second moment is taken about a PILOT ``c`` that is known
before the pass starts::

    d = x - c;  s1 = sum(d);  s2 = sum(d * d)        # one fusion
    var = max(s2 - s1 * (s1 / n), 0) / (n - ddof)

``s1`` and ``s2`` are sibling reductions of one expression, which XLA
emits as one multi-output fusion: every element is read once and takes
part in both sums.

The rounding error of this form scales with ``var + (mean - c)**2``
(the unshifted ``c = 0`` loses every digit at a mean of 1e4 and a
deviation of 1), so the pilot decides the robustness.  ``c`` is the MEAN
of a leading corner of the reduced extent, along EVERY reduced axis the
smallest power of two whose square covers the axis: at least ``sqrt(n)``
elements, so one outlier of size M moves ``c`` by at most ``M /
sqrt(n)``, no more than it adds to the deviation itself; a corner along
every axis, so it is small in whatever tiled layout the runtime gave the
base (one record of a lane-minor key axis costs a whole 128-lane
column); a power of two, so a constant array's pilot is the constant.
It is part of the same program: one launch.

A static window of an axis that is SHARDED over devices is the one thing
the corner must not be: GSPMD re-shards the window's result and moves
whole shards to do it (compiled for the described 2x2 v5e, a whole-stack
``std()`` of the 14.42 GB stack held 1.39 GB of temporaries and read
9.57 GB a chip where ``jnp.std`` reads 7.55).  So a caller that knows
the mesh (``multistat._stat_expr``) names the reduced axes it shards
``whole``: the pilot takes those whole and corners the rest, local to
every shard, one small all-reduce (3.78 GB a chip then, no temporary).
Where ONLY sharded axes are reduced (a default ``std()`` over the keys
of a mesh of several devices) the pilot is then the mean itself and the
program reads twice, as ``jnp.var`` does.

Complex, integer and boolean inputs keep ``jnp.var`` (numpy's
abs-squared and promotion rules are not worth re-spelling).  The choice
is by the dtype this code sees in its input and nothing else.

Both statistic tables (``tpu/array.py::_stat`` and
``tpu/multistat.py::_OPS``) hold :func:`var` and :func:`std`, so a
standalone terminal, a fused group and a batched lane trace one
arithmetic.
"""

import jax.numpy as jnp

from bolt_tpu.utils import prod, tupleize


def one_pass(dtype):
    """Whether ``var``/``std`` of ``dtype`` take the one-pass form: real
    floating data does, everything else is ``jnp.var``'s."""
    return bool(jnp.issubdtype(dtype, jnp.floating))


def _pilot(x, axes, whole=()):
    """The mean of ``x``'s leading corner over ``axes``, kept-shaped
    (``keepdims``): along each reduced axis of extent ``r`` the first
    ``b`` entries, ``b`` the smallest power of two with ``b * b >= r``;
    the axes in ``whole`` are taken whole."""
    corner = [slice(None)] * x.ndim
    for a in axes:
        r = x.shape[a]
        if a not in whole:
            corner[a] = slice(0, min(r, 1 << ((r - 1).bit_length() + 1) // 2))
    return jnp.mean(x[tuple(corner)], axis=axes, keepdims=True)


def _shifted(x, axis, dtype, keepdims, ddof, whole, finish=None):
    """The one-pass variance of real floating ``x``, ``finish`` applied
    to it in the dtype the sums are kept in, cast to the result's."""
    axes = tuple(range(x.ndim)) if axis is None else tuple(
        int(a) % x.ndim for a in tupleize(axis))
    out_dt = x.dtype if dtype is None else jnp.dtype(dtype)
    x = x.astype(jnp.promote_types(jnp.promote_types(x.dtype, out_dt),
                                   jnp.float32))
    d = x - _pilot(x, axes, whole)
    s1 = jnp.sum(d, axis=axes, keepdims=keepdims)
    s2 = jnp.sum(d * d, axis=axes, keepdims=keepdims)
    n = jnp.asarray(prod([x.shape[a] for a in axes]), x.dtype)
    # s1 * (s1 / n), not s1 * s1 / n: the square of a SUM overflows
    # float32 where no deviation's square does (2.6e9 elements 7e9 off)
    out = jnp.maximum(s2 - s1 * (s1 / n), 0) / (n - ddof)
    return (out if finish is None else finish(out)).astype(out_dt)


def var(x, axis=None, dtype=None, keepdims=False, ddof=0, whole=()):
    """``jnp.var(x, axis, dtype=, keepdims=, ddof=)``; real floating
    ``x`` in one pass (module docstring).  ``dtype`` is what the sums are
    kept in and the result's (``None``: ``x``'s own, float32 sums for a
    16-bit ``x``, as ``jnp.var``).  ``whole``: the reduced axes (as
    ``axis`` counts them, no negatives) that the pilot takes whole, for
    the caller that knows they are sharded."""
    if not one_pass(x.dtype):
        return jnp.var(x, axis=axis, dtype=dtype, keepdims=keepdims,
                       ddof=ddof)
    return _shifted(x, axis, dtype, keepdims, ddof, whole)


def std(x, axis=None, dtype=None, keepdims=False, ddof=0, whole=()):
    """``jnp.std``, over the same pass."""
    if not one_pass(x.dtype):
        return jnp.std(x, axis=axis, dtype=dtype, keepdims=keepdims,
                       ddof=ddof)
    return _shifted(x, axis, dtype, keepdims, ddof, whole, jnp.sqrt)
