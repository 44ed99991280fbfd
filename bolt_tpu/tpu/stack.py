"""Stacking: batching flat key records into blocks.

Reference: ``bolt/spark/stack.py :: StackedArray`` — ``_stack(size)`` groups
consecutive records' values into one ``(n, *value_shape)`` block per
partition so a user function hits BLAS once per block instead of once per
record; ``map`` operates on blocks, ``unstack`` restores records
(symbol-level citations, SURVEY.md §0).

On TPU the batching the reference buys with this machinery is native — every
``map`` is already one fused vectorised launch — so ``StackedArray`` is a
thin compatibility view: it exposes the same block-wise ``map`` contract
(``func`` sees ``(n, *value_shape)`` and must preserve ``n``), executing all
blocks in one compiled program.
"""

import jax
import jax.numpy as jnp

from bolt_tpu import engine as _engine
from bolt_tpu import stream as _streamlib
from bolt_tpu.obs import trace as _obs
from bolt_tpu.tpu.array import (BoltArrayTPU, _TRACE_ERRORS, _cached_jit,
                                _canon, _chain_apply, _check_live,
                                _check_value_shape, _constrain, _traceable)
from bolt_tpu.utils import prod


def _stack_map_body(data, func, split, size, canon=None):
    """The block-batched map program body: flatten records, vmap ``func``
    over full-size blocks plus one ragged tail, restore keys, optionally
    cast.  Geometry derives from ``data.shape``, so the SAME traced body
    serves the materialised program below AND the streaming executor's
    per-slab program (``bolt_tpu/stream.py``) — parity by construction."""
    kshape = data.shape[:split]
    vshape = data.shape[split:]
    n = prod(kshape)
    flat = data.reshape((n,) + vshape)
    if n == 0:
        # zero records (a filter with no survivors): func never runs,
        # but the empty output must still carry the value shape/dtype
        # func WOULD produce so empty and non-empty branches of one
        # pipeline stay consistent
        ob = jax.eval_shape(func, jax.ShapeDtypeStruct(
            (size,) + vshape, flat.dtype))
        return jnp.zeros(kshape + tuple(ob.shape[1:]), canon or ob.dtype)
    nfull = n // size
    outs = []
    if nfull:
        blocks = flat[:nfull * size].reshape((nfull, size) + vshape)
        out = jax.vmap(func)(blocks)
        if out.ndim < 2 or out.shape[:2] != (nfull, size):
            got = out.shape[1] if out.ndim >= 2 else "none"
            raise ValueError(
                "stacked map must preserve the record count: "
                "block of %d records -> %s" % (size, got))
        outs.append(out.reshape((nfull * size,) + out.shape[2:]))
    if n % size:
        tail = flat[nfull * size:]
        tout = func(tail)
        if tout.shape[0] != tail.shape[0]:
            raise ValueError(
                "stacked map must preserve the record count: "
                "block of %d records -> %d"
                % (tail.shape[0], tout.shape[0]))
        outs.append(tout)
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
    out = out.reshape(kshape + out.shape[1:])
    if canon is not None:
        out = out.astype(canon)   # fused into the same program
    return out


class StackedArray:
    """A block-batched view over a :class:`BoltArrayTPU`."""

    def __init__(self, barray, size):
        self._barray = barray
        self._size = int(size)

    @classmethod
    def stack(cls, barray, size=1000):
        if int(size) < 1:
            raise ValueError("stack size must be >= 1, got %r" % (size,))
        return cls(barray, size)

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
    def size(self):
        """Records per block (reference: the ``_stack(size)`` argument)."""
        return self._size

    @property
    def nblocks(self):
        n = prod(self.shape[:self.split])
        return -(-n // self._size)

    def map(self, func, value_shape=None, dtype=None):
        """Apply ``func`` block-wise: it receives ``(n, *value_shape)`` and
        must return ``(n, *new_value_shape)`` — record counts are preserved,
        as the reference requires for ``unstack`` to restore keys.  All
        blocks run in one compiled program, and ``func`` traces at most
        TWICE (vmap over the full-size blocks + one ragged tail), so the
        trace cost is independent of the block count — ``stacked(size=1)``
        over a million records compiles as fast as ``size=1000``."""
        func = _traceable(func)
        b = self._barray
        _engine.strict_guard(b, "stacked().map()")
        if b._stream is not None:
            # streaming source (out-of-core): record the block-batched
            # map as a device-side stage; the per-slab program applies
            # the SAME _stack_map_body at slab geometry
            out = _streamlib.stacked_map_stage(self, func, dtype)
            if out is not NotImplemented:
                return out
        split = b.split
        mesh = b.mesh
        kshape = b.shape[:split]
        vshape = b.shape[split:]
        n = prod(kshape)
        size = self._size
        # donation-aware terminal: a sole-owned deferred chain donates its
        # base into the block-batched program (input-sized output)
        donate = b.deferred and b._donatable()
        base, funcs = b._chain_parts()
        canon = None if dtype is None else _canon(dtype)
        if value_shape is not None:
            # validate BEFORE compiling/executing the full program (the
            # per-record output shape is the block shape minus the axis)
            try:
                ob = jax.eval_shape(func, jax.ShapeDtypeStruct(
                    (min(size, n) or size,) + vshape, b._aval.dtype))
            except _TRACE_ERRORS:
                # non-traceable func: skip hint validation (shape errors
                # would still surface at the real trace below)
                ob = None
            _check_value_shape(
                value_shape, None if ob is None else tuple(ob.shape[1:]))

        def build():
            def run(data):
                # ONE traced body — _stack_map_body above — serves this
                # materialised program, the streaming executor's
                # per-slab program AND (as the pattern) the serve
                # layer's batched programs: parity by construction
                data = _chain_apply(funcs, split, data)
                out = _stack_map_body(data, func, split, size, canon)
                return _constrain(out, mesh, split)
            return jax.jit(run, donate_argnums=(0,) if donate else ())

        fn = _cached_jit(("stack-map", func, funcs, base.shape,
                          str(base.dtype), split, size, canon, donate,
                          mesh), build)
        with _obs.span("stack.map", size=size, donate=donate):
            out = fn(_check_live(base))
        if donate:
            b._consume_donated("stacked().map()")
        return StackedArray(BoltArrayTPU(out, split, mesh), size)

    def unstack(self):
        """Back to a :class:`BoltArrayTPU` (reference:
        ``StackedArray.unstack``); a no-op unwrap here."""
        return self._barray

    def __repr__(self):
        s = "StackedArray\n"
        s += "mode: tpu\n"
        s += "shape: %s\n" % str(self.shape)
        s += "split: %d\n" % self.split
        s += "size: %d\n" % self._size
        s += "nblocks: %d\n" % self.nblocks
        return s
