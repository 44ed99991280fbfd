"""``{"call": "filter_sum", "above": t}``: the sum over the key axis of the
records whose largest value is above ``t`` (``b.filter(pred).sum()``, a
dynamic output shape inside the program).  A terminal.

This file and ``../traffic/filtered.json`` are the README's worked example
of a call the benchmark did not know, added as files only;
``test_added_files.py`` runs a cell made of them."""

import functools

import numpy as np

import reference


def bind(step, man):
    above = float(step["above"])

    def pred(v):
        return v.max() > above
    return lambda a: a.filter(pred).sum(axis=(0,))


def plan(p, step):
    if p.windowed:
        raise ValueError("filter_sum reads the whole source")
    p.terminal = FilterSum(float(step["above"]))


def traffic(step, t):
    """Every record is read once to be judged; the kept ones need not be
    read again, and one record is written."""
    t.read, t.written = t.elements(), t.elements() // t.sizes[0]


class FilterSum:
    def __init__(self, above):
        self.above = above

    def number(self, p, got, want):
        """``max |got - want|`` per record summed, in data units."""
        return reference.distance(got, want, float(p.sizes[0]))

    def resident_expected(self, ref, p):
        # the predicate is exact in float32 and a masked int32 sum over the
        # records cannot overflow: 2**31 / 2**bits records and more
        out = _program(p.bodies, self.above, False)(ref.data)
        return np.asarray(out).astype(np.float64)

    def resident_lowp(self, ref, p):
        out = _program(p.bodies, self.above, True)(ref.data)
        return np.asarray(out).astype(np.float64)


@functools.lru_cache(maxsize=None)
def _program(bodies, above, low):
    import jax
    import jax.numpy as jnp
    bf16 = reference.bf16 if low else (lambda x: x)

    def run(data):
        x = bf16(reference.apply(bodies, bf16(data)))
        keep = jnp.max(x, axis=tuple(range(1, x.ndim)), keepdims=True) > above
        kept = jnp.where(keep, x, 0)
        if low:
            return bf16(jnp.sum(kept, axis=0))
        return jnp.sum(kept.astype(jnp.int32), axis=0)
    return jax.jit(run)
