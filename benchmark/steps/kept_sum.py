"""``{"call": "kept_sum", "corner": [a, b, c], "above": t}``: BASELINE
config 4 on the resident stack, the sum over the key axis of the records
that pass a quality score (the mean of the record's leading ``a x b x c``
corner above ``t``):

    b.filter(lambda v: v[:a, :b, :c].mean() > t).sum(axis=(0,))

a dynamic output shape inside the program, and a predicate that is itself
a reduction inside the record.  A terminal: one record comes back.  (The
README's worked example judges a record by its largest value, which at
this record size keeps every record: 819,200 draws from 4,096 values.)"""

import functools

import numpy as np

import reference


def _args(step):
    return tuple(int(c) for c in step["corner"]), float(step["above"])


def bind(step, man):
    from bolt_tpu import engine
    if "filters_fused" not in engine.counters():
        # an older program gathers the survivors of a filter of this size
        # into a second stack, which does not fit beside the first
        raise SystemExit("kept_sum needs a program that folds a deferred "
                         "filter into its terminal (engine counter "
                         "filters_fused); this one has none")
    (a, b, c), above = _args(step)

    def pred(v):
        return v[:a, :b, :c].mean() > above
    return lambda arr: arr.filter(pred).sum(axis=(0,))


def plan(p, step):
    if p.windowed:
        raise ValueError("kept_sum reads the whole source")
    p.terminal = KeptSum(*_args(step))


def traffic(step, t):
    """Every record is read once; the kept ones need not be read again (the
    score reads a corner), and one record is written."""
    t.read, t.written = t.elements(), t.elements() // t.sizes[0]
    t.sizes = t.sizes[1:]


class KeptSum:
    def __init__(self, corner, above):
        self.corner, self.above = corner, above

    def number(self, p, got, want):
        """``max |got - want|`` per record summed, in data units."""
        return reference.distance(got, want, float(p.sizes[0]))

    def kept(self, ref, p):
        """How many records the score keeps, exactly."""
        prog = _program(p.bodies, self.corner, self.above, False, True)
        return int(prog(ref.data))

    def resident_expected(self, ref, p):
        # the score is exact in float32 (eight small integers and a
        # division by eight) and a masked int32 sum over the records
        # cannot overflow: 2**31 / 2**bits records and more
        out = _program(p.bodies, self.corner, self.above, False, False)(
            ref.data)
        return np.asarray(out).astype(np.float64)

    def resident_lowp(self, ref, p):
        out = _program(p.bodies, self.corner, self.above, True, False)(
            ref.data)
        return np.asarray(out).astype(np.float64)


@functools.lru_cache(maxsize=None)
def _program(bodies, corner, above, low, count):
    import jax
    import jax.numpy as jnp
    bf16 = reference.bf16 if low else (lambda x: x)
    a, b, c = corner

    def run(data):
        # the score from the corner alone, so that the mapped stack has one
        # reader and is never an array of its own
        score = bf16(reference.apply(bodies, bf16(data[:, :a, :b, :c])))
        keep = jnp.mean(score, axis=(1, 2, 3), keepdims=True) > above
        if count:
            return jnp.sum(keep.astype(jnp.int32))
        x = bf16(reference.apply(bodies, bf16(data)))
        kept = jnp.where(keep, x, 0)
        if low:
            return bf16(jnp.sum(kept, axis=0))
        return jnp.sum(kept.astype(jnp.int32), axis=0)
    return jax.jit(run)
