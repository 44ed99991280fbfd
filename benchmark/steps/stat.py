"""``{"call": "stat", "stat": "sum|mean|std|var|max|min", "axis": [..]}``:
a statistic over axes of what the steps before it left; ``axis`` absent is
bolt's default, every key axis.  A terminal: it answers the request."""

import functools

import numpy as np

import reference

STATS = ("sum", "mean", "std", "var", "max", "min")
EXACT = ("max", "min")


def _axes(step, split):
    ax = step.get("axis")
    return tuple(range(split)) if ax is None else tuple(int(a) for a in ax)


def bind(step, man):
    name = step["stat"]
    if name not in STATS:
        raise ValueError("unknown statistic %r" % (name,))
    ax = tuple(step["axis"]) if step.get("axis") is not None else None
    return lambda a: getattr(a, name)(axis=ax)


def plan(p, step):
    if step["stat"] not in STATS:
        raise ValueError("unknown statistic %r" % (step["stat"],))
    p.terminal = Stat(step["stat"], _axes(step, p.split))


def traffic(step, t):
    """Reads what is left once; its result is counted when it is written
    (the reduced axes removed), which for a full reduction is nothing."""
    axes = _axes(step, 1)
    kept = 1
    for ax, s in enumerate(t.sizes):
        if ax not in axes:
            kept *= s
    t.read, t.written = t.elements(), (kept if kept > 1 else 0)


class Stat:
    def __init__(self, stat, axes):
        self.stat, self.axes = stat, axes

    def reduced(self, p):
        return int(np.prod([p.sizes[a] for a in self.axes], dtype=np.int64))

    def number(self, p, got, want):
        """``max |got - want|``, per element reduced for a ``sum`` (so it
        reads in data units, like a mean); for ``max``/``min``, which are
        exact, the count of elements that differ."""
        if self.stat in EXACT:
            return float(reference.differing(got, want))
        den = float(self.reduced(p)) if self.stat == "sum" else 1.0
        return reference.distance(got, want, den)

    # -- over a resident device array ----------------------------------

    def resident_expected(self, ref, p):
        import jax.numpy as jnp
        bound = (1 << (ref.bits - 1)) + p.reach
        prog = _exact_program(p.bodies, p.sizes, self.stat, self.axes, bound)
        parts = [np.asarray(x) for x in
                 prog(ref.data, tuple(jnp.int32(s) for s in p.starts))]
        if self.stat in EXACT:
            return parts[0].astype(np.float64)
        n = self.reduced(p)
        s1 = parts[0].astype(np.int64).sum(axis=self.axes).astype(np.float64)
        if self.stat == "sum":
            return s1
        mean = s1 / n
        if self.stat == "mean":
            return mean
        s2 = parts[1].astype(np.int64).sum(axis=self.axes).astype(np.float64)
        var = s2 / n - mean * mean
        return var if self.stat == "var" else np.sqrt(var)

    def resident_lowp(self, ref, p):
        import jax.numpy as jnp
        prog = _lowp_program(p.bodies, p.sizes, self.stat, self.axes)
        out = prog(ref.data, tuple(jnp.int32(s) for s in p.starts))
        return np.asarray(out).astype(np.float64)

    # -- over a repeated host tile -------------------------------------

    def _whole_key_axis(self, ref, p):
        if self.stat not in ("sum", "mean") or self.axes != (0,) \
                or p.sizes != ref.shape:
            raise ValueError("over a tile the reference reads sum/mean "
                             "over the key axis of the whole source")

    def tile_expected(self, ref, p):
        """Block by block in float64, times the repeats."""
        self._whole_key_axis(ref, p)
        acc = np.zeros(ref.shape[1:], np.float64)
        for blk in ref.blocks():
            acc += reference.apply(p.bodies, blk.astype(np.float64)).sum(
                axis=0)
        total = acc * ref.repeats
        return total if self.stat == "sum" else total / ref.shape[0]

    def tile_lowp(self, ref, p):
        """Every block held, summed and accumulated in bfloat16 (see
        ``reference.bf16``), on the device."""
        import jax
        import jax.numpy as jnp
        self._whole_key_axis(ref, p)
        bf16, bodies = reference.bf16, p.bodies

        @jax.jit
        def fold(acc, blk):
            x = bf16(reference.apply(bodies, bf16(blk)))
            return bf16(acc + bf16(jnp.sum(x, axis=0)))
        acc = jnp.zeros(ref.shape[1:], jnp.float32)
        for _ in range(ref.repeats):
            for blk in ref.blocks():
                acc = fold(acc, blk)
        out = np.asarray(acc).astype(np.float64)
        return out if self.stat == "sum" else out / ref.shape[0]


@functools.lru_cache(maxsize=None)
def _exact_program(bodies, sizes, stat, axes, bound):
    import jax
    import jax.numpy as jnp

    def run(data, starts):
        x = jax.lax.dynamic_slice(data, starts, sizes)
        x = reference.apply(bodies, x)
        if stat in EXACT:
            return (getattr(jnp, stat)(x, axis=axes),)
        xi = x.astype(jnp.int32)
        out = (jnp.sum(xi, axis=reference.fits(sizes, axes, bound),
                       keepdims=True),)
        if stat in ("std", "var"):
            out += (jnp.sum(xi * xi, axis=reference.fits(
                sizes, axes, bound * bound), keepdims=True),)
        return out
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _lowp_program(bodies, sizes, stat, axes):
    import jax
    import jax.numpy as jnp
    bf16 = reference.bf16

    def run(data, starts):
        x = bf16(jax.lax.dynamic_slice(data, starts, sizes))
        x = bf16(reference.apply(bodies, x))
        return bf16(getattr(jnp, stat)(x, axis=axes))
    return jax.jit(run)
