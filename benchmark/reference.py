"""The plain reference: the same questions answered by ``jax.numpy`` and
NumPy alone, in exact arithmetic, and the same questions answered one
precision lower, in bfloat16 (the control that has to come out as not
correct).

It imports nothing of the program and takes nothing the program made.  Its
inputs are the benchmark's own: the device array or host tile that the
operand made from the seed, and the closed form they came from.

This file holds what every request shares: the ``Plan`` a request's steps
are read into, the two kinds of source (``ResidentReference`` over a device
array, ``TileReference`` over a repeated host tile), and the arithmetic the
answers are compared by.  How one call is answered lives with that call, in
``steps/<call>.py``, beside the program's side of it: a new call is a new
file there and no edit here.

Exactness.  The data are integers of ``bits`` significant bits, so every
sum is an integer.  Partial sums are taken on the device in int32 over as
many trailing axes as cannot overflow (``fits``), and finished on the host
in int64; sums of squares likewise.  ``expected`` is therefore the true
answer in float64, not another float32 rounding of it, and the number
compared is the program's distance from the truth.
"""

import numpy as np

import lattice

INT32_MAX = (1 << 31) - 1


class Plan:
    """One request's ``steps`` read as window -> elementwise maps -> one
    terminal.  Each step writes its own part through the ``plan(p, step)``
    of its ``steps/<call>.py``.  What a step may write:

    ``starts``, ``sizes``   the window of the source that is read (getitem)
    ``bodies``, ``reach``   the elementwise bodies in order, and how far
                            they can move a value's magnitude together (map)
    ``terminal``            the object that answers; see ``Reference``
    """

    def __init__(self, man, steps, shape, split=1):
        self.man, self.shape, self.split = man, tuple(shape), split
        self.starts = [0] * len(shape)
        self.sizes = list(shape)
        self.bodies = []
        self.reach = 0
        self.windowed = False
        self.terminal = None
        for s in steps:
            if self.terminal is not None:
                raise ValueError("a step after the terminal: %r" % (s,))
            man.module("steps", s["call"]).plan(self, s)
        if self.terminal is None:
            raise ValueError("no terminal in %r" % (steps,))
        self.starts, self.sizes = tuple(self.starts), tuple(self.sizes)
        self.bodies = tuple(self.bodies)


class Reference:
    """A seeded source and the questions asked of it.  The answering is the
    terminal's (``Plan.terminal``, made by the last step's module), by the
    kind of source:

    ``<KIND>_expected(ref, plan)``  the true answer, a float64 ndarray
    ``<KIND>_lowp(ref, plan)``      the control's answer, in bfloat16
    ``number(plan, got, want)``     the number compared with the limit
    ``on_device(ref, plan, out)``   for an answer that stays on the device
                                    (fetch ``ready``): the number compared,
                                    as a device scalar, by one fused pass
    ``lowp_on_device(ref, plan)``   its control

    A terminal gives the methods its cells need; a missing one is an
    ``AttributeError`` that names it."""

    KIND = None

    def __init__(self, man, shape, bits, seed, split=1):
        self.man, self.shape, self.bits = man, tuple(shape), int(bits)
        self.seed, self.split = seed, split

    def plan(self, steps):
        return Plan(self.man, steps, self.shape, self.split)

    def expected(self, steps):
        p = self.plan(steps)
        return getattr(p.terminal, self.KIND + "_expected")(self, p)

    def lowp(self, steps):
        p = self.plan(steps)
        return getattr(p.terminal, self.KIND + "_lowp")(self, p)

    def number(self, steps, got, want):
        p = self.plan(steps)
        return p.terminal.number(p, got, want)

    def on_device(self, steps, out):
        p = self.plan(steps)
        return p.terminal.on_device(self, p, out)

    def lowp_on_device(self, steps):
        p = self.plan(steps)
        return p.terminal.lowp_on_device(self, p)


class ResidentReference(Reference):
    """Answers over the device array ``data`` of the seeded lattice."""

    KIND = "resident"

    def __init__(self, man, data, shape, bits, seed, split=1):
        super().__init__(man, shape, bits, seed, split)
        self.data = data

    def constants(self):
        import jax.numpy as jnp
        a, b = lattice.constants(self.seed)
        return jnp.uint32(a), jnp.uint32(b)

    def data_mismatches(self, rng):
        """Sampled records of the device array against the closed form by
        NumPy: is the data what it claims to be?"""
        return _differing_records(
            lambda r: np.asarray(self.data[r:r + 1]), rng, self.shape,
            self.seed, self.bits)


class TileReference(Reference):
    """Answers over a streamed source that repeats the host ``tile``."""

    KIND = "tile"
    BLOCK = 512

    def __init__(self, man, tile, shape, bits, seed):
        super().__init__(man, shape, bits, seed, 1)
        self.tile = tile
        self.repeats = self.shape[0] // tile.shape[0]

    def blocks(self):
        for lo in range(0, self.tile.shape[0], self.BLOCK):
            yield self.tile[lo:lo + self.BLOCK]

    def data_mismatches(self, rng):
        return _differing_records(
            lambda r: self.tile[r:r + 1], rng,
            (self.tile.shape[0],) + self.shape[1:], self.seed, self.bits)


def _differing_records(record, rng, shape, seed, bits, records=4):
    """How many elements of ``records`` sampled records (``record(r)`` gives
    record ``r`` as held) differ from the closed form by NumPy."""
    rows = rng.choice(shape[0], size=records, replace=False)
    return sum(int((record(int(r)) != lattice.host_block(
        int(r), int(r) + 1, shape[1:], seed, bits)).sum()) for r in rows)


# -- arithmetic the terminals share --------------------------------------

def fits(sizes, axes, bound):
    """The longest run of trailing ``axes`` an int32 sum of values up to
    ``bound`` can take without overflow."""
    taken, room = [], INT32_MAX // bound
    for ax in sorted(axes, reverse=True):
        if sizes[ax] > room:
            break
        room //= sizes[ax]
        taken.append(ax)
    return tuple(sorted(taken))


def apply(bodies, x):
    for f in bodies:
        x = f(x)
    return x


def bf16(x):
    """``x`` rounded to what bfloat16 holds (8 exponent bits, 7 of
    mantissa), kept as float32.  A plain ``astype`` pair is not a control:
    XLA's default ``xla_allow_excess_precision`` removes it inside a fusion,
    and the "bfloat16" ROI mean then read exactly 0 from the truth on the
    chip.  ``reduce_precision`` is never removed.  What is modelled is
    bfloat16 storage of the data, of each map's result and of the answer,
    with float32 accumulation between them, as the chip does it."""
    import jax
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def count(differs):
    """How many are set, as float32: rows counted in int32 (a whole array
    can hold more than 2**31), then summed.  0 exactly when none is."""
    import jax.numpy as jnp
    rows = jnp.sum(differs.astype(jnp.int32), axis=-1)
    return jnp.sum(rows.astype(jnp.float32))


def distance(got, want, denominator):
    """``max |got - want| / denominator``; infinite where the shapes differ
    or an answer is not finite."""
    got = np.asarray(got)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    if got.size == 0:
        return 0.0
    return float(np.max(np.abs(got.astype(np.float64) - want))) / denominator


def differing(got, want):
    """For exact answers: the count of elements that differ."""
    got = np.asarray(got)
    if got.shape != want.shape:
        return int(max(got.size, want.size))
    return int((got.astype(np.float64) != want).sum())
