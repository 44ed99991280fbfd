"""``{"call": "swap", "kaxes": [..], "vaxes": [..]}``: bolt's key<->value
axis exchange, a pure re-axis.  A terminal whose answer is as large as the
source, so it stays on the device (fetch ``ready``) and is compared where
it lies: the number is the count of elements that differ from the closed
form of the re-axed lattice, by one fused pass over it and no second copy."""

import functools

import lattice
import reference


def bind(step, man):
    k, v = tuple(step["kaxes"]), tuple(step["vaxes"])
    return lambda a: a.swap(k, v)


def plan(p, step):
    if p.windowed or p.bodies:
        raise ValueError("the reference checks a pure re-axis of the whole "
                         "source")
    split, n = p.split, len(p.shape)
    keys = [k for k in range(split) if k not in step["kaxes"]]
    vals = [v for v in range(n - split) if v not in step["vaxes"]]
    p.terminal = Swap(tuple(keys + [split + v for v in step["vaxes"]]
                            + list(step["kaxes"])
                            + [split + v for v in vals]))


def traffic(step, t):
    """Reads every element once and writes it once in its new place.  What
    crosses the interconnect is not HBM traffic, and the all-to-all's
    staging copies are traffic the chip could in principle avoid: neither
    is counted, so a swap reads well under 100 %."""
    t.read = t.written = t.elements()


class Swap:
    def __init__(self, perm):
        self.perm = perm

    def on_device(self, ref, p, out):
        return _mismatch_program(ref.shape, ref.bits, self.perm)(
            out, *ref.constants())

    def lowp_on_device(self, ref, p):
        """The lattice moved in bfloat16 differs from the closed form in
        this many elements."""
        return _lowp_move_program(ref.shape, ref.bits, self.perm)(
            *ref.constants())


@functools.lru_cache(maxsize=None)
def _mismatch_program(shape, bits, perm):
    import jax

    def run(out, a, b):
        want = lattice.device_values(shape, a, b, bits, order=perm)
        return reference.count(out != want)
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _lowp_move_program(shape, bits, perm):
    import jax

    def run(a, b):
        want = lattice.device_values(shape, a, b, bits, order=perm)
        return reference.count(reference.bf16(want) != want)
    return jax.jit(run)
