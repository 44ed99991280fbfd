"""``{"call": "toseries", "kaxes": [..], "vaxes": [..]}``: Thunder's
``Images.toseries()``, which is bolt's ``swap(kaxes, vaxes)``, on a
``recording`` operand (``operands/recording.py``: a closed form of the pair
(frame, pixel), for a recording of more than ``2**32`` elements, which
``steps/swap.py``'s lattice refuses).  A terminal whose answer is as large
as the source and lies sharded over the chips, so it stays on the device
(fetch ``ready``) and is compared where it lies, sharded as it lies: the
number is the count of elements that differ from the closed form of the
re-axed recording, by one fused pass over it and no second copy
(``jax.numpy`` alone, nothing of the program).

Two controls.  ``lowp_on_device``: the recording moved in bfloat16 differs.
``displaced_on_device``: the answer against the closed form with one source
axis rolled, which counts what the answer itself would read with its blocks
moved the other way (a slab of frames placed a slab late; every chip's
block of rows on the next chip) without making the moved copy; it has to
differ too, which a tile that repeated or a check that ignored places would
not show."""

import functools

import reference


def bind(step, man):
    k, v = tuple(step["kaxes"]), tuple(step["vaxes"])
    return lambda a: a.swap(k, v)


def plan(p, step):
    if p.windowed or p.bodies:
        raise ValueError("the reference checks a pure re-axis of the whole "
                         "recording")
    split, n = p.split, len(p.shape)
    keys = [k for k in range(split) if k not in step["kaxes"]]
    vals = [v for v in range(n - split) if v not in step["vaxes"]]
    p.terminal = ToSeries(tuple(keys + [split + v for v in step["vaxes"]]
                                + list(step["kaxes"])
                                + [split + v for v in vals]))


def traffic(step, t):
    """Reads every element once and writes it once in its new place.  What
    crosses the interconnect is not HBM traffic, and the exchange's staging
    copies are traffic the chip could in principle avoid: neither is
    counted."""
    t.read = t.written = t.elements()


class ToSeries:
    def __init__(self, perm):
        self.perm = perm

    def on_device(self, ref, p, out):
        return _mismatch_program(ref.device_values, ref.shape, ref.bits,
                                 self.perm, None)(out, *ref.constants())

    def lowp_on_device(self, ref, p):
        """The recording moved in bfloat16 differs from the closed form in
        this many elements."""
        return _lowp_move_program(ref.device_values, ref.shape, ref.bits,
                                  self.perm)(*ref.constants())

    def displaced_on_device(self, ref, p, out, axis, by):
        """``out`` against the closed form with SOURCE axis ``axis`` rolled
        by ``by`` places: 0 exactly where moving the answer's blocks that
        far would go unseen."""
        return _mismatch_program(ref.device_values, ref.shape, ref.bits,
                                 self.perm, (int(axis), int(by)))(
            out, *ref.constants())


@functools.lru_cache(maxsize=None)
def _mismatch_program(device_values, shape, bits, perm, roll):
    import jax

    def run(out, a, b):
        want = device_values(shape, a, b, bits, order=perm, roll=roll)
        return reference.count(out != want)
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _lowp_move_program(device_values, shape, bits, perm):
    import jax

    def run(a, b):
        want = device_values(shape, a, b, bits, order=perm)
        return reference.count(reference.bf16(want) != want)
    return jax.jit(run)
