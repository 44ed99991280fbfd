"""``{"call": "toseries_narrow", "kaxes": [..], "vaxes": [..]}``: Thunder's
``Images.toseries()``, bolt's ``swap(kaxes, vaxes)``, on a ``recording_u16``
operand (``operands/recording_u16.py``: the session as 16-bit or 8-bit
integers, the element the files hold).  ``steps/toseries.py`` serves the
float32 recording and cannot serve this one: its ``out != want`` promotes,
so an answer the program had widened to float32 would compare equal to the
closed form and pass, and its bfloat16 control is ``reduce_precision`` of
the closed form, which takes floats alone.

The answer stays on the device (fetch ``ready``) and is compared where it
lies: FIRST its dtype and shape are held to the configuration's (an answer
of another element reads ``inf`` and no value of it is compared; a widened
copy of the array is the one thing this configuration exists to rule out),
then the number is the count of elements that differ from the closed form
of the re-axed session, by one fused pass in the stored integers and no
second copy (``jax.numpy`` alone, nothing of the program).

Two controls.  ``lowp_on_device``: the values moved through bfloat16 AND
back to the stored integer differ (12 bits do not survive 8).
``displaced_on_device``: the answer against the closed form with one source
axis rolled, which counts what the answer itself would read with its blocks
moved the other way (a slab of frames placed a slab late) without making
the moved copy; it has to differ too."""

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
    p.terminal = ToSeriesNarrow(tuple(
        keys + [split + v for v in step["vaxes"]] + list(step["kaxes"])
        + [split + v for v in vals]))


def traffic(step, t):
    """Reads every element once and writes it once in its new place, as
    ``steps/toseries.py`` counts it; the caller multiplies by the stored
    item size."""
    t.read = t.written = t.elements()


class ToSeriesNarrow:
    def __init__(self, perm):
        self.perm = perm

    def _held(self, ref, out):
        """Whether ``out`` is the configuration's element and shape."""
        return (out.dtype == ref.dtype
                and tuple(out.shape) == tuple(ref.shape[ax]
                                              for ax in self.perm))

    def on_device(self, ref, p, out):
        return self.displaced_on_device(ref, p, out, None, None)

    def lowp_on_device(self, ref, p):
        """The session moved through bfloat16 and back to its integers
        differs from the closed form in this many elements."""
        return _lowp_move_program(ref.device_values, ref.shape, ref.bits,
                                  self.perm, ref.dtype.name)(
            *ref.constants())

    def displaced_on_device(self, ref, p, out, axis, by):
        """``out`` against the closed form with SOURCE axis ``axis`` rolled
        by ``by`` places (``None``: as it stands): 0 exactly where moving
        the answer's blocks that far would go unseen."""
        import jax.numpy as jnp
        if not self._held(ref, out):
            return jnp.float32(float("inf"))
        roll = None if axis is None else (int(axis), int(by))
        return _mismatch_program(ref.device_values, ref.shape, ref.bits,
                                 self.perm, roll, ref.dtype.name)(
            out, *ref.constants())


@functools.lru_cache(maxsize=None)
def _mismatch_program(device_values, shape, bits, perm, roll, dtype):
    import jax

    def run(out, a, b):
        want = device_values(shape, a, b, bits, order=perm, roll=roll,
                             dtype=dtype)
        assert want.dtype == out.dtype      # nothing promotes below
        return reference.count(out != want)
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _lowp_move_program(device_values, shape, bits, perm, dtype):
    import jax
    import jax.numpy as jnp

    def run(a, b):
        want = device_values(shape, a, b, bits, order=perm, dtype=dtype)
        moved = reference.bf16(want.astype(jnp.float32)).astype(want.dtype)
        return reference.count(moved != want)
    return jax.jit(run)
