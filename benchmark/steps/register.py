"""``{"call": "register", "sampled": 256, "limits": {..}}``: Thunder's motion
correction at load, then its images-to-series, bound together as ONE request
on a session that arrives from the host (``operands/motion.py``):

    ref = bolt.fromcallback(load, (reference_frames, h, w), mesh,
                            dtype=float32).mean(axis=0).toarray()
    src = bolt.fromcallback(load, (frames, h, w), mesh, dtype=float32)
    disp = bolt.ops.register.fit(src, ref).toarray()
    reg = bolt.ops.register.transform(src, disp).swap((0,), (0, 1))

``ref`` is the mean of the first frames (a streamed statistic), ``disp`` the
``(frames, 2)`` int32 displacement trace ON THE HOST (one streamed pass, the
cross-correlation in the slab program, the result collected slab by slab),
``reg`` the registered series array still to be taken (a second streamed
pass, the keyed shift in front of the re-axis).  The handle is ``(reg,
disp)``; the fetch ``registered`` takes ``reg`` resident and complete and
keeps ``disp`` beside it.  ``chunks``, ``stream.spill``, ``prefetch`` and
``uploaders`` are not set.  A terminal.

What is compared, each reading with its own entry in ``limits`` (the
request kind carries ``"limit": 1``: the worst reading over its limit):

``registered``  where the answer lies, by one fused pass over it: the count
                of elements of the series array that differ from the closed
                form of the session shifted frame by frame by the
                displacements THAT REQUEST returned, and re-axed.  Limit 0:
                a whole-pixel shift with edge fill copies values.
``regret``      the benchmark's own plain reference (``jax.numpy`` alone,
                nothing of ``bolt_tpu``: ``fft2`` / ``ifft2`` in float32
                over EVERY frame, in blocks, once after the window) gives
                each frame's surface ``c``; the reading is the largest
                ``1 - c[got] / max(c)`` over all frames.  Regret and not
                equality: the reference image is a mean of moving frames,
                which leaves near-ties between neighbouring shifts that two
                float32 spellings may break differently.
``regret64``    the same over ``sampled`` frames against NumPy in float64:
                the program's distance from the truth.

Logged and held to nothing: the count of sampled frames whose displacement
differs from the float64 arg-max, and of all frames from the float32
reference's.
"""

import functools
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 64               # frames the float32 reference takes at once


def bind(step, man):
    from bolt_tpu.ops import register

    def call(op):
        ref = op.source(op.reference_frames).mean(axis=0).toarray()
        disp = register.fit(op.source(), ref).toarray()
        reg = register.transform(op.source(), disp).swap((0,), (0, 1))
        return reg, disp
    return call


def plan(p, step):
    if p.windowed or p.bodies:
        raise ValueError("register reads the whole session as it is")
    p.terminal = Register(int(step["sampled"]), step.get("limits", {}))


def traffic(step, t):
    """``fit`` reads the session once and writes two values a frame;
    ``transform`` and the re-axis read it once more and write it once in
    its new place.  The transforms' own passes are the program's."""
    frames = t.sizes[0]
    t.read, t.written = 2 * t.elements(), t.elements() + 2 * frames


def worst(parts, limits):
    """``max(reading / limit)`` over the named readings; a limit of 0 holds
    its reading to exactly 0.  At most 1 when every reading is within its
    limit."""
    number = 0.0
    for name, reading in parts.items():
        if not np.isfinite(reading):
            return float("inf")
        if name not in limits:
            continue
        limit = float(limits[name])
        if limit > 0:
            number = max(number, float(reading) / limit)
        elif reading != 0:
            return float("inf")
    return number


# -- the plain reference ---------------------------------------------------

def shifted(frames, disp):
    """``frames`` ``(b, h, w)`` each shifted by its own ``disp`` ``(b, 2)``:
    ``out[x, y] = frame[clip(x + dx), clip(y + dy)]``, by index arithmetic
    and ``take_along_axis`` (``jax.numpy`` alone)."""
    import jax.numpy as jnp
    _, h, w = frames.shape
    rows = jnp.clip(jnp.arange(h, dtype=jnp.int32)[None, :]
                    + disp[:, 0:1], 0, h - 1)
    cols = jnp.clip(jnp.arange(w, dtype=jnp.int32)[None, :]
                    + disp[:, 1:2], 0, w - 1)
    out = jnp.take_along_axis(frames, rows[:, :, None], axis=1)
    return jnp.take_along_axis(out, cols[:, None, :], axis=2)


def _divisor(n, top):
    return max(d for d in range(1, min(n, top) + 1) if n % d == 0)


@functools.lru_cache(maxsize=None)
def _registered_program(shape, spec_items, device_frames, lowp=False):
    """``(series, disp, salt, scene, walk) -> count`` of the elements of
    the ``(h, w, frames)`` series array that differ from the closed form
    of the session shifted by ``disp`` and re-axed; blocks of frames in
    one loop, so nothing array-sized is held beside the answer.

    ``lowp``: the control's count, with ``series`` ``None``: the session
    held in bfloat16, shifted and re-axed alike, stands for the answer;
    the shift copies values, so this counts the elements bfloat16 cannot
    hold."""
    import jax
    import jax.numpy as jnp
    import reference
    spec = dict(spec_items)
    frames, h, w = shape
    block = _divisor(frames, 128)

    def run(series, disp, salt, scene, walk):
        def body(i, acc):
            t0 = (i * block).astype(jnp.int32)
            made = device_frames(t0, block, spec, (h, w), salt, scene, walk)
            d = jax.lax.dynamic_slice(disp, (t0, 0), (block, 2))
            want = jnp.transpose(shifted(made, d), (1, 2, 0))
            if lowp:
                got = jnp.transpose(shifted(reference.bf16(made), d),
                                    (1, 2, 0))
            else:
                got = jax.lax.dynamic_slice(series, (0, 0, t0),
                                            (h, w, block))
            return acc + reference.count(got != want)
        return jax.lax.fori_loop(0, frames // block, body,
                                 jnp.zeros((), jnp.float32))
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _surface_program(lowp):
    """``(frames, image, got) -> (best, mine, at)`` a frame: the largest
    value of its float32 cross-correlation surface against ``image``, the
    surface's value at the displacement ``got``, and the surface's own
    arg-max (flat, before the cyclic adjustment)."""
    import jax
    import jax.numpy as jnp
    import reference

    def run(frames, image, got):
        b, h, w = frames.shape
        a = reference.bf16(frames) if lowp else frames
        c = jnp.abs(jnp.fft.ifft2(jnp.fft.fft2(a)
                                  * jnp.conj(jnp.fft.fft2(image))))
        flat = c.reshape(b, h * w)
        at = (got[:, 0] % h) * w + got[:, 1] % w
        mine = jnp.take_along_axis(flat, at[:, None], axis=1)[:, 0]
        return jnp.max(flat, axis=1), mine, jnp.argmax(flat, axis=1)
    return jax.jit(run)


def adjusted(at, h, w):
    """Flat arg-max ``at`` as ``(dx, dy)`` with the cyclic adjustment."""
    d = np.stack([at // w, at % w], axis=-1).astype(np.int64)
    n = np.asarray([h, w])
    return np.where(d > n // 2, d - n, d).astype(np.int32)


def surface64(frame, spectrum):
    """``|ifft2(fft2(frame) conj(fft2(image)))|`` by NumPy in float64,
    ``spectrum`` the conjugate transform of the image."""
    return np.abs(np.fft.ifft2(np.fft.fft2(frame.astype(np.float64))
                               * spectrum))


class Register:
    def __init__(self, sampled, limits):
        self.sampled, self.limits = sampled, limits
        self._readings, self.logged = {}, set()

    # -- what every request shares -----------------------------------------

    def image(self, ref):
        """The reference image as the plain reference makes it: the mean
        of the first frames in float64 (exact: integers)."""
        return np.mean(ref.tile[:ref.reference_frames], axis=0,
                       dtype=np.float64)

    def picks(self, ref):
        rng = np.random.default_rng(ref.seed)
        return np.sort(rng.choice(ref.shape[0],
                                  size=min(self.sampled, ref.shape[0]),
                                  replace=False))

    def surfaces(self, ref, got, lowp=False):
        """``(best, mine, at)`` over every frame by the float32 reference,
        the session uploaded in blocks of ``BLOCK`` frames."""
        import jax
        prog = _surface_program(lowp)
        image = jax.device_put(self.image(ref).astype(np.float32))
        out = []
        for lo in range(0, ref.shape[0], BLOCK):
            block = jax.device_put(ref.tile[lo:lo + BLOCK])
            out.append(prog(block, image, jax.device_put(got[lo:lo + BLOCK])))
            if len(out) > 2:
                out[-3] = tuple(np.asarray(x) for x in out[-3])
        return tuple(np.concatenate([np.asarray(o[k]) for o in out])
                     for k in range(3))

    def regrets(self, ref, got):
        """The readings of a displacement trace ``got``: ``regret``,
        ``regret64`` and the two counts that are only logged."""
        got = np.asarray(got)
        if got.shape != (ref.shape[0], 2) \
                or not np.issubdtype(got.dtype, np.integer):
            return {"regret": float("inf")}
        key = got.tobytes()
        if key in self._readings:
            return self._readings[key]
        _, h, w = ref.shape
        best, mine, at = self.surfaces(ref, got.astype(np.int32))
        picks = self.picks(ref)
        spectrum = np.conj(np.fft.fft2(self.image(ref)))

        def one(t):
            c = surface64(ref.tile[t], spectrum)
            d = got[t]
            return (1.0 - c[d[0] % h, d[1] % w] / c.max(),
                    int(np.argmax(c)))
        with ThreadPoolExecutor(8) as pool:
            r64, at64 = zip(*pool.map(one, picks))
        out = {
            "regret": float(np.max(1.0 - mine.astype(np.float64) / best)),
            "regret64": float(np.max(r64)),
            "differ": int((adjusted(at, h, w) != got).any(axis=1).sum()),
            "differ64": int((adjusted(np.asarray(at64), h, w)
                             != got[picks]).any(axis=1).sum()),
        }
        self._readings[key] = out
        return out

    # -- the answers ---------------------------------------------------------

    def on_device(self, ref, p, out):
        import jax.numpy as jnp
        series, disp = out
        if np.shape(disp) != (ref.shape[0], 2) or tuple(series.shape) != (
                ref.shape[1], ref.shape[2], ref.shape[0]):
            return Answer(self, ref, None, disp)    # no answer at all
        count = _registered_program(ref.shape, ref.spec_items,
                                    ref.device_frames)(
            series, jnp.asarray(np.asarray(disp, np.int32)),
            *ref.constants())
        return Answer(self, ref, count, disp)

    def lowp_on_device(self, ref, p):
        """The control: the session held in bfloat16 and nothing else.
        Its displacements are the float32 reference's arg-max over the
        rounded frames; its series array is the rounded session shifted by
        them."""
        import jax.numpy as jnp
        _, h, w = ref.shape
        zeros = np.zeros((ref.shape[0], 2), np.int32)
        _, _, at = self.surfaces(ref, zeros, lowp=True)
        disp = adjusted(at, h, w)
        count = _registered_program(ref.shape, ref.spec_items,
                                    ref.device_frames, lowp=True)(
            None, jnp.asarray(disp), *ref.constants())
        return Answer(self, ref, count, disp)


class Answer:
    """One request's check, as the driver holds it: the device count of
    ``registered`` (waited for inside the window's ``bench.check``) and the
    small displacement trace; ``float()`` of it, asked after the window, is
    the number compared with the kind's limit."""

    def __init__(self, terminal, ref, count, disp):
        self.terminal, self.ref = terminal, ref
        self.count, self.disp = count, np.asarray(disp)

    def block_until_ready(self):
        if self.count is not None:
            self.count.block_until_ready()
        return self

    def parts(self):
        if self.count is None:
            return {"registered": float("inf")}
        out = {"registered": float(self.count)}
        out.update(self.terminal.regrets(self.ref, self.disp))
        return out

    def __float__(self):
        parts = self.parts()
        line = "check register: " + ", ".join(
            "%s %.6g" % kv for kv in sorted(parts.items()))
        if line not in self.terminal.logged:     # once a distinct reading
            self.terminal.logged.add(line)
            print(line, flush=True)
        return worst(parts, self.terminal.limits)
