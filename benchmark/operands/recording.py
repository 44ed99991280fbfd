"""``{"name": "recording"}``: a WHOLE two-photon recording (``frames`` of
``frame_shape``, keyed by time) as a ``fromcallback`` source whose loader
costs what a page-cache-resident memmap of frame files costs: zero-copy
views of a seeded host tile.  The tile is the whole recording, one copy of
it and no second, so every frame holds its own values and a re-axis that
puts a slab of frames at another slab's place, or a chip's block at another
chip's, differs from the closed form.  A new lazy source per pass, as a
caller would make one.

The closed form is of the PAIR ``(t, p)``, frame and pixel within the frame
(``p = x * width + y``), each index under ``2**32``: ``lattice.py``'s form
is of ONE flat 32-bit index and refuses an array of more than ``2**32``
elements, which a recording of 40,960 frames of 512 x 512 is (1.07e10).

    P(p) = mix(p + B)                   a key a pixel
    K(t) = mix(t * GOLDEN + A)          a key a frame
    v(t, p) = (mix(P(p) ^ K(t)) >> (32 - bits)) - 2**(bits - 1)

in uint32 arithmetic, ``(A, B) = lattice.constants(seed)`` and ``mix`` the
32-bit mixing bijection ``operands/motion.py`` draws its noise by.  ``K`` is
injective in ``t`` (an odd multiplier, then a bijection), so no two frames
share a key, and the pair is mixed again whole, so two frames agree in a
pixel as often as chance gives (1 in ``2**bits``) and in their top bits
half the time, frames whose ``t`` differ by a large power of two among them
(a second odd multiplier on ``t`` inside the lattice's form leaves those
alike in their top bits; so does a lone multiplication of ``P ^ K``, which
read a tenth off one half when tried).  Values are integers in
``[-2**(bits-1), 2**(bits-1))`` held as float32: with ``bits = 12`` exact
in float32 and NOT in bfloat16.

One form, two spellings: ``values`` is written once over a namespace, NumPy
(``host_frames``: the tile, the sampled frames of the check) or
``jax.numpy`` (``device_values``: the check that runs where the answer
lies, sharded as it lies); ``tests/`` holds them to each other to the bit.
The tile is filled a few frames at a time from the table ``P`` (one table a
seed), without ``mix``'s last step (``x ^ (x >> 16)`` cannot reach the top
16 bits, and ``bits`` are at most that), by a pool of threads sized from
the cores this process may run on; ``host_frames`` is ``values`` as it
stands, so the check of the tile leans on neither shortcut.  Imports nothing of the program but
``bolt.fromcallback`` in ``operand()``.
"""

import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import lattice
import reference

_M1, _M2 = 0x7FEB352D, 0x846CA68B         # lowbias32 (Wellons), a bijection
GOLDEN = 0x9E3779B1                       # odd: t -> t * GOLDEN is injective
GROUP = 4            # frames filled by one NumPy call: its temporaries (two
#                      of GROUP frames a thread) stay in cache, and a thread
#                      asks for the interpreter lock a quarter as often


def mix(x, xp):
    """A 32-bit mixing hash of uint32 ``x`` in the namespace ``xp``."""
    u = xp.uint32
    x = x ^ (x >> u(16))
    x = x * u(_M1)
    x = x ^ (x >> u(15))
    x = x * u(_M2)
    return x ^ (x >> u(16))


def pixel_keys(p, b, xp):
    return mix(p + xp.uint32(b), xp)


def frame_keys(t, a, xp):
    return mix(t * xp.uint32(GOLDEN) + xp.uint32(a), xp)


def values(t, p, a, b, bits, xp):
    """The closed form for uint32 ``t`` and ``p`` (broadcast against each
    other), float32."""
    x = mix(pixel_keys(p, b, xp) ^ frame_keys(t, a, xp), xp)
    x = x >> xp.uint32(32 - bits)
    return x.astype(xp.float32) - xp.float32(1 << (bits - 1))


def check_sizes(frames, frame_shape, bits):
    if frames >= 1 << 32 or int(np.prod(frame_shape, dtype=np.int64)) \
            >= 1 << 32:
        raise ValueError("recording: a frame or pixel index overflows 32 "
                         "bits")
    if not 1 <= bits <= 16:
        raise ValueError("recording: values of 1 to 16 bits, not %d" % bits)


def host_frames(lo, hi, frame_shape, seed, bits):
    """Frames ``[lo, hi)`` as float32 ``(hi - lo,) + frame_shape``, by
    NumPy straight from the closed form."""
    a, b = lattice.constants(seed)
    pixels = int(np.prod(frame_shape, dtype=np.int64))
    with np.errstate(over="ignore"):
        t = np.arange(lo, hi, dtype=np.uint32)[:, None]
        p = np.arange(pixels, dtype=np.uint32)[None, :]
        out = values(t, p, a, b, bits, np)
    return out.reshape((hi - lo,) + tuple(frame_shape))


def fill_threads():
    """Threads that fill the tile: the cores this process may run on, less
    one for the interpreter."""
    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:
        cores = os.cpu_count() or 2
    return max(1, cores - 1)


def host_recording(frames, frame_shape, seed, bits, threads=None):
    """Frames ``[0, frames)`` as one float32 array, filled ``GROUP`` frames
    at a time (NumPy lets go of the interpreter lock in each step) by
    ``threads`` threads over contiguous runs of frames, from the pixel
    keys computed once."""
    check_sizes(frames, frame_shape, bits)
    a, b = lattice.constants(seed)
    pixels = int(np.prod(frame_shape, dtype=np.int64))
    out = np.empty((frames, pixels), np.float32)
    with np.errstate(over="ignore"):
        pk = pixel_keys(np.arange(pixels, dtype=np.uint32), b, np)
        fk = frame_keys(np.arange(frames, dtype=np.uint32), a, np)
    half, top = np.float32(1 << (bits - 1)), np.uint32(32 - bits)
    m1, m2, s16, s15 = (np.uint32(_M1), np.uint32(_M2), np.uint32(16),
                        np.uint32(15))
    threads = max(1, min(threads or fill_threads(), frames))
    step = -(-frames // threads)

    def fill(lo):
        hi = min(lo + step, frames)
        x = np.empty((GROUP, pixels), np.uint32)
        y = np.empty((GROUP, pixels), np.uint32)
        for t in range(lo, hi, GROUP):
            n = min(GROUP, hi - t)
            u, v = x[:n], y[:n]
            np.bitwise_xor(pk[None, :], fk[t:t + n, None], out=u)
            np.right_shift(u, s16, out=v)
            np.bitwise_xor(u, v, out=u)
            np.multiply(u, m1, out=u)
            np.right_shift(u, s15, out=v)
            np.bitwise_xor(u, v, out=u)
            np.multiply(u, m2, out=u)
            np.right_shift(u, top, out=u)
            rows = out[t:t + n]
            rows[...] = u                   # uint32 -> float32, exact
            rows -= half
    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(fill, range(0, frames, step)))
    return out.reshape((frames,) + tuple(frame_shape))


def device_values(shape, a, b, bits, order=None, roll=None):
    """Traced ``jax.numpy`` expression of the whole recording, float32.
    ``a`` and ``b`` are ``lattice.constants(seed)`` as uint32 scalars,
    arguments of the jitted caller, so one compiled program serves every
    seed.  ``order`` permutes the axes of the RESULT: ``order=(1, 2, 0)``
    gives ``transpose(recording, order)`` without the recording ever
    existing on the device, which is how a re-axis of 42.95 GB is checked
    with no second copy.  ``roll = (axis, by)`` gives ``numpy.roll`` of
    the recording by ``by`` places along its axis ``axis`` first, by
    arithmetic on that axis's index.  Built of iotas alone, so a caller
    that compares it with a sharded array gets it partitioned the same
    way."""
    import jax
    import jax.numpy as jnp
    order = tuple(range(len(shape))) if order is None else tuple(order)
    check_sizes(shape[0], shape[1:], bits)
    out_shape = tuple(int(shape[ax]) for ax in order)
    stride, acc = {}, 1
    for ax in range(len(shape) - 1, 0, -1):
        stride[ax] = acc
        acc *= int(shape[ax])
    t = p = None
    for pos, ax in enumerate(order):
        i = jax.lax.broadcasted_iota(jnp.uint32, out_shape, pos)
        if roll is not None and roll[0] == ax:
            n = int(shape[ax])
            i = (i + jnp.uint32((-int(roll[1])) % n)) % jnp.uint32(n)
        if ax == 0:
            t = i
        else:
            i = i * jnp.uint32(stride[ax])
            p = i if p is None else p + i
    return values(t, p, a, b, bits, jnp)


class Recording:
    def __init__(self, spec, config, mesh, seed):
        self.shape = (int(config["frames"]),) + tuple(config["frame_shape"])
        self.bits, self.seed, self.mesh = int(config["bits"]), seed, mesh
        if np.dtype(config["dtype"]) != np.float32 \
                or list(config["key_axes"]) != [0]:
            raise ValueError("a recording is (frames,) + frame_shape "
                             "float32 keyed by time")
        threads = fill_threads()
        t0 = time.perf_counter()
        self.tile = host_recording(self.shape[0], self.shape[1:], seed,
                                   self.bits, threads)
        self.tile.setflags(write=False)
        self.nbytes = int(self.tile.nbytes)
        print("recording tile: %d frames, %.3f GB of host memory, filled "
              "in %.3f s by %d threads"
              % (self.shape[0], self.nbytes / 1e9,
                 time.perf_counter() - t0, threads), flush=True)
        self.loader_seconds = []        # appended by the uploader threads
        self.loader_bytes = []

    def load(self, index):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.loader"):
            block = self.tile[tuple(index)]
        self.loader_seconds.append(time.perf_counter() - t0)
        self.loader_bytes.append(block.nbytes)
        return block

    def operand(self):
        import bolt_tpu as bolt
        return bolt.fromcallback(self.load, self.shape, self.mesh,
                                 dtype=np.float32)

    def reference(self, man):
        return RecordingReference(man, self)


class RecordingReference(reference.Reference):
    """The recording and its closed form.  A re-axis is answered on the
    device by the step's own terminal (``steps/toseries.py``: the count of
    elements that differ from the re-axed closed form), which needs the
    constants, the shape and ``device_values``; the sampled frames of the
    host tile are held to the closed form by NumPy as every tile is."""

    KIND = "tile"

    def __init__(self, man, op):
        super().__init__(man, op.shape, op.bits, op.seed, 1)
        self.tile = op.tile
        self.device_values = device_values

    def constants(self):
        import jax.numpy as jnp
        a, b = lattice.constants(self.seed)
        return jnp.uint32(a), jnp.uint32(b)

    def data_mismatches(self, rng, records=4):
        rows = rng.choice(self.shape[0], size=min(records, self.shape[0]),
                          replace=False)
        return sum(int((self.tile[int(r)] != host_frames(
            int(r), int(r) + 1, self.shape[1:], self.seed,
            self.bits)[0]).sum()) for r in rows)


make = Recording
