"""``{"name": "recording_u16"}``: a whole two-photon session AS THE CAMERA
WROTE IT (``frames`` of ``frame_shape``, keyed by time, unsigned 16-bit
words of ``bits`` significant bits) as a ``fromcallback`` source whose
loader costs what a page-cache-resident memmap of 16-bit frame files
costs: zero-copy views of a seeded host tile that is the whole session, one
copy of it and no second.  ``operands/recording.py`` holds the same
recording as float32 and refuses any other dtype; this is its closed form
with the element kept as the files hold it:

    P(p) = mix(p + B)                   a key a pixel
    K(t) = mix(t * GOLDEN + A)          a key a frame
    v(t, p) = mix(P(p) ^ K(t)) >> (32 - bits)

in uint32 arithmetic, no offset, in ``[0, 2**bits)``, held as uint16: a
12-bit digitiser's words.  ``mix``, the two keys and the limits on the sizes
are ``operands/recording.py``'s own functions (one form of the pair (frame,
pixel), for sessions of more than ``2**32`` elements, which ``lattice.py``
refuses: 20,480 frames of 512 x 512 are 5.37e9), so what is said there of
frames a power of two apart holds here.  With ``bits = 12`` the values are
exact in uint16 and NOT in bfloat16 (8 significant bits): the values moved
through bfloat16 and back differ, which is the step's low-precision
control.

One form, two spellings, as there: ``values`` over a namespace, NumPy
(``host_frames``, the tile) or ``jax.numpy`` (``device_values``, the check
that runs where the answer lies); ``benchmark/tests/test_recording_u16.py``
holds them to each other to the bit.  Nothing here is ever float: the
reference keeps the element too.  Imports nothing of the program but
``bolt.fromcallback`` in ``operand()``.
"""

import importlib.util
import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import lattice
import reference


def _sibling(name):
    """Another operand's file, by its path: ``operands/`` is no package."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_operands_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_rec = _sibling("recording")
mix, pixel_keys, frame_keys = _rec.mix, _rec.pixel_keys, _rec.frame_keys
check_sizes, fill_threads, GROUP = (_rec.check_sizes, _rec.fill_threads,
                                    _rec.GROUP)
NARROW = ("uint16", "int16", "uint8")     # what a camera or a codec stores


def check_dtype(dtype, bits):
    """The stored element: an integer of 8 or 16 bits that holds ``bits``
    unsigned bits."""
    dtype = np.dtype(dtype)
    if dtype.name not in NARROW:
        raise ValueError("a narrow recording is stored as one of %s, not %s"
                         % (", ".join(NARROW), dtype.name))
    if (1 << bits) - 1 > np.iinfo(dtype).max:
        raise ValueError("%d bits do not fit %s" % (bits, dtype.name))
    return dtype


def values(t, p, a, b, bits, xp, dtype=np.uint16):
    """The closed form for uint32 ``t`` and ``p`` (broadcast against each
    other), as ``dtype``."""
    x = mix(pixel_keys(p, b, xp) ^ frame_keys(t, a, xp), xp)
    return (x >> xp.uint32(32 - bits)).astype(dtype)


def host_frames(lo, hi, frame_shape, seed, bits, dtype=np.uint16):
    """Frames ``[lo, hi)`` as ``(hi - lo,) + frame_shape`` of ``dtype``, by
    NumPy straight from the closed form."""
    a, b = lattice.constants(seed)
    pixels = int(np.prod(frame_shape, dtype=np.int64))
    with np.errstate(over="ignore"):
        t = np.arange(lo, hi, dtype=np.uint32)[:, None]
        p = np.arange(pixels, dtype=np.uint32)[None, :]
        out = values(t, p, a, b, bits, np, dtype)
    return out.reshape((hi - lo,) + tuple(frame_shape))


def host_recording(frames, frame_shape, seed, bits, dtype=np.uint16,
                   threads=None):
    """Frames ``[0, frames)`` as one array of ``dtype``, filled ``GROUP``
    frames at a time by ``threads`` threads over contiguous runs of frames,
    from the pixel keys computed once and without ``mix``'s last step
    (``x ^ (x >> 16)`` cannot reach the top 16 bits, and ``bits`` are at
    most that), as ``operands/recording.py`` fills its tile."""
    check_sizes(frames, frame_shape, bits)
    dtype = check_dtype(dtype, bits)
    a, b = lattice.constants(seed)
    pixels = int(np.prod(frame_shape, dtype=np.int64))
    out = np.empty((frames, pixels), dtype)
    with np.errstate(over="ignore"):
        pk = pixel_keys(np.arange(pixels, dtype=np.uint32), b, np)
        fk = frame_keys(np.arange(frames, dtype=np.uint32), a, np)
    top = np.uint32(32 - bits)
    m1, m2, s16, s15 = (np.uint32(_rec._M1), np.uint32(_rec._M2),
                        np.uint32(16), np.uint32(15))
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
            out[t:t + n] = u                # under 2**bits: fits, exact
    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(fill, range(0, frames, step)))
    return out.reshape((frames,) + tuple(frame_shape))


def device_values(shape, a, b, bits, order=None, roll=None,
                  dtype=np.uint16):
    """Traced ``jax.numpy`` expression of the whole session as ``dtype``,
    with ``operands/recording.py::device_values``' arguments: ``order``
    permutes the axes of the RESULT (the re-axed session without the
    session ever existing on the device), ``roll = (axis, by)`` is
    ``numpy.roll`` of the session along a SOURCE axis first, by arithmetic
    on that axis's index.  Built of iotas alone."""
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
    return values(t, p, a, b, bits, jnp, dtype)


class NarrowRecording:
    def __init__(self, spec, config, mesh, seed):
        self.shape = (int(config["frames"]),) + tuple(config["frame_shape"])
        self.bits, self.seed, self.mesh = int(config["bits"]), seed, mesh
        self.dtype = check_dtype(config["dtype"], self.bits)
        if list(config["key_axes"]) != [0]:
            raise ValueError("a recording is (frames,) + frame_shape keyed "
                             "by time")
        threads = fill_threads()
        t0 = time.perf_counter()
        self.tile = host_recording(self.shape[0], self.shape[1:], seed,
                                   self.bits, self.dtype, threads)
        self.tile.setflags(write=False)
        self.nbytes = int(self.tile.nbytes)
        print("recording tile: %d frames of %s, %.3f GB of host memory, "
              "filled in %.3f s by %d threads"
              % (self.shape[0], self.dtype.name, self.nbytes / 1e9,
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
                                 dtype=self.dtype)

    def reference(self, man):
        return NarrowReference(man, self)


class NarrowReference(reference.Reference):
    """The session and its closed form, and the element it is stored as
    (``dtype``: the step holds the answer's to it before it compares a
    value).  A re-axis is answered on the device by the step's own
    terminal (``steps/toseries_narrow.py``); the sampled frames of the host
    tile are held to the closed form by NumPy as every tile is."""

    KIND = "tile"

    def __init__(self, man, op):
        super().__init__(man, op.shape, op.bits, op.seed, 1)
        self.tile, self.dtype = op.tile, op.dtype
        self.device_values = device_values  # takes the dtype: one program
        #                                     a (shape, dtype), not a seed

    def constants(self):
        import jax.numpy as jnp
        a, b = lattice.constants(self.seed)
        return jnp.uint32(a), jnp.uint32(b)

    def data_mismatches(self, rng, records=4):
        rows = rng.choice(self.shape[0], size=min(records, self.shape[0]),
                          replace=False)
        return sum(int((self.tile[int(r)] != host_frames(
            int(r), int(r) + 1, self.shape[1:], self.seed, self.bits,
            self.dtype)[0]).sum()) for r in rows)


make = NarrowRecording
