"""``{"name": "motion"}``: a two-photon session THAT MOVES, as a
``fromcallback`` source whose loader costs what a page-cache-resident memmap
of frame files costs (zero-copy views of a seeded host tile).  The data is
read for its values this time: a registration has to find the displacement
that was planted in every frame.  The tile is the WHOLE session (one
session, no second copy), so a slab registered at another slab's place, or a
frame shifted by its neighbour's displacement, differs from the closed form.

The closed form (``motion`` block of the configuration).  Frame ``t``, pixel
``(x, y)`` of an ``(h, w)`` frame:

    v[t, x, y] = S[x + m + W[t, 0], y + m + W[t, 1]] + N(t, x, y)

* ``S``: ONE fixed scene of ``(h + 2 m, w + 2 m)`` (``m`` the ``margin``):
  a resting level, ``blobs`` Gaussian cell bodies of seeded place, width
  and brightness, and fine texture (a per-pixel draw), rounded to integers
  and capped so that every value of the session stays under ``2**bits``.
  Made ONCE by NumPy in float64 and handed to both spellings as a table of
  int32, so no transcendental is ever computed twice;
* ``W``: the walk, ``(frames, 2)`` int32, a bounded random walk of steps in
  ``{-1, 0, 1}`` that is reflected at ``+-walk`` pixels on each axis: the
  field of view drifts as a preparation does.  A table too;
* ``N``: per-frame noise, uniform in ``[-noise, noise]``, by a 32-bit mixing
  hash of the absolute element index and the seed (a hash, not the lattice:
  an arithmetic progression along ``t`` would put a line in the correlation
  surface).

Every value is an integer in ``[0, 2**bits)``, ``bits = 14``: exact in
float32 and not in bfloat16.  One form, two spellings (``host_frames`` by
NumPy for the tile and the sampled frames of the check, ``device_frames`` by
``jax.numpy`` for the check that runs where the answer lies), held to each
other to the bit by ``tests/``.  Imports nothing of the program but
``bolt.fromcallback`` in ``operand()``.
"""

import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import lattice
import reference

_M1, _M2 = 0x7FEB352D, 0x846CA68B         # lowbias32 (Wellons), a bijection
PART = 8             # frames filled by one call: its temporaries stay in cache


def mix(x, xp):
    """A 32-bit mixing hash of uint32 ``x`` in the namespace ``xp``."""
    u = xp.uint32
    x = x ^ (x >> u(16))
    x = x * u(_M1)
    x = x ^ (x >> u(15))
    x = x * u(_M2)
    return x ^ (x >> u(16))


def uniform(h, top, xp):
    """Hash ``h`` (uint32) to an integer in ``[0, top]``, ``top < 2**15``."""
    u = xp.uint32
    return ((h >> u(16)) * u(top + 1)) >> u(16)


def check_spec(spec, frames, frame_shape, bits):
    h, w = frame_shape
    if frames * h * w > 1 << 32:
        raise ValueError("motion: the element index overflows 32 bits")
    if int(spec["walk"]) > int(spec["margin"]):
        raise ValueError("motion: the walk leaves the scene's margin")
    floor = int(spec["rest"]) - int(spec["noise"])
    if floor < 0 or int(spec["noise"]) >= 1 << 14 or bits > 15:
        raise ValueError("motion: values would leave [0, 2**%d)" % bits)


def scene(spec, frame_shape, seed, bits):
    """The fixed scene ``S``, int32 ``(h + 2 m, w + 2 m)``, by NumPy."""
    h, w = frame_shape
    m = int(spec["margin"])
    rng = np.random.default_rng([int(seed), 0x5CE7E])
    rows, cols = h + 2 * m, w + 2 * m
    out = np.full((rows, cols), float(spec["rest"]))
    u = np.arange(rows, dtype=np.float64)[:, None]
    v = np.arange(cols, dtype=np.float64)[None, :]
    lo, hi = spec["blob_width"]
    for _ in range(int(spec["blobs"])):
        cu, cv = rng.uniform(0, rows), rng.uniform(0, cols)
        s = rng.uniform(lo, hi)
        a = rng.uniform(0.1, 1.0) * float(spec["blob_peak"])
        # a blob reaches 4 widths: fill only that window
        u0, u1 = max(0, int(cu - 4 * s)), min(rows, int(cu + 4 * s) + 1)
        v0, v1 = max(0, int(cv - 4 * s)), min(cols, int(cv + 4 * s) + 1)
        out[u0:u1, v0:v1] += a * np.exp(
            -((u[u0:u1] - cu) ** 2 + (v[:, v0:v1] - cv) ** 2) / (2 * s * s))
    out += rng.integers(0, int(spec["texture"]) + 1, size=out.shape)
    cap = (1 << bits) - 1 - int(spec["noise"])
    return np.minimum(np.rint(out), cap).astype(np.int32)


def walk(spec, frames, seed):
    """The offsets ``W``, int32 ``(frames, 2)``: a reflected random walk
    within ``+-walk`` pixels, starting at 0."""
    rng = np.random.default_rng([int(seed), 0x3A1C])
    steps = rng.integers(-1, 2, size=(frames, 2))
    top = int(spec["walk"])
    if top == 0:
        return np.zeros((frames, 2), np.int32)
    # reflection at +-top: fold the free walk into a triangle wave
    free = np.cumsum(steps, axis=0) - steps[0]
    period = 4 * top
    folded = np.abs((free + top) % period - 2 * top) - top
    return (-folded).astype(np.int32)


def noise(t, x, y, spec, frame_shape, salt, xp):
    """``N`` for uint32 ``t``, ``x``, ``y`` (broadcast against each other),
    int32 in ``[-noise, noise]``."""
    h, w = frame_shape
    u = xp.uint32
    top = int(spec["noise"])
    index = (t * u(h) + x) * u(w) + y
    return uniform(mix(index + u(salt), xp), 2 * top,
                   xp).astype(xp.int32) - xp.int32(top)


def host_frames(lo, hi, spec, frame_shape, seed, tables):
    """Frames ``[lo, hi)`` as float32 ``(hi - lo, h, w)``, by NumPy."""
    sc, wk = tables
    h, w = frame_shape
    m = int(spec["margin"])
    _, salt = lattice.constants(seed)
    out = np.empty((hi - lo, h, w), np.float32)
    x = np.arange(h, dtype=np.uint32)[:, None]
    y = np.arange(w, dtype=np.uint32)[None, :]
    with np.errstate(over="ignore"):
        for i, t in enumerate(range(lo, hi)):
            ox, oy = m + int(wk[t, 0]), m + int(wk[t, 1])
            out[i] = sc[ox:ox + h, oy:oy + w] + noise(
                np.uint32(t), x, y, spec, frame_shape, salt, np)
    return out


def host_session(frames, spec, frame_shape, seed, tables, threads=None):
    """The whole session as one float32 array, filled in parts of ``PART``
    frames by a pool of threads (NumPy lets go of the interpreter lock in
    each step), holding one session and no second copy."""
    out = np.empty((frames,) + tuple(frame_shape), np.float32)

    def fill(lo):
        hi = min(lo + PART, frames)
        out[lo:hi] = host_frames(lo, hi, spec, frame_shape, seed, tables)
    threads = threads or max(1, min(12, (os.cpu_count() or 2) - 1))
    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(fill, range(0, frames, PART)))
    return out


def device_frames(t0, count, spec, frame_shape, salt, sc, wk):
    """Traced ``jax.numpy`` expression of frames ``[t0, t0 + count)``,
    float32 ``(count, h, w)``: ``t0`` a traced int32 scalar, ``salt``
    (``lattice.constants(seed)[1]`` as uint32), the scene ``sc`` and the
    walk ``wk`` arguments of the jitted caller, so one compiled program
    serves every block and every seed."""
    import jax
    import jax.numpy as jnp
    h, w = frame_shape
    m = int(spec["margin"])
    ts = t0 + jnp.arange(count, dtype=jnp.int32)
    at = jnp.take(wk, ts, axis=0) + jnp.int32(m)

    def crop(o):
        return jax.lax.dynamic_slice(sc, (o[0], o[1]), (h, w))
    shape = (count, h, w)
    iota = [jax.lax.broadcasted_iota(jnp.uint32, shape, k) for k in range(3)]
    n = noise(iota[0] + t0.astype(jnp.uint32), iota[1], iota[2], spec,
              frame_shape, salt, jnp)
    return (jax.vmap(crop)(at) + n).astype(jnp.float32)


class Motion:
    def __init__(self, spec, config, mesh, seed):
        try:
            from bolt_tpu.ops import register   # noqa: F401
        except ImportError as exc:
            # a program older than this configuration: say so now, before
            # the session is made and long before anything is uploaded
            raise SystemExit(
                "configuration %s needs a program with bolt_tpu.ops."
                "register (fit / transform on a streamed source); this "
                "one has none: %s" % (config["name"], exc))
        self.shape = (int(config["frames"]),) + tuple(config["frame_shape"])
        self.bits, self.seed, self.mesh = int(config["bits"]), seed, mesh
        self.spec = config["motion"]
        self.reference_frames = int(config["reference_frames"])
        if np.dtype(config["dtype"]) != np.float32 or len(self.shape) != 3 \
                or list(config["key_axes"]) != [0]:
            raise ValueError("a session is (frames, height, width) float32 "
                             "keyed by time")
        check_spec(self.spec, self.shape[0], self.shape[1:], self.bits)
        t0 = time.perf_counter()
        self.tables = (scene(self.spec, self.shape[1:], seed, self.bits),
                       walk(self.spec, self.shape[0], seed))
        self.tile = host_session(self.shape[0], self.spec, self.shape[1:],
                                 seed, self.tables)
        self.tile.setflags(write=False)
        # what a request streams: the reference image's frames, then the
        # session twice (fit, transform): steps/register.py's three passes
        self.nbytes = int(self.tile.nbytes) * 2 + int(
            self.tile[:self.reference_frames].nbytes)
        print("motion tile: %d frames, %.3f GB of host memory, filled in "
              "%.3f s; walk spans %s to %s pixels"
              % (self.shape[0], self.tile.nbytes / 1e9,
                 time.perf_counter() - t0,
                 self.tables[1].min(axis=0).tolist(),
                 self.tables[1].max(axis=0).tolist()), flush=True)
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

    def source(self, frames=None):
        """A new lazy source over the first ``frames`` frames (default:
        the session), as a caller would make one."""
        import bolt_tpu as bolt
        shape = (self.shape[0] if frames is None else int(frames),) \
            + self.shape[1:]
        return bolt.fromcallback(self.load, shape, self.mesh,
                                 dtype=np.float32)

    def operand(self):
        return self

    def reference(self, man):
        return MotionReference(man, self)


class MotionReference(reference.Reference):
    """The session and its closed form, for ``steps/register.py``'s
    terminal: the host tile (blocks of it go up to the device for the
    plain reference's own pass), the tables of the closed form as device
    arguments, and the check that the tile is what the closed form says."""

    KIND = "tile"

    def __init__(self, man, op):
        super().__init__(man, op.shape, op.bits, op.seed, 1)
        self.tile, self.spec, self.tables = op.tile, op.spec, op.tables
        self.reference_frames = op.reference_frames
        # what the terminal's device programs are cached by and built from
        self.spec_items = tuple(sorted(
            (k, tuple(v) if isinstance(v, list) else v)
            for k, v in op.spec.items()))
        self.device_frames = device_frames

    def constants(self):
        """``(salt, scene, walk)`` as the device programs take them."""
        import jax.numpy as jnp
        _, salt = lattice.constants(self.seed)
        return (jnp.uint32(salt), jnp.asarray(self.tables[0]),
                jnp.asarray(self.tables[1]))

    def data_mismatches(self, rng, records=4):
        """Sampled frames of the tile against the closed form by NumPy."""
        rows = rng.choice(self.shape[0], size=min(records, self.shape[0]),
                          replace=False)
        return sum(int((self.tile[int(r)] != host_frames(
            int(r), int(r) + 1, self.spec, self.shape[1:], self.seed,
            self.tables)[0]).sum()) for r in rows)


make = Motion
