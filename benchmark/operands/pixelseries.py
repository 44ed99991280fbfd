"""``{"name": "pixelseries"}``: a two-photon session as series keyed by
pixel, the array ``twophoton512-1chip.toseries`` leaves behind, made on the
device in one jitted call from the seed (element-wise: making it never holds
a second array) and handed to ``bolt.array`` where it lies, keyed by both
pixel axes.  This time the data is read for its VALUES: a per-pixel analysis
(dF/F by a percentile baseline, detrend, the Fourier tuning map) has to find
what was planted.

The closed form (``series`` block of the configuration).  Pixel ``p = x *
width + y``, time point ``t`` of ``T``:

    v[p, t] = R(p) + D(p, t) + S(p, t) + N(p, t)

* ``R(p)``: the resting level, an integer in ``[rest, rest + rest_span]``
  drawn by a 32-bit mixing hash of ``(p, seed)``: always far above zero, so
  a 20th-percentile baseline is a sane denominator;
* ``D(p, t) = (c1(p) t >> 13) + (c2(p) (t*t >> 14) >> 12)``: a slow drift of
  polynomial order 2 (at most ``drift`` counts a term over the session, the
  shifts floor it to whole counts), which a detrend of order 5 takes out;
* ``S(p, t) = (a(p) C[t] + b(p) Q[t]) >> 10``: the stimulus-locked sinusoid
  at bin ``freq``, ``C``/``Q`` the integer tables ``round(1024 cos)`` /
  ``round(1024 sin)`` of ``2 pi freq t / T`` made once by NumPy and handed to
  both spellings, ``a``, ``b`` integers in ``[-amplitude, amplitude]``: the
  planted amplitude is ``hypot(a, b)`` and the planted phase
  ``atan2(-b, a)``.  One pixel in three (by hash) has ``a = b = 0``: no
  tuning, coherence at the noise floor;
* ``N(p, t)``: noise, uniform in ``[-noise, noise]`` by the mixing hash of
  the absolute element index (a hash, not the lattice: the lattice is an
  arithmetic progression along ``t`` and would put a line in the spectrum).

What that gives.  With the noise's variance ``s2 = noise (noise + 1) / 3``
the coherence of a pixel of planted amplitude ``A`` is ``A / sqrt(A**2 + 2
s2)`` (the sinusoid's share of the one-sided non-DC energy), whatever its
resting level: 0.014 (``sqrt(2 / T)``, the floor) for the untuned third, up
to 0.94 at ``A = amplitude * sqrt(2)``, every value between for the rest.
The phase of a bin with no energy in it is ill-conditioned (the angle of a
complex number near zero), so phases are compared only where the reference
coherence passes the step's ``tuned`` threshold.

Every term is an integer and the sum stays inside ``[0, 2**bits)``: exact
in float32 and not in bfloat16, so a run one precision lower cannot pass.
One form, two spellings (NumPy for sampled pixels and the float64 reading,
``jax.numpy`` for the device), held to each other by ``tests/``.
"""

import functools

import numpy as np

import lattice
import reference

_M1, _M2 = 0x7FEB352D, 0x846CA68B         # lowbias32 (Wellons), a bijection


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


def tables(times, freq):
    """``(C, Q)``: ``round(1024 cos)``, ``round(1024 sin)`` of ``2 pi freq
    t / times`` as int32, by NumPy in float64 (handed to both spellings, so
    no transcendental is ever computed twice)."""
    w = 2.0 * np.pi * freq * np.arange(times, dtype=np.float64) / times
    return (np.rint(1024.0 * np.cos(w)).astype(np.int32),
            np.rint(1024.0 * np.sin(w)).astype(np.int32))


def check_spec(spec, times, bits):
    drift = int(spec["drift"])
    swing = (((drift * times) >> 13) + ((drift * ((times * times) >> 14))
                                        >> 12)
             + 2 * int(spec["amplitude"]) + int(spec["noise"]))
    reach = int(spec["rest"]) + int(spec["rest_span"]) + swing
    floor = int(spec["rest"]) - swing
    if reach >= 1 << bits or floor <= 0:
        raise ValueError("pixelseries: values span [%d, %d], outside (0, "
                         "2**%d)" % (floor, reach, bits))
    if times > 1 << 14 or not 1 <= int(spec["freq"]) <= times // 2:
        raise ValueError("pixelseries: at most 2**14 time points, and a "
                         "stimulus bin inside the spectrum")


def planted(p, spec, salt, xp):
    """``(R, c1, c2, a, b)`` of pixels ``p`` (uint32), each int32."""
    u, i = xp.uint32, xp.int32

    def draw(j, top):
        return uniform(mix(p * u(8) + u(j) + u(salt), xp), top, xp).astype(i)
    drift, amp = int(spec["drift"]), int(spec["amplitude"])
    rest = draw(0, int(spec["rest_span"])) + i(int(spec["rest"]))
    c1 = draw(1, 2 * drift) - i(drift)
    c2 = draw(2, 2 * drift) - i(drift)
    tuned = (draw(3, 2) != i(0)).astype(i)
    a = (draw(4, 2 * amp) - i(amp)) * tuned
    b = (draw(5, 2 * amp) - i(amp)) * tuned
    return rest, c1, c2, a, b


def values(p, t, cos, sin, spec, times, salt, xp):
    """The closed form for uint32 pixels ``p`` and time points ``t``
    (broadcast against each other), ``cos``/``sin`` the tables at ``t``;
    int32."""
    u, i = xp.uint32, xp.int32
    rest, c1, c2, a, b = planted(p, spec, salt, xp)
    ti = t.astype(i)
    square = ((t * t) >> u(14)).astype(i)
    drift = ((c1 * ti) >> i(13)) + ((c2 * square) >> i(12))
    wave = (a * cos + b * sin) >> i(10)
    top = int(spec["noise"])
    noise = uniform(mix(p * u(times) + t + u(salt), xp), 2 * top,
                    xp).astype(i) - i(top)
    return rest + drift + wave + noise


def host_rows(p, spec, times, seed):
    """Pixels ``p`` (any integer array) as float32 rows ``(.., times)``."""
    _, salt = lattice.constants(seed)
    cos, sin = tables(times, int(spec["freq"]))
    with np.errstate(over="ignore"):
        p = np.asarray(p).astype(np.uint32)[..., None]
        t = np.arange(times, dtype=np.uint32)
        return values(p, t, cos, sin, spec, times, salt,
                      np).astype(np.float32)


def device_values(shape, spec, salt, cos, sin):
    """Traced ``jax.numpy`` expression of the whole ``(height, width,
    times)`` array, float32: one element-wise fusion that writes the array
    and holds nothing beside it.  ``salt`` is ``lattice.constants(seed)[1]``
    as a uint32 scalar and ``cos``/``sin`` the tables, all arguments of the
    jitted caller, so one compiled program serves every seed."""
    import jax
    import jax.numpy as jnp
    height, width, times = shape
    iota = functools.partial(jax.lax.broadcasted_iota, jnp.uint32, shape)
    p = iota(0) * jnp.uint32(width) + iota(1)
    return values(p, iota(2), cos[None, None, :], sin[None, None, :], spec,
                  times, salt, jnp).astype(jnp.float32)


class PixelSeries:
    loader_seconds = loader_bytes = ()      # no loader: nothing to tally

    def __init__(self, spec, config, mesh, seed):
        import jax
        import jax.numpy as jnp
        import bolt_tpu as bolt
        from bolt_tpu import engine
        if "map_blocks" not in engine.counters():
            # a program older than this configuration lowers a map over
            # the whole array at once: the sort behind the percentile and
            # the FFT then ask for 40 GB of temporaries and XLA refuses
            # the program after the data has been made.  Say so now
            raise SystemExit(
                "configuration %s needs a program that lowers a record "
                "function with record-sized temporaries over blocks of "
                "records (engine counter map_blocks); this one has none"
                % config["name"])
        self.shape = tuple(config["pixels"]) + (int(config["times"]),)
        self.bits, self.seed = int(config["bits"]), seed
        self.spec = config["series"]
        if np.dtype(config["dtype"]) != np.float32 or len(self.shape) != 3 \
                or list(config["key_axes"]) != [0, 1]:
            raise ValueError("a pixel series is (height, width, times) "
                             "float32 keyed by both pixel axes")
        if int(np.prod(self.shape, dtype=np.int64)) > 1 << 32:
            raise ValueError("pixelseries index overflows 32 bits")
        check_spec(self.spec, self.shape[2], self.bits)
        P = jax.sharding.PartitionSpec
        sharding = jax.sharding.NamedSharding(mesh, P(mesh.axis_names[0]))
        make = jax.jit(
            lambda salt, c, q: device_values(self.shape, self.spec, salt,
                                             c, q),
            out_shardings=sharding)
        _, salt = lattice.constants(seed)
        cos, sin = tables(self.shape[2], int(self.spec["freq"]))
        self.data = make(jnp.uint32(salt), jnp.asarray(cos), jnp.asarray(sin))
        self.data.block_until_ready()
        self.array = bolt.array(self.data, context=mesh, axis=(0, 1))
        self.nbytes = int(np.prod(self.shape, dtype=np.int64)) * 4

    def operand(self):
        return self.array

    def reference(self, man):
        return PixelSeriesReference(man, self.data, self.shape, self.bits,
                                    self.seed, self.spec)


class PixelSeriesReference(reference.ResidentReference):
    """Answers over the device array of the seeded session: the array
    itself (read in blocks by the step's terminal, ``steps/tuning_map.py``),
    sampled pixels on the host for the float64 reading, and the check that
    the data is what the closed form says."""

    def __init__(self, man, data, shape, bits, seed, spec):
        super().__init__(man, data, shape, bits, seed, split=2)
        self.spec = spec

    def pixels(self, picks):
        """Pixels ``picks`` (flat indices) as held on the device, float64
        ``(len(picks), times)``."""
        width = self.shape[1]
        return np.stack([np.asarray(self.data[int(p) // width,
                                              int(p) % width])
                         for p in picks]).astype(np.float64)

    def data_mismatches(self, rng, slabs=4, rows=64):
        """Sampled runs of pixels of the device array against the closed
        form by NumPy: is the data what it claims to be?"""
        height, width, times = self.shape
        rows = min(rows, width)
        bad = 0
        for _ in range(slabs):
            x = int(rng.integers(height))
            y = int(rng.integers(width - rows + 1))
            held = np.asarray(self.data[x, y:y + rows])
            p = x * width + np.arange(y, y + rows, dtype=np.int64)
            bad += int((held != host_rows(p, self.spec, times,
                                          self.seed)).sum())
        return bad


make = PixelSeries
