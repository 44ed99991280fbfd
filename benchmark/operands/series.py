"""``{"name": "series"}``: a voxels x time matrix with a spectrum a PCA can
be checked on, made on the device in one jitted call from the seed and
handed to ``bolt.array`` where it lies, keyed by plane.

The closed form (``series`` block of the configuration).  Sample ``s`` is
voxel ``v`` of plane ``p`` (``s = p * voxels + v``), ``t`` the time point:

    x[s, t] = sum_j a_j(s) * c_j(t)  +  b(t)  +  e(s, t)

* ``c_j``: row ``rows[j]`` of the Hadamard matrix of order ``times`` (a
  power of two), entries +-1: the planted temporal components are exactly
  orthogonal, ``c_j . c_k = times * (j == k)``;
* ``a_j(s)``: an integer drawn uniformly from ``[-A_j, A_j]`` by a 32-bit
  mixing hash of ``(s, j, seed)``: the components' strengths
  ``amplitudes = [A_1 > A_2 > ...]`` fall by 15 % each, so the planted
  eigenvalues ``n * times * A_j (A_j + 1) / 3`` are 28 % apart;
* ``b(t)``: a baseline in ``[-baseline, baseline]`` per time point, so that
  centring has something to take away;
* ``e``: the benchmark's lattice (``lattice.py``) at ``noise_bits`` bits,
  whose own spectrum is not flat and is not needed: its largest eigenvalue
  is bounded by ``times * 4**noise_bits / 12`` a sample, far under the
  weakest planted one.

Every term is an integer and their magnitudes add up to less than
``2**(bits - 1)``, so every value is exact in float32 and not in bfloat16,
every product of two is below ``2**24``, and the reference can accumulate
Gram matrices exactly.  One form, two spellings (NumPy for sampled rows and
the check of the data, ``jax.numpy`` for the device), held to each other by
``tests/``.
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
    """Hash ``h`` (uint32) to an integer in ``[0, top]``, ``top < 2**15``:
    the high 16 bits scaled, all inside 32 bits."""
    u = xp.uint32
    return ((h >> u(16)) * u(top + 1)) >> u(16)


def check_spec(spec, times, bits):
    amps = [int(a) for a in spec["amplitudes"]]
    rows = [int(r) for r in spec["rows"]]
    if times & (times - 1) or len(rows) != len(amps) \
            or len(set(rows)) != len(rows) or not all(0 < r < times
                                                      for r in rows):
        raise ValueError("series: times is a power of two and rows are "
                         "distinct Hadamard rows in (0, times)")
    reach = sum(amps) + int(spec["baseline"]) \
        + (1 << (int(spec["noise_bits"]) - 1))
    if reach >= 1 << (bits - 1):
        raise ValueError("series: values reach %d, more than %d bits hold"
                         % (reach, bits))
    return amps, rows


def planted(s, t, spec, salt, xp):
    """``sum_j a_j(s) c_j(t) + b(t)`` as int32 for uint32 sample indices
    ``s`` and time points ``t`` (broadcast against each other)."""
    u, i = xp.uint32, xp.int32
    total = (uniform(mix(t * u(2) + u(1) + u(salt), xp),
                     2 * int(spec["baseline"]), xp).astype(i)
             - i(int(spec["baseline"])))
    ncomp = len(spec["amplitudes"])
    for j, (amp, row) in enumerate(zip(spec["amplitudes"], spec["rows"])):
        a = uniform(mix((s * u(ncomp) + u(j)) * u(2) + u(salt), xp),
                    2 * int(amp), xp).astype(i) - i(int(amp))
        par = t & u(int(row))                   # parity of popcount
        for shift in (16, 8, 4, 2, 1):
            par = par ^ (par >> u(shift))
        sign = i(1) - i(2) * (par & u(1)).astype(i)
        total = total + a * sign
    return total


def noise(index, a, b, bits, xp):
    """The lattice's value at absolute element ``index`` (uint32), int32."""
    u = xp.uint32
    x = (index * u(a) + u(b)) >> u(32 - bits)
    return x.astype(xp.int32) - xp.int32(1 << (bits - 1))


def host_rows(s, times, spec, seed):
    """Samples ``s`` (any integer array) as float32 rows ``(.., times)``."""
    a, b = lattice.constants(seed)
    with np.errstate(over="ignore"):
        s = np.asarray(s).astype(np.uint32)[..., None]
        t = np.arange(times, dtype=np.uint32)
        x = planted(s, t, spec, b, np) + noise(
            s * np.uint32(times) + t, a, b, int(spec["noise_bits"]), np)
    return x.astype(np.float32)


def device_values(shape, spec, a, b):
    """Traced ``jax.numpy`` expression of the whole ``(planes, voxels,
    times)`` array, float32; ``a``, ``b`` are ``lattice.constants`` as
    uint32 scalars (arguments of the jitted caller)."""
    import jax
    import jax.numpy as jnp
    planes, voxels, times = shape
    iota = functools.partial(jax.lax.broadcasted_iota, jnp.uint32, shape)
    s = iota(0) * jnp.uint32(voxels) + iota(1)
    t = iota(2)
    x = planted(s, t, spec, b, jnp) + noise(
        s * jnp.uint32(times) + t, a, b, int(spec["noise_bits"]), jnp)
    return x.astype(jnp.float32)


class Series:
    loader_seconds = loader_bytes = ()      # no loader: nothing to tally

    def __init__(self, spec, config, mesh, seed):
        import jax
        import jax.numpy as jnp
        import bolt_tpu as bolt
        from bolt_tpu import engine
        if "resplit_views" not in engine.counters():
            # a program older than this configuration would ask for a
            # second copy of the matrix to re-split it and for a third to
            # flatten it: it cannot hold this deployment, and says so now
            # instead of after minutes of set-up
            raise SystemExit(
                "configuration %s needs a program whose re-split is a view "
                "(engine counter resplit_views); this one has none"
                % config["name"])
        self.shape = (int(config["planes"]),) + tuple(config["record_shape"])
        self.bits, self.seed = int(config["bits"]), seed
        self.spec = config["series"]
        if np.dtype(config["dtype"]) != np.float32 or len(self.shape) != 3 \
                or list(config["key_axes"]) != [0]:
            raise ValueError("a series is (planes, voxels, times) float32 "
                             "keyed on axis 0")
        if int(np.prod(self.shape, dtype=np.int64)) > 1 << 32:
            raise ValueError("series index overflows 32 bits")
        check_spec(self.spec, self.shape[2], self.bits)
        P = jax.sharding.PartitionSpec
        sharding = jax.sharding.NamedSharding(mesh, P(mesh.axis_names[0]))
        make = jax.jit(
            lambda a, b: device_values(self.shape, self.spec, a, b),
            out_shardings=sharding)
        a, b = lattice.constants(seed)
        self.data = make(jnp.uint32(a), jnp.uint32(b))
        self.data.block_until_ready()
        self.array = bolt.array(self.data, context=mesh, axis=(0,))
        self.nbytes = int(np.prod(self.shape, dtype=np.int64)) * 4

    def operand(self):
        return self.array

    def reference(self, man):
        return SeriesReference(man, self.data, self.shape, self.bits,
                               self.seed, self.spec)


class SeriesReference(reference.ResidentReference):
    """Answers over the device array of the seeded series.  Beside what
    every resident reference gives, the exact second moments that the
    linear-algebra terminals (``steps/chunk_svd.py``, ``steps/pca.py``)
    start from."""

    def __init__(self, man, data, shape, bits, seed, spec):
        super().__init__(man, data, shape, bits, seed)
        self.spec = spec
        self._moments = {}

    def data_mismatches(self, rng, slabs=4, rows=65536):
        """Sampled slabs of the device array against the closed form by
        NumPy: is the data what it claims to be?"""
        planes, voxels, times = self.shape
        rows = min(rows, voxels)
        bad = 0
        for _ in range(slabs):
            p = int(rng.integers(planes))
            v = int(rng.integers(voxels - rows + 1))
            held = np.asarray(self.data[p, v:v + rows])
            s = p * voxels + np.arange(v, v + rows, dtype=np.int64)
            bad += int((held != host_rows(s, times, self.spec,
                                          self.seed)).sum())
        return bad

    def rows(self, patches, count):
        """``count`` rows from each ``(plane, voxel)`` of ``patches`` as
        held on the device, float64 ``(len(patches) * count, times)``."""
        return np.concatenate(
            [np.asarray(self.data[p, v:v + count]) for p, v in patches]
        ).astype(np.float64)

    def moments(self, rows, lowp=False):
        """Exact second moments of every block of ``rows`` consecutive
        voxels of every plane: ``(gram, total)``, int64 arrays
        ``(planes, blocks, times, times)`` and ``(planes, blocks, times)``
        with ``gram[p, g] = X^T X`` and ``total[p, g] = sum of the rows`` of
        block ``g`` of plane ``p``.  ``lowp``: of the data rounded to
        bfloat16 (still integers), which is exactly what one bfloat16 pass
        of the matrix unit multiplies.

        The data are integers of at most ``bits`` bits: ``x = 64 h + l``
        with ``h`` and ``l`` inside int8, so ``X^T X = 4096 H^T H + 64
        (H^T L + L^T H) + L^T L``, each an int8 product accumulated in
        int32 over a run of rows that cannot overflow it, finished in int64
        on the host."""
        key = (int(rows), bool(lowp))
        if key not in self._moments:
            self._moments[key] = self._exact_moments(*key)
        return self._moments[key]

    def _exact_moments(self, rows, lowp):
        planes, voxels, times = self.shape
        if voxels % rows:
            raise ValueError("blocks of %d rows do not tile %d voxels"
                             % (rows, voxels))
        run = rows
        while run * 63 * 63 > reference.INT32_MAX \
                or run << (self.bits - 1) > reference.INT32_MAX:
            if run % 2:
                raise ValueError("no run of %d rows fits int32" % rows)
            run //= 2
        prog = _moments_program(voxels // run, run, times, self.bits, lowp)
        gram = np.zeros((planes, voxels // rows, times, times), np.int64)
        total = np.zeros((planes, voxels // rows, times), np.int64)
        per = rows // run
        for p in range(planes):
            hh, hl, ll, tot = (np.asarray(x).astype(np.int64)
                               for x in prog(self.data[p]))
            g = 4096 * hh + 64 * (hl + np.swapaxes(hl, -1, -2)) + ll
            gram[p] = g.reshape(-1, per, times, times).sum(axis=1)
            total[p] = tot.reshape(-1, per, times).sum(axis=1)
        return gram, total


@functools.lru_cache(maxsize=None)
def _moments_program(runs, run, times, bits, lowp):
    import jax
    import jax.numpy as jnp

    def prog(plane):
        x = reference.bf16(plane) if lowp else plane
        xi = x.astype(jnp.int32).reshape(runs, run, times)
        h = (xi >> 6).astype(jnp.int8)
        low = (xi & 63).astype(jnp.int8)

        def dot(a, b):
            return jax.lax.dot_general(
                a, b, (((1,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.int32)
        return dot(h, h), dot(h, low), dot(low, low), jnp.sum(xi, axis=1)
    return jax.jit(prog)


make = Series
