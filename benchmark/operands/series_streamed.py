"""``{"name": "series_streamed", "reads": 2, "k": 8}``: a voxels x time
recording WHOLE on the host, ``(planes, voxels, times)`` float32 keyed by
plane, larger than the chip's memory, handed to the program as a
``fromcallback`` source whose loader costs what page-cache-resident plane
files cost: zero-copy views of the host table.  A new lazy source per
request, as a caller would make one.

The data is ``operands/series.py``'s closed form (``planted`` and ``noise``:
eight planted temporal components, a baseline, lattice noise; every value an
integer exact in float32), sample ``s = plane * voxels + voxel``.  The table
is WHOLE (every row its own values: a shorter tile repeated would read a
slab served in another's place as correct).  It is made on the device a
plane at a time as the dense row-major bytes of the plane (the device holds
``f32[voxels, 64]`` with the voxels on the lanes and hands it down through a
transpose; the flat form is a straight copy) and copied down into the table
by a pool of threads.  The NumPy spelling of the same form
(``series.host_rows``) holds sampled slabs of the table to it in every
run's check.

What a request is counted as (``nbytes``, the numerator of
``streamed_scan_GBps``): ``reads`` reads of the table and ``samples x k``
float32 of scores written, as ``steps/pca.py``'s ``traffic`` counts a PCA
(the components are not known until every sample has been seen, so the
scores are a second pass).

The reference (``KIND = "resident"``: ``steps/pca.py``'s terminal asks it
for exact ``moments`` and for ``rows`` as it asks ``series.py``'s) takes
nothing from the program, its loader or the host table: it makes every
plane again from the closed form on the device and takes the exact integer
second moments of that (``series.SeriesReference``'s own arithmetic over a
stand-in for the resident array).
"""

import functools
import importlib.util
import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import lattice

LANES = 128


def _sibling(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_operands_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


series = _sibling("series")


def _frozen(spec):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in spec.items()))


@functools.lru_cache(maxsize=None)
def _generator(voxels, times, spec_items, dense):
    """``(a, b, plane) -> `` plane ``plane`` of the closed form, float32:
    ``dense`` as its row-major bytes ``(voxels * times / 128, 128)`` (what
    comes down to the host table as a straight copy), else ``(voxels,
    times)`` as the device lays a plane out (what the reference reads)."""
    import jax
    import jax.numpy as jnp
    spec = dict(spec_items)
    shape = (voxels * times // LANES, LANES) if dense else (voxels, times)

    def make(a, b, plane):
        iota = functools.partial(jax.lax.broadcasted_iota, jnp.uint32, shape)
        if dense:
            at = iota(0) * jnp.uint32(LANES) + iota(1)
            v, t = at // jnp.uint32(times), at % jnp.uint32(times)
        else:
            v, t = iota(0), iota(1)
        s = plane * jnp.uint32(voxels) + v
        x = series.planted(s, t, spec, b, jnp) + series.noise(
            s * jnp.uint32(times) + t, a, b, int(spec["noise_bits"]), jnp)
        return x.astype(jnp.float32)
    return jax.jit(make)


def host_table(shape, spec, seed, threads=None):
    """The whole ``(planes, voxels, times)`` float32 host array, made on
    the device a plane at a time and copied down by a pool of threads."""
    import jax.numpy as jnp
    planes, voxels, times = shape
    if voxels * times % LANES:
        raise ValueError("a plane is a whole number of 128-lane rows")
    make = _generator(voxels, times, _frozen(spec), True)
    a, b = (jnp.uint32(c) for c in lattice.constants(seed))
    out = np.empty(shape, np.float32)

    def fill(p):
        out[p] = np.asarray(make(a, b, jnp.uint32(p))).reshape(voxels, times)
    threads = threads or max(1, min(6, (os.cpu_count() or 2) - 1))
    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(fill, range(planes)))
    return out


class SeriesStreamed:
    def __init__(self, spec, config, mesh, seed):
        from bolt_tpu import engine
        if "stream_gram_slabs" not in engine.counters():
            # a program older than this configuration cannot fold a
            # streamed source into a Gram matrix: it would try to hold the
            # recording on the device whole.  It says so now, before the
            # host table is made
            raise SystemExit(
                "configuration %s needs a program that folds a streamed "
                "source into a Gram matrix slab by slab (engine counter "
                "stream_gram_slabs); this one has none" % config["name"])
        source = config["streamed_source"]
        self.shape = (int(config["planes"]),) + tuple(config["record_shape"])
        self.bits, self.seed, self.mesh = int(config["bits"]), seed, mesh
        self.spec = config["series"]
        if np.dtype(config["dtype"]) != np.float32 or len(self.shape) != 3 \
                or list(config["key_axes"]) != [0] \
                or tuple(source["shape"]) != self.shape:
            raise ValueError("series_streamed is (planes, voxels, times) "
                             "float32 keyed on axis 0")
        if int(np.prod(self.shape, dtype=np.int64)) > 1 << 32:
            raise ValueError("series index overflows 32 bits")
        series.check_spec(self.spec, self.shape[2], self.bits)
        t0 = time.perf_counter()
        self.table = host_table(self.shape, self.spec, seed)
        self.table.setflags(write=False)
        samples = self.shape[0] * self.shape[1]
        # what a request is counted as: see the module's docstring
        self.nbytes = int(spec["reads"]) * int(self.table.nbytes) \
            + samples * int(spec["k"]) * 4
        print("series table: %d planes, %.3f GB of host memory, made on the "
              "device and copied down in %.3f s; a request counts %.3f GB"
              % (self.shape[0], self.table.nbytes / 1e9,
                 time.perf_counter() - t0, self.nbytes / 1e9), flush=True)
        self.loader_seconds = []        # appended by the uploader threads
        self.loader_bytes = []
        # the control of tools/skipped_slab.py: a slab's first plane -> the
        # first plane of the slab served in its place.  Empty in every run
        self.serve_instead = {}

    def load(self, index):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.loader"):
            lo, hi, _ = index[0].indices(self.shape[0])
            at = self.serve_instead.get(lo, lo)
            block = self.table[(slice(at, at + hi - lo),) + tuple(index[1:])]
        self.loader_seconds.append(time.perf_counter() - t0)
        self.loader_bytes.append(block.nbytes)
        return block

    def operand(self):
        import bolt_tpu as bolt
        return bolt.fromcallback(self.load, self.shape, self.mesh,
                                 dtype=np.float32)

    def reference(self, man):
        return StreamedSeriesReference(
            man, ClosedForm(self.shape, self.spec, self.seed), self.shape,
            self.bits, self.seed, self.spec, self.table)


class ClosedForm:
    """What ``series.SeriesReference`` indexes as its resident array:
    ``data[p]`` is plane ``p`` on the device and ``data[p, lo:hi]`` rows of
    it, each made from the closed form when asked for."""

    def __init__(self, shape, spec, seed):
        import jax.numpy as jnp
        self.shape = shape
        self._make = _generator(shape[1], shape[2], _frozen(spec), False)
        self._ab = tuple(jnp.uint32(c) for c in lattice.constants(seed))

    def __getitem__(self, index):
        import jax.numpy as jnp
        plane, rest = (index, ()) if isinstance(index, int) \
            else (index[0], index[1:])
        return self._make(*self._ab, jnp.uint32(plane))[tuple(rest)]


class StreamedSeriesReference(series.SeriesReference):
    """``series.SeriesReference`` over the closed form in the resident
    array's place; the host table held to that form on sampled slabs."""

    def __init__(self, man, data, shape, bits, seed, spec, table):
        super().__init__(man, data, shape, bits, seed, spec)
        self.table = table

    def data_mismatches(self, rng, slabs=4, rows=65536):
        """Sampled slabs of the host table against the closed form by
        NumPy: is the data what it claims to be?"""
        planes, voxels, times = self.shape
        rows = min(rows, voxels)
        bad = 0
        for _ in range(slabs):
            p = int(rng.integers(planes))
            v = int(rng.integers(voxels - rows + 1))
            s = p * voxels + np.arange(v, v + rows, dtype=np.int64)
            bad += int((self.table[p, v:v + rows] != series.host_rows(
                s, times, self.spec, self.seed)).sum())
        return bad


make = SeriesStreamed
