"""``{"name": "lineitem_streamed"}``: TPC-H's LINEITEM WHOLE on the host,
the seven columns Q6 and Q1 name, one row a record: ``(rows, 7)`` float32
keyed by row, larger than the chip's memory, handed to the program as a
``fromcallback`` source whose loader costs what a page-cache-resident
memmap of row files costs: zero-copy views of the host table.  A new lazy
source per pass, as a caller would make one.

The data is ``operands/lineitem.py``'s closed form (``columns``: dbgen's
marginals from a 32-bit mixing hash of ``(row, draw, seed)``).  That hash
mixes ``row * 8 + draw`` in 32 bits, which a table of more than 2**29 rows
overflows, so rows are taken in EPOCHS of 2**29: an epoch's salt is the
seed's plus the epoch times ``0x9E3779B9``.  Epoch 0 is ``lineitem.py``'s
table row for row; the rows past it are new draws, not repeats.

The table is WHOLE (every row its own values: a shorter tile repeated would
read a slab served in another's place as correct).  It is made on the
device, ``GENERATE`` rows at a time, as the dense row-major bytes of the
block (a ``(rows, 7)`` device array pads seven columns to eight sublanes and
comes down through a transpose; the flat form is a straight copy), and
copied down into the table by a pool of threads.  The NumPy spelling of the
same form (:func:`host_rows`) holds sampled slabs of the table to it in
every run's check.

The reference (``KIND = "resident"``: the terminals of ``steps/tpch_q6.py``
and ``steps/tpch_q1.py`` ask it for exact ``totals`` as they ask
``lineitem.py``'s) takes nothing from the program, its loader or the host
table: it makes the rows again from the closed form on the device, a block
at a time, as int32 columns, and sums limbs that cannot overflow, finished
in Python integers.
"""

import functools
import importlib.util
import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import lattice
import reference


def _sibling(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_operands_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


lineitem = _sibling("lineitem")
COLUMNS = lineitem.COLUMNS

EPOCH_BITS = 29                 # rows an epoch: row * 8 + draw < 2**32
GOLDEN = 0x9E3779B9             # 2**32 / phi: an epoch's step of the salt
GENERATE = 1 << 22              # rows made at a time on the device
BLOCK = 1 << 20                 # rows a block of the reference: an 11-bit
                                # limb summed over it stays below 2**31
LANES = 128


def epoch_columns(row, spec, b, xp):
    """The seven columns of rows ``row`` (uint32, any shape, below 2**32)
    as int32 arrays: ``lineitem.columns`` of the row within its epoch,
    under the epoch's salt."""
    u = xp.uint32
    low = row & u((1 << EPOCH_BITS) - 1)
    salt = u(b) + (row >> u(EPOCH_BITS)) * u(GOLDEN)
    return lineitem.columns(low, spec, salt, xp)


def host_rows(row, spec, seed):
    """Rows ``row`` (any integer array) as float32 ``(.., 7)``, by NumPy."""
    _, b = lattice.constants(seed)
    with np.errstate(over="ignore"):
        cols = epoch_columns(np.asarray(row).astype(np.uint32), spec, b, np)
    return np.stack(cols, axis=-1).astype(np.float32)


@functools.lru_cache(maxsize=None)
def _generator(block, spec_items):
    """``(b, start) -> (ceil(block * 7 / 128), 128)`` float32: rows
    ``[start, start + block)`` as their row-major bytes, padded to whole
    lanes.  Every element takes its own column's value by a chain of
    selects, so no column is an array of its own."""
    import jax
    import jax.numpy as jnp
    spec = dict(spec_items)
    c = len(COLUMNS)
    tiles = -(-block * c // LANES)

    def make(b, start):
        at = jax.lax.broadcasted_iota(jnp.uint32, (tiles, LANES), 0) \
            * jnp.uint32(LANES) \
            + jax.lax.broadcasted_iota(jnp.uint32, (tiles, LANES), 1)
        col = (at % jnp.uint32(c)).astype(jnp.int32)
        cols = epoch_columns(at // jnp.uint32(c) + start, spec, b, jnp)
        out = cols[-1]
        for k in range(c - 2, -1, -1):
            out = jnp.where(col == k, cols[k], out)
        return out.astype(jnp.float32)
    return jax.jit(make)


def host_table(rows, spec, seed, threads=None):
    """Rows ``[0, rows)`` as one float32 ``(rows, 7)`` host array, made on
    the device ``GENERATE`` rows at a time and copied down by a pool of
    threads; the last block starts where a whole one still fits and makes
    some rows a second time, the same."""
    import jax.numpy as jnp
    c = len(COLUMNS)
    block = min(rows, GENERATE)
    make = _generator(block, _frozen(spec))
    _, b = lattice.constants(seed)
    b = jnp.uint32(b)
    out = np.empty((rows, c), np.float32)
    flat = out.reshape(-1)

    def fill(lo):
        start = min(lo, rows - block)
        part = np.asarray(make(b, jnp.uint32(start)))
        flat[start * c:(start + block) * c] = part.reshape(-1)[:block * c]
    threads = threads or max(1, min(6, (os.cpu_count() or 2) - 1))
    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(fill, range(0, rows, block)))
    return out


def _frozen(spec):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in spec.items()))


class LineitemStreamed:
    def __init__(self, spec, config, mesh, seed):
        from bolt_tpu import engine
        if "stream_group_slabs" not in engine.counters():
            # a program older than this configuration cannot group a
            # streamed source: it would try to hold the table on the
            # device whole.  It says so now, before the host table is made
            raise SystemExit(
                "configuration %s needs a program that folds a streamed "
                "source into groups slab by slab (engine counter "
                "stream_group_slabs); this one has none" % config["name"])
        source = config["streamed_source"]
        self.shape = (int(config["rows"]),) + tuple(config["record_shape"])
        self.seed, self.spec, self.mesh = seed, config["lineitem"], mesh
        if np.dtype(config["dtype"]) != np.float32 \
                or self.shape[1:] != (len(COLUMNS),) \
                or list(config["key_axes"]) != [0] \
                or list(config["columns"]) != list(COLUMNS) \
                or tuple(source["shape"]) != self.shape:
            raise ValueError("lineitem_streamed is (rows, 7) float32 keyed "
                             "on axis 0 with the columns %s" % (COLUMNS,))
        if self.shape[0] >= 1 << 32:
            raise ValueError("lineitem_streamed row index overflows 32 bits")
        lineitem.check_spec(self.spec)
        t0 = time.perf_counter()
        self.table = host_table(self.shape[0], self.spec, seed)
        self.table.setflags(write=False)
        self.nbytes = int(self.table.nbytes)
        print("lineitem table: %d rows, %.3f GB of host memory, made on the "
              "device and copied down in %.3f s"
              % (self.shape[0], self.nbytes / 1e9,
                 time.perf_counter() - t0), flush=True)
        self.loader_seconds = []        # appended by the uploader threads
        self.loader_bytes = []
        # the control of tools/skipped_slab.py: a slab's first row -> the
        # first row of the slab served in its place.  Empty in every run
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
        return StreamedLineitemReference(man, self.table, self.shape,
                                         self.seed, self.spec)


class StreamedLineitemReference(reference.Reference):
    """The table's closed form and the questions asked of it: exact sums of
    integer terms over selected rows (``totals``), which the two queries'
    terminals are made of, from the closed form alone; and the host table
    held to that form on sampled slabs."""

    KIND = "resident"        # the terminals answer as over lineitem.py's

    def __init__(self, man, table, shape, seed, spec):
        super().__init__(man, shape, 24, seed)
        self.table, self.spec = table, spec
        self._totals = {}

    def data_mismatches(self, rng, slabs=4, rows=65536):
        """Sampled slabs of the host table against the closed form by
        NumPy: is the data what it claims to be?"""
        total = self.shape[0]
        rows = min(rows, total)
        bad = 0
        for _ in range(slabs):
            r = int(rng.integers(total - rows + 1))
            bad += int((self.table[r:r + rows] != host_rows(
                np.arange(r, r + rows, dtype=np.int64), self.spec,
                self.seed)).sum())
        return bad

    def totals(self, terms, lowp=False):
        """``lineitem.LineitemReference.totals`` over the closed form:
        for every pair of ``terms(cols) -> (select, values)`` the sum of
        ``value`` over the rows of ``select``, as ``[select][value]``
        Python integers, exact (``lowp``: float32 sums of the same terms
        over float32 columns, for a control that rounds what it holds).
        Asked once a distinct question; kept."""
        key = (terms, bool(lowp))
        if key not in self._totals:
            self._totals[key] = self._sum_blocks(terms, bool(lowp))
        return self._totals[key]

    def _sum_blocks(self, terms, lowp):
        import jax.numpy as jnp
        n = self.shape[0]
        block = min(BLOCK, n)
        prog = _block_program(terms, block, lowp, _frozen(self.spec))
        _, b = lattice.constants(self.seed)
        b = jnp.uint32(b)
        parts = []
        for lo in range(0, n, block):
            # the last block starts where a whole one still fits; the
            # rows it shares with the one before are masked out
            start = min(lo, n - block)
            parts.append(prog(b, jnp.uint32(start), jnp.int32(lo - start)))
        totals = None
        for part in parts:
            part = np.asarray(part)
            if lowp:
                part = part.astype(np.float64)
                totals = part if totals is None else totals + part
            else:
                limbs = [[sum(int(v) << (11 * k) for k, v in enumerate(val))
                          for val in sel] for sel in part]
                totals = limbs if totals is None else [
                    [x + y for x, y in zip(ra, rb)]
                    for ra, rb in zip(totals, limbs)]
        return totals


@functools.lru_cache(maxsize=None)
def _block_program(terms, block, lowp, spec_items):
    """One block of :meth:`StreamedLineitemReference.totals`: ``(b, start,
    skip) -> [select][value][limb]`` int32 (three 11-bit limbs a value,
    each summed over at most 2**20 rows: below 2**31), or ``[select]
    [value]`` float32 for ``lowp``; the rows from the closed form."""
    import jax
    import jax.numpy as jnp
    spec = dict(spec_items)

    def run(b, start, skip):
        at = jnp.arange(block, dtype=jnp.uint32)
        cols = list(epoch_columns(at + start, spec, b, jnp))
        fresh = at.astype(jnp.int32) >= skip
        if lowp:
            select, values = terms([c.astype(jnp.float32) for c in cols])
            return jnp.stack([jnp.stack([jnp.sum(jnp.where(
                s & fresh, v, jnp.float32(0))) for v in values])
                for s in select])
        select, values = terms(cols)
        out = []
        for s in select:
            keep = s & fresh
            out.append(jnp.stack([jnp.stack([
                jnp.sum(jnp.where(keep, (v >> (11 * k)) & 0x7FF, 0))
                for k in range(3)]) for v in values]))
        return jnp.stack(out)
    return jax.jit(run)


make = LineitemStreamed
