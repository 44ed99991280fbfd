"""``{"name": "lineitem"}``: a share of TPC-H's LINEITEM, the seven columns
Q6 and Q1 name, one row a record: ``(rows, 7)`` float32 keyed by row, made
on the device in one jitted call from the seed and handed to ``bolt.array``
where it lies.

The closed form (``lineitem`` block of the configuration; dbgen's
marginals, each an independent draw from a 32-bit mixing hash of ``(row,
draw, seed)``).  Days count from 1992-01-01 = 0:

    order date   uniform over 1992-01-01 .. 1998-08-02      (0 .. 2405)
    l_shipdate   order date + 1 .. 121 days
    receipt      l_shipdate + 1 .. 30 days                  (not stored)
    l_quantity   1 .. 50
    l_discount   0 .. 10 (percent)      l_tax   0 .. 8 (percent)
    l_extendedprice  l_quantity x a retail price of 900.00 .. 2098.99,
                     in cents
    l_linestatus F (0) if shipped by 1995-06-17 (day 1263), else O (1)
    l_returnflag R (2) or A (0) evenly if received by 1995-06-17, else
                 N (1)

Column order: ``(l_shipdate, l_quantity, l_extendedprice, l_discount,
l_tax, l_returnflag, l_linestatus)``.  Every value is an integer below
2**24 held as float32: exact in float32 and not in bfloat16.

One form, two spellings (NumPy for sampled rows and the check of the data,
``jax.numpy`` for the device), held to each other by ``tests/``.  The
reference over the same data reads the device array in blocks of rows and
sums int32 limbs that cannot overflow, finished in Python integers: exact
(a row's charge reaches 1.1e11 and a group's sum 5e18).
"""

import functools

import numpy as np

import lattice
import reference

_M1, _M2 = 0x7FEB352D, 0x846CA68B         # lowbias32 (Wellons), a bijection

DATE, QTY, PRICE, DISC, TAX, FLAG, STATUS = range(7)
COLUMNS = ("l_shipdate", "l_quantity", "l_extendedprice", "l_discount",
           "l_tax", "l_returnflag", "l_linestatus")
DRAWS = 8                                 # hash draws a row

BLOCK = 1 << 20      # rows a block of the reference: an 11-bit limb summed
                     # over it stays below 2**31


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


def columns(row, spec, salt, xp):
    """The seven columns of rows ``row`` (uint32, any shape) as int32
    arrays of that shape, in column order."""
    u, i = xp.uint32, xp.int32

    def draw(d, top):
        return uniform(mix(row * u(DRAWS) + u(d) + u(salt), xp), top,
                       xp).astype(i)
    today = i(int(spec["current_day"]))
    ship = draw(0, int(spec["order_days"]) - 1) + draw(
        1, int(spec["ship_after"]) - 1) + i(1)
    receipt = ship + draw(2, int(spec["receipt_after"]) - 1) + i(1)
    qty = draw(3, int(spec["quantity"]) - 1) + i(1)
    lo, hi = (int(c) for c in spec["retail_cents"])
    retail = i(lo) + draw(4, (hi - lo) // 100) * i(100) + draw(5, 99)
    disc = draw(6, int(spec["discount"]))
    tax = draw(7, int(spec["tax"]))
    coin = (mix(row * u(DRAWS) + u(2) + u(salt), xp) & u(1)).astype(i)
    flag = xp.where(receipt <= today, i(2) * coin, i(1))
    status = (ship > today).astype(i)
    return ship, qty, qty * retail, disc, tax, flag, status


def check_spec(spec):
    lo, hi = (int(c) for c in spec["retail_cents"])
    if (hi - lo) % 100 != 99 or (hi - lo) // 100 >= 1 << 15:
        raise ValueError("lineitem: retail_cents spans whole dollars")
    top = int(spec["quantity"]) * hi
    if top >= 1 << 24:
        raise ValueError("lineitem: a price of %d is not exact in float32"
                         % top)
    if max(int(spec["order_days"]), int(spec["ship_after"]),
           int(spec["receipt_after"]), int(spec["quantity"])) > 1 << 15:
        raise ValueError("lineitem: a draw of more than 2**15 values")


def host_rows(row, spec, seed):
    """Rows ``row`` (any integer array) as float32 ``(.., 7)``."""
    _, b = lattice.constants(seed)
    with np.errstate(over="ignore"):
        cols = columns(np.asarray(row).astype(np.uint32), spec, b, np)
    return np.stack(cols, axis=-1).astype(np.float32)


GENERATE = 1 << 22   # rows made at a time on the device


def device_values(rows, spec, b):
    """Traced ``jax.numpy`` expression of the whole ``(rows, 7)`` table,
    float32; ``b`` is ``lattice.constants``' second word as a uint32
    scalar (an argument of the jitted caller).  Made ``GENERATE`` rows at
    a time and written into the table in place, so that what the hashes
    need beside the table is a block's worth (as one expression over the
    whole table XLA keeps 8.7 GB of them beside its 9.6 GB, which does
    not fit: compiled for the v5e, PR 30); the last block starts where a
    whole one still fits and makes some rows a second time, the same.
    Every element takes its own column's value by a chain of selects, so
    no column is ever an array of its own."""
    import jax
    import jax.numpy as jnp
    block = min(rows, GENERATE)
    shape = (block, len(COLUMNS))

    def make(i, table):
        start = jnp.minimum(i * block, rows - block)
        row = jax.lax.broadcasted_iota(jnp.uint32, shape, 0) \
            + start.astype(jnp.uint32)
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        cols = columns(row, spec, b, jnp)
        out = cols[-1]
        for c in range(len(cols) - 2, -1, -1):
            out = jnp.where(col == c, cols[c], out)
        return jax.lax.dynamic_update_slice(
            table, out.astype(jnp.float32), (start, 0))
    return jax.lax.fori_loop(0, -(-rows // block), make,
                             jnp.zeros((rows, len(COLUMNS)), jnp.float32))


class Lineitem:
    loader_seconds = loader_bytes = ()      # no loader: nothing to tally

    def __init__(self, spec, config, mesh, seed):
        import jax
        import jax.numpy as jnp
        import bolt_tpu as bolt
        from bolt_tpu import engine
        if "filters_fused" not in engine.counters():
            # a program older than this configuration brings the whole
            # mask of a filter of this size to the host and gathers the
            # survivors into a second table, which does not fit; it says
            # so now instead of after the set-up
            raise SystemExit(
                "configuration %s needs a program that folds a deferred "
                "filter into the terminal that reads it (engine counter "
                "filters_fused); this one has none" % config["name"])
        self.shape = (int(config["rows"]),) + tuple(config["record_shape"])
        self.seed, self.spec = seed, config["lineitem"]
        if np.dtype(config["dtype"]) != np.float32 \
                or self.shape[1:] != (len(COLUMNS),) \
                or list(config["key_axes"]) != [0] \
                or list(config["columns"]) != list(COLUMNS):
            raise ValueError("lineitem is (rows, 7) float32 keyed on axis 0 "
                             "with the columns %s" % (COLUMNS,))
        if self.shape[0] * DRAWS >= 1 << 32:
            raise ValueError("lineitem row index overflows 32 bits")
        check_spec(self.spec)
        P = jax.sharding.PartitionSpec
        sharding = jax.sharding.NamedSharding(mesh, P(mesh.axis_names[0]))
        make = jax.jit(
            lambda b: device_values(self.shape[0], self.spec, b),
            out_shardings=sharding)
        _, b = lattice.constants(seed)
        self.data = make(jnp.uint32(b))
        self.data.block_until_ready()
        self.array = bolt.array(self.data, context=mesh, axis=(0,))
        self.nbytes = int(np.prod(self.shape, dtype=np.int64)) * 4

    def operand(self):
        return self.array

    def reference(self, man):
        return LineitemReference(man, self.data, self.shape, self.seed,
                                 self.spec)


class LineitemReference(reference.ResidentReference):
    """Answers over the device array of the seeded table: beside what
    every resident reference gives, exact sums of integer terms over
    selected rows (``totals``), which the two queries' terminals
    (``steps/tpch_q6.py``, ``steps/tpch_q1.py``) are made of."""

    def __init__(self, man, data, shape, seed, spec):
        super().__init__(man, data, shape, 24, seed)
        self.spec = spec

    def data_mismatches(self, rng, slabs=4, rows=65536):
        """Sampled slabs of the device array against the closed form by
        NumPy: is the data what it claims to be?"""
        total = self.shape[0]
        rows = min(rows, total)
        bad = 0
        for _ in range(slabs):
            r = int(rng.integers(total - rows + 1))
            held = np.asarray(self.data[r:r + rows])
            bad += int((held != host_rows(
                np.arange(r, r + rows, dtype=np.int64), self.spec,
                self.seed)).sum())
        return bad

    def totals(self, terms, lowp=False):
        """``terms(cols) -> (select, values)`` over a block of rows
        (``cols``: the seven columns as arrays of one shape; ``select``:
        bool arrays, ``values``: non-negative integer arrays below 2**31,
        both as flat lists): for every pair the sum of ``value`` over the
        rows of ``select``, as ``[select][value]`` Python integers,
        exact.  ``lowp``: float32 sums of the same terms, for a control
        that rounds what it holds (``terms`` then takes and gives float
        arrays)."""
        n = self.shape[0]
        prog = _block_program(terms, min(BLOCK, n), bool(lowp))
        totals = None
        for lo in range(0, n, BLOCK):
            # the last block starts where a whole one still fits; the
            # rows it shares with the one before are masked out
            start = max(0, min(lo, n - BLOCK))
            part = np.asarray(prog(self.data, np.int32(start),
                                   np.int32(lo - start)))
            if lowp:
                part = part.astype(np.float64)
                totals = part if totals is None else totals + part
            else:
                limbs = [[sum(int(v) << (11 * k) for k, v in enumerate(val))
                          for val in sel] for sel in part]
                totals = limbs if totals is None else [
                    [a + b for a, b in zip(ra, rb)]
                    for ra, rb in zip(totals, limbs)]
        return totals


@functools.lru_cache(maxsize=None)
def _block_program(terms, block, lowp):
    """One block of :meth:`LineitemReference.totals`: ``(data, start,
    skip) -> [select][value][limb]`` int32 (three 11-bit limbs a value,
    each summed over at most 2**20 rows: below 2**31), or ``[select]
    [value]`` float32 for ``lowp``."""
    import jax
    import jax.numpy as jnp

    def run(data, start, skip):
        rows = jax.lax.dynamic_slice(data, (start, 0),
                                     (block, data.shape[1]))
        fresh = jnp.arange(block, dtype=jnp.int32) >= skip
        if lowp:
            cols = [rows[:, c] for c in range(data.shape[1])]
            select, values = terms(cols)
            return jnp.stack([jnp.stack([jnp.sum(jnp.where(
                s & fresh, v, jnp.float32(0))) for v in values])
                for s in select])
        cols = [rows[:, c].astype(jnp.int32) for c in range(data.shape[1])]
        select, values = terms(cols)
        out = []
        for s in select:
            keep = s & fresh
            out.append(jnp.stack([jnp.stack([
                jnp.sum(jnp.where(keep, (v >> (11 * k)) & 0x7FF, 0))
                for k in range(3)]) for v in values]))
        return jnp.stack(out)
    return jax.jit(run)


make = Lineitem
