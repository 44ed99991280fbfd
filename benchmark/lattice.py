"""Seeded data by a closed form of the absolute element index.

``value(i) = ((i * A + B) mod 2**32 >> (32 - bits)) - 2**(bits - 1)`` with
``A`` odd and ``B`` drawn from the seed: integers in ``[-2**(bits-1),
2**(bits-1))`` held as float32.  Any slab, device shard or reference slice
regenerates the same values from ``(seed, index)`` alone, so the program's
input, the reference's input and the check of both need no shared buffer.

The benchmark runs ``bits = 12``: every value is exact in float32 and NOT in
bfloat16 (8 significant bits), so a run in the next lower precision cannot
pass a comparison that float32 passes.  (``chip_smoke.py``'s lattice, which
this is taken from, holds ``[-8, 8)``: exact in bfloat16 too.)

One form, two spellings: NumPy for the host tile and the sampled records of
the check, ``jax.numpy`` for data made on the device.  ``tests/`` holds them
to each other.
"""

import numpy as np

_MASK = (1 << 32) - 1


def constants(seed):
    """``(A, B)`` for ``seed`` (any non-negative whole number): a splitmix
    round, so neighbouring seeds share no structure; ``A`` is odd."""
    z = (int(seed) + 0x9E3779B97F4A7C15) & ((1 << 64) - 1)
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & ((1 << 64) - 1)
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & ((1 << 64) - 1)
    z ^= z >> 31
    return (z & _MASK) | 1, (z >> 32) & _MASK


def strides(shape):
    """Row-major element strides of ``shape``, as Python ints."""
    out, acc = [], 1
    for s in reversed(shape):
        out.append(acc)
        acc *= int(s)
    if acc > 1 << 32:
        raise ValueError("lattice index of shape %r overflows 32 bits"
                         % (tuple(shape),))
    return tuple(reversed(out))


def host_block(lo, hi, rec_shape, seed, bits):
    """Records ``[lo, hi)`` of the seeded array as float32, by NumPy."""
    a, b = constants(seed)
    rec = int(np.prod(rec_shape, dtype=np.int64))
    x = np.arange(lo * rec, hi * rec, dtype=np.uint32)
    x *= np.uint32(a)
    x += np.uint32(b)
    x >>= np.uint32(32 - bits)
    out = x.astype(np.float32)
    out -= np.float32(1 << (bits - 1))
    return out.reshape((hi - lo,) + tuple(rec_shape))


def host_tile(records, rec_shape, seed, bits, threads=8):
    """Records ``[0, records)`` by :func:`host_block`, in ``threads`` parts
    at once (NumPy lets go of the interpreter lock inside each step): a
    2 GiB tile in about a second instead of several, on every run's
    set-up."""
    from concurrent.futures import ThreadPoolExecutor
    out = np.empty((records,) + tuple(rec_shape), np.float32)
    step = -(-records // threads)

    def fill(lo):
        hi = min(lo + step, records)
        out[lo:hi] = host_block(lo, hi, rec_shape, seed, bits)
    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(fill, range(0, records, step)))
    return out


def device_values(shape, a, b, bits, order=None):
    """Traced ``jax.numpy`` expression of the whole seeded array, float32.
    ``a`` and ``b`` are :func:`constants` as uint32 scalars, passed as
    arguments of the jitted caller so that one compiled program serves
    every seed.

    ``order`` permutes the axes of the RESULT: ``order=(1, 0, 2, 3)`` gives
    ``transpose(array, order)`` without the array ever existing, which is
    how a re-axis of 15 GB is checked with no second copy."""
    import jax
    import jax.numpy as jnp
    order = tuple(range(len(shape))) if order is None else tuple(order)
    st = strides(shape)
    out_shape = tuple(int(shape[ax]) for ax in order)
    flat = jnp.zeros(out_shape, jnp.uint32)
    for pos, ax in enumerate(order):
        flat = flat + (jax.lax.broadcasted_iota(jnp.uint32, out_shape, pos)
                       * jnp.uint32(st[ax]))
    x = flat * a + b
    x = x >> jnp.uint32(32 - bits)
    return x.astype(jnp.float32) - jnp.float32(1 << (bits - 1))
