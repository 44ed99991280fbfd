"""What the linear-algebra terminals (``steps/chunk_svd.py``,
``steps/pca.py``) share on the reference's side: float64 spectra of exact
Gram matrices by NumPy, bfloat16 rounding of a host array, the block plan
of upstream Bolt's megabyte budget, and the reduction of several readings,
each with a limit of its own, to the one number a request kind is held to.
Imports nothing of the program."""

import numpy as np


def eigh_desc(gram):
    """``(eigenvalues, eigenvectors)`` of symmetric float64 ``gram``
    (batched over leading axes), descending, negative values clamped."""
    w, v = np.linalg.eigh(np.asarray(gram, np.float64))
    return np.maximum(w[..., ::-1], 0.0), v[..., ::-1]


def bf16(x):
    """``x`` rounded to the nearest bfloat16 (ties to even), as float64."""
    bits = np.ascontiguousarray(x, np.float32).view(np.uint32)
    rounded = (bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16))
                                           & np.uint32(1)))
    return (rounded & np.uint32(0xFFFF0000)).view(np.float32).astype(
        np.float64)


def block_rows(record_shape, itemsize, size, axis):
    """Rows of the blocks that upstream Bolt's ``chunk(size=<MB>,
    axis=(a,))`` cuts value axis ``a`` of one record into: the axis is
    halved (rounding up) until a block holds at most ``size`` megabytes
    of 10**6 bytes (``bolt/spark/chunk.py``, ``getplan``)."""
    plan = [int(s) for s in record_shape]
    while int(np.prod(plan, dtype=np.int64)) * itemsize > float(size) * 1e6 \
            and plan[axis] > 1:
        plan[axis] = -(-plan[axis] // 2)
    return plan[axis]


def worst(parts, limits):
    """``max(reading / limit)`` over the named readings of one answer: at
    most 1 exactly when every reading is within its own limit, so a kind
    whose terminal compares several things carries ``"limit": 1``.  A
    reading with no limit is held to nothing (``tools/parts.py`` prints
    every reading by name)."""
    number = 0.0
    for name, reading in parts.items():
        if not np.isfinite(reading):
            return float("inf")
        if name in limits:
            number = max(number, float(reading) / float(limits[name]))
    return number
