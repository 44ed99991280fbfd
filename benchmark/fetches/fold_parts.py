"""The answer of ``steps/tpch_q1.py`` as the caller holds it: the six sums
a group and the count a group, on the host.  The handle is the pair
``bolt.ops.segment_reduce(..., return_counts=True)`` gave (a tuple of bolt
arrays, one an aggregate, and the counts); all seven small results cross in
one batched transfer, which is also where the program is waited for."""

import numpy as np

ON_DEVICE = False


def take(handle):
    import jax
    sums, counts = handle
    got = jax.device_get([s.tojax() for s in sums] + [counts.tojax()])
    return {"sums": np.stack(got[:-1], axis=1), "counts": got[-1]}
