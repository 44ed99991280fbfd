"""The answer of ``steps/pca.py`` as the caller holds it: components,
singular values and mean are host arrays already (``bolt.ops.pca`` fetched
them); the scores, too large to bring back, are forced resident and
complete (``.cache().tojax()`` then ``block_until_ready``, as fetch
``ready`` does) and dropped with the handle, before the next request.

For the check, which looks at ``patch_rows`` rows of the scores at each of
the request's ``patches`` once the window has closed, those rows are cut
out on the device before the scores go: one small program of ``jax.numpy``
alone (nothing of the system under test), whose 12 KB stay on the device
until the check asks for them.  No row crosses to the host inside the
window.  The cut is waited for: until the host has seen it finish the
runtime keeps the scores it reads, and a ``pca_k8`` request that follows
another at once (the driver's warm-up has one such pair where the seed's
order starts with this kind) then held 2 x 1.34 GB of scores beside the
matrix, 13.43 GB for 12.08, in five runs of fourteen (PERF.md, PR 26)."""

import functools

ON_DEVICE = False


@functools.lru_cache(maxsize=None)
def _cut(patches, rows):
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda scores: jnp.concatenate(
        [scores[p, v:v + rows] for p, v in patches]))


def take(handle):
    scores = handle["scores"].cache().tojax()
    scores.block_until_ready()
    rows = _cut(handle["patches"], handle["patch_rows"])(scores)
    rows.block_until_ready()
    return {"components": handle["components"],
            "singular_values": handle["singular_values"],
            "mean": handle["mean"], "rows": rows}
