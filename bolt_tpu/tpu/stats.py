"""Explicit-collective streaming statistics for the TPU backend.

Reference: the ``rdd.aggregate(StatCounter(), merge, mergeStats)`` path
behind ``BoltArraySpark.stats/_stat`` (SURVEY §3.4): per-partition Welford
accumulation in Python workers, tree-combined across the cluster.  Here each
mesh shard computes its local moments on-device and the Chan combine is a
handful of ``psum``/``pmax``/``pmin`` collectives over the ICI — one
compiled ``shard_map`` program, no host involvement until the final scalar
fetch.

This module is the framework's canonical example of the explicit-collective
(``shard_map``) style; the everyday ``mean()/var()/std()`` methods use plain
``jnp`` reductions and let GSPMD insert the same collectives automatically.
Their ``var``/``std`` of real floating data read HBM ONCE: one pass of
moments shifted by a pilot mean (``tpu/moments.py``, error scaling with
``var + (mean - pilot)**2``), one round of ``psum``s.  The moments HERE are
centred on the finished mean (``m2``, what the Chan combine needs): one pass
where the ``fused_welford`` kernel serves the geometry, two on the jnp path.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from bolt_tpu.parallel.sharding import key_spec, spec_names
from bolt_tpu.statcounter import StatCounter
from bolt_tpu.tpu.array import _cached_jit
from bolt_tpu.utils import inshape, prod, tupleize


def _shard_moments(x, axes):
    """Per-shard ``(mu, m2, min, max)`` over ``axes`` (traced inside the
    shard_map body).  When the reduced axes are the leading contiguous
    ones — the ``stats()`` default — and the shard geometry tiles cleanly,
    the single-HBM-pass pallas kernel computes them (XLA cannot fuse the
    mean with the second moment CENTRED on it, so the jnp path below reads
    HBM twice; ``var()/std()`` escape that by centring on a pilot instead,
    ``tpu/moments.py``, which gives no ``m2`` about the true mean).
    Everything else takes the jnp path — identical semantics,
    allclose-level numerics.  A geometry the plan admits and Mosaic
    refuses is a bug to see: the compile error propagates."""
    if (axes == tuple(range(len(axes))) and len(axes) < x.ndim
            and jnp.issubdtype(x.dtype, jnp.floating)):
        from bolt_tpu.ops.kernels import fused_welford
        r = fused_welford(x)
        if r is not None:
            mu, m2, mn, mx = r
            if len(axes) > 1:
                # kernel reduced axis 0; Chan-combine the remaining
                # leading axes of the (small) moment arrays — groups of
                # equal count x.shape[0], so the combine is exact algebra
                red = tuple(range(len(axes) - 1))
                cnt = jnp.asarray(x.shape[0], mu.dtype)
                g = jnp.mean(mu, axis=red, keepdims=True)
                m2 = (jnp.sum(m2, axis=red)
                      + cnt * jnp.sum((mu - g) ** 2, axis=red))
                mu = g.reshape(x.shape[len(axes):])
                mn = jnp.min(mn, axis=red)
                mx = jnp.max(mx, axis=red)
            return mu, m2, mn, mx
    mu = jnp.mean(x, axis=axes)
    m2 = jnp.sum((x - jnp.mean(x, axis=axes, keepdims=True)) ** 2, axis=axes)
    return mu, m2, jnp.min(x, axis=axes), jnp.max(x, axis=axes)


def welford(barray, requested=("mean", "var", "std", "min", "max"),
            axis=None):
    """Single-pass count/mean/var/std/min/max over any axes, returned as a
    :class:`~bolt_tpu.statcounter.StatCounter` holding value-shaped moments.

    ``axis=None`` reduces over all key axes (the reference's ``stats()``).
    Any subset of key AND value axes is allowed — matching ``mean()`` /
    ``_stat`` (VERDICT r1 weak-6): value axes are whole on every shard, so
    they reduce locally and only mesh-mapped key dims join the collectives.
    Remaining axes stay as leading dimensions of each moment.
    """
    split = barray.split
    if axis is None:
        axes = tuple(range(split))
    else:
        axes = tuple(sorted(tupleize(axis)))
        inshape(barray.shape, axes)
    if len(axes) == 0:
        raise ValueError("at least one axis is required")

    mesh = barray.mesh
    shape = barray.shape
    spec = tuple(key_spec(mesh, shape, split))
    # mesh axes assigned to the reduced dims participate in the collectives
    # (a spec entry may carry SEVERAL mesh axes — flatten for psum)
    reduce_names = tuple(n for a in axes for n in spec_names(spec[a]))
    out_spec = P(*(spec[i] for i in range(len(shape)) if i not in axes))
    n_total = prod(tuple(shape[a] for a in axes))

    key = ("welford", shape, str(barray.dtype), axes, spec, mesh)

    def build():
        def local_moments(x):
            # x is the per-device shard; reduced dims may be divided across
            # the mesh, so this count is the LOCAL n.
            n_local = prod(tuple(x.shape[a] for a in axes))
            mu, m2, mn, mx = _shard_moments(x, axes)
            if reduce_names:
                n_loc = jnp.asarray(n_local, dtype=mu.dtype)
                n_tot = jax.lax.psum(n_loc, reduce_names)
                grand = jax.lax.psum(mu * n_loc, reduce_names) / n_tot
                # Chan et al.: total M2 = sum M2_i + sum n_i (mu_i - grand)^2
                m2 = jax.lax.psum(m2 + n_loc * (mu - grand) ** 2, reduce_names)
                mu = grand
                mx = jax.lax.pmax(mx, reduce_names)
                mn = jax.lax.pmin(mn, reduce_names)
            return mu, m2, mn, mx

        # check_vma=False: the pallas kernel's out_shape carries no vma
        # annotation, and every cross-device combine here is an explicit
        # psum/pmax/pmin — there is nothing for the varying-axes checker
        # to catch on this function
        from bolt_tpu._compat import shard_map
        return jax.jit(shard_map(
            local_moments, mesh=mesh, in_specs=P(*spec),
            out_specs=(out_spec, out_spec, out_spec, out_spec),
            check_vma=False))

    # shares the bounded LRU executable cache with every other op family
    out = _cached_jit(key, build)(barray._data)
    mu, m2, mn, mx = (np.asarray(jax.device_get(o)) for o in out)
    return StatCounter.from_moments(n_total, mu, m2, minValue=mn, maxValue=mx,
                                    stats=requested)
