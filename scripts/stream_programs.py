#!/usr/bin/env python3
"""Which programs the streamed doors compile, as names two trees can be
compared by: every streamed terminal, filter, codec, swap and collect
through the PUBLIC calls at toy sizes on the CPU (eight devices and one),
with JAX's persistent compile cache on.  Prints a SHA-256 over every
result, then the cache's file names: each is the key of one lowered
program, so two trees that print the same list lower the same programs,
and a refactor of ``bolt_tpu/stream.py`` that moves one is seen here
before a chip is asked (PR 57: 101 names, equal at the parent and the
change).

    for tree in <parent checkout> <change checkout>; do
        rm -rf /tmp/cc; mkdir /tmp/cc
        (cd /tmp && JAX_PLATFORMS=cpu JAX_COMPILATION_CACHE_DIR=/tmp/cc \\
            PYTHONPATH=$tree python $tree/scripts/stream_programs.py \\
            2>/dev/null > /tmp/programs.$(basename $tree))
    done; diff /tmp/programs.*

The cache's directory is part of every key: give both trees the SAME
path, one after the other.  A CPU run: it says what is compiled, never
how fast.
"""
import hashlib
import os

import numpy as np


def plus(v):
    return v + 1


def keep(r):
    return r[0] > 0


def label(r):
    return (r[1] > 0).astype(np.int32) * 2 + (r[2] > 0).astype(np.int32)


def terms(r):
    return (r[3], r[4] * r[5])


def glabel(r):
    return (r[0, 0] > 1).astype(np.int32)


def main():
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    import jax
    jax.config.update("jax_enable_x64", True)
    import bolt_tpu as bolt
    from bolt_tpu import engine, stream
    engine.persistent_cache()

    def src(a, m, chunks, **kw):
        return bolt.fromcallback(lambda i: a[tuple(i)], a.shape, m,
                                 dtype=a.dtype, chunks=chunks, **kw)

    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("k",))
    one = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("k",))
    rng = np.random.default_rng(0)
    x = rng.integers(-50, 50, size=(96, 4, 6)).astype(np.float32)
    t = rng.integers(-50, 50, size=(1000, 7)).astype(np.float32)
    p = rng.integers(-5, 5, size=(8, 256, 16)).astype(np.float32)

    out = []
    for m in (mesh, one):
        s = src(x, m, 8).map(plus)
        out += [s.sum().toarray(), s.mean().toarray(), s.var(ddof=1).toarray(),
                s.std().toarray(), s.reduce(np.maximum).toarray()]
        a, b, c, d = bolt.compute(s.sum(), s.var(), s.min(), s.ptp())
        out += [a.toarray(), b.toarray(), c.toarray(), d.toarray()]
        f = src(x, m, 8).filter(lambda r: r.sum() > 0)
        out += [f.sum().toarray(), f.mean().toarray()]
        for op in ("sum", "mean", "max"):
            g, n = bolt.ops.segment_reduce(src(x, m, 8), glabel, 2, op=op)
            out += [g.toarray(), n.toarray()]
        out.append(bolt.ops.cov(src(x.reshape(96, 24), m, 8)))
        with stream.codec("int8"):
            out.append(src(x, m, 8).sum().toarray())
        with stream.codec("bf16"):
            out.append(src(x, m, 8).map(plus).mean().toarray())
        out.append(s.swap((0,), (0,)).toarray())
        out.append(s.toarray())
    tq = src(t, one, 300).filter(keep)
    g, n = bolt.ops.segment_reduce(tq, label, 4, op="mean", value=terms)
    out += [np.asarray(jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(lambda v: v.toarray(), g))[0]), n.toarray()]
    out.append(src(t, one, 300).filter(keep).map(
        lambda r: r[1] * r[2]).sum().toarray())
    res = bolt.ops.pca(src(p, one, 2), k=3, axis=(0, 1))
    out.append(np.asarray(res[1]))
    h = hashlib.sha256()
    for o in out:
        h.update(np.ascontiguousarray(np.asarray(o)).tobytes())
    print("RESULTS", h.hexdigest())
    d = engine.persistent_cache_dir()
    names = sorted(n for n in os.listdir(d)
                   if not n.startswith("bolt_exported"))
    print("PROGRAMS", len(names))
    for n in names:
        print(n)


if __name__ == "__main__":
    main()
