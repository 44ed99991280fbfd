#!/usr/bin/env python3
"""What ``ops/register.py``'s spellings rest on: a slab of 512 x 512 frames
shifted frame by frame by its own integer displacement (edge fill), and the
cross-correlation arg-max of the same slab, each spelled several ways, one
jitted program a spelling, median wall of ``--calls`` calls after a warm-up,
every shift compared bit for bit with the two clamped takes.

    python3 scripts/shift_probe.py [--frames 128] [--calls 7]

Refuses the CPU (a CPU time says nothing about the chip); about a minute on
one chip cold.
"""

import argparse
import json
import statistics
import sys
import time

import numpy as np


def spellings(h, w):
    import jax
    import jax.numpy as jnp

    def takes(f, d):
        rows = jnp.clip(jnp.arange(h, dtype=jnp.int32) + d[0], 0, h - 1)
        cols = jnp.clip(jnp.arange(w, dtype=jnp.int32) + d[1], 0, w - 1)
        return jnp.take(jnp.take(f, rows, axis=0), cols, axis=1)

    def rows_only(f, d):
        rows = jnp.clip(jnp.arange(h, dtype=jnp.int32) + d[0], 0, h - 1)
        cols = jnp.clip(jnp.arange(w, dtype=jnp.int32) + d[1], 0, w - 1)
        return jnp.take(jnp.take(f, rows, axis=0).T, cols, axis=0).T

    def edge_slice(f, d):
        top = jnp.broadcast_to(f[:1], (h, w))
        bot = jnp.broadcast_to(f[-1:], (h, w))
        f = jax.lax.dynamic_slice(jnp.concatenate([top, f, bot], 0),
                                  (h + jnp.clip(d[0], -h, h), 0), (h, w))
        left = jnp.broadcast_to(f[:, :1], (h, w))
        right = jnp.broadcast_to(f[:, -1:], (h, w))
        return jax.lax.dynamic_slice(jnp.concatenate([left, f, right], 1),
                                     (0, w + jnp.clip(d[1], -w, w)), (h, w))

    def onehot(f, d):
        rows = jnp.clip(jnp.arange(h, dtype=jnp.int32) + d[0], 0, h - 1)
        cols = jnp.clip(jnp.arange(w, dtype=jnp.int32) + d[1], 0, w - 1)
        r = (rows[:, None] == jnp.arange(h)[None, :]).astype(f.dtype)
        c = (jnp.arange(w)[:, None] == cols[None, :]).astype(f.dtype)
        hi = jax.lax.Precision.HIGHEST
        return jnp.matmul(jnp.matmul(r, f, precision=hi), c, precision=hi)

    return {"takes": takes, "rows_only": rows_only,
            "edge_slice": edge_slice, "onehot": onehot}


def xcorrs(h, w):
    import jax.numpy as jnp

    def adjust(at):
        d = jnp.stack([at // w, at % w]).astype(jnp.int32)
        n = jnp.asarray([h, w], jnp.int32)
        return jnp.where(d > n // 2, d - n, d)

    def real(f, ref):
        c = jnp.fft.irfft2(jnp.fft.rfft2(f) * jnp.conj(jnp.fft.rfft2(ref)),
                           s=(h, w))
        return adjust(jnp.argmax(jnp.abs(c)))

    def complex_(f, ref):
        c = jnp.fft.ifft2(jnp.fft.fft2(f) * jnp.conj(jnp.fft.fft2(ref)))
        return adjust(jnp.argmax(jnp.abs(c)))

    return {"rfft2": real, "fft2": complex_}


def timed(fn, args, calls):
    fn(*args).block_until_ready()
    walls = []
    for _ in range(calls):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        walls.append(time.perf_counter() - t0)
    return statistics.median(walls)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--frames", type=int, default=128)
    ap.add_argument("--calls", type=int, default=7)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("scripts/shift_probe.py needs a TPU; JAX found %r"
                         % dev.platform)
    h = w = 512
    rng = np.random.default_rng(7)
    slab = jnp.asarray(rng.integers(0, 1 << 14, size=(args.frames, h, w))
                       .astype(np.float32))
    disp = rng.integers(-24, 25, size=(args.frames, 2)).astype(np.int32)
    disp[0], disp[1] = (-600, 3), (511, -511)        # past the frame
    disp = jnp.asarray(disp)
    ref = jnp.mean(slab[:16], axis=0)
    out = {"device": dev.device_kind, "frames": args.frames}
    want = None
    for name, one in spellings(h, w).items():
        fn = jax.jit(jax.vmap(one))
        got = fn(slab, disp)
        if want is None:
            want = got
        same = bool(jnp.array_equal(got, want))
        ms = timed(fn, (slab, disp), args.calls) * 1e3
        out["shift_%s_ms" % name] = ms
        out["shift_%s_same" % name] = same
        print("shift %-10s %8.3f ms a slab, equal to takes: %s"
              % (name, ms, same), flush=True)
        # in front of the transpose the streamed swap makes of the slab
        fn2 = jax.jit(lambda s, d, one=one: jnp.transpose(
            jax.vmap(one)(s, d), (1, 2, 0)))
        ms = timed(fn2, (slab, disp), args.calls) * 1e3
        out["shift_%s_transposed_ms" % name] = ms
        print("shift %-10s %8.3f ms a slab with the re-axis behind it"
              % (name, ms), flush=True)
    first = None
    for name, one in xcorrs(h, w).items():
        fn = jax.jit(jax.vmap(one, in_axes=(0, None)))
        got = np.asarray(fn(slab, ref))
        if first is None:
            first = got
        ms = timed(fn, (slab, ref), args.calls) * 1e3
        out["xcorr_%s_ms" % name] = ms
        out["xcorr_%s_differs" % name] = int((got != first).any(1).sum())
        print("xcorr %-6s %8.3f ms a slab, %d frames differ from rfft2's"
              % (name, ms, out["xcorr_%s_differs" % name]), flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
