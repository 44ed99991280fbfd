#!/usr/bin/env python3
"""What ``ops/register.py``'s spellings rest on: a slab of 512 x 512 frames
shifted frame by frame by its own integer displacement (edge fill), and the
cross-correlation arg-max of a slab of the benchmark's kind, each spelled
several ways, one jitted program a spelling, median wall of ``--calls``
calls after a warm-up; every shift compared bit for bit with the two
clamped takes, every cross-correlation's displacements with ``rfft2``'s and
its surface with NumPy's in float64.

    python3 scripts/shift_probe.py [--frames 64 128] [--calls 7]
    python3 scripts/shift_probe.py --no-shifts --frames 64 \
        --hw 256 512 1024 2048 --spellings rfft2 products

The second form gives the rows ``register.N_MAX`` is set from.  Refuses the
CPU (a CPU time says nothing about the chip); about two minutes on one chip
cold.
"""

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


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
    """The cross-correlation surface's spellings, one frame each."""
    import jax.numpy as jnp
    from bolt_tpu.ops import register
    nc, ns = w // 2 + 1, (w - 1) // 2

    def real(f, ref):
        return jnp.fft.irfft2(jnp.fft.rfft2(f) * jnp.conj(jnp.fft.rfft2(ref)),
                              s=(h, w))

    def complex_(f, ref):
        return jnp.fft.ifft2(jnp.fft.fft2(f) * jnp.conj(jnp.fft.fft2(ref)))

    def by_stages(f, ref):
        """The four stages written plainly, ELEVEN products a frame (ISSUE
        54's rehearsal): real to half-complex along ``w`` by one packed
        table, the complex stage along ``h`` as four real products, the
        spectra's product, four back along ``h`` and two along ``w``.
        Half as much arithmetic again as the module's four."""
        dot, trig = register._dot, register._cos_sin
        n, g = jnp.arange(w, dtype=jnp.int32), jnp.arange(h, dtype=jnp.int32)
        kc = jnp.arange(nc, dtype=jnp.int32)
        cw, sw = trig(w, n, kc)
        sw = sw[:, 1:1 + ns]
        ch, sh = trig(h, g, g)
        packed = jnp.concatenate([cw, -sw], axis=1)
        rows = ((1, nc - ns - 1), (0, 0))

        def spectrum(x):
            p = dot(x, packed, 1)
            re, im = p[:, :nc], p[:, nc:]
            return (dot(re, ch, 0) + jnp.pad(dot(im, sh, 0), rows),
                    jnp.pad(dot(im, ch, 0), rows) - dot(re, sh, 0))

        (ar, ai), (br, bi) = spectrum(f), spectrum(ref)
        dr, di = ar * br + ai * bi, ai * br - ar * bi
        zr = dot(dr, ch, 1) - dot(di, sh, 1)
        zi = dot(di, ch, 1) + dot(dr, sh, 1)
        twice = jnp.where((kc == 0) | (2 * kc == w), 1.0, 2.0) / (h * w)
        return (dot(zr, (cw * twice).T, 0)
                + dot(zi[1:1 + ns], (sw * (-2.0 / (h * w))).T, 0))

    return {"rfft2": real, "fft2": complex_,
            "products": register._surface_by_products,
            "products_by_stages": by_stages}


def session(frames, h, w, seed=7):
    """A slab of the benchmark's kind (``benchmark/operands/motion.py``,
    not imported): crops of one scene (resting level 1,500, Gaussian cell
    bodies up to 4,000, texture up to 200) at offsets within +-12 pixels,
    noise within +-150 a frame; integers, float32.  And the mean of its
    first 16 frames, the reference."""
    rng = np.random.default_rng(seed)
    m = 16
    rows, cols = h + 2 * m, w + 2 * m
    sc = np.full((rows, cols), 1500.0)
    for _ in range(max(4, 400 * h * w // (512 * 512))):
        cu, cv, sd = rng.uniform(0, rows), rng.uniform(0, cols), rng.uniform(2, 6)
        u = np.arange(max(0, int(cu) - 24), min(rows, int(cu) + 25))[:, None]
        v = np.arange(max(0, int(cv) - 24), min(cols, int(cv) + 25))[None, :]
        sc[u, v] += rng.uniform(400, 4000) * np.exp(  # a blob reaches 4 widths
            -((u - cu) ** 2 + (v - cv) ** 2) / (2 * sd * sd))
    sc = np.minimum(np.rint(sc + rng.integers(0, 201, size=sc.shape)), 16000)
    offs = rng.integers(-12, 13, size=(frames, 2))
    slab = np.stack([sc[m + a:m + a + h, m + b:m + b + w] for a, b in offs])
    slab = (slab + rng.integers(-150, 151, size=slab.shape)).astype(np.float32)
    return slab, slab[:16].mean(axis=0, dtype=np.float64).astype(np.float32)


def timed(fn, args, calls):
    fn(*args).block_until_ready()
    walls = []
    for _ in range(calls):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        walls.append(time.perf_counter() - t0)
    return statistics.median(walls)


def probe_shifts(out, frames, calls):
    import jax
    import jax.numpy as jnp
    h = w = 512
    rng = np.random.default_rng(7)
    slab = jnp.asarray(rng.integers(0, 1 << 14, size=(frames, h, w))
                       .astype(np.float32))
    disp = rng.integers(-24, 25, size=(frames, 2)).astype(np.int32)
    disp[0], disp[1] = (-600, 3), (511, -511)        # past the frame
    disp = jnp.asarray(disp)
    want = None
    for name, one in spellings(h, w).items():
        fn = jax.jit(jax.vmap(one))
        got = fn(slab, disp)
        if want is None:
            want = got
        same = bool(jnp.array_equal(got, want))
        ms = timed(fn, (slab, disp), calls) * 1e3
        out["shift_%s_ms" % name] = ms
        out["shift_%s_same" % name] = same
        print("shift %-10s %8.3f ms a slab, equal to takes: %s"
              % (name, ms, same), flush=True)
        # in front of the transpose the streamed swap makes of the slab
        fn2 = jax.jit(lambda s, d, one=one: jnp.transpose(
            jax.vmap(one)(s, d), (1, 2, 0)))
        ms = timed(fn2, (slab, disp), calls) * 1e3
        out["shift_%s_transposed_ms" % name] = ms
        print("shift %-10s %8.3f ms a slab with the re-axis behind it"
              % (name, ms), flush=True)


def probe_xcorrs(out, h, w, frames, names, calls):
    """Every spelling of ``names`` on slabs of as many BYTES as ``frames``
    frames of 512 x 512 hold: ms a slab, frames whose displacement differs
    from ``rfft2``'s, and (first slab size) the largest error of the
    surface against NumPy's in float64, over the surface's largest
    value."""
    import jax
    import jax.numpy as jnp
    first = True
    for at512 in frames:
        count = max(1, at512 * 512 * 512 // (h * w))
        slab, ref = session(count, h, w)
        if first:
            c64 = np.fft.irfft2(np.fft.rfft2(slab.astype(np.float64))
                                * np.conj(np.fft.rfft2(ref.astype(np.float64))),
                                s=(h, w))
            top = np.abs(c64).max()
        slab, ref = jnp.asarray(slab), jnp.asarray(ref)
        base = None
        for name in names:
            surface = xcorrs(h, w)[name]
            key = "xcorr_%s_%dx%d_%d" % (name, h, w, count)

            def where(f, r, surface=surface):   # crosscorr_shift's arg-max
                at = jnp.argmax(jnp.abs(surface(f, r)))
                return jnp.stack([at // w, at % w])
            fn = jax.jit(jax.vmap(where, in_axes=(0, None)))
            got = np.asarray(fn(slab, ref))
            if base is None:
                base = got
            out[key + "_ms"] = ms = timed(fn, (slab, ref), calls) * 1e3
            out[key + "_differ"] = differ = int((got != base).any(1).sum())
            line = ("xcorr %-18s %4d x %4d  %4d frames %9.3f ms a slab "
                    "(%7.2f us a frame), %d frames differ from %s's"
                    % (name, h, w, count, ms, ms * 1e3 / count, differ,
                       names[0]))
            if first:
                c = np.asarray(jax.jit(jax.vmap(surface, in_axes=(0, None)))(
                    slab, ref))
                out[key + "_err64"] = err = float(np.abs(c - c64).max() / top)
                line += ", surface within %.3g of float64's" % err
            print(line, flush=True)
        first = False


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--frames", type=int, nargs="+", default=[64, 128],
                    help="slab sizes, in frames of 512 x 512 (other frame "
                         "shapes get the same bytes)")
    ap.add_argument("--hw", type=int, nargs="+", default=[512],
                    help="sides of the square frames the cross-correlation "
                         "is probed at (256 512 1024 2048 set register.N_MAX)")
    ap.add_argument("--spellings", nargs="+", default=None,
                    help="the cross-correlation's spellings to run (all)")
    ap.add_argument("--no-shifts", action="store_true",
                    help="leave the shift's spellings out")
    ap.add_argument("--calls", type=int, default=7)
    args = ap.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("scripts/shift_probe.py needs a TPU; JAX found %r"
                         % dev.platform)
    out = {"device": dev.device_kind, "frames": args.frames, "hw": args.hw}
    if not args.no_shifts:
        probe_shifts(out, args.frames[-1], args.calls)
    for n in args.hw:
        probe_xcorrs(out, n, n, args.frames,
                     args.spellings or list(xcorrs(n, n)), args.calls)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
