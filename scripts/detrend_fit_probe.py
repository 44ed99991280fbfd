#!/usr/bin/env python3
"""``detrend``'s fit, three spellings, in front of ``fourier``'s five sums,
alone on one chip.

    python3 scripts/detrend_fit_probe.py [--pixels 131072] [--length 10240]
        [--orders 0 1 5 7 9 15] [--freq 16] [--runs 5] [--seed 0] [--out file]

The raw measurement under ``bolt_tpu/ops/series.py``'s
``_FIT_TERMS_ON_VPU``.  ``(pixels, length)`` float32 made on the device from
a seed (dF/F-like series: a drift of order 3, a sinusoid at bin ``freq`` on
two pixels in three, noise) goes through ``detrend(order)`` with the fit
``coef @ A.T`` spelt as

* ``matmul``: the thin matrix product at ``highest`` (XLA: a convolution),
* ``rows``: ``order`` broadcast multiply-adds against ``A``'s columns,
* ``horner``: Horner's rule in ``t`` (one constant row),

each as ONE jitted program, ``--runs`` timed calls after a warm-up, under
four consumers: ``fourier`` with no pass for the mean (what ``ops.fourier``
builds behind a ``detrend``), ``fourier`` as a bare one centres
(``matmul`` only: the program before PR 49), the residual written out
(``detrend(b).cache()``), and ``sum`` over the pixels.  A reading is the
median wall of a call, ``block_until_ready`` inside it, in ms and as GB/s of
ONE read of the array (and one write, where the residual is written); with
it the compiled program's temporaries.  Each ``fourier`` answer is compared
with NumPy in float64 over 256 sampled pixels (coherence everywhere, phase
where the float64 coherence is at least 0.3).  Refuses the CPU.  Runs in no
cell of the benchmark.

The last line of standard output is the table as one JSON object; ``--out``
writes the same to a file.

    python3 scripts/detrend_fit_probe.py --cell rows -- --workload
        pixelseries512-1chip.tuning --seed 7 --seconds 20 --trace 1

runs ``benchmark/run.py`` with what follows ``--`` in this process, with
``ops.detrend``'s fit spelt as named whatever the order: the same three
end to end, in the program the cell compiles.
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

SPELLINGS = ("matmul", "rows", "horner")


def detrend_fn(length, order, spelling):
    """``ops/series.py :: _detrend_fn``'s record function on the device
    with the fit spelt as ``spelling``."""
    import jax.numpy as jnp
    t = np.linspace(-1.0, 1.0, length)
    a_mat = np.vander(t, order + 1, increasing=True)
    pinv_a = np.linalg.pinv(a_mat)

    def f(v):
        a_ = jnp.asarray(a_mat, v.dtype)
        p_ = jnp.asarray(pinv_a, v.dtype)
        coef = jnp.matmul(v, p_.T, precision="highest")
        if spelling == "matmul":
            fit = jnp.matmul(coef, a_.T, precision="highest")
        elif spelling == "rows":
            fit = coef[..., :1]
            for k in range(1, order + 1):
                fit = fit + coef[..., k:k + 1] * a_[:, k]
        else:
            fit = coef[..., order:]
            for k in reversed(range(order)):
                fit = fit * a_[:, 1] + coef[..., k:k + 1]
        return v - fit
    return f


def analysis64(rows, order, freq):
    """``(coherence, phase)`` of float64 ``rows`` by NumPy alone."""
    t = np.linspace(-1.0, 1.0, rows.shape[-1])
    q, _ = np.linalg.qr(np.vander(t, order + 1, increasing=True))
    resid = rows - (rows @ q) @ q.T
    co = np.fft.rfft(resid - resid.mean(axis=-1, keepdims=True), axis=-1)
    coh = np.abs(co[:, freq]) / np.sqrt(np.sum(np.abs(co[:, 1:]) ** 2,
                                               axis=-1))
    return coh, np.angle(co[:, freq])


def timed(fn, x, runs):
    """Median wall of ``runs`` calls of the compiled ``fn`` (s), and its
    last answer."""
    walls = []
    for _ in range(runs + 1):                   # the first warms up
        out = None                  # one answer at a time on the device
        t0 = time.perf_counter()
        out = fn(x).block_until_ready()
        walls.append(time.perf_counter() - t0)
    return statistics.median(walls[1:]), out


def run_cell(spelling, argv):
    """``benchmark/run.py``'s ``main(argv)`` with every ``ops.detrend``
    along a record's one axis fitted by ``spelling``."""
    import functools
    import runpy
    from bolt_tpu.ops import series

    @functools.lru_cache(maxsize=None)
    def forced(length, order, ax):
        if ax != 0:
            raise ValueError("the probe's fit takes a record of one axis")
        f = detrend_fn(length, order, spelling)
        f.zero_mean_axis = ax
        return f
    series._detrend_fn = forced
    sys.argv = [os.path.join(ROOT, "benchmark", "run.py")] + list(argv)
    runpy.run_path(sys.argv[0], run_name="__main__")


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv[:1] == ["--cell"]:
        if len(argv) < 3 or argv[1] not in SPELLINGS or argv[2] != "--":
            sys.exit("usage: --cell {%s} -- <benchmark/run.py's arguments>"
                     % ",".join(SPELLINGS))
        return run_cell(argv[1], argv[3:])
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--pixels", type=int, default=131072)
    ap.add_argument("--length", type=int, default=10240)
    ap.add_argument("--orders", type=int, nargs="+",
                    default=[0, 1, 5, 7, 9, 15])
    ap.add_argument("--freq", type=int, default=16)
    ap.add_argument("--runs", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="a rehearsal at a toy size: its times mean nothing")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from bolt_tpu.ops import series

    dev = jax.devices()[0]
    if dev.platform == "cpu" and not args.allow_cpu:
        sys.exit("detrend_fit_probe: needs a chip; the CPU says nothing of "
                 "the vector unit")
    pixels, length, freq = args.pixels, args.length, args.freq
    gib = 4 * pixels * length / 2 ** 30

    @jax.jit
    def make(key):
        k = jax.random.split(key, 5)
        t = jnp.linspace(-1.0, 1.0, length)
        drift = sum(jax.random.uniform(k[i], (pixels, 1), jnp.float32,
                                       -0.05, 0.05) * t ** (i + 1)
                    for i in range(3))
        tuned = jnp.arange(pixels)[:, None] % 3 != 0
        amp = jax.random.uniform(k[3], (pixels, 1), jnp.float32, 0.02, 0.2)
        wave = jnp.where(tuned, amp, 0.0) * jnp.cos(
            2 * jnp.pi * freq * jnp.arange(length) / length
            + 6.0 * amp)
        return drift + wave + 0.05 * jax.random.normal(
            k[4], (pixels, length), jnp.float32)

    x = make(jax.random.PRNGKey(args.seed)).block_until_ready()
    picks = np.sort(np.random.default_rng(args.seed).choice(
        pixels, size=min(256, pixels), replace=False))
    rows64 = np.asarray(x[picks]).astype(np.float64)

    after = series._fourier_fn(freq, 0, 0.0).after_zero_mean
    bare = series._fourier_fn(freq, 0, 0.0)
    consumers = {
        "fourier": lambda det: lambda v: after(det(v)),
        "fourier_centring": lambda det: lambda v: bare(det(v)),
        "cache": lambda det: det,
    }
    table = {"device": dev.device_kind, "pixels": pixels, "length": length,
             "GiB": gib, "runs": args.runs, "readings": []}

    def read(order, spelling, consumer):
        det = detrend_fn(length, order, spelling)
        if consumer == "sum":
            fn = jax.jit(lambda d: jnp.sum(jax.vmap(det)(d), axis=0))
        else:
            fn = jax.jit(jax.vmap(consumers[consumer](det)))
        temp = fn.lower(x).compile().memory_analysis().temp_size_in_bytes
        wall, out = timed(fn, x, args.runs)
        moved = gib * (2 if consumer == "cache" else 1)
        row = {"order": order, "spelling": spelling, "consumer": consumer,
               "ms": 1e3 * wall, "GBps": moved * 2 ** 30 / 1e9 / wall,
               "temp_bytes": int(temp)}
        if consumer.startswith("fourier"):
            got = np.asarray(out[picks]).astype(np.float64)
            c64, p64 = analysis64(rows64, order, freq)
            turn = np.abs(np.angle(np.exp(1j * (got[:, 1] - p64))))
            row["coherence64"] = float(np.max(np.abs(got[:, 0] - c64)))
            row["phase64"] = float(np.max(turn[c64 >= 0.3], initial=0.0))
        table["readings"].append(row)
        print(json.dumps(row), flush=True)

    for order in args.orders:
        for spelling in SPELLINGS:
            read(order, spelling, "fourier")
        read(order, "matmul", "fourier_centring")
    for order in sorted({1, 5} & set(args.orders)) or args.orders[:1]:
        for spelling in SPELLINGS:
            read(order, spelling, "cache")
            read(order, spelling, "sum")

    line = json.dumps(table)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as fh:
            fh.write(line + "\n")
    print(line)


if __name__ == "__main__":
    main()
