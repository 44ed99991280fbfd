#!/usr/bin/env python3
"""A percentile by XLA's sort against the same percentile by selection,
by passes over HBM and by the kernel that holds a tile in VMEM.

    python3 scripts/select_probe.py [--lengths 64 256 1024 4096 10240]
        [--bits 1 2 4] [--kernel-bits 1 2 4] [--gib 1.0] [--runs 5]
        [--perc 20] [--out file]

The raw measurement under ``bolt_tpu/ops/select.py``'s constants: the
length from which a record's percentile is selected and not sorted
(``_SELECT_FROM``), how many bits of the key one pass over HBM decides
(``_BITS_A_PASS``), how many a pass over a tile in VMEM decides
(``_KERNEL_BITS``) and the length from which the kernel engages
(``_KERNEL_FROM``).  For each length, ``--gib`` GiB of float32 rows of that
length (14-bit integer counts with heavy ties, the kind the
``pixelseries512-1chip`` session holds, made on the device from a seed) go
through ``jnp.percentile`` (XLA's sort), through ``select._select`` at
each ``--bits`` and, where the length is whole groups of 128 lanes, through
``select._select_flat`` (the Mosaic kernel) at each ``--kernel-bits``, each
as ONE jitted program over the whole array, ``--runs`` timed calls after a
warm-up; a reading is the median wall of a call, ``block_until_ready``
inside it, as rows a second and as GB/s of rows.  Every selection is also
compared with the sort's answer bit for bit, on the device that ran both.  Needs a device that is not the CPU (refuses one: a
CPU sort says nothing of the chip's).  Runs in no cell of the benchmark.

The last line of standard output is the table as one JSON object; ``--out``
writes the same to a file.
"""

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def timed(fn, x, runs):
    """Median wall of ``runs`` calls of the compiled ``fn`` (s), and its
    last answer."""
    out = fn(x).block_until_ready()             # compiles
    walls = []
    for _ in range(runs):
        t0 = time.perf_counter()
        out = fn(x).block_until_ready()
        walls.append(time.perf_counter() - t0)
    return statistics.median(walls), out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--lengths", type=int, nargs="+",
                    default=[64, 256, 1024, 4096, 10240])
    ap.add_argument("--bits", type=int, nargs="+", default=[1, 2, 4])
    ap.add_argument("--kernel-bits", type=int, nargs="*", default=[1, 2, 4])
    ap.add_argument("--gib", type=float, default=1.0)
    ap.add_argument("--runs", type=int, default=5)
    ap.add_argument("--perc", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=37)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from bolt_tpu.ops import select

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        print("select_probe: the first device is the CPU; nothing to "
              "measure", file=sys.stderr)
        return 1
    print("device %s (%s); perc %g; %.2f GiB a length"
          % (dev, dev.device_kind, args.perc, args.gib), flush=True)

    rows_out = []
    for n in args.lengths:
        rows = int(args.gib * (1 << 30)) // (4 * n)
        x = jax.jit(lambda key: jax.random.randint(
            key, (rows, n), 4000, 10000).astype(jnp.float32))(
                jax.random.key(args.seed + n)).block_until_ready()
        by_sort = jax.jit(lambda v: jnp.percentile(v, args.perc, axis=1))

        def reading(wall):
            return {"s": wall, "rows_per_s": rows / wall,
                    "GBps": 4 * rows * n / wall / 1e9}

        wall, want = timed(by_sort, x, args.runs)
        want = np.asarray(want).view(np.int32)
        line = {"length": n, "rows": rows, "sort": reading(wall)}
        for bits in args.bits:
            by_select = jax.jit(lambda v, b=bits: select._select(
                v, args.perc, 1, False, b))
            wall, got = timed(by_select, x, args.runs)
            line["select%d" % bits] = dict(
                reading(wall), equal_to_sort=bool(np.array_equal(
                    np.asarray(got).view(np.int32), want)))
        for bits in args.kernel_bits if n % select._LANES == 0 else ():
            by_kernel = jax.jit(lambda v, b=bits: select._select_flat(
                v, args.perc, b)[:, 0])
            wall, got = timed(by_kernel, x, args.runs)
            line["kernel%d" % bits] = dict(
                reading(wall), equal_to_sort=bool(np.array_equal(
                    np.asarray(got).view(np.int32), want)))
        print(json.dumps(line), flush=True)
        rows_out.append(line)
        del x

    table = {"device": dev.device_kind, "perc": args.perc,
             "gib": args.gib, "runs": args.runs, "lengths": rows_out}
    text = json.dumps(table)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as fh:
            fh.write(text + "\n")
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
