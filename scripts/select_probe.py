#!/usr/bin/env python3
"""A percentile by XLA's sort against the same percentile by selection,
by passes over HBM and by the kernel that holds a tile in VMEM.

    python3 scripts/select_probe.py [--lengths 64 256 1024 4096 10240]
        [--bits 1 2 4] [--kernel-bits 1 2 4] [--gib 1.0] [--runs 5]
        [--perc 20] [--based [--records 262144] [--block 5352]] [--out file]

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
compared with the sort's answer bit for bit, on the device that ran both.

``--based`` adds the kernel's two ways to its block (PR 47), at the
``pixelseries512-1chip.tuning`` cell's shape: a resident ``(--records,
10240)`` float32 array (10.74 GB) walked in blocks of ``--block`` records as
``tpu/array.py :: _blocked_run`` walks it (the last block starts early), the
kernel handed a SLICE of the array (XLA writes the block out first, as every
program did before PR 47) and handed the array and the block's OFFSET (it
reads its tiles where they lie).  Each is one jitted loop over all the
blocks, ``--runs`` timed calls, read as ms a GiB of rows; the two keys and
the NaN verdict of every record of every block are compared bit for bit
between the two (a NaN and both zeros are planted in each block's last
tile).  This is the record of the kernel's correctness on the chip: the
cell's own check does not see a wrong percentile, the baseline cancels out
of both of its maps.

Needs a device that is not the CPU (refuses one: a CPU sort says nothing of
the chip's).  Runs in no cell of the benchmark.

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


def based(args):
    """The ``--based`` reading: the kernel over a slice of the resident
    array against the kernel over the array at an offset."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from bolt_tpu.ops import select

    n, block, length = args.records, args.block, 10240
    count = -(-n // block)
    low, high, *weights = select._ranks(length, args.perc)
    start_of = lambda i: jnp.minimum(i.astype(jnp.int32) * block, n - block)

    def fill(key):
        # made a block at a time, in place: the generator's bits for the
        # whole array would not fit beside it
        def one(i, out):
            x = jax.random.randint(jax.random.fold_in(key, i),
                                   (block, length), 4000, 10000)
            x = x.astype(jnp.float32).at[block - 1, 7].set(jnp.nan)
            x = x.at[block - 2, :4].set(jnp.array([0.0, -0.0, -0.0, 0.0]))
            return lax.dynamic_update_slice_in_dim(out, x, start_of(i), 0)
        return lax.fori_loop(0, count, one,
                             jnp.zeros((n, length), jnp.float32))

    def walk(keys_of):
        def loop(base):
            def one(i, out):
                keys = jnp.concatenate(
                    [k.astype(jnp.uint32) for k in keys_of(base, start_of(i))],
                    axis=1)
                return lax.dynamic_update_index_in_dim(out, keys, i, 0)
            return lax.fori_loop(0, count, one,
                                 jnp.zeros((count, block, 3), jnp.uint32))
        return jax.jit(loop)

    ways = {
        "slice": walk(lambda base, at: select._kernel_keys(
            lax.dynamic_slice_in_dim(base, at, block), low, high)),
        "based": walk(lambda base, at: select._kernel_keys(
            base, low, high, start=at, block=block)),
    }
    base = jax.jit(fill)(jax.random.key(args.seed)).block_until_ready()
    gib = count * block * length * 4 / (1 << 30)
    line = {"records": n, "block": block, "blocks": count,
            "last_start": n - block, "gib_a_call": gib}
    got = {}
    for name, fn in ways.items():
        wall, out = timed(fn, base, args.runs)
        got[name] = np.asarray(out)
        line[name] = {"s": wall, "ms_per_gib": 1e3 * wall / gib,
                      "ms_a_block": 1e3 * wall / count}
    same = [bool(np.array_equal(got["slice"][i], got["based"][i]))
            for i in range(count)]
    line["blocks_equal_to_the_bit"] = sum(same)
    line["unequal_blocks"] = [i for i, ok in enumerate(same) if not ok]
    # what was planted is what was found: the NaN verdicts against XLA's
    # own isnan over the array, and the sort's answer for the first and
    # the last block
    starts = [min(i * block, n - block) for i in range(count)]
    isnan = np.asarray(jax.jit(lambda v: jnp.isnan(v).any(axis=1))(base))
    line["nan_verdicts_equal_to_isnan"] = sum(
        bool(np.array_equal(got["based"][i, :, 2] != 0,
                            isnan[at:at + block]))
        for i, at in enumerate(starts))
    line["records_with_a_nan"] = int(isnan.sum())
    by_sort = jax.jit(lambda v: jnp.percentile(v, args.perc, axis=1))
    by_keys = jax.jit(lambda k: select._blend(
        k[:, 0:1], k[:, 1:2], k[:, 2:3] != 0, *weights, jnp.float32)[:, 0])
    ends = []
    for i in (0, count - 1):
        at = starts[i]
        want = np.asarray(by_sort(base[at:at + block])).view(np.int32)
        ends.append(bool(np.array_equal(np.asarray(by_keys(
            jnp.asarray(got["based"][i]))).view(np.int32), want)))
    line["first_and_last_block_equal_to_sort"] = ends
    print(json.dumps(line), flush=True)
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--lengths", type=int, nargs="*",
                    default=[64, 256, 1024, 4096, 10240])
    ap.add_argument("--based", action="store_true")
    ap.add_argument("--records", type=int, default=512 * 512)
    ap.add_argument("--block", type=int, default=5352)
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
    if args.based:
        table["based"] = based(args)
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
