#!/usr/bin/env python3
"""The glue of a four-chip swap, alone on one chip: ``n`` received pieces
laid side by side on the lanes, by the kernel at each tile and by XLA.

    python3 scripts/swap_merge_probe.py [--n 4] [--per-chip 1100]
        [--rest 50 64 64] [--tiles 0 16 32 64 128] [--runs 7] [--out file]

The raw measurement under ``bolt_tpu/parallel/swapmerge.py``'s
``_TILE_BYTES``.  ``(per_chip, n) + rest`` float32 made on the device from
a seed (the default is what one chip of ``stack4d-4chip.swap`` holds after
the exchange: 3.77 GB as the chip lays it out, ``per_chip`` on the lanes)
becomes ``(rest[0], n * per_chip) + rest[1:]`` as the swap's program does
it around ``swapmerge.merge`` (bitcasts and the kernel) at each ``--tiles``
(0: the tile ``swapmerge._tile`` computes) and by ``jnp.concatenate`` and a
transpose, each ONE jitted program, ``--runs`` timed calls after a warm-up;
a reading is the median wall of a call, ``block_until_ready`` inside it, in
ms and as GB/s of one read and one write of every element as the chip lays
them out.  Every answer is compared with the pieces bit for bit on the
device.  A tile Mosaic refuses (scoped VMEM) reads ``null``.  Refuses the
CPU.  Runs in no cell of the benchmark.

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
    walls = []
    for _ in range(runs + 1):                   # the first compiles
        out = None                  # one answer at a time on the device
        t0 = time.perf_counter()
        out = fn(x).block_until_ready()
        walls.append(time.perf_counter() - t0)
    return statistics.median(walls[1:]), out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=4)
    ap.add_argument("--per-chip", type=int, default=1100)
    ap.add_argument("--rest", type=int, nargs="+", default=[50, 64, 64])
    ap.add_argument("--tiles", type=int, nargs="+",
                    default=[0, 16, 32, 64, 128])
    ap.add_argument("--runs", type=int, default=7)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from bolt_tpu.parallel import swapmerge

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        sys.exit("swap_merge_probe: needs a chip; the CPU says nothing of "
                 "Mosaic's stores")
    n, per_chip, rest = args.n, args.per_chip, tuple(args.rest)
    rows = 1
    for extent in rest:
        rows *= extent
    pad = swapmerge._pad
    moved = 4 * rows * (n * pad(per_chip) + pad(n * per_chip))
    lead = list(range(2, 2 + len(rest)))

    @jax.jit
    def make(key):
        return jax.random.uniform(key, (per_chip, n) + rest, jnp.float32)

    pieces = make(jax.random.PRNGKey(args.seed % (2 ** 31))
                  ).block_until_ready()

    def glue(x, tile):
        flat = x.transpose([1] + lead + [0]).reshape(n, rows, per_chip)
        out = swapmerge.merge(flat, tile)
        return jnp.moveaxis(out.reshape(rest + (n * per_chip,)), -1, 1)

    def by_xla(x):
        whole = jnp.concatenate([x[:, i] for i in range(n)], 0)
        return jnp.moveaxis(whole, 0, 1)

    @jax.jit
    def mismatches(out, x):
        return sum(jnp.sum(out[:, i * per_chip:(i + 1) * per_chip]
                           != jnp.moveaxis(x[:, i], 0, 1))
                   for i in range(n))

    table = {"device": {"platform": dev.platform,
                        "device_kind": dev.device_kind},
             "n": n, "rows": rows, "per_chip": per_chip, "rest": rest,
             "bytes_moved": moved, "readings": []}

    def read(name, fn):
        try:
            wall, out = timed(jax.jit(fn), pieces, args.runs)
        except Exception as exc:                # Mosaic refused the tile
            row = {"name": name, "ms": None,
                   "error": str(exc).splitlines()[-1][:200]}
        else:
            row = {"name": name, "ms": wall * 1e3,
                   "GBps": moved / wall / 1e9,
                   "mismatches": int(mismatches(out, pieces))}
            del out
        table["readings"].append(row)
        print(json.dumps(row), flush=True)

    for tile in args.tiles:
        tile = tile or swapmerge._tile(rows, n, per_chip)
        read("swap_merge.tile%d" % tile,
             lambda x, tile=tile: glue(x, tile))
    read("xla.concatenate", by_xla)

    line = json.dumps(table)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as fh:
            fh.write(line + "\n")
    print(line)


if __name__ == "__main__":
    main()
