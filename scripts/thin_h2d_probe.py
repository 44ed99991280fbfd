#!/usr/bin/env python3
"""At what rate does a slab of THIN records go up one device's link?

    python3 scripts/thin_h2d_probe.py [--rows 2396745] [--columns 7]
        [--inflight 1 2] [--runs 4] [--gib 4] [--out file]

``scripts/h2d_probe.py``'s measurement for records of a few values each,
with nothing of bolt in the timed path: ``jax.device_put`` and
``block_until_ready`` of float32 host blocks to the first device, N threads
each putting one block and waiting for it before its next (what the
uploader pool does), in four forms of the same number of bytes a block:

* ``fat``     ``(512, 256, 128)``: ``stack4d-1chip.stream``'s slab, whose
              last two axes are whole ``(8, 128)`` tiles;
* ``thin``    ``(rows, columns)`` row-major, as a loader returns a slab of a
              table: the device holds it with the rows on the lanes
              (``{0,1:T(8,128)}``), so something between the host block and
              that layout transposes it;
* ``dense``   the same bytes as ``(rows // 128, 128 * columns)`` (the rows
              a multiple of 128; a zero-copy view), which pads nothing;
* ``reseat``  ``dense``, and then ONE program on the device that gives the
              block its shape: ``.reshape(r, 128, c).transpose(2, 0, 1)
              .reshape(c, rows).T``, donated, waited for.

After the table: the re-seating program's own time a block (the device's
wall around calls in a row), and whether what it gives equals the ``thin``
upload element for element.  Needs a device that is not the CPU.  Runs in
no cell of the benchmark.  The last line of standard output is one JSON
object; ``--out`` writes the same to a file.
"""

import argparse
import collections
import json
import os
import statistics
import sys
import threading
import time

import numpy as np

FAT = (512, 256, 128)


def by_threads(put, views, n):
    """N workers, one block each at a time; the wall of all."""
    todo = collections.deque(views)
    gate = threading.Barrier(n + 1)
    errors = []

    def work():
        gate.wait()
        try:
            while True:
                put(todo.popleft())
        except IndexError:              # the deque is empty: done
            pass
        except BaseException as exc:    # noqa: BLE001 - raised by the caller
            errors.append(exc)

    pool = [threading.Thread(target=work, daemon=True) for _ in range(n)]
    for t in pool:
        t.start()
    gate.wait()
    t0 = time.perf_counter()
    for t in pool:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    return wall


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=2396745)
    ap.add_argument("--columns", type=int, default=7)
    ap.add_argument("--inflight", type=int, nargs="+", default=[1, 2])
    ap.add_argument("--runs", type=int, default=4)
    ap.add_argument("--gib", type=float, default=4.0,
                    help="GiB moved a reading")
    ap.add_argument("--blocks", type=int, default=16,
                    help="distinct host blocks a form walks")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        print("thin_h2d_probe: the first device is the CPU; nothing to "
              "measure", file=sys.stderr)
        return 1
    c = args.columns
    rows = args.rows
    whole = rows // 128 * 128
    rng = np.random.default_rng(1)
    t0 = time.perf_counter()
    table = rng.integers(0, 1 << 20, size=(args.blocks * rows, c),
                         dtype=np.int32).astype(np.float32)
    table.setflags(write=False)
    fat_n = int(np.prod(FAT))
    fat_tile = table.reshape(-1)[:args.blocks * rows * c // fat_n * fat_n] \
        .reshape((-1,) + FAT[1:])
    print("table %.2f GiB in %.2f s; device %s (%s)"
          % (table.nbytes / (1 << 30), time.perf_counter() - t0, dev,
             dev.device_kind), flush=True)

    thin = [table[i * rows:(i + 1) * rows] for i in range(args.blocks)]
    dense = [t[:whole].reshape(whole // 128, 128 * c) for t in thin]
    fat = [fat_tile[i * FAT[0]:(i + 1) * FAT[0]]
           for i in range(fat_tile.shape[0] // FAT[0])]
    for v in (thin[0], dense[0], fat[0]):
        assert v.flags.c_contiguous and v.base is not None

    @jax.jit
    def reseat_keep(x):
        return x.reshape(whole // 128, 128, c).transpose(2, 0, 1) \
            .reshape(c, whole).T
    reseat = jax.jit(reseat_keep.__wrapped__, donate_argnums=(0,))

    def put(v):
        jax.device_put(v, dev).block_until_ready()

    def put_reseat(v):
        reseat(jax.device_put(v, dev)).block_until_ready()

    forms = {"fat": (fat, put), "thin": (thin, put), "dense": (dense, put),
             "reseat": (dense, put_reseat)}
    settings = [(f, n) for f in forms for n in args.inflight]
    for views, how in forms.values():   # the first copy of a shape pays
        how(views[0])                   # the runtime's set-up (and a compile)
    readings = {s: [] for s in settings}
    for r in range(args.runs):
        k = r % len(settings)
        for s in settings[k:] + settings[:k]:
            form, n = s
            views, how = forms[form]
            count = max(1, int(args.gib * (1 << 30)) // views[0].nbytes)
            walk = [views[i % len(views)] for i in range(count)]
            wall = by_threads(how, walk, n)
            readings[s].append(sum(v.nbytes for v in walk) / wall / 1e9)
    out_rows = []
    print("%8s %8s  %8s %8s %8s  readings (GB/s)"
          % ("form", "inflight", "median", "min", "max"))
    for s in settings:
        got = readings[s]
        out_rows.append({"form": s[0], "inflight": s[1],
                         "block_bytes": forms[s[0]][0][0].nbytes,
                         "median_GBps": statistics.median(got), "GBps": got})
        print("%8s %8d  %8.3f %8.3f %8.3f  %s"
              % (s[0], s[1], statistics.median(got), min(got), max(got),
                 " ".join("%.3f" % g for g in got)), flush=True)

    # the re-seating program alone, and what it gives
    held = jax.device_put(dense[0], dev)
    reseat_keep(held).block_until_ready()
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        outs = [reseat_keep(held) for _ in range(8)]
        for o in outs:
            o.block_until_ready()
        times.append((time.perf_counter() - t0) / 8 * 1e3)
    up = jax.device_put(thin[0][:whole], dev)
    same = bool(jnp.array_equal(reseat_keep(held), up))
    layouts = {}
    try:
        layouts = {"thin": str(up.format), "dense": str(held.format),
                   "reseated": str(reseat_keep(held).format)}
    except Exception as exc:            # noqa: BLE001 - a note, not a result
        layouts = {"error": repr(exc)}
    mem = reseat_keep.lower(held).compile().memory_analysis()
    note = {"reseat_ms_a_block": statistics.median(times),
            "reseat_ms": times, "reseat_equals_thin": same,
            "reseat_temp_bytes": int(mem.temp_size_in_bytes),
            "layouts": layouts}
    print("re-seating %d rows alone: %.3f ms a block (%s); equals the thin "
          "upload: %s; temp %d B" % (whole, note["reseat_ms_a_block"],
                                     " ".join("%.3f" % t for t in times),
                                     same, note["reseat_temp_bytes"]),
          flush=True)
    print("layouts: %s" % json.dumps(layouts), flush=True)
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "rows": rows, "columns": c, "gib_a_reading": args.gib,
           "runs": args.runs, "rows_of": out_rows, "reseat": note}
    line = json.dumps(out)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
