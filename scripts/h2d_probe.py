#!/usr/bin/env python3
"""How many host-to-device copies does one device's link want in flight?

    python3 scripts/h2d_probe.py [--slab-mib 64 128] [--inflight 1 2 3 4]
        [--runs 6] [--gib 8] [--tile-gib 2] [--devices 1] [--out file]

The raw measurement under ``bolt_tpu.stream.pool_size``'s auto rule
(ROADMAP S1 (a)), with nothing of bolt in the timed path: ``jax.device_put``
and ``block_until_ready`` of float32 views of a seeded host tile made the way
``benchmark/operands/callback.py`` makes the streamed cells' tile, to the
first device, with N copies in flight in two forms:

* ``threads``: N threads, each putting one slab and waiting for it before
  its next (what the uploader pool does);
* ``issuer``: one thread that keeps N puts issued and waits for the oldest.

With ``--devices D`` (a four-chip host: 4) the copies go to the first D
devices, ``--inflight`` copies in flight A DEVICE: ``threads`` runs N x D
workers, each bound to one device, ``issuer`` keeps N x D puts issued in
turn over the devices.  The first line then gives the host's ``MemTotal`` and
the cores the process may run on.

A reading is ``--gib`` GiB of slabs moved, bytes over the wall from the first
put to the last buffer ready, in GB/s in aggregate.  ``--runs`` readings a
setting; each round visits every setting once, and starts one setting
further along than the round before.  Needs a device that is not the CPU
(refuses one; a CPU "copy" is a memcpy).  Runs in no cell of the benchmark.

The last line of standard output is the table as one JSON object; ``--out``
writes the same to a file.
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

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import lattice  # noqa: E402

REC_SHAPE = (256, 128)          # stack4d's record: 128 KiB of float32
BITS = 12
SEED = 1                        # the values do not matter to a copy


def slabs(tile, slab_records, count):
    """``count`` zero-copy views of ``slab_records`` records, walking the
    tile as the streamed cells' loader does."""
    per_tile = tile.shape[0] // slab_records
    return [tile[(i % per_tile) * slab_records:
                 (i % per_tile + 1) * slab_records] for i in range(count)]


def by_threads(jax, devs, views, n):
    """N workers a device, one copy each at a time; the wall of all."""
    todo = collections.deque(views)
    gate = threading.Barrier(n * len(devs) + 1)
    errors = []

    def work(dev):
        gate.wait()
        try:
            while True:
                jax.device_put(todo.popleft(), dev).block_until_ready()
        except IndexError:              # the deque is empty: done
            pass
        except BaseException as exc:    # noqa: BLE001 - raised by the caller
            errors.append(exc)

    pool = [threading.Thread(target=work, args=(dev,), daemon=True)
            for dev in devs for _ in range(n)]
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


def by_issuer(jax, devs, views, n):
    """One thread, N puts a device issued before it waits for the oldest."""
    flight = collections.deque()
    t0 = time.perf_counter()
    for i, view in enumerate(views):
        if len(flight) == n * len(devs):
            flight.popleft().block_until_ready()
        flight.append(jax.device_put(view, devs[i % len(devs)]))
    while flight:
        flight.popleft().block_until_ready()
    return time.perf_counter() - t0


FORMS = {"threads": by_threads, "issuer": by_issuer}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--slab-mib", type=int, nargs="+", default=[64, 128])
    ap.add_argument("--inflight", type=int, nargs="+", default=[1, 2, 3, 4])
    ap.add_argument("--forms", nargs="+", default=sorted(FORMS, reverse=True),
                    choices=sorted(FORMS))
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--gib", type=float, default=8.0,
                    help="GiB moved a reading")
    ap.add_argument("--tile-gib", type=float, default=2.0)
    ap.add_argument("--devices", type=int, default=1,
                    help="copy to the first D devices, --inflight a device")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()[:args.devices]
    if len(devs) < max(args.devices, 1):
        print("h2d_probe: %d devices asked for, %d found"
              % (args.devices, len(devs)), file=sys.stderr)
        return 1
    dev = devs[0]
    if args.devices > 1:
        with open("/proc/meminfo") as f:
            mem = next(line for line in f if line.startswith("MemTotal"))
        print("host: %s, %d cores for this process, %d devices"
              % (" ".join(mem.split()), len(os.sched_getaffinity(0)),
                 len(devs)), flush=True)
    if dev.platform == "cpu":
        print("h2d_probe: the first device is the CPU; nothing to measure",
              file=sys.stderr)
        return 1
    rec_bytes = int(np.prod(REC_SHAPE)) * 4
    tile_records = int(args.tile_gib * (1 << 30)) // rec_bytes
    t0 = time.perf_counter()
    tile = lattice.host_tile(tile_records, REC_SHAPE, SEED, BITS)
    tile.setflags(write=False)
    print("tile %.2f GiB in %.2f s; device %s (%s)"
          % (tile.nbytes / (1 << 30), time.perf_counter() - t0, dev,
             dev.device_kind), flush=True)

    settings = [(mib, form, n) for mib in args.slab_mib
                for form in args.forms for n in args.inflight]
    views, nbytes = {}, {}
    for mib in args.slab_mib:
        slab_records = (mib << 20) // rec_bytes
        count = max(1, int(args.gib * (1 << 30)) // (mib << 20))
        views[mib] = slabs(tile, slab_records, count)
        nbytes[mib] = sum(v.nbytes for v in views[mib])
        # the first copy of a size pays the runtime's set-up for it
        by_issuer(jax, devs, views[mib][:4 * len(devs)], 2)
    readings = {s: [] for s in settings}
    for r in range(args.runs):
        k = r % len(settings)
        for s in settings[k:] + settings[:k]:
            mib, form, n = s
            wall = FORMS[form](jax, devs, views[mib], n)
            readings[s].append(nbytes[mib] / wall / 1e9)
    rows = []
    print("%8s %8s %8s  %8s %8s %8s  readings (GB/s)"
          % ("slab_MiB", "form", "inflight", "median", "min", "max"))
    for s in settings:
        mib, form, n = s
        got = readings[s]
        rows.append({"slab_mib": mib, "form": form, "inflight": n,
                     "median_GBps": statistics.median(got), "GBps": got})
        print("%8d %8s %8d  %8.3f %8.3f %8.3f  %s"
              % (mib, form, n, statistics.median(got), min(got), max(got),
                 " ".join("%.3f" % g for g in got)), flush=True)
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(devs)},
           "tile_gib": tile.nbytes / (1 << 30), "gib_a_reading": args.gib,
           "runs": args.runs, "rows": rows}
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
