#!/usr/bin/env python3
"""At what rate does a slab of 16-bit frames go up one device's link?

    python3 scripts/narrow_h2d_probe.py [--frames 128] [--hw 512 512]
        [--inflight 1 2] [--runs 4] [--gib 4] [--blocks 16] [--out file]

``scripts/thin_h2d_probe.py``'s measurement for elements NARROWER than 32
bits, with nothing of bolt in the timed path: ``jax.device_put`` and
``block_until_ready`` of zero-copy views of one seeded uint16 host tile to
the first device, N threads each putting one block and waiting for it
before its next (what the uploader pool does), the SAME BYTES a block in
five forms:

* ``u16``     ``(frames, h, w)`` uint16, as a loader returns a slab of a
              camera's frames: the device tiles it ``(8,128)(2,1)``, PAIRS
              of rows packed into one 32-bit sublane, which no row-major
              host block is;
* ``u32``     the same bytes as ``(frames, h, w // 2)`` uint32 (a view): the
              32-bit words the slab already is on the host;
* ``f32``     the same bytes as ``(frames // 2, h, w)`` float32 (a view):
              ``twophoton512-1chip.toseries``'s frames;
* ``u8``      the same bytes as ``(2 * frames, h, w)`` uint8 (a view): 8-bit
              frames, tiled ``(8,128)(4,1)``;
* ``unpack``  ``u32``, and then ONE program on the device that gives the
              block its element: ``lax.bitcast_convert_type(x, uint16)``
              and the reshape, donated, waited for: what a dense route for
              16-bit slabs would cost.

After the table: the unpacking program's own time a block, whether what it
gives equals the ``u16`` upload element for element, its temporaries, and
the layouts the device holds each form in.  Needs a device that is not the
CPU.  Runs in no cell of the benchmark.  The last line of standard output
is one JSON object; ``--out`` writes the same to a file.
"""

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

from thin_h2d_probe import by_threads  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--frames", type=int, default=128)
    ap.add_argument("--hw", type=int, nargs=2, default=[512, 512])
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
        print("narrow_h2d_probe: the first device is the CPU; nothing to "
              "measure", file=sys.stderr)
        return 1
    n, (h, w) = args.frames, args.hw
    if n % 2 or w % 2:
        ap.error("--frames and the width are even")
    rng = np.random.default_rng(1)
    t0 = time.perf_counter()
    tile = rng.integers(0, 1 << 12, size=(args.blocks * n, h, w),
                        dtype=np.uint16)
    tile.setflags(write=False)
    print("tile %.2f GiB in %.2f s; device %s (%s)"
          % (tile.nbytes / (1 << 30), time.perf_counter() - t0, dev,
             dev.device_kind), flush=True)

    u16 = [tile[i * n:(i + 1) * n] for i in range(args.blocks)]
    u32 = [b.view(np.uint32) for b in u16]
    f32 = [b.view(np.float32).reshape(n // 2, h, w) for b in u16]
    u8 = [b.view(np.uint8).reshape(2 * n, h, w) for b in u16]
    for v in (u16[0], u32[0], f32[0], u8[0]):
        assert v.flags.c_contiguous and v.base is not None
        assert v.nbytes == u16[0].nbytes

    @jax.jit
    def unpack_keep(x):
        return jax.lax.bitcast_convert_type(x, jnp.uint16).reshape(n, h, w)
    unpack = jax.jit(unpack_keep.__wrapped__, donate_argnums=(0,))

    def put(v):
        jax.device_put(v, dev).block_until_ready()

    def put_unpack(v):
        unpack(jax.device_put(v, dev)).block_until_ready()

    forms = {"u16": (u16, put), "u32": (u32, put), "f32": (f32, put),
             "u8": (u8, put), "unpack": (u32, put_unpack)}
    settings = [(f, k) for f in forms for k in args.inflight]
    for views, how in forms.values():   # the first copy of a shape pays
        how(views[0])                   # the runtime's set-up (and a compile)
    readings = {s: [] for s in settings}
    for r in range(args.runs):
        k = r % len(settings)
        for s in settings[k:] + settings[:k]:
            form, copies = s
            views, how = forms[form]
            count = max(1, int(args.gib * (1 << 30)) // views[0].nbytes)
            walk = [views[i % len(views)] for i in range(count)]
            wall = by_threads(how, walk, copies)
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

    # the unpacking program alone, and what it gives
    held = jax.device_put(u32[0], dev)
    unpack_keep(held).block_until_ready()
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        outs = [unpack_keep(held) for _ in range(8)]
        for o in outs:
            o.block_until_ready()
        times.append((time.perf_counter() - t0) / 8 * 1e3)
    del outs
    up = jax.device_put(u16[0], dev)
    same = bool(jnp.array_equal(unpack_keep(held), up))
    try:
        layouts = {"u16": str(up.format), "u32": str(held.format),
                   "unpacked": str(unpack_keep(held).format),
                   "f32": str(jax.device_put(f32[0], dev).format),
                   "u8": str(jax.device_put(u8[0], dev).format)}
    except Exception as exc:            # noqa: BLE001 - a note, not a result
        layouts = {"error": repr(exc)}
    mem = unpack_keep.lower(held).compile().memory_analysis()
    note = {"unpack_ms_a_block": statistics.median(times),
            "unpack_ms": times, "unpack_equals_u16": same,
            "unpack_temp_bytes": int(mem.temp_size_in_bytes),
            "layouts": layouts}
    print("unpacking %d frames alone: %.3f ms a block (%s); equals the u16 "
          "upload: %s; temp %d B" % (n, note["unpack_ms_a_block"],
                                     " ".join("%.3f" % t for t in times),
                                     same, note["unpack_temp_bytes"]),
          flush=True)
    print("layouts: %s" % json.dumps(layouts), flush=True)
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "frames": n, "hw": [h, w], "gib_a_reading": args.gib,
           "runs": args.runs, "rows_of": out_rows, "unpack": note}
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
