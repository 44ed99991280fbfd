#!/usr/bin/env python3
"""What the swap / collect resolver's window of unconfirmed place calls is
worth in a streamed cell, from ONE process on the chip.

    python3 scripts/swap_window_probe.py <cell> --seed N
        [--windows 1 2 3 4] [--passes 3] [--rounds 2]
        [--confirms early blocking] [--chunks 128 256 ...] [--tiny]

The cell's operand is built once (the host tile), then its one request is
repeated under bolt's tracer alone (``obs.enable()``, no profiler) at each
process-wide prefetch depth W (``stream.set_prefetch_depth``) with
``stream._SWAP_WINDOW_STEP`` and ``stream._FOLD_WINDOW_STEP`` set to 0, so
that W IS the resolver's window and ``execute``'s, ring W + pool
(``stream.swap_ring``, ``stream.fold_ring``; the shipped steps make a
caller's depth 2 a window of 3); the rounds walk the windows up and then
down.  ``--confirms blocking`` repeats each window with ``stream._retired``
answering no, so that a confirm is made only once the window is over
(``execute`` before PR 58, the resolver's early retirement off).
``--chunks`` repeats each window with the cell's ``fromcallback`` given
the caller's own ``chunks=`` (records a slab; 0 is the cell's own call,
which gives none), every round starting one setting further along; a
setting that fails (a slab the device has no room for) is a line with
its ``error`` and the probe goes on.  A JSON line a (round, W, confirm,
chunks): the requests' walls, GB/s streamed, the link's
own account (``transfer_seconds``), the consumer's and the pool's waits as
shares of the wall and per span (a folded pass is its ``stream.run``, a
placed one its ``stream.shuffle`` / ``stream.collect``), and the windows'
counters (``stream_windowed_slabs``, ``stream_early_retired_slabs``, the
high-water, a process maximum).  What PERF.md section 5's window tables
were read from (the resolver's, PR 56; ``scan_pca``'s Gram pass and
``stream`` in ``execute``'s, PR 58, beside ``stream_depth_probe.py``'s Q6
and Q1) and its table of ``toseries4``'s slabs (``--windows 3 --chunks
128 256 512 1024``, PR 60); ``--tiny`` rehearses at ``benchmark/tests``'
toy sizes on any device.  Runs in no cell of the benchmark; a cell of one
request a cycle (``toseries``, ``toseries4``, ``register``, ``scan_pca``,
``stream``)."""
import argparse
import functools
import itertools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import manifest as manifest_mod  # noqa: E402
import pipeline                  # noqa: E402
import run as bench_run          # noqa: E402

PER_SLAB = ("stream.sync", "stream.wait.slab", "stream.wait.ring",
            "stream.dispatch", "stream.compute")
SPANS = PER_SLAB + ("stream.ingest", "stream.shuffle", "stream.collect",
                    "stream.run", "stream.handover")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--windows", type=int, nargs="+", default=[1, 2, 3, 4])
    ap.add_argument("--passes", type=int, default=3)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--confirms", nargs="+", default=["early"],
                    choices=["early", "blocking"])
    ap.add_argument("--chunks", type=int, nargs="+", default=[0],
                    help="records a slab as the caller's chunks= (0: none)")
    ap.add_argument("--tiny", action="store_true",
                    help="benchmark/tests' toy sizes, on whatever device")
    args = ap.parse_args()
    roots = ((os.path.join(BENCH, "tests", "tiny"), BENCH) if args.tiny
             else (BENCH,))
    man = manifest_mod.Manifest(manifest_mod.REAL, roots=roots)
    cell = bench_run.Cell(man, args.cell, args.seed, 20.0, False,
                          require_tpu=not args.tiny)
    cell.open_device()
    cell.build()
    from bolt_tpu import engine, obs, stream
    stream._SWAP_WINDOW_STEP = 0        # the depth below IS the window,
    stream._FOLD_WINDOW_STEP = 0        # the resolver's and execute's
    is_done = stream._retired
    if args.tiny:
        stream._SLAB_BYTES = 16 * 16 * 32 * 4
    kinds = cell.traffic["requests"]
    (k, _, steps), = pipeline.expand(cell.traffic)
    call = pipeline.compile_call(man, steps)
    fetch = man.module("fetches", kinds[k]["fetch"]).take
    operand = cell.operand

    import bolt_tpu as bolt
    from_callback = bolt.fromcallback

    def request():
        out = fetch(call(operand.operand()))
        del out

    request()                           # programs compile here
    request()
    for rnd in range(args.rounds):
        order = args.windows if rnd % 2 == 0 else args.windows[::-1]
        at = rnd % len(args.chunks)
        slabs = args.chunks[at:] + args.chunks[:at]
        for w, confirm, chunks in itertools.product(order, args.confirms,
                                                    slabs):
            stream._retired = (is_done if confirm == "early"
                               else lambda handle: False)
            stream.set_prefetch_depth(w)
            # the operand's own call, with the caller's chunks= beside it
            bolt.fromcallback = from_callback if not chunks else \
                functools.partial(from_callback, chunks=chunks)
            obs.clear()
            try:
                request()               # settle at this depth and slab
                obs.enable()
                c0 = engine.counters()
                walls = []
                for _ in range(args.passes):
                    t0 = time.perf_counter()
                    request()
                    walls.append(time.perf_counter() - t0)
            except Exception as e:      # noqa: BLE001 (reported, not hidden)
                obs.disable()
                obs.clear()
                print(json.dumps({
                    "cell": args.cell, "round": rnd, "window": w,
                    "confirm": confirm, "chunks": chunks,
                    "error": "%s: %s" % (type(e).__name__, str(e)[:400]),
                    "peak_GB": cell.memory_peak() / 1e9}), flush=True)
                continue
            c1 = engine.counters()
            totals = obs.totals()
            # the folded pass's own spans (the consumer's thread: under
            # execute's stream.run), apart from a placed pass's
            fold = {}
            for path, (count, seconds, _, _) in obs.trace.path_totals().items():
                if "stream.run" in path:
                    at = fold.setdefault(path[-1].split("stream.")[-1],
                                         [0, 0.0])
                    at[0] += count
                    at[1] = round(at[1] + seconds, 4)
            obs.disable()
            obs.clear()
            d = {key: c1[key] - c0[key] for key in c1
                 if isinstance(c1[key], (int, float))}
            wall = sum(walls)
            up = d["transfer_bytes"]
            row = {
                "cell": args.cell, "round": rnd, "window": w,
                "confirm": confirm, "chunks": chunks,
                "walls_s": [round(x, 4) for x in walls],
                "GBps": up / wall / 1e9,
                "upload_GBps": up / max(d["transfer_seconds"], 1e-9) / 1e9,
                "copies_in_flight": d["transfer_copy_seconds"]
                / max(d["transfer_seconds"], 1e-9),
                "slabs": d["stream_chunks"],
                "windowed": d.get("stream_windowed_slabs"),
                "early": d.get("stream_early_retired_slabs"),
                "inflight_hw": c1["stream_inflight_high_water"],
                "workers_busy": totals.get("stream.ingest", {}).get(
                    "seconds", 0.0) / wall,
                "share": {n.split("stream.")[1]: round(
                    totals[n]["seconds"] / wall, 4)
                    for n in SPANS if n in totals},
                "us_a_span": {n.split("stream.")[1]: round(
                    1e6 * totals[n]["seconds"] / totals[n]["count"], 1)
                    for n in PER_SLAB if n in totals},
                "fold": fold,
                "peak_GB": cell.memory_peak() / 1e9,
            }
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
