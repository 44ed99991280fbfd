#!/usr/bin/env python3
"""Where a pass of ``lineitem-streamed-1chip.scan_q1q6`` spends its time,
a request kind at a time, at several depths of ``execute``'s confirm window.

    python3 scripts/stream_depth_probe.py [--windows 2 3 4 5]
        [--confirms blocking early] [--passes 3] [--seed N] [--tiny]

One process on the chip (~2.5 min held): the cell's operand is built once
(the 16.80 GB host table), then each of its two request kinds is sent
through the cell's own calls ``--passes`` times a setting, the last under
``obs.enable()``.  A setting is a window of W slabs (the caller's depth
stays 2 and ``stream._FOLD_WINDOW_STEP`` is set to W - 2, so the pool's
ring, the window and the forecast move together as they do in the shipped
code) and how a confirm is made: ``early`` is the shipped executor, which
hands a permit back as soon as the head of its window is done, ``blocking``
the one before PR 58, which confirms only once the window is over
(``stream._retired`` answers no).  A JSON line a pass: the wall, GB/s, the
link's busy seconds (``transfer_seconds``), the copies' own seconds,
``stream_early_retired_slabs``, the window's high-water of the pass, and
the seconds and counts of the ``stream.*`` and ``engine.*`` spans.  Feeds
PERF.md section 5's table "execute's window, since PR 58" (the rows of Q6
and Q1; ``scan_pca``'s and ``stream``'s are ``swap_window_probe.py``'s),
which ``_FOLD_WINDOW_STEP`` rests on; PR 51 read Q1's 2.3 ms blocks and
the ring's waits from it.  Runs in no cell of the benchmark; needs the
chip (``run.Cell`` refuses the CPU) unless ``--tiny`` rehearses it at
``benchmark/tests``' toy sizes."""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]

import manifest                  # noqa: E402
import pipeline                  # noqa: E402
import run                       # noqa: E402

KEYS = ("transfer_seconds", "transfer_copy_seconds", "stream_ingest_seconds",
        "stream_compute_seconds", "stream_wall_seconds", "dispatch_seconds",
        "dispatches", "stream_early_retired_slabs", "stream_chunks")
SPANS = ("stream.dispatch", "stream.sync", "stream.wait.slab",
         "stream.wait.ring", "stream.transfer", "engine.dispatch",
         "engine.signature", "engine.enqueue")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--windows", type=int, nargs="+", default=[2, 3, 4, 5])
    ap.add_argument("--confirms", nargs="+", default=["blocking", "early"],
                    choices=["blocking", "early"])
    ap.add_argument("--passes", type=int, default=3)
    ap.add_argument("--seed", type=int, default=12345)
    ap.add_argument("--tiny", action="store_true",
                    help="benchmark/tests' toy sizes, on whatever device")
    args = ap.parse_args()
    bench = os.path.join(ROOT, "benchmark")
    roots = ((os.path.join(bench, "tests", "tiny"), bench) if args.tiny
             else (bench,))
    man = manifest.Manifest(manifest.REAL, roots=roots)
    cell = run.Cell(man, "lineitem-streamed-1chip.scan_q1q6", args.seed,
                    0.0, False, require_tpu=not args.tiny)
    cell.open_device()
    cell.build()
    from bolt_tpu import engine, obs, stream
    if args.tiny:
        stream._SLAB_BYTES = 512 * 7 * 4
    kinds = cell.traffic["requests"]
    calls = {}
    for k, _, steps in pipeline.expand(cell.traffic):
        kind = kinds[k]
        calls[kind["kind"]] = (pipeline.compile_call(man, steps),
                               man.module("fetches", kind["fetch"]).take)
    is_done = stream._retired
    seen = []                   # a pass's own high-water: the counter
    record = engine.record_stream   # is a process maximum

    def spy(*a, **kw):
        seen.append(kw.get("inflight"))
        return record(*a, **kw)
    engine.record_stream = spy

    def one(kind, traced):
        call, take = calls[kind]
        if traced:
            obs.enable()
            obs.clear()
        del seen[:]
        c0 = engine.counters()
        t0 = time.perf_counter()
        take(call(cell.operand.operand()))
        wall = time.perf_counter() - t0
        c1 = engine.counters()
        row = {"kind": kind, "wall": round(wall, 4),
               "GBps": round(cell.operand.nbytes / wall / 1e9, 3),
               "inflight_hw": list(seen)}
        row.update({k: round(c1[k] - c0[k], 4) for k in KEYS})
        if traced:
            tot = obs.totals()
            obs.disable()
            obs.clear()
            row["spans"] = {s: (tot[s]["count"], round(tot[s]["seconds"], 4))
                            for s in SPANS if s in tot}
        return row

    for kind in calls:
        one(kind, False)            # the programs compile here
    for w in args.windows:
        for confirm in args.confirms:
            stream._FOLD_WINDOW_STEP = w - stream.prefetch_depth()
            stream._retired = (is_done if confirm == "early"
                               else lambda handle: False)
            for kind in calls:
                one(kind, False)    # settle at this setting
                for p in range(args.passes):
                    row = one(kind, p == args.passes - 1)
                    print(json.dumps(dict(window=w, confirm=confirm, **row)),
                          flush=True)


if __name__ == "__main__":
    main()
