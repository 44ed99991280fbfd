#!/usr/bin/env python3
"""Where a pass of ``lineitem-streamed-1chip.scan_q1q6`` spends its time,
a request kind at a time, at several ring depths and pool sizes.

    python3 scripts/stream_depth_probe.py

One process on the chip (~90 s held): the cell's operand is built once (the
16.80 GB host table), then each of its two request kinds is sent through
the cell's own calls three times a setting (the third under
``obs.enable()``), at the defaults and under ``stream.prefetch(4)``,
``prefetch(8)``, ``uploaders(3)`` and ``uploaders(4)``.  A line a pass: the
wall, GB/s, the link's busy seconds (``transfer_seconds``), the copies' own
seconds, and the seconds and counts of the ``stream.*`` and ``engine.*``
spans.  What PERF.md section 5, "scan_q1q6", reads Q1's 2.3 ms blocks and
the ring's waits from (PR 51).  Runs in no cell of the benchmark; needs
the chip (``run.Cell`` refuses the CPU)."""
import os, sys, time, json
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]
import manifest, pipeline, run
from bolt_tpu import engine, obs, stream

man = manifest.Manifest(manifest.REAL)
cell = run.Cell(man, "lineitem-streamed-1chip.scan_q1q6", 12345, 0.0, False)
cell.open_device(); cell.build()
kinds = cell.traffic["requests"]
calls = {}
for k, _, steps in pipeline.expand(cell.traffic):
    kind = kinds[k]
    calls[kind["kind"]] = (pipeline.compile_call(man, steps), man.module("fetches", kind["fetch"]).take)

KEYS = ("transfer_seconds", "transfer_copy_seconds", "stream_ingest_seconds", "stream_compute_seconds", "stream_wall_seconds", "dispatch_seconds", "dispatches")
SPANS = ("stream.dispatch", "stream.sync", "stream.wait.slab", "stream.wait.ring", "stream.transfer", "engine.dispatch", "engine.signature", "engine.enqueue")

def one(kind, traced, scope=None):
    call, take = calls[kind]
    if traced:
        obs.enable(); obs.clear()
    c0 = engine.counters(); t0 = time.perf_counter()
    if scope is None:
        take(call(cell.operand.operand()))
    else:
        with scope():
            take(call(cell.operand.operand()))
    wall = time.perf_counter() - t0; c1 = engine.counters()
    row = {"kind": kind, "wall": round(wall, 4), "GBps": round(cell.operand.nbytes / wall / 1e9, 3)}
    row.update({k: round(c1[k] - c0[k], 4) for k in KEYS})
    if traced:
        tot = obs.totals(); obs.disable(); obs.clear()
        row["spans"] = {s: (tot[s]["count"], round(tot[s]["seconds"], 4)) for s in SPANS if s in tot}
    return row

for kind in ("q6", "q1"):
    one(kind, False)            # compile
plans = [("default", None), ("prefetch4", lambda: stream.prefetch(4)), ("prefetch8", lambda: stream.prefetch(8)), ("uploaders3", lambda: stream.uploaders(3)), ("uploaders4", lambda: stream.uploaders(4))]
for name, scope in plans:
    for kind in ("q6", "q1"):
        if scope is not None:
            one(kind, False, scope)     # any new program compiles here
        for traced in (False, False, True):
            print(name, json.dumps(one(kind, traced, scope)), flush=True)
