#!/usr/bin/env python3
"""Record the small traces that ``tests/test_tracered.py`` checks the
reduction on.  On a machine with the chips:

    python3 benchmark/tools/record_trace.py <cell> <out.json> [events]

runs one short traced run of ``cell``, prints what the trace holds (planes,
lines, event counts, the commonest names), and writes the first ``events``
device operations of the window with the benchmark's host spans beside
them, as plain JSON.
"""

import collections
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]

import manifest
import run
import tracered


def describe(raw):
    for plane in raw["planes"]:
        print("plane %r" % plane["name"])
        for line in plane["lines"]:
            names = collections.Counter(ev[0] for ev in line["events"])
            print("  line %r: %d events; %s" % (
                line["name"], len(line["events"]),
                ", ".join("%s x%d" % nc for nc in names.most_common(6))))
            if line["events"]:
                first = min(ev[1] for ev in line["events"])
                last = max(ev[1] + ev[2] for ev in line["events"])
                print("    spans %d ns .. %d ns" % (first, last))


def main(cell, out_path, events=400):
    events = int(events)

    def keep(raw):
        describe(raw)
        starts = sorted(ev[1] for plane in raw["planes"]
                        if plane["name"].startswith(tracered.DEVICE_PLANE)
                        for line in plane["lines"]
                        if line["name"] == tracered.OPS_LINE
                        for ev in line["events"])
        window = [ev for plane in raw["planes"]
                  if plane["name"].startswith(tracered.HOST_PLANE)
                  for line in plane["lines"] for ev in line["events"]
                  if ev[0] == tracered.WINDOW_SPAN]
        inside = [s for s in starts if s >= window[0][1]]
        t0 = window[0][1]
        t1 = inside[min(events, len(inside) - 1)]
        small = tracered.shrink(raw, t0, t1)
        with open(out_path, "w") as fh:
            json.dump(small, fh, separators=(",", ":"))
        print("wrote %s: %s" % (out_path, tracered.reduce_trace(
            small, man.cell(cell)["chips"])))

    man = manifest.Manifest(manifest.REAL)
    out = run.run_cell(man, cell, 7, 2.0, True, keep_trace=keep)
    print(json.dumps(out))


if __name__ == "__main__":
    main(*sys.argv[1:])
