#!/usr/bin/env python3
"""Read what the limits in the traffic files are set from.  On a machine
with the chips, at the cell's own size:

    python3 benchmark/tools/control.py <cell> <seed> [<seed> ...]

For every seed, in one process: the data is made, every distinct request of
the cell is sent once through the program (the timed path's own calls and
programs) and its answer compared with the plain reference, which gives the
SOUND reading; then the control answers the same requests one precision
lower (bfloat16 for this float32 system) and is compared the same way.  The
last lines give, per request kind, the largest sound reading, the smallest
control reading, and their ratio: a limit belongs between the two with room
on both sides, and where the ratio is under 3 no limit will hold.
"""

import gc
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]

import manifest
import pipeline
import run


def readings(cell):
    """``{kind name: (sound, control)}`` for the cell's current seed, each
    the worst over the kind's distinct requests, as a run's check takes the
    worst over its sampled answers."""
    kinds = cell.traffic["requests"]
    man, ref = cell.manifest, cell.reference
    out = {}
    for k, _, steps in pipeline.expand(cell.traffic):
        kind = kinds[k]
        fetch = man.module("fetches", kind["fetch"])
        got = fetch.take(pipeline.compile_call(man, steps)(
            cell.operand.operand()))
        if fetch.ON_DEVICE:
            sound = float(ref.on_device(steps, got))
            control = float(ref.lowp_on_device(steps))
        else:
            want = ref.expected(steps)
            sound = ref.number(steps, got, want)
            control = ref.number(steps, ref.lowp(steps), want)
        got = None
        s, c = out.get(kind["kind"], (0.0, 0.0))
        out[kind["kind"]] = (max(s, sound), max(c, control))
    return out


def main(name, *seeds):
    man = manifest.Manifest(manifest.REAL)
    table = {}
    cell = None
    for seed in seeds:
        if cell is not None:
            cell.operand = cell.reference = None
            gc.collect()
        cell = run.Cell(man, name, int(seed), 0.0, False)
        cell.log = lambda msg: None
        cell.open_device()
        cell.build()
        for kind, (sound, control) in readings(cell).items():
            print("seed %s %s: sound %.6g control %.6g"
                  % (seed, kind, sound, control), flush=True)
            s, c = table.get(kind, (0.0, float("inf")))
            table[kind] = (max(s, sound), min(c, control))
    for kind, (sound, control) in table.items():
        print("%s %s over %d seeds: largest sound %.6g, smallest control "
              "%.6g, ratio %s" % (name, kind, len(seeds), sound, control,
                                  "inf" if sound == 0 else
                                  "%.3g" % (control / sound)))


if __name__ == "__main__":
    main(*sys.argv[1:])
