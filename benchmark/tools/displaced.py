#!/usr/bin/env python3
"""Read, on a machine with the chips and at the cell's own size, everything
a cell whose answer stays on the device is held by:

    python3 benchmark/tools/displaced.py <cell> <seed> [<seed> ...]

For every seed, in one process: the data is made, the cell's request is sent
once through the program, and its answer is read four ways by the terminal
of its step (``steps/toseries.py``): against the closed form (SOUND, which
has to read 0); the control one precision lower (the recording moved in
bfloat16, ``tools/control.py``'s reading); and the two controls of place,
the answer against the closed form with the frames rolled by one slab (a
slab placed a slab late) and with the rows rolled by one chip's block (every
chip's block on the next chip).  The three controls have to differ."""

import gc
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]

import manifest
import pipeline
import run


def readings(cell):
    man, ref = cell.manifest, cell.reference
    (k, _, steps), = pipeline.expand(cell.traffic)
    fetch = man.module("fetches", cell.traffic["requests"][k]["fetch"])
    handle = pipeline.compile_call(man, steps)(cell.operand.operand())
    slab = handle._stream.slab if handle._stream is not None else 1
    got = fetch.take(handle)
    plan = ref.plan(steps)
    block = got.shape[0] // cell.chips
    out = {"sound": float(ref.on_device(steps, got)),
           "bfloat16": float(ref.lowp_on_device(steps)),
           "a slab late (%d frames)" % slab: float(
               plan.terminal.displaced_on_device(ref, plan, got, 0, slab)),
           "a chip's block on (%d rows)" % block: float(
               plan.terminal.displaced_on_device(ref, plan, got,
                                                 plan.terminal.perm[0],
                                                 block))}
    got = None
    return out


def main(name, *seeds):
    man = manifest.Manifest(manifest.REAL)
    cell = None
    for seed in seeds:
        if cell is not None:
            cell.operand = cell.reference = None
            gc.collect()
        cell = run.Cell(man, name, int(seed), 0.0, False)
        cell.open_device()
        cell.build()
        elements = 1
        for s in cell.operand.shape:
            elements *= s
        for what, count in readings(cell).items():
            print("seed %s %s: %.0f of %d elements differ"
                  % (seed, what, count, elements), flush=True)


if __name__ == "__main__":
    main(*sys.argv[1:])
