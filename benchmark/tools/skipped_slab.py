#!/usr/bin/env python3
"""Does a streamed cell's check see a slab that was never read?  On a
machine with the chips, at the cell's own size:

    python3 benchmark/tools/skipped_slab.py <cell> <seed> [<seed> ...]

For every seed, in one process: the data is made, every distinct request of
the cell is sent once through the program and compared with the plain
reference (the SOUND reading); then the same requests are sent again with
the loader serving one slab's NEIGHBOUR in its place (the operand's
``serve_instead``: the slab in the middle of the pass is skipped and the one
behind it read twice) and compared the same way.  The configuration's
guarantee is that every row is read exactly once a query: the skipped
reading has to come out over the request's limit (``inf`` where a count
differs).  For operands with a ``serve_instead`` table and a streamed
source of default slabs (``operands/lineitem_streamed.py``).
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
    """``{kind name: (sound, skipped)}`` for the cell's current seed."""
    kinds = cell.traffic["requests"]
    man, ref, op = cell.manifest, cell.reference, cell.operand
    ranges = op.operand()._stream.slab_ranges()
    if len(ranges) < 3:
        raise SystemExit("the pass has %d slabs; nothing to skip"
                         % len(ranges))
    mid = (len(ranges) - 1) // 2
    swap = {ranges[mid][0]: ranges[mid + 1][0]}
    if ranges[mid + 1][1] - ranges[mid + 1][0] != \
            ranges[mid][1] - ranges[mid][0]:
        raise SystemExit("the neighbour of slab %d is shorter than it" % mid)
    out = {}
    for k, _, steps in pipeline.expand(cell.traffic):
        kind = kinds[k]
        fetch = man.module("fetches", kind["fetch"])
        call = pipeline.compile_call(man, steps)
        want = ref.expected(steps)
        got = []
        for table in ({}, swap):
            op.serve_instead = table
            try:
                got.append(ref.number(steps, fetch.take(call(op.operand())),
                                      want))
            finally:
                op.serve_instead = {}
        s, c = out.get(kind["kind"], (0.0, 0.0))
        out[kind["kind"]] = (max(s, got[0]), max(c, got[1]))
    return out


def main(name, *seeds):
    man = manifest.Manifest(manifest.REAL)
    cell = None
    for seed in seeds:
        if cell is not None:
            cell.operand = cell.reference = None
            gc.collect()
        cell = run.Cell(man, name, int(seed), 0.0, False)
        cell.log = lambda msg: None
        cell.open_device()
        cell.build()
        for kind, (sound, skipped) in readings(cell).items():
            limit = [float(k["limit"]) for k in cell.traffic["requests"]
                     if k["kind"] == kind][0]
            print("seed %s %s: sound %.6g, a slab skipped %.6g, limit %.6g: "
                  "%s" % (seed, kind, sound, skipped, limit,
                          "read as wrong" if not skipped <= limit
                          else "NOT SEEN"), flush=True)


if __name__ == "__main__":
    main(*sys.argv[1:])
