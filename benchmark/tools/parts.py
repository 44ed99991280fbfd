#!/usr/bin/env python3
"""The readings behind a kind that compares several things (``"limit": 1``
and per-reading ``limits`` in its step: ``steps/chunk_svd.py``,
``steps/pca.py``), one by one.  On a machine with the chips, at the cell's
own size:

    python3 benchmark/tools/parts.py <cell> <seed> [<seed> ...]

``control.py`` reads the one number a kind is held to (the worst reading
over its limit).  This prints, for every seed and every such request, each
reading of the SOUND answer (the timed path's own call), of the CONTROL
(``onepass``: the reference with the data rounded to bfloat16 where they
enter a product and nothing else rounded, one bfloat16 pass of the matrix
unit; what ``control.py`` reads) and of the answer with data and every
result held in bfloat16 (``bf16``); the last lines give per reading the
largest sound and the smallest of each of the other two over the seeds.  A
limit belongs over the first with room; the ``onepass`` column says which
limits a program at default matmul precision would break (one of the
cell's has to), the ``bf16`` column what every limit is far under.
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
    """``{kind: {column: {reading: value}}}`` for the cell's current seed."""
    kinds = cell.traffic["requests"]
    man, ref = cell.manifest, cell.reference
    out = {}
    for k, _, steps in pipeline.expand(cell.traffic):
        p = ref.plan(steps)
        if not hasattr(p.terminal, "parts"):
            continue
        fetch = man.module("fetches", kinds[k]["fetch"])
        got = fetch.take(pipeline.compile_call(man, steps)(
            cell.operand.operand()))
        want = ref.expected(steps)
        out[kinds[k]["kind"]] = {
            "sound": p.terminal.parts(got, want),
            "onepass": p.terminal.parts(ref.lowp(steps), want),
            "bf16": p.terminal.parts(
                p.terminal.resident_bf16(ref, p), want),
        }
    return out


def main(name, *seeds, require_tpu=True, man=None):
    man = man or manifest.Manifest(manifest.REAL)
    table = {}
    cell = None
    for seed in seeds:
        if cell is not None:
            cell.operand = cell.reference = None
            gc.collect()
        cell = run.Cell(man, name, int(seed), 0.0, False, require_tpu)
        cell.log = lambda msg: None
        cell.open_device()
        cell.build()
        for kind, columns in readings(cell).items():
            for part in sorted(columns["sound"]):
                s, o, c = (columns[col].get(part, float("nan"))
                           for col in ("sound", "onepass", "bf16"))
                print("seed %s %s %s: sound %.6g onepass %.6g bf16 %.6g"
                      % (seed, kind, part, s, o, c), flush=True)
                ms, mo, mc = table.get((kind, part),
                                       (0.0, float("inf"), float("inf")))
                table[kind, part] = (max(ms, s), min(mo, o), min(mc, c))
    for (kind, part), (s, o, c) in table.items():
        print("%s %s %s over %d seeds: largest sound %.6g, smallest onepass "
              "%.6g, smallest bf16 %.6g" % (name, kind, part, len(seeds),
                                               s, o, c))
    return table


if __name__ == "__main__":
    main(*sys.argv[1:])
