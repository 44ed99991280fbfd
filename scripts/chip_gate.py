#!/usr/bin/env python
"""One-command on-chip correctness gate (VERDICT r3 next-3).

Runs the ``-m chip`` parity subset (``tests/test_chip.py``) against the
REAL TPU with production numerics — x64 OFF, the actual XLA:TPU/Mosaic
lowering — the configuration the CPU-mesh suite structurally cannot
exercise.  Prints a one-line record as its last line; whoever ran it
copies that line where the round's records are kept (CHANGES.md).  This
process stays off JAX: the chip belongs to the pytest child.

Usage::

    python scripts/chip_gate.py
"""

import datetime
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    env = dict(os.environ, BOLT_TEST_CHIP="1")
    # the gate must see the real backend: strip the CPU-mesh overrides a
    # caller's shell may carry
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-m", "chip", "-q",
         "tests/test_chip.py", "tests/test_chip_matrix.py"],
        cwd=ROOT, env=env, capture_output=True, text=True)
    tail = (proc.stdout.strip().splitlines() or ["(no output)"])[-1]
    print(proc.stdout[-4000:])
    if proc.returncode != 0:
        print(proc.stderr[-4000:], file=sys.stderr)
    line = "- %s chip gate: %s (rc=%d)" % (
        datetime.date.today().isoformat(), tail, proc.returncode)
    print(line)
    return proc.returncode


if __name__ == "__main__":
    sys.exit(main())
