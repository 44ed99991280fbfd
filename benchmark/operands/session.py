"""``{"name": "session"}``: the configuration's recording (``frames`` of
``frame_shape``, keyed by time) as a ``fromcallback`` source whose loader
costs what a page-cache-resident memmap of frame files costs: zero-copy
views of a seeded host tile.  The tile is the WHOLE session, so every frame
holds its own values and a re-axis that puts a slab of frames at another
slab's place differs from the closed form (a shorter tile repeated, as the
``callback`` operand's, would read such a swap as correct).  A new lazy
source per pass, as a caller would make one.

The tile is filled in parts of ``PART`` frames by a pool of threads
(``lattice.host_block`` a part: NumPy lets go of the interpreter lock in
each step), so the temporaries stay a few parts large beside the 10.74 GB
it fills; the seconds that takes are logged and are part of ``setup_s``."""

import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import lattice
import reference

PART = 64            # frames filled by one call of lattice.host_block


def host_session(frames, frame_shape, seed, bits, threads=None):
    """Frames ``[0, frames)`` of the seeded lattice as one float32 array."""
    out = np.empty((frames,) + tuple(frame_shape), np.float32)

    def fill(lo):
        hi = min(lo + PART, frames)
        out[lo:hi] = lattice.host_block(lo, hi, frame_shape, seed, bits)
    threads = threads or max(1, min(12, (os.cpu_count() or 2) - 1))
    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(fill, range(0, frames, PART)))
    return out


class SessionReference(reference.TileReference):
    """The session and its closed form.  A re-axis is answered on the
    device by the step's own terminal (``steps/swap.py``: the count of
    elements that differ from the re-axed lattice), which needs the
    lattice's constants and the shape; the sampled frames of the host tile
    are held to the closed form by NumPy as every tile is."""

    constants = reference.ResidentReference.constants


class Session:
    def __init__(self, spec, config, mesh, seed):
        self.shape = (int(config["frames"]),) + tuple(config["frame_shape"])
        self.bits = int(config["bits"])
        self.seed = seed
        self.mesh = mesh
        t0 = time.perf_counter()
        self.tile = host_session(self.shape[0], self.shape[1:], seed,
                                 self.bits)
        self.tile.setflags(write=False)
        self.nbytes = int(self.tile.nbytes)
        print("session tile: %d frames, %.3f GB of host memory, filled in "
              "%.3f s" % (self.shape[0], self.nbytes / 1e9,
                          time.perf_counter() - t0), flush=True)
        self.loader_seconds = []        # appended by the uploader threads
        self.loader_bytes = []

    def load(self, index):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.loader"):
            block = self.tile[tuple(index)]
        self.loader_seconds.append(time.perf_counter() - t0)
        self.loader_bytes.append(block.nbytes)
        return block

    def operand(self):
        import bolt_tpu as bolt
        return bolt.fromcallback(self.load, self.shape, self.mesh,
                                 dtype=np.float32)

    def reference(self, man):
        return SessionReference(man, self.tile, self.shape, self.bits,
                                self.seed)


make = Session
