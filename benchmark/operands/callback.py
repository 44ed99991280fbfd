"""``{"name": "callback"}``: the configuration's ``streamed_source``
(``shape``, ``tile_records``), a ``fromcallback`` source larger than HBM
whose loader costs what a page-cache-resident memmap costs: zero-copy views
of a seeded host tile (record index modulo the tile; slabs are
tile-aligned).  A new lazy source per pass, as a caller would make one."""

import time

import numpy as np

import lattice
import reference


class Callback:
    def __init__(self, spec, config, mesh, seed):
        source = config["streamed_source"]
        self.shape = tuple(source["shape"])
        self.bits = int(config["bits"])
        self.seed = seed
        self.mesh = mesh
        self.tile_records = int(source["tile_records"])
        if self.shape[0] % self.tile_records:
            raise ValueError("the source is not a whole number of tiles")
        self.tile = lattice.host_tile(self.tile_records, self.shape[1:],
                                      seed, self.bits)
        self.tile.setflags(write=False)
        self.nbytes = int(np.prod(self.shape, dtype=np.int64)) * 4
        self.loader_seconds = []        # appended by the uploader threads
        self.loader_bytes = []

    def load(self, index):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.loader"):
            lo, hi, _ = index[0].indices(self.shape[0])
            at = lo % self.tile_records
            if at + (hi - lo) > self.tile_records:
                raise ValueError("slab [%d, %d) straddles the host tile"
                                 % (lo, hi))
            block = self.tile[(slice(at, at + hi - lo),) + tuple(index[1:])]
        self.loader_seconds.append(time.perf_counter() - t0)
        self.loader_bytes.append(block.nbytes)
        return block

    def operand(self):
        import bolt_tpu as bolt
        return bolt.fromcallback(self.load, self.shape, self.mesh,
                                 dtype=np.float32)

    def reference(self, man):
        return reference.TileReference(man, self.tile, self.shape, self.bits,
                                       self.seed)


make = Callback
