"""bolt_tpu — a TPU-native unified n-dimensional array.

One API over two backends (reference: ``bolt/__init__.py`` re-exports —
symbol-level citation, SURVEY.md §0):

* ``mode='local'`` — NumPy, the semantic oracle;
* ``mode='tpu'``  — a sharded ``jax.Array`` over a device mesh, with
  ``map``/``reduce``/statistics lowering to compiled SPMD programs and
  ``swap`` lowering to an ``all_to_all`` resharding.

>>> import bolt_tpu as bolt
>>> b = bolt.ones((8, 100, 50), context=mesh)   # keys: (8,) on the mesh
>>> b.map(lambda x: x + 1).sum().toarray()
"""

from bolt_tpu.obs.trace import clock as _clock     # standard library only
_T0 = _clock()                  # engine counter import_seconds, from here

__version__ = "0.5.0"

from bolt_tpu.factory import (array, concatenate, fromcallback, fromiter,
                              full, ones, rand, randn, zeros)
from bolt_tpu.base import BoltArray, HostFallbackWarning
from bolt_tpu.local.array import BoltArrayLocal
from bolt_tpu.tpu.array import BoltArrayTPU
from bolt_tpu.tpu.multistat import compute
from bolt_tpu._precision import precision
from bolt_tpu.utils import allclose
from bolt_tpu import profile as _profile   # arms the obs->profiler bridge
from bolt_tpu import engine as _engine

__all__ = ["array", "ones", "zeros", "full", "rand", "randn",
           "fromcallback", "fromiter", "concatenate", "compute",
           "allclose", "precision", "BoltArray", "BoltArrayLocal",
           "BoltArrayTPU", "HostFallbackWarning", "__version__"]

_SUBMODULES = ("analysis", "checkpoint", "engine", "obs", "profile",
               "parallel", "ops", "serve", "statcounter", "stream",
               "utils")


def __getattr__(name):
    # lazy submodule access (bolt.checkpoint, bolt.profile, ...) without
    # importing their heavier dependencies at package import
    if name in _SUBMODULES:
        import importlib
        return importlib.import_module("bolt_tpu." + name)
    raise AttributeError("module 'bolt_tpu' has no attribute %r" % (name,))


_engine.record_import(_clock() - _T0)       # ... to here
