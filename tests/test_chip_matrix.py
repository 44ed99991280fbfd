"""On-chip parity MATRIX (VERDICT r4 missing-2 / next-1).

The 12-test smoke gate (tests/test_chip.py) touched ~1% of the parity
surface on real hardware; this module re-runs the big enumerated
suites — the inherited-ndarray method matrix and a representative slice
of the ``__array_function__`` dispatch table — plus a deterministic
fuzz profile and the precision-policy envelope, against the REAL TPU
with production numerics (x64 OFF, Mosaic lowering).

Chip adaptations, deliberately minimal so the suites stay the same
code paths as the CPU-mesh run (SURVEY §4's "same code paths" idiom):

- inputs cast to production widths (f64→f32, i64→i32, c128→c64) BEFORE
  both backends see them, so the local numpy oracle computes in the
  same width the chip does;
- dtype parity is asserted through jax's canonicalization (the local
  backend keeps f64 results where numpy promotes; the chip answer must
  be the canonical narrow twin, never a silent f64);
- f32-appropriate tolerances.

Run via ``python scripts/chip_gate.py`` (sets BOLT_TEST_CHIP=1, -m
chip).  Off-gate the module skips.
"""

import numpy as np
import pytest

import bolt_tpu as bolt
from conftest import CHIP_GATE

pytestmark = [
    pytest.mark.chip,
    pytest.mark.skipif(not CHIP_GATE,
                       reason="on-chip gate only (scripts/chip_gate.py)"),
]


@pytest.fixture(scope="module")
def cmesh():
    import jax
    devs = np.array(jax.devices())
    return jax.sharding.Mesh(devs.reshape(devs.size), ("k",))


def _narrow(x):
    """Production-width twin of a host array."""
    x = np.asarray(x)
    if x.dtype == np.float64:
        return x.astype(np.float32)
    if x.dtype == np.int64:
        return x.astype(np.int32)
    if x.dtype == np.complex128:
        return x.astype(np.complex64)
    return x


def _fetch(v):
    """Host ndarray of a result."""
    return np.asarray(v.toarray() if hasattr(v, "toarray") else v)


def _same(name, lo, tp, rtol=3e-4, atol=3e-5):
    if isinstance(lo, (tuple, list)):
        assert isinstance(tp, (tuple, list)) and len(lo) == len(tp), name
        for a, b in zip(lo, tp):
            _same(name, a, b, rtol, atol)
        return
    a, b = _fetch(lo), _fetch(tp)
    assert a.shape == b.shape, (name, a.shape, b.shape)
    import jax.dtypes
    assert jax.dtypes.canonicalize_dtype(a.dtype) == \
        jax.dtypes.canonicalize_dtype(b.dtype), (name, a.dtype, b.dtype)
    assert np.allclose(a, b, rtol=rtol, atol=atol, equal_nan=True), \
        (name, np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64)
                      ).max() if a.dtype.kind in "fc" else (a, b))


def _run(fn, b):
    try:
        return ("ok", fn(b))
    except Exception as exc:                      # noqa: BLE001
        return ("err", type(exc))


# ----------------------------------------------------------------------
# 1. the inherited-ndarray METHOD matrix, production widths
# ----------------------------------------------------------------------

from test_ndarray_methods import CASES as METHOD_CASES  # noqa: E402


@pytest.mark.parametrize("name,make,fn", METHOD_CASES,
                         ids=[c[0] for c in METHOD_CASES])
def test_chip_method_matrix(cmesh, name, make, fn):
    x = _narrow(make())
    lo_status, lo = _run(fn, bolt.array(x.copy()))
    tp_status, tp = _run(fn, bolt.array(x.copy(), cmesh))
    assert lo_status == tp_status, (name, lo, tp)
    if lo_status == "err":
        assert lo is tp or issubclass(tp, lo) or issubclass(lo, tp), \
            (name, lo, tp)
    else:
        _same(name, lo, tp)


# ----------------------------------------------------------------------
# 2. the __array_function__ dispatch slice: every case list the
#    CPU-mesh suite enumerates, production widths
# ----------------------------------------------------------------------

from test_array_function import (  # noqa: E402
    DEVICE_CASES, TAIL2_CASES, TAIL2_CLEAN, TAIL9_CASES, TAIL_CASES)

_NP_CASES = ([("dev:" + n, c) for n, c in DEVICE_CASES]
             + [("tail:" + n, c) for n, c in TAIL_CASES]
             + [("tail2:" + n, c) for n, c in TAIL2_CASES + TAIL2_CLEAN]
             + [("tail9:" + n, c) for n, c in TAIL9_CASES])


def _np_x(name):
    rs = np.random.RandomState(41)
    if name.startswith("tail2:nan"):
        x = rs.randn(8, 6, 4)
        x.ravel()[::17] = np.nan
        return x.astype(np.float32)
    if name.startswith(("tail:", "tail2:", "tail9:")):
        return rs.randn(8, 6, 4).astype(np.float32)
    return np.random.RandomState(31).randn(16, 6, 4).astype(np.float32)


@pytest.mark.parametrize("name,call", _NP_CASES,
                         ids=[c[0] for c in _NP_CASES])
def test_chip_npdispatch_matrix(cmesh, name, call):
    x = _np_x(name)
    b = bolt.array(x, cmesh)
    lo_status, lo = _run(call, x)
    tp_status, tp = _run(call, b)
    assert lo_status == tp_status, (name, lo, tp)
    if lo_status == "err":
        assert lo is tp or issubclass(tp, lo) or issubclass(lo, tp), \
            (name, lo, tp)
        return
    # quantile/median-class reductions promote to f64 on the numpy side
    # only; values must still agree at f32 precision
    _same(name, lo, tp)


# ----------------------------------------------------------------------
# 3. deterministic fuzz profile: fixed op chains through the SAME op
#    implementations the hypothesis fuzzer draws from
# ----------------------------------------------------------------------

_CHAINS = [
    # portable surface only: the local oracle has no swap (reference-
    # faithful) and restricts take_along_axis's fancy indexing
    # explicit axes throughout — the backends' reduction DEFAULTS differ
    # by design (API.md's axis-default caveat)
    ("affine-swapaxes-sum", lambda b: (b * 2.0 + 1.0).swapaxes(1, 2)
     .sum(axis=(0, 1, 2))),
    ("ufunc-clip-mean", lambda b: np.tanh(b).clip(-0.5, 0.5)
     .mean(axis=(0, 1, 2))),
    ("filter-std", lambda b: b.filter(lambda v: v.mean() > 0)
     .std(axis=(0,))),
    ("accumulate-take", lambda b: np.add.accumulate(
        np.take(b, [0, 2], axis=1), axis=2)),
    # partition's within-partition order is unspecified — exact-compare
    # the deterministic sort family only
    ("sortfam", lambda b: np.sort(np.flip(b, 2), axis=2)),
    ("delete-matmul", lambda b: np.delete(b, 1, axis=2) @ np.ones(
        (3, 2), np.float32)),
    ("chunked-smooth", lambda b: _ops().smooth(
        b, 3, axis=(0,)).var(axis=(0,))),
    ("segment-mean", lambda b: _ops().segment_reduce(
        b, np.arange(8) % 3, num_segments=3, op="mean")),
]


def _ops():
    from bolt_tpu import ops
    return ops


@pytest.mark.parametrize("name,chain", _CHAINS,
                         ids=[c[0] for c in _CHAINS])
def test_chip_fuzz_profile(cmesh, name, chain):
    x = np.random.RandomState(51).randn(8, 6, 4).astype(np.float32)
    lo_status, lo = _run(chain, bolt.array(x))
    tp_status, tp = _run(chain, bolt.array(x, cmesh))
    assert lo_status == tp_status == "ok", (name, lo, tp)
    _same(name, lo, tp, rtol=1e-3, atol=1e-4)


# ----------------------------------------------------------------------
# 4. precision-policy envelope on real hardware: the scoped "default"
#    mode must stay inside its documented ~1e-2 relative envelope and
#    the pinned default must stay f32-tight
# ----------------------------------------------------------------------

def test_chip_precision_policy_envelope(cmesh):
    from bolt_tpu.ops import pca
    rs = np.random.RandomState(52)
    x = rs.randn(2048, 64).astype(np.float32)
    b = bolt.array(x, cmesh)
    _, c_hi, v_hi = pca(b, k=4)
    _, c_np, v_np = pca(bolt.array(x), k=4)
    assert np.allclose(v_hi, v_np, rtol=1e-4)          # pinned: tight
    with bolt.precision("default"):
        _, c_lo, v_lo = pca(b, k=4)
    assert np.allclose(v_lo, v_hi, rtol=5e-2)          # documented trade
    w = rs.randn(64, 16).astype(np.float32)
    hi = np.asarray((b @ w).toarray())
    with bolt.precision("default"):
        lo = np.asarray((b @ w).toarray())
    ref = x @ w
    assert np.abs(hi - ref).max() <= np.abs(lo - ref).max() + 1e-4
    # bf16's absolute error scales with the summands (row norm ~ sqrt(d))
    # rather than the result, which can cancel to ~0 — the envelope is
    # relative to the DATA scale
    assert np.abs(lo - ref).max() <= 5e-2 * np.abs(ref).max()
