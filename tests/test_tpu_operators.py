"""Elementwise operator tests (a deliberate superset of the reference: its
Spark array routes elementwise math through ``map`` — SURVEY §2.2)."""

import numpy as np
import pytest

import bolt_tpu as bolt
from bolt_tpu.utils import allclose


def _x():
    rs = np.random.RandomState(12)
    return rs.randn(8, 4, 5)


def test_scalar_ops(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    assert allclose((b + 1).toarray(), x + 1)
    assert allclose((1 + b).toarray(), 1 + x)
    assert allclose((b - 2).toarray(), x - 2)
    assert allclose((2 - b).toarray(), 2 - x)
    assert allclose((b * 3).toarray(), x * 3)
    assert allclose((b / 2).toarray(), x / 2)
    assert allclose((2 / (b + 10)).toarray(), 2 / (x + 10))
    assert allclose((b ** 2).toarray(), x ** 2)
    assert allclose((-b).toarray(), -x)
    assert allclose(abs(b).toarray(), abs(x))


def test_scalar_ops_defer(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    m = (b + 1) * 2 - 3
    assert m.deferred  # scalar ops fuse into the map chain
    assert allclose(m.toarray(), (x + 1) * 2 - 3)


def test_array_operand(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    other = np.random.RandomState(13).randn(*x.shape)
    assert allclose((b + other).toarray(), x + other)
    assert allclose((b * other).toarray(), x * other)
    # broadcasting into the full shape
    row = np.random.RandomState(14).randn(5)
    assert allclose((b + row).toarray(), x + row)
    with pytest.raises(ValueError):
        b + np.ones((9, 1, 1))  # incompatible shapes still reject


def test_array_operand_broadcast_outgrows_self(mesh):
    """numpy broadcasting is symmetric: np.ones(8) * b_scalar outgrows
    the device operand (this is how np.fft.fftfreq(n, d_device) is
    served compositionally).  Keys survive only while they stay the
    leading axes with unchanged lengths."""
    x = _x()
    b = bolt.array(x, mesh)
    s = b.mean(axis=(0, 1, 2))         # 0-d device scalar
    out = np.ones(8) * s
    assert isinstance(out, type(b)) and out.split == 0
    assert allclose(out.toarray(), np.ones(8) * x.mean())
    assert allclose((np.arange(6.0) + s).toarray(),
                    np.arange(6.0) + x.mean())
    # value-dim growth keeps the keys
    col = bolt.array(x[:, :, :1], mesh)
    grown = col * np.ones(5)
    assert grown.split == 1 and grown.shape == (8, 4, 5)
    assert allclose(grown.toarray(), x[:, :, :1] * np.ones(5))
    # leading-dim growth replicates
    led = b + np.ones((3, 8, 4, 5))
    assert led.split == 0
    assert allclose(led.toarray(), x + np.ones((3, 8, 4, 5)))


def test_bolt_operand(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    c = bolt.array(x * 2, mesh)
    out = b + c
    assert out.split == 1
    assert allclose(out.toarray(), x * 3)
    # local bolt array operand
    out = b + bolt.array(np.ones_like(x))
    assert allclose(out.toarray(), x + 1)


def test_comparisons(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    assert allclose((b > 0).toarray(), x > 0)
    assert allclose((b <= 0.5).toarray(), x <= 0.5)
    assert (b == b).toarray().all()
    assert not (b != b).toarray().any()
    assert (b > 0).dtype == np.bool_


def test_value_shaped_result_ops(mesh):
    # operators on a split=0 reduction result
    x = _x()
    s = bolt.array(x, mesh).sum()
    assert s.split == 0
    assert allclose((s + 1).toarray(), x.sum(axis=0) + 1)
    assert allclose(abs(s).toarray(), abs(x.sum(axis=0)))


def test_mixed_expression(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    out = ((b + 1) * (b - 1)).mean()
    assert allclose(out.toarray(), ((x + 1) * (x - 1)).mean(axis=0))


def test_numpy_left_operand_reflects(mesh):
    # numpy must defer to __radd__ etc. instead of gathering via __array__
    x = _x()
    b = bolt.array(x, mesh)
    out = np.ones_like(x) + b
    assert isinstance(out, type(b))
    assert allclose(out.toarray(), x + 1)
    out = np.float64(2.0) * b
    assert isinstance(out, type(b))
    assert allclose(out.toarray(), x * 2)


def test_eq_sentinel(mesh):
    b = bolt.array(_x(), mesh)
    assert (b == None) is False      # noqa: E711 — the point of the test
    assert (b != None) is True       # noqa: E711
    assert (b == "nope") is False


def test_neg_bool_parity(mesh):
    x = _x()
    with pytest.raises(TypeError):
        -(x > 0)                     # the numpy oracle rejects bool negate
    with pytest.raises(TypeError):
        -(bolt.array(x, mesh) > 0)   # and so must the TPU backend


def test_scalar_ops_cache_stable(mesh):
    from bolt_tpu.tpu.array import _JIT_CACHE
    b = bolt.array(_x(), mesh)
    (b + 1.0).sum().toarray()
    before = len(_JIT_CACHE)
    for _ in range(5):
        (b + 1.0).sum().toarray()
    assert len(_JIT_CACHE) == before  # identical expressions reuse programs


# ----------------------------------------------------------------------
# round-2 surface: floordiv, matmul, in-place forms, and numpy-ufunc
# dispatch into the deferred chain (VERDICT r1 weak-3 / next-5)
# ----------------------------------------------------------------------

def test_floordiv(mesh):
    x = _x() * 10
    b = bolt.array(x, mesh)
    assert allclose((b // 3).toarray(), x // 3)
    assert allclose((100 // (abs(b) + 1)).toarray(), 100 // (abs(x) + 1))
    other = np.random.RandomState(15).randn(*x.shape) + 5
    assert allclose((b // other).toarray(), x // other)


def test_mod_reflected(mesh):
    x = abs(_x()) + 1
    b = bolt.array(x, mesh)
    assert allclose((b % 2).toarray(), x % 2)
    assert allclose((7 % b).toarray(), 7 % x)
    assert allclose((2.0 ** b).toarray(), 2.0 ** x)


def test_matmul_batched_over_keys(mesh):
    x = _x()                       # (8, 4, 5), keys (8,)
    w = np.random.RandomState(16).randn(5, 3)
    b = bolt.array(x, mesh)
    out = b @ w
    assert out.split == 1          # keys survive as batch dims
    assert allclose(out.toarray(), x @ w)


def test_matmul_2d_and_reflected(mesh):
    rs = np.random.RandomState(17)
    x = rs.randn(8, 5)
    w = rs.randn(5, 8)
    b = bolt.array(x, mesh)
    assert allclose((b @ w).toarray(), x @ w)
    assert allclose((w @ b).toarray(), w @ x)
    assert allclose(np.matmul(w, b).toarray(), w @ x)


def test_matmul_bolt_operand(mesh):
    rs = np.random.RandomState(18)
    x, y = rs.randn(8, 4, 5), rs.randn(8, 5, 2)
    b, c = bolt.array(x, mesh), bolt.array(y, mesh)
    out = b @ c                    # stacked matmul over the shared key axis
    assert out.split == 1
    assert allclose(out.toarray(), x @ y)


def test_matmul_bad_shapes_raise(mesh):
    b = bolt.array(_x(), mesh)
    with pytest.raises(ValueError):
        b @ np.ones((7, 2))        # contraction mismatch: numpy's ValueError


def test_inplace_forms(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    orig = b
    b += 1
    b *= 2
    b //= 1
    assert allclose(b.toarray(), ((x + 1) * 2) // 1)
    # functional rebinding: the original array is untouched (jax immutability)
    assert allclose(orig.toarray(), x)


def test_numpy_ufunc_dispatch(mesh):
    x = _x()
    b = bolt.array(x, mesh)
    out = np.sin(b)
    assert isinstance(out, type(b))
    assert out.deferred            # routed into the deferred map chain
    assert allclose(out.toarray(), np.sin(x))
    assert allclose(np.exp(b).toarray(), np.exp(x))
    assert allclose(np.add(b, 1).toarray(), x + 1)
    assert allclose(np.add(np.ones_like(x), b).toarray(), x + 1)
    assert allclose(np.maximum(b, 0).toarray(), np.maximum(x, 0))
    assert np.isnan(b).toarray().sum() == 0


def test_numpy_ufunc_parity_both_backends(mesh):
    x = _x()
    lo, tp = bolt.array(x), bolt.array(x, mesh)
    for uf in (np.sin, np.exp, np.sqrt, np.tanh):
        a = uf(abs(lo) + 1).toarray()
        c = uf(abs(tp) + 1).toarray()
        assert allclose(a, c)


def test_ufunc_unsupported_methods_raise(mesh):
    b = bolt.array(_x(), mesh)
    with pytest.raises(TypeError):
        np.add.at(b, [0], 1.0)     # in-place scatter: explicit no
    with pytest.raises(TypeError):
        np.add.reduce(b, out=np.empty(b.shape[1:]))
    with pytest.raises(TypeError):
        np.add.reduce(b, where=np.zeros(b.shape, bool))
    with pytest.raises(TypeError):
        np.add(b, 1, out=np.empty(b.shape))


def test_ufunc_reduce_parity(mesh):
    """np.add.reduce(b) answers identically on both backends (VERDICT r4
    missing-3: the TPU side used to raise where ndarray served it)."""
    x = _x()
    lo, tp = bolt.array(x), bolt.array(x, mesh)
    cases = [
        lambda b: np.add.reduce(b),                    # default axis=0
        lambda b: np.add.reduce(b, axis=None),         # all axes
        lambda b: np.add.reduce(b, axis=(0, 2)),
        lambda b: np.add.reduce(b, axis=1, keepdims=True),
        lambda b: np.add.reduce(b, axis=()),           # no-op reduce
        lambda b: np.maximum.reduce(b, initial=100.0),
        lambda b: np.multiply.reduce(b, axis=2),
        lambda b: np.hypot.reduce(b),                  # frompyfunc twin
        lambda b: np.hypot.reduce(b, axis=(0, 1)),     # sequential path
        lambda b: np.add.reduce(b, axis=(0, 1), initial=7.0),
        lambda b: np.logical_and.reduce(abs(b) > 0.01),
        lambda b: np.logical_xor.reduce(b > 0),        # key-axis parity
        lambda b: np.logical_xor.reduce(b > 0, axis=(0, 1)),
        lambda b: np.logical_xor.reduce(b > 0, axis=2),
        lambda b: np.add.reduce(b, axis=(), initial=7.0),
        lambda b: np.subtract.reduce(b, axis=(), initial=7.0),
        lambda b: np.subtract.reduce(b, axis=1),       # left-fold parity
        lambda b: np.add.reduce(b, where=np.True_),    # semantic default
        lambda b: np.add.reduce(b, initial=np.array(5.0)),  # 0-d initial
    ]
    for f in cases:
        a, c = np.asarray(f(lo)), np.asarray(f(tp).toarray())
        assert a.shape == c.shape
        assert allclose(a, c)
    out = np.add.reduce(tp, axis=0)
    assert isinstance(out, type(tp)) and out.split == 0
    # duplicate axes: numpy's exact ValueError on both backends
    for b in (lo, tp):
        with pytest.raises(ValueError, match="duplicate value in 'axis'"):
            np.add.reduce(b, axis=(0, 0))
        # non-reorderable multi-axis reduce: numpy's ValueError, never an
        # order-dependent sequential value
        with pytest.raises(ValueError, match="reorderable"):
            np.subtract.reduce(b, axis=(0, 1))
    # numpy's generic non-reorderable reduce uses a buffer-striding order
    # that is not a fold at all (power.reduce([2,3,2,1.5]) == 2**1.5);
    # the TPU backend rejects loudly instead of serving different numbers
    with pytest.raises(TypeError):
        np.power.reduce(tp)
    with pytest.raises(TypeError):
        np.arctan2.reduce(tp)
    # bitwise_xor over the SHARDED key axis: XLA has no cross-partition
    # xor combine — loud reject; value-axis reduce still serves
    ti = bolt.array((np.arange(24).reshape(8, 3)), tp.mesh)
    with pytest.raises(TypeError):
        np.bitwise_xor.reduce(ti)
    assert allclose(np.asarray(np.bitwise_xor.reduce(ti, axis=1).toarray()),
                    np.bitwise_xor.reduce(np.arange(24).reshape(8, 3),
                                          axis=1))


def test_ufunc_accumulate_reduceat_parity(mesh):
    x = _x()
    lo, tp = bolt.array(x), bolt.array(x, mesh)
    cases = [
        lambda b: np.add.accumulate(b),                # default axis=0
        lambda b: np.add.accumulate(b, axis=2),
        lambda b: np.multiply.accumulate(b, axis=1),
        lambda b: np.maximum.accumulate(b),
        lambda b: np.add.reduceat(b, [0, 2, 5], axis=0),
        lambda b: np.add.reduceat(b, [0, 3], axis=1),
    ]
    for f in cases:
        a, c = np.asarray(f(lo)), np.asarray(f(tp).toarray())
        assert a.shape == c.shape
        assert allclose(a, c)
    out = np.add.accumulate(tp)
    assert isinstance(out, type(tp)) and out.split == tp.split
    # distributed index operand: fused on device, never np.asarray'd
    idx = bolt.array(np.array([0, 2, 5]), tp.mesh)
    got = np.add.reduceat(tp, idx)
    assert allclose(np.asarray(got.toarray()),
                    np.add.reduceat(np.asarray(lo), [0, 2, 5], axis=0))
    # host indices validate up front: numpy's IndexError on both
    # backends, not jax's silent clamp
    for b in (lo, tp):
        with pytest.raises(IndexError):
            np.add.reduceat(b, [0, 99], axis=0)
        with pytest.raises(IndexError):
            np.add.reduceat(b, [0, -2], axis=0)
        with pytest.raises(ValueError, match="does not allow multiple"):
            np.add.accumulate(b, axis=None)
        with pytest.raises(ValueError, match="does not allow multiple"):
            np.add.reduceat(b, [0], axis=None)
    # zero-length axis: index 0 is out of bounds on BOTH backends
    z_lo, z_tp = bolt.array(np.zeros((0, 3))), bolt.array(
        np.zeros((0, 3)), mesh)
    for b in (z_lo, z_tp):
        with pytest.raises(IndexError):
            np.add.reduceat(b, [0], axis=0)
    # where=1 is numpy's semantic default: served on both backends
    assert allclose(np.asarray(np.add.reduce(tp, where=1).toarray()),
                    np.add.reduce(np.asarray(lo), where=1))


def test_ufunc_outer_parity(mesh):
    x = _x()[:, 0, 0]              # 1-d keys
    w = np.linspace(-1.0, 1.0, 3)
    lo, tp = bolt.array(x), bolt.array(x, mesh)
    for f in (lambda b: np.subtract.outer(b, w),
              lambda b: np.add.outer(w, b),
              lambda b: np.add.outer(b, w, dtype=np.float32),
              lambda b: np.multiply.outer(b, np.ones((2, 2)))):
        a, c = np.asarray(f(lo)), np.asarray(f(tp).toarray())
        assert a.shape == c.shape
        assert allclose(a, c)
    # keys survive only on the leading operand
    assert np.subtract.outer(tp, w).split == 1
    assert np.add.outer(w, tp).split == 0


def test_matmul_2d_keeps_row_keys(mesh):
    # the canonical row-sharded case: (N, d) @ (d, k) keeps keys on N
    rs = np.random.RandomState(19)
    x, w = rs.randn(8, 5), rs.randn(5, 3)
    b = bolt.array(x, mesh)
    out = b @ w
    assert out.split == 1
    assert allclose(out.toarray(), x @ w)
    # matrix @ vector too
    v = rs.randn(5)
    out = b @ v
    assert out.split == 1
    assert allclose(out.toarray(), x @ v)
    # reverse 2-d contracts the keys: re-keyed to split=0
    y = rs.randn(3, 8)
    out = y @ b
    assert out.split == 0
    assert allclose(out.toarray(), y @ x)


def test_multi_output_ufuncs_unsupported(mesh):
    b = bolt.array(_x(), mesh)
    with pytest.raises(TypeError):
        np.modf(b)
    with pytest.raises(TypeError):
        np.divmod(b, 2.0)


def test_mesh_mismatch_raises(mesh):
    import jax
    x = _x()
    b = bolt.array(x, mesh)
    half = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("k",))
    c = bolt.array(x, half)
    with pytest.raises(ValueError, match="different meshes"):
        b + c
    with pytest.raises(ValueError, match="different meshes"):
        b.concatenate(c)
    with pytest.raises(ValueError, match="different meshes"):
        b @ c.values.reshape(5, 4)
    # explicit move works
    out = b + c.tolocal().totpu(context=mesh)
    assert bolt.allclose(out.toarray(), x * 2)


def test_jax_array_operands_no_host_roundtrip(mesh):
    # a jax.Array operand must feed the compiled op directly — routing it
    # through np.asarray would fetch it to host and re-upload on EVERY
    # call (measured 12 s/call for a 0.27 GB weight through a remote
    # attach). np.asarray on a non-fully-addressable array would also
    # simply crash, so this path is correctness too, not just speed.
    import jax
    import jax.numpy as jnp
    import numpy as np_mod
    x = _x()
    b = bolt.array(x, mesh)
    w = jnp.asarray(np_mod.ones(x.shape[1:], np_mod.float32))
    orig = np_mod.asarray
    seen = []
    def spy(a, *args, **kw):
        if isinstance(a, jax.Array):
            seen.append(type(a))
        return orig(a, *args, **kw)
    np_mod.asarray = spy
    try:
        out1 = (b + w).toarray()
        wj = jnp.asarray(np_mod.ones((5, 3), np_mod.float32))
        out2 = (b @ wj).toarray()
        b.concatenate(jnp.asarray(x.astype(np_mod.float32)))
    finally:
        np_mod.asarray = orig
    assert not seen, "jax operand was bounced through np.asarray"
    assert allclose(out1, x + 1)
    assert allclose(out2, x @ np_mod.ones((5, 3)))


def test_foreign_device_operand_falls_back(mesh):
    # a jax.Array committed OUTSIDE the mesh's devices must take the host
    # coercion path (feeding it to the mesh-sharded jit would raise
    # "incompatible devices"), preserving pre-round-2 behavior
    import jax
    x = _x()
    half = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("k",))
    b = bolt.array(x, half)
    w = jax.device_put(np.ones(x.shape), jax.devices()[6])
    assert allclose((b + w).toarray(), x + 1)


def test_dot_precision_option(mesh):
    # dot(precision=) opts into faster MXU passes; "highest" (default)
    # stays ulp-parity with the oracle, "default" is allclose at ~1e-2
    x = np.random.RandomState(70).randn(32, 16).astype(np.float32)
    w = np.random.RandomState(71).randn(16, 8).astype(np.float32)
    b = bolt.array(x, mesh)
    hi = b.dot(w)
    fast = b.dot(w, precision="default")
    ref = x @ w
    assert np.allclose(np.asarray(hi.toarray()), ref, rtol=1e-6, atol=1e-6)
    assert np.allclose(np.asarray(fast.toarray()), ref, rtol=3e-2, atol=3e-2)
    # distinct precisions are distinct compiled programs
    from bolt_tpu.tpu.array import _JIT_CACHE
    assert sum(1 for k in _JIT_CACHE
               if k[0] == "dot" and k[1] == (32, 16)) >= 2
