"""TPU-production dtype mode: x64 DISABLED (the suite's conftest enables
x64 for bit-parity with the NumPy oracle; real TPU sessions run without
it, where float64 requests canonicalise to float32 at construction —
docs/MIGRATION.md "Dtypes").  Runs in a subprocess so the main process's
x64 config is untouched."""

import os
import subprocess
import sys

import numpy as np

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
import jax
jax.config.update("jax_platforms", "cpu")
assert not jax.config.jax_enable_x64

import numpy as np
import bolt_tpu as bolt

mesh = jax.make_mesh((8,), ("k",))
x64 = np.random.RandomState(0).randn(64, 6, 4)          # float64 input

b = bolt.array(x64, mesh, axis=(0,))
assert b.dtype == np.float32, b.dtype                   # canonicalised
x32 = x64.astype(np.float32)
assert np.array_equal(b.toarray(), x32)

# the full pipeline stays f32 and matches the f32 oracle
m = b.map(lambda v: v * 2 + 1)
assert m.dtype == np.float32
assert np.allclose(m.toarray(), x32 * 2 + 1)
# f32-only accumulation differs from numpy's pairwise order by a few
# ulps: tolerance reflects the documented non-bit-exact f32 mode
assert np.allclose(np.asarray(b.mean(axis=(0,)).toarray()),
                   x32.mean(axis=0), rtol=1e-5, atol=1e-6)
st = b.stats()
assert np.allclose(np.asarray(st.mean()), x32.mean(axis=0),
                   rtol=1e-5, atol=1e-6)

s = b.swap((0,), (0,))
assert s.dtype == np.float32

f = b.filter(lambda v: v.mean() > 0)
keep = x32[x32.mean(axis=(1, 2)) > 0]
assert f.shape == keep.shape and np.allclose(f.toarray(), keep)

# constructors: f64 request comes back f32, ints survive untouched
o = bolt.ones((8, 4), mesh, dtype=np.float64)
assert o.dtype == np.float32
i = bolt.array(np.arange(8, dtype=np.int64).reshape(8, 1), mesh)
assert i.dtype == np.int32                              # jax canonical int

# linalg family under f32-only
from bolt_tpu.ops import pca, tallskinny_svd
scores, comps, svals = pca(b.map(lambda v: v.reshape(24)), k=2)
ref = np.linalg.svd(x32.reshape(64, 24).astype(np.float64),
                    compute_uv=False)[:2]
assert np.allclose(svals, ref, rtol=1e-4)
u, s_, vh = tallskinny_svd(np.asarray(x64.reshape(384, 4)))
assert np.asarray(u).dtype == np.float32

# order statistics, covariance and ndarray-parity methods stay f32-clean
from bolt_tpu.ops import cov
assert b.median().dtype == np.float32
assert np.allclose(np.asarray(b.quantile(0.5).toarray()),
                   np.median(x32, axis=0), atol=1e-6)
assert np.allclose(cov(b.map(lambda v: v.reshape(24))),
                   np.cov(x32.reshape(64, 24).astype(np.float64),
                          rowvar=False), rtol=1e-3, atol=1e-5)
assert np.array_equal(np.asarray(b.argmax(axis=0).toarray()),
                      np.argmax(x32, axis=0))
assert b.clip(-0.5, 0.5).dtype == np.float32
assert np.allclose(np.asarray(b.cumsum(axis=1).toarray()),
                   x32.cumsum(axis=1), rtol=1e-5, atol=1e-5)

# halo filters stay f32 and match the f32 local oracle (taps are python
# floats — weakly typed, no silent f64 promotion on either backend)
from bolt_tpu.ops import smooth
sm = smooth(b, 3, axis=(0,), size=(3,))
assert sm.dtype == np.float32
lo = smooth(bolt.array(x32), 3, axis=(0,), size=(3,))
assert lo.dtype == np.float32
assert np.allclose(sm.toarray(), lo.toarray(), rtol=1e-6, atol=1e-6)

# round-2 surfaces under f32-only production mode
w64 = np.random.RandomState(1).randn(4, 3)              # f64 operand
mm = b @ w64
assert mm.dtype == np.float32                           # no silent f64
assert np.allclose(mm.toarray(), x32 @ w64.astype(np.float32),
                   rtol=1e-5, atol=1e-5)
assert b.dot(w64).dtype == np.float32
assert np.sin(b).dtype == np.float32                    # ufunc dispatch
assert (b // 1.0).dtype == np.float32
assert np.array_equal(np.asarray(b.argsort(axis=0, kind="stable").toarray()),
                      x32.argsort(axis=0, kind="stable"))
# stats() through the fused_welford kernel path (128-aligned shard):
# f32 moments, parity with the f32 oracle
from bolt_tpu.ops.kernels import welford_plan
xk = np.random.RandomState(2).randn(32, 4, 128)
assert welford_plan((32 // 8,) + xk.shape[1:], 4) is not None  # kernel engages
bk = bolt.array(xk, mesh)
stk = bk.stats()
xk32 = xk.astype(np.float32)
assert np.asarray(stk.mean()).dtype == np.float32
assert np.allclose(np.asarray(stk.mean()), xk32.mean(axis=0),
                   rtol=1e-5, atol=1e-6)
assert np.allclose(np.asarray(stk.variance()), xk32.var(axis=0),
                   rtol=1e-4, atol=1e-5)

# grouped/set ops under f32-only
from bolt_tpu.ops import bincount, histogram, segment_reduce, unique
glabels = np.arange(64) % 4
gs = segment_reduce(b, glabels, op="mean")
assert gs.dtype == np.float32
assert np.allclose(np.asarray(gs.toarray()),
                   np.stack([x32[glabels == g].mean(axis=0)
                             for g in range(4)]), rtol=1e-5, atol=1e-6)
# int-input mean promotes through the CANONICAL float on BOTH backends:
# f32 here (x64 off), so the oracle and the TPU path agree on dtype
ints = np.arange(24, dtype=np.int32).reshape(8, 3)
ilabels = np.arange(8) % 2
for ib in (bolt.array(ints), bolt.array(ints, mesh)):
    im = segment_reduce(ib, ilabels, op="mean")
    assert np.asarray(im.toarray()).dtype == np.float32, ib.mode
iv = bolt.array((np.abs(x64) * 3).astype(np.int32), mesh)
assert np.array_equal(bincount(iv),
                      np.bincount((np.abs(x32) * 3).astype(np.int32).ravel()))
cu, eu = histogram(b, bins=8)
assert cu.dtype == np.int64 and cu.sum() == x32.size
uu = unique(bolt.array(np.floor(x64 * 2), mesh))
assert np.array_equal(uu, np.unique(np.floor(x32 * 2)))

# round-3 surfaces under f32-only production mode
bs = bolt.array(x64, mesh)
st = bs.set((0, slice(None), [0, 2]), 9.0)
assert st.dtype == np.float32
xs = x32.copy(); xs[0][:, [0, 2]] = 9.0
assert np.allclose(st.toarray(), xs)
srt = bolt.array(x64, mesh)
assert srt.sort(axis=1) is None and srt.dtype == np.float32
assert np.allclose(srt.toarray(), np.sort(x32, axis=1))
ns = np.sum(bs)                              # np dispatch, device-served
assert ns.mode == "tpu" and np.asarray(ns.toarray()).dtype == np.float32
vq = bs.quantile([0.25, 0.75])
assert vq.dtype == np.float32
assert np.allclose(np.asarray(vq.toarray()),
                   np.quantile(x32, [0.25, 0.75], axis=0), atol=1e-6)
nz = bs.map(lambda v: (v > 1.5).astype(np.int32)).nonzero()
assert all(i.dtype == np.int64 for i in nz)
assert np.array_equal(np.stack(nz, 1),
                      np.stack((x32 > 1.5).nonzero(), 1))
sm2 = smooth(bs, 3, axis=(0, 1))             # sepfilter kernel path
assert sm2.dtype == np.float32
lo2 = smooth(bolt.array(x32), 3, axis=(0, 1))
assert np.allclose(sm2.toarray(), lo2.toarray(), rtol=1e-5, atol=1e-6)
tgt = np.empty(bs.shape, np.float32)
assert bs.toarray(out=tgt) is tgt and np.array_equal(tgt, x32)

print("X64-OFF-OK")
"""


def test_pipeline_without_x64():
    env = dict(os.environ)
    env.pop("JAX_ENABLE_X64", None)
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "X64-OFF-OK" in out.stdout
