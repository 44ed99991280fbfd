"""Pallas kernel tests (interpret mode on the CPU mesh, and compile-only
for the described v5e): correctness against NumPy and the fallbacks."""

import re

import numpy as np
import pytest

import jax.numpy as jnp

from bolt_tpu.ops import kernels


def test_svdvals_tall_skinny_matches_numpy():
    import numpy as np
    from bolt_tpu.ops import svdvals
    rs = np.random.RandomState(9)
    x = rs.randn(1024, 16).astype(np.float32)
    got = np.asarray(svdvals(jnp.asarray(x)))
    expect = np.linalg.svd(x, compute_uv=False)
    assert np.allclose(got, expect, rtol=1e-3, atol=1e-3)
    # batched
    xb = rs.randn(4, 512, 8).astype(np.float32)
    gotb = np.asarray(svdvals(jnp.asarray(xb)))
    expectb = np.stack([np.linalg.svd(m, compute_uv=False) for m in xb])
    assert np.allclose(gotb, expectb, rtol=1e-3, atol=1e-3)
    # wide input falls back to full SVD
    xw = rs.randn(8, 64).astype(np.float32)
    assert np.allclose(np.asarray(svdvals(jnp.asarray(xw))),
                       np.linalg.svd(xw, compute_uv=False), rtol=1e-3, atol=1e-3)


def test_tallskinny_pca_reconstructs_spectrum():
    import numpy as np
    from bolt_tpu.ops import tallskinny_pca
    rs = np.random.RandomState(10)
    x = rs.randn(2048, 12).astype(np.float32)
    comps, svals = tallskinny_pca(jnp.asarray(x), k=5)
    u, s, vt = np.linalg.svd(x, full_matrices=False)
    assert np.allclose(np.asarray(svals), s[:5], rtol=1e-3, atol=1e-3)
    # components match up to sign
    for i in range(5):
        c = np.asarray(comps)[:, i]
        assert min(np.linalg.norm(c - vt[i]), np.linalg.norm(c + vt[i])) < 1e-2


def test_svdvals_dtype_breadth():
    import numpy as np
    from bolt_tpu.ops import svdvals, tallskinny_pca
    import pytest
    rs = np.random.RandomState(11)
    # float64 under x64 must take the Gram path without TypeError
    x64 = rs.randn(512, 8)
    got = np.asarray(svdvals(jnp.asarray(x64)))
    assert np.allclose(got, np.linalg.svd(x64, compute_uv=False), rtol=1e-6)
    # complex: Gram needs the conjugate transpose; spectrum is real
    xc = (rs.randn(512, 8) + 1j * rs.randn(512, 8)).astype(np.complex128)
    gotc = np.asarray(svdvals(jnp.asarray(xc)))
    assert not np.iscomplexobj(gotc)
    assert np.allclose(gotc, np.linalg.svd(xc, compute_uv=False), rtol=1e-6)
    # wide input to tallskinny_pca is rejected, not silently wrong
    with pytest.raises(ValueError):
        tallskinny_pca(jnp.asarray(rs.randn(8, 64)))


# ----------------------------------------------------------------------
# fused_welford: the single-HBM-pass moments kernel (round 2) and its
# wiring into stats()
# ----------------------------------------------------------------------

def test_fused_welford_direct():
    from bolt_tpu.ops.kernels import fused_welford, welford_plan
    for shape in [(64, 256), (128, 4, 128), (96, 8, 2, 128)]:
        x = np.random.RandomState(1).randn(*shape).astype(np.float32)
        plan = welford_plan(shape, 4)
        assert plan is not None, shape
        mu, m2, mn, mx = (np.asarray(v) for v in fused_welford(jnp.asarray(x)))
        assert np.allclose(mu, x.mean(axis=0), rtol=1e-5, atol=1e-6)
        assert np.allclose(m2, ((x - x.mean(axis=0)) ** 2).sum(axis=0),
                           rtol=1e-4, atol=1e-4)
        assert np.array_equal(mn, x.min(axis=0))
        assert np.array_equal(mx, x.max(axis=0))


def test_fused_welford_fallbacks():
    from bolt_tpu.ops.kernels import fused_welford
    assert fused_welford(jnp.zeros((64, 100))) is None       # unaligned
    assert fused_welford(jnp.zeros((64, 128), jnp.int32)) is None
    assert fused_welford(jnp.zeros((1, 128))) is None        # one row


def test_stats_kernel_path_parity(mesh):
    # shard shapes chosen so welford_plan ENGAGES inside the shard_map
    # body (128-aligned minor dim, >=2 local rows) — the stats() result
    # must match the local oracle either way
    import bolt_tpu as bolt
    from bolt_tpu.ops.kernels import welford_plan
    x = np.random.RandomState(2).randn(32, 4, 128)
    shard_shape = (32 // 8,) + x.shape[1:]
    assert welford_plan(shard_shape, x.itemsize) is not None
    b, lo = bolt.array(x, mesh), bolt.array(x)
    for axes in [(0,), (0, 1)]:
        t, a = b.stats(axis=axes), lo.stats(axis=axes)
        assert np.allclose(t.mean(), a.mean())
        assert np.allclose(t.variance(), a.variance())
        assert np.allclose(t.stdev(), a.stdev())
        assert np.array_equal(t.min(), a.min())
        assert np.array_equal(t.max(), a.max())
        assert t.count() == a.count()


def test_sepfilter1d_parity_all_axes():
    # the one-HBM-pass window kernel vs a numpy oracle on every axis
    # (interpret mode off-TPU; same code path as hardware)
    from bolt_tpu.ops.kernels import sepfilter1d
    rs = np.random.RandomState(60)
    x = jnp.asarray(rs.randn(6, 16, 256).astype(np.float32))
    taps = np.asarray([0.25, 0.5, 0.25])

    def oracle(a, ax, taps, mode):
        pad = [(0, 0)] * a.ndim
        pad[ax] = (len(taps) // 2,) * 2
        ap = np.pad(np.asarray(a), pad, mode=mode)
        out = np.zeros_like(np.asarray(a))
        for off, t in enumerate(taps):
            sl = [slice(None)] * a.ndim
            sl[ax] = slice(off, off + a.shape[ax])
            out += ap[tuple(sl)] * t
        return out

    for ax in (0, 1, 2):
        got = sepfilter1d(x, taps, ax, interpret=True)
        assert got is not None, ax
        assert np.allclose(np.asarray(got), oracle(x, ax, taps, "constant"),
                           rtol=1e-5, atol=1e-6), ax
        # Mosaic lowers no other numpy-pad mode (jax 0.9.0): the kernel
        # declines and the halo-chunked path serves them
        for mode in ("edge", "reflect", "symmetric"):
            assert sepfilter1d(x, taps, ax, mode=mode,
                               interpret=True) is None, (ax, mode)


def test_sepfilter1d_gates():
    from bolt_tpu.ops import kernels
    # non-float input, unaligned minor dim: kernel declines
    assert kernels.sepfilter1d(jnp.ones((8, 256), jnp.int32),
                               [1.0], 0, interpret=True) is None
    assert kernels.sepfilter1d(jnp.ones((8, 100), jnp.float32),
                               [0.5, 0.5, 0.0], 0, interpret=True) is None
    # minor-axis windows wider than the direct-path crossover (9) take
    # the banded-matmul path (round 4)...
    wide = [1.0 / 15] * 15
    x = jnp.asarray(np.random.RandomState(61).randn(4, 128, 256)
                    .astype(np.float32))
    got = kernels.sepfilter1d(x, wide, 2, interpret=True)
    assert got is not None
    ap = np.pad(np.asarray(x), ((0, 0), (0, 0), (7, 7)))
    expect = sum(ap[:, :, o:o + 256] * w for o, w in enumerate(wide))
    assert np.allclose(np.asarray(got), expect, rtol=1e-5, atol=1e-6)
    # ...which no longer needs the second-minor dim aligned (the old
    # transpose detour did)
    x2 = jnp.asarray(np.random.RandomState(62).randn(4, 100, 256)
                     .astype(np.float32))
    got2 = kernels.sepfilter1d(x2, wide, 2, interpret=True)
    assert got2 is not None
    ap2 = np.pad(np.asarray(x2), ((0, 0), (0, 0), (7, 7)))
    exp2 = sum(ap2[:, :, o:o + 256] * w for o, w in enumerate(wide))
    assert np.allclose(np.asarray(got2), exp2, rtol=1e-5, atol=1e-6)
    # a radius past one lane tile keeps the transpose detour, which DOES
    # need the second-minor dim aligned — unaligned declines
    huge = [1.0 / 259] * 259
    assert kernels.sepfilter1d(x2, huge, 2, interpret=True) is None
    got3 = kernels.sepfilter1d(x, huge, 2, interpret=True)
    ap3 = np.pad(np.asarray(x), ((0, 0), (0, 0), (129, 129)))
    exp3 = sum(ap3[:, :, o:o + 256] * w for o, w in enumerate(huge))
    assert np.allclose(np.asarray(got3), exp3, rtol=1e-5, atol=1e-5)
    # an unaligned lane dim with an unaligned second-minor dim declines
    # every path (band needs the lane 128-aligned, the detour needs the
    # second-minor)
    assert kernels.sepfilter1d(jnp.ones((4, 100, 250), jnp.float32),
                               wide, 2, interpret=True) is None
    # plan gating mirrors the direct-path cap
    assert kernels.sepfilter_plan((4, 128, 256), 4, 2, w=11) is None
    assert kernels.sepfilter_plan((4, 128, 256), 4, 2, w=9) is not None


def test_lane_band_paths():
    # the banded-matmul lane filter (round 4): pallas and XLA-conv
    # forms vs the shifted-slice oracle, exact to machine precision
    from bolt_tpu.ops import kernels
    from bolt_tpu.ops.overlap import _filter1d
    rs = np.random.RandomState(63)
    for shape, w in [((4, 6, 256), 17), ((3, 128), 11), ((2, 256), 255)]:
        x = rs.randn(*shape)
        taps = tuple((rs.rand(w) / w).tolist())
        want = _filter1d(x, len(shape) - 1, taps, "constant", np)
        for fn in (lambda a: kernels.lane_band_pallas(a, taps,
                                                      interpret=True),
                   lambda a: kernels.lane_band_conv(a, taps)):
            got = fn(jnp.asarray(x))
            assert got is not None, (shape, w)
            assert np.allclose(np.asarray(got), want, rtol=1e-12,
                               atol=1e-12), (shape, w)
    # refusals: unaligned lane dim, radius past one tile, int dtype
    assert kernels.lane_band_pallas(jnp.ones((4, 100)), (0.5,) * 17,
                                    interpret=True) is None
    assert kernels.lane_band_conv(jnp.ones((4, 256)), (0.1,) * 259) is None
    assert kernels.lane_band_conv(jnp.ones((4, 256), jnp.int32),
                                  (1.0,) * 11) is None
    # capability gate includes the band path — and is mode-aware, so it
    # cannot disagree with what sepfilter1d actually accepts
    assert kernels.sepfilter_capable((4, 100, 256), 4, 2, 17)
    assert not kernels.sepfilter_capable((4, 100, 250), 4, 2, 17)
    assert not kernels.sepfilter_capable((4, 128, 256), 4, 2, 17,
                                         mode="reflect")
    assert kernels.sepfilter_capable((4, 128, 256), 4, 2, 259)  # detour


# ---------------------------------------------------------------------
# compile-only: every pallas_call in ops/kernels.py through Mosaic
# ---------------------------------------------------------------------

_F32 = jnp.float32
_BOX9, _BOX25 = (1.0 / 9,) * 9, (1.0 / 25,) * 25
_MOSAIC_CASES = [
    ("welford-f32", lambda x: kernels.fused_welford(x, interpret=False),
     [((64, 64, 128), _F32)]),
    ("welford-bf16", lambda x: kernels.fused_welford(x, interpret=False),
     [((64, 64, 128), jnp.bfloat16)]),
    ("lane_band", lambda x: kernels.lane_band_pallas(
        x, _BOX25, interpret=False), [((16, 64, 256), _F32)]),
    ("lane_band-one-tile", lambda x: kernels.lane_band_pallas(
        x, _BOX25, interpret=False), [((16, 64, 128), _F32)]),
] + [
    ("sepfilter-ax%d-%dtap" % (ax, len(taps)),
     lambda x, ax=ax, taps=taps: kernels.sepfilter1d(
         x, taps, ax, interpret=False), [((16, 64, 256), _F32)])
    for ax in (0, 1, 2) for taps in (_BOX9, _BOX25)
] + [
    # ops/linalg.py's sweep chain (ISSUE 27): 64 is the benchmark's, 8 and
    # 2 pad the seats' halves to a tile, 5 is padded to 6 by the dummy
    ("jacobi-%s-%dx%dx%d" % (("vectors" if vec else "values",) + shape),
     lambda a, vec=vec: _jacobi_eigh(a, vectors=vec), [(shape, _F32)])
    for shape, vec in [((80, 64, 64), False), ((80, 64, 64), True),
                       ((300, 8, 8), True), ((300, 5, 5), False),
                       ((1000, 2, 2), True)]
] + [
    # ops/linalg.py's Gram pass (ISSUE 29): 64 is the benchmark's width, a
    # narrower one puts 64 // d row groups side by side; the second 64 has
    # a tail for dot_general behind the kernel's rows
    ("packed_gram-%dx%dx%d" % shape, lambda x: _svdvals(x), [(shape, _F32)])
    for shape in [(6, 16384, 64), (3, 20000, 64), (5, 16384, 32),
                  (2, 65536, 16), (1, 65536, 8)]
] + [
    # ops/select.py's selection on a tile held in VMEM (ISSUE 40): a block
    # of the benchmark's cell (its tiles of 64 records leave an edge of
    # 40), the shortest record it takes, one of 130 lane-groups (a loop of
    # unrolled spans), the longest a tile of 8 fits, a batch under 8, and
    # the percentile that needs no neighbour
    ("percentile_select-%dx%d-p%g" % (shape + (perc,)),
     lambda x, perc=perc: _percentile(x, perc), [(shape, _F32)])
    for shape, perc in [((5352, 10240), 20.0), ((5352, 10240), 100.0),
                        ((4096, 1024), 20.0), ((100, 16640), 20.0),
                        ((21, 98304), 50.0), ((5, 1280), 99.9)]
]


def _percentile(x, perc):
    import jax
    from bolt_tpu.ops import select
    return jax.vmap(lambda v: select.percentile(v, perc, 0, True))(x)


def _jacobi_eigh(a, vectors=False):
    from bolt_tpu.ops import jacobi_eigh
    return jacobi_eigh(a, vectors=vectors)


def _svdvals(x):
    from bolt_tpu.ops import svdvals
    return svdvals(x)


@pytest.fixture(scope="module")
def v5e_device():
    """One device of a compile-only v5e topology: libtpu compiles for
    it with no chip attached (and beside an attached one — checked on
    the chip host, PR 21)."""
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                        platform="tpu")
    return topo.devices[0]


@pytest.mark.parametrize("name,fn,avals", _MOSAIC_CASES,
                         ids=[c[0] for c in _MOSAIC_CASES])
def test_kernel_compiles_for_v5e(v5e_device, name, fn, avals):
    # interpret mode proves the arithmetic; only Mosaic proves the
    # kernel exists on the chip (uint8 -> f32 had no lowering, PR 21)
    import jax
    where = jax.sharding.SingleDeviceSharding(v5e_device)
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=where)
            for shape, dtype in avals]
    with jax.enable_x64(False):     # the chip's numerics; Mosaic has no i64
        text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, name    # the plan engaged


# ---------------------------------------------------------------------
# compile-only: a getitem window traced inside the statistic that reads
# it (ISSUE 25) must stay ONE fusion over the resident base on the chip
# — no window-sized slice or relayout copy written to HBM on the way
# ---------------------------------------------------------------------

_STACK = (3200, 200, 64, 64)          # the benchmark's resident stack


def _library_std(x, axis):
    # the expression the library's own statistic tables trace (ISSUE 61)
    from bolt_tpu.tpu.multistat import _OPS
    return _OPS["std"](x, axis=axis)


_WINDOW_CASES = [
    # name, (starts, sizes) of the window, statistic, its axes
    ("slice_mean", ((97,), (16,)), jnp.mean, (0, 1, 2, 3)),
    ("slice_std", ((41,), (16,)), _library_std, (0, 1, 2, 3)),
    ("slice_max", ((13,), (16,)), jnp.max, (0,)),
    ("roi_trace", ((0, 11, 5, 3), (3200, 8, 8, 8)), jnp.mean, (1, 2, 3)),
]


@pytest.mark.parametrize("name,window,op,axes", _WINDOW_CASES,
                         ids=[c[0] for c in _WINDOW_CASES])
def test_window_statistic_is_one_fusion_on_v5e(v5e_device, name, window,
                                               op, axes):
    import re
    import jax
    from bolt_tpu.tpu.array import _Window, _chain_apply
    starts, sizes = window
    funcs = (_Window(starts, sizes, (), 1),)
    win_shape = funcs[0].out_shape(_STACK)
    win_bytes = 4 * int(np.prod(win_shape))

    def stat(data):                    # the stat program's traced body
        return op(_chain_apply(funcs, 1, data), axis=axes)

    where = jax.sharding.SingleDeviceSharding(v5e_device)
    with jax.enable_x64(False):
        compiled = jax.jit(stat).lower(jax.ShapeDtypeStruct(
            _STACK, _F32, sharding=where)).compile()
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    # the whole base comes in, the small answer goes out
    assert "f32[%s]" % ",".join(map(str, _STACK)) in entry.splitlines()[0]
    # nothing of the window's size is written outside a fusion: no
    # standalone slice, no relayout copy of it, no temporary to hold it
    shaped = "f32[%s]" % ",".join(map(str, win_shape))
    outside = [ln for ln in entry.splitlines()[1:]
               if re.search(r"= %s\S* (slice|copy|fusion)\(" %
                            re.escape(shaped), ln)]
    assert not outside, outside
    assert not re.search(r"= \S+ slice\(", entry), name
    assert compiled.memory_analysis().temp_size_in_bytes < win_bytes


# ---------------------------------------------------------------------
# compile-only: var()/std() of real floating data read what they reduce
# ONCE (ISSUE 61).  ``jnp.std`` is a mean and then the squared deviations
# from it, two reductions XLA cannot fuse: 838.9 MB for a 16-record window
# of the stack (whose key axis is on the lanes, so 16 records cost a
# 128-lane column, 419.4 MB a read) and 20.97 GB for the whole stack.  The
# library's expression (tpu/moments.py) is a pilot over a small corner and
# ONE multi-output fusion that takes both shifted moments from one read
# ---------------------------------------------------------------------

_COLUMN = 4 * 128 * 200 * 64 * 64     # a 128-lane column of the stack
_ONE_PASS_CASES = [
    # name, the window in front (or none), its axes, bytes of ONE read
    ("window_std", ((41,), (16,)), (0, 1, 2, 3), _COLUMN),
    ("stack_std", None, (0, 1, 2, 3), 4 * int(np.prod(_STACK))),
    ("stack_std_a_record", None, (1, 2, 3), 4 * int(np.prod(_STACK))),
]


@pytest.mark.parametrize("name,window,axes,one_read", _ONE_PASS_CASES,
                         ids=[c[0] for c in _ONE_PASS_CASES])
def test_std_reads_what_it_reduces_once_on_v5e(v5e_device, name, window,
                                               axes, one_read):
    import re
    import jax
    from bolt_tpu.tpu.array import _Window, _chain_apply
    funcs = () if window is None else (_Window(*window, (), 1),)
    win_bytes = (one_read if window is None else
                 4 * int(np.prod(funcs[0].out_shape(_STACK))))

    def compiled(op):
        def stat(data):
            return op(_chain_apply(funcs, 1, data), axis=axes)
        where = jax.sharding.SingleDeviceSharding(v5e_device)
        with jax.enable_x64(False):
            return jax.jit(stat).lower(jax.ShapeDtypeStruct(
                _STACK, _F32, sharding=where)).compile()

    ours, jnps = compiled(_library_std), compiled(jnp.std)
    # the two-pass form reads twice (what the ledger's two operations,
    # slice_reduce_fusion and multiply_reduce_fusion, were) ...
    assert jnps.cost_analysis()["bytes accessed"] >= 1.95 * one_read
    # ... the library's once, pilot included
    assert ours.cost_analysis()["bytes accessed"] <= 1.05 * one_read
    text = ours.as_text()
    entry = text[text.index("ENTRY"):]
    shaped = re.escape("f32[%s]" % ",".join(map(str, _STACK)))
    param = re.search(r"(%\S+) = " + shaped + r"\S* parameter\(0\)",
                      entry).group(1)
    readers = [ln for ln in entry.splitlines()
               if re.search(r" fusion\(%s[,)]" % re.escape(param), ln)]
    # two fusions take the base: the pilot's, with one result, and ONE
    # with the two sums as its results (no second reader at size)
    both = [ln for ln in readers
            if re.search(r"= \(f32\[[^ ]*, f32\[[^ ]*\) fusion\(", ln)]
    assert len(readers) == 2 and len(both) == 1, readers
    assert not re.search(r"= \S+ (slice|copy)\(%s" % re.escape(param), entry)
    assert ours.memory_analysis().temp_size_in_bytes < min(win_bytes, 1 << 20)


def test_std_on_four_chips_takes_the_sharded_axis_whole(v5e_device):
    # a pilot cut out of the SHARDED key axis is a static window GSPMD
    # re-shards by moving whole shards (1.39 GB of temporaries and 9.57
    # GB read a chip for the 14.42 GB stack, where jnp.std reads 7.55):
    # told the mesh, the expression corners the value axes alone and the
    # program is one local pass and small all-reduces
    import jax
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from bolt_tpu.tpu.multistat import _stat_expr
    topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                        platform="tpu")
    mesh = Mesh(np.array(topo.devices).reshape(4), ("k",))
    stack = (4400,) + _STACK[1:]
    shard = 4 * int(np.prod(stack)) // 4

    def stat(data):
        return _stat_expr(data, "std", (0, 1, 2, 3), False, None, None,
                          mesh, 1)

    with jax.enable_x64(False):
        compiled = jax.jit(stat, out_shardings=NamedSharding(
            mesh, P())).lower(jax.ShapeDtypeStruct(
                stack, _F32, sharding=NamedSharding(mesh, P("k")))).compile()
    text = compiled.as_text()
    assert "collective-permute" not in text and "all-gather" not in text
    assert "all-reduce" in text
    assert compiled.cost_analysis()["bytes accessed"] <= 1.05 * shard
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# ---------------------------------------------------------------------
# compile-only: a deferred filter folded into the terminal that reads it
# at HBM size (ISSUE 30).  TPC-H Q6 and Q1 over this chip's half of
# LINEITEM at SF 100, (300018951, 7) float32, which the chip holds with
# the rows on the lanes (9.60 GB), and BASELINE config 4 on the resident
# stack: each ONE program beside its argument, nothing row-sized stored
# (no temporary as large as one column, 1.2 GB; the stack's under 0.1
# GB), no gather and no sort
# ---------------------------------------------------------------------

_LINEITEM = (300018951, 7)


def _q6_pred(r):
    return ((r[0] >= 731) & (r[0] < 1096) & (r[3] >= 5) & (r[3] <= 7)
            & (r[1] < 24))


def _q6_value(r):
    return r[2] * r[3]


def _q1_pred(r):
    return r[0] <= 2436


def _q1_group(r):
    return (3 * r[6] + r[5]).astype(jnp.int32)


def _q1_terms(r):
    disc_price = r[2] * (100 - r[3])
    return (r[1], r[2], disc_price, disc_price * (100 + r[4]), r[3],
            jnp.ones_like(r[0]))


def _corner(v):
    return v[0, 0, :8].mean() > 0


def _plus_one(v):
    return v + 1


def _filter_of(funcs, pred, shape, post=(), out=None):
    from bolt_tpu.tpu.array import _Filter
    import jax
    out = shape[1:] if out is None else out
    return _Filter(None, funcs, pred, 1, tuple(shape[1:]), shape[0], _F32,
                   post, jax.ShapeDtypeStruct(tuple(out), _F32))


def _q6_program(data):
    from bolt_tpu.tpu.array import _masked_stat_expr
    flat, mask = _filter_of((), _q6_pred, _LINEITEM, (_q6_value,),
                            ()).records(data)
    return _masked_stat_expr("sum", flat, mask, mask, (0,), False, None,
                             (), _F32)


def _q1_program(data):
    from bolt_tpu.tpu.array import _grouped_fold_expr
    fp = _filter_of((), _q1_pred, _LINEITEM)
    flat, mask = fp.records(data)
    return _grouped_fold_expr("sum", flat, mask, _q1_group, _q1_terms, 6,
                              fp.mapped(data))


def _stack_filter_program(data):
    from bolt_tpu.tpu.array import _masked_stat_expr
    flat, mask = _filter_of((_plus_one,), _corner, _STACK).records(data)
    return _masked_stat_expr("sum", flat, mask,
                             mask.reshape((_STACK[0], 1, 1, 1)), (0,),
                             False, None, _STACK[1:], _F32)


_FOLD_CASES = [
    # name, the terminal's traced body, argument shape, temp limit (bytes)
    ("tpch_q6", _q6_program, _LINEITEM, 1.2e9),
    ("tpch_q1", _q1_program, _LINEITEM, 1.2e9),
    ("stack_filter_sum", _stack_filter_program, _STACK, 0.1e9),
]


@pytest.mark.parametrize("name,program,shape,limit", _FOLD_CASES,
                         ids=[c[0] for c in _FOLD_CASES])
def test_filter_folded_into_its_terminal_is_one_pass_on_v5e(
        v5e_device, name, program, shape, limit):
    import re
    import jax
    where = jax.sharding.SingleDeviceSharding(v5e_device)
    with jax.enable_x64(False):
        compiled = jax.jit(program).lower(jax.ShapeDtypeStruct(
            shape, _F32, sharding=where)).compile()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < limit, name
    assert not re.search(r"= \S+ (gather|sort)\(", text), name
    entry = text[text.index("ENTRY"):]
    if shape == _LINEITEM:
        # the table is held with the rows on the lanes, the seven columns
        # on eight sublanes: 9.60 GB for 8.40 logical, and ONE fusion
        # reads it
        assert "f32[300018951,7]{0,1:T(8,128)}" in entry.splitlines()[1]
        assert mem.argument_size_in_bytes == -(-300018951 // 128) * 128 * 8 * 4
        assert len(re.findall(r" fusion\(", entry)) == 1, name
    else:
        assert mem.argument_size_in_bytes == 4 * int(np.prod(shape))


def test_a_stacked_value_is_written_out_row_sized_on_v5e(v5e_device):
    """Why ``segment_reduce``'s docstring asks for a tuple of scalars on a
    table of thin records: the same six aggregates as one ``jnp.stack``ed
    vector are a (rows, 6) temporary that the fold then reads, which at
    this size does not fit beside the table."""
    import jax
    from bolt_tpu.tpu.array import _grouped_fold_expr
    rows = 30_000_001                 # a tenth: what fails must compile

    def program(data):
        fp = _filter_of((), _q1_pred, (rows, 7))
        flat, mask = fp.records(data)
        return _grouped_fold_expr(
            "sum", flat, mask, _q1_group,
            lambda r: jnp.stack(_q1_terms(r)), 6)

    where = jax.sharding.SingleDeviceSharding(v5e_device)
    with jax.enable_x64(False):
        compiled = jax.jit(program).lower(jax.ShapeDtypeStruct(
            (rows, 7), _F32, sharding=where)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > rows * 4 * 6


# ---------------------------------------------------------------------
# the fold over a table of thin records as one Mosaic kernel (ISSUE 31,
# ``tpu/fold.py``).  On this CPU mesh ``thin_fold`` lowers to the
# expressions (``_masked_stat_expr``, ``_grouped_fold_expr``); the kernel
# itself runs here in Pallas' TPU interpret mode, with short blocks,
# against those expressions on the same table.  Every value is a small
# integer, so each float32 sum is exact in any order and the two must
# agree to the digit
# ---------------------------------------------------------------------

def _thin_table(rows, c, dtype, seed=0):
    rs = np.random.RandomState(seed + 31 * c)
    return rs.randint(0, 40, size=(rows, c)).astype(dtype)


def _thin_filter(pred, shape, dtype, post=(), funcs=()):
    import jax
    from bolt_tpu.tpu.array import _Filter
    rec = jax.ShapeDtypeStruct(tuple(shape[1:]), dtype)
    for f in funcs:
        rec = jax.eval_shape(f, rec)
    out = rec
    for f in post:
        out = jax.eval_shape(f, out)
    return _Filter(None, tuple(funcs), pred, 1, tuple(rec.shape), shape[0],
                   rec.dtype, tuple(post), out)


def _fold_both_ways(fold, x, block=2048, chunk=1024):
    """``(by the expressions, by the kernel)`` on the CPU, x64 off as on
    the chip."""
    import jax
    from jax.experimental.pallas import tpu as pltpu
    from bolt_tpu.tpu import fold as tf
    old = tf._FOLD_BLOCK, tf._FOLD_CHUNK
    tf._FOLD_BLOCK, tf._FOLD_CHUNK = block, chunk
    try:
        with jax.enable_x64(False):
            assert tf._kernel_serves(fold, jax.ShapeDtypeStruct(
                x.shape, x.dtype))
            want = jax.jit(lambda d: tf._plain(fold, d))(x)
            with pltpu.force_tpu_interpret_mode():
                got = jax.jit(lambda d: tf._kernel_fold(fold, d))(x)
    finally:
        tf._FOLD_BLOCK, tf._FOLD_CHUNK = old
    return jax.tree.leaves(want), jax.tree.leaves(got)


def _last(r):
    return r[-1]


def _first_above_10(r):
    return r[0] > 10


def _first_below_0(r):
    return r[0] < 0


def _first_times_last(r):
    return r[0] * r[-1]


def _label_mod5_less_1(r):
    # -1 and 3 lie outside [0, 3): those rows join no group
    return (r[-1] % 5).astype(jnp.int32) - 1


def _two_terms(r):
    return (r[0], r[0] * r[-1] + 1)


def _stat(name, ddof=None, keepdims=False):
    return ((name, (0,), keepdims, ddof),)


def _thin_cases():
    from bolt_tpu.tpu.fold import Chain, Fold
    f32, i32 = np.float32, np.int32
    cases = []

    def add(name, rows, c, dtype, make, spoil=None):
        cases.append(pytest.param(rows, c, dtype, make, spoil, id=name))

    q6 = lambda sh, dt: Fold(_thin_filter(                  # noqa: E731
        _first_above_10, sh, dt, post=(_first_times_last,)), _stat("sum"))
    q1 = lambda sh, dt: Fold(_thin_filter(_first_above_10, sh, dt),  # noqa
                             group=("sum", _label_mod5_less_1,
                                    _two_terms, 3))
    for c in (1, 4, 7, 8):
        add("q6-width%d-ragged" % c, 2 * 2048 + 1234 + c, c, f32, q6)
        add("q1-width%d-ragged" % c, 2 * 2048 + 1234 + c, c, f32, q1)
    add("q6-whole-blocks", 3 * 2048, 7, f32, q6)
    add("q6-under-one-block", 1024 + 77, 7, f32, q6)
    add("q1-under-one-block", 1024 + 77, 7, f32, q1)
    add("q6-int32", 2048 + 999, 4, i32, q6)
    add("q1-int32", 2048 + 999, 4, i32, q1)

    def nans(x):        # NaN and inf where the predicate drops the row
        x[x[:, 0] <= 10, 1:] = np.nan
        x[::7][x[::7, 0] <= 10, 1:] = np.inf
        return x
    add("q6-nan-inf-in-dropped-rows", 2048 + 999, 7, f32, q6, nans)
    add("q1-nan-inf-in-dropped-rows", 2048 + 999, 7, f32, q1, nans)
    none = lambda sh, dt: Fold(_thin_filter(                # noqa: E731
        _first_below_0, sh, dt), _stat("sum") + _stat("max"), True)
    add("every-row-dropped", 2048 + 999, 7, f32, none)
    add("every-row-dropped-grouped", 2048 + 999, 7, f32,
        lambda sh, dt: Fold(_thin_filter(_first_below_0, sh, dt),
                            group=("mean", _label_mod5_less_1, None, 3)))
    moments = lambda sh, dt: Fold(                          # noqa: E731
        _thin_filter(_first_above_10, sh, dt),
        _stat("mean") + _stat("var", 1) + _stat("std", 0, True)
        + _stat("min") + _stat("max") + _stat("sum", None, True), True)
    add("mean-var-std-extremes-as-one-group", 2048 + 999, 7, f32, moments)
    add("mean-of-int32-divides-by-the-masked-count", 2048 + 999, 4, i32,
        lambda sh, dt: Fold(_thin_filter(_first_above_10, sh, dt,
                                         post=(_last,)), _stat("mean")))
    add("maps-in-front-of-the-filter", 2048 + 999, 7, f32,
        lambda sh, dt: Fold(_thin_filter(
            _first_above_10, sh, dt, funcs=(_plus_one, _plus_one)),
            _stat("sum")))
    for op in ("mean", "max", "min"):
        add("grouped-%s" % op, 2048 + 999, 7, f32,
            lambda sh, dt, op=op: Fold(
                _thin_filter(_first_above_10, sh, dt),
                group=(op, _label_mod5_less_1, _two_terms, 3)))
    add("grouped-chain-no-filter", 2048 + 999, 7, f32,
        lambda sh, dt: Fold(Chain((_plus_one,), 1),
                            group=("sum", _label_mod5_less_1, None, 3)))
    return cases


@pytest.mark.parametrize("rows,c,dtype,make,spoil", _thin_cases())
def test_thin_fold_kernel_matches_the_expressions(rows, c, dtype, make,
                                                  spoil):
    x = _thin_table(rows, c, dtype)
    if spoil is not None:
        x = spoil(x)
    want, got = _fold_both_ways(make(x.shape, x.dtype), x)
    assert len(want) == len(got)
    for w, g in zip(want, got):
        assert w.shape == g.shape and w.dtype == g.dtype
        if np.issubdtype(w.dtype, np.integer):
            assert np.array_equal(w, g)         # every count is exact
        else:
            # sums of small integers are exact in any order; a quotient
            # of two such sums is the same division
            np.testing.assert_allclose(g, w, rtol=1e-6, atol=0)


def _thin_eligibility_cases():
    from bolt_tpu.tpu.array import _WithKeysFunc
    f32 = np.float32
    sums = _stat("sum")
    gather = np.array([0, 2])
    return [
        # name, shape, dtype, pred, post, stats, served
        ("tpch_q6", _LINEITEM, f32, _q6_pred, (_q6_value,), sums, True),
        ("width8", (50000, 8), f32, _first_above_10, (), sums, True),
        ("width9", (50000, 9), f32, _first_above_10, (), sums, False),
        ("fat-records", _STACK, f32, _corner, (), sums, False),
        ("float64", (50000, 7), np.float64, _first_above_10, (), sums,
         False),
        ("bfloat16", (50000, 7), jnp.bfloat16, _first_above_10, (), sums,
         False),
        ("a-reduction-inside-the-record", (50000, 7), f32,
         lambda r: r.sum() > 3, (), sums, False),
        ("a-gather", (50000, 7), f32, lambda r: r[gather].max() > 3, (),
         sums, False),
        ("a-sort", (50000, 7), f32, lambda r: jnp.sort(r)[0] > 3, (), sums,
         False),
        ("keyed-map", (50000, 7), f32, _first_above_10,
         (_WithKeysFunc(lambda kv: kv[1]),), sums, False),
        ("prod", (50000, 7), f32, _first_above_10, (), _stat("prod"),
         False),
        ("a-value-axis-reduced", (50000, 7), f32, _first_above_10, (),
         (("sum", (0, 1), False, None),), False),
        ("few-rows", (1000, 7), f32, _first_above_10, (), sums, False),
    ]


@pytest.mark.parametrize("name,shape,dtype,pred,post,stats,served",
                         _thin_eligibility_cases(),
                         ids=[c[0] for c in _thin_eligibility_cases()])
def test_which_folds_the_kernel_serves(name, shape, dtype, pred, post,
                                       stats, served):
    import jax
    from bolt_tpu.tpu import fold as tf
    from bolt_tpu.tpu.array import _Filter
    fp = _Filter(None, (), pred, 1, tuple(shape[1:]), shape[0], dtype, post,
                 jax.ShapeDtypeStruct(
                     () if post == (_q6_value,) else tuple(shape[1:]),
                     dtype))
    with jax.enable_x64(name == "float64"):
        assert tf._kernel_serves(tf.Fold(fp, stats), jax.ShapeDtypeStruct(
            shape, dtype)) == served


# ---------------------------------------------------------------------
# compile-only: BASELINE config 5 at HBM size (ISSUE 26).  Both programs
# of the series64-1chip configuration have to fit one 16 GB chip beside
# their 10.74 GB argument: no temporary as large as the data (a relayout
# of the sample axes, the chunk grid or the Gram matrix's runs), which
# the compiler refuses outright ("Used 20.00G of 15.75G hbm")
# ---------------------------------------------------------------------

_SERIES = (40, 1048576, 64)           # planes x voxels x time points


def _series_mesh(v5e_device):
    import jax
    return jax.sharding.Mesh(np.asarray([v5e_device]), ("k",))


def _compile_series(fn, v5e_device, shape=_SERIES, dtype=_F32):
    import jax
    mesh = _series_mesh(v5e_device)
    where = jax.sharding.NamedSharding(mesh,
                                       jax.sharding.PartitionSpec("k"))
    with jax.enable_x64(False):
        return jax.jit(fn).lower(jax.ShapeDtypeStruct(
            shape, dtype, sharding=where)).compile()


@pytest.mark.parametrize("center", [True, False],
                         ids=["centred", "uncentred"])
def test_whole_data_pca_fits_beside_its_argument_on_v5e(v5e_device, center):
    from bolt_tpu import engine
    from bolt_tpu.ops import linalg
    names = ("gram_kernel_programs", "gram_sums_programs")
    c0 = [engine.counters()[k] for k in names]
    program = linalg._pca_program((), 2, _SERIES[:2], _SERIES[2], 8, center,
                                  "highest", _series_mesh(v5e_device))
    compiled = _compile_series(program, v5e_device)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == 4 * int(np.prod(_SERIES))
    assert mem.temp_size_in_bytes < 0.1e9
    # the scores and nothing else of any size come out
    assert mem.output_size_in_bytes < 4 * int(np.prod(_SERIES[:2])) * 8 * 1.01
    # the Gram pass is ONE kernel call over a bitcast of the argument
    # (ISSUE 29): no matrix-unit fusion with a (64, 64) result is left
    text = compiled.as_text()
    _one_packed_gram(text, _SERIES[:1] + (1,), sums=center)
    assert not [ln for ln in text.splitlines()
                if "convolution" in ln and "f32[64,64]" in ln.split("=")[1][:40]]
    # and a centred program's mean comes out of that call (ISSUE 33): the
    # 10.74 GB are read by the kernel and by the projection, and by no
    # reduction of the mean's own
    readers = _argument_readers(text)
    assert len(readers) == 2, readers
    assert sum("tpu_custom_call" in ln for ln in readers) == 1, readers
    assert sum("convolution" in ln for ln in readers) == 1, readers
    assert [engine.counters()[k] for k in names] == [c0[0] + 1,
                                                     c0[1] + center]


# the cell's own shape, and the same deployment at 32 time points: its
# blocks are 2**20 rows, longer than any run a Gram matrix was ever cut
# into, and a block that svdvals cut a second time was a 10 GB relayout
@pytest.mark.parametrize("shape,block", [
    (_SERIES, (524288, 64)), ((40, 2097152, 32), (1048576, 32))],
    ids=["64-time-points", "32-time-points"])
def test_per_chunk_svd_fits_beside_its_argument_on_v5e(v5e_device, shape,
                                                       block):
    run, plan = _chunk_svd_program(v5e_device, shape)
    assert plan == block                      # upstream's default budget
    compiled = _compile_series(run, v5e_device, shape)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 0.1e9
    assert mem.output_size_in_bytes < 1e6
    # the 80 blocks' eigenproblems, reached under the map's two vmaps, are
    # ONE kernel call with the 80 on the lanes of one block (ISSUE 27)
    _one_jacobi_kernel(compiled.as_text(), block[1], 128)
    # and their 80 Gram matrices ONE call over the chunk grid's view of
    # the argument, planes x grid (ISSUE 29)
    _one_packed_gram(compiled.as_text(), (shape[0], shape[1] // block[0]))


def _chunk_svd_program(v5e_device, shape):
    """``ChunkedArray.map``'s uniform program of ``svdvals`` over
    upstream's default blocks of a plane-keyed ``shape``, and the block."""
    from bolt_tpu.ops import svdvals
    from bolt_tpu.tpu.chunk import _constrain_chunked, _uniform_map_body
    from bolt_tpu.utils import chunk_align, chunk_plan
    axes, size, padding = chunk_align(shape[1:], (0,), "150", None)
    plan = tuple(chunk_plan(shape[1:], 4, size, axes, padding=padding))
    mesh = _series_mesh(v5e_device)

    def run(data):
        out = _uniform_map_body(data, lambda blk: svdvals(blk)[None, :], 1,
                                plan)
        return _constrain_chunked(out, mesh, 1, {})
    return run, plan


def _kernel_calls(text, name):
    """The compiled program's Mosaic calls whose instruction is named
    after the kernel ``name`` (XLA names a kernel's instruction, and so
    its event on the device trace, after the kernel)."""
    import re
    return [ln for ln in text.splitlines() if "tpu_custom_call" in ln
            and re.match(r"\s*%" + re.escape(name) + r"(\.\d+)? = ", ln)]


def _argument_readers(text):
    """The instructions of the compiled program's entry computation that
    take its one argument as an operand, a bitcast of it counted as the
    argument itself."""
    import re
    entry = text[text.index("ENTRY "):].splitlines()[1:]
    held = set(re.findall(r"%(\S+) = \S+ parameter\(0\)", "\n".join(entry)))
    assert len(held) == 1, held
    readers = []
    for ln in entry:
        made = re.match(r"\s*(?:ROOT )?%(\S+) = ", ln)
        if not made or not held & set(
                re.findall(r"%([^\s,()]+)", ln.split(" = ", 1)[1])):
            continue
        if re.search(r" bitcast\(", ln):
            held.add(made.group(1))
        else:
            readers.append(ln)
    return readers


def _one_packed_gram(text, batch, sums=False):
    """ONE ``packed_gram`` call whose accumulators are one ``(128, 128)``
    and one ``(64, 64)`` an element of ``batch`` and nothing else, fed a
    bitcast (not a copy) of the program's argument, under a name that the
    benchmark does not take for the eigensolver's.  With ``sums`` it is
    the summing form: its own name, and a third result, the row sums by
    lane ``(64, 128)``."""
    import json
    import os
    import re
    from bolt_tpu.ops import linalg
    calls = _kernel_calls(text, linalg._GRAM_KERNEL_NAME)
    summing = _kernel_calls(text, linalg._GRAM_SUMS_KERNEL_NAME)
    assert len(summing) == sums and len(calls) == (not sums), (calls, summing)
    calls += summing
    dims = ",".join(map(str, batch))
    results = re.findall(r"f32\[[\d,]+\]", calls[0].split(" custom-call(")[0])
    assert results == ["f32[%s,128,128]" % dims, "f32[%s,64,64]" % dims] \
        + ["f32[%s,64,128]" % dims] * sums, calls[0]
    operands = set(re.search(r"custom-call\(([^)]*)\)", calls[0]).group(1)
                   .replace(" ", "").split(","))
    assert len(operands) == 1 and next(iter(operands)).startswith(
        "%bitcast"), calls[0]
    name = re.match(r"\s*%(\S+) = ", calls[0]).group(1)
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "benchmark", "metrics",
                           "gram_roofline.json")) as f:
        eigh = json.load(f)["args"]["eigh"]
    assert eigh and not [pat for pat in eigh if pat in name], (name, eigh)


def _one_jacobi_kernel(text, m, lanes):
    """The compiled program holds the sweep chain as ONE Mosaic call over
    ``lanes`` lanes of ``m x m`` matrices: no ``while`` holds the rounds,
    and the call's instruction, and so its event on the device trace, has
    a name that ``benchmark/metrics/eigh_ms.scan.json`` matches.  Any
    other Mosaic call of the program is ``packed_gram``'s."""
    import re
    from bolt_tpu.ops import linalg
    every = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    calls = _kernel_calls(text, linalg._KERNEL_NAME)
    assert len(every) == len(calls) + len(
        _kernel_calls(text, linalg._GRAM_KERNEL_NAME)), every
    assert len(calls) == 1, calls
    assert re.match(r"\s*%\S*custom-call\S* = ", calls[0]), calls[0]
    half = -(-(m // 2) // 8) * 8
    assert "f32[%d,%d]" % (4 * half * half, lanes) in calls[0], calls[0]
    assert " while(" not in text


@pytest.mark.parametrize("shape,lanes", [
    ((80, 64, 64), 128), ((130, 8, 8), 256), ((13, 10, 8, 8), 256)],
    ids=["80-of-64", "one-vmap-130", "two-vmaps-13x10"])
def test_jacobi_is_one_kernel_call_on_v5e(v5e_device, shape, lanes):
    # pallas_call's own batching rule would give a grid step, and ONE lane
    # in use, to each mapped matrix: the mapped sizes' product has to
    # arrive on the lanes of one call
    import jax
    fn = _jacobi_eigh
    for _ in shape[:-3]:
        fn = jax.vmap(fn)
    where = jax.sharding.SingleDeviceSharding(v5e_device)
    with jax.enable_x64(False):
        text = jax.jit(fn).lower(jax.ShapeDtypeStruct(
            shape, _F32, sharding=where)).compile().as_text()
    _one_jacobi_kernel(text, shape[-1], lanes)


def test_jacobi_keeps_the_scan_in_a_program_for_four_chips(v5e_device):
    # GSPMD cannot partition a Mosaic kernel ("Please wrap the call in a
    # shard_map"): outside shard_map a program for several chips has to
    # keep the lax.scan, and compile
    import jax
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                        platform="tpu")
    mesh = jax.sharding.Mesh(np.asarray(topo.devices), ("k",))
    where = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("k"))
    with jax.enable_x64(False):
        text = jax.jit(_jacobi_eigh).lower(jax.ShapeDtypeStruct(
            (400, 8, 8), _F32, sharding=where)).compile().as_text()
    assert "tpu_custom_call" not in text
    assert " while(" in text


# ---------------------------------------------------------------------
# compile-only: who gets packed_gram and who keeps dot_general (ISSUE 29)
# ---------------------------------------------------------------------

def test_a_plain_vmap_of_svdvals_keeps_dot_general_and_fits_on_v5e(
        v5e_device):
    # a stored (80, R, 64) under the user's own vmap batches to the shapes
    # the chunk grid's (40, 2, R, 64) does, in the OTHER physical order:
    # nobody named the axis, so nobody guesses, and the program is what it
    # was (a wrong guess is a copy of the whole array: refused at this size)
    import jax
    compiled = _compile_series(jax.vmap(_svdvals), v5e_device,
                               (80, 524288, 64))
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9
    from bolt_tpu.ops import linalg
    assert not _kernel_calls(compiled.as_text(), linalg._GRAM_KERNEL_NAME)


@pytest.mark.parametrize("dtype,planes", [
    ("bfloat16", 40), ("int16", 40), ("int16", 56)],
    ids=["bf16-5.4GB", "int16-5.4GB", "int16-7.5GB"])
@pytest.mark.parametrize("program", ["pca", "chunk_svd"])
def test_a_stored_narrow_series_is_widened_inside_the_fusion_on_v5e(
        v5e_device, program, dtype, planes):
    # a stored bfloat16 or integer series is widened to float32 INSIDE the
    # dot_general's fusion, never as a copy: the kernel, which XLA cannot
    # fuse a convert into, is not for it (a float32 copy of the 7.5 GB
    # int16 series is 15 GB: "Used 21.00G of 15.75G hbm")
    from bolt_tpu.ops import linalg
    shape = (planes,) + _SERIES[1:]
    if program == "pca":
        run = linalg._pca_program((), 2, shape[:2], shape[2], 8, True,
                                  "highest", _series_mesh(v5e_device))
    else:
        run, _ = _chunk_svd_program(v5e_device, shape)
    compiled = _compile_series(run, v5e_device, shape, jnp.dtype(dtype))
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == 2 * int(np.prod(shape))
    assert mem.temp_size_in_bytes < 0.1e9
    text = compiled.as_text()
    assert not _kernel_calls(text, linalg._GRAM_KERNEL_NAME) \
        + _kernel_calls(text, linalg._GRAM_SUMS_KERNEL_NAME)
    if program == "pca":
        # the centred program keeps its own mean: a reduction reads the
        # narrow argument beside the Gram pass and the projection
        readers = _argument_readers(text)
        assert len(readers) == 3, readers
        assert sum("reduce" in ln.split("=")[0] for ln in readers) == 1, \
            readers


def test_batched_svdvals_is_one_packed_gram_on_v5e(v5e_device):
    # leading axes of the operand itself are stored outside the rows
    compiled = _compile_series(_svdvals, v5e_device, (80, 524288, 64))
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9
    _one_packed_gram(compiled.as_text(), (80, 1))


@pytest.mark.parametrize("shape", [(8, 65536, 48), (8, 65536, 128),
                                   (8, 4000, 64)],
                         ids=["d48", "d128", "under-one-block"])
def test_gram_that_does_not_pack_keeps_dot_general_on_v5e(v5e_device,
                                                          shape):
    from bolt_tpu.ops import linalg
    text = _compile_series(
        lambda x: linalg._gram(x, jnp, "highest"), v5e_device,
        shape).as_text()
    assert "tpu_custom_call" not in text
    assert "convolution" in text


@pytest.mark.parametrize("precision", ["high", "default"])
def test_cheaper_precisions_keep_dot_general_on_v5e(v5e_device, precision):
    from bolt_tpu.ops import linalg
    text = _compile_series(
        lambda x: linalg._gram(x, jnp, precision), v5e_device,
        (8, 65536, 64)).as_text()
    assert "tpu_custom_call" not in text


def test_gram_keeps_dot_general_in_a_program_for_four_chips(v5e_device):
    # GSPMD does not partition a Mosaic kernel: outside shard_map a program
    # for several chips keeps the dot_general in runs, and compiles
    import jax
    from jax.experimental import topologies
    from bolt_tpu.ops import linalg
    topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                        platform="tpu")
    mesh = jax.sharding.Mesh(np.asarray(topo.devices), ("k",))
    where = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("k"))
    program = linalg._pca_program((), 2, (8, 65536), 64, 8, True, "highest",
                                  mesh)
    with jax.enable_x64(False):
        text = jax.jit(program).lower(jax.ShapeDtypeStruct(
            (8, 65536, 64), _F32, sharding=where)).compile().as_text()
    assert "tpu_custom_call" not in text
    assert "all-reduce" in text
    # the centred program's mean is its own reduction there (ISSUE 33)
    readers = _argument_readers(text)
    assert sum("reduce" in ln.split("=")[0] for ln in readers) == 1, readers


@pytest.mark.parametrize("center", [True, False],
                         ids=["centred", "uncentred"])
def test_cov_reads_the_series_once_on_v5e(v5e_device, center):
    # cov's one program at the cell's shape: ONE kernel call reads the
    # argument and nothing else does, centred (the summing form: the mean
    # from the same pass, ISSUE 33) or not (the form it had)
    from bolt_tpu.ops import linalg
    program = linalg._cov_program((), 2, _SERIES[:2], _SERIES[2], center, 1,
                                  "highest")
    compiled = _compile_series(program, v5e_device)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9
    text = compiled.as_text()
    _one_packed_gram(text, _SERIES[:1] + (1,), sums=center)
    readers = _argument_readers(text)
    assert len(readers) == 1 and "tpu_custom_call" in readers[0], readers


def test_the_lowering_counts_programs_with_the_kernel(v5e_device):
    from bolt_tpu import engine
    c0 = engine.counters()["gram_kernel_programs"]
    _compile_series(_svdvals, v5e_device, (2, 16384, 64))
    assert engine.counters()["gram_kernel_programs"] == c0 + 1
    _compile_series(_svdvals, v5e_device, (2, 16384, 48))
    assert engine.counters()["gram_kernel_programs"] == c0 + 1


# ---------------------------------------------------------------------
# compile-only: the fold over thin records through its ONE entry
# (``tpu/fold.py :: fold_records``, ISSUE 31).  TPC-H Q6 and Q1 at the
# benchmark's size are one ``thin_fold`` call over a bitcast of the table
# and nothing else reads it; every fold the kernel does not serve, and
# every program it cannot be placed in, keeps the text it had
# ---------------------------------------------------------------------

def _q6_fold(shape=_LINEITEM):
    from bolt_tpu.tpu.fold import Fold
    return Fold(_filter_of((), _q6_pred, shape, (_q6_value,), ()),
                (("sum", (0,), False, None),))


def _q1_fold(shape=_LINEITEM):
    from bolt_tpu.tpu.fold import Fold
    return Fold(_filter_of((), _q1_pred, shape),
                group=("sum", _q1_group, _q1_terms, 6))


def _compile_fold(fold, shape, where, entry=True):
    """The compiled program of ``fold`` over ``shape`` placed ``where``:
    through the entry the call sites use, or by the expressions alone."""
    import jax
    from bolt_tpu.tpu import fold as tf
    run = tf.fold_records if entry else tf._plain
    with jax.enable_x64(False):
        return jax.jit(lambda data: run(fold, data)).lower(
            jax.ShapeDtypeStruct(shape, _F32, sharding=where)).compile()


@pytest.mark.parametrize("make", [_q6_fold, _q1_fold],
                         ids=["tpch_q6", "tpch_q1"])
def test_thin_fold_is_one_kernel_over_a_bitcast_on_v5e(v5e_device, make):
    import re
    import jax
    compiled = _compile_fold(make(), _LINEITEM,
                             jax.sharding.SingleDeviceSharding(v5e_device))
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    assert len(_kernel_calls(text, "thin_fold")) == 1
    assert text.count("tpu_custom_call") == 1
    # the table as it is held, 9.60 GB, and a few accumulators beside it
    assert mem.argument_size_in_bytes == -(-300018951 // 128) * 128 * 8 * 4
    assert mem.temp_size_in_bytes < 16e6
    entry = text[text.index("ENTRY"):]
    # the kernel's operand is a VIEW of the table as it is held: a
    # bitcast, and nothing else takes the table or is of its size
    table = [ln for ln in entry.splitlines()[1:] if "300018951" in ln]
    assert len(table) == 3, table
    assert "= f32[300018951,7]{0,1:T(8,128)} parameter(0)" in table[0]
    assert re.search(r"= f32\[7,300018951\]\S* bitcast\(", table[1])
    assert "tpu_custom_call" in table[2]
    assert not re.search(r"= \S+ (copy|transpose|gather|sort)\(", text)


def _reads_whole_record(r):
    return r.sum() > 100


def _kept_texts(fold, shape, where):
    """``(through the entry, by the expressions)``: the two programs'
    computations, without what names the Python that traced them (the
    table of stack frames in front, each instruction's metadata)."""
    import re

    def computations(compiled):
        text = compiled.as_text()
        text = text[text.index("\n\n", text.index("StackFrames")):]
        return re.sub(r", metadata=\{[^}]*\}", "", text)
    return (computations(_compile_fold(fold, shape, where)),
            computations(_compile_fold(fold, shape, where, entry=False)))


@pytest.mark.parametrize("name", ["stack_filter_sum", "width9",
                                  "predicate-reduces-the-record",
                                  "tpch_q6-four-chips", "tpch_q1-four-chips"])
def test_what_the_kernel_does_not_serve_keeps_its_text_on_v5e(v5e_device,
                                                              name):
    import jax
    from jax.experimental import topologies
    from bolt_tpu.tpu.fold import Fold
    where = jax.sharding.SingleDeviceSharding(v5e_device)
    sums = (("sum", (0,), False, None),)
    if name == "stack_filter_sum":
        shape = _STACK
        fold = Fold(_filter_of((_plus_one,), _corner, _STACK), sums)
    elif name == "width9":
        shape = (30_000_001, 9)
        fold = Fold(_filter_of((), _q6_pred, shape, (_q6_value,), ()), sums)
    elif name == "predicate-reduces-the-record":
        shape = (30_000_001, 7)
        fold = Fold(_filter_of((), _reads_whole_record, shape), sums)
    else:
        # GSPMD does not partition a Mosaic kernel: outside shard_map a
        # program for several chips keeps the fusion
        shape = (300018952, 7)           # a row more: four equal shares
        fold = (_q6_fold if "q6" in name else _q1_fold)(shape)
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
        mesh = jax.sharding.Mesh(np.asarray(topo.devices), ("k",))
        where = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("k"))
    got, want = _kept_texts(fold, shape, where)
    assert "tpu_custom_call" not in got
    assert got == want
    if name == "stack_filter_sum":
        assert "select_reduce_fusion" in got


def test_the_lowering_counts_programs_with_the_fold_kernel(v5e_device):
    import jax
    from bolt_tpu import engine
    where = jax.sharding.SingleDeviceSharding(v5e_device)
    c0 = engine.counters()["fold_kernel_programs"]
    _compile_fold(_q6_fold((300_000, 7)), (300_000, 7), where)
    assert engine.counters()["fold_kernel_programs"] == c0 + 1
    _compile_fold(_q1_fold((300_000, 7)), (300_000, 7), where)
    assert engine.counters()["fold_kernel_programs"] == c0 + 2
    # a width the kernel does not serve, and the CPU: no kernel, no count
    _compile_fold(_q6_fold((300_000, 9)), (300_000, 9), where)
    _compile_fold(_q6_fold((300_000, 7)), (300_000, 7), None)
    assert engine.counters()["fold_kernel_programs"] == c0 + 2


# ---------------------------------------------------------------------
# compile-only: the ten programs of the throughput test's five query
# streams (ISSUE 46; ``benchmark/traffic/streams5.json``).  A parameter
# set is closed over as Python ints and is its own program: each is the
# ONE ``thin_fold`` call over the table as it is held that the
# validation set's is, with a few accumulators beside it
# ---------------------------------------------------------------------

def _stream_positions(kind):
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "traffic",
        "streams5.json")
    with open(path) as fh:
        kinds = {k["kind"]: k for k in json.load(fh)["requests"]}
    return kinds[kind]["positions"]


def _stream_fold(kind, position):
    from bolt_tpu.tpu.fold import Fold
    if kind == "q1":
        day = int(position["shipdate_to"])
        return Fold(_filter_of((), lambda r: r[0] <= day, _LINEITEM),
                    group=("sum", _q1_group, _q1_terms, 6))
    (d0, d1), (c0, c1) = position["shipdate"], position["discount"]
    q = int(position["quantity_below"])

    def pred(r):
        return ((r[0] >= d0) & (r[0] < d1) & (r[3] >= c0) & (r[3] <= c1)
                & (r[1] < q))
    return Fold(_filter_of((), pred, _LINEITEM, (_q6_value,), ()),
                (("sum", (0,), False, None),))


@pytest.mark.parametrize("stream", range(5))
@pytest.mark.parametrize("kind", ["q6", "q1"])
def test_a_stream_s_own_parameters_are_one_thin_fold_on_v5e(v5e_device,
                                                            kind, stream):
    import jax
    positions = _stream_positions(kind)
    assert len(positions) == 5
    compiled = _compile_fold(_stream_fold(kind, positions[stream]),
                             _LINEITEM,
                             jax.sharding.SingleDeviceSharding(v5e_device))
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    assert len(_kernel_calls(text, "thin_fold")) == 1
    assert text.count("tpu_custom_call") == 1
    assert mem.argument_size_in_bytes == -(-300018951 // 128) * 128 * 8 * 4
    assert mem.temp_size_in_bytes < 16e6


# ---------------------------------------------------------------------
# compile-only: the slab programs of LINEITEM at SF 100 WHOLE, streamed
# (ISSUE 51; ``lineitem-streamed-1chip.scan_q1q6``).  A slab of thin
# records arrives as a dense view of its bytes (whole groups of 128 rows,
# then the rows past them) and the program's first operation re-seats it:
# Q1's grouped fold is then ONE ``thin_fold`` call a slab, as over the
# resident table, and nothing holds two slabs of temp
# ---------------------------------------------------------------------

_SF100 = (600037902, 7)


def _streamed_lineitem(v5e_device, query):
    """``(source, terminal)`` of one query over the streamed table, as the
    public calls hand them to the executor."""
    from bolt_tpu import stream
    from bolt_tpu.tpu.array import BoltArrayTPU
    mesh = _series_mesh(v5e_device)
    src = stream.StreamSource.from_callback(lambda index: None, _SF100, 1,
                                            np.float32, mesh)
    assert src.slab == 2396745 and len(src.slab_ranges()) == 251
    assert src.slab_ranges()[-1] == (599186250, 600037902)   # 851,652 rows
    assert stream.thin_records(src.shape, src.dtype)
    b = BoltArrayTPU._streamed(src)
    if query == "q6":
        out = b.filter(_q6_pred).map(_q6_value)
        assert out.streaming                      # nothing was uploaded
        return out._stream, stream._Sum()
    source = b.filter(_q1_pred)._stream
    return source, stream._Group(("sum", _q1_group, _q1_terms, 6), source)


@pytest.mark.parametrize("fused", [False, True], ids=["first", "acc-fused"])
@pytest.mark.parametrize("rows", [2396745, 851652],
                         ids=["full-slab", "short-tail"])
@pytest.mark.parametrize("query", ["q6", "q1"])
def test_a_thin_slab_is_reseated_and_folded_in_place_on_v5e(v5e_device,
                                                            query, rows,
                                                            fused):
    import warnings
    import jax
    from bolt_tpu import stream
    source, terminal = _streamed_lineitem(v5e_device, query)
    mesh = source.mesh
    where = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    groups, rest = divmod(rows, 128)
    assert rest                                   # neither slab is whole
    slab = tuple(jax.ShapeDtypeStruct(shape, np.float32, sharding=where)
                 for shape in ((groups, 128 * 7), (rest, 7)))
    with jax.enable_x64(False), warnings.catch_warnings():
        warnings.filterwarnings("ignore",
                                message="Some donated buffers were not")
        first = stream._slab_program(source, terminal, (rows, 7),
                                     thin=True).lower(slab)
        if fused:
            acc = jax.tree_util.tree_map(
                lambda o: jax.ShapeDtypeStruct(o.shape, o.dtype,
                                               sharding=where),
                first.out_info)
            compiled = stream._slab_program(
                source, terminal, (rows, 7), fused=True,
                thin=True).lower(slab, acc).compile()
        else:
            compiled = first.compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    slab_bytes = rows * 7 * 4
    # the dense form pads nothing: the argument is the slab's own bytes
    # (and the partial it merges), not 8/7 of them
    assert slab_bytes <= mem.argument_size_in_bytes < slab_bytes + 65536
    assert mem.temp_size_in_bytes < 2 * slab_bytes          # one re-seat
    assert mem.output_size_in_bytes < 65536
    if query == "q1":
        assert len(_kernel_calls(text, "thin_fold")) == 1
        assert text.count("tpu_custom_call") == 1
        assert "f32[7,%d]" % rows in text         # the kernel's own view
    else:
        assert "tpu_custom_call" not in text      # the masked sum's fusion
    assert not re.search(r"= \S+ (gather|sort)\(", text)


# ---------------------------------------------------------------------
# compile-only: the streamed swap's place program at the two-photon
# session's size (ISSUE 32).  The swapped array is the program's own
# argument handed back: aliased, with a temp of one transposed slab —
# where the parts and their concatenation were 2-4 x the output
# ---------------------------------------------------------------------

_SESSION = (10240, 512, 512)          # frames x rows x columns


@pytest.mark.parametrize("frames", [64, 128],
                         ids=["half-a-lane-tile", "whole-lane-tiles"])
def test_place_program_aliases_its_output_on_v5e(v5e_device, frames):
    import warnings
    import jax
    from bolt_tpu.parallel import shuffle
    mesh = _series_mesh(v5e_device)
    plan = shuffle.plan_shuffle(_SESSION, np.float32, 1, (1, 2, 0), 2,
                                mesh, frames, None, None, ring=3)
    assert plan.out_shape == (512, 512, 10240) and plan.j0 == 2
    slab_shape = (frames,) + _SESSION[1:]
    program = shuffle.place_program(plan, (), mesh, None, np.float32,
                                    slab_shape, True, frames)
    where = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    with jax.enable_x64(False), warnings.catch_warnings():
        warnings.filterwarnings("ignore",
                                message="Some donated buffers were not")
        compiled = program.lower(
            jax.ShapeDtypeStruct(plan.out_shape, _F32, sharding=where),
            jax.ShapeDtypeStruct(slab_shape, _F32, sharding=where),
            jax.ShapeDtypeStruct((), np.uint32, sharding=where)).compile()
    mem = compiled.memory_analysis()
    # the compiler knows the offset is a multiple of the slab: the low
    # bits of the minor-axis index are known zero (cursor * frames)
    assert '"zeroes":"%d"' % (frames - 1) in compiled.as_text()
    out_bytes = 4 * int(np.prod(_SESSION))
    slab_bytes = 4 * int(np.prod(slab_shape))
    # the array and, beside it, the cursor (a scalar and the tuple)
    assert out_bytes <= mem.output_size_in_bytes < out_bytes + 4096
    assert out_bytes <= mem.alias_size_in_bytes          # in place
    # a slab shorter than a lane tile is padded to one in the temp
    assert mem.temp_size_in_bytes <= max(slab_bytes, 128 * 2**20) * 1.01
    assert mem.temp_size_in_bytes < 2 * 128 * 2**20
    # what the plan says the resident leg holds covers what it does hold:
    # the output, the ring, and this temp
    assert plan.resident_bytes >= (out_bytes + 3 * slab_bytes
                                   + min(mem.temp_size_in_bytes, slab_bytes))
    assert plan.resident_bytes < 16.9e9
    # and the session twice as long is refused by the same rule on a chip
    # that has 16.9 GB
    double = shuffle.plan_shuffle((20480, 512, 512), np.float32, 1,
                                  (1, 2, 0), 2, mesh, frames,
                                  int(16.9e9), None, ring=3)
    assert not double.resident


# the same session as the camera wrote it (ISSUE 59): 20,480 frames of
# 16-bit words in the bytes of the float32 one.  The slab, its transposed
# block and the array keep the element through the compiler too (no widen-
# transpose-narrow, no float32 of a slab), the update stays in place, and
# the block of a 64 MiB slab leaves HBM's temporaries altogether.  Handed
# the slab as its 32-bit WORDS (the dense route of one device: the link
# carries those at its rate and interleaves the loader's block on the host
# at two thirds of it), the compiler transposes the words, half the
# elements, and unpacks inside the update's fusion beside four bytes an
# element of temporaries, which ``stream.place_budget`` takes off the budget
_NARROW_SESSION = (20480, 512, 512)


@pytest.mark.parametrize("words", [False, True],
                         ids=["as-loaded", "as-words"])
@pytest.mark.parametrize("dtype,tile", [("uint16", "(2,1)"),
                                        ("int16", "(2,1)"),
                                        ("uint8", "(4,1)")])
def test_place_program_keeps_a_narrow_element_on_v5e(v5e_device, dtype,
                                                     tile, words):
    import warnings
    import jax
    from bolt_tpu.parallel import shuffle
    mesh = _series_mesh(v5e_device)
    frames, item = 128, np.dtype(dtype).itemsize
    plan = shuffle.plan_shuffle(_NARROW_SESSION, dtype, 1, (1, 2, 0), 2,
                                mesh, frames, int(16.9e9), None, ring=5)
    assert plan.out_shape == (512, 512, 20480) and plan.resident
    slab_shape = (frames,) + _NARROW_SESSION[1:]
    program = shuffle.place_program(plan, (), mesh, None, np.dtype(dtype),
                                    slab_shape, True, frames, words)
    where = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    slab = jax.ShapeDtypeStruct(slab_shape, dtype, sharding=where)
    if words:
        slab = (jax.ShapeDtypeStruct((frames, 512, 128 * item), np.uint32,
                                     sharding=where),)
    with jax.enable_x64(False), warnings.catch_warnings():
        warnings.filterwarnings("ignore",
                                message="Some donated buffers were not")
        compiled = program.lower(
            jax.ShapeDtypeStruct(plan.out_shape, dtype, sharding=where),
            slab,
            jax.ShapeDtypeStruct((), np.uint32, sharding=where)).compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    out_bytes = item * int(np.prod(_NARROW_SESSION))
    slab_bytes = item * int(np.prod(slab_shape))
    assert out_bytes == 10737418240 * item // 2
    assert out_bytes <= mem.output_size_in_bytes < out_bytes + 4096
    assert out_bytes <= mem.alias_size_in_bytes          # in place
    assert mem.argument_size_in_bytes < out_bytes + slab_bytes + 4096
    # the words' unpack holds four bytes an element beside the slab
    spare = 4 // item if words else 1
    assert mem.temp_size_in_bytes <= spare * slab_bytes
    # what the plan counts (the stored bytes an element) covers what is
    # held, with what place_budget takes off for a dense route
    assert plan.resident_bytes == out_bytes + 6 * slab_bytes
    assert plan.resident_bytes + words * spare * slab_bytes >= (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + 4 * slab_bytes)
    assert plan.resident_bytes + spare * slab_bytes < 12e9
    # the element all the way: packed sublanes, no float32, the offset's
    # low bits known, ONE copy of a slab's size (the transpose: of the
    # elements as loaded, of the words where those went up)
    assert not re.search(r"\bf(16|32|64)\[", text)        # nothing widens
    assert "T(8,128)%s" % tile in text
    assert '"zeroes":"%d"' % (frames - 1) in text
    entry = _computations(text)["ENTRY"]
    big = [ln for ln in entry if " copy(" in ln and "[128,512," in ln]
    assert len(big) == 1
    assert ("u32[128,512,%d]" % (128 * item) in big[0]) is words
    if not words:
        assert "[128,512,512]{2,1,0:T(8,128)}" not in text
        assert sum(" fusion(" in ln for ln in entry) == 1


# ---------------------------------------------------------------------
# compile-only: the per-pixel series analysis that follows toseries
# (configuration pixelseries512-1chip, PR 36): ops.normalize -> detrend
# -> fourier over the resident (512, 512, 10240) float32 series array.
# A sort and an FFT keep record-sized temporaries; over the whole array
# at once they are array-sized and the chip refuses the program, which
# is why tpu/blocks.py exists.  Lowered over the blocks the rule gives,
# the temporaries are a block's
# ---------------------------------------------------------------------

_PIXELS = (512, 512, 10240)
_V5E_HBM = int(15.75 * 2 ** 30)        # the chip's memory_stats() limit


def _tuning_chain():
    from bolt_tpu.ops import series
    # what ops.fourier builds behind a detrend: no pass for the mean
    return (series._normalize_fn("percentile", 20.0, 0, 0.0),
            series._detrend_fn(10240, 5, 0),
            series._fourier_fn(16, 0, 0.0).after_zero_mean,
            series._pick_fn(0, 0))


def _computations(text):
    """The computations of an optimised HLO module by name (the entry as
    ``"ENTRY"``): the lines of each, its ``ROOT`` wherever it stands
    moved to the end."""
    out, name = {}, None
    for ln in text.splitlines():
        head = re.match(r"(ENTRY )?(%[\w.\-]+) \(.*\{$", ln)
        if head:
            name = "ENTRY" if head.group(1) else head.group(2)
            out[name] = []
        elif ln == "}":
            out[name].sort(key=lambda ln: "ROOT " in ln)
            name = None
        elif name:
            out[name].append(ln)
    return out


# one of fourier's handles alone takes the whole chain and its pick; the
# pair takes the chain up to fourier's (512, 512, 2) map ONCE, as the
# shared parent's program (BoltArrayTPU._lower_from_shared, PR 39)
@pytest.mark.parametrize("picked", [1, 0], ids=["one-handle",
                                                "the-shared-parent"])
def test_the_tuning_map_compiles_blocked_and_writes_no_residual_on_v5e(
        v5e_device, picked):
    import jax
    from bolt_tpu.tpu.array import _Blocked, _chain_apply, _plan_blocks
    funcs = _tuning_chain()[:3 + picked]
    base = 4 * int(np.prod(_PIXELS))
    free = _V5E_HBM - base - 4 * 512 * 512 * (2 - picked)
    planned = _plan_blocks(funcs, 2, _PIXELS, np.float32, free)
    marker = planned[-1]
    assert type(marker) is _Blocked and planned[:-1] == funcs
    (records, block), = marker.runs
    assert records == 512 * 512 and 1024 <= block <= 16384
    where = jax.sharding.SingleDeviceSharding(v5e_device)
    arg = jax.ShapeDtypeStruct(_PIXELS, _F32, sharding=where)
    with jax.enable_x64(False):
        compiled = jax.jit(
            lambda d: _chain_apply(planned, 2, d)).lower(arg).compile()
        mem = compiled.memory_analysis()
        # the base, a 1 or 2 MB map, and a block's temporaries: under the
        # quarter of what is left that the rule allows them
        assert mem.argument_size_in_bytes == base
        assert mem.temp_size_in_bytes < 2e9
        assert mem.temp_size_in_bytes < 0.25 * free
        # and the rule's own estimate covers what the compiler needs
        assert mem.temp_size_in_bytes < _blocked_estimate(funcs, block)
        # the percentile is selected, not sorted (PR 37), and on a tile
        # held in VMEM (PR 40): no sort in the optimised program, ONE
        # Mosaic call under the selection's scope inside the loop over
        # blocks, and no loop of passes beside that loop
        text = compiled.as_text()
        assert not re.search(r"\bsort(\.\d+)? = |= \S+ sort\(", text)
        loops = re.findall(r" while\(.*?op_name=\"([^\"]*)\"", text)
        assert len(loops) == 1 and "percentile_select" not in loops[0]
        calls = re.findall(
            r"custom_call_target=\"tpu_custom_call\".*?op_name=\"([^\"]*)\"",
            text)
        assert len(calls) == 1 and "while/body" in calls[0]
        assert calls[0].endswith("percentile_select/pallas_call")
        # and that call reads its block where the array lies (PR 47): its
        # operands are the block's offset and the loop's carry of the
        # entry parameter, which nothing in the program copies, and no
        # instruction of the loop's body writes a block out by slicing
        # (every other reader has the slice fused into it)
        comps = _computations(text)
        body = comps[re.search(r" while\(.*?body=(%[\w.\-]+)", text).group(1)]
        flat = r"f32\[%d,%d\]" % (512 * 512, _PIXELS[2])
        call, = [ln for ln in body if "tpu_custom_call" in ln]
        made = {ln.split(" = ")[0].replace("ROOT ", "").strip(): ln
                for ln in body if " = " in ln}
        read = [made[name] for name in re.search(
            r"custom-call\(([^)]*)\)", call).group(1).split(", ")]
        assert len(read) == 2 and " = s32[1]" in read[0], read
        assert re.search(r" = %s\S* get-tuple-element\(" % flat, read[1]), read
        whole = r" = f32\[(%d,%d|512,512,%d)\]" % (512 * 512, _PIXELS[2],
                                                  _PIXELS[2])
        for ln in comps["ENTRY"]:
            if re.search(whole, ln):
                assert re.search(r" (parameter|bitcast)\(", ln), ln
        for ln in body:
            if not re.search(r" = f32\[%d,%d\]" % (block, _PIXELS[2]), ln):
                continue
            if " fusion(" in ln:
                ln = comps[re.search(r"calls=(%[\w.\-]+)", ln).group(1)][-1]
                assert "ROOT " in ln
            assert not re.search(r" (dynamic-slice|copy)\(", ln), ln
        # fourier's bin and energy are sums over the series (PR 44): no
        # transform in the program.  And the residual is never written
        # (PR 49: the fit is taken out element-wise and fourier spends no
        # pass on a mean its parent took out, so it is the residual's ONE
        # reader and XLA fuses the two): no instruction of the loop's
        # body has a block-sized float32 result, no temporary to speak of
        # (219,540,480 bytes before), and exactly three instructions read
        # the base: the kernel, detrend's projection, and one fusion that
        # normalises, takes the fit out and reduces to fourier's five sums
        assert not re.search(r"\bfft\b", text)
        assert mem.temp_size_in_bytes < 8 * 2 ** 20
        assert not [ln for ln in body if re.search(
            r" = \(?f32\[%d,%d\]" % (block, _PIXELS[2]), ln)]
        base_name = re.match(r"\s*(%\S+) = ", read[1]).group(1)
        readers = [ln for ln in body if " tuple(" not in ln and re.search(
            r"[(, ]%s[,)]" % re.escape(base_name), ln.split(" = ", 1)[1])]
        assert len(readers) == 3 and call in readers, readers
        fused = [ln for ln in readers if " fusion(" in ln]
        assert sorted(ln[:ln.index(" fusion(")].count("f32[%d]" % block)
                      for ln in fused) == [0, 5], fused
        # what the chip refused of the chain lowered whole was that
        # residual for every record at once; without it the whole chain
        # compiles too, beside the kernel's keys (134 MB a plane).  The
        # rule goes by its estimate of the selection, and blocks it still
        whole = jax.jit(
            lambda d: _chain_apply(funcs, 2, d)).lower(arg).compile()
        assert whole.memory_analysis().temp_size_in_bytes < 0.05 * base


# ``fourier`` straight over the resident array holds no primitive of
# ``blocks.HEAVY`` since PR 44 (it asked 40 G for its FFT before): the
# rule leaves it whole, and it compiles whole, two reads of the array (the
# mean, then the five sums in one fusion) with no temporary; float32
# products and sums on the vector unit, nothing on the MXU
def test_a_bare_fourier_compiles_whole_and_without_a_transform_on_v5e(
        v5e_device):
    import jax
    from bolt_tpu.tpu.array import _chain_apply, _plan_blocks
    from bolt_tpu.ops import series
    funcs = (series._fourier_fn(16, 0, 0.0),)
    base = 4 * int(np.prod(_PIXELS))
    free = _V5E_HBM - base - 4 * 512 * 512 * 2
    assert _plan_blocks(funcs, 2, _PIXELS, np.float32, free) == funcs
    where = jax.sharding.SingleDeviceSharding(v5e_device)
    arg = jax.ShapeDtypeStruct(_PIXELS, _F32, sharding=where)
    with jax.enable_x64(False):
        compiled = jax.jit(
            lambda d: _chain_apply(funcs, 2, d)).lower(arg).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == base
    assert mem.output_size_in_bytes == 4 * 512 * 512 * 2
    assert mem.temp_size_in_bytes < 64 * 2 ** 20     # of the rule's 1.54 GB
    text = compiled.as_text()
    assert not re.search(r"\bfft\b| while\(|convolution|\bdot\(", text)
    # two instructions read the array: the mean's reduce, and ONE
    # fusion of the five sums
    entry = text[text.index("\nENTRY "):]
    base_name = re.search(r"(%\S+) = f32\[512,512,10240\]\S* parameter\(0\)",
                          entry).group(1)
    reads = [line for line in entry.splitlines()
             if re.search(r"\(%s[,)]" % re.escape(base_name), line)]
    assert len(reads) == 2, reads
    assert any(" reduce(" in line for line in reads)
    sums, = [line for line in reads if " fusion(" in line]
    assert sums[:sums.index(" fusion(")].count("f32[512,512]") == 5, sums


# a ``fourier`` whose parent stage left the mean at zero spends no pass
# on it (PR 49), so it is ONE reader of what its parent made and XLA
# fuses the two: the array is read for the parent's own reductions
# (detrend's projection; center's mean; zscore's mean and deviation) and
# then ONCE for the five sums, with nothing written between.  ``detrend``
# takes its fit out by Horner's rule up to ``_FIT_TERMS_ON_VPU`` terms;
# above it the thin product stays, which XLA lowers as a convolution
# whose result it writes out here (records keyed by one axis; keyed by
# two it fuses that one too: the shapes decide, which is why the fit is
# not left to them)
_ONE_KEY = (131072, 10240)

_ZERO_MEAN_CASES = [
    # name, parent, shape, split, the parent's own reads, written out
    ("detrend-0", lambda s: s._detrend_fn(10240, 0, 0), _PIXELS, 2, 1, False),
    ("detrend-1", lambda s: s._detrend_fn(10240, 1, 0), _PIXELS, 2, 1, False),
    ("detrend-5", lambda s: s._detrend_fn(10240, 5, 0), _PIXELS, 2, 1, False),
    ("detrend-5-one-key-axis", lambda s: s._detrend_fn(10240, 5, 0),
     _ONE_KEY, 1, 1, False),
    ("detrend-15-one-key-axis", lambda s: s._detrend_fn(10240, 15, 0),
     _ONE_KEY, 1, 1, False),
    ("detrend-16-above-the-bound", lambda s: s._detrend_fn(10240, 16, 0),
     _ONE_KEY, 1, 1, True),
    ("center", lambda s: s._center_fn(0), _PIXELS, 2, 1, False),
    ("zscore", lambda s: s._zscore_fn(0, 0, 0.0), _PIXELS, 2, 2, False),
]


@pytest.mark.parametrize("name,parent,shape,split,own,written",
                         _ZERO_MEAN_CASES,
                         ids=[case[0] for case in _ZERO_MEAN_CASES])
def test_fourier_behind_a_zero_mean_parent_is_one_more_read_on_v5e(
        v5e_device, name, parent, shape, split, own, written):
    import jax
    from bolt_tpu.ops import series
    from bolt_tpu.tpu.array import _chain_apply, _plan_blocks
    funcs = (parent(series), series._fourier_fn(16, 0, 0.0).after_zero_mean)
    nbytes = 4 * int(np.prod(shape))
    assert _plan_blocks(funcs, split, shape, np.float32,
                        _V5E_HBM - nbytes) == funcs
    where = jax.sharding.SingleDeviceSharding(v5e_device)
    arg = jax.ShapeDtypeStruct(shape, _F32, sharding=where)
    with jax.enable_x64(False):
        compiled = jax.jit(
            lambda d: _chain_apply(funcs, split, d)).lower(arg).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    assert mem.argument_size_in_bytes == nbytes
    assert not re.search(r"\bfft\b| while\(", text)
    whole = r" = \(?f32\[%s\]" % ",".join(map(str, shape))
    entry = text[text.index("\nENTRY "):].splitlines()
    made = [ln for ln in entry if re.search(whole, ln)
            and " parameter(" not in ln]
    if written:
        # the product's residual, for the five sums to read back
        assert "convolution" in text and len(made) == 1, made
        assert nbytes <= mem.temp_size_in_bytes < 1.01 * nbytes
        return
    assert not made and mem.temp_size_in_bytes < 8 * 2 ** 20
    keys = r"f32\[%s\]" % ",".join(map(str, shape[:split]))
    reads = _argument_readers(text)
    assert len(reads) == own + 1, reads
    sums = [ln for ln in reads if " fusion(" in ln
            and len(re.findall(keys, ln[:ln.index(" fusion(")])) == 5]
    assert len(sums) == 1, reads
    if name in ("center", "zscore"):
        # the parent's mean, and no second one
        assert sum(" reduce(" in ln for ln in reads) == 1, reads


# which executor a selection gets is the lowering's to say, from the
# record's length, the key's width and what the program is lowered for
# (ISSUE 40): the kernel in a program for one v5e device; the passes for a
# key Mosaic has no type for, a record of which 8 do not fit a tile, one
# too short to hide a pass's cross-lane sums (select._KERNEL_FROM), a
# length that is not whole lane-groups, and a program GSPMD partitions
@pytest.mark.parametrize("name,shape,dtype,x64,kernel", [
    ("the-cell's-block", (5352, 10240), "float32", False, True),
    ("the-cell's-block-in-the-base", (5352, 10240), "float32", False, True),
    ("float32-under-x64", (64, 2048), "float32", True, True),
    ("float64-under-x64", (64, 2048), "float64", True, False),
    ("too-short-to-hide-a-pass", (64, 512), "float32", False, False),
    ("too-long-for-a-tile", (16, 98304 + 128), "float32", False, False),
    ("not-whole-lane-groups", (64, 2000), "float32", False, False),
], ids=lambda v: v if isinstance(v, str) and "-" in v else None)
def test_the_selections_executor_is_chosen_at_lowering_on_v5e(
        v5e_device, name, shape, dtype, x64, kernel):
    import jax
    from bolt_tpu import engine
    from bolt_tpu.ops import select, series
    fn = jax.vmap(series._normalize_fn("percentile", 20.0, 0, 0.0))
    where = jax.sharding.SingleDeviceSharding(v5e_device)
    based = name.endswith("in-the-base")
    if based:
        # the block as tpu/array.py :: _blocked_run hands it over: rows
        # of the cell's whole array from an offset the program is given
        rows, shape = fn, (512 * 512, shape[1])

        def fn(base, start):
            part = jax.lax.dynamic_slice_in_dim(base, start, 5352)
            with select.block_of(part, base, start, 8):
                return rows(part)
    count = lambda: tuple(engine.counters()["percentile_%s_lowerings" % k]
                          for k in ("kernel", "based"))
    before = count()
    with jax.enable_x64(x64):
        args = (jax.ShapeDtypeStruct(shape, np.dtype(dtype), sharding=where),
                jax.ShapeDtypeStruct((), np.int32, sharding=where))
        lowered = jax.jit(fn).lower(*args[:1 + based])
        text = lowered.as_text()
        if dtype != "float64":          # XLA's TPU compiler has no u64
            lowered.compile()           # keys; the lowering is the point
    assert count() == (before[0] + kernel, before[1] + based), name
    assert ("tpu_custom_call" in text) == kernel
    # the passes are a ``while``; the kernel leaves none
    assert ("stablehlo.while" in text) == (not kernel), name
    assert "stablehlo.sort" not in text


def test_the_selection_keeps_its_passes_in_a_program_for_four_chips(
        v5e_device):
    import jax
    from jax.experimental import topologies
    from bolt_tpu import engine
    from bolt_tpu.ops import series
    topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                        platform="tpu")
    mesh = jax.sharding.Mesh(np.asarray(topo.devices), ("k",))
    where = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("k"))
    fn = series._normalize_fn("percentile", 20.0, 0, 0.0)
    before = engine.counters()["percentile_kernel_lowerings"]
    with jax.enable_x64(False):
        text = jax.jit(jax.vmap(fn)).lower(jax.ShapeDtypeStruct(
            (64, 2048), _F32, sharding=where)).compile().as_text()
    assert engine.counters()["percentile_kernel_lowerings"] == before
    assert "tpu_custom_call" not in text and " while(" in text


# under a fully manual ``shard_map`` over those four chips each shard is
# one device's: the kernel, and inside the blocked lowering's loop it
# reads a block from the SHARD by its offset there (PR 47)
def test_the_selection_reads_a_shards_block_in_place_on_four_chips(
        v5e_device):
    import jax
    from jax.experimental import topologies
    from bolt_tpu import engine
    from bolt_tpu.ops import series
    from bolt_tpu.tpu.array import _sharded_blocked_run
    topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                        platform="tpu")
    mesh = jax.sharding.Mesh(np.asarray(topo.devices), ("k",))
    where = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("k"))
    run = (series._normalize_fn("percentile", 20.0, 0, 0.0),
           series._detrend_fn(2048, 3, 0))
    count = lambda: tuple(engine.counters()["percentile_%s_lowerings" % k]
                          for k in ("kernel", "based"))
    before = count()
    with jax.enable_x64(False):
        # 512 records a shard in blocks of 200: the last starts at 312
        text = jax.jit(lambda d: _sharded_blocked_run(
            run, 2, d, 200, mesh)).lower(jax.ShapeDtypeStruct(
                (64, 32, 2048), _F32, sharding=where)).compile().as_text()
    assert count() == (before[0] + 1, before[1] + 1)
    comps = _computations(text)
    body = comps[re.search(r" while\(.*?body=(%[\w.\-]+)", text).group(1)]
    call, = [ln for ln in body if "tpu_custom_call" in ln]
    assert "f32[512,2048]" in call and "f32[200,2048]" not in call, call


def _blocked_estimate(funcs, block):
    import jax
    from bolt_tpu.tpu import blocks
    from bolt_tpu.tpu.array import _record_fn
    rec = (jax.ShapeDtypeStruct(_PIXELS[2:], np.float32),
           jax.ShapeDtypeStruct((), np.int32),
           jax.ShapeDtypeStruct((), np.int32))
    heavy, live, _ = blocks.record_live_bytes(_record_fn(funcs), rec)
    assert heavy
    return live * block


# ---------------------------------------------------------------------
# compile-only: the streamed swap's place program on a ONE-PROCESS 2x2
# host (ISSUE 43).  ``plan.sharded`` means processes, so the exchange of
# a slab across the four chips is GSPMD's; what it has to stay is what it
# was read to be at the ``twophoton512-4chip`` cell's shapes (PERF.md, PR
# 43): ONE all-to-all of the slab as uploaded (a chip's 32 frames split
# over the four that shard the rows: whole lane tiles), no slab gathered
# whole on every chip, and the 10.74 GB a chip of the series array
# updated in place, at an offset whose low bits the compiler knows
# ---------------------------------------------------------------------

@pytest.mark.parametrize("slab,block", [
    # a caller's chunks=128 (the default before ISSUE 60): a chip's quarter
    # of the slab, the frames whole sublane tiles, the 512 of y on the lanes
    (128, r"f32\[4,128,512,32\]\{2,3,1,0:T\(8,128\)[^}]*\}"),
    # the default since ISSUE 60, a lane tile of frames a chip: the frames
    # themselves on the lanes, whole tiles of them
    (512, r"f32\[4,128,512,128\]\{3,2,1,0:T\(8,128\)[^}]*\}"),
], ids=["128-frames", "512-frames-the-default"])
def test_place_program_on_four_chips_is_one_all_to_all_and_in_place(
        v5e_device, slab, block):
    import re
    import jax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec
    from bolt_tpu.parallel import sharding as sh
    from bolt_tpu.parallel import shuffle
    topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                        platform="tpu")
    mesh = jax.sharding.Mesh(np.asarray(topo.devices[:4]), ("k",))
    from bolt_tpu import stream
    frames, frame = 40960, (512, 512)
    if slab == 512:                     # what the rule draws on this mesh
        assert slab == shuffle.lane_slab(
            stream._SLAB_BYTES // (512 * 512 * 4), frames, 512 * 512 * 4,
            (1, 2, 0), 2 * stream._SLAB_BYTES,
            shuffle.tile_width(mesh, (frames,) + frame, 1))
    plan = shuffle.plan_shuffle((frames,) + frame, np.float32, 1, (1, 2, 0),
                                2, mesh, slab, None, None, ring=7)
    assert not plan.sharded and plan.devices == 4 and plan.resident
    assert plan.alltoall_bytes == frames * 512 * 512 * 4 * 3 // 4
    slab_shape = (slab,) + frame
    slab_bytes = slab * 512 * 512 * 4
    with jax.enable_x64(False):
        prog = shuffle.place_program(plan, (), mesh, None,
                                     np.dtype(np.float32), slab_shape,
                                     True, slab)
        compiled = prog.lower(
            jax.ShapeDtypeStruct(plan.out_shape, _F32,
                                 sharding=sh.key_sharding(
                                     mesh, plan.out_shape, 2)),
            jax.ShapeDtypeStruct(slab_shape, _F32,
                                 sharding=sh.key_sharding(mesh, slab_shape,
                                                          1)),
            jax.ShapeDtypeStruct((), np.uint32, sharding=NamedSharding(
                mesh, PartitionSpec()))).compile()
    text = compiled.as_text()
    found = re.findall(r"= \S+ (all-to-all|all-gather|all-reduce|"
                       r"collective-permute|reduce-scatter)(?:-start)?\(",
                       text)
    assert found == ["all-to-all"], found
    # the exchange is of a chip's quarter of the slab
    assert re.search(block + r" all-to-all\(", text), \
        "the exchanged block changed"
    stats = compiled.memory_analysis()
    per_chip = frames * 512 * 512 * 4 // 4
    assert stats.alias_size_in_bytes == per_chip      # updated in place
    assert "input_output_alias={ {0}: (0, {}, may-alias) }" in text
    # nothing as large as a slab gathered whole beside the output
    assert stats.temp_size_in_bytes < slab_bytes
    assert stats.argument_size_in_bytes < per_chip + slab_bytes
    # the update is aligned: the offset's low bits are known
    assert "index_known_bits" in text


# ---------------------------------------------------------------------
# compile-only: the RESIDENT swap's program on the 2x2 host (ISSUE 45).
# GSPMD's program for ``stack4d-4chip.swap``'s shapes glues what a chip
# received in two passes (a ``copy`` into a staging layout, then a
# lane-merging ``reshape``: two temporaries of 3.77 GB); the explicit
# exchange and ``swap_merge`` (``parallel/swapmerge.py``) are ONE
# all-to-all, ONE kernel and bitcasts, one temporary, and the answer in
# the layout every later program of a caller expects.  Where the pieces
# are whole lane tiles GSPMD's own program is one pass already, which is
# why ``swapmerge.takes`` leaves those swaps to it.
# ---------------------------------------------------------------------

def _host_mesh():
    """The described 2x2 host as the one-axis mesh the benchmark's cell
    runs on."""
    import jax
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                        platform="tpu")
    return jax.sharding.Mesh(np.asarray(topo.devices[:4]), ("k",))


_SWAP_PERM = [1, 0, 2, 3]


def _swap_spec(mesh, records):
    import jax
    from bolt_tpu.parallel import sharding as sh
    shape = (records, 200, 64, 64)
    return jax.ShapeDtypeStruct(shape, _F32,
                                sharding=sh.key_sharding(mesh, shape, 1))


def _swap_on_the_host(records, glued):
    """``(compiled, plan)`` of ``swap((0,),(0,))`` of ``(records, 200, 64,
    64)`` float32 over the described 2x2 host: the glue's program, or the
    transpose under a constraint as ``_do_swap`` builds it."""
    import jax
    import jax.numpy as jnp
    from bolt_tpu.parallel import swapmerge
    from bolt_tpu.tpu.array import _constrain
    mesh = _host_mesh()
    spec = _swap_spec(mesh, records)
    plan = swapmerge.plan(mesh, spec.shape, _F32, 1, _SWAP_PERM, 1)
    fn = jax.jit(
        swapmerge.swapper(plan, mesh, _SWAP_PERM, spec.shape, _F32) if glued
        else lambda data: _constrain(jnp.transpose(data, _SWAP_PERM), mesh, 1))
    with jax.enable_x64(False):
        return fn.lower(spec).compile(), plan


def _after_the_exchange(text, nbytes):
    """The entry computation's operations from the one collective on that
    write an array of ``nbytes`` or more (a pass over the pieces), by
    opcode; bitcasts are views."""
    import re
    entry = text[text.index("ENTRY"):]
    found = re.findall(r"= \S+ (all-to-all|all-gather|all-reduce|"
                       r"collective-permute|reduce-scatter)(?:-start)?\(",
                       entry)
    assert found == ["all-to-all"], found
    passes = []
    for line in entry[entry.index(" all-to-all("):].splitlines()[1:]:
        m = re.search(r"= (\w+)\[([\d,]*)\]\S* ([\w-]+)\(", line)
        if m is None or m.group(3) in ("bitcast", "get-tuple-element"):
            continue
        size = np.dtype(np.float32).itemsize * int(np.prod(
            [int(d) for d in m.group(2).split(",") if d]))
        if size >= nbytes:
            passes.append(m.group(3))
    return passes


def _root_is(text, pattern):
    """Whether the entry computation's ROOT is an array matching
    ``pattern`` (shape and layout)."""
    import re
    entry = text[text.index("ENTRY"):]
    root = [line for line in entry.splitlines() if "ROOT" in line][0]
    return re.search(r"ROOT \S+ = " + pattern, root)


@pytest.mark.parametrize("records,glued", [(4400, True), (4096, False)],
                         ids=["1100-a-chip-glued", "1024-a-chip-gspmd"])
def test_swap_on_four_chips_is_one_all_to_all_and_one_pass(v5e_device,
                                                           records, glued):
    from bolt_tpu import engine
    from bolt_tpu.parallel import swapmerge
    before = engine.counters()["swap_merge_lowerings"]
    compiled, plan = _swap_on_the_host(records, glued)
    assert engine.counters()["swap_merge_lowerings"] == before + glued
    assert plan.per_chip == records // 4
    # the rule reads the same: pieces of whole lane tiles are GSPMD's
    laid = type("Laid", (), {"format": type("F", (), {"layout": type(
        "L", (), {"major_to_minor": (1, 2, 3, 0)})})})
    assert swapmerge.takes(plan, _host_mesh(), laid) == glued
    text = compiled.as_text()
    piece = records // 4 * 50 * 64 * 64 * 4
    passes = _after_the_exchange(text, piece)
    assert passes == (["custom-call"] if glued else ["copy"]), passes
    if glued:
        assert "swap_merge" in text and "tpu_custom_call" in text
    stats = compiled.memory_analysis()
    # one temporary of a chip's share as laid out, where GSPMD's program
    # for 1,100 a chip holds two (7.55 GB)
    assert stats.temp_size_in_bytes < 4.0e9
    assert stats.output_size_in_bytes == 50 * 64 * 64 * (
        -(-records // 128) * 128) * 4
    # the answer's default layout: the old key axis whole on the lanes
    assert _root_is(text, r"f32\[50,%d,64,64\]\{1,3,2,0:T\(8,128\)\}"
                    % records)


def test_gspmd_glues_the_cells_swap_in_two_passes(v5e_device):
    # what the glue replaces, so a compiler that learns the one pass is
    # noticed: then ``swapmerge.takes`` has nothing left to take
    compiled, _ = _swap_on_the_host(4400, False)
    passes = _after_the_exchange(compiled.as_text(), 1100 * 50 * 64 * 64 * 4)
    assert passes == ["copy", "reshape"], passes
    assert compiled.memory_analysis().temp_size_in_bytes > 7.0e9


def test_the_kept_export_of_the_swap_lowers_without_pallas(
        v5e_device, tmp_path, monkeypatch):
    # beside an on-disk cache the glue's program is kept exported
    # (``engine.exported``): the second lowering, a warm process's,
    # traces nothing of ``swapmerge.program`` and still holds the kernel
    import jax
    from bolt_tpu import engine
    from bolt_tpu.parallel import swapmerge
    engine.persistent_cache(str(tmp_path / "cache"))
    try:
        first, plan = _swap_on_the_host(4400, True)
        kept = list((tmp_path / "cache" / "bolt_exported").glob("*"))
        assert [k.name[:11] for k in kept] == ["swap_merge-"]
        assert _after_the_exchange(first.as_text(), 1100 * 50 * 64 * 64 * 4) \
            == ["custom-call"]
        assert first.memory_analysis().temp_size_in_bytes < 4.0e9

        def no_trace(*args):
            raise AssertionError("the kept export was not used")
        monkeypatch.setattr(swapmerge, "program", no_trace)
        mesh = _host_mesh()
        spec = _swap_spec(mesh, 4400)
        warm = jax.jit(swapmerge.swapper(plan, mesh, _SWAP_PERM, spec.shape,
                                         _F32), donate_argnums=(0,))
        with jax.enable_x64(False):
            text = warm.lower(spec).as_text()
        assert "tpu_custom_call" in text and "swap_merge" in text
    finally:
        engine.persistent_cache(enable=False)


# ---------------------------------------------------------------------
# compile-only: the slab program of a streamed ``ops.register.fit`` at
# the motion cell's shape (ISSUE 54): 64 frames of 512 x 512 float32, the
# reference an operand.  The surface is six float32 matrix products at
# HIGHEST in one layout; XLA's FFT was ten copies, six of them
# slab-sized, 7.21 GB accessed a 67 MB slab and 0.271 GB of temporaries
# ---------------------------------------------------------------------

def _fit_slab_program(v5e_device, frame):
    """The resolver's place program of ``fit`` over a ``fromcallback``
    session of ``frame`` frames, compiled: what ``stream.collect`` runs a
    slab (the plan without a budget: a described device has no memory
    statistics)."""
    import warnings
    import jax
    from bolt_tpu import stream
    from bolt_tpu.ops import register
    from bolt_tpu.parallel import shuffle
    from bolt_tpu.tpu.array import BoltArrayTPU
    mesh = _series_mesh(v5e_device)
    session = (10240,) + frame
    src = stream.StreamSource.from_callback(lambda index: None, session, 1,
                                            np.float32, mesh)
    where = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=where)
    with jax.enable_x64(False), warnings.catch_warnings():
        warnings.filterwarnings("ignore",
                                message="Some donated buffers were not")
        source = register.fit(BoltArrayTPU._streamed(src),
                              np.zeros(frame, np.float32))._stream
        st = stream.result_state(source)
        assert st.shape == (10240, 2) and st.dtype == np.int32
        plan = shuffle.plan_shuffle(st.shape, st.dtype, st.split, (0, 1),
                                    st.split, mesh, source.slab, None, None,
                                    ring=3)
        slab = (source.slab,) + frame
        program = shuffle.place_program(plan, source.stages, mesh, None,
                                        np.float32, slab, True, source.slab)
        return slab, program.lower(
            spec(plan.out_shape, np.int32), spec(slab, _F32),
            spec((), np.uint32), spec(frame, _F32)).compile()


def _slab_sized_moves(text, slab):
    """``copy`` and ``transpose`` operations of the entry computation whose
    result has as many elements as the slab, in any order of its axes."""
    entry = text[text.index("ENTRY"):]
    size = int(np.prod(slab))
    return [m.group(0) for m in re.finditer(
        r"= f32\[([\d,]+)\]\S* (copy|transpose)\(", entry)
        if int(np.prod([int(d) for d in m.group(1).split(",")])) >= size]


def test_fit_s_slab_program_is_float32_products_in_one_layout_on_v5e(
        v5e_device):
    slab, compiled = _fit_slab_program(v5e_device, (512, 512))
    assert slab == (64, 512, 512)
    text = compiled.as_text()
    products = re.findall(r" convolution\([^\n]*", text)
    assert len(products) == 6             # 2 for the reference, 4 a slab
    assert all("operand_precision={highest,highest}" in p for p in products)
    assert not re.search(r"bf16\[\d+,\d+", text)      # nothing held in bf16
    assert not re.search(r"= \S+ (fft|sort)\(", text)
    assert len(_slab_sized_moves(text, slab)) <= 1    # 6 by XLA's FFT
    assert compiled.cost_analysis()["bytes accessed"] < 3.0e9     # 7.21e9
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 0.3e9
    assert mem.output_size_in_bytes < 1 << 20     # the displacements alone



# ---------------------------------------------------------------------
# compile-only: the two slab programs of a series PCA over a recording
# past HBM (ISSUE 55; ``series64-streamed-1chip.scan_pca``): 64 planes of
# (1048576, 64) float32, 17.18 GB, a slab is one plane.  A plane arrives
# as a dense view of its bytes (rows of 8,192 lanes: the loader's
# row-major plane re-tiled on the host floods the profiler) and the
# program's first operation re-seats it, ONE copy of a slab.  Pass 1's
# program is then ONE ``packed_gram_sums`` call over a bitcast of that
# and takes no other temporary of a slab's size; pass 2's projects a
# plane and places its scores into the 2.15 GB result in place
# ---------------------------------------------------------------------

_RECORDING = (64, 1048576, 64)        # planes x voxels x time points
_PLANE = (1,) + _RECORDING[1:]
_DENSE_PLANE = (1, 8192, 8192)        # the same bytes, nothing padded


def _streamed_recording(v5e_device):
    from bolt_tpu import stream
    mesh = _series_mesh(v5e_device)
    src = stream.StreamSource.from_callback(lambda index: None, _RECORDING,
                                            1, np.float32, mesh)
    assert src.slab == 1 and len(src.slab_ranges()) == 64
    assert stream.thin_records(src.shape, src.dtype)
    assert stream.dense_route(src)
    assert stream.gram_refusal(src, (0, 1), passes=2) is None
    return src, mesh


@pytest.mark.parametrize("fused", [False, True], ids=["first", "acc-fused"])
@pytest.mark.parametrize("center", [True, False],
                         ids=["centred", "uncentred"])
def test_a_plane_is_one_packed_gram_over_a_bitcast_on_v5e(v5e_device,
                                                          center, fused):
    import warnings
    import jax
    from bolt_tpu import engine, stream
    from bolt_tpu.ops import linalg
    src, mesh = _streamed_recording(v5e_device)
    gram = stream._Gram((2, "highest", False, center))
    where = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    slab = (jax.ShapeDtypeStruct(_DENSE_PLANE, np.float32, sharding=where),)
    placed = engine.gram_kernel_lowerings()
    with jax.enable_x64(False), warnings.catch_warnings():
        warnings.filterwarnings("ignore",
                                message="Some donated buffers were not")
        first = stream._slab_program(src, gram, _PLANE,
                                     thin=True).lower(slab)
        if fused:
            acc = jax.tree_util.tree_map(
                lambda o: jax.ShapeDtypeStruct(o.shape, o.dtype,
                                               sharding=where),
                first.out_info)
            compiled = stream._slab_program(
                src, gram, _PLANE, fused=True,
                thin=True).lower(slab, acc).compile()
        else:
            compiled = first.compile()
    # the lowering said so, which is what stream_gram_kernel_slabs counts
    # (jax keeps a lowering it has made: "first" may be the last case's)
    assert engine.gram_kernel_lowerings() > placed
    text, mem = compiled.as_text(), compiled.memory_analysis()
    slab_bytes = 4 * int(np.prod(_PLANE))
    # the dense form pads nothing: the argument is the plane's own bytes
    assert slab_bytes <= mem.argument_size_in_bytes < slab_bytes + 65536
    # the re-seat of rows of 64 is TWO copies of a plane (the compiler
    # transposes the (8192, 8192) view whole, then regroups it), where
    # rows of seven take one: what place_budget takes off the budget
    assert slab_bytes <= mem.temp_size_in_bytes < 2 * slab_bytes + (1 << 20)
    assert mem.output_size_in_bytes < 65536       # (64, 64) and (64,)
    # ONE kernel call over the re-seated plane, and no matrix-unit fusion
    # with a (64, 64) result beside it
    assert text.count("tpu_custom_call") == 1
    calls = _kernel_calls(text, linalg._GRAM_SUMS_KERNEL_NAME if center
                          else linalg._GRAM_KERNEL_NAME)
    assert len(calls) == 1, calls
    assert not [ln for ln in text.splitlines() if "convolution" in ln
                and "f32[64,64]" in ln.split("=")[1][:40]]
    assert len(_slab_sized_moves(text, _PLANE)) == 2      # the re-seat


def test_the_scores_are_placed_into_their_result_in_place_on_v5e(
        v5e_device):
    import warnings
    import jax
    from bolt_tpu import stream
    from bolt_tpu.ops import linalg
    from bolt_tpu.parallel import shuffle
    src, mesh = _streamed_recording(v5e_device)
    k = 8
    scored = linalg._scores_source(src, 2, 64, True, "highest",
                                   np.zeros((64, k), np.float32),
                                   np.zeros(k, np.float32))
    assert stream.result_state(scored).shape == _RECORDING[:2] + (k,)
    # the rule the run decides by finds the scores inside a v5e's budget
    plan = shuffle.plan_shuffle(
        _RECORDING[:2] + (k,), np.float32, 1, (0, 1, 2), 1, mesh, 1,
        int(0.9 * _V5E_HBM) - 2 * 4 * int(np.prod(_PLANE)), None, ring=4,
        raw_slab_bytes=4 * int(np.prod(_PLANE)))
    out_bytes = 4 * int(np.prod(plan.out_shape))
    assert plan.resident and plan.out_shape == _RECORDING[:2] + (k,)
    assert out_bytes == 2147483648
    assert out_bytes + 4 * 268435456 <= plan.resident_bytes < 4e9
    program = shuffle.place_program(plan, scored.stages, mesh, None,
                                    np.float32, _PLANE, True, 1, True)
    where = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=where)
    with jax.enable_x64(False), warnings.catch_warnings():
        warnings.filterwarnings("ignore",
                                message="Some donated buffers were not")
        compiled = program.lower(
            spec(plan.out_shape, _F32), (spec(_DENSE_PLANE, _F32),),
            spec((), np.uint32), spec((64, k), _F32),
            spec((k,), _F32)).compile()
    mem = compiled.memory_analysis()
    assert out_bytes <= mem.output_size_in_bytes < out_bytes + 4096
    assert out_bytes <= mem.alias_size_in_bytes          # in place
    # the re-seat's two copies of a plane (537 MB: what place_budget
    # takes off the budget) and a plane's scores (33.5 MB)
    assert mem.temp_size_in_bytes < 2 * 268435456 + 0.1e9
    text = compiled.as_text()
    assert "operand_precision={highest,highest}" in text
    assert "tpu_custom_call" not in text
