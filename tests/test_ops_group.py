"""Segmented (grouped) reductions — the reduceByKey analog — and
bincount, on both backends vs a NumPy mirror."""

import numpy as np
import pytest

import bolt_tpu as bolt
from bolt_tpu.ops import bincount, segment_reduce
from bolt_tpu.utils import allclose


def _x(shape=(12, 4, 3), seed=80):
    return np.random.RandomState(seed).randn(*shape)


def _mirror(x, labels, nseg, op):
    out = []
    for g in range(nseg):
        rows = x[labels == g]
        if len(rows) == 0:
            if op in ("sum", "mean"):
                out.append(np.zeros(x.shape[1:]))
            else:
                out.append(np.full(x.shape[1:],
                                   -np.inf if op == "max" else np.inf))
        elif op == "sum":
            out.append(rows.sum(axis=0))
        elif op == "mean":
            out.append(rows.mean(axis=0))
        elif op == "max":
            out.append(rows.max(axis=0))
        else:
            out.append(rows.min(axis=0))
    return np.stack(out)


@pytest.mark.parametrize("op", ["sum", "mean", "max", "min"])
def test_segment_reduce_parity(mesh, op):
    x = _x()
    labels = np.array([0, 2, 1, 0, 2, 2, 1, 0, 3, 3, 0, 2])
    expected = _mirror(x, labels, 4, op)
    for b in (bolt.array(x), bolt.array(x, mesh)):
        out = segment_reduce(b, labels, op=op)
        assert out.shape == (4,) + x.shape[1:]
        assert allclose(out.toarray(), expected), (b.mode, op)
    t = segment_reduce(bolt.array(x, mesh), labels, op=op)
    assert t.split == 1


def test_segment_reduce_empty_group_and_num_segments(mesh):
    x = _x((6, 2))
    labels = np.array([0, 0, 3, 3, 3, 0])       # groups 1, 2 empty
    for b in (bolt.array(x), bolt.array(x, mesh)):
        out = np.asarray(segment_reduce(b, labels, num_segments=5).toarray())
        assert out.shape == (5, 2)
        assert np.allclose(out[1], 0) and np.allclose(out[2], 0)
        assert np.allclose(out[4], 0)
        assert np.allclose(out[0], x[labels == 0].sum(axis=0))


def test_segment_reduce_deferred_chain(mesh):
    x = _x()
    labels = np.arange(12) % 3
    b = bolt.array(x, mesh).map(lambda v: v * 2)   # deferred chain fuses in
    out = segment_reduce(b, labels, op="sum")
    assert allclose(out.toarray(), _mirror(x * 2, labels, 3, "sum"))


def test_segment_reduce_int_mean(mesh):
    x = np.arange(24, dtype=np.int64).reshape(8, 3)
    labels = np.array([0, 1, 0, 1, 0, 1, 0, 1])
    for b in (bolt.array(x), bolt.array(x, mesh)):
        out = np.asarray(segment_reduce(b, labels, op="mean").toarray())
        assert np.issubdtype(out.dtype, np.floating)
        assert np.allclose(out, _mirror(x.astype(float), labels, 2, "mean"))


def test_segment_reduce_errors(mesh):
    b = bolt.array(_x(), mesh)
    with pytest.raises(ValueError):
        segment_reduce(b, np.arange(5))           # wrong length
    with pytest.raises(ValueError):
        segment_reduce(b, np.arange(12), op="prod")
    with pytest.raises(ValueError):
        segment_reduce(b, np.arange(12) - 1)      # negative label
    with pytest.raises(ValueError):
        segment_reduce(b, np.arange(12), num_segments=5)  # label 11 > 4
    with pytest.raises(ValueError):
        segment_reduce(b, np.arange(12.0))        # non-integer labels


def test_bincount_parity(mesh):
    x = np.random.RandomState(81).randint(0, 9, size=(16, 5))
    for b in (bolt.array(x), bolt.array(x, mesh)):
        got = bincount(b)
        assert got.dtype == np.int64
        assert np.array_equal(got, np.bincount(x.reshape(-1)))
        got = bincount(b, minlength=20)
        assert np.array_equal(got, np.bincount(x.reshape(-1), minlength=20))
    with pytest.raises(TypeError):
        bincount(bolt.array(_x(), mesh))          # floats rejected
    with pytest.raises(ValueError):
        bincount(bolt.array(x - 5, mesh))         # negatives rejected


def test_segment_reduce_multi_key_axes(mesh):
    # split > 1: labels still address axis-0 records; the other key axes
    # ride along in the value block on BOTH backends
    x = _x((4, 2, 3, 2))
    labels = np.array([0, 1, 0, 1])
    lo = segment_reduce(bolt.array(x), labels, op="sum")
    tp = segment_reduce(bolt.array(x, mesh, axis=(0, 1)), labels, op="sum")
    expected = np.stack([x[labels == g].sum(axis=0) for g in range(2)])
    assert allclose(lo.toarray(), expected)
    assert allclose(tp.toarray(), expected)


def test_segment_reduce_device_labels_no_host_bounce(mesh, monkeypatch):
    # a jax.Array (or bolt TPU array) labels input must stay on device:
    # the label DATA never passes through np.asarray (ADVICE r2 / VERDICT
    # r2 #4: the bounce is a device->host->device copy of the labels);
    # only the two-scalar range validation syncs
    import jax.numpy as jnp
    from bolt_tpu.ops import group
    x = _x()
    labels_host = np.array([0, 2, 1, 0, 2, 2, 1, 0, 3, 3, 0, 2])
    expected = _mirror(x, labels_host, 4, "sum")
    dev_labels = jnp.asarray(labels_host)

    bounced = []
    real_asarray = np.asarray

    def spy(a, *args, **kwargs):
        if a is dev_labels:
            bounced.append(a)
        return real_asarray(a, *args, **kwargs)

    monkeypatch.setattr(group.np, "asarray", spy)
    b = bolt.array(x, mesh)
    for nseg in (None, 4):
        out = segment_reduce(b, dev_labels, num_segments=nseg, op="sum")
        assert allclose(out.toarray(), expected)
    assert not bounced
    # bolt TPU-array labels unwrap to the device array, same guarantee
    blabels = bolt.array(labels_host, mesh)
    out = segment_reduce(b, blabels, op="sum")
    assert allclose(out.toarray(), expected)
    # device labels still validate range
    with pytest.raises(ValueError):
        segment_reduce(b, jnp.asarray(labels_host - 1))
    with pytest.raises(ValueError):
        segment_reduce(b, dev_labels, num_segments=2)
    # foreign-mesh bolt labels are rejected loudly, like binary operands
    import jax
    other_mesh = jax.make_mesh((4, 2), ("a", "b"))
    with pytest.raises(ValueError, match="different meshes"):
        segment_reduce(b, bolt.array(labels_host, other_mesh))


def test_bincount_chunked_accumulation(mesh, monkeypatch):
    # force the x32-wraparound chunked path (ADVICE r2): int32 partials
    # per chunk, host-int64 combine — result identical to the one-shot
    # program at any chunk size, including a ragged tail
    from bolt_tpu.ops import group
    x = np.random.RandomState(84).randint(0, 9, size=(16, 5))
    expected = np.bincount(x.reshape(-1), minlength=11)
    monkeypatch.setattr(group, "_BINCOUNT_CHUNK", 17)   # 80 elems -> 5 chunks
    got = bincount(bolt.array(x, mesh), minlength=11)
    assert got.dtype == np.int64
    assert np.array_equal(got, expected)
    monkeypatch.setattr(group, "_BINCOUNT_CHUNK", 80)   # exact fit: no chunking
    assert np.array_equal(bincount(bolt.array(x, mesh), minlength=11), expected)


def test_segment_reduce_one_program_many_labels(mesh):
    # labels are a traced argument: distinct label vectors reuse ONE
    # compiled program (keying on label bytes would recompile per vector)
    from bolt_tpu.tpu.array import _JIT_CACHE
    x = _x()
    b = bolt.array(x, mesh)
    segment_reduce(b, np.arange(12) % 4, num_segments=4, op="sum")
    n_before = sum(1 for k in _JIT_CACHE if k[0] == "segreduce")
    segment_reduce(b, np.arange(12) % 2 * 3, num_segments=4, op="sum")
    segment_reduce(b, np.zeros(12, dtype=int), num_segments=4, op="sum")
    assert sum(1 for k in _JIT_CACHE if k[0] == "segreduce") == n_before


def test_bincount_empty(mesh):
    e = bolt.array(np.zeros((0, 3), np.int64), mesh)
    assert np.array_equal(bincount(e, minlength=4), np.zeros(4, np.int64))
    assert np.array_equal(bincount(bolt.array(np.zeros((0,), np.int64)),
                                   minlength=2), np.zeros(2, np.int64))


def test_unique_parity(mesh):
    from bolt_tpu.ops import unique
    x = np.random.RandomState(82).randint(0, 7, size=(9, 4)).astype(np.float64)
    for b in (bolt.array(x), bolt.array(x, mesh)):
        u = unique(b)
        assert np.array_equal(u, np.unique(x)), b.mode
        u, c = unique(b, return_counts=True)
        un, cn = np.unique(x, return_counts=True)
        assert np.array_equal(u, un) and np.array_equal(c, cn), b.mode
    # ints, all-same, and deferred chains
    i = bolt.array(np.full((4, 3), 5), mesh)
    u, c = unique(i, return_counts=True)
    assert np.array_equal(u, [5]) and np.array_equal(c, [12])
    m = bolt.array(x, mesh).map(lambda v: v * 0 + 2.0)
    assert np.array_equal(unique(m), [2.0])
    # empty
    e = bolt.array(np.zeros((0, 3)), mesh)
    u, c = unique(e, return_counts=True)
    assert u.size == 0 and c.size == 0


def test_unique_nan_semantics(mesh):
    from bolt_tpu.ops import unique
    x = np.array([[1.0, np.nan], [np.nan, 1.0]])
    un, cn = np.unique(x, return_counts=True)
    for b in (bolt.array(x), bolt.array(x, mesh)):
        u, c = unique(b, return_counts=True)
        # modern numpy collapses NaNs to one entry; counts aggregate
        assert u.shape == un.shape, b.mode
        assert np.isnan(u[-1]) and u[0] == 1.0
        assert np.array_equal(c, cn), b.mode


def test_topk_parity(mesh):
    from bolt_tpu.ops import topk
    x = np.random.RandomState(83).randn(8, 6, 5)
    for b in (bolt.array(x), bolt.array(x, mesh)):
        for axis in (-1, 0, 1):
            v, i = topk(b, 3, axis=axis)
            moved = np.moveaxis(x, axis, -1)
            ref_i = np.argsort(-moved, axis=-1, kind="stable")[..., :3]
            ref_v = np.take_along_axis(moved, ref_i, axis=-1)
            assert allclose(v.toarray(), np.moveaxis(ref_v, -1, axis)), (b.mode, axis)
            assert np.array_equal(np.asarray(i.toarray()),
                                  np.moveaxis(ref_i, -1, axis)), (b.mode, axis)
    t, _ = topk(bolt.array(x, mesh), 3, axis=2)
    assert t.split == 1 and t.shape == (8, 6, 3)
    # key-axis topk keeps the key role
    t, _ = topk(bolt.array(x, mesh), 2, axis=0)
    assert t.split == 1 and t.shape == (2, 6, 5)
    # ties: lower index first on both backends
    z = np.zeros((4, 4))
    for b in (bolt.array(z), bolt.array(z, mesh)):
        _, i = topk(b, 2)
        assert np.array_equal(np.asarray(i.toarray()), np.tile([0, 1], (4, 1)))


def test_topk_errors(mesh):
    from bolt_tpu.ops import topk
    b = bolt.array(_x(), mesh)
    with pytest.raises(ValueError):
        topk(b, 0)
    with pytest.raises(ValueError):
        topk(b, 99, axis=0)
    with pytest.raises(ValueError):
        topk(b, 1, axis=9)
    with pytest.raises(TypeError):
        topk(b, 1, axis=1.5)
    # deferred chain fuses in
    v, _ = topk(bolt.array(_x(), mesh).map(lambda r: -r), 2, axis=0)
    moved = np.moveaxis(-_x(), 0, -1)
    ref = np.moveaxis(np.take_along_axis(
        moved, np.argsort(-moved, axis=-1, kind="stable")[..., :2], -1), -1, 0)
    assert allclose(v.toarray(), ref)


def test_topk_dtype_and_nan_parity(mesh):
    # the review's repro set: unsigned wrap, INT_MIN, bools, NaNs — both
    # backends must agree with lax.top_k semantics
    from bolt_tpu.ops import topk
    cases = [
        np.array([[5, 0, 3]], dtype=np.uint32),
        np.array([[np.iinfo(np.int32).min, 4, -2]], dtype=np.int32),
        np.array([[True, False, True]]),
        np.array([[1.0, np.nan, 3.0, 2.0]]),
    ]
    for x in cases:
        lo_v, lo_i = topk(bolt.array(x), 2)
        tp_v, tp_i = topk(bolt.array(x, mesh), 2)
        lv, tv = np.asarray(lo_v.toarray()), np.asarray(tp_v.toarray())
        assert np.array_equal(lv, tv, equal_nan=True), (x.dtype, lv, tv)
        assert np.array_equal(np.asarray(lo_i.toarray()),
                              np.asarray(tp_i.toarray())), x.dtype
    with pytest.raises(TypeError):
        topk(bolt.array(cases[0], mesh), 2.7)


def test_segment_reduce_matmul_path(mesh):
    """Round-5: the one-hot MXU form (auto-picked for small segment
    counts) must match the scatter combine and the oracle exactly-
    enough, with numpy semantics for non-finite records preserved by
    the runtime fallback."""
    from bolt_tpu.ops import segment_reduce
    rs = np.random.RandomState(33)
    x = rs.randn(32, 6, 4)
    lab = rs.randint(0, 5, 32)
    b, lo = bolt.array(x, mesh), bolt.array(x)
    for op in ("sum", "mean"):
        gm = np.asarray(segment_reduce(
            b, lab, num_segments=5, op=op, method="matmul").toarray())
        gs = np.asarray(segment_reduce(
            b, lab, num_segments=5, op=op, method="scatter").toarray())
        e = np.asarray(segment_reduce(
            lo, lab, num_segments=5, op=op).toarray())
        assert np.allclose(gm, gs, rtol=1e-6, atol=1e-9)
        assert np.allclose(gm, e, rtol=1e-6, atol=1e-9)
    # per-call precision kwarg and the scoped policy both serve
    gm = segment_reduce(b, lab, num_segments=5, method="matmul",
                        precision="high")
    with bolt.precision("default"):
        gd = segment_reduce(b, lab, num_segments=5, method="matmul")
    assert np.allclose(np.asarray(gm.toarray()), np.asarray(gd.toarray()),
                       rtol=1e-5, atol=1e-8)


def test_segment_reduce_matmul_nonfinite_fallback(mesh):
    """0 x NaN would poison whole value columns through the one-hot
    matmul; the fused isfinite guard must fall back to scatter
    semantics at runtime — NaN/Inf stay confined to their own
    segment."""
    from bolt_tpu.ops import segment_reduce
    rs = np.random.RandomState(34)
    x = rs.randn(16, 5)
    x[3, 2] = np.nan
    x[7, 1] = np.inf
    x[9, 1] = -np.inf
    lab = rs.randint(0, 4, 16)
    b, lo = bolt.array(x, mesh), bolt.array(x)
    g = np.asarray(segment_reduce(
        b, lab, num_segments=4, method="matmul").toarray())
    e = np.asarray(segment_reduce(lo, lab, num_segments=4).toarray())
    assert np.array_equal(np.isnan(g), np.isnan(e))
    assert np.array_equal(np.isposinf(g), np.isposinf(e))
    assert np.array_equal(np.isneginf(g), np.isneginf(e))
    fin = np.isfinite(e)
    assert np.allclose(g[fin], e[fin])


def test_segment_reduce_method_validation(mesh):
    from bolt_tpu.ops import segment_reduce
    b = bolt.array(np.ones((8, 3), np.int32), mesh)
    with pytest.raises(ValueError, match="method"):
        segment_reduce(b, [0] * 8, num_segments=1, method="magic")
    # int sum cannot ride the (inexact) matmul; int MEAN promotes first
    with pytest.raises(ValueError, match="matmul"):
        segment_reduce(b, [0] * 8, num_segments=1, method="matmul")
    out = segment_reduce(b, [0] * 8, num_segments=1, op="mean",
                         method="matmul")
    assert np.allclose(np.asarray(out.toarray()), 1.0)
    with pytest.raises(ValueError, match="matmul"):
        segment_reduce(bolt.array(np.ones((4, 2)), mesh), [0] * 4,
                       num_segments=1, op="max", method="matmul")
    # the SAME invalid call rejects identically on the local oracle
    with pytest.raises(ValueError, match="matmul"):
        segment_reduce(bolt.array(np.ones((4, 2))), [0] * 4,
                       num_segments=1, op="max", method="matmul")
    # empty leading axis: forced matmul degrades to the (identical)
    # zeros result instead of crashing in a 0-size reshape
    z = bolt.array(np.zeros((0, 3)), mesh)
    out = segment_reduce(z, np.array([], dtype=np.int64), num_segments=4,
                         method="matmul")
    assert out.shape == (4, 3) and not np.asarray(out.toarray()).any()
