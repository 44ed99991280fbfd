"""HBM-scale guards (VERDICT r2 weak-4): ops with input-multiple
transients switch to bounded chunked paths above ``_CHUNK_MAX_BYTES``
(forced small here), and ops with inherently input-sized outputs check
their demand up front — a clear MemoryError instead of an opaque XLA
OOM."""

import numpy as np
import pytest

import bolt_tpu as bolt
from bolt_tpu.tpu import array as array_mod


def _x(shape=(32, 8, 6), seed=40):
    return np.random.RandomState(seed).randn(*shape)


def test_unique_chunked_parity(mesh, monkeypatch):
    # force the CHUNKED path (the shard-local path would otherwise serve
    # this multi-device layout first)
    import bolt_tpu.ops.group as group
    monkeypatch.setattr(group, "_unique_sharded", lambda *a: None)
    monkeypatch.setattr(array_mod, "_CHUNK_MAX_BYTES", 256)
    x = np.random.RandomState(41).randint(0, 13, size=(16, 9)).astype(float)
    b = bolt.array(x, mesh)
    u, c = bolt.ops.unique(b, return_counts=True)
    un, cn = np.unique(x, return_counts=True)
    assert np.array_equal(u, un) and np.array_equal(c, cn)
    assert u.dtype == un.dtype and c.dtype == np.int64
    # the chunked programs actually ran
    assert any(k[0] == "unique-chunk-sort" for k in array_mod._JIT_CACHE)
    # no-counts variant
    assert np.array_equal(bolt.ops.unique(b), un)


def test_unique_chunked_nan_merge(mesh, monkeypatch):
    # NaNs collapse to ONE entry across chunks, counts aggregated —
    # same as modern numpy on the whole array
    import bolt_tpu.ops.group as group
    monkeypatch.setattr(group, "_unique_sharded", lambda *a: None)
    monkeypatch.setattr(array_mod, "_CHUNK_MAX_BYTES", 64)
    x = np.array([[1.0, np.nan, 2.0, np.nan]] * 8)
    b = bolt.array(x, mesh)
    u, c = bolt.ops.unique(b, return_counts=True)
    un, cn = np.unique(x, return_counts=True)
    assert u.shape == un.shape
    assert np.isnan(u[-1]) and np.array_equal(u[:-1], un[:-1])
    assert np.array_equal(c, cn)


def test_unique_chunked_deferred_chain(mesh, monkeypatch):
    import bolt_tpu.ops.group as group
    monkeypatch.setattr(group, "_unique_sharded", lambda *a: None)
    monkeypatch.setattr(array_mod, "_CHUNK_MAX_BYTES", 128)
    x = np.random.RandomState(42).randint(0, 5, size=(12, 6)).astype(float)
    m = bolt.array(x, mesh).map(lambda v: v * 3)
    assert np.array_equal(bolt.ops.unique(m), np.unique(x * 3))


def test_argsort_chunked_parity(mesh, monkeypatch):
    monkeypatch.setattr(array_mod, "_CHUNK_MAX_BYTES", 512)
    x = _x()
    b = bolt.array(x, mesh)
    for axis, kind in [(1, None), (0, "stable"), (-1, "stable"), (2, None)]:
        got = b.argsort(axis=axis, kind=kind)
        assert got.split == b.split
        assert np.array_equal(np.asarray(got.toarray()),
                              x.argsort(axis=axis, kind="stable")
                              if kind else np.asarray(
                                  bolt.array(x).argsort(axis=axis).toarray())
                              ), (axis, kind)
    assert any(k[0] == "argsort-slab" for k in array_mod._JIT_CACHE)
    # flat argsort has no slab axis: falls through to the single program
    flat = bolt.array(x, mesh).argsort(axis=None, kind="stable")
    assert np.array_equal(np.asarray(flat.toarray()),
                          x.argsort(axis=None, kind="stable"))


def test_unique_sharded_path_parity(mesh, mesh2d):
    # the shard-local unique: per-shard sort/mask/gather + exact host
    # merge, zero collectives — serves every common multi-device layout
    from bolt_tpu.ops import unique
    import bolt_tpu.ops.group as group
    x = np.random.RandomState(45).randint(0, 9, size=(16, 6)).astype(float)
    x[3, 2] = np.nan
    x[9, 1] = np.nan
    for m in (mesh, mesh2d):
        import bolt_tpu as _b
        b = _b.array(x, m, axis=(0,) if m is mesh else (0, 1))
        u, c = unique(b, return_counts=True)
        un, cn = np.unique(x, return_counts=True)
        assert u.shape == un.shape
        assert np.array_equal(u[:-1], un[:-1]) and np.isnan(u[-1])
        assert np.array_equal(c, cn)
        # THIS mesh's shard program ran (key carries the mesh — without
        # this the 2-d iteration could pass on the 1-d mesh's entry);
        # compare by topology: ensure_auto may rebuild the Mesh object
        assert any(k[0] == "unique-shard-sort"
                   and k[-1].axis_names == m.axis_names
                   for k in array_mod._JIT_CACHE), m
    # deferred chains materialise through it
    mch = bolt.array(np.full((8, 4), 2.0), mesh).map(lambda v: v + 1)
    assert np.array_equal(unique(mch), [3.0])


def test_unique_sharded_declines_ineligible_layouts(mesh):
    # layouts the gate declines fall back to the whole-array program
    # with CORRECT COUNTS (a wrongly-accepting gate on a replicated
    # layout would multiply counts by the device count — values alone
    # would merge clean and hide it)
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from bolt_tpu.ops import unique
    from bolt_tpu.tpu.array import BoltArrayTPU
    # (a) replicated: 6 keys cannot divide 8 devices under key_spec
    x = np.random.RandomState(46).randint(0, 5, size=(6, 4)).astype(float)
    b = bolt.array(x, mesh)
    u, c = unique(b, return_counts=True)
    un, cn = np.unique(x, return_counts=True)
    assert np.array_equal(u, un) and np.array_equal(c, cn)
    # (b) uneven splits cannot even be CONSTRUCTED in this jax version
    # (NamedSharding rejects them at device_put) — the divisibility gate
    # in _unique_sharded is defense in depth for future/other layouts
    xu = np.zeros((12, 4))
    with pytest.raises(ValueError, match="evenly divide"):
        jax.device_put(xu, NamedSharding(mesh, P("k", None)))
    _ = BoltArrayTPU      # imported above; gate itself exercised in (a)


def test_unique_fallback_lowering_pinned(mesh, monkeypatch):
    # the whole-array fallback (declined layouts) still global-sorts;
    # pin its program so a GSPMD partitioner change is NOTICED (its
    # operand gather is the one documented lowering exception)
    import bolt_tpu.ops.group as group
    from bolt_tpu.ops import unique
    from bolt_tpu.tpu import array as array_mod
    monkeypatch.setattr(group, "_unique_sharded", lambda *a: None)
    x = np.random.RandomState(48).randint(0, 7, size=(64, 4)).astype(float)
    b = bolt.array(x, mesh)
    assert np.array_equal(unique(b), np.unique(x))
    fns = [v for k, v in array_mod._JIT_CACHE.items()
           if k[0] == "unique-sort"]
    assert fns
    txt = fns[-1].lower(b._data).compile().as_text()
    assert "sort" in txt


def test_topk_chunked_parity(mesh, monkeypatch):
    monkeypatch.setattr(array_mod, "_CHUNK_MAX_BYTES", 512)
    x = _x()
    b = bolt.array(x, mesh)
    for axis in (0, 1):
        v, i = bolt.ops.topk(b, 3, axis=axis)
        lv, li = bolt.ops.topk(bolt.array(x), 3, axis=axis)
        assert np.allclose(np.asarray(v.toarray()),
                           np.asarray(lv.toarray())), axis
        assert np.array_equal(np.asarray(i.toarray()),
                              np.asarray(li.toarray())), axis
    assert any(k[0] == "topk-slab" for k in array_mod._JIT_CACHE)


def test_topk_chunked_split_key(mesh, monkeypatch):
    # two arrays of the same shape but different splits must NOT share a
    # compiled cat program (r3 review finding: the key omitted split, so
    # the second call's outputs were constrained to the first's split)
    monkeypatch.setattr(array_mod, "_CHUNK_MAX_BYTES", 512)
    x = _x((8, 8, 6))
    v1, _ = bolt.ops.topk(bolt.array(x, mesh, axis=(0,)), 2, axis=1)
    v2, _ = bolt.ops.topk(bolt.array(x, mesh, axis=(0, 1)), 2, axis=2)
    assert v1.split == 1 and v2.split == 2
    lv1, _ = bolt.ops.topk(bolt.array(x), 2, axis=1)
    lv2, _ = bolt.ops.topk(bolt.array(x), 2, axis=2)
    assert np.allclose(np.asarray(v1.toarray()), np.asarray(lv1.toarray()))
    assert np.allclose(np.asarray(v2.toarray()), np.asarray(lv2.toarray()))


def test_np_quantile_numpy_only_method_falls_back(mesh):
    # jnp.quantile lacks numpy's other estimators; the dispatch serves
    # them on the host path instead of erroring (r3 review finding)
    x = _x()
    b = bolt.array(x, mesh)
    got = np.quantile(b, 0.5, method="inverted_cdf")
    assert np.allclose(got, np.quantile(x, 0.5, method="inverted_cdf"))


def test_slab_plan_picks_largest_carry_axis(monkeypatch):
    # a small first axis cannot cut slabs fine enough to honour the
    # byte bound; the plan must pick the LARGEST other axis (r3 review)
    monkeypatch.setattr(array_mod, "_CHUNK_MAX_BYTES", 1 << 10)
    cax, pairs = array_mod.slab_plan((2, 64, 8), axis=2, in_bytes=1 << 13)
    assert cax == 1
    assert len(pairs) == 8              # 8 KB / 1 KB target
    assert pairs[0][0] == 0 and pairs[-1][1] == 64
    assert array_mod.slab_plan((1, 16), axis=1, in_bytes=1 << 20) is None


def test_topk_hbm_check_engages(mesh, monkeypatch):
    # topk's unchunked paths carry the same up-front demand check as
    # sort/argsort (r3 review finding: it had none)
    monkeypatch.setattr(array_mod, "_HBM_LIMIT_OVERRIDE", 1 << 10)
    b = bolt.array(_x(), mesh)
    with pytest.raises(MemoryError, match="topk"):
        bolt.ops.topk(b, 2, axis=-1)


def test_small_inputs_skip_chunked_paths(mesh):
    # below the threshold nothing slab-shaped compiles
    x = _x((6, 4))
    bolt.ops.unique(bolt.array(x, mesh))
    bolt.array(x, mesh).argsort(axis=0)
    bolt.ops.topk(bolt.array(x, mesh), 2, axis=0)
    assert not any(k[0] in ("unique-chunk-sort", "argsort-slab",
                            "topk-slab")
                   for k in array_mod._JIT_CACHE
                   if len(k) > 1 and k[1] in ((6, 4), (24,)))


def test_hbm_check_known_limit_raises(mesh, monkeypatch):
    monkeypatch.setattr(array_mod, "_HBM_LIMIT_OVERRIDE", 1 << 10)
    b = bolt.array(_x(), mesh)
    with pytest.raises(MemoryError, match="cumsum"):
        b.cumsum()
    with pytest.raises(MemoryError, match="sort"):
        b.sort()
    with pytest.raises(MemoryError, match="argsort"):
        b.argsort(axis=None)
    # env var is honoured the same way
    monkeypatch.setattr(array_mod, "_HBM_LIMIT_OVERRIDE", None)
    monkeypatch.setenv("BOLT_HBM_BYTES", str(1 << 10))
    with pytest.raises(MemoryError, match="cumprod"):
        b.cumprod()


def test_hbm_check_under_limit_is_silent(mesh, monkeypatch):
    import warnings
    monkeypatch.setattr(array_mod, "_HBM_LIMIT_OVERRIDE", 1 << 40)
    b = bolt.array(_x(), mesh)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        b.cumsum(axis=0)
        b.argsort(axis=0)
