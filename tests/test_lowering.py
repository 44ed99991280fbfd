"""Communication-lowering contract tests (SURVEY §2.5): the compiled HLO of
each distributed operation must contain the collective the design maps it
to — ``swap`` → ``all-to-all`` (the reference's cluster shuffle), Welford
``stats`` → ``all-reduce`` (the reference's ``rdd.aggregate`` tree), halo
exchange → ``collective-permute``.  Inspecting the framework's own cached
compiled programs guards the contract against regressions in how GSPMD
chooses collectives."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import bolt_tpu as bolt
from bolt_tpu._compat import shard_map as _shard_map


def _hlo_of_cached(kind, arg):
    """Compiled HLO text of the framework's most recent cached jit program
    whose cache key starts with ``kind``."""
    from bolt_tpu.tpu import array as array_mod
    fns = [v for k, v in array_mod._JIT_CACHE.items() if k[0] == kind]
    assert fns, "no cached %r program" % kind
    return fns[-1].lower(arg).compile().as_text()


def test_swap_lowers_to_all_to_all(mesh):
    # out key axis (16) divides the 8-device mesh: GSPMD must use the
    # bandwidth-optimal all_to_all, not an all-gather
    x = np.random.RandomState(0).randn(8, 16, 6)
    b = bolt.array(x, mesh)
    s = b.swap((0,), (0,))
    assert s.split == 1
    txt = _hlo_of_cached("swap", b._data)
    assert "all-to-all" in txt
    assert "all-gather" not in txt


def test_swap_nondivisible_still_avoids_full_gather(mesh):
    # out key axis (4) does not divide 8 devices: key_sharding replicates,
    # which costs an all-gather — allowed, but the result must be correct
    x = np.random.RandomState(1).randn(8, 4, 6)
    s = bolt.array(x, mesh).swap((0,), (0,))
    assert np.allclose(s.toarray(), np.transpose(x, (1, 0, 2)))


def test_welford_stats_lowers_to_all_reduce(mesh):
    x = np.random.RandomState(2).randn(16, 4, 6)
    b = bolt.array(x, mesh)
    b.stats()  # populates the shared executable cache
    from bolt_tpu.tpu.array import _JIT_CACHE
    fns = [v for k, v in _JIT_CACHE.items() if k[0] == "welford"]
    assert fns
    txt = fns[-1].lower(b._data).compile().as_text()
    assert "all-reduce" in txt          # psum/pmax/pmin over the mesh axis


def test_halo_exchange_lowers_to_collective_permute(mesh):
    from jax.sharding import NamedSharding
    from bolt_tpu.parallel.halo import exchange_halo

    x = jnp.asarray(np.random.RandomState(3).randn(16, 4))
    sh = jax.device_put(x, NamedSharding(mesh, P("k")))
    f = _shard_map(lambda d: exchange_halo(d, axis=0, pad=1, axis_name="k"),
                      mesh=mesh, in_specs=P("k"), out_specs=P("k"))
    txt = jax.jit(f).lower(sh).compile().as_text()
    assert "collective-permute" in txt


def test_key_reduction_lowers_to_all_reduce(mesh):
    # sum over the sharded key axis: GSPMD inserts the psum tree
    x = np.random.RandomState(4).randn(16, 4, 6)
    b = bolt.array(x, mesh)
    s = b.sum(axis=(0,))
    assert np.allclose(np.asarray(s.toarray()), x.sum(axis=0))
    txt = _hlo_of_cached("stat", b._data)
    assert "all-reduce" in txt


def test_sharded_smooth_lowers_to_neighbour_collective(mesh2d):
    # sequence-parallel filtering: the value axis is mesh-split, so the
    # halo each block borrows must ride an inserted neighbour collective
    # (collective-permute, or all-to-all/all-gather if GSPMD so chooses) —
    # NOT a host round-trip, and the program must communicate
    from bolt_tpu.ops import smooth
    x = np.random.RandomState(5).randn(4, 16, 3)
    b = bolt.array(x, mesh2d, axis=(0,))
    out = smooth(b, 5, axis=(0,), size=(4,), shard={0: "b"})
    oracle = smooth(bolt.array(x), 5, axis=(0,), size=(4,))
    assert np.allclose(out.toarray(), oracle.toarray())
    txt = _hlo_of_cached("chunk-map-g", b._data)
    assert ("collective-permute" in txt or "all-to-all" in txt
            or "all-gather" in txt), "no inter-device halo communication"


# ---------------------------------------------------------------------
# round-2 op families (VERDICT r2 #6): on a sharded input none of these
# may lower to a FULL all-gather of the operand — scatter/sort + the
# collective the design maps them to.  `unique` is the documented
# exception below.
# ---------------------------------------------------------------------


def test_segment_reduce_lowers_to_scatter_all_reduce(mesh):
    import jax.numpy as jnp
    from bolt_tpu.ops import segment_reduce
    from bolt_tpu.tpu import array as array_mod
    x = np.random.RandomState(7).randn(64, 32)
    b = bolt.array(x, mesh)
    labels = np.arange(64) % 5
    out = segment_reduce(b, labels, op="sum")
    assert out.shape == (5, 32)
    fns = [v for k, v in array_mod._JIT_CACHE.items() if k[0] == "segreduce"]
    txt = fns[-1].lower(b._data, jnp.asarray(labels, jnp.int32)) \
        .compile().as_text()
    assert "scatter" in txt             # the segment combine
    assert "all-reduce" in txt          # cross-shard group merge
    assert "all-gather" not in txt      # operand never replicates


def test_take_on_sharded_axis_avoids_full_gather(mesh):
    import jax.numpy as jnp
    from bolt_tpu.tpu import array as array_mod
    x = np.random.RandomState(8).randn(64, 32)
    b = bolt.array(x, mesh)
    out = b.take([3, 1, 9], axis=0)     # gather along the SHARDED axis
    assert np.allclose(out.toarray(), x[[3, 1, 9]])
    fns = [v for k, v in array_mod._JIT_CACHE.items() if k[0] == "take"]
    txt = fns[-1].lower(b._data, jnp.asarray([3, 1, 9], jnp.int32)) \
        .compile().as_text()
    assert "all-gather" not in txt      # masked-sum gather, not replication
    assert "all-reduce" in txt


def test_argsort_along_sharded_axis_uses_all_to_all(mesh):
    from bolt_tpu.tpu import array as array_mod
    x = np.random.RandomState(9).randn(64, 32)
    b = bolt.array(x, mesh)
    out = b.argsort(axis=0, kind="stable")   # global sort ALONG the shards
    assert np.array_equal(np.asarray(out.toarray()),
                          x.argsort(axis=0, kind="stable"))
    fns = [v for k, v in array_mod._JIT_CACHE.items() if k[0] == "argsort"]
    txt = fns[-1].lower(b._data).compile().as_text()
    assert "all-to-all" in txt          # distributed sort exchange
    assert "all-gather" not in txt      # never the full operand


def test_value_axis_sort_argsort_are_collective_free(mesh):
    from bolt_tpu.tpu import array as array_mod
    x = np.random.RandomState(10).randn(64, 32)
    b = bolt.array(x, mesh)
    b.argsort(axis=1)
    c = bolt.array(x, mesh)
    c.sort(axis=1)
    for kind in ("argsort", "sort"):
        fns = [v for k, v in array_mod._JIT_CACHE.items() if k[0] == kind]
        txt = fns[-1].lower(b._data).compile().as_text()
        for coll in ("all-gather", "all-to-all", "all-reduce",
                     "collective-permute"):
            assert coll not in txt, (kind, coll)   # rows are shard-local


def test_topk_is_collective_free_on_value_axis(mesh):
    # lax.top_k all-gathers a sharded operand (measured); the argsort
    # formulation partitions cleanly — rows are shard-local, so top-k
    # along a value axis needs NO communication at all
    from bolt_tpu.ops import topk
    from bolt_tpu.tpu import array as array_mod
    x = np.random.RandomState(11).randn(64, 32)
    b = bolt.array(x, mesh)
    v, i = topk(b, 3, axis=1)
    lv, li = topk(bolt.array(x), 3, axis=1)
    assert np.allclose(np.asarray(v.toarray()), np.asarray(lv.toarray()))
    assert np.array_equal(np.asarray(i.toarray()), np.asarray(li.toarray()))
    fns = [v_ for k, v_ in array_mod._JIT_CACHE.items() if k[0] == "topk"]
    txt = fns[-1].lower(b._data).compile().as_text()
    for coll in ("all-gather", "all-to-all", "all-reduce",
                 "collective-permute"):
        assert coll not in txt, coll


def test_topk_on_sharded_axis_avoids_full_gather(mesh):
    from bolt_tpu.ops import topk
    from bolt_tpu.tpu import array as array_mod
    x = np.random.RandomState(12).randn(64, 32)
    b = bolt.array(x, mesh)
    v, i = topk(b, 3, axis=0)          # selection ALONG the shards
    lv, li = topk(bolt.array(x), 3, axis=0)
    assert np.allclose(np.asarray(v.toarray()), np.asarray(lv.toarray()))
    assert np.array_equal(np.asarray(i.toarray()), np.asarray(li.toarray()))
    fns = [v_ for k, v_ in array_mod._JIT_CACHE.items() if k[0] == "topk"]
    txt = fns[-1].lower(b._data).compile().as_text()
    assert "all-gather" not in txt      # all-to-all sort, not replication


def test_bincount_lowers_to_all_reduce_no_gather(mesh):
    from bolt_tpu.ops import bincount
    from bolt_tpu.tpu import array as array_mod
    x = np.random.RandomState(13).randint(0, 9, size=(64, 8))
    b = bolt.array(x, mesh)
    assert np.array_equal(bincount(b), np.bincount(x.ravel()))
    fns = [v for k, v in array_mod._JIT_CACHE.items() if k[0] == "bincount"]
    txt = fns[-1].lower(b._data).compile().as_text()
    assert "all-reduce" in txt
    assert "all-gather" not in txt


def test_unique_shard_local_is_collective_free(mesh):
    # round-3: unique on a sharded input runs SHARD-LOCAL (per-shard
    # sort/mask/gather via shard_map + exact host merge) — zero
    # collectives, where GSPMD's global 1-d sort would all-gather the
    # whole operand onto every device (measured; constraints and (n,1)
    # reshapes don't help).  Layouts the shard-local gate declines
    # (replicated dims, uneven splits, multi-process) fall back to the
    # whole-array program, whose global-sort gather remains the one
    # documented exception.
    from bolt_tpu.ops import unique
    from bolt_tpu.tpu import array as array_mod
    x = np.random.RandomState(14).randint(0, 7, size=(64, 4)).astype(float)
    b = bolt.array(x, mesh)
    assert np.array_equal(unique(b), np.unique(x))
    for kind in ("unique-shard-sort", "unique-shard-gather"):
        fns = [(k, v) for k, v in array_mod._JIT_CACHE.items()
               if k[0] == kind]
        assert fns, kind
    (k1, f1) = [(k, v) for k, v in array_mod._JIT_CACHE.items()
                if k[0] == "unique-shard-sort"][-1]
    txt = f1.lower(b._data).compile().as_text()
    assert "sort" in txt
    for coll in ("all-gather", "all-to-all", "all-reduce",
                 "collective-permute"):
        assert coll not in txt, coll


def test_quantile_lowers_to_sorted_collective_program(mesh):
    # a key-axis quantile over the sharded axis must sort on device and
    # combine across shards (GSPMD inserts the gather/reduce it needs)
    x = np.random.RandomState(6).randn(16, 6)
    b = bolt.array(x, mesh)
    out = b.quantile(0.5)
    assert np.allclose(out.toarray(), np.median(x, axis=0))
    from bolt_tpu.tpu import array as array_mod
    fns = [v for k, v in array_mod._JIT_CACHE.items() if k[0] == "quantile"]
    assert fns
    txt = fns[-1].lower(b._data, 0.5).compile().as_text()  # q is an ARG
    assert "sort" in txt


# ---------------------------------------------------------------------
# a basic-slice getitem in front of a statistic (ISSUE 25): the slice is
# an entry of the chain, traced inside the statistic's own program
# ---------------------------------------------------------------------

def test_window_and_statistic_are_one_program(mesh):
    from bolt_tpu import engine
    from bolt_tpu.tpu import array as array_mod
    # geometry unique to this test so every engine key is fresh
    x = np.random.RandomState(25).randn(32, 6, 7)
    b = bolt.array(x, mesh)
    before = set(array_mod._JIT_CACHE)
    c0 = engine.counters()
    got = b[5:21].mean(axis=(0, 1, 2)).toarray()
    c1 = engine.counters()
    assert np.allclose(got, x[5:21].mean())
    new = [k for k in array_mod._JIT_CACHE if k not in before]
    assert [k[0] for k in new] == ["stat"]        # and no "getitem"
    assert c1["dispatches"] - c0["dispatches"] == 1
    assert c1["getitems_fused"] - c0["getitems_fused"] == 1
    (key,) = new
    assert any(type(f) is array_mod._Window for f in key[2])
    lowered = array_mod._JIT_CACHE[key].lower(b._data)
    # the whole base is the program's one parameter, the scalar its result
    (arg,), _ = lowered.args_info
    assert tuple(arg.shape) == x.shape
    assert tuple(lowered.out_info.shape) == ()
    text = lowered.compile().as_text()
    entry = text[text.index("ENTRY"):]
    assert entry.count(" parameter(") == 1
    assert "f64[] " in entry.splitlines()[0].split("->")[1]
    # on the sharded key axis the slice is partitioned inside that one
    # program and its partial sums combined there
    assert "all-reduce" in text
