"""Profiling/timing instrumentation tests (the tracing slot of SURVEY §5)."""

import glob
import os

import numpy as np

import bolt_tpu as bolt
from bolt_tpu import profile


def test_timeit_and_throughput(mesh):
    b = bolt.ones((8, 32), mesh)
    result, secs = profile.timeit(lambda: b.map(lambda v: v * 2).sum()._data,
                                  iters=2, warmup=1)
    assert secs > 0
    assert np.allclose(np.asarray(result), np.full(32, 16.0))
    gbps = profile.throughput(profile.array_bytes(b), secs)
    assert gbps > 0


def test_array_bytes(mesh):
    b = bolt.ones((8, 4), mesh, dtype=np.float32)
    assert profile.array_bytes(b) == 8 * 4 * 4


def test_annotate_and_trace(tmp_path, mesh):
    """A region is named with ``obs.span`` (``profile.annotate`` is gone):
    outside a profile it records nothing and costs nothing, inside one it
    lands in the device trace as ``bolt.<name>``."""
    from jax.profiler import ProfileData
    from bolt_tpu import obs
    # the ring keeps what an earlier file of this worker recorded and
    # disabled without clearing: start from an empty one
    obs.clear()
    with obs.span("bolt-test-region"):
        bolt.ones((8, 2), mesh).sum().toarray()
    assert obs.spans() == []
    logdir = str(tmp_path / "trace")
    with profile.trace(logdir):
        with obs.span("bolt-test-region"):
            bolt.ones((8, 2), mesh).sum().toarray()
    obs.clear()
    assert os.path.isdir(logdir)
    path, = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    names = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert "bolt.bolt-test-region" in names


def test_debug_nans_toggle():
    import jax
    profile.debug_nans(True)
    assert jax.config.jax_debug_nans
    profile.debug_nans(False)
    assert not jax.config.jax_debug_nans


def test_memory_stats_dict():
    from bolt_tpu.profile import memory_stats
    s = memory_stats()
    assert isinstance(s, dict)  # CPU backend may expose {} or counters
    for k, v in s.items():
        assert isinstance(k, str) and isinstance(v, int)


def test_instrument_counts_ops_and_builds(mesh):
    import bolt_tpu as bolt
    from bolt_tpu import profile
    x = np.random.RandomState(0).randn(8, 4, 5)
    b = bolt.array(x, mesh)
    f = lambda v: v * 2
    with profile.instrument() as stats:
        for _ in range(3):
            b.map(f).sum().toarray()
        b.stats()
    assert "stat" in stats and stats["stat"]["calls"] == 3
    # one compiled program serves all three identical pipelines
    assert stats["stat"]["builds"] == 1
    assert "welford" in stats
    assert stats["stat"]["dispatch_s"] >= 0.0
    txt = profile.report(stats)
    assert "stat" in txt and "builds" in txt
    # the patch is scoped: outside the context the plain cache is back
    import bolt_tpu.tpu.array as arr
    import bolt_tpu.tpu.stats as stats_mod
    assert arr._cached_jit is stats_mod._cached_jit


def test_instrument_detects_recompiles(mesh):
    import bolt_tpu as bolt
    from bolt_tpu import profile
    b = bolt.array(np.random.RandomState(1).randn(8, 4), mesh)
    with profile.instrument() as stats:
        for _ in range(3):
            b.map(lambda v: v + 1).sum().toarray()   # fresh lambda: rebuilds
    assert stats["stat"]["builds"] == 3              # the smoking gun
