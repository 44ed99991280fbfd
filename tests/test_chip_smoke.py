"""chip_smoke.py off the chip: its phases at a tiny size on the CPU mesh,
its refusal to pass without a TPU, and where the compile cache goes.

The real run is ``python chip_smoke.py`` on a TPU host (one process, x64
off, real sizes); what can be pinned here is that every phase's control
flow and oracle still agree with the package, so a chip run is never the
first run of the file.
"""

import os
import subprocess
import sys

import jax
import pytest

from bolt_tpu import engine
from bolt_tpu.parallel.mesh import default_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = chip_smoke.Sizes(
    northstar=(64, 4, 8, 128), donate=(64, 8, 128),
    resident=(64, 8, 8, 256), svd=(8, 512, 16), svd_chunk=64,
    stream=(128, 8, 128), stream_chunks=16, swap_records=64,
    spill_records=64, spill_budget=1, serve_shape=(16, 8),
    serve_requests=16, serve_stream_records=32)


@pytest.fixture(scope="module")
def start():
    return engine.counters()


def test_streamed_phase(start, tmp_path):
    chip_smoke.phase_streamed(default_mesh(), TINY, 0, str(tmp_path))


def test_resident_phase():
    with engine.donation(0):        # the 64 MiB floor, scaled with TINY
        chip_smoke.phase_resident(default_mesh(), TINY, 0)


def test_served_phase():
    chip_smoke.phase_served(default_mesh(), TINY, 0)


def test_end_checks_pass_after_the_phases(start):
    chip_smoke.end_checks(start)


def test_refuses_to_pass_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


# ---------------------------------------------------------------------
# the compile cache can be placed from outside
# ---------------------------------------------------------------------

@pytest.fixture
def cache_state():
    before = jax.config.jax_compilation_cache_dir
    yield before
    engine.persistent_cache(enable=False)
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_placed_by_the_environment(tmp_path, monkeypatch,
                                         cache_state):
    placed = tmp_path / "placed"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(placed))
    monkeypatch.setattr(engine, "_CHECKOUT_CACHE_DIR",
                        str(tmp_path / "checkout" / ".jax_cache"))
    # the variable wins over an explicit argument, start_warm= included
    for got in (engine.persistent_cache(),
                engine.persistent_cache(str(tmp_path / "mine")),
                engine.warm_start(str(tmp_path / "warm"))):
        assert got == str(placed)
    assert engine.persistent_cache_dir() == str(placed)
    # jax's own setting is exactly as jax read it; nothing else created
    assert jax.config.jax_compilation_cache_dir == cache_state
    assert os.listdir(tmp_path) == []
    engine.persistent_cache(enable=False)
    assert jax.config.jax_compilation_cache_dir == cache_state


def test_cache_defaults_to_the_checkout(tmp_path, monkeypatch,
                                        cache_state):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert engine._CHECKOUT_CACHE_DIR == os.path.join(ROOT, ".jax_cache")
    beside = str(tmp_path / "checkout" / ".jax_cache")
    monkeypatch.setattr(engine, "_CHECKOUT_CACHE_DIR", beside)
    assert engine.persistent_cache() == beside
    assert jax.config.jax_compilation_cache_dir == beside
    assert os.path.isdir(beside)
