"""Test configuration: a fake 8-device CPU mesh.

The reference tests distribution with a local-mode SparkContext
(``test/conftest.py :: sc`` fixture, ``local[2]`` — SURVEY §4): same code
paths, no cluster.  The analog here is 8 virtual CPU devices via
``xla_force_host_platform_device_count``, so ``psum``/``all_to_all``/
sharding semantics run for real without TPU hardware.

x64 is enabled so dtypes match the NumPy oracle exactly (the reference is
bit-compatible with numpy defaults; SURVEY §7 "decide early").
"""

import os

# must be appended before the first backend initialisation
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402

# tests run on the virtual CPU mesh whatever the host holds (a chip
# host defaults JAX to the TPU) — EXCEPT under BOLT_TEST_CHIP=1, the
# on-chip correctness gate (scripts/chip_gate.py): real TPU backend with
# production x64-OFF numerics, running only the `-m chip` subset
# (tests/test_chip.py)
CHIP_GATE = os.environ.get("BOLT_TEST_CHIP", "").lower() in ("1", "true",
                                                             "yes")
if not CHIP_GATE:
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402


# the concurrency suites: every test in these modules runs under the
# armed lockdep witness (bolt_tpu/_lockdep) via the autouse fixture
# below — one observed rank inversion, self-deadlock or
# dispatch-under-lock anywhere in them fails the test that did it
_LOCKDEP_SUITES = frozenset({
    "test_serve", "test_serve_batching", "test_serve_streams",
    "test_stream",
    "test_supervisor", "test_multistat", "test_parity_locks",
    "test_podwatch",
})


def pytest_collection_modifyitems(config, items):
    """Under the chip gate the CPU-mesh/x64 assumptions of every other
    test are void — deselect everything unmarked so a bare
    ``BOLT_TEST_CHIP=1 pytest`` is safe without the wrapper script's
    ``-m chip`` flag.  Outside it, tag the concurrency suites with the
    ``lockdep`` marker so they run under the armed witness (and are
    selectable standalone via ``pytest -m lockdep``)."""
    if not CHIP_GATE:
        for item in items:
            base = os.path.basename(item.nodeid.split("::", 1)[0])
            if base[:-3] in _LOCKDEP_SUITES:
                item.add_marker(pytest.mark.lockdep)
        return
    skip = pytest.mark.skip(
        reason="BOLT_TEST_CHIP gate runs only the -m chip subset")
    for item in items:
        if "chip" not in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="module", autouse=True)
def _thread_census_gate():
    """Hygiene gate (ISSUE 17): no bolt-owned worker thread may outlive
    its test module.  A short drain window absorbs daemon workers that
    were signalled to exit but not yet reaped when teardown returns."""
    yield
    import time
    from bolt_tpu.obs import thread_census
    census = thread_census()
    deadline = time.monotonic() + 5.0
    while census and time.monotonic() < deadline:
        time.sleep(0.05)
        census = thread_census()
    assert census == {}, "module leaked worker threads: %s" % (census,)


@pytest.fixture(autouse=True)
def _lockdep_witness(request):
    """Arm the runtime lock-hierarchy witness around every ``lockdep``-
    marked test and fail the test on any NEW violation it recorded —
    the suites exercise the real thread pools, so a green run is an
    empirical no-inversion certificate for the lock inventory."""
    if "lockdep" not in request.keywords:
        yield
        return
    from bolt_tpu import _lockdep
    before = len(_lockdep.violations())
    was_enabled = _lockdep.enabled()
    _lockdep.enable()
    try:
        yield
    finally:
        if not was_enabled:
            _lockdep.disable()
    new = _lockdep.violations()[before:]
    assert not new, "lockdep violations during test:\n" + "\n".join(new)


@pytest.fixture(scope="session")
def mesh():
    """1-d 8-device mesh — the default distribution context."""
    return jax.make_mesh((8,), ("k",))


@pytest.fixture(scope="session")
def mesh2d():
    """2-d (4, 2) mesh for multi-axis key sharding."""
    return jax.make_mesh((4, 2), ("a", "b"))
