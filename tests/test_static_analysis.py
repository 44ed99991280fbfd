"""bolt_tpu.analysis: abstract pipeline checker + repo invariant linter.

Two halves (ISSUE 2 tentpole):

* the CHECKER — ``analysis.check``/``explain`` abstractly interpret a
  deferred pipeline (``_chain``/``_pending``/``_fpending``) with zero
  XLA compiles, predicting result shape/dtype/sharding per stage and
  emitting ``BLT0xx`` diagnostics; ``analysis.strict()`` makes every
  dispatching terminal run the checker first and refuse on
  error-severity findings;
* the LINTER — ``analysis.astlint`` enforces the repo invariants
  (``BLT1xx``: engine-routed jit, _compat-routed version-sensitive jax,
  resolver-routed precision, gate-routed ``._concrete``); zero findings
  on ``bolt_tpu/`` itself is a tier-1 invariant (also runnable
  standalone: ``pytest -m lint`` / ``scripts/lint_bolt.py --check``).

Every diagnostic code and every lint rule has a seeded violation here.
"""

import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bolt_tpu as bolt
from bolt_tpu import analysis, engine, obs
from bolt_tpu.analysis import PipelineError, astlint
from bolt_tpu.tpu.array import BoltArrayTPU

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _x():
    return np.random.RandomState(0).randn(16, 6, 4)


def _no_new_compiles(c0, c1):
    for k in ("misses", "aot_compiles", "dispatches"):
        assert c1[k] == c0[k], (k, c0[k], c1[k])


# ----------------------------------------------------------------------
# checker: predictions
# ----------------------------------------------------------------------

def test_check_concrete_array(mesh):
    b = bolt.array(_x(), mesh)
    c0 = engine.counters()
    rep = analysis.check(b)
    _no_new_compiles(c0, engine.counters())
    assert rep.ok and rep.shape == (16, 6, 4)
    assert np.dtype(rep.dtype) == np.float64
    assert rep.stages[0].spec is not None


def test_check_predicts_chain_shape_and_dtype(mesh):
    b = bolt.array(_x(), mesh).map(lambda v: v * 2).map(
        lambda v: v.sum(axis=0)).map(lambda v: v.astype(np.float32))
    c0 = engine.counters()
    rep = analysis.check(b)
    _no_new_compiles(c0, engine.counters())
    assert rep.ok
    assert len(rep.stages) == 4            # base + three map stages
    assert rep.shape == (16, 4)
    assert np.dtype(rep.dtype) == np.float32
    got = np.asarray(b.toarray())
    assert got.shape == rep.shape and got.dtype == rep.dtype


def test_check_with_keys_stage(mesh):
    b = bolt.array(_x(), mesh).map(lambda kv: kv[1] + kv[0][0],
                                   with_keys=True)
    rep = analysis.check(b)
    assert rep.ok and rep.shape == (16, 6, 4)
    assert "with_keys" in rep.stages[1].op


def test_check_deferred_filter_is_dynamic_and_does_not_resolve(mesh):
    b = bolt.array(_x(), mesh).map(lambda v: v + 1).filter(
        lambda v: v.mean() > 0)
    c0 = engine.counters()
    rep = analysis.check(b)
    _no_new_compiles(c0, engine.counters())
    assert b.pending                       # the checker did NOT resolve it
    assert rep.ok and rep.dynamic
    assert rep.shape == (None, 6, 4)
    assert rep.max_shape == (16, 6, 4)
    assert rep.has("BLT008")
    # reality check: resolving matches the predicted value dims/dtype
    got = np.asarray(b.toarray())
    assert got.shape[1:] == rep.shape[1:]
    assert got.dtype == np.dtype(rep.dtype)


def test_check_views_and_explain(mesh):
    b = bolt.array(_x(), mesh).map(lambda v: v * 3)
    rep = analysis.check(b.chunk(size=(3,), axis=(0,)))
    assert rep.ok and "chunked view" in rep.target
    rep2 = analysis.check(b.stacked(size=4))
    assert rep2.ok and "stacked view" in rep2.target
    txt = analysis.explain(b)
    assert "stage 0" in txt and "map" in txt and "OK" in txt


def test_check_local_array_trivial():
    b = bolt.array(_x())
    rep = analysis.check(b)
    assert rep.ok and rep.shape == (16, 6, 4)


# ----------------------------------------------------------------------
# checker: seeded diagnostics, one per code
# ----------------------------------------------------------------------

def test_blt001_stage_trace_failure(mesh):
    base = bolt.array(_x(), mesh)._data
    bad = BoltArrayTPU._deferred(
        base, (lambda v: v @ jnp.ones((99, 2)),), 1, mesh,
        jax.ShapeDtypeStruct((16, 2), np.float64))
    rep = analysis.check(bad)
    assert not rep.ok and rep.has("BLT001")
    d = [e for e in rep.errors if e.code == "BLT001"][0]
    assert d.stage == 1 and "abstract tracing" in d.message


def test_blt002_recorded_aval_lie(mesh):
    base = bolt.array(_x(), mesh)._data
    liar = BoltArrayTPU._deferred(
        base, (lambda v: v * 2,), 1, mesh,
        jax.ShapeDtypeStruct((16, 99), np.float32))   # lies twice
    rep = analysis.check(liar)
    assert not rep.ok and rep.has("BLT002")
    assert "(16, 99)" in str(rep)


def test_blt003_dtype_widening(mesh):
    b = bolt.array(_x().astype(np.float32), mesh).map(
        lambda v: v * np.float64(2))
    rep = analysis.check(b)
    assert rep.ok                          # warning, not error
    assert rep.has("BLT003")
    assert np.dtype(rep.dtype) == np.float64
    assert np.asarray(b.toarray()).dtype == np.float64   # it predicted reality


def test_blt004_indivisible_keys(mesh):
    b = bolt.array(np.random.RandomState(1).randn(6, 4), mesh)
    rep = analysis.check(b)
    assert rep.ok and rep.has("BLT004")
    w = [d for d in rep.warnings if d.code == "BLT004"][0]
    assert "mesh devices" in w.message and "(6,)" in w.message


def test_blt005_use_after_donate_names_operation(mesh):
    x = _x()
    with engine.donation(0):
        d = bolt.array(x, mesh).map(lambda v: v + 1)
        d.sum()                            # donates the sole-owned base
        rep = analysis.check(d)
        assert not rep.ok and rep.has("BLT005")
        assert "sum()" in rep.errors[0].message
        with pytest.raises(RuntimeError, match=r"donated to sum\(\)"):
            d.toarray()


def test_blt006_donation_forecast_is_side_effect_free(mesh):
    x = _x()
    with engine.donation(0):
        d = bolt.array(x, mesh).map(lambda v: v + 1)
        rep = analysis.check(d)
        assert rep.ok and rep.has("BLT006")
        # the forecast consumed nothing: the terminal still donates
        n0 = engine.counters()["donations"]
        d.sum()
        assert engine.counters()["donations"] == n0 + 1
    # outside the scope (default 64 MB floor) small chains do not donate
    d2 = bolt.array(x, mesh).map(lambda v: v + 1)
    assert not analysis.check(d2).has("BLT006")


def test_check_survives_malformed_split_state(mesh):
    # hand-built deferred state with split beyond the base rank: the
    # checker must DIAGNOSE (BLT001 from the impossible vmap), not crash
    # deriving shardings — and strict must refuse, not IndexError
    base = bolt.array(np.ones((8, 4)), mesh)._data
    bad = BoltArrayTPU._deferred(
        base, (lambda v: v,), 5, mesh,
        jax.ShapeDtypeStruct((8, 4), np.float64))
    rep = analysis.check(bad)
    assert not rep.ok and rep.has("BLT001")
    with analysis.strict():
        with pytest.raises(PipelineError):
            bad.sum()


def test_blt007_nonscalar_predicate_seeded(mesh):
    b = bolt.array(_x(), mesh)
    bad = BoltArrayTPU(None, 1, mesh)
    from bolt_tpu.tpu.array import _Filter
    bad._fpending = _Filter(
        b._data, (), lambda v: v > 0, 1, (6, 4), 16, np.dtype(np.float64),
        (), jax.ShapeDtypeStruct((6, 4), np.float64))
    rep = analysis.check(bad)
    assert not rep.ok and rep.has("BLT007")
    assert "scalar" in str(rep)


def test_donated_filter_metadata_raises_named_guard(mesh):
    # a filter array consumed by a donating fused terminal has no
    # recorded aval (its count was never synced): shape/dtype must hit
    # the NAMED donation guard, not AttributeError on the None aval
    with engine.donation(0):
        f = bolt.array(_x(), mesh).filter(lambda v: v.mean() > 0)
        f.sum()
        for read in (lambda: f.shape, lambda: f.dtype, lambda: f.toarray()):
            with pytest.raises(RuntimeError,
                               match=r"donated to filter\(\)\.sum\(\)"):
                read()


def test_donated_array_repr_never_raises(mesh):
    # printing an array is how users diagnose a donation — repr must
    # show the consuming terminal, not raise the guard itself
    with engine.donation(0):
        f = bolt.array(_x(), mesh).filter(lambda v: v.mean() > 0)
        f.sum()
        assert "filter().sum()" in repr(f)
        d = bolt.array(_x(), mesh).map(lambda v: v + 1)
        d.sum()
        r = repr(d)
        assert "sum()" in r and "(16, 6, 4)" in r


def test_donation_scope_is_thread_local(mesh):
    x = _x()
    floors = []
    inner = threading.Event()
    done = threading.Event()

    def other_thread():
        inner.wait(5)
        floors.append(engine.donation_min_bytes())
        # this thread is OUTSIDE the scope: the small chain must NOT
        # donate, and stays readable after its terminal
        d = bolt.array(x, mesh).map(lambda v: v + 1)
        d.sum()
        floors.append(d.toarray().shape)
        done.set()

    t = threading.Thread(target=other_thread)
    t.start()
    with engine.donation(0):
        inner.set()
        assert done.wait(30)
    t.join()
    assert floors[0] == engine.donation_min_bytes()   # default, not 0
    assert floors[0] and floors[0] >= 1
    assert floors[1] == (16, 6, 4)


def test_precision_alias_import_keeps_package_scope_callable(mesh):
    # loading the legacy alias module clobbers the package attribute
    # with the module object; the alias must stay CALLABLE so
    # bolt.precision("default") keeps working afterwards
    from bolt_tpu.precision import resolve as r   # triggers the clobber
    import bolt_tpu
    with bolt_tpu.precision("default"):
        assert r() == "default"
    assert r() == "highest"


def test_diagnostics_counter_fed_by_checker(mesh):
    c0 = engine.counters()["diagnostics"]
    analysis.check(bolt.array(_x(), mesh).filter(lambda v: v.mean() > 0))
    assert engine.counters()["diagnostics"] > c0   # >= the BLT008 info


# ----------------------------------------------------------------------
# strict scope: the engine's pre-dispatch gate
# ----------------------------------------------------------------------

def test_strict_clean_pipeline_dispatches(mesh):
    x = _x()
    with analysis.strict():
        c0 = engine.counters()["strict_checks"]
        out = bolt.array(x, mesh).map(lambda v: v + 1).sum()
        assert engine.counters()["strict_checks"] > c0
    assert np.allclose(np.asarray(out.toarray()), (x + 1).sum(axis=0),
                       equal_nan=True)


def test_strict_refuses_error_findings_before_any_compile(mesh):
    base = bolt.array(_x(), mesh)._data
    bad = BoltArrayTPU._deferred(
        base, (lambda v: v @ jnp.ones((99, 2)),), 1, mesh,
        jax.ShapeDtypeStruct((16, 2), np.float64))
    c0 = engine.counters()
    with analysis.strict():
        with pytest.raises(PipelineError, match="BLT001"):
            bad.sum()
        with pytest.raises(PipelineError, match="refusing to dispatch"):
            bad.reduce(np.add)
    c1 = engine.counters()
    _no_new_compiles(c0, c1)               # refused BEFORE compiling
    assert c1["strict_rejections"] >= c0["strict_rejections"] + 2
    # outside the scope the gate is disarmed: the failure is jax's own,
    # surfacing at the lazy terminal's first read
    with pytest.raises(Exception):
        bad.sum().cache()


def test_strict_gates_views_and_filters(mesh):
    base = bolt.array(_x(), mesh)._data
    bad = BoltArrayTPU._deferred(
        base, (lambda v: v @ jnp.ones((99, 2)),), 1, mesh,
        jax.ShapeDtypeStruct((16, 2), np.float64))
    with analysis.strict():
        with pytest.raises(PipelineError):
            bad.chunk(size=(3,), axis=(0,)).map(lambda blk: blk * 2)
        with pytest.raises(PipelineError):
            bad.stacked(size=4).map(lambda blk: blk - 1)
        with pytest.raises(PipelineError):
            bad.toarray()                  # chain materialisation
    # the scope unwound: a clean pipeline needs no strict bookkeeping
    assert bolt.array(_x(), mesh).map(lambda v: v).sum() is not None


def test_strict_is_thread_local(mesh):
    errs = []

    def other_thread():
        try:
            assert not analysis.in_strict()
            bolt.array(np.ones((8, 3)), mesh).map(lambda v: v + 1).sum()
        except Exception as exc:           # pragma: no cover
            errs.append(exc)

    with analysis.strict():
        assert analysis.in_strict()
        t = threading.Thread(target=other_thread)
        t.start()
        t.join()
    assert not errs
    assert not analysis.in_strict()


# ----------------------------------------------------------------------
# use-after-donate coverage for the view terminals (satellite):
# the guard names the donating operation; check flags it BEFORE the
# next dispatch is attempted
# ----------------------------------------------------------------------

def test_chunk_map_donation_guard_names_operation(mesh):
    x = np.abs(_x())
    with engine.donation(0):
        d = bolt.array(x, mesh).map(lambda v: v * 3)
        got = d.chunk(size=(3,), axis=(0,)).map(lambda blk: blk * 2)
        assert np.allclose(got.unchunk().toarray(), x * 6)
        rep = analysis.check(d)            # flagged before any dispatch
        assert not rep.ok and rep.has("BLT005")
        assert "chunk().map()" in rep.errors[0].message
        with pytest.raises(RuntimeError,
                           match=r"donated to chunk\(\)\.map\(\)"):
            d.toarray()


def test_stack_map_donation_guard_names_operation(mesh):
    x = np.abs(_x())
    with engine.donation(0):
        d = bolt.array(x, mesh).map(lambda v: v - 1)
        got = d.stacked(size=4).map(lambda blk: blk * 2)
        assert np.allclose(got.unstack().toarray(), (x - 1) * 2)
        rep = analysis.check(d)
        assert not rep.ok and rep.has("BLT005")
        assert "stacked().map()" in rep.errors[0].message
        with pytest.raises(RuntimeError,
                           match=r"donated to stacked\(\)\.map\(\)"):
            d.sum()


def test_swap_donation_guard_names_operation(mesh):
    with engine.donation(0):
        b = bolt.array(_x(), mesh)
        b.swap((0,), (0,), donate=True)
        rep = analysis.check(b)
        assert not rep.ok and rep.has("BLT005")
        with pytest.raises(RuntimeError, match=r"swap"):
            b.toarray()


# ----------------------------------------------------------------------
# every kind of deferred state an array can hold: the checker predicts
# its shape, dtype and dynamic-ness with zero XLA compiles and leaves no
# span open
# ----------------------------------------------------------------------

ADD1 = lambda v: v + 1
MEANPOS = lambda v: v.mean() > 0


def _streamed(mesh, shape=(16, 8, 4), **kw):
    x = (np.arange(int(np.prod(shape))) % 7).astype(
        np.float32).reshape(shape)
    return bolt.fromcallback(lambda idx: x[idx], shape, mesh,
                             dtype=np.float32, chunks=4, **kw)


def _f32(shape, seed=7):
    return np.random.RandomState(seed).randn(*shape).astype(np.float32)


# the pre-terminal state's builder, and a diagnostic that state's own
# forecast must carry (or None)
@pytest.mark.parametrize("build,code", [
    pytest.param(lambda m: bolt.array(
        np.ones((16, 8, 4), np.float32), m).map(ADD1), None,
        id="map-chain"),
    pytest.param(lambda m: bolt.array(
        _f32((16, 6, 4)), m).filter(MEANPOS), "BLT008",
        id="deferred-filter"),
    pytest.param(lambda m: bolt.array(
        _f32((8, 32, 4)), m).map(ADD1).chunk(size=(8,), axis=(0,)), None,
        id="chunked-view-over-chain"),
    pytest.param(lambda m: _streamed(m).chunk(
        size=(4,), axis=(0,)).map(ADD1), None,
        id="streamed-chunked-map"),
    pytest.param(lambda m: _streamed(m).map(ADD1), None,
                 id="streamed-map"),
    pytest.param(lambda m: _streamed(
        m, shape=(16, 8), per_process=True).map(ADD1), None,
        id="streamed-map-per-process"),
    pytest.param(lambda m: _streamed(m, codec="bf16").map(ADD1), "BLT016",
                 id="streamed-map-codec"),
    pytest.param(lambda m: _streamed(m).swap((0,), (0,)), "BLT017",
                 id="streamed-swap"),
    pytest.param(lambda m: bolt.array(
        _f32((16, 6, 4)), m)[4:12].map(ADD1), None,
        id="window-then-map"),
    pytest.param(lambda m: bolt.array(
        _f32((16, 6, 4)), m).map(ADD1).sum(), "BLT009",
        id="lazy-statistic"),
])
def test_deferred_states_check_clean(mesh, build, code):
    arr = build(mesh)
    obs.clear()
    obs.enable()
    try:
        c0 = engine.counters()
        rep = analysis.check(arr)
        _no_new_compiles(c0, engine.counters())
        assert rep.ok, rep.diagnostics
        assert code is None or rep.has(code), rep.diagnostics
        target = arr.unchunk() if hasattr(arr, "unchunk") else arr
        got_shape = tuple(target.shape)      # resolves/dispatches NOW
        got_dtype = np.dtype(target.dtype)
        if rep.dynamic:
            assert rep.shape[0] is None
            assert rep.shape[1:] == got_shape[1:]
        else:
            assert rep.shape == got_shape
        assert np.dtype(rep.dtype) == got_dtype
        assert obs.active_count() == 0
    finally:
        obs.disable()
        obs.clear()


# ----------------------------------------------------------------------
# the linter: zero findings on the package itself, and a seeded
# violation per rule
# ----------------------------------------------------------------------

@pytest.mark.lint
def test_lint_package_reports_zero_findings():
    findings = astlint.lint_package()
    assert findings == [], "\n".join(f.render() for f in findings)


@pytest.mark.lint
def test_lint_blt101_bare_jit():
    src = "import jax\nfn = jax.jit(lambda x: x + 1)\n"
    f = astlint.lint_source(src, "bolt_tpu/somewhere.py")
    assert [x.code for x in f] == ["BLT101"]
    # the engine-builder pattern is the sanctioned route
    ok = ("import jax\n"
          "def op(key):\n"
          "    def build():\n"
          "        return jax.jit(lambda x: x * 2)\n"
          "    return _cached_jit(key, build)\n")
    assert astlint.lint_source(ok, "bolt_tpu/somewhere.py") == []
    # inline lambda builders too
    ok2 = ("import jax\n"
           "fn = _cached_jit(('k',), lambda: jax.jit(lambda x: x))\n")
    assert astlint.lint_source(ok2, "bolt_tpu/somewhere.py") == []
    # engine.py itself is exempt; pragmas document exceptions
    assert astlint.lint_source(src, "bolt_tpu/engine.py") == []
    pragma = ("import jax\n"
              "@jax.jit  # lint: allow(BLT101 documented exception)\n"
              "def f(x):\n    return x\n")
    assert astlint.lint_source(pragma, "bolt_tpu/somewhere.py") == []
    # a bare decorator without the pragma is a finding
    dec = "import jax\n@jax.jit\ndef f(x):\n    return x\n"
    assert [x.code for x in astlint.lint_source(
        dec, "bolt_tpu/somewhere.py")] == ["BLT101"]
    # builder names resolve within the sink's ENCLOSING scope only: a
    # same-named local builder elsewhere must not whitelist a
    # direct-called jit
    cross = ("import jax\n"
             "def a(key):\n"
             "    def build():\n"
             "        return jax.jit(lambda x: x)\n"
             "    return _cached_jit(key, build)\n"
             "def b():\n"
             "    def build():\n"
             "        return jax.jit(lambda x: x)\n"
             "    return build()\n")
    found = astlint.lint_source(cross, "bolt_tpu/somewhere.py")
    assert [x.code for x in found] == ["BLT101"] and found[0].line == 8


@pytest.mark.lint
def test_lint_blt102_version_sensitive_jax():
    src = "from jax.experimental.shard_map import shard_map\n"
    assert [x.code for x in astlint.lint_source(
        src, "bolt_tpu/ops/foo.py")] == ["BLT102"]
    src2 = "import jax\nn = jax.lax.axis_size('k')\n"
    assert [x.code for x in astlint.lint_source(
        src2, "bolt_tpu/ops/foo.py")] == ["BLT102"]
    src3 = "import jax\nt = jax.sharding.AxisType.Auto\n"
    assert [x.code for x in astlint.lint_source(
        src3, "bolt_tpu/ops/foo.py")] == ["BLT102"]
    # _compat.py IS the shim: exempt
    assert astlint.lint_source(src, "bolt_tpu/_compat.py") == []
    # the blessed route is clean
    ok = "from bolt_tpu._compat import shard_map, axis_size\n"
    assert astlint.lint_source(ok, "bolt_tpu/ops/foo.py") == []


@pytest.mark.lint
def test_lint_blt103_precision_literals():
    src = ("import jax.numpy as jnp\n"
           "y = jnp.matmul(a, b, precision='highest')\n")
    assert [x.code for x in astlint.lint_source(
        src, "bolt_tpu/ops/foo.py")] == ["BLT103"]
    enum = ("from jax import lax\n"
            "y = lax.dot(a, b, precision=lax.Precision.HIGHEST)\n")
    assert [x.code for x in astlint.lint_source(
        enum, "bolt_tpu/ops/foo.py")] == ["BLT103"]
    # alias-aware: a renamed Precision import must not slip through
    aliased = ("from jax.lax import Precision as P\n"
               "y = jnp.matmul(a, b, precision=P.HIGHEST)\n")
    assert [x.code for x in astlint.lint_source(
        aliased, "bolt_tpu/ops/foo.py")] == ["BLT103"]
    # resolver-routed calls and pinned DEFAULTS are the sanctioned forms
    ok = ("import jax.numpy as jnp\n"
          "from bolt_tpu._precision import resolve\n"
          "def f(a, b, precision='highest'):\n"
          "    return jnp.matmul(a, b, precision=resolve(precision))\n")
    assert astlint.lint_source(ok, "bolt_tpu/ops/foo.py") == []


@pytest.mark.lint
def test_lint_blt104_concrete_bypass():
    src = "def f(b):\n    return b._concrete.shape\n"
    assert [x.code for x in astlint.lint_source(
        src, "bolt_tpu/ops/foo.py")] == ["BLT104"]
    # the gate's own module is exempt
    assert astlint.lint_source(src, "bolt_tpu/tpu/array.py") == []
    ok = "def f(b):\n    return b._data.shape\n"
    assert astlint.lint_source(ok, "bolt_tpu/ops/foo.py") == []


@pytest.mark.lint
def test_lint_blt107_stray_sync_points():
    # method form: x.block_until_ready()
    src = "def f(x):\n    return x.block_until_ready()\n"
    assert [x.code for x in astlint.lint_source(
        src, "bolt_tpu/ops/foo.py")] == ["BLT107"]
    # module-function form: jax.block_until_ready(tree)
    src2 = "import jax\n\ndef f(t):\n    return jax.block_until_ready(t)\n"
    assert [x.code for x in astlint.lint_source(
        src2, "bolt_tpu/tpu/chunk.py")] == ["BLT107"]
    # from-import form
    src3 = ("from jax import block_until_ready\n\n"
            "def f(t):\n    return block_until_ready(t)\n")
    assert any(x.code == "BLT107" for x in astlint.lint_source(
        src3, "bolt_tpu/tpu/stack.py"))
    # the sanctioned sync owners are exempt
    for home in ("bolt_tpu/stream.py", "bolt_tpu/engine.py",
                 "bolt_tpu/profile.py"):
        assert astlint.lint_source(src, home) == []
        assert astlint.lint_source(src2, home) == []
    # path anchoring: upstream.py does not inherit stream.py's pass
    assert any(x.code == "BLT107" for x in astlint.lint_source(
        src, "bolt_tpu/upstream.py"))
    # and the whole package lints clean with the rule armed
    assert astlint.lint_package() == []


@pytest.mark.lint
def test_lint_blt108_thread_construction_outside_blessed_homes():
    # dotted form
    src = ("import threading\n\n"
           "def f():\n    return threading.Thread(target=print)\n")
    assert [x.code for x in astlint.lint_source(
        src, "bolt_tpu/ops/foo.py")] == ["BLT108"]
    # from-import alias form
    src2 = ("from threading import Thread\n\n"
            "def f():\n    return Thread(target=print)\n")
    assert [x.code for x in astlint.lint_source(
        src2, "bolt_tpu/tpu/chunk.py")] == ["BLT108"]
    # pool executors count as thread construction too
    src3 = ("from concurrent.futures import ThreadPoolExecutor\n\n"
            "def f():\n    return ThreadPoolExecutor(4)\n")
    assert [x.code for x in astlint.lint_source(
        src3, "bolt_tpu/checkpoint.py")] == ["BLT108"]
    # renamed plain import must not dodge the rule
    src4 = ("import threading as t\n\n"
            "def f():\n    return t.Thread(target=print)\n")
    assert [x.code for x in astlint.lint_source(
        src4, "bolt_tpu/obs/trace.py")] == ["BLT108"]
    # the two blessed concurrency homes pass
    for home in ("bolt_tpu/stream.py", "bolt_tpu/serve.py"):
        for s in (src, src2, src3):
            assert astlint.lint_source(s, home) == []
    # path anchoring: preserve.py does not inherit serve.py's pass
    assert any(x.code == "BLT108" for x in astlint.lint_source(
        src, "bolt_tpu/preserve.py"))
    # locks/events/conditions are NOT construction — no finding
    ok = ("import threading\n\n"
          "L = threading.Lock()\nE = threading.Event()\n"
          "C = threading.Condition()\nT = threading.local()\n")
    assert astlint.lint_source(ok, "bolt_tpu/ops/foo.py") == []
    # the repo itself holds at zero findings with the rule armed
    assert astlint.lint_package() == []


@pytest.mark.lint
def test_lint_cli_check_mode_passes_on_repo():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint_bolt.py"),
         "--check"], capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "0 finding(s)" in out.stdout
    # seeded violation through the CLI: nonzero exit
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        bad = os.path.join(td, "bad.py")
        with open(bad, "w") as fh:
            fh.write("import jax\nf = jax.jit(lambda x: x)\n")
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "lint_bolt.py"),
             "--check", bad], capture_output=True, text=True, timeout=120)
        assert out.returncode == 1
        assert "BLT101" in out.stdout


@pytest.mark.lint
def test_lint_blt110_process_topology_calls():
    """BLT110: jax.distributed / jax.process_index / jax.process_count
    are confined to parallel/multihost.py (+ _compat.py) — the one
    process-topology home."""
    from bolt_tpu.analysis import astlint
    src = ("import jax\n\n"
           "def f():\n    return jax.process_index()\n")
    assert [x.code for x in astlint.lint_source(
        src, "bolt_tpu/ops/foo.py")] == ["BLT110"]
    src2 = ("import jax\n\n"
            "def f():\n    return jax.process_count() > 1\n")
    assert [x.code for x in astlint.lint_source(
        src2, "bolt_tpu/tpu/construct.py")] == ["BLT110"]
    # the bootstrap chain itself (attribute + call forms)
    src3 = ("import jax\n\n"
            "def up():\n    jax.distributed.initialize()\n")
    assert [x.code for x in astlint.lint_source(
        src3, "bolt_tpu/checkpoint.py")] == ["BLT110"]
    # import forms
    src4 = "import jax.distributed\n"
    assert [x.code for x in astlint.lint_source(
        src4, "bolt_tpu/ops/foo.py")] == ["BLT110"]
    src5 = "from jax import distributed\n"
    assert [x.code for x in astlint.lint_source(
        src5, "bolt_tpu/ops/foo.py")] == ["BLT110"]
    # alias-aware: a renamed jax must not dodge the rule
    src6 = ("import jax as j\n\n"
            "def f():\n    return j.process_index()\n")
    assert [x.code for x in astlint.lint_source(
        src6, "bolt_tpu/serve.py")] == ["BLT110"]
    # DEVICE attributes are data, not topology calls: no finding
    ok = ("def f(mesh):\n"
          "    return {d.process_index for d in mesh.devices.flat}\n")
    assert astlint.lint_source(ok, "bolt_tpu/ops/foo.py") == []
    # the blessed homes pass
    for home in ("bolt_tpu/parallel/multihost.py", "bolt_tpu/_compat.py"):
        for s in (src, src2, src3, src4, src5):
            assert astlint.lint_source(s, home) == []
    # path anchoring: mymultihost.py does not inherit the pass
    assert any(x.code == "BLT110" for x in astlint.lint_source(
        src, "bolt_tpu/parallel/mymultihost.py"))
    # pragma escape hatch
    pragma = ("import jax\n"
              "n = jax.process_count()  "
              "# lint: allow(BLT110 documented exception)\n")
    assert astlint.lint_source(pragma, "bolt_tpu/ops/foo.py") == []
    # the repo itself holds at zero findings with the rule armed
    assert astlint.lint_package() == []


def test_blt012_registered_and_single_process_quiet(mesh):
    """BLT012 is a registered error-severity code, and a single-process
    mesh never emits it (the divisibility rule is multi-process only —
    the 2-process cluster suite proves the firing side)."""
    from bolt_tpu.analysis.diagnostics import CODES
    assert CODES["BLT012"][0] == "error"
    x = np.arange(14 * 3, dtype=np.float32).reshape(14, 3)
    src = bolt.fromcallback(lambda idx: x[idx], (14, 3), mesh,
                            dtype=np.float32, chunks=3)  # uneven tail
    rep = analysis.check(src.map(lambda v: v + 1))
    assert not rep.has("BLT012")
