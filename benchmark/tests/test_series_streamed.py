"""The ``series64-streamed-1chip`` configuration at toy size on the CPU: the
host table against the closed form's two spellings (and against
``series.py``'s own device array), the reference's exact second moments from
the closed form against NumPy over the table, the cell
``series64-streamed-1chip.scan_pca`` run end to end through the streamed
executor in two passes a request, the control one precision lower and the
skipped-slab control reading as wrong, and the new metric files resolved
through the real manifest.  It finds its entries by name and pins no place."""

import json
import os

import numpy as np
import pytest

import manifest
import pipeline
import run

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny")
CONFIG = "series64-streamed-1chip"
CELL = "series64-streamed-1chip.scan_pca"
SEEDS = [3, 2**31 + 17, 4294967291]
PLANES = 6                     # a slab is one plane: six slabs a pass
NEW = {"gram_slabs_per_request", "gram_kernel_slabs_per_request",
       "slab_gram_ms.streamed", "gram_roofline.streamed",
       "pca_gram_pass_ms", "pca_project_pass_ms",
       "plane_reseat_ms.streamed"}


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(manifest.REAL,
                             roots=(TINY, os.path.dirname(HERE)))


@pytest.fixture
def plane_slabs(monkeypatch):
    """The caller sets no ``chunks``; at toy size the default 64 MiB slab
    would hold the whole recording, so the default itself is made one toy
    plane, as the real plane is past the real default."""
    from bolt_tpu import stream
    monkeypatch.setattr(stream, "_SLAB_BYTES", 2048 * 64 * 4)


def built(man, seed):
    cell = run.Cell(man, CELL, seed, 0.0, False, require_tpu=False)
    cell.log = lambda msg: None
    cell.open_device()
    cell.build()
    return cell


def steps_of(cell):
    (_, _, steps), = pipeline.expand(cell.traffic)
    return steps


def test_tiny_keeps_what_the_real_files_say(man):
    real = manifest.Manifest(manifest.REAL)
    tiny, full = man.config(CONFIG), real.config(CONFIG)
    for key in ("series", "dtype", "key_axes", "guarantees", "source",
                "reduced", "assumed", "architecture", "bits", "chips",
                "data"):
        assert tiny[key] == full[key]
    # the real sizes are the ones ISSUE 55 states: 64 planes, 17.18 GB
    shape = [full["planes"]] + full["record_shape"]
    assert shape == [64, 1048576, 64] == full["streamed_source"]["shape"]
    assert int(np.prod(shape, dtype=np.int64)) * 4 == 17179869184
    assert full["reduced"] == [] and full["architecture"] is None
    # series64-1chip's record, dtype, spectrum: unchanged
    base = real.config("series64-1chip")
    for key in ("record_shape", "dtype", "key_axes", "series", "bits",
                "chips"):
        assert full[key] == base[key]
    entry = [c for c in real.doc["configs"] if c["name"] == CONFIG][0]
    assert entry["source"] == full["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == [] and entry["file"].endswith(CONFIG + ".json")
    cell = real.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "scan_pca", 1)
    # the request is pca.json's pca_k8 but for where the rows are read
    t, q = real.traffic("scan_pca"), real.traffic("pca")
    (mine,), theirs = t["requests"], q["requests"][1]
    assert mine["kind"] == theirs["kind"] == "pca_k8"
    a, b = dict(mine["steps"][0]), dict(theirs["steps"][0])
    assert [p[0] for p in a.pop("patches")] == [0, 31, 63]
    b.pop("patches")
    a.pop("limits"), b.pop("limits")
    assert a == b and (mine["fetch"], mine["limit"]) == ("pca_parts", 1)
    assert (t["driver"], t["operand"], t["warmup_cycles"], t["sample_share"],
            t["trace_seconds"]) == (
        "closed_loop", {"name": "series_streamed", "reads": 2, "k": 8}, 1,
        1.0, 8)
    # the toy request is the real one but for the patches
    toy = man.traffic("scan_pca")["requests"][0]["steps"][0]
    assert {k: v for k, v in toy.items() if k != "patches"} == {
        k: v for k, v in mine["steps"][0].items() if k != "patches"}


@pytest.mark.parametrize("seed", SEEDS)
def test_the_host_table_is_the_closed_form_in_both_spellings(man, seed):
    cell = built(man, seed)
    op = man.module("operands", "series_streamed")
    shape = cell.operand.shape
    table = cell.operand.table
    assert shape == (PLANES, 2048, 64) == table.shape
    assert table.dtype == np.float32
    assert not table.flags.writeable and table.flags.c_contiguous
    spec = cell.config["series"]
    s = np.arange(shape[0] * shape[1])
    assert np.array_equal(table.reshape(-1, 64),
                          op.series.host_rows(s, 64, spec, seed))
    # series.py's own device array of the same shape holds the same values
    import jax.numpy as jnp
    import lattice
    a, b = lattice.constants(seed)
    assert np.array_equal(table, np.asarray(op.series.device_values(
        shape, spec, jnp.uint32(a), jnp.uint32(b))))
    assert np.array_equal(table, np.round(table))
    assert np.abs(table).max() < 1 << 11           # exact in float32
    assert cell.reference.data_mismatches(np.random.default_rng(seed)) == 0
    # what a request counts: two reads of the table and the scores
    assert cell.operand.nbytes == 2 * table.nbytes + s.size * 8 * 4
    # the loader hands out views of it, and tallies what it handed out
    block = cell.operand.load((slice(2, 3), slice(0, 2048), slice(0, 64)))
    assert block.base is not None and np.shares_memory(block, table)
    assert cell.operand.loader_bytes == [2048 * 64 * 4]


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_the_reference_takes_exact_moments_from_the_closed_form(man, seed):
    cell = built(man, seed)
    ref, table = cell.reference, cell.operand.table.astype(np.int64)
    assert ref.table is cell.operand.table and ref.KIND == "resident"
    # a reference that was never handed the table reads the same
    op = man.module("operands", "series_streamed")
    alone = op.StreamedSeriesReference(
        man, op.ClosedForm(ref.shape, ref.spec, seed), ref.shape, ref.bits,
        seed, ref.spec, None)
    gram, total = alone.moments(1024)
    blocks = table.reshape(PLANES, 2, 1024, 64)
    assert np.array_equal(gram, np.einsum("pgni,pgnj->pgij", blocks, blocks))
    assert np.array_equal(total, blocks.sum(axis=2))
    rows = alone.rows([(1, 5), (5, 1900)], 128)
    assert np.array_equal(rows, np.concatenate(
        [table[1, 5:133], table[5, 1900:2028]]).astype(np.float64))
    want = ref.expected(steps_of(cell))
    x = table.reshape(-1, 64).astype(np.float64)
    assert np.allclose(want["mean"], x.mean(axis=0), rtol=0, atol=1e-9)
    sv = np.linalg.svd(x - x.mean(axis=0), compute_uv=False)[:8]
    assert np.allclose(want["singular_values"], sv, rtol=1e-9)


@pytest.mark.parametrize("seed", SEEDS)
def test_one_precision_lower_reads_over_a_limit(man, seed):
    cell = built(man, seed)
    ref, steps = cell.reference, steps_of(cell)
    want = ref.expected(steps)
    p = ref.plan(steps)
    low = p.terminal.parts(ref.lowp(steps), want)
    assert low["scores"] > 3 * steps[0]["limits"]["scores"]
    assert ref.number(steps, ref.lowp(steps), want) > 1


@pytest.mark.parametrize("seed", SEEDS)
def test_the_cell_runs_in_two_passes_and_is_correct(man, seed, tmp_path,
                                                    plane_slabs):
    from bolt_tpu import engine, obs
    obs.enable()
    obs.clear()
    try:
        c0 = engine.counters()
        out = run.run_cell(man, CELL, seed, 0.3, False, require_tpu=False,
                           out_root=str(tmp_path))
        c1 = engine.counters()
        spans = obs.totals()
    finally:
        obs.disable()
        obs.clear()
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert set(out["metrics"]) == {"streamed_scan_GBps", "setup_s"}
    json.dumps(out)
    # every request streamed twice: nothing was uploaded whole
    assert "stream.materialize" not in spans
    calls = spans["linalg.pca"]["count"]
    for name in ("linalg.pca.gram_pass", "linalg.pca.decompose",
                 "linalg.pca.project_pass", "stream.run", "stream.collect"):
        assert spans[name]["count"] == calls, name
    assert "linalg.pca.launch" not in spans
    delta = {k: c1[k] - c0[k] for k in (
        "stream_gram_slabs", "stream_project_slabs", "stream_collect_slabs",
        "stream_gram_kernel_slabs", "stream_chunks")}
    assert delta["stream_gram_slabs"] == PLANES * calls
    assert delta["stream_project_slabs"] == PLANES * calls
    assert delta["stream_collect_slabs"] == PLANES * calls
    assert delta["stream_chunks"] == 2 * PLANES * calls
    assert delta["stream_gram_kernel_slabs"] == 0       # the CPU's lowering


def test_the_timed_path_broken_underneath_is_not_correct(man, tmp_path,
                                                         monkeypatch,
                                                         plane_slabs):
    step = man.module("steps", "pca")
    sound = step.bind

    def broken(s, m):                     # not centred
        return sound(dict(s, center=False), m)
    monkeypatch.setattr(step, "bind", broken)
    out = run.run_cell(man, CELL, 5, 0.3, False, require_tpu=False,
                       out_root=str(tmp_path))
    assert out["correct"] is False and out["failed"] > 0


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_a_skipped_slab_reads_as_wrong(man, seed, plane_slabs):
    """The loader serves plane 3 in plane 2's place, in both passes: every
    row is no longer in the Gram matrix exactly once, and the scores of the
    patch in that plane are another plane's."""
    cell = built(man, seed)
    ref, steps = cell.reference, steps_of(cell)
    fetch = man.module("fetches", "pca_parts")
    call = pipeline.compile_call(man, steps)
    want = ref.expected(steps)
    sound = ref.number(steps, fetch.take(call(cell.operand.operand())), want)
    assert sound < 1
    cell.operand.serve_instead = {2: 3}
    got = fetch.take(call(cell.operand.operand()))
    parts = ref.plan(steps).terminal.parts(got, want)
    assert parts["mean"] > steps[0]["limits"]["mean"]
    assert parts["scores"] > 100 * steps[0]["limits"]["scores"]
    # the tool that reads the same at the cell's own size
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "skipped_slab", os.path.join(os.path.dirname(HERE), "tools",
                                     "skipped_slab.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    cell.operand.serve_instead = {}
    table = tool.readings(cell)
    assert table["pca_k8"][0] < 1 < table["pca_k8"][1]
    assert cell.operand.serve_instead == {}


FAKE_TRACE = {"busy_s": 0.02, "window_s": 0.1,
              "ops_s": {"packed_gram_sums.1": 0.012, "fusion.7": 0.004,
                        "copy.1": 0.002, "copy_bitcast_fusion": 0.002},
              "idle_gaps_s": {"bench.call": 0.07}}


def test_a_traced_run_reads_the_per_layer_metrics(man, tmp_path,
                                                  monkeypatch, plane_slabs):
    import tracered
    from bolt_tpu import obs
    obs.disable()
    obs.clear()
    monkeypatch.setattr(tracered, "reduce_trace",
                        lambda raw, chips: FAKE_TRACE)
    out = run.run_cell(man, CELL, 5, 0.3, True, require_tpu=False,
                       out_root=str(tmp_path))
    assert out["correct"] is True
    got = out["metrics"]
    for name in ("upload_GBps", "loader_GBps", "stream_overlap_share",
                 "stream_wall_over_link", "program_ms.streamed",
                 "peak_hbm_GB.streamed", "upload_workers_busy",
                 "consumer_starved_share", "feeder_ring_wait_share",
                 "consumer_dispatch_share", "consumer_sync_share",
                 "slab_dispatch_us", "slab_sync_us", "setup_programs",
                 "setup_stream_warmup_s", "collect_place_us"):
        assert got[name]["value"] >= 0, name
    n = out["attempted"]
    assert got["gram_slabs_per_request"]["value"] == PLANES
    assert got["gram_kernel_slabs_per_request"]["value"] == 0
    assert got["compiles_in_window.streamed"]["value"] == 0
    assert got["pca_gram_pass_ms"]["value"] > 0
    assert got["pca_project_pass_ms"]["value"] > 0
    assert got["slab_gram_ms.streamed"]["value"] == pytest.approx(
        0.012 / n * 1e3)
    # the re-seat: both copies and the fusion behind them
    assert got["plane_reseat_ms.streamed"]["value"] == pytest.approx(
        0.004 / n * 1e3)
    # a slab is one plane, for one device: both passes go up dense
    assert got["thin_slabs_per_request"]["value"] == 2 * PLANES
    assert got["device_idle_share.streamed"]["value"] == pytest.approx(80.0)
    # a share of a published peak: nothing on a device without one
    assert "gram_roofline.streamed" not in got
    reader = man.module("readers", "slab_gram_roofline")
    cell = type("C", (), {"chips": 1,
                          "peaks": {"hbm_GBps": 819.0, "bf16_TFLOPs": 197.0},
                          "operand": type("O", (), {"shape": (64, 1048576,
                                                              64)})})
    ctx = {"cell": cell, "trace": {"ops_s": {"packed_gram_sums.1": 0.069,
                                             "fusion.2": 1.0}},
           "result": {"walls_s": [2.9, 2.9, 2.9]}}
    args = man.metric_spec("gram_roofline.streamed")["args"]
    # one read of 17.18 GB at 819 GB/s is 20.98 ms a request: HBM's bound,
    # not the matrix unit's 2.79 ms
    assert reader.read(ctx, **args) == pytest.approx(
        100 * 3 * (17179869184 / 819e9) / 0.069)
    assert 2 * 67108864 * 64 * 64 / 197e12 < 17179869184 / 819e9
    # a slab program that kept dot_general, and the parent: nothing
    ctx["trace"] = {"ops_s": {"fusion.2": 1.0}}
    assert reader.read(ctx, **args) is None
    ctx["trace"] = None
    assert reader.read(ctx, **args) is None


def test_the_new_metrics_resolve_through_the_real_manifest():
    real = manifest.Manifest(manifest.REAL)
    names = {m["name"] for m in real.cell_metrics(CELL, "per_layer")}
    assert NEW <= names and {"collect_place_us",
                             "thin_slabs_per_request"} <= names
    # what stack4d-1chip.stream reports, this cell reports
    stream = {m["name"] for m in real.cell_metrics("stack4d-1chip.stream",
                                                   "per_layer")}
    assert stream <= names
    for name in names:
        real.module("readers", real.metric_spec(name)["reader"])
    for name in NEW:
        entry = real.metrics[name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "streamed_scan_GBps"
    assert [m["name"] for m in real.doc["per_layer"][-len(NEW):]] == [
        "gram_slabs_per_request", "gram_kernel_slabs_per_request",
        "slab_gram_ms.streamed", "gram_roofline.streamed",
        "pca_gram_pass_ms", "pca_project_pass_ms", "plane_reseat_ms.streamed"]
    assert {m["name"] for m in real.cell_metrics(CELL, "end_to_end")} == {
        "streamed_scan_GBps", "setup_s"}
    # a program without the counters gives nothing, and does not raise
    reader = real.module("readers", "counter_ratio_known")

    class Old:
        def counter_delta(self, name):
            raise KeyError(name)
    for name in ("gram_slabs_per_request", "gram_kernel_slabs_per_request"):
        assert reader.read({"cell": Old(), "result": {"walls_s": [1.0]}},
                           **real.metric_spec(name)["args"]) is None


def test_a_program_without_the_gram_terminal_is_refused_at_once(
        man, monkeypatch):
    """The parent commit: the operand says so before the table is made."""
    from bolt_tpu import engine
    op = man.module("operands", "series_streamed")
    old = {k: v for k, v in engine.counters().items()
           if k != "stream_gram_slabs"}
    monkeypatch.setattr(engine, "counters", lambda: old)
    monkeypatch.setattr(op, "host_table", lambda *a, **k: pytest.fail(
        "the table was made"))
    with pytest.raises(SystemExit, match="stream_gram_slabs"):
        op.make({"name": "series_streamed", "reads": 2, "k": 8},
                man.config(CONFIG), None, 3)
