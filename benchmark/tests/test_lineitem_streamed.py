"""The ``lineitem-streamed-1chip`` configuration at toy size on the CPU: the
host table against the closed form's two spellings (and against
``lineitem.py``'s own rows inside the first epoch), the epochs past 2**29
rows, the reference's exact Q6 and Q1 from the closed form against a
row-by-row Python loop, the cell ``lineitem-streamed-1chip.scan_q1q6`` run
end to end through the streamed executor slab by slab, the control one
precision lower and the skipped-slab control reading as wrong, and the new
metric files resolved through the real manifest.  It finds its entries by
name and pins no place."""

import json
import os

import numpy as np
import pytest

import manifest
import pipeline
import run

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny")
CONFIG = "lineitem-streamed-1chip"
CELL = "lineitem-streamed-1chip.scan_q1q6"
SEEDS = [3, 2**31 + 17, 4294967291]
SLAB = 1536                    # rows a slab: three slabs and a tail of 392
DATE, QTY, PRICE, DISC, TAX, FLAG, STATUS = range(7)


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(manifest.REAL,
                             roots=(TINY, os.path.dirname(HERE)))


@pytest.fixture
def small_slabs(monkeypatch):
    """The caller sets no ``chunks``; at toy size the default 64 MiB slab
    would hold the whole table, so the default itself is made small."""
    from bolt_tpu import stream
    monkeypatch.setattr(stream, "_SLAB_BYTES", SLAB * 7 * 4)


def built(man, seed):
    cell = run.Cell(man, CELL, seed, 0.0, False, require_tpu=False)
    cell.log = lambda msg: None
    cell.open_device()
    cell.build()
    return cell


def kinds(cell):
    return {cell.traffic["requests"][k]["kind"]: steps
            for k, _, steps in pipeline.expand(cell.traffic)}


def test_tiny_keeps_what_the_real_files_say(man):
    real = manifest.Manifest(manifest.REAL)
    tiny, full = man.config(CONFIG), real.config(CONFIG)
    for key in ("lineitem", "columns", "record_shape", "dtype", "key_axes",
                "guarantees", "source", "reduced", "sf", "published_rows",
                "chips_sharing", "assumed", "architecture"):
        assert tiny[key] == full[key]
    # the real sizes are the ones ISSUE 51 states: SF 100 whole
    assert full["rows"] == 600037902 == full["published_rows"]
    assert full["streamed_source"]["shape"] == [600037902, 7]
    assert full["rows"] * 7 * 4 == 16801061256          # 16.80 GB
    assert full["reduced"] == [] and full["architecture"] is None
    assert full["chips"] == full["chips_sharing"] == 1 and full["sf"] == 100
    base = real.config("lineitem-1chip")
    for key in ("lineitem", "columns", "record_shape", "dtype", "key_axes"):
        assert full[key] == base[key]
    entry = [c for c in real.doc["configs"] if c["name"] == CONFIG][0]
    assert entry["source"] == full["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == [] and entry["file"].endswith(CONFIG + ".json")
    cell = real.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "scan_q1q6", 1)
    # the two request kinds are q1q6's letter for letter
    t, q = real.traffic("scan_q1q6"), real.traffic("q1q6")
    assert t["requests"] == q["requests"]
    assert (t["driver"], t["operand"], t["warmup_cycles"], t["sample_share"],
            t["trace_seconds"]) == ("closed_loop",
                                    {"name": "lineitem_streamed"}, 1, 1.0, 8)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_host_table_is_the_closed_form_in_both_spellings(man, seed):
    cell = built(man, seed)
    op = man.module("operands", "lineitem_streamed")
    rows = cell.operand.shape[0]
    table = cell.operand.table
    assert table.shape == (rows, 7) and table.dtype == np.float32
    assert not table.flags.writeable and table.flags.c_contiguous
    spec = cell.config["lineitem"]
    assert np.array_equal(table, op.host_rows(np.arange(rows), spec, seed))
    # inside the first epoch the rows are lineitem-1chip's own
    assert np.array_equal(table, op.lineitem.host_rows(np.arange(rows),
                                                       spec, seed))
    assert np.array_equal(table, np.round(table)) and table.min() >= 0
    assert table.max() < 1 << 24                  # exact in float32
    assert cell.reference.data_mismatches(np.random.default_rng(seed)) == 0
    # the loader hands out views of it, and tallies what it handed out
    block = cell.operand.load((slice(10, 50), slice(0, 7)))
    assert block.base is not None and np.shares_memory(block, table)
    assert cell.operand.loader_bytes == [40 * 7 * 4]


def test_the_table_is_made_in_blocks_that_need_not_tile_it(man, monkeypatch):
    op = man.module("operands", "lineitem_streamed")
    spec = man.config(CONFIG)["lineitem"]
    monkeypatch.setattr(op, "GENERATE", 1000)     # 7,000 values: not lanes
    rows = 3 * 1000 + 234
    got = op.host_table(rows, spec, 9, threads=3)
    assert np.array_equal(got, op.host_rows(np.arange(rows), spec, 9))


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_rows_past_an_epoch_are_new_draws_in_both_spellings(man, seed,
                                                            monkeypatch):
    """``lineitem.py`` mixes ``row * 8 + draw`` in 32 bits; past 2**29
    rows the epoch's salt changes, on the host and on the device alike."""
    import jax.numpy as jnp
    import lattice
    op = man.module("operands", "lineitem_streamed")
    spec = man.config(CONFIG)["lineitem"]
    epoch = 1 << op.EPOCH_BITS
    at = np.arange(epoch - 300, epoch + 300, dtype=np.int64)
    host = op.host_rows(at, spec, seed)
    # the device spelling over the same rows
    make = op._generator(600, op._frozen(spec))
    _, b = lattice.constants(seed)
    dev = np.asarray(make(jnp.uint32(b), jnp.uint32(epoch - 300)))
    assert np.array_equal(dev.reshape(-1)[:600 * 7].reshape(600, 7), host)
    # below the boundary: lineitem.py's rows; past it: not row 0.. again
    assert np.array_equal(host[:300], op.lineitem.host_rows(at[:300], spec,
                                                            seed))
    again = op.lineitem.host_rows(np.arange(300), spec, seed)
    assert not np.array_equal(host[300:], again)
    assert (host[300:] != again).any(axis=1).mean() > 0.9
    # and the reference's block program makes the same rows
    monkeypatch.setattr(op, "BLOCK", 256)
    ref = op.StreamedLineitemReference(man, None, (600, 7), seed, spec)

    def every(cols):
        return [cols[0] >= 0], [cols[k] for k in range(7)]
    prog = op._block_program(every, 600, False, op._frozen(spec))
    part = np.asarray(prog(jnp.uint32(b), jnp.uint32(epoch - 300),
                           jnp.int32(0)))
    sums = [sum(int(v) << (11 * k) for k, v in enumerate(val))
            for val in part[0]]
    assert sums == [int(v) for v in host.astype(np.int64).sum(axis=0)]
    assert ref.shape == (600, 7)


def loop_answers(rows):
    """Q6 and Q1 row by row in Python integers."""
    q6, sums, counts = 0, [[0] * 6 for _ in range(6)], [0] * 6
    for r in rows:
        d, q, p, disc, tax, flag, status = (int(v) for v in r)
        if 731 <= d < 1096 and 5 <= disc <= 7 and q < 24:
            q6 += p * disc
        if d <= 2436:
            g = 3 * status + flag
            dp = p * (100 - disc)
            for t, v in enumerate((q, p, dp, dp * (100 + tax), disc, 1)):
                sums[g][t] += v
            counts[g] += 1
    return q6, sums, counts


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_is_exact_against_a_python_loop(man, seed,
                                                      monkeypatch):
    cell = built(man, seed)
    op = man.module("operands", "lineitem_streamed")
    # in blocks that do not tile the table, from the closed form alone
    monkeypatch.setattr(op, "BLOCK", 1024)
    rows = cell.operand.shape[0]
    ref = op.StreamedLineitemReference(man, None, (rows, 7), seed,
                                       cell.config["lineitem"])
    steps = kinds(cell)
    q6, sums, counts = loop_answers(cell.operand.table)
    assert ref.expected(steps["q6"]) == float(q6)
    got = ref.expected(steps["q1"])
    assert got["counts"].tolist() == counts
    assert got["sums"].tolist() == [[float(v) for v in g] for g in sums]
    assert sum(counts) > 0.9 * rows and q6 > 0
    assert (np.asarray(counts) > 0).sum() == 4   # four occupied groups


@pytest.mark.parametrize("seed", SEEDS)
def test_one_precision_lower_reads_over_every_limit(man, seed):
    cell = built(man, seed)
    ref, steps = cell.reference, kinds(cell)
    for kind in ("q6", "q1"):
        p = ref.plan(steps[kind])
        want = ref.expected(steps[kind])
        low = p.terminal.parts(ref.lowp(steps[kind]), want)
        for name, limit in steps[kind][0]["limits"].items():
            assert low[name] > 10 * limit, (kind, name, low[name])
        assert ref.number(steps[kind], ref.lowp(steps[kind]), want) > 1


@pytest.mark.parametrize("seed", SEEDS)
def test_the_cell_runs_slab_by_slab_and_is_correct(man, seed, tmp_path,
                                                   small_slabs):
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
    assert out["attempted"] >= 2
    assert set(out["metrics"]) == {"streamed_scan_GBps", "setup_s"}
    json.dumps(out)
    # every request streamed: nothing was uploaded whole, no survivors
    # were built, and half the requests (Q1's) folded four slabs each
    assert "stream.materialize" not in spans
    passes = spans["stream.run"]["count"]
    assert c1["stream_chunks"] - c0["stream_chunks"] == 4 * passes
    assert c1["filters_fused"] - c0["filters_fused"] == passes
    assert c1["filter_compactions"] == c0["filter_compactions"]
    groups = spans["group.segment_reduce"]["count"]
    assert c1["stream_group_slabs"] - c0["stream_group_slabs"] == 4 * groups
    assert abs(2 * groups - passes) <= 1


def test_the_timed_path_broken_underneath_is_not_correct(man, tmp_path,
                                                         monkeypatch,
                                                         small_slabs):
    step = man.module("steps", "tpch_q1")
    sound = step.bind

    def broken(s, m):                     # one day too few
        return sound(dict(s, shipdate_to=2435), m)
    monkeypatch.setattr(step, "bind", broken)
    out = run.run_cell(man, CELL, 5, 0.3, False, require_tpu=False,
                       out_root=str(tmp_path))
    assert out["correct"] is False and out["failed"] > 0


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_a_skipped_slab_reads_as_wrong(man, seed, small_slabs, monkeypatch):
    """The loader serves slab 2 in slab 1's place: every row is no longer
    read exactly once, and the exact counts say so."""
    cell = built(man, seed)
    ref, steps = cell.reference, kinds(cell)["q1"]
    fetch = man.module("fetches", "fold_parts")
    call = pipeline.compile_call(man, steps)
    want = ref.expected(steps)
    sound = ref.number(steps, fetch.take(call(cell.operand.operand())), want)
    assert sound < 1
    cell.operand.serve_instead = {SLAB: 2 * SLAB}
    got = fetch.take(call(cell.operand.operand()))
    assert ref.number(steps, got, want) == float("inf")
    assert (np.asarray(got["counts"]) != want["counts"]).any()
    # the tool that reads the same at the cell's own size
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "skipped_slab", os.path.join(os.path.dirname(HERE), "tools",
                                     "skipped_slab.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    cell.operand.serve_instead = {}
    table = tool.readings(cell)
    assert table["q1"][0] < 1 and table["q1"][1] == float("inf")
    assert table["q6"][0] < 1
    assert cell.operand.serve_instead == {}


FAKE_TRACE = {"busy_s": 0.02, "window_s": 0.1,
              "ops_s": {"thin_fold.1": 0.012, "fusion.7": 0.006,
                        "copy.1": 0.002},
              "idle_gaps_s": {"bench.fetch": 0.07}}


def test_a_traced_run_reads_the_per_layer_metrics(man, tmp_path,
                                                  monkeypatch, small_slabs):
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
                 "setup_stream_warmup_s"):
        assert got[name]["value"] >= 0, name
    n = out["attempted"]
    # four slabs a Q1 request, none a Q6: half the window's requests
    assert 4 * ((n - 1) // 2) / n <= got["group_slabs_per_request"][
        "value"] <= 4 * ((n + 1) // 2) / n
    assert got["filters_fused_per_request.streamed"]["value"] == 1
    assert got["filter_compactions_per_request.streamed"]["value"] == 0
    assert got["compiles_in_window.streamed"]["value"] == 0
    assert got["group_stream_call_ms"]["value"] > 0
    assert got["slab_fold_ms.streamed"]["value"] == pytest.approx(
        0.012 / n * 1e3)
    assert got["device_idle_share.streamed"]["value"] == pytest.approx(80.0)
    # a share of a published peak: nothing on a device without one
    assert "fold_roofline.streamed" not in got
    reader = man.module("readers", "fold_roofline")
    cell = type("C", (), {"manifest": man, "chips": 1,
                          "peaks": {"hbm_GBps": 819.0},
                          "operand": type("O", (), {"shape": (600037902,
                                                              7)})})
    requests = pipeline.expand(man.traffic("scan_q1q6"))
    ctx = {"cell": cell, "trace": {"busy_s": 0.2},
           "result": {"requests": requests, "slots": [0, 1]}}
    assert reader.read(ctx) == pytest.approx(
        100 * (11 * 4 * 600037902 / 819e9) / 0.2)


def test_the_new_metrics_resolve_through_the_real_manifest():
    real = manifest.Manifest(manifest.REAL)
    names = {m["name"] for m in real.cell_metrics(CELL, "per_layer")}
    new = {"group_slabs_per_request", "slab_fold_ms.streamed",
           "group_stream_call_ms", "fold_roofline.streamed",
           "filters_fused_per_request.streamed",
           "filter_compactions_per_request.streamed"}
    assert new <= names
    # what stack4d-1chip.stream reports, this cell reports
    stream = {m["name"] for m in real.cell_metrics("stack4d-1chip.stream",
                                                   "per_layer")}
    assert stream <= names
    for name in names:
        real.module("readers", real.metric_spec(name)["reader"])
    for name in new:
        entry = real.metrics[name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "streamed_scan_GBps"
    assert {m["name"] for m in real.cell_metrics(CELL, "end_to_end")} == {
        "streamed_scan_GBps", "setup_s"}
    # a program without the counter gives nothing, and does not raise
    reader = real.module("readers", "counter_ratio_known")

    class Old:
        def counter_delta(self, name):
            raise KeyError(name)
    assert reader.read({"cell": Old(), "result": {"walls_s": [1.0]}},
                       **real.metric_spec("group_slabs_per_request")[
                           "args"]) is None


def test_a_program_without_the_grouped_terminal_is_refused_at_once(
        man, monkeypatch):
    """The parent commit: the operand says so before the table is made."""
    from bolt_tpu import engine
    op = man.module("operands", "lineitem_streamed")
    old = {k: v for k, v in engine.counters().items()
           if k != "stream_group_slabs"}
    monkeypatch.setattr(engine, "counters", lambda: old)
    monkeypatch.setattr(op, "host_table", lambda *a, **k: pytest.fail(
        "the table was made"))
    with pytest.raises(SystemExit, match="stream_group_slabs"):
        op.make({"name": "lineitem_streamed"}, man.config(CONFIG), None, 3)
