"""The ``lineitem-served-1chip`` configuration at toy size on the CPU: its
cell ``lineitem-served-1chip.streams5`` run end to end by the driver
``stream_loops`` with two streams through one ``serve.Server``, the
guarantees the driver holds a run to, the seven metrics of the layer
``serve queue`` through the real manifest, and the two new readers' sums
from a recorded result by hand.  Finds its entries by name and pins no
place in the manifest's lists."""

import json
import os

import pytest

import manifest
import pipeline
import run
import tracered

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny")
CONFIG = "lineitem-served-1chip"
CELL = "lineitem-served-1chip.streams5"
SEEDS = [3, 2**31 + 17, 4294967291]
QUEUE = {"serve_submit_us": ("us", "program_span"),
         "serve_queue_wait_ms": ("ms", "program_span"),
         "serve_lease_wait_ms": ("ms", "program_span"),
         "serve_run_ms": ("ms", "program_span"),
         "serve_workers_busy": ("x", "program_span"),
         "serve_leased_share": ("x", "program_counter"),
         "stream_share_min": ("x", "host_clock")}
FAKE_TRACE = {"window_s": 1.0, "busy_s": 0.5, "busy_s_per_chip": [0.5],
              "ops_s": {"thin_fold.1": 0.5},
              "idle_gaps_s": {"bench.fetch": 0.5}}


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(manifest.REAL,
                             roots=(TINY, os.path.dirname(HERE)))


@pytest.fixture(scope="module")
def real():
    return manifest.Manifest(manifest.REAL)


def test_tiny_keeps_what_the_real_files_say(man, real):
    tiny, full = man.config(CONFIG), real.config(CONFIG)
    table = real.config("lineitem-1chip")
    for key in ("lineitem", "columns", "record_shape", "dtype", "key_axes",
                "guarantees", "source", "reduced", "sf", "published_rows",
                "chips_sharing", "published_streams", "queries",
                "stream_share_floor"):
        assert tiny[key] == full[key], key
    # lineitem-1chip's table as it stands
    for key in ("rows", "lineitem", "columns", "record_shape", "dtype",
                "key_axes", "data", "sf", "published_rows", "chips_sharing"):
        assert full[key] == table[key], key
    assert set(table["guarantees"]) < set(full["guarantees"])
    assert {"completeness", "order", "no_starvation", "isolation"} \
        <= set(full["guarantees"])
    assert full["streams"] == 5 and tiny["streams"] == 2
    assert full["serve"]["budget_bytes"] == "bytes_limit"
    assert full["reduced"] == ["rows", "queries", "refresh_stream"]
    assert all(key in full for key in full["reduced"])
    assert full["architecture"] is None
    entry = [c for c in real.doc["configs"] if c["name"] == CONFIG][0]
    assert entry["source"] == full["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == full["reduced"]
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    cell = real.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "streams5", 1)
    assert len(cell["why"]) <= 200


def test_the_traffic_is_the_issue_s_five_parameter_sets(man, real):
    full, tiny = real.traffic("streams5"), man.traffic("streams5")
    q1q6 = {k["kind"]: k for k in real.traffic("q1q6")["requests"]}
    assert full["driver"] == "stream_loops"
    assert (full["warmup_cycles"], full["sample_share"],
            full["trace_seconds"]) == (4, 0.05, 3)
    kinds = {k["kind"]: k for k in full["requests"]}
    assert kinds["q6"]["submit"] == "pipeline"
    assert kinds["q1"]["submit"] == "callable"
    for name, kind in kinds.items():
        # q1q6's limits and fetches, unchanged
        assert kind["fetch"] == q1q6[name]["fetch"]
        assert kind["steps"][0]["limits"] == q1q6[name]["steps"][0]["limits"]
        assert kind["count"] == len(kind["positions"]) == 5
    requests = pipeline.expand(full)
    q6 = [steps[0] for k, _, steps in requests if k == 0]
    q1 = [steps[0] for k, _, steps in requests if k == 1]
    assert [(s["shipdate"], s["discount"], s["quantity_below"])
            for s in q6] == [([731, 1096], [5, 7], 24),
                             ([366, 731], [2, 4], 25),
                             ([1096, 1461], [8, 10], 24),
                             ([1461, 1827], [3, 5], 25),
                             ([1827, 2192], [6, 8], 24)]
    assert [s["shipdate_to"] for s in q1] == [2436, 2466, 2406, 2451, 2421]
    # stream 0 is q1q6's validation set
    assert {k: v for k, v in q6[0].items()} == q1q6["q6"]["steps"][0]
    assert {k: v for k, v in q1[0].items()} == q1q6["q1"]["steps"][0]
    # the toy copy is the first two streams of it
    for t, f in zip(tiny["requests"], full["requests"]):
        assert t["positions"] == f["positions"][:2] and t["count"] == 2
        assert t["steps"] == f["steps"] and t["submit"] == f["submit"]


@pytest.mark.parametrize("seed", SEEDS)
def test_two_streams_run_end_to_end_and_are_correct(man, seed, tmp_path):
    out = run.run_cell(man, CELL, seed, 0.3, False, require_tpu=False,
                       out_root=str(tmp_path))
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 4
    assert set(out["metrics"]) == {"scan_GBps", "setup_s"}
    json.dumps(out)


def driven(man, seed, tmp_path, seconds=0.3, trace=False):
    """The driver's own result for one toy run, and its cell."""
    cell = run.Cell(man, CELL, seed, seconds, trace, require_tpu=False,
                    out_root=str(tmp_path))
    lines = []
    cell.log = lines.append
    cell.open_device()
    cell.build()
    result = man.module("drivers", "stream_loops").run(cell)
    return cell, result, lines


def test_the_driver_holds_the_run_to_the_guarantees(man, tmp_path):
    cell, result, lines = driven(man, 11, tmp_path)
    n = len(result["walls_s"])
    assert result["raised"] == 0 and n == len(result["slots"]) >= 4
    assert result["serve"] == {"submitted": n, "completed": n, "failed": 0,
                               "rejected": 0, "expired": 0,
                               "leased": result["slots"].count(0)
                               + result["slots"].count(1)}
    assert sum(result["stream_counts"]) == n
    assert len(result["stream_counts"]) == 2
    assert result["bytes_done"] == n * cell.operand.nbytes
    # stream k sent its own two requests, in turn
    requests = result["requests"]
    assert {requests[s][1] for s in result["slots"]} == {0, 1}
    first = result["stream_counts"][0]
    mine = result["slots"][:first]
    assert {requests[s][1] for s in mine} == {0}
    assert all(requests[a][0] != requests[b][0]
               for a, b in zip(mine, mine[1:]))
    # the last answer of every distinct request is among the sampled
    assert {slot for slot, _ in result["sampled"]} == {0, 1, 2, 3}
    assert any("check completeness" in ln for ln in lines)
    assert any("check order: 0 answers" in ln for ln in lines)
    assert any("check no starvation" in ln for ln in lines)


def test_a_starved_stream_is_not_correct(man, tmp_path, monkeypatch):
    config = man.config(CONFIG)
    monkeypatch.setattr(man, "config", lambda name: dict(
        config, stream_share_floor=1.5))
    out = run.run_cell(man, CELL, 5, 0.3, False, require_tpu=False,
                       out_root=str(tmp_path))
    assert out["correct"] is False and out["failed"] == 1


def test_an_answer_to_another_stream_s_parameters_is_not_correct(
        man, tmp_path, monkeypatch):
    step = man.module("steps", "tpch_q1")
    sound = step.bind

    def crossed(s, m):           # stream 1 is handed stream 0's answer
        return sound(dict(s, shipdate_to=2436), m)
    monkeypatch.setattr(step, "bind", crossed)
    out = run.run_cell(man, CELL, 5, 0.3, False, require_tpu=False,
                       out_root=str(tmp_path))
    assert out["correct"] is False and out["failed"] > 0


def test_a_request_the_server_refuses_is_not_correct(man, tmp_path,
                                                     monkeypatch):
    config = man.config(CONFIG)
    # a budget under the table's bytes: every q6 is refused at submit
    # (BLT010), in the warm-up already, and the run says so and ends
    monkeypatch.setattr(man, "config", lambda name: dict(
        config, serve=dict(config["serve"], budget_bytes=1 << 20)))
    with pytest.raises(SystemExit, match="warm-up failed"):
        run.run_cell(man, CELL, 5, 0.3, False, require_tpu=False,
                     out_root=str(tmp_path))
    from bolt_tpu import serve
    assert serve.active() is None        # and leaves no server behind


@pytest.mark.parametrize("name", sorted(QUEUE))
def test_the_new_metrics_resolve_through_the_real_manifest(real, name):
    unit, source = QUEUE[name]
    entry = real.metrics[name]
    assert (entry["unit"], entry["source"], entry["group"]) == (
        unit, source, "per_layer")
    assert entry["layer"] == "serve queue" and entry["moves"] == "scan_GBps"
    assert entry["workloads"] == [CELL]
    spec = real.metric_spec(name)
    assert callable(real.module("readers", spec["reader"]).read)
    assert name in {m["name"] for m in real.cell_metrics(CELL, "per_layer")}


def test_the_cell_reports_what_q1q6_reports_and_the_queue_s(real):
    ours = {m["name"] for m in real.cell_metrics(CELL, "per_layer")}
    theirs = {m["name"] for m in
              real.cell_metrics("lineitem-1chip.q1q6", "per_layer")}
    assert ours == theirs | set(QUEUE)
    assert {m["name"] for m in real.cell_metrics(CELL, "end_to_end")} \
        == {"scan_GBps", "setup_s"}


def test_a_traced_run_reads_the_queue_s_metrics(man, tmp_path, monkeypatch):
    monkeypatch.setattr(tracered, "reduce_trace",
                        lambda raw, chips: FAKE_TRACE)
    out = run.run_cell(man, CELL, 7, 0.3, True, require_tpu=False,
                       out_root=str(tmp_path))
    assert out["correct"] is True
    got = {n: m["value"] for n, m in out["metrics"].items()}
    assert set(QUEUE) <= set(got)
    assert 0.4 <= got["serve_leased_share"] <= 0.6      # q6 leases, q1 not
    assert 0.8 <= got["stream_share_min"] <= 1.0
    assert 0 < got["serve_workers_busy"] < 4            # of four workers
    assert got["serve_lease_wait_ms"] < got["serve_run_ms"]
    assert got["filters_fused_per_request"] == 1.0
    assert got["compiles_in_window.scan"] == 0


def test_the_two_sums_by_hand(real, monkeypatch):
    share = real.module("readers", "stream_share_min").read
    assert share({"result": {"stream_counts": [316, 300, 316, 284, 316]}}) \
        == 284 / (1532 / 5)
    assert share({"result": {"stream_counts": [7, 7]}}) == 1.0
    assert share({"result": {}}) is None                 # one caller
    assert share({"result": {"stream_counts": [0, 0]}}) is None

    from bolt_tpu import obs
    busy = real.module("readers", "span_seconds_over_window").read
    rows = {"serve.run": {"count": 1580, "seconds": 1.5, "self_seconds": 1.0,
                          "bytes": 0}}
    monkeypatch.setattr(obs, "totals", lambda: rows)
    ctx = {"result": {"window_s": 20.0}}
    assert busy(ctx, span="serve.run") == 1.5 / 20.0
    assert busy(ctx, span="serve.lease") is None         # never recorded

    ratio = real.module("readers", "served_ratio").read
    served = {"result": {"serve": {"completed": 1580, "failed": 0,
                                   "leased": 790}}}
    args = real.metric_spec("serve_leased_share")["args"]
    assert ratio(served, **args) == 0.5
    # a program older than the counter, and a driver that ran no server
    assert ratio({"result": {"serve": {"completed": 9, "failed": 0}}},
                 **args) is None
    assert ratio({"result": {}}, **args) is None
