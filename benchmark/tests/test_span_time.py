"""The per-layer metrics that read the program's own spans (PR 24):
``readers/span_time.py`` on a whole traced run at toy sizes on the CPU,
and the eight metrics through the real ``BENCHMARK.json``.

A CPU trace holds no device plane, so ``tracered.reduce_trace`` is stood in
for; the profiler session, the program's spans and every reader are real."""

import os

import pytest

import manifest
import run
import tracered

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny")

SPAN_METRICS = {
    "fetch_force_us.scan": ("array.fetch.force", "request", 1e6, "us"),
    "fetch_force_us.latency": ("array.fetch.force", "request", 1e6, "us"),
    "fetch_wait_ms.scan": ("array.fetch.wait", "request", 1e3, "ms"),
    "fetch_wait_ms.latency": ("array.fetch.wait", "request", 1e3, "ms"),
    "fetch_copy_us.scan": ("array.fetch.copy", "request", 1e6, "us"),
    "fetch_copy_us.latency": ("array.fetch.copy", "request", 1e6, "us"),
    "engine_lookup_us": ("engine.lookup", "span", 1e6, "us"),
    "engine_enqueue_us": ("engine.enqueue", "span", 1e6, "us"),
}
CELLS = {"stack4d-1chip.reduce": ".scan", "stack4d-1chip.followups":
         ".latency"}


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(manifest.REAL,
                             roots=(TINY, os.path.dirname(HERE)))


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_the_metric_resolves_through_the_real_manifest(name):
    real = manifest.Manifest(manifest.REAL)
    span, per, scale, unit = SPAN_METRICS[name]
    spec = real.metric_spec(name)
    assert spec == {"reader": "span_time",
                    "args": {"span": span, "per": per, "scale": scale}}
    entry = real.metrics[name]
    assert entry["group"] == "per_layer"
    assert entry["source"] == "program_span" and entry["unit"] == unit
    assert entry["better"] == "lower"
    cells = entry["workloads"]
    suffix = "." + name.rsplit(".", 1)[1] if "." in name else ".latency"
    assert cells == [c for c, s in CELLS.items() if s == suffix]
    for cell in cells:
        assert name in {m["name"] for m in
                        real.cell_metrics(cell, "per_layer")}
        assert entry["moves"] in {m["name"] for m in
                                  real.cell_metrics(cell, "end_to_end")}


def test_the_new_entries_are_the_last_of_per_layer():
    real = manifest.Manifest(manifest.REAL)
    names = [m["name"] for m in real.doc["per_layer"]]
    assert set(names[-8:]) == set(SPAN_METRICS)
    assert names[-9] == "runtime_start_s"


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_traced_run_prints_them_and_an_untraced_run_does_not(
        man, cell, tmp_path, monkeypatch):
    from bolt_tpu import obs
    obs.disable()
    obs.clear()
    monkeypatch.setattr(tracered, "reduce_trace", lambda raw, chips: {
        "window_s": 1.0, "busy_s": 0.5, "busy_s_per_chip": [0.5],
        "ops_s": {"fusion": 0.5}, "idle_gaps_s": {"bench.fetch": 0.5}})
    want = {n for n in SPAN_METRICS if n.endswith(CELLS[cell])
            or (cell.endswith("followups") and n.startswith("engine_"))}
    plain = run.run_cell(man, cell, 7, 0.3, False, require_tpu=False,
                         out_root=str(tmp_path))
    assert plain["correct"] and not set(plain["metrics"]) & set(SPAN_METRICS)
    assert obs.totals() == {}               # nobody was looking
    traced = run.run_cell(man, cell, 7, 0.3, True, require_tpu=False,
                          out_root=str(tmp_path))
    assert traced["correct"]
    got = traced["metrics"]
    assert want <= set(got)
    assert not (set(SPAN_METRICS) - want) & set(got)
    requests = traced["attempted"]
    totals = obs.totals()
    for name in want:
        span, per, scale, unit = SPAN_METRICS[name]
        den = requests if per == "request" else totals[span]["count"]
        assert got[name]["unit"] == unit
        assert got[name]["value"] == pytest.approx(
            scale * totals[span]["seconds"] / den)
        assert got[name]["value"] > 0
    # the totals are the window's: one fetch a request, warm-up left out
    assert totals["array.fetch"]["count"] == requests
    assert obs.active_count() == 0
    obs.clear()


def test_the_reader_reads_nothing_from_a_program_without_totals(monkeypatch):
    import bolt_tpu.obs
    reader = manifest.Manifest(manifest.REAL).module("readers", "span_time")
    ctx = {"result": {"walls_s": [0.1]}}
    bolt_tpu.obs.clear()
    assert reader.read(ctx, "array.fetch.wait", "request", 1e3) is None
    monkeypatch.delattr(bolt_tpu.obs, "totals")     # the parent commit
    assert reader.read(ctx, "array.fetch.wait", "request", 1e3) is None
