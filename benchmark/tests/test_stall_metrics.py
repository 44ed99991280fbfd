"""The streamed pipeline's stall account (PR 48): six per-layer metrics that
are data alone.  Each resolves through the real manifest to one of the two
span readers that were there, reads a toy ``obs.totals()``, reports
nothing where the program never recorded its span (a parent commit; every
``--trace 0`` run), and a traced toy run of a streamed cell reports all
six."""

import os

import pytest

import manifest
import run

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny")
STREAMED = ["stack4d-1chip.stream", "twophoton512-1chip.toseries",
            "motion512-1chip.register", "twophoton512-4chip.toseries4"]
US = {"per": "span", "scale": 1e6}
# metric -> (reader, its arguments, unit, layer)
SIX = {
    "consumer_starved_share": ("span_seconds_over_window",
                               {"span": "stream.wait.slab"}, "x", "ingest"),
    "feeder_ring_wait_share": ("span_seconds_over_window",
                               {"span": "stream.wait.ring"}, "x", "ingest"),
    "consumer_dispatch_share": ("span_seconds_over_window",
                                {"span": "stream.dispatch"}, "x",
                                "streamed executor"),
    "consumer_sync_share": ("span_seconds_over_window",
                            {"span": "stream.sync"}, "x",
                            "streamed executor"),
    "slab_dispatch_us": ("span_time", dict(US, span="stream.dispatch"),
                         "us", "streamed executor"),
    "slab_sync_us": ("span_time", dict(US, span="stream.sync"), "us",
                     "streamed executor"),
}
# a window of 2 s: 80 slabs and the end-of-stream call
TOTALS = {
    "stream.wait.slab": {"count": 81, "seconds": 0.2},
    "stream.wait.ring": {"count": 82, "seconds": 1.5},
    "stream.dispatch": {"count": 80, "seconds": 0.08},
    "stream.sync": {"count": 80, "seconds": 1.6},
}
WANT = {"consumer_starved_share": 0.1, "feeder_ring_wait_share": 0.75,
        "consumer_dispatch_share": 0.04, "consumer_sync_share": 0.8,
        "slab_dispatch_us": 1000.0, "slab_sync_us": 20000.0}


@pytest.fixture(scope="module")
def real():
    return manifest.Manifest(manifest.REAL)


@pytest.fixture
def toy_totals(monkeypatch):
    from bolt_tpu import obs
    rows = {name: dict(row, self_seconds=row["seconds"], bytes=0)
            for name, row in TOTALS.items()}
    monkeypatch.setattr(obs, "totals", lambda: rows)
    return rows


def read(real, name, result):
    spec = real.metric_spec(name)
    reader = real.module("readers", spec["reader"])
    return reader.read({"result": result, "cell": None, "trace": None},
                       **spec.get("args", {}))


@pytest.mark.parametrize("name", sorted(SIX))
def test_the_metric_is_data_on_a_reader_that_was_there(real, name):
    reader, args, unit, layer = SIX[name]
    assert real.metric_spec(name) == {"reader": reader, "args": args}
    entry = real.metrics[name]
    assert entry["group"] == "per_layer"
    assert (entry["unit"], entry["layer"]) == (unit, layer)
    assert entry["source"] == "program_span"
    assert entry["moves"] == "streamed_scan_GBps"
    assert entry["workloads"] == STREAMED
    # what this PR adds under ``paths`` is this one kind of file
    assert os.path.isfile(os.path.join(os.path.dirname(HERE), "metrics",
                                       name + ".json"))
    assert os.path.isfile(os.path.join(os.path.dirname(HERE), "readers",
                                       reader + ".py"))


@pytest.mark.parametrize("cell", STREAMED)
def test_every_streamed_cell_reports_the_six_and_no_other_cell(real, cell):
    names = {m["name"] for m in real.cell_metrics(cell, "per_layer")}
    assert set(SIX) <= names
    for other in real.cells:
        if other not in STREAMED:
            assert not set(SIX) & {m["name"] for m in real.cell_metrics(
                other, "per_layer")}


@pytest.mark.parametrize("name", sorted(SIX))
def test_the_metric_reads_a_toy_totals(real, toy_totals, name):
    result = {"window_s": 2.0, "walls_s": [1.0, 1.0]}
    assert read(real, name, result) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(SIX))
def test_a_program_without_the_span_reports_nothing(real, monkeypatch, name):
    """The parent commit, and every run with tracing off: no row, or a row
    that never counted."""
    from bolt_tpu import obs
    result = {"window_s": 2.0, "walls_s": [1.0]}
    monkeypatch.setattr(obs, "totals", lambda: {})
    assert read(real, name, result) is None
    span = SIX[name][1]["span"]
    monkeypatch.setattr(obs, "totals", lambda: {
        span: {"count": 0, "seconds": 0.0, "self_seconds": 0.0, "bytes": 0}})
    assert read(real, name, result) is None


def test_the_consumers_three_shares_are_parts_of_one_thread(real,
                                                            toy_totals):
    """Starved, calling and blocked are disjoint on the consumer's thread,
    so they sum to at most the window; the ring's wait is the pool's
    threads' and sums over them."""
    result = {"window_s": 2.0, "walls_s": [1.0, 1.0]}
    parts = [read(real, name, result) for name in (
        "consumer_starved_share", "consumer_dispatch_share",
        "consumer_sync_share")]
    assert sum(parts) == pytest.approx(0.94)


FAKE_TRACE = {"window_s": 0.5, "busy_s": 0.4, "busy_s_per_chip": [0.4],
              "ops_s": {"copy.1": 0.05}, "idle_gaps_s": {"bench.fetch": 0.02}}


def test_a_traced_toy_run_of_a_streamed_cell_reports_all_six(
        tmp_path, monkeypatch):
    import tracered
    from bolt_tpu import obs, stream
    obs.disable()
    obs.clear()
    # at toy size the default slab would hold the whole session
    monkeypatch.setattr(stream, "_SLAB_BYTES", 16 * 8 * 16 * 4)
    monkeypatch.setattr(tracered, "reduce_trace",
                        lambda raw, chips: FAKE_TRACE)
    man = manifest.Manifest(manifest.REAL,
                            roots=(TINY, os.path.dirname(HERE)))
    out = run.run_cell(man, "twophoton512-1chip.toseries", 5, 0.3, True,
                       require_tpu=False, out_root=str(tmp_path))
    assert out["correct"] is True
    got = {name: out["metrics"][name]["value"] for name in SIX}
    assert all(value > 0 for value in got.values()), got
    assert got["consumer_starved_share"] + got["consumer_dispatch_share"] \
        + got["consumer_sync_share"] <= 1.0
    # the span the older metric reads holds the call and the block
    assert got["slab_dispatch_us"] + got["slab_sync_us"] \
        <= out["metrics"]["shuffle_dispatch_us"]["value"]
