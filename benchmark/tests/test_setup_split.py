"""The per-layer metrics that split ``setup_s`` (PR 34): the readers
``counter_at_start``, ``compile_log_max`` and ``setup_unplaced`` on a whole
traced run at toy sizes on the CPU through the real ``BENCHMARK.json``, and
on a program that lacks what they read.

A CPU trace holds no device plane, so ``tracered.reduce_trace`` is stood in
for; the engine's counters, its compile log and every reader are real."""

import os
import types

import pytest

import manifest
import run
import tracered

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny")

TIMES = ["setup_import_s", "setup_trace_lower_s", "setup_cache_read_s",
         "setup_xla_compile_s", "setup_stream_warmup_s"]
EVERYWHERE = {"setup_import_s", "setup_trace_lower_s", "setup_cache_read_s",
              "setup_xla_compile_s", "setup_programs",
              "setup_slowest_program_s", "setup_unplaced_s"}
STREAMED = {"stack4d-1chip.stream", "twophoton512-1chip.toseries"}
FAKE_TRACE = {"window_s": 1.0, "busy_s": 0.5, "busy_s_per_chip": [0.5],
              "ops_s": {"fusion": 0.5}, "idle_gaps_s": {"bench.fetch": 0.5}}


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(manifest.REAL,
                             roots=(TINY, os.path.dirname(HERE)))


@pytest.fixture(scope="module")
def real():
    return manifest.Manifest(manifest.REAL)


def test_eight_entries_are_appended_and_each_moves_setup_s(real):
    new = real.doc["per_layer"][-8:]
    assert {m["name"] for m in new} == EVERYWHERE | {"setup_stream_warmup_s"}
    cells = [w["name"] for w in real.doc["workloads"]]
    for m in new:
        assert m["moves"] == "setup_s" and m["better"] == "lower"
        assert m["source"] == "program_counter"
        assert m["unit"] == ("count" if m["name"] == "setup_programs"
                             else "s")
        want = ([c for c in cells if c in STREAMED]
                if m["name"] == "setup_stream_warmup_s" else cells)
        assert m["workloads"] == want
        real.module("readers", real.metric_spec(m["name"])["reader"])
    layers = {m["name"]: m["layer"] for m in new}
    assert layers["setup_import_s"] == "entry points"
    assert layers["setup_stream_warmup_s"] == "streamed executor"
    assert layers["setup_unplaced_s"] == "device"
    assert layers["setup_xla_compile_s"] == "engine"


@pytest.mark.parametrize("cell", ["stack4d-1chip.reduce",
                                  "stack4d-1chip.stream"])
def test_a_traced_run_prints_the_split_and_it_adds_up(man, cell, tmp_path,
                                                      monkeypatch):
    monkeypatch.setattr(tracered, "reduce_trace",
                        lambda raw, chips: FAKE_TRACE)
    seen = {}
    real_read = run.read_metrics

    def keep_cell(cell_, result, group):
        seen["cell"] = cell_
        return real_read(cell_, result, group)
    monkeypatch.setattr(run, "read_metrics", keep_cell)
    plain = run.run_cell(man, cell, 7, 0.3, False, require_tpu=False,
                         out_root=str(tmp_path))
    assert plain["correct"]
    assert not [n for n in plain["metrics"] if n.startswith("setup_")
                and n != "setup_s"]
    traced = run.run_cell(man, cell, 7, 0.3, True, require_tpu=False,
                          out_root=str(tmp_path))
    assert traced["correct"]
    got = {n: m["value"] for n, m in traced["metrics"].items()
           if n.startswith("setup_")}
    want = EVERYWHERE | ({"setup_stream_warmup_s"} if cell in STREAMED
                         else set())
    assert set(got) == want
    for name, value in got.items():
        assert value >= 0, name
    the_cell = seen["cell"]
    parts = sum(got[n] for n in TIMES if n in got)
    if cell not in STREAMED:
        # a resident cell's streamed part is 0 in a process of its own;
        # this one has run streamed cells before
        parts += (the_cell.counters0["stream_wall_seconds"]
                  - the_cell.counters0["stream_compile_seconds"])
    assert parts + got["setup_unplaced_s"] == pytest.approx(
        the_cell.setup_s, abs=1e-9)
    assert got["setup_programs"] >= 1
    assert got["setup_trace_lower_s"] > 0
    assert got["setup_import_s"] == the_cell.counters0["import_seconds"]
    # one program cannot have taken longer than all of them
    assert 0 < got["setup_slowest_program_s"] <= (
        the_cell.counters0["lower_seconds"]
        + the_cell.counters0["compile_seconds"])
    if cell in STREAMED:
        assert 0 < got["setup_stream_warmup_s"] \
            <= the_cell.counters0["stream_wall_seconds"]


class Stub:
    """A cell of a program from before PR 34: its counters lack the new
    keys and its engine keeps no compile log."""

    setup_s = 3.0
    counters0 = {"dispatches": 5, "lower_seconds": 0.1,
                 "stream_wall_seconds": 2.0}

    def __init__(self, man, compile_log=None):
        self.manifest = man
        self.said = []
        self.engine = types.SimpleNamespace()
        if compile_log is not None:
            self.engine.compile_log = lambda: compile_log

    def log(self, msg):
        self.said.append(msg)


@pytest.mark.parametrize("name", sorted(EVERYWHERE
                                        | {"setup_stream_warmup_s"}))
def test_a_program_without_the_counters_reads_as_nothing(real, name):
    spec = real.metric_spec(name)
    reader = real.module("readers", spec["reader"])
    stub = Stub(real)
    assert reader.read({"cell": stub}, **spec.get("args", {})) is None
    stub.counters0 = None                   # before any window began
    assert reader.read({"cell": stub}, **spec.get("args", {})) is None


def test_the_readers_arithmetic(real):
    stub = Stub(real, compile_log=[
        {"family": "stat", "program": "aa", "lower_s": 0.1,
         "compile_s": 0.2, "cache": "hit", "read_s": 0.1, "dispatches": 0},
        {"family": "swap", "program": "bb", "lower_s": 0.3,
         "compile_s": 0.4, "cache": "miss", "read_s": 0.0, "dispatches": 4},
        # compiled by the window's first request: not the set-up's
        {"family": "late", "program": "cc", "lower_s": 5.0,
         "compile_s": 5.0, "cache": "miss", "read_s": 0.0, "dispatches": 5},
    ])
    stub.counters0 = {
        "dispatches": 5, "import_seconds": 0.5, "trace_seconds": 0.25,
        "mlir_seconds": 0.125, "persistent_read_seconds": 0.0625,
        "backend_compile_seconds": 0.0, "compile_requests": 9,
        "stream_wall_seconds": 1.5, "stream_compile_seconds": 0.5}

    def value(name, cell=stub):
        spec = real.metric_spec(name)
        return real.module("readers", spec["reader"]).read(
            {"cell": cell}, **spec.get("args", {}))
    assert value("setup_import_s") == 0.5
    assert value("setup_trace_lower_s") == 0.375
    assert value("setup_cache_read_s") == 0.0625
    assert value("setup_xla_compile_s") == 0.0
    assert value("setup_programs") == 9
    assert value("setup_stream_warmup_s") == 1.0
    assert value("setup_unplaced_s") == 3.0 - 1.9375
    assert value("setup_slowest_program_s") == pytest.approx(0.7)
    assert "swap bb" in stub.said[-1] and "miss" in stub.said[-1]
    idle = Stub(real, compile_log=[])       # a process that compiled nothing
    idle.counters0 = stub.counters0
    assert value("setup_slowest_program_s", idle) is None
