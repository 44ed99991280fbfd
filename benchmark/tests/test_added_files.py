"""A later PR's cell whose request makes a call the benchmark does not know
is files of its own and entries in ``BENCHMARK.json``: ``added/`` holds a
new step (``steps/filter_sum.py``: the program's side, the reference's and
the roofline's) and a traffic mix that uses it, and nothing that was there
is edited.  The README's worked example is these files."""

import json
import os

import pytest

import manifest
import roofline
import run

HERE = os.path.dirname(os.path.abspath(__file__))
ADDED = os.path.join(HERE, "added")
TINY = os.path.join(HERE, "tiny")
CELL = "stack4d-1chip.filtered"


@pytest.fixture(scope="module")
def man(tmp_path_factory):
    with open(manifest.REAL) as fh:
        doc = json.load(fh)
    doc["workloads"].append({
        "name": CELL, "config": "stack4d-1chip", "traffic": "filtered",
        "chips": 1, "why": "the filtered sum of the resident stack"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "stack4d-1chip.reduce" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    path = tmp_path_factory.mktemp("added") / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    return manifest.Manifest(str(path),
                             roots=(ADDED, TINY, os.path.dirname(HERE)))


def go(man, tmp_path):
    return run.run_cell(man, CELL, 11, 0.3, False, require_tpu=False,
                        out_root=str(tmp_path))


def test_a_cell_on_a_new_step_runs_and_is_checked(man, tmp_path):
    out = go(man, tmp_path)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"scan_GBps", "setup_s"}
    # the filter keeps some records and drops others at this threshold
    ref = run.Cell(man, CELL, 11, 0.0, False, require_tpu=False)
    ref.log = lambda msg: None
    ref.open_device()
    ref.build()
    steps = ref.traffic["requests"][0]["steps"]
    want = ref.reference.expected(steps)
    everything = ref.reference.expected(
        [steps[0], {"call": "stat", "stat": "sum"}])
    assert 0 < abs(want).sum() < abs(everything).sum()
    # one precision lower reads over the limit
    low = ref.reference.number(steps, ref.reference.lowp(steps), want)
    assert low > ref.traffic["requests"][0]["limit"]
    shape = ref.operand.shape
    assert roofline.hbm_bytes(man, steps, shape, 4, 1) == 4 * (
        shape[0] + 1) * shape[1] * shape[2] * shape[3]


def test_the_new_step_broken_underneath_is_not_correct(man, tmp_path,
                                                       monkeypatch):
    step = man.module("steps", "filter_sum")
    sound = step.bind

    def broken(s, m):                     # keeps every record
        return sound(dict(s, above=-1e9), m)
    monkeypatch.setattr(step, "bind", broken)
    out = go(man, tmp_path)
    assert out["correct"] is False and out["failed"] > 0


def test_nothing_that_was_there_knows_the_new_names():
    bench = os.path.dirname(HERE)
    for folder, _, files in os.walk(bench):
        if os.path.commonpath([folder, HERE]) == HERE:
            continue
        for f in files:
            if f.endswith((".py", ".json")):
                with open(os.path.join(folder, f)) as fh:
                    text = fh.read()
                assert "filter_sum" not in text and "filtered" not in text
