"""A whole run at toy sizes on the CPU: ``run.run_cell`` with the look for
a chip skipped and everything else as in a real run: the real
``BENCHMARK.json`` with ``tiny/`` searched first, which holds only what has
to differ at toy sizes (the configurations, and the follow-ups' positions).  Sound runs come out correct; the timed path broken underneath comes
out not correct; and the control, one precision lower, reads over a limit
in every cell."""

import json
import os
import sys

import numpy as np
import pytest

import manifest
import pipeline
import run

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny")
CELLS = ["stack4d-1chip.reduce", "stack4d-4chip.swap",
         "stack4d-1chip.followups", "stack4d-1chip.stream"]
SEEDS = [3, 2**31 + 17, 4294967291]


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(manifest.REAL,
                             roots=(TINY, os.path.dirname(HERE)))


def go(man, cell, seed, tmp_path, seconds=0.3):
    return run.run_cell(man, cell, seed, seconds, False, require_tpu=False,
                        out_root=str(tmp_path))


def test_tiny_is_the_real_manifest_at_toy_sizes(man):
    real = manifest.Manifest(manifest.REAL)
    assert sorted(man.cells) == sorted(CELLS)
    tiny_files = sorted(os.path.relpath(os.path.join(d, f), TINY)
                        for d, _, fs in os.walk(TINY) for f in fs)
    assert tiny_files == ["configs/stack4d-1chip.json",
                          "configs/stack4d-4chip.json",
                          "traffic/followups.json"]
    for cell in CELLS:
        t = man.traffic(man.cell(cell)["traffic"])
        r = real.traffic(real.cell(cell)["traffic"])
        assert [(k["kind"], k["count"], k["fetch"], k["limit"])
                for k in t["requests"]] == [
            (k["kind"], k["count"], k["fetch"], k["limit"])
            for k in r["requests"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", SEEDS)
def test_a_sound_run_is_correct(man, cell, seed, tmp_path):
    out = go(man, cell, seed, tmp_path)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    want = {m["name"] for m in man.cell_metrics(cell, "end_to_end")}
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    json.dumps(out)


def test_the_multiset_is_the_same_for_every_seed(man):
    traffic = man.traffic("followups")
    requests = pipeline.expand(traffic)
    assert len(requests) == 64
    assert [sum(1 for k, _, _ in requests if k == i) for i in range(4)] \
        == [24, 24, 8, 8]
    orders = [sorted(pipeline.cycle_order(64, s)) for s in SEEDS]
    assert all(o == list(range(64)) for o in orders)
    assert list(pipeline.cycle_order(64, 3)) != list(
        pipeline.cycle_order(64, 4))


# -- the timed path, broken underneath ---------------------------------

def test_a_map_that_returns_its_input_unchanged(man, tmp_path, monkeypatch):
    monkeypatch.setattr(man.module("fns", "plus_one"), "body", lambda v: v)
    out = go(man, "stack4d-1chip.reduce", 5, tmp_path)
    assert out["correct"] is False and out["failed"] > 0


def test_a_swap_that_moves_the_wrong_axis(man, tmp_path, monkeypatch):
    sound = pipeline.compile_call

    def broken(man, steps):
        steps = [dict(s, vaxes=[1]) if s["call"] == "swap" else s
                 for s in steps]
        return sound(man, steps)
    monkeypatch.setattr(pipeline, "compile_call", broken)
    out = go(man, "stack4d-4chip.swap", 5, tmp_path)
    assert out["correct"] is False and out["failed"] > 0


@pytest.mark.parametrize("kind", [0, 1, 2, 3])
def test_an_answer_altered_where_it_is_produced(man, tmp_path, monkeypatch,
                                                kind):
    traffic = man.traffic("followups")
    stat = traffic["requests"][kind]["steps"][-1]["stat"]
    sound = pipeline.compile_call

    def broken(man, steps):
        call = sound(man, steps)
        if steps[-1]["stat"] != stat or (
                kind == 2) != (steps[-1].get("axis") == [1, 2, 3]):
            return call

        class Altered:
            def __init__(self, handle):
                self.handle = handle

            def toarray(self):
                x = np.array(self.handle.toarray())
                x.reshape(-1)[0] *= np.float32(1.001)
                x.reshape(-1)[0] += np.float32(0.5)
                return x
        return lambda operand: Altered(call(operand))
    monkeypatch.setattr(pipeline, "compile_call", broken)
    out = go(man, "stack4d-1chip.followups", 5, tmp_path)
    assert out["correct"] is False and out["failed"] > 0


def test_a_loader_that_leaves_out_a_part_of_the_batch(man, tmp_path,
                                                      monkeypatch):
    callback = man.module("operands", "callback").Callback
    sound = callback.load

    def broken(self, index):
        block = np.array(sound(self, index))
        block[-1] = 0
        return block
    monkeypatch.setattr(callback, "load", broken)
    out = go(man, "stack4d-1chip.stream", 5, tmp_path)
    assert out["correct"] is False and out["failed"] > 0


def test_a_request_that_raises_has_failed(man, tmp_path, monkeypatch):
    toarray = man.module("fetches", "toarray")
    sound = toarray.take
    calls = []

    def sometimes(handle):
        calls.append(1)
        if len(calls) % 50 == 0:
            raise RuntimeError("lost")
        return sound(handle)
    monkeypatch.setattr(toarray, "take", sometimes)
    out = go(man, "stack4d-1chip.reduce", 5, tmp_path)
    assert out["correct"] is False and out["failed"] > 0
    assert out["attempted"] > out["failed"]


# -- the control: one precision lower reads over a limit ----------------

@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", SEEDS)
def test_the_control_comes_out_not_correct(man, cell, seed):
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "tools"))
    import control
    c = run.Cell(man, cell, seed, 0.0, False, require_tpu=False)
    c.log = lambda msg: None
    c.open_device()
    c.build()
    limits = {k["kind"]: float(k["limit"]) for k in c.traffic["requests"]}
    table = control.readings(c)
    assert all(sound <= limits[kind] for kind, (sound, _) in table.items())
    assert any(low > limits[kind] for kind, (_, low) in table.items())


# -- the command itself -------------------------------------------------

def test_the_command_fails_without_a_tpu_and_prints_no_result():
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(HERE), "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not p.stdout.strip().splitlines()[-1].startswith("{")
