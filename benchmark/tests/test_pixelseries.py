"""The ``pixelseries512-1chip`` configuration at toy size on the CPU: the
session in its two spellings and what was planted in it, the plain
reference in its two precisions, the program against it and the control one
precision lower, the cell run end to end through the real manifest with the
map lowered over blocks, a program broken underneath, and the five metrics
it came with."""

import json
import os

import numpy as np
import pytest

import manifest
import pipeline
import roofline
import run

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny")
CONFIG = "pixelseries512-1chip"
CELL = "pixelseries512-1chip.tuning"
SEEDS = [3, 2**31 + 17, 4294967291]
NEW = {"map_blocks_per_request", "sort_ms.scan", "fft_ms.scan",
       "series_chain_us", "launches_per_request.scan"}
SHAPE = (12, 16, 256)


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(manifest.REAL,
                             roots=(TINY, os.path.dirname(HERE)))


@pytest.fixture
def little_hbm():
    """On the CPU no device limits a program, and the toy session would be
    lowered whole: give the rule the limit a chip's ``memory_stats()``
    gives it, scaled to the toy (the session, its maps and 100 KB)."""
    from bolt_tpu.tpu import array
    array._HBM_LIMIT_OVERRIDE = 4 * int(np.prod(SHAPE)) + 100_000
    yield
    array._HBM_LIMIT_OVERRIDE = None


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
    for key in ("source", "dtype", "key_axes", "chips", "bits", "series",
                "data", "guarantees", "assumed", "reduced", "architecture"):
        assert tiny[key] == full[key]
    # ISSUE 36's sizes: 512 x 512 pixels x 10,240 points float32 = 10.74 GB
    assert (full["pixels"], full["times"]) == ([512, 512], 10240)
    assert 512 * 512 * 10240 * 4 == 10737418240
    assert full["reduced"] == [] and full["architecture"] is None
    # found by name, not by place: the next configuration goes after it
    entry, = [c for c in real.doc["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == []
    assert entry["source"] == full["source"] and len(entry["source"]) <= 200
    assert real.cell(CELL)["chips"] == 1
    assert real.cell(CELL)["config"] == CONFIG
    traffic = real.traffic("tuning")
    kind, = traffic["requests"]
    assert kind["fetch"] == "toarray_pair" and kind["limit"] == 1
    step, = kind["steps"]
    assert (step["perc"], step["order"], step["freq"]) == (20.0, 5, 16)
    assert (traffic["sample_share"], traffic["warmup_cycles"]) == (1.0, 1)
    assert step["freq"] == full["series"]["freq"]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_session_is_the_closed_form_in_both_spellings(man, seed):
    cell = built(man, seed)
    op = man.module("operands", "pixelseries")
    assert cell.operand.shape == SHAPE and cell.operand.nbytes == 196608
    held = np.asarray(cell.operand.data)
    want = op.host_rows(np.arange(12 * 16), cell.config["series"], 256,
                        seed).reshape(SHAPE)
    assert held.dtype == np.float32 and np.array_equal(held, want)
    assert cell.reference.data_mismatches(np.random.default_rng(seed)) == 0
    # integers inside (0, 2**14): exact in float32, not in bfloat16
    assert np.array_equal(held, np.rint(held)) and held.min() > 0
    assert held.max() < 1 << 14
    import reference
    assert (np.asarray(reference.bf16(cell.operand.data)) != held).mean() > 0.9
    # keyed by pixel, both axes; the caller's array, not consumed
    b = cell.operand.operand()
    assert b.split == 2 and b.shape == SHAPE and b is cell.operand.operand()


def test_another_seed_is_another_session(man):
    a, b = built(man, 3), built(man, 4)
    assert not np.array_equal(np.asarray(a.operand.data),
                              np.asarray(b.operand.data))


def test_what_was_planted_is_what_the_analysis_finds(man):
    """At the real length (10,240 points, a few pixels): the coherence the
    planted amplitude gives, the planted phase, the floor of the untuned."""
    op = man.module("operands", "pixelseries")
    step = man.module("steps", "tuning_map")
    import lattice
    spec = manifest.Manifest(manifest.REAL).config(CONFIG)["series"]
    seed, times = 11, 10240
    p = np.arange(96)
    rows = op.host_rows(p, spec, times, seed).astype(np.float64)
    coh, ph = step.analysis64(rows, 20.0, 5, spec["freq"])
    _, salt = lattice.constants(seed)
    with np.errstate(over="ignore"):
        _, _, _, a, b = op.planted(p.astype(np.uint32), spec, salt, np)
    amp = np.hypot(a, b)
    s2 = spec["noise"] * (spec["noise"] + 1) / 3.0
    untuned = amp == 0
    assert 0.2 < untuned.mean() < 0.5
    assert np.max(coh[untuned]) < 0.06          # floor sqrt(2 / T) = 0.014
    want = amp / np.sqrt(amp ** 2 + 2 * s2)
    assert np.max(np.abs(coh - want)[~untuned]) < 0.03
    assert want.max() > 0.85
    strong = want > 0.3
    turn = np.abs(np.angle(np.exp(1j * (ph - np.arctan2(-b, a)))))
    assert np.max(turn[strong]) < 0.05


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_agrees_with_itself_in_float64(man, seed):
    cell = built(man, seed)
    steps, ref = steps_of(cell), cell.reference
    want = ref.expected(steps)
    term = ref.plan(steps).terminal
    every = np.arange(12 * 16)
    c64, p64 = man.module("steps", "tuning_map").analysis64(
        ref.pixels(every), 20.0, 5, 16)
    assert np.max(np.abs(want["coherence"].reshape(-1) - c64)) < 2e-5
    tuned = c64 >= term.tuned
    assert 0.2 < tuned.mean() < 0.9
    assert np.max(np.abs(np.angle(np.exp(1j * (
        want["phase"].reshape(-1) - p64))))[tuned]) < 2e-4
    assert np.array_equal(want["coherence64"], c64[want["picks"]])
    # the reference answering itself reads 0 against the float32 maps
    parts = term.parts({"coherence": want["coherence"],
                        "phase": want["phase"]}, want)
    assert parts["coherence"] == 0 and parts["phase"] == 0
    assert ref.number(steps, {"coherence": want["coherence"],
                              "phase": want["phase"]}, want) < 1


@pytest.mark.parametrize("seed", SEEDS)
def test_the_program_is_sound_and_the_control_is_not(man, seed, little_hbm):
    from bolt_tpu import engine
    cell = built(man, seed)
    steps, ref = steps_of(cell), cell.reference
    fetch = man.module("fetches", "toarray_pair")
    call = pipeline.compile_call(man, steps)
    c0 = engine.counters()
    got = fetch.take(call(cell.operand.operand()))
    c1 = engine.counters()
    assert got["coherence"].shape == got["phase"].shape == (12, 16)
    want = ref.expected(steps)
    assert ref.number(steps, got, want) < 1
    # fourier's two results are two chains: two programs, both blocked
    assert c1["dispatches"] - c0["dispatches"] == 2
    assert c1["blocked_chains"] >= 2        # traced once a process
    assert c1["map_blocks"] - c0["map_blocks"] >= 4
    # a second request compiles nothing
    fetch.take(call(cell.operand.operand()))
    c2 = engine.counters()
    assert c2["aot_compiles"] == c1["aot_compiles"]
    assert c2["misses"] == c1["misses"]
    # the same analysis of the session held in bfloat16 is over a limit
    low = ref.lowp(steps)
    term = ref.plan(steps).terminal
    parts = term.parts(low, want)
    assert ref.number(steps, low, want) > 1
    assert parts["coherence"] > 1e-4 or parts["phase"] > 1e-3
    # a malformed answer is infinitely wrong
    assert ref.number(steps, {"coherence": got["coherence"]}, want) == \
        float("inf")
    bad = dict(got, phase=np.full((12, 16), np.nan, np.float32))
    assert ref.number(steps, bad, want) == float("inf")


@pytest.mark.parametrize("seed", SEEDS)
def test_the_cell_runs_end_to_end_and_is_correct(man, seed, tmp_path,
                                                 little_hbm):
    out = run.run_cell(man, CELL, seed, 0.3, False, require_tpu=False,
                       out_root=str(tmp_path))
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"scan_GBps", "setup_s"}
    json.dumps(out)


def test_a_wrong_bin_underneath_is_not_correct(man, tmp_path, monkeypatch):
    """The timed path broken in the program: the transform read one bin
    off the stimulus."""
    from bolt_tpu.ops import series
    sound = series._fourier_fn
    monkeypatch.setattr(series, "_fourier_fn",
                        lambda freq, ax, eps: sound(freq - 1, ax, eps))
    out = run.run_cell(man, CELL, 5, 0.3, False, require_tpu=False,
                       out_root=str(tmp_path))
    assert out["correct"] is False and out["failed"] > 0


def test_the_maps_do_not_depend_on_the_baseline(man, tmp_path, monkeypatch):
    """What this check cannot see, written down: coherence and phase are
    invariant to a series' scale and offset, and dF/F = v / base - 1 is a
    scale and an offset, so a WRONG percentile (the median here) moves
    neither map beyond rounding and the run stays correct.  The baseline
    is held by ``tests/test_series_tuning.py`` (dF/F itself against
    NumPy's percentile), not by this cell (PERF.md section 7)."""
    from bolt_tpu.ops import series
    sound = series._normalize_fn
    monkeypatch.setattr(
        series, "_normalize_fn",
        lambda baseline, perc, ax, eps: sound(baseline, 50.0, ax, eps))
    out = run.run_cell(man, CELL, 5, 0.3, False, require_tpu=False,
                       out_root=str(tmp_path))
    assert out["correct"] is True


def test_an_older_program_is_refused_at_once(man, monkeypatch):
    from bolt_tpu import engine
    sound = engine.counters

    def older():
        c = sound()
        del c["map_blocks"]
        return c
    monkeypatch.setattr(engine, "counters", older)
    cell = run.Cell(man, CELL, 3, 0.0, False, require_tpu=False)
    cell.open_device()
    with pytest.raises(SystemExit, match="map_blocks"):
        cell.build()


FAKE_TRACE = {"busy_s": 0.09, "window_s": 0.1,
              "ops_s": {"sort.3": 0.05, "convolution_add_fusion.5": 0.012,
                        "copy.32": 0.006, "copy_bitcast_fusion.4": 0.002,
                        "fusion.1": 0.02},
              "idle_gaps_s": {"bench.fetch": 0.01}}


def test_a_traced_run_reads_the_per_layer_metrics(man, tmp_path,
                                                  monkeypatch, little_hbm):
    import tracered
    from bolt_tpu import obs
    obs.disable()
    obs.clear()
    monkeypatch.setattr(tracered, "reduce_trace",
                        lambda raw, chips: FAKE_TRACE)
    out = run.run_cell(man, CELL, 5, 0.3, True, require_tpu=False,
                       out_root=str(tmp_path))
    assert out["correct"] is True
    got, n = out["metrics"], out["attempted"]
    assert got["launches_per_request.scan"]["value"] == 2
    assert got["map_blocks_per_request"]["value"] >= 4
    assert got["sort_ms.scan"]["value"] == pytest.approx(50.0 / n)
    assert got["fft_ms.scan"]["value"] == pytest.approx(20.0 / n)
    assert got["series_chain_us"]["value"] > 0
    assert got["compiles_in_window.scan"]["value"] == 0
    assert got["device_idle_share.scan"]["value"] == pytest.approx(10.0)
    for name in ("program_ms.scan", "peak_hbm_GB.scan", "fetch_force_us.scan",
                 "fetch_wait_ms.scan", "fetch_copy_us.scan",
                 "runtime_start_s", "setup_programs"):
        assert got[name]["value"] >= 0, name
    # no published peaks for a CPU: the share is left out, not made up
    assert "hbm_roofline_share.scan" not in got


def test_the_roofline_counts_one_read_and_two_maps():
    real = manifest.Manifest(manifest.REAL)
    steps = real.traffic("tuning")["requests"][0]["steps"]
    shape = (512, 512, 10240)
    assert roofline.hbm_bytes(real, steps, shape, 4, 1) == \
        (512 * 512 * 10240 + 2 * 512 * 512) * 4


def test_the_new_metrics_resolve_through_the_real_manifest():
    real = manifest.Manifest(manifest.REAL)
    names = {m["name"] for m in real.cell_metrics(CELL, "per_layer")}
    # at least these: a later PR may give the cell one more
    assert names >= NEW | {
        "compiles_in_window.scan", "program_ms.scan",
        "hbm_roofline_share.scan", "device_idle_share.scan",
        "peak_hbm_GB.scan", "fetch_force_us.scan", "fetch_wait_ms.scan",
        "fetch_copy_us.scan", "runtime_start_s", "setup_import_s",
        "setup_trace_lower_s", "setup_cache_read_s", "setup_xla_compile_s",
        "setup_programs", "setup_slowest_program_s", "setup_unplaced_s"}
    for name in names:
        real.module("readers", real.metric_spec(name)["reader"])
    assert {m["name"] for m in real.cell_metrics(CELL, "end_to_end")} == {
        "scan_GBps", "setup_s"}
    for m in real.doc["per_layer"]:
        if m["name"] in NEW:
            assert CELL in m["workloads"] and m["moves"] == "scan_GBps"
    # what reads them: counters, spans and trace names, no new reader
    assert real.metric_spec("map_blocks_per_request") == {
        "reader": "counter_ratio",
        "args": {"num": ["map_blocks"], "den": ["requests"]}}
    assert real.metric_spec("launches_per_request.scan") == \
        real.metric_spec("launches_per_request")
    assert real.metric_spec("series_chain_us")["args"]["span"] == \
        "array.chain"
    # no event of the trace carries "fft": XLA's TPU FFT of 10,240 points
    # is 128-point DFTs on the matrix unit (convolution_*_fusion) and
    # relayout copies (copy.*, copy_bitcast_fusion.*), and nothing else in
    # this cell's programs has either name (PERF.md section 3)
    assert real.metric_spec("fft_ms.scan")["args"]["match"] == [
        "convolution", "copy"]
    assert real.metric_spec("sort_ms.scan")["args"]["match"] == ["sort"]
