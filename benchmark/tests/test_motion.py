"""The ``motion512-1chip`` configuration at toy size on the CPU: the moving
session in its two spellings, the shares its generator promises, the cell run
end to end through the real manifest with several slabs a pass, and the
check that tells a slab at another slab's place, a frame shifted by its
neighbour's displacement and a session held in bfloat16 from the answer.
Finds its entries by name and pins nothing of the manifest's order."""

import json
import os

import numpy as np
import pytest

import lattice
import manifest
import pipeline
import run

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny")
CONFIG, CELL = "motion512-1chip", "motion512-1chip.register"
SEEDS = [3, 2**31 + 17, 4294967291]
NEW = {"xcorr_ms.streamed", "collect_place_us", "keyed_slabs_per_request",
       "register_call_us"}
SLAB = 16                                 # frames a slab in these tests


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(manifest.REAL,
                             roots=(TINY, os.path.dirname(HERE)))


@pytest.fixture
def small_slabs(monkeypatch):
    """The caller sets no ``chunks``; at toy size the default 64 MiB slab
    would hold the whole session, so the default itself is made small: six
    slabs of ``SLAB`` frames a pass."""
    from bolt_tpu import stream
    monkeypatch.setattr(stream, "_SLAB_BYTES", SLAB * 16 * 32 * 4)


def built(man, seed):
    cell = run.Cell(man, CELL, seed, 0.0, False, require_tpu=False)
    cell.log = lambda msg: None
    cell.open_device()
    cell.build()
    return cell


def steps_of(cell):
    (_, _, steps), = pipeline.expand(cell.traffic)
    return steps


def answer(man, cell):
    """One request through the program: ``(series, disp)`` as the fetch
    hands it to the check."""
    call = pipeline.compile_call(man, steps_of(cell))
    return man.module("fetches", "registered").take(
        call(cell.operand.operand()))


def test_the_real_files_say_what_the_issue_says(man):
    real = manifest.Manifest(manifest.REAL)
    full, tiny = real.config(CONFIG), man.config(CONFIG)
    for key in ("source", "dtype", "key_axes", "chips", "bits", "data",
                "guarantees", "reduced", "architecture"):
        assert tiny[key] == full[key]
    assert (full["frames"], full["frame_shape"]) == (10240, [512, 512])
    assert full["frames"] * 512 * 512 * 4 == 10737418240
    assert full["reduced"] == [] and full["architecture"] is None
    assert full["reference_frames"] == 256 and full["bits"] == 14
    assert full["motion"]["walk"] == 12 and full["motion"]["margin"] == 16
    assert len(full["assumed"]) >= 6
    entry, = [c for c in real.doc["configs"] if c["name"] == CONFIG]
    assert entry["source"] == full["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == [] and len(entry["why"]) <= 200
    cell = real.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "register", 1)
    traffic = real.traffic("register")
    kind, = traffic["requests"]
    assert kind["limit"] == 1 and kind["fetch"] == "registered"
    step, = kind["steps"]
    assert step["call"] == "register" and step["sampled"] == 256
    assert step["limits"]["registered"] == 0
    assert 0 < step["limits"]["regret"] < 1
    assert 0 < step["limits"]["regret64"] < 1
    assert (traffic["sample_share"], traffic["warmup_cycles"],
            traffic["trace_seconds"]) == (1.0, 1, 8)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_two_spellings_agree_to_the_bit(man, seed):
    import jax
    import jax.numpy as jnp
    cell = built(man, seed)
    op = man.module("operands", "motion")
    tile, ref = cell.operand.tile, cell.reference
    assert tile.shape == cell.operand.shape == (96, 16, 32)
    assert tile.dtype == np.float32 and not tile.flags.writeable
    salt, scene, walk = ref.constants()
    spec = dict(ref.spec_items)
    made = jax.jit(lambda t0: op.device_frames(
        t0, 32, spec, (16, 32), salt, scene, walk))
    for t0 in (0, 32, 64):
        assert np.array_equal(np.asarray(made(jnp.int32(t0))),
                              tile[t0:t0 + 32])
    # parts that do not tile the session still fill it
    again = op.host_session(61, cell.operand.spec, (16, 32), seed,
                            (cell.operand.tables[0],
                             cell.operand.tables[1][:61]), threads=3)
    assert np.array_equal(again, tile[:61])
    assert ref.data_mismatches(np.random.default_rng(seed)) == 0
    # the loader hands out views, not copies
    block = cell.operand.load((slice(16, 32), slice(0, 16), slice(0, 32)))
    assert block.base is not None and np.shares_memory(block, tile)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_generator_keeps_its_promises(man, seed):
    """The walk bounded, one step at a time; every value an integer under
    ``2**bits``, exact in float32 and mostly not in bfloat16; neighbouring
    seeds share no scene."""
    real = manifest.Manifest(manifest.REAL)
    op = man.module("operands", "motion")
    spec = real.config(CONFIG)["motion"]
    walk = op.walk(spec, 10240, seed)
    assert walk.shape == (10240, 2) and walk.dtype == np.int32
    assert np.abs(walk).max() <= 12 and np.all(walk[0] == 0)
    assert np.abs(np.diff(walk, axis=0)).max() <= 1
    assert np.abs(walk).max() >= 8          # it does move
    scene = op.scene(spec, (512, 512), seed, 14)
    assert scene.shape == (544, 544) and scene.dtype == np.int32
    assert scene.min() >= spec["rest"]
    assert scene.max() + spec["noise"] < 1 << 14
    frames = op.host_frames(100, 102, spec, (512, 512), seed, (scene, walk))
    assert frames.min() >= 0 and frames.max() < 1 << 14
    assert np.array_equal(frames, np.rint(frames))
    import spectral
    assert np.mean(spectral.bf16(frames) != frames) > 0.8
    other = op.scene(spec, (512, 512), seed + 1, 14)
    assert np.mean(other != scene) > 0.9
    op.check_spec(spec, 10240, (512, 512), 14)
    with pytest.raises(ValueError, match="margin"):
        op.check_spec(dict(spec, walk=17), 10240, (512, 512), 14)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_program_registers_the_session_slab_by_slab(man, seed,
                                                        small_slabs):
    from bolt_tpu import engine
    cell = built(man, seed)
    steps, ref = steps_of(cell), cell.reference
    c0 = engine.counters()
    series, disp = answer(man, cell)
    c1 = engine.counters()
    assert series.shape == (16, 32, 96) and disp.shape == (96, 2)
    assert disp.dtype == np.int32 and isinstance(disp, np.ndarray)
    # the displacement takes the planted walk out, up to the reference
    # image's own place (one constant for the session)
    moved = disp + cell.operand.tables[1]
    assert np.abs(moved - np.round(moved.mean(axis=0))).max() <= 2
    # against NumPy: the session shifted frame by frame, re-axed
    rows = np.clip(np.arange(16)[None, :] + disp[:, :1], 0, 15)
    cols = np.clip(np.arange(32)[None, :] + disp[:, 1:], 0, 31)
    tile = cell.operand.tile
    want = np.stack([tile[t][np.ix_(rows[t], cols[t])] for t in range(96)])
    assert np.array_equal(np.asarray(series), np.transpose(want, (1, 2, 0)))
    got = ref.on_device(steps, (series, disp))
    parts = got.block_until_ready().parts()
    assert parts["registered"] == 0
    assert parts["regret"] <= 1e-6 and parts["regret64"] <= 1e-6
    assert float(got) <= 1
    # 1 + 6 + 6 slabs: the mean, the collect, the keyed swap
    assert c1["stream_chunks"] - c0["stream_chunks"] == 13
    assert c1["stream_collect_slabs"] - c0["stream_collect_slabs"] == 6
    assert c1["stream_keyed_slabs"] - c0["stream_keyed_slabs"] == 6
    assert c1["shuffle_bytes"] - c0["shuffle_bytes"] == tile.nbytes
    assert cell.operand.nbytes == 2 * tile.nbytes + tile[:16].nbytes
    # a second request, its reference image and displacements fresh
    # arrays, compiles nothing
    answer(man, cell)
    c2 = engine.counters()
    assert c2["aot_compiles"] == c1["aot_compiles"]
    assert c2["misses"] == c1["misses"]


@pytest.mark.parametrize("seed", SEEDS)
def test_what_is_not_the_answer_reads_as_not_correct(man, seed,
                                                     small_slabs):
    """Two slabs at each other's offsets, one frame shifted by its
    neighbour's displacement, a session held in bfloat16, and displacements
    a pixel off: each is over a limit."""
    import jax.numpy as jnp
    import spectral
    cell = built(man, seed)
    steps, ref = steps_of(cell), cell.reference
    limits = steps[0]["limits"]
    series, disp = answer(man, cell)
    sound = np.asarray(series)

    def number(series, disp):
        return float(ref.on_device(steps, (jnp.asarray(series), disp)))
    assert number(sound, disp) <= 1

    swapped = sound.copy()
    swapped[:, :, 0:SLAB] = sound[:, :, 2 * SLAB:3 * SLAB]
    swapped[:, :, 2 * SLAB:3 * SLAB] = sound[:, :, 0:SLAB]
    assert number(swapped, disp) == float("inf")

    # frame 40 shifted by frame 41's displacement (made to differ)
    other = disp.copy()
    other[41] = disp[40] + np.asarray([1, -2], np.int32)
    tile = cell.operand.tile
    rows = np.clip(np.arange(16) + other[41, 0], 0, 15)
    cols = np.clip(np.arange(32) + other[41, 1], 0, 31)
    wrong = sound.copy()
    wrong[:, :, 40] = tile[40][np.ix_(rows, cols)]
    got = ref.on_device(steps, (jnp.asarray(wrong), disp))
    assert got.parts()["registered"] > 100 and float(got) == float("inf")

    held = spectral.bf16(sound).astype(np.float32)
    got = ref.on_device(steps, (jnp.asarray(held), disp))
    assert got.parts()["registered"] > 0.5 * sound.size
    assert float(got) == float("inf")
    control = ref.lowp_on_device(steps)
    assert control.parts()["registered"] > 0.5 * sound.size
    assert float(control) == float("inf")

    # displacements a pixel off on every frame: the series array agrees
    # with THEM, and their regret is over its limit
    off = disp + np.asarray([1, 0], np.int32)
    rows = np.clip(np.arange(16)[None, :] + off[:, :1], 0, 15)
    cols = np.clip(np.arange(32)[None, :] + off[:, 1:], 0, 31)
    moved = np.stack([tile[t][np.ix_(rows[t], cols[t])] for t in range(96)])
    got = ref.on_device(steps, (jnp.asarray(np.transpose(moved, (1, 2, 0))),
                                off))
    parts = got.parts()
    assert parts["registered"] == 0 and parts["differ"] == 96
    assert parts["regret"] > limits["regret"]
    assert parts["regret64"] > limits["regret64"]
    assert float(got) > 1
    # and a trace of the wrong shape or type is no answer at all
    assert float(ref.on_device(steps, (jnp.asarray(sound), disp[:-1]))) \
        == float("inf")


@pytest.mark.parametrize("seed", SEEDS)
def test_the_cell_runs_end_to_end_and_is_correct(man, seed, tmp_path,
                                                 small_slabs):
    out = run.run_cell(man, CELL, seed, 0.3, False, require_tpu=False,
                       out_root=str(tmp_path))
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"streamed_scan_GBps", "setup_s"}
    json.dumps(out)


def test_a_frame_shifted_by_its_neighbours_underneath_is_not_correct(
        man, tmp_path, monkeypatch, small_slabs):
    """The timed path broken where this PR added to it: the keyed stage
    handed every slab's first key one frame late."""
    from bolt_tpu import engine
    from bolt_tpu.tpu import array as tpu_array
    sound = tpu_array._chain_apply
    engine.clear()                        # nothing compiled may be reused

    def late(funcs, split, data, key0=None):
        return sound(funcs, split, data,
                     key0=None if key0 is None else key0 + 1)
    monkeypatch.setattr(tpu_array, "_chain_apply", late)
    try:
        out = run.run_cell(man, CELL, 5, 0.3, False, require_tpu=False,
                           out_root=str(tmp_path))
    finally:
        engine.clear()                    # nor may what this compiled
    assert out["correct"] is False and out["failed"] > 0


FAKE_TRACE = {"busy_s": 0.06, "window_s": 0.1,
              "ops_s": {"fusion.7": 0.03, "copy.1": 0.01,
                        "bitcast_dynamic-update-slice_fusion": 0.02},
              "idle_gaps_s": {"bench.fetch": 0.04}}


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
    for name in ("shuffle_GBps", "shuffle_dispatch_us", "upload_GBps",
                 "loader_GBps", "stream_overlap_share",
                 "stream_wall_over_link", "program_ms.streamed",
                 "peak_hbm_GB.streamed", "upload_workers_busy",
                 "collect_place_us", "register_call_us", "setup_programs"):
        assert got[name]["value"] >= 0, name
    assert got["keyed_slabs_per_request"]["value"] == 6
    assert got["collect_place_us"]["value"] > 0
    assert got["register_call_us"]["value"] > 0
    assert got["compiles_in_window.streamed"]["value"] == 0
    assert got["device_idle_share.streamed"]["value"] == pytest.approx(40.0)
    # the device's seconds while the call (ref and fit) was open: the
    # span's host-clock length less the idle gaps given to it
    assert got["xcorr_ms.streamed"]["value"] > 0
    spec = man.metric_spec("xcorr_ms.streamed")
    assert spec["args"] == {"spans": ["bench.call"]}
    reader = man.module("readers", spec["reader"])
    assert reader.read(
        {"trace": {"idle_gaps_s": {"bench.call": 0.25, "bench.fetch": 9.0}},
         "result": {"walls_s": [1.0, 1.0], "span_s": {"bench.call": 1.0}}},
        **spec["args"]) == pytest.approx(375.0)
    # nothing where nothing was traced or timed, and never an error
    assert reader.read({"trace": None, "result": {"walls_s": [1.0]}},
                       **spec["args"]) is None
    assert reader.read({"trace": {"idle_gaps_s": {}},
                        "result": {"walls_s": [1.0]}},
                       **spec["args"]) is None


def test_the_new_metrics_resolve_through_the_real_manifest():
    real = manifest.Manifest(manifest.REAL)
    names = {m["name"] for m in real.cell_metrics(CELL, "per_layer")}
    assert NEW <= names
    for name in ("compiles_in_window.streamed", "upload_GBps", "loader_GBps",
                 "stream_overlap_share", "stream_wall_over_link",
                 "program_ms.streamed", "device_idle_share.streamed",
                 "peak_hbm_GB.streamed", "upload_workers_busy",
                 "shuffle_GBps", "shuffle_dispatch_us",
                 "setup_stream_warmup_s", "setup_programs"):
        assert name in names, name
    assert "rebucket_roofline" not in names
    for name in names:
        real.module("readers", real.metric_spec(name)["reader"])
    assert {m["name"] for m in real.cell_metrics(CELL, "end_to_end")} == {
        "streamed_scan_GBps", "setup_s"}
    for m in real.doc["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "streamed_scan_GBps"
    assert real.metric_spec("keyed_slabs_per_request")["args"] == {
        "num": ["stream_keyed_slabs"], "den": ["requests"]}
    assert real.metric_spec("collect_place_us")["args"]["span"] == \
        "stream.collect.place"
    assert real.metric_spec("register_call_us")["args"]["span"] == \
        "ops.register"


def test_a_program_without_the_module_is_refused_before_the_session(
        man, monkeypatch):
    """What the parent commit does with this cell's files: an error in
    words from the operand, before a frame is made."""
    import builtins
    real_import = builtins.__import__

    def no_register(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "bolt_tpu.ops" and "register" in (fromlist or ()):
            raise ImportError("cannot import name 'register'")
        return real_import(name, globals, locals, fromlist, level)
    monkeypatch.setattr(builtins, "__import__", no_register)
    cell = run.Cell(man, CELL, 3, 0.0, False, require_tpu=False)
    cell.log = lambda msg: None
    cell.open_device()
    with pytest.raises(SystemExit, match="needs a program with bolt_tpu.ops"):
        cell.build()
