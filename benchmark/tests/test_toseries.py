"""The ``twophoton512-1chip`` configuration at toy size on the CPU: the
session tile in its two spellings, the re-axis against NumPy's
``transpose`` of the host tile, the check that tells every frame's place
(two slabs at each other's offsets are not correct), the control one
precision lower, the cell run end to end through the real manifest with
several slabs a pass, and the three metrics it came with."""

import json
import os

import numpy as np
import pytest

import lattice
import manifest
import pipeline
import roofline
import run

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny")
CELL = "twophoton512-1chip.toseries"
SEEDS = [3, 2**31 + 17, 4294967291]
NEW = {"shuffle_GBps", "shuffle_dispatch_us", "rebucket_roofline"}
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
    monkeypatch.setattr(stream, "_SLAB_BYTES", SLAB * 8 * 16 * 4)


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
    tiny = man.config("twophoton512-1chip")
    full = real.config("twophoton512-1chip")
    for key in ("source", "dtype", "key_axes", "chips", "bits", "data",
                "guarantees", "reduced", "architecture"):
        assert tiny[key] == full[key]
    # ISSUE 32's sizes: 10,240 frames of 512 x 512 float32 = 10.74 GB
    assert (full["frames"], full["frame_shape"]) == (10240, [512, 512])
    assert full["frames"] * 512 * 512 * 4 == 10737418240
    assert full["reduced"] == [] and full["architecture"] is None
    entry, = [c for c in real.doc["configs"]
              if c["name"] == "twophoton512-1chip"]
    assert entry["source"] == full["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == [] and entry is real.doc["configs"][-1]
    assert real.doc["workloads"][-1] == real.cell(CELL)
    traffic = real.traffic("toseries")
    kind, = traffic["requests"]
    assert kind["limit"] == 0 and kind["fetch"] == "ready"
    assert kind["steps"] == [{"call": "swap", "kaxes": [0],
                              "vaxes": [0, 1]}]
    assert (traffic["sample_share"], traffic["warmup_cycles"],
            traffic["trace_seconds"]) == (1.0, 1, 8)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_tile_is_the_whole_session_and_the_closed_form(man, seed):
    cell = built(man, seed)
    tile = cell.operand.tile
    assert tile.shape == cell.operand.shape == (96, 8, 16)
    assert not tile.flags.writeable
    assert np.array_equal(tile, lattice.host_block(0, 96, (8, 16), seed, 12))
    # parts that do not tile the session still fill it
    op = man.module("operands", "session")
    assert np.array_equal(op.host_session(150, (8, 16), seed, 12, 3),
                          lattice.host_block(0, 150, (8, 16), seed, 12))
    assert cell.reference.data_mismatches(np.random.default_rng(seed)) == 0
    # the loader hands out views, not copies
    block = cell.operand.load((slice(16, 32), slice(0, 8), slice(0, 16)))
    assert block.base is not None and np.shares_memory(block, tile)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_is_numpys_transpose_of_the_host_tile(man, seed):
    """The closed form the device compares with IS the re-axed tile: 0
    elements of NumPy's ``transpose`` differ, one moved element is one."""
    import jax.numpy as jnp
    cell = built(man, seed)
    steps, ref = steps_of(cell), cell.reference
    want = np.transpose(cell.operand.tile, (1, 2, 0))
    assert ref.plan(steps).terminal.perm == (1, 2, 0)
    assert float(ref.on_device(steps, jnp.asarray(want))) == 0
    moved = want.copy()
    moved[3, 5, 7], moved[3, 5, 8] = want[3, 5, 8], want[3, 5, 7]
    assert float(ref.on_device(steps, jnp.asarray(moved))) == 2
    # the control: the lattice held in bfloat16 is not the lattice
    assert float(ref.lowp_on_device(steps)) > 0.5 * want.size


@pytest.mark.parametrize("seed", SEEDS)
def test_two_slabs_at_each_others_offsets_are_not_correct(man, seed):
    """What a tile shorter than the session could not tell: every frame in
    a right place of a wrong slab."""
    import jax.numpy as jnp
    cell = built(man, seed)
    steps, ref = steps_of(cell), cell.reference
    frames = cell.operand.tile.copy()
    frames[0:SLAB], frames[2 * SLAB:3 * SLAB] = (
        cell.operand.tile[2 * SLAB:3 * SLAB], cell.operand.tile[0:SLAB])
    wrong = np.transpose(frames, (1, 2, 0))
    count = float(ref.on_device(steps, jnp.asarray(wrong)))
    assert count > 0.9 * 2 * SLAB * 8 * 16   # all but chance collisions


@pytest.mark.parametrize("seed", SEEDS)
def test_the_program_re_axes_the_session_slab_by_slab(man, seed,
                                                      small_slabs):
    from bolt_tpu import engine
    cell = built(man, seed)
    steps = steps_of(cell)
    fetch = man.module("fetches", "ready")
    call = pipeline.compile_call(man, steps)
    c0 = engine.counters()
    handle = call(cell.operand.operand())
    assert handle._stream is not None and handle._stream.slab == SLAB
    got = fetch.take(handle)
    c1 = engine.counters()
    assert got.shape == (8, 16, 96)
    assert np.array_equal(np.asarray(got),
                          np.transpose(cell.operand.tile, (1, 2, 0)))
    assert float(cell.reference.on_device(steps, got)) == 0
    assert c1["stream_chunks"] - c0["stream_chunks"] == 96 // SLAB
    assert c1["shuffle_bytes"] - c0["shuffle_bytes"] == cell.operand.nbytes
    assert c1["spill_bytes"] == c0["spill_bytes"]
    # a second pass compiles nothing
    fetch.take(call(cell.operand.operand()))
    c2 = engine.counters()
    assert c2["aot_compiles"] == c1["aot_compiles"]
    assert c2["misses"] == c1["misses"]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_cell_runs_end_to_end_and_is_correct(man, seed, tmp_path,
                                                 small_slabs):
    out = run.run_cell(man, CELL, seed, 0.3, False, require_tpu=False,
                       out_root=str(tmp_path))
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"streamed_scan_GBps", "setup_s"}
    json.dumps(out)


def test_a_slab_placed_elsewhere_underneath_is_not_correct(man, tmp_path,
                                                           monkeypatch,
                                                           small_slabs):
    """The timed path broken where this PR changed it: every slab's block
    written one slab further on (the last wraps to the front)."""
    from bolt_tpu.parallel import shuffle
    sound = shuffle.place_program

    def broken(plan, *rest):
        prog = sound(plan, *rest)

        def run(out, buf, cursor):
            moved = (cursor + np.uint32(1)) % np.uint32(plan.nslabs)
            return prog(out, buf, moved)[0], cursor + np.uint32(1)
        return run
    monkeypatch.setattr(shuffle, "place_program", broken)
    out = run.run_cell(man, CELL, 5, 0.3, False, require_tpu=False,
                       out_root=str(tmp_path))
    assert out["correct"] is False and out["failed"] > 0


FAKE_TRACE = {"busy_s": 0.08, "window_s": 0.1,
              "ops_s": {"copy.1": 0.05,
                        "bitcast_dynamic-update-slice_fusion": 0.03},
              "idle_gaps_s": {"bench.fetch": 0.02}}


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
                 "peak_hbm_GB.streamed", "runtime_start_s"):
        assert got[name]["value"] >= 0, name
    assert got["shuffle_GBps"]["value"] > 0
    assert got["shuffle_dispatch_us"]["value"] > 0
    assert got["compiles_in_window.streamed"]["value"] == 0
    assert got["device_idle_share.streamed"]["value"] == pytest.approx(20.0)
    # no published peaks for a CPU: the share is left out, not made up
    assert "rebucket_roofline" not in got


def test_rebucket_roofline_counts_one_read_and_one_write(man):
    real = manifest.Manifest(manifest.REAL)
    steps = real.traffic("toseries")["requests"][0]["steps"]
    shape = (10240, 512, 512)
    elements = 10240 * 512 * 512
    assert roofline.hbm_bytes(real, steps, shape, 4, 1) == 2 * elements * 4

    class FakeCell:
        manifest = real
        chips = 1
        peaks = {"hbm_GBps": 819.0}

        class operand:
            pass
    FakeCell.operand.shape = shape
    spec = real.metric_spec("rebucket_roofline")
    reader = real.module("readers", spec["reader"])
    ctx = {"cell": FakeCell, "trace": {"busy_s": 0.5},
           "result": {"requests": [(0, 0, steps)], "slots": [0, 0, 0]}}
    least = 3 * 2 * elements * 4 / 819e9
    assert reader.read(ctx, **spec.get("args", {})) == pytest.approx(
        100 * least / 0.5)
    assert reader.read(dict(ctx, trace=None)) is None
    FakeCell.peaks = None                 # a device with no published peaks
    assert reader.read(ctx) is None


def test_the_new_metrics_resolve_through_the_real_manifest():
    real = manifest.Manifest(manifest.REAL)
    names = {m["name"] for m in real.cell_metrics(CELL, "per_layer")}
    assert names == NEW | {
        "compiles_in_window.streamed", "upload_GBps", "loader_GBps",
        "stream_overlap_share", "stream_wall_over_link",
        "program_ms.streamed", "device_idle_share.streamed",
        "peak_hbm_GB.streamed", "runtime_start_s"}
    for name in names:
        real.module("readers", real.metric_spec(name)["reader"])
    assert {m["name"] for m in real.cell_metrics(CELL, "end_to_end")} == {
        "streamed_scan_GBps", "setup_s"}
    last = [m["name"] for m in real.doc["per_layer"][-3:]]
    assert set(last) == NEW
    for m in real.doc["per_layer"][-3:]:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "streamed_scan_GBps"
    # what reads them: counters and a span the program has, no new code
    assert real.metric_spec("shuffle_GBps") == {
        "reader": "counter_ratio",
        "args": {"num": ["shuffle_bytes"], "den": ["shuffle_seconds"],
                 "scale": 1e-09}}
    assert real.metric_spec("shuffle_dispatch_us")["args"]["span"] == \
        "stream.compute"
