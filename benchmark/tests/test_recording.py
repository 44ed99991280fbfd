"""The ``twophoton512-4chip`` configuration at toy size on the CPU's four
virtual devices: the recording in its two spellings, no two frames alike,
the re-axis against NumPy's ``transpose`` of the host tile, the check that
reads 0 on a sound answer and fails all three controls (the recording held
in bfloat16; a slab placed a slab late; two chips' blocks exchanged), the
cell run end to end through the real manifest with several slabs a pass on
a one-process four-device mesh, and the metrics it came with.  Finds its
entries by name and pins nothing of the manifest's order."""

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
CONFIG, CELL = "twophoton512-4chip", "twophoton512-4chip.toseries4"
SEEDS = [3, 2**31 + 17, 4294967291]
NEW = {"collective_ms.streamed", "alltoall_GB_per_request",
       "upload_parts_per_slab"}
SHARED = {"compiles_in_window.streamed", "upload_GBps", "loader_GBps",
          "stream_overlap_share", "stream_wall_over_link",
          "program_ms.streamed", "device_idle_share.streamed",
          "peak_hbm_GB.streamed", "shuffle_GBps", "shuffle_dispatch_us",
          "upload_workers_busy", "setup_import_s", "setup_trace_lower_s",
          "setup_cache_read_s", "setup_xla_compile_s", "setup_programs",
          "setup_slowest_program_s", "setup_unplaced_s",
          "setup_stream_warmup_s", "runtime_start_s"}
SLAB = 16                                 # frames a slab in these tests
SHAPE = (96, 8, 16)


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(manifest.REAL,
                             roots=(TINY, os.path.dirname(HERE)))


@pytest.fixture
def small_slabs(monkeypatch):
    """The caller sets no ``chunks``; at toy size the default 64 MiB slab
    would hold the whole recording, so the default itself is made small:
    six slabs of ``SLAB`` frames a pass, four frames of each a device."""
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


def test_the_real_files_say_what_the_issue_says(man):
    real = manifest.Manifest(manifest.REAL)
    full, tiny = real.config(CONFIG), man.config(CONFIG)
    one = real.config("twophoton512-1chip")
    for key in ("source", "dtype", "key_axes", "chips", "bits", "data",
                "guarantees", "reduced", "architecture"):
        assert tiny[key] == full[key]
    assert (full["frames"], full["frame_shape"]) == (40960, [512, 512])
    assert full["frames"] * 512 * 512 * 4 == 42949672960
    assert full["frames"] * 512 * 512 > 1 << 32     # what lattice.py refuses
    assert full["chips"] == 4 and full["bits"] == 12
    assert full["reduced"] == [] and full["architecture"] is None
    assert full["guarantees"] == one["guarantees"]
    assert full["source"] != one["source"] and len(full["assumed"]) >= 5
    entry, = [c for c in real.doc["configs"] if c["name"] == CONFIG]
    assert entry["source"] == full["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == [] and len(entry["why"]) <= 200
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    cell = real.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "toseries4", 4)
    assert len(cell["why"]) <= 200
    traffic = real.traffic("toseries4")
    assert traffic["operand"] == {"name": "recording"}
    kind, = traffic["requests"]
    assert kind["limit"] == 0 and kind["fetch"] == "ready"
    assert kind["steps"] == [{"call": "toseries", "kaxes": [0],
                              "vaxes": [0, 1]}]
    assert (traffic["sample_share"], traffic["warmup_cycles"],
            traffic["trace_seconds"]) == (1.0, 1, 10)
    # at most half of the cells, rounded down, ask for four chips
    four = [w for w in real.doc["workloads"] if w["chips"] == 4]
    assert len(four) <= len(real.doc["workloads"]) // 2


@pytest.mark.parametrize("seed", SEEDS)
def test_the_two_spellings_agree_to_the_bit(man, seed):
    import jax
    cell = built(man, seed)
    op = man.module("operands", "recording")
    tile, ref = cell.operand.tile, cell.reference
    assert tile.shape == cell.operand.shape == SHAPE
    assert tile.dtype == np.float32 and not tile.flags.writeable
    # the tile (filled from the table of pixel keys) is the closed form
    assert np.array_equal(tile, op.host_frames(0, 96, SHAPE[1:], seed, 12))
    a, b = ref.constants()
    whole = jax.jit(lambda: op.device_values(SHAPE, a, b, 12))()
    assert np.array_equal(np.asarray(whole), tile)
    for order in [(1, 2, 0), (2, 0, 1), (0, 2, 1)]:
        made = jax.jit(lambda o=order: op.device_values(SHAPE, a, b, 12,
                                                        order=o))()
        assert np.array_equal(np.asarray(made), np.transpose(tile, order))
    for axis, by in [(0, SLAB), (1, 2), (2, -5)]:
        made = jax.jit(lambda r=(axis, by): op.device_values(
            SHAPE, a, b, 12, order=(1, 2, 0), roll=r))()
        assert np.array_equal(
            np.asarray(made),
            np.transpose(np.roll(tile, by, axis=axis), (1, 2, 0)))
    # runs of frames that do not divide among the threads still fill it
    assert np.array_equal(op.host_recording(61, SHAPE[1:], seed, 12, 7),
                          tile[:61])
    assert ref.data_mismatches(np.random.default_rng(seed)) == 0
    # the loader hands out views, not copies
    block = cell.operand.load((slice(16, 32), slice(0, 8), slice(0, 16)))
    assert block.base is not None and np.shares_memory(block, tile)


@pytest.mark.parametrize("seed", SEEDS)
def test_no_two_frames_are_alike_at_the_real_sizes(man, seed):
    """Sampled frames of the 40,960, the pairs a lattice with a second
    multiplier on ``t`` would leave alike among them (``t`` a large power
    of two apart): integers of 12 bits, mostly not bfloat16's, no pair of
    frames agreeing in more pixels than chance gives."""
    op = man.module("operands", "recording")
    rng = np.random.default_rng(seed)
    ts = sorted({0, 1, 2, 127, 128, 8192, 16384, 32768, 40959, 16384 + 128}
                | set(int(t) for t in rng.choice(40960, 6, replace=False)))
    frames = np.stack([op.host_frames(t, t + 1, (512, 512), seed, 12)[0]
                       for t in ts]).reshape(len(ts), -1)
    assert frames.min() >= -2048 and frames.max() < 2048
    assert np.array_equal(frames, np.rint(frames))
    assert frames.min() < -2000 and frames.max() > 2000   # the range is used
    import jax.numpy as jnp
    held = np.asarray(jnp.asarray(frames[0]).astype(jnp.bfloat16)
                      .astype(jnp.float32))
    assert (held != frames[0]).mean() > 0.5
    for i in range(len(ts)):
        for j in range(i + 1, len(ts)):
            same = float((frames[i] == frames[j]).mean())
            assert same < 4.0 / 4096, (ts[i], ts[j], same)
            top = float(((frames[i] >= 0) == (frames[j] >= 0)).mean())
            assert 0.49 < top < 0.51, (ts[i], ts[j], top)  # top bits too
    # neighbouring seeds share nothing either
    other = op.host_frames(0, 1, (512, 512), seed + 1, 12).reshape(-1)
    assert float((other == frames[0]).mean()) < 4.0 / 4096
    # an index past 32 bits is refused, not wrapped
    with pytest.raises(ValueError):
        op.check_sizes(1 << 32, (512, 512), 12)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_is_numpys_transpose_of_the_host_tile(man, seed):
    import jax.numpy as jnp
    cell = built(man, seed)
    steps, ref = steps_of(cell), cell.reference
    want = np.transpose(cell.operand.tile, (1, 2, 0))
    assert ref.plan(steps).terminal.perm == (1, 2, 0)
    assert float(ref.on_device(steps, jnp.asarray(want))) == 0
    moved = want.copy()
    moved[3, 5, 7], moved[3, 5, 8] = want[3, 5, 8], want[3, 5, 7]
    assert float(ref.on_device(steps, jnp.asarray(moved))) == 2
    # control 1: the recording held in bfloat16 is not the recording
    assert float(ref.lowp_on_device(steps)) > 0.5 * want.size


@pytest.mark.parametrize("seed", SEEDS)
def test_misplaced_blocks_are_not_correct(man, seed):
    """Controls 2 and 3, the answer really moved: a slab of frames placed
    a slab late (every frame in a right place of a wrong slab), and two
    chips' blocks of rows exchanged.  ``displaced_on_device`` counts the
    same without making the moved copy, which is what runs at 42.95 GB."""
    import jax.numpy as jnp
    cell = built(man, seed)
    steps, ref = steps_of(cell), cell.reference
    terminal, plan = ref.plan(steps).terminal, ref.plan(steps)
    tile = cell.operand.tile
    sound = jnp.asarray(np.transpose(tile, (1, 2, 0)))
    # one slab a slab late: slabs 2 and 3 trade places
    frames = tile.copy()
    frames[2 * SLAB:3 * SLAB], frames[3 * SLAB:4 * SLAB] = (
        tile[3 * SLAB:4 * SLAB], tile[2 * SLAB:3 * SLAB])
    count = float(ref.on_device(steps, jnp.asarray(
        np.transpose(frames, (1, 2, 0)))))
    assert count > 0.99 * 2 * SLAB * 8 * 16
    # two chips' blocks exchanged: rows [0, 2) and [2, 4) of x's 8
    rows = np.transpose(tile, (1, 2, 0)).copy()
    rows[0:2], rows[2:4] = rows[2:4].copy(), rows[0:2].copy()
    count = float(ref.on_device(steps, jnp.asarray(rows)))
    assert count > 0.99 * 4 * 16 * 96
    # every slab a slab late, every chip's block on the next chip: the
    # moved answer against the closed form reads what the sound answer
    # reads against the closed form moved
    for axis, by in [(0, SLAB), (1, 2)]:
        moved = jnp.asarray(np.transpose(np.roll(tile, by, axis=axis),
                                         (1, 2, 0)))
        literal = float(ref.on_device(steps, moved))
        assert literal > 0.99 * tile.size
        assert float(terminal.displaced_on_device(
            ref, plan, sound, axis, -by)) == literal
        assert float(terminal.displaced_on_device(
            ref, plan, moved, axis, by)) == 0
    assert float(terminal.displaced_on_device(ref, plan, sound, 0, 96)) == 0


@pytest.mark.parametrize("seed", SEEDS)
def test_the_program_re_axes_the_recording_on_four_devices(man, seed,
                                                           small_slabs):
    from bolt_tpu import engine
    cell = built(man, seed)
    assert cell.mesh.devices.size == 4
    steps = steps_of(cell)
    fetch = man.module("fetches", "ready")
    call = pipeline.compile_call(man, steps)
    c0 = engine.counters()
    handle = call(cell.operand.operand())
    assert handle._stream is not None and handle._stream.slab == SLAB
    got = fetch.take(handle)
    c1 = engine.counters()
    assert got.shape == (8, 16, 96)
    assert len(got.sharding.device_set) == 4
    assert {s.data.shape for s in got.addressable_shards} == {(2, 16, 96)}
    assert np.array_equal(np.asarray(got),
                          np.transpose(cell.operand.tile, (1, 2, 0)))
    assert float(cell.reference.on_device(steps, got)) == 0
    slabs = 96 // SLAB
    assert c1["stream_chunks"] - c0["stream_chunks"] == slabs
    assert c1["shuffle_bytes"] - c0["shuffle_bytes"] == cell.operand.nbytes
    assert c1["spill_bytes"] == c0["spill_bytes"]
    # what this configuration came with: three quarters of the recording
    # cross devices, and a slab goes up as four sub-blocks
    assert c1["stream_alltoall_bytes"] - c0["stream_alltoall_bytes"] \
        == cell.operand.nbytes * 3 // 4
    assert c1["stream_upload_parts"] - c0["stream_upload_parts"] \
        == 4 * slabs
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
    assert out["attempted"] > 0 and out["device"]["count"] >= 4
    assert set(out["metrics"]) == {"streamed_scan_GBps", "setup_s"}
    json.dumps(out)


def test_a_slab_placed_a_slab_late_underneath_is_not_correct(
        man, tmp_path, monkeypatch, small_slabs):
    """The timed path broken: every slab's block written one slab further
    on (the last wraps to the front)."""
    from bolt_tpu.parallel import shuffle
    sound = shuffle.place_program

    def broken(plan, *rest):
        prog = sound(plan, *rest)

        def place(out, buf, cursor):
            moved = (cursor + np.uint32(1)) % np.uint32(plan.nslabs)
            return prog(out, buf, moved)[0], cursor + np.uint32(1)
        return place
    monkeypatch.setattr(shuffle, "place_program", broken)
    out = run.run_cell(man, CELL, 5, 0.3, False, require_tpu=False,
                       out_root=str(tmp_path))
    assert out["correct"] is False and out["failed"] > 0


def test_blocks_on_the_wrong_chip_underneath_are_not_correct(
        man, tmp_path, monkeypatch, small_slabs):
    """The timed path broken across chips: the finished series array with
    every chip's block of rows handed to the next chip."""
    import jax.numpy as jnp
    from bolt_tpu.parallel import shuffle
    sound = shuffle.place_program

    def broken(plan, *rest):
        prog = sound(plan, *rest)

        def place(out, buf, cursor):
            out, cursor = prog(out, buf, cursor)
            return jnp.roll(out, plan.out_shape[0] // 4, axis=0), cursor
        return place
    monkeypatch.setattr(shuffle, "place_program", broken)
    out = run.run_cell(man, CELL, 5, 0.3, False, require_tpu=False,
                       out_root=str(tmp_path))
    assert out["correct"] is False and out["failed"] > 0


FAKE_TRACE = {"busy_s": 0.08, "window_s": 0.1,
              "ops_s": {"copy.1": 0.03, "all-to-all": 0.02,
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
    got, n = out["metrics"], out["attempted"]
    for name in ("shuffle_GBps", "shuffle_dispatch_us", "upload_GBps",
                 "loader_GBps", "stream_overlap_share",
                 "stream_wall_over_link", "program_ms.streamed",
                 "peak_hbm_GB.streamed", "runtime_start_s",
                 "upload_workers_busy"):
        assert got[name]["value"] >= 0, name
    assert got["compiles_in_window.streamed"]["value"] == 0
    assert got["device_idle_share.streamed"]["value"] == pytest.approx(20.0)
    # the three this configuration came with
    assert got["collective_ms.streamed"]["value"] == pytest.approx(
        0.02 / n * 1e3)
    assert got["alltoall_GB_per_request"]["value"] == pytest.approx(
        96 * 8 * 16 * 4 * 0.75 * 1e-9)
    assert got["upload_parts_per_slab"]["value"] == 4
    # no published peaks for a CPU: the roofline share is left out
    assert set(got) == NEW | SHARED


def test_the_new_readers_say_nothing_on_a_program_without_the_counters(man):
    """The benchmark as this PR leaves it is laid over the parent's
    program too, which has neither counter: the metric is left out of the
    line, and nothing raises."""
    real = manifest.Manifest(manifest.REAL)

    class OldCell:
        counters0 = {"stream_chunks": 0, "requests": 0}
        counters1 = {"stream_chunks": 640, "requests": 2}

        def counter_delta(self, name):
            return self.counters1[name] - self.counters0[name]
    ctx = {"cell": OldCell(), "result": {"walls_s": [1.0, 1.0]},
           "trace": None}
    for name in ("alltoall_GB_per_request", "upload_parts_per_slab"):
        spec = real.metric_spec(name)
        assert spec["reader"] == "counter_ratio_known"
        reader = real.module("readers", spec["reader"])
        assert reader.read(ctx, **spec["args"]) is None
    OldCell.counters0.update(stream_alltoall_bytes=0, stream_upload_parts=0)
    OldCell.counters1.update(stream_alltoall_bytes=2 * 32212254720,
                             stream_upload_parts=2560)
    read = {name: real.module("readers", "counter_ratio_known").read(
        ctx, **real.metric_spec(name)["args"])
        for name in ("alltoall_GB_per_request", "upload_parts_per_slab")}
    assert read["alltoall_GB_per_request"] == pytest.approx(32.21225472)
    assert read["upload_parts_per_slab"] == 4
    # a window in which no slab went up: nothing, not a division by zero
    OldCell.counters1["stream_chunks"] = 0
    assert real.module("readers", "counter_ratio_known").read(
        ctx, **real.metric_spec("upload_parts_per_slab")["args"]) is None
    # no collective on the trace (a one-chip program): nothing
    spec = real.metric_spec("collective_ms.streamed")
    reader = real.module("readers", spec["reader"])
    ctx["trace"] = {"ops_s": {"copy.1": 0.5}}
    assert reader.read(ctx, **spec["args"]) is None
    ctx["trace"] = {"ops_s": {"all-to-all.3": 0.5}}
    assert reader.read(ctx, **spec["args"]) == pytest.approx(250.0)


def test_the_metrics_resolve_through_the_real_manifest():
    real = manifest.Manifest(manifest.REAL)
    names = {m["name"] for m in real.cell_metrics(CELL, "per_layer")}
    assert names == NEW | SHARED | ROOFLINE
    for name in names:
        real.module("readers", real.metric_spec(name)["reader"])
    assert {m["name"] for m in real.cell_metrics(CELL, "end_to_end")} == {
        "streamed_scan_GBps", "setup_s"}
    by_name = {m["name"]: m for m in real.doc["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "streamed_scan_GBps"
    assert by_name["collective_ms.streamed"]["layer"] == \
        by_name["collective_ms.scan"]["layer"]
    # every metric the one-chip toseries lists, this cell lists too
    for m in real.doc["per_layer"]:
        if "twophoton512-1chip.toseries" in m.get("workloads", ()):
            assert CELL in m["workloads"], m["name"]
    # the bytes a pass cannot avoid, a chip: one read and one write of a
    # quarter of the recording
    steps = real.traffic("toseries4")["requests"][0]["steps"]
    assert roofline.hbm_bytes(real, steps, (40960, 512, 512), 4, 4) \
        == 2 * 40960 * 512 * 512 * 4 / 4


# the same arithmetic a chip on four as on one (``roofline.hbm_bytes`` divides
# by the chips, ``tracered``'s busy seconds are the mean over them), and read
# on the chips before it was listed: 14.8 % (PERF.md section 5, PR 43)
ROOFLINE = {"rebucket_roofline"}
