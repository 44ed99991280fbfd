"""The ``twophoton512-u16-1chip`` configuration at toy size on the CPU: the
session in its two spellings, stored as the files hold it; two frames
agreeing in a pixel as often as chance gives; the re-axis against NumPy's
``transpose`` of the host tile; the check that reads 0 on a sound answer,
``inf`` on an answer of another element, and fails both controls (the
values moved through bfloat16 and back; a slab placed a slab late); the
cell run end to end through the real manifest with several slabs a pass;
and the metrics it came with.  Finds its entries by name, compares subsets
and pins nothing of the manifest's order or of what other cells list."""

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
CONFIG, CELL = "twophoton512-u16-1chip", "twophoton512-u16-1chip.toseries16"
FLOAT_CELL = "twophoton512-1chip.toseries"
SEEDS = [3, 2**31 + 17, 4294967291]
NEW = {"wire_bytes_per_element", "narrow_slabs_per_request",
       "narrow_rebucket_roofline", "narrow_unpack_ms.streamed"}
FOUR_BYTES = {"rebucket_roofline"}        # readers that count 4 B a value
SHARED = {"compiles_in_window.streamed", "upload_GBps", "loader_GBps",
          "stream_overlap_share", "stream_wall_over_link",
          "program_ms.streamed", "device_idle_share.streamed",
          "peak_hbm_GB.streamed", "runtime_start_s", "shuffle_GBps",
          "shuffle_dispatch_us", "setup_import_s", "setup_trace_lower_s",
          "setup_cache_read_s", "setup_xla_compile_s", "setup_programs",
          "setup_slowest_program_s", "setup_stream_warmup_s",
          "setup_unplaced_s", "upload_workers_busy",
          "consumer_starved_share", "feeder_ring_wait_share",
          "consumer_dispatch_share", "consumer_sync_share",
          "slab_dispatch_us", "slab_sync_us", "windowed_slabs_per_request"}
SLAB = 16                                 # frames a slab in these tests
SHAPE = (96, 8, 16)


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
    monkeypatch.setattr(stream, "_SLAB_BYTES", SLAB * 8 * 16 * 2)


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
    assert (full["frames"], full["frame_shape"]) == (20480, [512, 512])
    assert (full["dtype"], full["bits"], full["chips"]) == ("uint16", 12, 1)
    # the bytes of the float32 session, twice its frames; as float32 it is
    # more than a chip holds, and more elements than lattice.py indexes
    assert full["frames"] * 512 * 512 * 2 == 10737418240 \
        == one["frames"] * 512 * 512 * 4
    assert full["frames"] * 512 * 512 * 4 > 16.9e9
    assert full["frames"] * 512 * 512 > 1 << 32
    assert full["reduced"] == [] and full["architecture"] is None
    for key in ("parity", "rounded"):
        assert full["guarantees"][key] == one["guarantees"][key]
    assert "stored" in full["guarantees"]
    assert full["source"] != one["source"] and len(full["assumed"]) >= 4
    entry, = [c for c in real.doc["configs"] if c["name"] == CONFIG]
    assert entry["source"] == full["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == [] and len(entry["why"]) <= 200
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    cell = real.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "toseries16", 1)
    assert len(cell["why"]) <= 200
    traffic = real.traffic("toseries16")
    assert traffic["operand"] == {"name": "recording_u16"}
    assert traffic["driver"] == "closed_loop"
    kind, = traffic["requests"]
    assert kind["limit"] == 0 and kind["fetch"] == "ready"
    assert kind["kind"] == "toseries16" and kind["count"] == 1
    assert kind["steps"] == [{"call": "toseries_narrow", "kaxes": [0],
                              "vaxes": [0, 1]}]
    assert (traffic["sample_share"], traffic["warmup_cycles"],
            traffic["trace_seconds"]) == (1.0, 1, 8)


@pytest.mark.parametrize("dtype", ["uint16", "int16", "uint8"])
@pytest.mark.parametrize("seed", SEEDS)
def test_the_two_spellings_agree_to_the_bit(man, seed, dtype):
    import jax
    import lattice
    op = man.module("operands", "recording_u16")
    bits = 12 if dtype != "uint8" else 8
    tile = op.host_recording(96, SHAPE[1:], seed, bits, dtype)
    assert tile.shape == SHAPE and tile.dtype == np.dtype(dtype)
    assert tile.min() >= 0 and tile.max() < 1 << bits
    # the tile (filled from the table of pixel keys) is the closed form
    assert np.array_equal(tile, op.host_frames(0, 96, SHAPE[1:], seed, bits,
                                               dtype))
    import jax.numpy as jnp
    a, b = (jnp.uint32(c) for c in lattice.constants(seed))
    whole = jax.jit(lambda: op.device_values(SHAPE, a, b, bits,
                                             dtype=dtype))()
    assert whole.dtype == np.dtype(dtype)
    assert np.array_equal(np.asarray(whole), tile)
    for order in [(1, 2, 0), (2, 0, 1), (0, 2, 1)]:
        made = jax.jit(lambda o=order: op.device_values(
            SHAPE, a, b, bits, order=o, dtype=dtype))()
        assert np.array_equal(np.asarray(made), np.transpose(tile, order))
    for axis, by in [(0, SLAB), (1, 2), (2, -5)]:
        made = jax.jit(lambda r=(axis, by): op.device_values(
            SHAPE, a, b, bits, order=(1, 2, 0), roll=r, dtype=dtype))()
        assert np.array_equal(
            np.asarray(made),
            np.transpose(np.roll(tile, by, axis=axis), (1, 2, 0)))
    # runs of frames that do not divide among the threads still fill it
    assert np.array_equal(
        op.host_recording(61, SHAPE[1:], seed, bits, dtype, 7), tile[:61])
    # it is the float32 recording's form without the offset
    f32 = man.module("operands", "recording").host_frames(
        0, 96, SHAPE[1:], seed, bits)
    assert np.array_equal(tile.astype(np.float32) - (1 << (bits - 1)), f32)


def test_what_the_element_cannot_hold_is_refused(man):
    op = man.module("operands", "recording_u16")
    with pytest.raises(ValueError):
        op.check_dtype("float32", 12)
    with pytest.raises(ValueError):
        op.check_dtype("uint8", 12)
    with pytest.raises(ValueError):
        op.check_sizes(1 << 32, (512, 512), 12)     # an index past 32 bits


@pytest.mark.parametrize("seed", SEEDS)
def test_the_tile_is_the_closed_form_and_the_loader_hands_out_views(man,
                                                                    seed):
    cell = built(man, seed)
    tile, ref = cell.operand.tile, cell.reference
    assert tile.shape == cell.operand.shape == SHAPE
    assert tile.dtype == np.uint16 and not tile.flags.writeable
    assert cell.operand.nbytes == 96 * 8 * 16 * 2
    assert ref.data_mismatches(np.random.default_rng(seed)) == 0
    block = cell.operand.load((slice(16, 32), slice(0, 8), slice(0, 16)))
    assert block.base is not None and np.shares_memory(block, tile)
    assert cell.operand.operand().dtype == np.uint16


@pytest.mark.parametrize("seed", SEEDS)
def test_two_frames_agree_in_a_pixel_as_often_as_chance_gives(man, seed):
    """Sampled frames of the 20,480 at the real frame size, frames a large
    power of two apart among them: integers of 12 bits that use the range,
    mostly not bfloat16's, no pair agreeing in more pixels than chance."""
    op = man.module("operands", "recording_u16")
    rng = np.random.default_rng(seed)
    ts = sorted({0, 1, 2, 127, 128, 8192, 16384, 20479, 16384 + 128}
                | set(int(t) for t in rng.choice(20480, 6, replace=False)))
    frames = np.stack([op.host_frames(t, t + 1, (512, 512), seed, 12)[0]
                       for t in ts]).reshape(len(ts), -1)
    assert frames.dtype == np.uint16
    assert frames.min() < 48 and 4048 < frames.max() < 4096
    import jax.numpy as jnp
    held = np.asarray(jnp.asarray(frames[0]).astype(jnp.bfloat16)
                      .astype(jnp.uint16))
    assert (held != frames[0]).mean() > 0.5
    for i in range(len(ts)):
        for j in range(i + 1, len(ts)):
            same = float((frames[i] == frames[j]).mean())
            assert same < 4.0 / 4096, (ts[i], ts[j], same)
            top = float(((frames[i] >= 2048) == (frames[j] >= 2048)).mean())
            assert 0.49 < top < 0.51, (ts[i], ts[j], top)
    other = op.host_frames(0, 1, (512, 512), seed + 1, 12).reshape(-1)
    assert float((other == frames[0]).mean()) < 4.0 / 4096


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_is_numpys_transpose_and_holds_the_dtype(man, seed):
    import jax.numpy as jnp
    cell = built(man, seed)
    steps, ref = steps_of(cell), cell.reference
    want = np.transpose(cell.operand.tile, (1, 2, 0))
    assert ref.plan(steps).terminal.perm == (1, 2, 0)
    assert float(ref.on_device(steps, jnp.asarray(want))) == 0
    moved = want.copy()
    moved[3, 5, 7], moved[3, 5, 8] = want[3, 5, 8], want[3, 5, 7]
    assert float(ref.on_device(steps, jnp.asarray(moved))) == 2
    # this configuration's own control: the right values in another
    # element are not the answer (steps/toseries.py's != would promote
    # and read 0), nor is another shape
    for widened in (np.float32, np.int32, np.int16):
        assert float(ref.on_device(
            steps, jnp.asarray(want.astype(widened)))) == float("inf")
    assert float(ref.on_device(steps, jnp.asarray(want[:, :, :95]))) \
        == float("inf")
    # the values moved through bfloat16 and back are not the session
    assert float(ref.lowp_on_device(steps)) > 0.5 * want.size


@pytest.mark.parametrize("seed", SEEDS)
def test_a_slab_placed_a_slab_late_is_not_correct(man, seed):
    import jax.numpy as jnp
    cell = built(man, seed)
    steps, ref = steps_of(cell), cell.reference
    plan = ref.plan(steps)
    terminal, tile = plan.terminal, cell.operand.tile
    sound = jnp.asarray(np.transpose(tile, (1, 2, 0)))
    frames = tile.copy()
    frames[2 * SLAB:3 * SLAB], frames[3 * SLAB:4 * SLAB] = (
        tile[3 * SLAB:4 * SLAB], tile[2 * SLAB:3 * SLAB])
    count = float(ref.on_device(steps, jnp.asarray(
        np.transpose(frames, (1, 2, 0)))))
    assert count > 0.99 * 2 * SLAB * 8 * 16
    moved = jnp.asarray(np.transpose(np.roll(tile, SLAB, axis=0),
                                     (1, 2, 0)))
    literal = float(ref.on_device(steps, moved))
    assert literal > 0.99 * tile.size
    assert float(terminal.displaced_on_device(
        ref, plan, sound, 0, -SLAB)) == literal
    assert float(terminal.displaced_on_device(
        ref, plan, moved, 0, SLAB)) == 0
    assert float(terminal.displaced_on_device(ref, plan, sound, 0, 96)) == 0


@pytest.mark.parametrize("seed", SEEDS)
def test_the_program_re_axes_the_session_and_keeps_the_element(
        man, seed, small_slabs):
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
    assert got.shape == (8, 16, 96) and got.dtype == np.uint16
    assert np.array_equal(np.asarray(got),
                          np.transpose(cell.operand.tile, (1, 2, 0)))
    assert float(cell.reference.on_device(steps, got)) == 0
    slabs = 96 // SLAB
    assert c1["stream_chunks"] - c0["stream_chunks"] == slabs
    assert c1["shuffle_bytes"] - c0["shuffle_bytes"] == cell.operand.nbytes
    # nothing was widened before the link: the bytes are the stored bytes
    assert c1["transfer_bytes"] - c0["transfer_bytes"] \
        == cell.operand.nbytes == 2 * 96 * 8 * 16
    assert c1["transfer_elements"] - c0["transfer_elements"] == 96 * 8 * 16
    assert c1["stream_narrow_slabs"] - c0["stream_narrow_slabs"] == slabs
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


def test_an_answer_widened_underneath_is_not_correct(
        man, tmp_path, monkeypatch, small_slabs):
    """The timed path broken the way this configuration exists to see: the
    right values, handed over as float32."""
    import jax.numpy as jnp
    fetch = man.module("fetches", "ready")
    sound = fetch.take
    monkeypatch.setattr(fetch, "take",
                        lambda handle: sound(handle).astype(jnp.float32))
    out = run.run_cell(man, CELL, 5, 0.3, False, require_tpu=False,
                       out_root=str(tmp_path))
    assert out["correct"] is False and out["failed"] > 0


# the operations of the cell's place program as the chip named them (my
# chip run, PR 59): the words' transpose, the words doubled, the unpack
# fused into the update; and the zero-fill of the array
FAKE_TRACE = {"busy_s": 0.08, "window_s": 0.1,
              "ops_s": {"copy.2": 0.01, "broadcast.9": 0.02,
                        "fusion.1": 0.03, "broadcast_in_dim.2": 0.02},
              "idle_gaps_s": {"bench.fetch": 0.02}}


def test_a_traced_run_reads_the_new_metrics(man, tmp_path, monkeypatch,
                                            small_slabs):
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
    assert got["wire_bytes_per_element"]["value"] == 2.0
    assert got["narrow_slabs_per_request"]["value"] == 96 // SLAB
    # the words doubled and the fusion that unpacks into the update; not
    # the transpose, nor the array's zero-fill
    assert got["narrow_unpack_ms.streamed"]["value"] == pytest.approx(
        0.05 / out["attempted"] * 1e3)
    assert got["compiles_in_window.streamed"]["value"] == 0
    assert got["device_idle_share.streamed"]["value"] == pytest.approx(20.0)
    # no published peaks for a CPU: the roofline share is left out
    assert "narrow_rebucket_roofline" not in got
    assert not FOUR_BYTES & set(got)


def test_the_roofline_counts_the_stored_item_size(man):
    """One read and one write of every element at two bytes each, over the
    busy time: half of what a reader that counts four would say."""
    real = manifest.Manifest(manifest.REAL)
    reader = real.module("readers", "rebucket_roofline_stored")
    steps = real.traffic("toseries16")["requests"][0]["steps"]
    shape = (20480, 512, 512)
    assert roofline.hbm_bytes(real, steps, shape, 2, 1) \
        == 2 * 20480 * 512 * 512 * 2

    class Op:
        dtype = np.dtype("uint16")

    class Cell:
        manifest, operand, chips = real, Op(), 1
        peaks = {"hbm_GBps": 819.0}
    Op.shape = shape
    ctx = {"cell": Cell(), "trace": {"busy_s": 0.2},
           "result": {"requests": [(0, 0, steps)], "slots": [0, 0]}}
    want = 100.0 * 2 * (4 * 20480 * 512 * 512 / 819e9) / 0.2
    assert reader.read(ctx) == pytest.approx(want)
    assert 0 < reader.read(ctx) < 100
    Op.dtype = np.dtype("float32")
    assert reader.read(ctx) == pytest.approx(2 * want)
    # nothing: untraced, no peaks, an operand that does not say its dtype
    assert reader.read(dict(ctx, trace=None)) is None
    Cell.peaks = None
    assert reader.read(ctx) is None
    Cell.peaks = {"hbm_GBps": 819.0}
    del Op.dtype
    assert reader.read(ctx) is None


def test_the_new_readers_say_nothing_on_a_program_without_the_counters():
    """The benchmark as this PR leaves it is laid over the parent's program
    too, which has neither counter: the metric is left out of the line, and
    nothing raises."""
    real = manifest.Manifest(manifest.REAL)

    class OldCell:
        counters0 = {"transfer_bytes": 0}
        counters1 = {"transfer_bytes": 4 << 20}

        def counter_delta(self, name):
            return self.counters1[name] - self.counters0[name]
    ctx = {"cell": OldCell(), "result": {"walls_s": [1.0, 1.0]},
           "trace": None}
    read = {}
    for name in ("wire_bytes_per_element", "narrow_slabs_per_request"):
        spec = real.metric_spec(name)
        assert spec["reader"] == "counter_ratio_known"
        reader = real.module("readers", spec["reader"])
        assert reader.read(ctx, **spec["args"]) is None
        read[name] = lambda n=name, r=reader: r.read(
            ctx, **real.metric_spec(n)["args"])
    OldCell.counters0.update(transfer_elements=0, stream_narrow_slabs=0)
    OldCell.counters1.update(transfer_elements=2 << 20,
                             stream_narrow_slabs=320)
    assert read["wire_bytes_per_element"]() == 2.0
    assert read["narrow_slabs_per_request"]() == 160
    # a window in which nothing went up: nothing, not a division by zero
    OldCell.counters1.update(transfer_bytes=0, transfer_elements=0)
    assert read["wire_bytes_per_element"]() is None


def test_the_new_metrics_resolve_through_the_real_manifest():
    real = manifest.Manifest(manifest.REAL)
    mine = {m["name"] for m in real.cell_metrics(CELL, "per_layer")}
    float32 = {m["name"] for m in real.cell_metrics(FLOAT_CELL,
                                                    "per_layer")}
    assert NEW <= mine
    for name in mine:
        real.module("readers", real.metric_spec(name)["reader"])
    assert {m["name"] for m in real.cell_metrics(CELL, "end_to_end")} == {
        "streamed_scan_GBps", "setup_s"}
    by_name = {m["name"]: m for m in real.doc["per_layer"]}
    for name in NEW:
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["moves"] == "streamed_scan_GBps"
    assert by_name["narrow_rebucket_roofline"]["layer"] == \
        by_name["rebucket_roofline"]["layer"]
    assert by_name["wire_bytes_per_element"]["layer"] == \
        by_name["upload_GBps"]["layer"]
    # the float32 cell's program and the as-loaded one name no such
    # operation: the metric reads nothing there
    spec = real.metric_spec("narrow_unpack_ms.streamed")
    reader = real.module("readers", spec["reader"])
    ctx = {"result": {"walls_s": [1.0]}, "trace": {"ops_s": {
        "copy.1": 0.3, "bitcast_dynamic-update-slice_fusion": 0.3,
        "broadcast_in_dim.2": 0.1, "convert_reduce_fusion": 0.001}}}
    assert reader.read(ctx, **spec["args"]) is None
    # what the float32 cell listed when this cell came, this cell lists
    # too, but for the readers that count four bytes a value; and the
    # wire's width is read in both
    assert SHARED <= mine and SHARED | FOUR_BYTES <= float32
    assert "wire_bytes_per_element" in float32
    assert not FOUR_BYTES & mine
    assert real.metric_spec("rebucket_roofline")["reader"] == "fold_roofline"
