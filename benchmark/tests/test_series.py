"""The ``series64-1chip`` configuration at toy size on the CPU: the seeded
series in its two spellings, the reference's exact second moments and
spectra against ``numpy.linalg.svd`` on the same data, the control one
precision lower reading over every limit, and the cell run end to end."""

import json
import os

import numpy as np
import pytest

import lattice
import manifest
import pipeline
import run
import spectral

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny")
CELL = "series64-1chip.pca"
SEEDS = [3, 2**31 + 17, 4294967291]


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(manifest.REAL,
                             roots=(TINY, os.path.dirname(HERE)))


def built(man, seed):
    cell = run.Cell(man, CELL, seed, 0.0, False, require_tpu=False)
    cell.log = lambda msg: None
    cell.open_device()
    cell.build()
    return cell


def kinds(cell):
    return {cell.traffic["requests"][k]["kind"]: steps
            for k, _, steps in pipeline.expand(cell.traffic)}


def test_tiny_keeps_what_the_real_files_say(man):
    real = manifest.Manifest(manifest.REAL)
    tiny, full = man.config("series64-1chip"), real.config("series64-1chip")
    for key in ("series", "bits", "dtype", "key_axes", "guarantees",
                "source"):
        assert tiny[key] == full[key]
    assert tiny["record_shape"][1] == full["record_shape"][1] == 64
    t, r = man.traffic("pca"), real.traffic("pca")
    assert [(k["kind"], k["count"], k["fetch"], k["limit"],
             sorted(k["steps"][0]["limits"])) for k in t["requests"]] == [
        (k["kind"], k["count"], k["fetch"], k["limit"],
         sorted(k["steps"][0]["limits"])) for k in r["requests"]]
    # the real sizes are the ones ISSUE 26 states
    shape = (full["planes"],) + tuple(full["record_shape"])
    assert shape == (40, 1048576, 64)
    assert spectral.block_rows(shape[1:], 4, "150", 0) == 524288


@pytest.mark.parametrize("seed", SEEDS)
def test_the_two_spellings_of_the_series_agree(man, seed):
    cell = built(man, seed)
    series = man.module("operands", "series")
    planes, voxels, times = cell.operand.shape
    host = series.host_rows(np.arange(planes * voxels), times,
                            cell.config["series"], seed)
    held = np.asarray(cell.operand.data)
    assert np.array_equal(held, host.reshape(held.shape))
    assert np.abs(held).max() < 1 << (cell.config["bits"] - 1)
    assert np.array_equal(held, np.round(held))
    assert cell.reference.data_mismatches(np.random.default_rng(seed)) == 0


def test_the_planted_spectrum_is_the_one_the_closed_form_promises(man):
    series = man.module("operands", "series")
    spec = man.config("series64-1chip")["series"]
    x = series.host_rows(np.arange(1 << 16), 64, spec, 11).astype(np.float64)
    x -= x.mean(axis=0)
    lam = np.linalg.eigvalsh(x.T @ x / len(x))[::-1]
    want = [64 * a * (a + 1) / 3 for a in spec["amplitudes"]]
    assert np.allclose(lam[:8], want, rtol=0.03)
    # the noise floor is far under the weakest planted component
    assert lam[8] < lam[7] / 20
    assert lam[8] < 64 * 4 ** spec["noise_bits"] / 12


@pytest.mark.parametrize("seed", SEEDS)
def test_exact_moments_and_spectra_against_numpy(man, seed):
    cell = built(man, seed)
    ref, steps = cell.reference, kinds(cell)
    x = np.asarray(cell.operand.data, np.float64)
    planes, voxels, times = x.shape
    rows = ref.plan(steps["chunk_svd"]).terminal.rows
    assert voxels // rows > 1                       # really chunked
    gram, total = ref.moments(rows)
    blocks = x.reshape(planes, voxels // rows, rows, times)
    assert np.array_equal(gram, np.einsum("pgri,pgrj->pgij", blocks,
                                          blocks).astype(np.int64))
    assert np.array_equal(total, blocks.sum(axis=2).astype(np.int64))
    want = ref.expected(steps["chunk_svd"])
    assert np.allclose(want, np.linalg.svd(blocks, compute_uv=False),
                       rtol=1e-9, atol=1e-6)
    # the moments of the data rounded to bfloat16 are exact too
    low = spectral.bf16(x).reshape(blocks.shape)
    assert np.array_equal(ref.moments(rows, lowp=True)[0], np.einsum(
        "pgri,pgrj->pgij", low, low).astype(np.int64))
    # whole-data PCA: singular values, span, rebuilt rows
    pca = ref.expected(steps["pca_k8"])
    flat = x.reshape(-1, times)
    centred = flat - flat.mean(axis=0)
    _, s, vt = np.linalg.svd(centred, full_matrices=False)
    assert np.allclose(pca["singular_values"], s[:8], rtol=1e-9)
    assert np.allclose(pca["mean"], flat.mean(axis=0), atol=1e-9)
    assert np.allclose(pca["components"] @ pca["components"].T,
                       vt[:8].T @ vt[:8], atol=1e-9)
    step = steps["pca_k8"][0]
    idx = np.concatenate([p * voxels + np.arange(v, v + step["patch_rows"])
                          for p, v in step["patches"]])
    assert np.allclose(pca["rows"], centred[idx] @ vt[:8].T @ vt[:8],
                       atol=1e-6)


def _answer(cell, kind, steps):
    """One answer of the timed path as the check holds it."""
    spec = next(k for k in cell.traffic["requests"] if k["kind"] == kind)
    fetch = cell.manifest.module("fetches", spec["fetch"])
    return spec, fetch.take(pipeline.compile_call(cell.manifest, steps)(
        cell.operand.operand()))


@pytest.mark.parametrize("seed", SEEDS)
def test_the_program_reads_under_every_limit_and_bfloat16_over(man, seed):
    cell = built(man, seed)
    ref = cell.reference
    for kind, steps in kinds(cell).items():
        p = ref.plan(steps)
        limits = steps[0]["limits"]
        want = ref.expected(steps)
        held_in_bf16 = p.terminal.parts(p.terminal.resident_bf16(ref, p),
                                        want)
        spec, got = _answer(cell, kind, steps)
        sound = p.terminal.parts(got, want)
        assert set(limits) <= set(held_in_bf16), kind
        for name, limit in limits.items():
            assert held_in_bf16[name] > limit, (kind, name,
                                                held_in_bf16[name])
            assert sound[name] < limit, (kind, name, sound[name])
        assert ref.number(steps, got, want) <= spec["limit"]


@pytest.mark.parametrize("seed", SEEDS)
def test_one_bfloat16_pass_makes_the_cell_not_correct(man, seed):
    # the control is the answer as ONE bfloat16 pass of the matrix unit
    # would give it.  A spectrum does not catch it (the rounding averages
    # out of a Gram matrix); the rebuilt rows of pca_k8 do, and one limit
    # of the cell is what the contract asks for
    cell = built(man, seed)
    ref = cell.reference
    over = {}
    for kind, steps in kinds(cell).items():
        p = ref.plan(steps)
        want = ref.expected(steps)
        control = p.terminal.parts(ref.lowp(steps), want)
        over[kind] = [name for name, limit in steps[0]["limits"].items()
                      if control[name] > limit]
    assert "scores" in over["pca_k8"], over
    steps = kinds(cell)["pca_k8"]
    assert ref.number(steps, ref.lowp(steps), ref.expected(steps)) > 1


def test_no_row_of_the_scores_crosses_to_the_host_in_the_fetch(man):
    # fetch pca_parts forces the scores and lets them go; what it keeps
    # for the check is a small DEVICE array, read when the check asks
    import jax
    cell = built(man, SEEDS[0])
    steps = kinds(cell)["pca_k8"]
    spec, got = _answer(cell, "pca_k8", steps)
    assert set(got) == {"components", "singular_values", "mean", "rows"}
    assert isinstance(got["rows"], jax.Array)
    assert got["rows"].shape == (3 * steps[0]["patch_rows"], steps[0]["k"])
    assert all(isinstance(got[name], np.ndarray)
               for name in ("components", "singular_values", "mean"))
    want = cell.reference.expected(steps)
    assert 0 < cell.reference.number(steps, got, want) <= spec["limit"]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_cell_runs_end_to_end(man, seed, tmp_path):
    out = run.run_cell(man, CELL, seed, 0.3, False, require_tpu=False,
                       out_root=str(tmp_path))
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 2
    assert set(out["metrics"]) == {"scan_GBps", "setup_s"}


FAKE_TRACE = {"window_s": 1.0, "busy_s": 0.8, "busy_s_per_chip": [0.8],
              "ops_s": {"fusion.20": 0.3, "convolution_bitcast_fusion": 0.3,
                        "while": 0.15, "custom-call.2": 0.05,
                        "fusion.50": 0.04},
              "idle_gaps_s": {"bench.fetch": 0.2}}


def test_a_traced_run_reads_the_new_per_layer_metrics(man, tmp_path,
                                                      monkeypatch):
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
    for name in ("pca_launch_us", "pca_fetch_us", "chunk_map_us",
                 "fetch_force_us.scan", "fetch_wait_ms.scan",
                 "fetch_copy_us.scan", "program_ms.scan",
                 "peak_hbm_GB.scan", "runtime_start_s"):
        assert got[name]["value"] >= 0, name
    assert got["compiles_in_window.scan"]["value"] == 0
    assert got["device_idle_share.scan"]["value"] == pytest.approx(20.0)
    # the Jacobi while and the eigh custom call, per request
    assert got["eigh_ms.scan"]["value"] == pytest.approx(200.0 / n)
    # one view a pca_k8 request, none a chunk_svd request
    assert abs(got["resplit_views_per_request"]["value"] - 0.5) <= 1.0 / n
    # no published peaks for a CPU: the share is left out, not made up
    assert "gram_roofline" not in got


def test_gram_roofline_is_the_least_time_over_all_but_the_eigensolver(man):
    class FakeCell:
        manifest = man
        chips = 1
        peaks = {"hbm_GBps": 819.0, "bf16_TFLOPs": 197.0}

        class operand:
            shape = (40, 1048576, 64)
    real = manifest.Manifest(manifest.REAL).traffic("pca")
    requests = [(k, 0, kind["steps"])
                for k, kind in enumerate(real["requests"])]
    ctx = {"cell": FakeCell, "trace": FAKE_TRACE,
           "result": {"requests": requests, "slots": [0, 1, 0, 1]}}
    reader = man.module("readers", "gram_roofline")
    spec = man.metric_spec("gram_roofline")
    n = 40 * 1048576
    least = 2 * (n * 64 * 4 / 819e9) + 2 * ((2 * n * 64 + n * 8) * 4 / 819e9)
    assert reader.read(ctx, **spec["args"]) == pytest.approx(
        100 * least / (0.8 - 0.15 - 0.05))
    assert reader.read(dict(ctx, trace=None), **spec["args"]) is None


def test_the_roofline_counts_one_pass_of_bytes_and_operations(man):
    import roofline
    steps = {k["kind"]: k["steps"]
             for k in manifest.Manifest(manifest.REAL).traffic("pca")[
                 "requests"]}
    shape = (40, 1048576, 64)
    t = roofline.Traffic(shape)
    man.module("steps", "chunk_svd").traffic(steps["chunk_svd"][0], t)
    n = 40 * 1048576
    assert (t.read, t.written, t.flops) == (n * 64, 0, 2 * n * 64 * 64)
    t = roofline.Traffic(shape)
    man.module("steps", "pca").traffic(steps["pca_k8"][0], t)
    assert (t.read, t.written) == (2 * n * 64, n * 8)
    assert t.flops == 2 * n * 64 * 64 + 2 * n * 64 * 8


def test_an_older_program_is_told_at_once(man, monkeypatch):
    from bolt_tpu import engine
    counters = engine.counters
    monkeypatch.setattr(
        engine, "counters",
        lambda: {k: v for k, v in counters().items()
                 if k != "resplit_views"})
    cell = run.Cell(man, CELL, 1, 0.0, False, require_tpu=False)
    cell.log = lambda msg: None
    cell.open_device()
    with pytest.raises(SystemExit, match="re-split is a view"):
        cell.build()
