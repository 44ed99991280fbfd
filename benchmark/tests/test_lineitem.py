"""The ``lineitem-1chip`` configuration at toy size on the CPU: the seeded
table in its two spellings, dbgen's shares, the reference's exact Q6 and
Q1 against a row-by-row Python loop, the control one precision lower
reading over every limit, and both cells this configuration came with run
end to end (``stack4d-1chip.filter`` is BASELINE config 4 on the stack)."""

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
CELL = "lineitem-1chip.q1q6"
STACK_CELL = "stack4d-1chip.filter"
SEEDS = [3, 2**31 + 17, 4294967291]
DATE, QTY, PRICE, DISC, TAX, FLAG, STATUS = range(7)


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(manifest.REAL,
                             roots=(TINY, os.path.dirname(HERE)))


def built(man, seed, cell=CELL):
    cell = run.Cell(man, cell, seed, 0.0, False, require_tpu=False)
    cell.log = lambda msg: None
    cell.open_device()
    cell.build()
    return cell


def kinds(cell):
    return {cell.traffic["requests"][k]["kind"]: steps
            for k, _, steps in pipeline.expand(cell.traffic)}


def test_tiny_keeps_what_the_real_files_say(man):
    real = manifest.Manifest(manifest.REAL)
    tiny, full = man.config("lineitem-1chip"), real.config("lineitem-1chip")
    for key in ("lineitem", "columns", "record_shape", "dtype", "key_axes",
                "guarantees", "source", "reduced", "sf", "published_rows",
                "chips_sharing"):
        assert tiny[key] == full[key]
    # the real sizes are the ones ISSUE 30 states: this chip's half of a
    # two-chip row-range split of SF 100
    assert full["rows"] == 300018951 == full["published_rows"] // 2
    assert full["reduced"] == ["rows"] and full["architecture"] is None
    entry = [c for c in real.doc["configs"]
             if c["name"] == "lineitem-1chip"][0]
    assert entry["source"] == full["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == full["reduced"]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_two_spellings_of_the_table_agree(man, seed):
    cell = built(man, seed)
    op = man.module("operands", "lineitem")
    rows = cell.operand.shape[0]
    host = op.host_rows(np.arange(rows), cell.config["lineitem"], seed)
    held = np.asarray(cell.operand.data)
    assert held.shape == (rows, 7) and np.array_equal(held, host)
    assert np.array_equal(held, np.round(held)) and held.min() >= 0
    assert held.max() < 1 << 24                   # exact in float32
    assert cell.reference.data_mismatches(np.random.default_rng(seed)) == 0


def test_the_table_is_made_in_blocks_that_need_not_tile_it(man,
                                                           monkeypatch):
    import jax
    import jax.numpy as jnp
    import lattice
    op = man.module("operands", "lineitem")
    spec = man.config("lineitem-1chip")["lineitem"]
    monkeypatch.setattr(op, "GENERATE", 4096)
    rows = 3 * 4096 + 1234
    _, b = lattice.constants(9)
    held = np.asarray(jax.jit(lambda b: op.device_values(rows, spec, b))(
        jnp.uint32(b)))
    assert np.array_equal(held, op.host_rows(np.arange(rows), spec, 9))


@pytest.mark.parametrize("seed", SEEDS)
def test_dbgen_shares_and_selectivities(man, seed):
    """ISSUE 30's figures, within half a point: Q1 keeps 98.6 % of the rows
    in four occupied groups of six at 24.6 / 0.65 / 48.7 / 24.6 % of the
    table (A/F, N/F, N/O, R/F: SF 1's validation answer has 1,478,493 /
    38,854 / 2,920,374 / 1,478,870 of 6,001,215 rows; of the 5,916,591
    selected they are 25.0 / 0.66 / 49.4 / 25.0 %), Q6 keeps 1.9 %."""
    op = man.module("operands", "lineitem")
    x = op.host_rows(np.arange(1 << 20), man.config(
        "lineitem-1chip")["lineitem"], seed).astype(np.int64)
    q1 = x[:, DATE] <= 2436
    assert abs(100 * q1.mean() - 98.6) < 0.5
    gid = (3 * x[:, STATUS] + x[:, FLAG])[q1]
    share = 100 * np.bincount(gid, minlength=6) / len(x)
    assert np.all(np.abs(share - [24.6, 0.65, 24.6, 0, 48.7, 0]) < 0.5)
    selected = 100 * np.bincount(gid, minlength=6) / q1.sum()
    assert np.all(np.abs(selected - [24.99, 0.657, 24.995, 0, 49.36, 0])
                  < 0.2)
    assert share[3] == share[5] == 0              # A/O and R/O never occur
    q6 = ((x[:, DATE] >= 731) & (x[:, DATE] < 1096) & (x[:, DISC] >= 5)
          & (x[:, DISC] <= 7) & (x[:, QTY] < 24))
    assert abs(100 * q6.mean() - 1.9) < 0.5
    # the marginals
    assert (x[:, QTY].min(), x[:, QTY].max()) == (1, 50)
    assert (x[:, DISC].min(), x[:, DISC].max()) == (0, 10)
    assert (x[:, TAX].min(), x[:, TAX].max()) == (0, 8)
    assert 1 <= x[:, DATE].min() <= 3 and 2520 <= x[:, DATE].max() <= 2526
    retail = x[:, PRICE] // x[:, QTY]
    assert np.all(x[:, PRICE] % x[:, QTY] == 0)
    assert 90000 <= retail.min() < 90200 and 209700 < retail.max() <= 209899


def loop_answers(rows):
    """Q6 and Q1 row by row in Python integers."""
    q6, sums, counts = 0, [[0] * 6 for _ in range(6)], [0] * 6
    for r in rows:
        d, q, p, disc, tax, flag, status = (int(v) for v in r)
        if 731 <= d < 1096 and 5 <= disc <= 7 and q < 24:
            q6 += p * disc
        if d <= 2436:
            g = 3 * status + flag
            dp = p * (100 - disc)
            for t, v in enumerate((q, p, dp, dp * (100 + tax), disc, 1)):
                sums[g][t] += v
            counts[g] += 1
    return q6, sums, counts


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_is_exact_against_a_python_loop(man, seed,
                                                      monkeypatch):
    cell = built(man, seed)
    op = man.module("operands", "lineitem")
    # a few hundred rows, in blocks that do not tile them
    rows = 611
    ref = op.LineitemReference(man, cell.operand.data[:rows], (rows, 7),
                               seed, cell.config["lineitem"])
    monkeypatch.setattr(op, "BLOCK", 256)
    steps = kinds(cell)
    q6, sums, counts = loop_answers(np.asarray(ref.data))
    assert ref.expected(steps["q6"]) == float(q6)
    got = ref.expected(steps["q1"])
    assert got["counts"].tolist() == counts
    assert got["sums"].tolist() == [[float(v) for v in g] for g in sums]
    assert sum(counts) > 500 and q6 > 0
    # the whole toy table: the exact answer is the int64 answer
    monkeypatch.undo()
    x = np.asarray(cell.operand.data).astype(np.int64)
    keep = x[x[:, DATE] <= 2436]
    want = cell.reference.expected(steps["q1"])
    assert want["counts"].sum() == len(keep)
    charge = keep[:, PRICE] * (100 - keep[:, DISC]) * (100 + keep[:, TAX])
    assert want["sums"][:, 3].sum() == float(charge.sum())


@pytest.mark.parametrize("seed", SEEDS)
def test_one_precision_lower_reads_over_every_limit(man, seed):
    cell = built(man, seed)
    ref, steps = cell.reference, kinds(cell)
    for kind in ("q6", "q1"):
        p = ref.plan(steps[kind])
        want = ref.expected(steps[kind])
        low = p.terminal.parts(ref.lowp(steps[kind]), want)
        limits = steps[kind][0]["limits"]
        assert set(limits) <= set(low)
        for name, limit in limits.items():
            assert low[name] > 10 * limit, (kind, name, low[name])
        assert ref.number(steps[kind], ref.lowp(steps[kind]), want) > 1
    # and the exact answer held in float32 reads under them
    q1 = ref.expected(steps["q1"])
    sound = {"sums": q1["sums"].astype(np.float32),
             "counts": q1["counts"].astype(np.int32)}
    assert ref.number(steps["q1"], sound, q1) < 0.1
    wrong = dict(sound, counts=sound["counts"] + np.eye(6, dtype=np.int32)[4])
    assert ref.number(steps["q1"], wrong, q1) == float("inf")


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell", [CELL, STACK_CELL])
def test_both_cells_run_end_to_end_and_are_correct(man, cell, seed,
                                                   tmp_path):
    out = run.run_cell(man, cell, seed, 0.3, False, require_tpu=False,
                       out_root=str(tmp_path))
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"scan_GBps", "setup_s"}
    json.dumps(out)


def test_the_timed_path_broken_underneath_is_not_correct(man, tmp_path,
                                                         monkeypatch):
    step = man.module("steps", "tpch_q6")
    sound = step.bind

    def broken(s, m):                     # one day too many
        return sound(dict(s, shipdate=[731, 1097]), m)
    monkeypatch.setattr(step, "bind", broken)
    out = run.run_cell(man, CELL, 5, 0.3, False, require_tpu=False,
                       out_root=str(tmp_path))
    assert out["correct"] is False and out["failed"] > 0


def test_each_request_is_one_launch_and_no_buffer(man):
    from bolt_tpu import engine
    for name in (CELL, STACK_CELL):
        cell = built(man, 7, name)
        for k, _, steps in pipeline.expand(cell.traffic):
            fetch = man.module("fetches",
                               cell.traffic["requests"][k]["fetch"])
            call = pipeline.compile_call(man, steps)
            fetch.take(call(cell.operand.operand()))        # compiled
            c0 = engine.counters()
            fetch.take(call(cell.operand.operand()))
            c1 = engine.counters()
            assert [c1[n] - c0[n] for n in (
                "dispatches", "filters_fused", "filter_compactions",
                "aot_compiles")] == [1, 1, 0, 0], (name, steps)


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_the_stack_cell_keeps_some_records_and_drops_some(man, seed):
    cell = built(man, seed, STACK_CELL)
    steps = kinds(cell)["kept_sum"]
    p = cell.reference.plan(steps)
    kept = p.terminal.kept(cell.reference, p)
    x = np.asarray(cell.operand.data) + 1
    assert kept == int((x[:, :2, :2, :2].mean(axis=(1, 2, 3)) > 0.5).sum())
    assert 0 < kept < cell.operand.shape[0]
    want = cell.reference.expected(steps)
    assert np.array_equal(want, x[x[:, :2, :2, :2].mean(axis=(1, 2, 3))
                                  > 0.5].sum(axis=0))
    low = cell.reference.number(steps, cell.reference.lowp(steps), want)
    assert low > cell.traffic["requests"][0]["limit"]


def test_the_score_keeps_about_half_of_the_real_stack():
    """At the real geometry (3,200 records of 200 x 64 x 64) the corner's
    mean is above 0.5 for 45-55 % of the records on all but a few seeds in
    a thousand: the lattice is an arithmetic progression from record to
    record, so a seed whose step lies beside a fraction of small
    denominator selects thirds or fifths (35-73 % seen in 3,000 seeds,
    0.27 % of them outside 45-55 %).  Neither the answer's correctness nor
    the pass's time depends on the share."""
    import lattice
    real = manifest.Manifest(manifest.REAL)
    config = real.config("stack4d-1chip")
    step = real.traffic("filter")["requests"][0]["steps"][1]
    assert step["corner"] == [2, 2, 2] and step["above"] == 0.5
    dims = config["record_shape"]
    rec = int(np.prod(dims))
    offs = np.array([a * dims[1] * dims[2] + b * dims[2] + c
                     for a in range(2) for b in range(2) for c in range(2)],
                    dtype=np.uint64)
    idx = (np.arange(config["records"], dtype=np.uint64)[:, None]
           * np.uint64(rec) + offs[None, :])
    inside = 0
    seeds = [int(v) for v in np.random.default_rng(30).integers(
        0, 2**32, 400)]
    for seed in seeds:
        a, b = lattice.constants(seed)
        x = ((idx * np.uint64(a) + np.uint64(b)) & np.uint64(0xFFFFFFFF)) \
            >> np.uint64(32 - config["bits"])
        v = x.astype(np.int64) - (1 << (config["bits"] - 1)) + 1
        inside += 0.45 <= (v.mean(axis=1) > 0.5).mean() <= 0.55
    assert inside >= 0.98 * len(seeds)


def test_the_roofline_counts_what_neither_query_can_avoid(man):
    real = manifest.Manifest(manifest.REAL)
    t = real.traffic("q1q6")
    shape = (300018951, 7)
    need = {k["kind"]: roofline.hbm_bytes(real, k["steps"], shape, 4, 1)
            for k in t["requests"]}
    assert need == {"q6": 4 * 4 * 300018951, "q1": 7 * 4 * 300018951}
    # under what the device holds (rows padded to eight sublanes), so the
    # share cannot pass 100 %
    assert sum(need.values()) < 2 * 8 * 4 * 300018951
    reader = real.module("readers", "fold_roofline")
    cell = type("C", (), {"manifest": real, "chips": 1,
                          "peaks": {"hbm_GBps": 819.0},
                          "operand": type("O", (), {"shape": shape})})
    requests = pipeline.expand(t)
    ctx = {"cell": cell, "trace": {"busy_s": 0.040},
           "result": {"requests": requests, "slots": [0, 1]}}
    assert reader.read(ctx) == pytest.approx(
        100 * (11 * 4 * 300018951 / 819e9) / 0.040)
    assert reader.read(dict(ctx, trace=None)) is None
    stack = real.traffic("filter")["requests"][0]["steps"]
    assert roofline.hbm_bytes(real, stack, (3200, 200, 64, 64), 4, 1) \
        == 4 * 3201 * 200 * 64 * 64


def test_the_new_metrics_resolve_through_the_real_manifest():
    real = manifest.Manifest(manifest.REAL)
    names = {m["name"] for m in real.cell_metrics(CELL, "per_layer")}
    assert {"fold_roofline", "filter_stat_us", "group_fold_us",
            "filters_fused_per_request", "filter_compactions_per_request",
            "program_ms.scan", "device_idle_share.scan", "peak_hbm_GB.scan",
            "compiles_in_window.scan", "fetch_force_us.scan",
            "fetch_wait_ms.scan", "fetch_copy_us.scan",
            "runtime_start_s"} == names
    stack = {m["name"] for m in real.cell_metrics(STACK_CELL, "per_layer")}
    assert stack == (names - {"fold_roofline", "group_fold_us"}) | {
        "hbm_roofline_share.scan"}
    for name in names | stack:
        real.module("readers", real.metric_spec(name)["reader"])
    assert {m["name"] for m in real.cell_metrics(CELL, "end_to_end")} == {
        "scan_GBps", "setup_s"}
