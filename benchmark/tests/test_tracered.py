"""The trace reduction, on a hand-made trace whose answers are worked out
here and on small traces recorded on the chip (tools/record_trace.py), which
are held to a slow reading of the same definitions."""

import json
import os

import numpy as np
import pytest

import tracered

HERE = os.path.dirname(os.path.abspath(__file__))


def plane(name, **lines):
    return {"name": name, "lines": [{"name": k.replace("_", " "), "events": v}
                                    for k, v in lines.items()]}


def ms(name, start, dur):
    return [name, int(start * 1e6), int(dur * 1e6)]


def hand_made(chips=1):
    """A 100 ms window.  Chip 0 runs a over [10, 30], b over [20, 40] (they
    overlap: the union is 30 ms, the sums 40), c over [60, 70] and one
    operation outside the window.  The caller's thread holds bench.call
    over [0, 15] and bench.fetch over [15, 100]; a loader thread holds
    bench.loader over [45, 55], inside the fetch."""
    host = plane("/host:CPU",
                 python3=[ms("bench.window", 0, 100), ms("bench.call", 0, 15),
                          ms("bench.fetch", 15, 85), ms("PjitFunction", 1, 2)],
                 uploader=[ms("bench.loader", 45, 10)])
    ops = [ms("%a.1 = f32[8]{0} fusion(f32[8]{0} %p)", 10, 20),
           ms("%b = f32[8]{0} copy(f32[8]{0} %a.1)", 20, 20),
           ms("%c = f32[] reduce(f32[8]{0} %b)", 60, 10),
           ms("%c = f32[] reduce(f32[8]{0} %b)", 120, 10)]
    planes = [host, plane("/device:TPU:0", XLA_Ops=ops,
                          XLA_Modules=[ms("jit_f", 10, 60)])]
    if chips == 4:
        for i in (1, 2, 3):       # the other chips run a alone
            planes.append(plane("/device:TPU:%d" % i, XLA_Ops=ops[:1]))
    return {"planes": planes}


def test_hand_made_one_chip():
    r = tracered.reduce_trace(hand_made(), 1)
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.040)        # union, not the sum
    assert r["ops_s"] == pytest.approx({"a.1": 0.020, "b": 0.020, "c": 0.010})
    # gaps of chip 0: [0, 10] is the call's; [40, 60] is the fetch's but
    # for the loader's [45, 55]; [70, 100] is the fetch's
    assert r["idle_gaps_s"] == pytest.approx({
        "bench.call": 0.010, "bench.loader": 0.010,
        "bench.fetch": 0.010 + 0.030, tracered.NO_SPAN: 0.0})
    assert sum(r["idle_gaps_s"].values()) == pytest.approx(
        r["window_s"] - r["busy_s_per_chip"][0])


def test_hand_made_four_chips_average_the_planes():
    r = tracered.reduce_trace(hand_made(4), 4)
    assert r["busy_s_per_chip"] == pytest.approx([0.040, 0.020, 0.020, 0.020])
    assert r["busy_s"] == pytest.approx(0.025)
    assert r["ops_s"]["a.1"] == pytest.approx(0.020)        # ran on all four
    assert r["ops_s"]["c"] == pytest.approx(0.010 / 4)      # on one of four


def test_too_few_device_planes_or_no_window_is_an_error():
    with pytest.raises(ValueError, match="device planes"):
        tracered.reduce_trace(hand_made(1), 4)
    t = hand_made()
    t["planes"][0]["lines"][0]["events"].pop(0)
    with pytest.raises(ValueError, match="bench.window"):
        tracered.reduce_trace(t, 1)


def test_a_gap_under_no_span():
    t = hand_made()
    t["planes"][0]["lines"][0]["events"][2] = ms("bench.fetch", 15, 50)
    gaps = tracered.reduce_trace(t, 1)["idle_gaps_s"]
    assert gaps[tracered.NO_SPAN] == pytest.approx(0.030)   # [70, 100]
    assert gaps["bench.fetch"] == pytest.approx(0.010)      # [40,45]+[55,60]


def test_the_checks_own_time_is_taken_out_of_everything():
    """bench.check over [25, 65]: of a [10, 30] 15 ms stay, of b [20, 40]
    5, of c [60, 70] 5; a fourth operation, the check's own, lies wholly
    inside and vanishes.  The window is 60 ms, the busy union [10, 25] +
    [65, 70] = 20 ms, the gaps [0, 10] and [70, 100]."""
    t = hand_made()
    t["planes"][0]["lines"][0]["events"].append(ms("bench.check", 25, 40))
    t["planes"][1]["lines"][0]["events"].append(
        ms("%convert_reduce_fusion = f32[] fusion(f32[8]{0} %x)", 45, 5))
    r = tracered.reduce_trace(t, 1)
    assert r["window_s"] == pytest.approx(0.060)
    assert r["busy_s"] == pytest.approx(0.020)
    assert r["ops_s"] == pytest.approx({"a.1": 0.015, "b": 0.005, "c": 0.005})
    assert r["idle_gaps_s"] == pytest.approx({
        "bench.call": 0.010, "bench.loader": 0.0, "bench.fetch": 0.030,
        tracered.NO_SPAN: 0.0})
    assert sum(r["idle_gaps_s"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_the_hosts_own_part_of_a_request_leaves_out_the_device_wait():
    import manifest
    man = manifest.Manifest(manifest.REAL)
    spec = man.metric_spec("host_ms_per_request.latency")
    reader = man.module("readers", spec["reader"])
    ctx = {"trace": tracered.reduce_trace(hand_made(), 1),
           "result": {"walls_s": [0.05, 0.05]}}
    # spans are open for all 100 ms; the device idles under call and fetch
    # for 10 + 40 ms (the loader's 10 are not theirs): 25 ms a request
    assert reader.read(ctx, **spec["args"]) == pytest.approx(25.0)
    assert reader.read(dict(ctx, trace=None), **spec["args"]) is None


def test_union_and_complement():
    iv = np.array([[5.0, 6.0], [0.0, 2.0], [1.0, 3.0], [3.0, 4.0], [5.5, 5.7]])
    merged = tracered.union(iv)
    assert merged.tolist() == [[0.0, 4.0], [5.0, 6.0]]
    assert tracered.total(merged) == 5.0
    assert tracered.complement(merged, 0.0, 10.0).tolist() == [
        [4.0, 5.0], [6.0, 10.0]]
    assert tracered.clip(merged, 1.0, 5.5).tolist() == [[1.0, 4.0], [5.0, 5.5]]
    assert tracered.union(np.zeros((0, 2))).shape == (0, 2)


def test_op_name_and_top():
    assert tracered.op_name(
        "%copy.2 = f32[16,200]{1,0:T(8,128)} copy(f32[16,200]{0,1} %slice.1)"
    ) == "copy.2"
    assert tracered.op_name("all-to-all.1") == "all-to-all.1"
    table = {"op%d" % i: float(i) for i in range(14)}
    rows = tracered.top(table)
    assert len(rows) == 10 and rows[0] == ["op13", 13.0]


def slow_reading(trace, chips):
    """The definitions again, one nanosecond-free step at a time: sample the
    window on a fine grid and count."""
    host = [ev for p in trace["planes"] if p["name"].startswith("/host:CPU")
            for line in p["lines"] for ev in line["events"]]
    (_, w0, wd), = [ev for ev in host if ev[0] == "bench.window"]
    grid = np.linspace(w0, w0 + wd, 200001)[:-1] + wd / 400000.0
    step = wd / 200000.0 * 1e-9
    busy = []
    for i in range(chips):
        (ops,) = [line["events"] for p in trace["planes"]
                  if p["name"] == "/device:TPU:%d" % i
                  for line in p["lines"] if line["name"] == "XLA Ops"]
        on = np.zeros(len(grid), bool)
        for _, s, d in ops:
            on |= (grid >= s) & (grid < s + d)
        busy.append(on)
    gaps = {}
    idle = ~busy[0]
    latest = np.full(len(grid), -np.inf)
    owner = np.full(len(grid), -1)
    names = sorted({ev[0] for ev in host if ev[0].startswith("bench.")}
                   - {"bench.window"})
    for j, name in enumerate(names):
        for _, s, d in [ev for ev in host if ev[0] == name]:
            inside = (grid >= s) & (grid < s + d) & (s > latest)
            latest[inside] = s
            owner[inside] = j
    for j, name in enumerate(names):
        gaps[name] = float((idle & (owner == j)).sum() * step)
    gaps[tracered.NO_SPAN] = float((idle & (owner == -1)).sum() * step)
    return [float(b.sum() * step) for b in busy], gaps


@pytest.mark.parametrize("name, chips", [("reduce_1chip", 1),
                                         ("swap_4chip", 4)])
def test_recorded_trace(name, chips):
    with open(os.path.join(HERE, "traces", name + ".json")) as fh:
        trace = json.load(fh)
    r = tracered.reduce_trace(trace, chips)
    busy, gaps = slow_reading(trace, chips)
    tol = r["window_s"] * 2e-4
    assert r["busy_s_per_chip"] == pytest.approx(busy, abs=tol)
    assert r["busy_s"] == pytest.approx(sum(busy) / chips, abs=tol)
    assert 0 < r["busy_s"] < r["window_s"]
    for key, seconds in gaps.items():
        assert r["idle_gaps_s"][key] == pytest.approx(seconds, abs=tol), key
    assert sum(r["idle_gaps_s"].values()) == pytest.approx(
        r["window_s"] - r["busy_s_per_chip"][0], abs=1e-9)
    # nothing nests in these programs, so per-op sums add up to busy
    assert sum(r["ops_s"].values()) == pytest.approx(r["busy_s"], rel=1e-6)
    assert all(" = " not in op for op in r["ops_s"])
