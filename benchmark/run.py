#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

A new process: fails without a TPU or on a device that ``peaks.json`` does
not know, makes its data from ``--seed``, warms every shape, measures for
``--seconds``, checks the answers of the window against the plain reference,
and prints one JSON object as the last line of its standard output.
Everything above that line is a log.  See README.md for the layout and for
how a later PR adds a cell.
"""

import os
import sys
import time

_T0 = time.time()

import argparse
import json
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import arith
import manifest as manifest_mod
import pipeline
import tracered


def log(msg):
    print(msg, flush=True)


class Cell:
    """What a driver, the check and the readers share for one run."""

    def __init__(self, man, name, seed, seconds, trace, require_tpu=True,
                 out_root=ROOT):
        self.manifest = man
        self.entry = man.cell(name)
        self.name, self.seed, self.seconds = name, int(seed), float(seconds)
        self.config = man.config(self.entry["config"])
        self.traffic = man.traffic(self.entry["traffic"])
        self.chips = int(self.entry["chips"])
        self.trace_dir = (os.path.join(out_root, ".bench_trace", name)
                          if trace else None)
        self.log = log
        self.require_tpu = require_tpu
        self.trace = None
        self.setup_s = None

    # -- set-up --------------------------------------------------------

    def open_device(self):
        import jax
        t0 = time.time()
        devices = jax.devices()
        # the runtime's own start: 6.6-14 s from run to run on one v5e host
        # and not the benchmark's or the program's work.  Left out of
        # setup_s, which could not hold its bound with it (medians of six
        # runs moved by 27 %), and reported as runtime_start_s
        self.reach_s = time.time() - t0
        dev = devices[0]
        self.device = {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(devices)}
        log("device: %s" % json.dumps(self.device))
        if self.require_tpu and dev.platform != "tpu":
            raise SystemExit("benchmark/run.py needs a TPU; JAX found "
                             "platform %r" % dev.platform)
        if len(devices) < self.chips:
            raise SystemExit("cell %s needs %d chips; JAX found %d"
                             % (self.name, self.chips, len(devices)))
        self.peaks = (manifest_mod.peaks(dev.device_kind, self.manifest.roots)
                      if dev.platform == "tpu" else None)
        self.devices = devices[:self.chips]
        self.mesh = pipeline.mesh_of(self.chips)

    def build(self):
        from bolt_tpu import engine
        self.engine = engine
        log("compile cache: %s" % engine.persistent_cache())
        spec = self.traffic["operand"]
        self.operand = self.manifest.module("operands", spec["name"]).make(
            spec, self.config, self.mesh, self.seed)
        self.reference = self.operand.reference(self.manifest)

    # -- the window's edges, called by the driver ----------------------

    def begin_window(self):
        import jax
        self.setup_s = time.time() - _T0 - self.reach_s
        if self.trace_dir:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self.counters0 = self.engine.counters()

    def end_window(self):
        import jax
        self.counters1 = self.engine.counters()
        if self.trace_dir:
            jax.profiler.stop_trace()
        self.peak_bytes = self.memory_peak()

    def memory_peak(self):
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
        if self.require_tpu and len(peaks) != len(self.devices):
            raise SystemExit("the device reports no peak_bytes_in_use")
        return max(peaks) if peaks else 0

    def counter_delta(self, name):
        return self.counters1[name] - self.counters0[name]


def number(cell, kind, steps, got, want=None):
    """The number compared for one answer ``got`` of a request of ``kind``;
    the request's terminal defines it (``steps/<call>.py``).  ``want`` is
    the reference's answer where the caller already holds it."""
    if cell.manifest.module("fetches", kind["fetch"]).ON_DEVICE:
        return float(got)        # compared where it lay: already the number
    if want is None:
        want = cell.reference.expected(steps)
    return cell.reference.number(steps, got, want)


def check(cell, result):
    """Every sampled answer of the window against the plain reference.
    Prints each number compared beside its limit; returns ``(correct,
    wrong)`` with ``wrong`` the count of sampled answers over their limit."""
    import numpy as np
    kinds = cell.traffic["requests"]
    want_of, worst, count = {}, {}, {}
    wrong = 0
    for slot, got in result["sampled"]:
        k, _, steps = result["requests"][slot]
        on_device = cell.manifest.module("fetches",
                                         kinds[k]["fetch"]).ON_DEVICE
        if not on_device and slot not in want_of:
            want_of[slot] = cell.reference.expected(steps)
        n = number(cell, kinds[k], steps, got, want_of.get(slot))
        worst[k] = max(worst.get(k, 0.0), n)
        count[k] = count.get(k, 0) + 1
        if not n <= float(kinds[k]["limit"]):
            wrong += 1
    ok = wrong == 0
    for k, kind in enumerate(kinds):
        if k not in count:
            log("check %s: no answer of this kind was sampled" % kind["kind"])
            ok = False
            continue
        log("check %s: %d answers, worst %.6g, limit %.6g"
            % (kind["kind"], count[k], worst[k], float(kind["limit"])))
    bad = cell.reference.data_mismatches(np.random.default_rng(cell.seed))
    log("check data: %d elements of the sampled records differ from the "
        "closed form, limit 0" % bad)
    return ok and bad == 0, wrong


def read_metrics(cell, result, group):
    ctx = {"cell": cell, "result": result, "trace": cell.trace}
    out = {}
    for m in cell.manifest.cell_metrics(cell.name, group):
        spec = cell.manifest.metric_spec(m["name"])
        reader = cell.manifest.module("readers", spec["reader"])
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(man, name, seed, seconds, trace, require_tpu=True,
             out_root=ROOT, keep_trace=None):
    """Set up, measure, check; returns the result object (not printed).
    ``keep_trace(raw)`` is handed the trace as read, before its reduction
    (tools/record_trace.py cuts the tests' recorded traces from it)."""
    cell = Cell(man, name, seed, seconds, trace, require_tpu, out_root)
    log("cell %s seed %d seconds %g trace %d: %s"
        % (name, cell.seed, cell.seconds, int(bool(trace)),
           cell.entry["why"]))
    cell.open_device()
    cell.build()
    driver = man.module("drivers", cell.traffic["driver"])
    result = driver.run(cell)
    log("set-up %.3f s from process start, less the %.3f s of the runtime's "
        "own start; window %.3f s (less %.3f s of checks inside it), %d requests "
        "(%d raised), peak %.3f GB"
        % (cell.setup_s, cell.reach_s, result["window_s"],
           result.get("check_s", 0.0), len(result["walls_s"]),
           result["raised"], cell.peak_bytes / 1e9))
    correct, wrong = check(cell, result)
    if cell.trace_dir:
        raw = tracered.read_xplane(tracered.find_xplane(cell.trace_dir))
        if keep_trace:
            keep_trace(raw)
        cell.trace = tracered.reduce_trace(raw, cell.chips)
        shutil.rmtree(cell.trace_dir, ignore_errors=True)
    walls = result["walls_s"]
    if len(walls) <= 24:
        log("request walls, s: %s" % " ".join("%.3f" % w for w in walls))
    log("request wall mean: %.4f ms over %d requests"
        % (sum(walls) / len(walls) * 1e3, len(walls)))
    for q in (50, 95, 99):
        if arith.supports_percentile(len(walls), q):
            log("request wall p%d: %.4f ms over %d requests"
                % (q, arith.percentile(walls, q) * 1e3, len(walls)))
    kinds = cell.traffic["requests"]
    if len(kinds) > 1:
        for k, kind in enumerate(kinds):
            of_kind = [w for w, s in zip(walls, result["slots"])
                       if result["requests"][s][0] == k]
            if arith.supports_percentile(len(of_kind), 50):
                log("request wall p50 of %s: %.4f ms over %d requests"
                    % (kind["kind"], arith.percentile(of_kind, 50) * 1e3,
                       len(of_kind)))
    out = {
        "correct": bool(correct and result["raised"] == 0),
        "attempted": len(walls),
        "failed": int(result["raised"] + wrong),
        "metrics": read_metrics(cell, result,
                                "per_layer" if trace else "end_to_end"),
        "device": dict(cell.device, memory_peak_bytes=cell.peak_bytes),
    }
    if cell.trace:
        out["device"]["busy_s"] = cell.trace["busy_s"]
        out["device"]["window_s"] = cell.trace["window_s"]
        out["breakdown"] = {
            "device_ops": tracered.top(cell.trace["ops_s"]),
            "idle_gaps": tracered.top(cell.trace["idle_gaps_s"]),
        }
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed is a non-negative whole number")
    man = manifest_mod.Manifest(manifest_mod.REAL)
    out = run_cell(man, args.workload, args.seed, args.seconds,
                   bool(args.trace))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
