#!/usr/bin/env python3
"""Whose time is the device's idle time?  One traced run of a benchmark cell,
its idle gaps laid over bolt's own spans.

    python3 scripts/host_gaps.py <cell> --seed <n> [--seconds <s>]
        [--out file] [--show N]

Runs the cell through ``benchmark/run.py::run_cell`` with the profiler on
(needs the chips the cell names).  ``bolt_tpu.obs`` spans record while a
profiler session is live and land in its trace as ``bolt.<name>`` events on
the device's clock, so the first chip's idle gaps (``benchmark/tracered.py``'s
interval arithmetic, the check's own instants taken out as there) can be
given to the innermost span open at the time.  Printed, in seconds of the
traced window and per request:

* the gaps by ``bench.*`` span (what the ledger's ``breakdown.idle_gaps``
  holds) and by ``bolt.*`` span, whole window;
* the same inside ``bench.fetch`` alone, and the share of it that falls in
  a named ``bolt.*`` span;
* where a streamed run lies in the window, the gaps by the ``bolt.*`` spans
  of the CONSUMER's thread alone (the one that opened ``stream.run``,
  ``stream.shuffle`` or ``stream.collect``: starved in ``stream.wait.slab``,
  calling in ``stream.dispatch``, blocked in ``stream.sync``), and beside it
  by those of every other thread (the pool's: ``stream.ingest``,
  ``stream.wait.ring``).  With a wait open on a pool thread and a span open
  on the consumer's, "the latest-started takes the time" picks either;
  and the idle time inside the consumer's ``bolt.stream.sync`` in the three
  parts given below for ``bolt.array.fetch.wait``: a block whose program
  runs at its END waited for the program to start (for something in front
  of it on the device), one whose program runs at its START waited after it;
* the idle time inside ``bolt.array.fetch.wait`` in three parts: before the
  first device operation under the span (launch latency), between its
  operations, and after the last (copy back and wake-up);
* how far the device plane's clock can be from the host plane's: the
  shifts of the device's timestamps under which every operation starts
  after the ``bolt.engine.enqueue`` that launched it and ends before the
  ``bolt.array.fetch.wait`` (or ``bench.fetch``) that awaited it; and the last two tables again at either
  end of that range.  The profiler aligns the two clocks to a millisecond
  or so, which is the size of the gaps being split;
* the programs that compiled inside the window, if any did, by family
  with their lowering and compile seconds and whether the on-disk cache
  served them (``engine.compile_log()``).

The last line of standard output is the same as one JSON object.
"""

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import manifest as manifest_mod  # noqa: E402
import run as bench_run          # noqa: E402
import tracered as tr            # noqa: E402

BOLT = "bolt."
RUNS = ("bolt.stream.run", "bolt.stream.shuffle", "bolt.stream.collect")
FETCH = "bench.fetch"
WAIT = "bolt.array.fetch.wait"
SYNC = "bolt.stream.sync"


def intersect(a, b):
    """The instants in both of two sets of merged intervals."""
    if len(a) == 0 or len(b) == 0:
        return np.zeros((0, 2))
    inside = tr.covered_before(b)
    edges = np.unique(np.concatenate([a.reshape(-1), b.reshape(-1)]))
    lo, hi = edges[:-1], edges[1:]
    mid = (lo + hi) / 2
    k = np.searchsorted(a[:, 0], mid, side="right") - 1
    in_a = (k >= 0) & (mid < a[np.maximum(k, 0), 1])
    in_b = (inside(hi) - inside(lo)) > (hi - lo) / 2
    keep = in_a & in_b
    return tr.union(np.stack([lo[keep], hi[keep]], axis=1))


def by_span(gaps, events, prefix, skip=()):
    """``gaps`` by the innermost (latest-started) open span among the host
    events whose name starts with ``prefix``."""
    # longest name first: where a child starts on its parent's very
    # nanosecond, ``attribute`` gives the tie to the one listed first
    names = sorted({ev[0] for ev in events if ev[0].startswith(prefix)}
                   - set(skip), reverse=True)
    spans = {n: tr.union(tr.intervals(events, {n})) for n in names}
    return tr.attribute(gaps, spans)


def wait_parts(gaps, busy, waits):
    """Idle seconds inside the ``waits`` intervals: before the first device
    operation that starts under each, between its operations, after the
    last; ``none`` where no operation started under it."""
    idle_before = tr.covered_before(gaps)
    out = {"before_first_op": 0.0, "between_ops": 0.0, "after_last_op": 0.0,
           "none": 0.0}
    starts = busy[:, 0] if len(busy) else np.zeros(0)
    for a, b in waits:
        idle = float(idle_before(b) - idle_before(a))
        i, j = np.searchsorted(starts, [a, b])
        # an operation already running at ``a`` belongs to this wait too
        if i > 0 and busy[i - 1, 1] > a:
            i -= 1
        if i >= j:
            out["none"] += idle
            continue
        first, last = max(busy[i, 0], a), min(busy[j - 1, 1], b)
        before = float(idle_before(first) - idle_before(a))
        after = float(idle_before(b) - idle_before(last))
        out["before_first_op"] += before
        out["after_last_op"] += after
        out["between_ops"] += idle - before - after
    return out


def host_spans(raw):
    """The host plane's ``bench.*`` and ``bolt.*`` events, every thread's."""
    return [ev for _, line in tr._lines(raw, tr.HOST_PLANE)
            for ev in line["events"]
            if ev[0].startswith(tr.SPAN_PREFIX) or ev[0].startswith(BOLT)]


def consumer_spans(raw):
    """The ``bolt.*`` events of the host plane in two lists: those of the
    threads that opened a streamed run's own span (``RUNS``; a line of the
    plane is a thread), and every other thread's."""
    mine, others = [], []
    for _, line in tr._lines(raw, tr.HOST_PLANE):
        events = [ev for ev in line["events"] if ev[0].startswith(BOLT)]
        ran = any(ev[0] in RUNS for ev in events)
        (mine if ran else others).extend(events)
    return mine, others


def first_chip_ops(raw):
    """The device operations of the first chip, as ``tracered`` orders the
    chips."""
    device = sorted(tr._lines(raw, tr.DEVICE_PLANE, tr.OPS_LINE),
                    key=lambda pl: int(
                        pl[0][len(tr.DEVICE_PLANE):].split()[0]))
    if not device:
        raise SystemExit("the trace holds no device plane: nothing ran on "
                         "a chip")
    return device[0][1]["events"]


ENQUEUE = "bolt.engine.enqueue"
OFFSET_RANGE_S, OFFSET_STEP_S = 3e-3, 1e-5


def clock_offset(ops, host, t0, t1, check):
    """How far the device plane's clock may be from the host plane's:
    ``(lo, hi)`` seconds, the shifts of the device's timestamps under which
    no operation runs where none can.  One caller, each answer in hand
    before the next call: the device can only be busy between a request's
    first ``bolt.engine.enqueue`` and the end of its fetch's
    ``bolt.array.fetch.wait`` (of its ``bench.fetch`` where there is none),
    or inside a ``bench.check``.  ``None`` where the trace holds no enqueue
    spans, or no shift within 3 ms fits."""
    fetches = tr.union(tr.intervals(host, {FETCH}))
    starts = np.sort(tr.intervals(host, {ENQUEUE})[:, 0])
    wait_ends = np.sort(tr.intervals(host, {WAIT})[:, 1])
    if len(fetches) == 0 or len(starts) == 0:
        return None
    allowed = []
    for after, (begin, end) in zip(
            np.concatenate(([-np.inf], fetches[:-1, 1])), fetches):
        k = np.searchsorted(starts, after, side="right")
        # the answer was there when the fetch's wait returned, where the
        # fetch has one (``toarray``); else when the fetch did
        w = np.searchsorted(wait_ends, end, side="right") - 1
        if w >= 0 and wait_ends[w] > begin:
            end = wait_ends[w]
        if k < len(starts) and starts[k] < end:
            allowed.append((starts[k], end))
    allowed += [(a - OFFSET_RANGE_S, b + OFFSET_RANGE_S) for a, b in check]
    closed = tr.complement(tr.clip(tr.union(
        np.asarray(allowed).reshape(-1, 2)), t0, t1), t0, t1)
    # away from the window's edges, where a shift would push work out
    inner = tr.clip(ops, t0 + 2 * OFFSET_RANGE_S, t1 - 2 * OFFSET_RANGE_S)
    limit = 5e-6 * len(fetches)
    fits = [d for d in np.arange(-OFFSET_RANGE_S, OFFSET_RANGE_S,
                                 OFFSET_STEP_S)
            if tr.total(intersect(inner + d, closed)) <= limit]
    return (float(min(fits)), float(max(fits))) if fits else None


def idle_tables(ops, check, host, t0, t1, consumer=((), ())):
    """The idle gaps of ``ops`` (merged busy intervals of the first chip)
    given to the spans, as the module docstring lists them; ``consumer``
    is ``consumer_spans``' pair."""
    busy = tr.clip(ops, t0, t1)
    both = tr.union(np.concatenate([busy, check]))
    gaps = tr.complement(both, t0, t1)
    in_fetch = intersect(gaps, tr.clip(tr.union(
        tr.intervals(host, {FETCH})), t0, t1))
    fetch_by_bolt = by_span(in_fetch, host, BOLT)
    idle_fetch = tr.total(in_fetch)
    mine, others = consumer
    streamed = {"idle_by_consumer": by_span(gaps, mine, BOLT),
                "idle_by_other_threads": by_span(gaps, others, BOLT),
                "idle_in_sync": wait_parts(gaps, busy, tr.clip(
                    tr.intervals(mine, {SYNC}), t0, t1))} \
        if mine else {}
    return {
        **streamed,
        "busy_s": tr.total(both) - tr.total(check),
        "idle_s": tr.total(gaps),
        "idle_by_bench": by_span(gaps, host, tr.SPAN_PREFIX,
                                 skip=(tr.WINDOW_SPAN, tr.CHECK_SPAN)),
        "idle_by_bolt": by_span(gaps, host, BOLT),
        "idle_in_fetch_s": idle_fetch,
        "idle_in_fetch_by_bolt": fetch_by_bolt,
        "named_share_of_fetch_idle": (
            1.0 - fetch_by_bolt[tr.NO_SPAN] / idle_fetch
            if idle_fetch > 0 else None),
        "idle_in_wait": wait_parts(gaps, busy, tr.clip(
            tr.intervals(host, {WAIT}), t0, t1)),
    }


def split(raw):
    """Everything printed, from the trace as ``tracered.read_xplane`` gives
    it: the tables with the device's timestamps as recorded, the bounds on
    the two planes' clock offset, and the tables again at either bound."""
    host = host_spans(raw)
    window = tr.intervals(host, {tr.WINDOW_SPAN})
    if len(window) != 1:
        raise SystemExit("expected one %r span, found %d"
                         % (tr.WINDOW_SPAN, len(window)))
    t0, t1 = float(window[0, 0]), float(window[0, 1])
    check = tr.clip(tr.union(tr.intervals(host, {tr.CHECK_SPAN})), t0, t1)
    ops = tr.union(tr.intervals(first_chip_ops(raw)))
    out = idle_tables(ops, check, host, t0, t1, consumer_spans(raw))
    out["window_s"] = (t1 - t0) - tr.total(check)
    out["bolt_events"] = sum(1 for ev in host if ev[0].startswith(BOLT))
    offset = clock_offset(ops, host, t0, t1, check)
    out["device_clock_offset_s"] = offset
    if offset is not None:
        out["at_offset"] = [dict(idle_tables(ops + d, check, host, t0, t1),
                                 offset_s=d) for d in offset]
    return out


def show(raw, count, out=print):
    """The first ``count`` requests of the window as they lie in the trace:
    every host span and device operation that touches the request's
    ``bench.fetch``, in microseconds from its start (device rows marked
    ``dev``), so that the two clocks can be checked against each other: an
    operation cannot start before its ``bolt.engine.enqueue`` does, nor end
    after the ``bolt.array.fetch.wait`` that waited for it."""
    host, device = host_spans(raw), first_chip_ops(raw)
    window = [ev for ev in host if ev[0] == tr.WINDOW_SPAN][0]
    fetches = sorted(ev for ev in host if ev[0] == FETCH
                     and ev[1] >= window[1])[:count]
    for _, f0, fd in fetches:
        lo, hi = f0 - 300000, f0 + fd + 100000
        rows = [(s, "    ", n, d) for n, s, d in host
                if s < hi and s + d > lo and n != tr.WINDOW_SPAN]
        rows += [(s, "dev ", tr.op_name(n), d) for n, s, d in device
                 if s < hi and s + d > lo]
        out("request at %.3f ms of the trace:" % (f0 * 1e-6))
        for s, where, name, d in sorted(rows):
            out("  %s%9.1f .. %9.1f us  %s" % (where, (s - f0) * 1e-3,
                                               (s + d - f0) * 1e-3, name))


def window_compiles():
    """The programs that compiled inside the traced window: the tracer
    records only while the profiler session is live, so its count of
    ``engine.compile`` spans is the window's, and that many of the engine's
    newest compile-log rows name them."""
    from bolt_tpu import engine, obs
    count = obs.totals().get("engine.compile", {}).get("count", 0)
    return engine.compile_log()[-count:] if count else []


def table(title, rows, requests):
    print(title)
    for name, seconds in sorted(rows.items(), key=lambda kv: -kv[1]):
        if seconds > 0:
            print("  %-36s %9.4f s  %9.1f us/request"
                  % (name, seconds, seconds / requests * 1e6))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0,
                    help="as run.py's; a traced window is the traffic "
                         "file's trace_seconds at most")
    ap.add_argument("--out", help="also write the JSON object here")
    ap.add_argument("--show", type=int, default=0, metavar="N",
                    help="print the first N requests' spans and device "
                         "operations as they lie in the trace")
    args = ap.parse_args(argv)
    man = manifest_mod.Manifest(manifest_mod.REAL)
    kept = []
    result = bench_run.run_cell(man, args.cell, args.seed, args.seconds,
                                True, keep_trace=kept.append)
    requests = result["attempted"]
    out = split(kept[0])
    out.update(cell=args.cell, seed=args.seed, requests=requests,
               correct=result["correct"],
               metrics={k: v["value"] for k, v in result["metrics"].items()})
    print("%s: window %.3f s, %d requests, first chip busy %.3f s, idle "
          "%.3f s; %d bolt.* events on the host plane"
          % (args.cell, out["window_s"], requests, out["busy_s"],
             out["idle_s"], out["bolt_events"]))
    if args.show:
        show(kept[0], args.show)
    out["compiled_in_window"] = window_compiles()
    for row in out["compiled_in_window"]:
        print("compiled in the window: %s %s, lower %.3f s, compile %.3f s "
              "(%s, read %.3f s)"
              % (row["family"], row["program"], row["lower_s"],
                 row["compile_s"], row["cache"], row["read_s"]))
    table("idle by bench.* span (the ledger's breakdown.idle_gaps):",
          out["idle_by_bench"], requests)
    table("idle by innermost bolt.* span, whole window:",
          out["idle_by_bolt"], requests)
    if "idle_by_consumer" in out:
        table("idle by innermost bolt.* span of the streamed run's own "
              "thread, the consumer's:", out["idle_by_consumer"], requests)
        table("  and beside it, by those of every other thread:",
              out["idle_by_other_threads"], requests)
        table("idle inside the consumer's %s, by where the device's work "
              "lies in it:" % SYNC, out["idle_in_sync"], requests)
    table("idle inside %s (%.4f s) by innermost bolt.* span:"
          % (FETCH, out["idle_in_fetch_s"]),
          out["idle_in_fetch_by_bolt"], requests)
    if out["named_share_of_fetch_idle"] is not None:
        print("  share of it inside a named bolt.* span: %.1f %%"
              % (100.0 * out["named_share_of_fetch_idle"]))
    table("idle inside %s, by where the device's work lies in it:" % WAIT,
          out["idle_in_wait"], requests)
    offset = out["device_clock_offset_s"]
    if offset is None:
        print("device clock against the host's: no bound (no %s spans, or "
              "no shift within 3 ms fits)" % ENQUEUE)
    else:
        print("device clock against the host's: the device's timestamps "
              "are consistent with what launched and awaited them only if "
              "shifted by %+.0f .. %+.0f us%s"
              % (offset[0] * 1e6, offset[1] * 1e6,
                 "" if offset[0] <= 0 <= offset[1] else
                 ": NOT as recorded; the tables above carry that error"))
        for at in out["at_offset"]:
            table("with the device shifted by %+.0f us: idle inside %s "
                  "(%.4f s) by innermost bolt.* span:"
                  % (at["offset_s"] * 1e6, FETCH, at["idle_in_fetch_s"]),
                  at["idle_in_fetch_by_bolt"], requests)
            table("  and inside %s:" % WAIT, at["idle_in_wait"], requests)
    line = json.dumps(out)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as fh:
            fh.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
