"""Closed loops through the serve queue: ``streams`` callers of ONE server
and one resident operand, each waiting for its answer before it asks again.

TPC-H's throughput test (clause 5.3) in Bolt's terms.  A stream is a thread
with a tenant of its own (``stream<k>``) that sends its own requests in
turn through ``bolt_tpu.serve``: the submit under ``bench.call``, then
``Future.result()`` and the kind's fetch to the host under ``bench.fetch``.
One ``bench.window`` on the main thread holds all of them, from the first
stream's start: every stream stops asking at the deadline and finishes the request it has in flight,
and the window closes at the last completion, so only completed requests
count and every one of them does.  ``walls_s`` are all streams' requests,
each from its submit to its answer on the host.

What a run can show of the configuration's guarantees is held here, and
every breach counts as a failed request (``raised``), so that ``correct``
is false: completeness (the server's ``submitted``, ``completed``,
``failed``, ``rejected``, ``expired`` over the window read n, n, 0, 0, 0),
order (a stream's Future is accepted, started and finished before its next
is accepted), no starvation (no stream completes fewer than the
configuration's ``stream_share_floor`` of the streams' mean).  Isolation is
the check's: a sampled answer is compared with the reference computed from
the parameters of the stream that asked.

What the configuration gives this driver:

    streams           how many loops; stream k sends position k of every
                      kind, so every kind has ``streams`` positions
    serve             ``budget_bytes``: a number, or ``"bytes_limit"`` for
                      the device's own limit by ``memory_stats()``; the
                      server is otherwise what ``serve.serving()`` starts
    stream_share_floor

and the traffic file:

    requests        kinds: steps, fetch, positions, limit (pipeline.py),
                    ``count`` equal to ``streams``, and ``submit``:
                    ``"pipeline"`` where the steps build a lazy bolt array
                    on the caller's thread and the array is submitted (the
                    server estimates it, leases for it and resolves it), or
                    ``"callable"`` where they launch when called and go as
                    a zero-argument callable that a worker runs
    warmup_cycles   cycles every stream runs through the server after
                    every distinct request has run once directly
    sample_share    share of a stream's answers kept for the check, drawn
                    from the seed; besides, the last answer of every
                    distinct request is kept
    trace_seconds   length of the window in a ``--trace 1`` run

The seed also chooses which of its requests each stream starts with.
"""

import threading
import time

import numpy as np

import pipeline

TAKE = 4096          # length of a stream's pre-drawn keep/skip list
ANSWER_S = 120.0     # a Future not answered by then has failed; no hang
COUNTED = ("submitted", "completed", "failed", "rejected", "expired",
           "leased")


class Stream:
    """One caller: its requests, its tenant, and what it saw."""

    def __init__(self, k, slots, first, take):
        self.slots, self.first, self.take = slots, first, take
        self.tenant = "stream%d" % k
        self.clear()

    def clear(self):
        self.walls, self.asked, self.sampled = [], [], []
        self.times = []      # a request's Future: accepted, started, done
        self.last_of = {}
        self.failed = 0
        self.call_s = self.fetch_s = 0.0
        self.t_end = None


def budget_of(cell):
    budget = cell.config["serve"]["budget_bytes"]
    if budget != "bytes_limit":
        return int(budget)
    stats = cell.devices[0].memory_stats() or {}
    if "bytes_limit" not in stats:
        raise SystemExit("the device reports no bytes_limit to size the "
                         "serving budget to")
    return int(stats["bytes_limit"])


def run(cell):
    """``cell``: run.Cell.  Returns what ``closed_loop.run`` returns, and
    ``stream_counts`` (completed requests a stream) and ``serve`` (the
    window's delta of the server's totals)."""
    import jax
    from bolt_tpu import serve
    traffic, operand, man = cell.traffic, cell.operand, cell.manifest
    kinds = traffic["requests"]
    requests = pipeline.expand(traffic)
    n_streams = int(cell.config["streams"])
    for kind in kinds:
        if int(kind["count"]) != n_streams \
                or len(kind["positions"]) != n_streams:
            raise ValueError("kind %s: stream_loops wants one position and "
                             "one request a stream (%d)"
                             % (kind["kind"], n_streams))
        if kind["submit"] not in ("pipeline", "callable"):
            raise ValueError("kind %s: submit is 'pipeline' or 'callable'"
                             % kind["kind"])
    calls = [pipeline.compile_call(man, steps) for _, _, steps in requests]
    fetches = [man.module("fetches", kinds[k]["fetch"]).take
               for k, _, _ in requests]
    lazy = [kinds[k]["submit"] == "pipeline" for k, _, _ in requests]
    rng = np.random.default_rng(cell.seed)
    streams = []
    for k in range(n_streams):
        slots = [s for s, (_, p, _) in enumerate(requests) if p == k]
        first = int(rng.integers(len(slots)))
        take = [bool(t) for t in
                rng.random(TAKE) < float(traffic["sample_share"])]
        streams.append(Stream(k, slots, first, take))

    # every distinct request once, directly (this is where programs compile)
    for slot in range(len(requests)):
        fetches[slot](calls[slot](operand.operand()))

    annotate = jax.profiler.TraceAnnotation
    clock = time.perf_counter

    def ask(sv, slot, tenant):
        if lazy[slot]:
            return sv.submit(calls[slot](operand.operand()), tenant=tenant)
        return sv.submit(lambda: calls[slot](operand.operand()),
                         tenant=tenant)

    def loop(sv, st, stop):
        """``stop(i, now)``: was request ``i``, answered at ``now``, the
        stream's last?"""
        i = 0
        while True:
            slot = st.slots[(st.first + i) % len(st.slots)]
            out = fut = None
            t0 = clock()
            try:
                with annotate("bench.call"):
                    fut = ask(sv, slot, st.tenant)
                t1 = clock()
                with annotate("bench.fetch"):
                    out = fetches[slot](fut.result(ANSWER_S))
                t2 = clock()
            except Exception as exc:     # a request that raises has failed
                t1 = t2 = clock()
                st.failed += 1
                cell.log("%s request %d (slot %d) raised %r"
                         % (st.tenant, i, slot, exc))
            st.walls.append(t2 - t0)
            st.asked.append(slot)
            st.times.append(fut and (fut.submitted_s, fut.started_s,
                                     fut.finished_s))
            st.call_s += t1 - t0
            st.fetch_s += t2 - t1
            if out is None:
                pass
            elif st.take[i & (TAKE - 1)]:
                st.sampled.append((slot, out))
            else:
                st.last_of[slot] = out
            if stop(i, t2):
                st.t_end = t2
                return
            i += 1

    def all_streams(sv, stop, seconds):
        """Every stream's loop at once, until each has met ``stop``."""
        threads = [threading.Thread(target=loop, args=(sv, st, stop),
                                    name="bench-" + st.tenant, daemon=True)
                   for st in streams]
        for th in threads:
            th.start()
        for th in threads:
            th.join(seconds + ANSWER_S)
            if th.is_alive():
                raise SystemExit("%s did not come back" % th.name)

    seconds = (min(cell.seconds, float(traffic["trace_seconds"]))
               if cell.trace_dir else cell.seconds)
    with serve.serving(budget_bytes=budget_of(cell)) as sv:
        warm = int(traffic["warmup_cycles"]) * len(streams[0].slots)
        all_streams(sv, lambda i, now: i + 1 >= warm, 0.0)
        if any(st.failed for st in streams):
            raise SystemExit("a request of the warm-up failed")
        for st in streams:
            st.clear()

        totals0 = sv.stats()["totals"]
        cell.begin_window()              # counters, profiler; set-up ends
        with annotate("bench.window"):
            t_start = clock()
            deadline = t_start + seconds
            all_streams(sv, lambda i, now: now >= deadline, seconds)
        cell.end_window()
        totals1 = sv.stats()["totals"]
    t_end = max(st.t_end for st in streams)
    served = {key: totals1[key] - totals0[key]
              for key in COUNTED if key in totals1}

    walls = [w for st in streams for w in st.walls]
    slots = [s for st in streams for s in st.asked]
    failed = sum(st.failed for st in streams)
    counts = [len(st.walls) - st.failed for st in streams]
    breaches = guarantees(cell, streams, served, len(walls), counts)
    sampled = [pair for st in streams for pair in st.sampled]
    for st in streams:
        sampled.extend(st.last_of.items())
    if cell.trace_dir:
        log_spans(cell)
    return {
        "requests": requests,            # (kind, position, steps) per slot
        "walls_s": walls, "slots": slots,
        "window_s": t_end - t_start, "check_s": 0.0,
        "bytes_done": (len(walls) - failed) * operand.nbytes,
        "raised": failed + breaches, "sampled": sampled,
        "span_s": {"bench.call": sum(st.call_s for st in streams),
                   "bench.fetch": sum(st.fetch_s for st in streams)},
        "stream_counts": counts, "serve": served,
    }


def guarantees(cell, streams, served, n, counts):
    """How many of the configuration's guarantees the window broke, each
    logged beside what it read."""
    breaches = 0
    want = {"submitted": n, "completed": n, "failed": 0, "rejected": 0,
            "expired": 0}
    got = {key: served.get(key) for key in want}
    cell.log("check completeness: the server's %s, want %s"
             % (got, list(want.values())))
    breaches += got != want
    late = 0
    for st in streams:
        flat = [t for times in st.times for t in (times or (None,))]
        if None in flat:                 # a request that never ran
            late += 1
        else:
            late += sum(a > b for a, b in zip(flat, flat[1:]))
    cell.log("check order: %d answers out of the order asked, limit 0"
             % late)
    breaches += late > 0
    floor = float(cell.config["stream_share_floor"])
    mean = sum(counts) / len(counts)
    share = min(counts) / mean if mean else 0.0
    cell.log("check no starvation: streams completed %s, the least %.4f of "
             "the mean, floor %g" % (counts, share, floor))
    breaches += not share >= floor
    return int(breaches)


def log_spans(cell):
    """A traced run's totals of the program's own spans on the queue's
    path, for the log: count and mean of each."""
    try:
        from bolt_tpu import obs
        rows = obs.totals()
    except (ImportError, AttributeError):
        return
    for name in sorted(rows):
        if name.startswith(("serve.", "engine.dispatch", "engine.enqueue")):
            row = rows[name]
            cell.log("span %s: %d, mean %.3f us" % (
                name, row["count"],
                row["seconds"] / max(row["count"], 1) * 1e6))
