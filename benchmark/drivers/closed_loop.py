"""A closed loop: one caller, each answer in hand before the next call.

Serves every cell whose callers wait for each reply: single requests
against a resident array (``reduce``, ``swap``, ``followups``) and whole
passes over a streamed source (``stream``); a pass is a request that takes
seconds.  The window closes when the first request completes after
``seconds``, so only completed requests count and every one of them does.
Where an answer is too large to keep until the window has closed (fetch
``ready``), the sampled ones are compared inside it, synchronously and
under a ``bench.check`` annotation; those seconds are the check's, not the
program's, and are taken out of ``window_s`` (and by ``tracered`` out of
the traced window and its busy union).

What the traffic file gives this driver:

    requests        kinds: count, steps, fetch, positions, limit (see
                    pipeline.py); the cycle is the fixed multiset of all
                    kinds' requests in an order the seed permutes
    warmup_cycles   whole cycles run after every distinct request has run
                    once, so that what is measured has settled
    sample_share    share of the window's answers kept for the check, the
                    choice drawn from the seed; besides, the last answer of
                    every distinct request is kept (of an answer that stays
                    on the device: the last of the window)
    trace_seconds   length of the window in a ``--trace 1`` run
"""

import time

import numpy as np

import pipeline

TAKE = 4096          # length of the pre-drawn keep/skip stream (a power of 2)


def run(cell):
    """``cell``: run.Cell.  Returns the dict the readers and the check
    read (see the end of this function)."""
    import jax
    traffic, operand = cell.traffic, cell.operand
    kinds = traffic["requests"]
    requests = pipeline.expand(traffic)
    man = cell.manifest
    calls = [pipeline.compile_call(man, steps) for _, _, steps in requests]
    how = [man.module("fetches", kinds[k]["fetch"]) for k, _, _ in requests]
    fetches = [f.take for f in how]
    on_device = [bool(f.ON_DEVICE) for f in how]
    order = [int(i) for i in pipeline.cycle_order(len(requests), cell.seed)]

    # every distinct request once (this is where programs compile), then
    # whole cycles until the host path has settled
    for slot in range(len(requests)):
        out = fetches[slot](calls[slot](operand.operand()))
        if on_device[slot]:
            # the check's own program compiles here too, not in the window
            cell.reference.on_device(requests[slot][2],
                                     out).block_until_ready()
        out = None
    for _ in range(int(traffic["warmup_cycles"])):
        for slot in order:
            fetches[slot](calls[slot](operand.operand()))

    rng = np.random.default_rng(cell.seed)
    take = [bool(t) for t in rng.random(TAKE) < float(traffic["sample_share"])]
    seconds = (min(cell.seconds, float(traffic["trace_seconds"]))
               if cell.trace_dir else cell.seconds)
    walls, slots, sampled = [], [], []
    last_of = {}                         # slot -> its newest host answer
    failed = 0
    call_s = fetch_s = check_s = 0.0
    loads0 = len(operand.loader_seconds)
    annotate = jax.profiler.TraceAnnotation
    clock = time.perf_counter
    n = len(order)

    cell.begin_window()                  # counters, profiler; set-up ends
    with annotate("bench.window"):
        i = 0
        t_start = clock()
        deadline = t_start + seconds
        while True:
            slot = order[i % n]
            out = None
            t0 = clock()
            try:
                with annotate("bench.call"):
                    handle = calls[slot](operand.operand())
                t1 = clock()
                with annotate("bench.fetch"):
                    out = fetches[slot](handle)
                t2 = clock()
            except Exception as exc:     # a request that raises has failed
                t1 = t2 = clock()
                failed += 1
                cell.log("request %d (slot %d) raised %r" % (i, slot, exc))
            handle = None
            walls.append(t2 - t0)
            slots.append(slot)
            call_s += t1 - t0
            fetch_s += t2 - t1
            last = t2 >= deadline
            if out is None:
                pass
            elif on_device[slot]:
                if last or take[i & (TAKE - 1)]:
                    # too large to keep: compared where it lies, by one
                    # fused pass, and waited for, so that the answer is
                    # gone before the next request needs its room
                    with annotate("bench.check"):
                        count = cell.reference.on_device(requests[slot][2],
                                                         out)
                        count.block_until_ready()
                    sampled.append((slot, count))
                    out = None
                    if not last:         # the last one runs after t_end
                        check_s += clock() - t2
            elif take[i & (TAKE - 1)]:
                sampled.append((slot, out))
            else:
                last_of[slot] = out
            out = None
            i += 1
            if last:
                break
        t_end = t2
    cell.end_window()
    sampled.extend(last_of.items())

    return {
        "requests": requests,            # (kind, position, steps) per slot
        "walls_s": walls, "slots": slots,
        "window_s": t_end - t_start - check_s, "check_s": check_s,
        "bytes_done": (len(walls) - failed) * operand.nbytes,
        "raised": failed, "sampled": sampled,
        "span_s": {"bench.call": call_s, "bench.fetch": fetch_s,
                   "bench.loader": sum(operand.loader_seconds[loads0:])},
        "loader_bytes": sum(operand.loader_bytes[loads0:]),
    }
