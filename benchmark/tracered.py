"""From a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes one ``.xplane.pb``; ``read_xplane`` turns it into
plain data (planes -> lines -> ``[name, start_ns, duration_ns]``) and
everything else here is arithmetic on that, so ``tests/`` checks it on a
small recorded trace with no chip and no profiler.

* busy: per device plane, the union of the intervals in which an operation
  ran (line ``XLA Ops``), clipped to the window; ``busy_s`` is the mean over
  the chips used.  Idle share is ``1 - busy_s / window_s``.
* window: the ``bench.window`` annotation the driver holds open around the
  measured loop, on the host plane; the device lines share its clock.
* the check's own time: where the driver has to compare an answer inside
  the window (one too large to keep), it does so synchronously under a
  ``bench.check`` annotation.  Those instants are taken out of everything:
  ``window_s`` is the window less them, and what ran on the device in them
  is in no busy union, no per-op sum and no idle gap.
* per-op sums: seconds by operation name, mean over the chips.  The trace
  names an operation by its whole HLO line (``%copy.2 = f32[...] copy(...)``);
  the name kept is the part before `` = `` without the ``%``, so the same
  operation of sixteen static slices is one row.  Operations that nest (a
  ``while`` and its body) are each counted, so the sums can pass
  ``busy_s``; the union cannot.
* idle gaps by span: the gaps of the FIRST chip's busy union, each cut at
  the boundaries of the benchmark's own host spans (``bench.*``) and given
  to the span open at the time, the latest-started where several are (the
  loader thread's span inside the caller's fetch), or to
  ``_no_span_open_``.
"""

import glob
import os

import numpy as np

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
CHECK_SPAN = "bench.check"
SPAN_PREFIX = "bench."
NO_SPAN = "_no_span_open_"


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return paths[-1]


def read_xplane(path):
    """The trace as plain data; needs ``jax`` only for its reader."""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def op_name(event_name):
    """``%copy.2 = f32[16,200]{1,0} copy(...)`` -> ``copy.2``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def intervals(events, names=None):
    """``events`` as an (n, 2) float array of ``[start, end)`` in seconds."""
    rows = [(s * 1e-9, (s + d) * 1e-9) for name, s, d in events
            if names is None or name in names]
    return np.asarray(rows, np.float64).reshape(-1, 2)


def union(iv):
    """Merged, sorted, disjoint intervals covering the same instants."""
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    first = np.concatenate(([True], iv[1:, 0] > ends[:-1]))
    starts = iv[first, 0]
    last = np.concatenate((first[1:], [True]))
    return np.stack([starts, ends[last]], axis=1)


def clip(iv, t0, t1):
    if len(iv) == 0:
        return iv
    out = np.stack([np.maximum(iv[:, 0], t0), np.minimum(iv[:, 1], t1)], 1)
    return out[out[:, 1] > out[:, 0]]


def total(iv):
    return float((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0.0


def covered_before(cut):
    """``t -> seconds of the disjoint, sorted intervals ``cut`` before t``
    (piecewise linear), so an interval's overlap with ``cut`` is
    ``f(end) - f(start)``."""
    if len(cut) == 0:
        return lambda t: np.zeros_like(np.asarray(t, np.float64))
    xs = cut.reshape(-1)
    ys = np.repeat(np.concatenate(([0.0], np.cumsum(cut[:, 1] - cut[:, 0]))),
                   2)[1:-1]
    return lambda t: np.interp(t, xs, ys)


def complement(merged, t0, t1):
    """The gaps of ``merged`` (disjoint, sorted, inside the window)."""
    edges = np.concatenate(([t0], merged.reshape(-1), [t1]))
    gaps = edges.reshape(-1, 2)
    return gaps[gaps[:, 1] > gaps[:, 0]]


def attribute(gaps, spans):
    """Seconds of ``gaps`` by the span open at the time.  ``spans`` maps a
    name to merged intervals; where several are open the latest-started
    takes the time."""
    out = {name: 0.0 for name in spans}
    out[NO_SPAN] = 0.0
    if len(gaps) == 0:
        return out
    cuts = [gaps.reshape(-1)] + [iv.reshape(-1) for iv in spans.values()]
    edges = np.unique(np.concatenate(cuts))
    lo, hi = edges[:-1], edges[1:]
    mid = (lo + hi) / 2

    def covering_start(iv):
        """Per segment: the start of the interval of ``iv`` that covers it,
        or -inf."""
        if len(iv) == 0:
            return np.full(len(mid), -np.inf)
        k = np.searchsorted(iv[:, 0], mid, side="right") - 1
        inside = (k >= 0) & (mid < iv[np.maximum(k, 0), 1])
        return np.where(inside, iv[np.maximum(k, 0), 0], -np.inf)

    length = np.where(np.isfinite(covering_start(gaps)), hi - lo, 0.0)
    owned = np.zeros(len(mid), bool)
    if spans:
        starts = np.stack([covering_start(iv) for iv in spans.values()])
        owner = np.argmax(starts, axis=0)
        owned = np.isfinite(starts.max(axis=0))
        for j, name in enumerate(spans):
            out[name] = float(length[owned & (owner == j)].sum())
    out[NO_SPAN] = float(length[~owned].sum())
    return out


def _lines(trace, plane_prefix, line_name=None):
    for plane in trace["planes"]:
        if plane["name"].startswith(plane_prefix):
            for line in plane["lines"]:
                if line_name is None or line["name"] == line_name:
                    yield plane["name"], line


def reduce_trace(trace, chips):
    """The whole reduction; see the module docstring."""
    device = sorted(_lines(trace, DEVICE_PLANE, OPS_LINE),
                    key=lambda pl: int(pl[0][len(DEVICE_PLANE):].split()[0]))
    device = device[:chips]
    if len(device) < chips:
        raise ValueError("the trace holds %d device planes with a line %r, "
                         "the cell used %d chips"
                         % (len(device), OPS_LINE, chips))
    host_events = [ev for _, line in _lines(trace, HOST_PLANE)
                   for ev in line["events"] if ev[0].startswith(SPAN_PREFIX)]
    window = intervals(host_events, {WINDOW_SPAN})
    if len(window) != 1:
        raise ValueError("expected one %r span in the trace, found %d"
                         % (WINDOW_SPAN, len(window)))
    t0, t1 = float(window[0, 0]), float(window[0, 1])

    check = clip(union(intervals(host_events, {CHECK_SPAN})), t0, t1)
    in_check = covered_before(check)

    busy, ops = [], {}
    first_gaps = None
    for _, line in device:
        merged = clip(union(intervals(line["events"])), t0, t1)
        # the device's busy instants and the check's, less the check's
        both = union(np.concatenate([merged, check]))
        busy.append(total(both) - total(check))
        if first_gaps is None:
            first_gaps = complement(both, t0, t1)
        raw = intervals(line["events"])
        a, b = np.maximum(raw[:, 0], t0), np.minimum(raw[:, 1], t1)
        kept = np.where(b > a, (b - a) - (in_check(b) - in_check(a)), 0.0)
        for (name, _, _), seconds in zip(line["events"], kept):
            if seconds > 1e-9:
                name = op_name(name)
                ops[name] = ops.get(name, 0.0) + float(seconds) / chips
    span_names = sorted({ev[0] for ev in host_events}
                        - {WINDOW_SPAN, CHECK_SPAN})
    spans = {n: clip(union(intervals(host_events, {n})), t0, t1)
             for n in span_names}
    gaps = attribute(first_gaps, spans)
    return {
        "window_s": (t1 - t0) - total(check),
        "busy_s": float(np.mean(busy)),
        "busy_s_per_chip": busy,
        "ops_s": ops,
        "idle_gaps_s": gaps,
    }


def top(table, n=10):
    """The ``n`` largest entries of a name -> seconds table, as the
    ``breakdown`` lists want them."""
    rows = sorted(table.items(), key=lambda kv: -kv[1])[:n]
    return [[name, seconds] for name, seconds in rows]


def shrink(trace, t0_ns, t1_ns, keep=("XLA Ops",)):
    """A small recorded trace for ``tests/``: the device lines in ``keep``
    and the host's ``bench.*`` events, inside ``[t0_ns, t1_ns]``, with the
    window span cut to it."""
    planes = []
    for plane in trace["planes"]:
        lines = []
        for line in plane["lines"]:
            device = plane["name"].startswith(DEVICE_PLANE) \
                and line["name"] in keep
            host = plane["name"].startswith(HOST_PLANE)
            events = []
            for name, s, d in line["events"]:
                if host and name == WINDOW_SPAN:
                    events.append([name, t0_ns, t1_ns - t0_ns])
                elif (device or (host and name.startswith(SPAN_PREFIX))) \
                        and s >= t0_ns and s + d <= t1_ns:
                    events.append([name, s, d])
            if events:
                lines.append({"name": line["name"], "events": events})
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}
