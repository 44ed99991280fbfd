"""Query streams through one server (ISSUE 46): TPC-H's throughput test
at toy size on the CPU mesh.

Five caller threads, one tenant each, each a closed loop of its own Q6
(submitted as the lazy array, so the server estimates it, leases for it
and resolves it) and its own Q1 (``ops.segment_reduce`` launches when
called, so it goes as a zero-argument callable) over ONE resident table.
Every answer is the answer to that stream's own parameters, equal to what
``mode='local'`` gives on the same data; a stream's answers come back in
the order it asked; the server answers every request exactly once; and
the queue's four spans (``serve.submit``, ``serve.queue``, ``serve.lease``,
``serve.run``) are recorded once a job while someone is looking and not
at all otherwise.
"""

import threading

import jax.numpy as jnp
import numpy as np
import pytest

import bolt_tpu as bolt
from bolt_tpu import obs, serve

pytestmark = pytest.mark.serve

DATE, QTY, PRICE, DISC, TAX, FLAG, STATUS = range(7)
STREAMS, CYCLES = 5, 8
# a stream's substitution parameters (benchmark/traffic/streams5.json):
# Q6 shipdate [from, before), discount [lo, hi], quantity below; Q1 day
PARAMETERS = [
    ((731, 1096), (5, 7), 24, 2436),
    ((366, 731), (2, 4), 25, 2466),
    ((1096, 1461), (8, 10), 24, 2406),
    ((1461, 1827), (3, 5), 25, 2451),
    ((1827, 2192), (6, 8), 24, 2421),
]
REVENUE, SUMS = 1e-5, 1e-4       # the cell's limits, relative


@pytest.fixture(autouse=True)
def _no_leaked_server():
    yield
    assert serve.active() is None, "a test leaked an active server"


def _table(rows=40_003, seed=46):
    """LINEITEM's seven columns as small integers in float32."""
    rng = np.random.default_rng(seed)
    qty = rng.integers(1, 51, rows)
    cols = [rng.integers(1, 2527, rows), qty,
            qty * rng.integers(90_000, 209_900, rows),
            rng.integers(0, 11, rows), rng.integers(0, 9, rows),
            rng.integers(0, 3, rows), rng.integers(0, 2, rows)]
    return np.stack(cols, axis=1).astype(np.float32)


def _group(r):
    return (3 * r[STATUS] + r[FLAG]).astype(np.int32)


def _terms(r):
    disc_price = r[PRICE] * (100 - r[DISC])
    one = np.ones_like(r[QTY]) if isinstance(r, np.ndarray) \
        else jnp.ones_like(r[QTY])
    return (r[QTY], r[PRICE], disc_price, disc_price * (100 + r[TAX]),
            r[DISC], one)


def _queries(parameters):
    """``(q6, q1)``: a stream's two calls on a bolt array of either
    mode, its parameters closed over as Python ints."""
    (d0, d1), (c0, c1), q, day = parameters

    def pred6(r):
        return ((r[DATE] >= d0) & (r[DATE] < d1) & (r[DISC] >= c0)
                & (r[DISC] <= c1) & (r[QTY] < q))

    def revenue(r):
        return r[PRICE] * r[DISC]

    def q6(b):
        return b.filter(pred6).map(revenue).sum()

    def q1(b):
        return bolt.ops.segment_reduce(
            b.filter(lambda r: r[DATE] <= day), labels=_group,
            num_segments=6, value=_terms, return_counts=True)
    return q6, q1


def _host(answer):
    """A Q6 answer as a float, a Q1 answer as ``(sums (6, 6), counts)``."""
    if isinstance(answer, tuple):
        sums, counts = answer
        return (np.stack([np.asarray(s.toarray(), np.float64)
                          for s in sums], axis=1),
                np.asarray(counts.toarray()))
    return float(np.asarray(answer.toarray()))


def _same(got, want):
    if isinstance(want, tuple):
        assert np.array_equal(got[1], want[1])          # counts: exact
        assert np.all(np.abs(got[0] - want[0])
                      <= SUMS * np.maximum(np.abs(want[0]), 1.0))
    else:
        assert abs(got - want) <= REVENUE * max(abs(want), 1.0)


def _stream(sv, b, k, queries, cycles, seen):
    """Stream ``k``'s closed loop: Q6 then Q1, ``cycles`` times; what it
    saw, in the order it asked, goes into ``seen[k]``."""
    q6, q1 = queries
    tenant = "stream%d" % k
    for _ in range(cycles):
        # the lazy array itself, then a callable a worker runs
        for kind, job in (("q6", q6(b)), ("q1", lambda: q1(b))):
            fut = sv.submit(job, tenant=tenant)
            seen[k].append((kind, _host(fut.result(timeout=120)), fut))


def _run_streams(sv, b, cycles=CYCLES):
    seen = [[] for _ in range(STREAMS)]
    threads = [threading.Thread(
        target=_stream, args=(sv, b, k, _queries(PARAMETERS[k]), cycles,
                              seen)) for k in range(STREAMS)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(300)
        assert not th.is_alive()
    return seen


def test_five_streams_through_one_server_each_its_own_answers(mesh):
    x = _table()
    b = bolt.array(x, mesh, axis=(0,))
    local = bolt.array(x)
    want = []
    for parameters in PARAMETERS:
        q6, q1 = _queries(parameters)
        want.append({"q6": _host(q6(local)), "q1": _host(q1(local))})
    # the five sets select different rows: an answer handed to the wrong
    # stream would not pass for its own
    assert len({w["q6"] for w in want}) == STREAMS
    assert len({int(w["q1"][1].sum()) for w in want}) == STREAMS
    with serve.serving(budget_bytes=1 << 30) as sv:
        before = sv.stats()["totals"]
        seen = _run_streams(sv, b)
        after = sv.stats()
    n = STREAMS * CYCLES * 2
    for k, answers in enumerate(seen):
        # in the order asked, each the answer to this stream's parameters
        assert [kind for kind, _, _ in answers] == ["q6", "q1"] * CYCLES
        for kind, got, _ in answers:
            _same(got, want[k][kind])
        times = [t for _, _, f in answers
                 for t in (f.submitted_s, f.started_s, f.finished_s)]
        assert times == sorted(times)
    delta = {key: after["totals"][key] - before[key]
             for key in ("submitted", "completed", "failed", "rejected",
                         "expired", "leased")}
    assert delta == {"submitted": n, "completed": n, "failed": 0,
                     "rejected": 0, "expired": 0, "leased": n // 2}
    done = [after["tenants"]["stream%d" % k]["completed"]
            for k in range(STREAMS)]
    assert min(done) >= 0.8 * sum(done) / STREAMS
    assert after["arbiter"]["in_use_bytes"] == 0


QUEUE_SPANS = ("serve.submit", "serve.queue", "serve.lease", "serve.run")


@pytest.mark.parametrize("looking", [True, False],
                         ids=["under-a-live-trace", "nobody-looking"])
def test_the_queue_s_spans_are_recorded_once_a_job(mesh, looking):
    b = bolt.array(_table(rows=4_003), mesh, axis=(0,))
    obs.disable()
    obs.clear()
    if looking:
        obs.enable()
    try:
        with serve.serving(budget_bytes=1 << 30) as sv:
            seen = _run_streams(sv, b, cycles=2)
        rows = obs.totals()
        assert obs.active_count() == 0
    finally:
        obs.disable()
        obs.clear()
    n = STREAMS * 2 * 2
    assert sum(len(answers) for answers in seen) == n
    if not looking:
        assert not set(QUEUE_SPANS) & set(rows)
        return
    # a lease is taken by the jobs the server could estimate: the Q6s
    assert {name: rows[name]["count"] for name in QUEUE_SPANS} == {
        "serve.submit": n, "serve.queue": n, "serve.lease": n // 2,
        "serve.run": n}
    # the wait in the queue is the Futures' own, accepted to started
    waited = sum(f.started_s - f.submitted_s
                 for answers in seen for _, _, f in answers)
    assert rows["serve.queue"]["seconds"] == pytest.approx(waited)


def test_record_lands_an_interval_timed_elsewhere():
    obs.disable()
    obs.clear()
    assert obs.record("probe.wait", 1.0, 1.5) is None    # nobody looking
    assert obs.totals() == {} and obs.spans() == []
    obs.enable()
    try:
        with obs.span("outer"):
            sp = obs.record("probe.wait", 2.0, 2.25, tenant="a")
            assert obs.current().name == "outer"      # on no thread's stack
        assert (sp.t0, sp.t1, sp.pid, sp.path) == (2.0, 2.25, 0,
                                                   ("probe.wait",))
        assert sp.attrs == {"tenant": "a"} and sp in obs.spans()
        row = obs.totals()["probe.wait"]
        assert (row["count"], row["seconds"]) == (1, 0.25)
        assert obs.totals()["outer"]["self_seconds"] == pytest.approx(
            obs.totals()["outer"]["seconds"])
        assert obs.active_count() == 0
    finally:
        obs.disable()
        obs.clear()
