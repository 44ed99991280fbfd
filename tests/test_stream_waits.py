"""The streamed pipeline's WAITS on the tracer (PR 48): who waits for whom.

* ``stream.wait.slab`` — the consumer inside ``_IngestPool.next``, starved
  of uploads, one span a call, in ``execute`` and in the swap/collect
  resolver alike;
* ``stream.wait.ring`` — an INGESTING thread with no ring permit to work
  under: a pool worker with no job to take (the dispenser hands one out the
  moment it holds a permit), an iterator's one thread in front of its pull;
* ``stream.dispatch`` — the compiled slab program's call alone, and in the
  resolver a ``stream.sync`` a slab for the confirm of a call dispatched
  earlier (``slab=`` names it; PR 56).

No test here holds a span against a wall-clock threshold (ROADMAP D9): a
span's seconds are compared with another's by a wide factor, under a delay
the test itself injects."""

import time

import numpy as np
import pytest

import bolt_tpu as bolt
from bolt_tpu import obs, stream

pytestmark = pytest.mark.obs

SLABS = 8
SHAPE = (SLABS * 4, 6, 5)
NEW = ("stream.wait.slab", "stream.wait.ring", "stream.dispatch")


@pytest.fixture(autouse=True)
def _tracer_reset():
    obs.disable()
    obs.clear()
    yield
    obs.disable()
    obs.clear()


def data():
    return np.arange(np.prod(SHAPE), dtype=np.float32).reshape(SHAPE)


def plus_one(v):
    return v + 1


def halves(v):
    return v.sum(axis=1) * 0.5


def source(mesh, delay=0.0):
    """``SLABS`` slabs of four records from a loader that takes ``delay``
    seconds a slab."""
    x = data()

    def load(index):
        if delay:
            time.sleep(delay)
        return x[tuple(index)]
    return bolt.fromcallback(load, SHAPE, mesh, dtype=x.dtype, chunks=4)


# consumer -> (the pass, its answer from the data, the run's span)
def _execute(b):
    return np.asarray(b.map(plus_one).sum().toarray())


def _swap(b):
    return np.asarray(b.swap((0,), (0,)).cache().toarray())


def _collect(b):
    return np.asarray(b.map(halves).cache().toarray())


PASSES = {
    "execute": (_execute, lambda x: (x + 1).sum(axis=0), "stream.run"),
    "swap": (_swap, lambda x: x.transpose(1, 0, 2), "stream.shuffle"),
    "collect": (_collect, lambda x: x.sum(axis=2) * 0.5, "stream.collect"),
}


def traced(mesh, consumer, delay=0.0, make=source):
    """One pass of ``consumer`` under the tracer, its programs compiled
    by a pass before it; returns ``(spans, totals)``."""
    run, want, _ = PASSES[consumer]
    run(make(mesh))
    obs.clear()
    obs.enable()
    try:
        got = run(make(mesh, delay))
        assert obs.active_count() == 0
        return obs.spans(), obs.totals()
    finally:
        obs.disable()
        assert np.array_equal(got, want(data()))


def seconds(totals, name):
    return totals.get(name, {"seconds": 0.0})["seconds"]


def named(spans, name):
    return [sp for sp in spans if sp.name == name]


def the_run(spans, consumer):
    run, = named(spans, PASSES[consumer][2])
    return run


# ----------------------------------------------------------------------
# (a) a slow loader: the consumer is starved, the pool is not held back
# ----------------------------------------------------------------------

@pytest.mark.parametrize("consumer", ["execute", "swap", "collect"])
def test_a_slow_loader_starves_the_consumer_and_not_the_pool(mesh, consumer):
    with stream.uploaders(1):
        spans, totals = traced(mesh, consumer, delay=0.03)
    run = the_run(spans, consumer)
    starved = seconds(totals, "stream.wait.slab")
    assert starved >= 0.5 * run.duration
    assert 4 * seconds(totals, "stream.wait.ring") <= starved
    assert 4 * seconds(totals, "stream.dispatch") <= starved


# ----------------------------------------------------------------------
# (b) a slow consumer: the pool waits for the ring, under the run's span
# ----------------------------------------------------------------------

@pytest.fixture
def slow_consumer(monkeypatch):
    """A delay at the consumer's own chaos seams, in front of every slab's
    dispatch."""
    hit = stream._chaos.hit

    def slow(point):
        if point in ("stream.dispatch", "stream.shuffle"):
            time.sleep(0.03)
        return hit(point)
    monkeypatch.setattr(stream._chaos, "hit", slow)


@pytest.mark.parametrize("consumer", ["execute", "swap", "collect"])
def test_a_slow_consumer_holds_the_pool_back(mesh, consumer, slow_consumer):
    # the smallest ring the scopes give: one worker's hand and one more
    with stream.uploaders(1), stream.prefetch(1):
        spans, totals = traced(mesh, consumer)
    run = the_run(spans, consumer)
    waits = named(spans, "stream.wait.ring")
    # a job a slab and the pill that ends the worker
    assert len(waits) == SLABS + 1
    assert all(sp.pid == run.sid and sp.rid == run.rid for sp in waits)
    assert all(sp.tid != run.tid for sp in waits)
    assert {sp.tname for sp in waits} == {"bolt-stream-upload-0"}
    assert {sp.attrs["worker"] for sp in waits} == {0}
    # the job the wait ended with names its slab; the pill names none
    assert sorted(sp.attrs["slab"] for sp in waits if "slab" in sp.attrs) \
        == list(range(SLABS))
    assert seconds(totals, "stream.wait.ring") \
        > seconds(totals, "stream.wait.slab")
    # the consumer's wait is its own thread's, under the run's span too
    starved = named(spans, "stream.wait.slab")
    assert all(sp.pid == run.sid and sp.tid == run.tid for sp in starved)


# ----------------------------------------------------------------------
# one stream.wait.slab a call of the pool, in slab order
# ----------------------------------------------------------------------

@pytest.mark.parametrize("consumer", sorted(PASSES))
def test_the_consumer_waits_once_a_slab_and_once_for_the_end(mesh, consumer):
    spans, totals = traced(mesh, consumer)
    waits = sorted(named(spans, "stream.wait.slab"), key=lambda sp: sp.t0)
    assert [sp.attrs.get("slab") for sp in waits] \
        == list(range(SLABS)) + [None]
    assert totals["stream.dispatch"]["count"] == SLABS
    assert [sp.attrs["slab"] for sp in sorted(
        named(spans, "stream.dispatch"), key=lambda sp: sp.t0)] \
        == list(range(SLABS))


# ----------------------------------------------------------------------
# (c) the resolver's stream.compute: a call a compute, a block a slab
# ----------------------------------------------------------------------

@pytest.mark.parametrize("consumer", ["swap", "collect"])
def test_the_resolvers_compute_is_one_call_and_one_block_a_slab(mesh,
                                                                consumer):
    """Since PR 56 the block is the CONFIRM of a call dispatched earlier:
    one ``stream.dispatch`` a ``stream.compute``, one ``stream.sync`` a
    slab over the run, each naming the slab it confirms, in slab order,
    under a later slab's compute, between two computes or in the drain."""
    spans, totals = traced(mesh, consumer)
    run = the_run(spans, consumer)
    computes = named(spans, "stream.compute")
    assert len(computes) == SLABS
    calls = {}
    for csp in computes:
        kids = [sp for sp in spans if sp.pid == csp.sid
                and sp.name.startswith("stream.")]
        call, = [sp for sp in kids if sp.name == "stream.dispatch"]
        assert call.attrs["slab"] == csp.attrs["slab"]
        assert csp.t0 <= call.t0 and call.t1 <= csp.t1
        # whatever else the compute holds is a confirm, after its call,
        # of this slab or an earlier one
        for sp in kids:
            if sp is not call:
                assert sp.name == "stream.sync" and call.t1 <= sp.t0
                assert sp.attrs["slab"] <= csp.attrs["slab"]
        places = [sp for sp in spans if sp.pid == call.sid
                  and sp.name == "stream.collect.place"]
        assert len(places) == (1 if consumer == "collect" else 0)
        calls[csp.attrs["slab"]] = call
    assert ("stream.collect.place" in totals) == (consumer == "collect")
    blocks = sorted(named(spans, "stream.sync"), key=lambda sp: sp.t0)
    assert totals["stream.sync"]["count"] == SLABS
    assert [sp.attrs for sp in blocks] \
        == [{"slabs": 1, "shuffle": True, "slab": g} for g in range(SLABS)]
    by_sid = {sp.sid: sp for sp in computes}
    for sp in blocks:
        # a block lies after its own slab's call, on the consumer's
        # thread, under a compute or under the run itself
        assert calls[sp.attrs["slab"]].t1 <= sp.t0 and sp.tid == run.tid
        assert sp.pid == run.sid or sp.pid in by_sid
    assert seconds(totals, "stream.dispatch") \
        <= seconds(totals, "stream.compute")


def test_executes_call_lies_inside_its_compute_and_its_syncs_do_not(mesh):
    spans, totals = traced(mesh, "execute")
    run = the_run(spans, "execute")
    computes = {sp.sid: sp for sp in named(spans, "stream.compute")}
    calls = named(spans, "stream.dispatch")
    assert len(calls) == len(computes) == SLABS
    assert sorted(sp.pid for sp in calls) == sorted(computes)
    # the window's confirmations stay where they were: under the run
    syncs = named(spans, "stream.sync")
    assert syncs and all(sp.pid == run.sid for sp in syncs)
    assert all("shuffle" not in sp.attrs for sp in syncs)


def test_a_codecs_decode_span_holds_the_call(mesh):
    with stream.codec("delta-f32"):
        spans, _ = traced(mesh, "execute")
    decodes = {sp.sid for sp in named(spans, "stream.decode")}
    calls = named(spans, "stream.dispatch")
    assert len(decodes) == len(calls) == SLABS
    assert {sp.pid for sp in calls} == decodes


# ----------------------------------------------------------------------
# (d) the consumer's account covers the run
# ----------------------------------------------------------------------

@pytest.mark.parametrize("consumer", sorted(PASSES))
def test_the_consumers_account_covers_the_run(mesh, consumer):
    with stream.uploaders(1):
        spans, _ = traced(mesh, consumer, delay=0.03)
    run = the_run(spans, consumer)
    account = [sp for sp in spans if sp.pid == run.sid and sp.tid == run.tid
               and sp.name in ("stream.wait.slab", "stream.compute",
                               "stream.sync")]
    covered = sum(sp.duration for sp in account)
    assert 0.9 * run.duration <= covered <= run.duration
    # disjoint, in order, on one thread
    account.sort(key=lambda sp: sp.t0)
    assert all(a.t1 <= b.t0 for a, b in zip(account, account[1:]))


# ----------------------------------------------------------------------
# (e) tracing off: nothing recorded, nothing allocated at the new sites
# ----------------------------------------------------------------------

@pytest.mark.parametrize("consumer", sorted(PASSES))
def test_off_the_new_sites_hand_back_none(mesh, consumer, monkeypatch):
    run, want, _ = PASSES[consumer]
    begin = stream._obs.begin
    seen = []

    def spy(name, parent=None, **attrs):
        sp = begin(name, parent=parent, **attrs)
        seen.append((name, sp))
        return sp
    monkeypatch.setattr(stream._obs, "begin", spy)
    assert not obs.enabled()
    assert np.array_equal(run(source(mesh)), want(data()))
    assert obs.totals() == {} and obs.spans() == []
    assert obs.active_count() == 0
    for name in NEW:
        at_site = [sp for n, sp in seen if n == name]
        assert len(at_site) >= SLABS and all(sp is None for sp in at_site)
    assert all(sp is None for _, sp in seen)


# ----------------------------------------------------------------------
# (f) an iterator's one thread waits for the ring in front of its pull
# ----------------------------------------------------------------------

def blocks(mesh, delay=0.0):
    x = data()

    def pull():
        for blk in np.array_split(x, SLABS):
            if delay:
                time.sleep(delay)
            yield blk
    return bolt.fromiter(pull(), SHAPE, mesh, dtype=x.dtype)


@pytest.mark.parametrize("consumer", sorted(PASSES))
def test_an_iterators_thread_records_its_wait_for_the_ring(
        mesh, consumer, slow_consumer):
    with stream.prefetch(1):
        spans, totals = traced(mesh, consumer, make=blocks)
    run = the_run(spans, consumer)
    waits = sorted(named(spans, "stream.wait.ring"), key=lambda sp: sp.t0)
    # a permit a block, and one for the pull that found the end
    assert [sp.attrs["slab"] for sp in waits] == list(range(SLABS + 1))
    assert all(sp.pid == run.sid and sp.tid != run.tid for sp in waits)
    assert {sp.tname for sp in waits} == {"bolt-stream-prefetch"}
    assert seconds(totals, "stream.wait.ring") \
        > seconds(totals, "stream.wait.slab")
    # the wait ends before the pull begins: it is no part of the ingest
    ingests = sorted(named(spans, "stream.ingest"), key=lambda sp: sp.t0)
    assert all(w.t1 <= i.t0 for w, i in zip(waits, ingests))


def test_a_slow_iterator_starves_the_consumer(mesh):
    spans, totals = traced(mesh, "execute", delay=0.03, make=blocks)
    run = the_run(spans, "execute")
    assert seconds(totals, "stream.wait.slab") >= 0.5 * run.duration
    assert 4 * seconds(totals, "stream.wait.ring") \
        <= seconds(totals, "stream.wait.slab")


# ----------------------------------------------------------------------
# a fault leaves no wait open
# ----------------------------------------------------------------------

@pytest.mark.parametrize("consumer", sorted(PASSES))
def test_a_failing_loader_leaves_no_span_open(mesh, consumer):
    x = data()

    def load(index):
        if index[0].start >= 8:
            raise OSError("disk gone")
        return x[tuple(index)]
    obs.enable()
    with pytest.raises(OSError, match="disk gone"):
        PASSES[consumer][0](bolt.fromcallback(load, SHAPE, mesh,
                                              dtype=x.dtype, chunks=4))
    obs.disable()
    assert obs.active_count() == 0
    waits = named(obs.spans(), "stream.wait.slab")
    assert waits and all(sp.t1 is not None for sp in waits)
