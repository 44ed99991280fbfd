"""A deferred array with more than one deferred consumer is run ONCE
(``BoltArrayTPU._lower_from_shared``): ``ops.fourier``'s coherence and
phase are two maps over one map, and fetching both used to run the whole
per-pixel chain twice.  The pair against the two-program result bit for
bit; that the chain's program runs once; that a chain with ONE consumer
lowers to the program text and the engine key it had at the parent
commit; what is not kept, not donated, and what a dropped handle
changes."""

import gc
import hashlib
import operator

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bolt_tpu as bolt
from bolt_tpu import analysis, engine, ops
from bolt_tpu.tpu import array as tpu_array

from test_series_tuning import sessions, shard_bytes, tuning, T, FREQ

COUNTED = ("shared_parent_runs", "shared_parent_hits", "map_blocks",
           "aot_compiles", "dispatches", "donations", "misses")


def since(before):
    after = engine.counters()
    return {k: after[k] - before[k] for k in COUNTED}


@pytest.fixture(scope="module")
def one_device():
    return jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("k",))


@pytest.fixture(scope="module")
def four_devices():
    return jax.sharding.Mesh(np.asarray(jax.devices()[:4]), ("k",))


@pytest.fixture
def little_hbm():
    """``little_hbm(arr, free)``: tell the blocks rule that the device has
    ``free`` bytes beside the base and the result of the deferred
    ``arr``, until the test ends (a fixture of these tests, not a knob of
    the program: ``tests/test_series_tuning.py :: tight``)."""
    def squeeze(arr, free):
        base = arr._chain[0]
        held = np.prod(base.sharding.shard_shape(base.shape)) \
            * base.dtype.itemsize
        tpu_array._HBM_LIMIT_OVERRIDE = int(held + shard_bytes(
            arr._mesh, arr._aval.shape, arr._aval.dtype, arr._split) + free)
    yield squeeze
    tpu_array._HBM_LIMIT_OVERRIDE = None


def two_programs(make):
    """The pair as it was fetched before: each map forced with the other
    handle gone, so each is the one consumer of its parent and lowers the
    whole chain from the base."""
    before = engine.counters()
    coh = make()[0].toarray()
    ph = make()[1].toarray()
    moved = since(before)
    assert moved["shared_parent_runs"] == moved["shared_parent_hits"] == 0
    return coh, ph, moved


# ---------------------------------------------------------------------
# the pair against the two-program result, to the bit
# ---------------------------------------------------------------------

_LOWERINGS = [
    # shape, key axes, devices, bytes left (None: lowered whole)
    ("whole-1dev", (12, 10, T), (0, 1), 1, None),
    ("whole-4dev", (12, 8, T), (0, 1), 4, None),
    ("blocked-1dev", (12, 10, T), (0, 1), 1, 200000),
    ("blocked-tail-1dev", (7, 11, T), (0, 1), 1, 200000),
    ("blocks-of-two-1dev", (12, 10, T), (0, 1), 1, 60000),
    ("blocked-4dev", (12, 8, T), (0, 1), 4, 200000),
    ("blocked-tail-4dev", (4, 7, T), (0, 1), 4, 100000),
    ("blocked-split1-4dev", (48, T), (0,), 4, 100000),
]


@pytest.mark.parametrize("name,shape,axis,devices,free", _LOWERINGS,
                         ids=[c[0] for c in _LOWERINGS])
def test_the_pair_is_the_two_program_result_to_the_bit(little_hbm, name,
                                                       shape, axis,
                                                       devices, free):
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:devices]), ("k",))
    x = sessions(shape)
    make = lambda: tuning(bolt.array(x, mesh, axis=axis))
    if free is not None:
        little_hbm(make()[0], free)
    want_coh, want_ph, before = two_programs(make)
    coh, ph = make()
    node = coh._links[-1].node
    assert node is ph._links[-1].node and len(node.consumers) == 2
    plan = coh._parent_of(len(coh._links) - 1)
    marked = plan._block_plan(*plan._chain)
    blocks = marked[-1].blocks if marked is not plan._chain[1] else 0
    start = engine.counters()
    got_coh, got_ph = coh.toarray(), ph.toarray()
    moved = since(start)
    assert got_coh.dtype == want_coh.dtype == np.float32
    assert np.array_equal(got_coh, want_coh)
    assert np.array_equal(got_ph, want_ph)
    # the chain once, and a pick each
    assert moved["shared_parent_runs"] == 1
    assert moved["shared_parent_hits"] == 2
    assert moved["dispatches"] == 3 and before["dispatches"] == 2
    if free is None:
        assert blocks == 0 and moved["map_blocks"] == 0
    else:
        assert blocks > 1, name
        if "tail" in name:
            records, block = marked[-1].runs[0]
            assert records % block
        if "two" in name:
            assert marked[-1].block_records == 2
        # one program's blocks, where the two programs ran theirs each
        assert moved["map_blocks"] == blocks
        assert before["map_blocks"] >= 2 * blocks - 2


def test_the_chain_compiles_and_runs_once(one_device):
    x = sessions((6, 5, T), seed=1)
    b = bolt.array(x, one_device, axis=(0, 1))
    coh, ph = tuning(b)
    start = engine.counters()
    coh.toarray()
    first = since(start)
    # the parent's program and the pick's
    assert first["aot_compiles"] == 2 and first["dispatches"] == 2
    assert first["shared_parent_runs"] == 1
    assert first["shared_parent_hits"] == 1
    start = engine.counters()
    ph.toarray()
    second = since(start)
    # a slice of the kept (6, 5, 2) result and nothing over the base
    assert second["aot_compiles"] == 1 and second["dispatches"] == 1
    assert second["shared_parent_runs"] == 0
    assert second["shared_parent_hits"] == 1


def test_a_second_request_runs_the_same_executables(one_device):
    # what the benchmark's window does: a fresh pair over the base a
    # request, nothing compiled after the first (compiles_in_window 0)
    b = bolt.array(sessions((6, 5, T), seed=2), one_device, axis=(0, 1))
    first = [h.toarray() for h in tuning(b)]
    start = engine.counters()
    again = [h.toarray() for h in tuning(b)]
    other = bolt.array(sessions((6, 5, T), seed=3), one_device, axis=(0, 1))
    [h.toarray() for h in tuning(other)]
    moved = since(start)
    assert moved["aot_compiles"] == 0 and moved["misses"] == 0
    assert moved["shared_parent_runs"] == 2     # every request runs it
    assert moved["shared_parent_hits"] == 4
    assert all(np.array_equal(a, b) for a, b in zip(first, again))


# ---------------------------------------------------------------------
# what decides: the live consumers and the parent's size
# ---------------------------------------------------------------------

def plus_one(v):
    return v + 1


def row_sums(v):
    return jnp.sum(v, axis=-1)


def first_two(v):
    return v[..., :2]


def doubled(v):
    return v * 2


def negated(v):
    return -v


def positive(v):
    return v > 0


def keymap(b, func):
    return b.map(func, axis=tuple(range(b.split)))


def test_a_parent_as_large_as_its_base_is_not_kept(one_device):
    x = sessions((6, 5, 64))
    b = bolt.array(x, one_device, axis=(0, 1))
    m = keymap(b, plus_one)                     # (6, 5, 64): the base's size
    p, q = keymap(m, doubled), keymap(m, negated)
    assert len(m._node.consumers) == 2 and p._shared_parent() is None
    start = engine.counters()
    got_p, got_q = p.toarray(), q.toarray()
    moved = since(start)
    assert moved["shared_parent_runs"] == moved["shared_parent_hits"] == 0
    assert moved["dispatches"] == 2 and m._node.kept is None
    assert np.array_equal(got_p, (x + 1) * 2)
    assert np.array_equal(got_q, -(x + 1))
    # one value fewer a record, and it is
    s = keymap(b, first_two)
    p, q = keymap(s, doubled), keymap(s, negated)
    start = engine.counters()
    got_p, got_q = p.toarray(), q.toarray()
    assert since(start)["shared_parent_runs"] == 1
    assert np.array_equal(got_p, x[..., :2] * 2)
    assert np.array_equal(got_q, -x[..., :2])


@pytest.mark.parametrize("when", ["before-any-force", "after-the-first"])
def test_a_consumer_forced_after_the_other_handle_is_dropped(one_device,
                                                             when):
    x = sessions((6, 5, T), seed=4)
    make = lambda: tuning(bolt.array(x, one_device, axis=(0, 1)))
    want_coh, want_ph, _ = two_programs(make)
    coh, ph = make()
    node = ph._links[-1].node
    start = engine.counters()
    if when == "before-any-force":
        del coh
        gc.collect()
        # one live consumer: the chain lowers whole, as it always did
        assert len(node.consumers) == 1 and ph._shared_parent() is None
        got = ph.toarray()
        moved = since(start)
        assert moved["shared_parent_hits"] == 0 and moved["dispatches"] == 1
        assert node.kept is None
    else:
        assert np.array_equal(coh.toarray(), want_coh)
        del coh
        gc.collect()
        # the kept result outlives the handle that ran it
        assert len(node.consumers) == 1 and node.kept is not None
        got = ph.toarray()
        moved = since(start)
        assert moved["shared_parent_runs"] == 1
        assert moved["shared_parent_hits"] == 2
    assert np.array_equal(got, want_ph)


def test_the_kept_result_dies_with_the_handles(one_device):
    import weakref
    b = bolt.array(sessions((6, 5, T), seed=5), one_device, axis=(0, 1))
    coh, ph = tuning(b)
    coh.toarray()
    kept = weakref.ref(ph._links[-1].node.kept)
    assert kept() is not None
    ph.toarray()
    # both forced: each holds its own result and nothing of the parent's
    assert coh._links == ph._links == () and coh._node is None
    gc.collect()
    assert kept() is None


@pytest.mark.parametrize("base_owned", [False, True],
                         ids=["base-held-elsewhere", "base-sole-owned"])
def test_a_kept_result_is_never_donated(one_device, base_owned):
    import weakref
    x = sessions((6, 5, T), seed=6)
    want = np.sum(two_programs(
        lambda: tuning(bolt.array(x, one_device, axis=(0, 1))))[1],
        dtype=np.float64)
    with engine.donation(0):                    # any size may be donated
        b = bolt.array(x, one_device, axis=(0, 1))
        coh, ph = tuning(b)
        base = b._data
        gone = weakref.ref(base)
        del b
        if base_owned:
            del base
        start = engine.counters()
        coh.toarray()
        kept = ph._links[-1].node.kept
        del coh
        # ph's chain may be the base's one owner now, and a sole-owned
        # chain donates its base to a terminal: but the parent's result
        # is kept, so ph reads that, and the base is let go, not re-read
        total = ph.sum()
        moved = since(start)
        assert moved["donations"] == 0 and moved["shared_parent_hits"] == 2
        assert moved["shared_parent_runs"] == 1
        assert ph._chain[0] is kept and (gone() is None) == base_owned
        # re-seated on the kept result, which the node still holds: no
        # later terminal of ph may hand it to XLA either
        assert not tpu_array._chain_donate_ok(ph._chain)
        ph.toarray()
        assert since(start)["donations"] == 0
        assert not kept.is_deleted()
        assert np.allclose(float(total.toarray()), want, rtol=1e-5)


def test_a_sole_owned_chain_with_nothing_kept_still_donates(one_device):
    x = sessions((6, 5, T), seed=10)
    with engine.donation(0):
        b = bolt.array(x, one_device, axis=(0, 1))
        coh, ph = tuning(b)
        del b, coh
        gc.collect()
        start = engine.counters()
        ph.sum()
        moved = since(start)
    assert moved["donations"] == 1 and moved["shared_parent_hits"] == 0


# ---------------------------------------------------------------------
# every way a consumer is forced
# ---------------------------------------------------------------------

_TERMINALS = [
    ("toarray", lambda a: a.toarray()),
    ("sum", lambda a: a.sum().toarray()),
    ("std", lambda a: a.std().toarray()),
    ("reduce", lambda a: a.reduce(operator.add).toarray()),
    ("filter-sum", lambda a: a.filter(positive, axis=(0, 1)).sum().toarray()),
    ("cache", lambda a: a.cache().toarray()),
    ("grandchild", lambda a: keymap(a, doubled).toarray()),
]


@pytest.mark.parametrize("name,take", _TERMINALS,
                         ids=[c[0] for c in _TERMINALS])
def test_whatever_forces_a_consumer_reads_the_kept_result(one_device, name,
                                                          take):
    x = sessions((6, 5, T), seed=7)
    make = lambda: tuning(bolt.array(x, one_device, axis=(0, 1)))
    want = [np.asarray(take(make()[i])) for i in (0, 1)]
    coh, ph = make()
    start = engine.counters()
    got = [np.asarray(take(coh)), np.asarray(take(ph))]
    moved = since(start)
    assert moved["shared_parent_runs"] == 1, name
    assert moved["shared_parent_hits"] == 2
    for g, w in zip(got, want):
        assert g.shape == w.shape
        # a terminal folded into the chain's own program sums in that
        # program's order; over the kept result in the pick's
        assert np.array_equal(g, w) if name in (
            "toarray", "cache", "grandchild") \
            else np.allclose(g, w, rtol=1e-5, atol=1e-6)


def test_a_forced_parent_is_its_consumers_base(one_device):
    x = sessions((6, 5, 64), seed=8)
    b = bolt.array(x, one_device, axis=(0, 1))
    m = keymap(b, row_sums)
    p, q = keymap(m, doubled), keymap(m, negated)
    sums = m.toarray()                          # the parent itself, first
    start = engine.counters()
    got_p, got_q = p.toarray(), q.toarray()
    moved = since(start)
    assert moved["shared_parent_runs"] == 0 and moved["shared_parent_hits"] == 2
    assert np.array_equal(got_p, sums * 2) and np.array_equal(got_q, -sums)
    # and the other way about: a consumer's force serves the parent
    m = keymap(b, row_sums)
    p, q = keymap(m, doubled), keymap(m, negated)
    p.toarray()
    start = engine.counters()
    assert np.array_equal(m.toarray(), sums)
    assert since(start)["dispatches"] == 0


def test_a_forced_parent_that_gives_its_buffer_away_leaves_the_base(
        one_device):
    x = sessions((6, 5, 64), seed=11)
    b = bolt.array(x, one_device, axis=(0, 1))
    m = keymap(b, first_two)
    p, q = keymap(m, doubled), keymap(m, negated)
    m.toarray()
    kept = p._links[-1].node.kept
    assert kept is m._concrete
    m.swap((0,), (0,), donate=True)             # m's buffer is XLA's now
    assert kept.is_deleted()
    start = engine.counters()
    got_p, got_q = p.toarray(), q.toarray()
    # run again from the base, once for the two of them
    assert since(start)["shared_parent_runs"] == 1
    assert np.array_equal(got_p, x[..., :2] * 2)
    assert np.array_equal(got_q, -x[..., :2])


def test_a_consumer_hung_on_a_reseated_chain_extends_the_new_chain(
        one_device):
    x = sessions((6, 5, 64), seed=9)
    b = bolt.array(x, one_device, axis=(0, 1))
    m = keymap(b, first_two)
    p, q = keymap(m, doubled), keymap(m, negated)
    early = keymap(p, plus_one)                 # hung before p is re-seated
    p.sum()                                     # re-seats p; p stays deferred
    assert p.deferred and p._chain[0] is m._node.kept
    late = keymap(p, plus_one)
    want = x[..., :2] * 2 + 1
    assert np.array_equal(late.toarray(), want)
    assert np.array_equal(early.toarray(), want)
    assert np.array_equal(q.toarray(), -x[..., :2])


def test_consumers_forced_from_many_threads_agree(one_device):
    """The node is shared by whatever threads hold its consumers
    (``bolt_tpu.serve`` runs pipelines on workers): a parent that two of
    them run at once is run twice, never wrongly, and the count of live
    consumers comes back to nothing."""
    import sys
    import threading
    x = sessions((6, 5, 64), seed=12)
    b = bolt.array(x, one_device, axis=(0, 1))
    m = keymap(b, first_two)
    scales = list(range(2, 18))
    consumers = [keymap(m, bolt.utils.with_operands(
        operator.mul, np.float32(k))) for k in scales]
    node = consumers[0]._links[-1].node
    got, errors = {}, []

    def force(i):
        try:
            got[i] = consumers[i].toarray()
        except Exception as exc:                # reported below
            errors.append(exc)
    start = engine.counters()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=force, args=(i,))
                   for i in range(len(scales))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in threads)
    moved = since(start)
    assert 1 <= moved["shared_parent_runs"] <= len(scales)
    assert moved["shared_parent_hits"] == len(scales)
    for i, k in enumerate(scales):
        assert np.array_equal(got[i], x[..., :2] * np.float32(k))
    del consumers
    gc.collect()
    assert not node.consumers


def test_explain_names_the_shared_parent(one_device, little_hbm):
    b = bolt.array(sessions((12, 10, T)), one_device, axis=(0, 1))
    coh, ph = tuning(b)
    little_hbm(coh, 200000)
    start = engine.counters()
    rep = analysis.check(coh)
    text = analysis.explain(coh)
    assert since(start)["dispatches"] == 0
    stage = rep.stages[3]                       # fourier's map, of 4
    # behind a detrend, it says so first (PR 49)
    centred = ("centred by its parent: no pass for the mean, one reader "
               "of the parent's result; ")
    assert stage.note == (centred + "shared parent: materialised once for "
                          "2 consumers")
    assert "materialised once for 2 consumers" in str(text)
    # the forecast blocks are the parent's program's
    note, = [d for d in rep.diagnostics if d.code == "BLT018"]
    parent = coh._parent_of(len(coh._links) - 1)
    plan = parent._block_plan(*parent._chain)[-1]
    assert note.stage == 3
    assert "blocked: %d blocks" % plan.blocks in note.message
    coh.toarray()
    assert analysis.check(ph).stages[3].note == (
        centred + "shared parent: materialised once, its result kept for "
        "the 1 still deferred")
    # one consumer: nothing to say
    alone = tuning(b)[0]
    assert not any("shared parent" in (s.note or "")
                   for s in analysis.check(alone).stages)


def test_local_mode_is_as_it_was():
    x = sessions((30, T))
    coh, ph = tuning(bolt.array(x))
    assert coh.toarray().shape == ph.toarray().shape == (30,)


# ---------------------------------------------------------------------
# one consumer: the program text and the engine key of the parent commit
# ---------------------------------------------------------------------

DATE, QTY, PRICE, DISC = 0, 1, 2, 3


def q6_pred(r):
    return ((r[DATE] >= 731) & (r[DATE] < 1096) & (r[DISC] >= 5)
            & (r[DISC] <= 7) & (r[QTY] < 24))


def q6_revenue(r):
    return r[PRICE] * r[DISC]


def _lowered(run, arg):
    """``{stable engine key: sha256 of the lowered text}`` of the programs
    ``run()`` adds to the engine, each lowered for ``arg``."""
    engine.clear()
    run()
    with engine._LOCK:
        entries = dict(engine._CACHE)
    return {
        hashlib.sha256(engine._stable_key(key).encode()).hexdigest()[:16]:
        hashlib.sha256(entry.lower(arg).as_text().encode()).hexdigest()
        for key, entry in entries.items()}


def single_consumer_program(mesh, cell):
    """One of the programs of the three cells nearest this code, at toy
    size: ``reduce``'s ``map(v + 1).sum()``, ``q1q6``'s Q6 product behind
    its filter, a ``followups`` window under a statistic; or one of two
    chains of maps over maps, which are hung on their parents' nodes one
    consumer each: three light maps under a sum, and the ``tuning``
    cell's whole chain taken by ONE of ``fourier``'s handles, the other
    dropped."""
    stack = bolt.array(np.zeros((48, 4, 6, 6), np.float32), mesh, axis=(0,))
    table = bolt.array(np.zeros((96, 7), np.float32), mesh, axis=(0,))
    series = bolt.array(np.zeros((12, 8, T), np.float32), mesh, axis=(0, 1))
    every = (0, 1, 2, 3)
    base, run = {
        "three-maps": (stack, lambda: stack.map(plus_one).map(plus_one).map(
            plus_one).sum(axis=every).toarray()),
        "tuning-one-handle": (series, lambda: tuning(series)[0].toarray()),
        "v+1": (stack, lambda: stack.map(plus_one).sum(axis=every).toarray()),
        "q1q6-product": (table, lambda: table.filter(q6_pred).map(
            q6_revenue).sum().toarray()),
        "followups-window": (stack,
                             lambda: stack[8:24].std(axis=every).toarray()),
    }[cell]
    return _lowered(run, base._data)


# what they read as at the parent commit (85d0d52, jax 0.9.0): printed
# there by ``single_consumer_program`` under ``tests/conftest.py``.  The
# tuning chain's text is PR 49's, under the engine key it has: that PR
# spelt ``detrend``'s fit element-wise and took the mean's pass out of a
# ``fourier`` behind it, the chain's last two maps (PR 44's text, with
# the FFT out of ``fourier``: 3ca6f753dc6fd6aa / b0cd407b4582c347...).
# The followups window's text is PR 61's, under the engine key it had:
# ``std`` of real floating data is traced as one pass of shifted moments
# (``tpu/moments.py``; ``jnp.std``'s two passes read 5dc44a0267fb0ce6...)
PARENT_PROGRAMS = {
    "three-maps": {
        "4e42b4d2a6a0b9d7":
        "6a407a87d54afd055b4c18d333db192d63f5faf22062374a7b509bdef320a1a2"},
    "tuning-one-handle": {
        "cd1ca571a63bba88":
        "d0768b8fd3abda321929786adf27ebcadcd288580e194c175323763cb7d44187"},
    "v+1": {"ccbde19964e72a4a":
            "26d723e7fcc2eb9405883b047b615105f311451d5688368ffc081acea2001d31"},
    "q1q6-product": {
        "3aa85cc3a3983c10":
        "d1dead2a598c914203bae4a98f76e3c8fe78287c1ca09f91d92753d53b040028"},
    "followups-window": {
        "5d663718c29e772c":
        "c22c92aa7bb7cb3e770849ec5d40aefc8541c809d00c2cad7c2029fde63b3838"},
}


@pytest.mark.skipif(jax.__version__ != "0.9.0",
                    reason="the recorded text is jax 0.9.0's")
@pytest.mark.parametrize("cell", sorted(PARENT_PROGRAMS))
def test_a_chain_with_one_consumer_lowers_as_it_did(four_devices, cell):
    start = engine.counters()
    got = single_consumer_program(four_devices, cell)
    assert got and got == PARENT_PROGRAMS[cell]
    assert since(start)["shared_parent_hits"] == 0
