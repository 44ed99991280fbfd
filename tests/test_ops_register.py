"""``bolt_tpu.ops.register``: the per-record functions against planted
displacements and against their NumPy spelling, and the two calls on the
three kinds of array (local, resident, streamed)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bolt_tpu as bolt
from bolt_tpu import engine, obs
from bolt_tpu.ops import register
from bolt_tpu.utils import code_token, with_operands


@pytest.fixture(scope="module")
def mesh():
    return jax.sharding.Mesh(np.array(jax.devices()), ("k",))


def scene(seed, h, w, margin):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 1 << 12, size=(h + 2 * margin,
                                          w + 2 * margin)).astype(np.float32)


def crop(sc, h, w, margin, off):
    return sc[margin + off[0]:margin + off[0] + h,
              margin + off[1]:margin + off[1] + w]


PLANTED = [(0, 0), (1, 0), (0, 1), (-1, 0), (0, -1), (3, -2), (-4, 5),
           (6, 6), (-6, -6), (2, -7)]


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("off", PLANTED)
def test_a_planted_displacement_is_found_with_its_sign(seed, off):
    """A frame that shows the reference's content ``off`` further along the
    scene has its content ``-off`` from the reference's: ``d = -off``, by
    the NumPy spelling and by the traced one alike."""
    h, w, m = 24, 40, 8
    sc = scene(seed, h, w, m)
    ref, frame = crop(sc, h, w, m, (0, 0)), crop(sc, h, w, m, off)
    want = np.asarray([-off[0], -off[1]], np.int32)
    got = register.crosscorr_shift(frame, ref)
    assert isinstance(got, np.ndarray) and got.dtype == np.int32
    assert np.array_equal(got, want)
    traced = jax.jit(register.crosscorr_shift)(frame, ref)
    assert traced.dtype == jnp.int32 and np.array_equal(traced, want)
    # and the shift takes it out: the registered frame is the reference's
    # content wherever no edge was repeated
    back = register.shift(frame, got)
    inner = (slice(8, h - 8), slice(8, w - 8))
    assert np.array_equal(back[inner], ref[inner])


@pytest.mark.parametrize("rolled,want", [
    ((12, 0), (12, 0)), ((13, 0), (-11, 0)), ((-11, 0), (-11, 0)),
    ((0, 20), (0, 20)), ((0, 21), (0, -19)), ((23, 39), (-1, -1)),
    ((12, 20), (12, 20)), ((-12, -20), (12, 20)), ((13, -19), (-11, -19))])
def test_the_cyclic_adjustment(rolled, want):
    """A reference rolled by ``d`` is a frame displaced by ``d`` exactly
    (cyclic): a component above half its axis names the shift the other
    way round (``n // 2`` itself is kept, one more is not), so every
    component lies in ``[-(n - 1) // 2, n // 2]``."""
    h, w = 24, 40
    ref = scene(9, h, w, 0)
    frame = np.roll(ref, rolled, axis=(0, 1))
    got = register.crosscorr_shift(frame, ref)
    assert np.array_equal(got, np.asarray(want, np.int32))
    assert np.array_equal(jax.jit(register.crosscorr_shift)(frame, ref), got)


def test_ties_go_to_the_first_maximum_in_c_order():
    h, w = 8, 16
    ref = np.zeros((h, w), np.float32)
    ref[0, 0] = 1.0
    flat = np.ones((h, w), np.float32)          # every shift ties
    assert np.array_equal(register.crosscorr_shift(flat, ref), [0, 0])
    two = np.zeros((h, w), np.float32)
    two[2, 9], two[1, 12] = 5.0, 5.0            # two equal peaks
    want = np.asarray([1, 12 - w], np.int32)    # (1, 12) comes first
    assert np.array_equal(register.crosscorr_shift(two, ref), want)
    assert np.array_equal(jax.jit(register.crosscorr_shift)(two, ref), want)


@pytest.mark.parametrize("delta", [(0, 0), (2, 0), (-2, 0), (0, 3), (0, -3),
                                   (5, -4), (-7, 9), (40, 1), (-1, -70)])
def test_the_shift_repeats_the_nearest_edge(delta):
    """Whole pixels, both signs, and past the frame: every value of the
    result is a value of the frame, the nearest edge's where the shift
    reads past one; the two spellings agree to the bit."""
    h, w = 12, 20
    frame = scene(3, h, w, 0)
    got = register.shift(frame, np.asarray(delta, np.int32))
    rows = np.clip(np.arange(h) + delta[0], 0, h - 1)
    cols = np.clip(np.arange(w) + delta[1], 0, w - 1)
    assert np.array_equal(got, frame[np.ix_(rows, cols)])
    traced = jax.jit(register.shift)(frame, jnp.asarray(delta, jnp.int32))
    assert np.array_equal(np.asarray(traced), got)
    if delta[0] > 0:
        assert np.array_equal(got[-1], got[-min(delta[0], h - 1) - 1])


def test_with_operands_is_a_callable_keyed_by_avals():
    def f(v, a):
        return v + a
    one = with_operands(f, np.ones(3, np.float32))
    two = with_operands(f, np.zeros(3, np.float32))
    other = with_operands(f, np.zeros(4, np.float32))
    assert np.array_equal(one(np.ones(3, np.float32)), [2, 2, 2])
    assert one.key() == two.key() != other.key()
    assert one != two and hash(one) != hash(two)    # an object's identity
    assert one.__name__ == "f" and code_token(one) == code_token(f)
    with pytest.raises(TypeError, match="callable"):
        with_operands(3, np.ones(3))
    with pytest.raises(TypeError, match="array"):
        with_operands(f, [1, 2, 3])


def session(seed, frames=40, h=16, w=24, margin=6, top=4):
    rng = np.random.default_rng(seed)
    sc = scene(seed, h, w, margin)
    offs = rng.integers(-top, top + 1, size=(frames, 2))
    data = np.stack([crop(sc, h, w, margin, o) for o in offs])
    return data, crop(sc, h, w, margin, (0, 0)), -offs.astype(np.int32)


@pytest.mark.parametrize("seed", [0, 5])
def test_fit_and_transform_on_the_three_kinds_of_array(mesh, seed):
    data, ref, planted = session(seed)
    local = bolt.array(data)
    disp = register.fit(local, ref).toarray()
    assert disp.dtype == np.int32 and np.array_equal(disp, planted)
    want = register.transform(local, disp).toarray()
    for t in (0, 7, 39):
        assert np.array_equal(want[t], register.shift(data[t], disp[t]))

    resident = bolt.array(data, mesh)
    assert np.array_equal(register.fit(resident, ref).toarray(), disp)
    assert np.array_equal(register.transform(resident, disp).toarray(), want)

    def source():
        return bolt.fromcallback(lambda i: data[tuple(i)], data.shape, mesh,
                                 dtype=np.float32, chunks=12)
    c0 = engine.counters()
    got = register.fit(source(), ref)
    assert got._stream is not None and got.shape == (40, 2)
    assert np.array_equal(got.toarray(), disp)
    series = register.transform(source(), disp).swap((0,), (0, 1))
    assert series._stream is not None
    assert np.array_equal(series.toarray(), np.transpose(want, (1, 2, 0)))
    c1 = engine.counters()
    assert c1["stream_collect_slabs"] - c0["stream_collect_slabs"] == 4
    assert c1["stream_keyed_slabs"] - c0["stream_keyed_slabs"] == 4
    # the whole pipeline against mode='local', the displacements a bolt
    # array as fit returned them
    piped = register.transform(source(), register.fit(source(), ref))
    assert np.array_equal(piped.swap((0,), (0, 1)).toarray(),
                          np.transpose(want, (1, 2, 0)))


def test_a_second_session_runs_the_same_executables(mesh):
    """Another reference image and other displacements are other OPERANDS
    of the programs the first session compiled: resident and streamed."""
    data, ref, _ = session(11)

    def source():
        return bolt.fromcallback(lambda i: data[tuple(i)], data.shape, mesh,
                                 dtype=np.float32, chunks=10)
    resident = bolt.array(data, mesh)

    def both(image, bump):
        d = register.fit(source(), image).toarray()
        assert np.array_equal(register.fit(resident, image).toarray(), d)
        a = register.transform(source(), d + bump).swap((0,), (0, 1))
        b = register.transform(resident, d + bump)
        return d, a.toarray(), b.toarray()
    d1, a1, b1 = both(ref, 0)
    mark = obs.clock()
    c0 = engine.counters()
    d2, a2, b2 = both(np.roll(ref, 2, axis=1), 1)
    c1 = engine.counters()
    assert not [r for r in engine.compile_log() if r["t0"] >= mark]
    assert c1["aot_compiles"] == c0["aot_compiles"]
    assert c1["misses"] == c0["misses"] and c1["fallbacks"] == c0["fallbacks"]
    assert not np.array_equal(d1, d2)
    local = bolt.array(data)
    assert np.array_equal(d2, register.fit(local, np.roll(ref, 2, 1)).toarray())
    want = register.transform(local, d2 + 1).toarray()
    assert np.array_equal(b2, want)
    assert np.array_equal(a2, np.transpose(want, (1, 2, 0)))


def test_the_calls_refuse_what_is_not_frames(mesh):
    data, ref, disp = session(2)
    b = bolt.array(data, mesh)
    with pytest.raises(ValueError, match="reference image has shape"):
        register.fit(b, ref[:, :-1])
    with pytest.raises(ValueError, match="displacements are integers"):
        register.transform(b, disp[:-1])
    with pytest.raises(ValueError, match="displacements are integers"):
        register.transform(b, disp.astype(np.float32))
    with pytest.raises(ValueError, match="2-d frames"):
        register.fit(bolt.array(data, mesh, axis=(0, 1)), ref)


def test_the_calls_are_spans_on_the_callers_thread(mesh):
    from bolt_tpu import obs
    data, ref, disp = session(4)
    obs.clear()
    obs.enable()
    try:
        b = bolt.array(data, mesh)
        register.fit(b, ref)
        register.transform(b, disp)
        row = obs.totals()["ops.register"]
    finally:
        obs.disable()
        obs.clear()
    assert row["count"] == 2 and row["seconds"] > 0
