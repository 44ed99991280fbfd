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


# ---------------------------------------------------------------------
# the surface as DFT matrix products (ISSUE 54): traced float32 frames of
# 128 to ``register.N_MAX`` a side; the frames above (24 x 40, 8 x 16)
# keep XLA's FFT and keep guarding it
# ---------------------------------------------------------------------

ON_MXU = [(128, 128), (128, 256), (192, 160), (129, 131)]


@pytest.mark.parametrize("off", [(0, 0), (1, 0), (0, -1), (3, -2), (-4, 5),
                                 (-6, -6), (2, -7)])
@pytest.mark.parametrize("h,w", ON_MXU)
def test_a_planted_displacement_is_found_by_the_products(h, w, off):
    """Powers of two, a non-square frame, axes that are no power of two
    and odd ones: ``d = -off`` by the NumPy spelling and by the traced
    one alike."""
    m = 8
    sc = scene(h + w, h, w, m)
    ref, frame = crop(sc, h, w, m, (0, 0)), crop(sc, h, w, m, off)
    assert register._by_products((h, w), frame.dtype)
    want = np.asarray([-off[0], -off[1]], np.int32)
    assert np.array_equal(register.crosscorr_shift(frame, ref), want)
    traced = jax.jit(register.crosscorr_shift)(frame, ref)
    assert traced.dtype == jnp.int32 and np.array_equal(traced, want)


@pytest.mark.parametrize("rolled,want", [
    ((64, 0), (64, 0)), ((65, 0), (-63, 0)), ((-63, 0), (-63, 0)),
    ((0, 128), (0, 128)), ((0, 129), (0, -127)), ((127, 255), (-1, -1)),
    ((64, 128), (64, 128)), ((-64, -128), (64, 128)),
    ((65, -127), (-63, -127))])
def test_the_cyclic_adjustment_of_the_products(rolled, want):
    h, w = 128, 256
    ref = scene(10, h, w, 0)
    frame = np.roll(ref, rolled, axis=(0, 1))
    got = register.crosscorr_shift(frame, ref)
    assert np.array_equal(got, np.asarray(want, np.int32))
    assert np.array_equal(jax.jit(register.crosscorr_shift)(frame, ref), got)


def moving_scene(seed, frames, h, w, margin=16, walk=12):
    """Frames of the benchmark's kind (``benchmark/operands/motion.py``):
    crops of one scene (a resting level of 1,500, Gaussian cell bodies up
    to 4,000 bright, texture up to 200) at offsets within ``walk`` pixels,
    noise within 150 a frame; integers, float32."""
    rng = np.random.default_rng(seed)
    rows, cols = h + 2 * margin, w + 2 * margin
    u, v = np.arange(rows)[:, None], np.arange(cols)[None, :]
    sc = np.full((rows, cols), 1500.0)
    for _ in range(400 * h * w // (512 * 512)):
        cu, cv = rng.uniform(0, rows), rng.uniform(0, cols)
        s = rng.uniform(2, 6)
        sc += rng.uniform(400, 4000) * np.exp(
            -((u - cu) ** 2 + (v - cv) ** 2) / (2 * s * s))
    sc = np.minimum(np.rint(sc + rng.integers(0, 201, size=sc.shape)), 16000)
    offs = rng.integers(-walk, walk + 1, size=(frames, 2))
    out = np.stack([crop(sc, h, w, margin, o) for o in offs])
    return (out + rng.integers(-150, 151, size=out.shape)).astype(np.float32)


@pytest.mark.parametrize("seed", [0, 1])
def test_the_products_surface_is_float64s_to_a_tenth_of_the_regret(seed):
    """512 x 512, the cell's frames: the traced surface against NumPy in
    float64 within 1e-5 of its largest value (the regret's limit is 1e-4;
    a dense DFT sums 512 products a value and reads 2-6e-6 here, XLA's FFT
    6e-7), and the displacement float64's own."""
    h = w = 512
    data = moving_scene(seed, 3, h, w)
    ref = data.mean(axis=0, dtype=np.float64).astype(np.float32)
    want = np.fft.irfft2(np.fft.rfft2(data.astype(np.float64))
                         * np.conj(np.fft.rfft2(ref.astype(np.float64))),
                         s=(h, w))
    got = jax.jit(jax.vmap(register._surface_by_products,
                           in_axes=(0, None)))(data, ref)
    assert got.dtype == jnp.float32
    assert np.abs(np.asarray(got) - want).max() < 1e-5 * np.abs(want).max()
    at = np.abs(want).reshape(3, -1).argmax(axis=1)
    d = np.stack([at // w, at % w], axis=1)
    d = np.where(d > np.asarray([h, w]) // 2, d - np.asarray([h, w]), d)
    assert np.array_equal(jax.jit(jax.vmap(register.crosscorr_shift,
                                           in_axes=(0, None)))(data, ref), d)


def _primitives(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


def _traced(h, w, dtype=np.float32):
    frames = jax.ShapeDtypeStruct((2, h, w), dtype)
    return list(_primitives(jax.make_jaxpr(jax.vmap(
        register.crosscorr_shift, in_axes=(0, None)))(
            frames, jax.ShapeDtypeStruct((h, w), dtype)).jaxpr))


@pytest.mark.parametrize("h,w", [(512, 512), (128, 256), (129, 131),
                                 (register.N_MAX, register.N_MAX)])
def test_every_product_is_float32_at_highest_and_no_fft_is_left(h, w):
    """The precision guard: the configuration states float32 arithmetic
    and the cell's checks cannot see the transform's precision, so it is
    held here."""
    eqns = _traced(h, w)
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 6                 # 2 for the reference, 4 a frame
    highest = jax.lax.Precision.HIGHEST
    for e in dots:
        assert tuple(e.params["precision"]) == (highest, highest)
        assert e.params["preferred_element_type"] == jnp.float32
        assert all(v.aval.dtype == jnp.float32
                   for v in list(e.invars) + list(e.outvars))
    assert not [e for e in eqns if e.primitive.name == "fft"]
    assert not [v.aval.dtype for e in eqns for v in e.outvars
                if v.aval.dtype in (jnp.bfloat16, jnp.float16)]


@pytest.mark.parametrize("h,w,dtype", [
    (64, 64, np.float32), (127, 512, np.float32), (512, 127, np.float32),
    (register.N_MAX + 1, register.N_MAX + 1, np.float32),
    (128, register.N_MAX + 1, np.float32), (512, 512, np.float64)])
def test_every_other_frame_keeps_xlas_fft(h, w, dtype):
    """Under the MXU's tile, past ``N_MAX`` on either axis, and float64
    under x64: ``jnp.fft`` as it stood, and no product."""
    assert not register._by_products((h, w), dtype)
    names = [e.primitive.name for e in _traced(h, w, dtype)]
    assert names.count("fft") == 3 and "dot_general" not in names


def test_the_rule_reads_the_frames_shape_and_type_and_nothing_else():
    import inspect
    assert list(inspect.signature(register.crosscorr_shift).parameters) \
        == ["frame", "reference"]
    assert list(inspect.signature(register.fit).parameters) \
        == ["images", "reference"]
    assert register._by_products((128, register.N_MAX), np.float32)
    assert register._by_products((512, 512), np.dtype("float32"))
    # integers are promoted to float32 by jax.numpy, and take the products
    eqns = _traced(128, 128, np.int16)
    assert "dot_general" in [e.primitive.name for e in eqns]
    # NumPy's side keeps np.fft at any size: it is the oracle
    ref = scene(1, 128, 128, 0)
    assert isinstance(register.crosscorr_shift(ref, ref), np.ndarray)


@pytest.mark.parametrize("seed", [0, 3])
def test_fit_by_the_products_on_the_three_kinds_of_array(mesh, seed):
    """128 x 128 frames: a ``fromcallback`` source, the resident array and
    ``mode='local'`` agree, and the counter counts the calls that took
    the products (the two traced ones) and not the others."""
    data, ref, planted = session(seed, frames=24, h=128, w=128, margin=8)
    c0 = engine.counters()["crosscorr_on_mxu"]
    disp = register.fit(bolt.array(data), ref).toarray()
    assert np.array_equal(disp, planted)
    assert engine.counters()["crosscorr_on_mxu"] == c0      # NumPy's FFT
    assert np.array_equal(register.fit(bolt.array(data, mesh),
                                       ref).toarray(), disp)
    assert engine.counters()["crosscorr_on_mxu"] == c0 + 1
    source = bolt.fromcallback(lambda i: data[tuple(i)], data.shape, mesh,
                               dtype=np.float32, chunks=8)
    got = register.fit(source, ref)
    assert got._stream is not None
    assert np.array_equal(got.toarray(), disp)
    assert engine.counters()["crosscorr_on_mxu"] == c0 + 2
    # frames under the rule's floor, and float64 ones, count nothing
    small, sref, _ = session(seed)
    register.fit(bolt.array(small, mesh), sref).toarray()
    register.fit(bolt.array(data.astype(np.float64), mesh), ref)
    assert engine.counters()["crosscorr_on_mxu"] == c0 + 2
