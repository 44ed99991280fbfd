"""Batched Jacobi eigensolver tests (CPU mesh).

``jacobi_eigh`` is the TPU-first engine behind the Gram-route
``svdvals``/``tallskinny_pca`` (BASELINE config 5); the oracle is
``numpy.linalg.eigvalsh`` in float64."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bolt_tpu.ops import jacobi_eigh, svdvals


def _gram(rs, b, n):
    x = rs.randn(b, 4 * n, n)
    return np.einsum("bni,bnj->bij", x, x)


@pytest.mark.parametrize("n", [1, 2, 3, 5, 8, 16, 17, 33, 64])
def test_matches_numpy_across_sizes(n):
    rs = np.random.RandomState(n)
    g = _gram(rs, 6, n)
    ref = np.linalg.eigvalsh(g)
    got = np.asarray(jacobi_eigh(jnp.asarray(g)))
    scale = np.abs(ref).max(axis=-1, keepdims=True)
    assert np.max(np.abs(got - ref) / scale) < 5e-11


def test_float32_precision_and_dtype():
    rs = np.random.RandomState(0)
    g = _gram(rs, 8, 16).astype(np.float32)
    got = jacobi_eigh(jnp.asarray(g))
    assert got.dtype == jnp.float32
    ref = np.linalg.eigvalsh(g.astype(np.float64))
    assert np.max(np.abs(np.asarray(got) - ref)
                  / np.abs(ref).max(axis=-1, keepdims=True)) < 1e-5


def test_indefinite_and_degenerate_spectra():
    rs = np.random.RandomState(1)
    # indefinite: symmetric but not PSD
    a = rs.randn(4, 12, 12)
    a = (a + np.swapaxes(a, -1, -2)) / 2
    ref = np.linalg.eigvalsh(a)
    got = np.asarray(jacobi_eigh(jnp.asarray(a)))
    assert np.allclose(got, ref, atol=1e-10)
    # repeated eigenvalues: identity and zero matrices are fixed points
    assert np.allclose(np.asarray(jacobi_eigh(jnp.eye(7))), np.ones(7))
    assert np.allclose(np.asarray(jacobi_eigh(jnp.zeros((3, 9, 9)))), 0.0)
    # diagonal input returns the sorted diagonal
    d = np.diag([3.0, -1.0, 2.0, 0.0, 5.0])
    assert np.allclose(np.asarray(jacobi_eigh(jnp.asarray(d))),
                       np.sort(np.diag(d)))


def test_eigenvectors():
    rs = np.random.RandomState(2)
    for n in (2, 3, 8, 17):
        a = rs.randn(5, n, n)
        a = (a + np.swapaxes(a, -1, -2)) / 2
        w, v = jacobi_eigh(jnp.asarray(a), vectors=True)
        w, v = np.asarray(w), np.asarray(v)
        # columns are orthonormal and diagonalize a: a @ v = v * w
        eye = np.broadcast_to(np.eye(n), (5, n, n))
        assert np.allclose(np.swapaxes(v, -1, -2) @ v, eye, atol=1e-10)
        assert np.allclose(a @ v, v * w[..., None, :], atol=1e-9)
        assert np.allclose(w, np.linalg.eigvalsh(a), atol=1e-10)


@pytest.mark.parametrize("n", [5, 6])  # odd n: the padded-dummy path
def test_extreme_scales_no_overflow(n):
    # the atan2 rotation must survive scales where tau = (aqq-app)/(2*apq)
    # would overflow f32 (the classic formula NaNs near convergence), and
    # the odd-n dummy sentinel must not square the entries (f32 1e30-scale
    # inputs would overflow to an inf sentinel and NaN the whole batch)
    rs = np.random.RandomState(3)
    base = _gram(rs, 2, n)
    for scale in (1e-30, 1e30):
        got = np.asarray(jacobi_eigh(jnp.asarray(base * scale)))
        assert np.all(np.isfinite(got))
        ref = np.linalg.eigvalsh(base * scale)
        assert np.allclose(got, ref, rtol=1e-9)
    got32 = np.asarray(jacobi_eigh(jnp.asarray(
        (base[0] * 1e30).astype(np.float32))))
    assert np.all(np.isfinite(got32))
    ref = np.linalg.eigvalsh(base[0] * 1e30)
    assert np.allclose(got32, ref, rtol=1e-4)


def test_integer_input_promotes():
    a = jnp.asarray([[2, 1], [1, 2]], jnp.int32)
    got = np.asarray(jacobi_eigh(a))
    assert np.allclose(got, [1.0, 3.0])


def test_complex_falls_back():
    rs = np.random.RandomState(4)
    x = rs.randn(6, 4) + 1j * rs.randn(6, 4)
    h = x.conj().T @ x
    got = np.asarray(jacobi_eigh(jnp.asarray(h)))
    assert np.allclose(got, np.linalg.eigvalsh(h), rtol=1e-9)
    w, v = jacobi_eigh(jnp.asarray(h), vectors=True)
    assert np.allclose(np.asarray(v) @ np.diag(np.asarray(w))
                       @ np.asarray(v).conj().T, h, atol=1e-9)


def test_bad_shape_rejected():
    with pytest.raises(ValueError):
        jacobi_eigh(jnp.zeros((3, 4)))
    with pytest.raises(ValueError):
        jacobi_eigh(jnp.zeros((5,)))


def test_jit_and_vmap_compose():
    import jax
    rs = np.random.RandomState(5)
    g = jnp.asarray(_gram(rs, 4, 8))
    ref = np.linalg.eigvalsh(np.asarray(g))
    got = np.asarray(jax.jit(jacobi_eigh)(g))
    assert np.allclose(got, ref, atol=1e-10)
    got_v = np.asarray(jax.vmap(jacobi_eigh)(g))
    assert np.allclose(got_v, ref, atol=1e-10)


def test_tsqr_matches_qr():
    import jax.numpy as jnp
    from bolt_tpu.ops import tsqr
    rs = np.random.RandomState(6)
    for shape in [(64, 8), (3, 100, 12), (40, 1)]:
        x = rs.randn(*shape)
        q, r = tsqr(jnp.asarray(x))
        q, r = np.asarray(q), np.asarray(r)
        d = shape[-1]
        eye = np.broadcast_to(np.eye(d), r.shape)
        assert np.allclose(np.swapaxes(q, -1, -2) @ q, eye, atol=1e-12)
        assert np.allclose(q @ r, x, atol=1e-12)
        # upper triangular with positive diagonal (unlike np.linalg.qr,
        # whose sign convention is unspecified)
        assert np.allclose(np.tril(r, -1), 0.0, atol=1e-12)
        assert np.all(np.diagonal(r, axis1=-2, axis2=-1) > 0)


def test_tsqr_f32_and_int_and_errors():
    import jax.numpy as jnp
    from bolt_tpu.ops import tsqr
    rs = np.random.RandomState(7)
    x = rs.randn(256, 6).astype(np.float32)
    q, r = tsqr(jnp.asarray(x))
    assert np.asarray(q).dtype == np.float32
    assert np.allclose(np.asarray(q) @ np.asarray(r), x, atol=1e-4)
    qi, ri = tsqr(jnp.asarray((x * 10).astype(np.int32)))
    assert np.issubdtype(np.asarray(qi).dtype, np.floating)
    with pytest.raises(ValueError):
        tsqr(jnp.zeros((4, 8)))


def test_tallskinny_svd_matches_numpy():
    from bolt_tpu.ops import tallskinny_svd
    rs = np.random.RandomState(8)
    for shape in [(128, 10), (4, 96, 6)]:
        x = rs.randn(*shape)
        u, s, vh = (np.asarray(a) for a in tallskinny_svd(jnp.asarray(x)))
        d = shape[-1]
        # reconstruction, orthonormality, descending spectrum
        assert np.allclose(u * s[..., None, :] @ vh, x, atol=1e-9)
        eye = np.broadcast_to(np.eye(d), s.shape[:-1] + (d, d))
        assert np.allclose(np.swapaxes(u, -1, -2) @ u, eye, atol=1e-8)
        assert np.allclose(s, np.linalg.svd(x, compute_uv=False), rtol=1e-9)
    # truncation
    x = rs.randn(64, 8)
    u, s, vh = tallskinny_svd(jnp.asarray(x), k=3)
    assert u.shape == (64, 3) and s.shape == (3,) and vh.shape == (3, 8)
    assert np.allclose(np.asarray(s),
                       np.linalg.svd(x, compute_uv=False)[:3], rtol=1e-9)


def test_tallskinny_svd_rank_deficient_and_errors():
    from bolt_tpu.ops import tallskinny_svd
    rs = np.random.RandomState(9)
    # rank-1 input: zero singular values give zero u columns, not NaN
    col = rs.randn(40, 1)
    x = col @ rs.randn(1, 5)
    u, s, vh = (np.asarray(a) for a in tallskinny_svd(jnp.asarray(x)))
    assert np.all(np.isfinite(u)) and np.all(np.isfinite(s))
    assert np.allclose(s[1:], 0.0, atol=1e-6 * s[0])
    assert np.allclose(u * s[None, :] @ vh, x, atol=1e-8 * abs(x).max())
    with pytest.raises(ValueError):
        tallskinny_svd(jnp.zeros((4, 8)))


def test_component_count_validated_across_family():
    from bolt_tpu.ops import tallskinny_pca, tallskinny_svd
    x = jnp.asarray(np.random.RandomState(10).randn(20, 5))
    for bad in (-1, 0, 99):
        with pytest.raises(ValueError):
            tallskinny_svd(x, k=bad)
        with pytest.raises(ValueError):
            tallskinny_pca(x, k=bad)


def test_jacobi_routing_branches():
    # the Jacobi-vs-QDWH route: big batches and vmapped contexts take
    # Jacobi; single small matrices and d > 64 take QDWH
    import jax
    from bolt_tpu.ops.linalg import _use_jacobi
    rs = np.random.RandomState(12)
    small = jnp.asarray(np.eye(8))
    assert not _use_jacobi(small)                      # batch*d = 8 < 2048
    big_batch = jnp.zeros((512, 8, 8))
    assert _use_jacobi(big_batch)                      # 512*8 >= 2048
    assert not _use_jacobi(jnp.zeros((4, 128, 128)))   # d > 64
    # correctness through each route (svdvals under vmap = config 5b path)
    x = rs.randn(32, 1024, 16).astype(np.float32)
    from bolt_tpu.ops import svdvals
    got = np.asarray(jax.jit(jax.vmap(svdvals))(jnp.asarray(x)))
    expect = np.stack([np.linalg.svd(m.astype(np.float64), compute_uv=False)
                       for m in x])
    assert np.allclose(got, expect, rtol=1e-3, atol=1e-2)
    # big-batch eager route
    got2 = np.asarray(svdvals(jnp.asarray(x)))
    assert np.allclose(got2, expect, rtol=1e-3, atol=1e-2)


def test_jacobi_routing_true_batch_under_vmap():
    # a small vmapped batch must NOT force the Jacobi route: the true
    # batch (outer vmap dims included) feeds the work threshold
    import jax
    from bolt_tpu.ops.linalg import _use_jacobi, _true_batch
    seen = {}
    def probe(tag):
        def f(g):
            seen[tag] = (_true_batch(g), _use_jacobi(g))
            return g
        return f
    jax.vmap(probe("small"))(jnp.zeros((4, 8, 8)))
    assert seen["small"] == (4, False)                  # 4*8 < 2048
    jax.vmap(probe("big"))(jnp.zeros((512, 8, 8)))
    assert seen["big"] == (512, True)                   # 512*8 >= 2048
    jax.vmap(jax.vmap(probe("nested")))(jnp.zeros((32, 16, 8, 8)))
    assert seen["nested"] == (512, True)                # nested vmaps compose


def test_jacobi_is_differentiable():
    # plain-lax iteration means AD needs no custom rules (XLA's eigh ships
    # hand-written JVPs): eigenvalue gradients match the analytic forms
    import jax
    rs = np.random.RandomState(13)
    a = rs.randn(6, 6)
    a = (a + a.T) / 2
    # d(sum of eigenvalues)/dA = I (trace identity)
    g = jax.grad(lambda m: jacobi_eigh(m).sum())(jnp.asarray(a))
    assert np.allclose(np.asarray(g), np.eye(6), atol=1e-8)
    # d(largest eigenvalue)/dA = v v^T of the top eigenvector
    g2 = jax.grad(lambda m: jacobi_eigh(m)[-1])(jnp.asarray(a))
    _, v = np.linalg.eigh(a)
    assert np.allclose(np.asarray(g2), np.outer(v[:, -1], v[:, -1]),
                       atol=1e-6)
    # and through the Gram-route svdvals pipeline vs finite differences —
    # BATCHED so the eigensolve really routes to jacobi_eigh (an unbatched
    # (6, 6) Gram would take XLA's eigh and test its JVP instead)
    from bolt_tpu.ops import svdvals
    from bolt_tpu.ops.linalg import _use_jacobi
    assert _use_jacobi(jnp.zeros((400, 6, 6)))
    x = rs.randn(400, 64, 6)
    g3 = np.asarray(jax.grad(
        lambda m: svdvals(m).sum())(jnp.asarray(x)))
    eps = 1e-6
    for i in range(3):
        xp = x.copy(); xp[7, 0, i] += eps
        xm = x.copy(); xm[7, 0, i] -= eps
        num = (np.linalg.svd(xp[7], compute_uv=False).sum()
               - np.linalg.svd(xm[7], compute_uv=False).sum()) / (2 * eps)
        assert abs(g3[7, 0, i] - num) < 1e-5


def test_lstsq_matches_numpy():
    from bolt_tpu.ops import lstsq
    rs = np.random.RandomState(14)
    a = rs.randn(200, 7)
    # matrix rhs
    b = rs.randn(200, 3)
    x = np.asarray(lstsq(jnp.asarray(a), jnp.asarray(b)))
    ref = np.linalg.lstsq(a, b, rcond=None)[0]
    assert np.allclose(x, ref, atol=1e-10)
    # vector rhs keeps the vector shape
    bv = rs.randn(200)
    xv = np.asarray(lstsq(jnp.asarray(a), jnp.asarray(bv)))
    assert xv.shape == (7,)
    assert np.allclose(xv, np.linalg.lstsq(a, bv, rcond=None)[0], atol=1e-10)
    # batched
    ab = rs.randn(4, 64, 5)
    bb = rs.randn(4, 64, 2)
    xb = np.asarray(lstsq(jnp.asarray(ab), jnp.asarray(bb)))
    refb = np.stack([np.linalg.lstsq(ab[i], bb[i], rcond=None)[0]
                     for i in range(4)])
    assert np.allclose(xb, refb, atol=1e-9)
    # conditioned columns: still accurate well inside the tsqr envelope
    ac = rs.randn(500, 6) * np.logspace(0, 3, 6)
    bc = rs.randn(500)
    xc = np.asarray(lstsq(jnp.asarray(ac), jnp.asarray(bc)))
    assert np.allclose(xc, np.linalg.lstsq(ac, bc, rcond=None)[0],
                       rtol=1e-7)
    with pytest.raises(ValueError):
        lstsq(jnp.zeros((4, 8)), jnp.zeros(4))     # wide
    with pytest.raises(ValueError):
        lstsq(jnp.zeros((8, 4)), jnp.zeros(7))     # row mismatch


def test_lstsq_dtype_promotion_and_complex_rejection():
    from bolt_tpu.ops import lstsq
    rs = np.random.RandomState(15)
    a32 = rs.randn(64, 4).astype(np.float32)
    b64 = rs.randn(64)
    x = lstsq(jnp.asarray(a32), jnp.asarray(b64))
    assert np.asarray(x).dtype == np.float64   # promoted, not narrowed
    with pytest.raises(ValueError):
        lstsq(jnp.asarray(a32), jnp.asarray(b64 + 1j * b64))


# ---------------------------------------------------------------------
# the sweep chain as one Mosaic kernel (ISSUE 27).  On this CPU mesh
# ``jacobi_eigh`` lowers to the ``lax.scan``; the kernel itself runs here
# in Pallas' TPU interpret mode, against that scan, at small sizes
# ---------------------------------------------------------------------

@pytest.mark.parametrize("m", range(2, 66, 2))
def test_fixed_seats_reproduce_round_robin(m):
    # pure NumPy: seat i of the top half against seat i of the bottom half
    # gives _round_robin's pairs round for round, the sign bit says which
    # of the two seats holds the pair's smaller index, and a sweep ends
    # with everyone back in the seats it began with
    from bolt_tpu.ops.linalg import _round_robin, _seat_bits, _seating
    seats, bits = _seating(m), _seat_bits(m)
    assert seats.shape == (m - 1, 2, m // 2) and bits.shape == (m - 1,)
    assert bits.dtype == np.int32
    for r in range(m - 1):
        top, bottom = seats[r]
        pairs = sorted(zip(np.minimum(top, bottom).tolist(),
                           np.maximum(top, bottom).tolist()))
        assert pairs == [tuple(p) for p in _round_robin(m)[r].tolist()]
        up = [(int(bits[r]) >> i) & 1 for i in range(m // 2)]
        assert up == (top < bottom).astype(int).tolist()
    top, bottom = seats[-1].tolist()
    if m > 2:       # one more re-seating closes the cycle
        top, bottom = ([top[0], bottom[0]] + top[1:-1],
                       bottom[1:] + [top[-1]])
    assert [top, bottom] == seats[0].tolist()


def _sym32(rs, batch, n):
    x = rs.randn(batch, 3 * n, n).astype(np.float32)
    return np.einsum("bni,bnj->bij", x, x)


@pytest.mark.parametrize("vectors", [False, True], ids=["values", "vectors"])
@pytest.mark.parametrize("batch", [1, 3, 130])
@pytest.mark.parametrize("n", [4, 7, 8])
def test_kernel_matches_the_scan(n, batch, vectors):
    # a few sweeps, far from converged: the two executors agree only if
    # they apply the same rotations in the same order.  130 matrices are
    # two lane blocks (the second 2 matrices and 126 of padding); n = 7 is
    # padded to 8 with the decoupled dummy, as jacobi_eigh pads it
    from jax.experimental.pallas import tpu as pltpu
    from bolt_tpu.ops.linalg import _lane_sweeps, _scan_sweeps
    g = _sym32(np.random.RandomState(100 * n + batch), batch, n)
    if n % 2:
        g = np.pad(g, [(0, 0), (0, 1), (0, 1)])
        g[:, n, n] = 1.0 + (n + 1) * np.abs(g).max(axis=(-2, -1))
    g = jnp.asarray(g)
    want_w, want_v = _scan_sweeps(g, 3, vectors)
    with pltpu.force_tpu_interpret_mode():
        got_w, got_v = _lane_sweeps(g, 3, vectors)
    assert (got_v is None) == (want_v is None)
    assert got_w.dtype == jnp.float32 and got_w.shape == want_w.shape
    scale = float(np.abs(np.asarray(g)).max())
    assert np.max(np.abs(np.asarray(got_w) - np.asarray(want_w))) \
        < 2e-5 * scale
    if vectors:
        assert got_v.shape == want_v.shape
        assert np.max(np.abs(np.asarray(got_v) - np.asarray(want_v))) < 2e-5


def test_kernel_atan2_matches_numpy():
    from bolt_tpu.ops.linalg import _atan2
    rs = np.random.RandomState(7)
    y = np.concatenate([rs.randn(4000) * 10.0 ** rs.randint(-30, 30, 4000),
                        [0.0, 0.0, 0.0, 1.0, -1.0, 3e38, 2e-38]])
    x = np.concatenate([rs.randn(4000) * 10.0 ** rs.randint(-30, 30, 4000),
                        [0.0, 2.0, -2.0, 0.0, 0.0, 3e38, -2e-38]])
    y, x = y.astype(np.float32), x.astype(np.float32)
    got = np.asarray(_atan2(jnp.asarray(y), jnp.asarray(x)))
    want = np.arctan2(y.astype(np.float64), x.astype(np.float64))
    assert got.dtype == np.float32 and np.all(np.isfinite(got))
    assert np.max(np.abs(got - want)) < 4e-7


def _eqns(jaxpr, name):
    """Every equation called ``name`` in a jaxpr and the jaxprs it holds."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_eqns(sub, name))
    return found


@pytest.mark.parametrize("shape", [(6, 4, 4), (5, 3, 4, 4), (5, 3, 2, 4, 4)],
                         ids=["one-vmap", "two-vmaps", "two-vmaps-of-a-batch"])
def test_vmap_folds_into_the_lane_batch(shape):
    # pallas_call's own batching rule would make every mapped axis a grid
    # axis, one lane in use each: the mapped axes must fold into the flat
    # batch of ONE jacobi_sweeps (one kernel call where it lowers for a
    # TPU; tests/test_ops_kernels.py counts that call's lanes)
    f = lambda a: jacobi_eigh(a, vectors=True)
    for _ in range(min(len(shape) - 2, 2)):
        f = jax.vmap(f)
    g = jnp.asarray(np.random.RandomState(3).randn(*shape).astype(np.float32))
    g = g + jnp.swapaxes(g, -1, -2)
    calls = _eqns(jax.make_jaxpr(f)(g).jaxpr, "jacobi_sweeps")
    assert len(calls) == 1
    assert calls[0].invars[0].aval.shape == (int(np.prod(shape[:-2])), 4, 4)
    w, v = f(g)
    want_w, want_v = jacobi_eigh(g, vectors=True)
    assert w.shape == shape[:-1] and v.shape == shape
    assert np.array_equal(np.asarray(w), np.asarray(want_w))
    assert np.array_equal(np.asarray(v), np.asarray(want_v))


def test_kernel_entry_differentiates_as_the_scan():
    # a pallas_call has no differentiation rule: the entry's is the scan's
    from bolt_tpu.ops.linalg import _chain_entry, _scan_sweeps
    rs = np.random.RandomState(11)
    g = jnp.asarray(_sym32(rs, 3, 6))
    t = jnp.asarray(rs.randn(3, 6).astype(np.float32))
    for vectors in (False, True):
        def loss(run):
            def f(a):
                w, v = run(a)
                extra = (v * v[..., ::-1]).sum() if vectors else 0.0
                return (w * t).sum() + extra
            return f
        got = jax.grad(loss(_chain_entry(5, vectors)))(g)
        want = jax.grad(loss(lambda a: _scan_sweeps(a, 5, vectors)))(g)
        assert got.dtype == jnp.float32
        assert np.array_equal(np.asarray(got), np.asarray(want))
    # and under vmap of grad (the scan's rule batches like any lax code)
    per = jax.vmap(jax.grad(lambda a: _chain_entry(5, False)(a)[0].sum()))(g)
    assert np.allclose(np.asarray(per), np.eye(6), atol=1e-5)


@pytest.mark.parametrize("devices,kernel", [(1, True), (4, False)],
                         ids=["one-device", "four-devices"])
def test_the_executor_is_chosen_when_the_program_is_lowered(devices, kernel):
    # lowered FOR a TPU on this CPU host (nothing compiles, nothing runs):
    # a program for one device holds the Mosaic kernel; GSPMD cannot
    # partition one, so a program for several devices keeps the scan
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:devices]), ("k",))
    where = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("k"))
    arg = jax.ShapeDtypeStruct((8, 6, 6), jnp.float32, sharding=where)
    text = jax.jit(jacobi_eigh).trace(arg).lower(
        lowering_platforms=("tpu",)).as_text()
    assert ("tpu_custom_call" in text) == kernel
    assert ("stablehlo.while" in text) != kernel
    cpu = jax.jit(jacobi_eigh).lower(arg).as_text()
    assert "tpu_custom_call" not in cpu and "stablehlo.while" in cpu


# ---------------------------------------------------------------------
# the Gram pass as one Mosaic kernel (ISSUE 29).  On this CPU mesh
# ``gram_products`` lowers to ``dot_general``; the kernel itself runs here
# in Pallas' TPU interpret mode, with a short block, against float64
# ---------------------------------------------------------------------

def _kernel_gram_here(x, cut=False, samples=False, block=128, sums=False):
    """The Gram matrices by the kernel; with ``sums`` the summing form's
    ``(matrices, sums)``."""
    from jax.experimental.pallas import tpu as pltpu
    from bolt_tpu.ops import linalg
    old, linalg._GRAM_BLOCK = linalg._GRAM_BLOCK, block
    try:
        with pltpu.force_tpu_interpret_mode(), jax.enable_x64(False):
            out = [np.asarray(part, np.float64) for part in
                   linalg._kernel_gram(jnp.asarray(x, jnp.float32),
                                       "highest", cut, samples, sums)]
    finally:
        linalg._GRAM_BLOCK = old
    return tuple(out) if sums else out[0]


def _gram64(x, samples=False):
    x = np.asarray(x, np.float64)
    g = np.einsum("...ni,...nj->...ij", x, x)
    return g.reshape((-1,) + g.shape[-2:]).sum(axis=0) if samples else g


@pytest.mark.parametrize("d", [8, 16, 32, 64])
@pytest.mark.parametrize("lead,cut", [((), False), ((3,), False),
                                      ((3, 5), True), ((2, 3, 2), True)],
                         ids=["one", "planes3", "planes3-grid5",
                              "keys2x3-grid2"])
@pytest.mark.parametrize("sums", [False, True], ids=["gram", "gram+sums"])
def test_packed_gram_is_exact_on_small_integers(d, lead, cut, sums):
    # integers under 2**6 over at most 2,048 rows: every product and every
    # partial sum is an integer under 2**24, so float32 holds the TRUTH and
    # any lost product, group or block shows as a whole number.  The
    # summing form (ISSUE 33) hands back the same matrices and the rows'
    # sums, a row group's from its own rows of the stacked block
    rs = np.random.RandomState(d + len(lead))
    n = 2 * (64 // d) * 128
    x = rs.randint(-63, 64, size=lead + (n, d))
    got = _kernel_gram_here(x, cut, sums=sums)
    whole = _gram64(x, samples=True)
    summed = _kernel_gram_here(x, samples=True, sums=sums)
    if sums:
        assert got[1].shape == lead + (d,)
        assert np.array_equal(got[1], x.sum(axis=-2))
        assert np.array_equal(summed[1], x.reshape(-1, d).sum(axis=0))
        got, summed = got[0], summed[0]
    assert got.shape == lead + (d, d)
    assert np.array_equal(got, _gram64(x))
    # a whole array's sum over the planes leaves 2**24 behind: to float32
    assert np.abs(summed - whole).max() <= 2e-7 * np.diag(whole).max()


@pytest.mark.parametrize("d,n", [(64, 3 * 128 + 37), (32, 2 * 256 + 255),
                                 (16, 4 * 128 + 1), (8, 8 * 128 + 1000),
                                 (64, 100)],
                         ids=["d64", "d32", "d16", "d8", "all-tail"])
@pytest.mark.parametrize("sums", [False, True], ids=["gram", "gram+sums"])
def test_packed_gram_with_a_tail_against_float64(d, n, sums):
    # rows beyond the kernel's whole steps go to dot_general and are added:
    # 12-bit data as the benchmark's, three planes (an odd count).  The
    # tail's rows add their jnp.sum to the kernel's sums: integers whose
    # column sums stay under 2**24, so every sum is the truth
    from bolt_tpu.ops.linalg import _products
    rs = np.random.RandomState(n)
    x = rs.randint(-2047, 2048, size=(3, n, d)).astype(np.float32)
    want = _gram64(x)
    scale = np.abs(np.diagonal(want, axis1=-2, axis2=-1)).max()
    got = _kernel_gram_here(x, sums=sums)
    summed = _kernel_gram_here(x, samples=True, sums=sums)
    if sums:
        assert np.array_equal(got[1], x.sum(axis=-2, dtype=np.float64))
        assert np.array_equal(summed[1], x.sum(axis=(0, 1),
                                               dtype=np.float64))
        got, summed = got[0], summed[0]
    assert np.abs(got - want).max() < 1e-6 * scale
    # and the dot_general it replaces, to the same 1e-6 of the diagonal
    plain = np.asarray(_products(jnp.asarray(x), jnp.asarray(x), "highest"))
    assert np.abs(got - plain).max() < 1e-6 * scale
    assert np.abs(summed - want.sum(axis=0)).max() < 1e-6 * 3 * scale


def test_packed_gram_keeps_all_of_float32():
    # "highest" is float32 by three bfloat16 pieces: data with a full
    # 24-bit mantissa must come out to float32's own rounding, which two
    # pieces and three products do not give (1e-5 here)
    rs = np.random.RandomState(29)
    x = (rs.randn(2, 1024, 64) * 1000.0).astype(np.float32)
    want = _gram64(x)
    scale = np.abs(np.diagonal(want, axis1=-2, axis2=-1)).max()
    assert np.abs(_kernel_gram_here(x) - want).max() < 5e-7 * scale


@pytest.mark.parametrize("d,block", [(64, 128), (64, 1024), (32, 512),
                                     (16, 256), (8, 128)])
def test_packed_gram_sums_are_of_the_float32_values(d, block):
    # the sums are of x itself, not of a rounded piece of it.  Full-mantissa
    # data on an offset (no value fits 16 bits) against float64, held to
    # twice what jnp.sum's own float32 order of summation errs by; a block
    # of several lane tiles takes the pairwise tree inside a step.  And the
    # summing form's Gram matrices are the plain form's, bit for bit
    rs = np.random.RandomState(d + block)
    n = 2 * (64 // d) * block + 77
    x = (rs.randn(3, n, d) * 1000.0 + 5000.0).astype(np.float32)
    gram, sums = _kernel_gram_here(x, block=block, sums=True)
    assert np.array_equal(gram, _kernel_gram_here(x, block=block))
    whole = _kernel_gram_here(x, samples=True, block=block, sums=True)[1]
    for got, axes in ((sums, -2), (whole, (0, 1))):
        want = x.sum(axis=axes, dtype=np.float64)
        plain = np.abs(np.asarray(jnp.sum(jnp.asarray(x), axis=axes),
                                  np.float64) - want).max()
        assert np.abs(got - want).max() <= 2 * max(
            plain, np.spacing(np.float32(want.max())))
    # a few odd 17-bit integers among zeros, in every row group's rows:
    # their third bfloat16 piece is not zero and their sums are exact in
    # float32, so a dropped piece is a wrong integer
    x = np.zeros((3, n, d), np.float32)
    rows = rs.choice(n, size=3 * (64 // d), replace=False)
    x[:, rows] = 2 * rs.randint(1 << 15, 1 << 16, size=(3, len(rows), d)) + 1
    assert np.array_equal(
        _kernel_gram_here(x, block=block, sums=True)[1],
        x.sum(axis=-2, dtype=np.float64))


@pytest.mark.parametrize("d", [32, 8])
def test_row_groups_do_not_leak_into_each_other(d):
    # the kernel's product holds group i against group j off its diagonal
    # blocks; with the second row range a million times the first and the
    # same rows, that block is 1e-6 of the answer: it must not be in it
    rs = np.random.RandomState(d)
    groups, n = 64 // d, (64 // d) * 256
    a = rs.randint(-2047, 2048, size=(n // groups, d)).astype(np.float64)
    x = np.concatenate([a] + [a * 1e6] * (groups - 1))
    want = _gram64(x)
    got = _kernel_gram_here(x)
    assert np.abs(got - want).max() < 2e-7 * np.abs(np.diag(want)).max()


def test_gram_products_folds_the_axes_a_chunked_map_names():
    # the chunk grid's axis is a cut of the rows, the key axis lies
    # outside: both fold into ONE gram_products over (keys, grid, n, d)
    from bolt_tpu.tpu.chunk import _uniform_map_body
    rs = np.random.RandomState(4)
    x = rs.randint(-63, 64, size=(5, 128, 8)).astype(np.float32)
    run = lambda data: _uniform_map_body(
        data, lambda blk: svdvals(blk)[None, :], 1, (32, 8))
    calls = _eqns(jax.make_jaxpr(run)(x).jaxpr, "gram_products")
    assert len(calls) == 1
    assert calls[0].invars[0].aval.shape == (5, 4, 32, 8)
    assert calls[0].params["cut"] is True and not calls[0].params["samples"]
    want = np.linalg.svd(x.reshape(5, 4, 32, 8).astype(np.float64),
                         compute_uv=False)
    assert np.allclose(np.asarray(run(x)), want, rtol=1e-4, atol=1e-3)
    # two key axes and the grid; a cut of the FEATURE axis is not named
    run2 = lambda data: _uniform_map_body(
        data, lambda blk: svdvals(blk)[None, :], 2, (32, 8))
    call, = _eqns(jax.make_jaxpr(run2)(x.reshape(5, 1, 128, 8)).jaxpr,
                  "gram_products")
    assert call.invars[0].aval.shape == (5, 1, 4, 32, 8)
    assert call.params["cut"] is True
    run3 = lambda data: _uniform_map_body(
        data, lambda blk: svdvals(blk)[None, :], 1, (64, 8))
    wide = jnp.asarray(rs.randn(5, 64, 16).astype(np.float32))
    jaxpr = jax.make_jaxpr(run3)(wide).jaxpr
    assert not _eqns(jaxpr, "gram_products") and _eqns(jaxpr, "dot_general")


def test_an_unnamed_vmap_keeps_dot_general():
    # a stored (B, n, d) under the user's own vmap has the chunk grid's
    # batched shapes in the other physical order: nobody guesses
    x = jnp.asarray(np.random.RandomState(6).randn(6, 64, 8)
                    .astype(np.float32))
    jaxpr = jax.make_jaxpr(jax.vmap(svdvals))(x).jaxpr
    assert not _eqns(jaxpr, "gram_products")
    assert _eqns(jaxpr, "dot_general")
    # leading axes of the operand itself are its own: one call
    call, = _eqns(jax.make_jaxpr(svdvals)(x).jaxpr, "gram_products")
    assert call.invars[0].aval.shape == (6, 64, 8)
    assert call.params["cut"] is False
    # a named axis OUTSIDE an unnamed one is batched over the dot_general
    from bolt_tpu.tpu.chunk import MappedAxis
    both = jax.vmap(jax.vmap(svdvals), axis_name=MappedAxis())
    jaxpr = jax.make_jaxpr(both)(x.reshape(2, 3, 64, 8)).jaxpr
    assert not _eqns(jaxpr, "gram_products")
    got = np.asarray(both(x.reshape(2, 3, 64, 8)))
    assert np.allclose(got.reshape(6, 8), np.asarray(svdvals(x)), rtol=1e-5)


@pytest.mark.parametrize("dtype,d,precision,served", [
    ("float32", 64, "highest", True), ("float32", 8, "highest", True),
    ("float32", 48, "highest", False), ("float32", 100, "highest", False),
    ("float32", 128, "highest", False), ("float32", 64, "high", False),
    ("float32", 64, "default", False), ("float64", 64, "highest", False),
    ("bfloat16", 64, "highest", False), ("complex64", 64, "highest", False)])
def test_who_the_gram_kernel_serves(dtype, d, precision, served):
    from bolt_tpu.ops.linalg import _gram, _kernel_serves
    x = jnp.zeros((256, d), dtype)
    assert _kernel_serves(x, precision) == served
    jaxpr = jax.make_jaxpr(lambda v: _gram(v, jnp, precision))(x).jaxpr
    assert bool(_eqns(jaxpr, "gram_products")) == served
    assert bool(_eqns(jaxpr, "dot_general")) != served


@pytest.mark.parametrize("dtype", ["bfloat16", "float16", "int16", "uint8"])
def test_a_widened_input_keeps_dot_general(dtype):
    # XLA fuses the widening convert into a dot_general, which then reads
    # the narrow array; into a Mosaic call it cannot, so the kernel would
    # be fed a float32 copy of the whole input.  What decides is the array
    # the caller GAVE, before _widen
    with jax.enable_x64(False):   # the chip's: integers widen to float32
        _widened_cases(jnp.ones((4, 256, 8), dtype))


def _widened_cases(x):
    from bolt_tpu.ops import linalg
    from bolt_tpu.tpu.chunk import _uniform_map_body
    program = linalg._pca_program((), 2, (4, 256), 8, 3, True, "highest",
                                  jax.sharding.Mesh(
                                      np.asarray(jax.devices()[:1]), ("k",)))
    chunked = lambda data: _uniform_map_body(
        data, lambda blk: svdvals(blk)[None, :], 1, (64, 8))
    for fn in (svdvals, chunked, program, linalg.tallskinny_svd,
               lambda v: linalg.tallskinny_pca(v[0])):
        jaxpr = jax.make_jaxpr(fn)(x).jaxpr
        assert not _eqns(jaxpr, "gram_products"), fn
        assert _eqns(jaxpr, "dot_general"), fn
        served = jax.make_jaxpr(fn)(x.astype(jnp.float32)).jaxpr
        assert _eqns(served, "gram_products"), fn
    # tsqr's second round reads its own float32 q1, which is written to
    # memory whatever reads it: only the first round's operand is widened
    rhs = jnp.ones(x.shape[:-1], jnp.float32)
    for fn in (linalg.tsqr, lambda v: linalg.lstsq(v, rhs)):
        calls = _eqns(jax.make_jaxpr(fn)(x).jaxpr, "gram_products")
        assert len(calls) == 1, fn
        assert len(_eqns(jax.make_jaxpr(fn)(x.astype(jnp.float32)).jaxpr,
                         "gram_products")) == 2, fn


def test_gram_products_differentiates_as_dot_general(monkeypatch):
    # a pallas_call has no differentiation rule: the entry's is the
    # dot_general path's, through svdvals and through a whole array's runs
    from bolt_tpu.ops import linalg
    rs = np.random.RandomState(12)
    x = jnp.asarray(rs.randn(3, 96, 8).astype(np.float32))
    loss = lambda v: (svdvals(v) ** 2).sum() + svdvals(v)[..., 0].sum()
    runs = lambda v: (linalg._sample_gram(v, "highest") ** 2).sum()
    got, got_runs = jax.grad(loss)(x), jax.grad(runs)(x)
    assert _eqns(jax.make_jaxpr(loss)(x).jaxpr, "gram_products")
    assert not _eqns(jax.make_jaxpr(jax.grad(loss))(x).jaxpr,
                     "gram_products")
    # with the sums a centring caller asks for (ISSUE 33): the rule is
    # dot_general's and jnp.sum's
    def centred(v):
        g, total = linalg._sample_gram(v, "highest", sums=True)
        return ((g - jnp.outer(total, total) / 288) ** 2).sum() \
            + (total ** 3).sum()
    got_sums = jax.grad(centred)(x)
    call, = _eqns(jax.make_jaxpr(centred)(x).jaxpr, "gram_products")
    assert call.params["sums"] and call.params["samples"]
    assert [v.aval.shape for v in call.outvars] == [(8, 8), (8,)]
    assert not _eqns(jax.make_jaxpr(jax.grad(centred))(x).jaxpr,
                     "gram_products")
    monkeypatch.setattr(linalg, "_kernel_serves", lambda x, precision: False)
    # (make_jaxpr caches a function's trace: a new one)
    assert not _eqns(jax.make_jaxpr(lambda v: loss(v))(x).jaxpr,
                     "gram_products")
    want, want_runs = jax.grad(loss)(x), jax.grad(runs)(x)
    assert got.dtype == jnp.float32 and np.all(np.isfinite(np.asarray(got)))
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(np.asarray(got_runs), np.asarray(want_runs))
    assert np.array_equal(np.asarray(got_sums),
                          np.asarray(jax.grad(lambda v: centred(v))(x)))


@pytest.mark.parametrize("dtype,d", [("float32", 8), ("float32", 5),
                                     ("complex64", 8), ("int16", 8)])
def test_centred_pca_and_cov_are_the_mean_pass_and_the_gram_pass_here(
        dtype, d):
    # where the kernel is not placed (this backend) a centring caller's
    # sums are jnp.sum beside the dot_general: the numbers of the program
    # that took jnp.mean itself, spelled out here as it was, to the bit
    import bolt_tpu as bolt
    from bolt_tpu import ops
    from bolt_tpu.ops import linalg
    rs = np.random.RandomState(d)
    x = (rs.randn(8, 96, d) * 50 + 300)
    x = (x + 1j * rs.randn(8, 96, d)) if dtype == "complex64" else x
    x = x.astype(dtype)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:8]), ("k",))
    b = bolt.array(x, mesh, axis=(0,))
    n, k = 8 * 96, 3

    @jax.jit
    def before(data):
        v, widened = linalg._features_last(data, (8, 96), d)
        mu = jnp.mean(v, axis=(0, 1))
        g = linalg._sample_gram(v, "highest", widened=widened)
        g = g - n * jnp.outer(jnp.conj(mu), mu)
        vec, ev = linalg._decompose_gram(g, k, jnp, linalg._tpu_eigh)
        c = linalg._sample_gram(v, "highest", second_conj=True,
                                widened=widened)
        c = c - n * jnp.outer(mu, jnp.conj(mu))
        idx = jnp.arange(d)
        c = c.at[idx, idx].set(jnp.maximum(jnp.real(c[idx, idx]), 0.0)
                               .astype(c.dtype))
        return vec, jnp.sqrt(ev), mu, c / (n - 1)

    vec, sv, mu, c = (np.asarray(a) for a in before(b.tojax()))
    _, got_vec, got_sv, got_mu = ops.pca(b, k=k, center=True, axis=(0, 1),
                                         return_mean=True)
    got_c, cov_mu = ops.cov(b, axis=(0, 1), return_mean=True)
    for got, want in ((got_mu, mu), (cov_mu, mu), (got_sv, sv),
                      (got_vec, vec), (got_c, c)):
        assert got.dtype == want.dtype
        assert np.array_equal(got, want)


@pytest.mark.parametrize("devices,kernel", [(1, True), (4, False)],
                         ids=["one-device", "four-devices"])
@pytest.mark.parametrize("sums", [False, True], ids=["gram", "gram+sums"])
def test_the_gram_executor_is_chosen_when_the_program_is_lowered(devices,
                                                                 kernel,
                                                                 sums):
    # lowered FOR a TPU on this CPU host (nothing compiles, nothing runs):
    # one device gets the kernel and is counted; several keep dot_general,
    # and so does this host's own backend, uncounted.  A caller that
    # centres gets the summing form (its own name, its own count beside
    # the other) and, where the kernel is not placed, its own reduction
    from bolt_tpu import engine
    from bolt_tpu.ops import linalg
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:devices]), ("k",))
    where = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("k"))
    arg = jax.ShapeDtypeStruct((8, 8192, 64), jnp.float32, sharding=where)
    fn = (lambda v: linalg._sample_gram(v, "highest", sums=True)) if sums \
        else svdvals
    names = ("gram_kernel_programs", "gram_sums_programs")
    c0 = [engine.counters()[k] for k in names]
    want = [c0[0] + kernel, c0[1] + (kernel and sums)]
    with jax.enable_x64(False):
        text = jax.jit(fn).trace(arg).lower(
            lowering_platforms=("tpu",)).as_text()
    assert ("packed_gram" in text) == kernel
    assert ("packed_gram_sums" in text) == (kernel and sums)
    assert ("stablehlo.reduce(%arg0" in text) == (sums and not kernel)
    assert [engine.counters()[k] for k in names] == want
    cpu = jax.jit(fn).lower(arg).as_text()
    assert "packed_gram" not in cpu and "dot_general" in cpu
    assert [engine.counters()[k] for k in names] == want
