"""TPU-first dense linear algebra for the per-chunk workloads.

The reference runs its PCA workload as per-chunk ``numpy.linalg.svd`` calls
inside Spark executors (``BASELINE`` config 5, the Thunder usage pattern);
the straight translation — ``jnp.linalg.svd`` / ``jnp.linalg.eigvalsh`` on a
batch of small matrices — lowers to XLA's QR-iteration / QDWH loops, which
are built for one big matrix and leave a large batch of tiny problems
almost entirely serial.  This module takes the TPU-native route instead:

* :func:`jacobi_eigh` — batched symmetric eigendecomposition by cyclic
  Jacobi with the parallel (round-robin) ordering.  Every round applies
  n/2 disjoint rotations to the whole batch at once: elementwise math,
  no matmuls, no data-dependent control flow, a fixed number of rounds.
  Real float32 in a program compiled for one TPU device runs all the
  rounds as ONE Mosaic kernel that keeps the batch in VMEM
  (``_jacobi_kernel``: 0.84 ms for 80 matrices of 64 x 64 on a v5e);
  every other dtype, backend and program runs them as one ``lax.scan``
  of two permutation gathers plus elementwise math a round
  (``_scan_sweeps``: 29.2 ms for the same, fifteen small XLA kernels a
  round).  What either costs on the chip is ``eigh_ms.scan`` of the
  benchmark's ``series64-1chip.pca`` cell (``PERF.md``, section 5; the
  ledger's lines of that cell, PR 26 for the scan and PR 27 for the
  kernel).
* :func:`svdvals` / :func:`tallskinny_pca` — singular values / principal
  components of tall-skinny blocks via the Gram matrix: the (n, d) data
  is touched once by an MXU matmul and the eigenproblem is only (d, d),
  routed to :func:`jacobi_eigh` when the batch is large enough to
  amortise the sweep chain (see ``_use_jacobi``), else XLA's QDWH.
  Real float32 of 8, 16, 32 or 64 features in a program for one TPU
  device takes the Gram matrix by ONE Mosaic kernel that reads the data
  where it lies and fills the matrix unit (``_packed_gram``: 10.74 GB
  in 15.1 ms on a v5e, HBM's pace, where the ``dot_general`` fusion
  took 29.3: ``PERF.md`` section 6, PR 29); every other input keeps
  ``dot_general``.

Rotation angles use ``0.5 * atan2(2*a_pq, a_qq - a_pp)`` — no divisions,
no overflow for any input scale (the textbook ``tau = (a_qq - a_pp) /
(2*a_pq)`` route overflows f32 near convergence and, on TPU, turns into
NaN through the rsqrt lowering); in the kernel the same formula, with
``_atan2`` for the ``atan2`` that Mosaic does not lower.  The row/column
updates are pure elementwise f32, so results do not depend on the MXU's
bf16 default the way a rotation-by-matmul formulation would.
"""

import math
from functools import lru_cache, partial

import numpy as np

import jax
import jax.numpy as jnp

from bolt_tpu._precision import resolve as _resolve
from bolt_tpu.obs import trace as _obs
from bolt_tpu.utils import prod


def _adjoint(x):
    """Conjugate transpose of the trailing two dims (plain transpose for
    real dtypes)."""
    xt = jnp.swapaxes(x, -1, -2)
    return jnp.conj(xt) if jnp.iscomplexobj(x) else xt


def _acc_dtype(dtype):
    """Accumulation dtype for the Gram matmul: widen half precisions to
    float32, never narrow (jax rejects a narrower preferred_element_type)."""
    if dtype in (jnp.bfloat16, jnp.float16):
        return jnp.float32
    return dtype


def _real_dtype(dtype):
    return jnp.finfo(dtype).dtype if jnp.issubdtype(dtype, jnp.complexfloating) \
        else dtype


@lru_cache(maxsize=None)
def _round_robin(n):
    """Parallel-ordering Jacobi schedule (the circle method): ``n`` even →
    ``n - 1`` rounds of ``n // 2`` disjoint (p, q) pairs covering every
    index, so one round rotates the whole matrix."""
    others = list(range(1, n))
    rounds = []
    for _ in range(n - 1):
        cur = [0] + others
        pairs = sorted((min(cur[i], cur[n - 1 - i]), max(cur[i], cur[n - 1 - i]))
                       for i in range(n // 2))
        rounds.append(pairs)
        others = others[-1:] + others[:-1]
    return np.asarray(rounds)  # (n-1, n//2, 2)


def _default_sweeps(n, dtype):
    """Sweeps until a Gram matrix has settled.  The parallel ordering
    converges quadratically only at the very end: a random Gram matrix of
    n = 64 is at f32 machine precision after log2(n) + 4 sweeps, a
    planted PCA's (eight strong components over a noise floor, six decades
    in all) after log2(n) + 7; stopped at + 4, as this was, that one read
    1e-4 of the largest eigenvalue, on the chip and off it (PERF.md, PR
    26).  So + 8, and + 12 for f64's longer mantissa.  A spectrum graded
    EVENLY over six decades takes log2(n) + 12 in f32: not served by the
    default."""
    extra = 12 if jnp.finfo(dtype).bits >= 64 else 8
    return max(6, int(math.ceil(math.log2(max(n, 2)))) + extra)


def jacobi_eigh(a, vectors=False, sweeps=None):
    """Batched symmetric/Hermitian-real eigendecomposition, TPU-first.

    Parameters mirror ``jnp.linalg.eigvalsh`` / ``eigh``: ``a`` is
    ``(..., n, n)`` symmetric real; returns ascending eigenvalues
    ``(..., n)``, or ``(w, v)`` with orthonormal columns ``a @ v = v * w``
    when ``vectors=True``.

    A fixed-iteration cyclic Jacobi with parallel ordering: ``sweeps *
    (n - 1)`` rounds, each applying ``n // 2`` disjoint rotations to
    every matrix in the batch; ``sweeps`` defaults to
    :func:`_default_sweeps`, and a spectrum graded evenly over many
    decades wants more (pass it).  The count is fixed on purpose: the
    time does not depend on the data.  Where the rounds run is chosen
    when the program is lowered (:func:`_sweep_chain`): float32 up to
    64 x 64 in a program for one TPU device is one Mosaic kernel with
    the batch on the lanes (``vmap`` folds into that batch), anything
    else one ``lax.scan``; both differentiate, by the scan's plain-lax
    rules.  Best for large batches of small ``n`` (the per-chunk PCA
    regime); for a single big matrix prefer ``jnp.linalg.eigh``.
    Complex input falls back to ``jnp.linalg``.
    """
    a = jnp.asarray(a)
    if a.ndim < 2 or a.shape[-1] != a.shape[-2]:
        raise ValueError("jacobi_eigh requires (..., n, n), got %s"
                         % (a.shape,))
    if jnp.iscomplexobj(a):
        return (jnp.linalg.eigh(a) if vectors else jnp.linalg.eigvalsh(a))
    if not jnp.issubdtype(a.dtype, jnp.floating):
        a = a.astype(jnp.float64 if jax.config.jax_enable_x64 else jnp.float32)
    n = a.shape[-1]
    if sweeps is None:
        sweeps = _default_sweeps(n, a.dtype)
    odd = n % 2
    m = n + odd
    if odd:
        pad = [(0, 0)] * (a.ndim - 2) + [(0, 1), (0, 1)]
        a = jnp.pad(a, pad)
        # dummy diagonal above the spectral radius (Gershgorin: rho <=
        # m * max|a|, computed without squaring so f32 inputs near the
        # dtype max don't overflow): every (i, dummy) pair then rotates by
        # theta = 0.5*atan2(0, big - a_ii) = 0 and the dummy stays
        # decoupled (a zero diagonal would swap itself in via theta = pi/2
        # and scramble the spectrum)
        big = 1.0 + m * jnp.max(jnp.abs(a), axis=(-2, -1))
        a = a.at[..., n, n].set(big)

    w, V = _sweep_chain(a, sweeps, vectors)
    if odd:
        w = w[..., :n]   # dummy never swaps, so it is still at index n
    order = jnp.argsort(w, axis=-1)
    if not vectors:
        return jnp.take_along_axis(w, order, axis=-1)
    if odd:
        V = V[..., :n, :n]
    V = jnp.take_along_axis(V, order[..., None, :], axis=-1)
    return jnp.take_along_axis(w, order, axis=-1), V


def _sweep_chain(a, sweeps, vectors):
    """``sweeps`` sweeps of the parallel ordering over ``(..., m, m)``,
    ``m`` even: the diagonal left and, with ``vectors``, the accumulated
    rotations (else ``None``), both in the input's index order.  One
    algorithm and one schedule, two executors.  Real float32 of at most
    ``_JACOBI_MAX_DIM`` goes through :func:`_chain_entry`, which chooses
    when the program is lowered: ONE Mosaic kernel where it is compiled
    for a TPU, the ``lax.scan`` of :func:`_scan_sweeps` elsewhere.
    Float64 and the half precisions are the scan's on every backend."""
    if a.dtype == jnp.float32 and a.shape[-1] <= _JACOBI_MAX_DIM:
        return _chain_entry(sweeps, vectors)(a)
    return _scan_sweeps(a, sweeps, vectors)


def _scan_sweeps(a, sweeps, vectors):
    """:func:`_sweep_chain` in plain ``lax``: one ``lax.scan`` of
    ``sweeps * (m - 1)`` rounds, each two permutation gathers plus
    elementwise math (fifteen small XLA kernels a round on a TPU: 33 us
    for 80 matrices of 64 x 64, ledger PR 26, ``eigh_ms.scan`` 14.7)."""
    m = a.shape[-1]
    sched = np.tile(_round_robin(m), (sweeps, 1, 1))      # (S, m//2, 2)
    P = sched[..., 0]
    Q = sched[..., 1]
    # per-round involution pi (p <-> q), precomputed host-side
    PI = np.tile(np.arange(m), (sched.shape[0], 1))
    rows = np.arange(sched.shape[0])[:, None]
    PI[rows, P] = Q
    PI[rows, Q] = P
    xs = (jnp.asarray(P), jnp.asarray(Q), jnp.asarray(PI))

    def rotate(M, pi, cv, sv, axis):
        # apply all n//2 disjoint rotations along one side:
        #   rows (axis=-2):  (Jt M)[i, :] = cv[i]*M[i, :] + sv[i]*M[pi[i], :]
        #   cols (axis=-1):  (M J)[:, j] = cv[j]*M[:, j] + sv[j]*M[:, pi[j]]
        coef = (cv[..., :, None], sv[..., :, None]) if axis == -2 \
            else (cv[..., None, :], sv[..., None, :])
        return coef[0] * M + coef[1] * jnp.take(M, pi, axis=axis)

    def step(carry, pqi):
        A, V = carry
        p, q, pi = pqi
        app = A[..., p, p]
        aqq = A[..., q, q]
        apq = A[..., p, q]
        theta = 0.5 * jnp.arctan2(2.0 * apq, aqq - app)
        c = jnp.cos(theta)
        s = jnp.sin(theta)
        zero = jnp.zeros(A.shape[:-2] + (m,), A.dtype)
        cv = zero.at[..., p].set(c).at[..., q].set(c)
        # both sides carry -s at p / +s at q:
        #   (Jt A)[p,:] = c A[p,:] - s A[q,:];  (B J)[:,p] = c B[:,p] - s B[:,q]
        sv = zero.at[..., p].set(-s).at[..., q].set(s)
        A = rotate(rotate(A, pi, cv, sv, -2), pi, cv, sv, -1)
        if V is not None:
            V = rotate(V, pi, cv, sv, -1)
        return (A, V), None

    V0 = jnp.broadcast_to(jnp.eye(m, dtype=a.dtype),
                          a.shape) if vectors else None
    (A, V), _ = jax.lax.scan(step, (a, V0), xs)
    return jnp.diagonal(A, axis1=-2, axis2=-1), V


# ---------------------------------------------------------------------
# the sweep chain as one Mosaic kernel.  The batch lies on the lanes and
# a matrix's rows on the major axis, so ``(m, m, B)`` is ``m`` slabs of
# ``(m, 128)``: 64 x 64 x 128 float32 = 2 MiB, resident in VMEM for all
# ``sweeps * (m - 1)`` rounds.  The circle method is run with the
# players moving through FIXED seats: seat ``i`` of the top half always
# pairs with seat ``i`` of the bottom half, and between rounds everyone
# but ``t0`` moves one seat on (``_seating``), so one loop body serves
# every round: no index is dynamic but the round's
# ---------------------------------------------------------------------

_LANES = 128
# XLA names the kernel's instruction, and so its event on the device
# trace, after this; the benchmark tells the eigensolver by a name that
# holds "while" or "custom-call" (benchmark/metrics/eigh_ms.scan.json,
# gram_roofline.json), so the name is part of what those metrics read
_KERNEL_NAME = "jacobi-sweeps-custom-call"


@lru_cache(maxsize=None)
def _seating(m):
    """Who sits where: ``(m - 1, 2, m // 2)`` original indices held by
    the top and the bottom seats in each round of a sweep.  Round 0 is
    ``0..h-1`` over ``m-1..h``; the re-seating ``top' = t0, b0, t1 ..
    t[h-2]``, ``bottom' = b1 .. b[h-1], t[h-1]`` is the circle method's
    rotation, so each round's ``(top[i], bottom[i])`` are
    :func:`_round_robin`'s pairs of that round, and after ``m - 1`` rounds
    everyone is back where the sweep began."""
    h = m // 2
    top, bottom = list(range(h)), [m - 1 - i for i in range(h)]
    rounds = []
    for _ in range(m - 1):
        rounds.append((top, bottom))
        if h > 1:
            top, bottom = ([top[0], bottom[0]] + top[1:h - 1],
                           bottom[1:] + [top[h - 1]])
    return np.asarray(rounds)


def _seat_bits(m):
    """One int32 a round: bit ``i`` says that seat ``i``'s top player is
    the pair's smaller original index (``_JACOBI_MAX_DIM`` / 2 = 32 seats:
    one word).  :func:`_scan_sweeps` rotates with ``p < q``; where the bit
    is clear the kernel's pair is ``(q, p)`` and ``a_qq - a_pp`` and ``s``
    change sign."""
    seats = _seating(m)
    up = (seats[:, 0] < seats[:, 1]).astype(np.uint32)
    bits = (up << np.arange(m // 2, dtype=np.uint32)).sum(axis=1)
    return bits.astype(np.uint32).view(np.int32)


def _atan2(y, x):
    """``atan2`` for the kernel: Mosaic (jax 0.9) lowers none.  Cephes'
    ``atanf`` on ``min/max`` of the magnitudes (so no quotient leaves
    ``[0, 1]`` whatever the scale), then the octant; ``atan2(0, 0) = 0``
    like ``lax.atan2``, and ``-0.0`` counts as zero."""
    ax, ay = jnp.abs(x), jnp.abs(y)
    hi, lo = jnp.maximum(ax, ay), jnp.minimum(ax, ay)
    t = lo / jnp.where(hi == 0.0, jnp.ones_like(hi), hi)
    far = t > 0.4142135623730951                  # tan(pi / 8)
    u = jnp.where(far, (t - 1.0) / (t + 1.0), t)
    z = u * u
    r = (((8.05374449538e-2 * z - 1.38776856032e-1) * z
          + 1.99777106478e-1) * z - 3.33329491539e-1) * z * u + u
    r = jnp.where(far, r + 0.25 * math.pi, r)
    r = jnp.where(ay > ax, 0.5 * math.pi - r, r)
    r = jnp.where(x < 0.0, math.pi - r, r)
    return jnp.where(y < 0.0, -r, r)


def _jacobi_kernel(bits_ref, a_ref, w_ref, *refs, m, rounds, vectors):
    """All ``rounds`` of the chain over one block of 128 lanes.

    ``a_ref`` is ``(mp * mp, 128)``: row ``r * mp + c`` holds entry
    ``(r, c)`` of every matrix, rows and columns in SEAT order, the top
    seats at ``0 .. h-1`` and the bottom seats at ``hp .. hp+h-1`` (``hp``
    is ``h`` rounded up to the 8 sublanes of a tile; what lies between is
    zero and stays zero).  ``w_ref`` ``(mp, 128)`` gets the diagonal,
    ``v_ref`` ``(m * mp, 128)`` the rotations' product, its rows in index
    order and its columns in seat order."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    v_ref = refs[0] if vectors else None
    acc, c_ref, s_ref = refs[-3:]
    h = m // 2
    mp = w_ref.shape[0]
    hp = mp // 2
    lanes = w_ref.shape[1]
    seat = jax.lax.broadcasted_iota(jnp.int32, (hp, lanes), 0)
    real = seat < h

    def slab(ref, r):
        return ref.at[pl.ds(r * mp, mp), :]

    def reseat(top, bottom):
        # the column side of the re-seating, on the sublanes: a roll and
        # a select (the row side is where a result slab is stored)
        if h == 1:
            return top, bottom
        new_top = jnp.where(seat == 0, top, jnp.where(
            seat == 1, pltpu.roll(bottom, 1, 0), pltpu.roll(top, 1, 0)))
        new_bottom = jnp.where(seat == h - 1, top,
                               pltpu.roll(bottom, hp - 1, 0))
        if h != hp:
            new_top = jnp.where(real, new_top, jnp.zeros_like(top))
            new_bottom = jnp.where(real, new_bottom, jnp.zeros_like(top))
        return new_top, new_bottom

    def columns(x, c, s):
        # (x J) for the round's h rotations, then the columns re-seated
        top, bottom = x[:hp], x[hp:]
        return reseat(c * top - s * bottom, c * bottom + s * top)

    def store(ref, r, halves):
        ref[pl.ds(r * mp, hp), :] = halves[0]
        ref[pl.ds(r * mp + hp, hp), :] = halves[1]

    acc[...] = a_ref[...]
    if vectors:
        column = jax.lax.broadcasted_iota(jnp.int32, (mp, lanes), 0)
        for r in range(m):        # identity: index r sits in seat r, or
            at = r if r < h else hp + (m - 1 - r)     # m-1-r of the bottom
            slab(v_ref, r)[...] = (column == at).astype(jnp.float32)

    def one_round(r, carry):
        att = acc[pl.ds(0, hp, stride=mp + 1), :]
        abb = acc[pl.ds(hp * mp + hp, hp, stride=mp + 1), :]
        atb = acc[pl.ds(hp, hp, stride=mp + 1), :]
        bits = bits_ref[jax.lax.rem(r, jnp.int32(m - 1))]
        up = (jax.lax.shift_right_logical(bits, seat) & 1) == 1
        theta = 0.5 * _atan2(2.0 * atb,
                             jnp.where(up, abb - att, att - abb))
        c = jnp.cos(theta)
        s = jnp.sin(theta)
        s = jnp.where(up, s, -s)
        c_ref[...] = c
        s_ref[...] = s

        def pair(i):
            t, b = slab(acc, i)[...], slab(acc, hp + i)[...]
            ci, si = c_ref[pl.ds(i, 1), :], s_ref[pl.ds(i, 1), :]
            return (columns(ci * t - si * b, c, s),
                    columns(ci * b + si * t, c, s))

        # rows: seat i's pair leaves for top[i + 1] and bottom[i - 1]
        # (t0 stays, b0 goes to top[1], t[h-1] to bottom[h-1]).  Going
        # down from h - 1, every store lands on a slab already read but
        # bottom[i - 1]'s, which waits one step in ``held``
        new_t, held = pair(h - 1)
        if h == 1:                      # one pair: nobody moves
            store(acc, 0, new_t)
            store(acc, hp, held)
        else:
            store(acc, hp + h - 1, new_t)
            for i in range(h - 2, -1, -1):
                new_t, new_b = pair(i)
                store(acc, hp + i, held)
                store(acc, i + (i > 0), new_t)
                held = new_b
            store(acc, 1, held)
        if vectors:
            for r_ in range(m):
                store(v_ref, r_, columns(slab(v_ref, r_)[...], c, s))
        return carry

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(rounds), one_round, 0)
    w_ref[pl.ds(0, hp), :] = acc[pl.ds(0, hp, stride=mp + 1), :]
    w_ref[pl.ds(hp, hp), :] = acc[pl.ds(hp * mp + hp, hp, stride=mp + 1), :]


def _lane_sweeps(a, sweeps, vectors):
    """:func:`_scan_sweeps` by the kernel, over a flat batch ``(B, m, m)``
    float32, ``m`` even: ``w (B, m)`` and ``v (B, m, m)`` or ``None``.
    The batch goes to the lanes, padded to blocks of 128 (a zero matrix
    rotates by ``atan2(0, 0) = 0``), one grid step a block; the few
    transposes around the call are XLA's, over B * m * m elements."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B, m = a.shape[0], a.shape[-1]
    h = m // 2
    hp = -(-h // 8) * 8
    mp = 2 * hp
    lanes = -(-max(B, 1) // _LANES) * _LANES
    # index -> seat order (top 0..h-1, bottom m-1..h), the halves padded
    # apart to hp, the batch last and padded to whole lane blocks
    seats = _seating(m)[0].reshape(-1)
    x = a[:, seats][:, :, seats].reshape(B, 2, h, 2, h)
    x = jnp.pad(x, ((0, lanes - B), (0, 0), (0, hp - h), (0, 0),
                    (0, hp - h)))
    x = x.reshape(lanes, mp * mp).T
    block = lambda rows: pl.BlockSpec((rows, _LANES), lambda g: (0, g))
    shapes = [jax.ShapeDtypeStruct((mp, lanes), jnp.float32)]
    if vectors:
        shapes.append(jax.ShapeDtypeStruct((m * mp, lanes), jnp.float32))
    out = pl.pallas_call(
        partial(_jacobi_kernel, m=m, rounds=sweeps * (m - 1),
                vectors=vectors),
        out_shape=shapes,
        grid=(lanes // _LANES,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), block(mp * mp)],
        out_specs=[block(s.shape[0]) for s in shapes],
        scratch_shapes=[pltpu.VMEM((mp * mp, _LANES), jnp.float32),
                        pltpu.VMEM((hp, _LANES), jnp.float32),
                        pltpu.VMEM((hp, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name=_KERNEL_NAME,
    )(jnp.asarray(_seat_bits(m)), x)
    # seat order back to index order
    back = np.argsort(seats)
    w = out[0].reshape(2, hp, lanes)[:, :h, :B].reshape(m, B)[back].T
    if not vectors:
        return w, None
    v = out[1].reshape(m, 2, hp, lanes)[:, :, :h, :B].reshape(m, m, B)
    return w, jnp.transpose(v[:, back], (2, 0, 1))


def _mosaic_fits(ctx):
    """Whether a Mosaic kernel can be placed in the program being lowered
    (``tpu_custom_call``'s own rule): a program for one device, or the
    inside of a fully manual ``shard_map``.  GSPMD does not partition a
    kernel, so a program for several chips outside ``shard_map`` keeps
    the scan."""
    axes = ctx.module_context.axis_context
    if hasattr(axes, "manual_axes"):
        return (axes.manual_axes | set(axes.mesh.manual_axes)
                == frozenset(axes.mesh.axis_names))
    return getattr(axes, "num_devices", 1) == 1


def _sweeps_primitive():
    """``jacobi_sweeps``: the chain over a flat batch ``(B, m, m)``
    float32, as a primitive because its executor is chosen when a program
    is LOWERED: by then the target is known (the engine lowers for the
    mesh it compiles for, a compile-only test for a described chip), at
    trace time it is not.

    Under ``vmap`` (a chunked map reaches here under two) ``pallas_call``'s
    own rule would make each mapped axis a grid axis: 80 kernel instances
    with ONE lane in use.  This rule folds the mapped axis into the flat
    batch and binds again, so nesting folds twice."""
    from jax._src import dispatch       # eager calls: jax's own cache
    from jax.extend.core import Primitive
    from jax.interpreters import batching, mlir
    prim = Primitive("jacobi_sweeps")
    prim.multiple_results = True
    prim.def_impl(partial(dispatch.apply_primitive, prim))

    @prim.def_abstract_eval
    def _(a, *, sweeps, vectors):
        out = [a.update(shape=a.shape[:2])]
        return out + [a] if vectors else out

    def lower(fits):
        def rule(ctx, a, *, sweeps, vectors):
            kernel = fits(ctx)
            fn = _lane_sweeps if kernel else _scan_sweeps
            # Mosaic has no 64-bit types; everything the kernel's side
            # traces is float32 and int32 whatever the session's x64 says
            with jax.enable_x64(jax.config.jax_enable_x64 and not kernel):
                return mlir.lower_fun(
                    lambda x: jax.tree.leaves(fn(x, sweeps, vectors)),
                    multiple_results=True)(ctx, a)
        return rule

    mlir.register_lowering(prim, lower(lambda ctx: False))
    mlir.register_lowering(prim, lower(_mosaic_fits), platform="tpu")

    def fold(args, dims, **params):
        a = jnp.moveaxis(args[0], dims[0], 0)
        out = prim.bind(a.reshape((-1,) + a.shape[2:]), **params)
        return [o.reshape(a.shape[:2] + o.shape[1:]) for o in out], \
            [0] * len(out)

    batching.primitive_batchers[prim] = fold
    return prim


_sweeps_p = _sweeps_primitive()


@lru_cache(maxsize=None)
def _chain_entry(sweeps, vectors):
    """:func:`_scan_sweeps` for ``(..., m, m)`` float32: the flattened
    batch through ``jacobi_sweeps``.  A ``pallas_call`` has no
    differentiation rule: this one's is the scan's, on the chip too."""

    @jax.custom_jvp
    def run(a):
        out = _sweeps_p.bind(a.reshape((-1,) + a.shape[-2:]),
                             sweeps=sweeps, vectors=vectors)
        w, v = [o.reshape(a.shape[:-2] + o.shape[1:])
                for o in out] + [None] * (not vectors)
        return w, v

    @run.defjvp
    def _(primals, tangents):
        return jax.jvp(lambda a: _scan_sweeps(a, sweeps, vectors),
                       primals, tangents)

    return run


# Jacobi-vs-QDWH routing: the batched sweep pays for small matrices in
# large batches and loses for a small batch*d (the sequential sweep
# chain is latency-bound) and for d > 64 (the scan's per-step O(B d^2)
# gathers outgrow QDWH's matmuls; the kernel seats at most 64 indices).
# Hence: small dims AND enough total work.  The thresholds were set for
# the scan on another host and toolchain, have no ledger line of their
# own and were NOT moved when the kernel came (PR 27), though it changes
# the trade: on a v5e 80 Grams of 64 x 64 cost 0.84 ms through the
# kernel, 29.2 ms through the scan and 13.7 ms through XLA's eigvalsh
# (PERF.md section 6, PR 27).  The one routed case the benchmark
# measures is ``series64-1chip.pca`` (80 Grams through Jacobi, one
# through QDWH: ``eigh_ms.scan``, PERF.md section 5).
_JACOBI_MAX_DIM = 64
_JACOBI_MIN_WORK = 2048          # batch * d below this -> QDWH


def _is_batch_tracer(g):
    # jax 0.9 deprecates jax.interpreters.batching.BatchTracer (attribute
    # access raises), so isinstance-check via _src with a name-scan
    # fallback; if both ever fail the routing degrades to the (correct,
    # slower-for-vmapped-grams) QDWH path, never to a wrong result
    try:
        from jax._src.interpreters import batching
        if isinstance(g, batching.BatchTracer):
            return True
    except Exception:
        pass
    return any(c.__name__ == "BatchTracer" for c in type(g).__mro__)


def _true_batch(g):
    """Total batch count including vmapped dims: under vmap the outer
    batch is invisible in ``g.shape`` (the per-chunk svdvals usage —
    BASELINE config 5b — maps over the chunk grid, so a single (d, d)
    Gram at trace time is really a whole batch of them); walk the
    batching tracers to recover the true amortisation."""
    batch = prod(g.shape[:-2])
    t = g
    while _is_batch_tracer(t) and hasattr(t, "val"):
        inner = t.val
        batch *= max(prod(inner.shape) // max(prod(t.shape), 1), 1)
        t = inner
    return batch


def _use_jacobi(g):
    d = g.shape[-1]
    if d > _JACOBI_MAX_DIM or jnp.iscomplexobj(g):
        return False
    return _true_batch(g) * d >= _JACOBI_MIN_WORK


def _gram_eigvalsh(g):
    return jacobi_eigh(g) if _use_jacobi(g) else jnp.linalg.eigvalsh(g)


def svdvals(x, gram_ratio=4):
    """Singular values of a (possibly batched) matrix, TPU-first.

    For tall-skinny blocks (rows >= ``gram_ratio`` * cols) — the shape of
    the reference's PCA workload (``BASELINE`` config 5: per-chunk SVD on
    ``(N, features)``) — the values come from the Gram matrix:
    ``sqrt(eigvalsh(x.T @ x))``.  The matmul runs on the MXU, and the
    eigendecomposition touches only a (cols, cols) matrix — routed to the
    batched :func:`jacobi_eigh` when cols <= 64 and the batch (or a
    vmapped context) amortises it, else XLA's QDWH — instead of XLA's
    QR-iteration SVD over the full block.

    The Gram matrix of real float32 with 8, 16, 32 or 64 columns is
    ``gram_products`` (:func:`_gram`): in a program for one TPU device a
    Mosaic kernel reads ``x`` once where it is stored, ``64 // cols`` row
    ranges of a block side by side so that the matrix unit is full, in
    contractions of ``_GRAM_BLOCK`` rows added outside the unit (no
    running-sum bias over a long block; rows that do not fill a step are
    a tail for ``dot_general``).  Batch axes of ``x`` itself and the axes
    of a bolt ``chunk().map`` fold into that one call; under any OTHER
    ``vmap``, and for complex, float64, half precisions and integers
    (widened to float32 inside the ``dot_general``'s fusion, never as a
    copy), other widths, the CPU, a program for several chips outside
    ``shard_map`` and under differentiation, it is one ``dot_general`` a
    block, as it always was: how a mapped axis lies in memory is known to
    the code that mapped it, not to this function, and the wrong view of
    a large array is a copy of it.  What this function cannot see is a
    float32 ``x`` computed in the same program (``svdvals(x * 2)``, the
    caller's own ``astype``): XLA fuses such a producer into a
    ``dot_general`` and cannot into a kernel, so ``x`` is written to HBM
    first (compiled for a v5e at ``(10, 1048576, 64)``: 2.68 GB of temp
    where there was none; under a chunked map and behind ``pca``'s
    deferred chain it was written before the kernel too).  Give the
    stored array.  The trade-off of the route is the classic
    one: forming the Gram matrix squares the condition number, so trailing
    singular values below ``sqrt(eps) * s_max`` lose accuracy — fine for
    PCA-style spectra, not for rank-revealing use.  Wide or near-square
    inputs fall back to ``jnp.linalg.svd``.
    """
    given = jnp.asarray(x)
    x = _widen(given, jnp)
    rows, cols = x.shape[-2], x.shape[-1]
    if rows >= gram_ratio * cols:
        g = _gram(x, jnp, _resolve("highest"), widened=x.dtype != given.dtype)
        ev = _gram_eigvalsh(g)                         # ascending, real
        ev = jnp.maximum(ev[..., ::-1], 0.0)           # descending, clamped
        return jnp.sqrt(ev).astype(_real_dtype(x.dtype))
    return jnp.linalg.svd(x, compute_uv=False)


def _check_k(k, d):
    """Validate a component-count request against ``d`` features; None
    means all."""
    if k is None:
        return d
    if not 1 <= k <= d:
        raise ValueError("k=%d out of range for %d features" % (k, d))
    return k


def _gram(x, xp, precision="highest", widened=False):
    """The Gram matrix ``X^H X`` of one ``(..., n, d)`` block — an MXU
    matmul on TPU ("highest" precision, f32 accumulation, unless the
    caller resolved a cheaper mode through the scoped policy).

    Real float32 of a width that packs into the matrix unit (``d`` of 8,
    16, 32, 64) goes through ``gram_products`` (:func:`_gram_primitive`):
    in a program for one TPU device the kernel :func:`_packed_gram` reads
    the block where it lies, ``64 // d`` row groups side by side, in
    contractions of ``_GRAM_BLOCK`` rows whose matrices are added outside
    the unit, so a block loses the unit's running-sum bias too (see
    ``_GRAM_RUN``).  Leading axes of ``x`` itself, and the axes a chunked
    map says it mapped, fold into that one call.

    Everything else is ONE ``dot_general`` over the block's rows:
    complex, float64, other widths, "default" precision, the CPU, a
    program for several chips outside ``shard_map``, differentiation, a
    block under a ``vmap`` whose origin nobody named, and ``widened``
    input: what the caller was GIVEN was bfloat16, float16 or integers
    and ``x`` is :func:`_widen`'s float32 of it.  XLA fuses that convert
    into a ``dot_general``, which then reads the narrow array; it cannot
    fuse it into a Mosaic call, so the kernel would be fed a float32 copy
    of the whole input written to HBM first (10.7 GB beside a stored
    ``int16[40,1048576,64]``: a 7.5 GB int16 series that compiled would
    be refused).  One contraction, never runs: a block is what a
    caller's ``vmap`` has cut from a longer axis already, and on the
    chip's tiled layout a second cut of that axis is a relayout copy of
    the whole array where the first was a bitcast (the same deployment
    at 32 time points, blocks of 2**20 rows cut once more, is refused at
    compile: "Used 20.00G of 15.75G hbm").  The price there is the
    matrix unit's bias over the block; a whole bolt array goes through
    :func:`_sample_gram` instead."""
    if xp is np:
        xt = np.swapaxes(x, -1, -2)
        return np.matmul(np.conj(xt) if np.iscomplexobj(x) else xt, x)
    if not widened and _kernel_serves(x, precision):
        return _gram_entry(precision, False)(x)[0]
    return _products(jnp.conj(x), x, precision)


def _products(u, v, precision):
    """``(.., len, d) x 2 -> (.., d, d)``: every leading axis a batch
    axis, the rows contracted on the matrix unit by one ``dot_general``."""
    k = u.ndim - 2
    acc = _acc_dtype(jnp.promote_types(u.dtype, v.dtype))
    return jax.lax.dot_general(
        u, v, (((k,), (k,)), (tuple(range(k)),) * 2),
        precision=precision, preferred_element_type=acc)


# rows of a whole array contracted in one go on the matrix unit.  At
# "high"/"highest" the unit's float32 running sum loses a little with every
# tile it adds, always downwards: on a v5e one contraction over 8,192 rows
# came out 1.0e-7 under the true Gram matrix's diagonal, over 65,536 rows
# 3.9e-6, over 524,288 rows 6.2e-6, and over 41.9 M rows 3.2e-3, worse than
# a single bfloat16 pass (PERF.md, PR 26; splitting costs no time there).
# So a whole array's sample axis is contracted in runs and the runs'
# matrices are added outside the unit, by a reduction that rounds to
# nearest.  This is the ``dot_general`` path's run; the kernel's is
# ``_GRAM_BLOCK``, shorter still, for a block under a chunked map too.
_GRAM_RUN = 1 << 14


def _run_gram(a, b, precision, widened=False, sums=False):
    """``sum over the sample axes of a[.., i] * b[.., j]`` for operands
    shaped ``samples + (d,)`` (every axis but the last contracted):
    ``(d, d)``; with ``sums`` (a caller that centres) ``(that, sum over
    the sample axes of a)``.

    The samples are contracted where they lie, never flattened to
    ``(n, d)`` first: on the chip's tiled layout that reshape is a
    relayout copy of the whole array wherever the sample axes are not
    adjacent in memory (``f32[40,1048576,64]`` would not compile beside
    its own copy on a 16 GB chip).

    Real float32 of a width that packs (``a is b`` then), stored as
    float32 (not ``widened``: see :func:`_gram`), is ``gram_products``
    with every leading axis a sample axis: in a program
    for one TPU device the stored array goes to :func:`_packed_gram`
    where it lies, in contractions of ``_GRAM_BLOCK`` rows, and the
    planes' matrices are added here; the sums are then the row sums of
    the blocks the kernel holds, and no pass of their own.  Every other
    input, backend and program keeps :func:`_gram_in_runs` (and
    ``jnp.sum``), and so does differentiation."""
    if a is b and not widened and _kernel_serves(a, precision):
        out = _gram_entry(precision, True, sums)(a)
        return tuple(out) if sums else out[0]
    total = _column_sums(a, True) if sums else None
    g = _gram_in_runs(a, b, precision)
    return (g, total) if sums else g


def _gram_in_runs(a, b, precision):
    """:func:`_run_gram` by ``dot_general``.  The last sample axis is cut
    into runs of ``_GRAM_RUN`` rows (and a shorter tail where it does not
    divide); every run of every leading sample index gives one ``(d, d)``
    product on the matrix unit, and those are summed outside it."""
    n, d = a.shape[-2], a.shape[-1]
    count, tail = divmod(n, _GRAM_RUN)
    parts = []
    if count:
        cut = count * _GRAM_RUN
        shape = a.shape[:-2] + (count, _GRAM_RUN, d)
        parts.append(_products(a[..., :cut, :].reshape(shape),
                               b[..., :cut, :].reshape(shape), precision))
    if tail:
        parts.append(_products(a[..., n - tail:, :], b[..., n - tail:, :],
                               precision))
    # the barrier keeps XLA from folding the sums back into one long
    # contraction (reduce-of-batched-dot is a rewrite it knows)
    parts = jax.lax.optimization_barrier(parts)
    return sum(jnp.sum(part, axis=tuple(range(part.ndim - 2)))
               for part in parts)


# ---------------------------------------------------------------------
# the Gram pass as one Mosaic kernel.  ``(d, K) x (K, d)`` with d = 64
# uses every 128-row tile of weights the matrix unit loads for 64
# streamed rows and fills a quarter of its 128 x 128 result; at "highest"
# that was 28.5 ms for one read of 10.74 GB on a v5e, 46 % of HBM
# (PERF.md section 5, PR 27).  "highest" is float32 by bfloat16 pieces,
# ``x = h + m + l`` of 8 significant bits each, and the six products
# that matter: ``hh + hm + mh + mm + hl + lh`` (what is dropped is under
# 2**-24 of a product).  The kernel stacks 64 feature rows' ``h`` and
# ``m`` on the sublanes, ``C = [H; M]`` of ``(128, T)``: ONE full
# product ``C C^T`` holds ``HH, HM, MH, MM`` in its four quadrants, every
# one of them wanted, and a quarter-size ``H L^T`` the rest (``lh`` is
# its transpose).  A width under 64 fills the 64 rows with ``64 // d``
# row groups of one batch element, whose Gram matrices are the diagonal
# blocks of the quadrants; the blocks between two groups are computed and
# dropped.
# ---------------------------------------------------------------------

# XLA names the kernel's instruction, and so its event on the device
# trace, after this.  It must hold neither "while" nor "custom-call":
# benchmark/metrics/gram_roofline.json and eigh_ms.scan.json take every
# device operation so named for the EIGENSOLVER and leave it out of
# gram_roofline's denominator
_GRAM_KERNEL_NAME = "packed_gram"
# the form that also hands back the blocks' row sums: a name of its own, so
# that a trace says which form a program ran, under the same two rules
_GRAM_SUMS_KERNEL_NAME = "packed_gram_sums"
# rows of one row group contracted in one go on the matrix unit; the
# blocks' matrices are added on the vector unit, which rounds to nearest
# (see ``_GRAM_RUN``: 1.0e-7 at 8,192 rows).  One constant, from one
# sweep on the chip (PERF.md section 6, PR 29): 64 x _GRAM_BLOCK float32,
# double-buffered, with its three bf16 pieces, sits in the default
# scoped VMEM
_GRAM_BLOCK = 8192
_GRAM_ROWS = _LANES // 2        # feature rows a step: half the sublanes


def _gram_groups(d):
    """Row groups of width ``d`` side by side in one step's 64 feature
    rows (64 -> 1, 32 -> 2, 16 -> 4, 8 -> 8); 0 where ``d`` does not pack
    (48, 100, anything over 64)."""
    return _GRAM_ROWS // d if d % 8 == 0 and _GRAM_ROWS % d == 0 else 0


def _kernel_serves(x, precision):
    """Whether ``gram_products`` takes this Gram matrix (what it lowers to
    is decided later, by the program's target).  Real float32, a width
    that packs, and "highest", whose products the kernel makes.  Complex,
    float64, other widths and the cheaper modes ("high", "default") keep
    ``dot_general``; so does float32 that :func:`_widen` made of a
    narrower stored array, which the callers test themselves (the dtype
    here no longer shows it)."""
    return (x.dtype == jnp.float32 and x.ndim >= 2
            and _gram_groups(x.shape[-1]) > 0 and precision == "highest")


def _gram_kernel(*refs, sums):
    """One grid step.  ``refs``: the row groups' blocks ``(d / 8, 8, T)``,
    then the accumulators of the batch element, ``(128, 128)`` for
    ``[H; M] [H; M]^T`` and ``(64, 64)`` for ``H L^T``, which stay in VMEM
    over the last grid axis.  The blocks are stacked on the sublanes to
    ``(64, T)`` float32 and split into three bfloat16 pieces on the
    vector unit; each product is ONE contraction of ``T`` rows on the
    matrix unit (the lanes of both sides), added to its accumulator in
    float32 on the vector unit.

    With ``sums`` a third accumulator ``(64, 128)`` takes the stacked
    block's row sums, a lane at a time: the float32 values themselves,
    before the split, added on the vector unit (which rounds to nearest);
    its 128 lanes are added up outside."""
    from jax.experimental import pallas as pl
    blocks, outs = refs[:-2 - sums], refs[-2 - sums:]
    full, quarter = outs[:2]

    @pl.when(pl.program_id(2) == 0)
    def _():
        for out in outs:
            out[...] = jnp.zeros_like(out)

    x = jnp.concatenate([r[...].reshape(-1, r.shape[-1]) for r in blocks],
                        axis=0)
    if sums:
        outs[2][...] += _pairwise([x[:, k:k + _LANES]
                                   for k in range(0, x.shape[1], _LANES)])
    h = x.astype(jnp.bfloat16)
    x = x - h.astype(jnp.float32)
    m = x.astype(jnp.bfloat16)
    low = (x - m.astype(jnp.float32)).astype(jnp.bfloat16)
    c = jnp.concatenate([h, m], axis=0)
    dot = partial(jax.lax.dot_general,
                  dimension_numbers=(((1,), (1,)), ((), ())),
                  preferred_element_type=jnp.float32)
    full[...] += dot(c, c)
    quarter[...] += dot(h, low)


def _pairwise(parts):
    """The sum of ``parts`` as a balanced tree, written depth first (few
    partial sums alive at a time)."""
    if len(parts) == 1:
        return parts[0]
    half = len(parts) // 2
    return _pairwise(parts[:half]) + _pairwise(parts[half:])


def _packed_gram(x, cut, sums=False):
    """``x^T x`` over the rows of every ``(n, d)`` of ``lead + (n, d)``
    float32 by the kernel: ``([lead + (d, d)], rows taken)``, or ``(None,
    0)`` where ``n`` is under one step's ``64 // d`` blocks; with ``sums``
    the list also holds the sums of those rows, ``lead + (d,)``.  With
    ``cut`` the last axis of ``lead`` is a cut of the stored row axis (a
    chunk grid); the axes before it lie outside the rows in memory
    (planes, keys).

    The operand reaches the kernel as a VIEW of the stored array, and on
    the chip's tiled layout (rows on the lanes, features on the
    sublanes: ``f32[40,1048576,64]`` is ``{1,2,0:T(8,128)}``) that takes
    the order written here: the features split into ``(d / 8, 8)``
    behind the rows, then ONE transpose to ``(planes, d / 8, grid, 8,
    rows)``, which is how a cut of the row axis lies in memory (the grid
    BETWEEN the two halves of the tiled feature axis).  XLA folds that to
    a bitcast.  The same view written from the other end
    (``swapaxes(-1, -2)`` first), a flattened ``(planes, d, grid *
    rows)``, and ``pallas_call``'s own batching under the map's ``vmap``s
    are each a relayout copy of the whole array: "Used 20.00G of 15.75G
    hbm" at the benchmark's size (compiled for the described v5e, ISSUE
    29).

    Row groups pair by row range within one batch element: grid step
    ``j`` reads blocks ``j, steps + j, ..`` of the same plane or chunk, so
    an odd number of planes needs nothing.  Rows from ``groups * steps *
    block`` on are the caller's tail."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    block = _GRAM_BLOCK
    lead, (n, d) = x.shape[:-2], x.shape[-2:]
    groups = _gram_groups(d)
    steps = n // (groups * block)
    if not steps:
        return None, 0
    grid = lead[-1] if cut else 1
    major = prod(lead) // grid
    view = x.reshape(major, grid, n, d // 8, 8).transpose(0, 3, 1, 4, 2)
    r = _GRAM_ROWS
    # [H; M] [H; M]^T and H L^T; with ``sums`` the row sums by lane
    shapes = [(_LANES, _LANES), (r, r)] + [(r, _LANES)] * sums
    full, quarter, *lanes = pl.pallas_call(
        partial(_gram_kernel, sums=sums),
        out_shape=[jax.ShapeDtypeStruct((major, grid) + shape, jnp.float32)
                   for shape in shapes],
        grid=(major, grid, steps),
        in_specs=[pl.BlockSpec((None, d // 8, None, 8, block),
                               lambda p, g, j, k=k: (p, 0, g, 0,
                                                     k * steps + j))
                  for k in range(groups)],
        out_specs=[pl.BlockSpec((None, None) + shape,
                                lambda p, g, j: (p, g, 0, 0))
                   for shape in shapes],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name=_GRAM_SUMS_KERNEL_NAME if sums else _GRAM_KERNEL_NAME,
    )(*[view] * groups)
    out = (full[..., :r, :r] + full[..., r:, r:]
           + (full[..., :r, r:] + full[..., r:, :r])
           + (quarter + jnp.swapaxes(quarter, -1, -2)))
    out = sum(out[..., k * d:(k + 1) * d, k * d:(k + 1) * d]
              for k in range(groups))
    # the lanes first, then the groups: a group's sums are rows k * d to
    # (k + 1) * d of the 64
    out = [out.reshape(lead + (d, d))] + [
        jnp.sum(jnp.sum(rows, axis=-1).reshape(lead + (groups, d)), axis=-2)
        for rows in lanes]
    return out, groups * steps * block


def _kernel_gram(x, precision, cut, samples, sums=False):
    """``gram_products`` where the kernel can be placed: the rows
    :func:`_packed_gram` takes, the tail by :func:`_plain_gram`."""
    out, done = _packed_gram(x, cut, sums)
    lead = tuple(range(x.ndim - 2))
    if samples and done:
        out = [jnp.sum(part, axis=lead) for part in out]
    if done < x.shape[-2]:
        tail = _plain_gram(x[..., done:, :], precision, samples, sums)
        out = tail if not done else [a + b for a, b in zip(out, tail)]
    return out


def _column_sums(x, samples):
    """The sums ``gram_products`` hands a caller that centres, as a pass
    of their own: over the rows of every block, or with ``samples`` over
    every axis but the features'."""
    return jnp.sum(x, axis=tuple(range(x.ndim - 1)) if samples else -2)


def _plain_gram(x, precision, samples, sums=False):
    """What ``gram_products`` computes, by ``dot_general``: one
    contraction a block, or a whole array's in runs; with ``sums`` the
    rows' sums by ``jnp.sum`` first, as a centring caller's own mean was
    before the primitive had them."""
    total = [_column_sums(x, samples)] if sums else []
    return [_gram_in_runs(x, x, precision) if samples
            else _products(x, x, precision)] + total


def _gram_primitive():
    """``gram_products``: the Gram matrices of ``lead + (n, d)`` float32,
    ``[lead + (d, d)]``, or with ``samples`` their sum ``[(d, d)]``.  A
    primitive for :func:`_sweeps_primitive`'s reason: whether a Mosaic
    kernel can be placed is known when the program is LOWERED.  In a
    program for one TPU device it is :func:`_packed_gram`; on the CPU,
    and in a program for several chips outside ``shard_map``, it is
    :func:`_plain_gram`, exactly what ran before the kernel was.

    ``sums`` is set by a caller that centres (``pca``, ``cov``): the
    result is then ``[gram, sums]``, the rows' sums ``lead + (d,)`` (with
    ``samples`` ``(d,)``) beside the matrices.  The kernel has every
    block in VMEM when it makes its products, so there the sums cost no
    pass over the data (:func:`_gram_kernel`); every other executor adds
    them up by ``jnp.sum``, the pass a mean was before.  Without ``sums``
    the kernel, its name and its outputs are what they were: an output
    nobody reads is not dropped from a Mosaic call, so nobody is handed
    one.

    Under ``vmap`` the rule folds a mapped axis into ``lead`` only where
    its NAME says how it lies in memory (``tpu/chunk.py :: MappedAxis``,
    which a chunked map gives the axes it maps).  A
    stored ``(80, R, 64)`` under a user's own ``jax.vmap`` and a chunk
    grid's ``(40, 2, R, 64)`` batch to the same shapes in the OTHER
    physical order; the wrong view is a copy of the whole array, so a
    mapped axis of unknown origin keeps ``dot_general``."""
    from jax._src import dispatch       # eager calls: jax's own cache
    from jax.extend.core import Primitive
    from jax.interpreters import mlir
    from jax._src.interpreters import batching
    prim = Primitive("gram_products")
    prim.multiple_results = True
    prim.def_impl(partial(dispatch.apply_primitive, prim))

    @prim.def_abstract_eval
    def _(x, *, precision, cut, samples, sums):
        d = x.shape[-1]
        lead = () if samples else x.shape[:-2]
        return [x.update(shape=lead + (d, d))] \
            + [x.update(shape=lead + (d,))] * sums

    def lower(fits):
        def rule(ctx, x, *, precision, cut, samples, sums):
            n, d = ctx.avals_in[0].shape[-2:]
            kernel = fits(ctx) and n >= _gram_groups(d) * _GRAM_BLOCK
            if kernel:
                from bolt_tpu import engine
                engine.record_gram_kernel_program(sums)
            fn = partial(_plain_gram, precision=precision, samples=samples,
                         sums=sums)
            if kernel:
                fn = partial(_kernel_gram, precision=precision, cut=cut,
                             samples=samples, sums=sums)
            # Mosaic has no 64-bit types (see ``jacobi_sweeps``)
            with jax.enable_x64(jax.config.jax_enable_x64 and not kernel):
                return mlir.lower_fun(fn, multiple_results=True)(ctx, x)
        return rule

    mlir.register_lowering(prim, lower(lambda ctx: False))
    mlir.register_lowering(prim, lower(_mosaic_fits), platform="tpu")

    def fold(axis_data, args, dims, *, precision, cut, samples, sums):
        from bolt_tpu.tpu.chunk import MappedAxis
        if dims[0] is None:
            out = prim.bind(args[0], precision=precision, cut=cut,
                            samples=samples, sums=sums)
            return out, [None] * len(out)
        x = jnp.moveaxis(args[0], dims[0], 0)
        name = axis_data.name
        named = isinstance(name, MappedAxis) and not samples
        # the one row cut folds first, in front of the block itself: keys
        # lie outside it in memory
        row_cut = named and name.rows == x.shape[-2] and x.ndim == 3 \
            and not cut
        if not (row_cut or named and not name.rows):
            out = jax.vmap(partial(_plain_gram, precision=precision,
                                   samples=samples, sums=sums))(x)
        else:
            out = prim.bind(x, precision=precision, cut=cut or row_cut,
                            samples=False, sums=sums)
        return out, [0] * len(out)

    batching.fancy_primitive_batchers[prim] = fold
    return prim


_gram_p = _gram_primitive()


@lru_cache(maxsize=None)
def _gram_entry(precision, samples, sums=False):
    """:func:`_plain_gram` for real float32 of a width that packs, through
    ``gram_products``.  A ``pallas_call`` has no differentiation rule:
    this one's is ``dot_general``'s (and ``jnp.sum``'s), on the chip
    too."""

    @jax.custom_jvp
    def run(x):
        return _gram_p.bind(x, precision=precision, cut=False,
                            samples=samples, sums=sums)

    @run.defjvp
    def _(primals, tangents):
        return jax.jvp(partial(_plain_gram, precision=precision,
                               samples=samples, sums=sums),
                       primals, tangents)

    return run


def _sample_gram(x, precision, second_conj=False, widened=False,
                 sums=False):
    """The Gram matrix of ``sample_shape + (d,)`` data over all its
    leading axes: ``sum conj(x[.., i]) * x[.., j]``, or with
    ``second_conj`` ``sum x[.., i] * conj(x[.., j])`` (``np.cov``'s
    convention; the two are one for real data).  With ``sums`` (a caller
    that centres) ``(that, sum x[.., i])``: the mean's numerator from
    the pass that is made anyway, where the kernel makes it.  See
    :func:`_run_gram`."""
    if not jnp.iscomplexobj(x):
        return _run_gram(x, x, precision, widened, sums)
    total = _column_sums(x, True) if sums else None
    a, b = (x, jnp.conj(x)) if second_conj else (jnp.conj(x), x)
    g = _run_gram(a, b, precision)
    return (g, total) if sums else g


def _project(x, vec, precision):
    """``x @ vec`` over the trailing feature axis of ``sample_shape +
    (d,)`` data, the sample axes left as they are."""
    return jax.lax.dot_general(
        x, vec, (((x.ndim - 1,), (0,)), ((), ())), precision=precision)


def _features_last(mapped, kshape, d):
    """``sample_shape + (d,)``: the feature axes merged into one (no
    reshape at all for a single feature axis), widened for the
    decomposition, and whether widening converted it (see :func:`_gram`).
    The sample axes are NOT merged."""
    if mapped.shape != kshape + (d,):
        mapped = mapped.reshape(kshape + (d,))
    x = _widen(mapped, jnp)
    return x, x.dtype != mapped.dtype


def _decompose_gram(g, k, xp, eigh_fn):
    """Eigendecompose a Gram matrix: returns ``(vec (d, k), ev (k,))`` in
    descending order with negative eigenvalues clamped to zero."""
    ev, vec = eigh_fn(g)                               # ascending
    ev = xp.maximum(ev[..., ::-1], 0.0)[..., :k]       # descending, clamped
    vec = vec[..., ::-1][..., :k]
    return vec, ev


def _gram_decompose(x, k, xp, eigh_fn, widened=False):
    """Shared Gram-route core for the PCA family: ``x`` is ``(n, d)``,
    returns ``(vec (d, k), ev (k,))`` in descending order.  ``xp`` is the
    array namespace (numpy for the local oracle, jnp inside jit) so the
    backends run the same sequence (the TPU pca program splices its
    centering fold between :func:`_gram` and :func:`_decompose_gram`)."""
    return _decompose_gram(_gram(x, xp, widened=widened), k, xp, eigh_fn)


def _tpu_eigh(g):
    if _use_jacobi(g):
        return jacobi_eigh(g, vectors=True)
    return jnp.linalg.eigh(g)


def _widen(x, xp):
    """Promote to a float dtype the decomposition can run in (ints would
    silently truncate components to zero)."""
    if not xp.issubdtype(x.dtype, xp.inexact):
        return x.astype(xp.float64 if (xp is np or jax.config.jax_enable_x64)
                        else xp.float32)
    if x.dtype in (jnp.bfloat16, jnp.float16):
        return x.astype(jnp.float32)
    return x


def lstsq(a, b):
    """Least-squares solution of tall-skinny ``a @ x ~ b``, TPU-first.

    ``a`` is ``(..., n, d)`` with ``n >= d`` and full column rank; ``b``
    is ``(..., n)`` or ``(..., n, k)``.  Returns ``x`` shaped
    ``(..., d)`` / ``(..., d, k)``.  Solved through :func:`tsqr`
    (CholeskyQR2): the O(n d^2) work is explicit-precision MXU matmuls,
    the triangular solve touches only (d, d), and one residual-refinement
    step scrubs the solve's rounding — no column-serial Householder
    sweep.  Same conditioning envelope as :func:`tsqr` (cond(a) up to
    ~1/sqrt(eps)); for rank-deficient or ill-conditioned systems use
    ``jnp.linalg.lstsq``.

    ``a`` (and ``b``) may also be bolt arrays: records are the rows (key
    axes flatten to ``n`` — axis 0 on the local backend), value axes
    flatten to the ``d`` features / ``k`` targets.  On mode 'tpu' the
    data stays sharded and GSPMD inserts the all-reduce for the
    Gram-sized contractions (unlike :func:`pca` this is not one cached
    program — a deferred chain materialises first).  Memory: the thin
    ``q`` is materialised at the size of ``a`` — for HBM-filling systems
    form the normal equations from Gram blocks instead (the
    :func:`tallskinny_pca` machinery).
    """
    if getattr(a, "mode", None) == "tpu":
        n = prod(a.shape[:a.split])
        a = a.tojax().reshape((n, prod(a.shape[a.split:])))
    elif getattr(a, "mode", None) == "local":
        a = np.asarray(a).reshape((a.shape[0], -1))
    if getattr(b, "mode", None) == "tpu":
        n = prod(b.shape[:b.split])
        rest = prod(b.shape[b.split:])
        bj = b.tojax()
        b = bj.reshape((n,)) if b.ndim == b.split else bj.reshape((n, rest))
    elif getattr(b, "mode", None) == "local":
        bl = np.asarray(b)
        b = bl if bl.ndim == 1 else bl.reshape((bl.shape[0], -1))
    given = jnp.asarray(a)
    a = _widen(given, jnp)
    b = _widen(jnp.asarray(b), jnp)
    if jnp.iscomplexobj(a) or jnp.iscomplexobj(b):
        raise ValueError("lstsq supports real systems; use jnp.linalg.lstsq "
                         "for complex ones")
    # promote, never narrow (an f64 b must not silently drop to f32 a)
    dt = jnp.promote_types(a.dtype, b.dtype)
    a, b = a.astype(dt), b.astype(dt)
    vec = b.ndim == a.ndim - 1
    if a.ndim < 2 or (not vec and b.ndim != a.ndim) \
            or b.shape[-2 if not vec else -1] != a.shape[-2]:
        raise ValueError(
            "lstsq needs a (..., n, d) and b (..., n) or (..., n, k); got "
            "%s and %s" % (a.shape, b.shape))
    if vec:
        b = b[..., None]
    q, r = _tsqr(a, a.dtype != given.dtype)
    y = jnp.matmul(_adjoint(q), b, precision=_resolve("highest"))
    x = jax.scipy.linalg.solve_triangular(r, y, lower=False)
    # one refinement pass: e = y - r x at full precision repairs the
    # solve's blocked-matmul rounding (see tsqr's r_inv note)
    e = y - jnp.matmul(r, x, precision=_resolve("highest"))
    x = x + jax.scipy.linalg.solve_triangular(r, e, lower=False)
    return x[..., 0] if vec else x


def tallskinny_svd(x, k=None):
    """Thin SVD ``(u, s, vh)`` of tall-skinny (batched) matrices via the
    Gram route: one MXU matmul over the ``(..., n, d)`` data, a (d, d)
    eigenproblem (:func:`jacobi_eigh` when ``d <= 64`` and the batch
    amortises it — see ``_use_jacobi``), and one more matmul for
    ``u = x @ v / s``.  Same accuracy trade-off as
    :func:`svdvals` (condition number squares): singular triplets below
    ``sqrt(eps) * s_max`` lose accuracy, and for exactly zero singular
    values the corresponding ``u`` columns are returned as zeros rather
    than an arbitrary orthonormal completion.  ``k`` truncates to the
    top components.  Descending order, ``numpy.linalg.svd`` conventions.
    """
    given = jnp.asarray(x)
    x = _widen(given, jnp)
    if x.ndim < 2 or x.shape[-2] < x.shape[-1]:
        raise ValueError("tallskinny_svd requires (..., n, d) with n >= d, "
                         "got %s; use jnp.linalg.svd" % (x.shape,))
    d = x.shape[-1]
    vec, ev = _gram_decompose(x, _check_k(k, d), jnp, _tpu_eigh,
                              widened=x.dtype != given.dtype)
    s = jnp.sqrt(ev)
    safe = jnp.where(s > 0, s, 1.0)
    u = jnp.matmul(x, vec, precision=_resolve("highest")) / safe[..., None, :]
    u = jnp.where(s[..., None, :] > 0, u, 0.0)
    return u, s.astype(_real_dtype(x.dtype)), _adjoint(vec)


def tsqr(x):
    """Thin QR of tall-skinny (batched) matrices by CholeskyQR2, TPU-first.

    ``x`` is ``(..., n, d)`` with ``n >= d``; returns ``(q, r)`` with
    orthonormal ``q`` (same shape), upper-triangular ``r`` with positive
    diagonal, and ``q @ r == x``.  Two rounds of ``R = chol(X^T X)^T;
    Q = X R^{-1}`` — all MXU matmuls and a (d, d) Cholesky, no
    column-by-column Householder loop (XLA's ``qr`` is serial in d and
    built for one big matrix).  CholeskyQR2's orthogonality error is
    ~machine-eps for cond(x) up to ~1/sqrt(eps) — beyond that (or rank
    deficient, where the Cholesky NaNs) use ``jnp.linalg.qr``.
    """
    given = jnp.asarray(x)
    x = _widen(given, jnp)
    return _tsqr(x, x.dtype != given.dtype)


def _tsqr(x, widened):
    """:func:`tsqr` of a float ``x``; ``widened``: it is a conversion of
    what the caller was given (see :func:`_gram`)."""
    if x.ndim < 2 or x.shape[-2] < x.shape[-1]:
        raise ValueError("tsqr requires (..., n, d) with n >= d, got %s"
                         % (x.shape,))

    d = x.shape[-1]
    eye = jnp.eye(d, dtype=x.dtype)

    def _chol_qr(a, widened=False):
        g = _gram(a, jnp, _resolve("highest"), widened=widened)
        l = jnp.linalg.cholesky(g)                       # g = l @ l^H
        r = _adjoint(l)
        # invert only the small (d, d) triangle, then apply by matmul so
        # the O(n d^2) work runs at controlled precision (TPU's
        # TriangularSolve applies blocked matmuls at the bf16 default,
        # which would cap orthogonality ~1e-3 on f32 data).  One Newton
        # step X <- X(2I - RX) at precision="highest" scrubs the solve's
        # own rounding back to f32 eps.
        r_inv = _adjoint(jax.scipy.linalg.solve_triangular(
            l, jnp.broadcast_to(eye, l.shape), lower=True))
        correction = 2.0 * eye - jnp.matmul(r, r_inv, precision=_resolve("highest"))
        r_inv = jnp.matmul(r_inv, correction, precision=_resolve("highest"))
        q = jnp.matmul(a, r_inv, precision=_resolve("highest"))
        return q, r

    q1, r1 = _chol_qr(x, widened)
    q, r2 = _chol_qr(q1)                                 # re-orthogonalise
    return q, jnp.matmul(r2, r1, precision=_resolve("highest"))


def pca(b, k=None, center=False, axis=None, return_mean=False,
        fetch=True, precision=None):
    """Distributed PCA of a bolt array: sample axes x feature axes, all
    in ONE compiled SPMD program.

    The reference ecosystem runs this workload by chunking the sample
    axis and doing per-chunk ``numpy.linalg.svd`` inside Spark executors
    (BASELINE config 5 is its kernel).  Here the whole decomposition is
    a single XLA program over the sharded array: the Gram matrix
    ``X^T X`` is one MXU matmul per shard whose partial products GSPMD
    combines with an ICI all-reduce (the ``rdd.aggregate`` tree of
    SURVEY §3.4, lowered to hardware), the small (d, d) eigenproblem is
    solved on-device (a single matrix routes to XLA's QDWH eigh; large
    batches take :func:`jacobi_eigh`), and the projection
    ``X @ V`` runs shard-local.  Scores keep the input's key sharding;
    data never gathers to one device or host.

    Parameters: ``b`` — a bolt array (TPU or local mode; locals run the
    same Gram route in NumPy, except that with ``center=True`` the TPU
    program folds the centering into the Gram algebraically
    (``Gc = G - n mu mu^H`` — the centred matrix is never materialised)
    while the oracle subtracts the mean explicitly: results agree to
    ~``eps_f32 * (||mu||/sigma)^2`` relative — exact for mean-zero data,
    ~1e-2 at a 200-sigma offset; pre-shift data with larger offsets);
    ``k`` — number of components (default: all
    ``d``); ``center`` — subtract per-feature means first: the sums
    come out of the Gram pass where the ``packed_gram`` kernel makes it
    (stored float32 of 8, 16, 32 or 64 features in a program for one
    TPU device: no pass of their own, engine counter
    ``gram_sums_programs``), elsewhere one fused pass + a tiny psum;
    ``axis`` — the sample axes, like
    ``map``'s (default: the TPU array's key axes / axis 0 locally;
    a TPU array aligns by swapping when they differ, reference
    ``_align`` semantics).

    Returns ``(scores, components, singular_values)``: scores is a bolt
    array shaped ``sample_shape + (k,)`` with the input's mode (and key
    sharding on TPU); components ``(d, k)`` and singular values ``(k,)``
    are NumPy arrays (descending).  With ``return_mean=True`` a fourth
    element is the per-feature mean ``(d,)`` that was subtracted (zeros
    when ``center=False``) — needed to project NEW data consistently:
    ``scores_new = (x_new - mean) @ components``.

    ``fetch=False`` (TPU mode) returns components/singular values/mean
    as DEVICE-resident ``jax.Array``s instead of host ndarrays: the call
    then syncs nothing — back-to-back pca calls (or downstream jnp use
    of the components) pipeline without paying a host round-trip each.

    ``precision=None`` resolves through the scoped policy
    (``bolt.precision``), pinned at ``"highest"`` — what the Gram and
    projection matmuls cost at it is ``gram_roofline`` of the
    benchmark's ``series64-1chip.pca`` cell (``PERF.md``, section 5);
    ``"default"`` trades ~1e-2 relative score accuracy for one bf16
    pass.  The local oracle always computes in f64.

    The sample axes are contracted where they lie (``_sample_gram``,
    ``_project``): a ``(K, N, d)`` array keyed on axis 0 with
    ``axis=(0, 1)`` is never flattened to ``(K*N, d)``, which on the
    chip's tiled layout is a relayout copy as large as the data.

    A lazy out-of-core source (``bolt.fromcallback`` / ``fromiter``) is
    never uploaded whole: the decomposition is TWO passes of the
    streamed executor over it, because the components are not known
    until every sample has been seen (:func:`_pca_streamed`).  Pass 1
    folds the Gram matrix and the column sums slab by slab
    (``stream.maybe_gram``: this program's own Gram body a slab, the
    partials added on the device), the ``(d, d)`` eigenproblem is solved
    on the device, and pass 2 maps the source by ``x -> x @ V - mu @ V``
    and collects the scores slab by slab into the one resident array
    (``stream.collect``), which has to fit beside the slabs in flight.
    What the executor does not take (a filter, a chunked or stacked
    stage or a swap in front, sample axes that are not the leading ones,
    several processes, a one-shot iterator, scores past the resident
    budget: ``stream.gram_refusal`` and ``collect_refusal`` say which)
    materialises first, as every such source did before.

    Spans: ``linalg.pca`` from entry to the results in hand, with
    ``linalg.pca.launch`` (alignment and the one program enqueued) and
    ``linalg.pca.fetch`` (the small results brought to the host;
    absent with ``fetch=False``) beneath it; over a streamed source, in
    ``launch``'s place, ``linalg.pca.gram_pass`` (pass 1, from its first
    slab asked for to the Gram matrix in hand), ``linalg.pca.decompose``
    (the eigensolver's program enqueued: nothing waits for it, it runs
    on the device while pass 2's first slab goes up) and
    ``linalg.pca.project_pass`` (pass 2, to the scores ready: the
    executor's own wait), the executor's own ``stream.*`` spans beneath
    them.
    """
    from bolt_tpu._precision import resolve
    pr = resolve(precision)
    if getattr(b, "mode", None) != "tpu":
        return _pca_local(b, k, center, axis, return_mean)
    with _obs.span("linalg.pca", k=k, center=bool(center)):
        out = _pca_streamed(b, k, center, axis, pr) if b.streaming \
            else NotImplemented
        if out is NotImplemented:
            with _obs.span("linalg.pca.launch"):
                out = _pca_launch(b, k, center, axis, pr)
        scores, vec, sv, mu = out
        if fetch:
            # ONE batched host fetch for the small results: separate
            # device_gets cost a full host round-trip EACH.  With
            # fetch=False nothing syncs: they stay on the device
            with _obs.span("linalg.pca.fetch"):
                vec, sv, mu = (np.asarray(a) for a in
                               jax.device_get((vec, sv, mu)))
        return (scores, vec, sv, mu) if return_mean else (scores, vec, sv)


def _pca_sizes(k, n, d):
    if n < d:
        raise ValueError(
            "pca requires #samples >= #features (got %d x %d); swap your "
            "key/value axes or use jnp.linalg.svd" % (n, d))
    return _check_k(k, d)


def _pca_local(b, k, center, axis, return_mean):
    """The NumPy oracle of :func:`pca`: same sequence, host-side."""
    _, b, x_full, split, shape, n, d = _samples_features(
        b, axis, "pca", hint="; for plain matrices use tallskinny_pca")
    k = _pca_sizes(k, n, d)
    x = _widen(x_full.reshape(n, d), np)
    mu = x.mean(axis=0) if center else np.zeros(d, x.dtype)
    if center:
        x = x - mu
    vec, ev = _gram_decompose(x, k, np, np.linalg.eigh)
    vec = np.ascontiguousarray(vec)
    scores = (x @ vec).reshape(shape[:split] + (k,))
    out = (type(b)(scores), vec, np.sqrt(ev).astype(_real_dtype(x.dtype)))
    return out + (mu,) if return_mean else out


def _pca_program(funcs, split, kshape, d, k, center, pr, mesh):
    """The traced body of :func:`pca`'s one program over the base buffer
    (``funcs``: the deferred map chain fused in front).  Module-level so
    that a compile-only test can lower exactly what ships."""
    from bolt_tpu.parallel.sharding import key_sharding
    from bolt_tpu.tpu.array import _chain_apply
    n = prod(kshape)

    def program(data):
        x, widened = _features_last(_chain_apply(funcs, split, data),
                                    kshape, d)
        # Centering folds into the Gram algebraically (round-4 fusion):
        #   (X - mu)^T (X - mu) = X^T X - n mu mu^T
        # so the centred matrix is NEVER materialised — the raw X is
        # read by exactly two MXU matmuls (Gram + projection), instead
        # of a mean pass, a centred copy (read+write), and two matmuls
        # over the copy.  The mean's sums come back with the Gram
        # matrix: the packed_gram kernel adds up the rows of the blocks
        # it holds (ISSUE 33), every other executor makes them a fused
        # reduction of its own.  The
        # projection offset is applied to the (k,)-sized result:
        #   (X - mu) @ V = X @ V - mu @ V.
        # Conditioning: the fold loses the centred formulation's
        # guard against cancellation when ||mu|| >> sigma — the Gram
        # loses ~eps_f32 * (mu/sigma)^2 relative accuracy (measured:
        # ~1e-4 at 20 sigma, ~1e-2 at 200 sigma — see
        # test_pca_centering_fold_large_offset).  Pre-shift data with
        # larger offsets.
        if not center:
            g, total = _sample_gram(x, pr, widened=widened), None
        else:
            g, total = _sample_gram(x, pr, widened=widened, sums=True)
        vec, sv, mu, off = _pca_decompose(g, total, n, k, pr)
        # pinned "highest": the MXU's bf16 default costs ~3 decimal
        # digits on f32 data — visible in scores at PCA scale; the
        # scoped policy buys it back where the user accepts that
        scores = _project(x, vec, pr)
        if center:
            scores = scores - off
        scores = jax.lax.with_sharding_constraint(
            scores, key_sharding(mesh, kshape + (k,), split))
        return scores, vec, sv, mu
    return program


def _pca_decompose(g, total, n, k, pr):
    """From the raw Gram matrix ``g`` and the column sums ``total``
    (``None``: not centred) of ``n`` samples to ``(vec (d, k), sv (k,),
    mu (d,), off (k,))``: the centring folded into the matrix
    (``G - n mu mu^H``), its top ``k`` eigenpairs, and the offset
    ``mu @ vec`` that the projection of raw samples takes away.  Traced
    by the resident program and, on its own, between a streamed source's
    two passes."""
    if total is None:
        mu = jnp.zeros(g.shape[-1], g.dtype)
    else:
        mu = total / n
        g = g - n * jnp.outer(jnp.conj(mu), mu)
    vec, ev = _decompose_gram(g, k, jnp, _tpu_eigh)
    return vec, jnp.sqrt(ev), mu, jnp.matmul(mu, vec, precision=pr)


def _pca_launch(b, k, center, axis, pr):
    """Align, build (once per key) and enqueue :func:`pca`'s one program;
    returns ``(scores bolt array, vec, sv, mu)`` with the small results
    still on the device."""
    from bolt_tpu.tpu.array import _cached_jit
    _, b, _, split, shape, n, d = _samples_features(b, axis, "pca")
    k = _pca_sizes(k, n, d)
    kshape = shape[:split]
    # a deferred map chain fuses INTO the PCA program (one XLA program,
    # no materialised intermediate), same as map/filter/reduce consumers
    base, funcs = b._chain_parts()
    mesh = b._mesh

    def build():
        return jax.jit(_pca_program(funcs, split, kshape, d, k, center, pr,
                                    mesh))

    fn = _cached_jit(("ops-pca", funcs, base.shape, str(base.dtype), split,
                      mesh, k, center, pr), build)
    scores, vec, sv, mu = fn(base)
    return type(b)(scores, split, mesh), vec, sv, mu


def _sample_axes(b, axis):
    """The sample axes a streamed source is asked for, sorted: ``axis``,
    or its key axes."""
    from bolt_tpu.utils import tupleize
    return tuple(sorted(tupleize(axis))) if axis is not None \
        else tuple(range(b.split))


@lru_cache(maxsize=None)
def _projection(lead, d, pr, center):
    """The record-wise map of a streamed pca's second pass, ``(record,
    vec[, off]) -> scores``: the record's ``lead`` sample axes kept, its
    features merged as :func:`_features_last` merges them, projected by
    the resident program's own :func:`_project`.  One function a
    geometry, so that every request runs the program of the first."""
    def project(rec, vec, *off):
        x, _ = _features_last(rec, rec.shape[:lead], d)
        out = _project(x, vec, pr)
        return out - off[0] if center else out
    return project


def _scores_source(src, m, d, center, pr, vec, off):
    """``src`` one stage longer: every record projected onto ``vec`` (and
    ``off`` taken away), the two as side operands of the slab program."""
    from bolt_tpu import stream
    from bolt_tpu.utils import with_operands
    st = stream.result_state(src)
    ops = (vec, off) if center else (vec,)
    return src.with_stage(("map", with_operands(
        _projection(m - st.split, d, pr, center), *ops)))


def _scores_plan_source(src, m, d, k, center, pr):
    """:func:`_scores_source` with components of the right shape and dtype
    and no values: what the scores' place is planned by before a slab
    moves (``stream.collect_refusal``; ``analysis.check``'s BLT021)."""
    from bolt_tpu import stream
    wide = _widen(jnp.zeros((), stream.result_state(src).dtype), jnp).dtype
    return _scores_source(src, m, d, center, pr, np.zeros((d, k), wide),
                          np.zeros(k, wide))


def _pca_streamed(b, k, center, axis, pr):
    """:func:`pca` over a lazy out-of-core source in two passes of the
    streamed executor, or NotImplemented where it does not take the
    source (the caller materialises): both refusals are asked before a
    slab moves.  Returns what :func:`_pca_launch` returns."""
    from bolt_tpu import stream
    from bolt_tpu.tpu.array import _cached_jit
    src, axes = b._stream, _sample_axes(b, axis)
    m = len(axes)
    if stream.gram_refusal(src, axes, passes=2) is not None:
        return NotImplemented
    st = stream.result_state(src)
    n, d = prod(st.shape[:m]), prod(st.shape[m:])
    k = _pca_sizes(k, n, d)
    if stream.collect_refusal(_scores_plan_source(
            src, m, d, k, center, pr)) is not None:
        # the scores have no resident place: the source materialises
        # whole, or is refused in words (BLT020), as before
        return NotImplemented
    with _obs.span("linalg.pca.gram_pass"):
        parts = stream.maybe_gram(b, axes, pr, sums=center, passes=2)
    with _obs.span("linalg.pca.decompose"):
        fn = _cached_jit(
            ("ops-pca-decompose", n, d, k, center, pr, str(parts[0].dtype)),
            lambda: jax.jit(lambda g, *total: _pca_decompose(
                g, total[0] if total else None, n, k, pr)))
        vec, sv, mu, off = fn(*parts)
    with _obs.span("linalg.pca.project_pass"):
        scores = stream.collect(
            _scores_source(src, m, d, center, pr, vec, off), project=True)
        # the scores keep the source's keys; the sample axes past them
        # become keys as the resident program's alignment makes them
        scores = scores._align(list(axes))
    return scores, vec, sv, mu


def tallskinny_pca(x, k=None):
    """Principal components of a tall-skinny ``(n, d)`` matrix via the
    Gram route: eigendecompose ``x.T @ x`` (d x d, MXU matmul; Jacobi
    when ``_use_jacobi`` says the shape profits), return
    ``(components (d, k), singular_values
    (k,))`` in descending order.  The reference runs this workload as
    per-chunk SVD through Spark (``BASELINE`` config 5); here the big
    matmul is the only pass over the data."""
    n, d = x.shape
    if n < d:
        raise ValueError(
            "tallskinny_pca requires n >= d (got %d x %d): the rank-%d Gram "
            "matrix would pad the spectrum with zero eigenvalues whose "
            "eigenvectors are arbitrary; use jnp.linalg.svd" % (n, d, n))
    given = jnp.asarray(x)
    x = _widen(given, jnp)
    vec, ev = _gram_decompose(x, _check_k(k, d), jnp, _tpu_eigh,
                              widened=x.dtype != given.dtype)
    return vec.astype(x.dtype), jnp.sqrt(ev).astype(_real_dtype(x.dtype))


def _samples_features(b, axis, name, hint=""):
    """Shared samples×features preamble for :func:`pca`/:func:`cov`:
    mode dispatch, sample-axis resolution (``_align`` on TPU, moveaxis
    locally), and the flattened (n, d) sizes.  Returns
    ``(mode, b, x_full, split, shape, n, d)`` where ``x_full`` is the
    axis-aligned host array in local mode (None on TPU)."""
    from bolt_tpu.utils import tupleize

    mode = getattr(b, "mode", None)
    if mode not in ("local", "tpu"):
        raise TypeError("%s expects a bolt array (mode 'local' or 'tpu')%s"
                        % (name, hint))
    if mode == "tpu":
        b = b._align(list(_sample_axes(b, axis)))
        split = b.split
        x_full = None
        shape = b.shape
    else:
        axes = sorted(tupleize(axis)) if axis is not None else [0]
        split = len(axes)
        # move sample axes to the front (the local analog of _align)
        x_full = np.moveaxis(np.asarray(b), axes, range(split))
        shape = x_full.shape
    return mode, b, x_full, split, shape, prod(shape[:split]), prod(shape[split:])


def cov(b, axis=None, center=True, ddof=1, return_mean=False,
        precision=None):
    """Feature-covariance matrix of a bolt array viewed as samples ×
    features, in ONE compiled SPMD program.

    Same sample/feature split as :func:`pca` (``axis`` names the sample
    axes, defaulting to the key axes / axis 0 locally; features are the
    flattened remaining axes): the centred Gram matmul runs shard-local
    on the MXU and GSPMD all-reduces the (d, d) partial products — data
    never gathers.  ``ddof=1`` gives the sample covariance (numpy's
    ``np.cov`` default); ``center=False`` divides the raw second moment
    ``X^T X`` by ``n - ddof`` instead.  Like :func:`pca`, the TPU
    program folds the centering into the Gram algebraically (the local
    oracle subtracts the mean explicitly) — entries lose
    ~``eps_f32 * (||mu||/sigma)^2`` relative accuracy at large mean
    offsets.  With ``center`` the mean's sums come out of the Gram pass
    where the ``packed_gram`` kernel makes it (see :func:`pca`), so the
    data are read once; elsewhere they are a fused reduction of their
    own.  Returns a (d, d) NumPy array;
    ``return_mean=True`` appends the per-feature mean.  Superset of the
    reference (its ecosystem computes this via per-chunk jobs).
    ``precision=None`` resolves through the scoped policy like
    :func:`pca` (the Gram matmul is the cost).  A lazy out-of-core source
    is read in ONE pass of the streamed executor and never uploaded
    whole, where :func:`pca` would stream its first pass."""
    from bolt_tpu._precision import resolve
    pr = resolve(precision)
    if getattr(b, "mode", None) == "tpu" and b.streaming:
        # a lazy out-of-core source: ONE pass of the streamed executor,
        # where it takes the source (see pca)
        out = _cov_streamed(b, axis, center, ddof, pr)
        if out is not NotImplemented:
            return _cov_fetch(out, return_mean)
    mode, b, x_full, split, shape, n, d = _samples_features(b, axis, "cov")
    _cov_sizes(n, ddof)

    if mode == "local":
        x = _widen(x_full.reshape(n, d), np)
        mu = x.mean(axis=0) if center else np.zeros(d, x.dtype)
        if center:
            x = x - mu
        # np.cov convention: C_ij = E[(x_i - mu_i) conj(x_j - mu_j)] —
        # the conjugate is on the SECOND factor
        c = (x.T @ np.conj(x)) / (n - ddof)
        return (c, mu) if return_mean else c

    from bolt_tpu.tpu.array import _cached_jit
    base, funcs = b._chain_parts()
    mesh = b._mesh

    def build():
        return jax.jit(_cov_program(funcs, split, shape[:split], d, center,
                                    ddof, pr))

    fn = _cached_jit(("ops-cov", funcs, base.shape, str(base.dtype), split,
                      mesh, center, ddof, pr), build)
    return _cov_fetch(fn(base), return_mean)


def _cov_sizes(n, ddof):
    if n - ddof <= 0:
        raise ValueError("cov needs more than ddof=%d samples, got %d"
                         % (ddof, n))


def _cov_streamed(b, axis, center, ddof, pr):
    """:func:`cov`'s ``(c, mu)`` over a lazy out-of-core source, the
    second moments folded slab by slab (``stream.maybe_gram``), or
    NotImplemented where the executor does not take the source."""
    from bolt_tpu import stream
    from bolt_tpu.tpu.array import _cached_jit
    axes = _sample_axes(b, axis)
    if stream.gram_refusal(b._stream, axes) is not None:
        return NotImplemented
    shape = stream.result_state(b._stream).shape
    n = prod(shape[:len(axes)])
    _cov_sizes(n, ddof)
    parts = stream.maybe_gram(b, axes, pr, sums=center, second_conj=True)
    fn = _cached_jit(
        ("ops-cov-finish", n, tuple(parts[0].shape), center, ddof,
         str(parts[0].dtype)),
        lambda: jax.jit(lambda c, *total: _cov_finish(
            c, total[0] if total else None, n, ddof)))
    return fn(*parts)


def _cov_fetch(out, return_mean):
    """``(c, mu)`` on the device -> what :func:`cov` returns."""
    c, mu = out
    if return_mean:
        c, mu = jax.device_get((c, mu))    # one batched round-trip
        return np.asarray(c), np.asarray(mu)
    return np.asarray(jax.device_get(c))


def _cov_program(funcs, split, kshape, d, center, ddof, pr):
    """The traced body of :func:`cov`'s one program over the base buffer,
    module-level for :func:`_pca_program`'s reason."""
    from bolt_tpu.tpu.array import _chain_apply
    n = prod(kshape)

    def program(data):
        x, widened = _features_last(_chain_apply(funcs, split, data),
                                    kshape, d)
        # same centering fold as pca (round 4): the centred copy is
        # never materialised — (X-mu)^T conj(X-mu) = X^T conj(X) -
        # n mu conj(mu)^T; same second-factor conjugation as np.cov.
        # Same conditioning envelope as pca's fold (~eps_f32 *
        # (mu/sigma)^2 relative error in the entries).  The mean's sums
        # come with the Gram matrix (see _pca_program)
        if not center:
            c, total = _sample_gram(x, pr, second_conj=True,
                                    widened=widened), None
        else:
            c, total = _sample_gram(x, pr, second_conj=True,
                                    widened=widened, sums=True)
        return _cov_finish(c, total, n, ddof)
    return program


def _cov_finish(c, total, n, ddof):
    """From the raw second moments ``c`` and the column sums ``total``
    (``None``: not centred) of ``n`` samples to ``(covariance, mu)``.
    Traced by the resident program and, on its own, behind a streamed
    source's one pass."""
    d = c.shape[-1]
    if total is None:
        mu = jnp.zeros(d, c.dtype)
    else:
        mu = total / n
        c = c - n * jnp.outer(mu, jnp.conj(mu))
        # the explicit-centering path this fold replaced computed
        # Xc^H Xc, whose diagonal (sum of squared moduli) cannot
        # go negative; the fold can cancel past f32 precision for
        # tiny-variance features on a large offset, so restore
        # the invariant (mirrors _decompose_gram's eigenvalue
        # clamp) — corrcoef's sqrt(diag) depends on it
        idx = jnp.arange(d)
        diag = jnp.maximum(jnp.real(c[idx, idx]), 0.0)
        c = c.at[idx, idx].set(diag.astype(c.dtype))
    return c / (n - ddof), mu


def corrcoef(b, axis=None, precision=None):
    """Feature-correlation matrix (Pearson) of a bolt array viewed as
    samples × features: :func:`cov` normalised by the outer product of
    the per-feature standard deviations (the (d, d) result is tiny, so
    the normalisation runs on host).  Zero-variance features yield
    NaN rows/columns, matching ``np.corrcoef``.  ``precision`` threads
    to the cov Gram like :func:`pca`'s."""
    c = cov(b, axis=axis, center=True, ddof=1, precision=precision)
    sd = np.sqrt(np.diag(c))
    with np.errstate(divide="ignore", invalid="ignore"):
        r = c / np.outer(sd, sd)
    return r
