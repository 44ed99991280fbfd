"""Whole-frame motion correction: the integer displacement of every frame
against a reference image by FFT cross-correlation, and the shift that
takes it out.

The first step of the imaging pipeline upstream Bolt was written for
(Thunder's ``thunder-registration``: ``CrossCorr().fit(images, reference)``
then ``model.transform(images)``, then ``Images.toseries()``).  The
reference mount was empty (SURVEY.md §0), so the semantics are stated
here, and these definitions are what runs:

* **the surface.**  For a frame ``a`` and the reference ``b``, both
  ``(h, w)``, ``c[d] = sum_x a[x + d] * b[x]`` with cyclic indices, that is
  ``c = ifft2(fft2(a) * conj(fft2(b)))``, real for real images and computed
  from the half-spectrum a real image has (``irfft2(rfft2(a) *
  conj(rfft2(b)))``: the same surface, every shift of it, half the
  arithmetic).  Arithmetic in the frame's floating type, float32 at the
  least.  **How it is computed** follows from what the code can see, the
  frame's shape and type, and from nothing else: traced, float32 frames
  of ``_N_MIN`` (128) to :data:`N_MAX` a side take the transforms as
  dense DFT matrix products (four a frame), float32 in and out at
  ``Precision.HIGHEST``, the slab in one layout from the frames to the
  surface (:func:`_surface_by_products`; engine counter
  ``crosscorr_on_mxu``); every other frame, float64 under x64 included,
  takes XLA's real FFT, and NumPy arrays NumPy's.  A dense product sums
  ``n`` terms a value where the FFT sums ``log2(n)`` stages, so it rounds
  a few times more (PERF.md section 2 has both readings); ties are the
  computed surface's, as they always were.
* **the displacement** of a frame is the arg-max of ``|c|``.  **Tie rule:**
  the first maximum in C order, as ``argmax`` gives it.  **Cyclic
  adjustment:** a component above half its axis (``d > n // 2``) names the
  shift the other way round, ``d - n``, so each component lies in
  ``[-(n - 1) // 2, n // 2]``.  **Sign convention:** ``d`` is where the
  frame's content lies relative to the reference's, ``a[x] ~ b[x - d]``: a
  frame that shows the reference moved two rows down has ``d = (2, 0)``.
  int32, ``(dx, dy)`` along the frame's two axes.
* **the shift** that registers a frame takes the displacement out:
  ``out[x] = a[clip(x + d, 0, n - 1)]`` on each axis, whole pixels, so
  every value of the result is a value of the frame (**edge rule:**
  positions that would read past an edge repeat the nearest edge row or
  column, scipy's ``mode="nearest"``).

:func:`crosscorr_shift` and :func:`shift` are per-record functions,
traceable by jax and runnable on NumPy arrays (the ``mode='local'``
oracle).  :func:`fit` and :func:`transform` are the two calls: deferred
maps like those of ``ops/series.py``, whose reference image and
displacements travel as OPERANDS of the compiled program
(``utils.with_operands``) and not as constants in it, so a second session
with another reference and other displacements runs the same executable.
On a streamed source (``bolt.fromcallback``) ``fit`` is a stage of the
slab program whose small result is collected slab by slab
(``stream.collect``), and ``transform`` is a KEYED stage (a ``with_keys``
map: frame ``t`` is shifted by ``displacements[t]``) that runs in front of
a streamed ``swap`` in the resolver's place program; the session is never
held whole on the device.
"""

import numpy as np
import jax.numpy as jnp
from jax import lax

from bolt_tpu import engine as _engine
from bolt_tpu.obs import trace as _obs
from bolt_tpu.utils import with_operands

# Which frames take the surface as DFT matrix products: float32 frames
# whose two axes both lie in [_N_MIN, N_MAX].  Under the MXU's 128-wide
# tile the products have nothing to fill; a dense product costs O(n) a
# value where the FFT costs O(log n), so past N_MAX XLA's FFT wins back.
# N_MAX from scripts/shift_probe.py --hw on one v5e chip (PR 54, call 3),
# ms a slab of 64 MiB of square float32 frames, XLA's rfft2 against the
# products:   256: 5.03 / 2.93    512: 9.27 / 3.92
#            1024: 7.88 / 6.73   2048: 8.92 / 12.36
_N_MIN = 128
N_MAX = 1024


def _xp(*arrays):
    """NumPy where every array is NumPy's (the oracle), else jax.numpy."""
    return np if all(isinstance(a, (np.ndarray, np.generic))
                     for a in arrays) else jnp


def _by_products(shape, dtype):
    """Whether traced frames of ``shape`` and ``dtype`` take the surface
    as matrix products (the rule above :data:`N_MAX`)."""
    return dtype == np.float32 and all(_N_MIN <= n <= N_MAX for n in shape)


def _cos_sin(n, rows, cols):
    """``cos`` and ``sin`` of ``2 pi r c / n`` for the int32 index vectors
    ``rows`` and ``cols``, float32, made in the program from its iotas
    (nothing of a table's size is a constant of the executable).  ``r c``
    is reduced modulo ``n`` in integers before an angle is formed, and the
    remainder folded to the first octant (in quarter steps, so an odd
    ``n`` folds exactly): the float32 angle is at most ``pi / 4`` and the
    table's error, 5e-8 where a float64 table rounded once has 3e-8, does
    not grow with the index."""
    q = 4 * ((rows[:, None] * cols[None, :]) % n)   # (pi / 2) q / n
    quad, r = q // n, q % n
    swap = 2 * r > n
    theta = (jnp.where(swap, n - r, r).astype(jnp.float32)
             * np.float32(np.pi / (2 * n)))
    c, s = jnp.cos(theta), jnp.sin(theta)
    c, s = jnp.where(swap, s, c), jnp.where(swap, c, s)
    odd = quad % 2 == 1
    return (jnp.where(odd, s, c) * jnp.where((quad == 1) | (quad == 2), -1, 1),
            jnp.where(odd, c, s) * jnp.where(quad >= 2, -1, 1))


def _dot(x, table, axis):
    """``x`` with its ``axis`` contracted where it lies against ``table``'s
    first: float32 in, float32 out, ``Precision.HIGHEST`` (the result's
    axes are ``x``'s others, then the table's second)."""
    return lax.dot_general(x, table, (((axis,), (0,)), ((), ())),
                           precision=(lax.Precision.HIGHEST,) * 2,
                           preferred_element_type=jnp.float32)


def _packed_tables(n):
    """The PACKED real transform of an axis of ``n`` values and its way
    back, ``(n, n)`` each.  A real row has ``n // 2 + 1`` cosine sums
    ``C[k] = sum_j x[j] cos(2 pi k j / n)`` and ``(n - 1) // 2`` sine sums
    ``S[k]``, ``k`` from 1, that are not zero (its spectrum is ``C - i
    S``): ``n`` numbers, the forward table's columns, cosines first.  On
    the way back a sum that has no conjugate twin in the half-spectrum
    (``k = 0``, and ``n / 2`` where ``n`` is even) counts once and every
    other twice."""
    k = jnp.arange(n // 2 + 1, dtype=jnp.int32)
    cos, sin = _cos_sin(n, jnp.arange(n, dtype=jnp.int32), k)
    sin = sin[:, 1:1 + (n - 1) // 2]
    once = (k == 0) | (2 * k == n)
    return (jnp.concatenate([cos, sin], axis=1),
            jnp.concatenate([(cos * jnp.where(once, 1.0, 2.0)).T,
                             2.0 * sin.T]))


def _surface_by_products(a, b):
    """The surface of float32 ``a`` against ``b``, both ``(h, w)``, by
    FOUR matrix products a frame: the packed transform along ``w``, then
    along ``h``, the spectra's product, and the two ways back.  Each
    product contracts its axis where it lies, so the packed square
    travels transposed, ``(w, h)``, between the two products along ``h``
    and the two along ``w`` undo each other's order: no transpose is
    written.

    The packed square holds, for ``g`` along ``h`` and ``k`` along ``w``,
    the four sums ``cc, sc, cs, ss`` of the frame against ``cos`` or
    ``sin`` of either angle, each in its quadrant (where a sine sum is
    zero the quadrant is a row or a column short, and padded).  The
    spectrum ``A = rfft2(frame)`` is ``A[g, k] = (cc - ss) - i (sc + cs)``
    and ``A[-g, k] = (cc + ss) - i (cs - sc)``; with ``p - i q`` and ``p'
    - i q'`` the same two values of ``A conj(B)``, the packed square of
    the surface is ``cc = p + p'``, ``sc = q - q'``, ``cs = q + q'``,
    ``ss = p' - p``, over ``2 h w``."""
    h, w = a.shape
    nh, nw = h // 2 + 1, w // 2 + 1                 # cosine sums an axis
    mh, mw = (h - 1) // 2, (w - 1) // 2             # sine sums, from 1
    (there_w, back_w), (there_h, back_h) = _packed_tables(w), _packed_tables(h)
    rows, cols = (1, nw - mw - 1), (1, nh - mh - 1)

    def spectrum(x):
        """``p, q, p', q'`` of ``rfft2(x)``, ``(nw, nh)`` each."""
        sums = _dot(_dot(x, there_w, 1), there_h, 0)            # (w, h)
        cc = sums[:nw, :nh]
        sc = jnp.pad(sums[:nw, nh:], ((0, 0), cols))
        cs = jnp.pad(sums[nw:, :nh], (rows, (0, 0)))
        ss = jnp.pad(sums[nw:, nh:], (rows, cols))
        return cc - ss, sc + cs, cc + ss, cs - sc

    (pa, qa, pa2, qa2), (pb, qb, pb2, qb2) = spectrum(a), spectrum(b)
    p, q = pa * pb + qa * qb, qa * pb - pa * qb                 # A conj(B)
    p2, q2 = pa2 * pb2 + qa2 * qb2, qa2 * pb2 - pa2 * qb2
    sums = jnp.concatenate([
        jnp.concatenate([p + p2, (q - q2)[:, 1:1 + mh]], axis=1),
        jnp.concatenate([(q + q2)[1:1 + mw],
                         (p2 - p)[1:1 + mw, 1:1 + mh]], axis=1)])
    return _dot(_dot(sums, back_h * (0.5 / h), 1), back_w * (1.0 / w), 0)


def crosscorr_shift(frame, reference):
    """The displacement ``(dx, dy)``, int32, of the 2-d ``frame`` against
    ``reference`` (same shape): the arg-max of the cyclic cross-correlation
    surface, adjusted (module docstring: surface, how it is computed, tie
    rule, cyclic adjustment, sign convention)."""
    xp = _xp(frame, reference)
    dt = xp.promote_types(frame.dtype, xp.float32)
    a, b = frame.astype(dt), reference.astype(dt)
    h, w = a.shape
    if xp is jnp and _by_products((h, w), dt):
        surface = _surface_by_products(a, b)
    else:
        surface = xp.fft.irfft2(xp.fft.rfft2(a) * xp.conj(xp.fft.rfft2(b)),
                                s=(h, w))
    at = xp.argmax(xp.abs(surface))
    d = xp.stack([at // w, at % w]).astype(xp.int32)
    n = xp.asarray([h, w], dtype=xp.int32)
    return xp.where(d > n // 2, d - n, d)


def shift(frame, delta):
    """``frame`` with the displacement ``delta = (dx, dy)`` taken out:
    ``out[x, y] = frame[clip(x + dx), clip(y + dy)]``, whole pixels, edge
    values repeated (module docstring: the edge rule).  Two clamped
    one-axis takes, so the result holds values of the frame and nothing
    else."""
    xp = _xp(frame, delta)
    h, w = frame.shape
    rows = xp.clip(xp.arange(h, dtype=xp.int32) + delta[0], 0, h - 1)
    cols = xp.clip(xp.arange(w, dtype=xp.int32) + delta[1], 0, w - 1)
    return xp.take(xp.take(frame, rows, axis=0), cols, axis=1)


def _shift_keyed(keyed, displacements):
    """:func:`shift` as a ``with_keys`` map body: the record at ``keys``
    by ``displacements[keys]``."""
    keys, frame = keyed
    if _xp(frame) is jnp:           # a traced key indexes a jax array
        displacements = jnp.asarray(displacements)
    return shift(frame, displacements[tuple(keys)])


def _frames(images):
    """``(key axes, frame shape)`` of ``images``: 2-d frames, every other
    axis a key."""
    if images.mode == "tpu":
        split = images.split
    else:
        split = images.ndim - 2
    if images.ndim - split != 2 or split < 1:
        raise ValueError(
            "registration takes 2-d frames keyed by the leading axes; got "
            "shape %s with %d key axes" % (tuple(images.shape), split))
    return tuple(range(split)), tuple(images.shape[split:])


def fit(images, reference):
    """The displacement of every frame of ``images`` (2-d frames keyed by
    the leading axes) against the ``reference`` image the caller passes:
    a bolt array of the keys' shape plus ``(2,)``, int32
    (:func:`crosscorr_shift` a frame).  A deferred map on either backend;
    ``reference`` is an operand of its program."""
    with _obs.span("ops.register", call="fit"):
        axes, fshape = _frames(images)
        if tuple(np.shape(reference)) != fshape:
            raise ValueError("the reference image has shape %s, the frames "
                             "%s" % (tuple(np.shape(reference)), fshape))
        if images.mode != "tpu":
            reference = np.asarray(reference)
        elif _by_products(fshape, jnp.promote_types(images.dtype,
                                                    jnp.float32)):
            _engine.record_crosscorr_on_mxu()
        return images.map(with_operands(crosscorr_shift, reference),
                          axis=axes)


def transform(images, displacements):
    """``images`` with every frame shifted by ITS OWN displacement
    (:func:`shift` of frame ``k`` by ``displacements[k]``, whole pixels,
    edge fill): what :func:`fit` returned, or any integer array of the
    keys' shape plus ``(2,)``.  A deferred ``with_keys`` map on either
    backend; ``displacements`` is an operand of its program."""
    with _obs.span("ops.register", call="transform"):
        axes, _ = _frames(images)
        kshape = tuple(images.shape[:len(axes)])
        if hasattr(displacements, "toarray"):
            displacements = displacements.toarray()
        if images.mode != "tpu" or not hasattr(displacements, "sharding"):
            displacements = np.asarray(displacements)
        if tuple(displacements.shape) != kshape + (2,) \
                or not np.issubdtype(displacements.dtype, np.integer):
            raise ValueError(
                "displacements are integers of shape %s (one (dx, dy) a "
                "frame), got %s %s" % (kshape + (2,), displacements.dtype,
                                       tuple(displacements.shape)))
        displacements = displacements.astype(np.int32)
        return images.map(with_operands(_shift_keyed, displacements),
                          axis=axes, with_keys=True)
