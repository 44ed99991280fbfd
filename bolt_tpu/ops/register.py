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
  by the real transforms (``irfft2(rfft2(a) * conj(rfft2(b)))``: the same
  surface, every shift of it, half the arithmetic).  Arithmetic in the
  frame's floating type, float32 at the least.
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

from bolt_tpu.obs import trace as _obs
from bolt_tpu.utils import with_operands


def _xp(*arrays):
    """NumPy where every array is NumPy's (the oracle), else jax.numpy."""
    return np if all(isinstance(a, (np.ndarray, np.generic))
                     for a in arrays) else jnp


def crosscorr_shift(frame, reference):
    """The displacement ``(dx, dy)``, int32, of the 2-d ``frame`` against
    ``reference`` (same shape): the arg-max of the cyclic cross-correlation
    surface, adjusted (module docstring: surface, tie rule, cyclic
    adjustment, sign convention)."""
    xp = _xp(frame, reference)
    dt = xp.promote_types(frame.dtype, xp.float32)
    a, b = frame.astype(dt), reference.astype(dt)
    h, w = a.shape
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
