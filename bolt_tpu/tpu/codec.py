"""Codec-encoded streaming ingest: move FEWER bytes over the link.

The streaming executor is transfer-bound by design: one host→device
pass per byte, with the device idle 98.9 % of a streamed pass (the
``stack4d-1chip.stream`` cell, PERF.md section 5) — so the remaining
single-chip lever is shrinking the bytes themselves (ROADMAP S1 (c),
SURVEY §2.3).  This module is the codec registry the executor
(``bolt_tpu.stream``) consults: uploader
workers ENCODE each slab on host (parallel, per worker, counted as
``codec_encode_seconds`` / ``codec_bytes_raw`` / ``codec_bytes_wire``),
the wire representation plus a tiny sidecar crosses the link, and the
slab program DECODES on device as the FIRST traced expression of the
existing partial/fold body — so decode costs zero extra HBM passes: the
decoded values stream straight into the same stage chain and terminal
partial the uncompressed path traces.

Registry (:func:`get` / :func:`names`):

========== ======== ======= ====================================
name       wire     ratio*  contract
========== ======== ======= ====================================
``bf16``   bfloat16 0.5     lossy down-cast; ~1e-2 relative
                            (:func:`bolt_tpu._precision.codec_bound`)
``f16``    float16  0.5     lossy down-cast; ~1e-3 relative
``int8``   uint8 +  0.25    lossy per-slab affine quantisation —
           sidecar          ``q = round((x - zp) / scale)``, the
                            float32 ``(scale, zp)`` pair rides as a
                            sidecar; worst case ~½·scale absolute
                            per element (finite values only)
``delta``  uint32   1.0     LOSSLESS: f32 bits delta-coded along the
(``delta-         (bit-     trailing value axis (wraparound uint32
``f32``)           exact)   arithmetic both ways), decoded by an
                            exact ``cumsum`` + bitcast — results are
                            BIT-IDENTICAL to uncompressed streaming
========== ======== ======= ====================================

\\* ratio = wire bytes / raw bytes for a float32 source.

What "bit-identical" covers, as measured on a v5e (PERF.md, PR 21): the
``delta-f32`` DECODE is bit-exact (a streamed swap under it equals the
raw swap element for element) and exact reductions — integer-valued
sums, ``min``, ``max`` — match the raw pass exactly; a float-rounded
moment (``var``) differed in its last bits, because XLA:TPU orders the
reduction differently once the decode is fused into the slab program.
Bit-identity of every reduction holds on XLA's CPU backend, where the
tests run.

Accuracy follows the ``_precision.resolve_accumulate`` contract
template: the default (no codec) is bit-exact; lossy codecs are an
explicit opt-in with parity bounds documented in
:func:`bolt_tpu._precision.codec_bound` and parity-locked in
tests/test_codec.py; order statistics (``min``/``max``/``ptp`` —
standalone or as fused multi-stat members) and integer/bool pipelines
REFUSE lossy codecs pointedly (quantising an argmax-adjacent answer is
never what the caller meant), while the lossless ``delta-f32`` codec is
accepted everywhere a float32 pipeline streams.

Selection: ``fromcallback(..., codec="bf16")`` / ``fromiter(...,
codec=...)`` per source, or the thread-local ``stream.codec("bf16")``
scope (same stack discipline as ``stream.uploaders``).  The whole stack
inherits the choice: checkpoint fingerprints include the codec id (a
resumed run never adopts a checkpoint cut under a different codec),
multi-process shards encode locally so DCN/gloo bytes shrink too
(sidecar-free codecs only — ``multihost.sidecar_codec_error``), the
serving arbiter leases the COMPRESSED slab bytes (admission floors
recompute via :meth:`Codec.ratio`), and ``analysis.check`` forecasts
the bytes saved as the BLT016 diagnostic.
"""

import numpy as np

import jax
import jax.numpy as jnp

from bolt_tpu._precision import codec_bound  # noqa: F401  (re-export)

# ---------------------------------------------------------------------
# the codec contract
# ---------------------------------------------------------------------


class Codec:
    """One wire codec: host-side :meth:`encode` (numpy, runs on the
    uploader workers) and device-side :meth:`decode` (a traced jax
    expression, fused into the slab program).

    The wire block always keeps the RAW block's shape — only the dtype
    changes — so slab sharding, per-process shard slicing and the
    donated-ring geometry are untouched; ``sidecar`` says whether
    :meth:`encode` returns per-slab side arrays (int8's scale/zero
    point) that must ride along to :meth:`decode`.  Sidecar codecs
    cannot run under a ``shard_map`` pod program (the per-process
    sidecars are not a replicated global value) — the executor refuses
    them there with the pointed
    ``multihost.sidecar_codec_error`` message."""

    name = None
    lossless = False
    sidecar = False

    def wire_dtype(self, dtype):
        """The wire dtype for source ``dtype`` — raises a pointed
        ``ValueError`` when this codec cannot encode it."""
        raise NotImplementedError

    def ratio(self, dtype):
        """wire bytes / raw bytes for ``dtype`` (sidecar excluded —
        it is O(1) per slab)."""
        dtype = np.dtype(dtype)
        return self.wire_dtype(dtype).itemsize / float(dtype.itemsize)

    def encode(self, block, delta_ok=True):
        """``(wire_block, sidecar_tuple)`` for one host slab block.
        ``delta_ok`` is False when the block has no trailing VALUE axis
        to transform along (an all-key-axes source) — only the delta
        codec consults it."""
        raise NotImplementedError

    def decode(self, wire, sidecar, dtype, delta_ok=True):
        """The traced device-side inverse: decoded values of ``dtype``
        with the raw block's shape.  Runs as the first expression of
        the slab program (inside ``shard_map`` on pods), so it must be
        shard-local: no cross-record dependence along the (sharded)
        key axes."""
        raise NotImplementedError

    def _refuse(self, dtype, why):
        raise ValueError(
            "codec %r cannot encode a %s pipeline: %s.  Stream "
            "uncompressed, or pick a codec from %r that supports the "
            "dtype" % (self.name, np.dtype(dtype), why, names()))


class _CastCodec(Codec):
    """Down-cast codecs (``bf16``/``f16``): the wire block is the raw
    block cast to a half-width float; decode is a cast back.  Lossy —
    the documented envelope is ``_precision.codec_bound(name)``
    relative — and sidecar-free, so they run unchanged on pods (each
    process encodes its local shard; the ``shard_map`` decode is
    elementwise)."""

    def __init__(self, name, np_wire):
        self.name = name
        self._np_wire = np_wire

    def wire_dtype(self, dtype):
        dtype = np.dtype(dtype)
        if not np.issubdtype(dtype, np.floating) \
                or dtype.itemsize <= self._np_wire().dtype.itemsize:
            self._refuse(dtype, "the down-cast needs a wider float "
                                "source (float32/float64)")
        return self._np_wire().dtype

    def encode(self, block, delta_ok=True):
        return np.asarray(block).astype(self.wire_dtype(block.dtype)), ()

    def decode(self, wire, sidecar, dtype, delta_ok=True):
        return wire.astype(dtype)


def _np_bf16():
    import ml_dtypes                     # jax's own dtype package
    return np.zeros((), ml_dtypes.bfloat16)


def _np_f16():
    return np.zeros((), np.float16)


class _Int8Codec(Codec):
    """Per-slab affine quantisation: ``q = round((x - zp) / scale)``
    into uint8, with the float32 ``(scale, zp)`` pair as a per-slab
    sidecar; decode is ``q * scale + zp``.  0.25x the wire bytes of a
    float32 source.  Lossy — worst case ~``scale / 2`` ABSOLUTE error
    per element (``scale`` = the slab's value range / 255) — and only
    defined for FINITE float values (a NaN/inf in the slab poisons the
    range; that is the caller's contract, like int8 accumulate's
    wraparound).  Encode is deterministic per block, so a resumed
    int8-encoded run re-derives the exact same sidecar scales for the
    remaining slabs — checkpoint-consistent by construction
    (tests/test_codec.py proves it across a kill -9)."""

    name = "int8"
    sidecar = True

    def wire_dtype(self, dtype):
        dtype = np.dtype(dtype)
        if not np.issubdtype(dtype, np.floating):
            self._refuse(dtype, "affine quantisation is defined for "
                                "float sources only")
        return np.dtype(np.uint8)

    def encode(self, block, delta_ok=True):
        block = np.asarray(block)
        self.wire_dtype(block.dtype)
        lo = float(block.min()) if block.size else 0.0
        hi = float(block.max()) if block.size else 0.0
        scale = (hi - lo) / 255.0
        if scale <= 0.0 or not np.isfinite(scale):
            scale = 1.0                     # constant slab: q == 0
        q = np.clip(np.rint((block - lo) / scale), 0, 255).astype(
            np.uint8)
        return q, (np.float32(scale), np.float32(lo))

    def decode(self, wire, sidecar, dtype, delta_ok=True):
        scale, zp = sidecar
        return (wire.astype(jnp.float32) * scale + zp).astype(dtype)


class _DictCodec(Codec):
    """LOSSLESS dictionary coding for low-cardinality INTEGER/bool
    pipelines (ISSUE 18): host encode builds the slab's sorted value
    dictionary (≤ 256 distinct values — IDs, labels, bucketed keys),
    ships uint8 indices as the wire block with the 256-entry dictionary
    as a per-slab sidecar, and the fused device decode is one gather
    (``dictionary[indices]``) — bit-identical by construction, at
    1/8 the wire bytes of an int64 key column.  This is the natural
    encoding for spilled shuffle buckets of integer keys
    (``checkpoint.spill_save`` applies it automatically), and a slab
    with MORE than 256 distinct values raises a pointed ValueError
    (the caller's cardinality contract, like int8's finite-values
    contract — never a silent fallback).

    Float pipelines are refused POINTEDLY: floating-point values are
    not dictionary-shaped data, and the lossy cast codecs (or lossless
    ``delta-f32``) are the float answer.  Sidecar codec → refused on
    pods like int8 (``multihost.sidecar_codec_error``)."""

    name = "dict"
    lossless = True
    sidecar = True

    def wire_dtype(self, dtype):
        dtype = np.dtype(dtype)
        if not (np.issubdtype(dtype, np.integer)
                or dtype == np.dtype(np.bool_)):
            self._refuse(dtype, "dictionary coding is defined for "
                                "integer/bool sources only — float "
                                "values are not dictionary-shaped "
                                "(use bf16/f16/int8/delta-f32 for "
                                "float pipelines)")
        return np.dtype(np.uint8)

    def encode(self, block, delta_ok=True):
        block = np.asarray(block)
        self.wire_dtype(block.dtype)
        values, inverse = np.unique(block, return_inverse=True)
        if values.size > 256:
            raise ValueError(
                "codec 'dict' needs <= 256 distinct values per slab, "
                "got %d: dictionary coding is for low-cardinality "
                "key/label columns — stream this source uncompressed"
                % values.size)
        # the sidecar dictionary is PADDED to a fixed 256 entries so
        # every slab shares one decode-program geometry (unused tail
        # repeats the last value — indices never reach it)
        table = np.empty(256, block.dtype)
        table[:values.size] = values
        table[values.size:] = values[-1] if values.size else 0
        wire = inverse.reshape(block.shape).astype(np.uint8)
        return wire, (table,)

    def decode(self, wire, sidecar, dtype, delta_ok=True):
        return sidecar[0][wire.astype(jnp.int32)].astype(dtype)


class _DeltaF32Codec(Codec):
    """The LOSSLESS byte-plane-friendly codec for bit-exact float32
    pipelines: the raw bits (viewed as uint32) are delta-coded along
    the TRAILING VALUE axis with wraparound uint32 subtraction, and the
    device decode is an exact wraparound ``cumsum`` + bitcast — both
    directions are pure integer arithmetic, so the decoded bits equal
    the raw bits exactly (NaN payloads included) and a delta-encoded
    streamed reduction is BIT-IDENTICAL to the uncompressed one
    (tested).  Wire bytes equal raw bytes (ratio 1.0): the win is the
    transform's compressibility for the storage/link layers beneath,
    while keeping the whole codec stack (fingerprints, counters, the
    fused on-device decode) exercised by a codec that is allowed
    EVERYWHERE — order stats and resumable bit-exact pipelines
    included.

    The delta axis is the LAST axis only when it is a value axis
    (``split < ndim``): value axes are never device-sharded, so the
    per-shard ``cumsum`` under a pod's ``shard_map`` sees every element
    it needs.  An all-key-axes source (``delta_ok=False``) skips the
    delta and ships the raw bitcast — still lossless, still one wire
    format per source geometry."""

    name = "delta-f32"
    lossless = True

    def wire_dtype(self, dtype):
        dtype = np.dtype(dtype)
        if dtype != np.dtype(np.float32):
            self._refuse(dtype, "the bit-plane delta transform is "
                                "defined for float32 sources only")
        return np.dtype(np.uint32)

    def encode(self, block, delta_ok=True):
        block = np.asarray(block)
        self.wire_dtype(block.dtype)
        u = np.ascontiguousarray(block).view(np.uint32)
        if not delta_ok or u.shape[-1] < 2:
            return u.copy(), ()
        d = u.copy()
        d[..., 1:] = u[..., 1:] - u[..., :-1]     # uint32 wraparound
        return d, ()

    def decode(self, wire, sidecar, dtype, delta_ok=True):
        acc = wire
        if delta_ok and wire.shape[-1] >= 2:
            acc = jnp.cumsum(wire.astype(jnp.uint32), axis=-1,
                             dtype=jnp.uint32)
        return jax.lax.bitcast_convert_type(acc, jnp.float32)


# ---------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------

_REGISTRY = {}


def register(codec):
    """Register a codec instance under its ``name`` (the extension
    door: a project-specific dictionary codec slots in here and the
    whole streaming stack — scopes, counters, fingerprints, arbiter
    ratios, BLT016 — picks it up)."""
    if not codec.name:
        raise ValueError("codec must carry a non-empty .name")
    _REGISTRY[codec.name] = codec
    return codec


def names():
    """The registered codec names, sorted."""
    return tuple(sorted(_REGISTRY))


def get(name):
    """The registered codec for ``name`` (a :class:`Codec` instance
    passes through) — pointed ``ValueError`` naming the known codecs
    otherwise."""
    if isinstance(name, Codec):
        return name
    c = _REGISTRY.get(name)
    if c is None:
        raise ValueError("unknown codec %r (known: %s)"
                         % (name, ", ".join(names())))
    return c


register(_CastCodec("bf16", _np_bf16))
register(_CastCodec("f16", _np_f16))
register(_Int8Codec())
register(_DeltaF32Codec())
register(_DictCodec())
