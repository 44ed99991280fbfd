"""Constructors for the ``mode='tpu'`` backend.

Reference: ``bolt/spark/construct.py :: ConstructSpark`` (symbol-level
citation, SURVEY.md §0).  Where the reference moves key axes to the front,
flattens, enumerates key tuples and ``sc.parallelize``-s the records, this
backend builds (or places) ONE global ``jax.Array`` with the key sharding —
``ones``/``zeros`` are materialised *directly sharded on device* via a jitted
constant with ``out_shardings``, never on the host (SURVEY §3.1: a 10 GB
array is never resident in driver memory).
"""

import numpy as np

import jax
import jax.numpy as jnp

from bolt_tpu.parallel import multihost as _multihost
from bolt_tpu.parallel.mesh import default_mesh, ensure_auto
from bolt_tpu.parallel.sharding import is_mesh, key_sharding
from bolt_tpu.utils import inshape, tupleize


class ConstructTPU:
    """Builds :class:`~bolt_tpu.tpu.array.BoltArrayTPU` instances."""

    @staticmethod
    def _argcheck(*args, **kwargs):
        """Claim the construction when a ``jax.sharding.Mesh`` appears as a
        positional arg or as ``context=``, or ``mode='tpu'`` is explicit
        (reference: ``ConstructSpark._argcheck`` detects a SparkContext)."""
        if kwargs.get("mode") == "tpu":
            return True
        if is_mesh(kwargs.get("context")):
            return True
        return any(is_mesh(a) for a in args)

    @staticmethod
    def _resolve(context):
        if context is None:
            return default_mesh()
        if not is_mesh(context):
            raise ValueError("context must be a jax.sharding.Mesh, got %r"
                             % (context,))
        return ensure_auto(context)

    @staticmethod
    def array(a, context=None, axis=(0,), dtype=None, npartitions=None):
        """Distribute an array-like with ``axis`` as the key axes.

        Key axes are moved to the front of the logical shape (the reference
        does the same before parallelizing: ``ConstructSpark._wrap``'s
        moveaxis+reshape).  ``npartitions`` is accepted for signature parity;
        the partition count is the mesh size.
        """
        from bolt_tpu.base import BoltArray
        from bolt_tpu.tpu.array import BoltArrayTPU
        mesh = ConstructTPU._resolve(context)
        axes = sorted(tupleize(axis))
        if len(axes) == 0:
            raise ValueError("at least one key axis is required")

        if isinstance(a, BoltArrayTPU):
            a = a._data
        elif isinstance(a, BoltArray):
            a = a.toarray()
        elif not isinstance(a, (np.ndarray, jax.Array)):
            # plain sequences (list/tuple/nested) need materializing before
            # the shape checks below
            a = np.asarray(a, dtype=dtype)

        inshape(a.shape, axes)
        rest = [i for i in range(a.ndim) if i not in axes]
        perm = axes + rest
        split = len(axes)
        multihost = _multihost.is_multiprocess(mesh)

        # device arrays stay on device: transpose/cast/reshard without a
        # host round-trip.  On a multi-host mesh this path also serves
        # global (non-fully-addressable) inputs, which CANNOT go to host;
        # a process-LOCAL device array there takes the host path below,
        # since device_put cannot scatter it across processes.
        if isinstance(a, jax.Array) and (not multihost
                                         or not a.is_fully_addressable):
            data = a if perm == list(range(a.ndim)) else jnp.transpose(a, perm)
            if dtype is not None:
                target = jax.dtypes.canonicalize_dtype(np.dtype(dtype))
                if target != data.dtype:
                    data = data.astype(target)
            from bolt_tpu import stream as _streamlib
            data = _streamlib.transfer(
                data, key_sharding(mesh, data.shape, split))
            return BoltArrayTPU(data, split, mesh)

        a = np.asarray(a, dtype=dtype)
        # canonicalise to what the backend holds (f64→f32 unless x64 is on):
        # explicit and silent, not warn-and-truncate
        a = a.astype(jax.dtypes.canonicalize_dtype(a.dtype))
        a = np.transpose(a, perm)
        sharding = key_sharding(mesh, a.shape, split)
        if multihost:
            # every process holds (or can produce) the full logical array;
            # each device picks out its own shard — the single-controller
            # construction path (SURVEY §7 hard part 6)
            data = jax.make_array_from_callback(
                a.shape, sharding, lambda idx: a[idx])
        else:
            from bolt_tpu import stream as _streamlib
            data = _streamlib.transfer(a, sharding)
        return BoltArrayTPU(data, split, mesh)

    @staticmethod
    def _device_build_spec(shape, context, axis, dtype):
        """Shared prologue for the build-directly-on-device constructors:
        ``(mesh, key-axes-first shape, split, canonical dtype, sharding)``
        — the key-axis permutation and dtype rules must stay identical
        across ``ones``/``zeros``/``rand``/``randn``."""
        mesh = ConstructTPU._resolve(context)
        shape = tupleize(shape)
        axes = sorted(tupleize(axis))
        if len(axes) == 0:
            raise ValueError("at least one key axis is required")
        inshape(shape, axes)
        rest = [i for i in range(len(shape)) if i not in axes]
        shape = tuple(shape[i] for i in axes + rest)
        if dtype is None:
            dtype = np.float64  # numpy's default, canonicalised below
        dtype = jax.dtypes.canonicalize_dtype(np.dtype(dtype))
        sharding = key_sharding(mesh, shape, len(axes))
        return mesh, shape, len(axes), dtype, sharding

    @staticmethod
    def _filled(fill, shape, context, axis, dtype):
        from bolt_tpu.tpu.array import BoltArrayTPU, _cached_jit
        mesh, shape, split, dtype, sharding = \
            ConstructTPU._device_build_spec(shape, context, axis, dtype)
        # engine-routed like every other program: repeated ones()/zeros()
        # of one geometry reuse ONE counted AOT executable.  Scalar fills
        # constant-fold into the program (key carries the value);
        # array-like fills — unhashable, so they cannot key — pass as a
        # broadcast ARGUMENT instead (key carries only their geometry,
        # and the cached closure pins no array memory).
        try:
            hash(fill)
            if fill != fill:
                # NaN: hashable but never equal to itself, so a raw key
                # would MISS (and insert) on every call — ride the
                # argument path, keyed on geometry only
                raise TypeError
        except TypeError:
            farr = np.asarray(fill)
            fn = _cached_jit(
                ("construct-full-arr", farr.shape, str(farr.dtype),
                 shape, str(dtype), sharding),
                lambda: jax.jit(lambda f: jnp.full(shape, f, dtype=dtype),
                                out_shardings=sharding))
            return BoltArrayTPU(fn(farr), split, mesh)
        fn = _cached_jit(
            ("construct-full", fill, shape, str(dtype), sharding),
            lambda: jax.jit(lambda: jnp.full(shape, fill, dtype=dtype),
                            out_shardings=sharding))
        return BoltArrayTPU(fn(), split, mesh)

    @staticmethod
    def _random(kind, shape, context, axis, dtype, seed):
        """Sharded random array, generated ON the devices: one jitted
        program with sharded output, so each device computes only its own
        shard's stream (threefry is counter-based/partitionable) and a
        10 GB random array never exists on the host — the same
        no-host-materialisation rule as ``ones``/``zeros``.  Extension
        beyond the reference factory (which has only
        array/ones/zeros/concatenate); RNG streams differ from the local
        backend's NumPy generator by construction."""
        from bolt_tpu.tpu.array import BoltArrayTPU, _cached_jit
        mesh, shape, split, dtype, sharding = \
            ConstructTPU._device_build_spec(shape, context, axis, dtype)
        if not jnp.issubdtype(dtype, jnp.floating):
            raise ValueError("random constructors require a float dtype, "
                             "got %s" % dtype)
        sampler = jax.random.normal if kind == "randn" else jax.random.uniform

        def builder():
            # seed is a traced argument: one compile per (kind, shape,
            # dtype, mesh), reused across seeds
            return jax.jit(
                lambda seed: sampler(jax.random.key(seed), shape,
                                     dtype=dtype),
                out_shardings=sharding)

        fn = _cached_jit(("construct-random", kind, shape, str(dtype), split,
                          mesh), builder)
        # normalize: any Python int works, matching the local backend
        return BoltArrayTPU(fn(jnp.uint32(seed % (1 << 32))), split, mesh)

    @staticmethod
    def fromcallback(fn, shape, context=None, axis=(0,), dtype=None,
                     chunks=None, checkpoint=None, per_process=False,
                     codec=None):
        """Build a distributed array by calling ``fn`` per index range —
        the sharded data-loader slot.

        ``fn(index)`` receives a tuple of per-axis ``slice`` objects
        covering one range of the KEY-AXES-FIRST logical ``shape`` and
        returns that block (anything ``np.asarray`` accepts: a memmap
        read, an HDF5/zarr slice, a computed tile).  The reference's
        analog is the driver-side ``sc.parallelize`` scatter
        (``bolt/spark/construct.py :: ConstructSpark.array``), which
        must materialise the full array at the driver first; here no
        full copy ever exists anywhere.

        With an EXPLICIT ``dtype`` (single-process) the result is a LAZY
        STREAMING source (ISSUE 3): nothing is produced or uploaded at
        construction.  Reduction terminals — directly or through a
        ``chunk()``/``stacked()`` view — stream the data slab-by-slab
        through the double-buffered out-of-core executor
        (:mod:`bolt_tpu.stream`), so datasets LARGER than device memory
        reduce in one pass; any other consumer materialises it with one
        callback call per device shard, exactly as before.  ``chunks``
        sets the records per streamed slab (default: a 64 MiB
        budget).  ``dtype=None`` means
        "whatever the callback produces" and stays eager (the element
        type cannot be known without calling the loader).

        Note ``shape`` is interpreted key-axes-first (like
        ``ones``/``zeros``): ``axis`` names which of those axes are
        keys, and they are moved to the front before ``fn`` sees slices.

        ``per_process=True`` opts into the MULTI-PROCESS ingest
        contract (``bolt_tpu.parallel.multihost``): on a mesh spanning
        processes, each host's streaming executor invokes ``fn`` only
        for its own contiguous sub-range of each slab's leading key
        axis and uploads only that shard — the pod-scale streaming
        path, with the cross-host fold done by mesh-axis collectives
        inside the slab program.  ``fn`` must therefore serve any index
        range on any host (a shared filesystem / object-store reader).
        Single-process meshes accept the flag as a no-op (local range =
        the whole slab), so one loader runs unchanged from laptop to
        pod.

        ``codec=`` names an ingest codec (the ``bolt_tpu.tpu.codec``
        registry: ``"bf16"``/``"f16"``/``"int8"``/``"delta-f32"``):
        streamed runs over this source ENCODE each slab on the
        uploader workers and DECODE on device inside the slab program,
        shipping the wire bytes instead of the raw ones.  Wins over
        any ``stream.codec()`` scope; materialising consumers ignore
        it (they upload raw).  Lossy codecs are an explicit accuracy
        opt-in — see the codec module's contract table.
        """
        from bolt_tpu.tpu.array import BoltArrayTPU
        explicit = dtype is not None
        mesh, shape, split, dtype, sharding = \
            ConstructTPU._device_build_spec(shape, context, axis, dtype)
        multihost = _multihost.is_multiprocess(mesh)
        if per_process and not explicit:
            raise ValueError(
                "fromcallback(per_process=True) requires an explicit "
                "dtype: the per-process contract is a streaming plan, "
                "and streaming sources record their element type up "
                "front")
        if explicit and (not multihost or per_process):
            # lazy streaming source; materialisation (stream.materialize)
            # replays the per-shard upload below bit-identically.  On a
            # multi-process mesh this is the per_process=True contract:
            # the executor invokes fn per host, for that host's shard of
            # each slab only.
            from bolt_tpu import stream as _streamlib
            src = _streamlib.StreamSource.from_callback(
                fn, shape, split, dtype, mesh, chunks=chunks,
                checkpoint=checkpoint, codec=codec)
            return BoltArrayTPU._streamed(src)
        # dtype=None means "whatever the callback produces" (the loader
        # knows its storage dtype); an explicit dtype converts each block
        dtype = dtype if explicit else None

        def produce(index):
            block = np.asarray(fn(index), dtype=dtype)
            want = tuple(len(range(*s.indices(n)))
                         for s, n in zip(index, shape))
            if block.shape != want:
                raise ValueError(
                    "fromcallback callback returned shape %s for index %s "
                    "(expected %s)" % (block.shape, index, want))
            return block

        from bolt_tpu.obs.trace import clock as _clock
        t0 = _clock()
        data = jax.make_array_from_callback(shape, sharding, produce)
        from bolt_tpu import engine as _engine
        _engine.record_transfer(data.nbytes, _clock() - t0,
                                elements=data.size)
        return BoltArrayTPU(data, split, mesh)

    @staticmethod
    def fromiter(blocks, shape, context=None, axis=(0,), dtype=None,
                 checkpoint=None, codec=None):
        """Lazy streaming construction from an ITERABLE of consecutive
        record blocks — the sequential twin of :meth:`fromcallback` for
        sources that cannot random-access (a decompression stream, a
        database cursor, a generator).

        ``blocks`` yields arrays in KEY-AXES-FIRST layout, concatenated
        along the first key axis; together they must cover ``shape``
        exactly.  ``dtype`` is REQUIRED (``np.fromiter`` precedent —
        blocks are consumed lazily, so the element type cannot be
        inferred up front).  Reduction terminals stream the iterator
        once through the out-of-core executor; materialising consumers
        assemble it on host first (needs host RAM for the full array).

        On a MULTI-PROCESS mesh, RE-ITERABLE sources (a list of blocks,
        an object with a fresh ``__iter__``) stream under the
        per-process contract (``bolt_tpu.parallel.multihost``): every
        process iterates its own copy of the iterable, slices out its
        shard of each global block, and uploads only that — the
        cross-host fold runs as mesh-axis collectives in the slab
        program.  One-shot iterators (generators, cursors) are refused
        with a pointed error below.
        """
        from bolt_tpu.tpu.array import BoltArrayTPU
        if dtype is None:
            raise ValueError(
                "fromiter requires an explicit dtype (blocks are consumed "
                "lazily, so the element type cannot be inferred up front)")
        mesh, shape, split, dtype, _ = \
            ConstructTPU._device_build_spec(shape, context, axis, dtype)
        if _multihost.is_multiprocess(mesh) \
                and iter(blocks) is blocks:
            # the BLT011 reasoning, terminally: a one-shot iterator dies
            # with its process, so a killed run can never re-stream it
            # (resume impossible) — and on a pod EVERY process must walk
            # the block sequence to slice its own shard of each slab,
            # which a single-consumption cursor cannot survive either:
            # ingest is impossible too.
            raise ValueError(
                "fromiter on a multi-process mesh requires a RE-ITERABLE "
                "source (e.g. a list of blocks, or an object whose "
                "__iter__ starts fresh): each process iterates its own "
                "copy and uploads only its per-process shard of every "
                "slab (bolt_tpu.parallel.multihost contract).  A "
                "one-shot iterator cannot serve that — nor can a killed "
                "run ever resume from it (the BLT011 rule: the iterator "
                "dies with the process).  Use fromcallback("
                "per_process=True) for random-access loaders")
        from bolt_tpu import stream as _streamlib
        src = _streamlib.StreamSource.from_iter(blocks, shape, split,
                                                dtype, mesh,
                                                checkpoint=checkpoint,
                                                codec=codec)
        return BoltArrayTPU._streamed(src)

    @staticmethod
    def randn(shape, context=None, axis=(0,), dtype=None, seed=0):
        """Sharded standard-normal array, generated directly on device."""
        return ConstructTPU._random("randn", shape, context, axis, dtype, seed)

    @staticmethod
    def rand(shape, context=None, axis=(0,), dtype=None, seed=0):
        """Sharded uniform [0, 1) array, generated directly on device."""
        return ConstructTPU._random("rand", shape, context, axis, dtype, seed)

    @staticmethod
    def ones(shape, context=None, axis=(0,), dtype=None):
        """Sharded array of ones, built directly on device."""
        return ConstructTPU._filled(1, shape, context, axis, dtype)

    @staticmethod
    def zeros(shape, context=None, axis=(0,), dtype=None):
        """Sharded array of zeros, built directly on device."""
        return ConstructTPU._filled(0, shape, context, axis, dtype)

    @staticmethod
    def full(shape, value, context=None, axis=(0,), dtype=None):
        """Sharded array filled with ``value``, built directly on device.
        Like ``numpy.full``, the dtype defaults to the fill value's (so
        this entry point agrees with the local backend even when called
        directly, not just through the factory)."""
        if dtype is None:
            dtype = np.asarray(value).dtype
        return ConstructTPU._filled(value, shape, context, axis, dtype)

    @staticmethod
    def concatenate(arrays, axis=0, context=None):
        """Concatenate a sequence of arrays along ``axis`` into one
        distributed array (reference: ``ConstructSpark.concatenate``)."""
        if not isinstance(arrays, (tuple, list)) or len(arrays) == 0:
            raise ValueError("concatenate requires a non-empty tuple of arrays")
        from bolt_tpu.base import BoltArray
        from bolt_tpu.tpu.array import BoltArrayTPU
        first = arrays[0]
        if isinstance(first, BoltArrayTPU):
            out = first
            for other in arrays[1:]:
                out = out.concatenate(other, axis=axis)
            return out
        mats = [a.toarray() if isinstance(a, BoltArray) else np.asarray(a)
                for a in arrays]
        return ConstructTPU.array(np.concatenate(mats, axis), context=context)
