"""The out-of-core shuffle planner: slab-wise re-axis for streamed
``swap`` (ISSUE 18).

``swap(kaxes, vaxes)`` is THE signature Bolt operation (the reference's
chunk → Spark shuffle → unchunk, SURVEY §3.3) — and the one core op a
streamed source could not reach without materialising fully.  This
module plans the two-phase pipeline that closes the gap:

* **phase 1 (re-bucket)**: every input slab streams up through the
  normal uploader path, and ONE compiled program per slab applies the
  pre-swap stage chain and the swap's transpose, producing that slab's
  contribution to the output — the full new-key extent, with the
  slab's input records along the axis the old record axis landed on
  (``j0 = perm.index(0)``).  A plan is SHARDED (``ShufflePlan.sharded``)
  when its mesh spans more than one PROCESS, and that alone picks the
  form of the exchange.  Sharded, the program runs under ``shard_map``
  with an explicit ``lax.all_to_all`` (split the new record axis,
  concatenate at ``j0``), so each slab costs exactly one collective and
  every process enters it on the same slab.  Not sharded, the transpose
  and the output's sharding constraint are left to GSPMD, whatever the
  mesh's DEVICE width (``ShufflePlan.devices``): on one device that is
  a local permute; on one process driving several chips the compiler
  emits ONE ``all-to-all`` of the slab as uploaded (the frames a chip
  holds, split over the chips that shard the new leading axis: whole
  lane tiles), transposes what arrives locally, and updates the
  aliased output at an offset whose low bits it knows.  The explicit
  form compiles to the same operations there (its transposed block is
  laid out frames second-minor, so nothing a quarter of a lane tile
  wide is ever exchanged), so the rule stays the process count;
  ``tests/test_ops_kernels.py`` compiles the place program for a
  described ``v5e:2x2`` and holds the compiler to it (PERF.md, PR 43).
* **phase 2 (re-assemble)**: RESIDENT, the swapped array is allocated
  once (:func:`alloc_program`) and each slab's program writes its
  transposed block INTO it at the slab's offset along ``j0``
  (:func:`place_program`: re-bucket and ``dynamic_update_slice`` in one
  program, the output donated and aliased), so the device holds the
  output, the ring of uploaded slabs and one program's temp — never
  the parts beside their concatenation.  Past the budget the blocks
  SPILL to encoded bucket files — ``out_block`` new-key records per
  bucket — which a fresh callback
  :class:`~bolt_tpu.stream.StreamSource` then streams through the SAME
  slab-program machinery as any other source (Spark's shuffle-spill
  reincarnated on the donation ring).

Parity is by construction: phase 1 traces the SAME
``jnp.transpose(perm)`` expression the materialised ``_do_swap``
compiles and the SAME ``_stage_apply`` bodies the materialised replay
uses, and transpose/split/concatenate are pure data movement — so a
streamed swap is bit-identical to the materialised one, resident or
spilled, single-process or pod.

The planner (:func:`plan_shuffle`) is consulted both by the executor
(``stream.resolve_swaps``) and abstractly by ``analysis.check`` (the
BLT017 forecast), so the forecast and the measured decision cannot
drift: both read the same resident/spill rule off the same budget
(``stream.swap_budget``: a ``spill`` scope's, the serving arbiter's,
else the device's own free memory).  The rule counts what the resident
programs hold: ``resident_bytes`` = the output + ``ring + 1`` slabs.
``ring`` (``stream.swap_ring``) is the resolver's window of dispatched,
unconfirmed place calls, whose donated slabs the device frees a call at
a time, plus one uploaded slab in the hand of every pool worker; the
one more is ONE program's transposed temp, because the calls of a
window run one after another on the device (each is handed the array
the call before it returns).
"""

import numpy as np

import jax
import jax.numpy as jnp

from bolt_tpu import engine as _engine
from bolt_tpu.parallel import multihost as _multihost
from bolt_tpu.parallel import sharding as _sharding
from bolt_tpu.utils import prod


class ShufflePlan:
    """The static description of one streamed-swap resolution.

    ``resident`` is the phase-2 decision: assemble the swapped array in
    place in HBM (True), or spill encoded bucket files and re-stream
    them (False).  ``resident_bytes`` is what the resident leg holds on
    the device at its peak (output + ``ring`` uploaded slabs + one
    program's temp) — the figure compared with ``budget``.
    ``alltoall_bytes`` is the planner's
    cross-device traffic model: the bytes that must cross device
    boundaries during phase 1 (0 when the record axis stays leading —
    a pure local permute), over the ``devices`` that shard the input
    record axis — several chips of ONE process count like any others
    (``sharded``, the process count, says only which form the exchange
    takes: module docstring)."""

    __slots__ = ("in_shape", "dtype", "split", "perm", "new_split",
                 "out_shape", "j0", "slab", "nslabs", "out_block",
                 "nbuckets", "total_bytes", "slab_bytes", "ring",
                 "resident_bytes", "budget", "resident", "spill_dir",
                 "alltoall_bytes", "devices", "sharded")

    def __init__(self, **kw):
        for name in self.__slots__:
            setattr(self, name, kw[name])

    def describe(self):
        """One-line human summary (the BLT017 message body)."""
        mb = 1024.0 * 1024.0
        mode = "resident" if self.resident else (
            "spill to %s" % (self.spill_dir or "<no spill dir>"))
        across = "" if self.devices <= 1 else (
            " across %d devices%s" % (
                self.devices, "" if self.sharded else " of one process"))
        return ("shuffle plan: %d slab%s -> %s (%.1f MiB working set, "
                "budget %s, %d bucket%s x %d records, all-to-all "
                "~%.1f MiB%s)"
                % (self.nslabs, "s" if self.nslabs != 1 else "", mode,
                   self.resident_bytes / mb,
                   ("%.1f MiB" % (self.budget / mb))
                   if self.budget is not None else "unbounded",
                   self.nbuckets, "s" if self.nbuckets != 1 else "",
                   self.out_block, self.alltoall_bytes / mb, across))


def _axis0_device_width(mesh, shape, split):
    """How many devices shard the LEADING key axis of ``shape`` under
    the key sharding — the divisor phase-2 bucket extents must honour
    so bucket slabs reshard cleanly."""
    if mesh is None:
        return 1
    spec = _sharding.key_spec(mesh, tuple(shape), split)
    names = _sharding.spec_names(spec[0] if len(spec) else None)
    return prod([mesh.shape[n] for n in names]) if names else 1


def _pick_out_block(extent, target_rows, mult):
    """Largest divisor of ``extent`` that is a multiple of ``mult`` and
    no larger than ``target_rows`` — the phase-2 bucket extent.  Falls
    back to the SMALLEST valid divisor when nothing fits under the
    target (better one oversized bucket than a refused plan); ``None``
    when no divisor honours ``mult`` at all."""
    mult = max(1, int(mult))
    divisors = [d for d in range(1, extent + 1)
                if extent % d == 0 and d % mult == 0]
    if not divisors:
        return None
    under = [d for d in divisors if d <= max(target_rows, 1)]
    return under[-1] if under else divisors[0]


def plan_shuffle(staged_shape, dtype, split, perm, new_split, mesh,
                 slab, budget, spill_dir, ring=1, raw_slab_bytes=None):
    """Plan one streamed-swap resolution over the POST-pre-stage
    geometry.

    ``staged_shape``/``dtype``/``split`` describe the stream AFTER the
    stages recorded before the swap (the value the swap's transpose
    actually sees); ``perm``/``new_split`` are the swap's permutation
    exactly as ``tpu/array.py :: _do_swap`` builds them; ``slab`` is
    the input records per slab; ``budget`` the resident ceiling in
    bytes (``None`` = unbounded → always resident); ``spill_dir``
    where bucket files would land; ``ring`` the uploaded slabs the
    executor keeps on the device, uploading or handed to a place call
    not yet confirmed (``stream.swap_ring``);
    ``raw_slab_bytes`` what ONE of them holds as uploaded, where the
    stages before the re-axis change a slab's size (a collect of small
    mapped records rings slabs far larger than what it places).  The
    plan records the mesh's two widths, each read once here:
    ``sharded`` (more than one process: the form of the exchange, module
    docstring) and ``devices`` (how many shard the input record axis:
    the traffic model's divisor).  Raises the pointed pod-geometry
    errors HERE, before any thread starts, mirroring BLT012."""
    staged_shape = tuple(int(s) for s in staged_shape)
    perm = tuple(int(p) for p in perm)
    out_shape = tuple(staged_shape[p] for p in perm)
    j0 = perm.index(0)
    itemsize = np.dtype(dtype).itemsize
    total_bytes = prod(out_shape) * itemsize
    n = staged_shape[0]
    nslabs = max(1, -(-n // max(slab, 1)))
    slab_bytes = min(slab, n) * prod(staged_shape[1:]) * itemsize
    sharded = _multihost.mesh_process_count(mesh) > 1

    # phase-2 bucket extent along the NEW leading key axis: must divide
    # the extent (buckets tile it exactly), honour the output key
    # sharding's device width (bucket slabs reshard cleanly — the
    # BLT012 analog), and on pods divide the per-process range (each
    # bucket wholly owned by ONE process, so spill files never cross
    # host boundaries)
    out_n = out_shape[0]
    dwidth = _axis0_device_width(mesh, out_shape, new_split)
    extent = out_n
    if sharded:
        nproc = _multihost.mesh_process_count(mesh)
        if out_n % nproc != 0:
            raise ValueError(
                "streamed swap on a %d-process pod needs the new "
                "leading key extent (%d) divisible by the process "
                "count — repartition or materialise the swap instead"
                % (nproc, out_n))
        extent = out_n // nproc
    target = max(1, (slab_bytes // max(
        prod(out_shape[1:]) * itemsize, 1)) or 1)
    out_block = _pick_out_block(extent, target, dwidth)
    if out_block is None:
        # nothing divides cleanly: fall back to whole-extent buckets
        out_block = extent
    nbuckets = out_n // out_block

    # the all-to-all traffic model: when the record axis stays leading
    # (perm[0] == 0) every record keeps its device and nothing crosses;
    # otherwise each device keeps 1/d of what it holds and ships the
    # rest — the standard all-to-all volume over the d devices that
    # shard the record axis of a SLAB, which is what is uploaded and
    # exchanged (a frame count the devices do not divide still goes up
    # in slabs they do; a slab they do not divide goes up whole to each
    # and nothing crosses; a short last slab counts like the others)
    d_in = _axis0_device_width(
        mesh, (max(1, min(slab, n)),) + staged_shape[1:], split)
    alltoall_bytes = 0 if perm[0] == 0 or d_in <= 1 else int(
        round(total_bytes * (d_in - 1) / d_in))

    # what the resident leg holds at its peak: the output (allocated
    # once, every place program aliases it), the ring of uploaded slabs
    # and ONE place program's temp (the slab's transposed block)
    ring = max(1, int(ring))
    resident_bytes = total_bytes + slab_bytes \
        + ring * max(slab_bytes, int(raw_slab_bytes or 0))
    resident = budget is None or resident_bytes <= budget
    return ShufflePlan(
        in_shape=staged_shape, dtype=np.dtype(dtype), split=int(split),
        perm=perm, new_split=int(new_split), out_shape=out_shape, j0=j0,
        slab=int(slab), nslabs=int(nslabs), out_block=int(out_block),
        nbuckets=int(nbuckets), total_bytes=int(total_bytes),
        slab_bytes=int(slab_bytes), ring=ring,
        resident_bytes=int(resident_bytes),
        budget=None if budget is None else int(budget),
        resident=bool(resident), spill_dir=spill_dir,
        alltoall_bytes=int(alltoall_bytes), devices=int(d_in),
        sharded=bool(sharded))


def _pod_axes_or_refuse(mesh, slab_shape, split, perm, out_slab_shape,
                        new_split):
    """The pod re-bucket geometry check: the explicit ``all_to_all``
    form needs the input record axis's mesh axes to be exactly the
    ones the OUTPUT leading key axis shards over (the collective splits
    the new record extent over the same devices it gathers the old one
    from), and the new leading axis must come from a REPLICATED value
    axis (its full extent is local).  Returns the mesh-axis name tuple;
    raises the pointed refusal otherwise."""
    in_spec = _sharding.key_spec(mesh, slab_shape, split)
    axes_in = _sharding.spec_names(in_spec[0] if len(in_spec) else None)
    out_spec = _sharding.key_spec(mesh, out_slab_shape, new_split)
    axes_out = _sharding.spec_names(out_spec[0] if len(out_spec)
                                    else None)
    if perm[0] == 0:
        return ()                     # no cross-device movement
    if perm[0] < split:
        raise ValueError(
            "streamed swap on a pod needs the new leading key axis to "
            "come from a value axis or stay the record axis; key axis "
            "%d moving to the front has per-process layout this "
            "executor does not reshard — materialise the swap instead"
            % (perm[0],))
    if axes_in != axes_out:
        raise ValueError(
            "streamed swap on a pod needs the output key sharding to "
            "reuse the input record axis's mesh axes (got %r -> %r); "
            "materialise the swap instead" % (axes_in, axes_out))
    return axes_in


def _program_key(tag, plan, pre_stages, mesh, codec_obj, raw_dtype,
                 raw_slab_shape):
    """Engine key of one per-slab program: (stages, slab geometry,
    perm, codec, topology) — uniform slabs compile exactly once per
    variant per process, the short last slab once more."""
    from bolt_tpu.stream import stage_keys
    return (tag, stage_keys(pre_stages), tuple(raw_slab_shape),
            str(raw_dtype),
            plan.split, plan.perm, plan.new_split, mesh,
            _multihost.topology_token() if plan.sharded else None,
            codec_obj.name if codec_obj is not None else None)


def _rebucket_body(plan, pre_stages, mesh, codec_obj, raw_dtype,
                   raw_slab_shape, delta_ok):
    """The traced phase-1 expression of ONE slab, ``data -> block``:
    fused codec decode (when streaming rode a codec), the pre-swap
    stage chain, and the swap's transpose — the EXACT expression the
    materialised ``swap`` compiles, so parity holds by construction.

    ``raw_slab_shape`` is the UPLOADED slab's shape (wire dtype under a
    codec); the result is that slab's transposed block: the full
    new-key extent with the slab's records at axis ``plan.j0``.  The
    body also takes the slab's first key (``key0``, for a keyed stage:
    ``None`` where the caller has none to give) and the chain's side
    operands (``stream.stage_extras`` order).  On
    pods the body runs under
    ``shard_map`` with ONE explicit ``lax.all_to_all`` per slab
    (``split_axis=0`` of the new layout, ``concat_axis=j0``, tiled) —
    the TPU-native form of the reference's cluster-wide shuffle."""
    from bolt_tpu.stream import _stage_apply
    split = plan.split
    perm = plan.perm
    j0 = plan.j0
    slab_rows = raw_slab_shape[0]

    def body(data, key0=None, operands=()):
        if codec_obj is None:
            x = data
        elif codec_obj.sidecar:
            x = codec_obj.decode(data[0], data[1:], raw_dtype, delta_ok)
        else:
            x = codec_obj.decode(data, (), raw_dtype, delta_ok)
        operands = iter(operands)
        for stg in pre_stages:
            x = _stage_apply(stg, split, x, key0, operands)
        return jnp.transpose(x, perm)

    if not plan.sharded:
        return body

    from jax.sharding import PartitionSpec
    from bolt_tpu import _compat
    from bolt_tpu.parallel.sharding import key_spec
    out_slab_shape = tuple(
        slab_rows if i == j0 else plan.out_shape[i]
        for i in range(len(plan.out_shape)))
    staged_slab = tuple(
        slab_rows if i == 0 else plan.in_shape[i]
        for i in range(len(plan.in_shape)))
    axes = _pod_axes_or_refuse(mesh, staged_slab, split, perm,
                               out_slab_shape, plan.new_split)

    def shard_body(data):
        y = body(data)
        if axes:
            # one collective per slab: split the (locally full) new
            # record axis over the devices that held the old one,
            # concatenating each device's incoming pieces at j0 —
            # device order equals global record order, so the glued
            # global equals the global transpose bit-for-bit
            for name in axes:
                y = jax.lax.all_to_all(y, name, split_axis=0,
                                       concat_axis=j0, tiled=True)
        return y

    in_specs = key_spec(mesh, staged_slab, split)
    out_entries = [None] * len(out_slab_shape)
    out_entries[0] = (axes[0] if len(axes) == 1 else tuple(axes)) \
        if axes else None
    if not axes:
        # record axis stays leading: its sharding is unchanged
        out_entries[j0] = in_specs[0] if len(in_specs) else None
    mapped = _compat.shard_map(
        shard_body, mesh, in_specs=in_specs,
        out_specs=PartitionSpec(*out_entries), check_vma=False)
    # a pod's stages carry neither a key nor operands (stream.map_stage
    # records none there): the body's other two arguments are empty
    return lambda data, key0=None, operands=(): mapped(data)


def rebucket_program(plan, pre_stages, mesh, codec_obj, raw_dtype,
                     raw_slab_shape, delta_ok):
    """The ONE compiled phase-1 program each input slab of the SPILL
    leg runs: :func:`_rebucket_body`, its block constrained to the
    output key sharding and handed back for the bucket files."""
    key = _program_key("stream-shuffle", plan, pre_stages, mesh,
                       codec_obj, raw_dtype, raw_slab_shape)

    def build():
        from bolt_tpu.tpu.array import _constrain
        body = _rebucket_body(plan, pre_stages, mesh, codec_obj,
                              raw_dtype, raw_slab_shape, delta_ok)
        if plan.sharded:
            return jax.jit(body, donate_argnums=(0,))

        def run(data, *operands):
            return _constrain(body(data, None, operands), mesh,
                              plan.new_split)
        return jax.jit(run, donate_argnums=(0,))

    return _engine.get(key, build)


def alloc_program(plan, mesh):
    """The RESIDENT leg's state, made ONCE a resolution: the swapped
    array, zero-filled under the new key sharding, and the placement
    cursor (a uint32 zero).  Every slab's :func:`place_program` is
    handed both and hands both back: the array aliased, the cursor
    advanced — so no slab's dispatch carries a host operand up the link
    the uploader is filling."""
    key = ("stream-shuffle-alloc", plan.out_shape, str(plan.dtype),
           plan.new_split, mesh,
           _multihost.topology_token() if plan.sharded else None)

    def build():
        from jax.sharding import NamedSharding, PartitionSpec
        from bolt_tpu.parallel.sharding import key_sharding
        return jax.jit(
            lambda: (jnp.zeros(plan.out_shape, plan.dtype),
                     jnp.zeros((), jnp.uint32)),
            out_shardings=(key_sharding(mesh, plan.out_shape,
                                        plan.new_split),
                           NamedSharding(mesh, PartitionSpec())))

    return _engine.get(key, build)


def place_program(plan, pre_stages, mesh, codec_obj, raw_dtype,
                  raw_slab_shape, delta_ok, unit, thin=False):
    """The ONE compiled program each input slab of the RESIDENT leg
    runs, ``(out, data, cursor, *operands) -> (out, cursor')``
    (``operands``: the side operands of the stages before the re-axis,
    none for most chains; a KEYED stage among them reads the slab's
    first key off the same cursor the placement does):
    :func:`_rebucket_body` and the placement of its block into the
    swapped array at record ``cursor * unit`` along ``j0``, in one
    program.  ``out`` is donated and the result aliases it (the update
    is in place: the device never holds the parts beside the whole).

    The offset is an OPERAND, so every uniform slab of every pass runs
    the same executable, and it is spelled ``cursor * unit`` with
    ``unit`` static (the records a slab: callback sources cut uniform
    slabs, so slab ``g`` lands at ``g * unit``; ``1`` for iterator
    sources, whose blocks are what the iterable yields): XLA then knows
    the offset's low bits, and where ``unit`` is a whole number of lane
    tiles on the minor axis the update is an aligned copy — half the
    device time of the same update at an offset it knows nothing about
    (PERF.md, PR 32).  Unsigned, because a signed index is first
    wrapped (``select(i < 0, i + n, i)``), which hides those bits.

    ``thin``: ``data`` is the dense form of a slab (``stream._dense_views``:
    thin records, or the words of elements narrower than 32 bits), given
    its shape ``raw_slab_shape`` and its element ``raw_dtype`` by
    ``stream._reseat`` first, as a fold's slab program is handed it."""
    unit = int(unit)
    key = _program_key("stream-shuffle-place", plan, pre_stages, mesh,
                       codec_obj, raw_dtype, raw_slab_shape) + (unit, thin)

    def build():
        from bolt_tpu.tpu.array import _constrain
        body = _rebucket_body(plan, pre_stages, mesh, codec_obj,
                              raw_dtype, raw_slab_shape, delta_ok)
        j0 = plan.j0
        step = -(-int(raw_slab_shape[0]) // unit)

        from bolt_tpu.stream import _reseat, stage_extras
        keyed, _ = stage_extras(pre_stages)

        def run(out, data, cursor, *operands):
            zero = jnp.zeros((), jnp.uint32)
            first = cursor * jnp.uint32(unit)
            at = tuple(first if i == j0 else zero
                       for i in range(out.ndim))
            block = body(_reseat(data, raw_dtype) if thin else data,
                         first.astype(jnp.int32) if keyed else None,
                         operands)
            out = jax.lax.dynamic_update_slice(out, block, at)
            return (_constrain(out, mesh, plan.new_split),
                    cursor + jnp.uint32(step))
        return jax.jit(run, donate_argnums=(0, 1))

    return _engine.get(key, build)


LANES = 128          # the minor-axis tile of a TPU array, in elements


def tile_width(mesh, shape, split):
    """:func:`lane_slab`'s ``width``: how many devices shard the record
    axis of a slab of ``shape[1:]`` records — asked of a slab of one lane
    tile a device of the mesh, which every device can take a part of, so
    the answer does not hang on how many records the default slab
    happened to hold and stands for every slab drawn at that width."""
    ndev = int(mesh.devices.size) if mesh is not None else 1
    return _axis0_device_width(
        mesh, (LANES * ndev,) + tuple(shape[1:]), split)


def lane_slab(slab, records, record_bytes, perm, ceiling, width):
    """Records a slab for a streamed swap whose caller chose none: where
    the old record axis lands MINOR (``perm`` ends in 0) a slab's block
    is ``slab`` elements wide on the lane axis, and one that is not a
    whole number of lane tiles is padded to one in every temp and
    written with masks — so ``slab`` is rounded UP to whole tiles,
    unless that many records pass ``ceiling`` bytes (records so fat
    that a tile of them is no slab any more).  On the chip, 512 x 512
    float32 frames: 128 a slab re-axed 9 % more bytes a second than the
    default 64, at half the device time (PERF.md, PR 32).

    ``width`` devices shard a slab's record axis (:func:`tile_width`):
    the tiles and the ceiling are then a DEVICE's, because a device's
    part is what one link carries in one copy and what one device
    holds of the ring — on the four-chip host a slab of whole tiles a
    device is a quarter of the place calls and a copy four times as
    large a link, 128 MiB where the mesh-wide slab's was 32 (PERF.md,
    PR 60).  The devices divide the slab; at ``width`` 1 this is the
    one-device rule to the digit."""
    tile = LANES * width
    if perm[-1] != 0 or slab % tile == 0:
        return slab
    whole = -(-slab // tile) * tile
    if whole * record_bytes > ceiling * width:
        return slab
    return min(whole, max(int(records), 1))
