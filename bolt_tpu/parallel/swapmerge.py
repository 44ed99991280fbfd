"""The receive side of a resident swap across chips, written once.

A resident ``swap`` whose sharded axis changes is an exchange and a glue
(``tpu/array.py :: _do_swap``).  The exchange is an ``all_to_all``: every
chip sends each other chip the part of its own records that the other
will own.  The glue is what is left on the receiving chip: ``n`` pieces,
one from each chip, each ``per_chip`` records wide, have to lie side by
side along the old key axis, which the answer holds whole.

On a TPU the resident array keeps its key axis on the LANES wherever the
value axes are short (the device's own layout: a ``(4400, 200, 64, 64)``
stack has 1,100 records a chip on the lanes, in 1,152).  Laying pieces
side by side on the lanes at offsets ``i * per_chip`` that are no
multiple of 128 is a lane shift, and GSPMD's program does it as a
transpose into a staging layout and then a lane-merging reshape: two
whole passes over the answer, and two temporaries of its size.  The work
is one pass, read ``n`` pieces and write one row, and :func:`program`
writes it as one: ``shard_map`` over the mesh axes that shard the keys,
``lax.all_to_all`` on the view the compiler takes anyway (the send side
is a bitcast), and ONE Mosaic kernel, ``swap_merge`` on a trace, that
reads a tile of rows of each piece and stores it at its lane offset.
Every reshape and transpose around the kernel is a bitcast under those
layouts, and the answer keeps the layout it has today.

:func:`plan` and :func:`takes` say from what a caller can see (the mesh,
the two key shardings, the permutation, the dtype; the chips and the
source's lane axis) whether a swap is of that kind; every other swap
keeps the ``jnp.transpose`` under a sharding constraint.  Where
``per_chip`` is a multiple of 128 the pieces are whole lane tiles and
XLA's own program is one pass already (PERF.md, PR 45), so those keep it
too.
"""

import hashlib
from collections import namedtuple
from functools import lru_cache, partial

import jax
from jax import lax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from bolt_tpu import engine as _engine
from bolt_tpu._compat import shard_map
from bolt_tpu.parallel.sharding import key_spec, spec_names
from bolt_tpu.utils import prod

_LANES = 128
# what the blocks of one grid step may hold in VMEM, both of them twice
# (the pipeline's two buffers); under Mosaic's 16 MiB of scoped VMEM with
# room for the stores' own temporaries
_TILE_BYTES = 6 << 20

# ``names``: the mesh axes that shard the source's key axis ``key`` and
# the answer's; ``moved``: the source's value axis that the answer
# shards; ``n`` chips along ``names``; ``per_chip`` records of ``key`` a
# chip; ``rows`` a chip glues, ``tile`` of them a grid step
Plan = namedtuple("Plan", "names n key moved per_chip rows tile "
                          "source answer")


def _one_sharded(spec):
    """``(axis, names)`` of the one sharded axis of ``spec``, or ``None``
    where none or several are sharded."""
    found = [(i, spec_names(e)) for i, e in enumerate(spec) if e is not None]
    return found[0] if len(found) == 1 else None


def _pad(extent):
    return -(-extent // _LANES) * _LANES


def _tile(rows, n, per_chip):
    """Rows of the answer a grid step glues: as many as ``_TILE_BYTES``
    holds of the ``n`` pieces and of the row they become, each padded to
    whole lane tiles and held twice; whole sublane tiles, or all of
    ``rows``.  0 where eight rows do not fit."""
    a_row = 2 * 4 * (n * _pad(per_chip) + _pad(n * per_chip))
    fit = _TILE_BYTES // a_row
    if rows <= fit:
        return rows
    return fit // 8 * 8


def on_tpu(mesh):
    """Whether ``mesh`` is of TPU chips (a described topology's too)."""
    return mesh.devices.flat[0].platform == "tpu"


def takes(p, mesh, data):
    """Whether the swap of plan ``p`` of the resident ``data`` is one that
    GSPMD's program glues in two passes and :func:`program` in one: a
    mesh of TPU chips, the sharded key axis on the lanes of ``data`` as
    the device laid it out, in pieces that are no whole lane tiles."""
    if not on_tpu(mesh) or p.per_chip % _LANES == 0:
        return False
    # a runtime that does not say how it laid the array out (a deleted
    # array, a backend without layouts) gives no layout: no glue
    layout = getattr(getattr(data, "format", None), "layout", None)
    return layout is not None and layout.major_to_minor[-1] == p.key


def plan(mesh, shape, dtype, split, perm, new_split):
    """The :class:`Plan` of the swap ``transpose(perm)`` of a resident
    array of ``shape`` with ``split`` key axes into ``new_split``, or
    ``None`` where the glue does not take it: the source's and the
    answer's key shardings each put ONE axis on the same mesh axes, that
    axis moves out of the keys, the elements are 32-bit, and eight rows
    of the answer fit the kernel's tile."""
    if mesh is None or mesh.devices.size < 2 \
            or jnp.dtype(dtype).itemsize != 4:
        return None
    out_shape = tuple(shape[p] for p in perm)
    source = key_spec(mesh, shape, split)
    answer = key_spec(mesh, out_shape, new_split)
    src, dst = _one_sharded(source), _one_sharded(answer)
    if src is None or dst is None or src[1] != dst[1]:
        return None
    key, moved = src[0], perm[dst[0]]
    n = prod([mesh.shape[name] for name in src[1]])
    if n < 2 or moved < split or perm.index(key) < new_split:
        return None
    per_chip = shape[key] // n
    rows = prod(shape) // shape[key] // n
    tile = _tile(rows, n, per_chip)
    if tile == 0:
        return None
    return Plan(src[1], n, key, moved, per_chip, rows, tile, source, answer)


def _merge_kernel(pieces, out, *, n, per_chip):
    for i in range(n):
        out[:, i * per_chip:(i + 1) * per_chip] = pieces[i]


def merge(pieces, tile, interpret=False):
    """``(rows, n * per_chip)`` from ``pieces (n, rows, per_chip)``: piece
    ``i`` at lanes ``i * per_chip`` of every row, in one pass."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n, rows, per_chip = pieces.shape
    # Mosaic has no 64-bit types: the index maps trace int32 whatever the
    # session's x64 says
    with jax.enable_x64(False):
        return pl.pallas_call(
            partial(_merge_kernel, n=n, per_chip=per_chip),
            out_shape=jax.ShapeDtypeStruct((rows, n * per_chip),
                                           pieces.dtype),
            grid=(pl.cdiv(rows, tile),),
            in_specs=[pl.BlockSpec((n, tile, per_chip),
                                   lambda i: (0, i, 0))],
            out_specs=pl.BlockSpec((tile, n * per_chip), lambda i: (i, 0)),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=interpret,
            name="swap_merge",
        )(pieces)


def program(p, mesh, perm):
    """The swap of plan ``p`` as a function of the resident array: the
    exchange and the glue, a chip's part under ``shard_map``.  On a mesh
    that is not of TPU chips the kernel is interpreted."""
    key, moved, n = p.key, p.moved, p.n
    interpret = not on_tpu(mesh)
    # a piece's axes after the exchange: the source's, with ``moved``
    # split in (the chip a piece came from, this chip's part of it)
    at = lambda s: s if s < moved else s + 1        # noqa: E731
    rest = [at(s) for s in perm if s != key]

    def local(x):
        shape = x.shape
        x = x.reshape(shape[:moved] + (n, shape[moved] // n)
                      + shape[moved + 1:])
        pieces = lax.all_to_all(x, p.names, moved, moved)
        rows = [pieces.shape[r] for r in rest]
        flat = pieces.transpose([moved] + rest + [at(key)]).reshape(
            n, p.rows, p.per_chip)
        glued = merge(flat, p.tile, interpret)
        return jnp.moveaxis(glued.reshape(rows + [n * p.per_chip]), -1,
                            perm.index(key))

    return shard_map(local, mesh, in_specs=p.source, out_specs=p.answer,
                     check_vma=False)


@lru_cache(maxsize=None)
def _source():
    """This module's text, hashed: what a kept export of :func:`program`
    was lowered from."""
    with open(__file__, "rb") as fh:
        return hashlib.sha256(fh.read()).hexdigest()


def swapper(p, mesh, perm, shape, dtype):
    """What ``_do_swap`` jits for plan ``p`` of a resident array of
    ``shape`` and ``dtype``: :func:`program`, counted
    (``swap_merge_lowerings``).  Where an on-disk cache is attached the
    program is kept there exported (``engine.exported``) and this is the
    export's call: a warm process reads it and imports no Pallas, which
    is most of what lowering the program costs."""
    _engine.record_swap_merge_lowering()
    make = partial(program, p, mesh, perm)
    kept = _engine.exported(
        "swap_merge",
        (_source(), tuple(p), tuple(perm), mesh.axis_names,
         mesh.devices.shape, mesh.devices.flat[0].device_kind),
        make,
        jax.ShapeDtypeStruct(shape, dtype,
                             sharding=NamedSharding(mesh, p.source)),
        out_shardings=NamedSharding(mesh, p.answer))
    return make() if kept is None else kept.call
