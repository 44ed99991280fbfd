"""The receive side of a resident swap across chips as ONE pass
(``bolt_tpu/parallel/swapmerge.py``, PR 45): an explicit ``all_to_all``
under ``shard_map`` and the kernel ``swap_merge`` that lays the pieces a
chip received side by side along the old key axis.

The glue is chosen from what ``_do_swap`` can see (``swapmerge.plan``: the
mesh, the two key shardings, the permutation, the dtype) and from two
facts only a TPU has (``swapmerge.takes``: chips, and the key axis on the
lanes in pieces that are no whole lane tiles), so on the CPU mesh every
swap keeps the transpose under a constraint.  These tests answer
``takes`` themselves and let Pallas interpret the kernel: every swap the
plan takes, and every one it does not, is held to the NumPy transpose,
which is what ``mode='local'`` holds, to the bit.  That the program
compiles for the chips to one pass is in ``tests/test_ops_kernels.py``,
the one file that loads the TPU's compiler."""

import numpy as np
import pytest

import jax

import bolt_tpu as bolt
from bolt_tpu import engine
from bolt_tpu.parallel import swapmerge


@pytest.fixture
def glue_everywhere(monkeypatch):
    """Every swap the plan takes is glued, the kernel interpreted: the
    two facts of a TPU answered as the four-chip host answers them."""
    monkeypatch.setattr(swapmerge, "takes", lambda p, mesh, data: True)
    engine.clear()
    yield
    engine.clear()


def mesh_of(n, names=("k",), shape=None):
    devices = np.array(jax.devices()[:n])
    return jax.sharding.Mesh(devices.reshape(shape or (n,)), names)


def lowerings():
    return engine.counters()["swap_merge_lowerings"]


def oracle(x, split, kaxes, vaxes):
    """``(transposed, new split)`` as ``swap``'s docstring gives them."""
    nvalue = x.ndim - split
    keys_rest = [k for k in range(split) if k not in kaxes]
    values_rest = [v for v in range(nvalue) if v not in vaxes]
    perm = (keys_rest + [split + v for v in vaxes] + list(kaxes)
            + [split + v for v in values_rest])
    return np.transpose(x, perm), len(keys_rest) + len(vaxes)


def ramp(shape, dtype=np.float32):
    # every element its own value: a piece at the wrong lanes cannot hide
    return np.arange(int(np.prod(shape)), dtype=dtype).reshape(shape)


def swapped(x, mesh, split, kaxes, vaxes, **kwargs):
    b = bolt.array(x, mesh, axis=tuple(range(split)))
    before = lowerings()
    out = b.swap(kaxes, vaxes, **kwargs)
    want, new_split = oracle(x, split, kaxes, vaxes)
    assert out.shape == want.shape and out.split == new_split
    assert out.dtype == x.dtype
    got = np.asarray(out.toarray())
    assert got.tobytes() == want.tobytes()
    return b, out, lowerings() - before


# ---------------------------------------------------------------------
# (a) swaps the glue takes: 2-, 3- and 4-D arrays over 2, 4 and 8
# devices, pieces of 1, 3, 76 (= 1100 % 128), 128, 204 and 1,100 records
# (lane offsets 0 / 76 / 24 / 100 on four chips: the cell's)
# ---------------------------------------------------------------------

def _taken():
    out = []
    for n in (2, 4, 8):
        for per_chip in (1, 3, 76, 128, 204, 1100):
            for rank, rest in ((2, ()), (3, (5,)), (4, (3, 2))):
                if per_chip == 1100 and (n != 4 or rank == 3):
                    continue
                out.append(pytest.param(
                    n, (n * per_chip, 2 * n) + rest,
                    id="n%d-p%d-%dd" % (n, per_chip, rank)))
    return out


@pytest.mark.parametrize("n,shape", _taken())
def test_a_glued_swap_is_the_transpose_to_the_bit(glue_everywhere, n, shape):
    mesh = mesh_of(n)
    _, out, lowered = swapped(ramp(shape), mesh, 1, (0,), (0,))
    assert lowered == 1
    assert out._data.sharding.is_equivalent_to(
        bolt.parallel.sharding.key_sharding(mesh, out.shape, 1), out.ndim)


@pytest.mark.parametrize("case", [
    # the moved-in value axis is not the first, and values stay behind it
    pytest.param(((4 * 5, 3, 8, 4), 1, (0,), (1,), 4, np.float32),
                 id="second-value"),
    # a key axis nothing shards rides along: the answer shards ITS second
    pytest.param(((4 * 7, 3, 8, 2), 2, (0,), (0,), 4, np.float32),
                 id="two-keys"),
    # several value axes move in; the first of them takes the chips
    pytest.param(((8 * 3, 16, 2, 5), 1, (0,), (0, 1), 8, np.float32),
                 id="two-values"),
    pytest.param(((4 * 76, 8), 1, (0,), (0,), 4, np.int32), id="int32"),
    pytest.param(((2 * 3, 4, 5), 1, (0,), (0,), 2, np.uint32), id="uint32"),
])
def test_swaps_of_other_axes_and_dtypes_are_glued_too(glue_everywhere, case):
    shape, split, kaxes, vaxes, n, dtype = case
    _, _, lowered = swapped(ramp(shape, dtype), mesh_of(n), split, kaxes,
                            vaxes)
    assert lowered == 1


def test_one_key_axis_over_two_mesh_axes_is_one_exchange(glue_everywhere):
    # key_spec spreads a lone key axis over the whole mesh: the exchange
    # runs over both names at once
    mesh = mesh_of(8, ("a", "b"), (2, 4))
    _, out, lowered = swapped(ramp((8 * 3, 16, 5)), mesh, 1, (0,), (0,))
    assert lowered == 1
    assert out._data.sharding.spec[0] == ("a", "b")


def test_donating_the_source_glues_and_consumes_it(glue_everywhere):
    b, out, lowered = swapped(ramp((4 * 76, 8, 3)), mesh_of(4), 1, (0,),
                              (0,), donate=True)
    assert lowered == 1
    with pytest.raises(RuntimeError):
        b.toarray()


def test_the_program_is_built_once_a_shape(glue_everywhere):
    mesh = mesh_of(4)
    x = ramp((4 * 3, 8))
    assert swapped(x, mesh, 1, (0,), (0,))[2] == 1
    assert swapped(x + 1, mesh, 1, (0,), (0,))[2] == 0


# ---------------------------------------------------------------------
# (b) swaps that keep the transpose under a constraint, whatever a TPU
# would answer: the counter stays where it was
# ---------------------------------------------------------------------

@pytest.mark.parametrize("case", [
    pytest.param(((9, 4), 1, (0,), (0,), 4, np.float32), id="undivided"),
    pytest.param(((12, 6), 1, (0,), (0,), 4, np.float32),
                 id="answer-undivided"),
    pytest.param(((12, 8), 1, (0,), (0,), 1, np.float32), id="one-device"),
    pytest.param(((12, 8), 1, (0,), (0,), 4, np.float64), id="float64"),
    pytest.param(((12, 8), 1, (0,), (0,), 4, np.int16), id="int16"),
    # the sharded axis stays where it is: the second key axis moves out
    pytest.param(((8, 6, 4), 2, (1,), (0,), 4, np.float32),
                 id="sharded-stays"),
])
def test_a_swap_the_plan_does_not_take_keeps_the_transpose(glue_everywhere,
                                                           case):
    shape, split, kaxes, vaxes, n, dtype = case
    _, _, lowered = swapped(ramp(shape, dtype), mesh_of(n), split, kaxes,
                            vaxes)
    assert lowered == 0


def test_two_sharded_key_axes_keep_the_transpose(glue_everywhere):
    mesh = mesh_of(4, ("a", "b"), (2, 2))
    _, _, lowered = swapped(ramp((4, 6, 8)), mesh, 2, (0,), (0,))
    assert lowered == 0


def test_a_deferred_chain_fuses_into_the_transpose(glue_everywhere):
    mesh = mesh_of(4)
    x = ramp((4 * 76, 8, 3))
    b = bolt.array(x, mesh).map(lambda v: v * 2 + 1)
    assert b.deferred
    before = lowerings()
    out = b.swap((0,), (0,))
    assert lowerings() == before
    want, _ = oracle(x * 2 + 1, 1, (0,), (0,))
    assert np.asarray(out.toarray()).tobytes() == want.tobytes()


def test_off_the_tpu_no_swap_is_glued():
    # ``takes`` as it is: the CPU mesh is no TPU
    engine.clear()
    for n in (1, 4, 8):
        assert swapped(ramp((8 * 76, 8, 3)), mesh_of(n), 1, (0,), (0,))[2] == 0


# ---------------------------------------------------------------------
# (c) beside an on-disk cache the lowered program is kept EXPORTED, and a
# warm process reads it: nothing of ``program`` is traced again (on the
# chip's host: Pallas is not imported, 1.3 s of a 4.5 s set-up)
# ---------------------------------------------------------------------

@pytest.fixture
def disk_cache(tmp_path, glue_everywhere):
    engine.persistent_cache(str(tmp_path / "cache"))
    yield tmp_path / "cache" / "bolt_exported"
    engine.persistent_cache(enable=False)


def _no_trace(*args, **kwargs):
    raise AssertionError("the kept export was not used")


@pytest.mark.parametrize("names,grid", [(("k",), (4,)), (("a", "b"), (2, 4))],
                         ids=["one-axis", "two-axes"])
def test_a_warm_process_reads_the_export_and_traces_nothing(
        disk_cache, monkeypatch, names, grid):
    mesh = mesh_of(int(np.prod(grid)), names, grid)
    x = ramp((8 * 76, 16, 3))
    assert swapped(x, mesh, 1, (0,), (0,))[2] == 1
    kept = sorted(disk_cache.glob("swap_merge-*.jaxexport"))
    assert len(kept) == 1 and not list(disk_cache.glob("*.tmp"))
    engine.clear()                      # what a new process starts with
    monkeypatch.setattr(swapmerge, "program", _no_trace)
    assert swapped(x, mesh, 1, (0,), (0,))[2] == 1
    b, _, lowered = swapped(x + 1, mesh, 1, (0,), (0,), donate=True)
    assert lowered == 1                 # its own engine key, the same file
    with pytest.raises(RuntimeError):
        b.toarray()
    assert sorted(disk_cache.glob("*")) == kept
    # another shape is another program, and another file
    with pytest.raises(AssertionError, match="kept export"):
        swapped(ramp((8 * 76, 8, 3)), mesh, 1, (0,), (0,))


def test_a_cut_export_is_said_and_written_again(disk_cache):
    mesh = mesh_of(4)
    x = ramp((4 * 76, 8, 3))
    swapped(x, mesh, 1, (0,), (0,))
    kept, = disk_cache.glob("swap_merge-*.jaxexport")
    whole = kept.read_bytes()
    kept.write_bytes(whole[:len(whole) // 2])
    engine.clear()
    with pytest.warns(RuntimeWarning, match="unreadable"):
        assert swapped(x, mesh, 1, (0,), (0,))[2] == 1
    again = jax.export.deserialize(bytearray(kept.read_bytes()))
    assert again.nr_devices == 4 and len(kept.read_bytes()) > len(whole) // 2


def test_the_export_is_keyed_by_what_decides_the_program(disk_cache,
                                                         monkeypatch):
    mesh = mesh_of(4)
    x = ramp((4 * 76, 8, 3))
    swapped(x, mesh, 1, (0,), (0,))
    engine.clear()
    # the module's own text is part of the key: an edited kernel is not
    # served from the file its predecessor left
    monkeypatch.setattr(swapmerge, "_source", lambda: "edited")
    swapped(x, mesh, 1, (0,), (0,))
    assert len(list(disk_cache.glob("swap_merge-*.jaxexport"))) == 2


def test_without_a_cache_nothing_is_exported(glue_everywhere):
    assert engine.persistent_cache_dir() is None
    assert engine.exported("swap_merge", (), _no_trace) is None


# ---------------------------------------------------------------------
# (d) the plan and the tile, from shapes alone
# ---------------------------------------------------------------------

def test_the_plan_of_the_cells_swap():
    mesh = mesh_of(4)
    p = swapmerge.plan(mesh, (4400, 200, 64, 64), np.float32, 1,
                       [1, 0, 2, 3], 1)
    assert (p.names, p.n, p.key, p.moved) == (("k",), 4, 0, 1)
    assert (p.per_chip, p.rows) == (1100, 50 * 64 * 64)
    # four pieces of 1,152 lanes and a row of 4,480, each twice, a row
    assert p.tile % 8 == 0
    assert p.tile * 2 * 4 * (4 * 1152 + 4480) <= swapmerge._TILE_BYTES
    assert (p.tile + 8) * 2 * 4 * (4 * 1152 + 4480) > swapmerge._TILE_BYTES


def test_a_tile_is_whole_sublane_tiles_or_every_row():
    assert swapmerge._tile(50, 4, 1100) == 50           # all of them
    assert swapmerge._tile(5, 2, 3) == 5
    for rows, n, per_chip in ((204800, 4, 1100), (10 ** 6, 8, 1),
                              (4096, 2, 7000)):
        tile = swapmerge._tile(rows, n, per_chip)
        assert 8 <= tile < rows and tile % 8 == 0
    # a row of which eight do not fit: no plan, the transpose stays
    assert swapmerge._tile(4096, 4, 1 << 20) == 0
    assert swapmerge.plan(mesh_of(4), (4 << 20, 4096), np.float32, 1,
                          [1, 0], 1) is None


def test_a_ragged_last_tile_is_glued_whole(glue_everywhere, monkeypatch):
    # 21 rows in tiles of 8: the last grid step holds five
    monkeypatch.setattr(swapmerge, "_TILE_BYTES",
                        8 * 2 * 4 * (4 * 128 + 384))
    assert swapmerge._tile(21, 4, 76) == 8
    _, _, lowered = swapped(ramp((4 * 76, 4 * 7, 3)), mesh_of(4), 1, (0,),
                            (0,))
    assert lowered == 1


def test_takes_asks_for_chips_lanes_and_a_shift():
    class Laid:
        def __init__(self, minor):
            self.format = type("F", (), {"layout": type(
                "L", (), {"major_to_minor": (1, 2, minor)})})

    mesh = mesh_of(4)
    p = swapmerge.plan(mesh, (4 * 76, 8, 3), np.float32, 1, [1, 0, 2], 1)
    assert not swapmerge.takes(p, mesh, Laid(0))        # no TPU
    as_tpu = type("M", (), {"devices": np.array(
        [type("D", (), {"platform": "tpu"})()])})()
    assert swapmerge.takes(p, as_tpu, Laid(0))
    assert not swapmerge.takes(p, as_tpu, Laid(2))      # keys off the lanes
    assert not swapmerge.takes(p, as_tpu, object())     # layout unknown
    whole = swapmerge.plan(mesh, (4 * 128, 8, 3), np.float32, 1,
                           [1, 0, 2], 1)
    assert not swapmerge.takes(whole, as_tpu, Laid(0))  # whole lane tiles
