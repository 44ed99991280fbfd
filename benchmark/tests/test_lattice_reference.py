"""The seeded data and the plain reference, against NumPy in float64."""

import numpy as np
import pytest

import lattice
import manifest
import reference
import roofline

MAN = manifest.Manifest(manifest.REAL)

SHAPE = (24, 6, 8, 8)
SEEDS = [0, 7, 2**31 + 11, 4294967291]


def whole(seed, bits=12, shape=SHAPE):
    return lattice.host_block(0, shape[0], shape[1:], seed, bits)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_two_spellings_agree(seed):
    import jax
    import jax.numpy as jnp
    a, b = lattice.constants(seed)
    dev = jax.jit(lambda a, b: lattice.device_values(SHAPE, a, b, 12))(
        jnp.uint32(a), jnp.uint32(b))
    host = whole(seed)
    assert np.array_equal(np.asarray(dev), host)
    moved = jax.jit(lambda a, b: lattice.device_values(
        SHAPE, a, b, 12, order=(1, 0, 2, 3)))(jnp.uint32(a), jnp.uint32(b))
    assert np.array_equal(np.asarray(moved), host.transpose(1, 0, 2, 3))
    assert np.array_equal(lattice.host_tile(24, SHAPE[1:], seed, 12, 5), host)


@pytest.mark.parametrize("seed", SEEDS)
def test_twelve_bits_do_not_fit_bfloat16(seed):
    import ml_dtypes
    x = whole(seed)
    assert x.min() >= -2048 and x.max() < 2048
    assert np.array_equal(x, np.round(x))
    lost = x.astype(ml_dtypes.bfloat16).astype(np.float32) != x
    assert lost.mean() > 0.5
    # the smoke's four bits do fit, which is why it is not used
    y = whole(seed, bits=4)
    assert np.array_equal(y.astype(ml_dtypes.bfloat16).astype(np.float32), y)


def test_seeds_differ_and_repeat():
    assert np.array_equal(whole(5), whole(5))
    assert not np.array_equal(whole(5), whole(6))
    assert lattice.constants(2**31 + 5)[0] % 2 == 1
    with pytest.raises(ValueError, match="overflows"):
        lattice.strides((70000, 70000))


STEPS = {
    "map_sum": [{"call": "map", "fn": "plus_one"},
                {"call": "stat", "stat": "sum", "axis": [0, 1, 2, 3]}],
    "slice_mean": [{"call": "getitem", "index": [[3, 19], None, None, None]},
                   {"call": "stat", "stat": "mean", "axis": [0, 1, 2, 3]}],
    "slice_std": [{"call": "getitem", "index": [[5, 21], None, None, None]},
                  {"call": "stat", "stat": "std", "axis": [0, 1, 2, 3]}],
    "roi": [{"call": "getitem", "index": [None, [1, 4], [2, 6], [0, 8]]},
            {"call": "stat", "stat": "mean", "axis": [1, 2, 3]}],
    "slice_max": [{"call": "getitem", "index": [[0, 16], None, None, None]},
                  {"call": "stat", "stat": "max", "axis": [0]}],
    "key_sum": [{"call": "stat", "stat": "sum"}],
}


def by_numpy(x, name):
    x = x.astype(np.float64)
    return {"map_sum": lambda: (x + 1).sum(),
            "slice_mean": lambda: x[3:19].mean(),
            "slice_std": lambda: x[5:21].std(),
            "roi": lambda: x[:, 1:4, 2:6, 0:8].mean(axis=(1, 2, 3)),
            "slice_max": lambda: x[0:16].max(axis=0),
            "key_sum": lambda: x.sum(axis=0)}[name]()


@pytest.mark.parametrize("name", sorted(STEPS))
@pytest.mark.parametrize("seed", SEEDS[:2])
def test_resident_reference_is_numpys_float64(name, seed):
    import jax.numpy as jnp
    x = whole(seed)
    ref = reference.ResidentReference(MAN, jnp.asarray(x), SHAPE, 12, seed)
    want = by_numpy(x, name)
    got = ref.expected(STEPS[name])
    assert got.shape == np.shape(want)
    assert np.allclose(got, want, rtol=1e-12, atol=1e-9)
    # and the control misses it by more than float32 would
    low = ref.lowp(STEPS[name])
    assert reference.distance(low, got, 1.0) > 100 * reference.distance(
        np.float32(want), got, 1.0)
    assert ref.data_mismatches(np.random.default_rng(seed)) == 0


def test_int32_partial_sums_cannot_overflow():
    assert reference.fits((3200, 200, 64, 64), (0, 1, 2, 3), 2049) == (1, 2, 3)
    assert reference.fits((3200, 200, 64, 64), (0, 1, 2, 3), 4096) == (2, 3)
    assert reference.fits((16, 200, 64, 64), (0, 1, 2, 3), 2049 ** 2) == (3,)
    assert reference.fits((4, 4), (0, 1), 2 ** 31) == ()


def test_a_re_axis_is_compared_where_it_lies():
    import jax.numpy as jnp
    x = whole(3)
    ref = reference.ResidentReference(MAN, jnp.asarray(x), SHAPE, 12, 3)
    steps = [{"call": "swap", "kaxes": [0], "vaxes": [0]}]
    assert ref.plan(steps).terminal.perm == (1, 0, 2, 3)
    moved = jnp.asarray(x.transpose(1, 0, 2, 3))
    assert float(ref.on_device(steps, moved)) == 0
    wrong = moved.at[2, 3, 4, 5].add(1.0).at[0, 0, 0, 0].add(-1.0)
    assert float(ref.on_device(steps, wrong)) == 2
    assert float(ref.lowp_on_device(steps)) > x.size / 2
    assert float(ref.on_device(steps, jnp.asarray(
        x.transpose(2, 1, 0, 3).reshape(moved.shape)))) > x.size / 2


def test_tile_reference_and_its_control():
    tile = lattice.host_block(0, 1024, (4, 8), 9, 12)
    shape = (4096, 4, 8)
    ref = reference.TileReference(MAN, tile, shape, 12, 9)
    steps = [{"call": "map", "fn": "plus_one"}, {"call": "stat", "stat": "sum"}]
    want = 4 * (tile.astype(np.float64) + 1).sum(axis=0)
    assert np.array_equal(ref.expected(steps), want)
    low = ref.lowp(steps)
    assert reference.distance(low, want, 4096.0) > 1e-2
    assert ref.data_mismatches(np.random.default_rng(1)) == 0
    with pytest.raises(ValueError, match="key axis"):
        ref.expected([{"call": "stat", "stat": "sum", "axis": [1]}])


def test_distance_and_differing():
    want = np.array([1.0, 2.0])
    assert reference.distance(np.array([1.0, 2.5], np.float32), want, 2.0) \
        == 0.25
    assert reference.distance(np.array([1.0]), want, 1.0) == float("inf")
    assert reference.distance(np.array([1.0, np.nan]), want, 1.0) \
        == float("inf")
    assert reference.differing(np.array([1.0, 3.0]), want) == 1
    assert reference.differing(np.array([1.0]), want) == 2


@pytest.mark.parametrize("steps, want", [
    # one read of the whole stack, a scalar out
    (STEPS["map_sum"], 3200 * 200 * 64 * 64 * 4),
    # one read and one write
    ([{"call": "swap", "kaxes": [0], "vaxes": [0]}],
     2 * 3200 * 200 * 64 * 64 * 4),
    # the slice read once, nothing written
    (STEPS["slice_mean"], 16 * 200 * 64 * 64 * 4),
    # the slice read once and one volume written
    (STEPS["slice_max"], (16 + 1) * 200 * 64 * 64 * 4),
])
def test_hbm_bytes(steps, want):
    shape = (3200, 200, 64, 64)
    assert roofline.hbm_bytes(MAN, steps, shape, 4, 1) == want
    assert roofline.hbm_bytes(MAN, steps, shape, 4, 4) == want / 4


def test_the_roofline_count_against_the_measured_pass():
    # the 10.49 GB pass took 14.4-15.4 ms inside block_until_ready (PERF.md,
    # PR 21): the share has to come out under 100 %
    need = roofline.hbm_bytes(MAN, STEPS["map_sum"], (3200, 200, 64, 64), 4, 1)
    assert 0.8 < (need / 819e9) / 14.4e-3 < 1.0
