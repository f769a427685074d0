"""The search whose node is a row of 128 keys (ops/joinops.py
`_count_below`, behind `_binary_search` for a one-array key and
`front_row_ids`): equal to numpy's searchsorted limited to the live
bound, whatever the array's length does to the tree's depth."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu.ops import joinops

LENGTHS = [1, 127, 128, 129, 16_384 + 5, 128 * 128 + 1, 128 * 1024 + 1]
DTYPES = [np.int32, np.int64, np.float64]


def sorted_keys(rng, dtype, n, bound):
    """`n` keys, sorted with duplicates on [0, bound), anything after:
    the rows a build side sorted last hold whatever they held."""
    if dtype == np.float64:
        live = np.sort(rng.normal(0, 1_000, bound).round(1))
    else:
        live = np.sort(rng.integers(-n, n, bound))
    return np.concatenate(
        [live, rng.integers(-5, 5, n - bound)]).astype(dtype)


def probes(rng, keys, bound):
    """Keys that are there, keys between them, the ends and beyond."""
    live = keys[:bound].astype(np.float64)
    lo, hi = (live[0], live[-1]) if bound else (0.0, 0.0)
    out = np.concatenate([
        live[rng.integers(0, max(bound, 1), 200)] if bound else [0.0],
        rng.uniform(lo - 3, hi + 3, 200).round(1),
        [lo, hi, lo - 1, hi + 1, lo - 1e6, hi + 1e6]])
    if keys.dtype != np.float64:
        out = np.floor(out)
    # an integer key is probed by 64-bit values, as the engine's
    # probe side gives them (ops/common.py `orderable_keys`)
    return out.astype(np.float64 if keys.dtype == np.float64 else np.int64)


def expected(keys, probe, bound, upper):
    return np.searchsorted(keys[:bound], probe,
                           side="right" if upper else "left")


@pytest.mark.parametrize("upper", [False, True], ids=["lower", "upper"])
@pytest.mark.parametrize("where", ["none", "mid", "full"])
@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_bounds_equal_searchsorted(dtype, n, where, upper):
    rng = np.random.default_rng(n)
    bound = {"none": 0, "mid": n // 2, "full": n}[where]
    keys = sorted_keys(rng, dtype, n, bound)
    probe = probes(rng, keys, bound)
    got = joinops._binary_search([jnp.asarray(keys)], [jnp.asarray(probe)],
                                 jnp.int32(bound), n, upper=upper)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got),
                                  expected(keys, probe, bound, upper))


@pytest.mark.parametrize("upper", [False, True], ids=["lower", "upper"])
def test_32_bit_build_probed_by_64_bit_keys_outside_its_range(upper):
    """The build side's 32-bit sort operand (`_fits_32_bits`): dead and
    null-keyed rows carry `_ABOVE_32`, and a probe key that no int32
    holds lies below or above every key."""
    rng = np.random.default_rng(3)
    n, bound = 5_000, 4_000
    keys = np.concatenate([
        np.sort(rng.integers(-2 ** 31, 2 ** 31 - 1, bound)),
        np.full(n - bound, joinops._ABOVE_32)]).astype(np.int32)
    keys[bound - 3:bound] = 2 ** 31 - 2  # the largest key a row may hold
    probe = np.concatenate([
        keys[rng.integers(0, bound, 100)].astype(np.int64),
        [-2 ** 31, -2 ** 31 - 1, -2 ** 40, 2 ** 31 - 2, 2 ** 31 - 1,
         2 ** 31, 2 ** 40, -2 ** 63, 2 ** 63 - 1]]).astype(np.int64)
    got = joinops._binary_search([jnp.asarray(keys)], [jnp.asarray(probe)],
                                 jnp.int32(bound), n, upper=upper)
    np.testing.assert_array_equal(np.asarray(got),
                                  expected(keys, probe, bound, upper))


@pytest.mark.parametrize("n,reads", [(128 * 128, 1), (128 * 128 + 1, 2),
                                     (128 ** 3, 2), (128 ** 3 + 1, 3)])
def test_the_trees_depth_follows_the_arrays_length(n, reads):
    """One more level, and one more row read a probe, each time the
    level below outgrows one row of last keys."""
    assert joinops.search_reads(n) == reads
    rng = np.random.default_rng(reads)
    keys = sorted_keys(rng, np.int32, n, n - 7)
    probe = probes(rng, keys, n - 7)
    for upper in (False, True):
        got = joinops._count_below(jnp.asarray(keys), jnp.asarray(probe),
                                   jnp.int32(n - 7), upper)
        np.testing.assert_array_equal(np.asarray(got),
                                      expected(keys, probe, n - 7, upper))


def test_probes_past_one_block_are_searched_a_block_at_a_time(monkeypatch):
    monkeypatch.setattr(joinops, "_PROBE_BLOCK", 256)
    rng = np.random.default_rng(11)
    n, bound = 3_000, 2_500
    keys = sorted_keys(rng, np.int64, n, bound)
    probe = rng.integers(-n - 5, n + 5, 1_000)
    got = joinops._count_below(jnp.asarray(keys), jnp.asarray(probe),
                               jnp.int32(bound), False)
    np.testing.assert_array_equal(np.asarray(got),
                                  expected(keys, probe, bound, False))


@pytest.mark.parametrize("dtype", [np.int32, np.int64])
@pytest.mark.parametrize("n,bound,block", [
    (1, 1, None), (127, 100, None), (128 * 128 + 5, 128 * 128 - 3, None),
    (3_000, 2_500, 256), (128 ** 3 + 1, 128 ** 3 - 9, None)])
def test_the_lower_bound_says_whether_its_key_equals_the_probe(
        monkeypatch, dtype, n, bound, block):
    """`with_equal`: whether `keys[lo] == probe` inside the bound, read
    off the row the last level fetched (a lookup join's `matched`, for
    no gather of its own): duplicates, rows that end at the bound, a
    probe wider than the keys and outside their width."""
    if block:
        monkeypatch.setattr(joinops, "_PROBE_BLOCK", block)
    rng = np.random.default_rng([n, bound])
    keys = sorted_keys(rng, dtype, n, bound)
    probe = np.concatenate([
        probes(rng, keys, bound),
        np.array([np.iinfo(np.int64).min, np.iinfo(np.int64).max,
                  2 ** 40, -2 ** 40], np.int64)])
    lo, equal = joinops._count_below(
        jnp.asarray(keys), jnp.asarray(probe), jnp.int32(bound), False,
        with_equal=True)
    want = expected(keys, probe, bound, False)
    np.testing.assert_array_equal(np.asarray(lo), want)
    at = np.clip(want, 0, n - 1)
    np.testing.assert_array_equal(
        np.asarray(equal), (want < bound) & (keys[at] == probe))
    # and with no bound: all of the array is sorted
    whole = np.sort(keys)
    lo, equal = joinops._count_below(jnp.asarray(whole), jnp.asarray(probe),
                                     None, False, with_equal=True)
    want = np.searchsorted(whole, probe, side="left")
    np.testing.assert_array_equal(np.asarray(lo), want)
    np.testing.assert_array_equal(
        np.asarray(equal),
        (want < n) & (whole[np.clip(want, 0, n - 1)] == probe))


def test_no_key_and_no_probe():
    none = jnp.zeros((0,), jnp.int64)
    some = jnp.arange(4, dtype=jnp.int64)
    assert joinops._count_below(none, some, jnp.int32(0), False).tolist() \
        == [0, 0, 0, 0]
    assert joinops._count_below(some, none, jnp.int32(4), True).shape == (0,)
    lo, equal = joinops._count_below(none, some, jnp.int32(0), False,
                                     with_equal=True)
    assert lo.tolist() == [0] * 4 and equal.tolist() == [False] * 4


@pytest.mark.parametrize("arrays,loops", [(1, False), (2, True)],
                         ids=["one-array", "tuple"])
def test_only_a_tuple_key_takes_the_loop(arrays, loops):
    """What the code sees in its input picks the path: a key of ONE
    array descends the tree of rows; a tuple of key arrays (strings
    packed to words, several columns) keeps its loop, a key a step."""
    rng = np.random.default_rng(5)
    n = 1_000
    build = np.sort(rng.integers(0, 50, n))
    keys = [jnp.asarray(build)] + [jnp.zeros(n, jnp.int64)] * (arrays - 1)
    probe = [jnp.asarray(rng.integers(-1, 51, 64))] \
        + [jnp.zeros(64, jnp.int64)] * (arrays - 1)

    def search(keys, probe):
        return joinops._binary_search(keys, probe, jnp.int32(n), n,
                                      upper=False)

    text = str(jax.make_jaxpr(search)(keys, probe))
    assert any(loop in text for loop in ("while[", "scan[")) == loops
    np.testing.assert_array_equal(
        np.asarray(search(keys, probe)),
        np.searchsorted(build, np.asarray(probe[0]), side="left"))


@pytest.mark.parametrize("slots,reads", [
    (1, 1), (128 * 128, 1), (57_344, 2), (65_536, 2), (128 ** 3, 2),
    (3_670_016, 3), (7_864_320, 3), (15_728_640, 3), (128 ** 4, 3),
    (128 ** 4 + 1, 4)])
def test_search_reads(slots, reads):
    """The record's `probeSteps` and `rowIdReads` at the widths the
    cells run: Q12's 15,728,640 build slots and 7,864,320-slot parts,
    the star's 3,670,016-slot parts and 57,344 survivors."""
    assert joinops.search_reads(slots) == reads


@pytest.mark.parametrize("capacity", [64, 4_096])
@pytest.mark.parametrize("share", [0.0, 0.01, 0.2, 1.0])
@pytest.mark.parametrize("width", [100, 128, 5_000, 128 * 1024 + 77])
def test_front_row_ids_equal_flatnonzero(width, share, capacity):
    rng = np.random.default_rng(width + capacity)
    keep = rng.random(width) < share
    ids, total = joinops.front_row_ids(jnp.asarray(keep), capacity)
    want = np.flatnonzero(keep)
    assert int(total) == len(want)
    assert ids.shape == (capacity,) and ids.dtype == jnp.int32
    kept = min(len(want), capacity)
    np.testing.assert_array_equal(np.asarray(ids)[:kept], want[:capacity])
    # past the total: garbage, but a row of the batch
    assert ((np.asarray(ids) >= 0) & (np.asarray(ids) < width)).all()
