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


# --- a block of clustered probes searches a window of the keys -------

BLOCK, WINDOW = 512, 16  # rows of 128 keys: a window holds 2,048 keys


@pytest.fixture
def small_blocks(monkeypatch):
    monkeypatch.setattr(joinops, "_PROBE_BLOCK", BLOCK)
    monkeypatch.setattr(joinops, "_WINDOW_ROWS", WINDOW)


def clustered_keys(rng, dtype, n, bound):
    """`n` keys, sorted and sparse on [0, bound) with a few duplicates
    (a build side whose probes each find at most a handful of keys),
    anything after."""
    live = np.sort(rng.choice(40 * n, bound, replace=True))
    return np.concatenate(
        [live, rng.integers(-5, 5, n - bound)]).astype(dtype)


def probes_of(rng, kind, keys, bound, nq):
    """`nq` probes of `keys[:bound]`. `sorted`: every block's answers
    lie in a few rows, in order; `shuffled`: the same rows a block, in
    any order inside it (a fact table clustered by its parent's key,
    not sorted by it); `spread`: over all of the keys; `mixed`: the
    first block spread, the others clustered."""
    top = int(keys[bound - 1]) if bound else 10
    sorted_ = np.sort(rng.integers(-3, top + 3, nq))
    if kind == "spread":
        return rng.permutation(sorted_)
    if kind == "sorted":
        return sorted_
    blocks = [rng.permutation(sorted_[i:i + BLOCK])
              for i in range(0, nq, BLOCK)]
    if kind == "mixed":
        blocks[0] = rng.integers(-3, top + 3, len(blocks[0]))
    return np.concatenate(blocks)


def narrow_blocks(keys, probe, valid, bound, upper):
    """What `_count_below` should report: the blocks whose valid
    probes' answers, as rows of the keys, differ by less than a
    window."""
    took = 0
    for i in range(0, len(probe), BLOCK):
        q = probe[i:i + BLOCK][valid[i:i + BLOCK]]
        if len(q):
            ends = expected(keys, np.array([q.min(), q.max()]), bound,
                            upper) // 128
            took += bool(ends[1] - ends[0] < WINDOW)
    return took


@pytest.mark.parametrize("upper", [False, True], ids=["lower", "upper"])
@pytest.mark.parametrize("where", ["none", "mid", "full", "unbounded"])
@pytest.mark.parametrize("kind", ["sorted", "shuffled", "spread", "mixed"])
@pytest.mark.parametrize("dtype", [np.int32, np.int64],
                         ids=lambda d: d.__name__)
def test_blocks_of_probes_equal_searchsorted_by_window_or_whole(
        small_blocks, dtype, kind, where, upper):
    """Each block of 512 probes over 6,000 keys (47 rows, a window of
    16): whichever way a block goes, the bound is numpy's, and the
    count of blocks that took the window is the count of blocks whose
    own keys allow it. 2,300 probes: the last block is padded."""
    rng = np.random.default_rng([upper, len(kind), len(where)])
    n, nq = 6_000, 2_300
    bound = {"none": 0, "mid": n // 2, "full": n, "unbounded": n}[where]
    keys = clustered_keys(rng, dtype, n, bound)
    probe = probes_of(rng, kind, keys, bound, nq).astype(np.int64)
    got, took = joinops._count_below(
        jnp.asarray(keys), jnp.asarray(probe),
        None if where == "unbounded" else jnp.int32(bound), upper,
        with_windowed=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  expected(keys, probe, bound, upper))
    want = narrow_blocks(keys, probe, np.ones(nq, bool), bound, upper)
    assert int(took) == want
    if kind in ("sorted", "shuffled") and where != "none":
        assert want == 5  # every block, the padded one too
    if kind == "mixed" and where != "none":
        assert want == 4  # all but the spread one
    if kind == "spread" and where != "none":
        assert want == 0


@pytest.mark.parametrize("garbage", [0, -5, "max"])
@pytest.mark.parametrize("kind", ["sorted", "shuffled"])
@pytest.mark.parametrize("dtype", [np.int32, np.int64],
                         ids=lambda d: d.__name__)
def test_invalid_probes_do_not_widen_a_blocks_span(
        small_blocks, dtype, kind, garbage):
    """A fifth of the probes are dead or null-keyed slots that hold
    garbage: every block stays narrow, every valid probe gets its
    bound and its `equal` bit, every other some position in range."""
    rng = np.random.default_rng([len(kind), dtype().itemsize])
    n, bound, nq = 6_000, 5_500, 2_300
    keys = clustered_keys(rng, dtype, n, bound)
    probe = probes_of(rng, kind, keys, bound, nq).astype(np.int64)
    # keep the blocks away from the low end, where 0 and -5 live
    probe = np.maximum(probe, int(keys[3_000]))
    valid = rng.random(nq) > 0.2
    held = np.iinfo(np.int64).max if garbage == "max" else garbage
    probe = np.where(valid, probe, held)
    lo, equal, took = joinops._count_below(
        jnp.asarray(keys), jnp.asarray(probe), jnp.int32(bound), False,
        with_equal=True, valid=jnp.asarray(valid), with_windowed=True)
    want = expected(keys, probe, bound, False)
    lo, equal = np.asarray(lo), np.asarray(equal)
    np.testing.assert_array_equal(lo[valid], want[valid])
    np.testing.assert_array_equal(
        equal[valid], ((want < bound)
                       & (keys[np.clip(want, 0, n - 1)] == probe))[valid])
    assert ((lo >= 0) & (lo <= bound)).all()
    assert int(took) == narrow_blocks(keys, probe, valid, bound, False) == 5
    # and without `valid` the garbage counts: blocks far from it are
    # wide
    lo, took = joinops._count_below(
        jnp.asarray(keys), jnp.asarray(probe), jnp.int32(bound), False,
        with_windowed=True)
    np.testing.assert_array_equal(np.asarray(lo), want)
    assert took.dtype == jnp.int32
    assert int(took) == narrow_blocks(
        keys, probe, np.ones(nq, bool), bound, False) < 3


@pytest.mark.parametrize("upper", [False, True], ids=["lower", "upper"])
def test_a_window_at_the_arrays_end_is_clipped_to_it(small_blocks, upper):
    """Probes of the last keys and beyond them: the window starts
    `_WINDOW_ROWS` before the array's end, not at the first probe's
    row, and a bound of `n` itself is inside it."""
    rng = np.random.default_rng(7)
    for n in (6_000, 47 * 128):  # a padded last row, and a full one
        keys = clustered_keys(rng, np.int32, n, n)
        probe = np.sort(np.concatenate([
            keys[-200:].astype(np.int64),
            rng.integers(keys[-1], keys[-1] + 50, 400)]))
        probe = np.concatenate([probe, probe])  # two blocks of 600
        got, took = joinops._count_below(
            jnp.asarray(keys), jnp.asarray(probe), jnp.int32(n), upper,
            with_windowed=True)
        np.testing.assert_array_equal(np.asarray(got),
                                      expected(keys, probe, n, upper))
        assert int(took) == 3


@pytest.mark.parametrize("with_equal", [False, True])
def test_64_bit_probes_outside_32_bit_keys_in_a_window(small_blocks,
                                                       with_equal):
    """Probes that no int32 holds lie below or above every key; a
    block that holds both ends cannot be narrow, one that holds the
    high end alone is."""
    rng = np.random.default_rng(9)
    n, bound = 6_000, 5_800
    keys = np.concatenate([
        np.sort(rng.integers(-2 ** 31, 2 ** 31 - 2, bound)),
        np.full(n - bound, joinops._ABOVE_32)]).astype(np.int32)
    high = np.sort(np.concatenate([
        keys[bound - 300:bound].astype(np.int64),
        [2 ** 31 - 1, 2 ** 31, 2 ** 40, 2 ** 63 - 1] * 53]))
    both = np.concatenate([high[:508], [-2 ** 31 - 1, -2 ** 40,
                                        -2 ** 63, -2 ** 31]])
    probe = np.concatenate([high, both])
    out = joinops._count_below(
        jnp.asarray(keys), jnp.asarray(probe), jnp.int32(bound), False,
        with_equal=with_equal, with_windowed=True)
    want = expected(keys, probe, bound, False)
    np.testing.assert_array_equal(np.asarray(out[0]), want)
    if with_equal:
        np.testing.assert_array_equal(
            np.asarray(out[1]),
            (want < bound) & (keys[np.clip(want, 0, n - 1)] == probe))
    assert int(out[-1]) == 1


def test_a_block_with_no_valid_probe_takes_a_window_too(small_blocks):
    """The end of a part that is not full: its positions mean nothing,
    and it does not pay the whole array's price for them."""
    keys = np.arange(0, 12_000, 2, dtype=np.int32)
    probe = np.arange(1_024, dtype=np.int64) + 4_000
    valid = np.arange(1_024) < 512  # the second block: none
    got, took = joinops._count_below(
        jnp.asarray(keys), jnp.asarray(probe), jnp.int32(6_000), False,
        valid=jnp.asarray(valid), with_windowed=True)
    got = np.asarray(got)
    np.testing.assert_array_equal(
        got[:512], expected(keys, probe, 6_000, False)[:512])
    assert ((got >= 0) & (got <= 6_000)).all()
    assert int(took) == 2


def test_a_nan_among_float_probes_leaves_the_block_to_the_whole_array(
        small_blocks):
    keys = np.arange(6_000, dtype=np.float64)
    probe = np.arange(1_024, dtype=np.float64) + 3_000.5
    probe[700] = np.nan
    got, took = joinops._count_below(
        jnp.asarray(keys), jnp.asarray(probe), jnp.int32(6_000), False,
        with_windowed=True)
    ok = ~np.isnan(probe)
    np.testing.assert_array_equal(
        np.asarray(got)[ok], expected(keys, probe, 6_000, False)[ok])
    assert int(took) == 1


def test_keys_no_longer_than_a_window_take_none(small_blocks):
    """Static: a bottom level of `_WINDOW_ROWS` rows or fewer has
    nothing to cut a window from."""
    keys = np.arange(WINDOW * 128, dtype=np.int32)
    probe = np.sort(np.random.default_rng(1).integers(0, 600, 1_500))

    def search(keys, probe):
        return joinops._count_below(keys, probe, None, False,
                                    with_windowed=True)

    assert "cond[" not in str(jax.make_jaxpr(search)(keys, probe))
    got, took = search(jnp.asarray(keys), jnp.asarray(probe))
    np.testing.assert_array_equal(np.asarray(got),
                                  expected(keys, probe, len(keys), False))
    assert int(took) == 0


@pytest.mark.parametrize("nq,branches", [(BLOCK, False), (BLOCK + 1, True)])
def test_only_more_probes_than_a_block_get_the_branch(small_blocks, nq,
                                                      branches):
    """Probes that fit one block are searched as they always were: no
    `cond`, no loop, the program a survivors' probe or `front_row_ids`
    had before."""
    keys = jnp.arange(6_000, dtype=jnp.int32)
    probe = jnp.arange(nq, dtype=jnp.int64)
    text = str(jax.make_jaxpr(
        lambda k, q: joinops._count_below(k, q, jnp.int32(6_000), False,
                                          with_equal=True))(keys, probe))
    assert ("cond[" in text) == branches
    assert ("scan[" in text or "while[" in text) == branches
    assert joinops.search_blocks(nq) == (2 if branches else 0)


@pytest.mark.parametrize("probes,blocks", [
    (57_344, 0), (122_880, 0), (131_072, 0), (131_073, 2),
    (3_670_016, 28), (7_864_320, 60)])
def test_search_blocks(probes, blocks):
    """The record's `searchBlocks` at the widths the cells run: the
    star's and Q12's survivors fit one block; a part of `lineitem`,
    probed at full width, is 60."""
    assert joinops.search_blocks(probes) == blocks


def test_probe_ranges_of_a_clustered_probe_side(small_blocks):
    """The expanding join's two searches (`probe_ranges`) through the
    windows: counts of a duplicate-key build side, dead probe slots
    holding zeros."""
    from spark_rapids_tpu.columnar.batch import ColumnBatch, DeviceColumn
    from spark_rapids_tpu.sqltypes import LongType, StructField, StructType

    rng = np.random.default_rng(13)
    schema = StructType([StructField("k", LongType(), True)])

    def batch(vals, rows):
        return ColumnBatch(schema, [DeviceColumn(
            LongType(), jnp.asarray(vals),
            jnp.asarray(np.arange(len(vals)) < rows))], jnp.int32(rows))

    build_keys = np.sort(rng.integers(1_000, 9_000, 6_000))
    probe = np.sort(rng.integers(900, 9_100, 2_048))
    probe[2_000:] = 0  # dead slots
    bt = joinops.build_side(batch(build_keys, 6_000), [0])
    lo, counts = joinops.probe_ranges(bt, batch(probe, 2_000), [0])
    want_lo = np.searchsorted(build_keys, probe[:2_000], side="left")
    want_hi = np.searchsorted(build_keys, probe[:2_000], side="right")
    np.testing.assert_array_equal(np.asarray(lo)[:2_000], want_lo)
    np.testing.assert_array_equal(np.asarray(counts)[:2_000],
                                  want_hi - want_lo)
    assert not np.asarray(counts)[2_000:].any()
