"""A lookup join on several plain integer keys, each with a stamped
range, probes as ONE packed key (ops/joinops.py `KeyPacking`,
`packed_key`; exec/joins.py `_build_positions`, `_build_index`;
exec/fused.py `build_table`): by position where the product of the
ranges passes the position gate, a search of one packed array where it
fits 63 bits, the tuple search only where it fits neither. A probe
value outside any one key's range matches nothing, so two keys never
alias; nulls match nothing; a duplicated pair loses the bet on unique
build keys and expands. Packed, tuple search and numpy agree."""

import collections

import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSparkSession
from spark_rapids_tpu.columnar.batch import ColumnBatch, DeviceColumn
from spark_rapids_tpu.exec.fused import _quantize_range
from spark_rapids_tpu.ops import joinops
from spark_rapids_tpu.sqltypes import (
    IntegerType,
    LongType,
    StructField,
    StructType,
)

BUILD_ROWS, PROBE_ROWS = 2_000, 6_000
#: probe rows of the engine's tests: more slots than the position
#: layout's table has entries (exec/fused.py `build_table`)
CHILD_ROWS = 20_000


# --- the kernels: packed by position, packed search, tuple search ---

def batch_of(cols, rows=None, stamped=True):
    """A ColumnBatch of (name, numpy values, nulls or None) columns,
    each stamped with its values' quantized range, as an upload does."""
    fields, out = [], []
    for name, values, nulls in cols:
        kind = IntegerType() if values.dtype == np.int32 else LongType()
        fields.append(StructField(name, kind, True))
        valid = np.ones(len(values), bool) if nulls is None else ~nulls
        vr = (_quantize_range(int(values.min()), int(values.max()))
              if stamped else None)
        out.append(DeviceColumn(kind, jnp.asarray(values),
                                jnp.asarray(valid), vrange=vr))
    n = len(cols[0][1]) if rows is None else rows
    return ColumnBatch(StructType(fields), out, jnp.int32(n))


def key_sets(case: str, seed: int = 3):
    """-> (build key columns, probe key columns, live probe rows): the
    build side's tuples unique (but where `case` says otherwise), the
    probe side's drawn from them and from tuples not held."""
    rng = np.random.default_rng([seed, len(case)])
    n_keys = 3 if case == "three_keys" else 2
    spans = {"negative_lo": [(-300, 40), (-9, 5)],
             "mixed_widths": [(0, 900), (0, 60)],
             "three_keys": [(1, 30), (0, 24), (5, 12)]}.get(
        case, [(1, 1_000), (0, 24)])[:n_keys]
    grid = np.stack(np.meshgrid(*[np.arange(lo, hi + 1) for lo, hi in spans],
                                indexing="ij"), -1).reshape(-1, n_keys)
    build = grid[rng.choice(len(grid), BUILD_ROWS, replace=False)]
    if case == "dup_pair":
        build[7] = build[3]
    held = build[rng.integers(0, BUILD_ROWS, PROBE_ROWS // 2)]
    other = np.stack([rng.integers(lo - 3, hi + 4, PROBE_ROWS // 2)
                      for lo, hi in spans], -1)
    probe = np.concatenate([held, other])
    if case == "outside_one_range":
        # (k - 1, hi + 1), hi the second key's STAMPED high end, would
        # pack as (k, lo) by a naive radix: each such pair below is one
        # the build side holds
        lo2, hi2 = _quantize_range(int(build[:, 1].min()),
                                   int(build[:, 1].max()))
        held_next = build[(build[:, 1] == lo2)
                          & (build[:, 0] > build[:, 0].min())]
        probe[:len(held_next), 0] = held_next[:, 0] - 1
        probe[:len(held_next), 1] = hi2 + 1
        assert len(held_next) > 10
    dtypes = [np.int32 if case == "mixed_widths" and i == 1 else np.int64
              for i in range(n_keys)]
    b_nulls = p_nulls = None
    if case == "nulls":
        b_nulls = rng.random(BUILD_ROWS) < 0.05
        p_nulls = rng.random(PROBE_ROWS) < 0.05
    bcols = [(f"b{i}", build[:, i].astype(dtypes[i]),
              b_nulls if i == 0 else None) for i in range(n_keys)]
    pcols = [(f"p{i}", probe[:, i].astype(dtypes[i]),
              p_nulls if i == 1 else None) for i in range(n_keys)]
    return bcols, pcols, PROBE_ROWS - 17


def numpy_rows(bcols, pcols, rows):
    """The build row each probe row matches (unique keys), and whether
    it matches: a plain dictionary of tuples."""
    def tuples(cols):
        vals = list(zip(*(c[1].tolist() for c in cols)))
        nulls = np.zeros(len(vals), bool)
        for c in cols:
            if c[2] is not None:
                nulls |= c[2]
        return vals, nulls

    bvals, bnull = tuples(bcols)
    where = {t: i for i, t in enumerate(bvals) if not bnull[i]}
    pvals, pnull = tuples(pcols)
    row = np.array([where.get(t, -1) for t in pvals])
    matched = (row >= 0) & ~pnull & (np.arange(len(pvals)) < rows)
    return row, matched


CASES = ["two_keys", "three_keys", "negative_lo", "mixed_widths", "nulls",
         "outside_one_range"]


@pytest.mark.parametrize("case", CASES)
def test_packed_lookup_equals_tuple_search_and_numpy(case):
    bcols, pcols, rows = key_sets(case)
    build, probe = batch_of(bcols), batch_of(pcols, rows)
    keys = list(range(len(bcols)))
    packed_batch, packed_keys, packing = joinops.packed_key(build, keys)
    assert packing is not None and packed_keys == [len(bcols)]
    assert packing.entries == np.prod([hi - lo + 1 for lo, hi in
                                       packing.ranges])
    want_row, want = numpy_rows(bcols, pcols, rows)

    # by position: one table of the product of the ranges
    pos = joinops.build_positions(packed_batch, packed_keys)._replace(
        packing=packing)
    assert pos.table.shape[0] == packing.entries
    at, matched, dup = (np.asarray(a) for a in
                        joinops.probe_positions(pos, probe, keys))
    np.testing.assert_array_equal(matched, want)
    np.testing.assert_array_equal(at[want], want_row[want])
    assert not dup.any()

    # a search of ONE packed array, and the tuple search of the keys
    searched = {}
    for name, (b, k, p) in {"packed": (packed_batch, packed_keys, packing),
                            "tuple": (build, keys, None)}.items():
        index = joinops.build_index(b, k)._replace(packing=p)
        assert len(index.keys) == (1 if p else len(keys))
        lo, ok = (np.asarray(a) for a in
                  joinops.probe_matched(index, probe, keys))
        np.testing.assert_array_equal(ok, want, err_msg=name)
        perm = np.asarray(index.perm)
        np.testing.assert_array_equal(perm[lo[ok]], want_row[ok],
                                      err_msg=name)
        again = np.asarray(joinops.second_match(
            index, probe, keys, jnp.asarray(lo), jnp.asarray(ok)))
        assert not again.any(), name
        searched[name] = ok
    np.testing.assert_array_equal(searched["packed"], searched["tuple"])
    assert want.any() and not want.all()


def test_a_duplicated_pair_is_marked_in_the_table_and_after_the_match():
    bcols, pcols, rows = key_sets("dup_pair")
    build, probe = batch_of(bcols), batch_of(pcols, rows)
    packed_batch, k, packing = joinops.packed_key(build, [0, 1])
    pos = joinops.build_positions(packed_batch, k)._replace(packing=packing)
    _, matched, dup = (np.asarray(a) for a in
                       joinops.probe_positions(pos, probe, [0, 1]))
    twice = np.array([tuple(r) == (bcols[0][1][3], bcols[1][1][3])
                      for r in zip(pcols[0][1], pcols[1][1])])
    np.testing.assert_array_equal(dup, matched & twice)
    assert dup.any()
    index = joinops.build_index(packed_batch, k)._replace(packing=packing)
    lo, ok = joinops.probe_matched(index, probe, [0, 1])
    again = np.asarray(joinops.second_match(index, probe, [0, 1], lo, ok))
    np.testing.assert_array_equal(again, np.asarray(ok) & twice)


def test_what_packs_and_what_keeps_the_tuple_search():
    rng = np.random.default_rng(9)
    small = rng.integers(0, 1_000, 500)
    wide = rng.integers(0, 2 ** 40, 500)
    assert joinops.key_packing(batch_of([("a", small, None)]), [0]) is None
    two = joinops.key_packing(
        batch_of([("a", small, None), ("b", small, None)]), [0, 1])
    assert two.ranges == ((0, 1_023), (0, 1_023)) and two.bits == 20
    assert two.pack([jnp.asarray([1_023]), jnp.asarray([1])])[0].dtype \
        == jnp.int32
    wider = joinops.key_packing(
        batch_of([("a", wide, None), ("b", small, None)]), [0, 1])
    assert wider.bits == 50
    assert wider.pack([jnp.asarray([5]), jnp.asarray([1])])[0].dtype \
        == jnp.int64
    # 40 + 40 bits do not fit one 64-bit key; an unstamped column has
    # no range to pack by
    assert joinops.key_packing(
        batch_of([("a", wide, None), ("b", wide, None)]), [0, 1]) is None
    assert joinops.key_packing(
        batch_of([("a", small, None), ("b", small, None)], stamped=False),
        [0, 1]) is None


# --- the engine: what the join record says, against a plain join ---

@pytest.fixture()
def spark():
    s = TpuSparkSession({"spark.sql.shuffle.partitions": 4})
    yield s
    s.stop()


def two_key_tables(first_span, second_span, dup=False, nulls=False,
                   seed=21):
    """A child whose (ck1, ck2) finds a parent for about half its rows,
    and a parent keyed on (pk1, pk2), unique but where `dup` holds one
    pair twice."""
    rng = np.random.default_rng(seed)
    pk1 = rng.integers(0, first_span, BUILD_ROWS)
    pk2 = rng.integers(0, second_span, BUILD_ROWS)
    pairs = np.unique(np.stack([pk1, pk2], 1), axis=0)
    rng.shuffle(pairs)
    if dup:
        pairs[5] = pairs[2]
    parent = pa.table({"pk1": pa.array(pairs[:, 0]),
                       "pk2": pa.array(pairs[:, 1]),
                       "pv": pa.array(np.arange(len(pairs)) * 10)})
    pick = pairs[rng.integers(0, len(pairs), CHILD_ROWS)]
    miss = rng.random(CHILD_ROWS) < 0.5
    pick[miss, 1] = rng.integers(0, second_span, miss.sum())
    child = pa.table({
        "ck1": pa.array(pick[:, 0]),
        "ck2": pa.array(pick[:, 1], mask=rng.random(CHILD_ROWS) < 0.05
                        if nulls else None),
        "row": pa.array(np.arange(CHILD_ROWS))})
    return child, parent


def joined(spark, child, parent):
    c, p = spark.createDataFrame(child), spark.createDataFrame(parent)
    return c.join(p, (F.col("ck1") == F.col("pk1"))
                  & (F.col("ck2") == F.col("pk2"))).select("row", "pv")


def plain(child, parent) -> collections.Counter:
    by_pair = collections.defaultdict(list)
    for a, b, v in zip(*(parent.column(n).to_pylist()
                         for n in ("pk1", "pk2", "pv"))):
        by_pair[(a, b)].append(v)
    out = collections.Counter()
    for a, b, r in zip(*(child.column(n).to_pylist()
                         for n in ("ck1", "ck2", "row"))):
        if b is not None:
            out.update((r, v) for v in by_pair.get((a, b), []))
    return out


def rows(table) -> collections.Counter:
    return collections.Counter(zip(table.column("row").to_pylist(),
                                   table.column("pv").to_pylist()))


# (spans of the two keys) -> (probe, keyArrays, packedBits)
LAYOUTS = {
    "position": ((200, 25), "position", 1, 13),
    "one_array": ((2 ** 21, 2 ** 12), "search", 1, 33),
    "tuple": ((2 ** 40, 2 ** 30), "search", 2, 0),
}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("nulls", [False, True])
def test_a_two_key_join_probes_as_one_packed_key_where_it_fits(
        spark, layout, nulls):
    spans, probe, arrays, bits = LAYOUTS[layout]
    child, parent = two_key_tables(*spans, nulls=nulls)
    got = joined(spark, child, parent).collect_arrow()
    rec = spark.last_execution
    assert rec["engine"] == "fused" and not rec["fallbacks"]
    assert rows(got) == plain(child, parent)
    (j,) = rec["join"]["joins"]
    assert j["lowering"] == "lookup" and rec["join"]["runs"] == 1
    assert (j["probe"], j["keyColumns"], j["keyArrays"], j["packedBits"]) \
        == (probe, 2, arrays, bits)
    slots = j["buildSlots"]
    assert j["probeSteps"] == (
        1 if probe == "position"
        else joinops.search_reads(slots) if arrays == 1
        else slots.bit_length() * 2)


# (spans of the two keys) -> (packedBits, keyBytes): 29 bits search a
# 32-bit key, 33 the 64-bit one a TPU v5e emulates
LEFT_LAYOUTS = {"int32_key": ((2 ** 17, 2 ** 12), 29, 4),
                "int64_key": ((2 ** 21, 2 ** 12), 33, 8)}


@pytest.mark.parametrize("layout", sorted(LEFT_LAYOUTS))
def test_a_left_join_on_a_packed_key_keeps_every_probe_row(spark, layout):
    """TPC-DS q5's `web_returns LEFT OUTER JOIN web_sales` on (item,
    order number): a row that matches nothing keeps its place, its
    build columns null, as a plain numpy join of the pairs gives."""
    spans, bits, width = LEFT_LAYOUTS[layout]
    child, parent = two_key_tables(*spans)
    c, p = spark.createDataFrame(child), spark.createDataFrame(parent)
    got = c.join(p, (F.col("ck1") == F.col("pk1"))
                 & (F.col("ck2") == F.col("pk2")), "left") \
        .select("row", "pv").collect_arrow()
    rec = spark.last_execution
    assert rec["engine"] == "fused" and not rec["fallbacks"]
    (j,) = rec["join"]["joins"]
    assert (j["lowering"], j["joinType"], j["probe"]) == \
        ("lookup", "left", "search")
    assert (j["keyArrays"], j["packedBits"], j["keyBytes"]) == \
        (1, bits, width)
    span2 = spans[1]
    pk = (parent.column("pk1").to_numpy() * span2
          + parent.column("pk2").to_numpy())
    ck = (child.column("ck1").to_numpy() * span2
          + child.column("ck2").to_numpy())
    order = np.argsort(pk)
    at = np.clip(np.searchsorted(pk[order], ck), 0, len(pk) - 1)
    hit = pk[order][at] == ck
    pv = parent.column("pv").to_numpy()[order][at]
    want = dict(zip(child.column("row").to_pylist(),
                    [int(v) if h else None for v, h in zip(pv, hit)]))
    assert 0.3 < hit.mean() < 0.7  # rows that match and rows that do not
    assert got.num_rows == child.num_rows
    assert dict(zip(got.column("row").to_pylist(),
                    got.column("pv").to_pylist())) == want


def test_a_duplicated_pair_expands_and_stays_right(spark):
    child, parent = two_key_tables(200, 25, dup=True)
    got = joined(spark, child, parent).collect_arrow()
    rec = spark.last_execution["join"]
    assert rec["rerunReasons"] == ["uniquenessLost"] and rec["runs"] == 2
    (j,) = rec["joins"]
    assert j["lowering"] == "expand"
    assert rows(got) == plain(child, parent)
    # the pair held twice gives its probe rows two rows each
    per_row = collections.Counter(got.column("row").to_pylist())
    assert max(per_row.values()) == 2


# --- what a lookup join reads of its build side ---

@pytest.mark.parametrize("by_rows", [True, False])
def test_build_columns_read_as_one_32_bit_array_equal_a_gather(
        by_rows, monkeypatch):
    """`DeviceColumn.gather` with the lookup join's reader
    (`joinops.read_entries`) reads an integer column with a stamped
    range as its offset from the range's low end, -1 for null, in one
    32-bit array (by rows of 128 where the column has at most
    `_POSITION_ROWS` of them): the same values and validity as the
    plain gather; a column without a range is gathered as it is."""
    if not by_rows:
        monkeypatch.setattr(joinops, "_POSITION_ROWS", 2)
    rng = np.random.default_rng(4)
    n = 3_000
    nulls = rng.random(n) < 0.1
    cols = [("wide", rng.integers(-2 ** 40, -2 ** 40 + 5_000, n), nulls),
            ("narrow", rng.integers(-30_000, 30_000, n).astype(np.int32),
             None),
            ("date", rng.integers(8_000, 10_000, n).astype(np.int32),
             nulls)]
    batch = batch_of(cols)
    price = DeviceColumn(LongType(), jnp.asarray(rng.integers(0, 9, n)),
                         jnp.asarray(~nulls))  # no stamped range
    rows = jnp.asarray(rng.integers(0, n, 20_000).astype(np.int32))
    assert (joinops.table_rows(n) > 0) == by_rows
    taken = []

    def take(array, at):
        taken.append(array.dtype)
        return joinops.read_entries(array, at)

    for col in list(batch.columns) + [price]:
        got = col.gather(rows, take)
        want = col.gather(rows)
        valid = np.asarray(want.validity)
        np.testing.assert_array_equal(np.asarray(got.validity), valid)
        np.testing.assert_array_equal(np.asarray(got.data)[valid],
                                      np.asarray(want.data)[valid])
        assert got.data.dtype == col.data.dtype
        assert got.vrange == col.vrange
    # a column whose stamped range spans fewer than 2^31 values went
    # through the reader, one array each; the others were gathered as
    # they are
    spans = [c.vrange[1] - c.vrange[0] for c in batch.columns]
    assert taken == [jnp.int32] * sum(s < 2 ** 31 - 1 for s in spans) \
        and len(taken) == 2


@pytest.mark.parametrize("dup", ["none", "unprobed", "probed"])
def test_a_full_width_search_bets_on_an_index_without_a_key_twice(
        spark, dup):
    """A search at the probe's full width, with no bet that brings its
    matches to the front, reads the key after each match only where
    the index holds a key twice (`joinops.second_match`): a key held
    twice that no probe row matches leaves the join a lookup, one that
    a probe row matches loses the bet on unique build keys and expands;
    the answer is the plain join's either way."""
    rng = np.random.default_rng(8)
    keys = rng.choice(2 ** 40, BUILD_ROWS, replace=False)
    if dup != "none":
        keys[1] = keys[0]
    parent = pa.table({"pk": pa.array(keys),
                       "pv": pa.array(np.arange(BUILD_ROWS) * 10)})
    held = keys if dup == "probed" else keys[2:]
    probe = held[rng.integers(0, len(held), CHILD_ROWS)]
    child = pa.table({"ck": pa.array(probe),
                      "row": pa.array(np.arange(CHILD_ROWS))})
    got = spark.createDataFrame(child).join(
        spark.createDataFrame(parent), F.col("ck") == F.col("pk")
    ).select("row", "pv").collect_arrow()
    rec = spark.last_execution["join"]
    (j,) = rec["joins"]
    lost = dup == "probed"
    assert (j["lowering"] == "expand") == lost
    assert rec["rerunReasons"] == (["uniquenessLost"] if lost else [])
    assert rec["runs"] == (2 if lost else 1)
    if not lost:  # a search of 2^40 keys' range, at full width
        assert (j["probe"], j["bet"]) == ("search", "")
    where = collections.defaultdict(list)
    for k, v in zip(keys.tolist(), parent.column("pv").to_pylist()):
        where[k].append(v)
    assert rows(got) == collections.Counter(
        (r, v) for r, k in zip(range(CHILD_ROWS), probe.tolist())
        for v in where[k])
