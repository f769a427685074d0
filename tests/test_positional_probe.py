"""A lookup join whose build side's one integer key has a stamped
range no larger than the slots it is probed from reads the build row
BY POSITION (ops/joinops.py `build_positions`, `probe_positions`;
exec/fused.py `build_table`), and an inner or semi join whose build
side sits under a filter bets on its own matches (`chain_joins`):
equal to a plain Python join for every join type, with what the run
did in `session.last_execution["join"]`."""

import collections

import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSparkSession
from spark_rapids_tpu.columnar.batch import ColumnBatch, DeviceColumn
from spark_rapids_tpu.exec import fused
from spark_rapids_tpu.ops import joinops
from spark_rapids_tpu.sqltypes import LongType, StructField, StructType

PROBE_ROWS, BUILD_ROWS = 20_000, 3_000
CAPACITY = fused.survivor_capacity(65_536)
HOWS = ["inner", "left", "left_semi", "left_anti", "existence"]
#: build keys: dense (a range of 4,096 for 65,536 probe slots: read by
#: position), sparse (a range of 2^33: searched), dup (dense, every
#: seventh key held twice), null (dense, 5% of both sides' keys null)
KEYS = ["dense", "sparse", "dup", "null"]


@pytest.fixture()
def spark():
    s = TpuSparkSession({"spark.sql.shuffle.partitions": 4})
    yield s
    s.stop()


def tables(keys: str):
    rng = np.random.default_rng([11, KEYS.index(keys)])
    bk = np.arange(BUILD_ROWS, dtype=np.int64)
    rng.shuffle(bk)  # a table of positions asks for no order
    if keys == "sparse":
        bk = bk * (2 ** 33 // BUILD_ROWS)
    if keys == "dup":
        bk[1::7] = bk[0::7][:len(bk[1::7])]
    k = rng.choice(np.concatenate([bk, bk.max() + 1 + bk[:500]]),
                   PROBE_ROWS)
    nulls = keys == "null"
    probe = pa.table({
        "k": pa.array(k, mask=rng.random(PROBE_ROWS) < 0.05
                      if nulls else None),
        "row": pa.array(np.arange(PROBE_ROWS, dtype=np.int64))})
    build = pa.table({
        "bk": pa.array(bk, mask=rng.random(BUILD_ROWS) < 0.05
                       if nulls else None),
        "bv": pa.array(np.arange(BUILD_ROWS, dtype=np.int64) * 10),
        "share": pa.array(rng.random(BUILD_ROWS))})
    return probe, build


def query(spark, probe, build, how, share=None):
    p, b = spark.createDataFrame(probe), spark.createDataFrame(build)
    if share is not None:
        b = b.filter(F.col("share") < share)
    return p.join(b, F.col("k") == F.col("bk"), how)


def plain_join(probe, build, how, share=None) -> collections.Counter:
    by_key = collections.defaultdict(list)
    for bk, bv, s in zip(*(build.column(c).to_pylist()
                           for c in ("bk", "bv", "share"))):
        if bk is not None and (share is None or s < share):
            by_key[bk].append(bv)
    out = collections.Counter()
    for k, row in zip(*(probe.column(c).to_pylist() for c in ("k", "row"))):
        matches = by_key.get(k, []) if k is not None else []
        if how == "left_semi":
            out.update([(row,)] if matches else [])
        elif how == "left_anti":
            out.update([] if matches else [(row,)])
        elif how == "existence":
            out[(row, bool(matches))] += 1
        elif how == "left" and not matches:
            out[(row, None)] += 1
        else:
            out.update((row, bv) for bv in matches)
    return out


def rows_of(table, how) -> collections.Counter:
    cols = {"left_semi": ["row"], "left_anti": ["row"],
            "existence": ["row", "exists"]}.get(how, ["row", "bv"])
    return collections.Counter(
        zip(*(table.column(c).to_pylist() for c in cols)))


@pytest.mark.parametrize("keys", KEYS)
@pytest.mark.parametrize("how", HOWS)
def test_join_by_position_equals_plain_join(spark, how, keys):
    probe, build = tables(keys)
    got = query(spark, probe, build, how).collect_arrow()
    rec = spark.last_execution
    assert rec["engine"] == "fused" and not rec["fallbacks"]
    assert rows_of(got, how) == plain_join(probe, build, how)
    (j,) = rec["join"]["joins"]
    assert j["joinType"] == how
    if keys == "dup" and how in ("inner", "left"):
        # a key held twice: the lookup lowerings lose their bet on
        # unique build keys whichever way they find the row
        assert rec["join"]["rerunReasons"] == ["uniquenessLost"]
        assert j["lowering"] == "expand"
        return
    assert rec["join"]["rerunReasons"] == []
    assert j["lowering"] == "lookup" and j["bet"] == ""
    if keys == "sparse":
        # 65,536 sorted keys are 512 rows of 128, whose last keys are 4
        # rows: two row reads under a top of 4 (joinops.search_reads)
        assert (j["probe"], j["probeSteps"], j["tableRows"]) == (
            "search", 2, 0)
    else:
        # a row of the table of 4,096 entries a slot
        assert (j["probe"], j["probeSteps"], j["tableRows"]) == (
            "position", 1, 32)
    assert j["searchedSlots"] == j["probeSlots"] == 65_536
    assert j["buildGather"] == (
        "matched" if how in ("inner", "left") else "none")
    # a probe by position sorts nothing; the search's index is one sort
    sorts = [s for s in (rec["sort"] or {"lowerings": []})["lowerings"]
             if s["program"].startswith("fused_buildprep_")]
    assert len(sorts) == (1 if keys == "sparse" else 0)


@pytest.mark.parametrize("keys", ["dense", "sparse", "null"])
@pytest.mark.parametrize("how", HOWS)
def test_a_filtered_build_side_bets_on_the_joins_matches(spark, how, keys):
    """1% of the build side passes its filter, so under 1/64 of the
    probe rows match: an inner or semi join brings them to the front of
    1,024 slots before it reads a build column. The others keep every
    probe row, or the rows WITHOUT a match, and place no bet."""
    probe, build = tables(keys)
    got = query(spark, probe, build, how, 0.01).collect_arrow()
    rec = spark.last_execution
    assert rec["engine"] == "fused" and not rec["fallbacks"]
    assert rows_of(got, how) == plain_join(probe, build, how, 0.01)
    assert rec["plan"]["pushedThroughJoin"] == 0  # the query's own filter
    (j,) = rec["join"]["joins"]
    assert rec["join"]["rerunReasons"] == []
    assert j["probe"] == ("search" if keys == "sparse" else "position")
    assert j["searchedSlots"] == j["probeSlots"] == 65_536
    if how in ("inner", "left_semi"):
        assert (j["lowering"], j["bet"]) == ("lookupSurvivors",
                                             "buildFilter")
        assert j["outputCapacity"] == CAPACITY
        # each match brought to the front read two rows of the mask's
        # prefix sum for its row id
        assert j["rowIdReads"] == 2
    else:
        assert (j["lowering"], j["bet"]) == ("lookup", "")
        assert j["outputCapacity"] == 65_536
        assert "rowIdReads" not in j
    # the filter went into the build side's own program: no program
    # that only compacts the rows it keeps
    assert j["buildRows"] == sum(
        1 for s in build.column("share").to_pylist() if s < 0.01)


@pytest.mark.parametrize("how", ["inner", "left_semi"])
def test_a_lost_bet_on_the_matches_reruns_once_and_is_remembered(spark, how):
    probe, build = tables("dense")
    want = plain_join(probe, build, how, 0.5)
    first = query(spark, probe, build, how, 0.5).collect_arrow()
    assert spark.last_execution["join"]["rerunReasons"] == \
        ["survivorOverflow"]
    assert rows_of(first, how) == want
    assert len(spark.fused_wide_joins) == 1
    again = query(spark, probe, build, how, 0.5).collect_arrow()
    rec = spark.last_execution
    assert rec["join"]["runs"] == 1 and rec["join"]["rerunReasons"] == []
    (j,) = rec["join"]["joins"]
    assert (j["lowering"], j["bet"], j["probe"]) == ("lookup", "",
                                                     "position")
    assert rec["compile"]["programsCompiled"] == 0
    assert rows_of(again, how) == want
    # another filter over the same tables is another bet
    query(spark, probe, build, how, 0.01).collect_arrow()
    assert spark.last_execution["join"]["joins"][0]["bet"] == "buildFilter"


#: entries of the largest table of positions read as rows of 128
ROW_GATE = joinops._POSITION_ROWS * joinops._LANES


def probe_batch(keys, nulls, rows):
    schema = StructType([StructField("k", LongType(), True)])
    return ColumnBatch(schema, [DeviceColumn(
        LongType(), jnp.asarray(keys), jnp.asarray(~nulls))],
        jnp.int32(rows))


def plain_positions(table, lo, keys, nulls, rows):
    """(row, matched, dup) of each probe slot, from numpy."""
    at = keys - lo
    inside = (~nulls & (np.arange(len(keys)) < rows)
              & (at >= 0) & (at < len(table)))
    held = table[np.clip(at, 0, len(table) - 1)]
    matched = inside & (held != -1)
    row = np.maximum(np.where(held < -1, -2 - held, held), 0)
    return row, matched, matched & (held < -1)


@pytest.mark.parametrize("entries", [1, 127, 128, 129, ROW_GATE,
                                     ROW_GATE + 1])
def test_a_table_read_by_rows_gives_what_its_entries_give(entries,
                                                          monkeypatch):
    """`probe_positions` reads a table of at most `_POSITION_ROWS` rows
    of 128 entries a row a slot and keeps one lane, a larger one an
    entry a slot: both give every probe slot the same (row, matched,
    dup) — absent (-1) and twice-held (-2 - row) entries, keys below
    and above the stamped range, null-keyed and dead slots."""
    rng = np.random.default_rng(entries)
    lo, slots, rows = 7_000, 20_000, 19_000
    ids = rng.integers(0, 102_000, entries)
    kind = rng.random(entries)
    table = np.where(kind < 0.2, -1, np.where(kind < 0.3, -2 - ids, ids)
                     ).astype(np.int32)
    table[0] = -2 - ids[0]  # held twice, whatever the size
    keys = rng.integers(lo - 40, lo + entries + 40, slots)
    keys[:4] = [-2 ** 40, 2 ** 40, lo - 1, lo + entries]
    nulls = rng.random(slots) < 0.05
    build = joinops.BuildPositions(None, jnp.asarray(table),
                                   jnp.int64(lo), jnp.int32(0),
                                   jnp.int32(0))
    probe = probe_batch(keys, nulls, rows)
    assert (joinops.table_rows(entries) > 0) == (entries <= ROW_GATE)
    by_rows = joinops.probe_positions(build, probe, [0])
    monkeypatch.setattr(joinops, "_POSITION_ROWS", 0)  # entries alone
    by_entries = joinops.probe_positions(build, probe, [0])
    for got, one in zip(by_rows, by_entries):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(one))
    # and both are right: a row is what a match reads
    row, matched, dup = plain_positions(table, lo, keys, nulls, rows)
    np.testing.assert_array_equal(np.asarray(by_rows[1]), matched)
    np.testing.assert_array_equal(np.asarray(by_rows[2]), dup)
    np.testing.assert_array_equal(np.asarray(by_rows[0])[matched],
                                  row[matched])
    assert matched.any() and dup.any() and (~matched[:rows]).any()


@pytest.mark.parametrize("read", ["rows", "entries"])
def test_the_join_record_says_how_the_table_was_read(spark, read,
                                                     monkeypatch):
    """`tableRows`: the rows of 128 entries a probe by position read its
    table as (the dense key's 4,096 entries: 32), or 0 where the table
    has more rows than `_POSITION_ROWS` and each slot read its entry."""
    if read == "entries":
        monkeypatch.setattr(joinops, "_POSITION_ROWS", 31)
    probe, build = tables("dense")
    got = query(spark, probe, build, "inner").collect_arrow()
    rec = spark.last_execution
    assert rows_of(got, "inner") == plain_join(probe, build, "inner")
    (j,) = rec["join"]["joins"]
    assert (j["probe"], j["probeSteps"]) == ("position", 1)
    assert j["tableRows"] == (32 if read == "rows" else 0)


def two_dimensions(seed=5):
    rng = np.random.default_rng(seed)
    fact = pa.table({
        "a": pa.array(rng.integers(0, 2_000, PROBE_ROWS)),
        "b": pa.array(rng.integers(0, 1_000, PROBE_ROWS)),
        "v": pa.array(rng.integers(0, 100, PROBE_ROWS).astype(np.float64))})
    dim_a = pa.table({"ak": pa.array(np.arange(2_000, dtype=np.int64)),
                      "a_attr": pa.array(np.arange(2_000) % 10),
                      "a_group": pa.array(np.arange(2_000) % 3)})
    dim_b = pa.table({"bk": pa.array(np.arange(1_000, dtype=np.int64)),
                      "b_attr": pa.array(np.arange(1_000) % 200),
                      "b_group": pa.array(np.arange(1_000) % 4)})
    return fact, dim_a, dim_b


def star(spark, fact, dim_a, dim_b):
    f, a, b = (spark.createDataFrame(t) for t in (fact, dim_a, dim_b))
    return (f.join(a, F.col("a") == F.col("ak"))
            .join(b, F.col("b") == F.col("bk"))
            .where((F.col("a_attr") < 5) & (F.col("b_attr") == 7))
            .groupBy("a_group", "b_group")
            .agg(F.sum("v").alias("total"))
            .orderBy("a_group", "b_group"))


def test_a_join_that_lost_its_bet_yields_to_the_next_that_still_bets(spark):
    """The first dimension's filter keeps half of the fact's rows (a
    lost bet), the second's 0.5%: from the second run on the selective
    join goes first and the other probes, and bets, over its survivors;
    the columns come out in the plan's order."""
    fact, dim_a, dim_b = two_dimensions()
    want = collections.Counter()
    for a, b, v in zip(*(fact.column(c).to_pylist() for c in "abv")):
        if a % 10 < 5 and b % 200 == 7:
            want[(a % 3, b % 4)] += v
    for run in range(2):
        got = star(spark, fact, dim_a, dim_b).collect_arrow()
        rec = spark.last_execution
        assert rec["engine"] == "fused" and not rec["fallbacks"]
        assert {(r["a_group"], r["b_group"]): r["total"]
                for r in got.to_pylist()} == dict(want)
        assert rec["plan"]["pushedThroughJoin"] == 3
        joins = rec["join"]["joins"]
        assert rec["join"]["rerunReasons"] == (
            ["survivorOverflow"] if run == 0 else [])
        # the settled order: the selective join over the part's slots,
        # then the other one over its 1,024 survivors' (too few, at
        # this size, for a bet of its own: at 4,096 and more it is a
        # new bet, under the moved join's own key)
        assert [j["buildRows"] for j in joins] == [5, 1_000]
        assert (joins[0]["probeSlots"], joins[0]["outputCapacity"]) == (
            65_536, CAPACITY)
        assert joins[0]["bet"] == "buildFilter" and joins[1]["bet"] == ""
    assert rec["compile"]["programsCompiled"] == 0
