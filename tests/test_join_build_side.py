"""What a join is called and how it is written no longer decide how it
runs on one chip (plan/overrides.py `_convert_join`, plan/logical.py
`estimate_rows`, exec/fused.py `_is_lookup_join`, `build_table`,
`chain_joins`): a shuffled hash join with unique build keys takes the
lookup lowering, an inner join builds the side with fewer rows whatever
the order of writing, a build side may hold a join, lost bets of every
kind are remembered for the session, and a final aggregate may find
more groups than `fusedExec.groupCapacity` in one run. Every answer
against the CPU oracle session (exec/cpu_eval.py)."""

import collections

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSparkSession
from spark_rapids_tpu.columnar.batch import ColumnBatch, DeviceColumn
from spark_rapids_tpu.exec import fused
from spark_rapids_tpu.exec import joins as J
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.sqltypes import StructField, StructType
from spark_rapids_tpu.sqltypes.datatypes import DateType, integer, long

SHUFFLED = {"spark.sql.shuffle.partitions": 4,
            "spark.sql.autoBroadcastJoinThreshold": -1}


@pytest.fixture()
def spark():
    s = TpuSparkSession(dict(SHUFFLED))
    yield s
    s.stop()


@pytest.fixture(scope="module")
def oracle():
    s = TpuSparkSession(dict(SHUFFLED,
                             **{"spark.rapids.tpu.test.cpuOracle": True}))
    yield s
    s.stop()


def rows(table) -> collections.Counter:
    return collections.Counter(zip(*(table.column(c).to_pylist()
                                     for c in table.column_names)))


def parent_child(dups=False, seed=5):
    """A parent with unique keys (or every ninth key twice) and a
    child whose foreign key finds a parent for two rows in three."""
    rng = np.random.default_rng(seed)
    pk = np.arange(0, 6_000, 2, dtype=np.int64)
    rng.shuffle(pk)
    if dups:
        pk[1::9] = pk[0::9][:len(pk[1::9])]
    parent = pa.table({"pk": pa.array(pk),
                       "pv": pa.array(np.arange(len(pk), dtype=np.int64)),
                       "share": pa.array(rng.random(len(pk)))})
    fk = rng.integers(0, 9_000, 20_000)
    child = pa.table({"fk": pa.array(fk, pa.int64(),
                                     mask=rng.random(20_000) < 0.03),
                      "cv": pa.array(np.arange(20_000, dtype=np.int64))})
    return parent, child


def joins_of(spark):
    return spark.last_execution["join"]["joins"]


def find(node, cls):
    out = [node] if isinstance(node, cls) else []
    for c in node.children:
        out += find(c, cls)
    return out


# --- the label ---

def test_a_shuffled_join_with_unique_build_keys_takes_the_lookup_lowering(
        spark, oracle):
    parent, child = parent_child()

    def q(s):
        return s.createDataFrame(child).join(
            s.createDataFrame(parent), F.col("fk") == F.col("pk"))

    phys, _ = q(spark)._physical()
    assert find(phys, J.TpuShuffledHashJoinExec)
    assert not find(phys, J.TpuBroadcastHashJoinExec)
    got = q(spark).collect_arrow()
    rec = spark.last_execution
    assert rec["engine"] == "fused" and not rec["fallbacks"]
    (j,) = joins_of(spark)
    assert (j["planned"], j["lowering"]) == ("shuffled", "lookup")
    assert j["buildSide"] == "right" and j["chosenBy"] == "rows"
    assert j["outputCapacity"] == j["probeSlots"]  # no expansion buffer
    assert rec["join"]["runs"] == 1
    assert rows(got) == rows(q(oracle).collect_arrow())


def test_one_with_duplicate_build_keys_still_expands_and_says_from_what(
        spark, oracle):
    parent, child = parent_child(dups=True)

    def q(s):
        return s.createDataFrame(child).join(
            s.createDataFrame(parent), F.col("fk") == F.col("pk"))

    got = q(spark).collect_arrow()
    rec = spark.last_execution["join"]
    assert rec["rerunReasons"] == ["uniquenessLost"] and rec["runs"] == 2
    (j,) = rec["joins"]
    assert (j["planned"], j["lowering"]) == ("shuffled", "expand")
    assert j["capacityFrom"] == "factor"
    assert rows(got) == rows(q(oracle).collect_arrow())
    # the bet is the session's to remember: not placed again
    assert rows(q(spark).collect_arrow()) == rows(got)
    again = spark.last_execution["join"]
    assert again["runs"] == 1 and again["rerunReasons"] == []
    assert again["joins"][0]["lowering"] == "expand"
    assert any(k[-1] == "unique" for k in spark.fused_wide_joins
               if isinstance(k[-1], str))


# --- the order of writing ---

def key_of(phys):
    from spark_rapids_tpu.parallel.plan_compiler import _plan_key

    (join,) = find(phys, J._DeviceJoinBase)
    return _plan_key(join)


def test_both_orders_of_writing_give_one_physical_join_and_one_answer(
        spark, oracle):
    parent, child = parent_child()

    def q(s, child_first):
        c, p = s.createDataFrame(child), s.createDataFrame(parent)
        on = F.col("fk") == F.col("pk")
        df = c.join(p, on) if child_first else p.join(c, on)
        return df.select("cv", "pv", "fk")

    a, _ = q(spark, True)._physical()
    b, _ = q(spark, False)._physical()
    assert key_of(a) == key_of(b)  # the parent is built either way
    (ja,), (jb,) = find(a, J._DeviceJoinBase), find(b, J._DeviceJoinBase)
    assert (ja.build_side, jb.build_side) == ("right", "left")
    assert ja.chosen_by == jb.chosen_by == "rows"
    got = q(spark, False).collect_arrow()
    assert spark.last_execution["plan"]["buildSidesSwapped"] == 1
    (j,) = joins_of(spark)
    assert (j["lowering"], j["buildSide"], j["chosenBy"]) == (
        "lookup", "left", "rows")
    assert j["buildRows"] == parent.num_rows
    want = rows(q(oracle, True).collect_arrow())
    assert rows(got) == want == rows(q(spark, True).collect_arrow())
    assert spark.last_execution["plan"]["buildSidesSwapped"] == 0


@pytest.mark.parametrize("how", ["inner", "left", "left_semi", "left_anti",
                                 "right", "full"])
def test_a_small_left_side_keeps_column_order_and_null_semantics(
        spark, oracle, how):
    parent, child = parent_child()

    def q(s):  # the SMALL side written first: only `inner` may swap
        return s.createDataFrame(parent).join(
            s.createDataFrame(child), F.col("pk") == F.col("fk"), how)

    got = q(spark).collect_arrow()
    want = q(oracle).collect_arrow()
    assert got.column_names == want.column_names
    assert got.schema.types == want.schema.types
    assert rows(got) == rows(want)
    swapped = spark.last_execution["plan"]["buildSidesSwapped"]
    assert swapped == (1 if how == "inner" else 0)
    if how in ("left", "full"):  # a parent without a child: nulls right
        assert got.column("cv").null_count > 0
    if how in ("right", "full"):  # a child without a parent: nulls left
        assert got.column("pv").null_count > 0


def test_a_filter_on_the_written_build_side_alone_keeps_the_order(spark):
    """20,000 rows against 3,000 under a filter nobody can size: the
    filtered side may hold a hundred rows, and is built as written."""
    parent, child = parent_child()
    big = pa.concat_tables([parent] * 8)  # 24,000 > 20,000 rows
    df = spark.createDataFrame(child).join(
        spark.createDataFrame(big).filter(F.col("share") < 0.01),
        F.col("fk") == F.col("pk"))
    phys, _ = df._physical()
    (j,) = find(phys, J._DeviceJoinBase)
    assert (j.build_side, j.chosen_by) == ("right", "written")


def test_rows_come_from_footers_tables_and_survive_filters_and_joins(
        spark, tmp_path):
    parent, child = parent_child()
    for name, table in (("parent", parent), ("child", child)):
        (tmp_path / name).mkdir()
        pq.write_table(table.slice(0, 1_000), tmp_path / name / "a.parquet")
        pq.write_table(table.slice(1_000), tmp_path / name / "b.parquet")
    p = spark.read.parquet(str(tmp_path / "parent"))
    c = spark.read.parquet(str(tmp_path / "child"))
    assert L.estimate_rows(p._plan) == 3_000
    assert L.estimate_rows(c.filter(F.col("cv") > 5)._plan) == 20_000
    joined = p.join(c, F.col("pk") == F.col("fk"))
    assert L.estimate_rows(joined._plan) == 20_000  # the FK side's
    assert L.estimate_rows(joined.limit(7)._plan) == 7
    assert L.estimate_size_bytes(joined._plan) is None
    assert L.estimate_rows(spark.createDataFrame(child)._plan) == 20_000
    assert not L.rows_are_a_bound(p._plan)
    assert L.rows_are_a_bound(c.filter(F.col("cv") > 5)._plan)
    cached = c.cache(storage="device")
    assert L.estimate_rows(spark.cache_manager.substitute(cached._plan)) \
        == 20_000


# --- a build side that holds a join ---

def three_tables():
    rng = np.random.default_rng(17)
    grand = pa.table({
        "gk": pa.array(np.arange(1, 401, dtype=np.int64)),
        "seg": pa.array(rng.integers(0, 5, 400), pa.int32())})
    pk = np.arange(0, 8_000, 2, dtype=np.int64)
    parent = pa.table({
        "pk": pa.array(pk),
        "pg": pa.array(rng.integers(1, 601, len(pk)), pa.int64()),
        "pday": pa.array(rng.integers(0, 1_000, len(pk)), pa.int32())})
    child = pa.table({
        "fk": pa.array(rng.integers(0, 9_000, 30_000), pa.int64()),
        "cday": pa.array(rng.integers(0, 1_000, 30_000), pa.int32()),
        "cv": pa.array(rng.random(30_000))})
    return grand, parent, child


def spec_order(s, tables):
    """grandparent, parent, child in the FROM list's order, then one
    WHERE: the shape of TPC-H Q3."""
    g, p, c = (s.createDataFrame(t) for t in tables)
    return (g.join(p, F.col("gk") == F.col("pg"))
            .join(c, F.col("fk") == F.col("pk"))
            .where((F.col("seg") == 1) & (F.col("pday") < 500)
                   & (F.col("cday") > 500))
            .groupBy("fk", "pday").agg(F.sum("cv").alias("total"))
            .select("fk", "total", "pday"))


def test_a_build_side_may_hold_a_join(spark, oracle):
    tables = three_tables()
    got = spec_order(spark, tables).collect_arrow()
    rec = spark.last_execution
    assert rec["engine"] == "fused" and not rec["fallbacks"]
    assert rec["plan"]["buildSidesSwapped"] == 2
    inner, outer = joins_of(spark)
    assert inner["buildRows"] < 400 and "buildJoins" not in inner
    assert outer["buildJoins"] == 1 and outer["buildSide"] == "left"
    assert outer["buildRows"] < 4_000  # the parents of segment 1 before 500
    assert {inner["lowering"], outer["lowering"]} <= {"lookup",
                                                      "lookupSurvivors"}
    assert all(j["planned"] == "shuffled" for j in (inner, outer))
    want = spec_order(oracle, tables).collect_arrow()
    assert got.column_names == want.column_names
    key = sorted(zip(got.column("fk").to_pylist(),
                     got.column("pday").to_pylist(),
                     got.column("total").to_pylist()))
    ref = sorted(zip(want.column("fk").to_pylist(),
                     want.column("pday").to_pylist(),
                     want.column("total").to_pylist()))
    assert [k[:2] for k in key] == [k[:2] for k in ref] and len(key) > 100
    assert np.allclose([k[2] for k in key], [k[2] for k in ref], rtol=1e-12)
    # hot: every bet lost in the first run is remembered
    spec_order(spark, tables).collect_arrow()
    assert spark.last_execution["join"]["runs"] == 1


def test_a_derived_build_side_is_handed_over_uncompacted_and_pruned(
        spark, monkeypatch):
    """The chain under a build side returns (batch, live mask) and
    computes only the columns the probe side's chain reads, the keys
    and what filters read: of [gk, seg, pk, pg, pday] the probe's
    aggregate reads `pday`, the join `pk`; the grandparent's columns
    and `pg` are zeros nobody gathers."""
    from spark_rapids_tpu.runtime import jit_cache

    marks = []
    real = jit_cache.cached_jit

    def spy(key, build, **kw):
        if key[0] == "fused":
            marks.extend(m for m in key[2] if isinstance(m, tuple)
                         and m and m[0] == "masked")
        return real(key, build, **kw)

    monkeypatch.setattr(jit_cache, "cached_jit", spy)
    spec_order(spark, three_tables()).collect_arrow()
    assert ("masked", (2, 4)) in marks   # the build chain: pk and pday
    assert ("masked",) in marks          # its buildprep takes the masks


def test_a_build_side_is_filtered_where_a_join_inside_it_is(spark):
    tables = three_tables()
    phys, _ = spec_order(spark, tables)._physical()
    outer = find(phys, J._DeviceJoinBase)[0]
    assert outer.build_is_filtered()
    g, p, c = (spark.createDataFrame(t) for t in tables)
    plain, _ = c.join(g.join(p, F.col("gk") == F.col("pg")),
                      F.col("fk") == F.col("pk"))._physical()
    assert not find(plain, J._DeviceJoinBase)[0].build_is_filtered()


# --- a probe-side filter that keeps most rows ---

def dated_batch(lo, hi, cap=65_536):
    cols = [DeviceColumn(DateType(), np.zeros(cap, np.int16),
                         np.ones(cap, bool), vrange=(lo, hi)),
            DeviceColumn(long, np.zeros(cap, np.int64), np.ones(cap, bool))]
    schema = StructType([StructField("day", DateType(), False),
                         StructField("plain", long, False)])
    return ColumnBatch(schema, cols, cap)


def bound(df_filter):
    """The bound condition of `createDataFrame(...).filter(cond)`."""
    return df_filter._plan.condition


def test_filter_share_reads_stamped_ranges_and_refuses_the_rest(spark):
    import datetime

    b = dated_batch(0, 16_383)
    frame = spark.createDataFrame(pa.table({
        "day": pa.array([datetime.date(1995, 3, 15)], pa.date32()),
        "plain": pa.array([1], pa.int64())}))
    day = datetime.date(1995, 3, 15)  # day 9,204 since 1970
    below = bound(frame.filter(F.col("day") < F.lit(day)))
    assert fused.filter_share(below, b) == pytest.approx(9_204 / 16_384)
    above = bound(frame.filter(F.lit(day) < F.col("day")))
    assert fused.filter_share(above, b) == pytest.approx(1 - 9_204 / 16_384)
    both = bound(frame.filter((F.col("day") < F.lit(day))
                              & (F.col("day") >= F.lit(day))))
    assert fused.filter_share(both, b) == pytest.approx(
        9_204 / 16_384 * (1 - 9_204 / 16_384))
    # no stamped range, or a conjunct that is not column-against-literal
    assert fused.filter_share(
        bound(frame.filter(F.col("plain") < 5)), b) is None
    assert fused.filter_share(
        bound(frame.filter((F.col("day") < F.lit(day))
                           & (F.col("plain") < F.col("plain")))), b) is None


def test_a_probe_filter_that_keeps_half_places_no_survivor_bet(spark):
    """The survivors' bet (1/64 of the slots) is hopeless for a filter
    whose own column's range says it keeps half: no lost run."""
    parent, child = parent_child()

    def q(cut):
        return spark.createDataFrame(child).filter(F.col("cv") < cut) \
            .join(spark.createDataFrame(parent), F.col("fk") == F.col("pk"))

    q(10_000).collect_arrow()  # cv is 0..19,999 in a range of 32,768
    rec = spark.last_execution["join"]
    assert rec["runs"] == 1 and rec["rerunReasons"] == []
    (j,) = rec["joins"]
    assert j["lowering"] == "lookup" and j["bet"] == ""
    assert j["filterShare"] == pytest.approx(10_000 / 32_768, abs=1e-4)
    q(100).collect_arrow()  # 0.3 %: the bet is placed, and holds
    (j,) = joins_of(spark)
    assert (j["lowering"], j["bet"]) == ("lookupSurvivors", "probeFilter")
    assert spark.last_execution["join"]["runs"] == 1


# --- more groups than fusedExec.groupCapacity ---

def test_a_final_aggregate_finds_more_groups_than_its_capacity_in_one_run(
        spark):
    n = 150_000
    rng = np.random.default_rng(3)
    t = pa.table({"k": pa.array(rng.permutation(n).astype(np.int64)),
                  "v": pa.array(np.ones(n))})
    (tmp := spark.createDataFrame(t)).count()
    df = tmp.repartition(4).groupBy("k").agg(F.sum("v").alias("s"))
    got = df.collect_arrow()
    rec = spark.last_execution
    assert rec["engine"] == "fused" and not rec["fallbacks"]
    assert got.num_rows == n
    assert sorted(got.column("k").to_pylist()) == list(range(n))
    assert set(got.column("s").to_pylist()) == {1.0}
    (g,) = rec["groups"]
    assert g["found"] == n and g["capacity"] >= n
    if rec["join"] is not None:
        assert rec["join"]["runs"] == 1


def test_a_group_capacity_that_overflows_later_grows_alone(spark):
    """The session looked once, at a small input; a later input with
    more groups overflows the final aggregate alone, which grows and is
    remembered, with no other program recompiled larger."""
    def frame(n):
        return spark.createDataFrame(pa.table({
            "k": pa.array(np.arange(200_000, dtype=np.int64) % n),
            "v": pa.array(np.ones(200_000))})) \
            .repartition(4).groupBy("k").agg(F.sum("v").alias("s"))

    assert frame(1_000).collect_arrow().num_rows == 1_000
    assert spark.last_execution["groups"] == [
        {"capacity": 65_536, "found": 1_000}] or \
        spark.last_execution["groups"][0]["found"] == 1_000
    got = frame(150_000).collect_arrow()
    assert got.num_rows == 150_000
    (g,) = spark.last_execution["groups"]
    assert g["found"] == 150_000
    assert frame(150_000).collect_arrow().num_rows == 150_000


# --- the plain reader's capacity ---

def test_plain_files_of_nearly_equal_length_share_one_capacity(
        spark, tmp_path):
    """TPC-H lineitem's files at SF10 hold 7,498,257 or 7,498,256 rows:
    at capacity == rows every program above them compiles twice."""
    rng = np.random.default_rng(2)
    for i, n in enumerate((1_200_001, 1_200_000)):
        pq.write_table(
            pa.table({"a": pa.array(rng.integers(0, 100, n), pa.int64()),
                      "b": pa.array(rng.random(n))}),
            tmp_path / f"part-{i}.parquet", compression="NONE",
            use_dictionary=False, row_group_size=n,
            data_page_size=64 << 20)
    df = spark.read.parquet(str(tmp_path)).cache(storage="device")
    assert df.count() == 2_400_001
    entry = spark.cache_manager.lookup(df._plan)
    caps = {p.capacity for p in entry.device_parts()}
    assert caps == {fused.bucket_capacity(1_200_001)}
    total = df.agg(F.sum("a").alias("s"), F.count("b").alias("n")) \
        .collect_arrow()
    assert total.column("n").to_pylist() == [2_400_001]
