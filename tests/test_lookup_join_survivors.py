"""The fused lookup join under a pending filter searches the filter's
survivors (exec/fused.py `survivor_capacity`, ops/joinops.py
`front_row_ids`, `probe_unique`): equal to the full-width lowering
and to a plain Python join, with the lost bets counted in
`session.last_execution["join"]` and remembered by the session."""

import collections
import datetime

import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.column import Column
from spark_rapids_tpu.api.session import TpuSparkSession
from spark_rapids_tpu.columnar.arrow_bridge import arrow_to_device
from spark_rapids_tpu.columnar.batch import ColumnBatch
from spark_rapids_tpu.exec import fused
from spark_rapids_tpu.expr.core import Literal
from spark_rapids_tpu.ops import joinops
from spark_rapids_tpu.sqltypes import DateType, TimestampType

PROBE_ROWS, BUILD_ROWS = 20_000, 3_000
#: an upload's least capacity is 65,536 slots, so the survivors of a
#: filter are brought to 1,024: 1% of the probe side fits, half of it
#: does not
CAPACITY = fused.survivor_capacity(65_536)
SHARE = {"absent": None, "sparse": 0.01, "dense": 0.5}


@pytest.fixture()
def spark():
    s = TpuSparkSession({"spark.sql.shuffle.partitions": 4})
    yield s
    s.stop()


def tables(unique: bool, nulls: bool, base: int = 2 ** 33):
    """`base` 2^33: keys that need their 64 bits; 0 or negative: keys
    whose stamped range fits 32, which the build side sorts as such."""
    rng = np.random.default_rng([7, unique, nulls])
    bk = np.arange(BUILD_ROWS, dtype=np.int64) * 3 + base
    if not unique:
        bk[1::7] = bk[0::7][:len(bk[1::7])]
    k = rng.choice(np.concatenate([bk, bk + 1]), PROBE_ROWS)
    null_k = rng.random(PROBE_ROWS) < 0.05 if nulls else None
    null_bk = rng.random(BUILD_ROWS) < 0.05 if nulls else None
    probe = pa.table({
        "k": pa.array(k, mask=null_k),
        "v": pa.array(rng.random(PROBE_ROWS)),
        "row": pa.array(np.arange(PROBE_ROWS, dtype=np.int64))})
    build = pa.table({
        "bk": pa.array(bk, mask=null_bk),
        "bv": pa.array(np.arange(BUILD_ROWS, dtype=np.int64) * 10)})
    return probe, build


def query(spark, probe, build, how: str, share):
    p, b = spark.createDataFrame(probe), spark.createDataFrame(build)
    if share is not None:
        p = p.filter(F.col("v") < share)
    return p.join(b, F.col("k") == F.col("bk"), how)


def plain_join(probe, build, how: str, share) -> collections.Counter:
    by_key = collections.defaultdict(list)
    for bk, bv in zip(*(build.column(c).to_pylist() for c in ("bk", "bv"))):
        if bk is not None:
            by_key[bk].append(bv)
    out = collections.Counter()
    for k, v, row in zip(*(probe.column(c).to_pylist()
                           for c in ("k", "v", "row"))):
        if share is not None and not v < share:
            continue
        matches = by_key.get(k, []) if k is not None else []
        if how == "left_semi":
            if matches:
                out[(row,)] += 1
        else:
            for bv in matches:
                out[(row, bv)] += 1
    return out


def rows_of(table, how: str) -> collections.Counter:
    cols = ["row"] if how == "left_semi" else ["row", "bv"]
    return collections.Counter(
        zip(*(table.column(c).to_pylist() for c in cols)))


@pytest.mark.parametrize("nulls", [False, True], ids=["keys", "null_keys"])
@pytest.mark.parametrize("unique", [True, False], ids=["unique", "dup"])
@pytest.mark.parametrize("mask", list(SHARE))
@pytest.mark.parametrize("how", ["inner", "left_semi"])
def test_survivor_join_equals_full_width_and_plain_join(
        spark, monkeypatch, how, mask, unique, nulls):
    probe, build = tables(unique, nulls)
    share = SHARE[mask]
    want = plain_join(probe, build, how, share)

    got = query(spark, probe, build, how, share).collect_arrow()
    rec = spark.last_execution
    assert rec["engine"] == "fused" and not rec["fallbacks"]
    assert rows_of(got, how) == want

    # what the run did, as the record counts it
    reasons = []
    if mask == "dense":
        reasons.append("survivorOverflow")
    if how == "inner" and not unique:
        reasons.append("uniquenessLost")
    join = rec["join"]
    assert join["rerunReasons"] == reasons
    assert join["runs"] == len(reasons) + 1
    (j,) = join["joins"]
    assert j["joinType"] == how and j["buildRows"] == BUILD_ROWS
    if how == "inner" and not unique:
        assert j["lowering"] == "expand"
    elif mask == "sparse":
        assert j["lowering"] == "lookupSurvivors"
        assert (j["probeSlots"], j["searchedSlots"],
                j["outputCapacity"]) == (65_536, CAPACITY, CAPACITY)
        # a survivor's row id: two row reads in the prefix sum of the
        # 65,536-slot mask (512 rows of 128, their last keys 4 rows)
        assert (j["bet"], j["rowIdReads"]) == ("probeFilter", 2)
    else:  # no mask below the join, or a bet that was lost
        assert j["lowering"] == "lookup"
        assert j["searchedSlots"] == j["probeSlots"] == 65_536
        assert "rowIdReads" not in j

    # the full-width lowering, in a session of its own
    monkeypatch.setattr(fused, "survivor_capacity", lambda n: None)
    other = TpuSparkSession({"spark.sql.shuffle.partitions": 4})
    try:
        wide = query(other, probe, build, how, share).collect_arrow()
        assert "survivorOverflow" not in \
            other.last_execution["join"]["rerunReasons"]
    finally:
        other.stop()
    assert rows_of(wide, how) == rows_of(got, how)


@pytest.mark.parametrize("base", [0, -5_000, 2 ** 31 - 9_000],
                         ids=["from_0", "negative", "past_32_bits"])
@pytest.mark.parametrize("how", ["inner", "left_semi", "left_anti"])
def test_keys_that_fit_32_bits_join_as_the_wide_ones_do(spark, how, base):
    """The last case's keys pass 2^31: its range does not fit, and the
    build side sorts two 64-bit operands as before."""
    probe, build = tables(True, True, base)
    got = query(spark, probe, build, how, 0.01).collect_arrow()
    (j,) = spark.last_execution["join"]["joins"]
    assert j["lowering"] == "lookupSurvivors"
    if how == "left_anti":
        kept = rows_of(got, "left_semi")
        semi = plain_join(probe, build, "left_semi", 0.01)
        passed = sum(1 for v in probe.column("v").to_pylist() if v < 0.01)
        assert sum(kept.values()) == passed - sum(semi.values())
        assert not set(kept) & set(semi)
    else:
        assert rows_of(got, how) == plain_join(probe, build, how, 0.01)


@pytest.mark.parametrize("how", ["inner", "left_semi"])
def test_a_lost_survivor_bet_is_not_placed_again_in_the_session(spark, how):
    probe, build = tables(True, False)
    want = plain_join(probe, build, how, 0.5)
    first = query(spark, probe, build, how, 0.5).collect_arrow()
    assert spark.last_execution["join"]["rerunReasons"] == \
        ["survivorOverflow"]
    assert len(spark.fused_wide_joins) == 1
    # the same plan, built anew: one run, at full width, nothing built
    again = query(spark, probe, build, how, 0.5).collect_arrow()
    rec = spark.last_execution
    assert rec["join"]["runs"] == 1 and rec["join"]["rerunReasons"] == []
    assert rec["join"]["joins"][0]["lowering"] == "lookup"
    assert rec["compile"]["programsCompiled"] == 0
    assert rows_of(first, how) == rows_of(again, how) == want
    # another filter over the same tables is another bet
    query(spark, probe, build, how, 0.01).collect_arrow()
    assert spark.last_execution["join"]["joins"][0]["lowering"] == \
        "lookupSurvivors"
    # and another session starts without the memory
    other = TpuSparkSession({"spark.sql.shuffle.partitions": 4})
    try:
        query(other, probe, build, how, 0.5).collect_arrow()
        assert other.last_execution["join"]["runs"] == 2
    finally:
        other.stop()


def test_join_record_is_on_the_span_and_the_bus(spark):
    probe, build = tables(True, False)
    query(spark, probe, build, "inner", 0.01).collect_arrow()
    rec = spark.last_execution["join"]
    events = spark.obs.query_events()
    (ev,) = [e for e in events if e["event"] == "join"]
    for k, v in rec["joins"][0].items():
        assert ev[k] == v
    assert ev["runs"] == 1
    spans = [e for e in events if e["event"] == "operator.span"]
    (execute,) = [e for e in spans if e["operator"] == "fused.execute"]
    assert execute["join"] == rec
    chains = [e for e in spans if e["operator"] == "fused.dispatch"
              and e.get("joins")]
    assert chains and all(
        e["joins"][0]["lowering"] == "lookupSurvivors" for e in chains)
    assert sum(e["joins"][0]["searchedSlots"] for e in chains) == \
        rec["joins"][0]["searchedSlots"]


def test_a_query_without_a_join_has_no_join_record(spark):
    probe, _ = tables(True, False)
    spark.createDataFrame(probe).filter(F.col("v") < 0.5).collect_arrow()
    assert spark.last_execution["join"] is None
    assert not [e for e in spark.obs.query_events() if e["event"] == "join"]


def test_obs_disabled_leaves_no_join_event_and_keeps_the_answer():
    probe, build = tables(True, False)
    s = TpuSparkSession({"spark.sql.shuffle.partitions": 4,
                         "spark.rapids.tpu.obs.enabled": False})
    try:
        got = query(s, probe, build, "inner", 0.01).collect_arrow()
        assert rows_of(got, "inner") == plain_join(probe, build, "inner",
                                                   0.01)
        assert s.obs.query_events() == []
        assert s.last_execution["join"]["joins"][0]["lowering"] == \
            "lookupSurvivors"
    finally:
        s.stop()


# --- the kernels ---

@pytest.mark.parametrize("share", [0.0, 0.01, 0.3, 1.0])
def test_front_row_ids(share):
    rng = np.random.default_rng(int(share * 100))
    keep = rng.random(5000) < share
    ids, total = joinops.front_row_ids(jnp.asarray(keep), 256)
    want = np.flatnonzero(keep)
    assert int(total) == want.size
    n = min(want.size, 256)
    assert np.array_equal(np.asarray(ids)[:n], want[:n])
    assert np.asarray(ids).max(initial=0) < keep.size


@pytest.mark.parametrize("nulls", [False, True], ids=["keys", "null_keys"])
@pytest.mark.parametrize("unique", [True, False], ids=["unique", "dup"])
def test_probe_unique_agrees_with_probe_ranges(unique, nulls):
    probe, build = tables(unique, nulls)
    bt = joinops.build_side(arrow_to_device(build), [0])
    pb = arrow_to_device(probe)
    lo, counts = joinops.probe_ranges(bt, pb, [0])
    lo1, matched, dup = joinops.probe_unique(bt, pb, [0])
    counts = np.asarray(counts)
    assert np.array_equal(np.asarray(matched), counts > 0)
    assert np.array_equal(np.asarray(dup), counts > 1)
    hit = counts > 0
    assert np.array_equal(np.asarray(lo1)[hit], np.asarray(lo)[hit])
    assert bool(np.any(counts > 1)) == (not unique)


@pytest.mark.parametrize("base, narrow", [
    (0, True), (-(2 ** 31), True), (2 ** 31 - 3 * BUILD_ROWS + 1, True),
    (2 ** 31 - 3 * BUILD_ROWS + 2, False), (2 ** 33, False)])
def test_build_side_sorts_one_32_bit_operand_where_the_range_fits(
        base, narrow):
    """With the range stamped (as the narrowed upload stamps it), the
    sorted keys are 32-bit exactly where the range leaves the value
    above it free; the table is the same table either way, dead and
    null-keyed rows last, wherever `live` left them."""
    _, build = tables(True, True, base)
    bk = build.column("bk").to_numpy(zero_copy_only=False)
    plain = arrow_to_device(build)
    assert not joinops._fits_32_bits(plain, [0])  # no range: as before
    lo, hi = int(np.nanmin(bk)), int(np.nanmax(bk))
    col = plain.columns[0].replace(vrange=(lo, hi))
    stamped = ColumnBatch(plain.schema, [col, plain.columns[1]],
                          plain.num_rows)
    assert joinops._fits_32_bits(stamped, [0]) == narrow
    live = jnp.asarray(np.random.default_rng(3).random(plain.capacity) < 0.9
                       ) & plain.live_mask()
    want = joinops.build_side(plain, [0], live)
    got = joinops.build_side(stamped, [0], live)
    assert got.keys[0].dtype == (jnp.int32 if narrow else jnp.int64)
    n = int(want.valid_bound)
    assert int(got.valid_bound) == n and 0 < n < BUILD_ROWS
    assert np.array_equal(np.asarray(got.keys[0])[:n],
                          np.asarray(want.keys[0])[:n])
    assert int(got.batch.num_rows) == int(jnp.sum(live))
    for a, b in zip(got.batch.columns, want.batch.columns):
        assert np.array_equal(np.asarray(a.data)[:n], np.asarray(b.data)[:n])
        assert np.asarray(a.validity)[:n].all()
    if narrow:  # what is not a valid key sorts after every key
        assert np.all(np.asarray(got.keys[0])[n:] == 2 ** 31 - 1)


def test_survivor_capacity():
    assert fused.survivor_capacity(7_864_320) == 122_880
    assert fused.survivor_capacity(65_536) == 1_024
    assert fused.survivor_capacity(2_048) is None
    for n in (4_096, 65_536, 1 << 20, 7_864_320, 8_388_608):
        cap = fused.survivor_capacity(n)
        assert cap % 1_024 == 0 and n // 64 <= cap <= n // 4


# --- date and datetime literals ---

DATES = [datetime.date(1993, 12, 31), datetime.date(1994, 1, 1),
         datetime.date(1994, 6, 30), datetime.date(1995, 1, 1), None]
STAMPS = [datetime.datetime(1994, 1, 1, 0, 0, 0),
          datetime.datetime(1994, 1, 1, 12, 30, 15, 250_000),
          datetime.datetime(1969, 12, 31, 23, 59, 59), None]


def test_date_literal_in_a_filter_equals_the_typed_literal(spark):
    df = spark.createDataFrame(pa.table({
        "d": pa.array(DATES, pa.date32()), "i": pa.array(range(5))}))
    day = datetime.date(1994, 1, 1)
    days = (day - datetime.date(1970, 1, 1)).days
    lit = F.lit(day)
    assert isinstance(lit.expr.dtype, DateType) and lit.expr.value == days
    got = df.filter((F.col("d") >= lit)
                    & (F.col("d") < F.lit(datetime.date(1995, 1, 1))))
    typed = df.filter(
        (F.col("d") >= Column(Literal(days, DateType())))
        & (F.col("d") < Column(Literal(days + 365, DateType()))))
    assert got.collect_arrow().equals(typed.collect_arrow())
    assert got.collect_arrow().column("i").to_pylist() == [1, 2]
    assert spark.last_execution["engine"] == "fused"


def test_datetime_literal_in_a_filter_equals_the_typed_literal(spark):
    df = spark.createDataFrame(pa.table({
        "t": pa.array(STAMPS, pa.timestamp("us", tz="UTC")),
        "i": pa.array(range(4))}))
    at = datetime.datetime(1994, 1, 1, 12, 30, 15, 250_000)
    micros = 757_427_415_250_000
    lit = F.lit(at)
    assert isinstance(lit.expr.dtype, TimestampType)
    assert lit.expr.value == micros
    aware = at.replace(tzinfo=datetime.timezone(datetime.timedelta(hours=2)))
    assert F.lit(aware).expr.value == micros - 2 * 3_600 * 1_000_000
    got = df.filter(F.col("t") < lit).collect_arrow()
    typed = df.filter(
        F.col("t") < Column(Literal(micros, TimestampType()))).collect_arrow()
    assert got.equals(typed)
    assert got.column("i").to_pylist() == [0, 2]
