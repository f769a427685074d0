"""A fused lookup join reads its build side only at the rows a probe
matched (exec/fused.py `lookup_join`, `build_table`; ops/joinops.py
`build_index`, `BuildIndex`, `rows_at`): "matched", and "none" where
the join reads no build column, equal a plain Python join and what
`build_side`'s sorted batch gives, whatever the shapes; the program
keys carry the mark."""

import collections
import os

import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.dataframe import DataFrame
from spark_rapids_tpu.api.session import TpuSparkSession
from spark_rapids_tpu.columnar.arrow_bridge import arrow_to_device
from spark_rapids_tpu.columnar.batch import ColumnBatch
from spark_rapids_tpu.exec import fused
from spark_rapids_tpu.ops import joinops
from spark_rapids_tpu.plan import logical as L

HOWS = ["inner", "left", "left_semi", "left_anti", "existence"]
READS_NO_COLUMN = ("left_semi", "left_anti", "existence")
PROBE_ROWS, BUILD_ROWS = 20_000, 3_000
SLOTS = 65_536  # an upload's least capacity
TAGS = ["1-URGENT", "2-HIGH", "3-MEDIUM", None]


#: one scan task a file: the build side's two files are two parts
CONF = {"spark.sql.shuffle.partitions": 4,
        "spark.rapids.sql.format.parquet.reader.type": "PERFILE"}


@pytest.fixture()
def spark():
    s = TpuSparkSession(CONF)
    yield s
    s.stop()


def tables(base: int):
    """`base` 0: keys whose stamped range fits 32 bits (one sort
    operand); 2^33: keys that need their 64 (rank + value operands).
    Null keys on both sides, a dictionary-encoded payload column with
    nulls, and the build side in two halves: two parquet files, which
    the join sees as two parts end to end with dead rows after each."""
    rng = np.random.default_rng([30, base != 0])
    bk = np.arange(BUILD_ROWS, dtype=np.int64) * 3 + base
    k = rng.choice(np.concatenate([bk, bk + 1]), PROBE_ROWS)
    probe = pa.table({
        "k": pa.array(k, mask=rng.random(PROBE_ROWS) < 0.05),
        "v": pa.array(rng.random(PROBE_ROWS)),
        "row": pa.array(np.arange(PROBE_ROWS, dtype=np.int64))})
    order = rng.permutation(BUILD_ROWS)  # the build side is not sorted
    build = pa.table({
        "bk": pa.array(bk[order], mask=rng.random(BUILD_ROWS) < 0.05),
        "bv": pa.array(order.astype(np.int64) * 10),
        "tag": pa.array([TAGS[i % 4] for i in order]).dictionary_encode()})
    return probe, [build.slice(0, 1_000), build.slice(1_000)]


def query(spark, probe, halves, how: str, share=0.01, at=None):
    p = spark.createDataFrame(probe)
    if share is not None:
        p = p.filter(F.col("v") < share)
    os.makedirs(str(at), exist_ok=True)
    for i, half in enumerate(halves):
        pq.write_table(half, os.path.join(str(at), f"part-{i}.parquet"),
                       use_dictionary=["tag"])
    b = spark.read.parquet(str(at))
    if how != "existence":
        return p.join(b, F.col("k") == F.col("bk"), how)
    plan = L.Join(p._plan, b._plan, "existence", [p["k"].expr],
                  [b["bk"].expr], exists_name="has")
    return DataFrame(plan, spark)


def plain_join(probe, halves, how: str, share=0.01) -> collections.Counter:
    build = pa.concat_tables(halves)
    by_key = {bk: (bv, tag) for bk, bv, tag in zip(
        *(build.column(c).to_pylist() for c in ("bk", "bv", "tag")))
        if bk is not None}
    out = collections.Counter()
    for k, v, row in zip(*(probe.column(c).to_pylist()
                           for c in ("k", "v", "row"))):
        if share is not None and not v < share:
            continue
        match = by_key.get(k)
        if how == "inner" and match:
            out[(row,) + match] += 1
        elif how == "left":
            out[(row,) + (match or (None, None))] += 1
        elif how == "left_semi" and match:
            out[(row,)] += 1
        elif how == "left_anti" and not match:
            out[(row,)] += 1
        elif how == "existence":
            out[(row, match is not None)] += 1
    return out


def rows_of(table, how: str) -> collections.Counter:
    cols = {"inner": ["row", "bv", "tag"], "left": ["row", "bv", "tag"],
            "existence": ["row", "has"]}.get(how, ["row"])
    return collections.Counter(
        zip(*(table.column(c).to_pylist() for c in cols)))


def the_join(session) -> dict:
    rec = session.last_execution
    assert rec["engine"] == "fused" and not rec["fallbacks"]
    (j,) = rec["join"]["joins"]
    return j


# --- the join's rows ---

@pytest.mark.parametrize("base", [0, 2 ** 33], ids=["32_bit", "64_bit"])
@pytest.mark.parametrize("how", HOWS)
def test_lookup_join_over_an_index_equals_a_plain_join(spark, tmp_path, how,
                                                       base):
    probe, halves = tables(base)
    want = plain_join(probe, halves, how)
    assert len(want) > 50
    got = query(spark, probe, halves, how, at=tmp_path).collect_arrow()
    j = the_join(spark)
    assert j["lowering"] == "lookupSurvivors"
    assert j["searchedSlots"] == fused.survivor_capacity(SLOTS)
    assert (j["buildSlots"], j["buildRows"]) == (2 * SLOTS, BUILD_ROWS)
    assert j["buildGather"] == \
        ("none" if how in READS_NO_COLUMN else "matched")
    assert "buildGatheredSlots" not in j
    assert rows_of(got, how) == want


@pytest.mark.parametrize("how", ["inner", "left"])
def test_lookup_join_without_a_filter_below_it(spark, tmp_path, how):
    """Every probe slot is searched and read through `perm`."""
    probe, halves = tables(2 ** 33)
    got = query(spark, probe, halves, how, share=None,
                at=tmp_path).collect_arrow()
    j = the_join(spark)
    assert (j["lowering"], j["buildGather"]) == ("lookup", "matched")
    assert j["searchedSlots"] == SLOTS
    assert rows_of(got, how) == plain_join(probe, halves, how, None)


# --- whatever the shapes ---

def wide_probe_narrow_build():
    """262,144 probe slots over a build side of one column: its two
    arrays and the key operand are 3 x 65,536 slots, fewer than the
    reads at `perm[lo]`."""
    rng = np.random.default_rng(31)
    n = 200_000
    bk = np.arange(BUILD_ROWS, dtype=np.int64) * 3
    probe = pa.table({
        "k": pa.array(rng.choice(np.concatenate([bk, bk + 1]), n)),
        "v": pa.array(rng.random(n)),
        "row": pa.array(np.arange(n, dtype=np.int64))})
    return probe, pa.table({"bk": pa.array(bk)})


def key_join(spark, probe, build, share):
    p = spark.createDataFrame(probe)
    if share is not None:
        p = p.filter(F.col("v") < share)
    return p.join(spark.createDataFrame(build), F.col("k") == F.col("bk"),
                  "inner")


def key_rows(probe, share) -> int:
    k = probe.column("k").to_numpy()
    v = probe.column("v").to_numpy()
    keep = np.ones(len(k), bool) if share is None else v < share
    return int(np.sum(keep & (k % 3 == 0)))


def test_a_small_build_side_under_a_wide_probe_is_left_as_it_lies(spark):
    """No threshold sends it back to a sorted batch (one more int32
    gather beside the search: PERF.md, PR 30)."""
    probe, build = wide_probe_narrow_build()
    got = key_join(spark, probe, build, None).collect_arrow()
    j = the_join(spark)
    assert j["searchedSlots"] == 262_144 > 3 * j["buildSlots"]
    assert (j["lowering"], j["buildGather"]) == ("lookup", "matched")
    assert got.num_rows == key_rows(probe, None)


def test_a_lost_survivor_bet_reruns_over_the_same_index(spark):
    probe, build = wide_probe_narrow_build()
    got = key_join(spark, probe, build, 0.01).collect_arrow()
    j = the_join(spark)
    assert (j["lowering"], j["searchedSlots"]) == ("lookupSurvivors", 4_096)
    assert got.num_rows == key_rows(probe, 0.01)
    got = key_join(spark, probe, build, 0.5).collect_arrow()
    rec = spark.last_execution["join"]
    assert rec["runs"] == 2 and rec["rerunReasons"] == ["survivorOverflow"]
    j = the_join(spark)
    assert (j["lowering"], j["searchedSlots"]) == ("lookup", 262_144)
    assert j["buildGather"] == "matched"
    assert got.num_rows == key_rows(probe, 0.5)


def test_a_build_side_with_a_key_twice_falls_to_the_expanding_join(spark):
    """The uniqueness flag is the probe's own (`dup`): the rerun sorts
    the whole build side, and the record says so."""
    probe, build = wide_probe_narrow_build()
    twice = pa.concat_tables([build, build.slice(0, 10)])
    got = key_join(spark, probe, twice, 0.01).collect_arrow()
    rec = spark.last_execution["join"]
    assert rec["rerunReasons"] == ["uniquenessLost"]
    j = the_join(spark)
    assert (j["lowering"], j["buildGather"]) == ("expand", "sorted")
    k = probe.column("k").to_numpy()[probe.column("v").to_numpy() < 0.01]
    assert got.num_rows == int(np.sum(k % 3 == 0) +
                               np.sum((k % 3 == 0) & (k < 30)))


# --- the program keys ---

@pytest.fixture()
def keyed(monkeypatch):
    """(kind, key, name) of every fused program named from here on."""
    seen = []
    real = fused.program_name

    def spy(tag, key):
        seen.append((tag, key, real(tag, key)))
        return seen[-1][2]

    monkeypatch.setattr(fused, "program_name", spy)
    return seen


@pytest.mark.parametrize("how", ["inner", "left_semi"])
def test_program_keys_carry_what_the_join_reads(spark, keyed, tmp_path, how):
    """The parent's `buildprep` made a sorted batch under the key
    without the mark: the mark names these programs apart from it
    (`fused_buildprep_<digest>`, `fused_chain_<digest>` in the trace
    and the ledger's breakdown)."""
    probe, halves = tables(0)
    query(spark, probe, halves, how, at=tmp_path).collect_arrow()
    gather = the_join(spark)["buildGather"]
    named = list(keyed)
    marks = {"buildprep": ("buildGather", gather),
             "chain": ("buildGather", (gather,))}
    for kind, mark in marks.items():
        ((key, name),) = {(key, name) for tag, key, name in named
                          if tag == kind}
        assert key[-1] == mark
        assert name != fused.program_name(kind, key[:-1])
    assert not [key for tag, key, _ in named
                if tag not in marks and "buildGather" in repr(key)]


def test_a_chain_without_a_join_has_no_mark(spark, keyed):
    probe, _ = tables(0)
    spark.createDataFrame(probe).filter(F.col("v") < 0.5) \
        .groupBy().count().collect_arrow()
    assert keyed and "buildGather" not in repr([k for _, k, _ in keyed])


# --- the kernels ---

@pytest.mark.parametrize("base, narrow", [(0, True), (2 ** 33, False)],
                         ids=["32_bit", "64_bit"])
def test_build_index_leaves_the_batch_and_returns_the_sorts_own_keys(
        base, narrow):
    _, halves = tables(base)
    build = pa.concat_tables(halves)
    batch = arrow_to_device(build)
    if narrow:  # the range the narrowed upload stamps
        bk = build.column("bk").drop_null().to_numpy()
        col = batch.columns[0].replace(vrange=(int(bk.min()), int(bk.max())))
        batch = ColumnBatch(batch.schema, [col] + list(batch.columns[1:]),
                            batch.num_rows)
    live = jnp.asarray(np.random.default_rng(3).random(batch.capacity) < 0.9
                       ) & batch.live_mask()
    idx = joinops.build_index(batch, [0], live)
    assert idx.batch is batch
    assert idx.perm.dtype == jnp.int32 and idx.perm.shape == (batch.capacity,)
    assert idx.capacity == batch.capacity
    assert sorted(np.asarray(idx.perm)) == list(range(batch.capacity))
    (keys,) = idx.keys
    assert keys.dtype == (jnp.int32 if narrow else jnp.int64)
    valid = np.asarray(live & batch.columns[0].validity)
    n = int(idx.valid_bound)
    assert n == valid.sum() and int(idx.num_rows) == int(jnp.sum(live))
    # the sort's own outputs are the keys taken by its permutation
    data = np.asarray(batch.columns[0].data)
    perm = np.asarray(idx.perm)
    assert valid[perm[:n]].all() and not valid[perm[n:]].any()
    assert np.array_equal(np.asarray(keys)[:n], data[perm[:n]])
    assert np.all(np.diff(np.asarray(keys)[:n]) > 0)
    # and `build_side` is the index with the batch moved by it
    bt = joinops.build_side(batch, [0], live)
    assert np.array_equal(np.asarray(bt.keys[0]), np.asarray(keys))
    assert int(bt.valid_bound) == n
    assert bt.batch.capacity == idx.capacity
    assert int(bt.batch.num_rows) == int(idx.num_rows)
    for got, was in zip(bt.batch.columns, batch.columns):
        assert np.array_equal(np.asarray(got.data)[:n],
                              np.asarray(was.data)[perm[:n]])
    # so a probe reads the same rows from either: the sorted batch at
    # `lo`, the batch as it lies at `perm[lo]` (joinops.rows_at)
    pb = arrow_to_device(pa.table({"k": build.column("bk").slice(0, 500)}))
    lo, matched, dup = joinops.probe_unique(idx, pb, [0])
    for ours, theirs in zip((lo, matched, dup),
                            joinops.probe_unique(bt, pb, [0])):
        assert np.array_equal(np.asarray(ours), np.asarray(theirs))
    rows, plain_read = joinops.rows_at(idx, jnp.clip(lo, 0, idx.capacity - 1))
    assert plain_read.shape == () and plain_read.dtype == jnp.bool_
    hit = np.asarray(matched)
    assert 0 < hit.sum() < 500
    for i in (1, 2):
        late = batch.columns[i].gather(rows)
        up_front = bt.batch.columns[i].gather(lo)
        assert np.array_equal(np.asarray(late.data)[hit],
                              np.asarray(up_front.data)[hit])
        assert np.array_equal(np.asarray(late.validity)[hit],
                              np.asarray(up_front.validity)[hit])
