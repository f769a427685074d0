"""A full-width lookup join's search takes a window of the index a
block of probes at a time where the probe side is clustered by its key
(ops/joinops.py `_count_below`, `search_blocks`), and the join's record
says how often: `searchBlocks`, `windowedBlocks` of
`session.last_execution["join"]` (exec/fused.py `note_join`,
`settle`)."""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSparkSession
from spark_rapids_tpu.ops import joinops

FACT_ROWS, PARENT_ROWS = 20_000, 3_000
#: an upload's least capacity is 65,536 slots: 16 blocks of 4,096
#: probes, of which the fact's rows fill 4 and reach into a fifth; the
#: index is 512 rows of 128 keys, a window 16 of them
BLOCK, WINDOW, SLOTS = 4_096, 16, 65_536
LIVE_BLOCKS = -(-FACT_ROWS // BLOCK)


@pytest.fixture()
def spark(monkeypatch):
    monkeypatch.setattr(joinops, "_PROBE_BLOCK", BLOCK)
    monkeypatch.setattr(joinops, "_WINDOW_ROWS", WINDOW)
    s = TpuSparkSession({"spark.sql.shuffle.partitions": 4})
    yield s
    s.stop()


def tables(order: str, stride: int, nulls: bool):
    """A parent of unique keys `stride` apart and a fact table of its
    foreign keys (half of them of no parent): `sorted` by the key,
    `clustered` (each run of 4,096 rows holds neighbouring parents, in
    any order), or `spread`."""
    rng = np.random.default_rng([stride, len(order), nulls])
    pk = np.arange(PARENT_ROWS, dtype=np.int64) * stride + 7
    fk = np.sort(rng.choice(np.concatenate([pk, pk + 1]), FACT_ROWS))
    if order == "clustered":
        fk = np.concatenate([rng.permutation(fk[i:i + BLOCK])
                             for i in range(0, FACT_ROWS, BLOCK)])
    elif order == "spread":
        fk = rng.permutation(fk)
    null_fk = rng.random(FACT_ROWS) < 0.05 if nulls else None
    fact = pa.table({
        "w_fk": pa.array(fk, mask=null_fk),
        "w_line": pa.array(np.arange(FACT_ROWS, dtype=np.int64))})
    parent = pa.table({
        "w_pk": pa.array(pk),
        "w_val": pa.array(np.arange(PARENT_ROWS, dtype=np.int64) * 10)})
    return fact, parent


def joined(spark, fact, parent):
    out = spark.createDataFrame(fact).join(
        spark.createDataFrame(parent), F.col("w_fk") == F.col("w_pk"),
        "inner").collect_arrow()
    rec = spark.last_execution
    assert rec["engine"] == "fused" and not rec["fallbacks"]
    (j,) = rec["join"]["joins"]
    return out, j


def plain_join(fact, parent):
    at = dict(zip(parent.column("w_pk").to_pylist(),
                  parent.column("w_val").to_pylist()))
    return sorted((line, at[fk]) for fk, line in zip(
        fact.column("w_fk").to_pylist(), fact.column("w_line").to_pylist())
        if fk in at)


def rows_of(out):
    return sorted(zip(out.column("w_line").to_pylist(),
                      out.column("w_val").to_pylist()))


@pytest.mark.parametrize("nulls", [False, True], ids=["keys", "null_keys"])
@pytest.mark.parametrize("order", ["sorted", "clustered"])
def test_a_clustered_probe_side_takes_the_window_in_every_live_block(
        spark, order, nulls):
    """Sparse parent keys (a range too wide for a table of positions):
    the search is blocked and every block is narrow: null keys, which
    hold zeros, and the dead slots of the fifth take no part, and the
    11 blocks of padding alone, whose positions nobody reads, do not
    pay for the whole index either."""
    fact, parent = tables(order, 1_000, nulls)
    out, j = joined(spark, fact, parent)
    assert rows_of(out) == plain_join(fact, parent)
    assert (j["lowering"], j["probe"]) == ("lookup", "search")
    assert j["searchedSlots"] == SLOTS
    assert j["searchBlocks"] == SLOTS // BLOCK == 16
    assert j["windowedBlocks"] == 16


def test_a_spread_probe_side_searches_the_whole_index(spark):
    fact, parent = tables("spread", 1_000, False)
    out, j = joined(spark, fact, parent)
    assert rows_of(out) == plain_join(fact, parent)
    # (the blocks of padding alone have nothing to be spread over)
    assert (j["probe"], j["searchBlocks"], j["windowedBlocks"]) \
        == ("search", 16, 16 - LIVE_BLOCKS)


def test_a_probe_by_position_searches_nothing(spark):
    """Dense parent keys are read by position: no search, no block."""
    fact, parent = tables("sorted", 3, False)
    out, j = joined(spark, fact, parent)
    assert rows_of(out) == plain_join(fact, parent)
    assert (j["probe"], j["searchBlocks"], j["windowedBlocks"]) \
        == ("position", 0, 0)


def test_probes_that_fit_one_block_are_not_counted(monkeypatch):
    """The unpatched block holds 131,072 probes: a 65,536-slot probe
    side is searched at once, and the record says 0 of 0."""
    s = TpuSparkSession({"spark.sql.shuffle.partitions": 4})
    try:
        fact, parent = tables("sorted", 1_001, False)
        out, j = joined(s, fact, parent)
        assert rows_of(out) == plain_join(fact, parent)
        assert (j["probe"], j["searchBlocks"], j["windowedBlocks"]) \
            == ("search", 0, 0)
    finally:
        s.stop()


def test_an_expanding_join_reports_no_blocks(spark):
    """Duplicate parent keys lose the uniqueness bet: the re-run's
    expanding join carries the fields, and counts nothing."""
    fact, parent = tables("sorted", 1_000, False)
    pk = parent.column("w_pk").to_numpy().copy()
    pk[1] = pk[0]
    parent = parent.set_column(0, "w_pk", pa.array(pk))
    spark.createDataFrame(fact).join(
        spark.createDataFrame(parent), F.col("w_fk") == F.col("w_pk"),
        "inner").collect_arrow()
    join = spark.last_execution["join"]
    assert join["rerunReasons"] == ["uniquenessLost"]
    (j,) = join["joins"]
    assert (j["lowering"], j["searchBlocks"], j["windowedBlocks"]) \
        == ("expand", 0, 0)


def test_the_benchmarks_join_reader_reads_the_new_fields(spark, monkeypatch):
    """What a `q3.windowed_blocks_per_query` would read (PERF.md
    section 7): `benchmark/layer_metrics/_join_record.per_query` over
    the `join` field of a query's `fused.execute` span; a program
    whose record lacks the field (this PR's parent) gives None."""
    from benchmark import span_window
    from benchmark.layer_metrics import _join_record

    fact, parent = tables("sorted", 1_000, False)
    joined(spark, fact, parent)
    record = spark.last_execution["join"]

    def trees(record):
        class Node:
            name, extra, children = "fused.execute", {"join": record}, []

        class Tree:
            name, extra, children = "query-1", {}, [Node()]

        return lambda ctx: [Tree(), Tree()]

    monkeypatch.setattr(span_window, "window_trees", trees(record))
    assert _join_record.per_query({}, "windowedBlocks") == 16
    assert _join_record.per_query({}, "searchBlocks") == 16
    before = dict(record, joins=[
        {k: v for k, v in j.items() if k != "windowedBlocks"}
        for j in record["joins"]])
    monkeypatch.setattr(span_window, "window_trees", trees(before))
    assert _join_record.per_query({}, "windowedBlocks") is None
    assert _join_record.per_query({}, "buildRows") == PARENT_ROWS


def test_the_blocks_are_on_the_bus_and_the_spans(spark):
    """The `join` event and the `fused.execute` span carry both
    fields; a chain program's `fused.dispatch` share the static one
    (the count is on the device until the query's fetch, like
    `buildRows`)."""
    fact, parent = tables("clustered", 1_000, False)
    _, j = joined(spark, fact, parent)
    events = spark.obs.query_events()
    (ev,) = [e for e in events if e["event"] == "join"]
    assert (ev["searchBlocks"], ev["windowedBlocks"]) == (16, 16)
    spans = [e for e in events if e["event"] == "operator.span"]
    (execute,) = [e for e in spans if e["operator"] == "fused.execute"]
    assert execute["join"]["joins"] == [j]
    (chain,) = [e for e in spans if e["operator"] == "fused.dispatch"
                and e.get("joins")]
    (share,) = chain["joins"]
    assert share["searchBlocks"] == 16
    assert "windowedBlocks" not in share and "buildRows" not in share
