"""What a fused query's UNION ALLs and Expands did: the `union` and
`expand` records of `session.last_execution`, the same records on the
query's `fused.execute` span, and each program's share of the Expands
on its `fused.dispatch` span (exec/fused.py `emit_parts`,
`chain_traced`; ops/common.py `note_expand`)."""

import collections

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSparkSession

#: rows of each file of the two branches: two files and one, the first
#: wider than a small file's capacity (65,536 slots)
FILES = {"a": [70_000, 2_000], "b": [1_500]}


@pytest.fixture()
def spark():
    s = TpuSparkSession({"spark.sql.shuffle.partitions": 4})
    yield s
    s.stop()


@pytest.fixture(scope="module")
def branches(tmp_path_factory):
    """-> {branch: (directory, its rows as one table)}: keys k in 0..4,
    ids in 0..9 as strings, a value to sum."""
    out = {}
    for name, sizes in FILES.items():
        d = tmp_path_factory.mktemp(name)
        parts = []
        for i, n in enumerate(sizes):
            rng = np.random.default_rng([len(name), i])
            t = pa.table({
                "k": pa.array(rng.integers(0, 5, n), pa.int32()),
                "id": pa.array([f"id{v}" for v in rng.integers(0, 10, n)]),
                "v": pa.array(rng.integers(0, 1_000, n) / 4.0)})
            pq.write_table(t, f"{d}/part-{i:02d}.parquet")
            parts.append(t)
        out[name] = (str(d), pa.concat_tables(parts))
    return out


def rolled_up(spark, branches):
    a, b = (spark.read.parquet(branches[n][0]).cache(storage="device")
            for n in ("a", "b"))
    return (a.unionAll(b).rollup(F.col("k"), F.col("id"))
            .agg(F.sum("v").alias("v")))


def plain_rollup(branches) -> dict:
    """(k, id) -> sum of v, with the subtotals (k, None) and the grand
    total (None, None)."""
    rows = pa.concat_tables([t for _, t in branches.values()])
    out = collections.defaultdict(float)
    for k, i, v in zip(*(rows.column(c).to_pylist() for c in ("k", "id",
                                                             "v"))):
        for key in ((k, i), (k, None), (None, None)):
            out[key] += v
    return dict(out)


def test_a_rollup_over_a_union_records_both(spark, branches):
    got = rolled_up(spark, branches).collect_arrow()
    rec = spark.last_execution
    assert rec["engine"] == "fused" and not rec["fallbacks"]
    want = plain_rollup(branches)
    answer = {(k, i): v for k, i, v in zip(
        *(got.column(c).to_pylist() for c in ("k", "id", "v")))}
    assert answer.keys() == want.keys()
    assert all(answer[key] == pytest.approx(want[key]) for key in want)

    (union,) = rec["union"]["unions"]
    assert union["branches"] == 2 and union["laidIntoOne"] is True
    assert union["paddedSlots"] == 0
    assert union["parts"] == len(FILES["a"]) + len(FILES["b"])
    rows = [sum(FILES["a"]), sum(FILES["b"])]
    assert all(slots >= n for slots, n in zip(union["branchSlots"], rows))

    expand = rec["expand"]
    assert expand["lowerings"]
    for e in expand["lowerings"]:
        # ROLLUP(k, id): (k, id), (k), ()
        assert e["sets"] == 3
        assert e["outputSlots"] == e["sets"] * e["inputSlots"]
        assert e["program"].startswith("fused_")
    # the Expand runs over every slot the union handed on
    assert sum(e["inputSlots"] for e in expand["lowerings"]) == \
        sum(union["branchSlots"])


def test_a_union_of_parts_too_wide_to_lay_into_one_pads_them(
        spark, branches, monkeypatch):
    """Parts of unequal capacities are padded to the widest where that
    adds at most half the Union's slots: ONE program above the Union
    for every part, and the same answer."""
    from spark_rapids_tpu.exec import fused

    want = plain_rollup(branches)
    monkeypatch.setattr(fused, "_UNION_LAID_SLOTS", 0)
    query = rolled_up(spark, branches)
    query.collect_arrow()  # fills the device caches
    got = query.collect_arrow()
    rec = spark.last_execution
    assert rec["engine"] == "fused" and not rec["fallbacks"]
    (union,) = rec["union"]["unions"]
    assert union["laidIntoOne"] is False and union["paddedSlots"] > 0
    lowerings = rec["expand"]["lowerings"]
    widths = [e["inputSlots"] for e in lowerings]
    assert len(widths) == 3 and len(set(widths)) == 1
    assert sum(widths) == sum(union["branchSlots"]) + union["paddedSlots"]
    assert len({e["program"] for e in lowerings}) == 1
    programs = [e["program"] for e in spark.obs.query_events()
                if e["event"] == "operator.span"
                and e["operator"] == "fused.dispatch"]
    assert sum(p.startswith("fused_pad_") for p in programs) == 2
    answer = {(k, i): v for k, i, v in zip(
        *(got.column(c).to_pylist() for c in ("k", "id", "v")))}
    assert answer.keys() == want.keys()
    assert all(answer[key] == pytest.approx(want[key]) for key in want)


def test_the_records_are_on_the_spans(spark, branches):
    query = rolled_up(spark, branches)
    query.collect_arrow()  # fills the device caches
    query.collect_arrow()
    rec = spark.last_execution
    spans = [e for e in spark.obs.query_events()
             if e["event"] == "operator.span"]
    (execute,) = [e for e in spans if e["operator"] == "fused.execute"]
    assert execute["union"] == rec["union"]
    assert execute["expand"] == rec["expand"]
    shares = [x for e in spans if e["operator"] == "fused.dispatch"
              for x in e.get("expand", [])]
    assert sorted(s["outputSlots"] for s in shares) == sorted(
        e["outputSlots"] for e in rec["expand"]["lowerings"])


def test_a_query_without_a_union_or_an_expand_has_neither(spark, branches):
    a = spark.read.parquet(branches["a"][0])
    a.groupBy("k").agg(F.sum("v")).collect_arrow()
    rec = spark.last_execution
    assert rec["engine"] == "fused"
    assert rec["union"] is None and rec["expand"] is None
