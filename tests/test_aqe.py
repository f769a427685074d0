"""Adaptive query execution (plan/aqe.py): stats-driven re-planning at
exchange boundaries — broadcast-join promotion with probe-side shuffle
cancellation, and tiny-partition coalescing (reference: GpuOverrides
applied per AQE query stage, GpuOverrides.scala:517-580)."""

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSparkSession
from spark_rapids_tpu.plan.aqe import AdaptiveQueryExecutor

_CONF = {
    "spark.sql.shuffle.partitions": 8,
    # static planner must not choose broadcast up front: file-scan
    # estimates are unknown, so equi-joins plan as shuffled hash
    "spark.sql.autoBroadcastJoinThreshold": 64 << 10,
    "spark.rapids.sql.fusedExec.enabled": False,
    "spark.rapids.sql.format.parquet.reader.type": "PERFILE",
}


@pytest.fixture()
def spark():
    s = TpuSparkSession(dict(_CONF))
    yield s
    s.stop()


def _write_parts(d, table, nfiles):
    import os

    os.makedirs(d, exist_ok=True)
    per = table.num_rows // nfiles
    for i in range(nfiles):
        pq.write_table(table.slice(i * per,
                                   per if i < nfiles - 1 else None),
                       f"{d}/p{i}.parquet")
    return str(d)


def _probe_build_tables():
    """A probe side of more than half the build side's rows: an exact
    count at most half a filtered side's bound is built statically
    (plan/overrides.py `_convert_join`), and nothing is left to
    promote."""
    rng = np.random.default_rng(3)
    probe_t = pa.table({
        "k": pa.array(rng.integers(0, 500, 30_000), type=pa.int64()),
        "v": pa.array(rng.random(30_000))})
    build_t = pa.table({
        "k": pa.array(np.arange(40_000) % 500, type=pa.int64()),
        "w": pa.array(np.arange(40_000), type=pa.int64())})
    return probe_t, build_t


def _find(n, cls):
    if isinstance(n, cls):
        return n
    for c in n.children:
        r = _find(c, cls)
        if r is not None:
            return r


def test_aqe_broadcast_promotion_and_correctness(spark, tmp_path):
    from spark_rapids_tpu.exec.joins import TpuShuffledHashJoinExec

    probe_t, build_t = _probe_build_tables()
    pd_ = _write_parts(tmp_path / "probe", probe_t, 4)
    bd = _write_parts(tmp_path / "build", build_t, 4)
    probe = spark.read.parquet(pd_)
    # filters down to ~100 rows at runtime; static planner cannot know
    build = spark.read.parquet(bd).filter(F.col("w") < 100)
    df = probe.join(build, on="k", how="inner")
    phys, _ = df._physical()
    # static plan chose the shuffled join
    assert _find(phys, TpuShuffledHashJoinExec) is not None
    ex = AdaptiveQueryExecutor(spark.rapids_conf)
    out = ex.execute(phys)
    assert any("broadcast promotion" in d for d in ex.decisions), \
        ex.decisions
    assert any("cancelled" in d for d in ex.decisions), ex.decisions
    want = probe_t.join(build_t.filter(pc.less(build_t.column("w"),
                                               100)),
                        keys="k", join_type="inner")
    assert out.num_rows == want.num_rows


def test_aqe_partition_coalescing(spark, tmp_path):
    rng = np.random.default_rng(5)
    t = pa.table({"k": pa.array(rng.integers(0, 30, 5000),
                                type=pa.int64()),
                  "v": pa.array(rng.random(5000))})
    d = _write_parts(tmp_path / "agg", t, 4)
    df = (spark.read.parquet(d)
          .groupBy("k").agg(F.sum("v").alias("s"),
                            F.count("*").alias("n")))
    phys, _ = df._physical()
    ex = AdaptiveQueryExecutor(spark.rapids_conf)
    out = ex.execute(phys)
    got = {r["k"]: (r["s"], r["n"]) for r in out.to_pylist()}
    w = t.group_by("k").aggregate([("v", "sum"), ("k", "count")])
    exp = {r["k"]: (r["v_sum"], r["k_count"]) for r in w.to_pylist()}
    assert set(got) == set(exp)
    for k in exp:
        assert got[k][1] == exp[k][1]
        assert abs(got[k][0] - exp[k][0]) < 1e-9 * max(
            1.0, abs(exp[k][0]))
    assert any("coalesced" in d for d in ex.decisions), ex.decisions


def test_aqe_through_public_api_matches_oracle(tmp_path):
    """collect_arrow routes through AQE by default for exchange-bearing
    eager plans; results match the CPU oracle."""
    from spark_rapids_tpu.testing.asserts import (
        assert_tpu_and_cpu_are_equal_collect,
    )

    probe_t, build_t = _probe_build_tables()
    pd_ = _write_parts(tmp_path / "probe", probe_t, 3)
    bd = _write_parts(tmp_path / "build", build_t, 3)

    def q(s):
        probe = s.read.parquet(pd_)
        build = s.read.parquet(bd).filter(F.col("w") < 100)
        return (probe.join(build, on="k", how="inner")
                .groupBy("k").agg(F.sum("v").alias("sv"),
                                  F.count("*").alias("n")))

    assert_tpu_and_cpu_are_equal_collect(q, conf=dict(_CONF))


def test_aqe_join_sides_coalesce_together(tmp_path):
    """Coalescing must never break a shuffled join's co-partitioning:
    both sides coalesce with ONE shared grouping (or not at all) —
    independent groupings would pair mismatched pids and silently drop
    matches (Spark coordinates join-side coalescing the same way)."""
    rng = np.random.default_rng(5)
    n = 3000
    left = pa.table({
        "k": pa.array(rng.integers(0, 200, n), type=pa.int64()),
        "x": pa.array(rng.random(n))})
    right = pa.table({
        "k": pa.array(rng.integers(0, 200, n), type=pa.int64()),
        "y": pa.array(rng.random(n))})
    _write_parts(str(tmp_path / "l"), left, 4)
    _write_parts(str(tmp_path / "r"), right, 4)

    conf = dict(_CONF)
    conf["spark.sql.autoBroadcastJoinThreshold"] = -1  # keep shuffled
    s = TpuSparkSession(conf)
    try:
        df = (s.read.parquet(str(tmp_path / "l"))
              .join(s.read.parquet(str(tmp_path / "r")), on="k",
                    how="inner")
              .groupBy().agg(F.count("*").alias("c"),
                             F.sum(F.col("x") + F.col("y")).alias("sxy")))
        got = df.collect_arrow()
    finally:
        s.stop()

    lk = np.asarray(left.column("k"))
    rk = np.asarray(right.column("k"))
    lx = np.asarray(left.column("x"))
    ry = np.asarray(right.column("y"))
    # oracle pair count and sum over the inner join
    import collections

    rcnt = collections.Counter(rk.tolist())
    rsum = collections.defaultdict(float)
    for k, y in zip(rk.tolist(), ry.tolist()):
        rsum[k] += y
    want_c = sum(rcnt.get(k, 0) for k in lk.tolist())
    want_s = sum(x * rcnt.get(k, 0) + rsum.get(k, 0.0)
                 for k, x in zip(lk.tolist(), lx.tolist()))
    assert got.column("c")[0].as_py() == want_c
    np.testing.assert_allclose(got.column("sxy")[0].as_py(), want_s,
                               rtol=1e-9)


def test_skew_join_split(tmp_path):
    # one probe key dominates: AQE must slice the skewed partition and
    # re-read the build side per slice, preserving join results
    rng = np.random.default_rng(11)
    skew_n, tail_n = 60_000, 100
    lk = np.concatenate([np.zeros(skew_n, np.int64),
                         np.repeat(np.arange(1, 31), tail_n)])
    left = pa.table({"k": pa.array(lk),
                     "x": pa.array(rng.random(len(lk)))})
    rk = np.repeat(np.arange(0, 31), 3)
    right = pa.table({"k": pa.array(rk),
                      "y": pa.array(rng.random(len(rk)))})
    _write_parts(str(tmp_path / "l"), left, 4)
    _write_parts(str(tmp_path / "r"), right, 2)
    conf = dict(_CONF)
    conf.update({
        "spark.sql.autoBroadcastJoinThreshold": -1,
        "spark.rapids.sql.batchSizeBytes": 200_000,
        "spark.sql.adaptive.skewJoin.skewedPartitionThresholdInBytes":
            50_000,
        "spark.sql.shuffle.partitions": 4,
    })
    s = TpuSparkSession(conf)
    try:
        df = (s.read.parquet(str(tmp_path / "l"))
              .join(s.read.parquet(str(tmp_path / "r")), on="k",
                    how="inner"))
        phys, _ = df._physical()
        ex = AdaptiveQueryExecutor(s.rapids_conf)
        got = ex.execute(phys)
        assert any("skew split" in d for d in ex.decisions), ex.decisions
        want_rows = skew_n * 3 + 30 * tail_n * 3
        assert got.num_rows == want_rows
        # spot-check the join result on the skewed key (column 0 is the
        # join key; the joined schema may carry k from both sides)
        k0_rows = pc.sum(pc.cast(pc.equal(got.column(0), 0),
                                 pa.int64())).as_py()
        assert k0_rows == skew_n * 3
    finally:
        s.stop()


def test_skew_split_disabled_by_conf(tmp_path):
    rng = np.random.default_rng(12)
    lk = np.concatenate([np.zeros(30_000, np.int64),
                         np.repeat(np.arange(1, 11), 50)])
    left = pa.table({"k": pa.array(lk),
                     "x": pa.array(rng.random(len(lk)))})
    right = pa.table({"k": pa.array(np.arange(0, 11)),
                      "y": pa.array(rng.random(11))})
    _write_parts(str(tmp_path / "l"), left, 2)
    _write_parts(str(tmp_path / "r"), right, 1)
    conf = dict(_CONF)
    conf.update({
        "spark.sql.autoBroadcastJoinThreshold": -1,
        "spark.rapids.sql.batchSizeBytes": 100_000,
        "spark.sql.adaptive.skewJoin.skewedPartitionThresholdInBytes":
            20_000,
        "spark.sql.adaptive.skewJoin.enabled": False,
        "spark.sql.shuffle.partitions": 4,
    })
    s = TpuSparkSession(conf)
    try:
        df = (s.read.parquet(str(tmp_path / "l"))
              .join(s.read.parquet(str(tmp_path / "r")), on="k",
                    how="inner"))
        phys, _ = df._physical()
        ex = AdaptiveQueryExecutor(s.rapids_conf)
        got = ex.execute(phys)
        assert not any("skew split" in d for d in ex.decisions)
        assert got.num_rows == 30_000 + 10 * 50
    finally:
        s.stop()


def test_skew_split_single_hot_partition(tmp_path):
    # ALL rows share one key (sizes like [0,0,0,big]): the median must
    # be taken over every partition, zeros included, or the hot
    # partition becomes its own median and never qualifies
    rng = np.random.default_rng(13)
    n = 40_000
    left = pa.table({"k": pa.array(np.zeros(n, np.int64)),
                     "x": pa.array(rng.random(n))})
    right = pa.table({"k": pa.array([0], type=pa.int64()),
                      "y": pa.array([1.5])})
    _write_parts(str(tmp_path / "l"), left, 2)
    _write_parts(str(tmp_path / "r"), right, 1)
    conf = dict(_CONF)
    conf.update({
        "spark.sql.autoBroadcastJoinThreshold": -1,
        "spark.rapids.sql.batchSizeBytes": 150_000,
        "spark.sql.adaptive.skewJoin.skewedPartitionThresholdInBytes":
            50_000,
        "spark.sql.shuffle.partitions": 4,
    })
    s = TpuSparkSession(conf)
    try:
        df = (s.read.parquet(str(tmp_path / "l"))
              .join(s.read.parquet(str(tmp_path / "r")), on="k",
                    how="inner"))
        phys, _ = df._physical()
        ex = AdaptiveQueryExecutor(s.rapids_conf)
        got = ex.execute(phys)
        assert any("skew split" in d for d in ex.decisions), ex.decisions
        assert got.num_rows == n
    finally:
        s.stop()
