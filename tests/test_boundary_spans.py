"""The spans of the query boundary: `fetch.wait` under `fetch` on each
fetch path (small result, large result, the device parts of a cache
fill), `fused.enqueue` under a dispatch of a built program and not
under the one that builds it, `plan.convert` under `plan`; and the
fields nothing read (`flagsNs`, `cacheHit`) are gone."""

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import spark_rapids_tpu.api.functions as F
from spark_rapids_tpu.api.session import TpuSparkSession
from spark_rapids_tpu.runtime import jit_cache


def _session(**conf):
    return TpuSparkSession({"spark.rapids.tpu.compileCache.enabled": False,
                            **conf})


def _query(s, rows=900):
    df = s.createDataFrame({"k": [i % 7 for i in range(rows)],
                            "v": [float(i) for i in range(rows)]})
    return (df.filter(F.col("v") > 5.0).groupBy("k")
            .agg(F.sum("v").alias("sv")).orderBy("k"))


def _named(root, name):
    return [sp for sp in root.walk() if sp.name == name]


def _children(sp, name):
    return [c for c in sp.children if c.name == name]


def _fetch_waits(root):
    fetches = _named(root, "fetch")
    assert fetches
    for f in fetches:
        wait, = _children(f, "fetch.wait")
        assert f.start_ns <= wait.start_ns <= wait.end_ns <= f.end_ns
        assert not wait.children
    return fetches


@pytest.fixture
def session():
    s = _session()
    yield s
    s.stop()


def test_cold_and_hot_dispatch_and_the_small_fetch(session):
    jit_cache.clear()  # the first run builds every program
    q = _query(session)
    trees = []
    for _ in range(2):
        q.collect_arrow()
        assert session.last_execution["engine"] == "fused"
        trees.append(session.obs.last_spans)
    cold, hot = trees
    for sp in _named(cold, "fused.dispatch"):
        assert [c.name for c in sp.children] == ["compile"]
    dispatches = _named(hot, "fused.dispatch")
    assert len(dispatches) >= 2
    for sp in dispatches:
        enqueue, = sp.children
        assert enqueue.name == "fused.enqueue" and not enqueue.children
        assert sp.start_ns <= enqueue.start_ns <= enqueue.end_ns <= sp.end_ns
    for root in trees:
        _fetch_waits(root)
        plan, = _children(root, "plan")
        convert, = plan.children
        assert convert.name == "plan.convert"
        assert plan.start_ns <= convert.start_ns <= convert.end_ns \
            <= plan.end_ns
        for sp in root.walk():
            if sp.kind == "operator":
                assert "flagsNs" not in sp.extra
                assert "cacheHit" not in sp.extra


def test_the_large_result_fetch_waits_too():
    s = _session(**{"spark.rapids.sql.fusedExec.singleSyncFetchMaxBytes": 0})
    try:
        _query(s).collect_arrow()
        assert s.last_execution["engine"] == "fused"
        fetch, = _fetch_waits(s.obs.last_spans)
        assert fetch.extra["rows"] == 7 and fetch.extra["bytes"] > 0
    finally:
        s.stop()


def test_the_fetch_of_a_cache_fills_parts_waits_too(session, tmp_path):
    d = tmp_path / "t"
    d.mkdir()
    for i in range(2):
        pq.write_table(pa.table({"k": [j % 5 for j in range(700)],
                                 "v": [float(j + i) for j in range(700)]}),
                       str(d / f"p{i}.parquet"))
    cached = (session.read.parquet(str(d)).filter(F.col("v") > 3.0)
              .cache(storage="device"))
    cached.groupBy("k").agg(F.sum("v").alias("sv")).collect_arrow()
    assert session.last_execution["engine"] == "fused"
    fetches = _fetch_waits(session.obs.last_spans)
    # the fill's parts stay on the device: its fetch takes the flags
    assert any(f.extra["rows"] == 0 for f in fetches)
