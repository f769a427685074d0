"""Observability subsystem (obs/): event bus, span trees, event log,
reports, Prometheus dump, metrics-level filtering.

Covers the PR-4 contracts: bus subscription under concurrency, span-tree
construction under speculation (losing attempt marked discarded), event
log rotation + atomic finalize + round-trip identity, qualification on
a CPU-fallback query matching the NOT_ON_TPU explain, and the
<5% overhead guard with the event log disabled.
"""

import itertools
import json
import os
import threading
import time

import pytest

import spark_rapids_tpu.api.functions as F
from spark_rapids_tpu.obs import eventlog, report
from spark_rapids_tpu.obs import spans as S
from spark_rapids_tpu.obs.events import (
    SCHEMA_VERSION,
    EventBus,
    EventHistory,
)


def _session(**conf):
    from spark_rapids_tpu.api.session import TpuSparkSession

    return TpuSparkSession(conf)


def _query(s, rows=600):
    df = s.createDataFrame({
        "k": [i % 7 for i in range(rows)],
        "v": [float(i) for i in range(rows)],
    })
    return (df.filter(F.col("v") > 5.0).groupBy("k")
            .agg(F.sum("v").alias("sv"), F.count("*").alias("n")))


# ------------------------------------------------------------- event bus

def test_bus_concurrent_emission_total_order():
    bus = EventBus()
    got = []
    bus.subscribe(got.append)
    n_threads, per = 8, 250

    def worker(t):
        for i in range(per):
            bus.emit("operator.span", operator=f"op{t}", wallNs=i,
                     deviceNs=0)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(got) == n_threads * per
    seqs = [e["seq"] for e in got]
    # a total order, no drops, no duplicates
    assert sorted(seqs) == list(range(1, n_threads * per + 1))
    assert bus.counts["operator.span"] == n_threads * per
    for e in got[:10]:
        assert e["schemaVersion"] == SCHEMA_VERSION
        assert "ts" in e and "queryId" in e


def test_bus_subscriber_errors_do_not_propagate():
    bus = EventBus()
    ok = []

    def bad(_ev):
        raise RuntimeError("subscriber bug")

    bus.subscribe(bad)
    bus.subscribe(ok.append)
    bus.emit("chaos", site="x")
    assert len(ok) == 1
    assert bus.subscriber_errors == 1
    bus.unsubscribe(bad)
    bus.emit("chaos", site="y")
    assert bus.subscriber_errors == 1


def test_event_history_ring_and_query_filter():
    h = EventHistory(capacity=100)
    for q in (1, 2):
        for i in range(10):
            h({"event": "compile", "queryId": q, "seq": i})
    assert h.last_query_id() == 2
    assert len(h.events(1)) == 10
    assert all(e["queryId"] == 2 for e in h.events(2))


# ------------------------------------------- span trees (incl. speculation)

def _synthetic_speculation_events():
    seq = itertools.count(1)

    def ev(event, **f):
        return {"event": event, "seq": next(seq), "ts": 0.0,
                "schemaVersion": SCHEMA_VERSION, "queryId": 1, **f}

    return [
        ev("query.start"),
        ev("stage.start", stage=5, name="result", tasks=2),
        ev("task.attempt.start", stage=5, task=0, attempt=0,
           worker="w0", speculative=False),
        ev("task.attempt.start", stage=5, task=1, attempt=0,
           worker="w1", speculative=False),
        ev("operator.span", stage=5, task=1, attempt=0,
           operator="TpuProjectExec", metric="opTime", wallNs=10_000,
           deviceNs=10_000),
        # the straggler gets a speculative duplicate...
        ev("task.attempt.start", stage=5, task=1, attempt=1,
           worker="w2", speculative=True),
        ev("operator.span", stage=5, task=1, attempt=1,
           operator="TpuProjectExec", metric="opTime", wallNs=4_000,
           deviceNs=4_000),
        # ...which commits first; the original attempt is discarded
        ev("task.attempt.end", stage=5, task=1, attempt=1, status="ok",
           wallMs=0.5, rows=10),
        ev("task.attempt.end", stage=5, task=1, attempt=0,
           status="discarded", wallMs=1.5, rows=None),
        ev("task.attempt.end", stage=5, task=0, attempt=0, status="ok",
           wallMs=0.3, rows=7),
        ev("stage.end", stage=5, name="result", status="ok"),
        ev("query.end", engine="eager", status="ok"),
    ]


def test_span_tree_speculation_loser_marked_discarded():
    trees = S.build_from_events(_synthetic_speculation_events())
    assert len(trees) == 1
    root = trees[0]
    assert root.status == "ok" and root.extra["engine"] == "eager"
    stage = root.children[0]
    assert stage.kind == "stage" and stage.name == "result"
    by_key = {(t.task, t.attempt): t for t in stage.children}
    loser = by_key[(1, 0)]
    winner = by_key[(1, 1)]
    assert loser.status == "discarded"
    assert winner.status == "ok" and winner.speculative
    # the losing attempt's operator spans are marked discarded too
    assert [c.status for c in loser.children] == ["discarded"]
    assert [c.status for c in winner.children] == ["ok"]
    # aggregation excludes discarded time but reports it separately
    totals = S.operator_totals(root)
    assert totals["TpuProjectExec"]["wallNs"] == 4_000
    assert totals["TpuProjectExec"]["discardedNs"] == 10_000
    # committed result rows come only from winning result-stage tasks
    assert S.task_rows(root) == 17
    assert S.tree_depth(root) == 4


def test_span_builder_live_query(tmp_path):
    s = _session(**{"spark.sql.shuffle.partitions": 2})
    try:
        out = _query(s).collect_arrow()
        root = s.obs.last_spans
        assert root is not None
        assert root.query_id == s.last_execution["queryId"]
        assert root.status == "ok"
        kinds = {sp.kind for sp in root.walk()}
        assert {"query", "stage", "task", "operator"} <= kinds
        assert out.num_rows == 7
    finally:
        s.stop()


# ------------------------------------------------------------- event log

def test_eventlog_rotation_and_finalize(tmp_path):
    d = str(tmp_path / "log")
    w = eventlog.EventLogWriter(d, rotate_bytes=4096)
    seq = itertools.count(1)

    def ev(event, **f):
        return {"event": event, "seq": next(seq), "ts": 1.5,
                "schemaVersion": SCHEMA_VERSION, "queryId": 3, **f}

    w(ev("query.start"))
    sent = [ev("operator.span", operator="Op" + "x" * 80,
               metric="opTime", wallNs=i, deviceNs=0)
            for i in range(120)]
    for e in sent:
        w(e)
    # still in progress: nothing finalized yet
    assert eventlog.log_files(d) == []
    assert any(p.endswith(".inprogress") for p in os.listdir(d))
    w(ev("query.end", engine="eager", status="ok"))
    files = eventlog.log_files(d, 3)
    assert len(files) > 1, "rotation should have produced parts"
    assert not any(p.endswith(".inprogress") for p in os.listdir(d))
    loaded = eventlog.load(d, 3)
    assert len(loaded) == 122
    # write order preserved across parts
    assert [e["seq"] for e in loaded] == list(range(1, 123))
    for e in loaded:
        assert eventlog.validate_event(e) == []


def test_eventlog_close_finalizes_crashed_query(tmp_path):
    d = str(tmp_path / "log")
    w = eventlog.EventLogWriter(d, rotate_bytes=1 << 20)
    w({"event": "query.start", "seq": 1, "ts": 0.0,
       "schemaVersion": SCHEMA_VERSION, "queryId": 9})
    w.close()  # session stop without query.end
    files = eventlog.log_files(d, 9)
    assert len(files) == 1
    trees = eventlog.load_spans(d, 9)
    assert trees[0].status == "unfinished"


def test_eventlog_round_trip_identical_span_tree(tmp_path):
    d = str(tmp_path / "log")
    s = _session(**{
        "spark.rapids.tpu.eventLog.enabled": True,
        "spark.rapids.tpu.eventLog.dir": d,
        "spark.sql.shuffle.partitions": 2,
    })
    try:
        _query(s).collect_arrow()
        qid = s.last_execution["queryId"]
        live = s.obs.last_spans
        trees = eventlog.load_spans(d, qid)
        assert len(trees) == 1
        assert trees[0].to_dict() == live.to_dict()
        # every line schema-validates
        for path in eventlog.log_files(d, qid):
            with open(path) as f:
                for line in f:
                    assert eventlog.validate_event(
                        json.loads(line)) == []
    finally:
        s.stop()


def test_eventlog_loader_rejects_bad_schema(tmp_path):
    p = tmp_path / "eventlog-q1-p1.jsonl"
    p.write_text('{"event": "nope.unknown", "seq": 1, "ts": 0, '
                 '"schemaVersion": 1, "queryId": 1}\n')
    with pytest.raises(eventlog.EventLogError):
        eventlog.load(str(p))
    assert eventlog.load(str(p), strict=False)


# --------------------------------------------------------------- reports

def test_qualification_on_cpu_fallback_query():
    import re

    from spark_rapids_tpu.explain import explain_potential_tpu_plan

    s = _session(**{
        "spark.rapids.sql.exec.Filter": False,
        "spark.sql.shuffle.partitions": 2,
    })
    try:
        q = _query(s)
        q.collect_arrow()
        rows = report.qualification_data(s)
        assert rows, "forced Filter fallback must appear"
        pairs = {(r["node"], r["reason"]) for r in rows}
        explain_pairs = set()
        for line in explain_potential_tpu_plan(
                q, mode="NOT_ON_TPU").splitlines():
            m = re.match(r"\s*(\w+) !NOT_ON_TPU (.+)$", line)
            if m:
                explain_pairs.add((m.group(1), m.group(2)))
        assert pairs == explain_pairs
        txt = report.qualification(s)
        assert "Filter" in txt and "kept on CPU" in txt
        prof = report.profile(s)
        assert "TPU profile" in prof and "top operators" in prof
        assert report.profile_data(s)["spanTreeDepth"] >= 3
    finally:
        s.stop()


def test_explain_executed_mode():
    from spark_rapids_tpu.explain import explain_potential_tpu_plan

    s = _session(**{"spark.sql.shuffle.partitions": 2})
    try:
        q = _query(s)
        q.collect_arrow()
        txt = explain_potential_tpu_plan(q, mode="EXECUTED")
        assert "Executed Plan" in txt
        assert "wall=" in txt and "total:" in txt
    finally:
        s.stop()


def test_prometheus_render():
    s = _session()
    try:
        _query(s).collect_arrow()
        txt = s.prometheus_metrics()
        assert "# TYPE srtpu_robustness_scheduler_tasksLaunched" in txt
        assert 'srtpu_events_total{event="query.start"}' in txt
        for line in txt.splitlines():
            assert line.startswith(("#", "srtpu_")), line
    finally:
        s.stop()


def test_robustness_metrics_keys_unchanged():
    """The unified-registry refactor must keep the exact key surface
    test_chaos.py / test_scheduler.py / bench.py consume."""
    s = _session()
    try:
        rm = s.robustness_metrics
        assert set(rm) == {"chaos", "retries", "shuffle", "scheduler",
                           "degrade", "admission", "sanitizer",
                           "device", "spill", "semaphoreTimeouts"}
        assert "queriesAdmitted" in rm["admission"]
        assert {"epoch", "fences", "recoveries"} <= set(rm["device"])
        assert "orphanedFilesSwept" in rm["spill"]
        assert set(rm["sanitizer"]) == {"cycles", "inversions",
                                        "victims", "enabled"}
        assert set(rm["shuffle"]) == {"fetchRetries", "checksumFailures",
                                      "orphanedFiles",
                                      "speculativeDiscards"}
        assert "tasksLaunched" in rm["scheduler"]
    finally:
        s.stop()


# ------------------------------------------------- metrics.level satellite

def test_metrics_level_filters_collection():
    from spark_rapids_tpu.runtime import metrics as M

    reg = M.MetricsRegistry(M.ESSENTIAL)
    dbg = reg.metric("debugOnly", M.DEBUG)
    mod = reg.metric("moderate", M.MODERATE)
    ess = reg.metric("essential", M.ESSENTIAL)
    dbg.add(5)
    mod.add(5)
    ess.add(5)
    # filtered metrics skip collection entirely (shared null sink)
    assert dbg is M.NULL_METRIC and dbg.value == 0
    assert mod is M.NULL_METRIC
    assert ess.value == 5
    assert set(reg.snapshot()) == {"essential"}
    with dbg.ns():
        pass  # no-op timing must still be a working context manager

    full = M.MetricsRegistry(M.DEBUG)
    d2 = full.metric("debugOnly", M.DEBUG)
    d2.add(3)
    assert full.snapshot()["debugOnly"] == 3


def test_metrics_level_conf_threads_into_plans():
    from spark_rapids_tpu.runtime import metrics as M

    s = _session(**{"spark.rapids.sql.metrics.level": "ESSENTIAL"})
    try:
        phys, _ = _query(s)._physical()
        assert phys.metrics.level == M.ESSENTIAL
    finally:
        s.stop()
    s = _session(**{"spark.rapids.sql.metrics.level": "DEBUG"})
    try:
        phys, _ = _query(s)._physical()
        assert phys.metrics.level == M.DEBUG
    finally:
        s.stop()


# ------------------------------------------------------- overhead guard

def test_obs_overhead_under_5pct_with_eventlog_disabled():
    """With the event log off, the always-on bus + span builder + the
    PR 6 transfer ledger (telemetry enabled, every H2D/D2H/shuffle site
    recording) must cost <5% of query wall time (plus a small absolute
    allowance for timer noise on shared CI hosts)."""

    def best_time(**conf):
        s = _session(**{"spark.sql.shuffle.partitions": 2, **conf})
        try:
            df = _query(s)
            df.collect_arrow()  # warm compiles
            best = float("inf")
            for _ in range(5):
                t0 = time.perf_counter()
                df.collect_arrow()
                best = min(best, time.perf_counter() - t0)
            return best
        finally:
            s.stop()

    t_off = best_time(**{"spark.rapids.tpu.obs.enabled": False,
                         "spark.rapids.tpu.telemetry.enabled": False})
    t_on = best_time(**{"spark.rapids.tpu.obs.enabled": True,
                        "spark.rapids.tpu.telemetry.enabled": True})
    assert t_on <= t_off * 1.05 + 0.05, (
        f"obs+telemetry overhead too high: {t_on:.4f}s with bus+ledger "
        f"vs {t_off:.4f}s without")


def test_obs_disabled_session_emits_nothing():
    from spark_rapids_tpu.obs import events as obs_events

    s = _session(**{"spark.rapids.tpu.obs.enabled": False})
    try:
        assert s.obs.bus is None and not obs_events.armed()
        _query(s).collect_arrow()
        assert s.obs.last_spans is None
        assert s.last_execution["engine"] is not None
    finally:
        s.stop()


# ------------------------------------- spans inside a query (ISSUE 24)

def _span_events(spans, qid=1, end=True):
    """A query's event stream from (name, spanId, parentId, startNs,
    endNs) rows, in the order given (a child's event comes at ITS end,
    so before its parent's)."""
    seq = itertools.count(1)

    def ev(event, **f):
        return {"event": event, "seq": next(seq), "ts": 0.0,
                "schemaVersion": SCHEMA_VERSION, "queryId": qid, **f}

    out = [ev("query.start")]
    for name, sid, pid, s, e in spans:
        out.append(ev("operator.span", operator=name, metric=None,
                      wallNs=e - s, deviceNs=0, rows=None, spanId=sid,
                      parentId=pid, startNs=s, endNs=e))
    if end:
        out.append(ev("query.end", engine="fused", status="ok"))
    return out


def test_spans_nest_by_parent_id_whatever_the_order_of_arrival():
    root, = S.build_from_events(_span_events([
        ("scan.decode", 4, 3, 110, 150),       # before its parent
        ("fused.prepare", 3, 2, 100, 200),     # before ITS parent
        ("plan", 5, 1, 10, 90),
        ("fused.execute", 2, 1, 95, 900),
        ("orphan", 7, 99, 20, 30),             # parent never reports
        ("query", 1, None, 0, 1000),
    ]))
    assert root.kind == "query" and root.name == "query-1"
    assert (root.start_ns, root.end_ns, root.wall_ns) == (0, 1000, 1000)
    # children in order of start; the orphan hangs off the root
    assert [c.name for c in root.children] == [
        "plan", "orphan", "fused.execute"]
    execute = root.children[2]
    assert [c.name for c in execute.children] == ["fused.prepare"]
    assert [c.name for c in execute.children[0].children] == [
        "scan.decode"]
    assert S.tree_depth(root) == 4
    # the orphan's [20, 30] lies inside plan's [10, 90]: counted once
    assert root.self_ns() == 1000 - 80 - 805


def test_self_time_with_overlapping_pool_thread_children():
    root, = S.build_from_events(_span_events([
        # two reader threads decode at once; an upload outlives the
        # scope that began it
        ("scan.decode", 3, 2, 100, 300),
        ("scan.decode", 4, 2, 200, 400),
        ("scan.h2d", 5, 2, 450, 700),
        ("fused.prepare", 2, 1, 50, 500),
        ("query", 1, None, 0, 1000),
    ]))
    prepare, = root.children
    # [100, 400] and [450, 500] of [50, 500]: 350 covered
    assert prepare.self_ns() == 450 - 350
    assert root.self_ns() == 1000 - 450
    totals = S.operator_totals(root)
    assert totals["scan.decode"]["wallNs"] == 400  # thread time
    assert totals["fused.prepare"]["wallNs"] == 100


def test_operator_totals_count_no_nanosecond_twice():
    """explain's `total:` line and the profile's top list sum
    operator_totals: a parent must not bring its children's time
    again."""
    root, = S.build_from_events(_span_events([
        ("fused.dispatch", 3, 2, 10, 40),
        ("fetch", 4, 2, 40, 90),
        ("fused.execute", 2, 1, 5, 95),
        ("query", 1, None, 0, 100),
    ]))
    totals = S.operator_totals(root)
    assert sum(t["wallNs"] for t in totals.values()) == 90
    assert totals["fused.execute"]["wallNs"] == 10
    s = _session()
    try:
        from spark_rapids_tpu.obs import telemetry

        q = _query(s)
        q.collect_arrow()
        assert telemetry.drain_uploads(10.0)
        live = s.obs.last_spans
        assert live.extra["engine"] == "fused"
        totals = S.operator_totals(live)
        # (an upload's span runs beside the thread that began it)
        h2d = totals["scan.h2d"]["wallNs"]
        summed = sum(t["wallNs"] for t in totals.values())
        assert 0 < summed - h2d <= live.wall_ns
        from spark_rapids_tpu.explain import explain_potential_tpu_plan

        txt = explain_potential_tpu_plan(q, mode="EXECUTED")
        total_ms = float(txt.split("total: wall=")[1].split("ms")[0])
        assert total_ms == pytest.approx(summed / 1e6, abs=0.01)
    finally:
        s.stop()


def test_operator_spans_start_before_they_end():
    evs = _span_events([("plan", 2, 1, 10, 90),
                        ("query", 1, None, 0, 100)], end=False)
    # an emitter that gives a duration only (at its end)
    evs.append({"event": "operator.span", "seq": 99, "ts": 2.0,
                "schemaVersion": SCHEMA_VERSION, "queryId": 1,
                "operator": "DeviceRecovery", "wallNs": 500_000_000,
                "deviceNs": 0})
    root, = S.build_from_events(evs)
    ops = [sp for sp in root.walk() if sp.kind == "operator"]
    assert len(ops) == 2
    for sp in ops:
        assert sp.start_ts < sp.end_ts
        assert sp.end_ns - sp.start_ns == sp.wall_ns
    old, = [sp for sp in ops if sp.name == "DeviceRecovery"]
    assert (old.start_ts, old.end_ts) == (1.5, 2.0)


def test_a_late_span_is_counted_and_still_hung_in_its_tree():
    evs = _span_events([("fused.prepare", 2, 1, 10, 50),
                        ("query", 1, None, 0, 100)])
    late, = _span_events([("scan.h2d", 3, 2, 20, 120)])[1:2]
    b = S.SpanBuilder()
    for ev in evs + [late]:
        b(ev)
    assert b.late_spans == 1
    assert [c.name for c in b.last.children[0].children] == ["scan.h2d"]
    # a span of a query the builder never saw is counted, nothing more
    b({**late, "queryId": 77})
    assert b.late_spans == 2


def test_explicit_parent_and_query_id_across_a_thread_pool():
    from concurrent.futures import ThreadPoolExecutor

    from spark_rapids_tpu.obs import ObsManager, telemetry
    from spark_rapids_tpu.obs import events as E

    obs = ObsManager()
    try:
        qid = E.begin_query()
        before = telemetry.ledger.query_summary(qid)["bytesMovedTotal"]
        with E.span("fused.prepare") as prepare:
            parent = E.current_span()
            assert parent == prepare.ref and parent.query_id == qid

            def work(i):
                assert E.effective_query_id() == 0  # a bare pool thread
                with E.span("scan.decode", parent=parent, path=str(i)):
                    assert E.effective_query_id() == qid
                    telemetry.record("h2d", "test.pool", 1000)
                    with E.span("inner"):  # nests by the thread's stack
                        pass
                assert E.effective_query_id() == 0
                E.record_span("scan.h2d", 1, 2, parent=parent, site="x")

            with ThreadPoolExecutor(max_workers=3) as pool:
                list(pool.map(work, range(3)))
        E.finish_query(qid, engine="fused", status="ok")
        root = obs.last_spans
        assert root.query_id == qid
        prepare_sp, = root.children
        names = sorted(c.name for c in prepare_sp.children)
        assert names == ["scan.decode"] * 3 + ["scan.h2d"] * 3
        for c in prepare_sp.children:
            assert c.query_id == qid
            if c.name == "scan.decode":
                assert [g.name for g in c.children] == ["inner"]
        moved = telemetry.ledger.query_summary(qid)["bytesMovedTotal"]
        assert moved - before == 3000  # the pool threads' rows are ITS
    finally:
        obs.close()


def test_fused_query_tree_and_the_ring_that_survives_stop(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.obs import telemetry

    d = tmp_path / "t"
    d.mkdir()
    for i in range(3):
        pq.write_table(pa.table({
            "k": [j % 5 for j in range(2000)],
            "v": [float(j + i) for j in range(2000)]}),
            str(d / f"p{i}.parquet"))
    s = _session()
    try:
        q = (s.read.parquet(str(d)).filter(F.col("v") > 5.0)
             .groupBy("k").agg(F.sum("v").alias("sv")))
        q.collect_arrow()
        assert s.last_execution["engine"] == "fused"
        assert telemetry.drain_uploads(10.0)
        root = s.obs.last_spans
        assert root.span_id is not None and root.end_ns > root.start_ns
        assert root.extra["engine"] == "fused"
        top = [c.name for c in root.children]
        assert top.count("plan") == 1 and top.count("fused.execute") == 1
        execute, = [c for c in root.children if c.name == "fused.execute"]
        inner = [c.name for c in execute.children]
        assert inner[0] == "fused.prepare" and inner[-1] == "fetch"
        assert inner.count("fused.dispatch") >= 2
        for sp in execute.children:
            assert sp.device_ns == 0  # no device time was measured
            if sp.name == "fused.dispatch":
                assert sp.extra["program"].startswith("fused_")
        prepare = execute.children[0]
        scans = [c.name for c in prepare.children]
        assert scans.count("scan.decode") == scans.count("scan.h2d") >= 1
        for sp in root.walk():  # every span lies on one clock
            assert root.start_ns <= sp.start_ns <= sp.end_ns
        # closure: the root's children and its self time are all of it
        assert root.self_ns() + sum(
            c.wall_ns for c in root.children) == root.wall_ns
        assert s.obs.spans.late_spans == 0
    finally:
        s.stop()
    assert S.ring.last(1)[0] is root  # outlives the session


def test_eventlog_rebuilds_the_nested_tree(tmp_path):
    d = str(tmp_path / "log")
    s = _session(**{"spark.rapids.tpu.eventLog.enabled": True,
                    "spark.rapids.tpu.eventLog.dir": d})
    try:
        _query(s).collect_arrow()
        assert s.last_execution["engine"] == "fused"
        qid = s.last_execution["queryId"]
        live = s.obs.last_spans
    finally:
        s.stop()
    loaded, = eventlog.load_spans(d, qid)
    assert loaded.to_dict() == live.to_dict()
    execute, = [c for c in loaded.children if c.name == "fused.execute"]
    assert {"fused.prepare", "fused.dispatch", "fetch"} <= {
        c.name for c in execute.children}
    for ev in eventlog.load(d, qid):
        if ev["event"] == "operator.span":
            assert ev["startNs"] <= ev["endNs"] and ev["spanId"]


def test_span_with_the_bus_off_emits_nothing():
    from spark_rapids_tpu.obs import events as E

    assert not E.armed()
    seen = []
    bus = EventBus()
    bus.subscribe(seen.append)  # a bus nobody installed
    with E.span("plan", nodes=3) as sp:
        assert sp.ref is None
        sp.set(rows=1)
        assert E.current_span().span_id is None
    E.record_span("scan.h2d", 1, 2)
    assert seen == [] and sp.fields == {"nodes": 3}
