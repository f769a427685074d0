"""Data-movement telemetry (obs/telemetry.py, PR 6): transfer-ledger
totals vs real collect sizes, HBM occupancy high-water vs the spill
catalog's own peak, roofline summary plumbing into
last_execution/profile/Prometheus, per-query event-log isolation for
concurrent tenants, process-pool event forwarding, Prometheus label
escaping, and the live HTTP endpoint's lifecycle."""

import itertools
import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu.api.functions as F
from spark_rapids_tpu.obs import eventlog, prom, telemetry
from spark_rapids_tpu.obs import events as obs_events
from spark_rapids_tpu.obs.events import SCHEMA_VERSION


def _session(**conf):
    from spark_rapids_tpu.api.session import TpuSparkSession

    return TpuSparkSession(conf)


def _table(rows=4096):
    return pa.table({
        "k": pa.array(np.arange(rows) % 11, type=pa.int64()),
        "v": pa.array(np.arange(rows, dtype=np.float64)),
    })


# ---------------------------------------------------------- the ledger

def test_ledger_totals_match_collect_sizes():
    """The h2d side of the ledger must cover the uploaded input (padded
    capacity buckets inflate it by a bounded factor), the d2h side the
    collected output — per query, within tolerance."""
    s = _session(**{"spark.sql.shuffle.partitions": 2})
    try:
        t = _table()
        df = (s.createDataFrame(t).filter(F.col("v") >= 0.0)
              .groupBy("k").agg(F.sum("v").alias("sv")))
        out = df.collect_arrow()
        tel = s.last_execution["telemetry"]
        assert tel is not None
        h2d = tel["bytesMoved"].get("h2d", 0)
        d2h = tel["bytesMoved"].get("d2h", 0)
        # uploads cover the input within a bounded factor: integer
        # narrowing can SHRINK the on-wire bytes (int64 keys ship at
        # observed width), padding/validity/variants can inflate them
        assert h2d >= 0.4 * t.nbytes, (h2d, t.nbytes)
        assert h2d <= 64 * t.nbytes, (h2d, t.nbytes)
        assert d2h > 0
        assert tel["bytesMovedTotal"] == sum(
            tel["bytesMoved"].values())
        assert tel["transfers"] >= 2
        assert tel["bytesPerOutputRow"] == pytest.approx(
            tel["bytesMovedTotal"] / out.num_rows, rel=1e-3)
        assert tel["wallMs"] > 0 and 0 <= tel["rooflineFrac"] <= 1.0
        # the per-site view decomposes the same bytes
        site_total = sum(c["bytes"] for c in tel["perSite"].values())
        assert site_total == tel["bytesMovedTotal"]
    finally:
        s.stop()


def test_hbm_highwater_matches_catalog_peak():
    """The occupancy timeline's per-query high-water must equal the
    catalog pool's own peak when one query owns every reservation."""
    from spark_rapids_tpu.columnar.arrow_bridge import arrow_to_device
    from spark_rapids_tpu.runtime.memory import SpillCatalog

    s = _session()
    try:
        qid = obs_events.begin_query()
        try:
            cat = SpillCatalog(device_limit=1 << 30,
                               host_limit=1 << 30)
            b1 = cat.add_batch(arrow_to_device(_table(2048)))
            b2 = cat.add_batch(arrow_to_device(_table(1024)))
            b1.close()
            b3 = cat.add_batch(arrow_to_device(_table(512)))
            b2.close()
            b3.close()
        finally:
            obs_events.finish_query(qid)
        summ = telemetry.query_summary(qid)
        assert summ["hbmPeakBytes"] == cat.pool.peak > 0
        # the process high-water covers this catalog's peak too
        assert telemetry.ledger.hbm_peak >= cat.pool.peak
        assert cat.buffer_count() == 0
    finally:
        s.stop()


def test_spill_transfers_recorded_per_direction():
    """Forced spill down to disk and back records d2h, spill-disk and
    h2d entries attributed to the owning query."""
    from spark_rapids_tpu.columnar.arrow_bridge import arrow_to_device
    from spark_rapids_tpu.runtime.memory import SpillCatalog, SpillTier

    s = _session()
    try:
        qid = obs_events.begin_query()
        try:
            cat = SpillCatalog(device_limit=1 << 30,
                               host_limit=1 << 30)
            sb = cat.add_batch(arrow_to_device(_table(2048)))
            cat.spill_device_bytes(sb.size_bytes)     # -> HOST (d2h)
            assert sb.tier == SpillTier.HOST
            cat.spill_host_bytes(sb.size_bytes)       # -> DISK
            assert sb.tier == SpillTier.DISK
            sb.get_batch()                            # unspill (h2d)
            assert sb.tier == SpillTier.DEVICE
            sb.close()
        finally:
            obs_events.finish_query(qid)
        sites = telemetry.query_summary(qid)["perSite"]
        assert sites["spill.toHost"]["bytes"] == sb.size_bytes
        assert sites["spill.toDisk"]["bytes"] == sb.size_bytes
        assert sites["spill.fromDisk"]["bytes"] == sb.size_bytes
        assert sites["spill.unspill"]["bytes"] == sb.size_bytes
        moved = telemetry.query_summary(qid)["bytesMoved"]
        assert moved["spill-disk"] == 2 * sb.size_bytes
    finally:
        s.stop()


def test_telemetry_disabled_is_inert():
    s = _session(**{"spark.rapids.tpu.telemetry.enabled": False})
    try:
        df = s.createDataFrame(_table(256)).groupBy("k").agg(
            F.count("*").alias("n"))
        df.collect_arrow()
        assert s.last_execution["telemetry"] is None
    finally:
        telemetry.ledger.enabled = True  # process state: restore
        s.stop()


def test_link_peaks_probe_and_cache():
    peaks = telemetry.link_peaks()
    assert peaks["devicePeakBytesPerS"] > 0
    assert peaks["roundTripMs"] > 0
    assert peaks["h2dBytesPerS"] > 0 and peaks["d2hBytesPerS"] > 0
    assert peaks is telemetry.link_peaks()  # in-process cache


@pytest.mark.parametrize("kind", ["TPU v7x", "NVIDIA H100", "unknown"])
def test_unknown_device_kind_is_an_error(kind):
    """No default row: a CPU's (or any other chip's) peak under an
    unlisted device's name is a wrong roofline, not a safe one."""
    with pytest.raises(KeyError, match="DEVICE_PEAK_BW"):
        telemetry.device_peak_bw(kind)


@pytest.mark.parametrize("kind,peak", [
    ("TPU v5 lite", 8.19e11), ("TPU v5e", 8.19e11), ("cpu", 5.0e10)])
def test_known_device_kinds_resolve(kind, peak):
    assert telemetry.device_peak_bw(kind) == peak


def test_link_probe_of_an_unlisted_device_raises(monkeypatch):
    monkeypatch.setattr(telemetry, "_device_kind", lambda: "TPU v9")
    monkeypatch.setattr(telemetry, "_peaks", None)
    monkeypatch.setattr(telemetry, "_peaks_path", lambda: None)
    with pytest.raises(KeyError, match="TPU v9"):
        telemetry.link_peaks(refresh=True)


def test_cached_peaks_of_another_device_kind_are_reprobed(
        tmp_path, monkeypatch):
    """CPU rehearsals and chip runs may share one cache directory: a
    peaks file measured on another kind must not be served."""
    path = str(tmp_path / "telemetry_peaks.json")
    with open(path, "w") as f:
        json.dump({"deviceKind": "TPU v5 lite",
                   "devicePeakBytesPerS": 8.19e11,
                   "h2dBytesPerS": 1.0, "d2hBytesPerS": 1.0,
                   "roundTripMs": 1.0, "probeBytes": 1}, f)
    monkeypatch.setattr(telemetry, "_peaks", None)
    monkeypatch.setattr(telemetry, "_peaks_path", lambda: path)
    peaks = telemetry.link_peaks()
    assert peaks["deviceKind"] == "cpu"
    with open(path) as f:
        assert json.load(f)["deviceKind"] == "cpu"  # rewritten


# ----------------------------------------------- telemetry.summary event

def test_summary_event_in_stream_and_profile(tmp_path):
    from spark_rapids_tpu.obs import report

    d = str(tmp_path / "log")
    s = _session(**{
        "spark.rapids.tpu.eventLog.enabled": True,
        "spark.rapids.tpu.eventLog.dir": d,
        "spark.sql.shuffle.partitions": 2,
    })
    try:
        (s.createDataFrame(_table()).groupBy("k")
         .agg(F.sum("v").alias("sv"))).collect_arrow()
        qid = s.last_execution["queryId"]
        tel = s.last_execution["telemetry"]
        events = eventlog.load(d, qid)
        summaries = [e for e in events
                     if e["event"] == "telemetry.summary"]
        assert len(summaries) == 1
        assert summaries[0]["bytesMoved"] == tel["bytesMoved"]
        by_dir = {}
        for e in events:
            if e["event"] == "transfer":
                by_dir[e["direction"]] = \
                    by_dir.get(e["direction"], 0) + e["bytes"]
        assert by_dir == tel["bytesMoved"]
        prof = report.profile_data(d)
        assert prof["telemetry"]["bytesMovedTotal"] == \
            tel["bytesMovedTotal"]
        assert {k: v["bytes"] for k, v in
                prof["dataMovement"].items()} == tel["bytesMoved"]
        txt = report.profile(d)
        assert "data movement:" in txt and "roofline:" in txt
    finally:
        s.stop()


def test_explain_executed_reports_data_moved():
    from spark_rapids_tpu.explain import explain_potential_tpu_plan

    s = _session(**{"spark.sql.shuffle.partitions": 2})
    try:
        q = (s.createDataFrame(_table()).groupBy("k")
             .agg(F.sum("v").alias("sv")))
        q.collect_arrow()
        txt = explain_potential_tpu_plan(q, mode="EXECUTED")
        assert "data moved:" in txt and "roofline_frac" in txt
    finally:
        s.stop()


# ------------------------------------------------- per-query event logs

def test_eventlog_concurrent_queries_isolated(tmp_path):
    """Two queries interleaving on the bus land in isolated per-query
    files, each replaying to its own identical span tree."""
    d = str(tmp_path / "log")
    w = eventlog.EventLogWriter(d, rotate_bytes=4096)
    seq = itertools.count(1)

    def ev(event, qid, **f):
        return {"event": event, "seq": next(seq), "ts": 0.0,
                "schemaVersion": SCHEMA_VERSION, "queryId": qid, **f}

    w(ev("query.start", 1))
    w(ev("query.start", 2))
    for i in range(60):  # crosses the rotation threshold for both
        w(ev("operator.span", 1, operator="OpA" + "x" * 60,
             metric="m", wallNs=i, deviceNs=0))
        w(ev("operator.span", 2, operator="OpB" + "y" * 60,
             metric="m", wallNs=i, deviceNs=0))
    w(ev("query.end", 1, engine="eager", status="ok"))
    # query 2 keeps writing AFTER query 1 finalized
    w(ev("operator.span", 2, operator="late", metric="m", wallNs=1,
         deviceNs=0))
    w(ev("query.end", 2, engine="eager", status="ok"))
    assert w.open_query_ids() == []
    l1 = eventlog.load(d, 1)
    l2 = eventlog.load(d, 2)
    assert all(e["queryId"] == 1 for e in l1) and len(l1) == 62
    assert all(e["queryId"] == 2 for e in l2) and len(l2) == 63
    assert len(eventlog.log_files(d, 1)) > 1  # rotation still works
    t1 = eventlog.load_spans(d, 1)
    t2 = eventlog.load_spans(d, 2)
    assert [t.query_id for t in t1] == [1]
    assert [t.query_id for t in t2] == [2]
    ops2 = [sp.name for sp in t2[0].walk() if sp.kind == "operator"]
    assert "late" in ops2 and not any("OpA" in o for o in ops2)


def test_eventlog_live_concurrent_sessions_round_trip(tmp_path):
    """Two live queries submitted from two threads of one session get
    isolated logs that replay to the live trees (the PR 5 NOTE)."""
    d = str(tmp_path / "log")
    s = _session(**{
        "spark.rapids.tpu.eventLog.enabled": True,
        "spark.rapids.tpu.eventLog.dir": d,
        "spark.sql.shuffle.partitions": 2,
    })
    try:
        start = threading.Barrier(2)

        def run():
            start.wait()
            (s.createDataFrame(_table()).filter(F.col("v") > 1.0)
             .groupBy("k").agg(F.sum("v").alias("sv"))).collect_arrow()

        threads = [threading.Thread(target=run) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        live = {t.query_id: t for t in s.obs.spans.completed}
        qids = sorted(live)[-2:]
        assert len(qids) == 2
        for q in qids:
            trees = eventlog.load_spans(d, q)
            assert len(trees) == 1
            assert trees[0].to_dict() == live[q].to_dict()
            for e in eventlog.load(d, q):
                assert e["queryId"] == q
    finally:
        s.stop()


# -------------------------------------------- process-pool forwarding

def test_process_pool_forwards_spans_and_transfers(tmp_path):
    """ProcessBackend attempts forward their operator spans + transfer
    records to the driver bus: the span tree matches an in-process
    shape, the event log round-trips identically, and worker bytes
    land in the driver ledger."""
    import pyarrow.parquet as pq

    from spark_rapids_tpu.parallel.process_pool import (
        ProcessBackend,
        ProcessWorkerPool,
    )
    from spark_rapids_tpu.runtime.scheduler import StageScheduler, Task

    frag = ("spark_rapids_tpu.parallel.process_pool:"
            "run_scan_agg_fragment")
    files = []
    for i in range(4):
        p = str(tmp_path / f"p{i}.parquet")
        pq.write_table(_table(512), p)
        files.append(p)
    d = str(tmp_path / "log")
    s = _session(**{
        "spark.rapids.tpu.eventLog.enabled": True,
        "spark.rapids.tpu.eventLog.dir": d,
    })
    pool = ProcessWorkerPool(2)
    try:
        qid = obs_events.begin_query()
        try:
            tasks = [Task(i, payload=(frag, {
                "files": [f], "keys": ["k"], "aggs": [("v", "sum")]}))
                for i, f in enumerate(files)]
            out = StageScheduler(
                None, name="mp-obs",
                backend=ProcessBackend(pool)).run(tasks)
            assert len(out) == 4
        finally:
            obs_events.finish_query(qid, engine="mp", status="ok",
                                    fallbacks=0, degradations=0)
        live = s.obs.spans.last
        assert live is not None and live.query_id == qid
        frag_spans = [sp for sp in live.walk()
                      if sp.kind == "operator"
                      and sp.name == "ScanAggFragment"]
        assert len(frag_spans) == 4
        # every forwarded span hangs under its task attempt
        assert all(sp.task is not None and sp.stage is not None
                   for sp in frag_spans)
        assert all(sp.wall_ns > 0 and sp.rows for sp in frag_spans)
        trees = eventlog.load_spans(d, qid)
        assert trees[0].to_dict() == live.to_dict()
        moved = telemetry.query_summary(qid)["bytesMoved"]
        assert moved.get("shuffle", 0) > 0  # worker result bytes
    finally:
        pool.close()
        s.stop()


# ---------------------------------------------------- prometheus format

def test_prom_label_escaping():
    assert prom.escape_label('plain') == 'plain'
    assert prom.escape_label('a"b') == r'a\"b'
    assert prom.escape_label('a\\b') == r'a\\b'
    assert prom.escape_label('a\nb') == r'a\nb'
    # backslash escapes FIRST: a literal \" must not double-escape
    assert prom.escape_label('\\"') == r'\\\"'


def test_prom_render_escapes_hostile_site_labels():
    """A site/operator name carrying quotes, backslashes or newlines
    must still produce parseable exposition text."""
    hostile = 'we"ird\\site\nname'
    telemetry.record("h2d", hostile, 1234, emit=False)
    try:
        txt = prom.render()
        line = next(l for l in txt.splitlines()
                    if "srtpu_transfer_bytes_total" in l
                    and "weird" not in l and "we" in l and "1234" in l)
        assert "\n" not in line
        assert r'we\"ird\\site\nname' in line
        # label section has balanced, parseable quoting once escape
        # sequences are consumed
        labels = line[line.index("{") + 1:line.rindex("}")]
        unescaped = labels.replace("\\\\", "").replace('\\"', "")
        assert unescaped.count('"') % 2 == 0, labels
        assert "\\" not in unescaped.replace("\\n", ""), labels
        for sample in txt.splitlines():
            assert sample.startswith(("#", "srtpu_")), sample
    finally:
        with telemetry.ledger._lock:
            telemetry.ledger.sites.pop(hostile, None)
            telemetry.ledger._site_dir.pop(hostile, None)


def test_prom_per_query_telemetry_families():
    s = _session()
    try:
        (s.createDataFrame(_table()).groupBy("k")
         .agg(F.sum("v").alias("sv"))).collect_arrow()
        qid = s.last_execution["queryId"]
        txt = s.prometheus_metrics()
        assert f'srtpu_query_bytes_moved{{queryId="{qid}"' in txt
        assert f'srtpu_query_hbm_peak_bytes{{queryId="{qid}"}}' in txt
        assert f'srtpu_query_roofline_frac{{queryId="{qid}"}}' in txt
        assert "srtpu_hbm_peakBytes" in txt
        assert "srtpu_transfer_bytes_total{" in txt
    finally:
        s.stop()


# -------------------------------------------------------- http endpoint

def test_http_endpoint_serves_and_shuts_down():
    s = _session(**{"spark.rapids.tpu.obs.http.enabled": True})
    try:
        (s.createDataFrame(_table()).groupBy("k")
         .agg(F.sum("v").alias("sv"))).collect_arrow()
        port = s.obs.http.port
        assert port > 0
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ).read().decode()
        for line in body.splitlines():
            assert line.startswith(("#", "srtpu_")), line
        q = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/queries", timeout=10
        ).read().decode())
        assert "admission" in q and "queries" in q
        qid = str(s.last_execution["queryId"])
        assert qid in q["queries"]
        assert q["queries"][qid]["bytesMovedTotal"] > 0
        assert urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10
        ).read() == b"ok\n"
    finally:
        s.stop()
    # leak-free: the thread is gone and the socket refuses
    assert not any(t.name == "srtpu-obs-http" and t.is_alive()
                   for t in threading.enumerate())
    with pytest.raises((urllib.error.URLError, ConnectionError,
                        OSError)):
        urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz",
                               timeout=2)


def test_http_disabled_by_default():
    s = _session()
    try:
        assert s.obs.http is None
    finally:
        s.stop()


# ------------------------------- uploads timed to completion (ISSUE 24)

def test_scan_upload_belongs_to_its_query_and_is_timed_to_completion(
        tmp_path, monkeypatch):
    """An uncached scan uploads from reader-pool threads, which have no
    query scope: the bytes must land in the QUERY's ledger all the
    same, and the `scan.upload` row's ns is the time to the transfer's
    completion (here held back by a forced delay), which the query
    itself never waits for."""
    import time

    import pyarrow.parquet as pq

    d = tmp_path / "t"
    d.mkdir()
    for i in range(3):  # three files: three tasks, a reader pool
        pq.write_table(_table(2048), str(d / f"p{i}.parquet"))
    delay_s = 0.25
    real_wait = telemetry._wait_ready

    def slow_wait(arrays):
        time.sleep(delay_s)  # srtpu-lint: disable=raw-sleep
        real_wait(arrays)

    s = _session()
    try:
        df = (s.read.parquet(str(d)).filter(F.col("v") >= 0.0)
              .groupBy("k").agg(F.sum("v").alias("sv")))
        df.collect_arrow()  # compiles
        assert telemetry.drain_uploads(10.0)
        monkeypatch.setattr(telemetry, "_wait_ready", slow_wait)
        before = {r["site"]: dict(r)
                  for r in telemetry.ledger.site_rows()}.get(
            "scan.upload", {"bytes": 0, "ns": 0, "count": 0})
        t0 = time.perf_counter()
        df.collect_arrow()
        took_s = time.perf_counter() - t0
        assert s.last_execution["engine"] == "fused"
        qid = s.last_execution["queryId"]
        tel = s.last_execution["telemetry"]
        site = tel["perSite"]["scan.upload"]
        assert site["count"] == 3 and site["bytes"] > 0
        assert tel["bytesMoved"]["h2d"] == site["bytes"]
        assert took_s < 3 * delay_s  # nobody waited for the watcher
        assert telemetry.drain_uploads(10.0)
        row = {r["site"]: r for r in telemetry.ledger.site_rows()}[
            "scan.upload"]
        assert row["bytes"] - before["bytes"] == site["bytes"]
        assert row["count"] - before["count"] == 3
        # one watcher closes them in turn: 1, 2 and 3 delays
        assert row["ns"] - before["ns"] >= 3 * delay_s * 1e9
        mine = telemetry.ledger.query_summary(qid)["perSite"][
            "scan.upload"]
        assert mine["ns"] == row["ns"] - before["ns"]
        # the span and the ledger agree
        spans = [sp for sp in s.obs.last_spans.walk()
                 if sp.name == "scan.h2d"]
        assert len(spans) == 3
        assert sum(sp.wall_ns for sp in spans) == mine["ns"]
        assert {sp.query_id for sp in spans} == {qid}
        assert all(sp.extra["site"] == "scan.upload" for sp in spans)
    finally:
        s.stop()
