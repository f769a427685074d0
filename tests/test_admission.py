"""Query governance suite — admission control, deadlines, cooperative
cancellation, per-query quotas, poison-query quarantine, and semaphore
fairness (PR 5).

The acceptance contract under test: over-capacity submissions always
get a clean QueryRejectedError (never an unbounded wait); a query
cancelled mid-execution — including while blocked on the semaphore and
inside retry/split loops — unwinds within a bounded latency, releases
its permits, and leaves the spill catalog leak-free; concurrent queries
through one session stay oracle-identical with chaos armed.
"""

import os
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import spark_rapids_tpu.api.functions as F
from spark_rapids_tpu.api.session import TpuSparkSession
from spark_rapids_tpu.columnar import arrow_to_device
from spark_rapids_tpu.obs import events as obs_events
from spark_rapids_tpu.runtime import admission, cancellation
from spark_rapids_tpu.runtime import semaphore as sem_mod
from spark_rapids_tpu.runtime.admission import AdmissionController
from spark_rapids_tpu.runtime.cancellation import CancelToken
from spark_rapids_tpu.runtime.errors import (
    QueryCancelledError,
    QueryDeadlineExceeded,
    QueryQuarantinedError,
    QueryQueueTimeout,
    QueryRejectedError,
    SemaphoreTimeout,
    TpuRetryOOM,
    TpuSplitAndRetryOOM,
    TpuSplitAndRetryOOM as _SplitOOM,  # noqa: F401 (alias clarity)
)
from spark_rapids_tpu.runtime.memory import SpillCatalog, get_catalog
from spark_rapids_tpu.runtime.retry import with_retry
from spark_rapids_tpu.runtime.semaphore import TpuSemaphore


def _batch(n=1000, base=0):
    t = pa.table({"a": pa.array(range(base, base + n), pa.int64()),
                  "b": pa.array([float(i) for i in range(n)],
                                pa.float64())})
    return arrow_to_device(t)


def _wait_until(pred, timeout_s=5.0, tick=0.002):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(tick)
    return False


# ------------------------------------------------ controller unit tests

def test_shed_immediately_when_queue_full():
    ctrl = AdmissionController(max_concurrent=1, queue_depth=0)
    hog = ctrl.submit(101, description="hog")
    t0 = time.monotonic()
    with pytest.raises(QueryRejectedError) as ei:
        ctrl.submit(102, description="victim")
    # a shed is an IMMEDIATE clean error carrying the running table
    assert time.monotonic() - t0 < 1.0
    assert "query=101" in str(ei.value)
    assert "hog" in str(ei.value)
    ctrl.finish(hog)
    ok = ctrl.submit(103)
    assert ok.state == "running"
    ctrl.finish(ok)


def test_queue_timeout_names_running_queries():
    ctrl = AdmissionController(max_concurrent=1, queue_depth=4,
                               queue_timeout_ms=80)
    hog = ctrl.submit(201, description="the-culprit")
    t0 = time.monotonic()
    with pytest.raises(QueryQueueTimeout) as ei:
        ctrl.submit(202)
    assert 0.05 < time.monotonic() - t0 < 3.0
    assert "the-culprit" in str(ei.value)
    assert admission.stats.snapshot()["queueTimeouts"] >= 1
    ctrl.finish(hog)


def test_priority_then_fifo_admission_order():
    ctrl = AdmissionController(max_concurrent=1, queue_depth=8,
                               queue_timeout_ms=10_000)
    hog = ctrl.submit(300, description="hog")
    order, threads = [], []

    def submit(qid, prio):
        h = ctrl.submit(qid, priority=prio)
        order.append(qid)
        ctrl.finish(h)

    for qid, prio in ((301, 0), (302, 5), (303, 0)):
        t = threading.Thread(target=submit, args=(qid, prio))
        t.start()
        threads.append(t)
        assert _wait_until(
            lambda n=len(threads): len(ctrl.queued_table()) == n)
    ctrl.finish(hog)
    for t in threads:
        t.join(10)
    # highest priority first, FIFO within equal priority
    assert order == [302, 301, 303]


def test_cancel_queued_query_leaves_queue_promptly():
    ctrl = AdmissionController(max_concurrent=1, queue_depth=8,
                               queue_timeout_ms=60_000)
    hog = ctrl.submit(400)
    errs = []

    def submit():
        try:
            ctrl.submit(401)
        except BaseException as e:
            errs.append(e)

    t = threading.Thread(target=submit)
    t.start()
    assert _wait_until(lambda: len(ctrl.queued_table()) == 1)
    t0 = time.monotonic()
    assert ctrl.cancel(401, "operator said so")
    t.join(5)
    assert time.monotonic() - t0 < 2.0
    assert len(errs) == 1 and isinstance(errs[0], QueryCancelledError)
    assert "operator said so" in str(errs[0])
    assert ctrl.queued_table() == []
    ctrl.finish(hog)


# -------------------------------------------------- cancel-token basics

def test_token_deadline_turns_into_cancel():
    tok = CancelToken(1, timeout_ms=10)
    assert _wait_until(lambda: tok.expired, 2.0)
    with pytest.raises(QueryDeadlineExceeded):
        tok.check()
    assert tok.cancelled  # expiry latched as a cancel → waiters wake


def test_token_quarantine_after_crashes():
    tok = CancelToken(2, quarantine_threshold=3)
    tok.record_worker_crash(1, 0, "w0")
    tok.record_worker_crash(1, 0, "w1")
    assert not tok.cancelled
    tok.record_worker_crash(2, 1, "w2")
    with pytest.raises(QueryQuarantinedError) as ei:
        tok.check()
    assert "crash history" in str(ei.value)
    assert "w1" in str(ei.value)


def test_cancel_unwinds_split_retry_loop_leak_free(tmp_path):
    cat = SpillCatalog(1 << 30, 1 << 30, spill_dir=str(tmp_path))
    from spark_rapids_tpu.runtime import memory as mem_mod

    old = mem_mod._catalog
    mem_mod._catalog = cat
    try:
        tok = CancelToken(3)
        calls = []

        def fn(sb):
            calls.append(sb.row_count())
            if len(calls) == 3:
                tok.cancel("mid-split cancel")
            raise TpuSplitAndRetryOOM("never fits")

        with cancellation.scope(tok):
            with pytest.raises(QueryCancelledError):
                list(with_retry(cat.add_batch(_batch()), fn))
        # the current piece AND every queued split piece must be closed
        assert cat.check_leaks() == 0
        assert cat.device_reserved() == 0
    finally:
        mem_mod._catalog = old


# ------------------------------------------------- semaphore governance

def test_semaphore_fifo_ticket_fairness():
    """Satellite: acquirers are served strictly in arrival order — a
    parked waiter can no longer starve behind later arrivals racing the
    wakeup (the regression the ticket queue exists to prevent)."""
    for _ in range(10):
        sem = TpuSemaphore(concurrent_tasks=1, acquire_timeout_ms=20_000)
        sem.acquire_if_necessary(0)
        order, threads = [], []
        for i in range(1, 6):
            def run(i=i):
                sem.acquire_if_necessary(i)
                order.append(i)
                sem.release_if_necessary(i)

            t = threading.Thread(target=run)
            t.start()
            threads.append(t)
            assert _wait_until(lambda n=i: sem.waiting() == n)
        sem.release_if_necessary(0)
        for t in threads:
            t.join(10)
        assert order == [1, 2, 3, 4, 5]


def test_semaphore_timeout_table_names_query_and_hold_time():
    """Satellite: the held-permit table names the holder's QUERY id and
    elapsed hold seconds, so a wedged-query diagnosis reads off which
    query to session.cancel()."""
    sem = TpuSemaphore(concurrent_tasks=1, acquire_timeout_ms=80)
    qid = obs_events.begin_query()
    try:
        sem.acquire_if_necessary(7)
    finally:
        obs_events.finish_query(qid)
    with pytest.raises(SemaphoreTimeout) as ei:
        sem.acquire_if_necessary(8)
    msg = str(ei.value)
    assert f"query={qid}" in msg
    assert "held_s=" in msg
    sem.release_if_necessary(7)


def test_semaphore_wait_cancelled_promptly():
    sem = TpuSemaphore(concurrent_tasks=1, acquire_timeout_ms=60_000)
    sem.acquire_if_necessary(1)
    tok = CancelToken(9)
    errs = []

    def blocked():
        try:
            sem.acquire_if_necessary(2, cancel=tok)
        except BaseException as e:
            errs.append(e)

    t = threading.Thread(target=blocked)
    t.start()
    assert _wait_until(lambda: sem.waiting() == 1)
    t0 = time.monotonic()
    tok.cancel("cut the line")
    t.join(5)
    assert time.monotonic() - t0 < 2.0  # bounded cancel latency
    assert len(errs) == 1 and isinstance(errs[0], QueryCancelledError)
    assert sem.waiting() == 0  # the dead waiter's ticket is gone
    sem.release_if_necessary(1)
    sem.acquire_if_necessary(3)  # queue not wedged
    sem.release_if_necessary(3)


# ------------------------------------------------- per-query mem quotas

def test_per_query_device_quota_isolates_offender(tmp_path):
    cat = SpillCatalog(1 << 30, 1 << 30, spill_dir=str(tmp_path),
                       query_quota_bytes=40_000)
    # two tenants, each within quota: both fine
    cat.reserve(30_000, tag="t", query_id=11)
    cat.reserve(30_000, tag="t", query_id=12)
    assert cat.query_device_reserved(11) == 30_000
    # tenant 11 over quota with nothing of its own to spill: split OOM
    # for tenant 11 ONLY — the message names the quota
    with pytest.raises(TpuSplitAndRetryOOM, match="quota"):
        cat.reserve(20_000, tag="t", query_id=11)
    assert cat.metrics["quota_oom"] == 1
    # tenant 12 is untouched by 11's pressure
    cat.reserve(9_000, tag="t", query_id=12)
    cat.release(30_000, query_id=11)
    cat.release(39_000, query_id=12)
    assert cat.device_reserved() == 0


def test_quota_spills_own_buffers_first(tmp_path):
    cat = SpillCatalog(1 << 30, 1 << 30, spill_dir=str(tmp_path),
                       query_quota_bytes=40_000)
    qid = obs_events.begin_query()
    try:
        bufs = [cat.add_batch(_batch(base=i * 1000)) for i in range(2)]
        assert cat.query_device_reserved(qid) > 0
        # the third batch crosses the quota: the gate spills THIS
        # query's own device buffers to make room instead of raising
        b3 = cat.add_batch(_batch(base=9000))
        assert cat.metrics["spill_to_host"] >= 1
        assert cat.query_device_reserved(qid) <= 40_000
        for b in bufs + [b3]:
            b.close()
    finally:
        obs_events.finish_query(qid)
    assert cat.check_leaks() == 0


# ---------------------------------------------- end-to-end session tests

def _mk_parquet(tmp_path, rows=60_000):
    rng = np.random.default_rng(7)
    path = os.path.join(str(tmp_path), "fact")
    os.makedirs(path, exist_ok=True)
    for i in range(2):
        pq.write_table(pa.table({
            "k": pa.array(rng.integers(0, 64, rows // 2), pa.int64()),
            "v": pa.array(rng.random(rows // 2) * 100.0),
        }), os.path.join(path, f"p{i}.parquet"))
    return path


def test_session_shed_and_recover(tmp_path):
    data = _mk_parquet(tmp_path, rows=4_000)
    s = TpuSparkSession({
        "spark.rapids.tpu.admission.maxConcurrentQueries": 1,
        "spark.rapids.tpu.admission.queue.maxDepth": 0,
    })
    try:
        ctrl = admission.get()
        hog = ctrl.submit(obs_events.allocate_query_id(),
                          description="hog")
        df = s.read.parquet(data).groupBy("k").agg(
            F.sum("v").alias("sv"))
        with pytest.raises(QueryRejectedError) as ei:
            df.collect_arrow()
        assert "hog" in str(ei.value)
        ctrl.finish(hog)
        out = df.collect_arrow()  # capacity back: the query runs
        assert out.num_rows == 64
        assert s.last_execution["admission"]["queueWaitMs"] >= 0
    finally:
        s.stop()


def test_session_deadline_exceeded_is_clean(tmp_path):
    data = _mk_parquet(tmp_path, rows=4_000)
    s = TpuSparkSession({
        "spark.rapids.tpu.query.timeoutMs": 1,
    })
    try:
        df = s.read.parquet(data).groupBy("k").agg(
            F.count("*").alias("n"))
        with pytest.raises(QueryDeadlineExceeded):
            df.collect_arrow()
        assert get_catalog().check_leaks() == 0
        assert sem_mod.get().holders() == 0
        # the session recovers for deadline-free queries
        s.conf.set("spark.rapids.tpu.query.timeoutMs", 0)
        assert df.collect_arrow().num_rows == 64
    finally:
        s.stop()


def test_cancel_while_blocked_on_semaphore(tmp_path):
    """Acceptance case: a query cancelled WHILE WAITING for device
    permits unwinds within a bounded latency and takes no permits."""
    data = _mk_parquet(tmp_path, rows=4_000)
    s = TpuSparkSession({
        "spark.rapids.sql.concurrentGpuTasks": 1,
        "spark.rapids.tpu.semaphore.acquireTimeoutMs": 60_000,
    })
    try:
        sem = sem_mod.get()
        sem.acquire_if_necessary(987_654)  # wedge: all permits held
        errs = []

        def run():
            try:
                s.read.parquet(data).groupBy("k").agg(
                    F.sum("v").alias("sv")).collect_arrow()
            except BaseException as e:
                errs.append(e)

        t = threading.Thread(target=run)
        t.start()
        assert _wait_until(lambda: sem.waiting() >= 1, 30.0)
        running = s.admission_status()["running"]
        assert len(running) == 1
        t0 = time.monotonic()
        assert s.cancel(running[0]["queryId"])
        t.join(15)
        assert not t.is_alive()
        assert time.monotonic() - t0 < 10.0
        assert len(errs) == 1 and \
            isinstance(errs[0], QueryCancelledError)
        sem.release_if_necessary(987_654)
        assert sem.holders() == 0  # the cancelled query took nothing
        get_catalog().check_leaks(raise_on_leak=True)
        assert s.admission_status()["running"] == []
    finally:
        s.stop()


def test_poison_query_quarantined_with_history(tmp_path):
    data = _mk_parquet(tmp_path, rows=4_000)
    s = TpuSparkSession({
        "spark.rapids.sql.fusedExec.enabled": False,
        "spark.rapids.tpu.chaos.enabled": True,
        "spark.rapids.tpu.chaos.sites": "worker.crash:p=1.0",
        "spark.rapids.tpu.stage.maxAttempts": 50,
        "spark.rapids.tpu.admission.quarantine.maxWorkerCrashes": 3,
    })
    try:
        df = s.read.parquet(data).groupBy("k").agg(
            F.count("*").alias("n"))
        before = admission.stats.snapshot()["queriesQuarantined"]
        with pytest.raises(QueryQuarantinedError) as ei:
            df.collect_arrow()
        assert "crash history" in str(ei.value)
        assert admission.stats.snapshot()["queriesQuarantined"] == \
            before + 1
        get_catalog().check_leaks(raise_on_leak=True)
    finally:
        s.stop()


def test_concurrent_queries_oracle_identical_under_chaos(tmp_path):
    """Satellite: N threads submitting distinct queries through ONE
    session, admission capacity below N (so queueing happens), chaos
    armed — every thread's every round matches the clean oracle, and
    the catalog is leak-free after."""
    data = _mk_parquet(tmp_path, rows=20_000)

    def build(s):
        fact = s.read.parquet(data)
        return [
            ("sum", fact.groupBy("k").agg(F.sum("v").alias("x"))
             .orderBy("k")),
            ("cnt", fact.filter(F.col("v") > 50.0).groupBy("k")
             .agg(F.count("*").alias("x")).orderBy("k")),
            ("top", fact.orderBy("v", ascending=False)
             .select("k", "v").limit(20)),
            ("avg", fact.groupBy("k").agg(F.avg("v").alias("x"))
             .orderBy("k")),
        ]

    clean = TpuSparkSession({})
    try:
        want = {name: df.collect_arrow().to_pydict()
                for name, df in build(clean)}
    finally:
        clean.stop()

    s = TpuSparkSession({
        "spark.rapids.tpu.admission.maxConcurrentQueries": 2,
        "spark.rapids.tpu.admission.queue.maxDepth": 16,
        "spark.rapids.tpu.chaos.enabled": True,
        "spark.rapids.tpu.chaos.seed": 11,
        "spark.rapids.tpu.chaos.sites":
            "io.read:p=0.2;worker.crash:p=0.05",
        "spark.rapids.tpu.stage.maxAttempts": 8,
        "spark.rapids.tpu.io.retry.backoffMs": 1,
        "spark.rapids.tpu.io.retry.maxBackoffMs": 5,
        "spark.rapids.tpu.io.retry.attempts": 6,
    })
    try:
        queries = build(s)
        errs, results = [], {}

        def worker(idx):
            try:
                name, df = queries[idx]
                for _ in range(2):
                    results[(idx, _)] = (name,
                                         df.collect_arrow().to_pydict())
            except BaseException as e:  # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(len(queries))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not errs, errs
        for (_idx, _r), (name, got) in results.items():
            assert got == want[name] or _float_close(got, want[name]), \
                f"{name} diverged under concurrent chaos"
        snap = admission.stats.snapshot()
        assert snap["queriesAdmitted"] >= 8
        get_catalog().check_leaks(raise_on_leak=True)
    finally:
        s.stop()


def _float_close(a, b, rel=1e-6):
    if set(a) != set(b):
        return False
    import math

    for col in a:
        if len(a[col]) != len(b[col]):
            return False
        for x, y in zip(a[col], b[col]):
            if isinstance(x, float) or isinstance(y, float):
                if not math.isclose(x, y, rel_tol=rel, abs_tol=1e-8):
                    return False
            elif x != y:
                return False
    return True


def test_cancel_storm_leaves_no_leaks(tmp_path):
    """Satellite acceptance: a storm of mid-flight cancels (landing in
    the planner, scheduler, shuffle, retry loops — wherever the query
    happens to be) leaves zero leaked buffers and zero held permits;
    check_leaks(raise_on_leak=True) passes."""
    data = _mk_parquet(tmp_path, rows=40_000)
    s = TpuSparkSession({
        "spark.rapids.sql.fusedExec.enabled": False,
        "spark.rapids.shuffle.mode": "MULTITHREADED",
        "spark.sql.shuffle.partitions": 4,
        "spark.rapids.sql.reader.batchSizeRows": 4096,
        "spark.rapids.memory.gpu.maxAllocBytes": 8 << 20,
    })
    try:
        df = s.read.parquet(data).repartition(4, "k").groupBy("k").agg(
            F.sum("v").alias("sv"))
        outcomes = []
        for i in range(6):
            err = []

            def run():
                try:
                    df.collect_arrow()
                    err.append(None)
                except QueryCancelledError as e:
                    err.append(e)

            t = threading.Thread(target=run)
            t.start()
            time.sleep(0.01 * i)  # cancel lands at varied depths
            s.cancel_all("storm")
            t.join(60)
            assert not t.is_alive()
            outcomes.append(err[0] if err else "hung")
        # a mix of cancelled and completed-before-cancel is fine; what
        # is NOT fine is leaks, held permits, or stuck slots
        assert all(o is None or isinstance(o, QueryCancelledError)
                   for o in outcomes), outcomes
        assert sem_mod.get().holders() == 0
        get_catalog().check_leaks(raise_on_leak=True)
        assert s.admission_status()["running"] == []
        out = df.collect_arrow()  # and the session still works
        assert out.num_rows == 64
    finally:
        s.stop()


def test_chaos_sites_cancel_race_and_slow_drain(tmp_path):
    """New chaos sites are result-equivalent: a cancel racing with
    completion and a delayed slot handoff change nothing observable."""
    data = _mk_parquet(tmp_path, rows=4_000)
    clean = TpuSparkSession({})
    try:
        want = clean.read.parquet(data).groupBy("k").agg(
            F.sum("v").alias("sv")).orderBy("k").collect_arrow()
    finally:
        clean.stop()
    s = TpuSparkSession({
        "spark.rapids.tpu.chaos.enabled": True,
        "spark.rapids.tpu.chaos.sites":
            "query.cancel_race:p=1.0;admission.slow_drain:p=1.0",
    })
    try:
        df = s.read.parquet(data).groupBy("k").agg(
            F.sum("v").alias("sv")).orderBy("k")
        for _ in range(3):
            got = df.collect_arrow()
            assert got.to_pydict() == want.to_pydict()
        assert s.admission_status()["running"] == []
        get_catalog().check_leaks(raise_on_leak=True)
    finally:
        s.stop()


def test_admission_events_and_queue_wait_span(tmp_path):
    data = _mk_parquet(tmp_path, rows=4_000)
    s = TpuSparkSession({
        "spark.rapids.tpu.admission.maxConcurrentQueries": 1,
    })
    try:
        ctrl = admission.get()
        hog = ctrl.submit(obs_events.allocate_query_id(),
                          description="hog")
        done = []

        def run():
            done.append(s.read.parquet(data).groupBy("k").agg(
                F.count("*").alias("n")).collect_arrow())

        t = threading.Thread(target=run)
        t.start()
        assert _wait_until(lambda: len(ctrl.queued_table()) == 1, 30.0)
        time.sleep(0.05)  # measurable queue wait
        ctrl.finish(hog)
        t.join(60)
        assert done and done[0].num_rows == 64
        counts = s.obs.bus.counts
        assert counts.get("admission.queued", 0) >= 1
        assert counts.get("admission.admitted", 0) >= 1
        assert s.last_execution["admission"]["queueWaitMs"] >= 40
        # the queue wait hangs on the query's span tree
        root = s.obs.last_spans
        waits = [sp for sp in root.children if sp.name == "admission"]
        assert len(waits) == 1
        assert waits[0].wall_ns >= 40_000_000
        assert waits[0].start_ns == root.start_ns  # inside the query
    finally:
        s.stop()


# ---------------------------------------------------------------------------
# drain ordering (serve/) — the intake valve vs queued work
# ---------------------------------------------------------------------------


def test_begin_drain_sheds_new_submissions_with_reason():
    ctrl = AdmissionController(max_concurrent=2, queue_depth=4)
    h = ctrl.submit(9001, description="pre-drain")
    ctrl.begin_drain("rolling restart")
    with pytest.raises(QueryRejectedError) as ei:
        ctrl.submit(9002, description="post-drain")
    assert ei.value.reason == "draining"
    assert "rolling restart" in str(ei.value)
    assert ctrl.status()["draining"] is True
    # in-flight work is untouched by the valve
    ctrl.finish(h)
    assert ctrl.quiescent()
    ctrl.end_drain()
    ok = ctrl.submit(9003)
    assert ok.state == "running"
    ctrl.finish(ok)
    assert ctrl.status()["draining"] is False


def test_drain_preserves_queued_queries():
    """Queries already IN the queue when the drain begins keep their
    slots and deadlines — drain is an intake valve, not a kill
    switch."""
    ctrl = AdmissionController(max_concurrent=1, queue_depth=4,
                               queue_timeout_ms=30_000)
    hog = ctrl.submit(9101, description="hog")
    admitted = []

    def queued_runner():
        h = ctrl.submit(9102, description="queued-before-drain")
        admitted.append(h)
        ctrl.finish(h)

    t = threading.Thread(target=queued_runner)
    t.start()
    assert _wait_until(lambda: len(ctrl.queued_table()) == 1, 10.0)
    ctrl.begin_drain()
    # a NEW submission sheds immediately...
    with pytest.raises(QueryRejectedError) as ei:
        ctrl.submit(9103)
    assert ei.value.reason == "draining"
    # ...but the queued query still gets its turn when capacity frees
    ctrl.finish(hog)
    t.join(30)
    assert admitted and admitted[0].query_id == 9102
    assert ctrl.quiescent()
    ctrl.end_drain()


def test_request_overrides_thread_priority_and_timeout(tmp_path):
    """serve/ threads a connection's priority class + per-request
    timeout through admission.request_overrides — thread-local, so
    concurrent connections on one session can't race each other's
    conf."""
    data = _mk_parquet(tmp_path, rows=2_000)
    s = TpuSparkSession({
        "spark.rapids.tpu.admission.maxConcurrentQueries": 1,
    })
    try:
        with admission.request_overrides(priority=42,
                                         description="vip"):
            got = s.read.parquet(data).groupBy("k").agg(
                F.count("*").alias("n")).collect_arrow()
        assert got.num_rows == 64
        rec = s.last_execution["admission"]
        assert rec["priority"] == 42
        # the override is scoped: the next query is back on conf
        s.range(0, 10).count()
        assert s.last_execution["admission"]["priority"] == 0
        assert admission.current_overrides() == {}
    finally:
        s.stop()
