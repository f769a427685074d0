"""Exactly-once output: transactional task/job commit protocol
(io/commit.py), crash-safe overwrite, and optimistic lakehouse
concurrency (lakehouse/delta.py / iceberg.py).

The reference proves its writer with HadoopMapReduceCommitProtocol
semantics tests; this suite does the same for the engine's analog:
six-format round-trips through the staged path, the deferred overwrite
swap surviving an injected job-commit failure byte-identical, a
`kill -9`'d process worker's re-attempt landing oracle-identical
output, the orphan sweep never touching a live job, and two concurrent
Delta appenders both committing under the optimistic-transaction loop.
"""

import glob
import json
import os
import signal
import threading
import time

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.api import functions as F  # noqa: F401
from spark_rapids_tpu.api.session import TpuSparkSession
from spark_rapids_tpu.io import commit as iocommit
from spark_rapids_tpu.runtime import faults
from spark_rapids_tpu.runtime.errors import RetryExhausted

_CONF = {
    "spark.rapids.tpu.io.retry.backoffMs": 1,
    "spark.rapids.tpu.io.retry.maxBackoffMs": 4,
}


@pytest.fixture()
def spark():
    s = TpuSparkSession(dict(_CONF))
    yield s
    s.stop()


@pytest.fixture(autouse=True)
def _isolated_faults():
    faults.install(faults.FaultRegistry())
    yield
    faults.install(faults.FaultRegistry())


def _arm(spec, seed=42):
    return faults.install(faults.FaultRegistry(
        seed, faults.parse_sites(spec, 0.05)))


def _table(n=60):
    return pa.table({
        "a": pa.array(range(n), type=pa.int64()),
        "s": pa.array([f"v{i % 3}" for i in range(n)]),
    })


def _tree(path):
    """{relpath: (size, crc)} of every visible file under path."""
    out = {}
    for dirpath, _dirs, names in os.walk(path):
        for nm in names:
            full = os.path.join(dirpath, nm)
            rel = os.path.relpath(full, path)
            if any(seg.startswith(("_", "."))
                   for seg in rel.split(os.sep)):
                continue
            out[rel] = (os.path.getsize(full), iocommit._crc32(full))
    return out


def _no_debris(root):
    bad = [f for f in glob.glob(os.path.join(root, "**", "*"),
                                recursive=True)
           if iocommit.TEMP_DIR in f or ".__new-" in f
           or ".__old-" in f or ".inprogress-" in f]
    assert not bad, bad


# ----------------------------------------------------- format round-trip

def test_six_format_roundtrip_committed(spark, tmp_path):
    df = spark.createDataFrame(_table())
    schema = pa.schema([("a", pa.int64()), ("s", pa.string())])
    for fmt in ("parquet", "orc", "csv", "json", "avro", "hivetext"):
        p = str(tmp_path / fmt)
        stats = df.write.format(fmt).save(p)
        assert stats.num_rows == 60 and stats.num_files == 1, fmt
        # the manifest is the commit point and validates clean
        man = iocommit.read_manifest(p)
        assert man is not None and len(man["files"]) == 1, fmt
        assert iocommit.validate_output(p) == 1, fmt
        reader = spark.read if fmt in ("parquet", "orc") \
            else spark.read.schema(schema)
        back = getattr(reader, "hivetext"
                       if fmt == "hivetext" else fmt)(p).collect_arrow()
        assert back.num_rows == 60, fmt
        assert sorted(back.column("a").to_pylist()) == list(range(60)), \
            fmt
    _no_debris(str(tmp_path))


def test_partitionby_special_chars_roundtrip(spark, tmp_path):
    """Hive layout with `/`, `=`, `%` and None in partition values:
    the escaped dirs stay flat and the read side decodes them back."""
    t = pa.table({
        "a": pa.array(range(8), type=pa.int64()),
        "k": pa.array(["x/y", "p=q", "50%", None] * 2),
    })
    p = str(tmp_path / "parts")
    spark.createDataFrame(t).write.partitionBy("k").parquet(p)
    dirs = sorted(d for d in os.listdir(p) if not d.startswith("_"))
    assert dirs == ["k=50%25", "k=__HIVE_DEFAULT_PARTITION__",
                    "k=p%3Dq", "k=x%2Fy"], dirs
    back = spark.read.parquet(p).collect_arrow()
    assert back.num_rows == 8
    assert sorted(set(back.column("k").to_pylist()),
                  key=lambda v: (v is None, v)) == \
        ["50%", "p=q", "x/y", None]


def test_append_and_job_unique_file_names(spark, tmp_path):
    df = spark.createDataFrame(_table(10))
    p = str(tmp_path / "app")
    df.write.parquet(p)
    df.write.mode("append").parquet(p)
    parts = glob.glob(os.path.join(p, "part-*.parquet"))
    assert len(parts) == 2  # job-tagged names never collide
    assert spark.read.parquet(p).collect_arrow().num_rows == 20


# ------------------------------------------- crash-safe overwrite swap

def test_overwrite_failure_leaves_old_bytes_identical(spark, tmp_path):
    p = str(tmp_path / "ow")
    spark.createDataFrame(_table(40)).write.parquet(p)
    before = _tree(p)
    assert before
    # every commit.job attempt fails -> the job aborts; the prior
    # output must survive byte-identical, with zero staging debris
    _arm("commit.job:p=1.0")
    with pytest.raises(RetryExhausted):
        spark.createDataFrame(_table(5)).write.mode(
            "overwrite").parquet(p)
    faults.install(faults.FaultRegistry())
    assert _tree(p) == before
    _no_debris(str(tmp_path))
    back = spark.read.parquet(p).collect_arrow()
    assert back.num_rows == 40


def test_overwrite_swaps_atomically_on_success(spark, tmp_path):
    p = str(tmp_path / "ow2")
    spark.createDataFrame(_table(40)).write.parquet(p)
    spark.createDataFrame(_table(7)).write.mode("overwrite").parquet(p)
    assert spark.read.parquet(p).collect_arrow().num_rows == 7
    assert iocommit.validate_output(p) == 1
    _no_debris(str(tmp_path))


def test_chaos_on_write_sites_still_exactly_once(spark, tmp_path):
    """io.write + commit.task faults are absorbed by the shared backoff
    discipline; the published output still counts every row once."""
    _arm("io.write:every=3;commit.task:every=2")
    p = str(tmp_path / "chaos")
    stats = spark.createDataFrame(_table(30)).write.parquet(p)
    assert stats.num_rows == 30
    assert iocommit.validate_output(p) == 1
    assert spark.read.parquet(p).collect_arrow().num_rows == 30
    _no_debris(str(tmp_path))


# ------------------------------------------------- reader-side contract

def test_reader_skips_staging_and_validates_manifest(spark, tmp_path):
    p = str(tmp_path / "val")
    spark.createDataFrame(_table(20)).write.parquet(p)
    # plant staging debris a scan must never surface
    os.makedirs(os.path.join(p, iocommit.TEMP_DIR, "deadjob"),
                exist_ok=True)
    pq.write_table(_table(5), os.path.join(
        p, iocommit.TEMP_DIR, "deadjob", "part-zzz.parquet"))
    assert spark.read.parquet(p).collect_arrow().num_rows == 20
    # corrupt a listed file -> validateOnRead surfaces the tear
    [data] = glob.glob(os.path.join(p, "part-*.parquet"))
    with open(data, "ab") as f:
        f.write(b"x")
    s2 = TpuSparkSession({
        **_CONF,
        "spark.rapids.tpu.write.manifest.validateOnRead": True})
    try:
        with pytest.raises(iocommit.ManifestMismatch):
            s2.read.parquet(p).collect_arrow()
    finally:
        s2.stop()


# ------------------------------------------------- kill -9 mid-write

def test_kill9_writer_mid_task_output_oracle_identical(tmp_path):
    """SIGKILL a process worker holding an in-flight write task: the
    re-attempt (different worker, different attempt dir) is the one
    that commits, and the published output equals the oracle exactly —
    no double-counted, partial, or missing rows."""
    from spark_rapids_tpu.parallel.process_pool import (
        ProcessBackend,
        ProcessWorkerPool,
    )
    from spark_rapids_tpu.runtime.scheduler import StageScheduler, Task

    src = str(tmp_path / "src.parquet")
    table = _table(120)
    pq.write_table(table, src)
    out = str(tmp_path / "out")
    committer = iocommit.JobCommitter(out, mode="error", fmt="parquet")
    assert committer.setup_job()
    n, step = 6, 20
    FRAG = "spark_rapids_tpu.io.commit:run_write_fragment"

    def spec(i, sleep_s):
        return {"fmt": "parquet", "src": src, "offset": i * step,
                "count": step, "staging": committer.staging, "task": i,
                "file_tag": committer.job_id, "sleep_s": sleep_s}

    pool = ProcessWorkerPool(3, hb_interval_ms=100, hb_timeout_ms=1200)
    try:
        tasks = [Task(i, payload=(FRAG, spec(i, 0.4)),
                      commit=lambda res, att, i=i:
                          committer.commit_task(i, res),
                      abort=lambda att, i=i: None)
                 for i in range(n)]
        pid = pool.worker_pid("worker-0")

        def killer():
            time.sleep(0.6)
            os.kill(pid, signal.SIGKILL)

        threading.Thread(target=killer, daemon=True).start()
        StageScheduler(None, name="write-kill",
                       backend=ProcessBackend(pool)).run(tasks)
        manifest = committer.commit_job()
    finally:
        pool.close()
    assert len(manifest["files"]) == n
    assert iocommit.validate_output(out) == n
    back = pq.read_table(out)
    assert back.num_rows == 120
    assert sorted(back.column("a").to_pylist()) == \
        table.column("a").to_pylist()
    _no_debris(str(tmp_path))


# ------------------------------------------------------- orphan sweep

def test_sweep_reclaims_dead_never_live(tmp_path):
    out = str(tmp_path / "t")
    os.makedirs(out)
    tmp_root = os.path.join(out, iocommit.TEMP_DIR)
    dead = os.path.join(tmp_root, "deadjob")
    live = os.path.join(tmp_root, "livejob")
    os.makedirs(dead)
    os.makedirs(live)
    import socket

    json.dump({"pid": 2 ** 22 + 11, "host": socket.gethostname()},
              open(os.path.join(dead, iocommit.OWNER_FILE), "w"))
    json.dump({"pid": os.getpid(), "host": socket.gethostname()},
              open(os.path.join(live, iocommit.OWNER_FILE), "w"))
    assert iocommit.sweep_orphans(out) == 1
    assert not os.path.isdir(dead)
    assert os.path.isdir(live)  # live job's staging untouched
    # fresh foreign staging (no readable owner) is inside the TTL: kept
    foreign = os.path.join(tmp_root, "foreign")
    os.makedirs(foreign)
    open(os.path.join(foreign, "f"), "w").write("x")
    assert iocommit.sweep_orphans(out) == 0
    assert os.path.isdir(foreign)
    # ...but expired foreign staging is reclaimed
    assert iocommit.sweep_orphans(out, ttl_s=0.0) == 1
    assert not os.path.isdir(foreign)


def test_sweep_restores_old_after_crashed_swap(tmp_path):
    """Crash exactly between the swap's two renames leaves only
    `<out>.__old-<job>`: the sweep puts the old data back."""
    out = str(tmp_path / "t")
    old = out + iocommit._OLD_TAG + "deadbeef"
    os.makedirs(old)
    pq.write_table(_table(9), os.path.join(old, "part-0.parquet"))
    assert iocommit.sweep_orphans(out) == 1
    assert pq.read_table(out).num_rows == 9


# --------------------------------------------- optimistic delta commits

def test_concurrent_delta_appends_both_land(spark, tmp_path,
                                            monkeypatch):
    """Two appenders that COLLIDE BY CONSTRUCTION: each is held at its
    first claim until both have read snapshot v0 and built version 1,
    so exactly one claim wins and the loser must retry on v2. (Left to
    the scheduler, the two often commit one after the other and the
    conflict path never runs.)"""
    from spark_rapids_tpu.lakehouse import delta as dmod

    p = str(tmp_path / "d")
    spark.createDataFrame(_table(10)).write.format("delta").save(p)
    both_built = threading.Barrier(2)
    first_claim = threading.local()
    real_commit = dmod._commit

    def held_commit(table_path, version, actions, *rest):
        if not getattr(first_claim, "made", False):
            first_claim.made = True
            assert version == 1, version
            both_built.wait(timeout=30)
        return real_commit(table_path, version, actions, *rest)

    monkeypatch.setattr(dmod, "_commit", held_commit)
    conflicts_before = iocommit.write_totals()["conflicts"]
    errs = []

    def appender(n):
        try:
            df = spark.createDataFrame(_table(n))
            df.write.format("delta").mode("append").save(p)
        except BaseException as e:  # pragma: no cover - surfaced below
            errs.append(e)

    ts = [threading.Thread(target=appender, args=(n,))
          for n in (20, 30)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    assert not errs, errs
    back = spark.read.delta(p).collect_arrow()
    assert back.num_rows == 60  # 10 + 20 + 30: nothing lost
    from spark_rapids_tpu.lakehouse.delta import _list_versions

    assert _list_versions(p) == [0, 1, 2]
    assert iocommit.write_totals()["conflicts"] == conflicts_before + 1


def test_delta_rewrite_conflict_is_concurrent_modification(spark,
                                                           tmp_path):
    """A DELETE retrying on top of a commit that removed its read set
    must fail with DeltaConcurrentModification, not silently resurrect
    or drop rows."""
    from spark_rapids_tpu.lakehouse import delta as dmod

    p = str(tmp_path / "d")
    spark.createDataFrame(_table(10)).write.format("delta").save(p)
    snap = dmod.load_snapshot(p)
    cur_files = set(snap.file_paths)
    # simulate: our read set was a file an interim commit removed
    with pytest.raises(dmod.DeltaConcurrentModification):
        dmod._check_rewrite_conflict(
            0, snap, cur_files | {"part-gone.parquet"}, False, "DELETE")
    # full-table rewrite + interim append -> also non-retryable
    with pytest.raises(dmod.DeltaConcurrentModification):
        dmod._check_rewrite_conflict(0, snap, set(), True, "OPTIMIZE")
    # partial rewrite + compatible interim append -> no conflict
    dmod._check_rewrite_conflict(0, snap, cur_files, False, "DELETE")


def test_delta_commit_conflict_chaos_site(spark, tmp_path):
    """commit.conflict chaos forces optimistic-loop retries; the write
    still lands exactly once."""
    _arm("commit.conflict:once")
    p = str(tmp_path / "d")
    spark.createDataFrame(_table(10)).write.format("delta").save(p)
    assert spark.read.delta(p).collect_arrow().num_rows == 10


# --------------------------------------------------- iceberg occ claim

def test_iceberg_commit_metadata_claim_and_retry(tmp_path):
    from spark_rapids_tpu.lakehouse import iceberg as ice

    p = str(tmp_path / "ice")

    def build_v1(cur):
        assert cur is None
        return {"n": 1}

    assert ice.commit_metadata(p, build_v1) == 1
    # loser path: claim v2 out from under the builder ONCE, the retry
    # must rebuild against the new current metadata and land v3
    state = {"stolen": False}

    def build_racing(cur):
        if not state["stolen"]:
            state["stolen"] = True
            with open(os.path.join(
                    p, "metadata", "v2.metadata.json"), "w") as f:
                json.dump({"n": "thief"}, f)
        return {"n": cur["n"]}

    assert ice.commit_metadata(p, build_racing) == 3
    assert ice._load_metadata(p) == {"n": "thief"}
    hint = open(os.path.join(p, "metadata", "version-hint.text")).read()
    assert hint.strip() == "3"


# ----------------------------------------------------- stats + events

def test_write_stats_stat_failure_counted(tmp_path):
    from spark_rapids_tpu.io.writers import WriteStats

    st = WriteStats()
    st.file_written(str(tmp_path / "missing.bin"), rows=5)
    assert st.stat_failures == 1 and st.num_rows == 5
    assert st.num_bytes == 0
    st.file_written("anything", rows=2, nbytes=17)  # staged-rename path
    assert st.num_bytes == 17 and st.num_files == 2


def test_unknown_options_once_per_job_event(spark, tmp_path):
    from spark_rapids_tpu.obs import events as obs

    seen = []
    bus = obs.get()
    assert bus is not None
    unsub = bus.subscribe(
        lambda ev: seen.append(ev) if ev["event"] == "write.options"
        else None)
    try:
        (spark.createDataFrame(_table(12)).write
         .option("bogus_option", 1).option("compression", "snappy")
         .parquet(str(tmp_path / "o")))
    finally:
        bus.unsubscribe(unsub)
    assert len(seen) == 1  # once per JOB, not per file
    assert seen[0]["ignored"] == ["bogus_option"]


def test_write_events_and_telemetry_block(spark, tmp_path):
    from spark_rapids_tpu.obs import events as obs
    from spark_rapids_tpu.obs import telemetry as tel

    seen = []
    bus = obs.get()
    assert bus is not None
    unsub = bus.subscribe(
        lambda ev: seen.append(ev)
        if ev["event"].startswith("write.") else None)
    try:
        spark.createDataFrame(_table(25)).write.parquet(
            str(tmp_path / "ev"))
    finally:
        bus.unsubscribe(unsub)
    kinds = [e["event"] for e in seen]
    assert kinds[0] == "write.start" and kinds[-1] == "write.commit"
    assert "write.task" in kinds
    commit_ev = seen[-1]
    assert commit_ev["rows"] == 25 and commit_ev["files"] == 1
    qid = commit_ev["queryId"]
    assert qid  # attributed to the save()'s query scope
    summ = tel.ledger.recent_query_summaries().get(qid)
    assert summ and summ["write"]["rows"] == 25
    # prometheus families render
    from spark_rapids_tpu.obs import prom

    text = prom.render(spark)
    assert "srtpu_write_jobs_total" in text
    assert "srtpu_query_write_bytes" in text
