"""Persistent cross-process compilation layer
(runtime/compile_cache.py) + fused variant dedup (exec/fused.py
run_program canonical keys): the round-5 cold-start killer.

Covers the acceptance surface: cross-process executable reuse, warmup
serving, version-skew invalidation, digest-collision safety, concurrent
writers, per-query compile metrics, and the canonical-key dedup that
stops expansion retries / re-lowerings / the ANSI channel from
recompiling the whole pipeline."""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSparkSession
from spark_rapids_tpu.runtime import compile_cache as cc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _abandoned_attempts_done(timeout: float = 30.0) -> None:
    """The compile metrics are deltas of a process-wide ledger, and an
    earlier module's abandoned task attempts (the stage scheduler
    closes its pool with shutdown(wait=False), so a straggler that
    lost to its speculative twin runs on) still build programs on
    their threads: let them finish before counting."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline and any(
            t.name.startswith("sched-") for t in threading.enumerate()):
        time.sleep(0.05)


@pytest.fixture()
def cache_session(tmp_path):
    """Session bound to an isolated cache dir; deconfigures after.
    The process jit cache is cleared so earlier tests' structurally
    identical programs don't turn this test's builds into hits."""
    from spark_rapids_tpu.runtime import jit_cache

    _abandoned_attempts_done()
    jit_cache.clear()
    cc.reset_for_tests()
    s = TpuSparkSession({
        "spark.rapids.tpu.compileCache.dir": str(tmp_path / "cache"),
        "spark.rapids.tpu.compileCache.warmup.enabled": False,
    })
    yield s
    s.stop()
    cc.reset_for_tests()


def _mini_q5(spark):
    """The bench shape in miniature: scan -> filter -> broadcast
    lookup join -> string-key aggregate."""
    fact = spark.createDataFrame(pa.table({
        "store": pa.array(np.arange(4000) % 50, type=pa.int64()),
        "amount": pa.array(np.arange(4000, dtype=np.float64)),
    }))
    dim = spark.createDataFrame(pa.table({
        "store": pa.array(np.arange(50), type=pa.int64()),
        "region": pa.array([f"r{i % 4}" for i in range(50)]),
    }))
    return (fact.filter(F.col("amount") > 10.0)
            .join(dim, on="store", how="inner")
            .groupBy("region")
            .agg(F.sum("amount").alias("s"),
                 F.count("*").alias("n")))


# ------------------------------------------------- per-query metrics

def test_compile_metrics_in_last_execution(cache_session):
    s = cache_session
    q = _mini_q5(s)
    out = q.collect_arrow()
    assert out.num_rows == 4
    comp = s.last_execution["compile"]
    assert s.last_execution["engine"] == "fused"
    assert comp["programsCompiled"] > 0
    assert comp["cacheHits"] == 0
    assert comp["variantCount"] == comp["programsCompiled"]
    assert comp["compileSeconds"] > 0
    # second run: everything structural-hits, nothing compiles
    q.collect_arrow()
    comp2 = s.last_execution["compile"]
    assert comp2["programsCompiled"] == 0
    assert comp2["cacheHits"] == comp["variantCount"]
    assert comp2["variantCount"] == comp["variantCount"]
    # ledger counters surfaced in session metrics
    snap = s.query_metrics.snapshot()
    assert snap["compile.programsCompiled"] == comp["programsCompiled"]
    assert snap["compile.cacheHits"] >= comp2["cacheHits"]


# ---------------------------------------------------- variant dedup

def test_expansion_change_recompiles_nothing_without_consumers(
        cache_session):
    """The dedup acceptance: canonical keys carry only consumed
    parameters, so re-running the bench-shaped query at a DIFFERENT
    expansion factor (the retry sweep's axis) recompiles zero programs
    — no program in this plan consumes the expansion factor. The old
    keys stamped every program with it: the sweep recompiled the
    whole pipeline."""
    from spark_rapids_tpu.exec.fused import FusedSingleChipExecutor

    s = cache_session
    q = _mini_q5(s)
    phys, _ = q._physical()

    ex1 = FusedSingleChipExecutor(s.rapids_conf, expansion=4)
    ex1.execute(phys)
    m1 = ex1.last_compile_metrics
    assert m1["programsCompiled"] > 0

    ex2 = FusedSingleChipExecutor(s.rapids_conf, expansion=8)
    ex2.execute(phys)
    m2 = ex2.last_compile_metrics
    assert m2["programsCompiled"] == 0, m2
    assert m2["cacheHits"] == m1["variantCount"]

    # group_cap IS consumed (aggregate shrink): only the agg-bearing
    # programs recompile, strictly fewer than the whole pipeline
    ex3 = FusedSingleChipExecutor(s.rapids_conf, expansion=4,
                                  group_cap=1 << 15)
    ex3.execute(phys)
    m3 = ex3.last_compile_metrics
    assert 0 < m3["programsCompiled"] < m1["variantCount"], m3


def test_ansi_flag_without_checks_shares_programs(tmp_path):
    """ANSI dedup: with no checkable expression in the plan, ANSI on
    traces byte-identically to ANSI off — the hoisted ansi_live key
    component lets both share compiled programs (the old key split
    them)."""
    from spark_rapids_tpu.runtime import jit_cache

    jit_cache.clear()
    cc.reset_for_tests()
    cache = str(tmp_path / "cache")
    base_conf = {
        "spark.rapids.tpu.compileCache.dir": cache,
        "spark.rapids.tpu.compileCache.warmup.enabled": False,
    }
    t = pa.table({"k": pa.array(np.arange(512) % 7, type=pa.int64()),
                  "v": pa.array(np.arange(512, dtype=np.float64))})

    def q(spark):
        # comparison + sum: nothing here raises under ANSI
        return (spark.createDataFrame(t)
                .filter(F.col("v") > 3.0)
                .groupBy("k").agg(F.min("v").alias("m"))
                .collect_arrow())

    s1 = TpuSparkSession(base_conf)
    try:
        q(s1)
        n1 = s1.last_execution["compile"]["programsCompiled"]
        assert n1 > 0
    finally:
        s1.stop()
    s2 = TpuSparkSession({**base_conf, "spark.sql.ansi.enabled": True})
    try:
        q(s2)
        comp = s2.last_execution["compile"]
        assert comp["programsCompiled"] == 0, comp
        assert comp["cacheHits"] == comp["variantCount"]
    finally:
        s2.stop()
        cc.reset_for_tests()


def test_shape_bucketing_shares_programs_across_similar_sizes():
    from spark_rapids_tpu.exec.fused import bucket_capacity

    # below the alignment floor: identical to the old 64Ki alignment
    assert bucket_capacity(1) == 1 << 16
    assert bucket_capacity((1 << 16) + 1) == 1 << 17
    # large caps land on 1/8-octave steps: similar sizes -> same bucket
    a, b = bucket_capacity(4_500_000), bucket_capacity(4_600_000)
    assert a == b
    # padding bounded by 12.5% + one step
    for n in (4_500_000, 9_000_001, 36_000_000):
        cap = bucket_capacity(n)
        assert n <= cap <= int(n * 1.126) + (1 << 16), (n, cap)


# ------------------------------------------- cross-process + warmup

_PROC_SCRIPT = textwrap.dedent("""
    import json, sys, time
    import jax; jax.config.update("jax_platforms", "cpu")
    import numpy as np, pyarrow as pa
    from spark_rapids_tpu.api.session import TpuSparkSession
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.runtime import compile_cache as cc

    cache_dir, warm = sys.argv[1], sys.argv[2] == "warm"
    s = TpuSparkSession({
        "spark.rapids.tpu.compileCache.dir": cache_dir,
        "spark.rapids.tpu.compileCache.warmup.enabled": warm,
        # tiny test programs must still export warmup artifacts
        "spark.rapids.tpu.compileCache.artifact.minCompileSecs": 0.0,
    })
    if warm:
        cc.warmup_join(120)
    t = pa.table({"k": pa.array(np.arange(2000) % 11,
                                type=pa.int64()),
                  "v": pa.array(np.arange(2000, dtype=np.float64))})
    out = (s.createDataFrame(t).filter(F.col("v") > 5.0)
           .groupBy("k").agg(F.sum("v").alias("s"))
           .collect_arrow())
    total = sum(out.column("s").to_pylist())
    cc.flush()
    print(json.dumps({"engine": s.last_execution["engine"],
                      "compile": s.last_execution["compile"],
                      "total": total}))
    s.stop()
""")


def _run_proc(cache_dir: str, mode: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    r = subprocess.run(
        [sys.executable, "-c", _PROC_SCRIPT, cache_dir, mode],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("{")][-1]
    return json.loads(line)


@pytest.mark.slow
def test_cross_process_warm_start(tmp_path):
    """The tentpole end-to-end: process 1 compiles cold and persists;
    process 2 (fresh interpreter, warmup on) serves every fused
    program from artifacts — zero XLA compile seconds — and produces
    identical results."""
    cache = str(tmp_path / "xproc")
    cold = _run_proc(cache, "cold")
    assert cold["engine"] == "fused"
    assert cold["compile"]["programsCompiled"] > 0
    assert cold["compile"]["warmHits"] == 0

    warm = _run_proc(cache, "warm")
    assert warm["engine"] == "fused"
    assert warm["total"] == cold["total"]  # warm executables correct
    assert warm["compile"]["programsCompiled"] == 0, warm
    assert warm["compile"]["warmHits"] == \
        cold["compile"]["programsCompiled"]
    assert warm["compile"]["compileSeconds"] == 0.0


@pytest.mark.slow
def test_version_skew_invalidates_artifacts(tmp_path):
    """Stale-artifact invalidation: a VERSION stamp mismatch (jax or
    plugin upgrade) wipes index + artifacts + XLA entries before any
    program loads."""
    cache = str(tmp_path / "skew")
    _run_proc(cache, "cold")
    assert os.listdir(os.path.join(cache, "index"))
    # simulate a plugin upgrade
    stamp = os.path.join(cache, "VERSION.json")
    tok = json.load(open(stamp))
    tok["plugin"] = tok["plugin"] + ".post-upgrade"
    with open(stamp, "w") as f:
        json.dump(tok, f)
    again = _run_proc(cache, "warm")
    # nothing served stale: the run recompiled from scratch
    assert again["compile"]["warmHits"] == 0
    assert again["compile"]["programsCompiled"] > 0


# ------------------------------------------------- index unit layer

def test_collision_mismatch_ignores_artifact(tmp_path):
    cc.reset_for_tests()
    s = TpuSparkSession({
        "spark.rapids.tpu.compileCache.dir": str(tmp_path / "c"),
        "spark.rapids.tpu.compileCache.warmup.enabled": False,
    })
    try:
        adir = os.path.join(cc.cache_dir(), "artifacts")
        # a digest whose .key sidecar names a DIFFERENT structural key
        with open(os.path.join(adir, "deadbeef.key"), "wb") as f:
            f.write(b"('some', 'other', 'key')")
        with open(os.path.join(adir, "deadbeef.bin"), "wb") as f:
            f.write(b"garbage")
        assert cc._load_artifact("deadbeef", "('the', 'real', 'key')") \
            is None
    finally:
        s.stop()
        cc.reset_for_tests()


def test_concurrent_index_writers_never_tear(tmp_path):
    cc.reset_for_tests()
    s = TpuSparkSession({
        "spark.rapids.tpu.compileCache.dir": str(tmp_path / "c"),
        "spark.rapids.tpu.compileCache.warmup.enabled": False,
    })
    try:
        digest = cc.key_digest(("t", "concurrent"))
        errs = []

        def hammer(i):
            try:
                for _ in range(30):
                    cc._record_index(digest, repr(("t", "concurrent")),
                                     "fused", 0.01, False)
            except Exception as e:  # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=hammer, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        # the entry parses (atomic-rename discipline: no torn JSON);
        # counts are best-effort last-writer-wins, only >= 1 guaranteed
        idx = cc.read_index()
        assert idx[digest]["tag"] == "fused"
        assert idx[digest]["count"] >= 1
    finally:
        s.stop()
        cc.reset_for_tests()


def test_disabled_conf_writes_nothing(tmp_path):
    cc.reset_for_tests()
    s = TpuSparkSession({
        "spark.rapids.tpu.compileCache.enabled": False,
        "spark.rapids.tpu.compileCache.dir": str(tmp_path / "off"),
    })
    try:
        t = pa.table({"v": pa.array(np.arange(64, dtype=np.float64))})
        s.createDataFrame(t).filter(F.col("v") > 1.0).collect_arrow()
        assert not cc.enabled()
        assert not os.path.exists(str(tmp_path / "off"))
    finally:
        s.stop()
        cc.reset_for_tests()


# ------------------------------------- placement: a cache that stays put

def test_dir_precedence_env_then_conf_then_fixed(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, then compileCache.dir, then the fixed
    path inside the checkout — never the temp dir, a pid or a time."""
    from spark_rapids_tpu.config import rapids_conf as rc

    conf = rc.RapidsConf(
        {"spark.rapids.tpu.compileCache.dir": str(tmp_path / "conf")})
    no_dir = rc.RapidsConf({"spark.rapids.tpu.compileCache.dir": ""})

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    # the variable wins, jax keeps the directory it read from it (None =
    # this module sets no path), our layers take a sub-directory
    assert cc.resolve_dirs(conf) == (str(tmp_path / "env" / "srtpu"),
                                     None)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert cc.resolve_dirs(conf) == (str(tmp_path / "conf"),
                                     str(tmp_path / "conf" / "xla"))
    root, xla = cc.resolve_dirs(no_dir)
    assert root == cc.FIXED_DIR and xla == os.path.join(root, "xla")
    assert cc.resolve_dirs(None) == (root, xla)  # the same every time
    assert root.startswith(REPO + os.sep)
    import tempfile

    assert not root.startswith(tempfile.gettempdir() + os.sep)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert os.path.basename(root) + "/" in f.read().split()


def test_env_dir_is_left_to_jax(tmp_path, monkeypatch):
    """With the variable set, configure() points jax nowhere else and
    keeps index + artifacts under <dir>/srtpu."""
    import jax

    env_dir = str(tmp_path / "outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    before = jax.config.jax_compilation_cache_dir
    cc.reset_for_tests()
    try:
        cc.configure()
        assert jax.config.jax_compilation_cache_dir == before
        assert cc.cache_dir() == os.path.join(env_dir, "srtpu")
        assert sorted(os.listdir(cc.cache_dir())) == [
            "VERSION.json", "artifacts", "index"]
    finally:
        cc.reset_for_tests()


def test_backend_change_wipes_nothing(tmp_path):
    """A CPU rehearsal and a chip run share one directory: the stamp
    names versions, not the backend, and a version mismatch clears the
    engine's index + artifacts but never jax's own entries."""
    root = str(tmp_path / "shared")
    for sub in ("index", "artifacts", "xla"):
        os.makedirs(os.path.join(root, sub))
        open(os.path.join(root, sub, "entry"), "w").close()
    assert "backend" not in cc.version_token()
    with open(os.path.join(root, "VERSION.json"), "w") as f:
        json.dump(cc.version_token(), f)
    cc._check_version_stamp(root)  # same versions: nothing touched
    assert all(os.path.exists(os.path.join(root, sub, "entry"))
               for sub in ("index", "artifacts", "xla"))
    with open(os.path.join(root, "VERSION.json"), "w") as f:
        json.dump({**cc.version_token(), "jax": "0.0.1"}, f)
    cc._check_version_stamp(root)
    assert not os.path.exists(os.path.join(root, "index", "entry"))
    assert not os.path.exists(os.path.join(root, "artifacts", "entry"))
    assert os.path.exists(os.path.join(root, "xla", "entry"))


def test_warmup_skips_other_backends_artifacts(tmp_path):
    """An artifact another backend exported would fail to compile here
    and be quarantined for both: warmup reads only this backend's."""
    import jax

    cc.reset_for_tests()
    s = TpuSparkSession({
        "spark.rapids.tpu.compileCache.dir": str(tmp_path / "c"),
        "spark.rapids.tpu.compileCache.warmup.enabled": False,
    })
    try:
        key = repr(("fused", "other-backend"))
        digest = cc.key_digest(("fused", "other-backend"))
        cc._record_index(digest, key, "fused", 1.0, True)
        entry = cc.read_index()[digest]
        assert entry["backend"] == jax.default_backend()
        entry["backend"] = "tpu"
        with open(cc._index_path(digest), "w") as f:
            json.dump(entry, f)
        adir = os.path.join(cc.cache_dir(), "artifacts")
        with open(os.path.join(adir, digest + ".key"), "w") as f:
            f.write(key)
        with open(os.path.join(adir, digest + ".bin"), "wb") as f:
            f.write(b"an export for another platform")
        before = cc.stats.snapshot()["artifactsQuarantined"]
        cc._warmup_run(top_k=8)
        assert cc.warm_count() == 0
        assert cc.stats.snapshot()["artifactsQuarantined"] == before
        assert os.path.exists(os.path.join(adir, digest + ".bin"))
    finally:
        s.stop()
        cc.reset_for_tests()


def test_warm_executable_that_fails_is_rebuilt_and_counted():
    """runtime/jit_cache.py keeps a query alive when a warm artifact
    does not run here (rebuilds live) — and now says so."""
    import jax.numpy as jnp

    from spark_rapids_tpu.runtime import jit_cache

    key = ("fused", "warm-rebuild-test")

    def broken(*_a, **_k):
        raise TypeError("aval drift")

    with cc._warm_lock:
        cc._warm[repr(key + jit_cache._env_token())] = broken
    before = cc.stats.snapshot()
    fn = jit_cache.cached_jit(key, lambda: (lambda x: x + 1))
    assert int(fn(jnp.int32(41))) == 42
    assert int(fn(jnp.int32(1))) == 2  # second call: no warm retry
    after = cc.stats.snapshot()
    assert after["warmRebuilds"] == before["warmRebuilds"] + 1
    assert after["warmHits"] == before["warmHits"] + 1
    assert after["programsCompiled"] == before["programsCompiled"] + 1


def test_xla_disk_cache_hits_are_counted(cache_session):
    """Layer 1 reports itself: a structurally identical program built
    again in this process after the in-memory caches are dropped is an
    XLA disk HIT, not a compile."""
    import jax

    from spark_rapids_tpu.runtime import jit_cache

    _mini_q5(cache_session).collect_arrow()
    first = cache_session.last_execution["compile"]
    assert first["xlaCacheMisses"] >= first["programsCompiled"] > 0
    jit_cache.clear()
    jax.clear_caches()
    _mini_q5(cache_session).collect_arrow()
    again = cache_session.last_execution["compile"]
    assert again["programsCompiled"] == first["programsCompiled"]
    assert again["xlaCacheHits"] >= first["programsCompiled"]


def test_export_failure_is_counted_not_silent(cache_session, tmp_path):
    """A fused program jax.export cannot serialize stays index-only —
    and the ledger says so. Programs over dictionary-encoded columns
    are such programs today (DeviceDictionary has no export
    serialization): every program of the bench's q5 and dup-key join."""
    import pyarrow.parquet as pq

    import spark_rapids_tpu.config.rapids_conf as rc

    pq.write_table(pa.table({
        "store": pa.array(np.arange(50), type=pa.int64()),
        "region": pa.array([f"r{i % 4}" for i in range(50)]),
    }), str(tmp_path / "dim.parquet"), use_dictionary=["region"])
    dim = cache_session.read.parquet(str(tmp_path / "dim.parquet"))
    cc._artifact_min_s = 0.0
    try:
        before = cc.stats.snapshot()["artifactExportFailures"]
        dim.groupBy("region").agg(F.count("*").alias("n")).collect_arrow()
        cc.flush()
        failed = cc.stats.snapshot()["artifactExportFailures"] - before
        index = cc.read_index()
        without = [e for e in index.values()
                   if e["tag"] == "fused" and not e["artifact"]]
        assert failed == len(without) > 0, (failed, index)
    finally:
        cc._artifact_min_s = rc.COMPILE_CACHE_ARTIFACT_MIN_S.default


def test_a_warm_artifact_carries_its_programs_name(tmp_path):
    """A fused program is one XLA module name in the device trace
    whoever built it: the index records the traced function's name,
    and warm-up compiles the loaded artifact under it (an entry from
    before there were names is left to be built live once more)."""
    from spark_rapids_tpu.runtime import jit_cache

    jit_cache.clear()
    cc.reset_for_tests()
    s = TpuSparkSession({
        "spark.rapids.tpu.compileCache.dir": str(tmp_path / "c"),
        "spark.rapids.tpu.compileCache.warmup.enabled": False,
        "spark.rapids.tpu.compileCache.artifact.minCompileSecs": 0.0,
    })
    try:
        _mini_q5(s).collect_arrow()
        assert s.last_execution["engine"] == "fused"
        cc.flush()
        served = {d: e for d, e in cc.read_index().items()
                  if e.get("artifact")}
        assert served
        for e in served.values():
            assert e["tag"] == "fused" and e["name"].startswith("fused_")
        old = sorted(served)[0]  # one entry forgets its name
        entry = dict(served[old])
        del entry["name"]
        with open(cc._index_path(old), "w") as f:
            json.dump(entry, f)
        cc._warmup_run(top_k=64)
        assert cc.warm_count() == len(served) - 1
        with cc._warm_lock:
            warm = dict(cc._warm)
        assert served[old]["key"] not in warm
        for d, e in served.items():
            if d != old:
                assert f"jit_{e['name']}" in warm[e["key"]].as_text()
    finally:
        s.stop()
        cc.reset_for_tests()
        jit_cache.clear()


# ------------------------- a keyless aggregate's programs are new keys

# what the parent of the dense keyless lowering called these programs
# (PERF_LEDGER.jsonl, PR 24): a cache that has seen that commit holds
# the scatter lowering under these names' keys
Q6_SCATTER = {"fused_chain_b633ac94", "fused_agg_dbebc581"}
Q1_NAMES = {"fused_chain_831fc292", "fused_agg_285fe60a",
            "fused_sort_dd0047ac", "fused_collect1_029f71ff"}


@pytest.fixture(scope="module")
def lineitem_100k(tmp_path_factory):
    from benchmark import run

    conf = run.load_json(run.HERE, "configs",
                         "tpch_sf10_lineitem_half.json")
    gen = run.load_module("datagen", conf["generator"])
    dirs = gen.generate(conf, 2_147_483_693,
                        str(tmp_path_factory.mktemp("lineitem")),
                        rows=100_000)
    # one task a file, as at the cell's real size: partial aggregates
    # in the chain, a final merge behind them
    return dirs, dict(conf["session_conf"], **{
        "spark.rapids.sql.reader.coalesceSizeBytes": 1})


def _dispatched(query, spark, dirs, cached=False):
    from benchmark import run
    from spark_rapids_tpu.obs import spans

    tables = {t: spark.read.parquet(d) for t, d in dirs.items()}
    if cached:
        tables = {t: df.cache(storage="device")
                  for t, df in tables.items()}
    out = run.load_module("queries", query).build(
        spark, tables).collect_arrow()
    assert run.not_fused(spark.last_execution) == ""
    return out, {sp.extra["program"]
                 for sp in spans.ring.last(1)[0].walk()
                 if sp.name == "fused.dispatch"}


def _without_dense(key):
    """The structural key the parent gave the same program: the
    aggregate's entry without its lowering."""
    if isinstance(key, tuple):
        return tuple(_without_dense(k) for k in key if k != "dense")
    return key


def test_keyless_programs_get_new_names_and_keyed_keep_theirs(
        lineitem_100k):
    dirs, conf = lineitem_100k
    s = TpuSparkSession(dict(conf, **{
        "spark.rapids.tpu.compileCache.enabled": False}))
    try:
        _, q6 = _dispatched("tpch_q6", s, dirs)
        _, q1 = _dispatched("tpch_q1", s, dirs, cached=True)
    finally:
        s.stop()
    assert {n.rsplit("_", 1)[0] for n in q6} == {
        "fused_chain", "fused_agg", "fused_collect1"}
    assert not q6 & Q6_SCATTER
    assert q1 == Q1_NAMES


def test_an_entry_under_the_parents_key_is_not_served(
        lineitem_100k, tmp_path):
    """A cache directory that has seen the parent holds Q6's scatter
    programs under the parent's keys. Re-file this run's artifacts
    under exactly those keys (their names come out as the ledger's),
    warm them up, and run Q6 again: none is taken."""
    import ast
    import shutil

    from spark_rapids_tpu.exec.fused import program_name
    from spark_rapids_tpu.runtime import jit_cache

    dirs, conf = lineitem_100k
    conf = dict(conf, **{
        "spark.rapids.tpu.compileCache.dir": str(tmp_path / "c"),
        "spark.rapids.tpu.compileCache.warmup.enabled": False,
        "spark.rapids.tpu.compileCache.artifact.minCompileSecs": 0.0})
    jit_cache.clear()
    cc.reset_for_tests()
    s = TpuSparkSession(conf)
    try:
        first, _ = _dispatched("tpch_q6", s, dirs)
        cc.flush()
        adir = os.path.join(cc.cache_dir(), "artifacts")
        refiled, old_keys = set(), set()
        for digest, e in cc.read_index().items():
            key = ast.literal_eval(e["key"])
            if e["tag"] != "fused" or "dense" not in e["key"]:
                continue
            assert e["artifact"], e
            old = _without_dense(key)
            old_repr, old_digest = repr(old), cc.key_digest(old)
            name = program_name(old[1], old[2])
            refiled.add(name)
            old_keys.add(old_repr)
            shutil.move(os.path.join(adir, digest + ".bin"),
                        os.path.join(adir, old_digest + ".bin"))
            os.remove(os.path.join(adir, digest + ".key"))
            with open(os.path.join(adir, old_digest + ".key"), "w") as f:
                f.write(old_repr)
            os.remove(cc._index_path(digest))
            with open(cc._index_path(old_digest), "w") as f:
                json.dump(dict(e, key=old_repr, name=name), f)
        assert refiled == Q6_SCATTER
    finally:
        s.stop()
        cc.reset_for_tests()
        jit_cache.clear()
    s = TpuSparkSession(conf)
    try:
        cc._warmup_run(top_k=64)
        with cc._warm_lock:
            assert old_keys <= set(cc._warm)
        others = cc.warm_count() - len(old_keys)  # collect1: same key
        again, names = _dispatched("tpch_q6", s, dirs)
        assert not names & Q6_SCATTER
        assert s.last_execution["compile"]["warmHits"] == others
        assert s.last_execution["compile"]["programsCompiled"] >= 2
        with cc._warm_lock:  # offered, and left alone
            assert set(cc._warm) == old_keys
        assert again.to_pylist() == first.to_pylist()
    finally:
        s.stop()
        cc.reset_for_tests()
        jit_cache.clear()
