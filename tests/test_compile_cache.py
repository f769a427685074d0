"""Persistent cross-process compilation layer
(runtime/compile_cache.py: jax's disk cache and nothing beside it) +
fused variant dedup (exec/fused.py run_program canonical keys): the
round-5 cold-start killer.

Covers the acceptance surface: cross-process executable reuse, a
changed lowering under an unchanged key, no thread and no file of the
engine's own, per-query compile metrics, and the canonical-key dedup
that stops expansion retries / re-lowerings / the ANSI channel from
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
    base_conf = {"spark.rapids.tpu.compileCache.dir": cache}
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


# ---------------------------------------------------- cross-process

_PROC_SCRIPT = textwrap.dedent("""
    import json, sys
    import jax; jax.config.update("jax_platforms", "cpu")
    import numpy as np, pyarrow as pa
    from spark_rapids_tpu.api.session import TpuSparkSession
    from spark_rapids_tpu.api import functions as F

    cache_dir, mode = sys.argv[1], sys.argv[2]
    s = TpuSparkSession({"spark.rapids.tpu.compileCache.dir": cache_dir})
    t = pa.table({"k": pa.array(np.arange(2000) % 11,
                                type=pa.int64()),
                  "v": pa.array(np.arange(2000, dtype=np.float64))})
    out = (s.createDataFrame(t).filter(F.col("v") > 5.0)
           .groupBy("k").agg(F.sum("v").alias("s"))
           .collect_arrow())
    total = sum(out.column("s").to_pylist())
    print(json.dumps({"engine": s.last_execution["engine"],
                      "compile": s.last_execution["compile"],
                      "total": total}), flush=True)
    if mode == "stop":
        s.stop()
    # mode "leave": the interpreter exits with the session open
""")

# one structural key, two lowerings: what a perf PR on a lowering does
# to a directory that has seen its parent
_STALE_SCRIPT = textwrap.dedent("""
    import sys
    import jax; jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    from spark_rapids_tpu.api.session import TpuSparkSession
    from spark_rapids_tpu.runtime import jit_cache

    cache_dir, lowering = sys.argv[1], sys.argv[2]
    s = TpuSparkSession({"spark.rapids.tpu.compileCache.dir": cache_dir})

    def program(x):
        return x + 1 if lowering == "parent" else x * 2

    program.__name__ = program.__qualname__ = "fused_chain_00000000"
    fn = jit_cache.cached_jit(("fused", "chain", "one-key"),
                              lambda: program)
    print("answer", int(fn(jnp.arange(8, dtype=jnp.int32))[3]), flush=True)
    s.stop()
""")


def _run_script(script: str, *argv: str) -> str:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    r = subprocess.run(
        [sys.executable, "-c", script, *argv],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert r.returncode == 0, (r.returncode, r.stderr[-2000:])
    return r.stdout


def _run_proc(cache_dir: str, mode: str) -> dict:
    out = _run_script(_PROC_SCRIPT, cache_dir, mode)
    return json.loads(
        [ln for ln in out.splitlines() if ln.startswith("{")][-1])


@pytest.fixture(scope="module")
def filled(tmp_path_factory):
    """(a directory one process has compiled the query into, what that
    process printed)."""
    cache = str(tmp_path_factory.mktemp("xproc"))
    cold = _run_proc(cache, "stop")
    assert cold["engine"] == "fused"
    assert cold["compile"]["programsCompiled"] > 0
    assert cold["compile"]["xlaCacheMisses"] > 0
    return cache, cold


def test_second_process_loads_every_program_from_the_directory(filled):
    """Process 2 (fresh interpreter) traces the same programs and jax's
    cache serves every executable: no XLA compile, the same answer."""
    cache, cold = filled
    warm = _run_proc(cache, "stop")
    assert warm["engine"] == "fused"
    assert warm["total"] == cold["total"]
    comp = warm["compile"]
    assert comp["programsCompiled"] == cold["compile"]["programsCompiled"]
    assert comp["xlaCacheMisses"] == 0, comp
    assert comp["xlaCacheHits"] > 0, comp


def test_a_process_that_exits_at_once_over_a_filled_directory_returns_0(
        filled):
    """One query over a directory another process filled, then the end
    of the interpreter with the session open: nothing of the engine's
    is still compiling (the warm-up thread aborted such a process, rc
    -6); `_run_script` asserts the return code."""
    cache, cold = filled
    assert _run_proc(cache, "leave")["total"] == cold["total"]


def test_a_changed_lowering_under_an_unchanged_key_is_not_served_stale(
        tmp_path):
    """jax keys its entries on the HLO, so the engine's key names a
    program and does not have to vouch for its lowering: the parent's
    program under the same structural key, in the same directory, is
    never handed to the change."""
    cache = str(tmp_path / "c")
    assert _run_script(_STALE_SCRIPT, cache, "parent").split()[-2:] == [
        "answer", "4"]
    assert _run_script(_STALE_SCRIPT, cache, "change").split()[-2:] == [
        "answer", "6"]


def _files_under(root):
    return {os.path.join(d, f): (st.st_ino, st.st_mtime_ns, st.st_size)
            for d, _, fs in os.walk(root) for f in fs
            for st in [os.stat(os.path.join(d, f))]}


def test_the_third_run_of_a_query_writes_nothing_under_the_cache_root(
        cache_session):
    """The hot path creates, renames and touches no file: jax writes an
    entry inside the compile that made it, and the engine writes none."""
    s = cache_session
    assert os.listdir(cc.cache_dir()) == ["xla"]
    q = _mini_q5(s)
    q.collect_arrow()
    q.collect_arrow()
    assert os.listdir(os.path.join(cc.cache_dir(), "xla"))
    before = _files_under(cc.cache_dir())
    q.collect_arrow()  # every program is resident
    assert s.last_execution["compile"]["programsCompiled"] == 0
    assert _files_under(cc.cache_dir()) == before


def test_the_compile_record_has_exactly_these_fields(cache_session):
    _mini_q5(cache_session).collect_arrow()
    assert set(cache_session.last_execution["compile"]) == {
        "programsCompiled", "cacheHits", "compileSeconds",
        "xlaCacheHits", "xlaCacheMisses", "variantCount"}
    assert set(cache_session.compile_cache_stats) == {
        "programsCompiled", "cacheHits", "compileSeconds",
        "xlaCacheHits", "xlaCacheMisses"}


def test_default_session_has_no_compile_cache_thread():
    """The default conf, not a test's: whatever the session starts, no
    thread of the compile cache is among it."""
    s = TpuSparkSession()
    try:
        assert cc.enabled()
        assert not [t.name for t in threading.enumerate()
                    if t.name.startswith("srtpu-compile-cache")]
    finally:
        s.stop()


REMOVED_KEYS = ["spark.rapids.tpu.compileCache.warmup.enabled",
                "spark.rapids.tpu.compileCache.warmup.topK",
                "spark.rapids.tpu.compileCache.artifact.minCompileSecs"]


@pytest.mark.parametrize("key", REMOVED_KEYS)
def test_a_removed_option_is_an_unknown_key_like_any_other(key):
    from spark_rapids_tpu.config import rapids_conf as rc

    assert key not in {e.key for e in rc.conf_entries()}
    with open(os.path.join(REPO, "docs", "configs.md")) as f:
        assert key not in f.read()
    s = TpuSparkSession({key: "1"})
    try:
        assert key in s.rapids_conf.unknown_keys
    finally:
        s.stop()


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
    # this module sets no path), the engine's root is a sub-directory
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
    keeps the engine's root at <dir>/srtpu, empty."""
    import jax

    env_dir = str(tmp_path / "outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    before = jax.config.jax_compilation_cache_dir
    cc.reset_for_tests()
    try:
        cc.configure()
        assert jax.config.jax_compilation_cache_dir == before
        assert cc.cache_dir() == os.path.join(env_dir, "srtpu")
        assert os.listdir(cc.cache_dir()) == []
    finally:
        cc.reset_for_tests()


def test_xla_disk_cache_hits_are_counted(cache_session):
    """jax's cache reports itself: a structurally identical program built
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


# ------------- every cell's programs come back from the disk, by name

@pytest.mark.parametrize("name", ["tpch_q1_resident",
                                  "tpch_q6_scan_uncached",
                                  "tpch_q12_join_resident"],
                         ids=["tpch_q1", "tpch_q6", "tpch_q12"])
def test_a_second_session_builds_a_cells_programs_from_the_disk_cache(
        name, tmp_path):
    """The one builder left, on each cell's query as `benchmark/run.py`
    builds it: after the in-process caches are dropped, a new session
    over the same directory traces every program again under the name
    it had, and XLA compiles none of them."""
    import jax

    from benchmark import run
    from spark_rapids_tpu.runtime import jit_cache

    cell = run.load_cell(name)
    gen = run.load_module("datagen", cell["config"]["generator"])
    dirs = gen.generate(cell["config"], 2_147_483_693,
                        str(tmp_path / "data"), rows=40_000)
    conf = dict(cell["config"]["session_conf"], **{
        "spark.rapids.sql.reader.coalesceSizeBytes": 1,
        "spark.rapids.tpu.compileCache.dir": str(tmp_path / "cache")})
    (query,) = cell["traffic"]["queries"]
    cached = "device" in cell["traffic"]["tables"].values()

    def one_session():
        _abandoned_attempts_done()
        jit_cache.clear()
        jax.clear_caches()
        cc.reset_for_tests()
        s = TpuSparkSession(conf)
        try:
            before = cc.stats.snapshot()
            out, names = _dispatched(query, s, dirs, cached=cached)
            # the device cache's fill compiles too: the whole session
            return out, names, cc.stats.delta(before, cc.stats.snapshot())
        finally:
            s.stop()
            cc.reset_for_tests()

    first, names, cold = one_session()
    assert cold["programsCompiled"] > 0 and cold["xlaCacheMisses"] > 0
    again, names_again, warm = one_session()
    assert names_again == names and len(names) >= 3
    assert warm["programsCompiled"] == cold["programsCompiled"]
    assert warm["xlaCacheMisses"] == 0, warm
    assert warm["xlaCacheHits"] >= warm["programsCompiled"]
    assert again.to_pylist() == first.to_pylist()
