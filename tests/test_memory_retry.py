"""Memory/spill/retry suites — the reference's RmmSparkRetrySuiteBase
family analog (WithRetrySuite, RapidsBufferCatalogSuite, ...): force tiny
pools and injected OOMs to exercise spill tiers and retry/split paths.
"""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar import arrow_to_device, device_to_arrow
from spark_rapids_tpu.runtime.errors import (
    TpuOOMError, TpuRetryOOM, TpuSplitAndRetryOOM,
)
from spark_rapids_tpu.runtime.memory import SpillCatalog, SpillTier
from spark_rapids_tpu.runtime.retry import (
    split_spillable_in_half_by_rows,
    with_retry,
    with_retry_no_split,
)
from spark_rapids_tpu.runtime.semaphore import TpuSemaphore


def _batch(n=1000, base=0):
    t = pa.table({"a": pa.array(range(base, base + n), pa.int64()),
                  "b": pa.array([float(i) for i in range(n)], pa.float64())})
    return arrow_to_device(t)


def _mk_catalog(device_limit, host_limit=1 << 30, tmpdir=None, **kw):
    return SpillCatalog(device_limit, host_limit, spill_dir=tmpdir, **kw)


def test_spill_to_host_on_pressure(tmp_path):
    cat = _mk_catalog(device_limit=80_000, tmpdir=str(tmp_path))
    b1 = cat.add_batch(_batch())          # 1024*(8+1+8+1) = 18KB each
    b2 = cat.add_batch(_batch())
    b3 = cat.add_batch(_batch())
    b4 = cat.add_batch(_batch())
    used = cat.device_reserved()
    # next add must evict someone
    b5 = cat.add_batch(_batch())
    tiers = [b.tier for b in (b1, b2, b3, b4, b5)]
    assert SpillTier.HOST in tiers
    assert cat.metrics["spill_to_host"] >= 1
    # unspill works and returns identical data
    got = device_to_arrow(b1.get_batch())
    assert got.column("a").to_pylist()[:3] == [0, 1, 2]
    assert b1.tier == SpillTier.DEVICE
    for b in (b1, b2, b3, b4, b5):
        b.close()
    assert cat.device_reserved() == 0


def test_spill_overflows_to_disk(tmp_path):
    cat = _mk_catalog(device_limit=50_000, host_limit=30_000,
                      tmpdir=str(tmp_path))
    bufs = [cat.add_batch(_batch(base=i * 1000)) for i in range(5)]
    assert cat.metrics["spill_to_disk"] >= 1
    assert any(b.tier == SpillTier.DISK for b in bufs)
    # disk -> device round trip preserves data
    disk_b = next(b for b in bufs if b.tier == SpillTier.DISK)
    idx = bufs.index(disk_b)
    got = device_to_arrow(disk_b.get_batch())
    assert got.column("a").to_pylist()[0] == idx * 1000
    for b in bufs:
        b.close()


def test_split_and_retry_oom_when_nothing_to_spill(tmp_path):
    cat = _mk_catalog(device_limit=10_000, tmpdir=str(tmp_path))
    with pytest.raises(TpuSplitAndRetryOOM):
        cat.add_batch(_batch())  # single batch larger than whole pool


def test_retry_oom_injection_once(tmp_path):
    cat = _mk_catalog(1 << 30, tmpdir=str(tmp_path),
                      oom_injection_mode="once")
    with pytest.raises(TpuRetryOOM):
        cat.add_batch(_batch())
    # second attempt succeeds (injection disarmed)
    b = cat.add_batch(_batch())
    assert cat.metrics["retry_oom_injected"] == 1
    b.close()


def test_with_retry_retries_after_injected_oom(tmp_path):
    cat = _mk_catalog(1 << 30, tmpdir=str(tmp_path))
    sb = cat.add_batch(_batch())
    attempts = {"n": 0}

    def fn(s):
        attempts["n"] += 1
        if attempts["n"] == 1:
            raise TpuRetryOOM("fake transient")
        return s.row_count()

    import spark_rapids_tpu.runtime.memory as mem
    old = mem._catalog
    mem._catalog = cat
    try:
        out = with_retry_no_split(sb, fn)
    finally:
        mem._catalog = old
    assert out == 1000 and attempts["n"] == 2


def test_with_retry_splits_input(tmp_path):
    cat = _mk_catalog(1 << 30, tmpdir=str(tmp_path))
    import spark_rapids_tpu.runtime.memory as mem
    old = mem._catalog
    mem._catalog = cat
    try:
        sb = cat.add_batch(_batch(1000))
        seen = []

        def fn(s):
            if s.row_count() > 300:
                raise TpuSplitAndRetryOOM("too big")
            seen.append(s.row_count())
            return s.row_count()

        results = list(with_retry(sb, fn))
    finally:
        mem._catalog = old
    assert sum(results) == 1000
    assert all(r <= 300 for r in results)
    # order preserved: pieces re-concatenate to original order
    assert cat.buffer_count() == 0  # all closed by the framework


def test_with_retry_split_preserves_order_and_data(tmp_path):
    cat = _mk_catalog(1 << 30, tmpdir=str(tmp_path))
    import spark_rapids_tpu.runtime.memory as mem
    old = mem._catalog
    mem._catalog = cat
    try:
        sb = cat.add_batch(_batch(500))
        calls = {"n": 0}

        def fn(s):
            calls["n"] += 1
            if calls["n"] == 1:
                raise TpuSplitAndRetryOOM("first call too big")
            return device_to_arrow(s.get_batch()).column("a").to_pylist()

        chunks = list(with_retry(sb, fn))
    finally:
        mem._catalog = old
    flat = [x for c in chunks for x in c]
    assert flat == list(range(500))


def test_split_limit_exceeded(tmp_path):
    cat = _mk_catalog(1 << 30, tmpdir=str(tmp_path))
    import spark_rapids_tpu.runtime.memory as mem
    old = mem._catalog
    mem._catalog = cat
    try:
        sb = cat.add_batch(_batch(64))

        def fn(s):
            raise TpuSplitAndRetryOOM("always")

        with pytest.raises(TpuOOMError):
            list(with_retry(sb, fn, split_limit=3))
    finally:
        mem._catalog = old


def test_semaphore_limits_concurrency():
    sem = TpuSemaphore(concurrent_tasks=2)
    sem.acquire_if_necessary(1)
    sem.acquire_if_necessary(2)
    assert sem.holders() == 2
    import threading

    acquired = threading.Event()

    def third():
        sem.acquire_if_necessary(3)
        acquired.set()

    t = threading.Thread(target=third, daemon=True)
    t.start()
    assert not acquired.wait(0.2)  # blocked
    sem.release_if_necessary(1)
    assert acquired.wait(2.0)
    sem.release_if_necessary(2)
    sem.release_if_necessary(3)
    assert sem.holders() == 0


def test_semaphore_reentrant():
    sem = TpuSemaphore(concurrent_tasks=1)
    sem.acquire_if_necessary(7)
    sem.acquire_if_necessary(7)  # no deadlock
    assert sem.holders() == 1
    sem.release_if_necessary(7)


# ------------------- Retryable checkpoint/restore (withRestoreOnRetry)

def test_with_restore_on_retry_restores_on_oom():
    """State mutated by a failed attempt is rolled back before the OOM
    propagates to the enclosing retry loop (reference Retryable.java +
    RmmRapidsRetryIterator.scala:234-261), so the re-attempt runs
    against clean state."""
    from spark_rapids_tpu.runtime.errors import TpuRetryOOM
    from spark_rapids_tpu.runtime.retry import (
        CheckpointedValue,
        retry_on_oom,
        with_restore_on_retry,
    )

    state = CheckpointedValue(0)
    attempts = {"n": 0}

    def body():
        state.value += 10  # mutation an aborted attempt must not keep
        attempts["n"] += 1
        if attempts["n"] == 1:
            raise TpuRetryOOM("injected")
        return state.value

    out = retry_on_oom(lambda: with_restore_on_retry(state, body))
    assert out == 10  # not 20: the first attempt's mutation rolled back
    assert attempts["n"] == 2


def test_pending_batches_restore_closes_orphans():
    """PendingBatches.restore closes spillables appended after the
    checkpoint — an aborted attempt leaks nothing from the catalog."""
    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.columnar.arrow_bridge import arrow_to_device
    from spark_rapids_tpu.runtime.memory import get_catalog
    from spark_rapids_tpu.runtime.retry import PendingBatches

    catalog = get_catalog()
    base = catalog.live_handles() if hasattr(catalog, "live_handles") \
        else None
    t = pa.table({"x": pa.array(np.arange(8), type=pa.int64())})

    p = PendingBatches()
    p.append(catalog.add_batch(arrow_to_device(t)), 8)
    p.checkpoint()
    p.append(catalog.add_batch(arrow_to_device(t)), 8)
    p.append(catalog.add_batch(arrow_to_device(t)), 8)
    assert len(p.items) == 3 and p.rows == 24
    p.restore()
    assert len(p.items) == 1 and p.rows == 8
    p.close()
    if base is not None:
        assert catalog.live_handles() == base


def test_restore_on_retry_split_storm_no_double_count(tmp_path):
    """Satellite (PR 2): with_restore_on_retry + PendingBatches under
    an injected split-and-retry STORM — every input batch suffers
    retry OOMs after partial appends AND split OOMs that halve it, and
    the checkpointed accumulator must come out with EXACTLY the input
    row count (no double counting from re-run attempts) and the spill
    catalog must be empty afterwards (no leaked entries from aborted
    attempts)."""
    from spark_rapids_tpu.runtime.retry import (
        PendingBatches,
        with_restore_on_retry,
    )

    cat = _mk_catalog(1 << 30, tmpdir=str(tmp_path))
    import spark_rapids_tpu.runtime.memory as mem
    old = mem._catalog
    mem._catalog = cat
    try:
        total_rows = 1000
        inputs = [cat.add_batch(_batch(total_rows))]
        pending = PendingBatches()
        storm = {"retries_left": 5}

        def body(sb):
            n = sb.row_count()
            # partial append FIRST — the state a failed attempt must
            # not keep
            pending.append(cat.add_batch(sb.get_batch()), n)
            if n > 300:
                raise TpuSplitAndRetryOOM("storm: too big")
            if storm["retries_left"] > 0:
                storm["retries_left"] -= 1
                raise TpuRetryOOM("storm: transient")
            return n

        done = list(with_retry(
            inputs, lambda sb: with_restore_on_retry(pending,
                                                     lambda: body(sb))))
        assert storm["retries_left"] == 0  # the storm actually fired
        assert sum(done) == total_rows
        assert pending.rows == total_rows  # no double-counted appends
        assert sum(sb.row_count() for sb in pending.items) == total_rows
        # nothing leaked: only the accumulator's own entries remain...
        assert cat.buffer_count() == len(pending.items)
        pending.close()
        # ...and closing it empties the catalog entirely
        assert cat.buffer_count() == 0
        assert cat.check_leaks() == 0
    finally:
        mem._catalog = old


def test_restore_on_retry_storm_checkpointed_value(tmp_path):
    """CheckpointedValue under the same storm: a scalar accumulator
    (e.g. an output-row counter) never counts an aborted attempt."""
    from spark_rapids_tpu.runtime.retry import (
        CheckpointedValue,
        with_restore_on_retry,
    )

    cat = _mk_catalog(1 << 30, tmpdir=str(tmp_path))
    import spark_rapids_tpu.runtime.memory as mem
    old = mem._catalog
    mem._catalog = cat
    try:
        inputs = [cat.add_batch(_batch(800))]
        counter = CheckpointedValue(0)
        fails = {"n": 4}

        def body(sb):
            counter.value += sb.row_count()
            if fails["n"] > 0:
                fails["n"] -= 1
                raise TpuRetryOOM("storm")
            return True

        list(with_retry(inputs,
                        lambda sb: with_restore_on_retry(
                            counter, lambda: body(sb))))
        assert counter.value == 800  # attempts re-ran, count did not
        assert cat.buffer_count() == 0
    finally:
        mem._catalog = old


# ----------------------------------------------- HBM size: read, not guessed

class _FakeDevice:
    def __init__(self, platform, kind, stats):
        self.platform, self.device_kind, self._stats = platform, kind, stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("device,want", [
    (_FakeDevice("tpu", "TPU v5 lite", {"bytes_limit": 16_909_336_576}),
     16_909_336_576),
    (_FakeDevice("cpu", "cpu", None), 16 << 30),
], ids=["tpu-reports-its-limit", "cpu-backend-nominal-pool"])
def test_detect_hbm_bytes_reads_the_device(monkeypatch, device, want):
    import jax

    from spark_rapids_tpu.runtime import memory

    monkeypatch.setattr(jax, "local_devices", lambda *a: [device])
    assert memory._detect_hbm_bytes() == want


def test_detect_hbm_bytes_never_guesses_for_a_chip(monkeypatch):
    """A TPU that reports no limit is an error: a pretended 16 GiB
    budgets the wrong chip."""
    import jax

    from spark_rapids_tpu.runtime import memory

    monkeypatch.setattr(
        jax, "local_devices",
        lambda *a: [_FakeDevice("tpu", "TPU v9", {})])
    with pytest.raises(RuntimeError, match="bytes_limit"):
        memory._detect_hbm_bytes()
