"""Device memory pool + tiered spill catalog.

Reference architecture being reproduced (SURVEY.md section 2.3):
- `RapidsBufferCatalog` (RapidsBufferCatalog.scala:62): catalog of
  spillable buffers across DEVICE -> HOST -> DISK tiers, synchronous
  spill on allocation failure (:592).
- `DeviceMemoryEventHandler`: alloc-failure -> spill-N-bytes callback.
- `SpillableColumnarBatch`: operator state parked spillable between
  per-batch steps (SpillableColumnarBatch.scala).
- `SpillPriorities`: lower value spills first.

TPU redesign: PJRT gives no per-allocation failure callback, so the pool
is a *reservation ledger* sitting in front of JAX: every operator batch
is registered with its byte size; `reserve()` checks the ledger against
the budget, synchronously spilling coldest-first (device_get -> pinned
numpy -> .npy file) until the reservation fits, then raises TpuRetryOOM /
TpuSplitAndRetryOOM exactly where RmmSpark would inject them. Tests force
tiny budgets + injection to exercise every path (the reference's
*RetrySuite strategy, SURVEY.md section 4 tier 2).
"""

from __future__ import annotations

import contextlib
import os
import tempfile
import threading
import time
import uuid
import zipfile
from enum import Enum
from typing import Dict, List, Optional

import jax
import numpy as np

from spark_rapids_tpu.columnar.batch import ColumnBatch
from spark_rapids_tpu.runtime.errors import (
    RetryExhausted,
    SpillFileError,
    TpuRetryOOM,
    TpuSplitAndRetryOOM,
)


class SpillTier(Enum):
    DEVICE = 0
    HOST = 1
    DISK = 2


#: uids of every SpillCatalog constructed by THIS process. The startup
#: orphan sweep removes spill files whose embedded catalog uid is not
#: in this set: a crashed process's leftovers (truncated .inprogress
#: writes AND completed files nothing references anymore) are garbage,
#: while a force-rebuilt session's previous catalog — whose live
#: spillables still reference their files — stays untouched.
_live_catalog_uids = set()
_live_uids_lock = threading.Lock()


class SpillPriority:
    """Lower spills first (reference SpillPriorities.scala)."""

    INPUT_FROM_SHUFFLE = -200
    ACTIVE_BATCHING = -100
    ACTIVE_ON_DECK = 0
    HOST_MEMORY = 100


class SpillableBatch:
    """A registered, spillable columnar batch (SpillableColumnarBatch
    analog). Not thread-safe per instance; the catalog lock serializes
    tier moves."""

    def __init__(self, catalog: "SpillCatalog", batch: ColumnBatch,
                 priority: int, query_id: int = 0):
        self._catalog = catalog
        self._priority = priority
        self._tier = SpillTier.DEVICE
        self._device_batch: Optional[ColumnBatch] = batch
        self._host_data = None
        self._disk_path: Optional[str] = None
        self._treedef = None
        self.query_id = query_id  # owning query (0 = unattributed)
        self.size_bytes = batch.device_size_bytes()
        self._rows = None  # lazy: row_count() syncs the device (one
        # round trip each; hundreds of parks per query)
        self.id = uuid.uuid4().hex[:12]
        self.closed = False
        # device-epoch stamp of the DEVICE-tier copy
        # (runtime/device_monitor.py): a device-loss recovery marks
        # every device-resident buffer lost; host/disk copies survive
        # and re-stamp on unspill
        from spark_rapids_tpu.runtime import device_monitor

        self.device_epoch = device_monitor.current_epoch()
        self._device_lost = False

    @property
    def tier(self) -> SpillTier:
        return self._tier

    def row_count(self) -> int:
        if self._rows is None:
            # the catalog RLock serializes against tier moves
            # (_to_host/_to_disk also run under it); whichever tier the
            # batch is on, its copy carries the count
            with self._catalog._lock:
                if self._rows is None:
                    if self._device_batch is not None:
                        self._rows = self._device_batch.row_count()
                    elif self._host_data is not None:
                        # num_rows is the LAST pytree leaf
                        self._rows = int(self._host_data[-1])
                    elif self._disk_path is not None:
                        def last():
                            with np.load(self._disk_path) as z:
                                return int(z[z.files[-1]])

                        self._rows = self._disk_io(
                            last, "read", self._disk_path)
                    else:
                        raise RuntimeError(
                            "row_count() on a closed SpillableBatch")
        return self._rows

    # --- tier transitions (called under catalog lock) ---

    def _to_host(self):
        assert self._tier == SpillTier.DEVICE
        import time as _time

        from spark_rapids_tpu.obs import telemetry
        from spark_rapids_tpu.runtime.profiler import annotate

        leaves, treedef = jax.tree_util.tree_flatten(self._device_batch)
        t0 = _time.monotonic_ns()
        with annotate(f"spill:D2H:{self.size_bytes}"):
            self._host_data = [np.asarray(jax.device_get(x))
                               for x in leaves]
        telemetry.record("d2h", "spill.toHost", self.size_bytes,
                         ns=_time.monotonic_ns() - t0,
                         query_id=self.query_id)
        self._treedef = treedef
        self._device_batch = None
        self._tier = SpillTier.HOST

    def _disk_io(self, fn, op: str, path: str):
        """Run one disk-tier spill read/write under the spill.disk
        backoff policy; terminal failure surfaces as a SpillFileError
        naming this buffer's id, tier, and path — never a raw
        numpy/OSError through an operator. A MISSING spill file is
        immediate (deleted out from under us: not transient)."""
        from spark_rapids_tpu.runtime import backoff

        try:
            return backoff.retry_io(
                fn, what=f"spill {op} {path}", site="spill.disk",
                retry_on=(OSError, ValueError, zipfile.BadZipFile,
                          EOFError),
                no_retry=(FileNotFoundError,), counter="spill.disk")
        except FileNotFoundError as e:
            raise SpillFileError(self.id, self._tier.name, path,
                                 op=op) from e
        except RetryExhausted as e:
            raise SpillFileError(self.id, self._tier.name, path,
                                 op=op) from e

    def _to_disk(self):
        assert self._tier == SpillTier.HOST
        import time as _time

        from spark_rapids_tpu.obs import telemetry
        from spark_rapids_tpu.runtime.profiler import annotate

        path = os.path.join(
            self._catalog.spill_dir,
            f"spill-{self._catalog.uid}-{self.id}.npz")

        def write_atomic():
            # crash consistency: a process dying mid-spill must never
            # leave a truncated file a later unspill trusts — write to
            # .inprogress, fsync, then atomically rename into place
            # (the catalog startup sweep reaps orphaned .inprogress
            # files of dead processes)
            tmp = path + ".inprogress"
            with open(tmp, "wb") as f:
                np.savez(f, *self._host_data)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)

        t0 = _time.monotonic_ns()
        with annotate(f"spill:HOST2DISK:{self.size_bytes}"):
            self._disk_io(write_atomic, "write", path)
        telemetry.record("spill-disk", "spill.toDisk", self.size_bytes,
                         ns=_time.monotonic_ns() - t0,
                         query_id=self.query_id)
        self._disk_path = path
        self._host_data = None
        self._tier = SpillTier.DISK

    def _host_from_disk(self):
        assert self._tier == SpillTier.DISK
        import time as _time

        from spark_rapids_tpu.obs import telemetry

        def load():
            with np.load(self._disk_path) as z:
                return [z[k] for k in z.files]

        t0 = _time.monotonic_ns()
        self._host_data = self._disk_io(load, "read", self._disk_path)
        telemetry.record("spill-disk", "spill.fromDisk", self.size_bytes,
                         ns=_time.monotonic_ns() - t0,
                         query_id=self.query_id)
        os.unlink(self._disk_path)
        self._disk_path = None
        self._tier = SpillTier.HOST

    def _to_device(self):
        if self._tier == SpillTier.DISK:
            self._host_from_disk()
        if self._tier == SpillTier.HOST:
            import time as _time

            from spark_rapids_tpu.obs import telemetry
            from spark_rapids_tpu.runtime import device_monitor
            from spark_rapids_tpu.runtime.profiler import annotate

            t0 = _time.monotonic_ns()
            # transfer-site fatal classification + device.fatal chaos:
            # an H2D upload into a dead backend is a fence trigger,
            # not a raw XlaRuntimeError through an operator
            with device_monitor.guard("spill.unspill", inject=True):
                with annotate(f"unspill:H2D:{self.size_bytes}"):
                    leaves = [jax.device_put(x)
                              for x in self._host_data]
            telemetry.record("h2d", "spill.unspill", self.size_bytes,
                             ns=_time.monotonic_ns() - t0,
                             query_id=self.query_id)
            self._device_batch = jax.tree_util.tree_unflatten(
                self._treedef, leaves)
            self._host_data = None
            self._tier = SpillTier.DEVICE
            # freshly uploaded: this copy belongs to the live backend
            from spark_rapids_tpu.runtime import device_monitor

            self.device_epoch = device_monitor.current_epoch()
            self._device_lost = False

    # --- public API ---

    def get_batch(self) -> ColumnBatch:
        """Materialize on device (unspilling if needed; reserves
        budget). A DEVICE-tier copy from a dead epoch raises
        DeviceLostError instead of handing out recycled device memory
        — the buffer was device-only when the device died, so the
        owner must recompute (lineage scheduler / query resubmit)."""
        if self._tier == SpillTier.DEVICE:
            from spark_rapids_tpu.runtime import device_monitor

            if self._device_lost:
                device_monitor.check_stale(
                    self.device_epoch, f"spillable buffer {self.id}")
                # lost flag without an epoch delta cannot happen (the
                # flag is only set by on_device_lost after a bump),
                # but never hand out a lost buffer either way
                from spark_rapids_tpu.runtime.errors import (
                    DeviceLostError,
                )

                raise DeviceLostError(
                    f"spillable buffer {self.id} was device-resident "
                    f"when the device was lost; recompute it",
                    epoch=self.device_epoch)
            device_monitor.check_stale(
                self.device_epoch, f"spillable buffer {self.id}")
        self._catalog.unspill(self)
        return self._device_batch

    def close(self):
        if self.closed:
            return
        self.closed = True
        self._catalog.remove(self)
        if self._disk_path and os.path.exists(self._disk_path):
            os.unlink(self._disk_path)
        self._device_batch = None
        self._host_data = None


class DeviceMemoryPool:
    """Reservation ledger for device HBM (the Rmm pool analog). Every
    successful reserve/release feeds the telemetry occupancy timeline
    (obs/telemetry.py) with the post-op total, so HBM occupancy over
    time is a recorded series, not a point probe."""

    def __init__(self, limit_bytes: int):
        self.limit = limit_bytes
        self.reserved = 0
        self.peak = 0
        self._lock = threading.RLock()

    def try_reserve(self, nbytes: int) -> bool:
        from spark_rapids_tpu.obs import telemetry

        with self._lock:
            if self.reserved + nbytes > self.limit:
                return False
            self.reserved += nbytes
            self.peak = max(self.peak, self.reserved)
            telemetry.hbm_global(self.reserved)
            return True

    def release(self, nbytes: int):
        from spark_rapids_tpu.obs import telemetry

        with self._lock:
            self.reserved = max(0, self.reserved - nbytes)
            telemetry.hbm_global(self.reserved)


class SpillCatalog:
    """RapidsBufferCatalog analog: tracks spillables, performs synchronous
    coldest-first spill when device reservations fail."""

    def __init__(self, device_limit: int, host_limit: int,
                 spill_dir: Optional[str] = None,
                 oom_injection_mode: str = "none",
                 oom_injection_filter: str = "",
                 oom_dump_dir: str = "",
                 query_quota_bytes: int = 0):
        self.pool = DeviceMemoryPool(device_limit)
        self.host_limit = host_limit
        self.host_used = 0
        self.spill_dir = spill_dir or tempfile.mkdtemp(prefix="srtpu-spill-")
        self.uid = uuid.uuid4().hex[:8]
        with _live_uids_lock:
            _live_catalog_uids.add(self.uid)
        self._buffers: Dict[str, SpillableBatch] = {}
        self._lock = threading.RLock()
        # per-query DEVICE reservation ledger (the quota unit,
        # spark.rapids.tpu.quota.device.maxBytesPerQuery); its own lock
        # because reserve() runs outside the catalog lock
        self.query_quota_bytes = max(0, int(query_quota_bytes))
        self._q_dev: Dict[int, int] = {}
        self._q_lock = threading.Lock()
        self._oom_mode = oom_injection_mode
        self._oom_filter = oom_injection_filter
        self._oom_dump_dir = oom_dump_dir
        self._oom_armed = oom_injection_mode in ("once", "always",
                                                 "split_once")
        self.metrics = {
            "spill_to_host": 0, "spill_to_disk": 0, "unspill": 0,
            "retry_oom_injected": 0, "quota_oom": 0,
            "orphaned_files_swept": 0, "device_lost_buffers": 0,
        }
        self._sweep_orphans()

    def _sweep_orphans(self) -> None:
        """Catalog-startup crash recovery: remove spill files owned by
        no live catalog of this process — truncated `.inprogress`
        writes AND completed files a dead process left behind (a crash
        loses every in-memory reference, so they are unreachable).
        Counted in metrics['orphaned_files_swept'] (the
        spill.orphanedFiles robustness metric)."""
        try:
            names = os.listdir(self.spill_dir)
        except OSError:
            return
        with _live_uids_lock:
            live = set(_live_catalog_uids)
        swept = 0
        for name in names:
            core = name[:-len(".inprogress")] \
                if name.endswith(".inprogress") else name
            if not (core.startswith("spill-") and core.endswith(".npz")):
                continue
            parts = core[len("spill-"):-len(".npz")].split("-")
            owner = parts[0] if len(parts) >= 2 else ""
            if owner in live:
                # a live catalog's file: completed files are
                # referenced by its spillables; an .inprogress file
                # may be a concurrent in-flight write — never touch
                continue
            try:
                os.unlink(os.path.join(self.spill_dir, name))
                swept += 1
            except OSError:
                pass
        self.metrics["orphaned_files_swept"] = swept

    # --- registration ---

    def add_batch(self, batch: ColumnBatch,
                  priority: int = SpillPriority.ACTIVE_ON_DECK
                  ) -> SpillableBatch:
        from spark_rapids_tpu.obs import events as obs_events

        qid = obs_events.effective_query_id()
        sb = SpillableBatch(self, batch, priority, query_id=qid)
        self.reserve(sb.size_bytes, tag="add_batch", query_id=qid)
        from spark_rapids_tpu.runtime import faults

        if faults.should_inject("device.lost_buffer"):
            # chaos site device.lost_buffer: poison THIS buffer's
            # device epoch so its next use hits the stale-handle gate
            # deterministically — the proof that pre-epoch handles
            # raise DeviceLostError instead of reading recycled memory
            sb.device_epoch -= 1
        with self._lock:
            self._buffers[sb.id] = sb
        return sb

    def remove(self, sb: SpillableBatch):
        with self._lock:
            if self._buffers.pop(sb.id, None) is None:
                return
            if sb.tier == SpillTier.DEVICE:
                if sb._device_lost:
                    # reservation already released by on_device_lost
                    # (the dead backend freed the HBM); a second
                    # release would corrupt the ledger
                    return
                self.pool.release(sb.size_bytes)
                self._q_release(sb.query_id, sb.size_bytes)
            elif sb.tier == SpillTier.HOST:
                self.host_used -= sb.size_bytes
                from spark_rapids_tpu.runtime import host_alloc

                host_alloc.get().pageable.release(sb.size_bytes)

    # --- reservation with synchronous spill ---

    def _maybe_inject_oom(self, tag: str):
        if not self._oom_armed:
            return
        if self._oom_filter and self._oom_filter not in tag:
            return
        if self._oom_mode in ("once", "split_once"):
            self._oom_armed = False
        self.metrics["retry_oom_injected"] += 1
        if self._oom_mode == "split_once":
            raise TpuSplitAndRetryOOM(f"injected split OOM at {tag}")
        raise TpuRetryOOM(f"injected OOM at {tag}")

    # --- per-query quota ledger (all under _q_lock) ---

    @staticmethod
    def _resolve_qid(query_id: Optional[int]) -> int:
        if query_id is not None:
            return query_id
        from spark_rapids_tpu.obs import events as obs_events

        return obs_events.effective_query_id()

    def _q_add(self, qid: int, nbytes: int) -> None:
        if not qid:
            return
        from spark_rapids_tpu.obs import telemetry

        with self._q_lock:
            cur = self._q_dev[qid] = self._q_dev.get(qid, 0) + nbytes
            telemetry.hbm_query(qid, cur)

    def _q_release(self, qid: int, nbytes: int) -> None:
        if not qid:
            return
        from spark_rapids_tpu.obs import telemetry

        with self._q_lock:
            left = self._q_dev.get(qid, 0) - nbytes
            if left > 0:
                self._q_dev[qid] = left
            else:
                self._q_dev.pop(qid, None)
            telemetry.hbm_query(qid, max(0, left))

    def query_device_reserved(self, query_id: int) -> int:
        with self._q_lock:
            return self._q_dev.get(query_id, 0)

    def _quota_admit(self, qid: int, nbytes: int, tag: str) -> None:
        """Per-query quota gate: an over-quota reservation first spills
        the OFFENDING query's own device buffers, then raises a
        retry-class OOM for that query only — session-wide pressure
        stays untouched (the Vortex capacity-isolation stance)."""
        quota = self.query_quota_bytes
        if not qid or quota <= 0:
            return
        with self._q_lock:
            cur = self._q_dev.get(qid, 0)
        if cur + nbytes <= quota:
            return
        freed = self.spill_device_bytes(cur + nbytes - quota,
                                        query_id=qid)
        with self._q_lock:
            cur = self._q_dev.get(qid, 0)
        if cur + nbytes <= quota:
            return
        self.metrics["quota_oom"] += 1
        if freed > 0:
            raise TpuRetryOOM(
                f"query {qid} over device quota reserving {nbytes} "
                f"(tag={tag}, quota={quota}, reserved={cur}); spilled "
                f"{freed} of its bytes, retry")
        raise TpuSplitAndRetryOOM(
            f"query {qid} device quota {quota} cannot fit {nbytes} "
            f"(tag={tag}, reserved={cur}); split the input and retry")

    def _note_quota_contention(self, qid: int) -> None:
        """Sanitizer hook at a failed reservation: sync the quota
        resource's holder set from the per-query ledger, then insert
        the transient wait-for edge (cycle detection runs on the
        insertion). A query spinning in TpuRetryOOM because OTHER
        queries' reservations fill the device is waiting on them just
        as surely as a parked semaphore ticket — this is what closes
        cross-class cycles (hold permits, wait memory / hold memory,
        wait permits)."""
        from spark_rapids_tpu.runtime import sanitizer as _san

        san = _san.active()
        if san is None:
            return
        now = time.monotonic()
        with self._q_lock:
            owners = {q: now for q, b in self._q_dev.items()
                      if b > 0 and q != qid}
        res = _san.quota_resource()
        san.report_holders(res, owners)
        san.note_contention(res, qid)

    def reserve(self, nbytes: int, tag: str = "",
                query_id: Optional[int] = None):
        """Reserve device bytes; spill synchronously if needed; raise
        TpuRetryOOM when spilling freed something (caller must retry) or
        TpuSplitAndRetryOOM when nothing can free enough. Reservations
        are tagged with the owning query (resolved from the obs task/
        query scope when not passed) and gated by the per-query quota
        BEFORE touching the shared pool."""
        self._maybe_inject_oom(tag)
        qid = self._resolve_qid(query_id)
        self._quota_admit(qid, nbytes, tag)
        if self.pool.try_reserve(nbytes):
            self._q_add(qid, nbytes)
            return
        shortfall = max(0, nbytes - (self.pool.limit - self.pool.reserved))
        freed = self.spill_device_bytes(shortfall)
        if self.pool.try_reserve(nbytes):
            self._q_add(qid, nbytes)
            return
        self._note_quota_contention(qid)
        if freed > 0:
            raise TpuRetryOOM(
                f"device pool exhausted reserving {nbytes} (tag={tag}); "
                f"spilled {freed} bytes, retry")
        # recoverable by design: with_retry splits the input and
        # re-attempts. Dumps happen only at TERMINAL failure sites
        # (runtime/retry.py dump_terminal_oom) so the split-retry hot
        # path stays free of file I/O under the catalog lock.
        raise TpuSplitAndRetryOOM(
            f"device pool cannot fit {nbytes} (tag={tag}, "
            f"limit={self.pool.limit}, reserved={self.pool.reserved}); "
            "split the input and retry")

    def release(self, nbytes: int, query_id: Optional[int] = None):
        self.pool.release(nbytes)
        self._q_release(self._resolve_qid(query_id), nbytes)

    @contextlib.contextmanager
    def reserved(self, nbytes: int, tag: str = ""):
        """Scoped reservation — operators wrap device compute whose
        output is ~nbytes so allocation pressure (and injected OOM)
        surfaces at a retryable point. The owning query is captured at
        entry so the exit releases the same ledger even if the thread's
        scopes changed."""
        from spark_rapids_tpu.runtime import sanitizer as _san

        qid = self._resolve_qid(None)
        self.reserve(nbytes, tag=tag, query_id=qid)
        # acquisition-order history: a scoped reservation is a held
        # resource of class "quota" for the sanitizer's lock-order
        # audit (e.g. taking semaphore permits while inside one is the
        # inversion of the usual permits-then-memory order)
        san = _san.active()
        res = _san.quota_resource("scoped")
        if san is not None:
            san.acquired(res, qid)
        try:
            yield
        finally:
            if san is not None:
                san.released(res, qid)
            self.release(nbytes, query_id=qid)

    def spill_device_bytes(self, target: int,
                           query_id: Optional[int] = None) -> int:
        """Spill coldest (lowest priority, largest first) device buffers
        until `target` bytes are freed (RapidsBufferCatalog.synchronousSpill
        analog). With `query_id` only THAT query's buffers are
        candidates — the quota gate degrades the offending query
        without disturbing its neighbors."""
        from spark_rapids_tpu.obs import telemetry

        telemetry.hbm_pressure(target, 0, query_id=query_id)
        freed = 0
        with self._lock:
            candidates = sorted(
                (b for b in self._buffers.values()
                 if b.tier == SpillTier.DEVICE and not b.closed
                 and not b._device_lost
                 and (query_id is None or b.query_id == query_id)),
                key=lambda b: (b._priority, -b.size_bytes))
            for b in candidates:
                if freed >= target:
                    break
                self._spill_one(b)
                freed += b.size_bytes
        return freed

    def _spill_one(self, b: SpillableBatch):
        from spark_rapids_tpu.obs import events as obs_events
        from spark_rapids_tpu.runtime import host_alloc

        pageable = host_alloc.get().pageable
        if (self.host_used + b.size_bytes <= self.host_limit
                and pageable.try_reserve(b.size_bytes)):
            b._to_host()
            self.pool.release(b.size_bytes)
            self._q_release(b.query_id, b.size_bytes)
            self.host_used += b.size_bytes
            self.metrics["spill_to_host"] += 1
            obs_events.emit("spill", component="catalog",
                            direction="down", fromTier="DEVICE",
                            toTier="HOST", bytes=b.size_bytes)
            return
        # host tier full (own threshold or the GLOBAL host budget,
        # runtime/host_alloc.py): go straight through to disk. The
        # transient host copy is force-accounted — the spill MUST
        # proceed to relieve HBM pressure, and the ledger staying
        # truthful makes concurrent callers feel the pressure
        pageable.reserve_force(b.size_bytes)
        try:
            b._to_host()
            b._to_disk()
        finally:
            pageable.release(b.size_bytes)
        self.pool.release(b.size_bytes)
        self._q_release(b.query_id, b.size_bytes)
        self.metrics["spill_to_disk"] += 1
        obs_events.emit("spill", component="catalog", direction="down",
                        fromTier="DEVICE", toTier="DISK",
                        bytes=b.size_bytes)

    def spill_host_bytes(self, target: int) -> int:
        """Push coldest host-tier buffers to disk until `target`
        pageable bytes are freed — HostAlloc's pressure valve
        (HostAlloc.scala blocking-alloc spills host store likewise)."""
        from spark_rapids_tpu.runtime import host_alloc

        pageable = host_alloc.get().pageable
        freed = 0
        with self._lock:
            cands = sorted(
                (x for x in self._buffers.values()
                 if x.tier == SpillTier.HOST),
                key=lambda x: (x._priority, -x.size_bytes))
            for hb in cands:
                if freed >= target:
                    break
                hb._to_disk()
                self.host_used -= hb.size_bytes
                pageable.release(hb.size_bytes)
                self.metrics["spill_to_disk"] += 1
                from spark_rapids_tpu.obs import events as obs_events

                obs_events.emit("spill", component="catalog",
                                direction="down", fromTier="HOST",
                                toTier="DISK", bytes=hb.size_bytes)
                freed += hb.size_bytes
        return freed

    def unspill(self, sb: SpillableBatch):
        with self._lock:
            if sb.tier == SpillTier.DEVICE:
                return
            was_host = sb.tier == SpillTier.HOST
            # reserve device room first (may cascade-spill others);
            # the reservation belongs to the buffer's OWNING query, not
            # whichever query happened to trigger the unspill
            self.reserve(sb.size_bytes, tag="unspill",
                         query_id=sb.query_id)
            sb._to_device()
            if was_host:
                self.host_used -= sb.size_bytes
                from spark_rapids_tpu.runtime import host_alloc

                host_alloc.get().pageable.release(sb.size_bytes)
            self.metrics["unspill"] += 1
            from spark_rapids_tpu.obs import events as obs_events

            obs_events.emit(
                "spill", component="catalog", direction="up",
                fromTier="HOST" if was_host else "DISK",
                toTier="DEVICE", bytes=sb.size_bytes)

    def on_device_lost(self):
        """Device-loss recovery hook (runtime/device_monitor.py): the
        dead backend's HBM is gone, so every DEVICE-tier buffer is
        marked lost (its owner's next get_batch raises DeviceLostError
        — recompute via lineage/resubmit) and its pool + per-query
        reservations are released so the ledger describes the FRESH
        backend. HOST/DISK-tier buffers are untouched: they restore
        lazily into the new epoch on their next unspill. Returns
        (restorable, dropped) buffer counts."""
        restorable = dropped = 0
        with self._lock:
            for b in self._buffers.values():
                if b.closed:
                    continue
                if b.tier == SpillTier.DEVICE:
                    if not b._device_lost:
                        b._device_lost = True
                        b._device_batch = None  # never touch it again
                        self.pool.release(b.size_bytes)
                        self._q_release(b.query_id, b.size_bytes)
                        dropped += 1
                else:
                    restorable += 1
            self.metrics["device_lost_buffers"] += dropped
        return restorable, dropped

    # --- stats ---

    def device_reserved(self) -> int:
        return self.pool.reserved

    def buffer_count(self) -> int:
        with self._lock:
            return len(self._buffers)

    def check_leaks(self, raise_on_leak: bool = False) -> int:
        """Leak tracking (MemoryCleaner / TaskRegistryTracker analog,
        reference Plugin.scala:562-577 shutdown-hook accounting): every
        SpillableBatch must be closed by its owning operator. Returns
        the number of live buffers; logs (or raises) when nonzero."""
        with self._lock:
            # close() removes a buffer from the catalog, so anything
            # still registered is by construction unclosed
            leaked = list(self._buffers.values())
        if leaked:
            import logging

            msg = (f"{len(leaked)} spillable buffer(s) leaked "
                   f"({sum(b.size_bytes for b in leaked)} bytes, tiers: "
                   f"{sorted({b.tier.name for b in leaked})})")
            if raise_on_leak:
                raise AssertionError(msg)
            logging.getLogger(__name__).warning(msg)
        return len(leaked)


_catalog: Optional[SpillCatalog] = None
_catalog_lock = threading.Lock()


def initialize_memory(conf=None, force: bool = False) -> SpillCatalog:
    """GpuDeviceManager.initializeMemory analog (reference
    GpuDeviceManager.scala:275-385): size the pool from conf/HBM and
    install the global catalog. force=True rebuilds with the new conf
    (used by session init so startup-only memory confs of a fresh
    session are honored; live spillables keep referencing their old
    catalog until closed)."""
    global _catalog
    from spark_rapids_tpu.config import rapids_conf as rc

    conf = conf or rc.RapidsConf()
    with _catalog_lock:
        if _catalog is not None and not force:
            return _catalog
        limit = conf.get(rc.MEMORY_LIMIT_BYTES)
        if not limit:
            hbm = _detect_hbm_bytes()
            limit = int(hbm * conf.get(rc.MEMORY_FRACTION))
        from spark_rapids_tpu.runtime import host_alloc

        host_alloc.initialize(conf.get(rc.PINNED_POOL_SIZE),
                              conf.get(rc.HOST_MEMORY_LIMIT))
        _catalog = SpillCatalog(
            device_limit=limit,
            host_limit=conf.get(rc.HOST_SPILL_STORAGE_SIZE),
            spill_dir=conf.get(rc.SPILL_DIR) or None,
            oom_injection_mode=conf.get(rc.OOM_INJECTION_MODE),
            oom_injection_filter=conf.get(rc.TEST_RETRY_OOM_INJECTION_FILTER),
            oom_dump_dir=conf.get(rc.OOM_DUMP_DIR),
            query_quota_bytes=conf.get(rc.QUOTA_DEVICE_BYTES_PER_QUERY),
        )
        return _catalog


#: Pool size on the CPU backend, whose devices report no memory limit:
#: the tests run there against the HBM of the chip the engine targets.
_CPU_BACKEND_POOL_BYTES = 16 << 30


def _detect_hbm_bytes() -> int:
    """The device's own memory limit. On platform `tpu` the device must
    say it (a guessed HBM size budgets the wrong chip); only the CPU
    backend, which has no such figure, gets the nominal pool. Asks a
    LOCAL device: in a multi-process mesh, devices()[0] may belong to
    another process, which cannot read its stats."""
    d = jax.local_devices()[0]
    stats = d.memory_stats()
    if stats and "bytes_limit" in stats:
        return int(stats["bytes_limit"])
    if d.platform == "cpu":
        return _CPU_BACKEND_POOL_BYTES
    raise RuntimeError(
        f"{d.platform} device {d.device_kind!r} reports no "
        f"memory_stats()['bytes_limit']; set "
        f"spark.rapids.memory.gpu.maxAllocBytes explicitly")


def get_catalog() -> SpillCatalog:
    if _catalog is None:
        return initialize_memory()
    return _catalog


def shutdown_memory():
    global _catalog
    with _catalog_lock:
        _catalog = None
