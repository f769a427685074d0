"""Device-loss resilience: fatal-TPU detection, fencing, warm recovery.

The reference plugin treats a fatal CUDA error as process-fatal
(Plugin.scala:651-675 exits so the cluster manager reschedules); a
long-running accelerated service cannot — a PJRT client crash or a
wedged TPU runtime must cost one recovery window, not the warm engine,
its compile cache, and every tenant's session ("Accelerating Presto
with GPUs", PAPERS.md). This module is the recovery subsystem:

- **Classification** (`classify`): every dispatch/transfer site routes
  device errors through `guard(site)`, which sorts them into
  `fatal` (XLA INTERNAL / device-lost / wedged-runtime markers, plus
  the `device.fatal` chaos site), `oom` (TpuOOMError — stays with the
  PR 5 TpuRetryOOM retry path, untouched here), and `other`
  (transient/logic errors, surfaced unchanged to their own recovery).
- **Fencing**: the first fatal observation flips the engine FENCED —
  new admissions queue, shed, or degrade to the CPU rung per
  `spark.rapids.tpu.device.recovery.fencedAdmission`, and every
  in-flight query is cancelled with a retryable `DeviceLostError`
  carrying the epoch (PR 7's sanitizer edges and the semaphore drain
  through the normal cancel unwind).
- **Device epoch**: a process-wide counter stamped on every
  `DeviceColumn` (columnar/batch.py) and spill-catalog device
  reservation (runtime/memory.py) and folded into the jit-cache trace
  environment (runtime/jit_cache.py). A stale handle raises
  `DeviceLostError` at use instead of touching a dead buffer; the
  epoch bumps EXACTLY once per fence.
- **Warm recovery** (background thread): wait for the fenced queries
  to drain, bump the epoch, rebuild the PJRT backend
  (`jax.extend.backend.clear_backends`), drop the DEVICE spill tier
  (host/disk tiers survive and unspill into the new epoch on next
  use; device-only state is recomputed by the lineage scheduler /
  query resubmission), invalidate the encoded-dictionary device cache
  (columnar/encoding.py), mark the HBM timeline, then unfence.
- **Resubmission**: the outermost collect (api/dataframe.py) catches
  `DeviceLostError`, waits for the fence to lift (`await_ready`), and
  resubmits once through admission — the retryVictim pattern.

Everything is observable: `device.fatal` / `device.fence` /
`device.recovery` events (epoch-tagged) plus DeviceFence/
DeviceRecovery operator spans, and the `device` block in
`session.robustness_metrics`.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Optional

from spark_rapids_tpu.runtime.errors import DeviceLostError, TpuOOMError
from spark_rapids_tpu.runtime.faults import InjectedFault

#: message markers of an unrecoverable runtime failure inside an
#: XlaRuntimeError (the CudaFatalException analog for PJRT): the device
#: or its client is gone, not one allocation or one program
_FATAL_MARKERS = (
    "INTERNAL:", "device lost", "DEVICE_LOST", "hardware", "halted",
    "device or resource busy", "Failed to connect", "client is dead",
    "backend is gone",
)

#: process-wide device epoch; read directly (plain int load) by the
#: DeviceColumn constructor and the jit-cache env token — bumped only
#: by the monitor under its lock, exactly once per fence
_EPOCH = 1


def current_epoch() -> int:
    return _EPOCH


def classify(exc: BaseException) -> str:
    """'fatal' | 'oom' | 'other'. Conservative on purpose: OOMs stay
    with the TpuRetryOOM retry/split machinery, transient XLA noise
    stays with backoff — only a dead device/runtime is fatal."""
    if isinstance(exc, DeviceLostError):
        return "fatal"  # already classified (stale-handle raise)
    if isinstance(exc, InjectedFault):
        return "fatal" if exc.site == "device.fatal" else "other"
    if isinstance(exc, TpuOOMError):
        return "oom"
    if type(exc).__name__ == "XlaRuntimeError":
        msg = str(exc)
        if "RESOURCE_EXHAUSTED" in msg:
            return "oom"
        if any(m in msg for m in _FATAL_MARKERS):
            return "fatal"
    return "other"


class DeviceMonitor:
    """Process-wide fence/epoch/recovery state machine."""

    def __init__(self, enabled: bool = True,
                 fenced_admission: str = "degrade",
                 resubmit: bool = True,
                 drain_timeout_ms: int = 30_000,
                 recovery_timeout_ms: int = 60_000,
                 rebuild_backend: bool = True):
        self.enabled = enabled
        self.fenced_admission = fenced_admission
        self.resubmit = resubmit
        self.drain_timeout_ms = max(0, int(drain_timeout_ms))
        self.recovery_timeout_ms = max(1, int(recovery_timeout_ms))
        self.rebuild_backend = rebuild_backend
        self._cv = threading.Condition()
        self._fenced = False
        self._fence_cause = ""
        self._stats: Dict[str, int] = {
            "fatalErrors": 0, "fences": 0, "recoveries": 0,
            "staleHandles": 0, "drainTimeouts": 0,
            "buffersDropped": 0, "buffersRestorable": 0,
            "resubmits": 0, "chipFences": 0, "chipRecoveries": 0,
            "hostFences": 0, "hostRecoveries": 0,
        }
        self.last_recovery_ms = 0.0

    # --- read surface ---

    @property
    def fenced(self) -> bool:
        return self._fenced

    @property
    def epoch(self) -> int:
        return _EPOCH

    def counters(self) -> Dict[str, int]:
        with self._cv:
            out = dict(self._stats)
        out["epoch"] = _EPOCH
        out["fenced"] = int(self._fenced)
        out["lastRecoveryMs"] = round(self.last_recovery_ms, 3)
        out["fencedChips"] = len(_fenced_chips)
        out["chipEpoch"] = _chip_epoch
        out["fencedHosts"] = len(_fenced_hosts)
        return out

    def note_stale_handle(self) -> None:
        with self._cv:
            self._stats["staleHandles"] += 1

    def note_resubmit(self) -> None:
        with self._cv:
            self._stats["resubmits"] += 1

    # --- fatal observation / fence ---

    def report_fatal(self, exc: BaseException, site: str
                     ) -> DeviceLostError:
        """One fatal device error observed at `site`. The FIRST
        observer fences the engine, cancels every running query with a
        retryable DeviceLostError, and starts the recovery thread;
        concurrent observers just get their error. Returns the
        DeviceLostError the caller must raise — the observer unwinds
        like any cancelled query, releasing its permits and buffers
        before recovery touches the backend."""
        from spark_rapids_tpu.obs import events as obs_events

        observed = _EPOCH
        err = DeviceLostError(
            f"device lost at {site} (epoch {observed}): "
            f"{type(exc).__name__}: {exc}", epoch=observed)
        if not self.enabled:
            return err
        with self._cv:
            self._stats["fatalErrors"] += 1
            first = not self._fenced
            if first:
                self._fenced = True
                self._fence_cause = f"{site}: {type(exc).__name__}"
                self._stats["fences"] += 1
        obs_events.emit("device.fatal", site=site, epoch=observed,
                        error=f"{type(exc).__name__}: {exc}")
        if first:
            self._fence(observed, site)
        return err

    def _fence(self, observed: int, site: str) -> None:
        from spark_rapids_tpu.obs import events as obs_events
        from spark_rapids_tpu.runtime import admission

        ctrl = admission.get()
        in_flight = ctrl.cancel_running(
            f"device lost at {site} (epoch {observed}); "
            f"fencing for warm recovery",
            error_cls=DeviceLostError)
        obs_events.emit("device.fence", epoch=observed, cause=site,
                        inFlight=in_flight)
        t = threading.Thread(target=self._recover,
                             args=(time.monotonic(),),
                             name="srtpu-device-recovery", daemon=True)
        t.start()

    # --- warm recovery (background) ---

    def _await_drain(self) -> bool:
        """Wait (bounded) for the fenced queries to unwind: no running
        admissions, no held semaphore permits. New queries admitted
        while fenced in 'degrade' mode run on the CPU rung and never
        take device permits, so the drain converges."""
        from spark_rapids_tpu.runtime import admission, semaphore

        deadline = time.monotonic() + self.drain_timeout_ms / 1000.0
        while time.monotonic() < deadline:
            ctrl = admission.get()
            with ctrl._cv:
                running = len(ctrl._running)
            if running == 0 and semaphore.get().holders() == 0:
                return True
            with self._cv:
                self._cv.wait(0.01)
        return False

    def _recover(self, t0: float) -> None:
        global _EPOCH
        from spark_rapids_tpu.obs import events as obs_events

        drained = self._await_drain()
        if not drained:
            with self._cv:
                self._stats["drainTimeouts"] += 1
        with self._cv:
            _EPOCH += 1  # exactly once per fence
            new_epoch = _EPOCH
        restorable = dropped = 0
        try:
            self._rebuild_backend()
            restorable, dropped = self._invalidate_device_state()
            clear_chip_fences()
        finally:
            ms = (time.monotonic() - t0) * 1000.0
            with self._cv:
                self._stats["recoveries"] += 1
                self._stats["buffersDropped"] += dropped
                self._stats["buffersRestorable"] += restorable
                self.last_recovery_ms = ms
                self._fenced = False
                self._fence_cause = ""
                self._cv.notify_all()
            obs_events.emit(
                "device.recovery", epoch=new_epoch,
                ms=round(ms, 3), drained=drained,
                restorableBuffers=restorable, droppedBuffers=dropped)
            # the recovery window on the (cross-query) span surface —
            # the fence has no single owning query, so the span hangs
            # off whatever scope observes it (usually none)
            end_ns = time.time_ns()
            obs_events.record_span(
                "DeviceRecovery", end_ns - int(ms * 1_000_000), end_ns,
                metric="recoveryMs")
            self._notify_admission()

    def _rebuild_backend(self) -> None:
        """Tear down and lazily rebuild the PJRT client. Dead arrays
        are unreachable by construction once the drain finished (every
        stale handle raises before dispatch), so dropping the client
        is safe; the next device_put initializes a fresh backend."""
        import jax

        jax.clear_caches()
        if not self.rebuild_backend:
            return
        try:
            import jax.extend as jex

            jex.backend.clear_backends()
        except Exception:
            # jax version without the API, or a wedged client refusing
            # teardown: epoch checks still fence every stale handle,
            # and the next dispatch re-raises if the device is dead
            pass

    def _invalidate_device_state(self):
        """Drop every pre-epoch device residue: DEVICE-tier spillables
        (host/disk tiers survive for lazy restore), the encoded
        dictionary device cache, and mark the HBM occupancy timeline.
        Compiled programs need no hook: the epoch in jit_cache's keys
        makes every pre-recovery entry a miss."""
        from spark_rapids_tpu.columnar import encoding
        from spark_rapids_tpu.obs import telemetry
        from spark_rapids_tpu.runtime import memory

        restorable = dropped = 0
        catalog = memory._catalog
        if catalog is not None:
            restorable, dropped = catalog.on_device_lost()
        encoding.invalidate_device_cache()
        telemetry.hbm_epoch_marker(_EPOCH)
        return restorable, dropped

    def _notify_admission(self) -> None:
        """Wake queued submissions parked behind the fence."""
        from spark_rapids_tpu.runtime import admission

        ctrl = admission.get()
        with ctrl._cv:
            ctrl._cv.notify_all()

    # --- waiters ---

    def await_ready(self, timeout_s: Optional[float] = None) -> bool:
        """Block until the fence lifts (the resubmission path's wait);
        True when unfenced within the timeout."""
        if timeout_s is None:
            timeout_s = self.recovery_timeout_ms / 1000.0
        deadline = time.monotonic() + timeout_s
        with self._cv:
            while self._fenced:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(left)
        return True


# ------------------------------------------------------ process wiring

_monitor = DeviceMonitor()
_lock = threading.Lock()


def get() -> DeviceMonitor:
    return _monitor


def install(monitor: DeviceMonitor) -> DeviceMonitor:
    global _monitor
    with _lock:
        _monitor = monitor
    return monitor


def configure(conf=None) -> DeviceMonitor:
    """Session-lifecycle hook (plugin.py TpuExecutorPlugin.init):
    rebuild the monitor from spark.rapids.tpu.device.recovery.*. The
    epoch is process-global and survives reconfiguration — stale
    handles from before a session cycle must stay stale."""
    from spark_rapids_tpu.config import rapids_conf as rc

    def get_(entry):
        return conf.get(entry) if conf is not None else entry.default

    return install(DeviceMonitor(
        enabled=bool(get_(rc.DEVICE_RECOVERY_ENABLED)),
        fenced_admission=get_(rc.DEVICE_RECOVERY_FENCED_ADMISSION),
        resubmit=bool(get_(rc.DEVICE_RECOVERY_RESUBMIT)),
        drain_timeout_ms=get_(rc.DEVICE_RECOVERY_DRAIN_TIMEOUT_MS),
        recovery_timeout_ms=get_(rc.DEVICE_RECOVERY_TIMEOUT_MS),
        rebuild_backend=bool(get_(rc.DEVICE_RECOVERY_REBUILD_BACKEND))))


def counters() -> Dict[str, int]:
    return _monitor.counters()


# ------------------------------------------------------ per-chip fence
#
# Process-wide fencing (above) is the hammer: ONE dead device takes the
# whole backend through drain/epoch-bump/rebuild. Multichip meshes
# deserve a scalpel — when chip k of n dies mid-collective, only its
# shards are lost; the other chips' HBM, compile cache, and in-flight
# work on other queries are intact. The mesh engine fences just the
# lost chip here, rebuilds its mesh over the survivors (keyed by the
# chip epoch so cached shard_map programs for the old topology are
# never reused), and recovers the lost shards from lineage by
# deterministic re-ingestion. A process-wide recovery clears the chip
# fence — the rebuilt backend starts with every device healthy.

_fenced_chips: set = set()
_chip_epoch = 0


def fence_chip(device_id: int, cause: str = "") -> int:
    """Fence ONE chip out of mesh execution; returns the new chip
    epoch. Idempotent per chip (re-fencing a fenced chip does not bump
    the epoch again)."""
    global _chip_epoch
    from spark_rapids_tpu.obs import events as obs_events

    mon = _monitor
    with mon._cv:
        if device_id in _fenced_chips:
            return _chip_epoch
        _fenced_chips.add(device_id)
        _chip_epoch += 1
        mon._stats["chipFences"] += 1
        epoch = _chip_epoch
    obs_events.emit("chip.fence", device=device_id, chipEpoch=epoch,
                    cause=cause)
    return epoch


def unfence_chip(device_id: int) -> None:
    """Return a chip to mesh service (operator action / post-repair)."""
    global _chip_epoch
    from spark_rapids_tpu.obs import events as obs_events

    mon = _monitor
    with mon._cv:
        if device_id not in _fenced_chips:
            return
        _fenced_chips.discard(device_id)
        _chip_epoch += 1
        epoch = _chip_epoch
    obs_events.emit("chip.unfence", device=device_id, chipEpoch=epoch)


def note_chip_recovery() -> None:
    with _monitor._cv:
        _monitor._stats["chipRecoveries"] += 1


def fenced_chips() -> set:
    with _monitor._cv:
        return set(_fenced_chips)


def chip_epoch() -> int:
    return _chip_epoch


def clear_chip_fences() -> None:
    """Process-wide recovery rebuilt the backend: every device is new,
    so per-chip (and per-host) fences from the old epoch no longer
    apply."""
    global _chip_epoch
    with _monitor._cv:
        if _fenced_chips or _fenced_hosts:
            _fenced_chips.clear()
            _fenced_hosts.clear()
            _chip_epoch += 1


# ------------------------------------------------------ per-host fence
#
# One rung up from the per-chip scalpel: on a TPU pod the real failure
# unit is a HOST — one process owns one host's chips, and when that
# process dies (heartbeat silence, dcn collective failure, kill -9)
# every chip it owned is gone at once. fence_host evicts the whole
# group in ONE step (one chip-epoch bump, so the mesh rebuilds exactly
# once rather than once per chip), the mesh engine re-plans over the
# surviving hosts, and the serve layer flips only capacity — /readyz
# stays ready with `fencedHosts` reported. unfence_host is the
# host-rejoin path (repaired host re-registers): its chips return to
# service and capacity bumps back.

_fenced_hosts: Dict[str, tuple] = {}  # host_id -> fenced device ids


def fence_host(host_id, device_ids, cause: str = "") -> int:
    """Fence every chip of one host in a single step; returns the new
    chip epoch. Idempotent per host (re-fencing bumps nothing)."""
    global _chip_epoch
    from spark_rapids_tpu.obs import events as obs_events

    hid = str(host_id)
    mon = _monitor
    with mon._cv:
        if hid in _fenced_hosts:
            return _chip_epoch
        ids = tuple(int(d) for d in device_ids)
        _fenced_hosts[hid] = ids
        _fenced_chips.update(ids)
        _chip_epoch += 1
        mon._stats["hostFences"] += 1
        epoch = _chip_epoch
    obs_events.emit("host.fence", host=hid, devices=list(ids),
                    chipEpoch=epoch, cause=cause)
    return epoch


def unfence_host(host_id) -> None:
    """Return a repaired host's chips to mesh service (the rejoin
    path: capacity bumps back up on the next mesh build)."""
    global _chip_epoch
    from spark_rapids_tpu.obs import events as obs_events

    hid = str(host_id)
    mon = _monitor
    with mon._cv:
        ids = _fenced_hosts.pop(hid, None)
        if ids is None:
            return
        _fenced_chips.difference_update(ids)
        _chip_epoch += 1
        epoch = _chip_epoch
    obs_events.emit("host.unfence", host=hid, devices=list(ids),
                    chipEpoch=epoch)


def note_host_recovery() -> None:
    with _monitor._cv:
        _monitor._stats["hostRecoveries"] += 1


def fenced_hosts() -> list:
    """Sorted ids of the currently host-fenced failure domains."""
    with _monitor._cv:
        return sorted(_fenced_hosts)


# ------------------------------------------------------- use-site API

def check_stale(epoch: Optional[int], what: str) -> None:
    """The stale-handle gate every device-buffer USE runs through: a
    handle stamped before the current epoch references memory the dead
    backend owned — raise instead of touching it."""
    if epoch is not None and epoch != _EPOCH:
        mon = _monitor
        mon.note_stale_handle()
        raise DeviceLostError(
            f"stale device handle: {what} was created in device epoch "
            f"{epoch}, current epoch is {_EPOCH} (the device was lost "
            f"and recovered in between; recompute or re-upload)",
            epoch=epoch)


def check_batch(batch) -> None:
    """Stale-epoch check over a ColumnBatch's columns (dispatch-input
    gate; BuildTable / BuildIndex wrappers are unwrapped like
    encoding_key does).
    Columns built inside traces re-stamp at the current epoch, so only
    genuinely pre-recovery uploads trip this."""
    cols = getattr(batch, "columns", None)
    if cols is None:
        inner = getattr(batch, "batch", None)
        cols = getattr(inner, "columns", None)
    if not cols:
        return
    for c in cols:
        check_stale(getattr(c, "epoch", None), "batch column")


@contextlib.contextmanager
def guard(site: str, detail: str = "", inject: bool = False):
    """Classification wrapper for one dispatch/transfer site. With
    `inject`, the site is also a `device.fatal` chaos site (the fault
    is raised inside the guard so it is classified, fenced, and
    recovered exactly like a real fatal error — never absorbed by the
    degrade ladder's InjectedFault handling)."""
    from spark_rapids_tpu.runtime import faults

    try:
        if inject:
            faults.maybe_inject("device.fatal", detail=detail or site)
        yield
    except DeviceLostError:
        raise  # already classified (stale handle / nested guard)
    except Exception as e:
        if _monitor.enabled and classify(e) == "fatal":
            raise _monitor.report_fatal(e, site) from e
        # recovery disabled: the raw error propagates to the legacy
        # fatal-error policy (plugin.on_task_failed) / its own handler
        raise
