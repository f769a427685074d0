"""Query admission control — the multi-tenant governance front door.

The device semaphore (runtime/semaphore.py) governs TASK concurrency
inside a query; nothing governed QUERIES. Under concurrent traffic a
second query could wedge behind the first's permits with no queueing
policy, no deadline, no cancel, and no per-query accounting — the
failure mode memory-aware engines design against (Theseus's admission
control over data movement, Vortex's explicit capacity management under
oversubscription; PAPERS.md). This module makes every query a
first-class governed unit:

- **Admission**: at most `admission.maxConcurrentQueries` queries
  execute; up to `admission.queue.maxDepth` more wait in a
  priority-then-FIFO queue (priority from `query.priority`); anything
  past that is load-shed IMMEDIATELY with QueryRejectedError carrying
  the running-query table. Queued queries time out after
  `admission.queue.timeoutMs` with the same diagnostics — a submission
  is never an unbounded wait.
- **Deadlines + cancellation**: every admitted query gets a CancelToken
  (runtime/cancellation.py) with `query.timeoutMs` as its deadline
  (queue wait counts); `cancel(query_id)` / `cancel_all()` cancel
  queued queries instantly and running queries at their next
  cooperative yield point.
- **Quarantine**: the token is also the poison-query ledger — worker
  crashes recorded by the stage scheduler trip
  `admission.quarantine.maxWorkerCrashes` into a fast
  QueryQuarantinedError with the crash history.

Re-entrancy mirrors the semaphore's per-task discipline: a nested
collect on a thread that already holds a slot (cache materialization,
writes that read) rides the enclosing query's admission, so nesting can
never self-deadlock the queue.

Observability: `admission.*` events (queued/admitted/shed/cancelled/
deadline/quarantined) land on the obs bus, an `admission` operator
span records the queue wait on the query's span tree, and the counter
ledger surfaces in `session.robustness_metrics["admission"]` and
bench.py's admission block. Chaos sites `admission.slow_drain` (delayed
slot handoff) and `query.cancel_race` (a cancel landing exactly at
completion) harden the drain and finish paths.
"""

from __future__ import annotations

import contextlib
import heapq
import itertools
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from spark_rapids_tpu.runtime.cancellation import CancelToken
from spark_rapids_tpu.runtime.errors import (
    QueryDeadlineExceeded,
    QueryQuarantinedError,
    QueryQueueTimeout,
    QueryRejectedError,
)

# --------------------------------------------------------------- stats

_FIELDS = ("queriesSubmitted", "queriesAdmitted", "queriesQueued",
           "queriesShed", "queueTimeouts", "queriesCancelled",
           "deadlineExceeded", "queriesQuarantined")


class _AdmissionStats:
    """Process-wide admission ledger (the scheduler.stats pattern)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._v = {f: 0 for f in _FIELDS}
        self.queue_wait_ms_total = 0.0
        self.queue_wait_ms_max = 0.0
        self.cancel_latency_ms_max = 0.0
        self._waits = deque(maxlen=1024)
        self._cancel_lat = deque(maxlen=1024)

    def add(self, field: str, n: int = 1) -> None:
        with self._lock:
            self._v[field] += n

    def record_wait(self, ms: float) -> None:
        with self._lock:
            self.queue_wait_ms_total += ms
            self.queue_wait_ms_max = max(self.queue_wait_ms_max, ms)
            self._waits.append(ms)

    def record_cancel_latency(self, ms: float) -> None:
        with self._lock:
            self.cancel_latency_ms_max = max(
                self.cancel_latency_ms_max, ms)
            self._cancel_lat.append(ms)

    @staticmethod
    def _pct(sorted_vals: List[float], q: float) -> float:
        if not sorted_vals:
            return 0.0
        i = min(len(sorted_vals) - 1,
                int(round(q * (len(sorted_vals) - 1))))
        return sorted_vals[i]

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            out = dict(self._v)
            waits = sorted(self._waits)
            lats = sorted(self._cancel_lat)
            out["queueWaitMsTotal"] = round(self.queue_wait_ms_total, 3)
            out["queueWaitMsMax"] = round(self.queue_wait_ms_max, 3)
            out["queueWaitMsP50"] = round(self._pct(waits, 0.50), 3)
            out["queueWaitMsP99"] = round(self._pct(waits, 0.99), 3)
            out["cancelLatencyMsMax"] = round(
                self.cancel_latency_ms_max, 3)
            out["cancelLatencyMsP50"] = round(self._pct(lats, 0.50), 3)
            out["cancelLatencyMsP99"] = round(self._pct(lats, 0.99), 3)
        return out


stats = _AdmissionStats()


# -------------------------------------------------------------- handle

class QueryHandle:
    """One governed query: identity, token, and lifecycle stamps."""

    __slots__ = ("query_id", "token", "priority", "description",
                 "submitted_at", "admitted_at", "finished_at", "state",
                 "thread_name", "queue_wait_ms")

    def __init__(self, query_id: int, token: CancelToken,
                 priority: int, description: str):
        self.query_id = query_id
        self.token = token
        self.priority = priority
        self.description = description
        self.submitted_at = time.monotonic()
        self.admitted_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        self.state = "queued"
        self.thread_name = threading.current_thread().name
        self.queue_wait_ms = 0.0

    def row(self) -> dict:
        now = time.monotonic()
        anchor = self.admitted_at or self.submitted_at
        return {"queryId": self.query_id, "state": self.state,
                "priority": self.priority,
                "elapsedS": round(now - anchor, 3),
                "thread": self.thread_name,
                "description": self.description}


# ---------------------------------------------------------- controller

_tls = threading.local()


class AdmissionController:
    """Bounded priority/FIFO admission queue + cancel registry."""

    def __init__(self, enabled: bool = True, max_concurrent: int = 4,
                 queue_depth: int = 16, queue_timeout_ms: int = 120_000,
                 quarantine_crashes: int = 8):
        self.enabled = enabled
        self.max_concurrent = max(1, int(max_concurrent))
        self.queue_depth = max(0, int(queue_depth))
        self.queue_timeout_ms = max(0, int(queue_timeout_ms))
        self.quarantine_crashes = max(0, int(quarantine_crashes))
        self.draining = False
        self.drain_reason = ""
        self._cv = threading.Condition()
        self._running: Dict[int, QueryHandle] = {}
        self._finished: Dict[int, QueryHandle] = {}
        # heap of (-priority, fifo_seq, query_id); the handle map is
        # authoritative — a cancelled entry lazily pops as a ghost
        self._heap: List[tuple] = []
        self._queued: Dict[int, QueryHandle] = {}
        self._fifo = itertools.count(0)

    # --- diagnostics ---

    def running_table(self) -> List[dict]:
        with self._cv:
            return [h.row() for h in
                    sorted(self._running.values(),
                           key=lambda h: h.query_id)]

    def queued_table(self) -> List[dict]:
        with self._cv:
            return [h.row() for h in
                    sorted(self._queued.values(),
                           key=lambda h: h.query_id)]

    @staticmethod
    def _fence_mode() -> str:
        """Admission policy while the engine is FENCED for device-loss
        recovery (runtime/device_monitor.py): '' (not fenced) |
        'degrade' (admit; dispatch serves the CPU rung) | 'queue'
        (hold until the fence lifts) | 'shed' (reject at submit)."""
        from spark_rapids_tpu.runtime import device_monitor

        mon = device_monitor.get()
        return mon.fenced_admission if mon.fenced else ""

    def _capacity_diag(self) -> str:
        rows = ", ".join(
            f"query={r['queryId']} elapsed={r['elapsedS']}s "
            f"prio={r['priority']} [{r['description']}]"
            for r in self.running_table()) or "none"
        return (f"{len(self._running)}/{self.max_concurrent} running, "
                f"queue {len(self._queued)}/{self.queue_depth}; "
                f"running queries holding capacity: [{rows}]")

    # --- submission ---

    def submit(self, query_id: int, priority: int = 0,
               timeout_ms: int = 0, description: str = "") -> QueryHandle:
        """Admit (possibly after queueing) or shed. Returns a RUNNING
        handle; raises QueryRejectedError / QueryQueueTimeout /
        QueryCancelledError-family — never waits unboundedly (the queue
        timeout, the query deadline, and cancellation all break the
        wait)."""
        from spark_rapids_tpu.obs import events as obs_events

        token = CancelToken(query_id, timeout_ms=timeout_ms,
                            description=description,
                            quarantine_threshold=self.quarantine_crashes)
        handle = QueryHandle(query_id, token, priority, description)
        stats.add("queriesSubmitted")
        if self.draining:
            # drain shed precedes every other admission verdict
            # (including enabled=False): a draining engine accepts NO
            # new top-level queries, while already-queued queries keep
            # their slots/deadlines and in-flight queries' nested
            # collects ride their enclosing handle (they never reach
            # submit()).
            stats.add("queriesShed")
            obs_events.emit("admission.shed", queryId=query_id,
                            reason="draining",
                            running=len(self._running))
            raise QueryRejectedError(
                f"query {query_id} rejected: the engine is draining"
                f"{' (' + self.drain_reason + ')' if self.drain_reason else ''}; "
                f"no new submissions are accepted (queued queries keep "
                f"their slots)", reason="draining")
        if not self.enabled:
            from spark_rapids_tpu.runtime import sanitizer as _san

            with self._cv:
                handle.state = "running"
                handle.admitted_at = time.monotonic()
                self._running[query_id] = handle
            stats.add("queriesAdmitted")
            san = _san.active()
            if san is not None:
                san.acquired(_san.ADMISSION, query_id)
            return handle
        fence = self._fence_mode()
        if fence == "shed":
            from spark_rapids_tpu.runtime import device_monitor

            stats.add("queriesShed")
            obs_events.emit("admission.shed", queryId=query_id,
                            reason="device fenced",
                            running=len(self._running))
            raise QueryRejectedError(
                f"query {query_id} rejected: the engine is FENCED for "
                f"device-loss recovery (epoch "
                f"{device_monitor.get().epoch}, "
                f"device.recovery.fencedAdmission=shed); retry after "
                f"recovery", reason="device fenced")
        with self._cv:
            if len(self._running) < self.max_concurrent and \
                    not self._heap and fence != "queue":
                self._admit_locked(handle)
                return handle
            if len(self._queued) >= self.queue_depth:
                stats.add("queriesShed")
                diag = self._capacity_diag()
                obs_events.emit("admission.shed", queryId=query_id,
                                reason="queue full",
                                running=len(self._running))
                raise QueryRejectedError(
                    f"query {query_id} rejected (admission queue "
                    f"full): {diag}", reason="queue full")
            # enqueue
            self._queued[query_id] = handle
            heapq.heappush(self._heap,
                           (-priority, next(self._fifo), query_id))
            stats.add("queriesQueued")
            obs_events.emit("admission.queued", queryId=query_id,
                            depth=len(self._queued),
                            running=len(self._running))

        def wake():
            with self._cv:
                self._cv.notify_all()

        token.on_cancel(wake)
        # wait-for edge: this queued query waits on the slot class held
        # by every running query (runtime/sanitizer.py); a cycle
        # through admission can only close via another resource class,
        # but the edge makes the full wedge visible when it does
        from spark_rapids_tpu.runtime import sanitizer as _san

        san = _san.active()
        wait_rec = None
        if san is not None:
            wait_rec = san.begin_wait(_san.ADMISSION, query_id,
                                      token=token, wake=wake)
        queue_deadline = (
            None if self.queue_timeout_ms <= 0
            else time.monotonic() + self.queue_timeout_ms / 1000.0)
        try:
            with self._cv:
                while True:
                    if wait_rec is not None:
                        wait_rec.check()  # deadlock-victim exit
                    if token.cancelled or token.expired:
                        self._drop_queued_locked(query_id)
                        token.check()  # raises (turns expiry into cancel)
                    if len(self._running) < self.max_concurrent and \
                            self._front_locked() == query_id and \
                            self._fence_mode() != "queue":
                        self._pop_front_locked()
                        self._queued.pop(query_id, None)
                        self._admit_locked(handle)
                        return handle
                    wait_s = None
                    if queue_deadline is not None:
                        wait_s = queue_deadline - time.monotonic()
                        if wait_s <= 0:
                            self._drop_queued_locked(query_id)
                            stats.add("queueTimeouts")
                            stats.add("queriesShed")
                            diag = self._capacity_diag()
                            obs_events.emit(
                                "admission.shed", queryId=query_id,
                                reason="queue timeout",
                                running=len(self._running))
                            raise QueryQueueTimeout(
                                f"query {query_id} timed out after "
                                f"{self.queue_timeout_ms}ms in the "
                                f"admission queue: {diag}")
                    r = token.remaining_s()
                    if r is not None:
                        wait_s = r if wait_s is None else min(wait_s, r)
                        wait_s += 0.001
                    self._cv.wait(wait_s)
        except BaseException:
            with self._cv:
                self._drop_queued_locked(query_id)
                self._cv.notify_all()  # a new front may now be eligible
            raise
        finally:
            if wait_rec is not None:
                san.end_wait(wait_rec)
            token.remove_on_cancel(wake)

    def _front_locked(self) -> Optional[int]:
        while self._heap:
            qid = self._heap[0][2]
            if qid in self._queued:
                return qid
            heapq.heappop(self._heap)  # ghost of a dropped entry
        return None

    def _pop_front_locked(self) -> None:
        heapq.heappop(self._heap)

    def _drop_queued_locked(self, query_id: int) -> None:
        self._queued.pop(query_id, None)  # heap entry pops as a ghost

    def _admit_locked(self, handle: QueryHandle) -> None:
        from spark_rapids_tpu.obs import events as obs_events
        from spark_rapids_tpu.runtime import sanitizer as _san

        handle.state = "running"
        handle.admitted_at = time.monotonic()
        handle.queue_wait_ms = round(
            (handle.admitted_at - handle.submitted_at) * 1000.0, 3)
        self._running[handle.query_id] = handle
        stats.add("queriesAdmitted")
        stats.record_wait(handle.queue_wait_ms)
        san = _san.active()
        if san is not None:
            san.acquired(_san.ADMISSION, handle.query_id)
        obs_events.emit("admission.admitted", queryId=handle.query_id,
                        waitMs=handle.queue_wait_ms)

    # --- completion ---

    def finish(self, handle: QueryHandle, status: str = "ok") -> None:
        """Release the slot and hand it to the next queued query.
        `status`: ok | error | cancelled | deadline | quarantined."""
        from spark_rapids_tpu.obs import events as obs_events
        from spark_rapids_tpu.runtime import cancellation, faults

        token = handle.token
        if status == "ok" and \
                faults.should_inject("query.cancel_race"):
            # a cancel racing with completion: the result already
            # exists, so the late cancel must change nothing — the
            # release below still runs exactly once
            token.cancel("injected query.cancel_race")
        lat = token.unwind_latency_s()
        if status in ("cancelled", "deadline", "quarantined") and \
                lat is not None:
            stats.record_cancel_latency(lat * 1000.0)
        if status == "cancelled":
            stats.add("queriesCancelled")
            obs_events.emit("admission.cancelled",
                            queryId=handle.query_id,
                            reason=token._reason,
                            latencyMs=round((lat or 0) * 1000.0, 3))
        elif status == "deadline":
            stats.add("deadlineExceeded")
            obs_events.emit("admission.deadline",
                            queryId=handle.query_id,
                            reason=token._reason,
                            latencyMs=round((lat or 0) * 1000.0, 3))
        elif status == "quarantined":
            stats.add("queriesQuarantined")
            obs_events.emit("admission.quarantined",
                            queryId=handle.query_id,
                            reason=token._reason,
                            crashes=len(token.crashes))
        slow = faults.should_inject("admission.slow_drain")
        if slow:
            # delayed handoff (never under the lock); interruptible so
            # a cancelled query's unwind never rides out chaos latency
            # (lint rule raw-sleep)
            cancellation.sleep_interruptible(0.02)
        from spark_rapids_tpu.runtime import sanitizer as _san

        san = _san.active()
        if san is not None and handle.state == "running":
            san.released(_san.ADMISSION, handle.query_id)
        with self._cv:
            handle.state = "done"
            handle.finished_at = time.monotonic()
            self._running.pop(handle.query_id, None)
            self._finished[handle.query_id] = handle
            if len(self._finished) > 256:
                for k in sorted(self._finished)[:-128]:
                    del self._finished[k]
            self._cv.notify_all()

    # --- drain API ---

    def begin_drain(self, reason: str = "") -> None:
        """Stop accepting NEW top-level submissions (they shed with
        QueryRejectedError reason='draining'). Already-queued queries
        keep their slots and deadlines and still admit as capacity
        frees; running queries (and their nested collects) are
        untouched. Idempotent; `end_drain` re-opens the front door."""
        with self._cv:
            self.draining = True
            self.drain_reason = reason

    def end_drain(self) -> None:
        with self._cv:
            self.draining = False
            self.drain_reason = ""

    def quiescent(self) -> bool:
        """True when nothing is running or queued (the drain-complete
        condition the serving layer polls)."""
        with self._cv:
            return not self._running and not self._queued

    # --- cancel API ---

    def cancel(self, query_id: int, reason: str = "cancelled by user"
               ) -> bool:
        """Cancel a running or queued query by id. True when the
        token newly latched (False: unknown id or already done)."""
        with self._cv:
            h = self._running.get(query_id) or self._queued.get(query_id)
        if h is None:
            return False
        return h.token.cancel(reason)

    def cancel_all(self, reason: str = "cancelled by user") -> int:
        with self._cv:
            handles = list(self._running.values()) + \
                list(self._queued.values())
        return sum(1 for h in handles if h.token.cancel(reason))

    def cancel_where(self, predicate, reason: str = "cancelled by user"
                     ) -> int:
        """Cancel the running/queued queries whose handle satisfies
        `predicate` — the tenant-scoped cancel surface of the serving
        layer (serve handles carry a `serve:<tenant>:<class>`
        description, so a tenant can only ever unwind its own work)."""
        with self._cv:
            handles = [h for h in list(self._running.values())
                       + list(self._queued.values()) if predicate(h)]
        return sum(1 for h in handles if h.token.cancel(reason))

    def cancel_running(self, reason: str, error_cls=None) -> int:
        """Cancel only the RUNNING queries (the device-loss fence:
        queued queries never touched the dead device — they keep their
        queue positions and run after recovery). `error_cls` lets the
        fence unwind them with a retryable DeviceLostError instead of
        plain QueryCancelledError."""
        from spark_rapids_tpu.runtime.errors import QueryCancelledError

        with self._cv:
            handles = list(self._running.values())
        cls = error_cls or QueryCancelledError
        return sum(1 for h in handles
                   if h.token.cancel(reason, error_cls=cls))

    def status(self) -> dict:
        return {"running": self.running_table(),
                "queued": self.queued_table(),
                "maxConcurrentQueries": self.max_concurrent,
                "queueMaxDepth": self.queue_depth,
                "draining": self.draining}

    def load(self) -> dict:
        """Cheap numeric load signal for the fleet layer: surfaced
        through /readyz so the router can shed toward the least-loaded
        replica and back off one that is saturating (queriesShed is
        cumulative — the router watches its derivative)."""
        with self._cv:
            running = len(self._running)
            queued = len(self._queued)
        return {"running": running, "queued": queued,
                "maxConcurrentQueries": self.max_concurrent,
                "queueMaxDepth": self.queue_depth,
                "queriesShed": stats.snapshot().get("queriesShed", 0),
                "draining": bool(self.draining)}


# ------------------------------------------------------ process wiring

_controller = AdmissionController()
_lock = threading.Lock()


def get() -> AdmissionController:
    return _controller


def install(controller: AdmissionController) -> AdmissionController:
    """Swap the process controller (tests, bench's governed burst)."""
    global _controller
    with _lock:
        _controller = controller
    return controller


def configure(conf=None) -> AdmissionController:
    """Session-lifecycle hook (plugin.py TpuExecutorPlugin.init):
    rebuild the controller from spark.rapids.tpu.admission.* — running
    queries of a prior controller keep their handles/tokens; only the
    queue policy is fresh."""
    global _controller
    from spark_rapids_tpu.config import rapids_conf as rc

    def get_(entry):
        return conf.get(entry) if conf is not None else entry.default

    with _lock:
        old = _controller
        _controller = AdmissionController(
            enabled=bool(get_(rc.ADMISSION_ENABLED)),
            max_concurrent=get_(rc.ADMISSION_MAX_CONCURRENT),
            queue_depth=get_(rc.ADMISSION_QUEUE_DEPTH),
            queue_timeout_ms=get_(rc.ADMISSION_QUEUE_TIMEOUT_MS),
            quarantine_crashes=get_(rc.ADMISSION_QUARANTINE_CRASHES))
    # nobody will ever drain the replaced controller's queue again —
    # cancel its queued tokens so their waiters unwind cleanly instead
    # of waiting out a timeout (or forever)
    with old._cv:
        queued = list(old._queued.values())
    for h in queued:
        h.token.cancel("admission controller reconfigured while queued")
    return _controller


# ----------------------------------------------------- session surface

@contextlib.contextmanager
def request_overrides(priority: Optional[int] = None,
                      timeout_ms: Optional[int] = None,
                      description: Optional[str] = None):
    """Per-REQUEST admission parameters for this thread: the serving
    layer (serve/server.py) runs many concurrent queries with distinct
    priority classes through ONE session, so the session-wide
    query.priority / query.timeoutMs confs would race across
    connections. AdmissionScope consults the innermost active override
    before falling back to the session conf. Nests; None fields fall
    through to the next level."""
    prev = getattr(_tls, "overrides", None)
    ov = dict(prev or {})
    if priority is not None:
        ov["priority"] = int(priority)
    if timeout_ms is not None:
        ov["timeout_ms"] = int(timeout_ms)
    if description is not None:
        ov["description"] = str(description)
    _tls.overrides = ov
    try:
        yield ov
    finally:
        _tls.overrides = prev


def current_overrides() -> dict:
    return getattr(_tls, "overrides", None) or {}


class AdmissionScope:
    """Context manager the collect path enters around a query
    (api/dataframe.py): re-entrant per thread — a nested collect rides
    the enclosing query's handle/token — and maps the exit exception
    onto the admission finish status."""

    def __init__(self, session, description: str = ""):
        self.session = session
        self.description = description
        self.handle: Optional[QueryHandle] = None
        self.nested = False
        self._cancel_scope = None
        self._ctrl: Optional[AdmissionController] = None

    def __enter__(self) -> QueryHandle:
        from spark_rapids_tpu.config import rapids_conf as rc
        from spark_rapids_tpu.obs import events as obs_events
        from spark_rapids_tpu.runtime import cancellation

        outer = getattr(_tls, "handle", None)
        if outer is not None:
            self.nested = True
            self.handle = outer
            return outer
        conf = self.session.rapids_conf
        # pin the controller that admits us: the slot must release on
        # the SAME controller even if a new session swaps the process
        # one while this query runs
        self._ctrl = get()
        qid = obs_events.allocate_query_id()
        ov = current_overrides()
        self.handle = self._ctrl.submit(
            qid,
            priority=ov.get("priority", conf.get(rc.QUERY_PRIORITY)),
            timeout_ms=ov.get("timeout_ms",
                              conf.get(rc.QUERY_TIMEOUT_MS)),
            description=ov.get("description", self.description))
        _tls.handle = self.handle
        self._cancel_scope = cancellation.scope(self.handle.token)
        self._cancel_scope.__enter__()
        return self.handle

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self.nested:
            return False
        _tls.handle = None
        if self._cancel_scope is not None:
            self._cancel_scope.__exit__(exc_type, exc, tb)
        if exc is None:
            status = "ok"
        elif isinstance(exc, QueryQuarantinedError):
            status = "quarantined"
        elif isinstance(exc, QueryDeadlineExceeded):
            status = "deadline"
        elif self.handle.token.cancelled:
            status = "cancelled"
        else:
            status = "error"
        (self._ctrl or get()).finish(self.handle, status)
        return False


def current_handle() -> Optional[QueryHandle]:
    return getattr(_tls, "handle", None)
