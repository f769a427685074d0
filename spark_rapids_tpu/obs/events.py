"""Typed, thread-safe query-event bus — the observability substrate.

The reference plugin threads GpuMetric levels and NVTX ranges through
every operator and ships standalone qualification/profiling tools that
read Spark event logs. This module unifies that surface for the engine:
every layer (planner, scheduler, shuffle, spill catalog, compile cache,
degradation ladder, chaos harness) emits TYPED events into one process
bus; span trees (obs/spans.py), the JSONL event log (obs/eventlog.py),
the qualification/profile reports (obs/report.py) and the Prometheus
dump (obs/prom.py) are all views over this stream.

Schema: every event is a flat JSON object carrying the envelope keys
`event` (type name), `seq` (bus-monotonic), `ts` (unix seconds),
`schemaVersion`, and `queryId` (the enclosing query, 0 outside one),
plus per-type payload fields. Task-scoped emissions (operator spans
inside a scheduler attempt) additionally inherit `stage`/`task`/
`attempt`/`speculative` from the thread's task scope, which is how the
span builder hangs operator spans under the right task attempt.

Emitters call the module-level `emit(...)`, which is a None-check when
no session installed a bus (`spark.rapids.tpu.obs.enabled=false`, or no
session yet) — hot paths pay nothing when tracing is off.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

SCHEMA_VERSION = 1

#: Stable event-type registry: name -> payload field summary (doc'd in
#: docs/observability.md; eventlog validation accepts only these).
EVENT_TYPES: Dict[str, str] = {
    "query.start": "queryId",
    "query.end": "engine, status, fallbacks, degradations",
    "plan.placement": "node, depth, onDevice, reason",
    "stage.start": "stage, name, tasks",
    "stage.end": "stage, name, status",
    "task.attempt.start": "stage, task, attempt, worker, speculative",
    "task.attempt.end": "stage, task, attempt, status, wallMs, rows",
    "operator.span": "operator, metric, wallNs, deviceNs, rows",
    "shuffle.write": "shuffleId, reducePid, bytes, staged",
    "shuffle.fetch": "shuffleId, reducePid, blocks, bytes",
    "shuffle.retry": "shuffleId, reducePid, block",
    "spill": "component, direction, fromTier, toTier, bytes",
    "transfer": "direction (h2d|d2h|spill-disk|shuffle|ici|dcn), "
                "site, bytes, ns",
    "telemetry.summary":
        "bytesMoved, bytesMovedTotal, hbmPeakBytes, rooflineFrac, "
        "linkFrac, bytesPerOutputRow, wallMs",
    "compile": "kind (miss|hit|warm|quarantine|warmRebuild|"
               "exportFailed), seconds, error",
    "degrade": "kind, from, to, reason",
    "chaos": "site",
    "admission.queued": "queryId, depth, running",
    "admission.admitted": "queryId, waitMs",
    "admission.shed": "queryId, reason, running",
    "admission.cancelled": "queryId, reason, latencyMs",
    "admission.deadline": "queryId, reason, latencyMs",
    "admission.quarantined": "queryId, reason, crashes",
    "sanitizer.deadlock": "cycle, victim, policy",
    "sanitizer.inversion": "first, second, detail",
    "device.fatal": "site, epoch, error",
    "device.fence": "epoch, cause, inFlight",
    "device.recovery":
        "epoch, ms, drained, restorableBuffers, droppedBuffers",
    "chip.fence": "device, chipEpoch, cause",
    "chip.unfence": "device, chipEpoch",
    "chip.recovery": "device, chipEpoch, shards, survivors, ms",
    "host.fence": "host, devices, chipEpoch, cause",
    "host.unfence": "host, devices, chipEpoch",
    "host.recovery":
        "host, devices, chipEpoch, hosts, survivorHosts, shards, "
        "survivors, ms",
    "ici.retry": "detail, left",
    "dcn.retry": "detail, left",
    "multihost.init": "processes, processIndex, devices, localDevices",
    "serve.connect": "tenant, priorityClass, addr",
    "serve.disconnect": "tenant, queries, bytesOut",
    "serve.query":
        "tenant, priorityClass, planCache, status, rows, wallMs",
    "serve.shed": "tenant, reason",
    "serve.drain": "phase, inFlight, connections",
    "serve.dedupe": "tenant, requestId, outcome (replay|joined|evicted)",
    "serve.escalate": "inFlight, connections",
    "serve.retry": "site, attempt, delayMs",
    "fleet.replica": "name, phase (spawn|ready|exit|restart|giveup), "
                     "pid, port, restarts",
    "fleet.health": "replica, ready, consecutiveFailures",
    "fleet.failover":
        "requestId, tenant, fromReplica, toReplica, reason",
    "fleet.drain": "phase, replicas",
    "stream.start": "partitions, windowBytes, prefetchThreads",
    "stream.partition": "unit, rows, bytes, retired",
    "stream.window": "action (admit|evict|spill|recover|mesh), bytes, "
                     "inUse",
    "stream.end": "partitions, retired, recoveries, windowPeakBytes, "
                  "overlapFraction",
    "write.start": "jobId, path, format, mode, tasks",
    "write.task": "jobId, task, files, bytes, rows",
    "write.commit": "jobId, files, bytes, rows, commitMs, swapped",
    "write.abort": "jobId, reason",
    "write.options": "format, ignored",
    "write.conflict": "path, kind, error",
}

#: Envelope keys present on EVERY event (eventlog validation contract).
REQUIRED_KEYS = ("event", "seq", "ts", "schemaVersion", "queryId")


class EventBus:
    """Synchronous fan-out bus. Emission is serialized under one lock
    so subscribers observe a total order matching `seq` — the property
    the span builder and the event-log writer both rely on. Subscriber
    exceptions are counted, never propagated into the query."""

    def __init__(self):
        self._lock = threading.Lock()
        self._subs: List[Callable[[dict], None]] = []
        self._seq = 0
        self.counts: Dict[str, int] = {}
        self.subscriber_errors = 0

    def subscribe(self, fn: Callable[[dict], None]) -> Callable:
        with self._lock:
            self._subs.append(fn)
        return fn

    def unsubscribe(self, fn: Callable[[dict], None]) -> None:
        with self._lock:
            if fn in self._subs:
                self._subs.remove(fn)

    def emit(self, event: str, **fields) -> dict:
        ev = {"event": event, "schemaVersion": SCHEMA_VERSION,
              "queryId": current_query_id(), "ts": round(time.time(), 6)}
        ctx = task_context()
        if ctx:
            ev.update(ctx)
        ev.update(fields)
        with self._lock:
            self._seq += 1
            ev["seq"] = self._seq
            self.counts[event] = self.counts.get(event, 0) + 1
            for fn in list(self._subs):
                try:
                    fn(ev)
                except Exception:
                    self.subscriber_errors += 1
        return ev


class EventHistory:
    """Ring-buffer subscriber retaining recent events so live-session
    reports (obs/report.py) work without an event log."""

    def __init__(self, capacity: int = 100_000):
        self._events: deque = deque(maxlen=max(100, int(capacity)))
        self._lock = threading.Lock()

    def __call__(self, ev: dict) -> None:
        with self._lock:
            self._events.append(ev)

    def events(self, query_id: Optional[int] = None) -> List[dict]:
        with self._lock:
            evs = list(self._events)
        if query_id is None:
            return evs
        return [e for e in evs if e.get("queryId") == query_id]

    def last_query_id(self) -> Optional[int]:
        with self._lock:
            for e in reversed(self._events):
                if e.get("queryId"):
                    return e["queryId"]
        return None


# ------------------------------------------------------ process wiring

_bus: Optional[EventBus] = None
_install_lock = threading.Lock()


def install(bus: Optional[EventBus]) -> Optional[EventBus]:
    """Make `bus` the process emit target (session lifecycle hook)."""
    global _bus
    with _install_lock:
        _bus = bus
    return bus


def uninstall(bus: EventBus) -> None:
    """Remove `bus` if it is still the active one (a newer session's
    bus must not be torn down by an older session's stop())."""
    global _bus
    with _install_lock:
        if _bus is bus:
            _bus = None


def get() -> Optional[EventBus]:
    return _bus


def armed() -> bool:
    return _bus is not None


def emit(event: str, **fields) -> None:
    """Hot-path entry: one None-check when tracing is off."""
    bus = _bus
    if bus is not None:
        bus.emit(event, **fields)


# ------------------------------------------------------- query context
#
# THREAD-LOCAL: each submitting thread owns its query scope, so
# concurrent queries through one session get distinct ids (the
# multi-tenant governance unit, runtime/admission.py). Nested collects
# on the same thread (cache materialization, writes that read) still
# fold into the enclosing query's stream; scheduler pool threads
# inherit the id through the task scope below.

_query_counter = itertools.count(1)
_query_tls = threading.local()


def allocate_query_id() -> int:
    """Reserve a query id BEFORE the query scope opens — the admission
    controller names queued/shed queries by the same id their events
    and span tree will carry once (if) they run."""
    return next(_query_counter)


def begin_query(qid: Optional[int] = None) -> int:
    """Enter a query scope on this thread; emits `query.start` for the
    OUTERMOST scope only. A preallocated `qid` (admission) is honored
    at the outermost scope; nested scopes keep the enclosing id."""
    depth = getattr(_query_tls, "depth", 0)
    _query_tls.depth = depth + 1
    if depth == 0:
        _query_tls.qid = qid if qid is not None else next(_query_counter)
        emit("query.start")
    return _query_tls.qid


def finish_query(qid: int, **fields) -> None:
    """Leave a query scope; the outermost exit emits `query.end` with
    the caller's summary fields (engine, status, ...)."""
    depth = max(0, getattr(_query_tls, "depth", 0) - 1)
    _query_tls.depth = depth
    if depth == 0:
        # emit BEFORE clearing the id so the end event carries it
        emit("query.end", **fields)
        _query_tls.qid = 0


def current_query_id() -> int:
    return getattr(_query_tls, "qid", 0)


def effective_query_id() -> int:
    """Query attribution for code that may run in a scheduler pool
    thread: the task scope's captured query id first, else this
    thread's own query scope (memory quotas and semaphore diagnostics
    resolve their owner through this)."""
    ctx = task_context()
    if ctx and ctx.get("queryId"):
        return ctx["queryId"]
    return current_query_id()


# -------------------------------------------------------- task context

_task_ctx = threading.local()


@contextlib.contextmanager
def task_scope(stage: int, task: int, attempt: int,
               speculative: bool = False,
               query_id: Optional[int] = None):
    """Tag the current thread with a scheduler attempt identity; events
    emitted inside (operator spans above all) inherit it. Nests: an
    exchange map stage running inside a result task re-tags to the
    inner attempt and restores on exit. `query_id` carries the
    submitting thread's (thread-local) query scope into pool threads —
    emit() lets it override the pool thread's own empty scope."""
    prev = getattr(_task_ctx, "ctx", None)
    ctx = {"stage": stage, "task": task, "attempt": attempt,
           "speculative": bool(speculative)}
    if query_id:
        ctx["queryId"] = query_id
    _task_ctx.ctx = ctx
    try:
        yield
    finally:
        _task_ctx.ctx = prev


def task_context() -> dict:
    return getattr(_task_ctx, "ctx", None) or {}


# ------------------------------------------------------- plan emission

def emit_plan_placement(meta) -> None:
    """Walk a tagged PlanMeta tree (plan/overrides.py) and emit one
    `plan.placement` event per node — the structured twin of
    explain_potential_tpu_plan: `reason` is the exact '; '-joined
    string the NOT_ON_TPU report prints, which is what lets
    obs.report.qualification() match it verbatim."""
    if not armed():
        return

    def walk(m, depth: int) -> None:
        on_dev = m.can_run_on_device
        emit("plan.placement", node=type(m.node).__name__, depth=depth,
             onDevice=bool(on_dev),
             reason=None if on_dev else "; ".join(m.reasons))
        for c in m.children:
            walk(c, depth + 1)

    walk(meta, 0)
