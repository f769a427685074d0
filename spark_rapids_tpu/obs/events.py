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
from typing import Callable, Dict, List, NamedTuple, Optional

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
    "operator.span": "operator, metric, wallNs, deviceNs, rows, spanId, "
                     "parentId, startNs, endNs (+ the span's own fields)",
    "shuffle.write": "shuffleId, reducePid, bytes, staged",
    "shuffle.fetch": "shuffleId, reducePid, blocks, bytes",
    "shuffle.retry": "shuffleId, reducePid, block",
    "spill": "component, direction, fromTier, toTier, bytes",
    "transfer": "direction (h2d|d2h|spill-disk|shuffle|ici|dcn), "
                "site, bytes, ns",
    "telemetry.summary":
        "bytesMoved, bytesMovedTotal, hbmPeakBytes, rooflineFrac, "
        "linkFrac, bytesPerOutputRow, wallMs",
    "compile": "kind (miss|hit), seconds",
    "degrade": "kind, from, to, reason",
    "join": "lowering, joinType, buildRows, buildSlots, probeSlots, "
            "searchedSlots, searchBlocks, windowedBlocks, "
            "outputCapacity, runs",
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


# --------------------------------------------------------------- spans
#
# One scope = one `jax.profiler.TraceAnnotation("srtpu:<name>")` (a host
# event of the profiler's xplane) + one `operator.span` event at exit.
# `startNs`/`endNs` are `time.time_ns()`: CLOCK_REALTIME, the clock the
# xplane's host events are stamped with (docs/observability.md has the
# offset a profiler session applies, as measured on a v5e). Spans nest
# by a thread-local stack; work handed to another thread names its
# parent explicitly.

_span_ids = itertools.count(1)
_span_tls = threading.local()
_annotation = None  # jax.profiler.TraceAnnotation, on the first span


class SpanRef(NamedTuple):
    """What a span's children on OTHER threads need of it."""
    span_id: Optional[int]
    query_id: int


def current_span() -> SpanRef:
    """The innermost open span of this thread and the thread's query:
    the `parent=` to hand to a pool thread."""
    stack = getattr(_span_tls, "stack", None)
    return SpanRef(stack[-1] if stack else None, effective_query_id())


def _emit_span(bus: EventBus, name: str, span_id: int,
               parent_id: Optional[int], start_ns: int, end_ns: int,
               fields: dict) -> None:
    """The one place an `operator.span` event is put together. Field
    `device=True` copies the wall time into `deviceNs` (the eager
    engine's convention: no device time is measured here); field
    `operator` overrides the label the event carries."""
    wall = end_ns - start_ns
    bus.emit("operator.span",
             operator=fields.pop("operator", name),
             metric=fields.pop("metric", None), wallNs=wall,
             deviceNs=wall if fields.pop("device", False) else 0,
             rows=fields.pop("rows", None), spanId=span_id,
             parentId=parent_id, startNs=start_ns, endNs=end_ns,
             **fields)


def record_span(name: str, start_ns: int, end_ns: int,
                parent: Optional[SpanRef] = None, **fields) -> None:
    """Emit one finished span. For a scope that was not a `with` block
    on one thread: a wait that ended before its owner was known, a
    transfer whose end another thread observed. `parent` defaults to
    this thread's innermost open span; an explicit one also names the
    query when this thread has none."""
    bus = _bus
    if bus is None:
        return
    if parent is None:
        parent = current_span()
    elif parent.query_id and not effective_query_id():
        fields["queryId"] = parent.query_id
    _emit_span(bus, name, next(_span_ids), parent.span_id,
               int(start_ns), int(end_ns), fields)


class span:
    """`with span(name, parent=None, **fields) as sp:` — the span
    primitive. With the bus off it costs the TraceAnnotation and one
    None check. `sp.set(...)` adds fields known only inside the scope
    (rows, bytes); `sp.ref` is the handle a child on another thread
    passes as `parent=`, and such a child also lends its thread the
    parent's query id while it is open, so ledger rows recorded under
    it are the query's. `start_ns` backdates a scope whose beginning
    was observed before the scope could be opened."""

    __slots__ = ("name", "fields", "ref", "_parent", "_start", "_ann",
                 "_lent_qid")

    def __init__(self, name: str, parent: Optional[SpanRef] = None,
                 start_ns: Optional[int] = None, **fields):
        self.name = name
        self.fields = fields
        self.ref: Optional[SpanRef] = None  # set while armed and open
        self._parent = parent
        self._start = start_ns
        self._lent_qid = False

    def set(self, **fields) -> None:
        if self.ref is not None and self.fields is not None:
            self.fields.update(fields)

    def discard(self) -> None:
        """Leave no event: the scope found nothing to do (the read
        that finds its iterator exhausted)."""
        self.fields = None

    def __enter__(self) -> "span":
        global _annotation
        if _annotation is None:
            from jax.profiler import TraceAnnotation

            _annotation = TraceAnnotation
        self._ann = _annotation("srtpu:" + self.name)
        self._ann.__enter__()
        if _bus is None:
            return self
        parent = self._parent
        if parent is None:
            parent = current_span()
        elif parent.query_id and not effective_query_id():
            _query_tls.qid = parent.query_id
            self._lent_qid = True
        self._parent = parent
        self.ref = SpanRef(next(_span_ids),
                           parent.query_id or effective_query_id())
        stack = getattr(_span_tls, "stack", None)
        if stack is None:
            stack = _span_tls.stack = []
        stack.append(self.ref.span_id)
        if self._start is None:
            self._start = time.time_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        ref = self.ref
        if ref is not None:
            end = time.time_ns()
            stack = _span_tls.stack
            # generators that hold a scope open across a yield may
            # close out of order: remove this span wherever it sits
            if stack and stack[-1] == ref.span_id:
                stack.pop()
            elif ref.span_id in stack:
                stack.remove(ref.span_id)
            self.ref = None
            bus = _bus
            if bus is not None and self.fields is not None:
                if exc_type is not None:
                    self.fields.setdefault("status", "error")
                _emit_span(bus, self.name, ref.span_id,
                           self._parent.span_id, self._start, end,
                           self.fields)
            if self._lent_qid:
                _query_tls.qid = 0
                self._lent_qid = False
        self._ann.__exit__(exc_type, exc, tb)
        return False


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
