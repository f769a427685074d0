"""Query -> stage -> task -> operator span trees, built from the bus.

The reference attributes device work to plan nodes through NVTX ranges
read back in Nsight; the TPU engine's equivalent is this tree: every
scheduler attempt is a task span, every scope opened with
`obs.events.span` (PhysicalPlan.timed, profiler.annotate_with_metric,
the fused engine's plan / prepare / decode / h2d / dispatch / fetch
scopes) is an operator span with a start and an end on the profiler
trace's clock, nested under the span that was open when it began
(`parentId`), and losing speculative attempts keep their spans marked
`discarded` so double-counted time is visible instead of silently
folded in.

Finished trees also go to `ring`, a process-wide buffer that outlives
`session.stop()`: what measures a window reads it after the fact.

The builder is a plain bus subscriber; `build_from_events` replays a
recorded stream (obs/eventlog.py loader) through the SAME logic, which
is what makes a loaded log reconstruct the identical tree the live
session built.
"""

from __future__ import annotations

import threading
from collections import OrderedDict, deque
from typing import Callable, Dict, Iterable, List, Optional


class Span:
    """One node of the tree. `kind` is query|stage|task|operator."""

    __slots__ = ("kind", "name", "query_id", "stage", "task", "attempt",
                 "speculative", "start_ts", "end_ts", "wall_ns",
                 "device_ns", "rows", "status", "children", "extra",
                 "span_id", "parent_id", "start_ns", "end_ns")

    def __init__(self, kind: str, name: str, query_id: int = 0,
                 stage: Optional[int] = None, task: Optional[int] = None,
                 attempt: Optional[int] = None, speculative: bool = False,
                 start_ts: Optional[float] = None):
        self.kind = kind
        self.name = name
        self.query_id = query_id
        self.stage = stage
        self.task = task
        self.attempt = attempt
        self.speculative = speculative
        self.start_ts = start_ts
        self.end_ts: Optional[float] = None
        self.wall_ns: int = 0
        self.device_ns: int = 0
        self.rows: Optional[int] = None
        self.status = "open"
        self.children: List["Span"] = []
        self.extra: Dict[str, object] = {}
        self.span_id: Optional[int] = None
        self.parent_id: Optional[int] = None
        #: the interval on the trace's clock; None on a span whose
        #: emitter gave a duration only
        self.start_ns: Optional[int] = None
        self.end_ns: Optional[int] = None

    def self_ns(self) -> int:
        """The span's duration minus what its children cover of it:
        the union of their intervals, clipped to its own. Children on
        pool threads overlap one another, and one may end after its
        parent has (an upload that outlives the scope that began it)."""
        if not self.children:
            return self.wall_ns
        if self.start_ns is None:
            return max(0, self.wall_ns - sum(c.wall_ns
                                             for c in self.children))
        covered, upto = 0, self.start_ns
        for s, e in sorted((c.start_ns, c.end_ns) for c in self.children
                           if c.start_ns is not None):
            s, e = max(s, upto), min(e, self.end_ns)
            if e > s:
                covered += e - s
                upto = e
        return max(0, self.end_ns - self.start_ns - covered)

    def to_dict(self) -> dict:
        d = {"kind": self.kind, "name": self.name,
             "queryId": self.query_id, "status": self.status,
             "startTs": self.start_ts, "endTs": self.end_ts,
             "wallNs": self.wall_ns, "deviceNs": self.device_ns,
             "rows": self.rows}
        if self.span_id is not None:
            d["spanId"] = self.span_id
            d["startNs"], d["endNs"] = self.start_ns, self.end_ns
            d["selfNs"] = self.self_ns()
        if self.stage is not None:
            d["stage"] = self.stage
        if self.task is not None:
            d["task"] = self.task
        if self.attempt is not None:
            d["attempt"] = self.attempt
        if self.speculative:
            d["speculative"] = True
        if self.children:
            d["children"] = [c.to_dict() for c in self.children]
        return d

    def walk(self) -> Iterable["Span"]:
        yield self
        for c in self.children:
            yield from c.walk()

    def __repr__(self):
        return (f"Span({self.kind} {self.name!r} status={self.status} "
                f"children={len(self.children)})")


def tree_depth(root: Optional[Span]) -> int:
    if root is None:
        return 0
    return 1 + max((tree_depth(c) for c in root.children), default=0)


def operator_totals(root: Optional[Span],
                    include_discarded: bool = False) -> Dict[str, dict]:
    """Aggregate operator spans by operator name:
    {name: {wallNs, deviceNs, rows, count, discardedNs}}. Spans nest,
    so each contributes its SELF time: a sum over the names counts no
    nanosecond twice. Discarded (losing-attempt) spans contribute only
    to discardedNs unless `include_discarded`."""
    out: Dict[str, dict] = {}
    if root is None:
        return out
    for s in root.walk():
        if s.kind != "operator":
            continue
        t = out.setdefault(s.name, {"wallNs": 0, "deviceNs": 0,
                                    "rows": 0, "count": 0,
                                    "discardedNs": 0})
        own = s.self_ns()
        if s.status == "discarded" and not include_discarded:
            t["discardedNs"] += own
            continue
        t["wallNs"] += own
        t["deviceNs"] += min(s.device_ns, own)
        if s.rows:
            t["rows"] += s.rows
        t["count"] += 1
    return out


def task_rows(root: Optional[Span]) -> Optional[int]:
    """Committed result-stage row total (the query's output rows) when
    task attempt ends carried row counts."""
    if root is None:
        return None
    total, seen = 0, False
    for s in root.walk():
        if s.kind == "task" and s.status == "ok" and s.rows is not None \
                and s.extra.get("result_stage"):
            total += s.rows
            seen = True
    return total if seen else None


def _start_of(span: "Span") -> int:
    return span.start_ns or 0


def _ns(ts: float) -> int:
    """An event's `ts` (unix seconds) on the spans' nanosecond clock."""
    return int(round(ts * 1e9))


class TreeRing:
    """Finished query trees, newest last, bounded by trees and by
    spans (an eager query over many partitions has thousands)."""

    def __init__(self, max_trees: int = 4096, max_spans: int = 1 << 18):
        self._max_trees, self._max_spans = max_trees, max_spans
        self._trees: deque = deque()
        self._spans = 0
        self._lock = threading.Lock()

    def append(self, root: "Span", n: Optional[int] = None) -> None:
        """`n`: the tree's number of spans, where the caller has it."""
        if n is None:
            n = sum(1 for _ in root.walk())
        with self._lock:
            self._trees.append((root, n))
            self._spans += n
            while len(self._trees) > 1 and (
                    len(self._trees) > self._max_trees
                    or self._spans > self._max_spans):
                self._spans -= self._trees.popleft()[1]

    def last(self, n: Optional[int] = None) -> List["Span"]:
        """The newest `n` trees (all of them by default), oldest
        first."""
        with self._lock:
            trees = [t for t, _ in self._trees]
        return trees if n is None else trees[max(0, len(trees) - n):]

    def clear(self) -> None:
        with self._lock:
            self._trees.clear()
            self._spans = 0

    def __len__(self) -> int:
        return len(self._trees)


#: The process-wide ring (the telemetry.ledger / compile_cache.stats
#: pattern): every session's builder appends to it, nothing clears it
#: at `session.stop()`. 4096 trees hold a 40 s window of 10 ms queries.
ring = TreeRing()


class _TreeState:
    def __init__(self, root: Span):
        self.root = root
        self.stages: Dict[int, Span] = {}
        self.tasks: Dict[tuple, Span] = {}
        self.by_id: Dict[int, Span] = {}
        #: spans that arrived before their parent (a child's event is
        #: emitted at ITS end, which comes first), by the parent's id
        self.waiting: Dict[int, List[Span]] = {}

    def place(self, sp: Span, fallback: Span) -> None:
        """Hang `sp` under its parent, or park it until the parent's
        own event arrives; no `parentId` means `fallback`."""
        if sp.span_id is not None:
            self.by_id[sp.span_id] = sp
            sp.children.extend(self.waiting.pop(sp.span_id, ()))
        if sp.parent_id is None:
            fallback.children.append(sp)
        elif sp.parent_id in self.by_id:
            self.by_id[sp.parent_id].children.append(sp)
        else:
            self.waiting.setdefault(sp.parent_id, []).append(sp)

    def settle(self) -> int:
        """At the query's end: spans whose parent never reported hang
        off the root; every span's children go in order of start; a
        span still open is `unfinished`. -> the number of spans."""
        for spans in self.waiting.values():
            self.root.children.extend(spans)
        self.waiting.clear()
        n = 0
        for s in self.root.walk():
            n += 1
            if len(s.children) > 1:
                s.children.sort(key=_start_of)
            if s.status == "open":
                s.status = "unfinished"
        return n


class SpanBuilder:
    """Bus subscriber incrementally building one tree per query.
    Thread-safe: the bus serializes delivery, but `build_from_events`
    and tests may drive it directly, so it keeps its own lock.
    `ring` receives every finished tree (the live builder's is the
    process-wide one; a replay keeps its trees to itself)."""

    #: finished trees that still take a span arriving late
    _DONE_KEEP = 64

    def __init__(self, on_complete: Optional[Callable[[Span], None]] = None,
                 keep: int = 4, ring: Optional[TreeRing] = None):
        self._on_complete = on_complete
        self._keep = max(1, keep)
        self._ring = ring
        self._live: Dict[int, _TreeState] = {}
        self._done: "OrderedDict[int, _TreeState]" = OrderedDict()
        self.completed: List[Span] = []
        self.last: Optional[Span] = None
        #: spans that arrived after their query's `query.end` (a
        #: watcher thread's, by a hair): counted, and still hung in
        #: the finished tree while the builder remembers it
        self.late_spans = 0
        self._handlers: Dict[str, Optional[Callable]] = {}
        self._lock = threading.Lock()

    # --- subscriber entry ---

    def __call__(self, ev: dict) -> None:
        name = ev["event"]
        try:
            handler = self._handlers[name]
        except KeyError:
            handler = self._handlers[name] = getattr(
                self, "_on_" + name.replace(".", "_"), None)
        if handler is None:
            return
        with self._lock:
            handler(ev)

    # --- per-event handlers (called under lock) ---

    def _state(self, ev: dict) -> Optional[_TreeState]:
        return self._live.get(ev.get("queryId") or 0)

    def _on_query_start(self, ev: dict) -> None:
        qid = ev.get("queryId") or 0
        root = Span("query", f"query-{qid}", qid, start_ts=ev["ts"])
        root.start_ns = _ns(ev["ts"])
        self._live[qid] = _TreeState(root)

    def _on_query_end(self, ev: dict) -> None:
        qid = ev.get("queryId") or 0
        st = self._live.pop(qid, None)
        if st is None:
            return
        root = st.root
        if root.end_ns is None:  # no `query` span gave the interval
            root.end_ts = ev["ts"]
            root.end_ns = max(_ns(ev["ts"]), root.start_ns)
            root.wall_ns = root.end_ns - root.start_ns
        root.status = ev.get("status", "ok")
        for key in ("engine", "fallbacks", "degradations"):
            root.extra[key] = ev.get(key)
        n_spans = st.settle()
        self._done[qid] = st
        while len(self._done) > self._DONE_KEEP:
            self._done.popitem(last=False)
        self.completed.append(root)
        del self.completed[:-self._keep]
        self.last = root
        if self._ring is not None:
            self._ring.append(root, n_spans)
        if self._on_complete is not None:
            try:
                self._on_complete(root)
            except Exception:
                pass

    def _on_stage_start(self, ev: dict) -> None:
        st = self._state(ev)
        if st is None:
            return
        sp = Span("stage", str(ev.get("name", "stage")),
                  ev.get("queryId") or 0, stage=ev.get("stage"),
                  start_ts=ev["ts"])
        sp.start_ns = _ns(ev["ts"])
        sp.extra["tasks"] = ev.get("tasks")
        st.stages[ev.get("stage")] = sp
        st.root.children.append(sp)

    def _on_stage_end(self, ev: dict) -> None:
        st = self._state(ev)
        if st is None:
            return
        sp = st.stages.get(ev.get("stage"))
        if sp is not None:
            sp.end_ts = ev["ts"]
            sp.end_ns = _ns(ev["ts"])
            sp.status = ev.get("status", "ok")

    def _stage_for(self, st: _TreeState, ev: dict) -> Span:
        sid = ev.get("stage")
        sp = st.stages.get(sid)
        if sp is None:
            # task events may outrun their stage record on a replay
            # slice; synthesize a stage container rather than drop them
            sp = Span("stage", f"stage-{sid}", ev.get("queryId") or 0,
                      stage=sid, start_ts=ev["ts"])
            sp.start_ns = _ns(ev["ts"])
            st.stages[sid] = sp
            st.root.children.append(sp)
        return sp

    def _on_task_attempt_start(self, ev: dict) -> None:
        st = self._state(ev)
        if st is None:
            return
        stage_sp = self._stage_for(st, ev)
        key = (ev.get("stage"), ev.get("task"), ev.get("attempt"))
        sp = Span("task",
                  f"{stage_sp.name}[{ev.get('task')}]#{ev.get('attempt')}",
                  ev.get("queryId") or 0, stage=ev.get("stage"),
                  task=ev.get("task"), attempt=ev.get("attempt"),
                  speculative=bool(ev.get("speculative")),
                  start_ts=ev["ts"])
        sp.start_ns = _ns(ev["ts"])
        sp.extra["worker"] = ev.get("worker")
        if stage_sp.name == "result":
            sp.extra["result_stage"] = True
        st.tasks[key] = sp
        stage_sp.children.append(sp)

    def _on_task_attempt_end(self, ev: dict) -> None:
        st = self._state(ev)
        if st is None:
            return
        key = (ev.get("stage"), ev.get("task"), ev.get("attempt"))
        sp = st.tasks.get(key)
        if sp is None:
            return
        sp.end_ts = ev["ts"]
        sp.end_ns = _ns(ev["ts"])
        sp.status = ev.get("status", "ok")
        if ev.get("wallMs") is not None:
            sp.wall_ns = int(ev["wallMs"] * 1_000_000)
        if ev.get("rows") is not None:
            sp.rows = ev["rows"]
        if sp.status != "ok":
            # a losing/failed attempt's operator work is non-result
            # work: mark the whole subtree so time attribution can
            # separate it (the speculation-accounting contract)
            for child in sp.children:
                for s in child.walk():
                    s.status = sp.status
        # accumulate device time upward for committed attempts
        elif sp.device_ns == 0:
            sp.device_ns = sum(c.device_ns for c in sp.children)

    def _on_operator_span(self, ev: dict) -> None:
        st = self._state(ev)
        if st is None:
            self.late_spans += 1
            st = self._done.get(ev.get("queryId") or 0)
            if st is None:
                return
        wall = int(ev.get("wallNs") or 0)
        if ev.get("startNs") is not None:
            start_ns, end_ns = int(ev["startNs"]), int(ev["endNs"])
        else:  # a duration only, emitted at its end
            end_ns = _ns(ev["ts"])
            start_ns = end_ns - wall
        if (ev.get("operator") == "query" and ev.get("parentId") is None
                and ev.get("spanId") is not None
                and ev.get("stage") is None):
            # the `query` span IS the root: it gives the root its
            # interval on the spans' clock and its id to its children
            sp = st.root
            sp.extra.update({k: v for k, v in ev.items()
                             if k not in _SPAN_KEYS})
        else:
            sp = Span("operator", str(ev.get("operator")),
                      ev.get("queryId") or 0, stage=ev.get("stage"),
                      task=ev.get("task"), attempt=ev.get("attempt"),
                      speculative=bool(ev.get("speculative")))
            sp.device_ns = int(ev.get("deviceNs") or 0)
            sp.rows = ev.get("rows")
            sp.status = ev.get("status", "ok")
            # the span's own fields (program, site, bytes, ...) are
            # read off the event itself: no copy on the query's path
            sp.extra = ev
            sp.parent_id = ev.get("parentId")
        sp.span_id = ev.get("spanId")
        sp.start_ns, sp.end_ns, sp.wall_ns = start_ns, end_ns, wall
        sp.start_ts, sp.end_ts = start_ns / 1e9, end_ns / 1e9
        if sp is st.root:
            st.by_id[sp.span_id] = sp
            sp.children.extend(st.waiting.pop(sp.span_id, ()))
            return
        key = (ev.get("stage"), ev.get("task"), ev.get("attempt"))
        task = st.tasks.get(key) if ev.get("stage") is not None else None
        st.place(sp, task if task is not None else st.root)


#: Keys of an `operator.span` event that Span carries as attributes;
#: the `query` span's other fields join the root's `extra` (an
#: operator span's `extra` is its event).
_SPAN_KEYS = frozenset((
    "event", "seq", "ts", "schemaVersion", "queryId", "stage", "task",
    "attempt", "speculative", "operator", "wallNs", "deviceNs", "rows",
    "status", "spanId", "parentId", "startNs", "endNs"))


def build_from_events(events: Iterable[dict]) -> List[Span]:
    """Replay a recorded event stream into finished span trees (one per
    query). Streams cut off before `query.end` still return their
    partial tree, marked `unfinished`."""
    done: List[Span] = []
    builder = SpanBuilder(on_complete=done.append, keep=1_000_000)
    for ev in events:
        builder(ev)
    for st in builder._live.values():
        st.settle()
        root = st.root
        root.status = "unfinished"
        done.append(root)
    return done
