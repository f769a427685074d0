"""Observability subsystem: event bus, span trees, event log, reports.

Layout (see docs/observability.md):

- `events.py`    typed thread-safe event bus + query/task context
- `spans.py`     query->stage->task->operator span trees from the bus
- `eventlog.py`  conf-gated JSONL event log (per-query files, rotation,
                 atomic finalize) + loader reconstructing span trees
- `telemetry.py` data-movement transfer ledger, HBM occupancy timeline,
                 roofline accounting (per-query bytesMoved/hbmPeak/
                 rooflineFrac)
- `report.py`    qualification + profile reports (live session or log)
- `prom.py`      Prometheus text-exposition dump
- `http.py`      conf-gated live scrape endpoint (/metrics, /queries)
- `registry.py`  unified views over every engine counter

The session owns one `ObsManager` (api/session.py): it wires the bus,
the span builder, the in-memory history and the optional event-log
writer, and installs the bus as the process emit target that every
runtime module's `events.emit(...)` hooks feed.
"""

from __future__ import annotations

from typing import List, Optional

from spark_rapids_tpu.obs import events as events  # noqa: F401
from spark_rapids_tpu.obs.events import EventBus, EventHistory
from spark_rapids_tpu.obs import spans as _spans
from spark_rapids_tpu.obs.spans import Span, SpanBuilder


class ObsManager:
    """Session-scoped observability wiring (created in
    TpuSparkSession.__init__, closed in stop())."""

    def __init__(self, conf=None):
        from spark_rapids_tpu.config import rapids_conf as rc
        from spark_rapids_tpu.obs import telemetry

        def get(entry):
            return conf.get(entry) if conf is not None else entry.default

        # the transfer ledger is bus-independent: it keeps counting
        # with obs.enabled=false (its own conf gates it)
        telemetry.configure(conf)
        self.enabled = bool(get(rc.OBS_ENABLED))
        self.bus: Optional[EventBus] = None
        self.history: Optional[EventHistory] = None
        self.spans: Optional[SpanBuilder] = None
        self.writer = None
        self.http = None
        if not self.enabled:
            return
        self.bus = EventBus()
        self.history = EventHistory(get(rc.OBS_HISTORY_EVENTS))
        self.spans = SpanBuilder(ring=_spans.ring)
        self.bus.subscribe(self.history)
        self.bus.subscribe(self.spans)
        if get(rc.EVENTLOG_ENABLED):
            from spark_rapids_tpu.obs.eventlog import EventLogWriter

            self.writer = EventLogWriter(
                get(rc.EVENTLOG_DIR),
                rotate_bytes=get(rc.EVENTLOG_ROTATE_BYTES))
            self.bus.subscribe(self.writer)
        events.install(self.bus)

    def start_http(self, session, conf=None) -> None:
        """Bring up the conf-gated live scrape endpoint (obs/http.py).
        Independent of obs.enabled: the Prometheus dump renders plain
        process counters even with the bus off."""
        from spark_rapids_tpu.obs import http as obs_http

        self.http = obs_http.maybe_start(session, conf)

    @property
    def last_spans(self) -> Optional[Span]:
        """Span tree of the most recently completed query."""
        return self.spans.last if self.spans is not None else None

    def query_events(self, query_id: Optional[int] = None) -> List[dict]:
        if self.history is None:
            return []
        if query_id is None:
            query_id = self.history.last_query_id()
        return self.history.events(query_id)

    def close(self) -> None:
        if self.http is not None:
            try:
                self.http.close()
            except Exception:
                pass
            self.http = None
        if self.writer is not None:
            self.writer.close()
        if self.bus is not None:
            events.uninstall(self.bus)
