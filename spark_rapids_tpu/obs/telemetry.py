"""Data-movement telemetry: transfer ledger, HBM occupancy, roofline.

The round-5 review measured a hot query at roofline_frac ~ 0.006, and
every planned optimization (ICI-resident shuffle, compressed
execution, out-of-core streaming) is a bytes-moved optimization. The
reference stack's profiling tool attributes transfer volume per
operator to drive exactly that tuning loop; this module is the engine's
equivalent measurement substrate:

- **Transfer ledger**: every byte-crossing site (H2D uploads, D2H
  materialization at collect, shuffle write/fetch, disk spill/unspill)
  calls `record(direction, site, bytes, ns)`; entries are attributed to
  the owning query through the obs query/task scope (obs/events.py) and
  mirrored onto the event bus as `transfer` events so the event log is
  a complete audit of data movement. Directions are the four physical
  channels: `h2d`, `d2h`, `spill-disk` (disk I/O of the spill tiers),
  and `shuffle` (inter-task/inter-process block movement).

- **HBM occupancy timeline**: the SpillCatalog's reservation ledger
  (runtime/memory.py) feeds `hbm_global` / `hbm_query` on every device
  reserve/release, so the process keeps a bounded (ts, reservedBytes)
  timeline, a global high-water mark that tracks the pool's own peak,
  and a per-query device-footprint peak — a query's peak HBM usage is
  a reported number, not a guess. Spill pressure (synchronous spills
  triggered by a failed reservation) is counted per query.

- **Roofline accounting**: `link_peaks()` measures the H2D/D2H link
  once per process (a timed `device_put`/`device_get` of a fixed
  buffer, and the round trip of one small dispatch + fetch) and reads
  the device HBM peak bandwidth from the public spec table — a device
  kind the table does not list is an error, never a default. The
  result is cached as JSON beside the compile cache's index
  (runtime/compile_cache.py), stamped with the device kind it was
  measured on, so another kind re-probes and a warm process never pays
  the probe.
  `query_summary()` combines the peaks with the per-query ledger into
  `rooflineFrac` (achieved bytes/s over the query wall time vs the
  device HBM peak — the same definition bench.py has always used),
  `linkFrac` (link-crossing bytes/s vs the measured H2D link), and
  `bytesPerOutputRow`.

The ledger is deliberately independent of `obs.enabled`: counters keep
working with the bus off (record() just skips the event emission), and
`spark.rapids.tpu.telemetry.enabled=false` reduces every site to one
boolean check.
"""

from __future__ import annotations

import atexit
import json
import os
import threading
import time
from collections import OrderedDict, deque
from typing import Dict, List, Optional

from spark_rapids_tpu.obs import events as _events

#: The physical data-movement channels a transfer is tagged with.
#: `ici` is the inter-chip interconnect: bytes moved by mesh collectives
#: (all_to_all / all_gather inside SPMD programs) that never touch a
#: host link — the proof surface for "host bytes went to zero" on an
#: ICI-resident exchange. `dcn` is the cross-host data-center network
#: tier of a multi-host mesh: bytes moved by collectives over the host
#: axis (hierarchical-agg finals, broadcast builds, dictionary
#: reconciliation syncs) — the planner's job is to keep this number
#: far below `ici`.
DIRECTIONS = ("h2d", "d2h", "spill-disk", "shuffle", "ici", "dcn")

#: Peak HBM bandwidth per chip, bytes/s (public TPU specs; the cpu
#: backend the tests run on gets a nominal DDR figure so fractions stay
#: defined there). bench.py and chip_smoke.py read this table too — one
#: source of truth. A kind that is not listed is an error
#: (`device_peak_bw`): add the row with its source, do not default.
DEVICE_PEAK_BW = {
    "TPU v4": 1.2e12,
    "TPU v5e": 8.19e11,
    "TPU v5 lite": 8.19e11,
    "TPU v5p": 2.765e12,
    "TPU v6e": 1.64e12,
    "cpu": 5.0e10,
}

_PROBE_BYTES = 8 << 20          # link probe transfer size
_PROBE_REPEATS = 5              # timed repeats per direction (median)
_QUERY_KEEP = 64                # per-query ledgers retained
_TIMELINE_KEEP = 4096           # (ts, reservedBytes) samples retained
_INTERVAL_KEEP = 4096           # per-query busy intervals per kind


def _busy_union(spans) -> List[tuple]:
    """Merge (t0, t1) spans into a sorted disjoint union."""
    out: List[tuple] = []
    for t0, t1 in sorted(spans):
        if t1 <= t0:
            continue
        if out and t0 <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], t1))
        else:
            out.append((t0, t1))
    return out


def _overlap_fraction(a_spans, b_spans) -> Optional[float]:
    """|union(a) ∩ union(b)| over the shorter busy total — the
    pipelining figure of merit: 1.0 means the cheaper stage ran
    entirely under the cover of the other; 0.0 means fully
    serialized. None when either timeline is empty."""
    a, b = _busy_union(a_spans), _busy_union(b_spans)
    if not a or not b:
        return None
    inter = 0.0
    i = j = 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            inter += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    shorter = min(sum(t1 - t0 for t0, t1 in a),
                  sum(t1 - t0 for t0, t1 in b))
    if shorter <= 0:
        return None
    return max(0.0, min(1.0, inter / shorter))


def _cell() -> Dict[str, int]:
    return {"bytes": 0, "ns": 0, "count": 0}


class _QueryLedger:
    """Per-query accumulation (one per queryId, bounded LRU)."""

    __slots__ = ("by_direction", "by_site", "hbm_peak", "hbm_current",
                 "spill_pressure", "final", "enc_actual", "enc_plain",
                 "ici_host_avoided", "labels", "stream", "intervals",
                 "write")

    def __init__(self):
        self.by_direction: Dict[str, Dict[str, int]] = {}
        self.by_site: Dict[str, Dict[str, int]] = {}
        self.hbm_peak = 0
        self.hbm_current = 0
        self.spill_pressure = 0
        self.final: Optional[dict] = None  # end-of-query summary
        # caller-attached attribution (serve/: tenant, priorityClass);
        # merged into query_summary so /queries rows carry their owner
        self.labels: Optional[dict] = None
        # encoded execution: bytes actually staged for encoded columns
        # vs what the decoded representation would have staged
        self.enc_actual = 0
        self.enc_plain = 0
        # host-link bytes an ICI-resident exchange kept off h2d/d2h
        # (the d2h + h2d round trip of the decoded payload the host
        # shuffle path would have moved for the same rows)
        self.ici_host_avoided = 0
        # streaming executor stats (stream/): windowPeakBytes is a max,
        # partitionsStreamed/recoveries are sums
        self.stream: Dict[str, int] = {}
        # busy-interval timeline per kind ("h2d" | "compute"): bounded
        # (t0, t1) monotonic spans feeding overlapFraction
        self.intervals: Dict[str, List[tuple]] = {}
        # commit-protocol write stats (io/commit.py): bytes/files/rows
        # published and job-commit wall time, all sums
        self.write: Dict[str, int] = {}


class TransferLedger:
    """Process-wide data-movement ledger (the compile_cache.stats
    pattern: one module singleton, per-query views carved out of it)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.enabled = True
        self.totals: Dict[str, Dict[str, int]] = {}
        self.sites: Dict[str, Dict[str, int]] = {}
        self._site_dir: Dict[str, str] = {}
        self._queries: "OrderedDict[int, _QueryLedger]" = OrderedDict()
        # HBM occupancy
        self.hbm_reserved = 0
        self.hbm_peak = 0
        self.pressure_events = 0
        self.timeline: deque = deque(maxlen=_TIMELINE_KEEP)
        self.device_epoch = 1  # stamped by hbm_epoch_marker on recovery
        # encoded-execution savings (process totals)
        self.enc_actual = 0
        self.enc_plain = 0
        # host-link bytes ICI collectives kept off h2d/d2h (process)
        self.ici_host_avoided = 0

    # --- transfer recording ---

    def record(self, direction: str, site: str, nbytes: int,
               ns: int = 0, query_id: Optional[int] = None,
               emit: bool = True) -> None:
        """Account one transfer. `query_id` defaults to the calling
        thread's effective query (task scope first — pool threads —
        then the thread's own query scope); `ns` is the wall time the
        caller measured around the transfer (0 when the site dispatches
        asynchronously and has no honest number)."""
        if not self.enabled or nbytes <= 0:
            return
        qid = query_id if query_id is not None \
            else _events.effective_query_id()
        with self._lock:
            for cell in (self.totals.setdefault(direction, _cell()),
                         self.sites.setdefault(site, _cell()),
                         self._query(qid).by_direction.setdefault(
                             direction, _cell()),
                         self._query(qid).by_site.setdefault(
                             site, _cell())):
                cell["bytes"] += int(nbytes)
                cell["ns"] += int(ns)
                cell["count"] += 1
            self._site_dir[site] = direction
        if emit:
            # an explicit owner (a pool thread's upload) names the
            # query on the event too, so log and ledger agree
            owner = {"queryId": qid} if query_id else {}
            _events.emit("transfer", direction=direction, site=site,
                         bytes=int(nbytes), ns=int(ns), **owner)

    def add_ns(self, direction: str, site: str, ns: int,
               query_id: int) -> None:
        """The time of a transfer whose bytes `record(..., ns=0)`
        counted when it was enqueued, added when its completion was
        observed (`put_watched`): the row's `ns` is then time to
        completion, never the time of an asynchronous enqueue."""
        if not self.enabled or ns <= 0:
            return
        with self._lock:
            q = self._query(query_id)
            for cell in (self.totals.setdefault(direction, _cell()),
                         self.sites.setdefault(site, _cell()),
                         q.by_direction.setdefault(direction, _cell()),
                         q.by_site.setdefault(site, _cell())):
                cell["ns"] += int(ns)

    def record_encoded(self, site: str, actual_bytes: int,
                       plain_bytes: int,
                       query_id: Optional[int] = None) -> None:
        """Account one encoded-representation saving: `actual_bytes`
        is what the encoded column stages for transfer, `plain_bytes`
        what its decoded padded layout would have staged. Feeds the
        per-query bytesSavedEncoded / effectiveCompressionRatio
        summary fields (ROADMAP item 2's effective-compression
        metric)."""
        if not self.enabled or plain_bytes <= 0:
            return
        qid = query_id if query_id is not None \
            else _events.effective_query_id()
        with self._lock:
            self.enc_actual += int(actual_bytes)
            self.enc_plain += int(plain_bytes)
            q = self._query(qid)
            q.enc_actual += int(actual_bytes)
            q.enc_plain += int(plain_bytes)

    def record_ici(self, site: str, nbytes: int,
                   host_equiv_bytes: int = 0,
                   query_id: Optional[int] = None) -> None:
        """Account one mesh collective: `nbytes` crossed the ICI
        fabric inside an SPMD program (static send-buffer bytes x mesh
        size, derived at trace time — collectives cannot self-report
        from inside jit); `host_equiv_bytes` is what the host-shuffle
        path would have moved over h2d+d2h for the same payload (the
        decoded-layout round trip), feeding the per-query
        `hostBytesAvoided` summary field."""
        if not self.enabled or nbytes <= 0:
            return
        qid = query_id if query_id is not None \
            else _events.effective_query_id()
        self.record("ici", site, nbytes, query_id=qid)
        if host_equiv_bytes > 0:
            with self._lock:
                self.ici_host_avoided += int(host_equiv_bytes)
                self._query(qid).ici_host_avoided += \
                    int(host_equiv_bytes)

    def record_dcn(self, site: str, nbytes: int,
                   query_id: Optional[int] = None) -> None:
        """Account one CROSS-HOST mesh collective: `nbytes` crossed the
        DCN tier of a multi-host mesh (collectives over the host axis —
        per-shard static bytes x shard count, derived at trace time
        like record_ici). Separate direction so the ici/dcn placement
        split the topology-aware planner makes is a measured number."""
        if not self.enabled or nbytes <= 0:
            return
        qid = query_id if query_id is not None \
            else _events.effective_query_id()
        self.record("dcn", site, nbytes, query_id=qid)

    def record_interval(self, kind: str, t0: float, t1: float,
                        query_id: Optional[int] = None) -> None:
        """Account one busy interval of a pipelined stage ("h2d" |
        "compute", monotonic seconds) on the owning query's timeline —
        the substrate for overlapFraction (streaming executor's proof
        that transfer and compute actually overlapped)."""
        if not self.enabled or t1 <= t0:
            return
        qid = query_id if query_id is not None \
            else _events.effective_query_id()
        if not qid:
            return
        with self._lock:
            spans = self._query(qid).intervals.setdefault(kind, [])
            spans.append((float(t0), float(t1)))
            if len(spans) > _INTERVAL_KEEP:
                del spans[:len(spans) - _INTERVAL_KEEP]

    def record_stream(self, query_id: Optional[int] = None,
                      **fields) -> None:
        """Fold streaming-executor stats into the owning query's
        ledger: *Peak*/*Bytes-max keys (windowPeakBytes) keep the max,
        counters (partitionsStreamed, recoveries) accumulate."""
        if not self.enabled:
            return
        qid = query_id if query_id is not None \
            else _events.effective_query_id()
        if not qid:
            return
        with self._lock:
            st = self._query(qid).stream
            for k, v in fields.items():
                if v is None:
                    continue
                if k.endswith("PeakBytes") or k.endswith("Budget"):
                    st[k] = max(st.get(k, 0), int(v))
                else:
                    st[k] = st.get(k, 0) + int(v)

    def record_write(self, query_id: Optional[int] = None,
                     **fields) -> None:
        """Fold one committed write job's stats (io/commit.py
        commit_job: bytes, files, rows, jobs, commitMs) into the
        owning query's ledger — the per-query `write` block of
        query_summary."""
        if not self.enabled:
            return
        qid = query_id if query_id is not None \
            else _events.effective_query_id()
        if not qid:
            return
        with self._lock:
            w = self._query(qid).write
            for k, v in fields.items():
                if v is None:
                    continue
                w[k] = w.get(k, 0) + int(v)

    def record_forwarded(self, fields: dict,
                         query_id: Optional[int] = None) -> None:
        """Fold a worker-forwarded `transfer` event (process pool) into
        the driver ledger and re-emit it on the driver bus under the
        driver's query attribution."""
        self.record(str(fields.get("direction", "shuffle")),
                    str(fields.get("site", "worker")),
                    int(fields.get("bytes") or 0),
                    ns=int(fields.get("ns") or 0),
                    query_id=query_id)

    # --- HBM occupancy (SpillCatalog hooks) ---

    def hbm_global(self, reserved: int) -> None:
        """Called by the device pool after every reserve/release with
        its post-op total; keeps the process timeline + high-water."""
        if not self.enabled:
            return
        with self._lock:
            self.hbm_reserved = reserved
            if reserved > self.hbm_peak:
                self.hbm_peak = reserved
            self.timeline.append((round(time.time(), 6), reserved))

    def hbm_query(self, query_id: int, reserved: int) -> None:
        """Called by the catalog's per-query quota ledger with the
        query's post-op device reservation total."""
        if not self.enabled or not query_id:
            return
        with self._lock:
            q = self._query(query_id)
            q.hbm_current = reserved
            if reserved > q.hbm_peak:
                q.hbm_peak = reserved

    def hbm_pressure(self, target: int, freed: int,
                     query_id: Optional[int] = None) -> None:
        """A failed device reservation forced a synchronous spill."""
        if not self.enabled:
            return
        qid = query_id if query_id is not None \
            else _events.effective_query_id()
        with self._lock:
            self.pressure_events += 1
            if qid:
                self._query(qid).spill_pressure += 1

    # --- views ---

    def _query(self, qid: int) -> _QueryLedger:
        """Under lock: the (possibly new) ledger for a query id."""
        q = self._queries.get(qid)
        if q is None:
            q = self._queries[qid] = _QueryLedger()
            while len(self._queries) > _QUERY_KEEP:
                self._queries.popitem(last=False)
        return q

    def query_summary(self, query_id: int,
                      wall_s: Optional[float] = None,
                      output_rows: Optional[int] = None) -> dict:
        """One query's data-movement report: bytes moved by direction
        and site, HBM footprint peak, and — when the caller supplies
        the query wall time — rooflineFrac/linkFrac."""
        if not self.enabled:
            return {}
        with self._lock:
            q = self._queries.get(query_id)
            by_dir = {} if q is None else {
                d: dict(c) for d, c in q.by_direction.items()}
            by_site = {} if q is None else {
                s: dict(c) for s, c in q.by_site.items()}
            hbm_peak = 0 if q is None else q.hbm_peak
            pressure = 0 if q is None else q.spill_pressure
            enc_actual = 0 if q is None else q.enc_actual
            enc_plain = 0 if q is None else q.enc_plain
            ici_avoided = 0 if q is None else q.ici_host_avoided
            labels = None if q is None or not q.labels \
                else dict(q.labels)
            stream = {} if q is None else dict(q.stream)
            write = {} if q is None else dict(q.write)
            intervals = {} if q is None else {
                k: list(v) for k, v in q.intervals.items()}
        total = sum(c["bytes"] for c in by_dir.values())
        link = sum(by_dir.get(d, _cell())["bytes"]
                   for d in ("h2d", "d2h"))
        out = {
            "bytesMoved": {d: by_dir[d]["bytes"] for d in sorted(by_dir)},
            "bytesMovedTotal": total,
            "transfers": sum(c["count"] for c in by_dir.values()),
            "perSite": by_site,
            "hbmPeakBytes": hbm_peak,
            "spillPressureEvents": pressure,
        }
        if labels:
            out["labels"] = labels
        ici = by_dir.get("ici", _cell())["bytes"]
        if ici > 0:
            # ICI-resident shuffle: bytes that rode the mesh fabric
            # instead of the host links, and the h2d+d2h round trip
            # of the decoded payload those collectives displaced
            out["iciBytes"] = ici
            out["hostBytesAvoided"] = ici_avoided
        dcn = by_dir.get("dcn", _cell())["bytes"]
        if dcn > 0:
            # multi-host mesh: bytes that had to cross the slow DCN
            # tier (hierarchical finals / broadcast builds) — compare
            # against iciBytes to see the planner's placement win
            out["dcnBytes"] = dcn
        if stream:
            # streaming executor (stream/): window high-water, how many
            # partition units streamed through it, and the measured
            # H2D/compute busy-interval overlap — the out-of-core
            # pipelining proof (overlapFraction > 0 means transfer hid
            # under compute or vice versa; None when a stage timeline
            # is empty)
            out["windowPeakBytes"] = stream.get("windowPeakBytes", 0)
            out["partitionsStreamed"] = stream.get(
                "partitionsStreamed", 0)
            if stream.get("recoveries"):
                out["streamRecoveries"] = stream["recoveries"]
            frac = _overlap_fraction(intervals.get("h2d", ()),
                                     intervals.get("compute", ()))
            if frac is not None:
                out["overlapFraction"] = round(frac, 4)
        if write:
            # commit-protocol writes (io/commit.py): what this query
            # published and how long the job commit(s) took
            out["write"] = write
        if enc_plain > 0 and enc_actual > 0:
            # encoded execution's measured win: bytes the dictionary
            # representation kept OFF the staging/transfer paths, and
            # the resulting effective compression of those columns
            out["bytesSavedEncoded"] = enc_plain - enc_actual
            out["effectiveCompressionRatio"] = round(
                enc_plain / enc_actual, 3)
        if output_rows:
            out["bytesPerOutputRow"] = round(total / output_rows, 3)
        if wall_s and wall_s > 0:
            peaks = link_peaks()
            out["wallMs"] = round(wall_s * 1000, 3)
            out["rooflineFrac"] = round(
                (total / wall_s) / peaks["devicePeakBytesPerS"], 6)
            if peaks.get("h2dBytesPerS"):
                out["linkFrac"] = round(
                    (link / wall_s) / peaks["h2dBytesPerS"], 6)
        return out

    def label_query(self, query_id: int, **labels) -> None:
        """Attach attribution labels (serve/server.py: tenant,
        priorityClass) to a query's ledger; they ride every later
        query_summary / recent_query_summaries row under `labels`, so
        /queries shows WHOSE bytes each query moved."""
        if not self.enabled or not query_id or not labels:
            return
        with self._lock:
            q = self._query(query_id)
            q.labels = {**(q.labels or {}), **labels}

    def query_labels(self, query_id: int) -> dict:
        with self._lock:
            q = self._queries.get(query_id)
            return dict(q.labels) if q is not None and q.labels else {}

    def merge_final(self, query_id: int, patch: dict) -> None:
        """Patch keys into an already-finalized query summary — the
        write path's hook: a save() collects (which finalizes the
        read-side summary) and only THEN commits its output, so the
        `write` block lands by merge instead of racing finalization."""
        if not self.enabled or not query_id or not patch:
            return
        with self._lock:
            q = self._queries.get(query_id)
            if q is not None and q.final:
                q.final.update(patch)

    def finalize_query(self, query_id: int, summary: dict) -> None:
        """Retain a query's end-of-run summary (with wall time and
        roofline fractions) so /metrics and /queries report finished
        queries with their full numbers."""
        if not self.enabled or not query_id or not summary:
            return
        with self._lock:
            self._query(query_id).final = dict(summary)

    def recent_query_summaries(self) -> Dict[int, dict]:
        """Summaries of the retained queries, most recent last (the
        /queries and /metrics per-query payload): the finalized
        end-of-run summary (with roofline fractions) for finished
        queries, the live ledger view for in-flight ones."""
        with self._lock:
            # labels may land AFTER finalization (serve learns the
            # query id from the collect record) — merge at read time
            finals = {qid: ({**q.final, "labels": dict(q.labels)}
                            if q.labels else dict(q.final))
                      for qid, q in self._queries.items()
                      if qid and q.final}
            live = [qid for qid, q in self._queries.items()
                    if qid and not q.final]
        out = {qid: self.query_summary(qid) for qid in live}
        out.update(finals)
        return out

    def registry_view(self) -> dict:
        """Numeric process-level snapshot for the unified registry
        (obs/registry.py flatten -> plain Prometheus gauges)."""
        with self._lock:
            return {
                "hbm": {"reservedBytes": self.hbm_reserved,
                        "peakBytes": self.hbm_peak,
                        "pressureEvents": self.pressure_events,
                        "deviceEpoch": self.device_epoch},
                "bytesMoved": {d: c["bytes"]
                               for d, c in self.totals.items()},
                "transfers": {d: c["count"]
                              for d, c in self.totals.items()},
                "encoded": {"actualBytes": self.enc_actual,
                            "plainBytes": self.enc_plain,
                            "savedBytes": max(
                                0, self.enc_plain - self.enc_actual)},
                "ici": {"bytes": self.totals.get(
                            "ici", _cell())["bytes"],
                        "hostBytesAvoided": self.ici_host_avoided},
                "dcn": {"bytes": self.totals.get(
                            "dcn", _cell())["bytes"]},
            }

    def site_rows(self) -> List[dict]:
        """Per-site process totals for the labeled Prometheus family:
        [{site, direction, bytes, ns, count}]."""
        with self._lock:
            return [{"site": s, "direction": self._site_dir.get(s, ""),
                     **c} for s, c in sorted(self.sites.items())]

    def hbm_timeline(self, last: int = 512) -> List[list]:
        """The most recent (ts, reservedBytes) occupancy samples."""
        with self._lock:
            return [list(x) for x in list(self.timeline)[-last:]]

    def hbm_epoch_marker(self, epoch: int) -> None:
        """Device-loss recovery marker: stamp the HBM occupancy
        timeline with the post-recovery reservation level (the lost
        DEVICE-tier releases have already walked the level down
        through hbm_global) so a reader sees the reset edge and which
        epoch owns the samples after it."""
        if not self.enabled:
            return
        with self._lock:
            self.device_epoch = epoch
            self.timeline.append(
                (round(time.time(), 6), self.hbm_reserved,
                 f"epoch={epoch}"))


ledger = TransferLedger()

# module-level aliases: instrumented sites stay one short call
record = ledger.record
record_encoded = ledger.record_encoded
record_ici = ledger.record_ici
record_dcn = ledger.record_dcn
record_forwarded = ledger.record_forwarded
record_interval = ledger.record_interval
record_stream = ledger.record_stream
record_write = ledger.record_write
merge_final = ledger.merge_final
hbm_global = ledger.hbm_global
hbm_query = ledger.hbm_query
hbm_pressure = ledger.hbm_pressure
hbm_epoch_marker = ledger.hbm_epoch_marker
query_summary = ledger.query_summary


def _tree_bytes(x) -> int:
    """Total byte size of a jax pytree's array leaves (0 for leaves
    without nbytes — python scalars ride along for free)."""
    import jax

    return sum(getattr(leaf, "nbytes", 0)
               for leaf in jax.tree_util.tree_leaves(x))


def ledgered_put(x, site: str, device=None):
    """`jax.device_put` with the crossing ledgered — the wrapper the
    raw-transfer lint rule (tools/lint) steers every H2D site through
    when it is not already inside an instrumented function. Also a
    device-loss classification point (runtime/device_monitor.py): an
    upload into a dead backend fences the engine for warm recovery
    instead of leaking a raw XlaRuntimeError."""
    import time as _time

    import jax

    from spark_rapids_tpu.runtime import device_monitor

    nbytes = _tree_bytes(x)
    t0 = _time.monotonic_ns()
    with device_monitor.guard(f"transfer.h2d:{site}"):
        out = jax.device_put(x) if device is None \
            else jax.device_put(x, device)
    record("h2d", site, nbytes, ns=_time.monotonic_ns() - t0)
    return out


def ledgered_get(x, site: str):
    """`jax.device_get` with the crossing ledgered; covers everything
    from full-column D2H pulls down to the scalar syncs (row counts,
    ANSI flags) that would otherwise leak out of the movement
    accounting. Fatal-classified like ledgered_put — a D2H sync is
    where a wedged device usually first surfaces."""
    import time as _time

    import jax

    from spark_rapids_tpu.runtime import device_monitor

    t0 = _time.monotonic_ns()
    with device_monitor.guard(f"transfer.d2h:{site}"):
        out = jax.device_get(x)
    record("d2h", site, _tree_bytes(out),
           ns=_time.monotonic_ns() - t0)
    return out


# -------------------------------------------- uploads, to completion

def _wait_ready(arrays) -> None:
    import jax

    jax.block_until_ready(arrays)


class _UploadWatcher:
    """One daemon thread that waits for uploaded arrays and then
    closes their `scan.h2d` span and adds the time to the ledger row.
    It only observes: the thread that uploaded and the dispatch that
    consumes the arrays never wait for it."""

    def __init__(self):
        self._cv = threading.Condition()
        self._items: deque = deque()
        self._pending = 0
        self._thread: Optional[threading.Thread] = None

    def watch(self, arrays, site: str, nbytes: int, start_ns: int,
              parent: "_events.SpanRef") -> None:
        with self._cv:
            self._items.append((arrays, site, nbytes, start_ns, parent))
            self._pending += 1
            if self._thread is None or not self._thread.is_alive():
                if self._thread is None:
                    # a daemon thread that interpreter shutdown catches
                    # inside block_until_ready (C++ frames) aborts the
                    # process: let the last uploads close first
                    atexit.register(self.drain, 5.0)
                self._thread = threading.Thread(
                    target=self._run, name="srtpu-upload-watcher",
                    daemon=True)
                self._thread.start()
            self._cv.notify_all()

    def _run(self) -> None:
        from jax.profiler import TraceAnnotation

        while True:
            with self._cv:
                while not self._items:
                    self._cv.wait()
                arrays, site, nbytes, start_ns, parent = \
                    self._items.popleft()
            fields = {"site": site, "bytes": nbytes}
            try:
                with TraceAnnotation("srtpu:scan.h2d"):
                    _wait_ready(arrays)
            except Exception as e:  # a lost device: the query's to raise
                fields.update(status="error",
                              error=f"{type(e).__name__}: {e}"[:200])
            end_ns = time.time_ns()
            del arrays
            ledger.add_ns("h2d", site, end_ns - start_ns, parent.query_id)
            _events.record_span("scan.h2d", start_ns, end_ns,
                                parent=parent, **fields)
            with self._cv:
                self._pending -= 1
                self._cv.notify_all()

    def drain(self, timeout: float = 30.0) -> bool:
        """Wait until every watched upload has been closed (tests, and
        whoever reads spans right after a query)."""
        with self._cv:
            return self._cv.wait_for(lambda: self._pending == 0, timeout)


_upload_watcher = _UploadWatcher()
drain_uploads = _upload_watcher.drain


def put_watched(x, site: str, nbytes: int,
                parent: Optional["_events.SpanRef"] = None):
    """`jax.device_put` of a scan's upload, timed to its COMPLETION
    off the caller's path. The bytes are ledgered now, under the query
    `parent` names (a reader-pool thread has no query scope of its
    own); a `scan.h2d` span from this call to the arrays' readiness,
    and the same nanoseconds on the ledger row, follow from the
    watcher thread. `parent`: `events.current_span()` of the thread
    that owns the query, this thread's by default."""
    import jax

    if parent is None:
        parent = _events.current_span()
    start_ns = time.time_ns()
    out = jax.device_put(x)
    record("h2d", site, nbytes, query_id=parent.query_id)
    if ledger.enabled or _events.armed():
        _upload_watcher.watch(out, site, nbytes, start_ns, parent)
    return out


def configure(conf=None) -> None:
    """Session-lifecycle hook: honor spark.rapids.tpu.telemetry.enabled
    (counters persist across sessions like every process ledger)."""
    from spark_rapids_tpu.config import rapids_conf as rc

    if conf is not None:
        ledger.enabled = bool(conf.get(rc.TELEMETRY_ENABLED))


# ------------------------------------------------------- roofline peaks

_peaks: Optional[dict] = None
_peaks_lock = threading.Lock()
_PEAKS_FILE = "telemetry_peaks.json"


def device_peak_bw(kind: str) -> float:
    """Spec-table HBM peak for a `device_kind`; KeyError for a kind the
    table does not list (a CPU's peak under a chip's name is the wrong
    answer, not a safe one)."""
    for k, v in DEVICE_PEAK_BW.items():
        if k.lower() in str(kind).lower():
            return v
    raise KeyError(
        f"device kind {kind!r} is not in obs.telemetry.DEVICE_PEAK_BW "
        f"({sorted(DEVICE_PEAK_BW)}): add its published peak HBM "
        f"bandwidth there")


def require_tpu(who: str):
    """The device `who` measures, or SystemExit: a measurement path
    that finds no accelerator fails, it does not fall back to the CPU
    (the chip is touched by the calling process only — no probing
    child). Nothing goes to stdout: off the chip there is no result."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"{who} measures a TPU; JAX found platform "
            f"{dev.platform!r} ({dev.device_kind}). Tests and "
            f"rehearsals run on the CPU (README 'Running'); a number "
            f"comes from a chip run only.")
    return dev


def _peaks_path() -> Optional[str]:
    from spark_rapids_tpu.runtime import compile_cache

    root = compile_cache.cache_dir()
    if root is None:
        return None
    return os.path.join(root, _PEAKS_FILE)


def _device_kind() -> str:
    import jax

    dev = jax.devices()[0]
    return str(getattr(dev, "device_kind", dev.platform))


def _probe_link() -> dict:
    """Measure the host<->device link: device_put (H2D) and device_get
    (D2H) of a fixed buffer and the round trip of one small dispatch +
    fetch — each the median of a few timed repeats AFTER one untimed
    pass (the first transfer and the first dispatch of a process pay
    one-time set-up and the slice's compile) — plus the device HBM
    peak from the spec table."""
    import statistics

    import jax
    import numpy as np

    kind = _device_kind()
    peak = device_peak_bw(kind)
    buf = np.zeros(_PROBE_BYTES // 8, dtype=np.float64)

    def timed(fn):
        t0 = time.perf_counter()
        out = fn()
        return out, max(time.perf_counter() - t0, 1e-9)

    def put():
        return jax.block_until_ready(jax.device_put(buf))

    on_dev = put()
    jax.device_get(on_dev)
    jax.device_get(on_dev[:8])
    h2d, d2h, trips = [], [], []
    for _ in range(_PROBE_REPEATS):
        on_dev, dt = timed(put)
        h2d.append(dt)
        d2h.append(timed(lambda: jax.device_get(on_dev))[1])
        trips.append(timed(lambda: jax.device_get(on_dev[:8]))[1])
    return {
        "deviceKind": kind,
        "devicePeakBytesPerS": peak,
        "h2dBytesPerS": round(buf.nbytes / statistics.median(h2d), 1),
        "d2hBytesPerS": round(buf.nbytes / statistics.median(d2h), 1),
        "roundTripMs": round(statistics.median(trips) * 1000, 4),
        "probeBytes": buf.nbytes,
        "probeRepeats": _PROBE_REPEATS,
    }


def link_peaks(refresh: bool = False) -> dict:
    """Measured link + device peaks, probed once and cached — first in
    process memory, then (when the compile cache is configured) as JSON
    beside its index so restarted processes skip the probe. A cached
    file counts only for the device kind it was measured on: CPU
    rehearsals and chip runs may share one cache directory."""
    global _peaks
    with _peaks_lock:
        if _peaks is not None and not refresh:
            return _peaks
        path = _peaks_path()
        if path is not None and not refresh:
            try:
                with open(path) as f:
                    loaded = json.load(f)
                if (isinstance(loaded, dict)
                        and loaded.get("deviceKind") == _device_kind()
                        and "roundTripMs" in loaded):
                    _peaks = loaded
                    return _peaks
            except (OSError, ValueError):
                pass
        _peaks = _probe_link()
        if path is not None:
            try:
                tmp = path + ".tmp"
                with open(tmp, "w") as f:
                    json.dump(_peaks, f)
                os.replace(tmp, path)
            except OSError:
                pass
        return _peaks
