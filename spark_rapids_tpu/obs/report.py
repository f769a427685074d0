"""Qualification & profiling reports — the spark-rapids-tools analog.

The reference ships standalone qualification/profiling tools that read
Spark event logs and answer two questions: WHAT stayed on CPU (and
would the plugin help), and WHERE did the time go. Same surface here,
over the obs event stream: both reports run against a LIVE session
(its in-memory event history) or a SAVED event log path — the offline
workflow a fleet operator uses for regression triage.

- `qualification(source)`: every operator the planner kept on CPU,
  with the exact fallback reason the NOT_ON_TPU explain prints and an
  estimated share of query wall time attributed to it from the span
  tree.
- `profile(source)`: top-N operators by device time, shuffle/spill
  byte totals per tier, compile cache ratios, and
  retry/speculation/degradation/chaos counts.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Union

from spark_rapids_tpu.obs import spans as _spans

Source = Union[str, list, object]


def _events_from(source: Source) -> List[dict]:
    if isinstance(source, str):
        from spark_rapids_tpu.obs import eventlog

        return eventlog.load(source)
    if isinstance(source, list):
        return source
    obs = getattr(source, "obs", None)
    if obs is not None and obs.history is not None:
        return obs.history.events()
    raise TypeError(
        "report source must be an event-log path, a list of events, or "
        "a session with observability enabled "
        "(spark.rapids.tpu.obs.enabled)")


def _last_query(events: List[dict]) -> List[dict]:
    qids = [e["queryId"] for e in events if e.get("queryId")]
    if not qids:
        return []
    last = qids[-1]
    return [e for e in events if e.get("queryId") == last]


def _tree_for(events: List[dict]) -> Optional[_spans.Span]:
    trees = _spans.build_from_events(events)
    return trees[-1] if trees else None


def _fallback_share(node: str, totals: Dict[str, dict],
                    total_wall: int) -> Optional[float]:
    """Wall-time share of the CPU exec(s) implementing a logical node:
    placement events carry LOGICAL names (Filter), spans carry physical
    exec names (CpuFilterExec) — match on the embedded logical name."""
    if total_wall <= 0:
        return None
    wall = sum(t["wallNs"] for name, t in totals.items()
               if name.startswith("Cpu") and node in name)
    if wall == 0:
        return None
    return wall / total_wall


# ---------------------------------------------------------- qualification

def qualification_data(source: Source) -> List[dict]:
    """Rows for every planner CPU fallback of the (last) query:
    [{node, depth, reason, timeShare}]. `reason` is verbatim the
    '; '-joined string explain_potential_tpu_plan(mode='NOT_ON_TPU')
    prints for that node."""
    events = _last_query(_events_from(source))
    tree = _tree_for(events)
    totals = _spans.operator_totals(tree)
    total_wall = sum(t["wallNs"] for t in totals.values())
    rows = []
    for ev in events:
        if ev["event"] != "plan.placement" or ev.get("onDevice"):
            continue
        rows.append({
            "node": ev["node"],
            "depth": ev.get("depth", 0),
            "reason": ev.get("reason") or "",
            "timeShare": _fallback_share(ev["node"], totals, total_wall),
        })
    return rows


def qualification(source: Source) -> str:
    """Human-readable qualification report (CPU-fallback inventory)."""
    rows = qualification_data(source)
    if not rows:
        return ("== TPU qualification ==\n"
                "(every planned operator runs on device)")
    lines = ["== TPU qualification ==",
             f"{len(rows)} operator(s) kept on CPU:"]
    for r in rows:
        share = ("  ~" + f"{100.0 * r['timeShare']:.1f}% of query time"
                 if r["timeShare"] is not None else "")
        lines.append(f"  {'  ' * r['depth']}{r['node']}: "
                     f"{r['reason']}{share}")
    return "\n".join(lines)


# ---------------------------------------------------------------- profile

def profile_data(source: Source, top_n: int = 10) -> dict:
    """Structured profile of the (last) query in `source`. Sanitizer
    verdicts are the exception to last-query scoping: a wait-for cycle
    spans queries by construction (and the retried victim finishes
    LAST), so the audit section aggregates over the whole source."""
    all_events = _events_from(source)
    events = _last_query(all_events)
    tree = _tree_for(events)
    totals = _spans.operator_totals(tree)
    top = sorted(totals.items(), key=lambda kv: -kv[1]["deviceNs"])
    counts: Dict[str, int] = {}
    shuffle = {"bytesWritten": 0, "bytesFetched": 0, "writes": 0,
               "fetches": 0, "retries": 0}
    spill = {"toHostBytes": 0, "toDiskBytes": 0, "unspillBytes": 0}
    compile_c = {"miss": 0, "hit": 0}
    recovery = {"attempts": 0, "retried": 0, "speculated": 0,
                "discarded": 0, "lost": 0, "failed": 0,
                "degradations": 0, "chaosInjections": 0}
    movement: Dict[str, Dict[str, int]] = {}
    sanitizer = {"deadlocks": 0, "inversions": 0, "victims": 0,
                 "lastCycle": None}
    telemetry_summary = None
    for ev in events:
        et = ev["event"]
        counts[et] = counts.get(et, 0) + 1
        if et == "shuffle.write":
            shuffle["writes"] += 1
            shuffle["bytesWritten"] += ev.get("bytes") or 0
        elif et == "shuffle.fetch":
            shuffle["fetches"] += 1
            shuffle["bytesFetched"] += ev.get("bytes") or 0
        elif et == "shuffle.retry":
            shuffle["retries"] += 1
        elif et == "spill":
            b = ev.get("bytes") or 0
            if ev.get("direction") == "up":
                spill["unspillBytes"] += b
            elif ev.get("toTier") == "HOST":
                spill["toHostBytes"] += b
            else:
                spill["toDiskBytes"] += b
        elif et == "compile":
            kind = ev.get("kind", "miss")
            compile_c[kind] = compile_c.get(kind, 0) + 1
        elif et == "task.attempt.start":
            recovery["attempts"] += 1
            if ev.get("speculative"):
                recovery["speculated"] += 1
        elif et == "task.attempt.end":
            status = ev.get("status")
            if status in ("discarded", "lost", "failed"):
                recovery[status] = recovery.get(status, 0) + 1
            if status == "lost":
                recovery["retried"] += 1
        elif et == "degrade":
            recovery["degradations"] += 1
        elif et == "chaos":
            recovery["chaosInjections"] += 1
        elif et == "transfer":
            d = movement.setdefault(str(ev.get("direction")),
                                    {"bytes": 0, "count": 0})
            d["bytes"] += ev.get("bytes") or 0
            d["count"] += 1
        elif et == "telemetry.summary":
            # end-of-query roofline record (the last one wins: nested
            # collects never emit it, so there is exactly one per query)
            telemetry_summary = {
                k: ev.get(k) for k in
                ("bytesMoved", "bytesMovedTotal", "hbmPeakBytes",
                 "rooflineFrac", "linkFrac", "bytesPerOutputRow",
                 "wallMs") if ev.get(k) is not None}
    for ev in all_events:
        et = ev["event"]
        if et == "sanitizer.deadlock":
            sanitizer["deadlocks"] += 1
            if ev.get("victim") is not None:
                sanitizer["victims"] += 1
            sanitizer["lastCycle"] = ev.get("cycle")
        elif et == "sanitizer.inversion":
            sanitizer["inversions"] += 1
    served = compile_c["hit"]
    requests = served + compile_c["miss"]
    return {
        "queryId": events[-1]["queryId"] if events else None,
        "eventCounts": counts,
        "spanTreeDepth": _spans.tree_depth(tree),
        "topOperators": [
            {"operator": name, **t} for name, t in top[:top_n]],
        "outputRows": _spans.task_rows(tree),
        "shuffle": shuffle,
        "spill": spill,
        "compile": {**compile_c,
                    "cacheServedRatio": (served / requests
                                         if requests else None)},
        "recovery": recovery,
        "sanitizer": sanitizer,
        "dataMovement": movement,
        "telemetry": telemetry_summary,
    }


def profile(source: Source, top_n: int = 10) -> str:
    """Human-readable profile report."""
    d = profile_data(source, top_n)
    lines = ["== TPU profile ==",
             f"query {d['queryId']}; span tree depth "
             f"{d['spanTreeDepth']}; output rows {d['outputRows']}"]
    lines.append(f"top operators by device time (of "
                 f"{len(d['topOperators'])} shown):")
    for t in d["topOperators"]:
        lines.append(
            f"  {t['operator']}: device {t['deviceNs'] / 1e6:.2f} ms, "
            f"wall {t['wallNs'] / 1e6:.2f} ms, calls {t['count']}"
            + (f", rows {t['rows']}" if t["rows"] else "")
            + (f", discarded {t['discardedNs'] / 1e6:.2f} ms"
               if t["discardedNs"] else ""))
    sh, sp = d["shuffle"], d["spill"]
    lines.append(f"shuffle: {sh['bytesWritten']} B written over "
                 f"{sh['writes']} block(s), {sh['bytesFetched']} B "
                 f"fetched, {sh['retries']} retrie(s)")
    lines.append(f"spill: {sp['toHostBytes']} B to host, "
                 f"{sp['toDiskBytes']} B to disk, "
                 f"{sp['unspillBytes']} B unspilled")
    c = d["compile"]
    ratio = ("n/a" if c["cacheServedRatio"] is None
             else f"{100.0 * c['cacheServedRatio']:.0f}%")
    lines.append(f"compile: {c['miss']} compiled, {c['hit']} cache "
                 f"hit(s), cache-served {ratio}")
    r = d["recovery"]
    lines.append(f"recovery: {r['attempts']} attempt(s), "
                 f"{r['retried']} retried, {r['speculated']} "
                 f"speculated, {r['discarded']} discarded, "
                 f"{r['degradations']} degradation(s), "
                 f"{r['chaosInjections']} chaos injection(s)")
    sz = d["sanitizer"]
    if sz["deadlocks"] or sz["inversions"]:
        lines.append(
            f"sanitizer: {sz['deadlocks']} deadlock cycle(s) "
            f"detected, {sz['victims']} victim(s) unwound, "
            f"{sz['inversions']} order inversion(s)")
        if sz["lastCycle"]:
            rows = "; ".join(
                f"query {r['queryId']} waits on {r['waitsOn']}"
                for r in sz["lastCycle"])
            lines.append(f"  last cycle: {rows}")
    if d["dataMovement"]:
        parts = [f"{dd} {v['bytes']} B/{v['count']} transfer(s)"
                 for dd, v in sorted(d["dataMovement"].items())]
        lines.append("data movement: " + ", ".join(parts))
    tel = d.get("telemetry")
    if tel:
        rf = tel.get("rooflineFrac")
        bpr = tel.get("bytesPerOutputRow")
        lines.append(
            f"roofline: {tel.get('bytesMovedTotal', 0)} B moved, "
            f"hbm peak {tel.get('hbmPeakBytes', 0)} B"
            + (f", roofline_frac {rf}" if rf is not None else "")
            + (f", {bpr} B/output row" if bpr is not None else ""))
    return "\n".join(lines)
