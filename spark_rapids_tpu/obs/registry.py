"""Unified metric registry views.

Every failure-domain and performance counter in the engine lives in its
owning module (faults, backoff, shuffle manager, stage scheduler,
degradation ladder, compile ledger, semaphore, spill catalog); this
module is the ONE place that assembles them. `session.robustness_metrics`
and bench.py's robustness block are views over `robustness_snapshot()`
(their keys are a stable contract — test_chaos.py/test_scheduler.py pin
them), and the Prometheus dump (obs/prom.py) flattens
`unified_snapshot()`.
"""

from __future__ import annotations

from typing import Dict, Optional


def robustness_snapshot() -> dict:
    """One snapshot of every failure-domain counter (PR 2/3): chaos
    injections per site, backoff retries per domain, shuffle
    fetch/checksum recoveries + orphaned/discarded blocks,
    stage-scheduler recoveries, degradation-ladder demotions +
    circuit-breaker state, and semaphore timeouts. Key layout is pinned
    by existing tests."""
    from spark_rapids_tpu.runtime import admission as _adm
    from spark_rapids_tpu.runtime import backoff, degrade, faults
    from spark_rapids_tpu.runtime import device_monitor as _dm
    from spark_rapids_tpu.runtime import memory as _mem
    from spark_rapids_tpu.runtime import sanitizer as _san
    from spark_rapids_tpu.runtime import scheduler as _sched
    from spark_rapids_tpu.runtime import semaphore as sem
    from spark_rapids_tpu.shuffle.manager import get_shuffle_manager

    mgr = get_shuffle_manager()
    cat = _mem._catalog
    return {
        "chaos": faults.counters(),
        "retries": backoff.counters(),
        "shuffle": {"fetchRetries": mgr.fetch_retries,
                    "checksumFailures": mgr.checksum_failures,
                    "orphanedFiles": mgr.orphaned_files,
                    "speculativeDiscards": mgr.speculative_discards},
        "scheduler": _sched.stats.snapshot(),
        "degrade": degrade.counters(),
        "admission": _adm.stats.snapshot(),
        "sanitizer": _san.counters(),
        "device": _dm.counters(),
        "spill": {
            "orphanedFilesSwept":
                0 if cat is None
                else cat.metrics.get("orphaned_files_swept", 0),
            "deviceLostBuffers":
                0 if cat is None
                else cat.metrics.get("device_lost_buffers", 0)},
        "semaphoreTimeouts": sem.get().timeouts,
    }


def unified_snapshot(session=None) -> dict:
    """The full observability surface as one nested dict: robustness
    counters, the compile ledger, spill-catalog + shuffle byte
    ledgers, per-session query metrics, and bus event counts."""
    from spark_rapids_tpu.obs import events as _events
    from spark_rapids_tpu.obs import telemetry as _telemetry
    from spark_rapids_tpu.runtime.compile_cache import stats
    from spark_rapids_tpu.shuffle.manager import get_shuffle_manager

    mgr = get_shuffle_manager()
    out = {
        "robustness": robustness_snapshot(),
        "compile": stats.snapshot(),
        "shuffle": {"bytesWritten": mgr.bytes_written,
                    "bytesInMemory": mgr.bytes_in_memory,
                    "blocksSpilled": mgr.blocks_spilled},
        "telemetry": _telemetry.ledger.registry_view(),
    }
    try:
        from spark_rapids_tpu.runtime.memory import _catalog

        if _catalog is not None:
            out["memory"] = dict(_catalog.metrics)
    except Exception:
        pass
    try:
        import sys

        srv = sys.modules.get("spark_rapids_tpu.serve.server")
        daemon = srv.active_daemon() if srv is not None else None
        if daemon is not None:
            st = daemon.status()
            out["serve"] = {
                "connections": len(st["connections"]),
                "inFlight": st["inFlight"],
                "queriesServed": st["queriesServed"],
                "planCache": st["planCache"],
                "tenants": st["tenants"],
            }
            if st.get("dedupe"):
                out["serve"]["dedupe"] = st["dedupe"]
    except Exception:
        pass
    try:
        import sys

        # fleet block: router + supervisor counters fold in when this
        # process hosts them (same sys.modules pattern as serve — no
        # import cost when the fleet layer never loaded), flattening
        # into the srtpu_fleet_* prom families
        fleet = {}
        rtr_mod = sys.modules.get("spark_rapids_tpu.serve.router")
        rtr = rtr_mod.active_router() if rtr_mod is not None else None
        if rtr is not None:
            fleet["router"] = rtr.stats_snapshot()
        sup_mod = sys.modules.get(
            "spark_rapids_tpu.serve.supervisor")
        sup = sup_mod.active_supervisor() if sup_mod is not None \
            else None
        if sup is not None:
            fleet["supervisor"] = sup.stats_snapshot()
        if fleet:
            out["fleet"] = fleet
    except Exception:
        pass
    bus = _events.get()
    if session is not None and getattr(session, "obs", None) is not None:
        bus = session.obs.bus or bus
    if bus is not None:
        out["events"] = dict(bus.counts)
    if session is not None:
        out["query"] = session.query_metrics.snapshot()
    return out


def flatten(d: dict, prefix: str = "",
            out: Optional[Dict[str, float]] = None) -> Dict[str, float]:
    """Nested counter dict -> flat {dotted.name: number}; non-numeric
    leaves drop."""
    if out is None:
        out = {}
    for k, v in d.items():
        name = f"{prefix}.{k}" if prefix else str(k)
        if isinstance(v, dict):
            flatten(v, name, out)
        elif isinstance(v, bool):
            out[name] = 1.0 if v else 0.0
        elif isinstance(v, (int, float)):
            out[name] = v
    return out
