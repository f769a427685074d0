"""Tracing/profiling — the NVTX-range integration analog (reference
NvtxWithMetrics.scala:21-34 threads named ranges + metrics through every
operator; docs/dev/nvtx_profiling.md workflow).

On TPU the equivalents are jax.profiler traces (viewable in
TensorBoard/Perfetto) and TraceAnnotation named ranges. The session
exposes start/stop; operators annotate their partition execution so
device work attributes to plan nodes in the timeline."""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

_active = False
_lock = threading.Lock()


def start_trace(log_dir: str) -> None:
    """Begin a profiler session (jax.profiler.start_trace); view with
    TensorBoard or Perfetto."""
    global _active
    import jax

    with _lock:
        if not _active:
            jax.profiler.start_trace(log_dir)
            _active = True


def stop_trace() -> None:
    global _active
    import jax

    with _lock:
        if _active:
            jax.profiler.stop_trace()
            _active = False


def is_active() -> bool:
    return _active


@contextlib.contextmanager
def annotate(name: str):
    """Named range around operator work (NvtxWithMetrics role). Cheap
    enough to leave on unconditionally — annotations no-op outside a
    profiler session."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


@contextlib.contextmanager
def annotate_with_metric(name: str, metric, span: Optional[dict] = None):
    """Named range COUPLED with a nanosecond metric — the exact
    NvtxWithMetrics contract (one scope, both the timeline range and
    the operator metric accumulate) — and, when the obs bus is armed,
    a span in the query's tree: a caller of `obs.events.span`, the one
    span mechanism. `span` supplies extra span fields (operator name
    override, device flag, rows); the thread's scheduler task scope is
    inherited by the event automatically."""
    import time as _time

    from spark_rapids_tpu.obs import events as _events

    t0 = _time.monotonic_ns()
    try:
        with _events.span(name, metric=metric.name, **(span or {})):
            yield
    finally:
        metric.add(_time.monotonic_ns() - t0)


def save_device_memory_profile(path: str) -> Optional[str]:
    """Write a pprof-format device memory profile (the OOM-dump role,
    reference RapidsConf.scala:403-414 gpuOomDumpDir + heap dumps).
    Returns the path, or None when the backend has no profile."""
    import jax

    try:
        jax.profiler.save_device_memory_profile(path)
        return path
    except Exception:
        return None


def dump_oom_state(dump_dir: str, reason: str,
                   catalog=None) -> Optional[str]:
    """On an unrecoverable device OOM: device memory profile + a JSON
    snapshot of the RAISING spill catalog (per-tier buffer
    sizes/priorities) so the failure is diagnosable after the fact."""
    import json
    import os
    import time

    try:
        os.makedirs(dump_dir, exist_ok=True)
        import uuid

        stamp = time.strftime("%Y%m%d-%H%M%S")
        # uuid keeps same-second dumps (split storms, threads) distinct
        base = os.path.join(dump_dir,
                            f"oom-{stamp}-{uuid.uuid4().hex[:8]}")
        if catalog is None:
            from spark_rapids_tpu.runtime.memory import get_catalog

            catalog = get_catalog()
        cat = catalog
        with cat._lock:
            bufs = [{"tier": b.tier.name, "bytes": b.size_bytes,
                     "priority": b._priority}
                    for b in cat._buffers.values()]
        state = {
            "reason": reason,
            "device_limit": cat.pool.limit,
            "device_reserved": cat.pool.reserved,
            "host_used": cat.host_used,
            "buffers": bufs,
            "metrics": dict(cat.metrics),
        }
        with open(base + ".json", "w") as f:
            json.dump(state, f, indent=2)
        save_device_memory_profile(base + ".prof")
        return base + ".json"
    except Exception:
        return None
