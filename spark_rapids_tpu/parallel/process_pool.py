"""Cross-process worker pool — the multi-executor backend of the stage
scheduler (runtime/scheduler.py).

The SPMD mesh engine (parallel/plan_compiler.py) is all-or-nothing: a
dead process deadlocks the collectives. This pool is the complementary
task-parallel transport, shaped like the reference's executor fleet
(one OS process per executor, driver-side liveness via the heartbeat
plane): the driver hands each worker picklable task attempts — a
LINEAGE DESCRIPTOR of (importable fragment function, input split +
plan-fragment args) — and a `kill -9`'d worker is a NORMAL event:

- liveness: each worker registers with the driver's HeartbeatServer
  (parallel/heartbeat.py) and beats on a daemon thread; the pool's
  `check_lost` merges heartbeat expiry (`dead_peers`) with the OS-level
  process sentinel, so a SIGKILL is noticed within one beat interval.
- eviction: a lost worker is excluded for the session
  (`evicted_workers`); its in-flight partitions are re-dispatched to
  surviving workers by the scheduler (recomputedPartitions).
- results travel a shared queue; per-worker task queues make
  reassignment race-free (a dead worker's queued tasks are simply
  re-sent elsewhere — tasks are deterministic and commit-once).

`run_scan_agg_fragment` is the built-in executable form of a scan →
filter → grouped-partial-aggregation lineage fragment (pyarrow
semantics, matching the CPU oracle) used by the multiprocess recovery
tests and as the reference shape for custom fragments.
"""

from __future__ import annotations

import multiprocessing as mp
import pickle
import queue as _queue
import threading
import time
import traceback
from typing import Any, Dict, List, Optional


def _import_callable(path: str):
    """'package.module:function' -> callable."""
    import importlib

    mod, _, fn = path.partition(":")
    if not fn:
        raise ValueError(f"fragment path {path!r} is not module:function")
    return getattr(importlib.import_module(mod), fn)


def run_scan_agg_fragment(spec: dict):
    """Execute one scan->filter->partial-agg lineage fragment.

    spec = {
      "files":   [parquet paths]          # this task's input split
      "filter":  (col, pc_fn_name, value) # optional, e.g. ("v","greater",0.2)
      "derive_mod": (name, src, modulus)  # optional derived group key
      "keys":    [group column names]
      "aggs":    [(col, "sum"|"count"|...)]
      "sleep_s": float                    # optional straggler/testing stall
    }
    Returns the PARTIAL pyarrow aggregate for the split; the driver
    merges partials. Pure + deterministic per spec — safe to re-run on
    any worker at any time.
    """
    import numpy as np
    import pyarrow as pa
    import pyarrow.compute as pc
    import pyarrow.parquet as pq

    from spark_rapids_tpu.obs import events as obs_events
    from spark_rapids_tpu.obs import telemetry

    if spec.get("sleep_s"):
        # forked worker: no CancelToken exists in this process — the
        # driver-side scheduler handles stragglers via speculation
        time.sleep(float(spec["sleep_s"]))  # srtpu-lint: disable=raw-sleep
    t0 = time.time_ns()
    t = pa.concat_tables([pq.read_table(p) for p in spec["files"]])
    f = spec.get("filter")
    if f is not None:
        col, op, val = f
        t = t.filter(getattr(pc, op)(t.column(col), val))
    d = spec.get("derive_mod")
    if d is not None:
        name, src, modulus = d
        g = np.asarray(t.column(src)) % int(modulus)
        t = t.append_column(name, pa.array(g, type=pa.int64()))
    out = t.group_by(list(spec["keys"])).aggregate(
        [tuple(a) for a in spec["aggs"]])
    # observability parity with in-process attempts: one operator span
    # for the fragment + the partial-result bytes that will cross the
    # process boundary back to the driver. Both land on the WORKER's
    # local bus and are forwarded with the task result (ProcessBackend
    # re-emits them under the driver's query/task identity).
    telemetry.record("shuffle", "worker.result", out.nbytes)
    obs_events.record_span("ScanAggFragment", t0, time.time_ns(),
                           metric="fragmentTime", rows=out.num_rows)
    return out


#: Envelope + task-identity keys stripped from forwarded events: the
#: driver re-emits through its own bus, which reassigns all of them
#: under the driver's query scope and the attempt's task identity.
#: Span ids count per process, so a worker's would collide with the
#: driver's: its spans hang by their task identity instead.
_FWD_STRIP = ("seq", "ts", "schemaVersion", "queryId", "stage", "task",
              "attempt", "speculative", "worker", "spanId", "parentId")


def _worker_main(worker_id: str, task_q, result_q, hb_addr,
                 hb_interval_ms: int,
                 host_id: Optional[str] = None) -> None:
    """Worker process loop: register with the heartbeat plane, then
    drain the private task queue until the None sentinel. A task is
    (stage, task_index, attempt, fragment_path, args); results are
    pickled so arbitrary fragment outputs travel the shared queue.

    Observability: the worker installs its OWN event bus — critically
    replacing any bus inherited across fork(), whose subscribers (span
    builder, event-log file handle) belong to the DRIVER and must never
    see worker writes — and collects everything a task emits
    (operator spans, transfer records). The collected payloads ride the
    result tuple back; ProcessBackend re-emits them on the driver bus
    under the proper task scope, so a ProcessBackend run produces the
    same span trees and transfer ledger as an in-process run."""
    from spark_rapids_tpu.obs import events as obs_events

    obs_events.install(None)  # drop the fork-inherited driver bus
    collected: List[dict] = []
    wbus = obs_events.EventBus()
    wbus.subscribe(collected.append)
    obs_events.install(wbus)
    client = None
    if hb_addr is not None:
        from spark_rapids_tpu.parallel.heartbeat import HeartbeatClient

        try:
            client = HeartbeatClient(tuple(hb_addr), worker_id,
                                     "127.0.0.1", 0,
                                     interval_ms=hb_interval_ms,
                                     host_id=host_id)
        except OSError:
            pass  # driver plane gone; the sentinel still covers us
    result_q.put(("ready", worker_id, None, None, None))

    def drain_events() -> List[dict]:
        evs = [{k: v for k, v in e.items() if k not in _FWD_STRIP}
               for e in collected]
        collected.clear()
        return evs

    while True:
        item = task_q.get()
        if item is None:
            break
        stage, idx, attempt, fn_path, args = item
        try:
            fn = _import_callable(fn_path)
            out = pickle.dumps(fn(args))
            result_q.put(("ok", worker_id, stage, idx, attempt, out,
                          drain_events()))
        except BaseException:
            result_q.put(("err", worker_id, stage, idx, attempt,
                          traceback.format_exc(), drain_events()))
    if client is not None:
        client.close()


class _WorkerHandle:
    __slots__ = ("proc", "task_q")

    def __init__(self, proc, task_q):
        self.proc = proc
        self.task_q = task_q


class ProcessWorkerPool:
    """N worker processes + driver-side heartbeat plane + shared result
    queue. Survives kill -9 of individual workers; all-workers-dead
    surfaces as a clean WorkerLost from the scheduler."""

    def __init__(self, num_workers: int = 2,
                 start_method: Optional[str] = None,
                 heartbeat: bool = True,
                 hb_interval_ms: int = 100,
                 hb_timeout_ms: int = 1500,
                 hosts: int = 0):
        from spark_rapids_tpu.parallel.heartbeat import HeartbeatServer

        methods = mp.get_all_start_methods()
        # fork keeps worker startup instant (no re-import of the
        # engine); workers only run pyarrow fragments, never the jax
        # backend, so forking under an initialized backend is safe
        method = start_method or (
            "fork" if "fork" in methods else "spawn")
        ctx = mp.get_context(method)
        self._result_q = ctx.Queue()
        self._hb_server = HeartbeatServer(timeout_ms=hb_timeout_ms) \
            if heartbeat else None
        self._hb_dead: set = set()
        self._lock = threading.Lock()
        if self._hb_server is not None:
            self._hb_server.manager.on_death(self._on_hb_death)
        self._workers: Dict[str, _WorkerHandle] = {}
        self._excluded: set = set()
        # host failure domains: hosts > 1 partitions the workers into
        # contiguous host groups and registers each with its host_id —
        # one SIGKILL'd member then evicts the WHOLE group atomically
        # through the heartbeat plane's host grouping. hosts <= 1
        # keeps the classic independent per-worker timeouts.
        nw = max(1, num_workers)
        self._host_of: Dict[str, Optional[str]] = {}
        hb_addr = (list(self._hb_server.address)
                   if self._hb_server is not None else None)
        for i in range(nw):
            wid = f"worker-{i}"
            host_id = (f"host{i * int(hosts) // nw}"
                       if hosts and int(hosts) > 1 else None)
            self._host_of[wid] = host_id
            task_q = ctx.Queue()
            proc = ctx.Process(
                target=_worker_main,
                args=(wid, task_q, self._result_q, hb_addr,
                      hb_interval_ms, host_id),
                name=f"srtpu-{wid}", daemon=True)
            proc.start()
            self._workers[wid] = _WorkerHandle(proc, task_q)

    def _on_hb_death(self, executor_id: str) -> None:
        with self._lock:
            if executor_id in self._workers:
                self._hb_dead.add(executor_id)

    def on_host_death(self, cb) -> None:
        """Hook the heartbeat plane's atomic host-group eviction feed
        (fired with the host_id) — the device monitor's fence_host
        glue for pool deployments."""
        if self._hb_server is not None:
            self._hb_server.manager.on_host_death(cb)

    def worker_host(self, worker_id: str) -> Optional[str]:
        return self._host_of.get(worker_id)

    def host_workers(self, host_id: str) -> List[str]:
        return sorted(w for w, h in self._host_of.items()
                      if h == host_id)

    # --- scheduler-facing surface ---

    def live_workers(self) -> List[str]:
        with self._lock:
            return [w for w in self._workers if w not in self._excluded]

    def evicted_workers(self) -> List[str]:
        with self._lock:
            return sorted(self._excluded)

    def worker_pid(self, worker_id: str) -> int:
        return self._workers[worker_id].proc.pid

    def submit(self, worker_id: str, item: tuple) -> None:
        self._workers[worker_id].task_q.put(item)

    def poll(self, timeout: float):
        try:
            return self._result_q.get(timeout=timeout)
        except _queue.Empty:
            return None

    def check_lost(self) -> List[str]:
        """Workers newly observed dead: heartbeat expiry (dead_peers
        triggers the prune) OR the OS process sentinel. Either signal
        condemns the worker's WHOLE host group when host failure
        domains are on — the sentinel usually wins the race against
        the heartbeat timeout, and it must not evict members one at a
        time while the rest of the half-dead host keeps tasks."""
        if self._hb_server is not None:
            self._hb_server.manager.dead_peers()  # prunes + fires cbs
        lost = []
        with self._lock:
            for wid, h in self._workers.items():
                if wid in self._excluded:
                    continue
                if not h.proc.is_alive() or wid in self._hb_dead:
                    lost.append(wid)
        hosts = {self._host_of.get(w) for w in lost} - {None}
        if hosts:
            if self._hb_server is not None:
                for hid in sorted(hosts):
                    # fires on_death (-> _hb_dead) + on_host_death
                    # (-> the device monitor's fence_host glue)
                    self._hb_server.manager.condemn_host(hid)
            with self._lock:
                for wid in self._workers:
                    if (wid not in self._excluded and wid not in lost
                            and self._host_of.get(wid) in hosts):
                        lost.append(wid)
        return lost

    def evict(self, worker_id: str) -> None:
        """Exclude for the session; reap the process if still running."""
        with self._lock:
            if worker_id in self._excluded:
                return
            self._excluded.add(worker_id)
            h = self._workers.get(worker_id)
        if self._hb_server is not None:
            self._hb_server.manager.evict(worker_id)
        if h is not None and h.proc.is_alive():
            h.proc.terminate()
            h.proc.join(timeout=1.0)

    def close(self) -> None:
        for wid, h in self._workers.items():
            if wid not in self._excluded and h.proc.is_alive():
                try:
                    h.task_q.put(None)
                except Exception:
                    pass
        deadline = time.monotonic() + 2.0
        for h in self._workers.values():
            h.proc.join(timeout=max(0.0, deadline - time.monotonic()))
            if h.proc.is_alive():
                h.proc.terminate()
        if self._hb_server is not None:
            self._hb_server.close()


class ProcessBackend:
    """Adapt a ProcessWorkerPool to the StageScheduler backend API.
    Tasks MUST carry a picklable `payload` lineage descriptor; the
    in-memory `run` closure cannot cross a process boundary."""

    def __init__(self, pool: ProcessWorkerPool):
        self.pool = pool

    def workers(self) -> List[str]:
        return self.pool.live_workers()

    def parallelism(self) -> int:
        return max(1, len(self.pool.live_workers()))

    def replacement_worker(self) -> Optional[str]:
        return None  # real processes: eviction is for the session

    def submit(self, task, attempt: int, worker: str, _fn, _on_orphan,
               stage: int) -> None:
        if task.payload is None:
            raise TypeError(
                f"task {task.index} has no picklable payload — the "
                f"process backend needs a (module:function, args) "
                f"lineage descriptor")
        fn_path, args = task.payload
        self.pool.submit(worker, (stage, task.index, attempt, fn_path,
                                  args))

    def poll(self, timeout: float):
        ev = self.pool.poll(timeout)
        if ev is None or ev[0] == "ready":
            return None
        kind, wid, stage, idx, attempt = ev[0], ev[1], ev[2], ev[3], \
            ev[4]
        value: Any = ev[5]
        self._replay_events(ev[6] if len(ev) > 6 else None,
                            stage, idx, attempt, wid)
        if kind == "ok":
            value = pickle.loads(value)
        else:
            value = RuntimeError(
                f"task {idx} attempt {attempt} failed on {wid}:\n"
                f"{value}")
        return (kind, idx, attempt, wid, value, stage)

    @staticmethod
    def _replay_events(events, stage: int, idx: int, attempt: int,
                       wid: str) -> None:
        """Re-emit worker-forwarded events on the driver bus under this
        attempt's task identity (poll runs on the scheduler's driver
        thread, so the query scope is the submitting query's) — the
        cross-process half of the obs contract: span trees and the
        transfer ledger look the same as an in-process run. Transfer
        records also fold into the driver's byte ledger."""
        if not events:
            return
        from spark_rapids_tpu.obs import events as obs_events
        from spark_rapids_tpu.obs import telemetry

        for fe in events:
            fields = dict(fe)
            name = fields.pop("event", None)
            if name is None:
                continue
            if name == "transfer":
                # record() re-emits the bus event itself
                telemetry.record_forwarded(fields)
                continue
            obs_events.emit(name, stage=stage, task=idx,
                            attempt=attempt, worker=wid, **fields)

    def lost_workers(self) -> List[str]:
        return self.pool.check_lost()

    def evict(self, worker: str) -> None:
        self.pool.evict(worker)

    def close(self) -> List[tuple]:
        return []  # the pool outlives individual stages
