"""Physical operator base — the GpuExec analog.

Reference contract (`GpuExec.scala:214,377`): a physical operator exposes
columnar execution over partitioned iterators of batches, with metrics
and spill-aware state. Here:

- `PhysicalPlan.execute_partition(pid, ctx)` returns an iterator of
  payloads: device `ColumnBatch` for TPU operators, `pa.Table` for CPU
  fallback operators. Transition nodes convert between them.
- Exchanges are stage barriers: `TpuShuffleExchangeExec` materializes its
  child's partitions into the in-process shuffle manager before reduce
  partitions iterate.
- `collect()` drives all partitions through a task thread pool, each task
  guarded by the device semaphore (GpuSemaphore admission model).
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from typing import Iterator, List, Optional

import pyarrow as pa

from spark_rapids_tpu.columnar.batch import ColumnBatch
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.runtime import semaphore as sem
from spark_rapids_tpu.sqltypes import StructType

_task_counter = itertools.count(1)


class TaskContext:
    def __init__(self, task_id: int, conf):
        self.task_id = task_id
        self.conf = conf


def new_task_context(conf) -> TaskContext:
    """Fresh task identity (semaphore accounting is per task id)."""
    return TaskContext(next(_task_counter), conf)


class PhysicalPlan:
    """Base physical node. is_tpu distinguishes device vs CPU operators."""

    is_tpu = True

    def __init__(self, children: List["PhysicalPlan"], schema: StructType,
                 conf=None):
        self.children = children
        self.schema = schema
        self.conf = conf
        # collection level honors spark.rapids.sql.metrics.level:
        # metrics above it skip collection, not just the snapshot
        self.metrics = M.MetricsRegistry(M.conf_level(conf))

    @property
    def num_partitions(self) -> int:
        return self.children[0].num_partitions if self.children else 1

    def execute_partition(self, pid: int, ctx: TaskContext) -> Iterator:
        raise NotImplementedError

    @contextlib.contextmanager
    def timed(self, metric_name: str, level: int = M.MODERATE):
        """One scope = the operator metric + a profiler range + an
        `operator.span` event in the query's span tree (the
        NvtxWithMetrics coupling, extended to the obs bus). Replaces
        the ad-hoc `self.metrics[...].ns()` operator timing; rows are
        attributed from the numOutputRows delta when the operator
        tracks it."""
        from spark_rapids_tpu.obs import events as obs_events

        m = self.metrics.metric(metric_name, level)
        rows_before = self.metrics.peek(M.NUM_OUTPUT_ROWS)
        t0 = time.monotonic_ns()
        with obs_events.span(type(self).__name__, metric=metric_name,
                             device=self.is_tpu) as sp:
            try:
                yield
            finally:
                m.add(time.monotonic_ns() - t0)
                dr = self.metrics.peek(M.NUM_OUTPUT_ROWS) - rows_before
                if dr > 0:
                    sp.set(rows=dr)

    def _maybe_dump(self, table: pa.Table, pid: int) -> None:
        """Debug batch dump (DumpUtils.dumpToParquetFile role): when
        spark.rapids.sql.debug.dumpBatchesPath is set, every operator
        output partition lands as a parquet file for offline repro."""
        from spark_rapids_tpu.config import rapids_conf as rc

        path = self.conf.get(rc.DEBUG_DUMP_PATH) if self.conf else ""
        if not path:
            return
        import os

        import pyarrow.parquet as pq

        try:
            os.makedirs(path, exist_ok=True)
            name = f"{type(self).__name__}-p{pid}-{next(_task_counter)}"
            pq.write_table(table, os.path.join(path, name + ".parquet"))
        except Exception as e:
            import logging

            # a debug-only dump must never fail the query
            logging.getLogger(__name__).warning(
                "batch dump to %s failed: %s", path, e)

    # --- driver-side actions ---

    def _premater_cached_entries(self) -> None:
        """Materialize cold relation-cache entries BEFORE any task takes
        semaphore permits: materialization runs a nested fused execute
        with a FRESH task id, and a nested acquire under held permits
        deadlocks (duck-typed to avoid importing operators here)."""
        entry = getattr(self, "entry", None)
        if entry is not None and hasattr(entry, "materialize"):
            entry.materialize()
        for c in self.children:
            c._premater_cached_entries()

    def collect(self) -> pa.Table:
        """Run all partitions -> one arrow table (driver collect).

        The result stage runs as a stage-scheduler TaskSet
        (runtime/scheduler.py): each partition is a deterministic,
        re-runnable task, so a crashed (virtual) worker evicts + the
        partition re-runs elsewhere, and straggling partitions get a
        speculative duplicate under commit-once — Spark's
        DAGScheduler/TaskSetManager semantics for the in-process
        engine."""
        from spark_rapids_tpu.columnar.arrow_bridge import device_to_arrow
        from spark_rapids_tpu.runtime.scheduler import (
            StageScheduler,
            Task,
            tree_consuming,
        )
        from spark_rapids_tpu.sqltypes.datatypes import to_arrow_type

        self._premater_cached_entries()

        def run(pid: int, _attempt: int) -> Optional[pa.Table]:
            from spark_rapids_tpu.runtime.profiler import (
                annotate_with_metric,
            )

            task_id = next(_task_counter)
            ctx = TaskContext(task_id, self.conf)
            parts = []
            try:
                # one scope = timeline range + the task-time metric
                # (the NvtxWithMetrics coupling)
                with annotate_with_metric(
                        f"{type(self).__name__}.p{pid}",
                        self.metrics[M.TASK_TIME],
                        span={"operator": type(self).__name__,
                              "device": self.is_tpu}):
                    for payload in self.execute_partition(pid, ctx):
                        if isinstance(payload, ColumnBatch):
                            parts.append(device_to_arrow(payload))
                        else:
                            parts.append(payload)
            except BaseException as exc:
                # fatal-error policy (Plugin.scala:651-675 onTaskFailed):
                # unrecoverable device failures may exit the process so
                # the cluster manager reschedules this executor
                from spark_rapids_tpu.plugin import executor_plugin

                executor_plugin().on_task_failed(exc)
                raise
            finally:
                sem.get().release_if_necessary(task_id)
            if not parts:
                return None
            out = pa.concat_tables(parts, promote_options="none")
            self._maybe_dump(out, pid)
            return out

        n = self.num_partitions
        sched = StageScheduler(self.conf, name="result",
                               rerunnable=not tree_consuming(self))
        tables = sched.run(
            [Task(pid, run=lambda a, p=pid: run(p, a),
                  lineage=f"result pid={pid}") for pid in range(n)])
        good = [t for t in tables if t is not None and t.num_rows >= 0]
        if not good:
            arrow_schema = pa.schema([
                pa.field(f.name, to_arrow_type(f.dataType), f.nullable)
                for f in self.schema.fields])
            return pa.table({f.name: pa.array([], f.type)
                             for f in arrow_schema},
                            schema=arrow_schema)
        return pa.concat_tables(good, promote_options="none")

    def pretty(self, indent: int = 0) -> str:
        marker = "Tpu" if self.is_tpu else "Cpu*"
        s = "  " * indent + self._node_string()
        for c in self.children:
            s += "\n" + c.pretty(indent + 1)
        return s

    def _node_string(self) -> str:
        return type(self).__name__
