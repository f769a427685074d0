"""Physical operators: TPU device execs + CPU fallback execs.

TPU operators are the GpuExec family redesigned for XLA (SURVEY.md
section 2.5): each hot path is a jitted function over ColumnBatch
pytrees, compiled once per (expression tree, schema, capacity bucket) and
cached by JAX. CPU operators execute the same semantics with pyarrow and
serve as per-operator fallback AND the differential-test oracle.

Operator -> reference mapping:
- TpuProjectExec/TpuFilterExec   <- GpuProjectExec/GpuFilterExec
  (basicPhysicalOperators.scala:350,783)
- TpuHashAggregateExec           <- GpuHashAggregateExec
  (GpuAggregateExec.scala:175-400): partial/final modes around an
  exchange, sort-based device groupby.
- TpuShuffleExchangeExec         <- GpuShuffleExchangeExecBase
  (GpuShuffleExchangeExecBase.scala:261): device hash partition ->
  contiguous slices -> shuffle manager; reduce side coalesces
  (GpuShuffleCoalesceExec).
- TpuShuffledHashJoinExec        <- GpuShuffledHashJoinExec
  (GpuShuffledHashJoinExec.scala:107) via sorted-build gather maps.
- TpuSortExec                    <- GpuSortExec (GpuSortExec.scala:151).
- TpuFileScanExec                <- GpuFileSourceScanExec + multi-file
  readers (GpuParquetScan.scala:1072,2051).
"""

from __future__ import annotations

import itertools
from contextlib import closing, nullcontext
from typing import Dict, Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from spark_rapids_tpu.columnar.arrow_bridge import (
    arrow_to_device,
    device_to_arrow,
)
from spark_rapids_tpu.columnar.batch import (
    ColumnBatch,
    DeviceColumn,
    concat_batches,
    next_capacity,
)
from spark_rapids_tpu.exec import cpu_eval
from spark_rapids_tpu.exec.base import PhysicalPlan, TaskContext
from spark_rapids_tpu.expr import Alias, BoundReference, EvalContext
from spark_rapids_tpu.expr.aggregates import AggregateFunction
from spark_rapids_tpu.io import readers
from spark_rapids_tpu.ops import filterops, partition, segmented
from spark_rapids_tpu.plan.logical import SortOrder
from spark_rapids_tpu.runtime import semaphore as sem
from spark_rapids_tpu.runtime import metrics as M
from spark_rapids_tpu.shuffle.manager import get_shuffle_manager
from spark_rapids_tpu.sqltypes import StringType, StructField, StructType
from spark_rapids_tpu.sqltypes.datatypes import long, to_arrow_type


def _acquire(ctx: TaskContext):
    sem.get().acquire_if_necessary(ctx.task_id)


def _build_ansi_check(conf, exprs, key_base):
    """Compiled ANSI overflow-mask reduction for an operator's
    expressions (expr/ansicheck.py), or None when ANSI mode is off or
    nothing in the tree can raise. One extra tiny program per batch —
    ANSI trades throughput for eager errors, like the reference's ANSI
    kernels."""
    from spark_rapids_tpu.config import rapids_conf as rc
    from spark_rapids_tpu.expr import ansicheck
    from spark_rapids_tpu.runtime.jit_cache import cached_jit

    if conf is None or not conf.get(rc.ANSI_ENABLED):
        return None
    if not any(ansicheck.has_ansi_checks(e) for e in exprs):
        return None
    return cached_jit(("ansi_check",) + tuple(key_base),
                      lambda: ansicheck.check_fn(list(exprs)))


# ---------------------------------------------------------------- sources

class LocalRelationExec(PhysicalPlan):
    is_tpu = False

    def __init__(self, table: pa.Table, schema, conf, num_slices: int = 1):
        super().__init__([], schema, conf)
        self.table = table
        self.num_slices = max(1, min(num_slices, max(1, table.num_rows)))

    @property
    def num_partitions(self):
        return self.num_slices

    def execute_partition(self, pid, ctx):
        n = self.table.num_rows
        per = (n + self.num_slices - 1) // self.num_slices
        lo = min(pid * per, n)
        hi = min(lo + per, n)
        yield self.table.slice(lo, hi - lo)


class TpuCachedRelationExec(PhysicalPlan):
    """Source over a device-resident cache entry (Spark
    InMemoryTableScanExec role; exec/relation_cache.py). The fused
    executor consumes the entry's device parts directly (no host
    traffic); this eager path serves host tables for CPU consumers."""

    def __init__(self, entry, schema, conf):
        super().__init__([], schema, conf)
        self.entry = entry

    @property
    def num_partitions(self):
        return max(1, self.entry.num_parts())

    def execute_partition(self, pid, ctx):
        if pid < self.entry.num_parts():
            _acquire(ctx)  # device-resident from the first touch
            yield self.entry.device_part(pid)


class RangeExec(PhysicalPlan):
    """TPU range source (GpuRangeExec analog)."""

    def __init__(self, start, end, step, num_partitions, schema, conf):
        super().__init__([], schema, conf)
        self.start, self.end, self.step = start, end, step
        self._parts = max(1, num_partitions)

    @property
    def num_partitions(self):
        return self._parts

    def execute_partition(self, pid, ctx):
        _acquire(ctx)
        total = max(0, (self.end - self.start + self.step -
                        (1 if self.step > 0 else -1)) // self.step)
        per = (total + self._parts - 1) // self._parts
        lo = min(pid * per, total)
        hi = min(lo + per, total)
        count = hi - lo
        if count <= 0:
            return
        cap = next_capacity(count)
        vals = (self.start +
                (jnp.arange(cap, dtype=jnp.int64) + lo) * self.step)
        col = DeviceColumn(long, vals, jnp.ones((cap,), bool))
        yield ColumnBatch(self.schema, [col], count)


class TpuFileScanExec(PhysicalPlan):
    """Multi-file columnar scan; strategy per conf (PERFILE/COALESCING/
    MULTITHREADED/AUTO — GpuParquetScan.scala:1072,2051):
    - PERFILE: one read task per file,
    - COALESCING (and AUTO, for local files): pack small files into one
      task up to the coalesce target,
    - MULTITHREADED: same task split, but decode runs on the shared
      reader pool overlapping the consumer's device compute.
    Pushed row-group filters (predicate pushdown) come from the logical
    optimizer via FileScan.pushed_filters."""

    def __init__(self, fmt: str, paths: List[str], schema, conf,
                 pushed_columns: Optional[List[str]] = None,
                 pushed_filters=None, options: Optional[dict] = None):
        super().__init__([], schema, conf)
        self.fmt = fmt
        self.paths = paths
        self.pushed_columns = pushed_columns
        self.pushed_filters = pushed_filters or None
        self.options = options or {}
        from spark_rapids_tpu.config import rapids_conf as rc

        self._batch_rows = conf.get(rc.MAX_READER_BATCH_SIZE_ROWS)
        self._nthreads = conf.get(rc.MULTITHREADED_READ_NUM_THREADS)
        self._strategy = conf.get(rc.PARQUET_READER_TYPE)
        # encoded execution: request string columns as DICTIONARY
        # arrays from parquet so low-cardinality columns arrive as
        # codes and upload encoded (spark.rapids.tpu.encoded.*)
        self._read_dict = (conf.get(rc.ENCODED_ENABLED)
                           and conf.get(rc.ENCODED_READ_DICTIONARY))
        coalesce_bytes = conf.get(rc.READER_COALESCE_BYTES)
        self._part_spec = self.options.get("partition_spec")
        if fmt in ("iceberg", "delta"):
            # per-file tasks: each data file carries its own delete
            # set / deletion vector and column projection
            # (lakehouse/iceberg.py, lakehouse/delta.py)
            self._tasks = [[p] for p in paths] or [[]]
        elif fmt == "parquet":
            if self._part_spec is not None:
                # hive-partitioned layout: per-file tasks (each file
                # carries its own partition values), statically pruned
                # by pushed filters on partition columns
                # (GpuFileSourceScanExec partition pruning role)
                files = readers.expand_paths(paths, ".parquet")
                files = self._prune_partition_files(files)
                self._tasks = [[f] for f in files] or [[]]
            elif self._strategy == "PERFILE":
                self._tasks = [[f] for f in readers.expand_paths(
                    paths, ".parquet")] or [[]]
            else:
                self._tasks = readers.split_parquet_tasks(
                    paths, coalesce_bytes)
        elif fmt in ("orc", "avro"):
            self._tasks = readers.split_file_tasks(paths, "." + fmt,
                                                   coalesce_bytes)
        elif fmt == "hivetext":
            self._tasks = readers.split_file_tasks(paths, ".txt",
                                                   coalesce_bytes)
        else:
            self._tasks = [[p] for p in readers.expand_paths(
                paths, "." + fmt)]

    @property
    def num_partitions(self):
        return max(1, len(self._tasks))

    def _node_string(self) -> str:
        # stamped by stream.stamp_stream_strategy for explain() after
        # a streaming run (the mesh [strategy=ici] discipline)
        st = getattr(self, "stream_strategy", None)
        s = type(self).__name__
        return f"{s} [strategy={st}]" if st else s

    def _prune_partition_files(self, files: List[str]) -> List[str]:
        """Drop files whose partition values contradict pushed filters
        (static partition pruning; dynamic pruning calls
        prune_partitions with runtime key sets)."""
        part_cols, file_values = self._part_spec
        kinds = dict(part_cols)
        ops_fn = {"=": lambda a, b: a == b, "!=": lambda a, b: a != b,
                  "<": lambda a, b: a < b, "<=": lambda a, b: a <= b,
                  ">": lambda a, b: a > b, ">=": lambda a, b: a >= b}
        out = []
        for f in files:
            vals = file_values.get(f, {})
            keep = True
            for name, op, value in (self.pushed_filters or []):
                if name not in vals or op not in ops_fn:
                    continue
                pv = readers.partition_value(vals[name], kinds[name])
                if pv is None or not ops_fn[op](pv, value):
                    keep = False
                    break
            if keep:
                out.append(f)
        return out

    def prune_partitions(self, col: str, allowed) -> int:
        """DYNAMIC partition pruning (GpuFileSourceScanExec.scala DPP
        role): keep only files whose `col` partition value is in
        `allowed` (runtime build-side key set). Returns files dropped.
        Only valid before execution starts."""
        if self._part_spec is None:
            return 0
        part_cols, file_values = self._part_spec
        kinds = dict(part_cols)
        if col not in kinds:
            return 0
        before = sum(len(t) for t in self._tasks)
        kept = []
        for t in self._tasks:
            fs = [f for f in t
                  if readers.partition_value(
                      file_values.get(f, {}).get(col, ""),
                      kinds[col]) in allowed]
            if fs:
                kept.append(fs)
        self._tasks = kept or [[]]
        return before - sum(len(t) for t in self._tasks)

    def _append_partition_columns(self, table: pa.Table,
                                  path: str) -> pa.Table:
        from spark_rapids_tpu.sqltypes.datatypes import to_arrow_type

        part_cols, file_values = self._part_spec
        kinds = dict(part_cols)
        declared = {f.name: to_arrow_type(f.dataType)
                    for f in self.schema.fields}
        vals = file_values.get(path, {})
        want = self.pushed_columns or [f.name for f in self.schema.fields]
        arrays, names = [], []
        for name in want:
            if name in kinds:
                # the scan schema (user-declared or inferred) wins over
                # the directory inference for the column's type
                typ = declared.get(
                    name, pa.int64() if kinds[name] else pa.string())
                raw = vals.get(name, "")
                if raw == "__HIVE_DEFAULT_PARTITION__":
                    pv = None
                elif pa.types.is_string(typ):
                    pv = raw
                elif pa.types.is_floating(typ):
                    pv = float(raw)
                else:
                    pv = int(raw)
                arrays.append(pa.array([pv] * table.num_rows, type=typ))
            else:
                arrays.append(table.column(name))
            names.append(name)
        return pa.table(dict(zip(names, arrays)))

    def _dict_columns(self, cols) -> Optional[List[str]]:
        """String columns to read as parquet DICTIONARY arrays — only
        on the device path (self.is_tpu): the CPU engine and oracle
        keep plain string chunks."""
        from spark_rapids_tpu.sqltypes import StringType as _Str

        if not self._read_dict or not self.is_tpu \
                or self.fmt != "parquet":
            return None
        part_names = (set()
                      if self._part_spec is None
                      else {n for n, _ in self._part_spec[0]})
        out = [f.name for f in self.schema.fields
               if isinstance(f.dataType, _Str)
               and f.name not in part_names
               and (cols is None or f.name in cols)]
        return out or None

    def _host_tables(self, files) -> Iterator[pa.Table]:
        cols = self.pushed_columns
        if self.fmt == "parquet" and self._part_spec is not None:
            part_names = {n for n, _ in self._part_spec[0]}
            data_cols = None if cols is None else [
                c for c in cols if c not in part_names]

            rd = self._dict_columns(data_cols)

            def gen():
                for f in files:
                    # row-group stats pruning applies to data columns
                    # exactly as on the unpartitioned path (partition-
                    # column predicates are skipped: the data file has
                    # no such column, _row_group_may_match keeps it)
                    if self.pushed_filters:
                        it = readers.read_parquet_task_filtered(
                            [f], data_cols, self._batch_rows,
                            self.pushed_filters, read_dictionary=rd)
                    else:
                        it = readers.read_parquet_task(
                            [f], data_cols, self._batch_rows,
                            read_dictionary=rd)
                    for t in it:
                        yield self._append_partition_columns(t, f)

            return gen()
        if self.fmt == "iceberg":
            from spark_rapids_tpu.lakehouse.iceberg import read_data_file

            ctx = self.options["iceberg_ctx"]
            return iter([read_data_file(ctx, f, cols) for f in files])
        if self.fmt == "delta":
            from spark_rapids_tpu.lakehouse.delta import read_data_file

            ctx = self.options["delta_ctx"]
            return iter([read_data_file(ctx, f, cols) for f in files])
        if self.fmt == "parquet":
            rd = self._dict_columns(cols)
            if self._strategy == "MULTITHREADED":
                return readers.read_parquet_multithreaded(
                    files, cols, self._batch_rows, self._nthreads,
                    filters=self.pushed_filters, read_dictionary=rd)
            if self.pushed_filters:
                return readers.read_parquet_task_filtered(
                    files, cols, self._batch_rows, self.pushed_filters,
                    read_dictionary=rd)
            return readers.read_parquet_task(files, cols,
                                             self._batch_rows,
                                             read_dictionary=rd)
        if self.fmt == "csv":
            return iter([readers.read_csv(f) for f in files])
        if self.fmt == "json":
            return iter([readers.read_json(f) for f in files])
        if self.fmt == "orc":
            return iter([readers.read_orc(f, columns=cols) for f in files])
        if self.fmt == "avro":
            from spark_rapids_tpu.io.avro import read_avro

            return iter([read_avro(f).select(cols) if cols
                         else read_avro(f) for f in files])
        if self.fmt == "hivetext":
            from spark_rapids_tpu.io.hivetext import read_hive_text
            from spark_rapids_tpu.sqltypes.datatypes import to_arrow_type

            at = pa.schema([pa.field(f.name, to_arrow_type(f.dataType),
                                     f.nullable)
                            for f in self.schema.fields])
            tabs = [read_hive_text(f, at) for f in files]
            return iter([t.select(cols) if cols else t for t in tabs])
        raise ValueError(f"format {self.fmt}")

    def execute_partition(self, pid, ctx):
        if pid >= len(self._tasks) or not self._tasks[pid]:
            return
        for table in self._host_tables(self._tasks[pid]):
            _acquire(ctx)  # device admission right before H2D
            self.metrics[M.NUM_INPUT_ROWS].add(table.num_rows)
            yield arrow_to_device(table)


class CpuFileScanExec(TpuFileScanExec):
    is_tpu = False

    def execute_partition(self, pid, ctx):
        if pid >= len(self._tasks) or not self._tasks[pid]:
            return
        yield from self._host_tables(self._tasks[pid])


# ------------------------------------------------------------ transitions

class ArrowToDeviceExec(PhysicalPlan):
    """Host arrow -> device batch (GpuRowToColumnarExec role)."""

    def __init__(self, child, conf):
        super().__init__([child], child.schema, conf)

    def execute_partition(self, pid, ctx):
        for table in self.children[0].execute_partition(pid, ctx):
            _acquire(ctx)
            yield arrow_to_device(table)


class DeviceToArrowExec(PhysicalPlan):
    """Device batch -> host arrow (GpuColumnarToRowExec role)."""

    is_tpu = False

    def __init__(self, child, conf):
        super().__init__([child], child.schema, conf)

    def execute_partition(self, pid, ctx):
        for batch in self.children[0].execute_partition(pid, ctx):
            yield device_to_arrow(batch)


# ------------------------------------------------------- project / filter

class TpuProjectExec(PhysicalPlan):
    def __init__(self, exprs: List[Alias], child, schema, conf):
        from spark_rapids_tpu.runtime.jit_cache import aliases_key, cached_jit

        super().__init__([child], schema, conf)
        self.exprs = exprs
        from spark_rapids_tpu.runtime.jit_cache import detached

        self._jitted = cached_jit(("project", aliases_key(exprs)),
                                  lambda: detached(self)._run)
        self._ansi_jit = _build_ansi_check(
            conf, [a for a in exprs], ("project", aliases_key(exprs)))

    def _run(self, batch: ColumnBatch) -> ColumnBatch:
        from spark_rapids_tpu.columnar import encoding as _enc

        ctx = EvalContext(batch)
        # eval_preserving: bare column selections pass dictionary-
        # encoded columns through UNdecoded (late materialization)
        cols = [_enc.eval_preserving(e, ctx) for e in self.exprs]
        return ColumnBatch(self.schema, cols, batch.num_rows)

    def execute_partition(self, pid, ctx):
        with self.timed(M.OP_TIME):
            for batch in self.children[0].execute_partition(pid, ctx):
                if self._ansi_jit is not None:
                    from spark_rapids_tpu.expr.ansicheck import raise_if_set

                    raise_if_set(self._ansi_jit(batch))
                out = self._jitted(batch)
                self.metrics[M.NUM_OUTPUT_BATCHES].add(1)
                yield out


class CpuProjectExec(PhysicalPlan):
    is_tpu = False

    def __init__(self, exprs, child, schema, conf):
        super().__init__([child], schema, conf)
        self.exprs = exprs

    def execute_partition(self, pid, ctx):
        with self.timed(M.OP_TIME):
            for table in self.children[0].execute_partition(pid, ctx):
                arrays = [cpu_eval.eval_expr(e, table).combine_chunks()
                          for e in self.exprs]
                # from_arrays keeps duplicate output names (legal in
                # Spark)
                yield pa.Table.from_arrays(
                    arrays, names=[e.name for e in self.exprs])


class TpuExpandExec(PhysicalPlan):
    """One output batch per projection per input batch (reference
    GpuExpandExec.scala iterates projections per batch to bound peak
    memory the same way)."""

    def __init__(self, projections, child, schema, conf):
        from spark_rapids_tpu.runtime.jit_cache import aliases_key, cached_jit
        from spark_rapids_tpu.runtime.jit_cache import detached

        super().__init__([child], schema, conf)
        self.projections = projections
        det = detached(self)
        self._jitted = [
            cached_jit(("expand", i, aliases_key(p)),
                       lambda i=i: lambda b: det._run(b, i))
            for i, p in enumerate(projections)]

    def _run(self, batch: ColumnBatch, i: int) -> ColumnBatch:
        ctx = EvalContext(batch)
        cols = [e.eval(ctx) for e in self.projections[i]]
        return ColumnBatch(self.schema, cols, batch.num_rows)

    def execute_partition(self, pid, ctx):
        with self.timed(M.OP_TIME):
            for batch in self.children[0].execute_partition(pid, ctx):
                for fn in self._jitted:
                    out = fn(batch)
                    self.metrics[M.NUM_OUTPUT_BATCHES].add(1)
                    yield out


class CpuExpandExec(PhysicalPlan):
    is_tpu = False

    def __init__(self, projections, child, schema, conf):
        super().__init__([child], schema, conf)
        self.projections = projections

    def execute_partition(self, pid, ctx):
        from spark_rapids_tpu.sqltypes.datatypes import to_arrow_type

        names = [e.name for e in self.projections[0]]
        types = [to_arrow_type(f.dataType) for f in self.schema.fields]
        for table in self.children[0].execute_partition(pid, ctx):
            for proj in self.projections:
                arrays = []
                for e, at in zip(proj, types):
                    arr = cpu_eval.eval_expr(e, table).combine_chunks()
                    if arr.type != at:
                        arr = arr.cast(at)
                    arrays.append(arr)
                yield pa.Table.from_arrays(arrays, names=names)


def _sample_uniform01(pos, seed: int, xp):
    """Deterministic per-row uniform in [0,1) from (seed, global row
    position) — two rounds of 32-bit avalanche mixing; identical
    numpy/jnp implementations keep the device engine and the CPU oracle
    selecting the same rows."""
    x = pos.astype(xp.uint32)
    x = x ^ xp.uint32(seed & 0xFFFFFFFF)
    for _ in range(2):
        x = (x ^ (x >> xp.uint32(16))) * xp.uint32(0x7FEB352D)
        x = (x ^ (x >> xp.uint32(15))) * xp.uint32(0x846CA68B)
        x = x ^ (x >> xp.uint32(16))
    return x.astype(xp.float64) / 4294967296.0


class TpuSampleExec(PhysicalPlan):
    """Bernoulli sample without replacement, on device."""

    def __init__(self, fraction, seed, child, conf):
        from spark_rapids_tpu.runtime.jit_cache import cached_jit, detached

        super().__init__([child], child.schema, conf)
        self.fraction = fraction
        self.seed = seed
        det = detached(self)
        self._jitted = cached_jit(("sample", fraction, seed),
                                  lambda: det._run)

    def _run(self, batch: ColumnBatch, offset, pid) -> ColumnBatch:
        cap = batch.capacity
        # partition id folds into the position stream (traced scalar, so
        # one compiled program serves every partition)
        pos = offset + jnp.arange(cap, dtype=jnp.int64) \
            + pid * jnp.int64(0x5DEECE66D)
        u = _sample_uniform01(pos, self.seed, jnp)
        keep = batch.live_mask() & (u < self.fraction)
        return filterops.compact(batch, keep)

    def execute_partition(self, pid, ctx):
        with self.timed(M.OP_TIME):
            offset = 0
            pid_arr = jnp.int64(pid)
            for batch in self.children[0].execute_partition(pid, ctx):
                out = self._jitted(batch, jnp.int64(offset), pid_arr)
                offset += batch.row_count()
                yield out


class CpuSampleExec(PhysicalPlan):
    """Arrow-side sample; also handles with-replacement (Poisson row
    repetition), which has no fixed-shape device lowering."""

    is_tpu = False

    def __init__(self, fraction, seed, with_replacement, child, conf):
        super().__init__([child], child.schema, conf)
        self.fraction = fraction
        self.seed = seed
        self.with_replacement = with_replacement
        self._off = {}

    def execute_partition(self, pid, ctx):
        self._off[pid] = 0
        # one RNG stream per partition (not per batch) so successive
        # batches draw fresh Poisson counts
        rng = np.random.default_rng(
            (self.seed * 1_000_003 + pid) & 0xFFFFFFFF)
        for table in self.children[0].execute_partition(pid, ctx):
            n = table.num_rows
            offset = self._off[pid]
            self._off[pid] = offset + n
            if self.with_replacement:
                counts = rng.poisson(self.fraction, n)
                idx = np.repeat(np.arange(n), counts)
                yield table.take(pa.array(idx))
            else:
                pos = (np.arange(offset, offset + n, dtype=np.int64)
                       + pid * 0x5DEECE66D)
                u = _sample_uniform01(pos, self.seed, np)
                yield table.filter(pa.array(u < self.fraction))


class _PandasExecBase(PhysicalPlan):
    """Shared plumbing for the pandas-exchange execs (the
    GpuArrowEvalPythonExec family roles): gather the host child into one
    table per partition, apply through the worker pool."""

    is_tpu = False

    def _workers(self):
        from spark_rapids_tpu.config import rapids_conf as rcm

        return (self.conf.get(rcm.CONCURRENT_PYTHON_WORKERS)
                if self.conf else 4)

    def _out_arrow_schema(self):
        from spark_rapids_tpu.sqltypes.datatypes import to_arrow_type

        return pa.schema([
            pa.field(f.name, to_arrow_type(f.dataType), f.nullable)
            for f in self.schema.fields])

    @staticmethod
    def _gather(child, pid, ctx):
        tables = list(child.execute_partition(pid, ctx))
        if not tables:
            return None
        return pa.concat_tables(tables, promote_options="none")


class CpuMapInPandasExec(_PandasExecBase):
    def __init__(self, fn, schema, child, conf):
        super().__init__([child], schema, conf)
        self.fn = fn

    def execute_partition(self, pid, ctx):
        from spark_rapids_tpu.udf.pandas_udf import map_in_pandas

        table = self._gather(self.children[0], pid, ctx)
        if table is None:
            return
        yield map_in_pandas(self.fn, table, self._out_arrow_schema(),
                            num_workers=self._workers())


class CpuGroupedMapInPandasExec(_PandasExecBase):
    def __init__(self, key_names, fn, schema, child, conf):
        super().__init__([child], schema, conf)
        self.key_names = key_names
        self.fn = fn

    def execute_partition(self, pid, ctx):
        from spark_rapids_tpu.udf.pandas_udf import (
            apply_in_pandas_grouped,
        )

        table = self._gather(self.children[0], pid, ctx)
        if table is None:
            return
        yield apply_in_pandas_grouped(self.fn, self.key_names, table,
                                      self._out_arrow_schema(),
                                      num_workers=self._workers())


class CpuCoGroupedMapInPandasExec(_PandasExecBase):
    def __init__(self, key_names, fn, schema, left, right, conf):
        super().__init__([left, right], schema, conf)
        self.key_names = key_names
        self.fn = fn

    def execute_partition(self, pid, ctx):
        from spark_rapids_tpu.udf.pandas_udf import (
            apply_in_pandas_cogrouped,
        )

        left = self._gather(self.children[0], pid, ctx)
        right = self._gather(self.children[1], pid, ctx)
        if left is None and right is None:
            return
        lsch = self.children[0].schema
        rsch = self.children[1].schema
        from spark_rapids_tpu.sqltypes.datatypes import to_arrow_type

        def empty(sch):
            return pa.schema([
                pa.field(f.name, to_arrow_type(f.dataType), f.nullable)
                for f in sch.fields]).empty_table()

        yield apply_in_pandas_cogrouped(
            self.fn, self.key_names,
            left if left is not None else empty(lsch),
            right if right is not None else empty(rsch),
            self._out_arrow_schema(), num_workers=self._workers())


class TpuFilterExec(PhysicalPlan):
    def __init__(self, condition, child, conf):
        from spark_rapids_tpu.runtime.jit_cache import cached_jit

        super().__init__([child], child.schema, conf)
        self.condition = condition
        from spark_rapids_tpu.runtime.jit_cache import detached

        self._jitted = cached_jit(("filter", condition.key()),
                                  lambda: detached(self)._run)
        self._ansi_jit = _build_ansi_check(
            conf, [condition], ("filter", condition.key()))

    def _run(self, batch: ColumnBatch) -> ColumnBatch:
        ctx = EvalContext(batch)
        pred = self.condition.eval(ctx)
        keep = pred.data & pred.validity
        return filterops.compact(batch, keep)

    def execute_partition(self, pid, ctx):
        with self.timed(M.FILTER_TIME):
            for batch in self.children[0].execute_partition(pid, ctx):
                if self._ansi_jit is not None:
                    from spark_rapids_tpu.expr.ansicheck import raise_if_set

                    raise_if_set(self._ansi_jit(batch))
                yield self._run_jit(batch)

    def _run_jit(self, batch):
        return self._jitted(batch)


class CpuFilterExec(PhysicalPlan):
    is_tpu = False

    def __init__(self, condition, child, conf):
        super().__init__([child], child.schema, conf)
        self.condition = condition

    def execute_partition(self, pid, ctx):
        import pyarrow.compute as pc

        with self.timed(M.FILTER_TIME):
            for table in self.children[0].execute_partition(pid, ctx):
                mask = cpu_eval.eval_expr(self.condition, table)
                yield table.filter(pc.fill_null(mask, False))


# -------------------------------------------------------------- aggregate

def _buffer_schema(grouping: List[Alias], aggs: List[Alias]) -> StructType:
    fields = [StructField(g.name, g.dtype, True) for g in grouping]
    for i, a in enumerate(aggs):
        fn: AggregateFunction = a.children[0]
        for j, bt in enumerate(fn.buffer_types()):
            fields.append(StructField(f"{a.name}#buf{j}", bt, True))
    return StructType(fields)


class TpuHashAggregateExec(PhysicalPlan):
    """mode='partial' emits [keys..., buffers...]; mode='final' consumes
    them post-shuffle and emits [keys..., results...]. mode='complete'
    does both in one step (single-partition plans)."""

    def __init__(self, mode: str, grouping: List[Alias], aggs: List[Alias],
                 child, conf):
        assert mode in ("partial", "final", "complete")
        self.mode = mode
        self.grouping = grouping
        self.aggs = aggs
        out_schema = (_buffer_schema(grouping, aggs) if mode == "partial"
                      else StructType(
                          [StructField(g.name, g.dtype, True)
                           for g in grouping] +
                          [StructField(a.name, a.dtype, True)
                           for a in aggs]))
        super().__init__([child], out_schema, conf)
        from spark_rapids_tpu.runtime.jit_cache import aliases_key, cached_jit

        from spark_rapids_tpu.runtime.jit_cache import detached

        from spark_rapids_tpu.config import rapids_conf as rc

        # baked at plan time: `detached` strips conf from the cached
        # bound methods, so trace-time conf reads would always see None
        self._mm_ok = conf is None or conf.get(rc.AGG_MATMUL_ENABLED)
        self._mm_max_bins = (conf.get(rc.AGG_MATMUL_MAX_BINS)
                             if conf is not None else None)
        self._mm_chunk = (conf.get(rc.AGG_MATMUL_CHUNK_ROWS)
                          if conf is not None else None)
        base_key = ("agg", mode, self._mm_ok, self._mm_max_bins,
                    self._mm_chunk, aliases_key(grouping),
                    aliases_key(aggs)) + self.lowering_key()
        det = detached(self)
        if any(not a.children[0].jittable for a in aggs):
            # collect_list/percentile family: update/merge output widths
            # are data-dependent (largest group), so the phases run in
            # jax eager mode — still on device, just not traced.
            self._jit_partial = det._partial
            self._jit_merge = det._merge_final
            self._jit_merge_buffers = det._merge_buffers
        else:
            self._jit_partial = cached_jit(base_key + ("partial",),
                                           lambda: det._partial)
            self._jit_merge = cached_jit(base_key + ("merge_final",),
                                         lambda: det._merge_final)
            self._jit_merge_buffers = cached_jit(
                base_key + ("merge_buffers",), lambda: det._merge_buffers)
        # ANSI checks evaluate the grouping/agg INPUT expressions, which
        # only exist against the source batch (partial/complete input)
        self._ansi_jit = None if mode == "final" else _build_ansi_check(
            conf, list(grouping) + list(aggs), base_key)

    def lowering_key(self) -> tuple:
        """What a program key must carry of HOW this aggregate reduces,
        beyond its expressions: a keyless aggregate reduces densely
        (`_reductions`), and a program cache that has seen the scatter
        lowering under the bare key must not serve it. Empty for a keyed
        aggregate, whose keys and program names stay what they were."""
        return () if self.grouping else ("dense",)

    def _node_string(self) -> str:
        s = type(self).__name__
        return s if self.grouping else f"{s} [reduce=dense]"

    # --- phases (each a single XLA program) ---

    def _grouped(self, batch: ColumnBatch, key_idx, live=None):
        return segmented.group_by(batch, key_idx, live)

    def _reductions(self):
        """Trace-time context for this aggregate's update/merge calls:
        without a grouping key every row is in segment 0, so the
        segmented primitives reduce densely instead of scattering each
        row into one slot (segmented.one_segment)."""
        return nullcontext() if self.grouping else segmented.one_segment()

    @staticmethod
    def _bin_ranges(work: ColumnBatch, nkeys: int):
        """Static per-key (lo, hi) value bounds when EVERY group key is
        an integer column carrying upload-time vrange metadata and the
        total bin count fits the capacity — enables the sort-free
        bin-space partial aggregation (`_partial_binned`, with MXU
        matmul reductions on TPU via segmented.binned_bins)."""
        if nkeys == 0:
            return None
        ranges, total = [], 1
        for i in range(nkeys):
            c = work.columns[i]
            vr = getattr(c, "vrange", None)
            if (vr is None or c.data.ndim != 1
                    or not jnp.issubdtype(c.data.dtype, jnp.integer)):
                return None
            total *= vr[1] - vr[0] + 2
            if total > min(work.capacity, 1 << 20):
                return None
            ranges.append(vr)
        return ranges

    def _partial(self, batch: ColumnBatch, live=None) -> ColumnBatch:
        from spark_rapids_tpu.columnar import encoding as _encoding

        nkeys = len(self.grouping)
        # evaluate grouping + agg inputs into a working batch;
        # eval_preserving keeps dictionary-encoded group keys as CODES
        # (their [0, K) vrange then rides the sort-free binned path)
        ctx = EvalContext(batch)
        work_cols = [_encoding.eval_preserving(g, ctx)
                     for g in self.grouping]
        # each aggregate may take 0 (count(*)), 1, or 2+ (corr/covar)
        # input expressions
        input_groups = []
        for a in self.aggs:
            fn: AggregateFunction = a.children[0]
            input_groups.append([e.eval(ctx) for e in fn.children])
        fields = [StructField(g.name, g.dtype, True) for g in self.grouping]
        concrete = [c for grp in input_groups for c in grp]
        for i, c in enumerate(concrete):
            fields.append(StructField(f"in{i}", c.dtype, True))
        work = ColumnBatch(StructType(fields), work_cols + concrete,
                           batch.num_rows)
        if not work.columns:
            # global COUNT(*): no key or input columns — group the source
            # batch so capacity/live-mask come from the real data (a
            # zero-column batch reports the minimum capacity bucket)
            work = ColumnBatch(batch.schema, batch.columns, batch.num_rows)
        ranges = self._bin_ranges(work, nkeys)
        if ranges is not None and all(
                a.children[0].binned_safe for a in self.aggs):
            return self._partial_binned(work, ranges, input_groups, live)
        g = self._grouped(work, list(range(nkeys)), live)
        cap = work.capacity
        out_cols: List[DeviceColumn] = []
        # group key columns: first row of each segment (gather keeps
        # every leaf — including the dictionary of an encoded key;
        # plain keys keep the historical vrange drop so their treedefs
        # — and the compiled-program cache keyed on them — are stable)
        for ki in range(nkeys):
            col = g.sorted_batch.columns[ki]
            safe = jnp.clip(g.first_pos, 0, cap - 1)
            out = col.gather(safe)
            if out.encoding is None and out.vrange is not None:
                out = out.replace(vrange=None)
            out_cols.append(out)
        ci = nkeys
        for a, grp in zip(self.aggs, input_groups):
            fn: AggregateFunction = a.children[0]
            k = len(grp)
            if k == 0:
                vals = None
            elif k == 1:
                vals = g.sorted_batch.columns[ci]
            else:
                vals = [g.sorted_batch.columns[ci + j] for j in range(k)]
            ci += k
            with self._reductions():
                out_cols.extend(fn.update(vals, g.live, g.gid, cap))
        return ColumnBatch(_buffer_schema(self.grouping, self.aggs),
                           out_cols, g.num_groups)

    def _partial_binned(self, work: ColumnBatch, ranges, input_groups,
                        live) -> ColumnBatch:
        """Sort-free partial aggregation entirely in BIN space.

        Row work is one elementwise pass (bin id per row) plus the
        segmented reductions; everything group-shaped lives at the
        static bin-count capacity, NOT the row capacity — group keys
        are decoded analytically from the bin index (inverting
        bin = sum((value - lo + 1) * stride)), so no giant first-pos
        scatter/gather over the row space exists at all. On TPU the
        reductions ride the MXU (segmented.binned_bins); elsewhere they
        stay scatter-adds over the small bin space."""
        from spark_rapids_tpu.columnar.batch import next_capacity

        nkeys = len(self.grouping)
        cap = work.capacity
        if live is None:
            live = work.live_mask()
        gid64 = jnp.zeros((cap,), jnp.int64)
        stride = 1
        for i, (lo, hi) in enumerate(ranges):
            c = work.columns[i]
            code = jnp.where(c.validity,
                             c.data.astype(jnp.int64) - lo + 1, 0)
            gid64 = gid64 + code * stride
            stride *= hi - lo + 2
        bcap = next_capacity(stride)
        gid = jnp.clip(gid64, 0, bcap - 1).astype(jnp.int32)
        mm_ok = self._mm_ok

        with segmented.unsorted_gids(), (
                segmented.binned_bins(stride, self._mm_max_bins,
                                      self._mm_chunk)
                if mm_ok else nullcontext()):
            out_cols: List[DeviceColumn] = []
            # analytic key decode: bin index -> key values, in bin space
            idx = jnp.arange(bcap, dtype=jnp.int64)
            stride_i = 1
            for ki, (lo, hi) in enumerate(ranges):
                base = hi - lo + 2
                code = (idx // stride_i) % base
                stride_i *= base
                col = work.columns[ki]
                # lo-1 is the null bin's decoded placeholder, so the
                # stamped bound includes it. An ENCODED key column's
                # analytic decode is its CODE (vrange [0, K)) — the
                # dictionary handle rides along so the key stays
                # encoded until something truly needs the strings.
                out_cols.append(DeviceColumn(
                    col.dtype, (code - 1 + lo).astype(col.data.dtype),
                    code > 0, vrange=(lo - 1, hi),
                    encoding=col.encoding))
            ci = nkeys
            fast = self._binned_all_sums(input_groups, live, gid, bcap,
                                         work, ci)
            if fast is not None:
                counts, agg_cols = fast
                out_cols.extend(agg_cols)
            else:
                counts = segmented.seg_count(live, gid, bcap)
                for a, grp in zip(self.aggs, input_groups):
                    fn: AggregateFunction = a.children[0]
                    k = len(grp)
                    if k == 0:
                        vals = None
                    elif k == 1:
                        vals = work.columns[ci]
                    else:
                        vals = [work.columns[ci + j] for j in range(k)]
                    ci += k
                    out_cols.extend(fn.update(vals, live, gid, bcap))
            occupied = counts > 0
            num_groups = jnp.sum(occupied).astype(jnp.int32)
        # bins -> dense group positions (front-compacted like the
        # sorted path's segment-id outputs)
        perm = segmented.dense_bin_perm(occupied, bcap)
        out_cols = [c.gather(perm) for c in out_cols]
        return ColumnBatch(_buffer_schema(self.grouping, self.aggs),
                           out_cols, num_groups)

    def _binned_all_sums(self, input_groups, live, gid, bcap, work,
                         ci0):
        """ALL reductions of a Sum/Average/Count-only aggregate (the
        canonical OLAP shape) plus the bin-occupancy count as ONE
        matmul sweep: each extra weight vector rides the same one-hot
        tiles (segmented._mm_pass_multi), so the whole partial costs
        barely more than a single reduction. A sum owns one weight
        vector or, an integer with no tight static bound, one per byte
        of its column's width (segmented._mm_sum_plan). Returns
        (occupancy_counts, buffer_cols) or None when the shape doesn't
        qualify (other aggregate functions, decimal128 sums, or no
        matmul backend) — the generic per-function update loop then
        runs instead."""
        from spark_rapids_tpu.expr.aggregates import Average, Count, Sum
        from spark_rapids_tpu.ops import decimal128 as d128

        b = segmented.mm_bins_active()
        if b is None:
            return None
        fns = [a.children[0] for a in self.aggs]
        if not all(type(f) in (Sum, Average, Count) for f in fns):
            return None
        if any(d128.is_wide(f.buffer_types()[0]) for f in fns
               if isinstance(f, (Sum, Average))):
            return None
        weights: List[jnp.ndarray] = []
        accs: List = []
        chunk = segmented.mm_chunk()
        guard = False
        slots = []  # ("sum", plan, w_i, cnt_i, out_t) | ("count", cnt_i)
        # Dedup count reductions on semantic identity (source column
        # index, or "live" for the bare live mask) — id() of temporary
        # arrays can alias across frees in eager execution.
        count_idx_by_key: Dict[object, int] = {}

        def add_count(valid, key) -> int:
            i = count_idx_by_key.get(key)
            if i is None:
                i = len(weights)
                weights.append(valid.astype(jnp.float32))
                accs.append(jnp.int64)
                count_idx_by_key[key] = i
            return i

        ci = ci0
        for fn in fns:
            k = len(fn.children)
            if isinstance(fn, (Sum, Average)):
                col = work.columns[ci]
                valid = col.validity & live
                # the column's own dtype and stamp, before any cast to
                # the sum type: the plan counts limbs from its width
                plan = segmented._mm_sum_plan(col.data, valid, col.vrange)
                if plan is None:
                    return None
                segmented.note_sum_lowering(plan.kind)
                chunk = min(chunk, plan.chunk)
                guard = guard or plan.guard
                wi = len(weights)
                weights.extend(plan.weights)
                accs.extend([plan.acc] * len(plan.weights))
                slots.append(("sum", plan, wi, add_count(valid, ("col", ci)),
                              fn.buffer_types()[0]))
            else:  # Count
                if k == 0:
                    slots.append(("count", add_count(live, "live")))
                else:
                    valid = work.columns[ci].validity & live
                    slots.append(("count", add_count(valid, ("col", ci))))
            ci += k
        occ_i = add_count(live, "live")
        outs = segmented._mm_pass_multi(weights, gid, b, chunk, accs,
                                        guard_nonfinite=guard)
        outs = [segmented._pad_bins(o, bcap) for o in outs]
        ones = jnp.ones((bcap,), bool)
        from spark_rapids_tpu.sqltypes.datatypes import long as _long

        cols: List[DeviceColumn] = []
        for slot in slots:
            if slot[0] == "sum":
                _, plan, wi, cnt_i, out_t = slot
                total = plan.combine(outs[wi:wi + len(plan.weights)])
                cnt = outs[cnt_i]
                cols.append(DeviceColumn(
                    out_t, total.astype(out_t.np_dtype), cnt > 0))
                cols.append(DeviceColumn(_long, cnt, ones))
            else:
                cols.append(DeviceColumn(_long, outs[slot[1]], ones))
        return outs[occ_i], cols

    def _merge_keys_prefix(self, g, nkeys: int, cap: int
                           ) -> List[DeviceColumn]:
        out_cols: List[DeviceColumn] = []
        for ki in range(nkeys):
            col = g.sorted_batch.columns[ki]
            safe = jnp.clip(g.first_pos, 0, cap - 1)
            # gather keeps every leaf (dictionary encodings included);
            # plain keys keep the historical vrange drop (stable
            # treedefs for the compiled-program cache)
            out = col.gather(safe)
            if out.encoding is None and out.vrange is not None:
                out = out.replace(vrange=None)
            out_cols.append(out)
        return out_cols

    def _merge_final(self, batch: ColumnBatch) -> ColumnBatch:
        nkeys = len(self.grouping)
        g = self._grouped(batch, list(range(nkeys)))
        cap = batch.capacity
        out_cols = self._merge_keys_prefix(g, nkeys, cap)
        ci = nkeys
        for a in self.aggs:
            fn: AggregateFunction = a.children[0]
            nb = len(fn.buffer_types())
            bufs = [g.sorted_batch.columns[ci + j] for j in range(nb)]
            ci += nb
            with self._reductions():
                merged = fn.merge(bufs, g.live, g.gid, cap)
            out_cols.append(fn.evaluate(merged))
        return ColumnBatch(self.schema, out_cols, g.num_groups)

    def _merge_buffers(self, batch: ColumnBatch) -> ColumnBatch:
        """Merge partial buffers into compacted buffers WITHOUT final
        evaluation — the reference's merge pass over concatenated
        partials (GpuAggregateExec merge mode), used to bound memory
        while more input is still arriving."""
        nkeys = len(self.grouping)
        g = self._grouped(batch, list(range(nkeys)))
        cap = batch.capacity
        out_cols = self._merge_keys_prefix(g, nkeys, cap)
        ci = nkeys
        for a in self.aggs:
            fn: AggregateFunction = a.children[0]
            nb = len(fn.buffer_types())
            bufs = [g.sorted_batch.columns[ci + j] for j in range(nb)]
            ci += nb
            with self._reductions():
                out_cols.extend(fn.merge(bufs, g.live, g.gid, cap))
        return ColumnBatch(_buffer_schema(self.grouping, self.aggs),
                           out_cols, g.num_groups)

    # --- out-of-core driver ---

    def execute_partition(self, pid, ctx):
        from spark_rapids_tpu.config import rapids_conf as rc
        from spark_rapids_tpu.runtime.memory import get_catalog
        from spark_rapids_tpu.runtime.retry import (
            PendingBatches,
            retry_on_oom,
            with_restore_on_retry,
            with_retry,
        )

        catalog = get_catalog()
        target_rows = (self.conf.get(rc.BATCH_SIZE_ROWS) if self.conf
                       else 1 << 20)

        def park(b):
            return retry_on_oom(lambda: catalog.add_batch(b))

        pending = PendingBatches()  # spillable buffer-schema batches
        # closing(): a cancel or non-retry failure that unwinds past
        # the with_restore_on_retry boundary must still unregister the
        # batches parked in EARLIER iterations (restore only rolls back
        # to the last input boundary), and an abandoned generator (a
        # LIMIT that stops consuming) must not strand its parked
        # batches either. close() is idempotent; the normal paths
        # close before yielding.
        with self.timed(M.AGG_TIME), closing(pending):

            def reduce_pending():
                def step():
                    batches = [sb.get_batch() for sb in pending.items]
                    merged = concat_batches(batches) if len(batches) > 1 \
                        else batches[0]
                    with catalog.reserved(merged.device_size_bytes(),
                                          "agg_merge"):
                        return self._jit_merge_buffers(merged)

                compacted = retry_on_oom(step)
                pending.close()
                pending.append(park(compacted),
                               # one exact sync per COMPACTION (rare) —
                               # a capacity estimate here could exceed
                               # the threshold permanently and re-trigger
                               # full merges on every input batch
                               compacted.row_count())

            for batch in self.children[0].execute_partition(pid, ctx):
                if self._ansi_jit is not None:
                    from spark_rapids_tpu.expr.ansicheck import raise_if_set

                    raise_if_set(self._ansi_jit(batch))
                if self.mode == "final":
                    pending.append(park(batch), batch.capacity)
                else:
                    sb = park(batch)

                    def part_fn(s):
                        b = s.get_batch()
                        with catalog.reserved(b.device_size_bytes(),
                                              "agg_partial"):
                            return self._jit_partial(b)

                    def consume(sb=sb):
                        for part in with_retry(sb, part_fn):
                            pending.append(park(part), part.capacity)

                    # a failure mid-batch (e.g. an OOM past its retry
                    # budget) rolls PENDING back to the last input
                    # boundary and closes the orphans — the task fails
                    # leak-free and idempotent for task-level retry
                    # (withRestoreOnRetry role)
                    with_restore_on_retry(pending, consume)
                if len(pending.items) > 1 and pending.rows > 2 * target_rows:
                    reduce_pending()

            if not pending.items:
                if len(self.grouping) == 0 and self.mode in ("final",
                                                             "complete"):
                    # global agg over empty input -> one default row
                    yield self._empty_global_result()
                return
            batches = [sb.get_batch() for sb in pending.items]
            merged = concat_batches(batches) if len(batches) > 1 \
                else batches[0]
            pending.close()
            if self.mode == "partial":
                yield self._jit_merge_buffers(merged)
                return
            if (self.grouping and
                    merged.row_count() > max(target_rows, 1)):
                # high-cardinality fallback: re-partition buffers by key
                # hash and finalize each part separately (the reference's
                # repartition-based agg fallback, GpuAggregateExec)
                yield from self._finalize_partitioned(merged)
            else:
                yield self._jit_merge(merged)

    def _finalize_partitioned(self, merged: ColumnBatch):
        from spark_rapids_tpu.config import rapids_conf as rc
        from spark_rapids_tpu.ops import partition as P

        target_rows = (self.conf.get(rc.BATCH_SIZE_ROWS) if self.conf
                       else 1 << 20)
        nparts = max(2, -(-merged.row_count() // max(target_rows, 1)))
        key_idx = list(range(len(self.grouping)))
        for piece in P.split_to_slices(merged, key_idx, nparts,
                                       seed=P.SUB_PARTITION_SEED):
            if piece is not None:
                yield self._jit_merge(piece)

    def _empty_global_result(self):
        cols = []
        for a in self.aggs:
            fn = a.children[0]
            from spark_rapids_tpu.expr.aggregates import Count

            cap = 1024
            from spark_rapids_tpu.expr.aggregates import CountDistinct
            from spark_rapids_tpu.sqltypes import ArrayType

            if isinstance(fn, Count) or (isinstance(fn, CountDistinct)
                                         and fn.name == "count_distinct"):
                cols.append(DeviceColumn(
                    long, jnp.zeros((cap,), jnp.int64),
                    jnp.ones((cap,), bool)))
            elif isinstance(a.dtype, ArrayType):
                # collect_list/set over empty input: empty array, not null
                et = a.dtype.elementType
                cols.append(DeviceColumn(
                    a.dtype, jnp.zeros((cap, 1), et.np_dtype),
                    jnp.ones((cap,), bool),
                    jnp.zeros((cap,), jnp.int32),
                    jnp.zeros((cap, 1), bool)))
            else:
                dt = a.dtype
                cols.append(DeviceColumn(
                    dt, jnp.zeros((cap,), dt.np_dtype),
                    jnp.zeros((cap,), bool)))
        return ColumnBatch(self.schema, cols, 1)


class CpuHashAggregateExec(PhysicalPlan):
    """Arrow group_by fallback/oracle (complete mode only: runs before
    any exchange on the gathered partition)."""

    is_tpu = False

    _ARROW_FN = {"sum": "sum", "count": "count", "min": "min", "max": "max",
                 "last": "last",
                 "avg": "mean", "first": "first"}

    def __init__(self, grouping, aggs, child, schema, conf):
        super().__init__([child], schema, conf)
        self.grouping = grouping
        self.aggs = aggs

    def _pandas_groupby(self, work: "pa.Table", key_names, in_groups
                        ) -> "pa.Table":
        """Oracle path for aggregates arrow's hash kernels lack
        (corr/covar/moments/collect/percentile/distinct): per-group
        numpy evaluation of the Spark formulas."""
        import pandas as pd

        # arrow-backed dtypes: NULL stays pd.NA (distinct from float NaN,
        # which Spark treats as a VALUE) and int64-with-nulls keeps its
        # integer identity instead of round-tripping through float64
        df = work.to_pandas(types_mapper=pd.ArrowDtype)

        def _nn(s):
            return s.dropna().to_numpy(dtype=np.float64, na_value=np.nan)

        def _one(fn, sub: "pd.DataFrame", names):
            x = sub[names[0]]
            nm = fn.name
            if nm == "corr":
                pair = sub[[names[0], names[1]]].dropna()
                n = len(pair)
                if n == 0:
                    return None
                a = pair[names[0]].to_numpy(np.float64)
                b = pair[names[1]].to_numpy(np.float64)
                va = a.var()
                vb = b.var()
                if va == 0 or vb == 0:
                    return None
                return float(((a - a.mean()) * (b - b.mean())).mean()
                             / np.sqrt(va * vb))
            if nm in ("covar_pop", "covar_samp"):
                pair = sub[[names[0], names[1]]].dropna()
                n = len(pair)
                ddof = 0 if nm == "covar_pop" else 1
                if n < 1 + ddof:
                    return None
                a = pair[names[0]].to_numpy(np.float64)
                b = pair[names[1]].to_numpy(np.float64)
                return float(((a - a.mean()) * (b - b.mean())).sum()
                             / (n - ddof))
            if nm in ("var_pop", "var_samp", "stddev_pop",
                      "stddev_samp", "skewness", "kurtosis",
                      "percentile", "approx_percentile"):
                # float conversion only for the numeric moments family
                # (string inputs reach other branches, e.g. distinct)
                v = _nn(x)
                n = len(v)
            if nm in ("var_pop", "var_samp", "stddev_pop", "stddev_samp"):
                ddof = 0 if nm.endswith("pop") else 1
                if n < 1 + ddof:
                    return None
                r = v.var(ddof=ddof)
                return float(np.sqrt(r) if nm.startswith("stddev") else r)
            if nm == "skewness":
                if n == 0:
                    return None
                m2 = ((v - v.mean()) ** 2).sum()
                m3 = ((v - v.mean()) ** 3).sum()
                if m2 == 0:
                    return None
                return float(np.sqrt(n) * m3 / m2 ** 1.5)
            if nm == "kurtosis":
                if n == 0:
                    return None
                m2 = ((v - v.mean()) ** 2).sum()
                m4 = ((v - v.mean()) ** 4).sum()
                if m2 == 0:
                    return None
                return float(n * m4 / (m2 * m2) - 3.0)
            if nm in ("percentile", "approx_percentile"):
                if n == 0:
                    return None
                return float(np.percentile(v, fn.percentage * 100.0,
                                           method="linear"))
            raw = x.dropna()
            if nm == "collect_list":
                return list(raw)
            if nm == "collect_set":
                return list(pd.unique(raw))
            if nm == "count_distinct":
                return int(raw.nunique())
            if nm == "sum_distinct":
                u = pd.Series(pd.unique(raw))
                return None if len(u) == 0 else u.sum()
            if nm == "bool_and":
                return None if len(raw) == 0 else bool(raw.all())
            if nm == "bool_or":
                return None if len(raw) == 0 else bool(raw.any())
            if nm == "count":
                return int(len(raw))
            if nm == "sum":
                return None if len(raw) == 0 else raw.sum()
            if nm == "avg":
                if len(raw) == 0:
                    return None
                from spark_rapids_tpu.sqltypes import DecimalType as _D

                if isinstance(fn.dtype, _D):
                    # exact decimal mean, HALF_UP at the output scale
                    import decimal as _dm

                    with _dm.localcontext() as ctx:
                        ctx.prec = 60
                        tot = sum(_dm.Decimal(v) for v in raw)
                        q = _dm.Decimal(1).scaleb(-fn.dtype.scale)
                        return (tot / len(raw)).quantize(
                            q, rounding=_dm.ROUND_HALF_UP)
                return float(raw.mean())
            if nm == "min":
                return None if len(raw) == 0 else raw.min()
            if nm == "max":
                return None if len(raw) == 0 else raw.max()
            if nm in ("first", "last", "any_value"):
                src = raw if fn.ignore_nulls else x
                if len(src) == 0:
                    return None
                val = src.iloc[-1 if nm == "last" else 0]
                return None if pd.isna(val) else val
            raise NotImplementedError(f"cpu oracle aggregate {nm}")

        if key_names:
            grouped = df.groupby(key_names, dropna=False, sort=False)
            groups = list(grouped)
        else:
            groups = [((), df)]
        out_rows = {a.name: [] for a in self.aggs}
        key_rows = {k: [] for k in key_names}
        for key_val, sub in groups:
            if key_names:
                kv = key_val if isinstance(key_val, tuple) else (key_val,)
                for k, v in zip(key_names, kv):
                    key_rows[k].append(None if pd.isna(v) else v)
            for a, names in zip(self.aggs, in_groups):
                out_rows[a.name].append(_one(a.children[0], sub, names))
        out = {}
        for g_ in self.grouping:
            out[g_.name] = pa.array(key_rows[g_.name],
                                    type=to_arrow_type(g_.dtype))
        for a in self.aggs:
            out[a.name] = pa.array(out_rows[a.name],
                                   type=to_arrow_type(a.dtype))
        return pa.table(out)

    def execute_partition(self, pid, ctx):
        import pyarrow.compute as pc

        with self.timed(M.AGG_TIME):
            yield from self._agg_partition(pid, ctx, pc)

    def _agg_partition(self, pid, ctx, pc):
        tables = list(self.children[0].execute_partition(pid, ctx))
        if not tables:
            tables = []
        table = (pa.concat_tables(tables, promote_options="none")
                 if tables else None)
        if table is None:
            return
        # evaluate grouping exprs + agg inputs as columns (an aggregate
        # may take 0, 1, or 2+ inputs — corr/covar are bivariate)
        cols = {}
        for g_ in self.grouping:
            cols[g_.name] = cpu_eval.eval_expr(g_, table)
        in_groups = []
        for i, a in enumerate(self.aggs):
            fn: AggregateFunction = a.children[0]
            names = []
            if not fn.children:
                nm = f"__in{i}"
                cols[nm] = pa.chunked_array([
                    pa.array(np.ones(table.num_rows, np.int64))])
                names.append(nm)
            else:
                for j, e in enumerate(fn.children):
                    nm = f"__in{i}_{j}"
                    cols[nm] = cpu_eval.eval_expr(e, table)
                    names.append(nm)
            in_groups.append(names)
        work = pa.table(cols)
        key_names = [g_.name for g_ in self.grouping]
        from spark_rapids_tpu.sqltypes import DecimalType as _Dec

        def _needs_pandas(a):
            fn = a.children[0]
            if fn.name not in self._ARROW_FN:
                return True
            # arrow's hash_mean rounds decimals at the INPUT scale;
            # Spark's avg is exact sum/count at scale+4
            return (fn.name == "avg" and fn.children
                    and isinstance(fn.children[0].dtype, _Dec))

        if any(_needs_pandas(a) for a in self.aggs):
            yield self._pandas_groupby(work, key_names, in_groups)
            return
        in_names = [names[0] for names in in_groups]
        agg_specs = []
        for i, a in enumerate(self.aggs):
            fn = a.children[0]
            arrow_fn = self._ARROW_FN[fn.name]
            if fn.name == "count" and fn.input is None:
                agg_specs.append((in_names[i], "sum"))
            elif fn.name in ("first", "last"):
                # pyarrow defaults skip_nulls=True; Spark's ignore_nulls
                # must be honored on the oracle path too
                agg_specs.append((in_names[i], arrow_fn,
                                  pc.ScalarAggregateOptions(
                                      skip_nulls=fn.ignore_nulls)))
            else:
                agg_specs.append((in_names[i], arrow_fn))
        if key_names:
            res = work.group_by(key_names, use_threads=False).aggregate(
                agg_specs)
        else:
            flat = {}
            for spec, a in zip(agg_specs, self.aggs):
                nm, fnname = spec[0], spec[1]
                if len(spec) > 2:  # first/last carry null options
                    val = getattr(pc, fnname)(work.column(nm),
                                              options=spec[2])
                else:
                    val = getattr(pc, fnname)(work.column(nm))
                flat[a.name] = pa.array([val.as_py()],
                                        type=to_arrow_type(a.dtype))
            yield pa.table(flat)
            return
        # rename result columns to output names and cast to Spark types
        out = {}
        for k in key_names:
            out[k] = res.column(k)
        for spec, a in zip(agg_specs, self.aggs):
            nm, fnname = spec[0], spec[1]
            col = res.column(f"{nm}_{fnname}")
            out[a.name] = pc.cast(col, to_arrow_type(a.dtype))
        yield pa.table(out)


# --------------------------------------------------------------- exchange

class TpuShuffleExchangeExec(PhysicalPlan):
    """Device hash/round-robin/single partitioning + in-process shuffle.

    Map side runs once as a stage-scheduler TaskSet (driven by the
    first reduce task to arrive): each map task is a deterministic,
    re-runnable attempt over one child partition (lineage = child
    subtree + partition id) whose output blocks stay STAGED under
    (map_id, attempt) until the scheduler commits them — commit-once
    makes speculative duplicates safe, and `fetch_blocks` recomputes
    exactly the map task owning blocks a reducer lost
    (runtime/scheduler.py). Reduce side fetches + coalesces back to
    device.
    """

    def __init__(self, child, key_exprs: Optional[List], num_partitions,
                 conf):
        super().__init__([child], child.schema, conf)
        self.key_exprs = key_exprs  # None -> round robin / single
        self._nparts = max(1, num_partitions)
        self._shuffle_id = None
        self._map_done = False
        import threading

        self._lock = threading.Lock()
        from spark_rapids_tpu.config import rapids_conf as rc

        # DEVICE mode: blocks stay HBM-resident as spillables in the
        # catalog — no device->host->device round trip per exchange
        # (RapidsCachingWriter + ShuffleBufferCatalog role)
        self._device_mode = bool(
            conf is not None and conf.get(rc.SHUFFLE_MODE) == "DEVICE")
        # device-mode reduce fetches CONSUME blocks (closed after the
        # last partition drains) — the scheduler must not re-run or
        # duplicate tasks over this subtree (scheduler.tree_consuming)
        self.consuming = self._device_mode
        self._dev_blocks: List = []  # [(SpillableBatch, np offsets)]
        self._staged_dev: Dict = {}  # (map_id, attempt) -> blocks
        self._fetches_left = self._nparts
        # separate from _lock: map tasks park blocks WHILE the map-stage
        # coordinator holds _lock
        self._blocks_lock = threading.Lock()
        from spark_rapids_tpu.runtime.jit_cache import cached_jit

        kkey = (tuple(k.key() for k in key_exprs)
                if key_exprs else None)
        from spark_rapids_tpu.runtime.jit_cache import detached

        self._jit_partition = cached_jit(
            ("exchange_partition", kkey, self._nparts),
            lambda: detached(self)._partition_batch)

    #: planner-chosen shuffle transport: "host" (serialized blocks via
    #: the in-process shuffle manager) or "ici" (the mesh engine
    #: compiles this exchange to an on-device all_to_all over the
    #: interconnect -- set per node by
    #: MeshQueryExecutor.plan_exchange_strategies when both sides are
    #: mesh-resident and iciShuffle is enabled)
    ici_strategy = "host"

    def _node_string(self) -> str:
        base = type(self).__name__
        if self.ici_strategy == "ici":
            return f"{base} [strategy=ici]"
        return base

    @property
    def num_partitions(self):
        return self._nparts

    def _partition_batch(self, batch: ColumnBatch):
        if self.key_exprs:
            ctx = EvalContext(batch)
            key_cols = [e.eval(ctx) for e in self.key_exprs]
            fields = list(batch.schema.fields) + [
                StructField(f"__k{i}", c.dtype, True)
                for i, c in enumerate(key_cols)]
            work = ColumnBatch(StructType(fields),
                               batch.columns + key_cols, batch.num_rows)
            kidx = list(range(len(batch.columns),
                              len(batch.columns) + len(key_cols)))
            pid = partition.hash_partition_ids(work, kidx, self._nparts)
            pb = partition.partition_by_ids(work, pid, self._nparts)
            sorted_batch = pb.batch.select(list(range(len(batch.columns))))
            return sorted_batch, pb.counts
        pb = partition.round_robin_partition(batch, self._nparts)
        return pb.batch, pb.counts

    def _park_device_block(self, batch: ColumnBatch, offs: np.ndarray,
                           staged: List):
        from spark_rapids_tpu.runtime.memory import SpillPriority, \
            get_catalog
        from spark_rapids_tpu.runtime.retry import retry_on_oom

        sb = retry_on_oom(lambda: get_catalog().add_batch(
            batch, SpillPriority.INPUT_FROM_SHUFFLE))
        staged.append((sb, offs))

    def _map_task(self, mgr, cpid: int, attempt: int):
        """One map-task ATTEMPT: execute a child partition,
        device-partition its batches, STAGE contiguous slices under
        (map_id=cpid, attempt) — invisible to reducers until the
        scheduler commits this attempt (per-map-task parallel, the
        reference's writer slots,
        RapidsShuffleInternalManagerBase.scala:238). Deterministic:
        the lineage (child subtree + cpid) reproduces identical blocks
        on any re-run."""
        from spark_rapids_tpu.exec.base import new_task_context

        staged_dev: List = []
        if self._device_mode:
            with self._blocks_lock:
                self._staged_dev[(cpid, attempt)] = staged_dev
        tctx = new_task_context(self.conf)
        try:
            for batch in self.children[0].execute_partition(cpid, tctx):
                if self._nparts == 1:
                    if self._device_mode:
                        self._park_device_block(
                            batch,
                            np.array([0, batch.row_count()], np.int64),
                            staged_dev)
                    else:
                        # encoded=True: dictionary columns cross the
                        # shuffle as codes + a per-block dictionary
                        # reference, not decoded values
                        mgr.put(self._shuffle_id, 0,
                                device_to_arrow(batch, encoded=True),
                                map_id=cpid, attempt=attempt)
                    continue
                sorted_batch, counts = self._jit_partition(batch)
                offs = np.concatenate(
                    [[0], np.cumsum(np.asarray(counts))])
                if self._device_mode:
                    self._park_device_block(sorted_batch, offs,
                                            staged_dev)
                    continue
                host = device_to_arrow(sorted_batch, encoded=True)
                for rp in range(self._nparts):
                    lo, hi = int(offs[rp]), int(offs[rp + 1])
                    if hi > lo:
                        mgr.put(self._shuffle_id, rp,
                                host.slice(lo, hi - lo),
                                map_id=cpid, attempt=attempt)
        finally:
            sem.get().release_if_necessary(tctx.task_id)

    def _commit_map(self, mgr, cpid: int, attempt: int,
                    replace: bool = False):
        if self._device_mode:
            with self._blocks_lock:
                blocks = self._staged_dev.pop((cpid, attempt), [])
                self._dev_blocks.extend(blocks)
        else:
            mgr.commit_map_output(self._shuffle_id, cpid, attempt,
                                  replace=replace)

    def _abort_map(self, mgr, cpid: int, attempt: int):
        if self._device_mode:
            with self._blocks_lock:
                blocks = self._staged_dev.pop((cpid, attempt), [])
            for sb, _ in blocks:
                sb.close()
        else:
            mgr.discard_attempt(self._shuffle_id, cpid, attempt)

    def _run_map_stage(self, ctx):
        from spark_rapids_tpu.runtime.scheduler import (
            StageScheduler,
            Task,
            tree_consuming,
        )

        with self._lock:
            if self._map_done:
                return
            mgr = get_shuffle_manager()
            self._shuffle_id = mgr.new_shuffle_id()
            nchild = self.children[0].num_partitions
            tasks = [
                Task(c,
                     run=lambda attempt, c=c:
                         self._map_task(mgr, c, attempt),
                     commit=lambda _res, attempt, c=c:
                         self._commit_map(mgr, c, attempt),
                     abort=lambda attempt, c=c:
                         self._abort_map(mgr, c, attempt),
                     lineage=f"map shuffle={self._shuffle_id} "
                             f"cpid={c}")
                for c in range(nchild)]
            sched = StageScheduler(
                self.conf, name=f"shuffle{self._shuffle_id}-map",
                rerunnable=not tree_consuming(self.children[0]))
            try:
                sched.run(tasks)
            except BaseException:
                # a failed map stage leaks nothing: close committed
                # device blocks and drop this shuffle's host blocks
                # (staged attempts included) so a retry starts clean
                with self._blocks_lock:
                    blocks, self._dev_blocks = self._dev_blocks, []
                for sb, _ in blocks:
                    sb.close()
                if not self._device_mode:
                    mgr.remove_shuffle(self._shuffle_id)
                raise
            self._map_done = True

    def fetch_blocks(self, pid: int) -> List[pa.Table]:
        """Reduce-side fetch with LOST-OUTPUT RECOVERY: a
        ShuffleFetchError that survived the block-level retry budget
        and names its owning map task re-runs ONLY that task from its
        lineage (bounded by spark.rapids.tpu.stage.maxAttempts), then
        retries the fetch — the DAGScheduler's missing-map-output
        resubmission, scoped to single tasks."""
        from spark_rapids_tpu.config import rapids_conf as rc
        from spark_rapids_tpu.runtime.errors import ShuffleFetchError

        mgr = get_shuffle_manager()
        max_att = (self.conf.get(rc.STAGE_MAX_ATTEMPTS)
                   if self.conf is not None
                   else rc.STAGE_MAX_ATTEMPTS.default)
        for att in range(max(1, max_att)):
            try:
                return mgr.fetch(self._shuffle_id, pid)
            except ShuffleFetchError as e:
                map_id = getattr(e, "map_id", None)
                if map_id is None or att + 1 >= max_att:
                    raise
                self._recompute_map_output(mgr, map_id)
        raise AssertionError("unreachable")  # pragma: no cover

    def _recompute_map_output(self, mgr, map_id: int):
        """Re-run one lost map task from lineage and atomically replace
        its blocks (identical by determinism, so reducers that already
        fetched other partitions stay consistent)."""
        from spark_rapids_tpu.runtime import scheduler as _sched

        with self._lock:  # serialize recomputes across reduce tasks
            attempt = mgr.recompute_attempt(self._shuffle_id, map_id)
            try:
                self._map_task(mgr, map_id, attempt)
            except BaseException:
                self._abort_map(mgr, map_id, attempt)
                raise
            self._commit_map(mgr, map_id, attempt, replace=True)
            _sched.stats.add("recomputedPartitions")

    def _fetch_device(self, pid) -> Iterator[ColumnBatch]:
        """Reduce-side device fetch: gather this partition's row range
        out of every HBM-resident block, coalesce on device."""
        from spark_rapids_tpu.runtime.retry import retry_on_oom

        with self._blocks_lock:
            blocks = list(self._dev_blocks)
        pieces = []
        for sb, offs in blocks:
            lo, hi = int(offs[pid]), int(offs[pid + 1])
            if hi <= lo:
                continue

            def slice_step(s=sb, lo=lo, hi=hi):
                b = s.get_batch()
                cap = next_capacity(hi - lo)
                idx = jnp.clip(jnp.arange(cap, dtype=jnp.int32) + lo,
                               0, b.capacity - 1)
                return b.gather(idx, hi - lo)

            pieces.append(retry_on_oom(slice_step))
        done = False
        with self._blocks_lock:
            self._fetches_left -= 1
            done = self._fetches_left <= 0
        if done:
            for sb, _ in blocks:
                sb.close()
        if not pieces:
            return
        merged = (concat_batches(pieces) if len(pieces) > 1
                  else pieces[0])
        # ShuffleCoalesce batch-size discipline, same as the host path
        from spark_rapids_tpu.config import rapids_conf as rc

        max_rows = (self.conf.get(rc.BATCH_SIZE_ROWS) if self.conf
                    else 1 << 20)
        total = merged.row_count()
        if total <= max_rows:
            yield merged
            return
        for off in range(0, total, max_rows):
            count = min(max_rows, total - off)
            cap = next_capacity(count)
            idx = jnp.clip(jnp.arange(cap, dtype=jnp.int32) + off, 0,
                           merged.capacity - 1)
            yield merged.gather(idx, count)

    def execute_partition(self, pid, ctx):
        # Exchanges are stage barriers: release this task's device
        # permits before blocking on the map stage, or reduce tasks
        # starve the map tasks (GpuSemaphore releaseIfNecessary-before-
        # blocking discipline, GpuShuffleExchangeExecBase)
        sem.get().release_if_necessary(ctx.task_id)
        self._run_map_stage(ctx)
        if self._device_mode:
            _acquire(ctx)
            yield from self._fetch_device(pid)
            return
        tables = self.fetch_blocks(pid)
        if not tables:
            return
        merged = pa.concat_tables(tables, promote_options="none")
        _acquire(ctx)
        # coalesce to device respecting batch size (ShuffleCoalesce)
        from spark_rapids_tpu.config import rapids_conf as rc

        max_rows = self.conf.get(rc.BATCH_SIZE_ROWS) if self.conf else 1 << 20
        for off in range(0, max(merged.num_rows, 1), max_rows):
            piece = merged.slice(off, min(max_rows,
                                          merged.num_rows - off))
            if piece.num_rows or merged.num_rows == 0:
                yield arrow_to_device(piece)
            if merged.num_rows == 0:
                break


class TpuRangeShuffleExchangeExec(TpuShuffleExchangeExec):
    """Sample-based range exchange (GpuRangePartitioner.scala +
    GpuShuffleExchangeExecBase): the map stage parks every child batch
    spillable, samples the sort keys to derive num_partitions-1 bounds,
    then range-partitions each batch by vectorized lexicographic binary
    search against the bounds. Partition p holds the p-th global key
    range, so per-partition sorts concatenate into a total order —
    global sort no longer funnels through one partition."""

    def __init__(self, child, orders: List[SortOrder], num_partitions,
                 conf, samples_per_batch: int = 64):
        super().__init__(child, None, num_partitions, conf)
        self.orders = orders
        self._samples = samples_per_batch

    def _run_map_stage(self, ctx):
        from spark_rapids_tpu.ops import sortops
        from spark_rapids_tpu.ops.common import sort_permutation
        from spark_rapids_tpu.ops.joinops import _binary_search
        from spark_rapids_tpu.runtime.memory import get_catalog
        from spark_rapids_tpu.runtime.retry import retry_on_oom

        with self._lock:
            if self._map_done:
                return
            mgr = get_shuffle_manager()
            self._shuffle_id = mgr.new_shuffle_id()
            catalog = get_catalog()
            parked = []
            # the whole map stage (parking, sampling, partitioning) must
            # clean up parked buffers + device blocks on ANY failure
            try:
                nchild = self.children[0].num_partitions
                for cpid in range(nchild):
                    for b in self.children[0].execute_partition(cpid,
                                                                ctx):
                        parked.append(retry_on_oom(
                            lambda bb=b: catalog.add_batch(bb)))
                if not parked:
                    self._map_done = True
                    return
                npt = self._nparts
                samples = None
                for sb in parked:
                    b = sb.get_batch()
                    keys = sortops.order_keys(b, self.orders)
                    s_n = min(self._samples, b.capacity)
                    pos = (jnp.arange(s_n, dtype=jnp.int32) *
                           b.capacity) // s_n
                    samp = [jnp.take(k, pos) for k in keys]
                    samples = (samp if samples is None else
                               [jnp.concatenate([a, c])
                                for a, c in zip(samples, samp)])
                total_s = int(samples[0].shape[0])
                perm = sort_permutation(samples, total_s)
                skeys = [jnp.take(g, perm) for g in samples]
                # garbage/dead sample rows carry leading null-rank 2
                live_ct = jnp.sum(skeys[0] < 2).astype(jnp.int32)
                j = jnp.clip((jnp.arange(npt - 1, dtype=jnp.int32) + 1) *
                             live_ct // npt, 0, total_s - 1)
                bounds = [jnp.take(k, j) for k in skeys]
                self._range_partition_parked(parked, bounds, npt, mgr,
                                             sortops, _binary_search)
            except BaseException:
                with self._blocks_lock:
                    blocks, self._dev_blocks = self._dev_blocks, []
                for bsb, _ in blocks:
                    bsb.close()
                for sb in parked:
                    sb.close()
                raise
            self._map_done = True

    def _range_partition_parked(self, parked, bounds, npt, mgr, sortops,
                                _binary_search):
            for sb in parked:
                b = sb.get_batch()
                keys = sortops.order_keys(b, self.orders)
                dest = _binary_search(bounds, keys, jnp.int32(npt - 1),
                                      max(npt - 1, 1), upper=True)
                pb = partition.partition_by_ids(b, dest, npt)
                offs = np.concatenate([[0],
                                       np.cumsum(np.asarray(pb.counts))])
                if self._device_mode:
                    # range map stage is single-attempt (sampling spans
                    # every child partition): blocks commit directly
                    staged: List = []
                    self._park_device_block(pb.batch, offs, staged)
                    with self._blocks_lock:
                        self._dev_blocks.extend(staged)
                    sb.close()
                    continue
                host = device_to_arrow(pb.batch)
                for rp in range(npt):
                    lo, hi = int(offs[rp]), int(offs[rp + 1])
                    if hi > lo:
                        mgr.put(self._shuffle_id, rp,
                                host.slice(lo, hi - lo))
                sb.close()


class CpuShuffleExchangeExec(PhysicalPlan):
    is_tpu = False

    def __init__(self, child, key_exprs, num_partitions, conf):
        super().__init__([child], child.schema, conf)
        self.key_exprs = key_exprs
        self._nparts = max(1, num_partitions)
        self._shuffle_id = None
        self._map_done = False
        import threading

        self._lock = threading.Lock()

    @property
    def num_partitions(self):
        return self._nparts

    def _map_task(self, mgr, cpid: int, attempt: int, ctx):
        """One deterministic CPU map-task attempt: staged, attempt-
        tagged puts — same commit-once / lost-output lineage discipline
        as the device exchange, so the CPU-oracle engine recovers
        identically."""
        for table in self.children[0].execute_partition(cpid, ctx):
            if self._nparts == 1:
                mgr.put(self._shuffle_id, 0, table,
                        map_id=cpid, attempt=attempt)
                continue
            if self.key_exprs is None:
                # round-robin (repartition(n) without keys)
                pid_arr = np.arange(table.num_rows) % self._nparts
                for rp in range(self._nparts):
                    piece = table.filter(pa.array(pid_arr == rp))
                    if piece.num_rows:
                        mgr.put(self._shuffle_id, rp, piece,
                                map_id=cpid, attempt=attempt)
                continue
            # CPU murmur3 partition matching device partitioning
            # (native murmur3_host kernel via cpu_eval when available)
            from spark_rapids_tpu.expr import Murmur3Hash

            h = cpu_eval.eval_expr(
                Murmur3Hash(*self.key_exprs), table)
            pid_arr = np.mod(np.asarray(h), self._nparts)
            pid_arr = np.where(pid_arr < 0, pid_arr + self._nparts,
                               pid_arr)
            for rp in range(self._nparts):
                mask = pa.array(pid_arr == rp)
                piece = table.filter(mask)
                if piece.num_rows:
                    mgr.put(self._shuffle_id, rp, piece,
                            map_id=cpid, attempt=attempt)

    def _run_map_stage(self, ctx):
        from spark_rapids_tpu.runtime.scheduler import (
            StageScheduler,
            Task,
        )

        with self._lock:
            if self._map_done:
                return
            mgr = get_shuffle_manager()
            self._shuffle_id = mgr.new_shuffle_id()
            nchild = self.children[0].num_partitions
            sid = self._shuffle_id
            tasks = [
                Task(c,
                     run=lambda attempt, c=c:
                         self._map_task(mgr, c, attempt, ctx),
                     commit=lambda _res, attempt, c=c:
                         mgr.commit_map_output(sid, c, attempt),
                     abort=lambda attempt, c=c:
                         mgr.discard_attempt(sid, c, attempt),
                     lineage=f"cpu-map shuffle={sid} cpid={c}")
                for c in range(nchild)]
            try:
                StageScheduler(self.conf,
                               name=f"shuffle{sid}-cpumap").run(tasks)
            except BaseException:
                mgr.remove_shuffle(sid)
                raise
            self._map_done = True

    def execute_partition(self, pid, ctx):
        from spark_rapids_tpu.config import rapids_conf as rc
        from spark_rapids_tpu.runtime import scheduler as _sched
        from spark_rapids_tpu.runtime.errors import ShuffleFetchError

        self._run_map_stage(ctx)
        mgr = get_shuffle_manager()
        max_att = (self.conf.get(rc.STAGE_MAX_ATTEMPTS)
                   if self.conf is not None
                   else rc.STAGE_MAX_ATTEMPTS.default)
        for att in range(max(1, max_att)):
            try:
                tables = mgr.fetch(self._shuffle_id, pid)
                break
            except ShuffleFetchError as e:
                map_id = getattr(e, "map_id", None)
                if map_id is None or att + 1 >= max_att:
                    raise
                with self._lock:
                    attempt = mgr.recompute_attempt(self._shuffle_id,
                                                    map_id)
                    try:
                        self._map_task(mgr, map_id, attempt, ctx)
                    except BaseException:
                        mgr.discard_attempt(self._shuffle_id, map_id,
                                            attempt)
                        raise
                    mgr.commit_map_output(self._shuffle_id, map_id,
                                          attempt, replace=True)
                    _sched.stats.add("recomputedPartitions")
        if tables:
            yield pa.concat_tables(tables, promote_options="none")


# ------------------------------------------------------------------ joins
# (join family lives in exec/joins.py; re-exported for planner use)

from spark_rapids_tpu.exec.joins import (  # noqa: E402,F401
    CpuJoinExec,
    TpuBroadcastHashJoinExec,
    TpuBroadcastNestedLoopJoinExec,
    TpuShuffledHashJoinExec,
)


# ------------------------------------------------------------------- sort

class TpuSortExec(PhysicalPlan):
    """Out-of-core sort (GpuSortExec.scala:151-633): sort each input
    batch into a spillable run, then merge runs pairwise with the
    no-resort merge kernel. Peak device residency is two runs + output;
    parked runs spill under pressure and per-run work retries/splits on
    OOM."""

    def __init__(self, orders: List[SortOrder], child, conf,
                 chunk_rows: Optional[int] = None):
        super().__init__([child], child.schema, conf)
        self.orders = orders
        self.chunk_rows = chunk_rows
        from spark_rapids_tpu.ops import sortops
        from spark_rapids_tpu.runtime.jit_cache import cached_jit, orders_key

        from spark_rapids_tpu.runtime.jit_cache import detached

        okey = orders_key(orders)
        det = detached(self)
        self._jitted = cached_jit(("sort", okey), lambda: det._run)
        self._jit_merge = cached_jit(
            ("sort_merge", okey),
            lambda: (lambda a, b, cap: sortops.merge_sorted(
                a, b, det.orders, out_cap=cap)),
            static_argnums=2)

    def _run(self, batch: ColumnBatch) -> ColumnBatch:
        from spark_rapids_tpu.ops import sortops

        return sortops.sort_batch(batch, self.orders)

    def execute_partition(self, pid, ctx):
        from spark_rapids_tpu.runtime.memory import get_catalog
        from spark_rapids_tpu.runtime.retry import retry_on_oom, with_retry

        catalog = get_catalog()
        with self.timed(M.SORT_TIME):
            runs = []  # spillable sorted runs
            for batch in self.children[0].execute_partition(pid, ctx):
                sb = retry_on_oom(lambda b=batch: catalog.add_batch(b))

                def sort_fn(s):
                    b = s.get_batch()
                    with catalog.reserved(b.device_size_bytes(),
                                          "sort_batch"):
                        return self._jitted(b)

                for run in with_retry(sb, sort_fn):
                    runs.append(retry_on_oom(
                        lambda r=run: catalog.add_batch(r)))
            if not runs:
                return
            while len(runs) > 1:
                nxt = []
                for i in range(0, len(runs) - 1, 2):
                    out_cap = next_capacity(runs[i].row_count() +
                                            runs[i + 1].row_count())

                    def step(ra=runs[i], rb=runs[i + 1], cap=out_cap):
                        a = ra.get_batch()
                        b = rb.get_batch()
                        with catalog.reserved(
                                a.device_size_bytes() +
                                b.device_size_bytes(), "sort_merge"):
                            return self._jit_merge(a, b, cap)

                    m = retry_on_oom(step)
                    runs[i].close()
                    runs[i + 1].close()
                    nxt.append(retry_on_oom(
                        lambda mm=m: catalog.add_batch(mm)))
                if len(runs) % 2:
                    nxt.append(runs[-1])
                runs = nxt
            if self.chunk_rows is None:
                out = runs[0].get_batch()
                runs[0].close()
                yield out
                return
            # chunked emission: slice the merged run into bounded
            # batches so downstream operators (batched window) never
            # hold the whole partition's intermediates
            final = runs[0]
            total = final.row_count()
            for lo in range(0, max(total, 1), self.chunk_rows):
                count = min(self.chunk_rows, total - lo)
                if count <= 0:
                    break

                def slice_step(sb=final, lo=lo, count=count):
                    b = sb.get_batch()
                    cap = next_capacity(count)
                    idx = jnp.clip(
                        jnp.arange(cap, dtype=jnp.int32) + lo, 0,
                        b.capacity - 1)
                    return b.gather(idx, count)

                yield retry_on_oom(slice_step)
            final.close()


class CpuSortExec(PhysicalPlan):
    is_tpu = False

    def __init__(self, orders, child, conf):
        super().__init__([child], child.schema, conf)
        self.orders = orders

    def execute_partition(self, pid, ctx):
        import pyarrow.compute as pc

        with self.timed(M.SORT_TIME):
            yield from self._sorted_partition(pid, ctx, pc)

    def _sorted_partition(self, pid, ctx, pc):
        tables = list(self.children[0].execute_partition(pid, ctx))
        if not tables:
            return
        table = pa.concat_tables(tables, promote_options="none")
        # arrow's null_placement is GLOBAL, but Spark's nulls_first is
        # per-key: sort each key as (is_null indicator, value) pairs —
        # the indicator groups a key's nulls where its order wants
        # them, making the global placement irrelevant
        view_cols, view_names, sort_keys = [], [], []
        for i, o in enumerate(self.orders):
            assert isinstance(o.expr, BoundReference)
            col = table.column(o.expr.ordinal)
            view_cols.append(pc.is_null(col))
            view_names.append(f"__n{i}")
            sort_keys.append((
                f"__n{i}",
                "descending" if o.nulls_first else "ascending"))
            view_cols.append(col)
            view_names.append(f"__v{i}")
            sort_keys.append((
                f"__v{i}",
                "ascending" if o.ascending else "descending"))
        view = pa.table(dict(zip(view_names, view_cols)))
        idx = pc.sort_indices(view, sort_keys=sort_keys)
        yield table.take(idx)


# ------------------------------------------------------------ limit/union

class TpuCoalesceBatchesExec(PhysicalPlan):
    """Concatenate small device batches toward a goal before the
    consumer — the GpuCoalesceBatches role (TargetSize goal of the
    lattice, GpuCoalesceBatches.scala:170-226). Sized by CAPACITY (no
    device sync per batch); a lone batch passes through untouched.

    The eager engine inserts this after chunked scans and
    repartition exchanges, where many small batches would otherwise
    each pay a per-batch dispatch; the fused and mesh
    engines treat it as identity (their stages already operate on
    whole-partition data)."""

    def __init__(self, child, conf, target_rows: Optional[int] = None):
        super().__init__([child], child.schema, conf)
        from spark_rapids_tpu.config import rapids_conf as rc

        self.target_rows = target_rows or (
            conf.get(rc.BATCH_SIZE_ROWS) if conf else 1 << 20)

    def _flush(self, pending):
        if len(pending) == 1:
            return pending[0]
        with self.timed(M.OP_TIME):
            return concat_batches(pending)

    def execute_partition(self, pid, ctx):
        pending: List[ColumnBatch] = []
        rows = 0
        for b in self.children[0].execute_partition(pid, ctx):
            pending.append(b)
            rows += b.capacity
            if rows >= self.target_rows:
                yield self._flush(pending)
                pending, rows = [], 0
        if pending:
            yield self._flush(pending)

    def _node_string(self):
        return f"TpuCoalesceBatchesExec[TargetRows({self.target_rows})]"


class TpuLocalLimitExec(PhysicalPlan):
    def __init__(self, n, child, conf):
        super().__init__([child], child.schema, conf)
        self.n = n

    def execute_partition(self, pid, ctx):
        remaining = self.n
        for batch in self.children[0].execute_partition(pid, ctx):
            if remaining <= 0:
                return
            out = filterops.slice_head(batch, remaining)
            remaining -= out.row_count()
            yield out


class CpuLocalLimitExec(PhysicalPlan):
    is_tpu = False

    def __init__(self, n, child, conf):
        super().__init__([child], child.schema, conf)
        self.n = n

    def execute_partition(self, pid, ctx):
        remaining = self.n
        for t in self.children[0].execute_partition(pid, ctx):
            if remaining <= 0:
                return
            piece = t.slice(0, min(remaining, t.num_rows))
            remaining -= piece.num_rows
            yield piece


class UnionExec(PhysicalPlan):
    """Partition-concatenating union (GpuUnionExec analog); children's
    partitions are appended."""

    def __init__(self, children, schema, conf, tpu: bool):
        super().__init__(children, schema, conf)
        self.is_tpu = tpu

    @property
    def num_partitions(self):
        return sum(c.num_partitions for c in self.children)

    def execute_partition(self, pid, ctx):
        for c in self.children:
            if pid < c.num_partitions:
                yield from c.execute_partition(pid, ctx)
                return
            pid -= c.num_partitions


# --------------------------------------------------------------- generate

class TpuGenerateExec(PhysicalPlan):
    """explode/posexplode over the padded-matrix array layout
    (GpuGenerateExec.scala analog). Two-phase data-dependent expansion:
    a count pass picks the output capacity bucket on the host, then one
    gather program materializes (row, element) pairs — the same
    discipline as the join gather maps."""

    def __init__(self, pass_through: List[Alias], gen_alias: Alias,
                 position: bool, child, conf):
        from spark_rapids_tpu.sqltypes.datatypes import integer

        fields = [StructField(a.name, a.dtype, a.nullable)
                  for a in pass_through]
        if position:
            fields.append(StructField("pos", integer, False))
        fields.append(StructField(gen_alias.name, gen_alias.dtype, True))
        super().__init__([child], StructType(fields), conf)
        self.pass_through = pass_through
        self.gen_alias = gen_alias
        self.position = position

    def _explode_to_cap(self, batch: ColumnBatch, out_cap: int,
                        _pre=None):
        """Trace-safe explode into a static capacity; returns
        (batch, overflow) — shared by the eager path (exact capacity,
        which passes its sizing-pass results via _pre to avoid a second
        evaluation of the array expression) and the mesh SPMD lowering
        (static + recompile-on-overflow)."""
        from spark_rapids_tpu.ops import joinops
        from spark_rapids_tpu.sqltypes.datatypes import integer

        if _pre is None:
            ectx = EvalContext(batch)
            arr = self.gen_alias.children[0].children[0].eval(ectx)
            counts = jnp.where(batch.live_mask() & arr.validity,
                               arr.lengths, 0).astype(jnp.int32)
        else:
            ectx, arr, counts = _pre
        lo = jnp.zeros((batch.capacity,), jnp.int32)
        pi, ei, total = joinops.expand_gather_maps(lo, counts, out_cap)
        overflow = total > out_cap
        cols = [a.eval(ectx).gather(pi) for a in self.pass_through]
        if self.position:
            cols.append(DeviceColumn(
                integer, ei.astype(jnp.int32),
                jnp.ones((out_cap,), bool)))
        safe_e = jnp.clip(ei, 0, arr.data.shape[1] - 1)
        vals = arr.data[pi, safe_e]
        ev = arr.elem_validity[pi, safe_e]
        if arr.elem_lengths is not None:
            # array<string>: elements become a padded string column
            cols.append(DeviceColumn(
                self.gen_alias.dtype, vals, ev,
                arr.elem_lengths[pi, safe_e]))
        else:
            cols.append(DeviceColumn(self.gen_alias.dtype, vals, ev))
        out = ColumnBatch(self.schema, cols,
                          jnp.minimum(total, out_cap))
        return out, overflow

    def _explode_batch(self, batch: ColumnBatch) -> ColumnBatch:
        from spark_rapids_tpu.runtime.memory import get_catalog

        ectx = EvalContext(batch)
        arr = self.gen_alias.children[0].children[0].eval(ectx)
        counts = jnp.where(batch.live_mask() & arr.validity,
                           arr.lengths, 0).astype(jnp.int32)
        from spark_rapids_tpu.obs import telemetry

        total = int(telemetry.ledgered_get(jnp.sum(counts),
                                           "generate.counts"))
        cap_out = next_capacity(max(total, 1))
        row_bytes = batch.device_size_bytes() // max(1, batch.capacity)
        with get_catalog().reserved(cap_out * (row_bytes + 16),
                                    "generate"):
            out, _ovf = self._explode_to_cap(batch, cap_out,
                                             _pre=(ectx, arr, counts))
            return out

    def execute_partition(self, pid, ctx):
        from spark_rapids_tpu.runtime.retry import retry_on_oom

        for batch in self.children[0].execute_partition(pid, ctx):
            out = retry_on_oom(lambda b=batch: self._explode_batch(b))
            if out.row_count() > 0:
                yield out


class CpuGenerateExec(PhysicalPlan):
    is_tpu = False

    def __init__(self, pass_through, gen_alias, position, child, conf):
        from spark_rapids_tpu.sqltypes.datatypes import integer

        fields = [StructField(a.name, a.dtype, a.nullable)
                  for a in pass_through]
        if position:
            fields.append(StructField("pos", integer, False))
        fields.append(StructField(gen_alias.name, gen_alias.dtype, True))
        super().__init__([child], StructType(fields), conf)
        self.pass_through = pass_through
        self.gen_alias = gen_alias
        self.position = position

    def execute_partition(self, pid, ctx):
        import pyarrow.compute as pc

        for table in self.children[0].execute_partition(pid, ctx):
            arr = cpu_eval.eval_expr(
                self.gen_alias.children[0].children[0],
                table).combine_chunks()
            parent = pc.list_parent_indices(arr)
            flat = pc.list_flatten(arr)
            arrays = []
            names = []
            for a in self.pass_through:
                arrays.append(cpu_eval.eval_expr(a, table)
                              .combine_chunks().take(parent))
                names.append(a.name)
            if self.position:
                p = np.asarray(parent)
                pos = np.arange(len(p)) - np.searchsorted(p, p,
                                                          side="left")
                arrays.append(pa.array(pos.astype(np.int32)))
                names.append("pos")
            arrays.append(flat)
            names.append(self.gen_alias.name)
            yield pa.Table.from_arrays(arrays, names=names)


# ----------------------------------------------------------------- window

def window_streaming_mode(window_exprs: List[Alias]) -> Optional[str]:
    """Streaming strategy for specs the bounded-halo path can't chunk
    (round-4 verdict item #6; reference GpuRunningWindowExec.scala +
    GpuUnboundedToUnboundedAggWindowExec.scala):

    - "running": every expression is row_number/rank/dense_rank or a
      sum/min/max/count over ROWS UNBOUNDED PRECEDING..CURRENT ROW —
      chunks evaluate independently and a carried per-partition state
      fixes up the prefix that continues the previous chunk's
      partition (the scan-fixer pattern).
    - "u2u": every expression is a jittable aggregate over the WHOLE
      partition (unbounded..unbounded, or no frame and no order) —
      two passes: per-chunk partial aggregation by partition key, then
      a re-scan joining each row to its partition's result.

    None -> whole-partition materialization remains the fallback."""
    from spark_rapids_tpu.expr import windows as we
    from spark_rapids_tpu.expr.aggregates import (
        AggregateFunction,
        Count,
        First,
        Max,
        Min,
        Sum,
    )

    spec = window_exprs[0].children[0].spec
    fixed_width_keys = all(
        getattr(e.dtype, "np_dtype", None) is not None
        and not isinstance(e.dtype, StringType)
        for e in (list(spec.partitions) +
                  [o.expr for o in spec.orders]))
    kinds = set()
    for a in window_exprs:
        wexpr = a.children[0]
        fn = wexpr.function
        frame = wexpr.spec.frame
        if isinstance(fn, (we.RowNumber, we.Rank, we.DenseRank)):
            kinds.add("running")
            continue
        if not isinstance(fn, AggregateFunction) or not fn.jittable:
            return None
        if isinstance(fn, First):
            # first/last are ORDER-sensitive; the two-pass aggregate
            # sees chunk-arrival order, not the spec's ORDER BY
            return None
        whole = (frame is not None and frame.lower is None
                 and frame.upper is None) or (
            frame is None and not wexpr.spec.orders)
        if whole:
            kinds.add("u2u")
            continue
        from spark_rapids_tpu.ops import decimal128 as d128

        if (isinstance(fn, (Sum, Min, Max, Count))
                and frame is not None and frame.frame_type == "rows"
                and frame.lower is None and frame.upper == 0
                and not d128.is_wide(fn.dtype)  # 2-limb carry shapes
                and all(getattr(c.dtype, "np_dtype", None) is not None
                        and not isinstance(c.dtype, StringType)
                        and not d128.is_wide(c.dtype)
                        for c in fn.children)):
            kinds.add("running")
            continue
        return None
    if kinds == {"running"}:
        # the carried key state is fixed-shape 1-row arrays; variable-
        # width (string) keys change shape across chunks
        return "running" if fixed_width_keys else None
    if kinds == {"u2u"}:
        return "u2u"
    return None  # mixed specs keep the whole-partition path


def window_halo(window_exprs: List[Alias]) -> Optional[int]:
    """Rows of context a chunked window evaluation needs on each side, or
    None when the spec is not chunkable (ranking / running / unbounded /
    RANGE frames need whole-partition or carried state). Chunkable: ROWS
    frames with finite bounds, and lead/lag (bounded by |offset|) — the
    GpuBatchedBoundedWindowExec case."""
    from spark_rapids_tpu.expr import windows as we

    halo = 0
    for a in window_exprs:
        wexpr = a.children[0]
        fn = wexpr.function
        frame = wexpr.spec.frame
        if isinstance(fn, we.Lead):  # Lag subclasses Lead
            halo = max(halo, abs(fn.offset))
            continue
        if isinstance(fn, we.WindowFunction):
            return None  # ranking family: needs partition-prefix state
        if (frame is None or frame.frame_type != "rows" or
                frame.lower is None or frame.upper is None):
            return None
        halo = max(halo, abs(frame.lower), abs(frame.upper))
    return halo


class TpuWindowExec(PhysicalPlan):
    """Window operator (GpuWindowExec analog, window/GpuWindowExecMeta
    .scala:673): one sorted pass per (partitionBy, orderBy) spec
    evaluates every frame/function in a single XLA program — prefix sums
    for sum/count frames, a doubling sparse table for min/max frames,
    binary search for RANGE value bounds (ops/windowops.py). Input rows
    are preserved; window columns are appended.

    With presorted=True + halo=H (planner pairs this exec with a chunked
    TpuSortExec on the partition+order keys), execution is BATCHED: each
    sorted chunk is evaluated with H rows of carried prefix and H rows of
    peeked suffix, so device intermediates are bounded by the chunk size
    instead of the whole partition (GpuBatchedBoundedWindowExec.scala
    role)."""

    def __init__(self, window_exprs: List[Alias], child, conf,
                 presorted: bool = False, halo: Optional[int] = None,
                 mode: Optional[str] = None):
        from spark_rapids_tpu.expr import windows as we

        base = child.schema
        extra = [StructField(a.name, a.dtype, True) for a in window_exprs]
        super().__init__([child], StructType(list(base.fields) + extra),
                         conf)
        self.window_exprs = window_exprs
        self.presorted = presorted
        self.halo = halo
        self.mode = mode  # None | "running" | "u2u" (streaming paths)
        self.spec0: we.WindowSpecDef = window_exprs[0].children[0].spec
        from spark_rapids_tpu.runtime.jit_cache import aliases_key, cached_jit

        self._jitted = cached_jit(
            ("window", aliases_key(window_exprs)),
            lambda: __import__("spark_rapids_tpu.runtime.jit_cache",
                               fromlist=["detached"]).detached(self)._run)

    def _run(self, batch: ColumnBatch) -> ColumnBatch:
        from spark_rapids_tpu.expr import aggregates as AGG
        from spark_rapids_tpu.expr import windows as we
        from spark_rapids_tpu.expr.aggregates import (
            Average, Count, First, Max, Min, Sum,
        )
        from spark_rapids_tpu.ops import windowops as W
        from spark_rapids_tpu.sqltypes import StringType

        ctx = EvalContext(batch)
        spec0 = self.spec0
        part_cols = [p.eval(ctx) for p in spec0.partitions]
        order_cols = [(o.expr.eval(ctx), o.ascending, o.nulls_first)
                      for o in spec0.orders]
        sw = W.sort_for_window(batch, part_cols, order_cols)
        has_order = bool(spec0.orders)
        cap = batch.capacity
        new_cols: List[DeviceColumn] = []

        def to_original(data, valid):
            return (jnp.take(data, sw.inv, axis=0),
                    jnp.take(valid, sw.inv))

        for alias in self.window_exprs:
            wexpr: we.WindowExpression = alias.children[0]
            fn = wexpr.function
            frame = wexpr.spec.frame
            dt = wexpr.dtype

            if isinstance(fn, we.RowNumber):
                d, v = W.row_number(sw), jnp.ones((cap,), bool)
            elif isinstance(fn, we.Rank):
                d, v = W.rank(sw), jnp.ones((cap,), bool)
            elif isinstance(fn, we.DenseRank):
                d, v = W.dense_rank(sw), jnp.ones((cap,), bool)
            elif isinstance(fn, we.PercentRank):
                d, v = W.percent_rank(sw), jnp.ones((cap,), bool)
            elif isinstance(fn, we.CumeDist):
                d, v = W.cume_dist(sw), jnp.ones((cap,), bool)
            elif isinstance(fn, we.NTile):
                d, v = W.ntile(sw, fn.n), jnp.ones((cap,), bool)
            elif isinstance(fn, we.Lead):  # Lag subclasses Lead
                col = fn.input.eval(ctx)
                sorted_col = col.gather(sw.perm)
                vals, ok, inside = W.lead_lag(
                    sorted_col.data, sorted_col.validity, sw, fn.offset)

                def shifted(leaf):
                    return W.lead_lag(leaf, sorted_col.validity, sw,
                                      fn.offset)[0]

                from spark_rapids_tpu.columnar.batch import row_select \
                    as row_sel

                lens = (None if sorted_col.lengths is None
                        else shifted(sorted_col.lengths))
                ev = (None if sorted_col.elem_validity is None
                      else shifted(sorted_col.elem_validity))
                el = (None if sorted_col.elem_lengths is None
                      else shifted(sorted_col.elem_lengths))
                if fn.default is not None:
                    dcol = fn.default.eval(ctx).gather(sw.perm)
                    vals = row_sel(inside, vals, dcol.data)
                    ok = jnp.where(inside, ok, dcol.validity)
                    if lens is not None:
                        lens = jnp.where(inside, lens, dcol.lengths)
                    if ev is not None:
                        ev = row_sel(inside, ev, dcol.elem_validity)
                    if el is not None:
                        el = row_sel(inside, el, dcol.elem_lengths)
                d_o, v_o = to_original(vals, ok)
                lens_o = None if lens is None else jnp.take(lens, sw.inv)
                new_cols.append(DeviceColumn(
                    dt, d_o, v_o, lens_o,
                    None if ev is None
                    else jnp.take(ev, sw.inv, axis=0),
                    elem_lengths=None if el is None
                    else jnp.take(el, sw.inv, axis=0)))
                continue
            else:
                # aggregate over frames
                inp = fn.input.eval(ctx) if fn.input is not None else None
                inp_s = inp.gather(sw.perm) if inp is not None else None
                if frame is None:
                    start, end = W.default_frame_bounds(sw, has_order)
                elif frame.frame_type == "rows":
                    start, end = W.rows_frame_bounds(sw, frame.lower,
                                                     frame.upper)
                else:
                    oc_s = order_cols[0][0].gather(sw.perm)
                    start, end = W.range_frame_bounds(
                        sw, oc_s, W.segment_ids_sorted(sw),
                        frame.lower, frame.upper,
                        nulls_first=spec0.orders[0].nulls_first)
                if isinstance(fn, Count):
                    valid_s = (inp_s.validity if inp_s is not None
                               else jnp.ones((cap,), bool))
                    d = W.frame_count(valid_s, sw, start, end)
                    v = jnp.ones((cap,), bool)
                elif isinstance(fn, Sum):
                    cnt = W.frame_count(inp_s.validity, sw, start, end)
                    d = W.frame_sum(inp_s.data, inp_s.validity, sw, start,
                                    end, dt.np_dtype)
                    v = cnt > 0
                elif isinstance(fn, Average):
                    cnt = W.frame_count(inp_s.validity, sw, start, end)
                    s = W.frame_sum(inp_s.data, inp_s.validity, sw, start,
                                    end, jnp.float64)
                    d = s / jnp.maximum(cnt, 1).astype(jnp.float64)
                    v = cnt > 0
                elif isinstance(fn, (Min, Max)):
                    cnt = W.frame_count(inp_s.validity, sw, start, end)
                    d = W.frame_minmax(inp_s.data, inp_s.validity, sw,
                                       start, end, isinstance(fn, Max))
                    d = d.astype(inp_s.data.dtype)
                    v = cnt > 0
                elif isinstance(fn, First):  # Last subclasses First
                    from spark_rapids_tpu.expr.aggregates import Last

                    is_last = isinstance(fn, Last)
                    d, v = W.frame_first_last(
                        inp_s.data, inp_s.validity, sw, start, end,
                        last=is_last, ignore_nulls=fn.ignore_nulls)
                    if isinstance(dt, StringType):
                        lens, _ = W.frame_first_last(
                            inp_s.lengths, inp_s.validity, sw, start, end,
                            last=is_last, ignore_nulls=fn.ignore_nulls)
                        d_o, v_o = to_original(d, v)
                        new_cols.append(DeviceColumn(
                            dt, d_o, v_o, jnp.take(lens, sw.inv)))
                        continue
                elif isinstance(fn, (AGG.VariancePop, AGG.VarianceSamp)):
                    # moments over frames from prefix sums: the device
                    # RollingAggregation analog (GpuWindowExpression
                    # moment family); StddevPop/Samp subclass these
                    f64 = inp_s.data.astype(jnp.float64)
                    cnt = W.frame_count(inp_s.validity, sw, start, end)
                    n = cnt.astype(jnp.float64)
                    s1 = W.frame_sum(f64, inp_s.validity, sw, start,
                                     end, jnp.float64)
                    s2 = W.frame_sum(f64 * f64, inp_s.validity, sw,
                                     start, end, jnp.float64)
                    m2 = jnp.maximum(s2 - s1 * (s1 / jnp.maximum(n, 1.0)),
                                     0.0)
                    if isinstance(fn, AGG.VarianceSamp):
                        d = m2 / jnp.maximum(n - 1.0, 1.0)
                        v = cnt >= 2
                    else:
                        d = m2 / jnp.maximum(n, 1.0)
                        v = cnt >= 1
                    if isinstance(fn, (AGG.StddevPop, AGG.StddevSamp)):
                        d = jnp.sqrt(d)
                elif isinstance(fn, AGG.CollectList):  # CollectSet too
                    d, v, lens, ev = W.frame_collect(
                        inp_s.data, inp_s.validity, sw, start, end,
                        frame, distinct=isinstance(fn, AGG.CollectSet))
                    d_o, v_o = to_original(d, v)
                    new_cols.append(DeviceColumn(
                        dt, d_o, v_o, jnp.take(lens, sw.inv),
                        jnp.take(ev, sw.inv, axis=0)))
                    continue
                else:
                    raise NotImplementedError(
                        f"window function {type(fn).__name__}")
            d_o, v_o = to_original(d, v)
            new_cols.append(DeviceColumn(dt, d_o, v_o))
        return ColumnBatch(self.schema, list(batch.columns) + new_cols,
                           batch.num_rows)

    def execute_partition(self, pid, ctx):
        with self.timed(M.WINDOW_TIME):
            _acquire(ctx)
            if self.presorted and self.halo is not None:
                yield from self._execute_batched(pid, ctx)
                return
            if self.mode == "running":
                yield from self._execute_running(pid, ctx)
                return
            if self.mode == "u2u":
                yield from self._execute_u2u(pid, ctx)
                return
            from spark_rapids_tpu.runtime.memory import get_catalog
            from spark_rapids_tpu.runtime.retry import retry_on_oom

            catalog = get_catalog()
            pending = []
            for batch in self.children[0].execute_partition(pid, ctx):
                pending.append(retry_on_oom(
                    lambda b=batch: catalog.add_batch(b)))
            if not pending:
                return

            def step():
                batches = [sb.get_batch() for sb in pending]
                merged = concat_batches(batches) if len(batches) > 1 \
                    else batches[0]
                with catalog.reserved(2 * merged.device_size_bytes(),
                                      "window_concat"):
                    return self._jitted(merged)

            out = retry_on_oom(step)
            for sb in pending:
                sb.close()
            yield out

    # --- bounded-frame batched path ---

    @staticmethod
    def _slice_rows(batch: ColumnBatch, start: int, count: int
                    ) -> ColumnBatch:
        cap = next_capacity(count)
        idx = jnp.clip(jnp.arange(cap, dtype=jnp.int32) + start, 0,
                       batch.capacity - 1)
        return batch.gather(idx, count)

    def _window_chunk(self, prefix: Optional[ColumnBatch],
                      chunk: ColumnBatch,
                      suffix: Optional[ColumnBatch]) -> ColumnBatch:
        """Evaluate one sorted chunk with halo context and slice out the
        chunk's own rows. Input order == sorted order (the child is a
        chunked TpuSortExec), so row positions survive the exec's stable
        internal sort."""
        parts = [p for p in (prefix, chunk, suffix) if p is not None]
        merged = concat_batches(parts) if len(parts) > 1 else parts[0]
        out = self._jitted(merged)
        start = prefix.row_count() if prefix is not None else 0
        return self._slice_rows(out, start, chunk.row_count())

    def _execute_batched(self, pid, ctx):
        from spark_rapids_tpu.runtime.memory import get_catalog
        from spark_rapids_tpu.runtime.retry import retry_on_oom

        catalog = get_catalog()
        h = max(self.halo, 1)
        prefix: Optional[ColumnBatch] = None  # last h rows seen
        pending: Optional[ColumnBatch] = None  # chunk awaiting suffix
        for batch in self.children[0].execute_partition(pid, ctx):
            if pending is not None:
                suffix = self._slice_rows(
                    batch, 0, min(h, batch.row_count()))
                yield retry_on_oom(
                    lambda p=prefix, c=pending, s=suffix:
                    self._window_chunk(p, c, s))
                joined = (concat_batches([prefix, pending])
                          if prefix is not None else pending)
                tail_n = min(h, joined.row_count())
                prefix = self._slice_rows(
                    joined, joined.row_count() - tail_n, tail_n)
            pending = batch
        if pending is not None:
            yield retry_on_oom(
                lambda p=prefix, c=pending: self._window_chunk(p, c, None))

    # --- running-window streaming path (GpuRunningWindowExec role) ---

    def _running_plan(self):
        """Static fixer plan: per window expr, how the carried state
        adjusts the in-chunk value."""
        from spark_rapids_tpu.expr import windows as we
        from spark_rapids_tpu.expr.aggregates import Count, Max, Min, Sum

        plan = []
        for a in self.window_exprs:
            fn = a.children[0].function
            if isinstance(fn, we.RowNumber):
                plan.append("rownum")
            elif isinstance(fn, we.DenseRank):
                plan.append("dense")
            elif isinstance(fn, we.Rank):
                plan.append("rank")
            elif isinstance(fn, Count):
                plan.append("count")
            elif isinstance(fn, Sum):
                plan.append("sum")
            elif isinstance(fn, Min):
                plan.append("min")
            else:
                assert isinstance(fn, Max), fn
                plan.append("max")
        return plan

    @staticmethod
    def _rows_eq(col: DeviceColumn, ref_data, ref_valid) -> jnp.ndarray:
        """Per-row null-safe equality of a key column against a 1-row
        carried reference (null == null, and NaN == NaN — partition
        membership uses the sort's total order, where NaNs group)."""
        d = col.data
        if d.ndim == 2:
            eq = jnp.all(d == ref_data, axis=1)
        else:
            r = ref_data.reshape(())
            eq = d == r
            if jnp.issubdtype(d.dtype, jnp.floating):
                eq = eq | (jnp.isnan(d) & jnp.isnan(r))
        both_null = ~col.validity & ~ref_valid.reshape(())
        return both_null | (col.validity & ref_valid.reshape(()) & eq)

    def _running_fix(self, out: ColumnBatch, carry: dict):
        """Traced: adjust the prefix of a sorted chunk that continues
        the carried partition, then refresh the carry from the chunk's
        last row. All state stays on device (1-row arrays)."""
        ctx = EvalContext(out)
        spec = self.spec0
        nbase = len(self.schema.fields) - len(self.window_exprs)
        live = out.live_mask()
        nr = jnp.asarray(out.num_rows, jnp.int32).reshape(())
        last = jnp.maximum(nr - 1, 0)

        pcols = [p.eval(ctx) for p in spec.partitions]
        ocols = [o.expr.eval(ctx) for o in spec.orders]
        mask = live & carry["live"].reshape(())
        for i, c in enumerate(pcols):
            mask = mask & self._rows_eq(c, carry[f"pk{i}"],
                                        carry[f"pkv{i}"])
        peer = mask
        for j, c in enumerate(ocols):
            peer = peer & self._rows_eq(c, carry[f"ok{j}"],
                                        carry[f"okv{j}"])

        plan = self._running_plan()
        new_cols = list(out.columns)
        for i, kind in enumerate(plan):
            col = out.columns[nbase + i]
            cv, cvv = carry[f"v{i}"], carry[f"vv{i}"]
            cvs = cv.reshape(cv.shape[1:]) if cv.ndim > 1 else \
                cv.reshape(())
            cvvs = cvv.reshape(())
            if kind == "rownum":
                d = jnp.where(mask, col.data + carry["n"].reshape(()),
                              col.data).astype(col.data.dtype)
                col = col.replace(data=d)
            elif kind == "rank":
                shifted = col.data + carry["n"].reshape(())
                d = jnp.where(peer, cvs.astype(shifted.dtype), shifted)
                col = col.replace(data=jnp.where(
                    mask, d, col.data).astype(col.data.dtype))
            elif kind == "dense":
                # the chunk's first distinct order-group continues the
                # carried group iff the first masked row is a peer
                first_peer = jnp.any(peer & (jnp.cumsum(
                    mask.astype(jnp.int32)) == 1))
                off = cvs - jnp.where(first_peer, 1, 0)
                col = col.replace(data=jnp.where(
                    mask, col.data + off, col.data)
                    .astype(col.data.dtype))
            elif kind == "count":
                col = col.replace(data=jnp.where(
                    mask & cvvs, col.data + cvs.astype(col.data.dtype),
                    col.data))
            else:  # sum / min / max with null-skipping combine
                both = mask & cvvs & col.validity
                c_only = mask & cvvs & ~col.validity
                if kind == "sum":
                    comb = col.data + cvs.astype(col.data.dtype)
                elif kind == "min":
                    comb = jnp.minimum(col.data,
                                       cvs.astype(col.data.dtype))
                else:
                    comb = jnp.maximum(col.data,
                                       cvs.astype(col.data.dtype))
                d = jnp.where(both, comb,
                              jnp.where(c_only,
                                        cvs.astype(col.data.dtype),
                                        col.data))
                col = col.replace(data=d,
                                  validity=col.validity | (mask & cvvs))
            new_cols[nbase + i] = col
        fixed = ColumnBatch(out.schema, new_cols, out.num_rows)

        # refresh the carry from the FIXED chunk's last row
        has = nr > 0

        def keep(new, old):
            return jnp.where(has, new, old)

        nc = dict(carry)
        nc["live"] = keep(jnp.ones((1,), bool), carry["live"])
        for i, c in enumerate(pcols):
            nc[f"pk{i}"] = keep(
                jnp.take(c.data, last, axis=0)[None], carry[f"pk{i}"])
            nc[f"pkv{i}"] = keep(jnp.take(c.validity, last)[None],
                                 carry[f"pkv{i}"])
        for j, c in enumerate(ocols):
            nc[f"ok{j}"] = keep(
                jnp.take(c.data, last, axis=0)[None], carry[f"ok{j}"])
            nc[f"okv{j}"] = keep(jnp.take(c.validity, last)[None],
                                 carry[f"okv{j}"])
        # rows so far in the last row's partition
        in_last = live
        for i, c in enumerate(pcols):
            in_last = in_last & self._rows_eq(
                c, jnp.take(c.data, last, axis=0),
                jnp.take(c.validity, last)[None])
        cnt = jnp.sum(in_last).astype(jnp.int64)
        cont = jnp.take(mask, last)  # last row still in carry partition
        nc["n"] = keep((cnt + jnp.where(cont, carry["n"].reshape(()),
                                        0))[None], carry["n"])
        for i, kind in enumerate(plan):
            col = fixed.columns[nbase + i]
            nc[f"v{i}"] = keep(jnp.take(col.data, last, axis=0)[None],
                               carry[f"v{i}"])
            nc[f"vv{i}"] = keep(jnp.take(col.validity, last)[None],
                                carry[f"vv{i}"])
        return fixed, nc

    def _running_init_carry(self, batch: ColumnBatch) -> dict:
        """Zero carry matching the chunk's key/value shapes."""
        ctx = EvalContext(batch)
        spec = self.spec0
        nbase = len(self.schema.fields) - len(self.window_exprs)
        carry = {"live": jnp.zeros((1,), bool),
                 "n": jnp.zeros((1,), jnp.int64)}

        def z(c):
            return (jnp.zeros((1,) + c.data.shape[1:], c.data.dtype),
                    jnp.zeros((1,), bool))

        for i, p in enumerate(spec.partitions):
            carry[f"pk{i}"], carry[f"pkv{i}"] = z(p.eval(ctx))
        for j, o in enumerate(spec.orders):
            carry[f"ok{j}"], carry[f"okv{j}"] = z(o.expr.eval(ctx))
        for i, a in enumerate(self.window_exprs):
            f = self.schema.fields[nbase + i]
            np_dt = f.dataType.np_dtype
            carry[f"v{i}"] = jnp.zeros((1,), np_dt)
            carry[f"vv{i}"] = jnp.zeros((1,), bool)
        return carry

    def _execute_running(self, pid, ctx):
        """Sorted chunks + carried per-partition scan state: device
        residency stays O(chunk) while ranking/running frames stay
        exact across chunk boundaries."""
        from spark_rapids_tpu.runtime.jit_cache import (
            aliases_key,
            cached_jit,
            detached,
        )
        from spark_rapids_tpu.runtime.retry import retry_on_oom

        det = detached(self)

        def step(batch, carry):
            return det._running_fix(det._run(batch), carry)

        # cached_jit returns a jax.jit wrapper that retraces per input
        # shape, so the key needs no shape component
        jitted = cached_jit(
            ("window_running", aliases_key(self.window_exprs)),
            lambda: step)
        carry = None
        for batch in self.children[0].execute_partition(pid, ctx):
            if carry is None:
                carry = self._running_init_carry(batch)
            out, carry = retry_on_oom(
                lambda b=batch, c=carry: jitted(b, c))
            yield out

    # --- unbounded-to-unbounded two-pass path ---

    @staticmethod
    def _null_safe_keys(batch: ColumnBatch, key_cols):
        """Append [IsNull marker, zero-filled value] per key column so
        null partitions probe-match their own group (the engine's join
        probe drops null keys; zero-filling invalid rows plus the
        marker makes every key column non-null while preserving
        distinctness). -> (work batch, key ordinals)."""
        from spark_rapids_tpu.sqltypes.datatypes import boolean

        cols = list(batch.columns)
        fields = list(batch.schema.fields)
        idxs = []
        for k, c in enumerate(key_cols):
            isn = DeviceColumn(boolean, ~c.validity,
                               jnp.ones((c.capacity,), bool))
            vb = (c.validity[:, None] if c.data.ndim == 2
                  else c.validity)
            coal = c.replace(
                data=jnp.where(vb, c.data, jnp.zeros_like(c.data)),
                validity=jnp.ones((c.capacity,), bool),
                lengths=None if c.lengths is None
                else jnp.where(c.validity, c.lengths, 0))
            idxs.append(len(cols))
            cols.append(isn)
            fields.append(StructField(f"__wn{k}", boolean, False))
            idxs.append(len(cols))
            cols.append(coal)
            fields.append(StructField(f"__wv{k}", c.dtype, False))
        return (ColumnBatch(StructType(fields), cols, batch.num_rows),
                idxs)

    def _execute_u2u(self, pid, ctx):
        """Two passes (GpuUnboundedToUnboundedAggWindowExec role):
        (1) park chunks in the spill catalog while folding per-chunk
        partition partials into one bounded buffer batch; (2) finalize
        the aggregates and re-scan the parked chunks, each row looking
        up its partition's result (null-safe key probe). Device
        residency is O(chunk + #partitions), never the whole input."""
        from spark_rapids_tpu.ops import joinops
        from spark_rapids_tpu.runtime.memory import get_catalog
        from spark_rapids_tpu.runtime.retry import retry_on_oom

        catalog = get_catalog()
        spec = self.spec0
        grouping = [Alias(p, f"__wk{i}")
                    for i, p in enumerate(spec.partitions)]
        aggs = [Alias(a.children[0].function, a.name)
                for a in self.window_exprs]
        child = self.children[0]
        agg = TpuHashAggregateExec("partial", grouping, aggs, child,
                                   self.conf)
        parked, pend_parts = [], []
        partials = None

        def fold_partials():
            """Fold parked per-chunk partials into one buffer batch —
            batched (every FOLD_EVERY chunks) so the concat's host
            sync and the full-buffer re-merge amortize."""
            nonlocal partials
            if not pend_parts:
                return
            bs = [] if partials is None else [partials]
            bs += [retry_on_oom(sb.get_batch) for sb in pend_parts]
            partials = retry_on_oom(
                lambda: agg._jit_merge_buffers(concat_batches(bs)))
            while pend_parts:
                pend_parts.pop().close()

        from spark_rapids_tpu.config import rapids_conf as _rc

        FOLD_EVERY = (self.conf.get(_rc.WINDOW_U2U_FOLD)
                      if self.conf is not None else 8)
        try:
            for batch in child.execute_partition(pid, ctx):
                parked.append(retry_on_oom(
                    lambda b=batch: catalog.add_batch(b)))
                p = retry_on_oom(lambda b=batch: agg._jit_partial(b))
                pend_parts.append(retry_on_oom(
                    lambda pp=p: catalog.add_batch(pp)))
                if len(pend_parts) >= FOLD_EVERY:
                    fold_partials()
            if not parked:
                return
            fold_partials()
            # a FINAL-mode twin evaluates buffers -> results (its
            # schema is the result layout; the partial node's is the
            # buffer layout)
            agg_f = TpuHashAggregateExec("final", grouping, aggs,
                                         child, self.conf)
            final = retry_on_oom(
                lambda: agg_f._jit_merge(partials))  # [keys, results]
            nk = len(grouping)
            build = None
            if nk:
                fwork, fidx = self._null_safe_keys(
                    final, [final.columns[i] for i in range(nk)])
                build = retry_on_oom(
                    lambda: joinops.build_side(fwork, fidx))

            while parked:
                sb = parked[0]
                b = retry_on_oom(sb.get_batch)
                if nk:
                    ctx2 = EvalContext(b)
                    key_cols = [g.children[0].eval(ctx2)
                                for g in grouping]
                    pwork, pidx = self._null_safe_keys(b, key_cols)
                    lo, counts = retry_on_oom(
                        lambda: joinops.probe_ranges(build, pwork,
                                                     pidx))
                    safe = jnp.clip(lo, 0, build.batch.capacity - 1)
                    src = build.batch
                    matched = counts > 0
                else:
                    # single global partition: broadcast row 0
                    safe = jnp.zeros((b.capacity,), jnp.int32)
                    src = final
                    matched = jnp.ones((b.capacity,), bool)
                res_cols = []
                for i in range(len(self.window_exprs)):
                    rc = src.columns[nk + i].gather(safe)
                    res_cols.append(rc.replace(
                        validity=rc.validity & matched))
                out = ColumnBatch(self.schema,
                                  list(b.columns) + res_cols,
                                  b.num_rows)
                parked.pop(0).close()
                yield out
        finally:
            # early exit (LIMIT-closed generator, OOM escalation) must
            # not leak parked spillables for the query lifetime
            for sb in parked + pend_parts:
                try:
                    sb.close()
                except Exception:
                    pass


class CpuWindowExec(PhysicalPlan):
    """Brute-force window oracle over arrow tables (per-row frame scan) —
    intentionally simple; it is the differential-test truth, not a fast
    path."""

    is_tpu = False

    def __init__(self, window_exprs: List[Alias], child, schema, conf):
        super().__init__([child], schema, conf)
        self.window_exprs = window_exprs

    def execute_partition(self, pid, ctx):
        with self.timed(M.WINDOW_TIME):
            tables = list(self.children[0].execute_partition(pid, ctx))
            if not tables:
                return
            table = pa.concat_tables(tables, promote_options="none")
            yield self._compute(table)

    def _compute(self, table: pa.Table) -> pa.Table:
        from spark_rapids_tpu.exec.window_oracle import compute_windows

        return compute_windows(table, self.window_exprs)
