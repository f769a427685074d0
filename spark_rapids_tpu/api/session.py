"""TpuSparkSession — the plugin lifecycle + session entry point.

Covers the responsibilities of the reference's driver/executor plugins
(`Plugin.scala:412-684`): validate the device, initialize the memory
pool/spill catalog (GpuDeviceManager.initializeGpuAndMemory), install the
semaphore with the configured concurrency, and expose conf + read/write
entry points. As a standalone engine it also owns what Spark itself would:
session state, DataFrame creation, and the reader API.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

import pyarrow as pa

from spark_rapids_tpu.config import rapids_conf as rc


class TpuSparkSessionBuilder:
    def __init__(self):
        self._conf: Dict[str, object] = {}

    def config(self, key: str, value) -> "TpuSparkSessionBuilder":
        self._conf[key] = value
        return self

    def master(self, _: str) -> "TpuSparkSessionBuilder":
        return self

    def appName(self, _: str) -> "TpuSparkSessionBuilder":
        return self

    def getOrCreate(self) -> "TpuSparkSession":
        return TpuSparkSession(self._conf)


class DataFrameReader:
    def __init__(self, session: "TpuSparkSession"):
        self.session = session
        self._options: Dict[str, object] = {}
        self._schema = None
        self._format = "parquet"

    def option(self, k, v):
        self._options[k] = v
        return self

    def schema(self, s):
        if not hasattr(s, "fields"):
            # a pyarrow.Schema normalizes to the engine StructType here
            # so every format reader sees one schema shape
            from spark_rapids_tpu.columnar.arrow_bridge import (
                schema_from_arrow,
            )

            s = schema_from_arrow(s)
        self._schema = s
        return self

    def format(self, fmt: str):
        self._format = fmt
        return self

    def load(self, path: str):
        from spark_rapids_tpu.io.datasource import lookup_format

        ext = lookup_format(self._format)
        if ext is None:
            # built-in providers (iceberg, ...) register on first use
            import spark_rapids_tpu.lakehouse  # noqa: F401

            ext = lookup_format(self._format)
        if ext is not None:
            return ext(self.session, path, self._schema, self._options)
        if self._format == "delta":
            return self.delta(path)
        return getattr(self, self._format)(path)

    def delta(self, path: str):
        from spark_rapids_tpu.lakehouse.delta import read_delta

        return read_delta(self.session, path)

    def hivetext(self, *paths: str):
        """Hive LazySimpleSerDe text table ('\\x01' fields, '\\N'
        nulls); requires .schema(...) since the format has no header."""
        from spark_rapids_tpu.api.dataframe import DataFrame
        from spark_rapids_tpu.plan.logical import FileScan

        if self._schema is None:
            raise ValueError("hivetext requires an explicit schema")
        schema = self._schema
        if not hasattr(schema, "fields"):
            from spark_rapids_tpu.columnar.arrow_bridge import (
                schema_from_arrow,
            )

            schema = schema_from_arrow(schema)
        return DataFrame(FileScan("hivetext", list(paths), schema,
                                  self._options), self.session)

    def parquet(self, *paths: str):
        import pyarrow as _pa

        from spark_rapids_tpu.api.dataframe import DataFrame
        from spark_rapids_tpu.columnar.arrow_bridge import schema_from_arrow
        from spark_rapids_tpu.io.readers import (
            discover_partitions,
            expand_paths,
            infer_parquet_schema,
        )
        from spark_rapids_tpu.plan.logical import FileScan

        files = expand_paths(list(paths), ".parquet")
        from spark_rapids_tpu.io.readers import resolve_input_paths

        part_cols, file_values = discover_partitions(
            files, resolve_input_paths(list(paths)))
        opts = dict(self._options)
        arrow_schema = (None if self._schema is not None
                        else infer_parquet_schema(list(paths)))
        if part_cols:
            # partition columns materialize from the directory layout
            # (PartitioningAwareFileIndex role); they are appended
            # after the file columns, Spark-style. With an explicit
            # user schema the spec still attaches — the values come
            # from the directories, typed per the declared field.
            if self._schema is None:
                for name, is_int in part_cols:
                    if name not in arrow_schema.names:
                        arrow_schema = arrow_schema.append(_pa.field(
                            name, _pa.int64() if is_int else _pa.string()))
            opts["partition_spec"] = (part_cols, file_values)
        schema = self._schema or schema_from_arrow(arrow_schema)
        return DataFrame(FileScan("parquet", list(paths), schema,
                                  opts), self.session)

    def csv(self, path: str, header: bool = True, **kw):
        from spark_rapids_tpu.api.dataframe import DataFrame
        from spark_rapids_tpu.columnar.arrow_bridge import schema_from_arrow
        from spark_rapids_tpu.io.readers import expand_paths, read_csv
        from spark_rapids_tpu.plan.logical import FileScan

        if self._schema is not None:
            schema = self._schema
        else:
            # schema inference samples ONE file — committed write
            # output is a directory of part files
            sample_path = (expand_paths([path], ".csv") or [path])[0]
            sample = read_csv(sample_path, header=header, **kw)
            schema = schema_from_arrow(sample.schema)
        opts = dict(self._options)
        opts["header"] = header
        return DataFrame(FileScan("csv", [path], schema, opts),
                         self.session)

    def json(self, path: str):
        from spark_rapids_tpu.api.dataframe import DataFrame
        from spark_rapids_tpu.columnar.arrow_bridge import schema_from_arrow
        from spark_rapids_tpu.io.readers import expand_paths, read_json
        from spark_rapids_tpu.plan.logical import FileScan

        if self._schema is not None:
            schema = self._schema
        else:
            sample_path = (expand_paths([path], ".json") or [path])[0]
            sample = read_json(sample_path)
            schema = schema_from_arrow(sample.schema)
        return DataFrame(FileScan("json", [path], schema, self._options),
                         self.session)

    def orc(self, *paths: str):
        from spark_rapids_tpu.api.dataframe import DataFrame
        from spark_rapids_tpu.columnar.arrow_bridge import schema_from_arrow
        from spark_rapids_tpu.io.readers import infer_orc_schema
        from spark_rapids_tpu.plan.logical import FileScan

        schema = self._schema or schema_from_arrow(
            infer_orc_schema(list(paths)))
        return DataFrame(FileScan("orc", list(paths), schema,
                                  self._options), self.session)

    def avro(self, *paths: str):
        from spark_rapids_tpu.api.dataframe import DataFrame
        from spark_rapids_tpu.columnar.arrow_bridge import schema_from_arrow
        from spark_rapids_tpu.io.readers import infer_avro_schema
        from spark_rapids_tpu.plan.logical import FileScan

        schema = self._schema or schema_from_arrow(
            infer_avro_schema(list(paths)))
        return DataFrame(FileScan("avro", list(paths), schema,
                                  self._options), self.session)


_active: Optional["TpuSparkSession"] = None
_active_lock = threading.Lock()


class TpuSparkSession:
    builder = None  # class attribute set below

    def __init__(self, conf: Optional[Dict[str, object]] = None):
        from spark_rapids_tpu.exec.relation_cache import CacheManager

        from spark_rapids_tpu.runtime.metrics import MetricsRegistry

        self._settings = dict(conf or {})
        self.rapids_conf = rc.RapidsConf(self._settings)
        self.cache_manager = CacheManager()
        # engine-dispatch observability (which engine ran each query and
        # why faster engines fell back — see DataFrame.collect_arrow)
        self.query_metrics = MetricsRegistry()
        self.last_execution = None
        #: plan keys (`_plan_key`) of the lookup joins that lost their
        #: survivor bet in this session: the fused engine lowers those
        #: at full width from then on (exec/fused.py SurvivorOverflow)
        self.fused_wide_joins = set()
        self._init_runtime()
        # the session OWNS the observability wiring (obs/): event bus,
        # span builder, event history, and the conf-gated event-log
        # writer; runtime modules emit into it process-wide
        from spark_rapids_tpu.obs import ObsManager

        self.obs = ObsManager(self.rapids_conf)
        # conf-gated live scrape endpoint (/metrics, /queries) — the
        # first piece of the service front-end (obs/http.py)
        self.obs.start_http(self, self.rapids_conf)
        global _active
        with _active_lock:
            _active = self

    def _init_runtime(self):
        """Plugin lifecycle (Plugin.scala:412-545): driver init fixes
        up/broadcasts the conf, executor init brings up the device
        runtime. Standalone, both run here."""
        from spark_rapids_tpu.plugin import (
            TpuDriverPlugin,
            executor_plugin,
        )

        coord = self.rapids_conf.get(rc.MULTIHOST_COORDINATOR)
        if coord:
            # join the cluster BEFORE any backend touch so
            # jax.devices() spans every process (multihost.initialize
            # is idempotent across sessions in one process)
            from spark_rapids_tpu.parallel import multihost

            nproc = self.rapids_conf.get(rc.MULTIHOST_NUM_PROCESSES)
            pid = self.rapids_conf.get(rc.MULTIHOST_PROCESS_ID)
            multihost.initialize(
                coord, nproc if nproc > 0 else None,
                pid if pid >= 0 else None)
        self._conf_map = TpuDriverPlugin().init(self.rapids_conf)
        self._executor_plugin = executor_plugin()
        self._executor_plugin.init(self.rapids_conf)

    # --- conf ---

    class _ConfView:
        def __init__(self, session):
            self._s = session

        def get(self, key: str, default=None):
            try:
                return self._s.rapids_conf[key]
            except KeyError:
                return self._s._settings.get(key, default)

        def set(self, key: str, value):
            self._s._settings[key] = value
            self._s.rapids_conf = rc.RapidsConf(self._s._settings)

    @property
    def conf(self):
        return TpuSparkSession._ConfView(self)

    # --- UDF registry (UDFRegistration / hiveUDFs.scala surface) ---

    @property
    def udf(self):
        from spark_rapids_tpu.udf.hive_udf import UDFRegistration

        if not hasattr(self, "_udf_reg"):
            self._udf_reg = UDFRegistration(self)
        return self._udf_reg

    # --- data sources ---

    @property
    def read(self) -> DataFrameReader:
        return DataFrameReader(self)

    def createDataFrame(self, data, schema=None):
        from spark_rapids_tpu.api.dataframe import DataFrame
        from spark_rapids_tpu.plan.logical import LocalRelation

        if isinstance(data, pa.Table):
            table = data
        elif hasattr(data, "dtypes") and hasattr(data, "columns"):
            table = pa.Table.from_pandas(data, preserve_index=False)
        elif isinstance(data, dict):
            table = pa.table(data)
        elif isinstance(data, list) and schema is not None:
            names = schema if isinstance(schema, list) else schema.names
            cols = list(zip(*data)) if data else [[] for _ in names]
            table = pa.table({n: list(c) for n, c in zip(names, cols)})
        else:
            raise TypeError("createDataFrame accepts arrow Table, pandas "
                            "DataFrame, dict of columns, or list of rows "
                            "with schema")
        return DataFrame(LocalRelation(table), self)

    def range(self, start: int, end: Optional[int] = None, step: int = 1,
              numPartitions: int = 1):
        from spark_rapids_tpu.api.dataframe import DataFrame
        from spark_rapids_tpu.plan.logical import Range

        if end is None:
            start, end = 0, start
        return DataFrame(Range(start, end, step, numPartitions), self)

    # --- write ---

    def write_parquet(self, df, path: str):
        from spark_rapids_tpu.io.readers import write_parquet

        write_parquet(df.collect_arrow(), path)

    def explainPotentialTpuPlan(self, df) -> str:
        """Execute-free placement report: tag the plan and return the
        would-be device placement with fallback reasons (the ExplainPlan
        public API, reference GpuOverrides.scala:4500
        explainPotentialGpuPlan)."""
        _phys, meta = df._physical()
        txt = meta.explain(only_not_on_device=False)
        return txt or "(all operators place on device)"

    # --- profiling (NvtxWithMetrics / nvtx_profiling.md analog) ---

    def startProfiler(self, log_dir: str):
        from spark_rapids_tpu.runtime import profiler

        profiler.start_trace(log_dir)

    def stopProfiler(self):
        from spark_rapids_tpu.runtime import profiler

        profiler.stop_trace()

    @property
    def compile_cache_stats(self):
        """Process compile ledger (runtime/compile_cache.py): programs
        compiled / structural cache hits / compile seconds / jax's
        disk-cache hits and misses. Per-query deltas live in
        last_execution['compile']."""
        from spark_rapids_tpu.runtime.compile_cache import stats

        return stats.snapshot()

    @property
    def robustness_metrics(self):
        """One snapshot of every failure-domain counter (PR 2/3): chaos
        injections per site, backoff retries per domain, shuffle
        fetch/checksum recoveries + orphaned/discarded blocks,
        stage-scheduler recoveries (retries, speculation, recomputed
        partitions, evicted workers), degradation-ladder demotions +
        circuit-breaker state, and semaphore timeouts. A view over the
        unified registry (obs/registry.py); keys are a stable contract.
        bench.py folds this into its JSON so BENCH_* tracks robustness
        overhead."""
        from spark_rapids_tpu.obs import registry as obs_registry

        return obs_registry.robustness_snapshot()

    def prometheus_metrics(self) -> str:
        """Every engine counter in Prometheus text exposition
        (obs/prom.py) — expose behind a scrape endpoint for
        dashboards."""
        from spark_rapids_tpu.obs import prom

        return prom.render(self)

    # --- query governance (runtime/admission.py) ---

    def cancel(self, query_id: int,
               reason: str = "cancelled by user") -> bool:
        """Cancel a running or queued query by the id reported in
        last_execution['queryId'] / the admission tables. A queued
        query leaves the queue immediately; a running one unwinds at
        its next cooperative yield point, releasing its semaphore
        permits and spill-catalog buffers. True when the cancel newly
        latched."""
        from spark_rapids_tpu.runtime import admission

        return admission.get().cancel(query_id, reason)

    def cancel_all(self, reason: str = "cancelled by user") -> int:
        """Cancel every running and queued query; returns how many
        tokens newly latched."""
        from spark_rapids_tpu.runtime import admission

        return admission.get().cancel_all(reason)

    def admission_status(self) -> dict:
        """Running + queued query tables (ids, priorities, elapsed
        time, descriptions) and the conf'd capacity — the table a
        QueryRejectedError prints, live."""
        from spark_rapids_tpu.runtime import admission

        return admission.get().status()

    # --- serving (serve/server.py) ---

    def serve(self, conf: Optional[Dict[str, object]] = None
              ) -> "object":
        """Start a query-service daemon over THIS session's warm
        engine and return it (already listening; `.port` carries the
        bound port). The daemon borrows the session — `daemon.stop()`
        drains and closes sockets but leaves the session running.
        `conf` entries are applied to the session settings first (the
        usual place to pass a fixed `spark.rapids.tpu.serve.port` or
        tenant caps)."""
        from spark_rapids_tpu.serve.server import QueryServiceDaemon

        if conf:
            for k, v in conf.items():
                self._settings[k] = v
            self.rapids_conf = rc.RapidsConf(self._settings)
        return QueryServiceDaemon(session=self).start()

    def stop(self):
        global _active
        try:
            # finalize any in-flight event log + release the bus (a
            # newer session's bus survives: uninstall is identity-gated)
            self.obs.close()
        except Exception:
            pass
        try:
            self.cache_manager.clear()
        except Exception:
            pass
        try:
            from spark_rapids_tpu.runtime.memory import _catalog

            if _catalog is not None:
                _catalog.check_leaks(
                    raise_on_leak=bool(self.rapids_conf.get(
                        rc.LEAK_DETECTION)))
        finally:
            # admission permits of tasks the session abandoned (e.g. a
            # partially-consumed ColumnarRdd iterator) must not starve
            # the next session — the executor-plugin shutdown resets
            # GpuSemaphore likewise
            from spark_rapids_tpu.runtime import semaphore as _sem

            _sem.initialize(
                self.rapids_conf.get(rc.CONCURRENT_TPU_TASKS),
                self.rapids_conf.get(rc.SEMAPHORE_ACQUIRE_TIMEOUT_MS))
            # the session must deregister even when the leak check
            # raises, or active() keeps returning a dead session
            with _active_lock:
                _active = None

    @staticmethod
    def active() -> Optional["TpuSparkSession"]:
        return _active


TpuSparkSession.builder = TpuSparkSessionBuilder()
