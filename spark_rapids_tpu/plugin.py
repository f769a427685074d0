"""Plugin lifecycle — the SQLPlugin / RapidsDriverPlugin /
RapidsExecutorPlugin surface (reference: sql-plugin-api SQLPlugin.scala,
Plugin.scala:412-684, ColumnarOverrideRules Plugin.scala:49-56).

Standalone, the session owns the process, so the "driver" and
"executor" hooks both run inside TpuSparkSession construction — but the
lifecycle is factored exactly like the reference so an embedding
framework (or a future multi-process deployment) can drive the hooks
itself:

- TpuDriverPlugin.init: validate/fix up the conf, produce the conf map
  to broadcast to executors (Plugin.scala:439-464).
- TpuExecutorPlugin.init: validate the device, initialize the memory
  pool + spill catalog, shuffle env, and semaphore
  (Plugin.scala:484-545), and install the fatal-error policy.
- ColumnarOverrideRules: the rule objects a planner integration would
  inject (pre = TpuOverrides, post = transition insertion — both are
  applied by plan_query here).
"""

from __future__ import annotations

import sys
from typing import Dict, Optional

from spark_rapids_tpu.config import rapids_conf as rc
from spark_rapids_tpu.config.rapids_conf import FATAL_ERROR_EXIT


class TpuDriverPlugin:
    """Driver-side init: conf validation + broadcastable conf map."""

    def init(self, conf: rc.RapidsConf) -> Dict[str, object]:
        unknown = getattr(conf, "unknown_keys", [])
        bad = [k for k in unknown if k.startswith("spark.rapids")]
        bad += self._unmatched_op_switches(conf)
        if bad:
            import warnings

            warnings.warn(
                f"unknown spark.rapids.* conf keys ignored: {sorted(bad)}")
        # the executor-broadcast conf map (RapidsConf.rapidsConfMap role)
        return {k: v for k, v in conf._values.items()}

    @staticmethod
    def _unmatched_op_switches(conf: rc.RapidsConf) -> list:
        """Per-operator switch keys naming no known logical operator /
        expression class — a typo'd switch must warn, not silently
        no-op (the registered-key diagnostic, extended to the dynamic
        namespace)."""
        switches = getattr(conf, "_op_switches", {})
        if not switches:
            return []
        import inspect

        import spark_rapids_tpu.expr as E
        import spark_rapids_tpu.plan.logical as L
        from spark_rapids_tpu.expr.core import Expression

        logical = {type_.__name__ for type_ in vars(L).values()
                   if inspect.isclass(type_)
                   and issubclass(type_, L.LogicalPlan)}
        import spark_rapids_tpu.expr.aggregates as _A
        import spark_rapids_tpu.expr.windows as _W
        import spark_rapids_tpu.udf.pandas_udf as _P

        exprs = {c.__name__
                 for mod in (E, _A, _W, _P)
                 for c in vars(mod).values()
                 if inspect.isclass(c) and issubclass(c, Expression)}
        bad = []
        for (kind, name) in switches:
            valid = logical if kind == "exec" else exprs
            if name not in valid:
                bad.append(f"spark.rapids.sql.{kind}.{name}")
        return bad


class TpuExecutorPlugin:
    """Executor-side init (Plugin.scala:484-545 analog)."""

    def __init__(self):
        self.initialized = False

    def init(self, conf: rc.RapidsConf):
        from spark_rapids_tpu.io import filecache
        from spark_rapids_tpu.runtime import admission, compile_cache, \
            degrade, device_monitor, faults, memory, sanitizer, semaphore
        from spark_rapids_tpu.shuffle.manager import configure_shuffle

        self._validate_device()
        # chaos registry FIRST: every later init step is itself a
        # consumer of an injection site (io.read, spill.disk)
        faults.configure(conf)
        degrade.configure(conf)
        # device-loss monitor before anything that can touch the
        # backend: the very first dispatch is already fatal-classified
        # and fence-recoverable (the process device epoch survives
        # reconfiguration)
        device_monitor.configure(conf)
        # query governance front door (admission queue + cancel
        # registry) — after faults so admission.slow_drain is armed
        admission.configure(conf)
        # concurrency sanitizer BEFORE the semaphore so the very first
        # acquire is already under wait-for-graph surveillance
        sanitizer.configure(conf)
        filecache.configure(conf)  # FileCache.init (Plugin.scala:545)
        # persistent compilation layer BEFORE any program compiles, so
        # the whole session rides the disk cache
        compile_cache.configure(conf)
        memory.initialize_memory(conf, force=True)
        semaphore.initialize(
            conf.get(rc.CONCURRENT_TPU_TASKS),
            conf.get(rc.SEMAPHORE_ACQUIRE_TIMEOUT_MS),
            atomic_query_groups=conf.get(
                rc.SEMAPHORE_ATOMIC_QUERY_GROUPS))
        configure_shuffle(
            conf.get(rc.SHUFFLE_MODE),
            shuffle_dir=conf.get(rc.SPILL_DIR) or None,
            num_threads=conf.get(rc.MULTITHREADED_READ_NUM_THREADS),
            codec=conf.get(rc.SHUFFLE_COMPRESSION_CODEC),
            spill_threshold=conf.get(rc.SHUFFLE_SPILL_THRESHOLD),
            checksum=conf.get(rc.SHUFFLE_CHECKSUM_ENABLED))
        self._fatal_exit_code = conf.get(FATAL_ERROR_EXIT)
        self.initialized = True

    def _validate_device(self):
        """Device/arch validation (validateGpuArchitecture role): jax
        must initialize and expose at least one device."""
        import jax

        devs = jax.devices()
        if not devs:
            raise RuntimeError("no jax devices available")

    def on_task_failed(self, exc: BaseException) -> bool:
        """Fatal-error policy (Plugin.scala:651-675): unrecoverable
        device/runtime failures optionally kill the process so the
        cluster manager reschedules. Returns True when the error is
        classified fatal."""
        fatal = _is_fatal_device_error(exc)
        if fatal and getattr(self, "_fatal_exit_code", 0):
            sys.stderr.write(
                f"fatal device error, exiting "
                f"{self._fatal_exit_code}: {exc}\n")
            sys.stderr.flush()
            sys.exit(self._fatal_exit_code)
        return fatal

    def shutdown(self):
        from spark_rapids_tpu.runtime import memory

        memory.shutdown_memory()


def _is_fatal_device_error(exc: BaseException) -> bool:
    """Classify unrecoverable device failures (the CudaFatalException
    analog) by delegating to the device monitor's taxonomy
    (runtime/device_monitor.py) — one classifier for the exit policy
    and the warm-recovery fence. A DeviceLostError is explicitly NOT
    process-fatal: it is the already-classified, already-being-
    recovered form, and killing the process would throw away the warm
    engine the recovery just saved."""
    from spark_rapids_tpu.runtime import device_monitor
    from spark_rapids_tpu.runtime.errors import DeviceLostError

    if isinstance(exc, DeviceLostError):
        return False
    return device_monitor.classify(exc) == "fatal"


class ColumnarOverrideRules:
    """The rule pair a planner integration injects (ColumnarOverrideRules
    Plugin.scala:49-56). `pre` tags + converts, `post` is the transition
    insertion — both run inside plan_query for the standalone engine."""

    def pre_columnar_transitions(self, conf: rc.RapidsConf):
        from spark_rapids_tpu.plan.overrides import TpuOverrides

        return TpuOverrides(conf)

    def post_columnar_transitions(self, conf: rc.RapidsConf):
        # transition insertion lives inside TpuOverrides._convert
        # (_to_device/_to_host); exposed for API parity
        return None


_executor_plugin: Optional[TpuExecutorPlugin] = None


def executor_plugin() -> TpuExecutorPlugin:
    global _executor_plugin
    if _executor_plugin is None:
        _executor_plugin = TpuExecutorPlugin()
    return _executor_plugin
