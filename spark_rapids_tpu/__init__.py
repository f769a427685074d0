"""spark-rapids-tpu: a TPU-native Spark-accelerator-class columnar SQL engine.

This package provides the capabilities of the NVIDIA RAPIDS Accelerator for
Apache Spark (reference: /root/reference, liurenjie1024/spark-rapids
24.04.0-SNAPSHOT) re-designed TPU-first:

- Columnar operators (scan/project/filter/hash-aggregate/join/sort/window/
  exchange) whose kernels are XLA computations over Arrow-layout device
  buffers (reference L4, SURVEY.md section 2.5) instead of cuDF/CUDA calls.
- A planner/override engine that tags each plan node for device placement
  with per-type support checks and explain output (reference
  GpuOverrides.scala / RapidsMeta.scala / TypeChecks.scala).
- A device runtime with a reservation-based HBM budget, DEVICE->HOST->DISK
  spill catalog, OOM retry/split execution and a task-admission semaphore
  (reference RapidsBufferCatalog.scala, RmmRapidsRetryIterator.scala,
  GpuSemaphore.scala).
- A shuffle layer: host-serialized shuffle v1 plus an ICI all-to-all
  collective transport over a jax.sharding.Mesh replacing the reference's
  UCX P2P transport (reference sql-plugin/.../shuffle/, shuffle-plugin/).

The engine is standalone (no JVM): it ships its own Spark-compatible
DataFrame frontend and a CPU (pyarrow) execution backend that doubles as
the differential-testing oracle, mirroring the reference's CPU-vs-GPU
integration test strategy (SURVEY.md section 4).
"""

import jax as _jax

# Spark semantics require 64-bit integers (LongType, TimestampType) and
# float64 (DoubleType). TPU v5e emulates int64 exactly and runs f64
# arithmetic at reduced precision (docs/compatibility.md); what its
# compiler still refuses (64-bit bitcast) is pinned by
# tests/test_chip_compile.py.
_jax.config.update("jax_enable_x64", True)

__version__ = "0.1.0"

from spark_rapids_tpu.api.session import TpuSparkSession  # noqa: E402,F401
from spark_rapids_tpu.explain import (  # noqa: E402,F401
    explain_potential_tpu_plan,
)
