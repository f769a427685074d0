#!/usr/bin/env bash
# Chaos gate — the *RetrySuite forced-fault strategy applied end to
# end: re-run a fast tier-1 query subset with seeded fault injection
# armed at EVERY site (runtime/faults.py), one site at a time and then
# all together, and assert the results match the clean run (keys
# exactly; float aggregates to 1e-6 relative, since a demotion down
# the engine ladder legitimately changes accumulation order). A query
# that survives chaos by producing WRONG data is the failure mode this
# gate exists to catch.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export XLA_FLAGS="${XLA_FLAGS:---xla_force_host_platform_device_count=8}"

echo "== chaos equivalence harness (per-site + all-site) =="
python - <<'PY'
import jax

jax.config.update("jax_platforms", "cpu")
# f64 device math: engine demotions then differ only by summation
# order (~1e-12 relative), so the comparison tolerance can stay tight
jax.config.update("jax_enable_x64", True)

import math
import os
import tempfile

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from spark_rapids_tpu.api.session import TpuSparkSession
import spark_rapids_tpu.api.functions as F

# --- dataset: small enough to be fast, shaped like the bench (fact +
# --- string dim join + agg), written once per run
root = tempfile.mkdtemp(prefix="srtpu_chaos_")
rng = np.random.default_rng(0)
N, STORES = 40_000, 64
fact_dir = os.path.join(root, "fact")
dim_dir = os.path.join(root, "dim")
os.makedirs(fact_dir), os.makedirs(dim_dir)
for i in range(2):
    pq.write_table(pa.table({
        "store": pa.array(rng.integers(0, STORES, N // 2), pa.int64()),
        "amount": pa.array(rng.random(N // 2) * 100.0),
        "qty": pa.array(rng.integers(1, 50, N // 2), pa.int64()),
    }), os.path.join(fact_dir, f"part-{i}.parquet"))
pq.write_table(pa.table({
    "store": pa.array(np.arange(STORES), pa.int64()),
    "region": pa.array([f"r{i % 7}" for i in range(STORES)]),
}), os.path.join(dim_dir, "dim.parquet"))


def queries(s):
    fact = s.read.parquet(fact_dir)
    dim = s.read.parquet(dim_dir)
    yield ("join_agg", fact.filter(F.col("amount") > 10.0)
           .join(dim, on="store", how="inner")
           .groupBy("region")
           .agg(F.sum("amount").alias("rev"), F.count("*").alias("n")))
    yield ("sort_limit", fact.orderBy("amount", ascending=False)
           .select("store", "amount").limit(50))
    # key repartition forces a REAL shuffle exchange (blocks through
    # the manager), so shuffle.fetch/deserialize sites actually fire
    yield ("repart_agg", fact.repartition(4, "store").groupBy("store")
           .agg(F.avg("qty").alias("aq")).orderBy("store"))


def run_all(conf):
    s = TpuSparkSession(conf)
    try:
        out = {}
        for name, df in queries(s):
            t = df.collect_arrow()
            keys = [c for c, f in zip(t.column_names, t.schema.types)
                    if not pa.types.is_floating(f)]
            out[name] = t.sort_by(
                [(c, "ascending") for c in keys or t.column_names]
            ).to_pydict()
        return out, s.robustness_metrics
    finally:
        s.stop()


def same(a, b):
    """Key columns byte-equal; float columns to 1e-6 relative."""
    if set(a) != set(b):
        return False
    for col in a:
        va, vb = a[col], b[col]
        if len(va) != len(vb):
            return False
        for x, y in zip(va, vb):
            if isinstance(x, float) or isinstance(y, float):
                if not math.isclose(x, y, rel_tol=1e-6, abs_tol=1e-8):
                    return False
            elif x != y:
                return False
    return True


# shuffle-exercising conf: eager engine + MULTITHREADED file shuffle
# so shuffle.fetch/deserialize sites actually fire; a small device
# pool with a ZERO host spill store forces disk-tier spills so the
# spill.disk site fires too
BASE_EAGER = {"spark.rapids.sql.fusedExec.enabled": False,
              "spark.rapids.shuffle.mode": "MULTITHREADED",
              "spark.sql.shuffle.partitions": 4,
              "spark.rapids.sql.reader.batchSizeRows": 4096,
              "spark.rapids.memory.gpu.maxAllocBytes": 4 << 20,
              "spark.rapids.memory.host.spillStorageSize": 0,
              # fast chaos retries: the gate budget is seconds
              "spark.rapids.tpu.io.retry.backoffMs": 1,
              "spark.rapids.tpu.io.retry.maxBackoffMs": 5,
              "spark.rapids.tpu.io.retry.attempts": 6}

baseline, _ = run_all({})
baseline_eager, _ = run_all(BASE_EAGER)

# scheduler-domain sites (PR 3) fire in the eager engine's stage
# scheduler (result + shuffle map stages). worker.crash retries whole
# task attempts and shuffle.lost_output recomputes map tasks, so these
# runs are SLOW-AWARE: the task attempt budget is widened and the
# straggler probability kept low (each injected straggler stalls an
# attempt ~0.2s before speculation's duplicate wins).
SITES = ["io.read:p=0.3", "shuffle.fetch:p=0.3",
         "shuffle.deserialize:p=0.2",
         "spill.disk:p=0.3", "device.dispatch:once",
         "worker.crash:p=0.2", "task.straggler:p=0.1",
         "shuffle.lost_output:once"]

SCHED_CONF = {"spark.rapids.tpu.stage.maxAttempts": 8,
              "spark.rapids.tpu.speculation.enabled": True,
              "spark.rapids.tpu.speculation.quantile": 0.5,
              "spark.rapids.tpu.speculation.multiplier": 1.3,
              "spark.rapids.tpu.speculation.minTaskRuntimeMs": 40}

failures = 0
for spec in SITES + [";".join(SITES)]:
    label = spec if len(spec) < 40 else "ALL-SITES"
    for base, want in (({}, baseline), (BASE_EAGER, baseline_eager)):
        conf = {**base, **SCHED_CONF,
                "spark.rapids.tpu.chaos.enabled": True,
                "spark.rapids.tpu.chaos.seed": 42,
                "spark.rapids.tpu.chaos.sites": spec,
                "spark.rapids.tpu.io.retry.backoffMs": 1,
                "spark.rapids.tpu.io.retry.maxBackoffMs": 5,
                "spark.rapids.tpu.io.retry.attempts": 6}
        got, robust = run_all(conf)
        mode = "eager" if base else "fused"
        for name in want:
            if not same(got[name], want[name]):
                print(f"FAIL {label} [{mode}] {name}: results differ")
                failures += 1
        inj = sum(v["injected"] for v in robust["chaos"].values())
        sch = {k: v for k, v in robust["scheduler"].items()
               if v and k != "tasksLaunched" and k != "stagesRun"}
        print(f"ok   {label} [{mode}]: {inj} faults injected, "
              f"retries={robust['retries']}, "
              f"sched={sch}, "
              f"degrade={ {k: v for k, v in robust['degrade'].items() if v} }")
assert failures == 0, f"{failures} chaos mismatches"
print("chaos equivalence: PASS")
PY

echo "== targeted fault-injection suite =="
python -m pytest tests/test_chaos.py tests/test_memory_retry.py \
    tests/test_scheduler.py tests/test_scheduler_mp.py -q \
    -p no:cacheprovider

echo "CHAOS PASS"
