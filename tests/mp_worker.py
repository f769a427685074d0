"""Cross-process distributed-execution worker (launched by
test_multiprocess.py, one OS process per "host").

Each worker is the analog of one reference executor process
(RapidsShuffleClient.scala:95 / RapidsShuffleServer.scala:71 peers):
it joins the jax.distributed coordination service, owns a slice of the
global device mesh, decodes ONLY its own shard of the scan's file list,
and participates in the plan's all_to_all / all_gather collectives —
which XLA routes over the cross-process fabric (gloo on CPU here,
ICI/DCN on a real pod). collect() returns the full result on every
process via a process allgather (mesh_exec.fetch_host).

Protocol: argv = [data_dir, out_dir]; env SRTPU_MP_{COORD,NPROC,PID}.
Writes <out_dir>/result_<pid>.parquet plus <out_dir>/ok_<pid> on
success (contents = ingest-stats JSON), or <out_dir>/err_<pid> with
the traceback on failure.
"""

import json
import os
import sys
import traceback


def main() -> None:
    import jax

    # must run before any backend touch: test workers never take a chip
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    coord = os.environ["SRTPU_MP_COORD"]
    nproc = int(os.environ["SRTPU_MP_NPROC"])
    pid = int(os.environ["SRTPU_MP_PID"])
    data_dir, out_dir = sys.argv[1], sys.argv[2]

    import pyarrow.parquet as pq

    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.session import TpuSparkSession
    from spark_rapids_tpu.parallel import multihost, plan_compiler

    # the session joins the cluster itself (multihost.* confs)
    spark = TpuSparkSession({
        "spark.rapids.tpu.multihost.coordinator": coord,
        "spark.rapids.tpu.multihost.numProcesses": nproc,
        "spark.rapids.tpu.multihost.processId": pid,
        "spark.sql.shuffle.partitions": 4,
        "spark.sql.autoBroadcastJoinThreshold": -1,
    })
    assert jax.process_count() == nproc, jax.process_count()
    spark.conf.set("spark.rapids.tpu.mesh",
                   multihost.global_device_count())
    try:
        fact = spark.read.parquet(data_dir)
        dim = spark.createDataFrame(_dim_table())
        df = (fact.filter(F.col("v") > 0.2)
                  .join(dim, on="k", how="inner")
                  .groupBy("g")
                  .agg(F.sum("v").alias("s"), F.count("*").alias("c")))
        got = df.collect_arrow()

        stats = dict(plan_compiler.last_ingest_stats)
        if not stats:
            raise AssertionError(
                "mesh ingestion never ran (thread-pool fallback?)")
        if stats["files"] >= stats["total_files"]:
            raise AssertionError(
                f"process {pid} decoded ALL {stats['total_files']} files"
                " — ingestion is not process-local: " + json.dumps(stats))

        pq.write_table(got, os.path.join(out_dir, f"result_{pid}.parquet"))

        # second scenario, same cluster: HEAVILY SKEWED join keys (90%
        # of rows share one key) — the all_to_all slot-capacity
        # overflow + whole-program recompile discipline must converge
        # cross-process (every process must take the same retry path
        # or the collectives deadlock)
        skew = spark.createDataFrame(_skew_table())
        dim2 = spark.createDataFrame(_dim_table())
        df2 = (skew.join(dim2, on="k", how="inner")
                   .groupBy("g")
                   .agg(F.sum("v").alias("s"), F.count("*").alias("c")))
        got2 = df2.collect_arrow()
        pq.write_table(got2,
                       os.path.join(out_dir, f"result2_{pid}.parquet"))
        with open(os.path.join(out_dir, f"ok_{pid}"), "w") as f:
            json.dump(stats, f)
    finally:
        spark.stop()


def _skew_table():
    """Deterministic (identical on every process — SPMD inputs must
    agree) skewed fact: 90% of rows carry key 7."""
    import numpy as np
    import pyarrow as pa

    rng = np.random.default_rng(3)
    n = 4000
    keys = np.where(rng.random(n) < 0.9, 7,
                    rng.integers(0, 50, n)).astype(np.int64)
    return pa.table({"k": pa.array(keys),
                     "v": pa.array(rng.random(n))})


def _dim_table():
    import numpy as np
    import pyarrow as pa

    ks = np.arange(0, 50, dtype=np.int64)
    return pa.table({"k": pa.array(ks),
                     "g": pa.array(ks % 5, type=pa.int64())})


if __name__ == "__main__":
    try:
        main()
    except Exception:
        out_dir = sys.argv[2] if len(sys.argv) > 2 else "."
        pid = os.environ.get("SRTPU_MP_PID", "x")
        with open(os.path.join(out_dir, f"err_{pid}"), "w") as f:
            f.write(traceback.format_exc())
        raise
