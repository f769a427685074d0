"""Multichip scaling bench: q5 throughput by shard count, on real chips.

The q5 join+agg shape executed at increasing shard counts with the
mesh SPMD engine (hash exchanges compiled to on-device all-to-all over
ICI, encoded codes on the wire), against the incumbent single-chip
engine at its DEFAULT configuration (fused stage compiler,
host-serialized MULTITHREADED shuffle). Scaling is reported as
``throughput(mesh@n) / throughput(single@1)`` from raw wall clock: the
speedup a query sees when its execution spreads over n chips and its
shuffles stop leaving the device fabric.

Shard counts are the powers of two up to the chips this process can
see. A virtual mesh (XLA host devices timesharing the host cores) runs
the same program shapes and counts the same bytes, which is what the
tests and ci/multichip_check.sh use it for, but its wall clock says
nothing about chips: ``main()`` refuses a platform other than `tpu`,
and nothing computed on virtual devices is printed beside a
device_kind.

Its own command, its own process (a chip belongs to one process):
``python -m spark_rapids_tpu.tools.multichip_bench`` prints one JSON
line. ``chip_smoke.py --chips 4`` is the quicker proof that the mesh
path runs at all.
"""

from __future__ import annotations

import json
import os
import statistics
import tempfile
import time
from typing import Dict, Sequence

ROWS = int(os.environ.get("SRTPU_MULTICHIP_ROWS", 2_000_000))
FILES = 8
STORES = 2000
REGIONS = 12
REPEATS = 3
DATA_DIR = os.path.join(tempfile.gettempdir(),
                        f"srtpu_multichip_{ROWS}")
DIM_DIR = DATA_DIR + "_dim"


def ensure_data() -> int:
    """q5-shaped dataset: FILES fact parquet parts + a string-region
    dim (dictionary-encoded pages so the encoded path engages and the
    mesh ingestion must reconcile per-shard dictionaries). Returns
    fact arrow bytes."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    marker = os.path.join(DATA_DIR, "_DONE")
    if os.path.exists(marker):
        return int(open(marker).read())
    os.makedirs(DATA_DIR, exist_ok=True)
    os.makedirs(DIM_DIR, exist_ok=True)
    rng = np.random.default_rng(0)
    per = ROWS // FILES
    total = 0
    for i in range(FILES):
        t = pa.table({
            "store": pa.array(rng.integers(0, STORES, per),
                              type=pa.int64()),
            "amount": pa.array(rng.random(per) * 100.0,
                               type=pa.float64()),
            "qty": pa.array(rng.integers(1, 100, per), type=pa.int64()),
        })
        total += t.nbytes
        pq.write_table(t, os.path.join(DATA_DIR, f"part-{i}.parquet"),
                       compression="NONE", use_dictionary=False,
                       row_group_size=per)
    dim = pa.table({
        "store": pa.array(np.arange(STORES), type=pa.int64()),
        "region": pa.array(
            [f"region_{i % REGIONS:02d}" for i in range(STORES)],
            type=pa.large_string()),
    })
    pq.write_table(dim, os.path.join(DIM_DIR, "dim.parquet"),
                   use_dictionary=["region"])
    with open(marker, "w") as f:
        f.write(str(total))
    return total


def _q5(spark):
    from spark_rapids_tpu.api import functions as F

    fact = spark.read.parquet(DATA_DIR)
    dim = spark.read.parquet(DIM_DIR)
    return (fact.filter(F.col("amount") > 10.0)
            .join(dim, on="store", how="inner")
            .groupBy("region")
            .agg(F.sum("amount").alias("rev"),
                 F.count("*").alias("sales")))


def _session(extra: Dict) -> "object":
    from spark_rapids_tpu.api.session import TpuSparkSession

    conf = {
        "spark.sql.shuffle.partitions": 8,
        # shuffled join on both rows: the exchange IS the measurement
        "spark.sql.autoBroadcastJoinThreshold": -1,
    }
    conf.update(extra)
    return TpuSparkSession(conf)


def _timed_run(spark, repeats: int = REPEATS):
    df = _q5(spark)
    out = df.collect_arrow()  # cold: compiles + caches
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = df.collect_arrow()
        times.append(time.perf_counter() - t0)
    rec = spark.last_execution or {}
    return out, statistics.median(times), rec


def device_shards() -> Sequence[int]:
    """1, 2, 4, ... up to the devices this process can see."""
    import jax

    have = len(jax.devices())
    return tuple(n for n in (1, 2, 4, 8) if n <= have)


def run_scaling(shards: Sequence[int], repeats: int = REPEATS) -> Dict:
    """The MULTICHIP block: q5 throughput per shard count + the ledger's
    ici-vs-host byte split for the mesh execution."""
    import jax

    from spark_rapids_tpu.obs import telemetry

    input_bytes = ensure_data()
    need = max(shards)
    have = len(jax.devices())
    if have < need:
        raise RuntimeError(
            f"run_scaling needs {need} devices, have {have}")

    rows = {}
    baseline_thr = None
    oracle = None
    # shards=1: the incumbent single-chip engine at its defaults
    # (fused stage compiler on, host-serialized MULTITHREADED shuffle)
    spark = _session({})
    try:
        out, med, rec = _timed_run(spark, repeats)
        oracle = {r: (round(v, 2), s) for r, v, s in zip(
            out.column("region").to_pylist(),
            out.column("rev").to_pylist(),
            out.column("sales").to_pylist())}
        baseline_thr = input_bytes / med / 1e9
        rows[1] = {
            "engine": rec.get("engine"),
            "median_s": round(med, 3),
            "gbps": round(baseline_thr, 3),
            "scaling": 1.0,
        }
    finally:
        spark.stop()

    mesh_ledgers = {}
    for n in shards:
        if n == 1:
            continue
        spark = _session({"spark.rapids.tpu.mesh": n})
        try:
            out, med, rec = _timed_run(spark, repeats)
            got = {r: (round(v, 2), s) for r, v, s in zip(
                out.column("region").to_pylist(),
                out.column("rev").to_pylist(),
                out.column("sales").to_pylist())}
            assert set(got) == set(oracle), (sorted(got), sorted(oracle))
            for k in oracle:
                assert got[k][1] == oracle[k][1], (k, got[k], oracle[k])
                assert abs(got[k][0] - oracle[k][0]) <= max(
                    1e-6 * abs(oracle[k][0]), 0.05), (k, got[k],
                                                      oracle[k])
            thr = input_bytes / med / 1e9
            tel = (rec.get("telemetry") or {})
            moved = tel.get("bytesMoved") or {}
            rows[n] = {
                "engine": rec.get("engine"),
                "meshDevices": rec.get("meshDevices"),
                "median_s": round(med, 3),
                "gbps": round(thr, 3),
                "scaling": round(thr / baseline_thr, 3),
                "iciBytes": tel.get("iciBytes"),
                "hostBytesAvoided": tel.get("hostBytesAvoided"),
                "shuffleHostBytes": moved.get("shuffle", 0),
            }
            mesh_ledgers[n] = moved
        finally:
            spark.stop()

    top = max(n for n in shards if n in rows)
    dev = jax.devices()[0]
    moved_top = mesh_ledgers.get(top, {})
    return {
        "metric": "q5 scan+join+agg throughput by shard count "
                  "(mesh SPMD over ICI vs default single-chip engine)",
        "rows": ROWS,
        "input_mib": input_bytes >> 20,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "baseline": "single-chip engine, default conf "
                    "(fused, MULTITHREADED host shuffle)",
        "shards": {str(k): v for k, v in sorted(rows.items())},
        "scaling_at_%d" % top: rows[top]["scaling"],
        "scaling_efficiency_at_%d" % top: round(
            rows[top]["scaling"] / top, 3),
        # the proof the exchange left the host: mesh execution moved
        # ICI bytes and ZERO shuffle-direction (host) bytes
        "ici_vs_h2d": {
            "ici": moved_top.get("ici", 0),
            "h2d": moved_top.get("h2d", 0),
            "shuffle_host": moved_top.get("shuffle", 0),
        },
        "process_ici": telemetry.ledger.registry_view().get("ici"),
    }


def _agg_only(spark):
    from spark_rapids_tpu.api import functions as F

    return (spark.read.parquet(DATA_DIR)
            .groupBy("store")
            .agg(F.sum("amount").alias("rev"),
                 F.count("*").alias("sales")))


def run_hosts(n: int) -> Dict:
    """The multi-host axis (PR 17): the SAME n chips flat (1xn — every
    exchange on ICI) vs split into two simulated host failure domains
    (2 x n/2 — hash exchanges keep their heavy stage on ICI, only the
    cross-host stage and reduced partial-agg buffers cross DCN). On one
    machine both fabrics are the same host backplane, so wall-clock is
    flat by construction; the measurement is the LEDGER split the
    DCN-aware planner produces — byte COUNTS, which hold on any
    backend: `dcn_vs_ici` for the q5 exchange-bearing plan (must stay
    < 1), and `dcn_reduction_factor` (ici/dcn) for an agg-only shape —
    the factor by which the reduce-then-DCN placement keeps traffic on
    the fast fabric rather than the cross-host links."""
    ensure_data()

    def ledger(spark, q):
        out = q(spark).collect_arrow()
        rec = spark.last_execution or {}
        tel = rec.get("telemetry") or {}
        moved = tel.get("bytesMoved") or {}
        return out, rec.get("engine"), {
            "iciBytes": moved.get("ici", 0),
            "dcnBytes": moved.get("dcn", 0),
        }

    spark = _session({"spark.rapids.tpu.mesh": n})
    try:
        out_flat, eng_flat, flat = ledger(spark, _q5)
    finally:
        spark.stop()

    spark = _session({"spark.rapids.tpu.mesh": n,
                      "spark.rapids.tpu.multihost.simulatedHosts": 2})
    try:
        out_2x4, eng_2x4, q5_2x4 = ledger(spark, _q5)
        _, _, agg_2x4 = ledger(spark, _agg_only)
    finally:
        spark.stop()

    assert eng_flat == "mesh" and eng_2x4 == "mesh", (eng_flat, eng_2x4)
    flat_rev = {r: round(v, 2) for r, v in zip(
        out_flat.column("region").to_pylist(),
        out_flat.column("rev").to_pylist())}
    rev_2x4 = {r: round(v, 2) for r, v in zip(
        out_2x4.column("region").to_pylist(),
        out_2x4.column("rev").to_pylist())}
    assert set(flat_rev) == set(rev_2x4), (flat_rev, rev_2x4)

    dcn, ici = q5_2x4["dcnBytes"], q5_2x4["iciBytes"]
    adcn, aici = agg_2x4["dcnBytes"], agg_2x4["iciBytes"]
    return {
        "metric": f"q5 byte placement, 1x{n} flat vs 2x{n // 2} host "
                  f"domains (hash exchanges on ICI, reduced traffic "
                  f"on DCN)",
        "q5_flat": flat,
        "q5_2hosts": {**q5_2x4,
                      "dcn_vs_ici": (round(dcn / ici, 3) if ici
                                     else None)},
        "agg_2hosts": agg_2x4,
        "dcn_reduction_factor": round(aici / adcn, 3) if adcn else None,
    }


def main() -> None:
    from spark_rapids_tpu.obs.telemetry import require_tpu

    # a virtual mesh checks answers and byte counts
    # (tests/test_mesh_query.py, ci/multichip_check.sh), never speed
    require_tpu("multichip_bench")
    shards = device_shards()
    block = run_scaling(shards)
    if max(shards) >= 4:
        block["hosts"] = run_hosts(max(shards))
    print(json.dumps(block))


if __name__ == "__main__":
    main()
