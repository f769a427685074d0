"""chip_smoke.py — the quickest proof that the engine still starts on a
TPU: session -> planner -> fused engine, once, through the entry points
a user calls, at the one data size with a chip history.

    python chip_smoke.py            # one chip (what the driver runs)
    python chip_smoke.py --chips 4  # only the across-chips path

One process, the only one that touches JAX (a chip belongs to one
process; there is no probing child). On a platform other than `tpu`
the script exits non-zero at its first phase and prints no result —
the CPU rehearsal drives the phases below by importing them
(tests/test_chip_smoke.py).

Default run, every phase one short JSON line on stdout:

- device:  kind, count, HBM limit as the device reports it, the link
           as measured now, native runtime or pure Python, cache dir.
- data:    bench.py's tables from --seed (36M-row fact of 4 x 8 B
           columns, 1,098 MiB; 2000-row dim with a string region; the
           2-rows-per-key dup dim). --rows shrinks it for rehearsals.
- query x3 through TpuSparkSession -> read.parquet -> collect_arrow():
           an UNCACHED scan -> filter -> aggregate (parquet decode and
           H2D upload on the path), then over cache(storage="device")
           the q5 lookup-join + string group-by and the dup-key join.
           Each answer is compared with pyarrow on the same data and
           must have run on the fused engine with no fallback,
           degradation or NOT_ON_TPU placement: a smoke that passed on
           a lower rung of the ladder has not passed.
- served:  session.serve() in this process, three tenants' threads
           through ServeClient; answers equal the direct collects.

Cold, nearly all of the run is XLA compilation (CHANGES.md PR 21 has
the table): the served spec is the scan query's own shape so that it
adds two small programs, not a third two-minute one.

With --chips 4 it runs ONLY the mesh path and what it is compared
with: q5 with spark.rapids.tpu.mesh=4 against the one-chip fused
answer and pyarrow; engine `mesh`, ICI bytes > 0, result shards on
four distinct devices.

Any phase that raises ends the run non-zero. The last line is the
result: {"ok": true, "device": {"platform", "kind", "count"}}.
"""

import argparse
import json
import shutil
import statistics
import sys
import tempfile
import threading
import time

import pyarrow.compute as pc
import pyarrow.parquet as pq

import bench

HOT_RUNS = 3
#: No further hot repeat of a query once its repeats have used this
#: much: on a v5e the dup-key join takes ~2.5 min per run, and three
#: of them would be most of the run's time limit for one median.
HOT_BUDGET_S = 60.0
SCAN_QTY_OVER = 50
SERVE_TENANTS = (("acme", "interactive"), ("globex", "standard"),
                 ("initech", "batch"))
SERVE_BINDINGS = ({"lo": SCAN_QTY_OVER}, {"lo": 20}, {"lo": 80})

#: What this run sets beside bench.py's session conf. The dup-key
#: join lowers to the blocking expanded join, whose static output
#: capacity is next_pow2(expansionFactor x padded probe capacity): at
#: the default factor 4 that is 2^28 rows = 14.6 GB of output + 4.9 GB
#: of temporaries for 36M probe rows (the TPU compiler's own
#: memory_analysis), which no 16 GB chip holds. Factor 1 gives 2^26
#: rows, still above the 36M rows the join really produces.
SMOKE_CONF = {"spark.rapids.sql.fusedExec.expansionFactor": 1}


class SmokeFailure(AssertionError):
    """A phase ran and its outcome is not the one the smoke accepts."""


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# ------------------------------------------------------------- queries

def scan_query(fact, qty_over: int = SCAN_QTY_OVER):
    """Scan -> filter -> aggregate by the 2000-value store key: with no
    device cache under it, parquet decode and H2D upload are on the
    path, and the aggregate takes the binned one-hot-matmul reductions
    at 2000 groups."""
    from spark_rapids_tpu.api import functions as F

    return (fact.filter(F.col("qty") > qty_over)
            .groupBy("store")
            .agg(F.sum("amount").alias("rev"),
                 F.count("*").alias("sales")))


def cpu_scan_query(t):
    f = t.filter(pc.greater(t.column("qty"), SCAN_QTY_OVER))
    return f.group_by("store").aggregate(
        [("amount", "sum"), ("store", "count")])


def check_scan(out, cpu_out) -> None:
    bench.check_grouped(out, cpu_out, "store",
                        sums=[("rev", "amount_sum")],
                        counts=[("sales", "store_count")])


def served_spec(fact_dir: str) -> dict:
    """scan_query as a wire spec, its literal a bound parameter."""
    return {"op": "agg",
            "input": {"op": "filter",
                      "input": {"op": "parquet", "path": fact_dir},
                      "cond": {"fn": ">", "args": [{"col": "qty"},
                                                   {"param": "lo"}]}},
            "groupBy": ["store"],
            "aggs": [{"fn": "sum", "col": "amount", "as": "rev"},
                     {"fn": "count", "as": "sales"}]}


def _by_store(table) -> dict:
    return {s: (r, n) for s, r, n in zip(
        table.column("store").to_pylist(),
        table.column("rev").to_pylist(),
        table.column("sales").to_pylist())}


# -------------------------------------------------------------- phases

def phase_device(chips: int):
    """The device this run proves, or SystemExit. Prints what the
    device and the link say of themselves; guesses nothing."""
    import jax

    from spark_rapids_tpu import native
    from spark_rapids_tpu.obs import telemetry
    from spark_rapids_tpu.runtime import compile_cache

    dev = telemetry.require_tpu("chip_smoke.py")
    devs = jax.devices()
    if len(devs) < chips:
        raise SystemExit(
            f"--chips {chips} needs {chips} devices, JAX found "
            f"{len(devs)}")
    stats = dev.memory_stats() or {}
    if "bytes_limit" not in stats:
        raise SmokeFailure(f"{dev.device_kind} reports no bytes_limit")
    compile_cache.configure()
    link = telemetry.link_peaks(refresh=True)  # unknown kind: KeyError
    say("device", platform=dev.platform, kind=dev.device_kind,
        count=len(devs), hbmBytesLimit=int(stats["bytes_limit"]),
        hbmPeakBytesPerS=link["devicePeakBytesPerS"],
        h2dBytesPerS=link["h2dBytesPerS"],
        d2hBytesPerS=link["d2hBytesPerS"],
        roundTripMs=link["roundTripMs"],
        nativeRuntime=native.runtime_in_use(),
        compileCacheDir=compile_cache.cache_dir(),
        jaxCacheDir=jax.config.jax_compilation_cache_dir)
    return dev


def phase_data(root: str, rows: int, seed: int) -> bench.BenchData:
    t0 = time.perf_counter()
    data = bench.ensure_data(root, rows, seed)
    say("data", rows=rows, seed=seed,
        factMiB=round(data.fact_bytes / 2**20, 1),
        seconds=round(time.perf_counter() - t0, 2))
    return data


def _require_engine(spark, name: str, engine: str) -> dict:
    """last_execution must show `engine` with nothing under it."""
    rec = spark.last_execution
    if (rec["engine"] != engine or rec["fallbacks"]
            or rec["degradations"]):
        raise SmokeFailure(
            f"{name}: engine={rec['engine']!r} (want {engine!r}) "
            f"fallbacks={rec['fallbacks']} "
            f"degradations={rec['degradations']}")
    return rec


def run_query(spark, name: str, df, check, engine: str = "fused"):
    """One query cold, then up to HOT_RUNS hot (while HOT_BUDGET_S
    lasts); placed, checked, counted. -> the cold answer."""
    from spark_rapids_tpu.explain import explain_potential_tpu_plan

    placement = explain_potential_tpu_plan(df, mode="NOT_ON_TPU")
    if "NOT_ON_TPU" in placement:
        raise SmokeFailure(f"{name}: {placement}")
    t0 = time.perf_counter()
    out = df.collect_arrow()
    cold_s = time.perf_counter() - t0
    rec = _require_engine(spark, name, engine)
    check(out)
    hot = []
    while len(hot) < HOT_RUNS and sum(hot) < HOT_BUDGET_S:
        t0 = time.perf_counter()
        check(df.collect_arrow())
        hot.append(time.perf_counter() - t0)
        _require_engine(spark, name, engine)
    say("query", name=name, engine=engine, correct=True,
        coldSeconds=round(cold_s, 3),
        hotMedianSeconds=round(statistics.median(hot), 4),
        hotRuns=len(hot),
        compile=rec["compile"],  # of the cold run
        bytesMoved=(rec.get("telemetry") or {}).get("bytesMoved"))
    return out


def phase_queries(spark, data: bench.BenchData):
    """The three fused queries against pyarrow on the same files."""
    host_fact = pq.read_table(data.fact_dir)
    host_dim = pq.read_table(data.dim_dir)
    host_dup = pq.read_table(data.dup_dir)
    want_scan = cpu_scan_query(host_fact)
    want_q5 = bench.cpu_query(host_fact, host_dim)
    want_dup = bench.cpu_dupjoin_query(host_fact, host_dup)
    del host_fact

    # uncached FIRST: once the fact relation is registered as
    # device-cached, structurally equal scans are served from HBM
    run_query(spark, "scan_filter_agg_uncached",
              scan_query(spark.read.parquet(data.fact_dir)),
              lambda out: check_scan(out, want_scan))
    base = spark.read.parquet(data.fact_dir).cache(storage="device")
    dim = spark.read.parquet(data.dim_dir).cache(storage="device")
    dup = spark.read.parquet(data.dup_dir).cache(storage="device")
    run_query(spark, "q5_lookup_join_string_groupby",
              bench.engine_query(base, dim),
              lambda out: bench.check_q5(out, want_q5))
    run_query(spark, "dupkey_join_expanded",
              bench.dupjoin_query(base, dup),
              lambda out: bench.check_dupjoin(out, want_dup))


def phase_served(spark, data: bench.BenchData, rounds: int = 2) -> None:
    """session.serve() over the same warm session: three tenants send
    `rounds` requests each from their own threads; every served answer
    equals the direct collect under the same binding; clean drain."""
    from spark_rapids_tpu.serve.client import ServeClient

    spec = served_spec(data.fact_dir)
    fact = spark.read.parquet(data.fact_dir)
    direct = [_by_store(scan_query(fact, b["lo"]).collect_arrow())
              for b in SERVE_BINDINGS]
    daemon = spark.serve()
    lat_ms, errors = [], []
    lock = threading.Lock()

    def tenant_loop(tenant: str, pclass: str) -> None:
        try:
            with ServeClient.connect(daemon, tenant, pclass) as c:
                for r in range(rounds):
                    i = r % len(SERVE_BINDINGS)
                    t0 = time.perf_counter()
                    got = _by_store(c.query(spec,
                                            params=SERVE_BINDINGS[i]))
                    ms = (time.perf_counter() - t0) * 1000.0
                    if got != direct[i]:
                        raise SmokeFailure(
                            f"served answer for {tenant} under "
                            f"{SERVE_BINDINGS[i]} differs from the "
                            f"direct collect")
                    with lock:
                        lat_ms.append(ms)
        except BaseException as e:  # re-raised from the main thread
            with lock:
                errors.append(e)

    try:
        threads = [threading.Thread(target=tenant_loop, args=tp)
                   for tp in SERVE_TENANTS]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        alive = [t.name for t in threads if t.is_alive()]
        hit_ratio = daemon.plan_cache.stats.snapshot()["hitRatio"]
    finally:
        report = daemon.drain()
        daemon.stop()
    if errors:
        raise errors[0]
    if alive or len(lat_ms) != rounds * len(SERVE_TENANTS):
        raise SmokeFailure(
            f"served: {len(lat_ms)} answers, threads alive: {alive}")
    if report.get("inFlight") or report.get("cancelled"):
        raise SmokeFailure(f"served: unclean drain: {report}")
    say("served", tenants=len(SERVE_TENANTS), answered=len(lat_ms),
        correct=True,
        latencyMsMedian=round(statistics.median(lat_ms), 2),
        latencyMsMax=round(max(lat_ms), 2),
        planCacheHitRatio=hit_ratio, drain=report)


def phase_mesh(data: bench.BenchData, conf: dict, chips: int) -> None:
    """q5 on `chips` devices as one SPMD program, against the one-chip
    fused answer and pyarrow. Reads parquet on both sides: the mesh
    engine ingests per shard, it does not reshard a one-chip cache."""
    from spark_rapids_tpu.api.session import TpuSparkSession

    want = bench.cpu_query(pq.read_table(data.fact_dir),
                           pq.read_table(data.dim_dir))

    def q5(spark):
        return bench.engine_query(spark.read.parquet(data.fact_dir),
                                  spark.read.parquet(data.dim_dir))

    spark = TpuSparkSession(conf)
    try:
        one = run_query(spark, "q5_one_chip", q5(spark),
                        lambda out: bench.check_q5(out, want))
    finally:
        spark.stop()
    spark = TpuSparkSession({**conf, "spark.rapids.tpu.mesh": chips})
    try:
        def check(out):
            bench.check_q5(out, want)
            bench.check_grouped(out, one, "region",
                                sums=[("rev", "rev")],
                                counts=[("sales", "sales")])

        run_query(spark, f"q5_mesh_{chips}", q5(spark), check,
                  engine="mesh")
        moved = (spark.last_execution["telemetry"] or {}).get(
            "bytesMoved") or {}
        devices = spark.last_execution["meshDevices"]
    finally:
        spark.stop()
    if not moved.get("ici", 0) > 0:
        raise SmokeFailure(f"mesh: no ICI bytes recorded: {moved}")
    if len(set(devices)) != chips:
        raise SmokeFailure(
            f"mesh: result shards on devices {devices}, want {chips} "
            f"distinct")
    say("mesh", chips=chips, iciBytes=moved["ici"],
        resultShardDevices=devices, h2dBytes=moved.get("h2d", 0),
        shuffleHostBytes=moved.get("shuffle", 0))


# ---------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the across-chips path")
    ap.add_argument("--rows", type=int, default=bench.ROWS,
                    help="fact rows (shrink for rehearsals only)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_enable_x64", True)
    dev = phase_device(args.chips)

    from spark_rapids_tpu.api.session import TpuSparkSession
    from spark_rapids_tpu.runtime import compile_cache

    root = tempfile.mkdtemp(prefix="srtpu_chip_smoke_")
    try:
        data = phase_data(root, args.rows, args.seed)
        conf = {**bench._session_conf(), **SMOKE_CONF}
        if args.chips > 1:
            phase_mesh(data, conf, args.chips)
        else:
            spark = TpuSparkSession(conf)
            try:
                phase_queries(spark, data)
                phase_served(spark, data)
            finally:
                spark.stop()
        from spark_rapids_tpu.obs import telemetry

        view = telemetry.ledger.registry_view()
        say("totals", compileCacheDir=compile_cache.cache_dir(),
            compile=compile_cache.stats.snapshot(),
            # process-wide: uploads made on reader threads are not
            # attributed to their query's own ledger
            bytesMoved=view["bytesMoved"],
            poolPeakBytes=view["hbm"]["peakBytes"],
            devicePeakBytesInUse=(dev.memory_stats() or {}).get(
                "peak_bytes_in_use"))
    finally:
        shutil.rmtree(root, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
