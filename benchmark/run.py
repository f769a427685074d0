"""Run one cell of BENCHMARK.json once, on the chip.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, the only one that touches JAX. Set-up (everything before
the window): make the configuration's tables from the seed as parquet
in a temporary directory, open a `TpuSparkSession`, read the tables
(and cache them on the device where the traffic says so), run every
query of the traffic twice so that each program is compiled or loaded
from the disk cache. Then the window: the traffic's closed loop drives
`collect_arrow()` for `--seconds` seconds on the caller's clock. After
it: the peak device memory is read, the session is stopped, and every
answer of the window is compared with the query's plain reference on
the same files. The last line of standard output is the result.

On any platform but `tpu`, or with fewer chips than the cell asks
for, the run exits non-zero and prints no result.

What belongs to one cell is data found by name: the configuration
`configs/<config>.json` with its generator `datagen/<generator>.py`,
the traffic `traffic/<traffic>.json`, each query `queries/<query>.py`,
each metric's reader `end_to_end/<metric>.py` or
`layer_metrics/<metric>.py`, the limits of the comparison
`limits/<cell>.json`. A new cell is new files and one entry in
BENCHMARK.json.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WINDOW_MARK = "bench:window"
#: Every query of the traffic runs this many times before the window:
#: the first compiles or loads, the second shows the hot path is hot.
WARM_RUNS = 2


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py, by path: a metric's name has dots."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(name: str) -> dict:
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(has: {sorted(cells)})")
    cell = dict(cells[name])
    files = {c["name"]: c["file"] for c in bench["configs"]}
    cell["config"] = load_json(ROOT, files[cell["config"]])
    cell["traffic"] = load_json(HERE, "traffic", cell["traffic"] + ".json")
    cell["limits"] = load_json(HERE, "limits", name + ".json")
    cell["queries"] = {q: load_module("queries", q)
                       for q in cell["traffic"]["queries"]}

    def reported(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    cell["end_to_end"] = reported(bench["end_to_end"])
    cell["per_layer"] = reported(bench["per_layer"])
    return cell


def require_device(chips: int, any_platform: bool = False):
    """The first device, or SystemExit: no chip, no result."""
    import jax

    jax.config.update("jax_enable_x64", True)
    devices = jax.devices()
    if not any_platform and devices[0].platform != "tpu":
        raise SystemExit(
            f"benchmark/run.py measures a TPU; JAX found platform "
            f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chip(s), JAX found "
                         f"{len(devices)}")
    return devices[0]


def session_conf(config: dict) -> dict:
    """The configuration's session settings, and where the compile
    cache lives: where JAX_COMPILATION_CACHE_DIR says if it is set
    (the program then sets no other), else a fixed directory here."""
    conf = dict(config["session_conf"])
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        conf["spark.rapids.tpu.compileCache.dir"] = os.path.join(
            HERE, ".compile_cache")
    return conf


def open_tables(spark, cell: dict, dirs: dict) -> dict:
    tables = {}
    for name, mode in cell["traffic"]["tables"].items():
        df = spark.read.parquet(dirs[name])
        tables[name] = df.cache(storage="device") if mode == "device" else df
    return tables


def check_cached(spark, cell: dict, tables: dict) -> None:
    """A device-cached table has one part per parquet file: fewer means
    that the cache was filled by a host collect and one upload, the
    silent fallback of exec/relation_cache.py."""
    for name, mode in cell["traffic"]["tables"].items():
        if mode != "device":
            continue
        entry = spark.cache_manager.lookup(tables[name]._plan)
        parts = entry.num_parts() if entry is not None else 0
        files = len(os.listdir(tables[name]._plan.paths[0]))
        if parts != files:
            raise SystemExit(
                f"cached table {name}: {parts} device part(s) for {files} "
                f"file(s); the relation cache left the fused engine")


def not_fused(rec: dict) -> str:
    """Why this execution does not count, or ''."""
    if rec is None:
        return "no execution record"
    if rec["engine"] != "fused" or rec["fallbacks"] or rec["degradations"]:
        return (f"engine={rec['engine']!r} fallbacks={rec['fallbacks']} "
                f"degradations={rec['degradations']}")
    return ""


def ledger_totals() -> dict:
    """Process-wide bytes, ns and count per direction and per site."""
    from spark_rapids_tpu.obs import telemetry

    by_dir, by_site = {}, {}
    for row in telemetry.ledger.site_rows():
        cell = by_dir.setdefault(row["direction"],
                                 {"bytes": 0, "ns": 0, "count": 0})
        for k in cell:
            cell[k] += row[k]
        by_site[row["site"]] = {k: row[k] for k in ("bytes", "ns", "count")}
    return {"direction": by_dir, "site": by_site}


def ledger_delta(before: dict, after: dict) -> dict:
    out = {}
    for view in after:
        out[view] = {}
        for key, cell in after[view].items():
            was = before[view].get(key, {})
            d = {k: v - was.get(k, 0) for k, v in cell.items()}
            if any(d.values()):
                out[view][key] = d
    return out


def say(*words) -> None:
    print(*words, file=sys.stderr, flush=True)


def judge(cell: dict, window: dict, controls=()) -> tuple:
    """Every answer of the window against its query's plain reference
    on the same files -> the numbers compared, each with its limit.
    `controls` names lower precisions: the reference is then computed
    in each, put in the program's place and compared the same way
    (benchmark/control.py); -> their readings beside the numbers."""
    import pyarrow.parquet as pq

    from benchmark import compare

    queries, limits = cell["queries"], cell["limits"]
    specs = {q: m.ANSWER for q, m in queries.items()}
    host = {n: pq.read_table(d) for n, d in window["dirs"].items()}
    references = {q: m.reference(host) for q, m in queries.items()}
    tie_tol = limits["sum_rel_err"]
    compared = compare.compare_all(window["answers"], references, specs,
                                   tie_tol)
    compared["failed"] = window["failed"]
    numbers = {k: {"value": compared[k], "limit": limits[k]}
               for k in ("rows_wrong", "sum_rel_err", "failed")}
    readings = {}
    for precision in controls:
        stand_in = [(q, m.reference(host, precision=precision)
                     .slice(0, specs[q]["limit"]))
                    for q, m in queries.items()]
        readings[precision] = compare.compare_all(stand_in, references, specs,
                                              tie_tol)
    return numbers, readings


@contextlib.contextmanager
def scratch_dirs():
    """Where the tables and the trace go: under TMPDIR, gone at exit."""
    dirs = [tempfile.mkdtemp(prefix="srtpu_bench_"),
            tempfile.mkdtemp(prefix="srtpu_bench_trace_")]
    try:
        yield dirs
    finally:
        for d in dirs:
            shutil.rmtree(d, ignore_errors=True)


def measure(cell: dict, device, seed: int, seconds: float, trace: bool,
            data_dir: str, trace_dir: str, rows: int = None) -> dict:
    """Set-up and the window; the session is stopped on the way out.
    -> what the window saw, with `dirs`, `setup_s`, `first_query_s`,
    the ledger's delta and the device's peak memory."""
    import jax
    from spark_rapids_tpu.api.session import TpuSparkSession
    from spark_rapids_tpu.runtime import compile_cache

    from benchmark import loadgen

    def at(what: str) -> None:
        say(f"setup: {what} at {time.perf_counter() - T_START:.2f} s")

    config, traffic = cell["config"], cell["traffic"]
    at("device and imports ready")
    generator = load_module("datagen", config["generator"])
    dirs = generator.generate(config, seed, data_dir, rows=rows)
    at("tables generated")
    spark = TpuSparkSession(session_conf(config))
    try:
        tables = open_tables(spark, cell, dirs)
        plan = [(q, cell["queries"][q].build(spark, tables))
                for q in traffic["queries"]]
        first_query_s = None
        for run in range(WARM_RUNS):
            for q, df in plan:
                t = time.perf_counter()
                df.collect_arrow()
                took = time.perf_counter() - t
                if first_query_s is None:
                    first_query_s = took
                why = not_fused(spark.last_execution)
                if why:
                    raise SystemExit(f"warm-up, {q}: {why}")
                say(f"setup: {q} run {run}: {took:.3f} s, compile "
                    f"{spark.last_execution['compile']}")
        check_cached(spark, cell, tables)
        compile_cache.warmup_join(300)
        compile_cache.flush()
        if trace:
            from spark_rapids_tpu.obs import telemetry

            say("setup: link", json.dumps(telemetry.link_peaks(refresh=True)))
        gc.collect()
        gc.freeze()
        at("window opens")
        setup_s = time.perf_counter() - T_START

        ledger_before = ledger_totals()
        if trace:
            # a trace holds an event for every operation the device runs,
            # half a million a second: the traced window is the traffic's
            # `trace_seconds` where that is shorter
            seconds = min(seconds, traffic["trace_seconds"])
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_MARK):
                window = loadgen.closed_loop(spark, plan, seconds, seed,
                                             not_fused)
        finally:
            if trace:
                jax.profiler.stop_trace()
            gc.unfreeze()
        stats = device.memory_stats() or {}
        window.update(
            dirs=dirs, setup_s=setup_s, first_query_s=first_query_s,
            ledger=ledger_delta(ledger_before, ledger_totals()),
            memory_peak_bytes=int(stats.get("peak_bytes_in_use", 0)))
        return window
    finally:
        spark.stop()


def read_trace(trace_dir: str, chips: int, cpu_stand_in: bool) -> dict:
    from benchmark import trace_reduce

    t = time.perf_counter()
    xplane = trace_reduce.find_xplane(trace_dir)
    loaded = trace_reduce.load_trace(xplane, cpu_stand_in=cpu_stand_in)
    t0, t1 = trace_reduce.window_of(loaded, WINDOW_MARK)
    reduced = trace_reduce.reduce_trace(loaded, t0, t1, chips)
    say(f"trace: {os.path.getsize(xplane) / 1e6:.0f} MB read and reduced "
        f"in {time.perf_counter() - t:.2f} s")
    return reduced


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             rows: int = None, any_platform: bool = False,
             controls=()) -> dict:
    """The whole run; -> the result line as a dict. `rows`,
    `any_platform` and `controls` are the hooks of the rehearsal, the
    tests and benchmark/control.py: the command never sets them."""
    import jax

    cell = load_cell(name)
    device = require_device(cell["chips"], any_platform)
    peaks = load_json(HERE, "peaks.json")["device_kind"]
    if device.device_kind not in peaks and not any_platform:
        raise SystemExit(f"device kind {device.device_kind!r} is not in "
                         f"benchmark/peaks.json")
    with scratch_dirs() as (data_dir, trace_dir):
        window = measure(cell, device, seed, seconds, trace, data_dir,
                         trace_dir, rows)
        reduced = None
        if trace:
            reduced = read_trace(trace_dir, cell["chips"], any_platform)
            say("trace: sites", json.dumps(window["ledger"]["site"]))
        t = time.perf_counter()
        numbers, control_readings = judge(cell, window, controls)
        say(f"reference and comparison: {time.perf_counter() - t:.2f} s")

    done = len(window["latencies_s"])
    correct = done > 0 and all(
        n["value"] <= n["limit"] for n in numbers.values())
    ctx = {"cell": cell, "config": cell["config"], "window": window,
           "done": done, "trace": reduced,
           "peaks": peaks.get(device.device_kind)}
    kind = "layer_metrics" if trace else "end_to_end"
    metrics = {}
    for m in cell["per_layer"] if trace else cell["end_to_end"]:
        value = load_module(kind, m["name"]).read(ctx) if done else None
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()),
           "memory_peak_bytes": window["memory_peak_bytes"]}
    result = {"correct": bool(correct), "attempted": window["attempted"],
              "failed": window["failed"], "metrics": metrics, "device": dev}
    if reduced is not None:
        dev["busy_s"], dev["window_s"] = reduced["busy_s"], reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    if control_readings:
        result["controls"] = control_readings
    result["compared"] = numbers
    for why in window["failures"][:5]:
        say("failed:", why)
    if done:
        lat = sorted(window["latencies_s"])
        say(f"window: {done} queries in {window['window_s']:.3f} s; latency "
            f"min {lat[0] * 1e3:.1f}, median {lat[done // 2] * 1e3:.1f}, "
            f"max {lat[-1] * 1e3:.1f} ms")
    say(f"answers compared: {len(window['answers'])}")
    for k, n in numbers.items():
        say(f"compared: {k} = {n['value']} (limit {n['limit']})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
