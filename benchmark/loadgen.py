"""The traffic generator: one general loop that a traffic file
parameterises (`traffic/<name>.json`).

`loop: closed`, `clients: 1`: a notebook, a BI tool or an ETL job
submits its next query when the last one returned. The queries go in
the file's order, round and round; the seed only picks where the
rotation starts, so every seed gives the same work in another order.
The clock is the caller's, around `collect_arrow()`, which ends with
the Arrow table on the host.

The window closes with the query that is running when `seconds` have
passed: a query that has started is finished and counted, and the
window's length is taken to its end, so a rate is all the work over
all the time whatever the length of one query.
"""

import time


def closed_loop(spark, plan: list, seconds: float, seed: int, not_fused
                ) -> dict:
    """`plan`: [(query name, DataFrame)]. `not_fused(record)` says why
    an execution does not count, or ''. -> what the window saw."""
    import jax

    answers, latencies, names, failures = [], [], [], []
    programs = 0
    at = seed % len(plan)
    attempted = 0
    begin = time.perf_counter()
    now = begin
    while now - begin < seconds:
        name, df = plan[at]
        at = (at + 1) % len(plan)
        attempted += 1
        t0 = now
        try:
            with jax.profiler.TraceAnnotation("bench:query:" + name):
                out = df.collect_arrow()
            now = time.perf_counter()
            rec = spark.last_execution
            why = not_fused(rec)
            programs += rec["compile"]["programsCompiled"]
        except Exception as e:  # the cell goes on; the query is failed
            now = time.perf_counter()
            why = f"{type(e).__name__}: {e}"
        if why:
            failures.append(f"{name}: {why}")
            continue
        answers.append((name, out))
        names.append(name)
        latencies.append(now - t0)
    return {"window_s": now - begin, "attempted": attempted,
            "failed": len(failures), "failures": failures,
            "answers": answers, "names": names, "latencies_s": latencies,
            "programs_compiled": programs}
