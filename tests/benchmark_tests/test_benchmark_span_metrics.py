"""The six `program_span` metrics on hand-written span trees with
known answers, what their readers do with a program that has no ring
of trees, and the names the fused programs carry into the device
trace: the same in every process."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import run, span_window
from spark_rapids_tpu.obs import spans as S

MS = 1_000_000


def node(name, start_ms, end_ms, *children):
    sp = S.Span("operator", name)
    sp.start_ns, sp.end_ns = int(start_ms * MS), int(end_ms * MS)
    sp.wall_ns = sp.end_ns - sp.start_ns
    sp.status = "ok"
    sp.children = list(children)
    return sp


def tree(at_ms, status="ok", engine="fused", **extra):
    """One query of 100 ms that begins at `at_ms`: entry work 1 + 2 ms
    around plan (4 ms) and fused.execute (93 ms); prepare 40 ms with
    two reader threads decoding 25 and 30 ms at once and two uploads
    that overlap by 5 ms, the second outliving prepare; three
    dispatches of 2, 3 and 1 ms, the first compiling for 1.5 ms."""
    t = at_ms

    def n(name, a, b, *ch):
        return node(name, t + a, t + b, *ch)

    prepare = n("fused.prepare", 5, 45,
                n("scan.decode", 6, 31), n("scan.decode", 7, 37),
                n("scan.h2d", 31, 41), n("scan.h2d", 36, 48))
    execute = n("fused.execute", 5, 98, prepare,
                n("fused.dispatch", 45, 47, n("compile", 45.25, 46.75)),
                n("fused.dispatch", 47, 50), n("fused.dispatch", 50, 51),
                n("fetch", 51, 97.5))
    root = n("query-1", 0, 100, n("plan", 1, 5), execute)
    root.kind, root.status = "query", status
    root.extra.update(engine=engine, fallbacks=0, degradations=0, **extra)
    return root


def ctx_of(trees, h2d_bytes=34_000_000):
    S.ring.clear()
    for t in trees:
        S.ring.append(t)
    return {"done": len(trees), "window": {
        "attempted": len(trees),
        "ledger": {"direction": {"h2d": {"bytes": h2d_bytes}}}}}


@pytest.fixture
def window():
    # an older query that the window must not count, then the window:
    # two good queries, one that failed and one that left the engine
    S.ring.clear()
    S.ring.append(tree(-1000))
    ctx = ctx_of([])
    for t in (tree(0), tree(200, status="error"),
              tree(400, engine="eager"), tree(600)):
        S.ring.append(t)
    ctx["window"]["attempted"] = 4
    ctx["done"] = 2
    yield ctx
    S.ring.clear()


@pytest.mark.parametrize("metric, expected", [
    ("entry.self_ms_per_query", 100 - 4 - 93),
    ("plan.ms_per_query", 4.0),
    ("scan.prepare_ms_per_query", 40.0),
    ("scan.decode_busy_ms_per_query", 25.0 + 30.0),
    # 34 MB a query over the union [31, 48] ms of its two uploads
    ("scan.h2d_gb_per_s", 34e6 / (2 * 17e-3) / 1e9),
    ("fused.dispatch_ms_per_query", 2 + 3 + 1 - 1.5),
])
def test_reader_on_known_trees(window, metric, expected):
    value = run.load_module("layer_metrics", metric).read(window)
    assert value == pytest.approx(expected, rel=1e-9)


def test_window_is_the_newest_attempted_trees_minus_the_failed(window):
    trees = span_window.window_trees(window)
    assert [t.start_ns // MS for t in trees] == [0, 600]
    window["window"]["attempted"] = 6  # more than the ring ever saw
    assert span_window.window_trees(window) is None


def test_readers_find_nothing_in_a_program_without_the_ring(
        window, monkeypatch):
    """The parent of the PR that added the spans has no `spans.ring`:
    each reader returns None, none raises."""
    monkeypatch.delattr(S, "ring")
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    mine = [m["name"] for m in bench["per_layer"]
            if m["source"] == "program_span"]
    assert len(mine) == 6
    for name in mine:
        assert run.load_module("layer_metrics", name).read(window) is None


def test_union_of_intervals():
    assert span_window.union_ns([]) == 0
    assert span_window.union_ns([(5, 9), (0, 3), (2, 4), (8, 8)]) == 8


# --- program names: the same plan, two processes, one name ---

NAMES = """
import json, sys
import numpy as np, pyarrow as pa, pyarrow.parquet as pq
import spark_rapids_tpu.api.functions as F
from spark_rapids_tpu.api.session import TpuSparkSession
from spark_rapids_tpu.obs import spans

d = sys.argv[1]
s = TpuSparkSession({"spark.rapids.tpu.compileCache.enabled": False})
q = (s.read.parquet(d).filter(F.col("v") > 5.0).groupBy("k")
     .agg(F.sum("v").alias("sv")).orderBy("k"))
q.collect_arrow()
assert s.last_execution["engine"] == "fused"
names = [sp.extra["program"] for sp in spans.ring.last(1)[0].walk()
         if sp.name == "fused.dispatch"]
s.stop()
print(json.dumps(names))
"""


def test_program_names_are_the_same_in_two_processes(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    d = tmp_path / "t"
    d.mkdir()
    for i in range(2):
        pq.write_table(pa.table({
            "k": [j % 3 for j in range(500)],
            "v": [float(j) for j in range(500)]}),
            str(d / f"p{i}.parquet"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONHASHSEED="random")
    runs = []
    for _ in range(2):
        done = subprocess.run(
            [sys.executable, "-c", NAMES, str(d)], env=env, cwd=run.ROOT,
            capture_output=True, text=True, timeout=300)
        assert done.returncode == 0, done.stderr[-2000:]
        runs.append(json.loads(done.stdout.strip().splitlines()[-1]))
    assert runs[0] == runs[1]
    kinds = {n.rsplit("_", 1)[0] for n in runs[0]}
    assert {"fused_chain", "fused_agg", "fused_sort"} <= kinds
    for n in runs[0]:
        assert len(n.rsplit("_", 1)[1]) == 8
        int(n.rsplit("_", 1)[1], 16)


def test_the_xla_module_carries_the_program_name():
    """jit names the XLA module after the traced function: `jit_<name>`
    is what the device trace shows, from a live build and from a disk
    artifact that the warm-up thread loaded."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_tpu.exec.fused import program_name

    name = program_name("chain", (("TpuFilterExec", "x"),))
    assert name == program_name("chain", (("TpuFilterExec", "x"),))
    assert name != program_name("chain", (("TpuFilterExec", "y"),))
    assert name.startswith("fused_chain_")

    def fn(x):
        return x + 1

    fn.__name__ = fn.__qualname__ = name
    text = jax.jit(fn).lower(jnp.ones(4)).as_text()
    assert f"@jit_{name}" in text
