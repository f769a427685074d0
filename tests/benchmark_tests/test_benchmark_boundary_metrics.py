"""The four readers of the query boundary's spans (`fetch.wait`,
`fused.enqueue`, `plan.convert`) on hand-written span trees with known
answers; each finds nothing, and says None, where its spans are
absent; the readers that time these layers whole read the same with
the new children as without; and a traced rehearsal of a cell, with
the four metrics listed for it, reports each of them."""

import pytest

from benchmark import run
from spark_rapids_tpu.obs import spans as S

MS = 1_000_000
NEW = ["boundary.host_ms_per_query", "fetch.own_ms_per_query",
       "fused.dispatch_host_ms_per_query", "plan.convert_ms_per_query"]


def node(name, start_ms, end_ms, *children):
    sp = S.Span("operator", name)
    sp.start_ns, sp.end_ns = int(start_ms * MS), int(end_ms * MS)
    sp.wall_ns = sp.end_ns - sp.start_ns
    sp.status = "ok"
    sp.children = list(children)
    return sp


def tree(at_ms, status="ok", engine="fused", new=True):
    """One query of 100 ms that begins at `at_ms`: plan 1-5 ms, its
    conversion 2-4.5; three dispatches of 2, 3 and 1 ms, the first
    compiling for 1.5 ms (no enqueue), the others enqueueing for 1.5
    and 0.5 ms; a fetch 51-97.5 that waits on the device 52-90.
    `new=False`: the same tree as the parent of these spans gives it."""
    t = at_ms

    def n(name, a, b, *ch):
        return node(name, t + a, t + b, *ch)

    def only(*ch):
        return ch if new else ()

    execute = n("fused.execute", 5, 98,
                n("fused.prepare", 5, 45),
                n("fused.dispatch", 45, 47, n("compile", 45.25, 46.75)),
                n("fused.dispatch", 47, 50,
                  *only(n("fused.enqueue", 48, 49.5))),
                n("fused.dispatch", 50, 51,
                  *only(n("fused.enqueue", 50.25, 50.75))),
                n("fetch", 51, 97.5, *only(n("fetch.wait", 52, 90))))
    root = n("query-1", 0, 100,
             n("plan", 1, 5, *only(n("plan.convert", 2, 4.5))), execute)
    root.kind, root.status = "query", status
    root.extra.update(engine=engine, fallbacks=0, degradations=0)
    return root


def ctx_of(trees, counted):
    S.ring.clear()
    S.ring.append(tree(-1000))  # before the window: never counted
    for t in trees:
        S.ring.append(t)
    return {"done": counted, "window": {"attempted": len(trees)}}


@pytest.fixture
def window():
    """Five queries: two counted, one that failed, two counted. The
    pairs are (0, 200) and (600, 750); none crosses the failed one."""
    yield ctx_of([tree(0), tree(200), tree(400, status="error"),
                  tree(600), tree(750)], 4)
    S.ring.clear()


def read(metric, ctx):
    return run.load_module("layer_metrics", metric).read(ctx)


@pytest.mark.parametrize("metric, expected", [
    # from a query's fetch.wait end (90) to the next one's first
    # enqueue end (48-49.5; the compiling dispatch enqueues nothing)
    ("boundary.host_ms_per_query",
     ((200 + 49.5 - 90) + (750 + 49.5 - 690)) / 2),
    ("fetch.own_ms_per_query", 46.5 - 38),
    ("fused.dispatch_host_ms_per_query", (2 + 3 + 1) - (1.5 + 0.5) - 1.5),
    ("plan.convert_ms_per_query", 2.5),
])
def test_reader_on_known_trees(window, metric, expected):
    assert read(metric, window) == pytest.approx(expected, rel=1e-9)


def test_no_pair_crosses_a_query_that_does_not_count():
    try:
        ctx = ctx_of([tree(0), tree(200, engine="eager"), tree(400)], 2)
        assert read("boundary.host_ms_per_query", ctx) is None
        ctx = ctx_of([tree(0), tree(200, engine="eager"), tree(400),
                      tree(500)], 3)
        assert read("boundary.host_ms_per_query", ctx) == pytest.approx(
            500 + 49.5 - 490)
    finally:
        S.ring.clear()


@pytest.mark.parametrize("metric", NEW)
def test_trees_without_the_new_spans_give_none(metric):
    try:
        ctx = ctx_of([tree(a, new=False) for a in (0, 200, 400)], 3)
        assert read(metric, ctx) is None
    finally:
        S.ring.clear()


@pytest.mark.parametrize("metric", NEW)
def test_none_in_a_program_without_the_ring(window, monkeypatch, metric):
    monkeypatch.delattr(S, "ring")
    assert read(metric, window) is None


@pytest.mark.parametrize("metric", [
    "fused.dispatch_ms_per_query", "plan.ms_per_query",
    "entry.self_ms_per_query"])
def test_the_whole_layers_read_the_same_with_the_new_children(metric):
    try:
        values = [read(metric, ctx_of([tree(a, new=new) for a in (0, 200)],
                                      2))
                  for new in (False, True)]
    finally:
        S.ring.clear()
    assert values[0] == pytest.approx(values[1], rel=1e-12)
    assert values[0] > 0


def test_traced_rehearsal_reports_the_four(tmp_path, monkeypatch):
    """Q1 at 60,000 rows through the harness, with the four metrics
    listed for the cell as a BENCHMARK.json entry would list them."""
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr("tempfile.tempdir", None)
    monkeypatch.setattr(run, "session_conf",
                        lambda config: dict(config["session_conf"]))
    load_cell = run.load_cell

    def with_the_four(name):
        cell = load_cell(name)
        cell["per_layer"] = cell["per_layer"] + [
            {"name": m, "unit": "ms"} for m in NEW]
        return cell

    monkeypatch.setattr(run, "load_cell", with_the_four)
    res = run.run_cell("tpch_q1_resident", 2_147_483_777, 0.5, True,
                       rows=60_000, any_platform=True)
    assert res["correct"] is True
    value = {k: m["value"] for k, m in res["metrics"].items()}
    assert set(NEW) <= set(value)
    for m in NEW:
        assert value[m] > 0, m
    # the boundary holds the next query's planning, conversion included
    assert (value["boundary.host_ms_per_query"]
            > value["plan.convert_ms_per_query"])
