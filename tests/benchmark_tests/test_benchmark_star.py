"""`tpcds_star_resident` off the chip: the four queries written as the
specification's templates read (aliases, join conditions, one `where`
over the joined frame) against the plain reference AND the older
files' answers, the cell through the harness's rehearsal hooks to a
result line of the contract's shape, faults planted under it, the
cell's control refused, the four `star.*` readers, and how the parent
of the PR that added the cell fails: while the first query is built."""

import json
import os

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmark import compare, run

CELL = "tpcds_star_resident"
SEED = 2_147_483_693
ROWS = 100_000
CONFIG = run.load_json(run.HERE, "configs",
                       "tpcds_sf10_store_sales_star.json")
QUERIES = {"tpcds_spec_q3": "tpcds_q3", "tpcds_spec_q42": "tpcds_q42",
           "tpcds_spec_q52": "tpcds_q52", "tpcds_spec_q55": "tpcds_q55"}


@pytest.fixture(autouse=True)
def cache_outside_the_checkout(monkeypatch):
    """The tests' compile cache is conftest's, not benchmark/'s own."""
    monkeypatch.setattr(run, "session_conf",
                        lambda config: dict(config["session_conf"]))


def rehearse(trace=False, seconds=0.6, rows=ROWS, **kw):
    return run.run_cell(CELL, SEED, seconds, trace, rows=rows,
                        any_platform=True, **kw)


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    gen = run.load_module("datagen", CONFIG["generator"])
    dirs = gen.generate(CONFIG, SEED, str(tmp_path_factory.mktemp("star")),
                        rows=ROWS)
    return dirs, {t: pq.read_table(d) for t, d in dirs.items()}


@pytest.fixture(scope="module")
def spark():
    from spark_rapids_tpu.api.session import TpuSparkSession

    session = TpuSparkSession(dict(CONFIG["session_conf"]))
    yield session
    session.stop()


# --- the queries as the specification writes them ---

@pytest.mark.parametrize("query", sorted(QUERIES))
def test_spec_query_equals_the_reference_and_the_older_file(query, data,
                                                            spark):
    dirs, host = data
    spec, older = (run.load_module("queries", q)
                   for q in (query, QUERIES[query]))
    assert spec.ANSWER == older.ANSWER
    assert spec.device_bytes(CONFIG) == older.device_bytes(CONFIG) > 0
    tables = {t: spark.read.parquet(d).cache(storage="device")
              for t, d in dirs.items()}
    got = spec.build(spark, tables).collect_arrow()
    rec = spark.last_execution
    assert run.not_fused(rec) == ""
    # the WHERE's conjuncts went below the joins, each to its dimension
    assert rec["plan"]["pushedThroughJoin"] >= 2
    assert rec["sort"]["maxKeyOperands"] == 1
    want = spec.reference(host)
    assert want.num_rows > 0
    res = compare.compare_answer(got, want, spec.ANSWER, tie_tol=1e-9)
    assert res["rows_wrong"] == 0
    assert res["sum_rel_err"] < 1e-9  # doubles are exact on the CPU
    was = older.build(spark, tables).collect_arrow()
    assert run.not_fused(spark.last_execution) == ""
    assert got.column_names == was.column_names
    assert compare.compare_answer(got, was, spec.ANSWER,
                                  tie_tol=1e-9)["rows_wrong"] == 0
    assert got.num_rows == was.num_rows == min(
        spec.ANSWER["limit"], want.num_rows)


def test_traffic_and_limits_are_the_cells_own():
    cell = run.load_cell(CELL)
    assert list(cell["queries"]) == sorted(QUERIES, key=lambda q: int(
        q.rsplit("q", 1)[1]))
    assert cell["traffic"]["tables"] == {
        "store_sales": "device", "date_dim": "device", "item": "device"}
    assert cell["traffic"]["trace_seconds"] == 15
    assert cell["chips"] == 1 and cell["config"]["reduced"] == []
    assert {m["name"] for m in cell["end_to_end"]} == {"query_ms",
                                                       "setup_s"}
    assert {m["name"] for m in cell["per_layer"]} == {
        "star.hbm_roofline", "star.probe_slot_steps_per_query",
        "star.agg_slots_per_query", "star.sort_key_operands_max"}
    limits = cell["limits"]
    assert limits["rows_wrong"] == 0 and limits["failed"] == 0
    assert limits["control"] in ("float32", "bfloat16")


# --- the cell through the harness ---

def test_cell_runs_to_a_result_line(tmp_path, monkeypatch):
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr("tempfile.tempdir", None)
    res = rehearse()
    json.dumps(res)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 2
    assert set(res["metrics"]) == {"query_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["compared"]["rows_wrong"] == {"value": 0, "limit": 0}
    assert [f for f in os.listdir(tmp_path)
            if f.startswith("srtpu_bench")] == []


def test_traced_run_reports_the_star_metrics(tmp_path, monkeypatch):
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr("tempfile.tempdir", None)
    res = rehearse(trace=True)
    # off the chip there is no peak bandwidth: the roofline's reader
    # finds nothing and is left out, as the contract asks
    assert set(res["metrics"]) == {
        "star.probe_slot_steps_per_query", "star.agg_slots_per_query",
        "star.sort_key_operands_max"}
    value = {k: m["value"] for k, m in res["metrics"].items()}
    assert value["star.sort_key_operands_max"] == 1
    # 8 parts of 65,536 slots; the date join's sorted index is searched
    # (17 steps: at this size its table of positions would have more
    # entries than the slots it is read from), its matches are brought
    # to 1,024 slots, and what follows counts those
    assert value["star.probe_slot_steps_per_query"] >= 8 * 65_536
    assert value["star.agg_slots_per_query"] >= 8 * 1_024
    assert res["correct"] is True


def test_the_star_roofline_counts_each_query_of_the_window():
    loaded = run.load_cell(CELL)
    peaks = run.load_json(run.HERE, "peaks.json")["device_kind"]["TPU v5 lite"]
    names = sorted(QUERIES) * 5
    ctx = {"cell": loaded, "config": loaded["config"], "peaks": peaks,
           "window": {"names": names}, "trace": {"busy_s": 20 * 0.4}}
    read = run.load_module("layer_metrics", "star.hbm_roofline").read
    a_query = 28_800_991 * 16 + 73_049 * 12 + 102_000 * 16
    assert read(ctx) == pytest.approx(100 * a_query / 819e9 / 0.4)
    assert 0 < read(ctx) < 100
    ctx["trace"] = None
    assert read(ctx) is None


@pytest.mark.parametrize("metric", [
    "star.probe_slot_steps_per_query", "star.agg_slots_per_query",
    "star.sort_key_operands_max"])
def test_star_readers_find_nothing_in_a_program_without_the_records(
        metric, monkeypatch):
    """The parent of the PR that added the records has the spans, a
    `join` field without `probeSteps` and no `sort` field: the readers
    return None and do not raise."""
    from benchmark import span_window

    class Node:
        name, children = "fused.execute", []
        extra = {"root": "TpuLocalLimitExec", "join": {
            "runs": 1, "joins": [{"searchedSlots": 4, "lowering": "lookup"}]}}

    class Tree:
        name, extra, children = "query-1", {}, [Node()]

    read = run.load_module("layer_metrics", metric).read
    monkeypatch.setattr(span_window, "window_trees", lambda ctx: [Tree()])
    assert read({}) is None
    monkeypatch.setattr(span_window, "window_trees", lambda ctx: None)
    assert read({}) is None


# --- faults planted under the harness: `correct` has to read false ---

def collect_with(monkeypatch, alter):
    from spark_rapids_tpu.api.dataframe import DataFrame

    real = DataFrame.collect_arrow
    monkeypatch.setattr(DataFrame, "collect_arrow",
                        lambda self: alter(real(self)))


def sum_scaled(table, by):
    """The first row's sum (every answer's last column) times `by`."""
    name = table.column_names[-1]
    col = table.column(name).to_pylist()
    col[0] *= by
    return table.set_column(table.num_columns - 1, name,
                            pa.array(col, pa.float64()))


def far_rows_swapped(table):
    """The first and the last row of an answer trade places: their
    sums (or years) are no tie."""
    if table.num_rows < 2:
        return table
    order = list(range(table.num_rows))
    order[0], order[-1] = order[-1], order[0]
    return table.take(order)


@pytest.mark.parametrize("alter, number", [
    (lambda t: t.slice(1), "rows_wrong"),
    (far_rows_swapped, "rows_wrong"),
    (lambda t: sum_scaled(t, 1 + 1e-4), "sum_rel_err"),
], ids=["row_dropped", "rows_swapped_beyond_a_tie", "sum_off_by_1e-4"])
def test_an_altered_answer_is_not_correct(alter, number, monkeypatch):
    collect_with(monkeypatch, alter)
    res = rehearse()
    assert res["correct"] is False and res["failed"] == 0
    n = res["compared"][number]
    assert n["value"] > n["limit"]


def test_the_cells_control_is_refused():
    """The reference computed in the precision below the one the
    configuration states for these sums, in the program's place: one
    of the cell's limits has to refuse it."""
    limits = run.load_cell(CELL)["limits"]
    res = rehearse(controls=(limits["control"],))
    assert res["correct"] is True
    reading = res["controls"][limits["control"]]
    assert (reading["sum_rel_err"] > limits["sum_rel_err"]
            or reading["rows_wrong"] > 0)
    assert res["compared"]["sum_rel_err"]["value"] <= \
        limits["sum_rel_err"] / 100


# --- the parent of the PR that added the cell ---

def test_without_dataframe_alias_the_first_query_fails_while_it_is_built(
        monkeypatch):
    """The driver tries a new cell on the parent commit with this PR's
    benchmark files laid over it. The parent has no `DataFrame.alias`
    (and could not compile a three-key sort in half an hour): `build`
    raises AttributeError while the harness builds its list of
    queries, before any query runs and before anything is compiled."""
    from spark_rapids_tpu.api.dataframe import DataFrame

    ran = []
    monkeypatch.delattr(DataFrame, "alias")
    monkeypatch.setattr(DataFrame, "collect_arrow",
                        lambda self: ran.append(self))
    with pytest.raises(AttributeError, match="alias"):
        rehearse()
    assert ran == []
