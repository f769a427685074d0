"""`tpcds_q5_resident` off the chip: the generator's invariants (the
specification's row counts, each web return a line of web_sales,
dsdgen's business ids), the engine's whole ROLLUP against the plain
reference, the cell through the harness's rehearsal hooks to a result
line of the contract's shape, faults planted under it, the float32
control refused, and the five `dsq5.*` readers."""

import datetime
import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmark import compare, control, run
from benchmark.datagen import tpcds_three_channels as gen
from benchmark.queries import tpcds_spec_q5 as q5

CELL = "tpcds_q5_resident"
SEED = 2_147_483_801
ROWS = 100_000
CONFIG = run.load_json(run.HERE, "configs", "tpcds_sf10_three_channels.json")
METRICS = {"dsq5.hbm_roofline", "dsq5.union_slots_per_query",
           "dsq5.expand_slots_per_query", "dsq5.probe_slot_steps_per_query",
           "dsq5.sort_slots_per_query"}


@pytest.fixture(autouse=True)
def cache_outside_the_checkout(monkeypatch):
    """The tests' compile cache is conftest's, not benchmark/'s own."""
    monkeypatch.setattr(run, "session_conf",
                        lambda config: dict(config["session_conf"]))


def rehearse(trace=False, seconds=0.3, rows=ROWS, **kw):
    return run.run_cell(CELL, SEED, seconds, trace, rows=rows,
                        any_platform=True, **kw)


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    dirs = gen.generate(CONFIG, SEED, str(tmp_path_factory.mktemp("dsq5")),
                        rows=ROWS)
    return dirs, {name: pq.read_table(path) for name, path in dirs.items()}


# --- the configuration and the generator ---

def test_the_configuration_states_the_published_row_counts():
    scale = CONFIG["scale"]
    assert {t: scale[t + "_rows"] for t in gen.TABLES} == {
        "store_sales": 28_800_991, "store_returns": 2_875_432,
        "catalog_sales": 14_401_261, "catalog_returns": 1_439_749,
        "web_sales": 7_197_566, "web_returns": 719_217,
        "date_dim": 73_049, "store": 102, "catalog_page": 12_000,
        "web_site": 42}
    assert gen.table_rows(CONFIG)["web_orders"] == 600_000
    assert CONFIG["reduced"] == [] and CONFIG["architecture"] is None
    conf = CONFIG["session_conf"]
    assert conf["spark.rapids.tpu.degrade.enabled"] is False
    # every sum in f64 scatter-adds, not the MXU sweep's f32 chunks
    assert conf["spark.rapids.sql.agg.matmulSegments.enabled"] is False
    assert "spark.sql.autoBroadcastJoinThreshold" not in conf
    assert set(CONFIG["schema"]) == set(gen.TABLES)
    for name, cols in CONFIG["schema"].items():
        assert list(cols) == gen.SCHEMAS[name].names, name


def test_row_counts_and_schemas(data):
    _, host = data
    n = gen.table_rows(CONFIG, ROWS)
    for name in gen.FACTS:
        assert host[name].num_rows == n[name], name
    for name in gen.DIMENSIONS:
        assert host[name].num_rows == CONFIG["scale"][name + "_rows"], name
    for name in gen.TABLES:
        assert host[name].schema.equals(gen.SCHEMAS[name]), name


def test_every_web_return_is_a_line_of_web_sales(data):
    """So that the left outer join matches as in dsdgen: web_sales'
    key (item, order number) is unique, and each return carries the
    key of a line of its own, returned 1..90 days after the sale."""
    _, host = data
    ws, wr = host["web_sales"], host["web_returns"]

    def pairs(t, item, order):
        return (t.column(item).to_numpy().astype(np.int64) << 32) \
            + t.column(order).to_numpy()

    sold = pairs(ws, "ws_item_sk", "ws_order_number")
    back = pairs(wr, "wr_item_sk", "wr_order_number")
    assert len(np.unique(sold)) == len(sold)
    assert len(np.unique(back)) == len(back)
    at = np.searchsorted(np.sort(sold), back)
    assert np.all(np.sort(sold)[at] == back)
    line = np.argsort(sold)[at]
    sold_on = ws.column("ws_sold_date_sk").to_numpy(zero_copy_only=False)
    back_on = wr.column("wr_returned_date_sk").to_numpy(zero_copy_only=False)
    dated = ~np.isnan(sold_on[line])
    assert np.array_equal(dated, ~np.isnan(back_on))
    lag = back_on[dated] - sold_on[line][dated]
    assert lag.min() >= 1 and lag.max() <= gen.RETURN_DAYS
    orders = ws.column("ws_order_number").to_numpy()
    lines = np.bincount(orders)[1:]
    assert lines.min() >= 8 and lines.max() <= 16


def test_keys_dates_and_ids_keep_the_specifications_domains(data):
    _, host = data
    for table, col, hi in (("store_sales", "ss_store_sk", 102),
                           ("catalog_returns", "cr_catalog_page_sk", 12_000),
                           ("web_sales", "ws_web_site_sk", 42)):
        keys = host[table].column(col)
        assert 0.02 < keys.null_count / len(keys) < 0.06, col
        values = keys.drop_null().to_numpy()
        assert values.min() >= 1 and values.max() <= hi, col
        assert len(np.unique(values)) > min(hi, len(values)) // 4, col
    dates = host["date_dim"]
    assert dates.column("d_date_sk").to_pylist()[:2] == [2415022, 2415023]
    assert dates.column("d_date").to_pylist()[0] == datetime.date(1900, 1, 2)
    day = dates.column("d_date_sk").to_numpy()[
        np.array(dates.column("d_date").to_pylist()) == q5.SALES_DATE]
    assert day.tolist() == [2451780]
    ids = host["store"].column("s_store_id").cast(pa.string()).to_pylist()
    assert ids[:3] == ["AAAAAAAABAAAAAAA", "AAAAAAAACAAAAAAA",
                       "AAAAAAAADAAAAAAA"]
    assert gen.business_id(16) == "AAAAAAAAABAAAAAA"
    assert len(set(ids)) == 102 and {len(i) for i in ids} == {16}


def file_bytes(dirs):
    return {(name, f): open(os.path.join(path, f), "rb").read()
            for name, path in dirs.items() for f in sorted(os.listdir(path))}


def test_the_same_seed_gives_the_same_bytes(tmp_path):
    a = file_bytes(gen.generate(CONFIG, 3_100_007_777, str(tmp_path / "a"),
                                rows=20_000))
    b = file_bytes(gen.generate(CONFIG, 3_100_007_777, str(tmp_path / "b"),
                                rows=20_000))
    c = file_bytes(gen.generate(CONFIG, 3_100_007_778, str(tmp_path / "c"),
                                rows=20_000))
    files = CONFIG["scale"]["files"]
    assert len(a) == sum(files.values()) + len(gen.DIMENSIONS) and a == b
    assert all(a[k] != c[k] for k in a if k[0] in gen.FACTS)


# --- the reference, and the engine against it ---

def test_reference_uses_nothing_of_the_engine():
    with open(q5.__file__) as f:
        text = f.read()
    assert "spark_rapids_tpu" not in text[text.index("def _rows_of"):]


def test_the_engines_whole_rollup_equals_the_reference(data):
    """Every row of the ROLLUP, not cut to the 100 the query keeps: at
    SF10 those are the grand total, the catalog channel's subtotal and
    98 catalog pages, so the store and web channels show only there."""
    from spark_rapids_tpu.api.session import TpuSparkSession

    dirs, host = data
    want = q5.reference(dict(host))
    channels = want.column("channel").to_pylist()
    assert channels[0] is None and want.column("id").to_pylist()[0] is None
    assert {"store channel", "catalog channel", "web channel"} \
        <= set(channels)
    spark = TpuSparkSession(dict(CONFIG["session_conf"]))
    try:
        tables = {n: spark.read.parquet(d).cache(storage="device")
                  for n, d in dirs.items()}
        got = q5.ordered_rollup(spark, tables).collect_arrow()
        assert run.not_fused(spark.last_execution) == ""
        (j,) = [j for j in spark.last_execution["join"]["joins"]
                if j["joinType"] == "left"]
    finally:
        spark.stop()
    assert (j["keyColumns"], j["keyArrays"]) == (2, 1)
    whole = dict(q5.ANSWER, limit=None)
    assert got.num_rows == want.num_rows
    assert compare.compare_answer(got, want, whole, 1e-11) == {
        "rows_wrong": 0, "sum_rel_err": pytest.approx(0, abs=1e-13)}


def test_q5_bytes_count_each_column_once():
    rows = {t: CONFIG["scale"][t + "_rows"] for t in gen.TABLES}
    facts = (24 * (rows["store_sales"] + rows["store_returns"]
                   + rows["catalog_sales"] + rows["catalog_returns"])
             + 32 * rows["web_sales"] + 28 * rows["web_returns"]
             + 8 * rows["date_dim"])
    assert q5.input_bytes(CONFIG) == facts + 8 * (102 + 12_000 + 42)
    assert q5.device_bytes(CONFIG) == facts + 5 * 102 + 6 * 12_000 + 5 * 42


# --- the cell through the harness ---

def test_cell_runs_to_a_result_line(tmp_path, monkeypatch):
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr("tempfile.tempdir", None)
    res = rehearse()
    json.dumps(res)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"query_ms", "setup_s"}
    assert res["compared"]["rows_wrong"] == {"value": 0, "limit": 0}
    assert [f for f in os.listdir(tmp_path)
            if f.startswith("srtpu_bench")] == []


def test_traced_run_reports_the_dsq5_metrics(tmp_path, monkeypatch):
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr("tempfile.tempdir", None)
    res = rehearse(trace=True)
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert listed == METRICS
    # off the chip there is no peak bandwidth: that reader finds
    # nothing and is left out, as the contract asks
    assert set(res["metrics"]) == listed - {"dsq5.hbm_roofline"}
    value = {k: m["value"] for k, m in res["metrics"].items()}
    n = gen.table_rows(CONFIG, ROWS)
    # every fact slot goes through a union, and the channels' rows too
    assert value["dsq5.union_slots_per_query"] > sum(n[t] for t in gen.FACTS)
    # ROLLUP(channel, id): three grouping sets of the channels' rows
    assert value["dsq5.expand_slots_per_query"] % 3 == 0
    assert value["dsq5.expand_slots_per_query"] > 0
    assert value["dsq5.probe_slot_steps_per_query"] > n["store_sales"]
    # the left join's index over web_sales is sorted every query
    assert value["dsq5.sort_slots_per_query"] > n["web_sales"]
    assert res["correct"] is True


def test_the_dsq5_roofline_counts_each_column_once():
    loaded = run.load_cell(CELL)
    peaks = run.load_json(run.HERE, "peaks.json")["device_kind"]["TPU v5 lite"]
    ctx = {"cell": loaded, "config": loaded["config"], "peaks": peaks,
           "window": {"names": ["tpcds_spec_q5"] * 10},
           "trace": {"busy_s": 10 * 0.5}}
    read = run.load_module("layer_metrics", "dsq5.hbm_roofline").read
    assert read(ctx) == pytest.approx(
        100 * q5.device_bytes(CONFIG) / 819e9 / 0.5)
    assert 0 < read(ctx) < 100
    ctx["trace"] = None
    assert read(ctx) is None


def window_of(**extra):
    """A window of two queries whose `fused.execute` spans hold
    `extra`."""
    class Node:
        name, children = "fused.execute", []

    Node.extra = extra

    class Tree:
        name, extra, children = "query-1", {}, [Node()]

    return [Tree(), Tree()]


def test_the_dsq5_counters_read_the_records(monkeypatch):
    from benchmark import span_window

    records = {
        "union": {"unions": [
            {"branches": 2, "parts": 3, "branchSlots": [100, 20]},
            {"branches": 3, "parts": 3, "branchSlots": [8, 16, 8]}]},
        "expand": {"lowerings": [
            {"program": "fused_chain_0", "sets": 3, "inputSlots": 16,
             "outputSlots": 48},
            {"program": "fused_chain_1", "sets": 3, "inputSlots": 16,
             "outputSlots": 48}]},
        "join": {"runs": 1, "joins": [
            {"searchedSlots": 100, "probeSteps": 2},
            {"searchedSlots": None, "probeSteps": 1}]},
        "sort": {"maxKeyOperands": 1, "lowerings": [
            {"by": "sort", "slots": 128, "program": "fused_buildprep_0"},
            {"by": "group", "slots": 64, "program": "fused_chain_0"}]},
    }
    monkeypatch.setattr(span_window, "window_trees",
                        lambda ctx: window_of(**records))

    def read(metric):
        return run.load_module("layer_metrics", metric).read({})

    assert read("dsq5.union_slots_per_query") == 152
    assert read("dsq5.expand_slots_per_query") == 96
    assert read("dsq5.probe_slot_steps_per_query") == 200
    assert read("dsq5.sort_slots_per_query") == 192


@pytest.mark.parametrize("metric", sorted(METRICS - {"dsq5.hbm_roofline"}))
def test_dsq5_readers_find_nothing_in_a_program_without_the_record(
        metric, monkeypatch):
    """The parent of the PR that added the `union` and `expand` records
    writes neither: those readers, like the others, give no reading."""
    from benchmark import span_window

    read = run.load_module("layer_metrics", metric).read
    monkeypatch.setattr(span_window, "window_trees",
                        lambda ctx: window_of(root="TpuSortExec"))
    assert read({}) is None
    monkeypatch.setattr(span_window, "window_trees", lambda ctx: None)
    assert read({}) is None


# --- faults planted under the harness: `correct` has to read false ---

def collect_with(monkeypatch, alter):
    from spark_rapids_tpu.api.dataframe import DataFrame

    real = DataFrame.collect_arrow
    monkeypatch.setattr(DataFrame, "collect_arrow",
                        lambda self: alter(real(self)))


def scaled(table, factor):
    sales = table.column("sales").to_numpy().copy()
    sales[0] *= factor
    return table.set_column(table.column_names.index("sales"), "sales",
                            pa.array(sales))


@pytest.mark.parametrize("alter, number", [
    (lambda t: scaled(t, 1 + 1e-6), "sum_rel_err"),
    (lambda t: t.slice(1), "rows_wrong"),
    (lambda t: t.take([1, 0] + list(range(2, t.num_rows))), "rows_wrong"),
], ids=["the_grand_total_off_in_the_seventh_digit", "row_missing",
        "rows_swapped"])
def test_an_altered_answer_is_not_correct(alter, number, monkeypatch):
    collect_with(monkeypatch, alter)
    res = rehearse()
    assert res["correct"] is False and res["failed"] == 0
    n = res["compared"][number]
    assert n["value"] > n["limit"]


# --- the control ---

def test_the_float32_control_is_not_correct():
    """The reference with every sum in float32, in the program's place:
    keys and order hold, the sums do not."""
    res = rehearse(controls=("bfloat16", "float32"))
    assert res["correct"] is True
    limits = run.load_cell(CELL)["limits"]
    assert limits["control"] == "float32"
    assert limits["rows_wrong"] == 0 and limits["failed"] == 0
    low = res["controls"]["float32"]
    # refused with room: 5.95e-8 here, 6.0e-7 and more at SF10
    assert low["sum_rel_err"] > 10 * limits["sum_rel_err"]
    assert res["controls"]["bfloat16"]["sum_rel_err"] > low["sum_rel_err"]
    assert res["compared"]["sum_rel_err"]["value"] < limits["sum_rel_err"]


def test_control_py_sees_the_control_refused(monkeypatch, capsys):
    def small(name, seed, seconds, trace, **kw):
        return run.run_cell(name, seed, seconds, trace, rows=ROWS,
                            any_platform=True, **kw)

    monkeypatch.setattr(control, "run_cell", small)
    assert control.main(["--workload", CELL, "--seeds", "2",
                         "--seconds", "0.2"]) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    seeds, summary = lines[:-1], lines[-1]
    assert len(seeds) == 2 and all(row["correct"] for row in seeds)
    assert summary["control"] == "float32"
    assert summary["control_refused"] == [True, True]
    assert summary["program_sum_rel_err_max"] < summary["limit"]
