"""`tpch_q5_join_resident` off the chip: the generator's invariants
(the specification's fixed nations and regions, each line's supplier
among its part's four), the plain reference against an independent
pyarrow join, the cell through the harness's rehearsal hooks to a
result line of the contract's shape, faults planted under it, the
float32 control refused, and the six `q5.*` readers."""

import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

from benchmark import control, run
from benchmark.datagen import tpch_customer_orders_lineitem as q3gen
from benchmark.datagen import tpch_six_tables as gen
from benchmark.queries import tpch_q5

CELL = "tpch_q5_join_resident"
SEED = 2_147_483_777
ROWS = 120_000
CONFIG = run.load_json(run.HERE, "configs", "tpch_sf10_six_tables.json")
METRICS = {"q5.hbm_roofline", "q5.probe_slot_steps_per_query",
           "q5.join_key_arrays_max", "q5.join_output_slots_per_query",
           "q5.build_rows_per_query", "q5.reruns_per_query"}


@pytest.fixture(autouse=True)
def cache_outside_the_checkout(monkeypatch):
    """The tests' compile cache is conftest's, not benchmark/'s own."""
    monkeypatch.setattr(run, "session_conf",
                        lambda config: dict(config["session_conf"]))


def rehearse(trace=False, seconds=0.3, rows=ROWS, **kw):
    return run.run_cell(CELL, SEED, seconds, trace, rows=rows,
                        any_platform=True, **kw)


@pytest.fixture(scope="module")
def host(tmp_path_factory):
    out = tmp_path_factory.mktemp("q5")
    dirs = gen.generate(CONFIG, SEED, str(out), rows=ROWS)
    return {name: pq.read_table(path) for name, path in dirs.items()}


# --- the configuration and the generator ---

def test_the_configuration_states_the_published_row_counts():
    assert CONFIG["scale"] == {
        "scale_factor": 10, "lineitem_rows": 59_986_052,
        "orders_rows": 15_000_000, "customer_rows": 1_500_000,
        "supplier_rows": 100_000, "nation_rows": 25, "region_rows": 5,
        "files": 8}
    assert gen.table_rows(CONFIG) == (1_500_000, 15_000_000, 59_986_052,
                                      100_000)
    assert CONFIG["reduced"] == [] and CONFIG["architecture"] is None
    customers, orders, lines, suppliers = gen.table_rows(CONFIG, ROWS)
    assert (customers, orders, lines) == q3gen.table_rows(CONFIG, ROWS)
    assert suppliers == -(-ROWS * 100_000 // 59_986_052)


def test_spark_s_default_broadcast_threshold_stands():
    """Neither large join is broadcast by a raised threshold, nothing is
    degraded, and the query file gives the planner no hint."""
    conf = CONFIG["session_conf"]
    assert conf.get("spark.sql.autoBroadcastJoinThreshold",
                    10_485_760) == 10_485_760
    assert conf["spark.rapids.tpu.degrade.enabled"] is False
    # revenue is summed in f64, not in the MXU sweep's f32 chunks
    assert conf["spark.rapids.sql.agg.matmulSegments.enabled"] is False
    with open(tpch_q5.__file__) as f:
        text = f.read()
    assert "broadcast(" not in text.split('"""', 2)[2]
    assert ".hint(" not in text
    at = [text.index(f".join({t}") for t in
          ("orders", "lineitem", "supplier", "nation", "region")]
    assert text.index("(customer") < at[0] and at == sorted(at)


def test_row_counts_and_schemas(host):
    customers, orders, lines, suppliers = gen.table_rows(CONFIG, ROWS)
    counts = {"customer": customers, "orders": orders, "lineitem": lines,
              "supplier": suppliers, "nation": 25, "region": 5}
    for name, n in counts.items():
        assert host[name].num_rows == n, name
    for name in gen.TABLES:
        schema = getattr(gen, name.upper() + "_SCHEMA")
        assert host[name].schema.equals(schema), name


def test_nation_and_region_are_the_specifications_rows(host):
    nation, region = host["nation"], host["region"]
    assert nation.column("n_nationkey").to_pylist() == list(range(25))
    names = nation.column("n_name").cast(pa.string()).to_pylist()
    assert names[:3] == ["ALGERIA", "ARGENTINA", "BRAZIL"]
    assert names[24] == "UNITED STATES" and len(set(names)) == 25
    assert region.column("r_name").cast(pa.string()).to_pylist() == [
        "AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
    asia = [n for n, r in zip(names, nation.column("n_regionkey").to_pylist())
            if r == 2]
    assert asia == ["INDIA", "INDONESIA", "JAPAN", "CHINA", "VIETNAM"]


def test_keys_keep_the_specifications_domains(host):
    suppliers = host["supplier"].num_rows
    assert np.array_equal(host["supplier"].column("s_suppkey").to_numpy(),
                          np.arange(1, suppliers + 1))
    assert np.array_equal(host["customer"].column("c_custkey").to_numpy(),
                          np.arange(1, host["customer"].num_rows + 1))
    for table, col in (("customer", "c_nationkey"),
                       ("supplier", "s_nationkey")):
        keys = host[table].column(col).to_numpy()
        assert keys.min() == 0 and keys.max() == 24, col
    suppkey = host["lineitem"].column("l_suppkey").to_numpy()
    assert suppkey.min() >= 1 and suppkey.max() <= suppliers


def test_each_line_is_supplied_by_one_of_its_parts_four(host):
    """`part_keys` replays Q3's draws: each line's price is its part's
    retail price times a quantity of 1..50, and its supplier is one of
    the four that 4.2.3 gives that part."""
    customers, orders, lines, suppliers = gen.table_rows(CONFIG, ROWS)
    n_orders = gen.file_rows(orders, 8)[0]
    n_lines = gen.file_rows(lines, 8)[0]
    part = gen.part_keys(SEED, 0, n_orders, n_lines, customers, 10)
    first = host["lineitem"].slice(0, n_lines)
    retail = 90_000 + (part // 10) % 20_001 + 100 * (part % 1000)
    qty = np.round(first.column("l_extendedprice").to_numpy() * 100) / retail
    assert np.allclose(qty, np.round(qty)) and qty.min() >= 1
    assert qty.max() <= 50
    four = np.stack([gen.supplier_of(part, i, suppliers) for i in range(4)])
    got = first.column("l_suppkey").to_numpy()
    assert np.all((four == got).any(axis=0))
    assert len(np.unique(four[:, 0])) == 4  # four suppliers, not one
    assert all((four[i] == got).mean() > 0.2 for i in range(4))


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
    assert len(a) == 27 and a == b
    varied = [k for k in a if k[0] in ("customer", "orders", "lineitem",
                                       "supplier")]
    assert all(a[k] != c[k] for k in varied)


# --- the reference ---

def pyarrow_q5(tables) -> pa.Table:
    """Q5 by pyarrow's own filters, hash joins and group-by."""
    lo = pa.scalar(tpch_q5.DATE, pa.date32())
    hi = pa.scalar(tpch_q5.DATE_END, pa.date32())
    orders = tables["orders"]
    orders = orders.filter(pc.and_(
        pc.greater_equal(orders.column("o_orderdate"), lo),
        pc.less(orders.column("o_orderdate"), hi)))
    nation = tables["nation"]
    nation = nation.set_column(1, "n_name",
                               nation.column("n_name").cast(pa.string()))
    region = tables["region"]
    region = region.set_column(1, "r_name",
                               region.column("r_name").cast(pa.string()))
    region = region.filter(pc.equal(region.column("r_name"), "ASIA"))
    inner = {"join_type": "inner"}
    joined = (tables["customer"]
              .join(orders, keys="c_custkey", right_keys="o_custkey",
                    **inner)
              .join(tables["lineitem"], keys="o_orderkey",
                    right_keys="l_orderkey", **inner)
              .join(tables["supplier"], keys=["l_suppkey", "c_nationkey"],
                    right_keys=["s_suppkey", "s_nationkey"], **inner)
              .join(nation, keys="c_nationkey", right_keys="n_nationkey",
                    **inner)
              .join(region, keys="n_regionkey", right_keys="r_regionkey",
                    **inner))
    value = pc.multiply(joined.column("l_extendedprice"),
                        pc.subtract(1.0, joined.column("l_discount")))
    out = joined.append_column("value", value) \
        .group_by(["n_name"]).aggregate([("value", "sum")])
    return out.rename_columns({"value_sum": "revenue"}) \
        .select(["n_name", "revenue"]) \
        .sort_by([("revenue", "descending")])


def test_reference_equals_an_independent_pyarrow_join(host):
    want = pyarrow_q5(host)
    got = tpch_q5.reference(dict(host))
    assert got.schema.equals(want.schema) and got.num_rows == want.num_rows
    assert got.num_rows == 5  # the five nations of ASIA
    assert got.column("n_name").to_pylist() == want.column("n_name").to_pylist()
    assert np.allclose(got.column("revenue").to_numpy(),
                       want.column("revenue").to_numpy(), rtol=1e-12)
    assert np.all(np.diff(got.column("revenue").to_numpy()) <= 0)


def test_reference_uses_nothing_of_the_engine():
    with open(tpch_q5.__file__) as f:
        text = f.read()
    body = text[text.index("def _rows_of"):]
    assert "spark_rapids_tpu" not in body


def test_q5_bytes_count_each_column_once():
    assert tpch_q5.input_bytes(CONFIG) == (
        59_986_052 * 32 + 15_000_000 * 20 + 1_500_000 * 16 + 100_000 * 16
        + 25 * 20 + 5 * 12)
    assert tpch_q5.device_bytes(CONFIG) == (
        59_986_052 * 32 + 15_000_000 * 20 + 1_500_000 * 16 + 100_000 * 16
        + 25 * 17 + 5 * 9)


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
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == {"query_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["compared"]["rows_wrong"] == {"value": 0, "limit": 0}
    assert res["compared"]["sum_rel_err"]["value"] < 1e-12  # exact here
    assert [f for f in os.listdir(tmp_path)
            if f.startswith("srtpu_bench")] == []


def test_traced_run_reports_the_q5_metrics(tmp_path, monkeypatch):
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr("tempfile.tempdir", None)
    res = rehearse(trace=True)
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert listed == METRICS
    # off the chip there is no peak bandwidth: that reader finds
    # nothing and is left out, as the contract asks
    assert set(res["metrics"]) == listed - {"q5.hbm_roofline"}
    value = {k: m["value"] for k, m in res["metrics"].items()}
    # the supplier join's two keys are probed as one packed key
    assert value["q5.join_key_arrays_max"] == 1
    # no join expanded: every join hands on at most its probe's slots
    _, _, lines, _ = gen.table_rows(CONFIG, ROWS)
    assert 0 < value["q5.join_output_slots_per_query"] < 8 * lines
    assert value["q5.probe_slot_steps_per_query"] > lines
    # the first join builds `customer`; the second the orders of 1994
    # under it, then `supplier`, `nation` and `region`'s one row
    customers, orders, _, suppliers = gen.table_rows(CONFIG, ROWS)
    assert customers + suppliers < value["q5.build_rows_per_query"] \
        < customers + orders // 4 + suppliers + 26
    assert value["q5.reruns_per_query"] == 0
    assert res["correct"] is True


def test_the_q5_roofline_counts_each_column_once():
    loaded = run.load_cell(CELL)
    peaks = run.load_json(run.HERE, "peaks.json")["device_kind"]["TPU v5 lite"]
    ctx = {"cell": loaded, "config": loaded["config"], "peaks": peaks,
           "window": {"names": ["tpch_q5"] * 10}, "trace": {"busy_s": 10 * 2.0}}
    read = run.load_module("layer_metrics", "q5.hbm_roofline").read
    assert read(ctx) == pytest.approx(
        100 * tpch_q5.device_bytes(CONFIG) / 819e9 / 2.0)
    assert 0 < read(ctx) < 100
    ctx["trace"] = None
    assert read(ctx) is None


def records_with(joins):
    """A window of two queries whose join records hold `joins`."""
    class Node:
        name, children = "fused.execute", []
        extra = {"join": {"joins": joins, "runs": 1}}

    class Tree:
        name, extra, children = "query-1", {}, [Node()]

    return [Tree(), Tree()]


def test_the_q5_counters_read_the_join_records(monkeypatch):
    from benchmark import span_window

    joins = [
        {"searchedSlots": 100, "probeSteps": 1, "keyArrays": 1,
         "outputCapacity": 100, "buildRows": 7},
        {"searchedSlots": 400, "probeSteps": 3, "keyArrays": 1,
         "outputCapacity": 400, "buildRows": 50},
        {"searchedSlots": 400, "probeSteps": 36, "keyArrays": 2,
         "outputCapacity": 400, "buildRows": 3},
    ]
    monkeypatch.setattr(span_window, "window_trees",
                        lambda ctx: records_with(joins))

    def read(metric):
        return run.load_module("layer_metrics", metric).read({})

    assert read("q5.probe_slot_steps_per_query") == 100 + 1_200 + 14_400
    assert read("q5.join_key_arrays_max") == 2
    assert read("q5.join_output_slots_per_query") == 900
    assert read("q5.build_rows_per_query") == 60
    assert read("q5.reruns_per_query") == 0


@pytest.mark.parametrize("metric", sorted(METRICS - {"q5.hbm_roofline"}))
def test_q5_readers_find_nothing_in_a_program_without_the_record(
        metric, monkeypatch):
    from benchmark import span_window

    class Node:
        name, extra, children = "fused.execute", {"root": "TpuSortExec"}, []

    class Tree:
        name, extra, children = "query-1", {}, [Node()]

    read = run.load_module("layer_metrics", metric).read
    monkeypatch.setattr(span_window, "window_trees", lambda ctx: [Tree()])
    assert read({}) is None
    monkeypatch.setattr(span_window, "window_trees", lambda ctx: None)
    assert read({}) is None


def test_the_key_arrays_reader_finds_nothing_in_an_older_record(
        monkeypatch):
    """A record without `keyArrays` (the program before it was added)
    gives no reading, where the other counters still read."""
    from benchmark import span_window

    joins = [{"searchedSlots": 100, "probeSteps": 1, "outputCapacity": 100}]
    monkeypatch.setattr(span_window, "window_trees",
                        lambda ctx: records_with(joins))
    read = run.load_module("layer_metrics", "q5.join_key_arrays_max").read
    assert read({}) is None
    steps = run.load_module("layer_metrics",
                            "q5.probe_slot_steps_per_query").read
    assert steps({}) == 100


# --- faults planted under the harness: `correct` has to read false ---

def collect_with(monkeypatch, alter):
    from spark_rapids_tpu.api.dataframe import DataFrame

    real = DataFrame.collect_arrow
    monkeypatch.setattr(DataFrame, "collect_arrow",
                        lambda self: alter(real(self)))


def scaled(table, factor):
    rev = table.column("revenue").to_numpy().copy()
    rev[0] *= factor
    return table.set_column(table.column_names.index("revenue"), "revenue",
                            pa.array(rev))


@pytest.mark.parametrize("alter, number", [
    (lambda t: scaled(t, 1 + 1e-4), "sum_rel_err"),
    (lambda t: t.slice(1), "rows_wrong"),
    (lambda t: t.take([1, 0] + list(range(2, t.num_rows))), "rows_wrong"),
], ids=["a_sum_off_in_the_fifth_digit", "row_missing", "rows_swapped"])
def test_an_altered_answer_is_not_correct(alter, number, monkeypatch):
    collect_with(monkeypatch, alter)
    res = rehearse()
    assert res["correct"] is False and res["failed"] == 0
    n = res["compared"][number]
    assert n["value"] > n["limit"]


# --- the control ---

def test_the_float32_control_is_not_correct():
    """The reference with the product and the sum in float32, in the
    program's place: names and order hold, the sums do not. The
    configuration states f64 sums (the binned group-by's MXU sweep,
    whose f32 chunk partials read like the float32 reference, is off)."""
    res = rehearse(controls=("bfloat16", "float32"))
    assert res["correct"] is True
    limits = run.load_cell(CELL)["limits"]
    assert limits["control"] == "float32"
    assert limits["rows_wrong"] == 0 and limits["failed"] == 0
    low = res["controls"]["float32"]
    assert low["sum_rel_err"] > 1e3 * limits["sum_rel_err"]
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
