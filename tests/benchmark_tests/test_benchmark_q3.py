"""`tpch_q3_join_resident` off the chip: the generator's invariants
(customers, their orders and each order's lines), the plain reference
against an independent pyarrow join, the cell through the harness's
rehearsal hooks to a result line of the contract's shape, faults planted
under it, the float32 control refused, and the four `q3.*` readers."""

import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

from benchmark import control, run
from benchmark.datagen import tpch_customer_orders_lineitem as gen
from benchmark.datagen.tpch_lineitem import days
from benchmark.queries import tpch_q3

CELL = "tpch_q3_join_resident"
SEED = 2_147_483_777
ROWS = 120_000
CONFIG = run.load_json(run.HERE, "configs",
                       "tpch_sf10_customer_orders_lineitem.json")


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
    out = tmp_path_factory.mktemp("q3")
    dirs = gen.generate(CONFIG, SEED, str(out), rows=ROWS)
    return {name: pq.read_table(path) for name, path in dirs.items()}


# --- the configuration and the generator ---

def test_the_configuration_states_the_published_row_counts():
    assert CONFIG["scale"] == {
        "scale_factor": 10, "lineitem_rows": 59_986_052,
        "orders_rows": 15_000_000, "customer_rows": 1_500_000, "files": 8}
    assert gen.table_rows(CONFIG) == (1_500_000, 15_000_000, 59_986_052)
    assert CONFIG["reduced"] == [] and CONFIG["architecture"] is None
    customers, orders, lines = gen.table_rows(CONFIG, ROWS)
    assert lines == ROWS
    assert orders == -(-ROWS * 15_000_000 // 59_986_052)
    assert customers == -(-ROWS * 1_500_000 // 59_986_052)


def test_spark_s_default_broadcast_threshold_stands():
    """Neither join is broadcast by a raised threshold, nothing is
    degraded, and the query file gives the planner no hint."""
    conf = CONFIG["session_conf"]
    assert conf.get("spark.sql.autoBroadcastJoinThreshold",
                    10_485_760) == 10_485_760
    assert conf["spark.rapids.tpu.degrade.enabled"] is False
    with open(tpch_q3.__file__) as f:
        text = f.read()
    assert "broadcast(" not in text.split('"""', 2)[2]
    assert ".hint(" not in text
    first, second = text.index(".join(orders"), text.index(".join(lineitem")
    assert text.index("(customer") < first < second  # the FROM list's order


def test_row_counts_are_the_scales_exactly(host):
    customers, orders, lines = gen.table_rows(CONFIG, ROWS)
    assert host["customer"].num_rows == customers
    assert host["orders"].num_rows == orders
    assert host["lineitem"].num_rows == lines
    assert host["customer"].schema.equals(gen.CUSTOMER_SCHEMA)
    assert host["orders"].schema.equals(gen.ORDERS_SCHEMA)
    assert host["lineitem"].schema.equals(gen.LINEITEM_SCHEMA)


def test_every_line_has_its_order_and_every_order_its_customer(host):
    ckey = host["customer"].column("c_custkey").to_numpy()
    okey = host["orders"].column("o_orderkey").to_numpy()
    ocust = host["orders"].column("o_custkey").to_numpy()
    lkey = host["lineitem"].column("l_orderkey").to_numpy()
    assert np.array_equal(ckey, np.arange(1, len(ckey) + 1))  # dense
    assert np.all(np.diff(okey) > 0) and np.all((okey - 1) % 32 < 8)
    assert np.all(np.diff(lkey) >= 0)  # clustered by order
    keys, counts = np.unique(lkey, return_counts=True)
    assert np.array_equal(keys, okey)
    assert counts.min() >= 1 and counts.max() <= 7
    assert ocust.min() >= 1 and ocust.max() <= len(ckey)
    assert not np.any(ocust % 3 == 0)  # a third of the customers: no order
    # uniform over the others: both residues, and most such customers
    assert abs(np.mean(ocust % 3 == 1) - 0.5) < 0.02
    assert len(np.unique(ocust)) > 0.9 * (len(ckey) - len(ckey) // 3)


def test_keys_not_divisible_by_three_are_enumerated_in_order():
    class Counter:
        def integers(self, lo, hi, n, dtype):
            return np.arange(lo, lo + n, dtype=dtype)

    got = gen.customer_keys_with_orders(Counter(), 8, 12)
    assert got.tolist() == [1, 2, 4, 5, 7, 8, 10, 11]


def test_dates_segments_and_prices_keep_the_specifications_domains(host):
    odate = host["orders"].column("o_orderdate").cast(pa.int32()).to_numpy()
    assert odate.min() >= days(1992, 1, 1)
    assert odate.max() <= days(1998, 12, 31) - 151
    okey = host["orders"].column("o_orderkey").to_numpy()
    lkey = host["lineitem"].column("l_orderkey").to_numpy()
    ship = host["lineitem"].column("l_shipdate").cast(pa.int32()).to_numpy()
    offset = ship - odate[np.searchsorted(okey, lkey)]
    assert offset.min() >= 1 and offset.max() <= 121
    assert set(host["orders"].column("o_shippriority").to_pylist()) == {0}
    assert set(host["customer"].column("c_mktsegment").to_pylist()) == \
        set(gen.SEGMENTS)
    disc = host["lineitem"].column("l_discount").to_numpy()
    assert disc.min() == 0.0 and disc.max() == 0.10
    assert np.allclose(disc * 100, np.round(disc * 100))
    price = host["lineitem"].column("l_extendedprice").to_numpy()
    assert price.min() >= 900.0 and price.max() <= 50 * 2_100.0
    assert np.allclose(price * 100, np.round(price * 100))


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
    assert len(a) == 24 and a == b
    assert all(a[k] != c[k] for k in a)


# --- the reference ---

def pyarrow_q3(tables) -> pa.Table:
    """Q3 by pyarrow's own filters, hash joins and group-by."""
    date = pa.scalar(tpch_q3.DATE, pa.date32())
    customer = tables["customer"]
    customer = customer.set_column(
        1, "c_mktsegment", customer.column("c_mktsegment").cast(pa.string()))
    customer = customer.filter(
        pc.equal(customer.column("c_mktsegment"), "BUILDING"))
    orders = tables["orders"]
    orders = orders.filter(pc.less(orders.column("o_orderdate"), date))
    li = tables["lineitem"]
    li = li.filter(pc.greater(li.column("l_shipdate"), date))
    joined = customer.join(orders, keys="c_custkey", right_keys="o_custkey",
                           join_type="inner") \
        .join(li, keys="o_orderkey", right_keys="l_orderkey",
              join_type="inner")
    value = pc.multiply(joined.column("l_extendedprice"),
                        pc.subtract(1.0, joined.column("l_discount")))
    out = joined.append_column("value", value) \
        .group_by(["o_orderkey", "o_orderdate", "o_shippriority"]) \
        .aggregate([("value", "sum")])
    return out.rename_columns({"o_orderkey": "l_orderkey",
                               "value_sum": "revenue"}) \
        .select(["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]) \
        .sort_by([("revenue", "descending"), ("o_orderdate", "ascending")])


def test_reference_equals_an_independent_pyarrow_join(host):
    want = pyarrow_q3(host)
    got = tpch_q3.reference(dict(host))
    assert got.schema.equals(want.schema) and got.num_rows == want.num_rows
    assert got.num_rows > 100  # the whole grouped answer, not the first 10
    for name in ("l_orderkey", "o_orderdate", "o_shippriority"):
        assert got.column(name).to_pylist() == want.column(name).to_pylist()
    assert np.allclose(got.column("revenue").to_numpy(),
                       want.column("revenue").to_numpy(), rtol=1e-12)
    rev = got.column("revenue").to_numpy()
    assert np.all(np.diff(rev) <= 0)  # revenue descending


def test_reference_uses_nothing_of_the_engine():
    with open(tpch_q3.__file__) as f:
        text = f.read()
    body = text[text.index("def _rows_of"):]
    assert "spark_rapids_tpu" not in body


def test_q3_bytes_count_each_column_once():
    assert tpch_q3.input_bytes(CONFIG) == (
        59_986_052 * 28 + 15_000_000 * 24 + 1_500_000 * 12)
    assert tpch_q3.device_bytes(CONFIG) == (
        59_986_052 * 28 + 15_000_000 * 24 + 1_500_000 * 9)


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
    assert res["compared"]["sum_rel_err"]["value"] < 1e-12
    assert [f for f in os.listdir(tmp_path)
            if f.startswith("srtpu_bench")] == []


def test_traced_run_reports_the_q3_metrics(tmp_path, monkeypatch):
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr("tempfile.tempdir", None)
    res = rehearse(trace=True)
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert listed == {"q3.hbm_roofline", "q3.join_output_slots_per_query",
                      "q3.build_rows_per_query", "q3.reruns_per_query"}
    # off the chip there is no peak bandwidth: that reader finds
    # nothing and is left out, as the contract asks
    assert set(res["metrics"]) == listed - {"q3.hbm_roofline"}
    value = {k: m["value"] for k, m in res["metrics"].items()}
    # sides chosen by rows: the segment's customers are built, and the
    # orders of theirs before the date: far fewer rows than either of
    # the sides written on the right (all of orders, all of lineitem)
    customers, orders, _ = gen.table_rows(CONFIG, ROWS)
    # (a fifth of the customers + a tenth of the orders)
    assert 0.1 * customers < value["q3.build_rows_per_query"] < 0.25 * orders
    assert value["q3.reruns_per_query"] == 0
    assert 0 < value["q3.join_output_slots_per_query"] < 2 ** 28
    assert res["correct"] is True


def test_the_q3_roofline_counts_each_column_once():
    loaded = run.load_cell(CELL)
    peaks = run.load_json(run.HERE, "peaks.json")["device_kind"]["TPU v5 lite"]
    ctx = {"cell": loaded, "config": loaded["config"], "peaks": peaks,
           "window": {"names": ["tpch_q3"] * 10}, "trace": {"busy_s": 10 * 2.0}}
    read = run.load_module("layer_metrics", "q3.hbm_roofline").read
    assert read(ctx) == pytest.approx(
        100 * tpch_q3.device_bytes(CONFIG) / 819e9 / 2.0)
    assert 0 < read(ctx) < 100
    ctx["trace"] = None
    assert read(ctx) is None


@pytest.mark.parametrize("metric", [
    "q3.join_output_slots_per_query", "q3.build_rows_per_query",
    "q3.reruns_per_query"])
def test_q3_readers_find_nothing_in_a_program_without_the_record(
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
    (lambda t: scaled(t, 1 + 1e-9), "sum_rel_err"),
    (lambda t: t.slice(1), "rows_wrong"),
    (lambda t: t.take([1, 0] + list(range(2, t.num_rows))), "rows_wrong"),
], ids=["a_sum_off_in_the_ninth_digit", "row_missing", "rows_swapped"])
def test_an_altered_answer_is_not_correct(alter, number, monkeypatch):
    collect_with(monkeypatch, alter)
    res = rehearse()
    assert res["correct"] is False and res["failed"] == 0
    n = res["compared"][number]
    assert n["value"] > n["limit"]


def test_a_customer_part_left_out_is_not_correct(monkeypatch):
    """The cached `customer` loses a file's part: the orders of its
    customers find no match and their groups are missing."""
    from spark_rapids_tpu.exec.relation_cache import DeviceCacheEntry

    real = DeviceCacheEntry.device_parts

    def short(self):
        parts = real(self)
        return parts[:-1] if len(parts[0].columns) == 2 else parts

    monkeypatch.setattr(DeviceCacheEntry, "device_parts", short)
    res = rehearse()
    assert res["correct"] is False
    assert res["compared"]["rows_wrong"]["value"] > 0


# --- the control ---

def test_the_float32_control_is_not_correct():
    """The reference with the product and the sum in float32, in the
    program's place: keys, order and the cut hold, the sums do not."""
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
