"""`tpch_q12_join_resident` off the chip: the generator's invariants
(orders and their lines), the plain reference against an independent
pyarrow join, the cell through the harness's rehearsal hooks to a
result line of the contract's shape, faults planted under it, the
float32 control refused, and the four `join.*` readers."""

import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

from benchmark import compare, run
from benchmark.datagen import tpch_lineitem_orders as gen
from benchmark.datagen.tpch_lineitem import days
from benchmark.queries import tpch_q12

CELL = "tpch_q12_join_resident"
SEED = 2_147_483_777
ROWS = 120_000
CONFIG = run.load_json(run.HERE, "configs", "tpch_sf10_lineitem_orders.json")


@pytest.fixture(autouse=True)
def cache_outside_the_checkout(monkeypatch):
    """The tests' compile cache is conftest's, not benchmark/'s own."""
    monkeypatch.setattr(run, "session_conf",
                        lambda config: dict(config["session_conf"]))


def rehearse(trace=False, seconds=0.3, rows=ROWS, **kw):
    return run.run_cell(CELL, SEED, seconds, trace, rows=rows,
                        any_platform=True, **kw)


def read_tables(dirs):
    return {name: pq.read_table(path) for name, path in dirs.items()}


@pytest.fixture(scope="module")
def host(tmp_path_factory):
    out = tmp_path_factory.mktemp("q12")
    return read_tables(gen.generate(CONFIG, SEED, str(out), rows=ROWS))


# --- the generator ---

def test_the_configuration_states_the_published_row_counts():
    assert CONFIG["scale"] == {"scale_factor": 10, "lineitem_rows": 59_986_052,
                               "orders_rows": 15_000_000, "files": 8}
    assert gen.table_rows(CONFIG) == (15_000_000, 59_986_052)
    assert CONFIG["reduced"] == [] and CONFIG["architecture"] is None
    orders, lines = gen.table_rows(CONFIG, ROWS)
    assert lines == ROWS and orders == -(-ROWS * 15_000_000 // 59_986_052)


def test_row_counts_are_the_scales_exactly(host):
    orders, lines = gen.table_rows(CONFIG, ROWS)
    assert host["orders"].num_rows == orders
    assert host["lineitem"].num_rows == lines
    assert host["orders"].schema.equals(gen.ORDERS_SCHEMA)
    assert host["lineitem"].schema.equals(gen.LINEITEM_SCHEMA)


@pytest.mark.parametrize("orders, lines", [
    (1_875_000, 7_498_257),   # a file of the cell: SF10's 15M and 59,986,052 by 8
    (1_000, 1_000), (1_000, 7_000), (1_000, 3_999)])
def test_line_counts_sum_to_the_rows_asked_for(orders, lines):
    counts = gen.line_counts(np.random.default_rng(5), orders, lines)
    assert counts.size == orders and int(counts.sum()) == lines
    assert counts.min() >= 1 and counts.max() <= gen.MAX_LINES
    if orders > 100_000:  # the adjustment moves few lines: still uniform
        share = np.bincount(counts, minlength=8)[1:] / orders
        assert np.all(np.abs(share - 1 / 7) < 0.003)


def test_line_counts_refuses_what_cannot_be_dealt():
    with pytest.raises(ValueError):
        gen.line_counts(np.random.default_rng(5), 10, 71)
    with pytest.raises(ValueError):
        gen.line_counts(np.random.default_rng(5), 10, 9)


def test_order_keys_are_sparse_unique_and_ascending(host):
    key = host["orders"].column("o_orderkey").to_numpy()
    assert np.all(np.diff(key) > 0)
    assert np.all((key - 1) % 32 < 8)  # 8 used of every 32
    assert np.array_equal(gen.order_keys(0, 10),
                          [1, 2, 3, 4, 5, 6, 7, 8, 33, 34])
    # SF10's last order: 15,000,000 orders reach key 60,000,000 - 24
    assert gen.order_keys(14_999_999, 1)[0] == 59_999_976


def test_every_line_has_its_order_and_an_order_one_to_seven_lines(host):
    okey = host["orders"].column("o_orderkey").to_numpy()
    lkey = host["lineitem"].column("l_orderkey").to_numpy()
    assert np.all(np.diff(lkey) >= 0)  # clustered by order, as dbgen writes
    keys, counts = np.unique(lkey, return_counts=True)
    assert np.array_equal(keys, okey)  # every order has lines, every line an order
    assert counts.min() >= 1 and counts.max() <= 7


def test_line_dates_keep_the_specifications_offsets(host):
    ship, commit, receipt = (
        host["lineitem"].column(c).cast(pa.int32()).to_numpy()
        for c in ("l_shipdate", "l_commitdate", "l_receiptdate"))
    assert (receipt - ship).min() >= 1 and (receipt - ship).max() <= 30
    # ship = o + 1..121, commit = o + 30..90
    assert (ship - commit).min() >= 1 - 90 and (ship - commit).max() <= 121 - 30
    assert ship.min() >= days(1992, 1, 2)
    assert receipt.max() <= days(1998, 12, 31)
    modes = set(host["lineitem"].column("l_shipmode").to_pylist())
    assert modes == set(gen.SHIP_MODES)
    assert set(host["orders"].column("o_orderpriority").to_pylist()) == \
        set(gen.PRIORITIES)


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
    assert len(a) == 16 and a == b
    assert all(a[k] != c[k] for k in a)


# --- the reference ---

def pyarrow_q12(tables) -> pa.Table:
    """Q12 by pyarrow's own filter, hash join and group-by."""
    li = tables["lineitem"]
    li = li.set_column(li.schema.get_field_index("l_shipmode"), "l_shipmode",
                       li.column("l_shipmode").cast(pa.string()))
    orders = tables["orders"]
    orders = orders.set_column(
        1, "o_orderpriority", orders.column("o_orderpriority").cast(pa.string()))
    receipt = li.column("l_receiptdate")
    keep = pc.and_(
        pc.and_(pc.is_in(li.column("l_shipmode"),
                         value_set=pa.array(["MAIL", "SHIP"])),
                pc.less(li.column("l_commitdate"), receipt)),
        pc.and_(pc.less(li.column("l_shipdate"), li.column("l_commitdate")),
                pc.and_(pc.greater_equal(receipt, pa.scalar(
                            tpch_q12.DATE_FROM, pa.date32())),
                        pc.less(receipt, pa.scalar(
                            tpch_q12.DATE_TO, pa.date32())))))
    joined = li.filter(keep).join(orders, keys="l_orderkey",
                                  right_keys="o_orderkey", join_type="inner")
    high = pc.is_in(joined.column("o_orderpriority"),
                    value_set=pa.array(["1-URGENT", "2-HIGH"]))
    joined = joined.append_column("high", high.cast(pa.int64())) \
                   .append_column("low", pc.invert(high).cast(pa.int64()))
    out = joined.group_by("l_shipmode").aggregate(
        [("high", "sum"), ("low", "sum")]).sort_by("l_shipmode")
    return out.rename_columns(
        {"high_sum": "high_line_count", "low_sum": "low_line_count"}) \
        .select(["l_shipmode", "high_line_count", "low_line_count"])


def test_reference_equals_an_independent_pyarrow_join(host):
    want = pyarrow_q12(host)
    got = tpch_q12.reference(host)
    assert got.column_names == want.column_names
    assert got.to_pydict() == want.to_pydict()
    assert got.column("l_shipmode").to_pylist() == ["MAIL", "SHIP"]
    assert min(got.column("high_line_count").to_pylist()) > 50


def test_q12_bytes_count_each_column_once():
    # lineitem: a 64-bit key, three date32, a code; orders: a key, a code
    assert tpch_q12.input_bytes(CONFIG) == \
        59_986_052 * 24 + 15_000_000 * 12
    assert tpch_q12.device_bytes(CONFIG) == \
        59_986_052 * 21 + 15_000_000 * 9


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
    assert [f for f in os.listdir(tmp_path)
            if f.startswith("srtpu_bench")] == []


def test_traced_run_reports_the_join_metrics(tmp_path, monkeypatch):
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr("tempfile.tempdir", None)
    res = rehearse(trace=True)
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    # off the chip there is no peak memory and no peak bandwidth: those
    # readers find nothing and are left out, as the contract asks
    silent = {"device.hbm_peak_gb", "fused.hbm_roofline", "join.hbm_roofline"}
    assert set(res["metrics"]) == listed - silent
    assert {m for m in listed if m.startswith("join.")} == {
        "join.build_rows_per_query", "join.searched_slots_per_query",
        "join.retries_per_query", "join.hbm_roofline"}
    value = {k: m["value"] for k, m in res["metrics"].items()}
    orders, _ = gen.table_rows(CONFIG, ROWS)
    assert value["join.build_rows_per_query"] == orders
    # 8 parts of 65,536 slots each bring their survivors to 1,024
    assert value["join.searched_slots_per_query"] == 8 * 1_024
    assert value["join.retries_per_query"] == 0
    assert res["correct"] is True


def test_the_join_roofline_counts_each_column_once():
    loaded = run.load_cell(CELL)
    peaks = run.load_json(run.HERE, "peaks.json")["device_kind"]["TPU v5 lite"]
    ctx = {"cell": loaded, "config": loaded["config"], "peaks": peaks,
           "window": {"names": ["tpch_q12"] * 10}, "trace": {"busy_s": 10 * 3.0}}
    share = run.load_module("layer_metrics", "join.hbm_roofline").read(ctx)
    assert share == pytest.approx(
        100 * (59_986_052 * 21 + 15_000_000 * 9) / 819e9 / 3.0)
    assert 0 < share < 100
    ctx["trace"] = None
    assert run.load_module("layer_metrics", "join.hbm_roofline").read(ctx) \
        is None


@pytest.mark.parametrize("metric", [
    "join.build_rows_per_query", "join.searched_slots_per_query",
    "join.retries_per_query"])
def test_join_readers_find_nothing_in_a_program_without_the_record(
        metric, monkeypatch):
    """The parent of the PR that added the record has the spans and no
    `join` field on them: the readers return None and do not raise."""
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


def moved(table, changes):
    """`changes`: {column: amount added to its first value}."""
    for name, by in changes.items():
        col = table.column(name).to_pylist()
        col[0] += by
        table = table.set_column(table.column_names.index(name), name,
                                 pa.array(col, pa.int64()))
    return table


@pytest.mark.parametrize("alter", [
    lambda t: moved(t, {"low_line_count": -1}),
    lambda t: moved(t, {"high_line_count": -1, "low_line_count": 1}),
    lambda t: t.slice(1),
    lambda t: t.take([1, 0]),
], ids=["a_line_dropped", "a_line_joined_to_another_order", "row_missing",
        "rows_swapped"])
def test_an_altered_answer_is_not_correct(alter, monkeypatch):
    collect_with(monkeypatch, alter)
    res = rehearse()
    assert res["correct"] is False and res["failed"] == 0
    assert res["compared"]["rows_wrong"]["value"] > 0


def test_an_orders_part_left_out_is_not_correct(monkeypatch):
    """The cached `orders` loses a file's part: its orders' lines find
    no match and every count is short. (`lineitem` has five columns.)"""
    from spark_rapids_tpu.exec.relation_cache import DeviceCacheEntry

    real = DeviceCacheEntry.device_parts

    def short(self):
        parts = real(self)
        return parts[:-1] if len(parts[0].columns) == 2 else parts

    monkeypatch.setattr(DeviceCacheEntry, "device_parts", short)
    res = rehearse()
    assert res["correct"] is False
    assert res["compared"]["rows_wrong"]["value"] > 0


def test_join_keys_collided_to_32_bits_are_not_correct(monkeypatch):
    """The program's join keys cut to float32, the fault the control
    stands for, with keys above 2^24: lines find orders that are not
    their own (and the uniqueness bet is lost on the way)."""
    import jax.numpy as jnp

    from spark_rapids_tpu.ops import joinops

    real = joinops._join_keys

    def collided(batch, key_idxs, live):
        vals, valid = real(batch, key_idxs, live)
        return [v.astype(jnp.float32).astype(v.dtype) for v in vals], valid

    from spark_rapids_tpu.runtime import jit_cache

    high_keys(monkeypatch)
    monkeypatch.setattr(joinops, "_join_keys", collided)
    jit_cache.clear()  # programs traced before the fault was planted
    try:
        res = rehearse()
    finally:
        jit_cache.clear()  # and those traced with it
    assert res["correct"] is False
    assert res["compared"]["rows_wrong"]["value"] > 0


# --- the control ---

def high_keys(monkeypatch, first_order=6_000_000):
    """The generator's orders numbered from `first_order`: keys above
    2^24 at a size a test run holds, as 11 of SF10's 15 million are."""
    real = run.load_module

    def load(kind, name):
        module = real(kind, name)
        if kind == "datagen":
            keys = module.order_keys
            module.order_keys = lambda first, n: keys(first + first_order, n)
        return module

    monkeypatch.setattr(run, "load_module", load)


def test_the_float32_control_is_not_correct(monkeypatch):
    """The reference with its join keys rounded to float32, in the
    program's place: keys above 2^24 collide in pairs, a line finds two
    orders, and the counts are wrong on both rows. The program, on the
    same files, is correct."""
    high_keys(monkeypatch)
    res = rehearse(controls=("bfloat16", "float32"))
    assert res["correct"] is True
    limits = run.load_cell(CELL)["limits"]
    assert limits["control"] == "float32"
    assert limits["rows_wrong"] == 0 and limits["failed"] == 0
    assert res["controls"]["float32"]["rows_wrong"] == 2
    assert res["controls"]["bfloat16"]["rows_wrong"] == 2


def test_below_two_to_the_24_float32_holds_every_key(host):
    """Why the control needs the cell's own size: the first 4.2 million
    orders have keys that float32 holds exactly."""
    assert host["orders"].column("o_orderkey").to_numpy().max() < 2 ** 24
    exact = tpch_q12.reference(host)
    assert tpch_q12.reference(host, precision="float32").equals(exact)
    res = compare.compare_all([("tpch_q12", exact)], {"tpch_q12": exact},
                              {"tpch_q12": tpch_q12.ANSWER}, 1e-9)
    assert res["rows_wrong"] == 0 and res["sum_rel_err"] == 0.0


def test_control_py_sees_the_control_refused(monkeypatch, capsys):
    from benchmark import control

    def small(name, seed, seconds, trace, **kw):
        return run.run_cell(name, seed, seconds, trace, rows=ROWS,
                            any_platform=True, **kw)

    high_keys(monkeypatch)
    monkeypatch.setattr(control, "run_cell", small)
    assert control.main(["--workload", CELL, "--seeds", "2",
                         "--seconds", "0.2"]) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    seeds, summary = lines[:-1], lines[-1]
    assert len(seeds) == 2 and all(row["correct"] for row in seeds)
    assert summary["control"] == "float32"
    assert summary["control_refused"] == [True, True]
    assert summary["control_rows_wrong"] == [2, 2]
    assert summary["program_sum_rel_err_max"] == 0.0
