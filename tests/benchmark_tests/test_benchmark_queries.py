"""Each query's answer from the engine equals its plain reference at
100k rows, on the fused engine, and the comparison refuses answers
that are wrong in each way an answer can be."""

import math

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmark import compare, run

ROWS = 100_000
SEED = 2_147_483_693
CONFIG_OF = {"tpch_q1": "tpch_sf10_lineitem", "tpch_q6": "tpch_sf10_lineitem",
             "tpcds_q3": "tpcds_sf10_store_sales_star",
             "tpcds_q42": "tpcds_sf10_store_sales_star",
             "tpcds_q52": "tpcds_sf10_store_sales_star",
             "tpcds_q55": "tpcds_sf10_store_sales_star"}


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    out = {}
    for name in set(CONFIG_OF.values()):
        conf = run.load_json(run.HERE, "configs", name + ".json")
        gen = run.load_module("datagen", conf["generator"])
        dirs = gen.generate(conf, SEED, str(tmp_path_factory.mktemp(name)),
                            rows=ROWS)
        out[name] = (conf, dirs, {t: pq.read_table(d)
                                  for t, d in dirs.items()})
    return out


@pytest.fixture(scope="module")
def spark():
    from spark_rapids_tpu.api.session import TpuSparkSession

    session = TpuSparkSession({"spark.sql.shuffle.partitions": 8})
    yield session
    session.stop()


@pytest.mark.parametrize("query", sorted(CONFIG_OF))
def test_engine_answer_equals_reference_on_fused(query, data, spark):
    conf, dirs, host = data[CONFIG_OF[query]]
    module = run.load_module("queries", query)
    tables = {t: spark.read.parquet(d) for t, d in dirs.items()}
    got = module.build(spark, tables).collect_arrow()
    assert run.not_fused(spark.last_execution) == ""
    want = module.reference(host)
    assert want.num_rows > 0
    res = compare.compare_answer(got, want, module.ANSWER, tie_tol=1e-9)
    assert res["rows_wrong"] == 0
    assert res["sum_rel_err"] < 1e-9  # doubles are exact on the CPU
    if module.ANSWER["limit"]:
        assert got.num_rows == min(module.ANSWER["limit"], want.num_rows)
    assert module.input_bytes(conf) > 0 and module.device_bytes(conf) > 0


def test_star_answer_is_cut_by_the_limit_in_order(spark, tmp_path):
    """At 2M fact rows q3 has more groups than the limit keeps, so the
    engine's cut and order are held against the reference's."""
    conf = run.load_json(run.HERE, "configs",
                         "tpcds_sf10_store_sales_star.json")
    gen = run.load_module("datagen", conf["generator"])
    dirs = gen.generate(conf, SEED, str(tmp_path), rows=2_000_000)
    q3 = run.load_module("queries", "tpcds_q3")
    want = q3.reference({t: pq.read_table(d) for t, d in dirs.items()})
    assert want.num_rows > q3.ANSWER["limit"]
    tables = {t: spark.read.parquet(d) for t, d in dirs.items()}
    got = q3.build(spark, tables).collect_arrow()
    assert run.not_fused(spark.last_execution) == ""
    assert got.num_rows == q3.ANSWER["limit"]
    res = compare.compare_answer(got, want, q3.ANSWER, tie_tol=1e-9)
    assert res == {"rows_wrong": 0, "sum_rel_err": pytest.approx(0, abs=1e-9)}
    # the reference's own first 100 rows pass; its rows 1..100 do not
    assert compare.compare_answer(want.slice(0, 100), want, q3.ANSWER,
                                  1e-9)["rows_wrong"] == 0
    assert compare.compare_answer(want.slice(1, 100), want, q3.ANSWER,
                                  1e-9)["rows_wrong"] > 0


def test_float32_reference_differs_but_keeps_the_keys(data):
    _, _, host = data["tpch_sf10_lineitem"]
    q1 = run.load_module("queries", "tpch_q1")
    want, low = q1.reference(host), q1.reference(host, precision="float32")
    res = compare.compare_answer(low, want, q1.ANSWER, tie_tol=1e-9)
    assert res["rows_wrong"] == 0 and 1e-9 < res["sum_rel_err"] < 1e-3


# --- the comparison itself, on small made-up answers ---

SPEC = {"keys": ["k"], "exact": ["n"], "approx": ["s"],
        "order": [("s", "desc"), ("k", "asc")], "limit": 3}


def table(rows):
    return pa.table({"k": [r[0] for r in rows], "n": [r[1] for r in rows],
                     "s": [float(r[2]) for r in rows]})


WANT = table([("a", 5, 900), ("b", 4, 800), ("c", 3, 700.0000001),
              ("d", 2, 700), ("e", 1, 100)])


@pytest.mark.parametrize("rows, wrong, err", [
    ([("a", 5, 900), ("b", 4, 800), ("c", 3, 700.0000001)], 0, 0.0),
    # c and d tie within the tolerance: either may take the last place
    ([("a", 5, 900), ("b", 4, 800), ("d", 2, 700)], 0, 0.0),
    # e does not tie with c: an intruder past the cut
    ([("a", 5, 900), ("b", 4, 800), ("e", 1, 100)], 1, 0.0),
    # out of order, not a tie
    ([("b", 4, 800), ("a", 5, 900), ("c", 3, 700.0000001)], 1, 0.0),
    # a count off by one
    ([("a", 6, 900), ("b", 4, 800), ("c", 3, 700.0000001)], 1, 0.0),
    # a row too few, a row unknown, a row twice
    ([("a", 5, 900), ("b", 4, 800)], 1, 0.0),
    ([("a", 5, 900), ("b", 4, 800), ("z", 3, 700)], 1, 0.0),
    ([("a", 5, 900), ("a", 5, 900), ("b", 4, 800)], 1, 0.0),
    # a sum off by a thousandth
    ([("a", 5, 900.9), ("b", 4, 800), ("c", 3, 700.0000001)], 0, 0.001),
])
def test_compare_answer(rows, wrong, err):
    res = compare.compare_answer(table(rows), WANT, SPEC, tie_tol=1e-6)
    assert res["rows_wrong"] == wrong
    assert res["sum_rel_err"] == pytest.approx(err, abs=1e-12)


def test_compare_answer_refuses_missing_columns_and_nan():
    got = table([("a", 5, 900), ("b", 4, 800), ("c", 3, math.nan)])
    assert compare.compare_answer(got, WANT, SPEC, 1e-6)["sum_rel_err"] \
        == math.inf
    res = compare.compare_answer(got.drop(["n"]), WANT, SPEC, 1e-6)
    assert res["rows_wrong"] > 0


def test_compare_all_judges_every_answer_and_equal_ones_once():
    good = table([("a", 5, 900), ("b", 4, 800), ("c", 3, 700.0000001)])
    bad = table([("a", 5, 900), ("b", 4, 800), ("e", 1, 100)])
    res = compare.compare_all([("q", good), ("q", good), ("q", bad)],
                              {"q": WANT}, {"q": SPEC}, tie_tol=1e-6)
    assert res == {"rows_wrong": 1, "sum_rel_err": 0.0, "answers": 3,
                   "distinct": 2}
