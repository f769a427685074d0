"""Conjuncts of a filter over a join that read one side only move
below the join (plan/optimizer.py `_push_through_join`, Catalyst's
PushPredicateThroughJoin): to either side of an inner join, to the
preserved side of an outer, semi or anti join, nowhere under a full
join; a conjunct over both sides stays. The answers equal the CPU
oracle's on the plan as written, null keys on both sides."""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSparkSession
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan import optimizer
from spark_rapids_tpu.testing.asserts import assert_tables_equal

N_LEFT, N_RIGHT = 4_000, 300


def tables():
    rng = np.random.default_rng(33)
    left = pa.table({
        "k": pa.array(rng.integers(0, 400, N_LEFT),
                      mask=rng.random(N_LEFT) < 0.05),
        "a": pa.array(rng.integers(0, 10, N_LEFT)),
        "x": pa.array(rng.random(N_LEFT))})
    right = pa.table({
        "rk": pa.array(np.arange(N_RIGHT, dtype=np.int64),
                       mask=rng.random(N_RIGHT) < 0.05),
        "b": pa.array(rng.integers(0, 10, N_RIGHT)),
        "y": pa.array(rng.random(N_RIGHT))})
    return left, right


def joined(spark, how):
    left, right = tables()
    lf, rt = spark.createDataFrame(left), spark.createDataFrame(right)
    return lf.join(rt, F.col("k") == F.col("rk"), how)


#: conjuncts by what they read
LEFT_ONLY = lambda: F.col("a") < 5                       # noqa: E731
RIGHT_ONLY = lambda: F.col("b") == 3                     # noqa: E731
BOTH = lambda: F.col("x") < F.col("y")                   # noqa: E731

#: join type -> (conjuncts of the WHERE, how many move, what stays)
CASES = {
    "inner": ([LEFT_ONLY, RIGHT_ONLY, BOTH], 2, 1),
    "left": ([LEFT_ONLY, RIGHT_ONLY, BOTH], 1, 2),
    "right": ([LEFT_ONLY, RIGHT_ONLY, BOTH], 1, 2),
    "full": ([LEFT_ONLY, RIGHT_ONLY, BOTH], 0, 3),
    "left_semi": ([LEFT_ONLY, lambda: F.col("x") < 0.5], 2, 0),
    "left_anti": ([LEFT_ONLY], 1, 0),
}


def conjunction(makers):
    cond = None
    for make in makers:
        cond = make() if cond is None else cond & make()
    return cond


def conjuncts_of(plan, kind):
    """Conjuncts of every Filter directly over a node of `kind`."""
    out = []
    if isinstance(plan, L.Filter) and isinstance(plan.children[0], kind):
        out += optimizer._split_conjuncts(plan.condition)
    for c in plan.children:
        out += conjuncts_of(c, kind)
    return out


@pytest.mark.parametrize("how", list(CASES))
def test_conjuncts_move_to_the_side_they_read(how):
    makers, moved, stay = CASES[how]
    spark = TpuSparkSession({})
    try:
        df = joined(spark, how).where(conjunction(makers))
        notes = {}
        plan = optimizer.optimize(df._plan, notes)
        assert notes.get("pushedThroughJoin", 0) == moved
        assert len(conjuncts_of(plan, L.Join)) == stay
        assert len(conjuncts_of(plan, L.LocalRelation)) == moved
        # the engine's answer on the rewritten plan ...
        got = df.collect_arrow()
        assert spark.last_execution["plan"]["pushedThroughJoin"] == moved
        assert not spark.last_execution["fallbacks"]
    finally:
        spark.stop()
    # ... equals the CPU oracle's on the plan as the query wrote it
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(optimizer, "_push_through_join", lambda p, notes: p)
        oracle = TpuSparkSession({"spark.rapids.tpu.test.cpuOracle": True})
        try:
            want = joined(oracle, how).where(
                conjunction(makers)).collect_arrow()
            assert oracle.last_execution["plan"]["pushedThroughJoin"] == 0
        finally:
            oracle.stop()
    assert want.num_rows > 0
    assert_tables_equal(got, want)


def test_a_conjunct_goes_down_through_two_joins_to_its_relation():
    """`fact join a join b where a_attr and b_attr`: the first
    dimension's conjunct passes the outer join, then the inner one."""
    spark = TpuSparkSession({})
    try:
        left, right = tables()
        fact = spark.createDataFrame(left)
        dim_a = spark.createDataFrame(right)
        dim_b = spark.createDataFrame(right.rename_columns(
            ["rk2", "b2", "y2"]))
        df = (fact.join(dim_a, F.col("k") == F.col("rk"))
              .join(dim_b, F.col("a") == F.col("rk2"))
              .where((F.col("b") == 3) & (F.col("b2") < 7)
                     & (F.col("y") < F.col("y2"))))
        notes = {}
        plan = optimizer.optimize(df._plan, notes)
        # b2 once, b twice (below the outer join, then the inner one)
        assert notes["pushedThroughJoin"] == 3
        assert isinstance(plan, L.Filter)  # y < y2 reads both sides
        assert len(conjuncts_of(plan, L.Join)) == 1
        assert len(conjuncts_of(plan, L.LocalRelation)) == 2
        outer = plan.children[0]
        assert isinstance(outer.children[0], L.Join)  # no filter between
        df.explain()
        assert df.collect_arrow().num_rows >= 0
        assert spark.last_execution["plan"] == {
            "pushedThroughJoin": 3, "buildSidesSwapped": 0,
            "nodes": spark.last_execution["plan"]["nodes"]}
    finally:
        spark.stop()


def test_a_conjunct_that_calls_user_code_stays():
    from spark_rapids_tpu.sqltypes.datatypes import boolean

    spark = TpuSparkSession({})
    try:
        small = F.pandas_udf(lambda a: a < 5, returnType=boolean)
        df = joined(spark, "inner").where(small(F.col("a"))
                                          & (F.col("b") == 3))
        notes = {}
        plan = optimizer.optimize(df._plan, notes)
        assert notes["pushedThroughJoin"] == 1  # b == 3 alone
        assert len(conjuncts_of(plan, L.Join)) == 1
    finally:
        spark.stop()
