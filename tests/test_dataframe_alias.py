"""`DataFrame.alias` and qualified column names, as a query's FROM
clause writes them (`from date_dim dt ... where dt.d_moy = 11`), and
`where` as `filter`."""

import pyarrow as pa
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSparkSession


@pytest.fixture(scope="module")
def spark():
    s = TpuSparkSession({})
    yield s
    s.stop()


@pytest.fixture(scope="module")
def frames(spark):
    emp = spark.createDataFrame(pa.table({
        "id": [1, 2, 3, 4], "boss": [None, 1, 1, 2],
        "name": ["ann", "bob", "cy", "di"]}))
    dept = spark.createDataFrame(pa.table({
        "id": [1, 2], "dept": ["ops", "dev"]}))
    return emp, dept


def test_where_is_filter(frames):
    emp, _ = frames
    a = emp.where(F.col("id") > 2).collect_arrow()
    b = emp.filter(F.col("id") > 2).collect_arrow()
    assert a.equals(b) and a.column("id").to_pylist() == [3, 4]


@pytest.mark.parametrize("use", ["select", "where", "groupBy", "orderBy",
                                 "getitem"])
def test_a_qualified_name_means_its_frames_column(frames, use):
    emp, _ = frames
    e, b = emp.alias("e"), emp.alias("b")
    j = e.join(b, F.col("e.boss") == F.col("b.id"))
    if use == "select":
        got = j.select(F.col("e.name"), F.col("b.name").alias("boss_name"))
        assert got.columns == ["name", "boss_name"]
        assert sorted(zip(*(got.collect_arrow().column(c).to_pylist()
                            for c in got.columns))) == [
            ("bob", "ann"), ("cy", "ann"), ("di", "bob")]
    elif use == "where":
        got = j.where(F.col("b.name") == "ann").select(F.col("e.id"))
        assert sorted(got.collect_arrow().column("id").to_pylist()) == [2, 3]
    elif use == "groupBy":
        got = j.groupBy(F.col("b.name")).agg(F.count("*").alias("n"))
        assert got.columns == ["name", "n"]
        assert dict(zip(*(got.collect_arrow().column(c).to_pylist()
                          for c in got.columns))) == {"ann": 2, "bob": 1}
    elif use == "orderBy":
        got = j.orderBy(F.col("e.id").desc()).limit(1).select(
            F.col("e.name"))
        assert got.collect_arrow().column("name").to_pylist() == ["di"]
    else:
        got = j.select(j["b.name"])
        assert got.columns == ["name"]
        assert sorted(got.collect_arrow().column("name").to_pylist()) == [
            "ann", "ann", "bob"]


def test_an_unqualified_name_still_means_the_one_column_of_that_name(frames):
    emp, dept = frames
    j = emp.alias("e").join(dept.alias("d"), F.col("e.boss") == F.col("d.id"))
    # `dept` is in one input only: no qualifier needed
    got = j.select(F.col("name"), F.col("dept")).collect_arrow()
    assert sorted(zip(*(got.column(c).to_pylist() for c in got.column_names)
                      )) == [("bob", "ops"), ("cy", "ops"), ("di", "dev")]


def test_a_name_two_aliased_inputs_hold_is_ambiguous(frames):
    emp, dept = frames
    j = emp.alias("e").join(dept.alias("d"), F.col("e.boss") == F.col("d.id"))
    with pytest.raises(ValueError, match="ambiguous.*e.id.*d.id"):
        j.select(F.col("id"))
    with pytest.raises(ValueError, match="ambiguous"):
        j.where(F.col("id") == 1)


def test_without_an_alias_the_first_column_of_a_name_is_meant(frames):
    """As before `alias` existed: a join of unaliased frames keeps both
    `id` columns and a bare `id` is the left one."""
    emp, dept = frames
    j = emp.join(dept, emp["boss"] == dept["id"])
    assert sorted(j.select(F.col("id")).collect_arrow()
                  .column("id").to_pylist()) == [2, 3, 4]


def test_an_unknown_qualifier_is_an_unknown_column(frames):
    emp, _ = frames
    with pytest.raises(KeyError):
        emp.alias("e").select(F.col("x.id"))


def test_semi_and_anti_joins_keep_the_left_inputs_alias(frames):
    emp, dept = frames
    semi = emp.alias("e").join(dept.alias("d"),
                               F.col("e.boss") == F.col("d.id"), "left_semi")
    assert sorted(semi.select(F.col("e.id")).collect_arrow()
                  .column("id").to_pylist()) == [2, 3, 4]
    with pytest.raises(KeyError):
        semi.select(F.col("d.dept"))
