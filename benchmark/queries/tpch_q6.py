"""TPC-H Q6, forecasting revenue change (specification v3, section
2.4.6), with its validation parameters DATE = 1994-01-01, DISCOUNT =
0.06, QUANTITY = 24:

    select sum(l_extendedprice*l_discount) as revenue
    from lineitem
    where l_shipdate >= date '1994-01-01'
      and l_shipdate < date '1994-01-01' + interval '1' year
      and l_discount between 0.06 - 0.01 and 0.06 + 0.01
      and l_quantity < 24

The discount bounds are the decimal results 0.05 and 0.07, which the
generator's k/100 doubles meet exactly; in binary floating point
0.06 + 0.01 is below 0.07 and would drop a third of the rows.
"""

import pyarrow as pa

from benchmark.datagen.tpch_lineitem import days
from benchmark.reference import Precision, column

DATE_FROM, DATE_TO = days(1994, 1, 1), days(1995, 1, 1)
DISCOUNT_LO, DISCOUNT_HI, QUANTITY = 0.05, 0.07, 24

ANSWER = {"keys": [], "exact": [], "approx": ["revenue"], "order": [],
          "limit": None}


def build(spark, tables):
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import Column
    from spark_rapids_tpu.expr.core import Literal
    from spark_rapids_tpu.sqltypes import DateType

    ship = F.col("l_shipdate")
    return (tables["lineitem"]
            .filter((ship >= Column(Literal(DATE_FROM, DateType())))
                    & (ship < Column(Literal(DATE_TO, DateType())))
                    & (F.col("l_discount") >= DISCOUNT_LO)
                    & (F.col("l_discount") <= DISCOUNT_HI)
                    & (F.col("l_quantity") < QUANTITY))
            .agg(F.sum(F.col("l_extendedprice") * F.col("l_discount"))
                 .alias("revenue")))


def reference(tables, precision: str = "float64") -> pa.Table:
    p = Precision(precision)
    t = tables["lineitem"]
    ship = column(t, "l_shipdate").astype("int32")
    disc = column(t, "l_discount")
    keep = ((ship >= DATE_FROM) & (ship < DATE_TO)
            & (disc >= DISCOUNT_LO) & (disc <= DISCOUNT_HI)
            & (column(t, "l_quantity") < QUANTITY))
    product = p.mul(column(t, "l_extendedprice")[keep], disc[keep])
    return pa.table({"revenue": [float(product.sum(dtype=p.acc))]})


def input_bytes(config: dict) -> int:
    """Logical Arrow bytes of the four columns Q6 reads: three doubles
    and a date32 per row."""
    return config["scale"]["lineitem_rows"] * (3 * 8 + 4)


def device_bytes(config: dict) -> int:
    return input_bytes(config)
