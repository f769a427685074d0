"""TPC-H Q1, pricing summary report (specification v3, section 2.4.1),
with its validation parameter DELTA = 90:

    select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
           sum(l_extendedprice) as sum_base_price,
           sum(l_extendedprice*(1-l_discount)) as sum_disc_price,
           sum(l_extendedprice*(1-l_discount)*(1+l_tax)) as sum_charge,
           avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price,
           avg(l_discount) as avg_disc, count(*) as count_order
    from lineitem
    where l_shipdate <= date '1998-12-01' - interval '90' day
    group by l_returnflag, l_linestatus
    order by l_returnflag, l_linestatus
"""

import numpy as np
import pyarrow as pa

from benchmark.datagen.tpch_lineitem import LINE_STATUS, RETURN_FLAGS, days
from benchmark.reference import Precision, column, group_counts, group_sums

SHIP_CUTOFF = days(1998, 12, 1) - 90
COLUMNS = ["l_quantity", "l_extendedprice", "l_discount", "l_tax",
           "l_shipdate", "l_returnflag", "l_linestatus"]

ANSWER = {
    "keys": ["l_returnflag", "l_linestatus"],
    "exact": ["count_order"],
    "approx": ["sum_qty", "sum_base_price", "sum_disc_price", "sum_charge",
               "avg_qty", "avg_price", "avg_disc"],
    "order": [("l_returnflag", "asc"), ("l_linestatus", "asc")],
    "limit": None,
}


def build(spark, tables):
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import Column
    from spark_rapids_tpu.expr.core import Literal
    from spark_rapids_tpu.sqltypes import DateType

    # F.lit(datetime.date) raises TypeError (expr/core.py): a date
    # literal is built from its days since the epoch
    cutoff = Column(Literal(SHIP_CUTOFF, DateType()))
    price, disc, tax = (F.col("l_extendedprice"), F.col("l_discount"),
                        F.col("l_tax"))
    return (tables["lineitem"]
            .filter(F.col("l_shipdate") <= cutoff)
            .groupBy("l_returnflag", "l_linestatus")
            .agg(F.sum("l_quantity").alias("sum_qty"),
                 F.sum("l_extendedprice").alias("sum_base_price"),
                 F.sum(price * (1 - disc)).alias("sum_disc_price"),
                 F.sum(price * (1 - disc) * (1 + tax)).alias("sum_charge"),
                 F.avg("l_quantity").alias("avg_qty"),
                 F.avg("l_extendedprice").alias("avg_price"),
                 F.avg("l_discount").alias("avg_disc"),
                 F.count("*").alias("count_order"))
            .orderBy("l_returnflag", "l_linestatus"))


def reference(tables, precision: str = "float64") -> pa.Table:
    p = Precision(precision)
    t = tables["lineitem"]
    n_status = len(LINE_STATUS)
    groups = len(RETURN_FLAGS) * n_status
    gid = (column(t, "l_returnflag").astype(np.int64) * n_status
           + column(t, "l_linestatus"))
    gid[column(t, "l_shipdate").astype("int32") > SHIP_CUTOFF] = groups
    qty, price, disc, tax = (p.cast(column(t, c)) for c in COLUMNS[:4])
    disc_price = p.mul(price, p.sub(1, disc))
    count = group_counts(gid, groups)
    live = np.flatnonzero(count)
    sums = {"sum_qty": qty, "sum_base_price": price,
            "sum_disc_price": disc_price,
            "sum_charge": p.mul(disc_price, p.add(1, tax)), "sum_disc": disc}
    sums = {k: group_sums(v, gid, groups, p)[live] for k, v in sums.items()}
    n = count[live]
    return pa.table({
        "l_returnflag": [RETURN_FLAGS[g // n_status] for g in live],
        "l_linestatus": [LINE_STATUS[g % n_status] for g in live],
        "sum_qty": sums["sum_qty"],
        "sum_base_price": sums["sum_base_price"],
        "sum_disc_price": sums["sum_disc_price"],
        "sum_charge": sums["sum_charge"],
        "avg_qty": sums["sum_qty"] / n,
        "avg_price": sums["sum_base_price"] / n,
        "avg_disc": sums["sum_disc"] / n,
        "count_order": n,
    })


def input_bytes(config: dict) -> int:
    """Logical Arrow bytes of the columns Q1 reads: four doubles, a
    date32 and two int32 dictionary codes per row."""
    return config["scale"]["lineitem_rows"] * (4 * 8 + 4 + 2 * 4)


def device_bytes(config: dict) -> int:
    """The least a device must read for Q1 whatever implements it: the
    same seven columns, flags as one byte each (three and two values)."""
    return config["scale"]["lineitem_rows"] * (4 * 8 + 4 + 2 * 1)
