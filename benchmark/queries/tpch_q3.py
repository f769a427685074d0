"""TPC-H Q3, the shipping priority query (specification v3, section
2.4.3), with its validation parameters SEGMENT = BUILDING, DATE =
1995-03-15:

    select l_orderkey,
           sum(l_extendedprice * (1 - l_discount)) as revenue,
           o_orderdate, o_shippriority
    from customer, orders, lineitem
    where c_mktsegment = 'BUILDING'
      and c_custkey = o_custkey
      and l_orderkey = o_orderkey
      and o_orderdate < date '1995-03-15'
      and l_shipdate > date '1995-03-15'
    group by l_orderkey, o_orderdate, o_shippriority
    order by revenue desc, o_orderdate
    -- the first 10 rows

The DataFrame is that text as it reads: the three tables joined in the
FROM list's order on the WHERE clause's join conditions, then ONE
`where` with the three predicates (the optimizer, not the query, moves
each below the join whose side it reads; the planner, not the query,
picks each join's build side). No hint, no `broadcast()`.
"""

import datetime

import numpy as np
import pyarrow as pa

from benchmark.datagen.tpch_customer_orders_lineitem import SEGMENTS
from benchmark.datagen.tpch_lineitem import days
from benchmark.reference import Precision, column, group_sums

SEGMENT = "BUILDING"
DATE = datetime.date(1995, 3, 15)
DAY = days(1995, 3, 15)
LIMIT = 10

ANSWER = {"keys": ["l_orderkey", "o_orderdate", "o_shippriority"],
          "exact": [], "approx": ["revenue"],
          "order": [("revenue", "desc"), ("o_orderdate", "asc")],
          "limit": LIMIT}


def build(spark, tables):
    from spark_rapids_tpu.api import functions as F

    customer, orders, lineitem = (
        tables[t] for t in ("customer", "orders", "lineitem"))
    revenue = F.sum(F.col("l_extendedprice") * (1 - F.col("l_discount")))
    return (customer
            .join(orders, F.col("c_custkey") == F.col("o_custkey"))
            .join(lineitem, F.col("l_orderkey") == F.col("o_orderkey"))
            .where((F.col("c_mktsegment") == SEGMENT)
                   & (F.col("o_orderdate") < F.lit(DATE))
                   & (F.col("l_shipdate") > F.lit(DATE)))
            .groupBy("l_orderkey", "o_orderdate", "o_shippriority")
            .agg(revenue.alias("revenue"))
            .select("l_orderkey", "revenue", "o_orderdate",
                    "o_shippriority")
            .orderBy(F.col("revenue").desc(), F.col("o_orderdate"))
            .limit(LIMIT))


def _rows_of(keys: np.ndarray, wanted: np.ndarray):
    """For each of `wanted`, the row of `keys` (unique) that holds it,
    and whether one does."""
    by_key = np.argsort(keys, kind="stable")
    pos = np.searchsorted(keys[by_key], wanted)
    pos[pos == len(keys)] = 0
    row = by_key[pos]
    return row, keys[row] == wanted


def reference(tables, precision: str = "float64") -> pa.Table:
    """Plain numpy: the three masks, each join an argsort of the parent's
    unique key and a searchsorted of the child's foreign key, np.unique
    for the groups. The WHOLE grouped answer in the query's order, not
    cut to the limit: the comparison needs the rows beyond it to judge
    ties. `precision` is what the product and the sum are computed in
    (a CONTROL below float64); keys, dates and the joins stay exact."""
    p = Precision(precision)
    customer, orders, li = (
        tables[t] for t in ("customer", "orders", "lineitem"))
    building = column(customer, "c_mktsegment") == SEGMENTS.index(SEGMENT)
    odate = column(orders, "o_orderdate").astype("int32")
    cust_row, has_cust = _rows_of(column(customer, "c_custkey"),
                                  column(orders, "o_custkey"))
    order_ok = (odate < DAY) & has_cust & building[cust_row]
    shipped = np.flatnonzero(
        column(li, "l_shipdate").astype("int32") > DAY)
    order_row, has_order = _rows_of(column(orders, "o_orderkey"),
                                    column(li, "l_orderkey")[shipped])
    keep = has_order & order_ok[order_row]
    lines, order_row = shipped[keep], order_row[keep]
    keys = np.stack([
        column(li, "l_orderkey")[lines],
        odate[order_row].astype(np.int64),
        column(orders, "o_shippriority")[order_row].astype(np.int64),
    ], axis=1)
    uniq, gid = np.unique(keys, axis=0, return_inverse=True)
    value = p.mul(column(li, "l_extendedprice")[lines],
                  p.sub(1.0, column(li, "l_discount")[lines]))
    revenue = group_sums(value, gid.reshape(-1), len(uniq), p)
    return pa.table({
        "l_orderkey": pa.array(uniq[:, 0], pa.int64()),
        "revenue": pa.array(revenue, pa.float64()),
        "o_orderdate": pa.array(uniq[:, 1].astype(np.int32), pa.date32()),
        "o_shippriority": pa.array(uniq[:, 2].astype(np.int32), pa.int32()),
    }).sort_by([("revenue", "descending"), ("o_orderdate", "ascending")])


def input_bytes(config: dict) -> int:
    """Logical Arrow bytes of the columns Q3 reads: of each line a
    64-bit key, two doubles and a date32; of each order two 64-bit
    keys, a date32 and an int32; of each customer a 64-bit key and an
    int32 dictionary code."""
    scale = config["scale"]
    return (scale["lineitem_rows"] * (8 + 8 + 8 + 4)
            + scale["orders_rows"] * (8 + 8 + 4 + 4)
            + scale["customer_rows"] * (8 + 4))


def device_bytes(config: dict) -> int:
    """The least a device must read for Q3 whatever implements it: each
    of those columns once, the coded segment as one byte (five
    values)."""
    scale = config["scale"]
    return (scale["lineitem_rows"] * (8 + 8 + 8 + 4)
            + scale["orders_rows"] * (8 + 8 + 4 + 4)
            + scale["customer_rows"] * (8 + 1))
