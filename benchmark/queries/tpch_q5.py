"""TPC-H Q5, the local supplier volume query (specification v3,
section 2.4.5), with its validation parameters REGION = ASIA, DATE =
1994-01-01:

    select n_name, sum(l_extendedprice * (1 - l_discount)) as revenue
    from customer, orders, lineitem, supplier, nation, region
    where c_custkey = o_custkey
      and l_orderkey = o_orderkey
      and l_suppkey = s_suppkey
      and c_nationkey = s_nationkey
      and s_nationkey = n_nationkey
      and n_regionkey = r_regionkey
      and r_name = 'ASIA'
      and o_orderdate >= date '1994-01-01'
      and o_orderdate < date '1994-01-01' + interval '1' year
    group by n_name
    order by revenue desc

The DataFrame is that text as it reads: the six tables joined in the
FROM list's order on the WHERE clause's join conditions (the supplier
join on both of its equalities, one of whose columns, `c_nationkey`,
comes from the customer two joins below), then ONE `where` with the
three other predicates. No hint, no `broadcast()`: the optimizer moves
each predicate, the planner picks each join's build side.
"""

import datetime

import numpy as np
import pyarrow as pa

from benchmark.datagen.tpch_lineitem import days
from benchmark.reference import Precision, column, group_sums

REGION = "ASIA"
DATE = datetime.date(1994, 1, 1)
DATE_END = datetime.date(1995, 1, 1)
DAY, DAY_END = days(1994, 1, 1), days(1995, 1, 1)

ANSWER = {"keys": ["n_name"], "exact": [], "approx": ["revenue"],
          "order": [("revenue", "desc")], "limit": None}


def build(spark, tables):
    from spark_rapids_tpu.api import functions as F

    customer, orders, lineitem, supplier, nation, region = (
        tables[t] for t in ("customer", "orders", "lineitem", "supplier",
                            "nation", "region"))
    revenue = F.sum(F.col("l_extendedprice") * (1 - F.col("l_discount")))
    return (customer
            .join(orders, F.col("c_custkey") == F.col("o_custkey"))
            .join(lineitem, F.col("l_orderkey") == F.col("o_orderkey"))
            .join(supplier, (F.col("l_suppkey") == F.col("s_suppkey"))
                  & (F.col("c_nationkey") == F.col("s_nationkey")))
            .join(nation, F.col("s_nationkey") == F.col("n_nationkey"))
            .join(region, F.col("n_regionkey") == F.col("r_regionkey"))
            .where((F.col("r_name") == REGION)
                   & (F.col("o_orderdate") >= F.lit(DATE))
                   & (F.col("o_orderdate") < F.lit(DATE_END)))
            .groupBy("n_name")
            .agg(revenue.alias("revenue"))
            .orderBy(F.col("revenue").desc()))


def _rows_of(keys: np.ndarray, wanted: np.ndarray):
    """For each of `wanted`, the row of `keys` (unique) that holds it,
    and whether one does."""
    by_key = np.argsort(keys, kind="stable")
    pos = np.searchsorted(keys[by_key], wanted)
    pos[pos == len(keys)] = 0
    row = by_key[pos]
    return row, keys[row] == wanted


#: one entry: the tables the joins were last computed over (held, so
#: that no other table takes their ids) and what the joins keep of them
_JOINED = {}


def _joined(tables):
    """-> (the lines that reach the group-by, each one's nation row):
    the joins, exact whatever the precision, computed once for a set of
    tables, which the reference and each control read alike."""
    names = ("customer", "orders", "lineitem", "supplier", "nation",
             "region")
    key = tuple(id(tables[t]) for t in names)
    if _JOINED.get("key") == key:
        return _JOINED["kept"]
    customer, orders, li, supplier, nation, region = (
        tables[t] for t in names)
    odate = column(orders, "o_orderdate").astype("int32")
    cust_row, has_cust = _rows_of(column(customer, "c_custkey"),
                                  column(orders, "o_custkey"))
    order_ok = (odate >= DAY) & (odate < DAY_END) & has_cust
    order_row, has_order = _rows_of(column(orders, "o_orderkey"),
                                    column(li, "l_orderkey"))
    lines = np.flatnonzero(has_order & order_ok[order_row])
    order_row = order_row[lines]
    cust_nation = column(customer, "c_nationkey")[cust_row[order_row]]
    supp_row, has_supp = _rows_of(column(supplier, "s_suppkey"),
                                  column(li, "l_suppkey")[lines])
    supp_nation = column(supplier, "s_nationkey")[supp_row]
    keep = has_supp & (supp_nation == cust_nation)
    lines, supp_nation = lines[keep], supp_nation[keep]
    nation_row, has_nation = _rows_of(column(nation, "n_nationkey"),
                                      supp_nation)
    region_row, has_region = _rows_of(
        column(region, "r_regionkey"),
        column(nation, "n_regionkey")[nation_row])
    r_name = region.column("r_name").combine_chunks().dictionary_decode()
    in_region = r_name.to_numpy(zero_copy_only=False)[region_row] == REGION
    keep = has_nation & has_region & in_region
    kept = lines[keep], nation_row[keep]
    _JOINED.clear()
    _JOINED.update(key=key, tables=dict(tables), kept=kept)
    return kept


def reference(tables, precision: str = "float64") -> pa.Table:
    """Plain numpy: each join an argsort of the parent's unique key and
    a searchsorted of the child's foreign key, the supplier's nation
    compared with the customer's, one sum per nation by its row. The
    whole answer in the query's order. `precision` is what the product
    and the sum are computed in (a CONTROL below float64); keys, dates
    and the joins stay exact."""
    p = Precision(precision)
    li, nation = tables["lineitem"], tables["nation"]
    lines, nation_row = _joined(tables)
    value = p.mul(column(li, "l_extendedprice")[lines],
                  p.sub(1.0, column(li, "l_discount")[lines]))
    nations = nation.num_rows
    revenue = group_sums(value, nation_row, nations, p)
    found = np.flatnonzero(np.bincount(nation_row, minlength=nations))
    names = nation.column("n_name").combine_chunks().dictionary_decode()
    return pa.table({
        "n_name": names.take(pa.array(found)).cast(pa.string()),
        "revenue": pa.array(revenue[found], pa.float64()),
    }).sort_by([("revenue", "descending")])


def input_bytes(config: dict) -> int:
    """Logical Arrow bytes of the columns Q5 reads: of each line two
    64-bit keys and two doubles; of each order two 64-bit keys and a
    date32; of each customer and each supplier two 64-bit keys; of
    each nation two 64-bit keys and an int32 dictionary code; of each
    region a 64-bit key and an int32 code."""
    scale = config["scale"]
    return (scale["lineitem_rows"] * (8 + 8 + 8 + 8)
            + scale["orders_rows"] * (8 + 8 + 4)
            + scale["customer_rows"] * (8 + 8)
            + scale["supplier_rows"] * (8 + 8)
            + scale["nation_rows"] * (8 + 4 + 8)
            + scale["region_rows"] * (8 + 4))


def device_bytes(config: dict) -> int:
    """The least a device must read for Q5 whatever implements it: each
    of those columns once, the coded names as one byte (25 and 5
    values)."""
    scale = config["scale"]
    return (scale["lineitem_rows"] * (8 + 8 + 8 + 8)
            + scale["orders_rows"] * (8 + 8 + 4)
            + scale["customer_rows"] * (8 + 8)
            + scale["supplier_rows"] * (8 + 8)
            + scale["nation_rows"] * (8 + 1 + 8)
            + scale["region_rows"] * (8 + 1))
