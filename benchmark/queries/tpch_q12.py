"""TPC-H Q12, shipping modes and order priority (specification v3,
section 2.4.12), with its validation parameters SHIPMODE1 = MAIL,
SHIPMODE2 = SHIP, DATE = 1994-01-01:

    select l_shipmode,
           sum(case when o_orderpriority = '1-URGENT'
                     or o_orderpriority = '2-HIGH'
               then 1 else 0 end) as high_line_count,
           sum(case when o_orderpriority <> '1-URGENT'
                     and o_orderpriority <> '2-HIGH'
               then 1 else 0 end) as low_line_count
    from orders, lineitem
    where o_orderkey = l_orderkey
      and l_shipmode in ('MAIL', 'SHIP')
      and l_commitdate < l_receiptdate
      and l_shipdate < l_commitdate
      and l_receiptdate >= date '1994-01-01'
      and l_receiptdate < date '1994-01-01' + interval '1' year
    group by l_shipmode
    order by l_shipmode

The DataFrame is the query as a PySpark user writes it: the filter on
`lineitem`, the join to `orders` on the foreign key, the keyed
aggregate, the sort; the dates are `datetime.date` literals.
"""

import datetime

import numpy as np
import pyarrow as pa

from benchmark.datagen.tpch_lineitem import days
from benchmark.datagen.tpch_lineitem_orders import PRIORITIES, SHIP_MODES
from benchmark.reference import Precision, column

DATE_FROM, DATE_TO = datetime.date(1994, 1, 1), datetime.date(1995, 1, 1)
DAY_FROM, DAY_TO = days(1994, 1, 1), days(1995, 1, 1)
MODES = ("MAIL", "SHIP")
HIGH = ("1-URGENT", "2-HIGH")

ANSWER = {"keys": ["l_shipmode"],
          "exact": ["high_line_count", "low_line_count"], "approx": [],
          "order": [("l_shipmode", "asc")], "limit": None}


def build(spark, tables):
    from spark_rapids_tpu.api import functions as F

    priority, receipt = F.col("o_orderpriority"), F.col("l_receiptdate")
    high = (priority == HIGH[0]) | (priority == HIGH[1])
    low = (priority != HIGH[0]) & (priority != HIGH[1])
    return (tables["lineitem"]
            .filter(F.col("l_shipmode").isin(*MODES)
                    & (F.col("l_commitdate") < receipt)
                    & (F.col("l_shipdate") < F.col("l_commitdate"))
                    & (receipt >= F.lit(DATE_FROM))
                    & (receipt < F.lit(DATE_TO)))
            .join(tables["orders"],
                  F.col("l_orderkey") == F.col("o_orderkey"))
            .groupBy("l_shipmode")
            .agg(F.sum(F.when(high, 1).otherwise(0))
                 .alias("high_line_count"),
                 F.sum(F.when(low, 1).otherwise(0))
                 .alias("low_line_count"))
            .orderBy("l_shipmode"))


def reference(tables, precision: str = "float64") -> pa.Table:
    """Plain numpy: filter, then for each surviving line the orders
    whose key equals its own, counted by priority class. `precision`
    rounds the join keys (a CONTROL: float32 holds 24 bits of a key, so
    keys above 2^24 collide and a line finds orders that are not its
    own)."""
    p = Precision(precision)
    li, orders = tables["lineitem"], tables["orders"]
    ship, commit, receipt = (
        column(li, c).astype("int32")
        for c in ("l_shipdate", "l_commitdate", "l_receiptdate"))
    mode = column(li, "l_shipmode")
    wanted = [SHIP_MODES.index(m) for m in MODES]
    keep = (np.isin(mode, wanted) & (commit < receipt) & (ship < commit)
            & (receipt >= DAY_FROM) & (receipt < DAY_TO))
    lkey = p.cast(column(li, "l_orderkey")[keep])
    okey = p.cast(column(orders, "o_orderkey"))
    is_high = np.isin(column(orders, "o_orderpriority"),
                      [PRIORITIES.index(h) for h in HIGH])
    by_key = np.argsort(okey, kind="stable")
    okey = okey[by_key]
    high_before = np.concatenate([[0], np.cumsum(is_high[by_key])])
    lo = np.searchsorted(okey, lkey, side="left")
    hi = np.searchsorted(okey, lkey, side="right")
    high = high_before[hi] - high_before[lo]
    low = (hi - lo) - high
    mode = mode[keep]
    found = [m for m in sorted(wanted) if (hi - lo)[mode == m].sum()]
    return pa.table({
        "l_shipmode": [SHIP_MODES[m] for m in found],
        "high_line_count": pa.array(
            [int(high[mode == m].sum()) for m in found], pa.int64()),
        "low_line_count": pa.array(
            [int(low[mode == m].sum()) for m in found], pa.int64()),
    })


def input_bytes(config: dict) -> int:
    """Logical Arrow bytes of the columns Q12 reads: of each line a
    64-bit key, three date32 and an int32 dictionary code; of each
    order a 64-bit key and an int32 dictionary code."""
    scale = config["scale"]
    return (scale["lineitem_rows"] * (8 + 3 * 4 + 4)
            + scale["orders_rows"] * (8 + 4))


def device_bytes(config: dict) -> int:
    """The least a device must read for Q12 whatever implements it:
    each of those columns once, the two coded strings as one byte each
    (seven and five values)."""
    scale = config["scale"]
    return (scale["lineitem_rows"] * (8 + 3 * 4 + 1)
            + scale["orders_rows"] * (8 + 1))
