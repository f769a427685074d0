"""Rows on the build side of a query's joins: `buildRows` of
last_execution["join"], summed over the query's joins, mean over the
window's queries. Says which side the first join built: `customer`
(1.5M at SF10), whose count the planner knows exactly, or the 2.28M
orders of 1994 written on the right; the other four joins add the
derived side, `supplier`, `nation` and `region`'s one row."""

from benchmark.layer_metrics import _join_record


def read(ctx):
    return _join_record.per_query(ctx, "buildRows")
