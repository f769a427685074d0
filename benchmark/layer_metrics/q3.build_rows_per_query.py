"""Rows on the build side of a query's joins: `buildRows` of
last_execution["join"], summed over the query's joins, mean over the
window's queries. Says which side was built: at SF10 about 1.45M (the
orders of the segment's customers before the date) + 0.3M (the
segment's customers) with sides chosen by rows, 15M + 60M where the
build side is the side written on the right."""

from benchmark.layer_metrics import _join_record


def read(ctx):
    return _join_record.per_query(ctx, "buildRows")
