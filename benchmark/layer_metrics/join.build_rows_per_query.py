"""Rows on the build side of a query's joins (the sorted table a
lookup join searches): `buildRows` of last_execution["join"], summed
over the query's joins, mean over the window's queries."""

from benchmark.layer_metrics import _join_record


def read(ctx):
    return _join_record.per_query(ctx, "buildRows")
