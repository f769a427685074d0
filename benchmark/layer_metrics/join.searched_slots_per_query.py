"""Probe slots a query's joins searched: `searchedSlots` of
last_execution["join"] (the capacity the binary search ran over, all
parts), summed over the query's joins, mean over the window's queries.
A lookup join under a selective filter searches the filter's
survivors, 1/64 of `probeSlots`; one that lost that bet, or has no
filter below it, searches every slot."""

from benchmark.layer_metrics import _join_record


def read(ctx):
    return _join_record.per_query(ctx, "searchedSlots")
