"""Slots the query's UNION ALLs hand on: `branchSlots` of
last_execution["union"], summed over a query's unions and their
branches, mean over the window's queries. A Union is its branches'
parts laid end to end, so this is every slot the programs above it
run over: each fact with its returns (the three channels' CTEs), and
the three channels' grouped rows under the ROLLUP."""

from benchmark.layer_metrics import _execute_record


def read(ctx):
    recs = _execute_record.records(ctx, "union")
    if not recs:
        return None
    return sum(sum(u["branchSlots"]) for r in recs
               for u in r["unions"]) / len(recs)
