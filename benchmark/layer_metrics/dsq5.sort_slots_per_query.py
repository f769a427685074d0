"""Slots a query's programs sort: `slots` of every sort and group-by
in last_execution["sort"], summed over the query's dispatches, mean
over the window's queries. The left join's index over web_sales' 7.2M
slots is sorted again by every query, and is most of it."""

from benchmark.layer_metrics import _sort_record


def read(ctx):
    recs = _sort_record.records(ctx)
    if not recs:
        return None
    return sum(s["slots"] for r in recs for s in r["lowerings"]) / len(recs)
