"""Slots the partial aggregates grouped over: `slots` of every
group-by (`by: group`) that a chain program (`fused_chain_*`) sorted
for, from last_execution["sort"], summed over the query's dispatches,
mean over the window's queries. After a selective join's bet the
aggregate groups the survivors' slots, 1/64 of a part's or less, not
every slot of the part."""

from benchmark.layer_metrics import _sort_record


def read(ctx):
    recs = _sort_record.records(ctx)
    if not recs:
        return None
    total = sum(s["slots"] for r in recs for s in r["lowerings"]
                if s["by"] == "group"
                and s["program"].startswith("fused_chain_"))
    return total / len(recs)
