"""Slot-steps q5's joins spent finding their build rows:
`searchedSlots` x `probeSteps` of last_execution["join"], summed over
the query's joins, mean over the window's queries, as
`q5.probe_slot_steps_per_query` reads TPC-H Q5's. The six date
lookups probe every slot of the facts; the left join searches
web_returns' slots in web_sales' index over one packed key. A join
whose width is an aggregate's own is not counted. A program whose
record has no `probeSteps` gives None."""

from benchmark.layer_metrics import _join_record


def read(ctx):
    recs = _join_record.records(ctx)
    if not recs:
        return None
    total = 0
    for r in recs:
        for j in r["joins"]:
            if j.get("probeSteps") is None:
                return None
            total += (j.get("searchedSlots") or 0) * j["probeSteps"]
    return total / len(recs)
