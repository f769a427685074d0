"""Slot-steps a query's joins spent finding their build rows:
`searchedSlots` x `probeSteps` of last_execution["join"], summed over
the query's joins, mean over the window's queries. A probe by
position is 1 step a slot; a search of one sorted array the rows it
reads, one a level; a search of a tuple of key arrays a key of each a
step, log2 of the build side's slots of them (what the supplier join
on two keys pays where its keys are not packed into one). A program
whose record has no `probeSteps` gives None."""

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
