"""Slot-steps a query's joins spent finding their build rows:
`searchedSlots` x `probeSteps` of last_execution["join"], summed over
the query's joins, mean over the window's queries. A probe by
position (`probe: position`) is 1 step a slot; a search of a sorted
index is log2 of the build side's slots. A join over the survivors of
an earlier join's bet counts only those slots; one above a pushed-down
aggregate, whose capacity the host's walk does not know (`None` in the
record; not this cell at its own size), is not counted. A program
whose record has no `probeSteps` (the parent of the PR that added it)
gives None."""

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
