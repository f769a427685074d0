"""Key arrays the widest join of a query probes with: the most
`keyArrays` of last_execution["join"] over a query's joins, mean over
the window's queries. 1 where every join on several integer keys packs
them into one key (the supplier join of Q5 on `l_suppkey, c_nationkey`);
the join's `keyColumns` where it searches a tuple of them. A program
whose record has no `keyArrays` (one older than the field) gives
None."""

from benchmark.layer_metrics import _join_record


def read(ctx):
    recs = _join_record.records(ctx)
    if not recs:
        return None
    widest = []
    for r in recs:
        arrays = [j.get("keyArrays") for j in r["joins"]]
        if not arrays or None in arrays:
            return None
        widest.append(max(arrays))
    return sum(widest) / len(widest)
