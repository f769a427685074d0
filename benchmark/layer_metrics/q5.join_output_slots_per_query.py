"""Slots a query's joins hand on: `outputCapacity` of
last_execution["join"], summed over the query's joins, mean over the
window's queries. Says whether a join expanded: a lookup join hands on
its probe side's slots, or the capacity its survivors were brought to;
one that expands hands on its output buffer, the expansion factor
times the larger side. A join above an aggregate, whose capacity the
host's walk does not know, gives None."""

from benchmark.layer_metrics import _join_record


def read(ctx):
    return _join_record.per_query(ctx, "outputCapacity")
