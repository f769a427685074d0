"""Slots a query's joins hand on: `outputCapacity` of
last_execution["join"], summed over the query's joins, mean over the
window's queries. A lookup join hands on its probe side's slots, or
the capacity its survivors were brought to; a join that expands hands
on what its output buffer was sized to (2^28 at SF10 where that is the
expansion factor times the larger side). A join above an aggregate,
whose capacity the host's walk does not know (`None` in the record;
not this cell), gives None."""

from benchmark.layer_metrics import _join_record


def read(ctx):
    return _join_record.per_query(ctx, "outputCapacity")
