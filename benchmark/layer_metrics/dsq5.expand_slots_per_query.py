"""Slots the query's Expands write: `outputSlots` of
last_execution["expand"], summed over a query's programs, mean over
the window's queries. An Expand writes its input once per grouping
set (ROLLUP(channel, id): three), so this is `sets` x `inputSlots`."""

from benchmark.layer_metrics import _execute_record


def read(ctx):
    recs = _execute_record.records(ctx, "expand")
    if not recs:
        return None
    return sum(e["outputSlots"] for r in recs
               for e in r["lowerings"]) / len(recs)
