"""Runs a query repeated because a join's bet was lost (survivors
over their capacity, build keys not unique, a capacity overflow):
`runs` - 1 of last_execution["join"], mean over the window's queries.
Should read 0; a count, so 0 is a reading."""

from benchmark.layer_metrics import _join_record


def read(ctx):
    recs = _join_record.records(ctx)
    if not recs:
        return None
    return sum(r["runs"] - 1 for r in recs) / len(recs)
