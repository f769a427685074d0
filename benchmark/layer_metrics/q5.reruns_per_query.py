"""Runs a query repeated: `runs` - 1 of last_execution["join"], mean
over the window's queries; the record's `rerunReasons` says why (a
lost uniqueness or survivor bet, a group capacity that overflowed). A
bet lost once a session reads 0 in the window; one paid on every query
reads 1 or more. A count, so 0 is a reading."""

from benchmark.layer_metrics import _join_record


def read(ctx):
    recs = _join_record.records(ctx)
    if not recs:
        return None
    return sum(r["runs"] - 1 for r in recs) / len(recs)
