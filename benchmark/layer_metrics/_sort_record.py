"""The window's sort records, for the readers of the `star.*`
counters that count sorts.

The program writes how each program of a query lowered its sorts and
group-bys to `session.last_execution["sort"]` and, the same record,
to the `sort` field of the query's `fused.execute` span, where a
reader finds it after the window (benchmark/span_window.py), as
`_join_record.py` finds the joins'. A program without the record (the
parent of the PR that added it) gives none, and a reader then returns
None.
"""

from benchmark import span_window


def records(ctx):
    """One record per counted query of the window, or None."""
    trees = span_window.window_trees(ctx)
    if not trees:
        return None
    found = [s.extra.get("sort") for t in trees
             for s in span_window.under(t, "fused.execute")]
    found = [r for r in found if r]
    return found if len(found) == len(trees) else None
