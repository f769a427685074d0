"""A field of the window's `fused.execute` spans, for the readers of
counters that the program writes there beside `join` and `sort`
(`_join_record.py`, `_sort_record.py`): `union` and `expand`.

A program without the field (the parent of the PR that added it), or
one whose event bus was off, gives none, and a reader then returns
None. A query that ran no Union or no Expand writes none either.
"""

from benchmark import span_window


def records(ctx, field: str):
    """One record per counted query of the window, or None."""
    trees = span_window.window_trees(ctx)
    if not trees:
        return None
    found = [s.extra.get(field) for t in trees
             for s in span_window.under(t, "fused.execute")]
    found = [r for r in found if r]
    return found if len(found) == len(trees) else None
