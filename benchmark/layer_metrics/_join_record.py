"""The window's join records, for the readers of the `join.*`
counters.

The program writes what a query's joins did to
`session.last_execution["join"]` and, the same record, to the `join`
field of the query's `fused.execute` span, which is where a reader
finds it after the window (benchmark/span_window.py: the ring of
finished span trees outlives the session). A program without the
record (the parent of the PR that added it), or one whose event bus
was off, gives none, and a reader then returns None.
"""

from benchmark import span_window


def records(ctx):
    """One record per counted query of the window, or None."""
    trees = span_window.window_trees(ctx)
    if not trees:
        return None
    found = [s.extra.get("join") for t in trees
             for s in span_window.under(t, "fused.execute")]
    found = [r for r in found if r]
    return found if len(found) == len(trees) else None


def per_query(ctx, field: str):
    """Mean over the window's queries of `field` summed over a query's
    joins, or None."""
    recs = records(ctx)
    if not recs:
        return None
    values = [j.get(field) for r in recs for j in r["joins"]]
    if any(v is None for v in values):
        return None
    return sum(values) / len(recs)
