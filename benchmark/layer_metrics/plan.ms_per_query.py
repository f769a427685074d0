"""The `plan` span per query: cache substitution, optimize, plan_query
(`DataFrame._physical`)."""

from benchmark import span_window


def read(ctx):
    trees = span_window.window_trees(ctx)
    return span_window.ms_per_query(trees, "plan") if trees else None
