"""The `plan.convert` span per query: the overrides pass of
`DataFrame._physical` (tagging, conversion, row estimates, the build
sides), without the cache substitution, time pinning and optimize
that the rest of `plan` holds."""

from benchmark import span_window


def read(ctx):
    trees = span_window.window_trees(ctx)
    path = ("plan", "plan.convert")
    if not trees or not any(span_window.under(t, *path) for t in trees):
        return None
    return span_window.ms_per_query(trees, *path)
