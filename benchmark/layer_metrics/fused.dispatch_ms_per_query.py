"""Host time of the program launches per query: the `fused.dispatch`
spans (key, cached_jit, enqueue) minus their `compile` children."""

from benchmark import span_window


def read(ctx):
    trees = span_window.window_trees(ctx)
    if not trees:
        return None
    path = ("fused.execute", "fused.dispatch")
    return (span_window.ms_per_query(trees, *path)
            - span_window.ms_per_query(trees, *path, "compile"))
