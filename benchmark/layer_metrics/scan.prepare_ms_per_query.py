"""Wall time of `fused.prepare` per query: validate, decode on the
reader threads and the uploads' enqueue; what the first launch waits
for."""

from benchmark import span_window


def read(ctx):
    trees = span_window.window_trees(ctx)
    if not trees:
        return None
    return span_window.ms_per_query(trees, "fused.execute", "fused.prepare")
