"""The fetch's own time per query: the `fetch` spans minus their
`fetch.wait` children, so the flags' assembly, the copy to the host,
the Arrow conversion and `settle` (exec/fused.py `_run`), without the
device work the dispatches left outstanding."""

from benchmark import span_window


def read(ctx):
    trees = span_window.window_trees(ctx)
    path = ("fused.execute", "fetch")
    if not trees or not any(span_window.under(t, *path, "fetch.wait")
                            for t in trees):
        return None
    return (span_window.ms_per_query(trees, *path)
            - span_window.ms_per_query(trees, *path, "fetch.wait"))
