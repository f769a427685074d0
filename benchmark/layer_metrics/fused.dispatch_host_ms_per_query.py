"""The program launches per query without their programs' enqueues:
the `fused.dispatch` spans minus their `fused.enqueue` and `compile`
children, so the key, the cache probe, the device checks and the flag
slices after the call. The slices are device operations of their own,
so once about five programs are outstanding the wait for the device
falls on them: this reads back-pressure as well as host work."""

from benchmark import span_window


def read(ctx):
    trees = span_window.window_trees(ctx)
    path = ("fused.execute", "fused.dispatch")
    if not trees or not any(span_window.under(t, *path, "fused.enqueue")
                            for t in trees):
        return None
    return (span_window.ms_per_query(trees, *path)
            - span_window.ms_per_query(trees, *path, "fused.enqueue")
            - span_window.ms_per_query(trees, *path, "compile"))
