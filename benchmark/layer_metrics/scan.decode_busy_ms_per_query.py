"""Sum of the `scan.decode` spans per query, over all reader threads:
parquet read to narrowed numpy, up to the device_put. Thread time, so
it can exceed the wall time of `fused.prepare`."""

from benchmark import span_window


def read(ctx):
    trees = span_window.window_trees(ctx)
    if not trees:
        return None
    return span_window.ms_per_query(trees, "fused.execute", "fused.prepare",
                                    "scan.decode")
