"""Upload rate while anything was uploading: the window's `h2d` bytes
(ledger) over the union of the `scan.h2d` intervals, each from its
device_put to the transfer's completion. Uploads overlap on one link,
so the union and not the sum."""

from benchmark import span_window


def read(ctx):
    trees = span_window.window_trees(ctx)
    h2d = ctx["window"]["ledger"]["direction"].get("h2d")
    if not trees or not h2d:
        return None
    busy_ns = span_window.union_ns(
        (s.start_ns, s.end_ns) for t in trees for s in span_window.under(
            t, "fused.execute", "fused.prepare", "scan.h2d"))
    return h2d["bytes"] / busy_ns if busy_ns else None
