"""Self time of the `query` span per query: what the entry does itself
(cache probe, telemetry summary, events, metrics) and no child span
(`admission`, `plan`, `fused.execute`) covers."""

from benchmark import span_window


def read(ctx):
    trees = span_window.window_trees(ctx)
    if not trees:
        return None
    return sum(t.self_ns() for t in trees) / 1e6 / len(trees)
