"""The window's span trees, for the readers of the `program_span`
metrics.

The program keeps every finished query's span tree in a process-wide
ring that outlives `session.stop()` (`spark_rapids_tpu/obs/spans.py`
`ring`). Nothing runs a query after the window, so the window's trees
are the ring's newest `attempted` ones; a query that failed, or left
the fused engine, is left out, as it is from the latencies. A program
without the ring (the parent of the PR that added the spans), or one
whose event bus was off, gives no trees, and a reader then returns
None.

A tree's nodes have `name`, `children`, `start_ns`, `end_ns`,
`wall_ns` and `self_ns()`, on the clock of the profiler trace's host
events.
"""


def window_trees(ctx):
    """The span trees of the window's counted queries, or None."""
    try:
        from spark_rapids_tpu.obs import spans
    except ImportError:
        return None
    ring = getattr(spans, "ring", None)
    attempted = ctx["window"]["attempted"]
    if ring is None or len(ring) < attempted:
        return None
    trees = [t for t in ring.last(attempted)
             if t.status == "ok" and t.extra.get("engine") == "fused"
             and not t.extra.get("fallbacks")
             and not t.extra.get("degradations")]
    return trees or None


def under(node, *names):
    """The spans reached from `node` by children named `names[0]`, then
    theirs named `names[1]`, ...: `under(tree, "fused.execute",
    "fused.prepare")` is the query's own prepare, not that of a cache
    fill nested inside it."""
    nodes = [node]
    for name in names:
        nodes = [c for n in nodes for c in n.children if c.name == name]
    return nodes


def ms_per_query(trees, *names):
    """Mean over the trees of the summed wall time of `under(tree,
    *names)`, in ms."""
    total = sum(s.wall_ns for t in trees for s in under(t, *names))
    return total / 1e6 / len(trees)


def union_ns(intervals):
    """Length of the union of (start, end) intervals."""
    total, upto = 0, None
    for s, e in sorted(intervals):
        if upto is None or s > upto:
            total += e - s
            upto = e
        elif e > upto:
            total += e - upto
            upto = e
    return total
