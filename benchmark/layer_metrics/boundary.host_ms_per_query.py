"""The device's idle at the query boundary, timed by the host: for each
pair of consecutive counted queries of the window, from the end of the
first one's last `fetch.wait` (the host learns that its device work is
done) to the end of the next one's first `fused.enqueue` (its first
program is on the device's queue); the mean over the pairs. A query
that does not count, between two that do, leaves no pair across it."""

from benchmark import span_window


def read(ctx):
    trees = span_window.window_trees(ctx)
    if not trees:
        return None
    from spark_rapids_tpu.obs import spans

    counted = {id(t) for t in trees}
    window = spans.ring.last(ctx["window"]["attempted"])
    gaps = []
    for a, b in zip(window, window[1:]):
        if id(a) not in counted or id(b) not in counted:
            continue
        done = [s.end_ns for s in a.walk() if s.name == "fetch.wait"]
        launched = [s for s in b.walk() if s.name == "fused.enqueue"]
        if done and launched:
            first = min(launched, key=lambda s: s.start_ns)
            gaps.append(first.end_ns - max(done))
    return sum(gaps) / 1e6 / len(gaps) if gaps else None
