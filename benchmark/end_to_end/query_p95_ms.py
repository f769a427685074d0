"""95th percentile of the latency of every query of the window (the
caller's clock around collect_arrow), linear between ranks. Reported
where a window holds 200 queries or more, so that ten lie beyond it."""

import numpy as np


def read(ctx):
    return float(np.percentile(ctx["window"]["latencies_s"], 95)) * 1000.0
