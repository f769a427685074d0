"""Share of the HBM roofline: the least time the chip could take to
read the columns each query must read (`device_bytes` of the query's
file over the peak of peaks.json) over the device's busy time. The
bound is bytes: these queries do a few operations per value."""


def read(ctx):
    trace, peaks = ctx["trace"], ctx["peaks"]
    if not trace or not trace["busy_s"] or not peaks:
        return None
    queries = ctx["cell"]["queries"]
    need = sum(queries[q].device_bytes(ctx["config"])
               for q in ctx["window"]["names"])
    return 100.0 * need / peaks["hbm_bytes_per_s"] / trace["busy_s"]
