"""Share of the HBM roofline of Q3: the least time the chip could take
to read each column the query reads, once (`device_bytes` of the
query's file over the peak of peaks.json), over the device's busy
time. The full-width search of the big join, the derived build side
and the wide aggregate are that time together; the bound is bytes.
Read as `join.hbm_roofline` is."""


def read(ctx):
    trace, peaks = ctx["trace"], ctx["peaks"]
    if not trace or not trace["busy_s"] or not peaks:
        return None
    queries = ctx["cell"]["queries"]
    need = sum(queries[q].device_bytes(ctx["config"])
               for q in ctx["window"]["names"])
    return 100.0 * need / peaks["hbm_bytes_per_s"] / trace["busy_s"]
