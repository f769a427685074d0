"""Share of the HBM roofline of Q5: the least time the chip could take
to read each column the query reads, once (`device_bytes` of the
query's file over the peak of peaks.json), over the device's busy
time. The full-width search of `lineitem` into the customers' orders
and the three full-width probes after it are that time together; the
bound is bytes. Read as `q3.hbm_roofline` is."""


def read(ctx):
    trace, peaks = ctx["trace"], ctx["peaks"]
    if not trace or not trace["busy_s"] or not peaks:
        return None
    queries = ctx["cell"]["queries"]
    need = sum(queries[q].device_bytes(ctx["config"])
               for q in ctx["window"]["names"])
    return 100.0 * need / peaks["hbm_bytes_per_s"] / trace["busy_s"]
