"""Share of the HBM roofline of the star's rotation: the least time
the chip could take to read what each query of the window must read,
once (`device_bytes` of the query's file: 16 B a fact row and the
dimension columns it touches, over the peak of peaks.json), over the
device's busy time. It is the share of their roofline of the kernels
the star's queries are made of (the positional probes, the survivors'
row-ids, the packed sorts) taken together: on this cell they are the
device's time. The bound is bytes: a probe is one read per key. Read
as `join.hbm_roofline` is."""


def read(ctx):
    trace, peaks = ctx["trace"], ctx["peaks"]
    if not trace or not trace["busy_s"] or not peaks:
        return None
    queries = ctx["cell"]["queries"]
    need = sum(queries[q].device_bytes(ctx["config"])
               for q in ctx["window"]["names"])
    return 100.0 * need / peaks["hbm_bytes_per_s"] / trace["busy_s"]
