"""Logical Arrow bytes of the columns each completed query reads (a
count from the configuration's shapes, `input_bytes` of the query's
file) over the window's length."""


def read(ctx):
    queries = ctx["cell"]["queries"]
    total = sum(queries[q].input_bytes(ctx["config"])
                for q in ctx["window"]["names"])
    return total / ctx["window"]["window_s"] / 1e9
