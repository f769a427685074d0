"""Device busy time per query: the union of the device operations'
intervals in the traced window over its queries."""


def read(ctx):
    trace = ctx["trace"]
    return trace["busy_s"] * 1000.0 / ctx["done"] if trace else None
