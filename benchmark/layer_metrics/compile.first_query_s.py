"""The caller's clock around the first collect_arrow() of the process:
compilation on a cold disk cache, loading on a warm one; in resident
cells the device cache is filled inside it."""


def read(ctx):
    return ctx["window"]["first_query_s"]
