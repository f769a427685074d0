"""Bytes uploaded per query: the ledger's process-wide `h2d` delta
over the window's queries."""


def read(ctx):
    h2d = ctx["window"]["ledger"]["direction"].get("h2d")
    return h2d["bytes"] / ctx["done"] if h2d else None
