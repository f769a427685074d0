"""Bytes fetched from the device per query: the ledger's `d2h` delta
over the window's queries."""


def read(ctx):
    d2h = ctx["window"]["ledger"]["direction"].get("d2h")
    return d2h["bytes"] / ctx["done"] if d2h else None
