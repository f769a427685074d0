"""The window's length over the queries it completed: all the time of
the window over all its work."""


def read(ctx):
    return ctx["window"]["window_s"] * 1000.0 / ctx["done"]
