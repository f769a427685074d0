"""Programs built inside the window: the sum of
last_execution["compile"]["programsCompiled"] over its queries.
Should read 0; a count, so 0 is a reading."""


def read(ctx):
    return ctx["window"]["programs_compiled"]
