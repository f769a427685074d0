"""All of the run before the window, from the start of the process:
imports, data generation, the session, the device cache, compiling or
loading every program, the warm-up queries."""


def read(ctx):
    return ctx["window"]["setup_s"]
