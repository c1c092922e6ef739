"""Process start to the start of the window's first step."""


def read(ctx):
    return ctx["setup_s"]
