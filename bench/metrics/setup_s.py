"""Set-up time: process start to the window's start (loading, weights,
engine build, warm-up and compilation, filling the batch)."""


def read(ctx):
    return ctx["setup_s"]
