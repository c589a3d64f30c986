"""Output tokens per second: every token stamped inside the window over the
window's length."""


def read(ctx):
    t0, t1 = ctx["t0"], ctx["t1"]
    return ctx["log"].tokens_in(t0, t1) / (t1 - t0)
