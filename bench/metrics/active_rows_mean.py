"""Mean number of rows decoded per scheduler step in the window (read by
the harness from the tokens each step produced)."""


def read(ctx):
    n = [len(d) for _, d in ctx["ticks"] if d]
    return sum(n) / len(n) if n else None
