"""Mean wall time of the decode StepFn over the window (ms): the delta of
the program's ``stepfn_wall_s{kind="decode"}`` histogram, which times
each call until its result is ready."""


def read(ctx):
    s, n = ctx["stepfn"].get("decode", (0.0, 0))
    return 1e3 * s / n if n else None
