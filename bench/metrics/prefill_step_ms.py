"""Mean wall time of the admission-prefill StepFn over the window (ms):
the delta of ``stepfn_wall_s{kind="prefill"}``."""


def read(ctx):
    s, n = ctx["stepfn"].get("prefill", (0.0, 0))
    return 1e3 * s / n if n else None
