"""Mean host time of one admission over the window (ms): from the start of
a request's admission prefill to its first token on the host, the
program's ``t_first − t_admit`` stamps, over the requests whose admission
began in the window.  For a monolithic admission the two stamps bound the
``sched.admit`` phase: the prefill StepFn call, the splice into the live
batch and the first token's readout."""


def read(ctx):
    t0, t1 = ctx["t0"], ctx["t1"]
    took = []
    for req in ctx["reqs"].values():
        if not hasattr(req, "t_admit"):
            return None  # a program without the stamps
        if (req.t_admit is not None and req.t_first is not None
                and t0 <= req.t_admit < t1):
            took.append(req.t_first - req.t_admit)
    return 1e3 * sum(took) / len(took) if took else None
