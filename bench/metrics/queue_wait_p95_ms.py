"""95th percentile of each request's queue wait, from its submission to the
start of its admission prefill (the program's ``t_admit − t_submit``
stamps), over the requests due in the window before the profiler's capture
stopped (ms); a request never admitted counts as infinite.  A traced run
stops the capture inside the window and the host is then away writing it
out, so a request admitted after that point waited behind the write-out,
not behind the scheduler: it is left out."""
from stats import percentile


def read(ctx):
    log, t0 = ctx["log"], ctx["t0"]
    t_stop = t0 + ctx.get("trace_host_s", ctx["t1"] - t0)
    waits = []
    for idx, due in log.due.items():
        if not t0 <= due < t_stop:
            continue
        req = ctx["reqs"][idx]
        if not hasattr(req, "t_admit"):
            return None  # a program without the stamps
        if req.t_admit is None:
            waits.append(float("inf"))
        elif req.t_admit <= t_stop:
            waits.append(req.t_admit - req.t_submit)
    return 1e3 * percentile(waits, 95) if waits else None
