"""Host time per busy scheduler tick outside the StepFn calls, over the
traced stretch (ms): each ``sched.step`` span of the profiler's capture
that holds an ``exec.<kind>`` span (an admission prefill, a prefill chunk,
a decode, or propose + verify), less those ``exec.<kind>`` spans.  The open
loop's idle spins, which call no StepFn, are left out."""
import phases


def read(ctx):
    t = phases.traced(ctx)
    if t is None:
        return None
    ns = phases.busy_tick_host_ns(*t)
    return 1e-6 * sum(ns) / len(ns) if ns else None
