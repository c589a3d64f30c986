"""Share of the traced stretch in which the device idled while the
innermost open program span was one of the scheduler's ``sched.*`` phases
(%): the idle the scheduler's own host work causes, apart from idle inside
the executor's ``exec.*`` calls and idle outside ``sched.step``.  Also logs
the whole idle split over the phases, the executor and the harness."""
import phases


def read(ctx):
    t = phases.traced(ctx)
    if t is None or not phases.has_program_spans(t[0]):
        return None
    trace, w0, w1 = t
    idle = phases.idle_by_span(trace, w0, w1)
    print(phases.idle_line(idle, w1 - w0), flush=True)
    return 100.0 * phases.idle_split(idle)["sched"] / (w1 - w0)
