"""The program's own phases and counters, read back for the per-layer
metrics.

The scheduler runs each phase of its tick under a ``sched.<phase>`` span and
the executor each StepFn call under ``exec.<kind>`` (with a child
``exec.<kind>.wait`` around the device wait).  The spans land in the
profiler's capture on the device operations' clock.  A request carries
``time.perf_counter()`` stamps: ``t_submit``, ``t_admit`` (its admission
prefill starts) and ``t_first`` (its first token on the host), the clock on
which the harness stamps due times and tokens.

The harness snapshots the program's metrics registry (``Engine.metrics()``)
at the window's two ends and hands both to the readers (``ctx["metrics0"]``,
``ctx["metrics1"]``); ``window_change`` reads a counter's or a histogram's
change between them.

A program without these spans, stamps or counters reads as nothing
(``None``), never as zero.
"""
from __future__ import annotations

import bisect

import trace_reduce

PROGRAM = ("sched.", "exec.")  # span names the program writes
HARNESS = "harness"  # idle with no program span open (outside sched.step)
CTX_KEY = "capture"  # where the harness hands readers its traced capture


def traced(ctx: dict):
    """(trace, t0, t1): the run's profiler capture (``trace_reduce.load``)
    and the bounds of its ``bench.traced`` span, as the harness loaded them
    (``run.load_capture``); None in a run without a capture."""
    return ctx.get(CTX_KEY)


def window_change(metrics0: dict, metrics1: dict, name: str, **labels):
    """The change between two registry snapshots of the counter or histogram
    ``name``, over its series whose labels include ``labels``: a float for
    a counter, (seconds, observations) for a histogram; None where the
    program has no such family."""
    fam = metrics1.get(name)
    if fam is None:
        return None
    kind = fam["kind"]
    if kind not in ("counter", "histogram"):
        raise ValueError(f"{name} is a {kind}: it has no change to read")
    want = {k: str(v) for k, v in labels.items()}

    def total(snap):
        series = [s for s in snap.get(name, {"series": []})["series"]
                  if want.items() <= s["labels"].items()]
        if kind == "counter":
            return sum((s["value"] for s in series), 0.0)
        t, n = 0.0, 0
        for s in series:
            t, n = t + s["sum"], n + s["count"]
        return t, n

    if kind == "counter":
        return total(metrics1) - total(metrics0)
    (t1, n1), (t0, n0) = total(metrics1), total(metrics0)
    return t1 - t0, n1 - n0


def has_program_spans(trace: dict) -> bool:
    return any(name.startswith("sched.") for _, _, name in trace["host"])


def busy_tick_host_ns(trace: dict, t0: float, t1: float) -> list:
    """Host nanoseconds outside StepFn calls of each tick in ``[t0, t1]``
    that called one: a ``sched.step`` span less the ``exec.<kind>`` spans
    inside it.  A tick that holds no ``exec.<kind>`` span (an open loop's
    idle spin) is left out."""
    calls = sorted((s, e) for s, e, n in trace["host"]
                   if n.startswith("exec.") and n.count(".") == 1)
    starts = [s for s, _ in calls]
    out = []
    for s, e, n in trace["host"]:
        if n != "sched.step" or s < t0 or e > t1:
            continue
        inside = [c for c in calls[bisect.bisect_left(starts, s):
                                   bisect.bisect_right(starts, e)]
                  if c[1] <= e]
        if inside:
            out.append((e - s) - sum(ce - cs for cs, ce in inside))
    return out


def idle_by_span(trace: dict, t0: float, t1: float) -> dict:
    """Idle nanoseconds of ``[t0, t1]`` (averaged over the chips) per
    program span: each stretch in which no operation ran goes to the
    innermost ``sched.*``/``exec.*`` span open at its midpoint
    (``trace_reduce.attribute`` over the program's spans alone), or to
    ``HARNESS`` where none is."""
    gaps = []
    for ops in trace["devices"].values():
        merged = trace_reduce._union(
            [(max(s, t0), min(e, t1)) for s, e, _ in ops if e > t0 and s < t1])
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    spans = [h for h in trace["host"] if h[2].startswith(PROGRAM)]
    out = trace_reduce.attribute(gaps, spans, len(trace["devices"]))
    if "no host event" in out:
        out[HARNESS] = out.pop("no host event")
    return out


def idle_split(idle: dict) -> dict:
    """{"sched", "exec", "harness"}: idle nanoseconds by layer."""
    out = {"sched": 0.0, "exec": 0.0, HARNESS: 0.0}
    for name, ns in idle.items():
        out[name.split(".", 1)[0] if name != HARNESS else HARNESS] += ns
    return out


def idle_line(idle: dict, window_ns: float) -> str:
    """One log line: the idle share of a traced stretch ``window_ns`` long,
    split over the ``sched.*`` phases, the ``exec.*`` calls and the harness
    (``idle`` as ``idle_by_span`` gives it)."""
    pct = {k: 100.0 * v / window_ns for k, v in idle.items()}
    split = {k: 100.0 * v / window_ns for k, v in idle_split(idle).items()}

    def parts(prefix):
        return ", ".join(f"{k} {v:.3f}" for k, v in sorted(
            pct.items(), key=lambda kv: -kv[1]) if k.startswith(prefix))

    return (f"device idle by program phase (% of the traced stretch): "
            f"sched {split['sched']:.3f} [{parts('sched.')}] | exec "
            f"{split['exec']:.3f} [{parts('exec.')}] | harness "
            f"{split[HARNESS]:.3f} | total {sum(split.values()):.3f}")


def ttft_terms(ctx: dict) -> dict:
    """{request index: (lateness, queue wait, admission, rest of the tick)}
    in seconds, for each request due in the window with a first token:
    ``t_submit`` − due, ``t_admit`` − ``t_submit``, ``t_first`` −
    ``t_admit``, and the harness's first-token stamp − ``t_first``.  The
    four add up to the harness's time to first token."""
    log, t0, t1 = ctx["log"], ctx["t0"], ctx["t1"]
    out = {}
    for idx, due in log.due.items():
        req = ctx["reqs"][idx]
        if not t0 <= due < t1 or getattr(req, "t_first", None) is None:
            continue
        out[idx] = (req.t_submit - due, req.t_admit - req.t_submit,
                    req.t_first - req.t_admit, log.stamps[idx][0] - req.t_first)
    return out
