"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to device metrics.

Device planes are those named ``/device:TPU:<n>``; an operation is an event
on a device plane's ``XLA Ops`` line.  Over a window ``[t0, t1]`` (in the
trace's nanoseconds) the reduction gives, per chip and then averaged:

- ``busy_ns``: the union of operation intervals (overlaps counted once);
- ``op_ns``: total device time per operation (its HLO instruction name);
- ``kernel_ns``: total device time of the operations whose HLO text matches
  a kernel's pattern;
- ``collective_ns`` / ``exposed_collective_ns``: time in collective
  operations (all-reduce, all-gather, ...), and the part of it during which
  no other operation ran on that chip;
- ``idle_gaps``: each stretch with no operation, attributed to the
  innermost host event (``TraceAnnotation`` spans and the runtime's own
  host events) running at the gap's midpoint.
"""
from __future__ import annotations

import heapq
import re
from collections import defaultdict
from pathlib import Path

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute",
    re.I)


def find_xplane(log_dir) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def load(path) -> dict:
    """{"devices": {plane: [(start, end, name)]}, "host": [(s, e, name)]}
    in nanoseconds, from a trace file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((e.start_ns, e.start_ns + e.duration_ns,
                                e.name) for e in line.events)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events if e.duration_ns > 0)
    return {"devices": devices, "host": host}


def window_of(trace: dict, span_name: str):
    """(start, end) of the first host event called ``span_name``."""
    for s, e, name in trace["host"]:
        if name == span_name:
            return s, e
    raise KeyError(f"no host event {span_name!r} in the trace")


def reduce(trace: dict, t0: float, t1: float, kernels: dict) -> dict:
    """Metrics of ``[t0, t1]``; ``kernels`` maps a metric's kernel name to
    a regular expression over operation names."""
    pats = {k: re.compile(v) for k, v in kernels.items()}
    n_dev = len(trace["devices"])
    if n_dev == 0:
        raise ValueError("the trace holds no TPU device plane")
    busy = 0.0
    op_ns = defaultdict(float)
    kern = defaultdict(float)
    coll = exposed = 0.0
    gaps_all = []
    for plane, ops in trace["devices"].items():
        ops = [(max(s, t0), min(e, t1), n) for s, e, n in ops
               if e > t0 and s < t1]
        merged = _union([(s, e) for s, e, _ in ops])
        busy += sum(e - s for s, e in merged)
        for s, e, n in ops:
            op_ns[short_name(n)] += e - s
            for k, p in pats.items():
                if p.search(n):
                    kern[k] += e - s
        other = _union([(s, e) for s, e, n in ops if not COLLECTIVE.search(n)])
        for s, e, n in ops:
            if COLLECTIVE.search(n):
                coll += e - s
                exposed += (e - s) - _overlap(s, e, other)
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        gaps_all.extend((edges[i], edges[i + 1])
                        for i in range(0, len(edges), 2)
                        if edges[i + 1] > edges[i])
    return {
        "n_devices": n_dev,
        "window_ns": t1 - t0,
        "busy_ns": busy / n_dev,
        "op_ns": {k: v / n_dev for k, v in op_ns.items()},
        "kernel_ns": {k: kern.get(k, 0.0) / n_dev for k in pats},
        "collective_ns": coll / n_dev,
        "exposed_collective_ns": exposed / n_dev,
        "idle_gaps": attribute(gaps_all, trace["host"], n_dev),
    }


def short_name(op: str) -> str:
    """An operation's HLO instruction name: the trace names an operation
    by its whole HLO text (``%fusion.3 = bf16[...] fusion(...)``)."""
    return op.split(" = ", 1)[0].lstrip("%")


def _overlap(s, e, merged) -> float:
    tot = 0.0
    for a, b in merged:
        if b <= s:
            continue
        if a >= e:
            break
        tot += min(b, e) - max(a, s)
    return tot


def attribute(gaps, host, n_dev: int = 1) -> dict:
    """Idle nanoseconds per host event name: each gap goes to the host
    event that started last among those running at its midpoint — the
    innermost span on the host's stack then ("no host event" if none).
    One sweep over gaps and host events, both in time order."""
    host = sorted(host)
    out = defaultdict(float)
    live = []  # heap of (-index, end) of the host events begun so far
    j = 0
    for s, e in sorted(gaps, key=lambda g: g[0] + g[1]):
        mid = (s + e) / 2
        while j < len(host) and host[j][0] <= mid:
            heapq.heappush(live, (-j, host[j][1]))
            j += 1
        while live and live[0][1] < mid:  # ended: later gaps lie later
            heapq.heappop(live)
        name = host[-live[0][0]][2] if live else "no host event"
        out[name] += (e - s) / n_dev
    return dict(out)


def top(d: dict, n: int = 10) -> list:
    """The ``n`` largest entries as [[name, seconds], ...]."""
    items = sorted(d.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in items]
