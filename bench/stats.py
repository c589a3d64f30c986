"""End-to-end metric arithmetic over one measured window.

The harness stamps every output token with the host time at which the
scheduler step that produced it returned (``TokenLog``).  From those
stamps:

- a rate is all tokens stamped inside the window over the window's length;
- an inter-token gap is the distance between consecutive tokens of one
  request, counted when the later token lies in the window;
- time to first token is the first stamp minus the request's due time, over
  every request due in the window;
- a tail is the percentile over all such samples, never over per-request
  means.
"""
from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0–100), linear between order statistics."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("percentile of no samples")
    pos = (v.size - 1) * q / 100.0
    lo = int(np.floor(pos))
    frac = pos - lo
    if frac == 0.0:
        return float(v[lo])
    a, b = v[lo], v[lo + 1]
    return float(b) if np.isinf(b) else float(a + frac * (b - a))


class TokenLog:
    """Per-request token stamps of one run."""

    def __init__(self):
        self.stamps: dict = {}  # request index -> [host seconds, ...]
        self.due: dict = {}  # request index -> due host seconds

    def add(self, idx: int, n_new: int, t: float) -> None:
        self.stamps.setdefault(idx, []).extend([t] * n_new)

    def tokens_in(self, t0: float, t1: float) -> int:
        return sum(int(((np.asarray(s) > t0) & (np.asarray(s) <= t1)).sum())
                   for s in self.stamps.values())

    def gaps_in(self, t0: float, t1: float) -> list:
        """Inter-token gaps (s) whose later token lies in (t0, t1]."""
        out = []
        for s in self.stamps.values():
            s = np.asarray(s)
            if s.size < 2:
                continue
            later = s[1:]
            keep = (later > t0) & (later <= t1)
            out.extend((later - s[:-1])[keep].tolist())
        return out

    def ttfts(self, due_from: float, due_to: float) -> tuple:
        """(first-token delays of requests due in [due_from, due_to) that
        have a token, number due there with none)."""
        got, missing = [], 0
        for idx, due in self.due.items():
            if not due_from <= due < due_to:
                continue
            s = self.stamps.get(idx)
            if s:
                got.append(s[0] - due)
            else:
                missing += 1
        return got, missing
