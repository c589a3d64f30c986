"""The one traffic generator: a mix file plus a seed → the requests of a run.

A mix (``bench/traffic/<mix>.json``) is parameters only:

- ``prompt_buckets`` / ``prompt_weights``: prompt lengths and their shares;
- ``output_min`` / ``output_max``: new tokens per request, uniform;
- ``arrival``: ``"saturated"`` (``rows`` requests are admitted before the
  window and a ``backlog`` stays queued through it) or ``"poisson"`` (open
  loop at ``rate_rps`` requests per second from the window's start);
- ``n_requests``: how many requests one run draws from;
- ``schedule_seed``: the seed of the schedule's order;
- ``rows`` and ``compression``/``importance``: the batch width and the
  HeadKV settings the engine is built with (read by ``cells.py``).

Every seed gets the same schedule: exact bucket shares, evenly spaced
output quantiles and evenly spaced exponential inter-arrival gaps, put in
one order drawn from the mix's ``schedule_seed``.  The run's seed draws
the prompt tokens (and the weights).  An order drawn per seed moved the
open-loop cell's TTFT and ITL tails by about 45% between seeds, against 1%
between two runs of one seed, on a TPU v5e: with tens of requests
in a window, which ones collide is the tail.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Planned:
    """One request as the generator plans it."""

    index: int
    prompt: np.ndarray  # (T,) int32
    max_new_tokens: int
    due_s: float  # offset of its due time from the window's start
    fill: bool = False  # saturated cells: admitted before the window


def _bucket_counts(weights, n: int) -> np.ndarray:
    """Largest-remainder split of ``n`` into shares ``weights``."""
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    raw = w * n
    counts = np.floor(raw).astype(np.int64)
    order = np.argsort(-(raw - counts), kind="stable")
    counts[order[: n - counts.sum()]] += 1
    return counts


def _even_quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def plan(mix: dict, seed: int, vocab_size: int) -> list:
    """The run's requests in submission order (fills first)."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32])
    order = np.random.default_rng(int(mix["schedule_seed"]))
    n = int(mix["n_requests"])
    lens = np.repeat(np.asarray(mix["prompt_buckets"], np.int64),
                     _bucket_counts(mix["prompt_weights"], n))
    lo, hi = int(mix["output_min"]), int(mix["output_max"])
    outs = lo + np.floor(_even_quantiles(n) * (hi - lo + 1)).astype(np.int64)
    lens = lens[order.permutation(n)]
    outs = outs[order.permutation(n)]
    due = np.zeros(n)
    fill = np.zeros(n, bool)
    if mix["arrival"] == "poisson":
        gaps = -np.log1p(-_even_quantiles(n)) / float(mix["rate_rps"])
        due = np.cumsum(gaps[order.permutation(n)])
    elif mix["arrival"] == "saturated":
        rows = int(mix["rows"])
        fill[:rows] = True
        # rows admitted before the window carry an evenly spread share of
        # their output left, as rows of a long-running batch do, so
        # retirements and admissions start at once and keep a steady pace
        left = np.maximum(1, np.round(outs[:rows] * _even_quantiles(rows)
                                      [order.permutation(rows)])
                          ).astype(np.int64)
        outs = outs.copy()
        outs[:rows] = left
    else:
        raise ValueError(f"unknown arrival {mix['arrival']!r}")
    out = []
    for i in range(n):
        prompt = rng.integers(0, vocab_size, size=int(lens[i]), dtype=np.int32)
        out.append(Planned(index=i, prompt=prompt,
                           max_new_tokens=int(outs[i]), due_s=float(due[i]),
                           fill=bool(fill[i])))
    return out
