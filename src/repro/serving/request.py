"""Request lifecycle for continuous batching (DESIGN.md §7).

A ``Request`` carries one prompt through the scheduler's state machine::

    QUEUED ──admit──▶ PREFILLING ──splice──▶ DECODING ──EOS/max──▶ FINISHED

PREFILLING is transient inside a single scheduler tick (prefill runs
synchronously, then the sub-state is spliced into the live batch row), but it
is modeled explicitly so telemetry can attribute time-to-first-token to the
prefill, and so a future async-prefill engine can hold requests there.

Timestamps are recorded in *scheduler steps* (one decode tick each) and in
seconds; the benchmark reports both.  ``arrival_time`` / ``finish_time``
(and ``deadline_s``) are ``time.time()`` values for the frontend's deadlines
and end-to-end latency.  Time to first token and inter-token latency come
from ``time.perf_counter()`` stamps alone — ``t_submit`` / ``t_admit`` /
``t_first`` / ``t_last`` and the per-gap ``itl_gaps`` — the clock a load
generator stamps due times and tokens on, so a request's time to first
token splits into queue wait (``t_admit - t_submit``), admission
(``t_first - t_admit``) and whatever follows on the caller's side.
"""
from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np


class RequestState(enum.Enum):
    QUEUED = "queued"
    PREFILLING = "prefilling"
    DECODING = "decoding"
    FINISHED = "finished"
    CANCELLED = "cancelled"  # retired early (client disconnect / shed)


@dataclass
class Request:
    """One generation request and its realized lifecycle telemetry."""

    req_id: int
    prompt: np.ndarray  # (T,) int32 token ids
    arrival_step: int = 0  # scheduler step at which the request exists
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    # multi-tenant serving metadata (DESIGN.md §13).  Defaults keep every
    # pre-frontend caller unchanged: one anonymous tenant, one priority
    # class, no deadline.  ``priority`` is an integer class index where
    # *lower is more urgent* (0 = interactive); the scheduler's preemption
    # victim choice and queue pick are priority-aware but degenerate to the
    # historical FIFO/youngest-first behavior when all priorities are equal.
    tenant: str = "default"
    priority: int = 1
    deadline_s: Optional[float] = None  # wall-clock budget from arrival

    state: RequestState = RequestState.QUEUED
    row: Optional[int] = None  # live batch row while DECODING
    generated: List[int] = field(default_factory=list)
    logits: Optional[List[np.ndarray]] = None  # per-token logits if collected

    admit_step: Optional[int] = None
    first_token_step: Optional[int] = None
    finish_step: Optional[int] = None
    arrival_time: Optional[float] = None
    finish_time: Optional[float] = None
    # perf_counter stamps (DESIGN.md §12): submitted (to the frontend or the
    # scheduler, whichever saw it first), its admission prefill started, its
    # first token on the host, its latest token on the host (the start of
    # the next inter-token gap); ``itl_gaps`` holds one entry per pair of
    # consecutive tokens (0 for the later tokens of a multi-token commit)
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_last: Optional[float] = None
    itl_gaps: List[float] = field(default_factory=list)
    n_preemptions: int = 0  # times evicted back to QUEUED (paged backend)
    degraded_from: Optional[int] = None  # original max_new_tokens pre-degrade
    # prefix cache (DESIGN.md §14): stamped on a hit — (L,) full blocks per
    # layer reused from the index (admission charges only unshared blocks)
    prefix_shared_blocks: Optional[np.ndarray] = None
    prefix_hit_tokens: int = 0  # matched prefix length on admission (0 = miss)
    # speculative decoding (DESIGN.md §16): lifetime draft-token counts —
    # acceptance = spec_accepted / spec_proposed feeds the adaptive depth
    # and the per-request acceptance histogram at retirement
    spec_proposed: int = 0
    spec_accepted: int = 0

    @property
    def prompt_len(self) -> int:
        return int(np.asarray(self.prompt).shape[0])

    @property
    def n_generated(self) -> int:
        return len(self.generated)

    @property
    def is_finished(self) -> bool:
        """Terminal: the request will generate no further tokens (normal
        retirement or cancellation)."""
        return self.state in (RequestState.FINISHED, RequestState.CANCELLED)

    @property
    def cancelled(self) -> bool:
        return self.state is RequestState.CANCELLED

    def deadline_exceeded(self, now: Optional[float] = None) -> bool:
        """True when a wall-clock deadline was set and has elapsed (always
        False for requests without a deadline or an arrival stamp)."""
        if self.deadline_s is None or self.arrival_time is None:
            return False
        import time as _time
        now = _time.time() if now is None else now
        return (now - self.arrival_time) > self.deadline_s

    def reset_for_requeue(self) -> None:
        """Preemption (recompute policy): drop all generated state so a
        later re-admission replays the request from its prompt.  Decoding
        is deterministic (argmax), so the replay produces the same tokens;
        arrival/queueing telemetry is preserved, admission telemetry is
        cleared (it will be re-stamped)."""
        self.state = RequestState.QUEUED
        self.row = None
        self.generated = []
        if self.logits is not None:
            self.logits = []
        self.admit_step = None
        self.first_token_step = None
        self.t_admit = self.t_first = self.t_last = None
        self.itl_gaps = []
        self.prefix_shared_blocks = None  # re-stamped on re-admission
        self.prefix_hit_tokens = 0
        self.spec_proposed = 0  # the replay re-speculates from scratch
        self.spec_accepted = 0
        self.n_preemptions += 1

    def queueing_steps(self) -> Optional[int]:
        if self.admit_step is None:
            return None
        return self.admit_step - self.arrival_step

    def latency_steps(self) -> Optional[int]:
        """Arrival → last token, in scheduler steps."""
        if self.finish_step is None:
            return None
        return self.finish_step - self.arrival_step

    def latency_seconds(self) -> Optional[float]:
        if self.finish_time is None or self.arrival_time is None:
            return None
        return self.finish_time - self.arrival_time

    def ttft_steps(self) -> Optional[int]:
        """Arrival → first token, in scheduler steps."""
        if self.first_token_step is None:
            return None
        return self.first_token_step - self.arrival_step

    def ttft_seconds(self) -> Optional[float]:
        """Submission → first token on the host (queueing + admission)."""
        if self.t_first is None or self.t_submit is None:
            return None
        return self.t_first - self.t_submit

    def mean_itl_seconds(self) -> Optional[float]:
        """Mean of the request's inter-token gaps (the streaming cadence a
        client sees); None until a second token exists."""
        if not self.itl_gaps:
            return None
        return sum(self.itl_gaps) / len(self.itl_gaps)


def poisson_arrivals(n_requests: int, rate: float,
                     rng: np.random.Generator) -> np.ndarray:
    """(n,) sorted integer arrival steps with ``rate`` requests/step.

    Inter-arrival gaps are exponential with mean ``1/rate`` (rounded down to
    whole scheduler steps), i.e. a discretized Poisson process; the first
    request always arrives at step 0 so a trace never starts idle.
    """
    if rate <= 0:
        raise ValueError("rate must be positive")
    if n_requests == 0:
        return np.zeros(0, dtype=int)
    gaps = np.floor(rng.exponential(1.0 / rate, size=n_requests)).astype(int)
    arrivals = np.cumsum(gaps)
    return arrivals - arrivals[0]


def synthesize_requests(
    n_requests: int,
    rate: float,
    vocab_size: int,
    min_prompt: int = 16,
    max_prompt: int = 48,
    max_new_tokens: int = 12,
    seed: int = 0,
    tenant_mix: Optional[Dict[str, float]] = None,
    tenant_priorities: Optional[Dict[str, int]] = None,
    prefix_templates: int = 0,
    prefix_len: int = 0,
    shared_fraction: float = 0.0,
) -> List[Request]:
    """A reproducible Poisson trace of random-token requests.

    ``tenant_mix`` assigns each request a tenant sampled from the given
    ``{name: weight}`` distribution (weights are normalized); without it
    every request belongs to the anonymous ``"default"`` tenant, so
    pre-frontend callers see identical traces.  ``tenant_priorities`` maps
    tenant names to priority-class indices (missing tenants keep the
    `Request` default).

    Shared-prefix traces (DESIGN.md §14): with ``prefix_templates > 0``,
    ``shared_fraction`` of the requests start with one of the template
    prefixes (``prefix_len`` tokens each, drawn once per template) followed
    by a unique random suffix; the rest stay fully random at the same total
    length, so sharing changes the cache topology but never the workload
    size.  Tenants bind to templates round-robin when a tenant mix is
    given, modeling per-tenant system prompts.
    """
    rng = np.random.default_rng(seed)
    arrivals = poisson_arrivals(n_requests, rate, rng)
    names, probs = None, None
    if tenant_mix:
        names = sorted(tenant_mix)
        w = np.asarray([float(tenant_mix[n]) for n in names])
        if (w < 0).any() or w.sum() <= 0:
            raise ValueError(f"tenant_mix weights must be non-negative with "
                             f"a positive sum, got {tenant_mix}")
        probs = w / w.sum()
    templates = None
    if prefix_templates > 0:
        if prefix_len <= 0:
            raise ValueError("prefix_templates > 0 requires prefix_len > 0")
        if not 0.0 <= shared_fraction <= 1.0:
            raise ValueError(f"shared_fraction must be in [0, 1], "
                             f"got {shared_fraction}")
        if prefix_len >= min_prompt:
            raise ValueError(f"prefix_len ({prefix_len}) must leave room "
                             f"for a unique suffix (min_prompt "
                             f"{min_prompt})")
        templates = [rng.integers(0, vocab_size, size=prefix_len)
                     .astype(np.int32) for _ in range(prefix_templates)]
    reqs = []
    for i, step in enumerate(arrivals):
        T = int(rng.integers(min_prompt, max_prompt + 1))
        # legacy draw order (T, prompt, tenant) when no templates are in
        # play, so pre-existing seeded traces stay bit-identical
        prompt = (rng.integers(0, vocab_size, size=T).astype(np.int32)
                  if templates is None else None)
        kw = {}
        tenant = None
        if names is not None:
            tenant = names[int(rng.choice(len(names), p=probs))]
            kw["tenant"] = tenant
            if tenant_priorities and tenant in tenant_priorities:
                kw["priority"] = int(tenant_priorities[tenant])
        if templates is not None:
            if rng.random() < shared_fraction:
                t_ix = (names.index(tenant) % len(templates)
                        if tenant is not None
                        else int(rng.integers(len(templates))))
                suffix = rng.integers(0, vocab_size,
                                      size=T - prefix_len).astype(np.int32)
                prompt = np.concatenate([templates[t_ix], suffix])
            else:
                prompt = rng.integers(0, vocab_size, size=T).astype(np.int32)
        reqs.append(Request(req_id=i, prompt=prompt, arrival_step=int(step),
                            max_new_tokens=max_new_tokens, **kw))
    return reqs


def latency_percentiles(requests: List[Request]) -> dict:
    """p50/p99 of end-to-end latency and TTFT over the finished subset, in
    steps and seconds (seconds only when the stamps were recorded), and of
    ITL over every inter-token gap of those requests (``itl_s``, the same
    samples as the registry's ``itl_s`` histogram).

    Keys for an observable are present only when at least one request
    recorded it — an empty trace returns just ``{"n_finished": 0}``, never
    NaN percentiles (callers print ``n/a`` for missing keys).
    """
    samples = {
        "steps": [r.latency_steps() for r in requests],
        "s": [r.latency_seconds() for r in requests],
        "ttft_steps": [r.ttft_steps() for r in requests],
        "ttft_s": [r.ttft_seconds() for r in requests],
        "itl_s": [g for r in requests for g in r.itl_gaps],
    }
    out = {"n_finished": sum(1 for v in samples["steps"] if v is not None)}
    for key, vals in samples.items():
        vals = [v for v in vals if v is not None]
        if vals:
            out[f"p50_{key}"] = float(np.percentile(vals, 50))
            out[f"p99_{key}"] = float(np.percentile(vals, 99))
    return out
