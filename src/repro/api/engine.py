"""The `Engine` facade: one front door for FairKV serving.

Owns the full serving composition — parameter init, plan construction,
slot-layout weight permutation, and cache state — behind a handful of
methods, so no caller re-wires
``ModelConfig → init params → plan → slot weights → prefill → decode``
by hand (DESIGN.md §8):

- **one-shot batch**: `Engine.generate(prompts, max_new_tokens)` runs
  prefill + compression + a jitted decode loop and returns a
  `GenerationResult` (tokens, logits, realized per-head lengths, plan
  metrics, timings).
- **continuous**: `submit` / `step` / `stream` / `run_trace` wrap the
  request scheduler (`repro.serving.scheduler.Scheduler`, DESIGN.md §7);
  `stream` yields per-token `StreamEvent`s as requests progress.
- **replanning**: `replan()` rebuilds the head placement — from a measured
  profile and/or per-shard speed factors in one-shot mode, or from the
  realized live-cache profile (migrating the cache in place) in continuous
  mode — the PR-1 online-replanning path as a first-class method.
- **profiling**: `measure_profile(batch)` runs a profiling prefill and
  returns the (L, H) realized per-head retained lengths (the paper's §4.1
  offline statistic) for feeding back into `replan` or a fresh `build`.

The facade holds the *original-layout* parameters (`.params`) so replans
can re-slotify, and exposes the low-level pieces (`.plan`,
`.plan_arrays`, `.serve_params`, `.scheduler`) for telemetry and tests.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.config import DTYPES as _DTYPES
from repro.api.config import EngineConfig
from repro.api.stats import EngineStats, collect_stats
from repro.cache.slot_cache import PlanArrays
from repro.core.placement import HeadPlacement
from repro.core.planner import PlannerConfig, build_plan
from repro.core.profiles import profile_from_lengths, synthetic_profile
from repro.exec.base import make_executor
from repro.models import init_params
from repro.obs import Obs
from repro.serving import engine as _serve
from repro.serving.cache_backend import make_cache_backend
from repro.serving.request import Request
from repro.serving.scheduler import Scheduler

# ---------------------------------------------------------------------------
# Result / event types
# ---------------------------------------------------------------------------


@dataclass
class GenerationResult:
    """Output of `Engine.generate` (one-shot batch mode).

    ``tokens[:, 0]`` is the prefill argmax (the first generated token);
    ``tokens[:, 1:]`` come from the decode loop.  ``logits`` aligns with
    ``tokens``: entry t is the distribution the t-th token was taken from.
    ``lengths`` is the realized per-head retained-length tensor
    (L, Hkv, B) — the paper's workload observable; ``realized_profile``,
    ``efficiency`` and ``makespan`` are derived from it against the active
    plan (None for attention-free models).
    """

    tokens: np.ndarray  # (B, 1 + steps)
    logits: Optional[np.ndarray]  # (B, 1 + steps, V) when collected
    lengths: np.ndarray  # (L, Hkv, B) realized retained lengths
    realized_profile: Optional[np.ndarray]  # (L, Hkv)
    efficiency: Optional[float]  # plan E (Eq. 5) on the realized profile
    makespan: Optional[float]  # plan max-shard load on the realized profile
    prefill_s: float
    step_s: List[float] = field(default_factory=list)  # per-decode-step wall


@dataclass(frozen=True)
class StreamEvent:
    """One generated token from the continuous-mode `Engine.stream`."""

    req_id: int
    token: int
    index: int  # position within the request's generated sequence
    step: int  # scheduler step that produced it
    finished: bool  # True on the request's last token


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


class Engine:
    """Facade over the FairKV serving stack.  Construct via `Engine.build`."""

    def __init__(self, cfg: EngineConfig, params: dict, plan: HeadPlacement,
                 profile: Optional[np.ndarray],
                 head_importance: Optional[np.ndarray] = None,
                 mesh=None):
        if mesh is not None and cfg.executor == "local":
            raise ValueError(
                "mesh= was passed but executor='local' runs on a single "
                "device and would silently ignore it; set "
                "EngineConfig(executor='mesh') to run on the mesh")
        self.cfg = cfg
        self.params = params  # original layout — kept for re-slotify
        self.plan = plan
        self.profile = profile  # (L, H) planning profile (None: attn-free)
        self.head_importance = head_importance  # headkv per-head weights
        self.mesh = mesh
        self.pa = PlanArrays.from_plan(plan)
        # observability (DESIGN.md §12): one registry + trace per engine,
        # threaded through the executor, backend, and (lazily) the scheduler
        self.obs = Obs.build(cfg.obs)
        # executor (DESIGN.md §10): owns the compiled prefill/decode StepFns;
        # weights and plan arrays are StepFn *arguments*, so replans swap
        # placements without recompiling
        self.executor = make_executor(cfg.executor, cfg.model,
                                      cfg.compression,
                                      exec_cfg=cfg.executor_cfg, mesh=mesh,
                                      paging=cfg.paging, obs=self.obs)
        self.sp = self.executor.shard_params(
            _serve.slotify_params(params, plan, cfg.model))
        # cache storage backend (DESIGN.md §9): "slot" | "paged" | plugin
        self.backend = make_cache_backend(
            cfg.cache_backend, cfg.model, cfg.compression,
            max_live_tokens=cfg.scheduler.max_live_tokens, paging=cfg.paging,
            n_shards=cfg.n_shards,
            max_live_tokens_per_shard=cfg.scheduler.max_live_tokens_per_shard,
            pool_partitions=self.executor.pool_partitions,
            row_partitions=self.executor.row_partitions, obs=self.obs)
        self.state: Optional[_serve.ServeState] = None
        self._mode: Optional[str] = None  # "oneshot" | "continuous" (last used)
        # persisted straggler speed factors (set by a speed-aware replan);
        # later replans and a lazily-created scheduler inherit them so the
        # mitigation is never silently reverted
        self._shard_speeds: Optional[np.ndarray] = None
        self._scheduler: Optional[Scheduler] = None
        self._next_req_id = 0
        # drain() before the scheduler exists (e.g. a signal landing during
        # build) must still stick — applied on first _ensure_scheduler
        self._drain_pending = False

    # ---- construction ------------------------------------------------------

    @classmethod
    def build(cls, cfg: EngineConfig, *, params: Optional[dict] = None,
              profile: Optional[np.ndarray] = None, rng=None, mesh=None,
              head_importance: Optional[np.ndarray] = None) -> "Engine":
        """Assemble an engine: params (init'd if not given), plan, slot
        weights.

        ``profile`` is the (L, H) expected per-head workload the planner
        optimizes; default is a synthetic profile seeded from
        ``cfg.profile_seed`` / ``cfg.profile_skew`` (swap in a measured one
        from `measure_profile` for paper-faithful planning).  ``mesh`` is
        the (data, model) device mesh the ``mesh`` executor runs on
        (DESIGN.md §10) — required there, rejected with ``executor='local'``
        (a silently ignored mesh is a misconfiguration, not a fallback).
        """
        model = cfg.model
        dtype = _DTYPES[cfg.dtype]
        if params is None:
            rng = jax.random.PRNGKey(cfg.seed) if rng is None else rng
            params = init_params(model, rng, dtype=dtype,
                                 max_seq_len=cfg.max_seq_len)
        if model.attention_free:
            plan = build_plan(np.ones((model.n_layers, 1)), 1,
                              PlannerConfig(mode="sha", slots_per_shard=1))
            profile = None
        else:
            if profile is None:
                profile = synthetic_profile(
                    model.n_layers, model.n_kv_heads,
                    budget=cfg.compression.budget, skew=cfg.profile_skew,
                    seed=cfg.profile_seed)
            plan = build_plan(profile, cfg.n_shards, cfg.planner)
        return cls(cfg, params, plan, profile,
                   head_importance=head_importance, mesh=mesh)

    # ---- low-level views ---------------------------------------------------

    @property
    def plan_arrays(self) -> PlanArrays:
        return self.pa

    @property
    def serve_params(self) -> dict:
        return self.sp

    @property
    def dtype(self):
        return _DTYPES[self.cfg.dtype]

    def _invalidate(self) -> None:
        """Plan changed: rebuild slot weights + plan arrays.  The executor's
        StepFn takes both as arguments, so nothing recompiles (the shapes
        are replan-invariant — slot grid and capacity are fixed)."""
        self.pa = PlanArrays.from_plan(self.plan)
        self.sp = self.executor.shard_params(
            _serve.slotify_params(self.params, self.plan, self.cfg.model))

    # ---- one-shot serving --------------------------------------------------

    def prefill(self, batch: Union[Dict[str, jnp.ndarray], np.ndarray],
                rows: Optional[jnp.ndarray] = None):
        """Run the prompt through prefill+compression; holds the resulting
        cache on ``self.state``.  Returns (logits (B, V), lengths
        (L, Hkv, B))."""
        batch = self._as_batch(batch)
        state, logits, lengths = self.executor.prefill(
            self.sp, batch, self.pa, rows=rows,
            head_importance=self.head_importance)
        self.state = state
        self._mode = "oneshot"
        return logits, lengths

    def generate(self, prompts: Union[Dict[str, jnp.ndarray], np.ndarray],
                 max_new_tokens: int,
                 teacher_tokens: Optional[np.ndarray] = None,
                 collect_logits: bool = True) -> GenerationResult:
        """One-shot batch generation: prefill + ``max_new_tokens`` decode
        steps.

        ``prompts`` is a (B, T) int token array or a prepared batch dict.
        ``teacher_tokens`` (B, max_new_tokens), when given, forces the token
        *fed* at each decode step (teacher forcing for fidelity evals); the
        returned ``tokens`` are still the model's argmax choices.
        """
        t0 = time.perf_counter()
        logits, lengths = self.prefill(prompts)
        jax.block_until_ready(logits)
        prefill_s = time.perf_counter() - t0
        # one-shot TTFT is the prefill wall (no queue to wait in)
        self.obs.metrics.histogram(
            "ttft_s", help="time to first token (queue wait + prefill "
                           "wall time)").observe(prefill_s)
        # re-house the prefilled cache in the configured backend's layout
        # (identity for "slot"; "paged" allocates blocks proportional to the
        # realized retained lengths).  One-shot mode has no request queue to
        # preempt into, so an undersized pool is a config error, not a
        # scheduling event — fail with the remedy instead of a raw signal.
        from repro.paging.block_pool import PoolExhausted
        try:
            self.state = self.backend.from_prefill(self.state, self.pa)
        except PoolExhausted as e:
            raise ValueError(
                f"cache pool too small for one-shot generation ({e}); "
                f"raise PagingConfig.n_blocks or leave it 0 for "
                f"worst-case sizing") from e
        state = self.state
        tokens = [np.asarray(state.last_tokens)]
        logits_all = [np.asarray(logits)] if collect_logits else None
        step_s: List[float] = []
        for t in range(max_new_tokens):
            tok = (state.last_tokens if teacher_tokens is None
                   else jnp.asarray(teacher_tokens[:, t], jnp.int32))
            try:
                state = self.backend.prepare_decode(state, None)
            except PoolExhausted as e:
                raise ValueError(
                    f"cache pool ran dry at decode step {t} ({e}); one-shot "
                    f"generation cannot preempt — raise "
                    f"PagingConfig.n_blocks") from e
            t0 = time.perf_counter()
            state, lg = self.executor.decode(self.sp, state, self.pa, tok)
            # rebind immediately: decode donated the previous state's
            # buffers, so self.state must never outlive a step — a failure
            # on a later iteration would otherwise leave the engine holding
            # deleted arrays
            self.state = state
            jax.block_until_ready(lg)
            step_s.append(time.perf_counter() - t0)
            self.obs.metrics.histogram(
                "itl_s", help="inter-token latency (per-request mean in "
                              "continuous mode; per-step in one-shot mode)"
                ).observe(step_s[-1])
            tokens.append(np.asarray(state.last_tokens))
            if collect_logits:
                logits_all.append(np.asarray(lg))
        lengths_np = np.asarray(lengths)
        realized = eff = mk = None
        if lengths_np.size:
            realized = profile_from_lengths(np.asarray(lengths_np, np.float64))
            eff = float(self.plan.efficiency(realized))
            mk = float(self.plan.makespan(realized))
        return GenerationResult(
            tokens=np.stack(tokens, axis=1),
            logits=(np.stack(logits_all, axis=1) if collect_logits else None),
            lengths=lengths_np, realized_profile=realized, efficiency=eff,
            makespan=mk, prefill_s=prefill_s, step_s=step_s)

    def measure_profile(self, batch: Union[Dict, np.ndarray]) -> np.ndarray:
        """Profiling pass (paper §4.1): run prefill+compression on a sample
        batch and return the (L, H) mean realized per-head lengths.

        The compression selection is plan-independent, so the measurement is
        valid for planning *any* layout.  Engine state is left untouched.
        """
        saved = self.state
        try:
            _, lengths = self.prefill(batch)
            return profile_from_lengths(np.asarray(lengths, np.float64))
        finally:
            self.state = saved

    def _as_batch(self, batch) -> Dict[str, jnp.ndarray]:
        if isinstance(batch, dict):
            return batch
        return {"tokens": jnp.asarray(batch, jnp.int32)}

    # ---- replanning --------------------------------------------------------

    def replan(self, profile: Optional[np.ndarray] = None,
               shard_speeds: Optional[Sequence[float]] = None) -> dict:
        """Rebuild the head placement and swap it in.

        Continuous mode (scheduler live): delegates to the scheduler's
        online replan — live-cache migration with accept/reject scoring
        (DESIGN.md §7) — planning from the realized profile unless
        ``profile`` and/or ``shard_speeds`` (straggler mitigation,
        DESIGN.md §6) override the inputs.  One-shot mode: the plan is
        rebuilt from ``profile`` (default: the build-time profile) and
        optional ``shard_speeds``; a live one-shot cache is migrated into
        the new layout.
        """
        if self._scheduler is not None:
            event = self._scheduler.replan(profile=profile,
                                           shard_speeds=shard_speeds)
            self._sync_from_scheduler()
            return event
        if self.cfg.model.attention_free:
            raise ValueError("attention-free models have no head placement "
                             "to replan")
        prof = self.profile if profile is None else np.asarray(profile)
        if shard_speeds is not None:
            self._shard_speeds = np.asarray(shard_speeds, float)
        old_pa = self.pa
        self.plan = build_plan(prof, self.cfg.n_shards, self.cfg.planner,
                               shard_speeds=self._shard_speeds)
        self.profile = prof
        self._invalidate()
        migrated = False
        if self.state is not None and self.state.cache is not None:
            from repro.cache.slot_cache import SlotCache, migrate_cache
            if isinstance(self.state.cache, SlotCache):
                # prefill leaves the cache in slot layout regardless of
                # backend (generate() adopts it later); migrate it in place
                cache = migrate_cache(self.state.cache, old_pa, self.pa)
            else:
                _, commit = self.backend.migrate_cache(self.state.cache,
                                                       old_pa, self.pa)
                cache = commit()
            self.state = dataclasses.replace(self.state, cache=cache)
            migrated = True
        return {"plan": self.plan, "migrated_cache": migrated,
                "shard_speeds": (None if self._shard_speeds is None
                                 else list(self._shard_speeds))}

    # ---- continuous serving ------------------------------------------------

    @property
    def scheduler(self) -> Optional[Scheduler]:
        """The live continuous-batching scheduler (None until first
        `submit` / `step` / `stream`)."""
        return self._scheduler

    def _ensure_scheduler(self) -> Scheduler:
        self._mode = "continuous"
        if self._scheduler is None:
            # the scheduler gets its OWN backend instance: backends carry
            # allocator state (pool + table mirror), and a later one-shot
            # generate() resets the engine's backend — sharing one instance
            # would silently invalidate the scheduler's live block topology
            self._scheduler = Scheduler(
                self.cfg.model, self.params, self.plan,
                self.cfg.compression, self.cfg.scheduler,
                planner_cfg=self.cfg.planner, dtype=self.dtype,
                serve_params=self.sp,  # same plan -> reuse slot weights
                backend=make_cache_backend(
                    self.cfg.cache_backend, self.cfg.model,
                    self.cfg.compression,
                    max_live_tokens=self.cfg.scheduler.max_live_tokens,
                    paging=self.cfg.paging,
                    n_shards=self.cfg.n_shards,
                    max_live_tokens_per_shard=(
                        self.cfg.scheduler.max_live_tokens_per_shard),
                    pool_partitions=self.executor.pool_partitions,
                    row_partitions=self.executor.row_partitions,
                    obs=self.obs),
                # the executor is shared: its StepFn caches are keyed by
                # batch shape and cache layout, so one-shot and continuous
                # traces coexist without evicting each other
                executor=self.executor,
                head_importance=self.head_importance,
                obs=self.obs, plan_profile=self.profile,
                prefix_cfg=self.cfg.prefix,
                spec_cfg=self.cfg.speculation)
            # inherit any one-shot straggler mitigation
            self._scheduler.shard_speeds = self._shard_speeds
            if self._drain_pending:
                self._scheduler.drain()
        return self._scheduler

    def _sync_from_scheduler(self) -> None:
        """Adopt the scheduler's plan/weights after an online replan (the
        scheduler owns them in continuous mode)."""
        sched = self._scheduler
        if sched is not None and sched.plan is not self.plan:
            self.plan, self.pa, self.sp = sched.plan, sched.pa, sched.sp

    def warmup(self) -> None:
        """Compile the continuous decode step outside any timed region (an
        all-inactive step has the same trace signature as live ones).

        The decode StepFn donates its state argument, so the warmup result
        must be adopted — holding the old state would keep deleted buffers.
        An all-inactive tick leaves cache contents/lengths/positions
        untouched; only ``decode_steps`` (the ring-write phase) is restored
        so a warmed scheduler stays step-for-step identical to a cold one.
        With requests already live the tick would be a *real* decode
        (appends included), so warmup is a no-op then — the step is
        compiled by that point anyway.
        """
        sched = self._ensure_scheduler()
        if sched.active:
            return
        steps0 = sched.state.decode_steps + 0  # fresh buffer: survives donation
        state, _ = sched._decode(sched.state, sched.active_mask())
        sched.state = dataclasses.replace(state, decode_steps=steps0)

    def submit(self, request: Union[Request, np.ndarray, Sequence[int]],
               max_new_tokens: int = 16, eos_id: Optional[int] = None,
               arrival_step: int = 0, tenant: str = "default",
               priority: int = 1,
               deadline_s: Optional[float] = None) -> Request:
        """Queue a request (continuous mode).  Accepts a prepared `Request`
        or a raw prompt token sequence; ``tenant`` / ``priority`` /
        ``deadline_s`` thread the multi-tenant metadata (DESIGN.md §13)
        onto a raw-prompt submission (a prepared `Request` carries its
        own)."""
        if not isinstance(request, Request):
            request = Request(req_id=self._next_req_id,
                              prompt=np.asarray(request, np.int32),
                              arrival_step=arrival_step,
                              max_new_tokens=max_new_tokens, eos_id=eos_id,
                              tenant=tenant, priority=priority,
                              deadline_s=deadline_s)
        self._next_req_id = max(self._next_req_id, request.req_id + 1)
        self._ensure_scheduler().submit(request)
        return request

    def cancel(self, request_id: int) -> bool:
        """Retire an in-flight or queued request early (continuous mode):
        its batch row and — on the paged backend — its pool blocks are
        released immediately (refcounts decremented), exactly like a
        normal retirement.  The client-disconnect path for SSE streams.
        Returns False when the id is unknown or already finished."""
        if self._scheduler is None:
            return False
        return self._scheduler.cancel(request_id)

    def drain(self) -> None:
        """Graceful shutdown (continuous mode): stop admitting, let live
        rows decode to completion.  `run_trace` then cancels queued and
        unsubmitted requests and returns; safe to call from a signal
        handler mid-trace (it only sets a flag)."""
        self._drain_pending = True
        if self._scheduler is not None:
            self._scheduler.drain()

    def step(self) -> dict:
        """One scheduler tick: admit → decode → retire → (maybe) replan."""
        ev = self._ensure_scheduler().step()
        self._sync_from_scheduler()
        return ev

    def stream(self, requests: Sequence[Request],
               max_steps: int = 10_000) -> Iterator[StreamEvent]:
        """Drive a request trace, yielding a `StreamEvent` per generated
        token as scheduler steps complete (per-request token iteration).

        Requests are submitted at their ``arrival_step``; iteration ends
        when every request has finished or ``max_steps`` elapses.  Trace
        telemetry stays available on `self.scheduler` afterwards.
        """
        sched = self._ensure_scheduler()
        pending = sorted(requests, key=lambda r: (r.arrival_step, r.req_id))
        emitted = {r.req_id: 0 for r in pending}
        i = 0
        # completion is judged on *these* requests, not the scheduler's
        # global finish count — other in-flight requests finishing must not
        # truncate this stream
        while (any(not r.is_finished for r in pending)
               and sched.step_idx < max_steps):
            while (i < len(pending)
                   and pending[i].arrival_step <= sched.step_idx):
                self.submit(pending[i])
                i += 1
            ev = sched.step()
            self._sync_from_scheduler()
            for req in pending:
                n = req.n_generated
                while emitted[req.req_id] < n:
                    k = emitted[req.req_id]
                    emitted[req.req_id] = k + 1
                    yield StreamEvent(
                        req_id=req.req_id, token=req.generated[k], index=k,
                        step=ev["step"],
                        finished=req.is_finished and k == n - 1)

    def run_trace(self, requests: Sequence[Request],
                  max_steps: int = 10_000) -> dict:
        """Drive a full trace to completion; returns the scheduler's summary
        telemetry (steps, tokens/s, mid-stream admissions, replan log)."""
        out = self._ensure_scheduler().run(requests, max_steps=max_steps)
        self._sync_from_scheduler()
        return out

    # ---- observability (DESIGN.md §12) -------------------------------------

    def stats(self) -> EngineStats:
        """One typed snapshot of the engine's operational state: nested
        ``scheduler`` / ``pool`` / ``prefix`` / ``plan`` / ``speculation``
        sections (`repro.api.stats.EngineStats`).  Always constructible —
        sections without a live source come back with ``None`` fields and
        an empty ``detail`` instead of raising.  Supersedes the loose
        `memory_stats` / `prefix_stats` / `imbalance` / `replan_log`
        accessors, which remain as thin delegates (DESIGN.md §8)."""
        return collect_stats(self)

    def prefix_stats(self) -> dict:
        """Deprecated: use ``stats().prefix`` (typed) — this returns its
        raw ``detail`` dict (empty until a continuous scheduler with
        sharing enabled exists)."""
        return self.stats().prefix.detail

    def metrics(self) -> dict:
        """Deterministic snapshot of every metric family (counters, gauges,
        histograms with cumulative buckets); ``{}`` when obs is disabled."""
        return self.obs.metrics.snapshot()

    def metrics_prometheus(self) -> str:
        """Prometheus text exposition of the metrics registry."""
        return self.obs.metrics.to_prometheus()

    def metrics_jsonl(self) -> str:
        """One JSON object per metric series (appendable log format)."""
        return self.obs.metrics.to_jsonl()

    def trace_export(self) -> str:
        """Chrome trace-event JSON of the recent span window — load in
        Perfetto or chrome://tracing."""
        return self.obs.trace.export_json()

    # ---- continuous-mode telemetry ----------------------------------------

    @property
    def finished_requests(self) -> List[Request]:
        return [] if self._scheduler is None else self._scheduler.finished

    @property
    def replan_log(self) -> List[dict]:
        """Deprecated: use ``stats().scheduler.replan_log``."""
        return self.stats().scheduler.replan_log

    def imbalance(self) -> float:
        """Deprecated: use ``stats().scheduler.imbalance``.  max/mean
        realized per-shard KV load (continuous mode); raises until the
        continuous scheduler exists (the typed field is None instead)."""
        v = self.stats().scheduler.imbalance
        if v is None:
            raise RuntimeError("imbalance() requires the continuous "
                               "scheduler; call submit/stream first")
        return v

    def memory_stats(self) -> dict:
        """Deprecated: use ``stats().pool`` (typed) — this returns its raw
        ``detail`` dict.  Reports whichever mode (one-shot / continuous)
        ran most recently, so interleaved use never returns a stale idle
        cache; raises with no live cache (the typed section is empty
        instead)."""
        pool = self.stats().pool
        if not pool.detail:
            raise RuntimeError("memory_stats() needs a live cache; call "
                               "generate/prefill or submit/stream first")
        return pool.detail
