"""Continuous-batching scheduler over the paged cache (DESIGN.md §7, §9).

The engine's decode step is batch-shaped: every tick runs all ``max_rows``
batch rows, and a retired row (``lengths == 0`` everywhere) contributes
exactly zero work inside ``paged_fairkv_decode`` and zero output through
the o-projection.  Continuous batching therefore reduces to *row
bookkeeping*:

- a **freelist** hands out retired rows to queued requests;
- **admission** prefills the new request alone — with slot-cache ownership
  evaluated at its target global row id (``prefill(..., rows=[row])``) — and
  the paged backend pages the resulting sub-state into the live batch
  (`PagedBackend.splice`) when its free blocks cover the request;
- **retirement** (EOS or max-new-tokens) frees the row's blocks, clears its
  state (`PagedBackend.release_rows`) and returns the row to the freelist.

On top of the lifecycle the scheduler watches the *realized* per-shard KV
load (Σ ``lengths`` per shard, the paper's Eq. 4 observable) over a sliding
window; when the max/mean imbalance stays above a threshold for the whole
window (hysteresis) and a cooldown has elapsed, it rebuilds the
``HeadPlacement`` from the realized per-head profile (``build_plan``),
re-slotifies the weights, and migrates the live cache into the new layout
(`PagedBackend.migrate_cache`) — the online form of
``examples/straggler_replan.py``.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.cache.slot_cache import PlanArrays
from repro.compression.base import CompressionConfig
from repro.compression.policies import layer_keep_bound
from repro.configs.base import ModelConfig
from repro.core.placement import HeadPlacement
from repro.core.planner import PlannerConfig, build_plan
from repro.exec.base import Executor, make_executor
from repro.obs import NULL_OBS, Obs
from repro.paging.block_pool import PoolExhausted
from repro.prefix import PrefixConfig, PrefixEntry, PrefixIndex
from repro.serving import engine as _serve
from repro.serving.engine import slotify_params
from repro.serving.request import (Request, RequestState,
                                   latency_percentiles)
from repro.serving.speculation import SpeculationConfig

if TYPE_CHECKING:  # paging.backend imports the serving package itself
    from repro.paging.backend import PagedBackend


# ---------------------------------------------------------------------------
# Row freelist
# ---------------------------------------------------------------------------


class RowFreelist:
    """Free batch rows, handed out lowest-index-first (deterministic)."""

    def __init__(self, n_rows: int):
        self.n_rows = n_rows
        self._free = sorted(range(n_rows))

    def __len__(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.n_rows - len(self._free)

    def acquire(self) -> Optional[int]:
        return self._free.pop(0) if self._free else None

    def release(self, row: int) -> None:
        if not 0 <= row < self.n_rows:
            raise ValueError(f"row {row} out of range [0, {self.n_rows})")
        if row in self._free:
            raise ValueError(f"row {row} double-freed")
        self._free.append(row)
        self._free.sort()


# ---------------------------------------------------------------------------
# Chunked prefill job (DESIGN.md §14)
# ---------------------------------------------------------------------------


@dataclass
class _ChunkJob:
    """One in-flight chunked prefill: the request sits in PREFILLING with a
    row reserved while `Scheduler.step` advances its private B=1 sub-state
    one chunk per tick.  No live-state blocks are held until the final
    chunk splices (atomic on PoolExhausted), so aborting a job only
    unwinds the row, the pin, and the request state."""

    req: Request
    row: int
    prompt: np.ndarray
    state: object  # B=1 ServeState accumulating retained chunks
    next_pos: int = 0  # absolute position of the next chunk's first token
    entry: Optional[PrefixEntry] = None  # pinned seed entry on a prefix hit
    seed_tokens: int = 0  # tokens covered by the seed (0 = cold start)
    # full-chunk boundary -> (L, H) cumulative retained lengths, snapshotted
    # as each chunk lands (the donor-side input to index registration)
    boundaries: Dict[int, np.ndarray] = field(default_factory=dict)
    last_logits: Optional[np.ndarray] = None


# ---------------------------------------------------------------------------
# Replan trigger (hysteresis)
# ---------------------------------------------------------------------------


@dataclass
class ReplanTrigger:
    """Fires when imbalance stays above ``threshold`` for a full sliding
    ``window`` of observations, at most once per ``cooldown`` steps.

    The window acts as hysteresis: one transient spike (e.g. right after an
    admission, before other rows catch up) never triggers a replan.
    """

    window: int = 8
    threshold: float = 1.25
    cooldown: int = 16
    _history: deque = field(default_factory=deque, repr=False)
    _last_fire: Optional[int] = None

    def observe(self, imbalance: float) -> None:
        """Record one per-step imbalance observation."""
        self._history.append(float(imbalance))
        while len(self._history) > self.window:
            self._history.popleft()

    def ready(self, step: int) -> bool:
        """Armed: full window above threshold + cooldown elapsed."""
        if len(self._history) < self.window:
            return False
        if any(x <= self.threshold for x in self._history):
            return False
        return (self._last_fire is None
                or step - self._last_fire >= self.cooldown)

    def fire(self, step: int) -> None:
        """Consume the armed state (called when a replan actually runs)."""
        self._last_fire = step
        self._history.clear()


# ---------------------------------------------------------------------------
# Scheduler
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class SchedulerConfig:
    # fixed decode batch width (row slots); the admission budget beyond
    # free rows is the paged pool's free blocks (per model-shard partition
    # on a mesh, DESIGN.md §10), sized by `PagingConfig`
    max_rows: int = 4
    replan_window: int = 8
    replan_threshold: float = 1.25
    replan_cooldown: int = 16
    replan_min_rows: int = 2  # don't replan a near-empty batch
    enable_replan: bool = True
    collect_logits: bool = False  # keep per-token logits on each Request


class Scheduler:
    """Admission + interleaved decode + retirement + online replanning."""

    def __init__(
        self,
        cfg: ModelConfig,
        params: dict,
        plan: HeadPlacement,
        ccfg: CompressionConfig,
        scfg: SchedulerConfig,
        planner_cfg: Optional[PlannerConfig] = None,
        dtype=jnp.float32,
        serve_params: Optional[dict] = None,
        backend: Optional["PagedBackend"] = None,
        executor: Optional[Executor] = None,
        head_importance: Optional[np.ndarray] = None,
        obs: Optional[Obs] = None,
        plan_profile: Optional[np.ndarray] = None,
        prefix_cfg: Optional[PrefixConfig] = None,
        spec_cfg: Optional[SpeculationConfig] = None,
    ):
        if cfg.is_encoder_decoder or cfg.is_vlm:
            raise NotImplementedError(
                "continuous batching supports token-prompt decoder models")
        self.cfg = cfg
        self.params = params  # original layout — kept for re-slotify on replan
        self.plan = plan
        self.pa = PlanArrays.from_plan(plan)
        self.ccfg = ccfg
        self.scfg = scfg
        self.pcfg = planner_cfg or PlannerConfig(
            mode=plan.mode, slots_per_shard=plan.slots_per_shard,
            r_max=plan.r_max, batch_cap=scfg.max_rows)
        self.dtype = dtype
        # the paged cache: block storage + admission accounting (DESIGN.md §9)
        if backend is None:
            from repro.paging.backend import PagedBackend
            backend = PagedBackend(cfg, ccfg)
        self.backend = backend
        # executor: the compiled StepFns the hot loop runs (DESIGN.md §10);
        # sp/pa are StepFn *arguments*, so replans swap placements through
        # the same executable — no retrace
        self.executor = (executor if executor is not None
                         else make_executor("local", cfg, ccfg,
                                            paging=self.backend.paging))
        # serve_params: pre-slotified weights for *this plan* (the Engine
        # facade passes its own copy so the permutation isn't paid twice)
        self.sp = (serve_params if serve_params is not None
                   else self.executor.shard_params(
                       slotify_params(params, plan, cfg)))
        # per-head weights for importance-driven policies (headkv): admission
        # prefills must compress with the same budgets the profile was
        # measured under, or realized loads drift from the plan
        self.head_importance = head_importance
        # observability (DESIGN.md §12): one shared registry/trace pair for
        # the whole stack.  Threading it into the backend and executor makes
        # pool counters and StepFn timings land in the same registry the
        # scheduler's load gauges use — backend *before* init_state, so the
        # paged backend's BlockPool is born with the live handle
        self.obs = obs if obs is not None else NULL_OBS
        if obs is not None:
            self.backend.obs = self.obs
            self.executor.obs = self.obs
        # the per-head profile the current plan was planned from (for the
        # shard_projected_load gauge); refreshed on every accepted replan
        self.plan_profile = (None if plan_profile is None
                             else np.asarray(plan_profile, np.float64))
        # born sharded: the mesh executor lays the empty state out under its
        # decode specs, so the cache never sits replicated on one device
        self.state = self.executor.shard_state(
            self.backend.init_state(self.pa, scfg.max_rows, dtype))

        # prefix cache + chunked prefill (DESIGN.md §14).  Chunking needs
        # only the dense-attention chunk StepFn; block *sharing* further
        # needs a single-partition pool (shared blocks must be valid for
        # any recipient row — a mesh pool pins blocks to the donor's
        # (shard, row-partition) device).
        self.prefix_cfg = prefix_cfg if prefix_cfg is not None \
            else PrefixConfig()
        self.prefilling: Dict[int, _ChunkJob] = {}  # row -> in-flight job
        self._chunk_ok = (self.prefix_cfg.chunk_tokens > 0
                          and cfg.family == "dense"
                          and not cfg.attention_free)
        self.prefix: Optional[PrefixIndex] = None
        pool = self.backend.pool
        if (self.prefix_cfg.enabled and self._chunk_ok
                and pool is not None and pool.n_partitions == 1):
            self.prefix = PrefixIndex(self.prefix_cfg.chunk_tokens,
                                      self.prefix_cfg.max_entries,
                                      obs=self.obs)
            self.prefix.pool = pool

        # speculative decoding (DESIGN.md §16): propose k draft tokens per
        # tick against the live paged cache, verify them in one multi-query
        # pass, commit the accepted run.  Provisional blocks come from the
        # same pool as ordinary decode growth; rejection trims them back.
        self.spec = spec_cfg if (spec_cfg is not None
                                 and spec_cfg.enabled) else None
        if self.spec is not None:
            _serve._spec_supported(cfg)  # dense decoder-only models
            d = self.spec.draft_layers
            if d > cfg.n_layers:
                raise ValueError(
                    f"speculation.draft_layers={d} exceeds the model's "
                    f"{cfg.n_layers} layers")
        # per-row adaptive speculation depth (request-scoped: seeded at
        # max_k on admission, dropped with the row)
        self._spec_depth: Dict[int, int] = {}
        # persisted straggler speed factors (set by a speed-aware replan):
        # imbalance() and every later replan score/plan against them, so an
        # auto-replan never silently reverts the mitigation
        self.shard_speeds: Optional[np.ndarray] = None
        self.queue: deque = deque()
        self.active: Dict[int, Request] = {}  # row -> request
        self.freelist = RowFreelist(scfg.max_rows)
        self.trigger = ReplanTrigger(window=scfg.replan_window,
                                     threshold=scfg.replan_threshold,
                                     cooldown=scfg.replan_cooldown)
        self.step_idx = 0
        self.n_replans = 0
        self.n_preemptions = 0
        self.n_cancellations = 0
        # graceful shutdown (DESIGN.md §13): once draining, admission stops
        # but live rows keep decoding to completion — set via drain()
        self.draining = False
        self.replan_log: List[dict] = []  # {step, imbalance_before/after}
        # per-shard realized load at the tick holding the most KV (the
        # balance a run reached; the final tick is empty once rows retire)
        self.peak_shard_load = np.zeros(plan.n_shards)
        self.finished: List[Request] = []
        if self.obs.enabled:
            # pre-register outcome series so exports show explicit zeros
            c = self.obs.metrics.counter(
                "sched_replans_total",
                help="replan attempts by outcome (accepted replans migrated "
                     "the live cache; rejected left state untouched)")
            c.inc(0, outcome="accepted")
            c.inc(0, outcome="rejected")
            self._sample_plan_metrics()

    # ---- phases and token stamps (DESIGN.md §12) ---------------------------

    @contextmanager
    def _phase(self, phase: str, **args):
        """One phase of the tick: a ``sched.<phase>`` span (ring and any
        running profiler capture) and a ``sched_phase_s{phase=}`` sample."""
        t0 = time.perf_counter()
        with self.obs.trace.span("sched." + phase, **args):
            yield
        self.obs.metrics.histogram(
            "sched_phase_s",
            help="host seconds per scheduler phase (phase=step is the whole "
                 "tick; the others nest inside it)",
        ).observe(time.perf_counter() - t0, phase=phase)

    def _stamp_tokens(self, req: Request, n: int, t: float) -> None:
        """``n`` tokens of ``req`` reached the host at ``t``: one ``itl_s``
        sample per gap (a multi-token commit's later gaps are 0, as its
        client sees them)."""
        h = self.obs.metrics.histogram(
            "itl_s", help="inter-token gap, seconds: one sample per pair of "
                          "consecutive tokens of a request (per decode step "
                          "in one-shot mode)")
        for i in range(n):
            gap = t - req.t_last if i == 0 else 0.0
            req.itl_gaps.append(gap)
            h.observe(gap)
        req.t_last = t

    def _first_token(self, req: Request, first: int) -> None:
        """The first token is on the host: stamp it, count the admission."""
        req.generated.append(first)
        req.first_token_step = self.step_idx
        req.t_first = req.t_last = time.perf_counter()
        self.obs.metrics.counter(
            "sched_admissions_total",
            help="requests admitted (prefilled + spliced)").inc()
        ttft = req.ttft_seconds()
        if ttft is not None:
            self.obs.metrics.histogram(
                "ttft_s", help="time to first token, seconds: submit to the "
                               "first token on the host (queue wait + "
                               "admission; the prefill alone in one-shot "
                               "mode)").observe(ttft)

    # ---- engine plumbing ---------------------------------------------------

    def _decode(self, state, active):
        """One decode tick through the executor's StepFn."""
        return self.executor.decode(self.sp, state, self.pa,
                                    state.last_tokens, active=active)

    # ---- speculative decoding (DESIGN.md §16) ------------------------------

    def _spec_depths(self) -> np.ndarray:
        """(max_rows,) speculation depth for this tick: the per-request
        adaptive depth clamped by the remaining token budget (a row never
        proposes past its own ``max_new_tokens``) and by cache headroom
        (an at-capacity row degrades to q_len = 1, i.e. plain decode)."""
        depth = np.zeros(self.scfg.max_rows, np.int32)
        lens = (np.asarray(self.state.cache.lengths)
                if self.state.cache is not None else None)
        cap = self.backend.capacity
        for row, req in self.active.items():
            want = self._spec_depth.setdefault(row, self.spec.max_k)
            remaining = req.max_new_tokens - req.n_generated
            headroom = cap - (int(lens[:, :, row].max())
                              if lens is not None else 0)
            depth[row] = max(0, min(want, remaining - 1, headroom - 1))
        return depth

    def _decode_tick_speculative(self, events: dict) -> None:
        """One speculative tick: propose up to k draft tokens per row, one
        multi-query verify pass, commit the accepted run (1..k+1 tokens).

        Provisional cache entries are appended by propose/verify through the
        ordinary block-pool path (`prepare_decode(n_tokens=...)` reserves
        them up front, preempting if the pool is dry); after verify,
        `trim_rows` returns every block past the committed lengths to the
        pool — the rollback side of the trial-commit.  TTFT is untouched
        (stamped at admission); ITL stays honest because a multi-token
        commit reaches the host, and the client, as one burst: one real
        gap, then zero gaps (`_stamp_tokens`)."""
        spec = self.spec
        d = spec.draft_layers if spec.draft_layers > 0 else self.cfg.n_layers
        with self._phase("prepare_decode"):
            depth = self._spec_depths()
            self._prepare_decode(n_tokens=int(depth.max()) + 1)
        if not self.active:  # everything got preempted reserving blocks
            return
        q_lens = jnp.asarray(depth + 1, jnp.int32)
        mask = self.active_mask()
        with self._phase("decode", rows=len(self.active),
                         spec_max_depth=int(depth.max())):
            st, props = self.executor.propose(
                self.sp, self.state, self.pa, jnp.asarray(depth),
                active=mask, draft_layers=d, max_k=spec.max_k)
            tokens = jnp.concatenate(
                [st.last_tokens[:, None], jnp.asarray(props)], axis=1)
            st, g, n_commit, logits = self.executor.verify(
                self.sp, st, self.pa, tokens, q_lens,
                active=mask, draft_layers=d)
        with self._phase("readout"):
            self._commit_speculative(st, g, n_commit, logits, depth)
        for row in sorted(self.active):
            req = self.active[row]
            if self._done(req):
                self._retire(req)
                events["finished"].append(req.req_id)

    def _commit_speculative(self, st, g, n_commit, logits,
                            depth: np.ndarray) -> None:
        """Trim the rejected provisional entries, read the verified tokens
        back to the host and commit each row's accepted run."""
        spec = self.spec
        self.state = self.backend.trim_rows(st, sorted(self.active))
        g_np, nc = np.asarray(g), np.asarray(n_commit)
        logits_np = np.asarray(logits) if self.scfg.collect_logits else None
        now = time.perf_counter()
        tick_proposed = tick_accepted = 0
        for row in sorted(self.active):
            req = self.active[row]
            n, prop = int(nc[row]), int(depth[row])
            req.spec_proposed += prop
            req.spec_accepted += max(0, n - 1)
            tick_proposed += prop
            tick_accepted += max(0, n - 1)
            # commit the accepted run, truncating at EOS / max_new_tokens
            # (the cache may hold a few tokens past the cut; the row is
            # retired right after, which frees them with the row)
            n0 = req.n_generated
            for i in range(n):
                req.generated.append(int(g_np[row, i]))
                if logits_np is not None:
                    req.logits.append(logits_np[row, i])
                if self._done(req):
                    break
            self._stamp_tokens(req, req.n_generated - n0, now)
            if spec.adaptive and prop > 0:
                alpha = (n - 1) / prop
                want = self._spec_depth[row]
                if alpha < spec.low_acceptance:
                    self._spec_depth[row] = max(spec.min_k, want - 1)
                elif alpha >= spec.high_acceptance:
                    self._spec_depth[row] = min(spec.max_k, want + 1)
        if self.obs.enabled:
            m = self.obs.metrics
            m.counter("spec_proposed_total",
                      help="draft tokens proposed by speculative decode"
                      ).inc(tick_proposed)
            m.counter("spec_accepted_total",
                      help="draft tokens accepted by the verify pass"
                      ).inc(tick_accepted)
            depths = [self._spec_depth[r] for r in self.active]
            m.gauge("spec_depth",
                    help="mean adaptive speculation depth over live rows"
                    ).set(float(np.mean(depths)))

    # ---- load accounting ---------------------------------------------------

    def live_tokens(self) -> int:
        """Σ retained lengths over the whole live cache (all layers/slots)."""
        if self.state.cache is None:
            return 0
        return int(np.asarray(self.state.cache.lengths).sum())

    def per_shard_load(self) -> np.ndarray:
        """(n_shards,) realized Σ lengths per shard — the Eq. 4 observable."""
        S_per = self.plan.slots_per_shard
        if self.state.cache is None:
            return np.zeros(self.plan.n_shards)
        lens = np.asarray(self.state.cache.lengths)  # (L, S, B)
        per_slot = lens.sum(axis=(0, 2))  # (S,)
        return per_slot.reshape(self.plan.n_shards, S_per).sum(axis=1)

    def _imbalance_from(self, load: np.ndarray) -> float:
        """max/mean of an already-computed per-shard load vector (the step
        loop computes the load once and feeds both this and the gauges)."""
        if self.shard_speeds is not None:
            load = load / self.shard_speeds
        mean = load.mean()
        return float(load.max() / mean) if mean > 0 else 1.0

    def imbalance(self) -> float:
        """max/mean per-shard realized load (1.0 = perfectly fair); under
        persisted ``shard_speeds`` it is the *time* imbalance load/speed."""
        return self._imbalance_from(self.per_shard_load())

    # ---- observability sampling (DESIGN.md §12) ----------------------------

    def _sample_plan_metrics(self) -> None:
        """Gauge the *projected* per-shard load of the current plan under
        the profile it was planned from — the planner's promise, against
        which ``shard_load_tokens`` shows the realized truth."""
        if self.plan_profile is None:
            return
        g = self.obs.metrics.gauge(
            "shard_projected_load",
            help="planner-projected per-shard load of the active placement "
                 "under the profile it was planned from")
        for s, v in enumerate(self.plan.per_shard_load(self.plan_profile)):
            g.set(float(v), shard=str(s))

    def _sample_step_metrics(self, load: np.ndarray, imb: float) -> None:
        """Per-tick gauges (host-side; called only when obs is on)."""
        m = self.obs.metrics
        g = m.gauge("shard_load_tokens",
                    help="realized Σ retained KV tokens per model shard "
                         "(the paper's Eq. 4 observable)")
        for s, v in enumerate(load):
            g.set(float(v), shard=str(s))
        m.gauge("sched_imbalance",
                help="max/mean per-shard realized load (1.0 = fair); "
                     "speed-normalized under persisted shard_speeds"
                ).set(imb)
        m.gauge("sched_active_rows",
                help="batch rows holding a live request").set(
            len(self.active))
        m.gauge("sched_queue_depth",
                help="requests waiting in the FCFS queue").set(
            len(self.queue))
        m.gauge("sched_prefilling_rows",
                help="rows held by in-flight chunked prefills "
                     "(DESIGN.md §14)").set(len(self.prefilling))
        if self.prefix is not None:
            st = self.prefix.stats()
            m.gauge("prefix_entries",
                    help="prompt-prefix boundaries held by the index").set(
                st["entries"])
            m.gauge("prefix_shared_blocks",
                    help="pool blocks referenced by prefix entries").set(
                st["blocks_held"])
            # bytes the pool did NOT have to duplicate: every reference
            # beyond the first on an allocated block is a block of KV the
            # sharing recipients would otherwise each hold privately
            pool = self.backend.pool
            extra = int(np.maximum(pool.refcount - 1, 0).sum())
            c = self.state.cache
            if c is not None:
                blk_bytes = (2 * c.k_pool.shape[2] * c.k_pool.shape[3]
                             * c.k_pool.dtype.itemsize)
                m.gauge("prefix_bytes_saved",
                        help="KV bytes deduplicated by prefix sharing "
                             "(Σ (refcount−1) · block bytes)").set(
                    extra * blk_bytes)
        self.backend.sample_metrics(self.state)
        pe = self.obs.cfg.print_every
        if pe > 0 and self.step_idx % pe == 0:
            print(f"[obs] step={self.step_idx} active={len(self.active)} "
                  f"queued={len(self.queue)} finished={len(self.finished)} "
                  f"imbalance={imb:.3f} preemptions={self.n_preemptions} "
                  f"replans={self.n_replans}", flush=True)

    def realized_profile(self) -> np.ndarray:
        """(L, H) mean retained length per head over *active* rows.

        Replicas of one head own disjoint rows, so summing ``lengths`` over
        the head's slots recovers each row's full per-head length.
        """
        lens = np.asarray(self.state.cache.lengths)  # (L, S, B)
        sh = np.asarray(self.pa.slot_head)  # (L, S)
        L, S, B = lens.shape
        H = self.plan.n_heads
        rows = sorted(self.active)
        if not rows:
            raise RuntimeError("no active rows to profile")
        prof = np.zeros((L, H), dtype=np.float64)
        for h in range(H):
            contrib = np.where(sh[:, :, None] == h, lens, 0)  # (L, S, B)
            prof[:, h] = contrib[:, :, rows].sum(axis=1).mean(axis=1)
        return np.maximum(prof, 1.0)

    # ---- admission ---------------------------------------------------------

    def submit(self, req: Request) -> None:
        # fail fast on a request that could never be admitted: FCFS would
        # head-of-line block behind it until max_steps with no diagnostic
        reason = self.backend.never_fits(req)
        if reason is not None:
            raise ValueError(
                f"request {req.req_id} can never be admitted: {reason}")
        req.state = RequestState.QUEUED
        if req.arrival_time is None:
            req.arrival_time = time.time()
        if req.t_submit is None:
            req.t_submit = time.perf_counter()
        self.queue.append(req)

    def _estimated_cost(self, req: Request) -> int:
        """Projected worst-case blocks (the per-policy keep bounds plus the
        request's decode growth)."""
        return self.backend.request_cost(req)

    def admissible(self, req: Request) -> bool:
        if len(self.freelist) == 0:
            return False
        # in-flight chunked prefills hold rows but no blocks until their
        # final-chunk splice: charge them as pending so admission does not
        # promise the same free blocks twice (DESIGN.md §14)
        pending = [j.req for j in self.prefilling.values()]
        return self.backend.admissible(self.state, req, pending=pending)

    def _admit(self, req: Request) -> Optional[int]:
        """Prefill + splice; returns the row, or None when the cache
        backend ran out of memory even after preempting (caller requeues)."""
        row = self.freelist.acquire()
        assert row is not None
        with self._phase("admit", req=req.req_id, prompt_len=req.prompt_len,
                         row=row):
            req.state = RequestState.PREFILLING
            req.row = row
            req.admit_step = self.step_idx
            req.t_admit = time.perf_counter()
            batch = {"tokens": jnp.asarray(req.prompt, jnp.int32)[None, :]}
            sub, logits, _lens = self.executor.prefill(
                self.sp, batch, self.pa, rows=jnp.asarray([row]),
                head_importance=self.head_importance)
            try:
                with self._phase("splice", req=req.req_id):
                    self.state = self.backend.splice(self.state, sub,
                                                     np.asarray([row]))
            except PoolExhausted:
                # admission never preempts (only decode growth does —
                # evicting older in-flight work to admit newer would invert
                # FCFS): undo and let the caller requeue.  Unreachable while
                # admissible()'s charge dominates the splice need; this
                # guards a looser admission estimate.
                self.freelist.release(row)
                req.state = RequestState.QUEUED
                req.row = None
                req.admit_step = req.t_admit = None
                return None
            with self._phase("first_token", req=req.req_id):
                first = int(np.asarray(sub.last_tokens)[0])
            self._first_token(req, first)
            if self.scfg.collect_logits:
                req.logits = [np.asarray(logits[0])]
            req.state = RequestState.DECODING
            self.active[row] = req
            if self._done(req):
                self._retire(req)
            return row

    def _done(self, req: Request) -> bool:
        if req.n_generated >= req.max_new_tokens:
            return True
        return req.eos_id is not None and req.generated[-1] == req.eos_id

    # ---- chunked prefill + prefix sharing (DESIGN.md §14) ------------------

    def _should_chunk(self, req: Request) -> bool:
        """Prompts longer than one chunk go through the chunked path; a
        prompt that fits in a single chunk gains nothing from it."""
        return (self._chunk_ok
                and req.prompt_len > self.prefix_cfg.chunk_tokens)

    def _stamp_prefix_hit(self, req: Request) -> Optional[PrefixEntry]:
        """Look up the longest shared prefix and stamp the request's
        admission discount (`prefix_shared_blocks`); returns the entry so
        the admission loop can seed from it without a second lookup."""
        if self.prefix is None or not self._should_chunk(req):
            req.prefix_shared_blocks = None
            return None
        entry = self.prefix.lookup(np.asarray(req.prompt, np.int32))
        if entry is None:
            req.prefix_shared_blocks = None
            req.prefix_hit_tokens = 0
            return None
        req.prefix_hit_tokens = entry.tokens
        bs = self.backend.block_size
        full = np.asarray(entry.lengths) // bs  # (L, H) full blocks per head
        req.prefix_shared_blocks = full.sum(axis=1).astype(np.int64)
        return entry

    def _head_slot_table(self, entry: PrefixEntry, row: int):
        """Map an entry's head-indexed blocks onto the slots owning each
        head *for this row* → ((L, S, 1, M) ids, (L, S, 1) lengths).
        Replicas of one head serve disjoint rows, so donor and recipient
        may home the same head in different slots; block content is
        head-level, so rehoming is purely a table rewrite."""
        sh = np.asarray(self.pa.slot_head)
        ri = np.asarray(self.pa.replica_idx)
        rc = np.asarray(self.pa.replica_count)
        own = (sh >= 0) & ((row % np.maximum(rc, 1)) == ri)  # (L, S)
        L, S = sh.shape
        M = self.backend.max_blocks
        tbl = np.zeros((L, S, 1, M), np.int32)
        lens = np.zeros((L, S, 1), np.int32)
        n = min(entry.table.shape[2], M)
        for l, s in zip(*np.nonzero(own)):
            h = int(sh[l, s])
            tbl[l, s, 0, :n] = entry.table[l, h, :n]
            lens[l, s, 0] = entry.lengths[l, h]
        return tbl, lens

    def _seed_from_entry(self, entry: PrefixEntry, row: int):
        """Materialize a matched prefix into a fresh B=1 sub-state.

        The entry's blocks are viewed through a synthetic one-row table and
        gathered with `paged_to_slot` — a deep copy, so the shared blocks
        are read, never aliased; the final splice maps the same full blocks
        back into the row's stored table without rewriting them."""
        from repro.paging.paged_cache import PagedCache, paged_to_slot
        live = self.state.cache
        tbl, lens = self._head_slot_table(entry, row)
        view = PagedCache(k_pool=live.k_pool, v_pool=live.v_pool,
                          pos_pool=live.pos_pool,
                          block_table=jnp.asarray(tbl),
                          lengths=jnp.asarray(lens),
                          positions=jnp.full((1,), entry.tokens, jnp.int32))
        slot = paged_to_slot(view, self.backend.capacity)
        return _serve.init_serve_state(self.cfg, self.pa, 1, self.ccfg,
                                       dtype=self.dtype, cache=slot)

    def _start_chunked(self, req: Request,
                       entry: Optional[PrefixEntry]) -> int:
        """Begin a chunked prefill: reserve the row, seed from the matched
        prefix boundary (if any), and leave the job in ``prefilling`` —
        `step` advances it one chunk per tick, so decode ticks for live
        rows interleave instead of stalling behind a long prompt."""
        row = self.freelist.acquire()
        assert row is not None
        req.state = RequestState.PREFILLING
        req.row = row
        req.admit_step = self.step_idx
        req.t_admit = time.perf_counter()
        prompt = np.asarray(req.prompt, np.int32)
        if entry is not None:
            sub = self._seed_from_entry(entry, row)
            self.prefix.pin(entry)  # immune to eviction while we read it
            start = entry.tokens
        else:
            sub = _serve.init_serve_state(self.cfg, self.pa, 1, self.ccfg,
                                          dtype=self.dtype)
            start = 0
        self.prefilling[row] = _ChunkJob(req=req, row=row, prompt=prompt,
                                         state=sub, next_pos=start,
                                         entry=entry, seed_tokens=start)
        return row

    def _chunk_quota(self, T: int, n: int) -> np.ndarray:
        """(L,) per-head keep cap for an ``n``-token chunk of a ``T``-token
        prompt: the monolithic per-head bound prorated by the chunk's share
        of the prompt (floor 1, so every chunk may retain something).  The
        union over chunks then tracks the monolithic budget to within one
        block of ceil slack per chunk — exact for policy "none"."""
        H, L = self.cfg.n_kv_heads, self.cfg.n_layers
        full = np.asarray([layer_keep_bound(self.ccfg.policy, self.ccfg,
                                            T, H, l, L) // H
                           for l in range(L)], np.int64)
        return np.maximum(1, np.ceil(full * n / T)).astype(np.int32)

    def _run_chunks(self, events: dict) -> None:
        """Advance every in-flight chunked prefill by exactly one chunk —
        the §14 interleaving contract: live-row decode latency is bounded
        by one chunk plus one decode step, never a whole prefill."""
        Ck = self.prefix_cfg.chunk_tokens
        for row in sorted(self.prefilling):
            job = self.prefilling[row]
            T = int(job.prompt.shape[0])
            n = min(Ck, T - job.next_pos)
            chunk = np.zeros((1, Ck), np.int32)
            chunk[0, :n] = job.prompt[job.next_pos:job.next_pos + n]
            with self._phase("prefill_chunk", req=job.req.req_id,
                             start=job.next_pos, tokens=n):
                job.state, logits, lens = self.executor.prefill_chunk(
                    self.sp, chunk, self.pa, job.state,
                    rows=np.asarray([row], np.int32),
                    start=np.asarray([job.next_pos], np.int32),
                    valid=np.asarray([n], np.int32),
                    quota=self._chunk_quota(T, n),
                    head_importance=self.head_importance)
            job.next_pos += n
            if n == Ck:  # full-chunk boundary: snapshot for registration
                job.boundaries[job.next_pos] = np.asarray(lens)[:, :, 0]
            if job.next_pos >= T:
                job.last_logits = np.asarray(logits)
                self._finish_chunked(job, events)

    def _finish_chunked(self, job: _ChunkJob, events: dict) -> None:
        """Final chunk landed: splice the sub-state into the live batch
        (sharing the seed's full blocks), stamp the first token — TTFT
        spans submit → here, across every chunk — and register this
        prompt's boundaries as new prefix entries."""
        req, row = job.req, job.row
        shared = None
        if job.entry is not None:
            shared, _ = self._head_slot_table(job.entry, row)
        while True:
            try:
                with self._phase("splice", req=req.req_id):
                    if shared is not None:
                        self.state = self.backend.splice(
                            self.state, job.state, np.asarray([row]),
                            shared_blocks=shared)
                    else:
                        self.state = self.backend.splice(
                            self.state, job.state, np.asarray([row]))
                break
            except PoolExhausted:
                # cheapest memory first: entries held only by the index
                if self.prefix is not None and self.prefix.evict_lru():
                    continue
                self._abort_job(job, requeue=True)
                return
        del self.prefilling[row]
        if job.entry is not None:
            self.prefix.unpin(job.entry)
        with self._phase("first_token", req=req.req_id):
            first = int(np.asarray(job.state.last_tokens)[0])
        self._first_token(req, first)
        if self.scfg.collect_logits:
            req.logits = [job.last_logits[0]]
        req.state = RequestState.DECODING
        self.active[row] = req
        # register before any retirement: entries take their own refs off
        # the row's table, which release_rows would zero
        self._register_boundaries(job)
        if self._done(req):
            self._retire(req)
            events["finished"].append(req.req_id)

    def _abort_job(self, job: _ChunkJob, requeue: bool) -> None:
        """Unwind a job whose splice never landed: no blocks are held, so
        only the row, the pin, and the request state roll back."""
        del self.prefilling[job.row]
        if job.entry is not None:
            self.prefix.unpin(job.entry)
        self.freelist.release(job.row)
        req = job.req
        req.row = None
        if requeue:
            req.state = RequestState.QUEUED
            req.admit_step = req.t_admit = None
            req.generated = []
            req.prefix_shared_blocks = None
            req.prefix_hit_tokens = 0
            self.queue.appendleft(req)

    def _register_boundaries(self, job: _ChunkJob) -> None:
        """Donor side of the index: adopt this prompt's full-chunk
        boundaries.  Each entry stores *full blocks only* with lengths
        truncated to the block-aligned prefix — the partial tail block is
        private to the row (its later appends would leak into sharers);
        the dropped remainder is re-copied from the seed gather for future
        hits, trading a few tokens of retained context for safe sharing."""
        if self.prefix is None:
            return
        bs = self.backend.block_size
        sh = np.asarray(self.pa.slot_head)
        ri = np.asarray(self.pa.replica_idx)
        rc = np.asarray(self.pa.replica_count)
        row = job.row
        own = (sh >= 0) & ((row % np.maximum(rc, 1)) == ri)
        L, S = sh.shape
        H, M = self.cfg.n_kv_heads, self.backend.max_blocks
        for t_j, key in self.prefix.chain_keys(job.prompt):
            if t_j <= job.seed_tokens or t_j not in job.boundaries:
                continue
            lens_h = job.boundaries[t_j]  # (L, H) retained at the boundary
            full = (lens_h // bs) * bs  # block-aligned shareable prefix
            if not full.any():
                continue
            table = np.zeros((L, H, M), np.int32)
            for l, s in zip(*np.nonzero(own)):
                h = int(sh[l, s])
                nb = int(full[l, h]) // bs
                if nb:
                    table[l, h, :nb] = self.backend.table[l, s, row, :nb]
            self.prefix.register(key, t_j, table, full.astype(np.int32))

    def prefix_stats(self) -> dict:
        """Index counters + entry census (empty dict when sharing is off)."""
        return {} if self.prefix is None else self.prefix.stats()

    def _release_row(self, req: Request) -> None:
        """Free a live request's row and its blocks — shared by
        retirement, cancellation, and preemption."""
        row = req.row
        self.state = self.backend.release_rows(self.state, np.asarray([row]))
        del self.active[row]
        self.freelist.release(row)
        self._spec_depth.pop(row, None)

    def _retire(self, req: Request) -> None:
        with self._phase("retire", req=req.req_id,
                         n_generated=req.n_generated):
            self._release_row(req)
            req.state = RequestState.FINISHED
            req.finish_step = self.step_idx
            req.finish_time = time.time()
            req.row = None
            self.finished.append(req)
            m = self.obs.metrics
            m.counter("sched_retirements_total",
                      help="requests retired (EOS or max-new-tokens)").inc()
            if req.arrival_time is not None:
                m.histogram("e2e_s", help="end-to-end request latency"
                            ).observe(req.finish_time - req.arrival_time)
            if req.spec_proposed > 0:
                m.histogram("spec_acceptance",
                            help="per-request draft acceptance rate "
                                 "(accepted / proposed over the lifetime)"
                            ).observe(req.spec_accepted / req.spec_proposed)

    # ---- cancellation + draining (DESIGN.md §13) ---------------------------

    def cancel(self, req_id: int) -> bool:
        """Retire a request early (client disconnect, deadline shed).

        An in-flight row is released exactly like a normal retirement —
        its blocks go back to the pool (refcounts decremented) — so
        cancellation conserves pool capacity.  A still-queued request is
        simply removed.  The request lands in ``finished`` with state
        CANCELLED so trace drivers and streams observe a terminal state.
        Returns False when the id is unknown or already finished.
        """
        req = next((r for r in self.active.values()
                    if r.req_id == req_id), None)
        if req is not None:
            self._release_row(req)
        else:
            job = next((j for j in self.prefilling.values()
                        if j.req.req_id == req_id), None)
            if job is not None:  # mid-chunked-prefill: no blocks held yet
                req = job.req
                self._abort_job(job, requeue=False)
            else:
                req = next((r for r in self.queue
                            if r.req_id == req_id), None)
                if req is None:
                    return False
                self.queue.remove(req)
        req.state = RequestState.CANCELLED
        req.finish_step = self.step_idx
        req.finish_time = time.time()
        req.row = None
        self.finished.append(req)
        self.n_cancellations += 1
        self.obs.metrics.counter(
            "sched_cancellations_total",
            help="requests retired early (client disconnect / deadline "
                 "shed); rows and blocks are released like a normal "
                 "retirement").inc()
        self.obs.trace.instant("cancel", req=req_id)
        return True

    def drain(self) -> None:
        """Graceful shutdown: stop admitting (queued requests stay queued
        for the driver to cancel or report), finish decoding live rows.
        `run` cancels the queue and sheds unsubmitted arrivals itself."""
        self.draining = True

    # ---- preemption (DESIGN.md §9) -----------------------------------------

    def _evict(self, victim: Request) -> None:
        """Preempt one live request back to QUEUED (recompute policy),
        freeing its rows/blocks.  Re-queued at the front: among equal
        priorities it is oldest by FCFS."""
        self._release_row(victim)
        victim.reset_for_requeue()
        self.queue.appendleft(victim)
        self.n_preemptions += 1
        self.obs.metrics.counter(
            "sched_preemptions_total",
            help="evictions back to QUEUED (pool exhaustion or priority "
                 "pressure), lowest-priority-youngest-first").inc()
        self.obs.trace.instant("preempt", req=victim.req_id,
                               priority=victim.priority)

    def _preempt_one(self) -> bool:
        """Evict the least-important, then youngest, active request.
        Victim choice protects invested work within a priority class: the
        most recently admitted request has the least progress to replay;
        across classes, low-priority (higher index) rows go first — the
        frontend's SLO enforcement lever (DESIGN.md §13).  Returns False
        when there is nothing (left) to evict."""
        victims = list(self.active.values())
        if not victims:
            return False
        self._evict(max(victims,
                        key=lambda r: (r.priority, r.admit_step, r.req_id)))
        return True

    def preempt_lower_priority(self, than: int) -> bool:
        """Evict one active request whose priority class is strictly less
        urgent than ``than`` (priority index greater), if any — called by
        the frontend when a high-priority request is starving behind a
        full batch.  Returns False when no such victim exists."""
        victims = [r for r in self.active.values() if r.priority > than]
        if not victims:
            return False
        self._evict(max(victims,
                        key=lambda r: (r.priority, r.admit_step, r.req_id)))
        return True

    def _prepare_decode(self, n_tokens: int = 1) -> None:
        """Backend pre-tick hook with preemption: guarantee every active
        row's next ``n_tokens`` appends have backing storage, evicting the
        youngest requests while the pool is dry."""
        while True:
            try:
                self.state = self.backend.prepare_decode(
                    self.state, sorted(self.active), n_tokens=n_tokens)
                return
            except PoolExhausted as e:
                # reclaim index-only prefix entries before evicting live
                # work — dropping a cache entry costs a future recompute,
                # preempting a request costs a guaranteed one (§14)
                if self.prefix is not None and self.prefix.evict_lru():
                    continue
                if not self._preempt_one():
                    raise RuntimeError(
                        "cache pool exhausted with nothing left to preempt "
                        "— the pool is too small for a single request "
                        f"({e}); raise PagingConfig.n_blocks") from e

    # ---- replanning --------------------------------------------------------

    def should_replan(self) -> bool:
        """Trigger armed (full window above threshold + cooldown elapsed) and
        enough live rows for the realized profile to be meaningful."""
        return (self.scfg.enable_replan
                and len(self.active) >= self.scfg.replan_min_rows
                and not self.prefilling  # sub-states pin the current plan
                and self.trigger.ready(self.step_idx))

    @staticmethod
    def _imbalance_of(lengths: np.ndarray, n_shards: int,
                      slots_per_shard: int,
                      shard_speeds: Optional[Sequence[float]] = None) -> float:
        """max/mean per-shard load; with ``shard_speeds`` the *time*
        imbalance load_j / speed_j (what a straggler-aware plan optimizes)."""
        per_slot = np.asarray(lengths).sum(axis=(0, 2))
        load = per_slot.reshape(n_shards, slots_per_shard).sum(axis=1)
        if shard_speeds is not None:
            load = load / np.asarray(shard_speeds, float)
        mean = load.mean()
        return float(load.max() / mean) if mean > 0 else 1.0

    def replan(self, profile: Optional[np.ndarray] = None,
               shard_speeds: Optional[Sequence[float]] = None) -> dict:
        """Rebuild the placement and migrate the live cache + weights into
        the new slot layout if it actually helps.

        Default: plan from the realized per-head profile of the active rows.
        ``profile`` overrides the planning input; ``shard_speeds`` plans
        against heterogeneous shard speeds (straggler mitigation,
        DESIGN.md §6) — both reachable live via ``Engine.replan``.  Passed
        speeds persist: subsequent trigger-fired replans keep planning and
        scoring against them (pass ``shard_speeds=np.ones(n_shards)`` to
        clear).

        The planner optimizes the *mean-over-rows* per-head profile, which at
        small row counts can mispredict the row-granular replica split — so
        the candidate layout is scored on the realized lengths post-migration
        and rejected (no state change, cooldown still consumed) unless it
        strictly reduces the per-shard imbalance.
        """
        with self._phase("replan"):
            event = self._replan_impl(profile, shard_speeds)
        # outcome counter is the single source of truth for replan counts
        # (benchmarks read it instead of re-tallying replan_log)
        self.obs.metrics.counter("sched_replans_total").inc(
            outcome="accepted" if event["accepted"] else "rejected")
        return event

    def _replan_impl(self, profile: Optional[np.ndarray],
                     shard_speeds: Optional[Sequence[float]]) -> dict:
        if shard_speeds is not None:
            self.shard_speeds = np.asarray(shard_speeds, float)
        speeds = self.shard_speeds
        if self.prefilling:
            # chunked sub-states are laid out under the current plan and
            # prefix seeds reference the current pool: migrating under them
            # would corrupt both.  Reject; the trigger path never gets here
            # (should_replan), only direct Engine.replan calls can.
            before = self.imbalance()
            event = {"step": self.step_idx, "imbalance_before": before,
                     "imbalance_after": before, "accepted": False,
                     "rejected_reason": "chunked prefills in flight"}
            self.replan_log.append(event)
            return event
        # before/after under the same metric: speed-normalized when planning
        # against heterogeneous shards, raw otherwise
        before = self._imbalance_of(np.asarray(self.state.cache.lengths),
                                    self.plan.n_shards,
                                    self.plan.slots_per_shard, speeds)
        profile = (self.realized_profile() if profile is None
                   else np.asarray(profile, np.float64))
        new_plan = build_plan(profile, self.plan.n_shards, self.pcfg,
                              shard_speeds=speeds)
        new_pa = PlanArrays.from_plan(new_plan)
        try:
            cand_lengths, commit = self.backend.migrate_cache(
                self.state.cache, self.pa, new_pa,
                active_rows=sorted(self.active))
        except PoolExhausted as e:
            # block rounding under the new ownership split doesn't fit the
            # pool: reject without touching state (cooldown still consumed)
            event = {"step": self.step_idx, "imbalance_before": before,
                     "imbalance_after": before, "accepted": False,
                     "rejected_reason": f"pool exhausted: {e}"}
            self.replan_log.append(event)
            return event
        after = self._imbalance_of(np.asarray(cand_lengths),
                                   new_plan.n_shards,
                                   new_plan.slots_per_shard, speeds)
        event = {"step": self.step_idx, "imbalance_before": before,
                 "imbalance_after": after, "accepted": after < before - 1e-9}
        if not event["accepted"]:
            event["imbalance_after"] = before
            self.replan_log.append(event)
            return event
        self.state = dataclasses.replace(self.state, cache=commit())
        self.plan, self.pa = new_plan, new_pa
        self.sp = self.executor.shard_params(
            slotify_params(self.params, new_plan, self.cfg))
        if self.prefix is not None:
            # the backend rebuilt its pool from live tables only (shared
            # rows were deep-copied private): the index's references died
            # with the old pool, so drop entries without decref'ing and
            # rebind to the new pool — sharing re-warms from new admits
            self.prefix.flush(decref=False)
            self.prefix.pool = self.backend.pool
        # no StepFn rebuild: sp/pa are executor arguments, shapes unchanged
        self.n_replans += 1
        self.replan_log.append(event)
        if self.obs.enabled:
            # the new plan's promise, from the profile it was planned from
            self.plan_profile = profile
            self._sample_plan_metrics()
        return event

    # ---- main loop ---------------------------------------------------------

    def active_mask(self) -> jnp.ndarray:
        m = np.zeros(self.scfg.max_rows, dtype=bool)
        for row in self.active:
            m[row] = True
        return jnp.asarray(m)

    def step(self) -> dict:
        """One scheduler tick: admit → decode → retire → (maybe) replan.

        Every phase runs under a ``sched.<phase>`` span and feeds
        ``sched_phase_s{phase=}`` (DESIGN.md §12): ``step`` the whole tick;
        inside it ``admit`` (with the executor's prefill, ``splice`` and
        ``first_token``), ``admit_chunked``, ``prefill_chunk``,
        ``prepare_decode``, ``decode``, ``readout``, ``retire``, ``load``
        and ``replan``."""
        with self._phase("step", step=self.step_idx):
            return self._step()

    def _step(self) -> dict:
        events: dict = {"step": self.step_idx, "admitted": [], "finished": [],
                        "preempted": 0, "replanned": False}
        preempted_before = self.n_preemptions
        # admission: fill free rows from the queue, best (priority, FIFO)
        # first — with uniform priorities this is exactly the historical
        # strict FCFS (including preempted victims re-admitting first via
        # appendleft); a more urgent class jumps the line.  Head-of-line
        # blocking is per pick: the chosen request gates admission, so a
        # large urgent request is never starved by smaller later ones.
        # Draining (graceful shutdown) stops admission entirely.
        while self.queue and not self.draining:
            i = min(range(len(self.queue)),
                    key=lambda j: (self.queue[j].priority, j))
            req = self.queue[i]
            # prefix lookup before the admissibility check: a hit discounts
            # the shared blocks from the request's charge (DESIGN.md §14)
            entry = self._stamp_prefix_hit(req)
            if not self.admissible(req):
                break
            del self.queue[i]
            if self._should_chunk(req):
                with self._phase("admit_chunked", req=req.req_id):
                    row = self._start_chunked(req, entry)
                events["admitted"].append((req.req_id, row))
                continue
            row = self._admit(req)
            if row is None:  # backend memory dry even after preemption
                self.queue.appendleft(req)
                break
            events["admitted"].append((req.req_id, row))
            if req.is_finished:  # max_new_tokens == 1 or instant EOS
                events["finished"].append(req.req_id)
        # one chunk for each in-flight chunked prefill, then one decode
        # tick: long prompts never head-of-line-block live rows (§14)
        if self.prefilling:
            self._run_chunks(events)
        # one interleaved decode tick for every live row — speculative
        # (k draft proposals + one multi-query verify, DESIGN.md §16) when
        # configured, single-token greedy otherwise
        if self.active and self.spec is not None:
            self._decode_tick_speculative(events)
        elif self.active:
            with self._phase("prepare_decode"):
                self._prepare_decode()  # may preempt (paged pool dry)
            if self.active:
                with self._phase("decode", rows=len(self.active)):
                    self.state, logits = self._decode(self.state,
                                                      self.active_mask())
                with self._phase("readout"):
                    toks = np.asarray(self.state.last_tokens)
                    logits_np = (np.asarray(logits)
                                 if self.scfg.collect_logits else None)
                    now = time.perf_counter()
                    for row in sorted(self.active):
                        req = self.active[row]
                        req.generated.append(int(toks[row]))
                        self._stamp_tokens(req, 1, now)
                        if logits_np is not None:
                            req.logits.append(logits_np[row])
                for row in sorted(self.active):
                    req = self.active[row]
                    if self._done(req):
                        self._retire(req)
                        events["finished"].append(req.req_id)
        events["preempted"] = self.n_preemptions - preempted_before
        # load accounting + replan trigger (hysteresis inside the trigger);
        # the load vector feeds the trigger and the gauges from one compute
        with self._phase("load"):
            load = self.per_shard_load()
            if load.sum() > self.peak_shard_load.sum():
                self.peak_shard_load = load
            imb = self._imbalance_from(load)
            self.trigger.observe(imb)
            if self.obs.enabled:
                self._sample_step_metrics(load, imb)
        if self.should_replan():
            self.trigger.fire(self.step_idx)
            events["replan"] = self.replan()
            events["replanned"] = True
        self.step_idx += 1
        return events

    def run(self, requests: Sequence[Request],
            max_steps: int = 10_000) -> dict:
        """Drive a full trace: submit by ``arrival_step``, tick until every
        request is FINISHED (or ``max_steps``).  Returns summary telemetry."""
        pending = sorted(requests, key=lambda r: (r.arrival_step, r.req_id))
        n_total = len(pending)
        i = 0
        first_decode_step: Optional[int] = None
        mid_stream_admissions = 0
        t0 = time.time()
        while len(self.finished) < n_total and self.step_idx < max_steps:
            if self.draining:
                # graceful shutdown: cancel everything not yet decoding
                # (queued + unsubmitted arrivals) so the loop converges on
                # the in-flight rows alone, which decode to completion
                for req in list(self.queue):
                    self.cancel(req.req_id)
                while i < len(pending):
                    req = pending[i]
                    req.state = RequestState.CANCELLED
                    self.finished.append(req)
                    self.n_cancellations += 1
                    i += 1
                if not self.active and not self.prefilling:
                    break
            while (not self.draining and i < len(pending)
                   and pending[i].arrival_step <= self.step_idx):
                self.submit(pending[i])
                i += 1
            ev = self.step()
            if ev["admitted"] and first_decode_step is not None:
                mid_stream_admissions += len(ev["admitted"])
            if self.active or ev["finished"]:
                if first_decode_step is None:
                    first_decode_step = ev["step"]
        wall = time.time() - t0
        total_tokens = sum(r.n_generated for r in self.finished)
        summary = {
            "steps": self.step_idx,
            "wall_s": wall,
            "finished": len(self.finished),
            "total": n_total,
            "generated_tokens": total_tokens,
            "mid_stream_admissions": mid_stream_admissions,
            "replans": self.n_replans,
            "replan_log": list(self.replan_log),
            "preemptions": self.n_preemptions,
            "peak_shard_load": self.peak_shard_load.tolist(),
            "cancelled": sum(1 for r in self.finished if r.cancelled),
            "drained": self.draining,
            "latency": latency_percentiles(
                [r for r in self.finished if not r.cancelled]),
            "memory": self.backend.memory_stats(self.state),
        }
        if wall > 0:
            summary["tokens_per_s"] = total_tokens / wall
        else:
            # timer resolution can make a tiny trace's wall collapse to 0 —
            # an honest 0.0 with a note beats a division to inf
            summary["tokens_per_s"] = 0.0
            summary["tokens_per_s_note"] = "wall_too_short"
        return summary
