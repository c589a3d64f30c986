"""Cache backends: the storage strategy behind the serving engine.

A ``CacheBackend`` owns how per-(slot, row) KV is *stored* and *accounted* —
not how it is computed: prefill, the decode math, ownership, and compression
are backend-independent.  Two built-ins register here and in
``repro.paging.backend``:

- ``"slot"``  — the dense slot cache (DESIGN.md §2): every (slot, row)
  padded to static capacity ``C``.  Simple, zero bookkeeping, memory cost
  independent of realized compression.
- ``"paged"`` — the block-pool cache (DESIGN.md §9): fixed-size blocks
  allocated proportional to realized retained lengths; admission is a
  free-*block* budget and running dry preempts instead of corrupting.

Backends are registered with ``@repro.api.register_cache_backend`` and
selected by ``EngineConfig.cache_backend``; the scheduler and the `Engine`
facade call only this interface, so a third-party backend (e.g. quantized
blocks, CPU offload) plugs in without touching either.

Contract notes: state-transforming methods are *pure* on the ServeState
pytree but may mutate backend-internal host bookkeeping (allocator state);
``splice`` / ``prepare_decode`` may raise ``PoolExhausted``, which the
scheduler treats as a preemption signal; ``migrate_cache`` returns a
``(candidate_cache, commit)`` pair so a replan can be scored and *rejected*
without leaking backend bookkeeping.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import numpy as np

from repro.api.registry import register_cache_backend
from repro.cache.slot_cache import PlanArrays, migrate_cache
from repro.compression.base import CompressionConfig
from repro.compression.policies import (
    layer_keep_bound,
    projected_request_tokens,
)
from repro.configs.base import ModelConfig
from repro.obs import NULL_OBS
from repro.paging.block_pool import PagingConfig, PoolExhausted  # noqa: F401
from repro.serving import engine as _serve
from repro.serving.request import Request


class CacheBackend:
    """Interface; see module docstring for the contract.

    Budget geometry (DESIGN.md §10): ``n_shards`` is the plan's model-shard
    count, so admission can be enforced **per model shard** — the resource
    that actually runs out on a sharded mesh is one shard's memory, not the
    global sum.  ``max_live_tokens_per_shard`` is the slot backend's
    per-shard admission budget (None disables the check);
    ``pool_partitions`` / ``row_partitions`` split the paged backend's
    block pool into per-(model shard, data shard) partitions (the mesh
    executor's layout, where each partition lives on one device and its
    free list is that shard's budget).

    ``donate_state`` is the executor's flag (``ExecutorConfig``): a backend
    whose admission writes the live state in place donates it under the
    same contract as the decode StepFn.
    """

    name: str = "?"

    def __init__(self, model_cfg: ModelConfig, ccfg: CompressionConfig,
                 max_live_tokens: Optional[int] = None,
                 paging: Optional[PagingConfig] = None,
                 n_shards: int = 1,
                 max_live_tokens_per_shard: Optional[int] = None,
                 pool_partitions: int = 1,
                 row_partitions: int = 1,
                 obs=None,
                 donate_state: bool = True):
        self.cfg = model_cfg
        self.ccfg = ccfg
        self.max_live_tokens = max_live_tokens
        self.paging = paging or PagingConfig()
        self.n_shards = int(n_shards)
        self.max_live_tokens_per_shard = max_live_tokens_per_shard
        self.pool_partitions = int(pool_partitions)
        self.row_partitions = int(row_partitions)
        self.donate_state = bool(donate_state)
        # observability handle (DESIGN.md §12); NULL_OBS unless the Engine
        # facade threads its live Obs through
        self.obs = obs if obs is not None else NULL_OBS

    # ---- state lifecycle ---------------------------------------------------

    def init_state(self, pa: PlanArrays, batch: int, dtype):
        """Empty B-row ServeState in this backend's layout."""
        raise NotImplementedError

    def from_prefill(self, state, pa: PlanArrays):
        """Adopt a full-batch prefill result (one-shot mode)."""
        return state

    def splice(self, state, sub, rows):
        """Splice a prefilled slot-layout sub-state into ``rows``."""
        raise NotImplementedError

    def release_rows(self, state, rows):
        """Retire rows: clear state, reclaim backing memory."""
        raise NotImplementedError

    def prepare_decode(self, state, active: Optional[Sequence[int]],
                       n_tokens: int = 1):
        """Host hook before a decode tick: guarantee the next ``n_tokens``
        appends of every active row have backing storage (speculative
        ticks write up to k+1 tokens).  ``None`` = all rows."""
        return state

    def migrate_cache(self, cache, old_pa: PlanArrays, new_pa: PlanArrays,
                      active_rows: Optional[Sequence[int]] = None
                      ) -> Tuple[object, Callable[[], object]]:
        """Trial a re-layout under ``new_pa``.

        Returns ``(preview_lengths, commit)``: the candidate's (L, S, B)
        realized lengths — enough to score accept/reject — and a commit
        callback that materializes and returns the migrated cache (call it
        only on accept; rejected trials then never pay the full device
        re-layout).  Infeasibility (e.g. block rounding under the new
        ownership split) raises before scoring, never inside commit."""
        raise NotImplementedError

    # ---- admission accounting ----------------------------------------------

    def request_cost(self, req: Request) -> int:
        """Projected cost in backend units (tokens / blocks) — telemetry
        and fail-fast; an upper bound on what the request can ever pin."""
        raise NotImplementedError

    def admissible(self, state, req: Request,
                   pending: Sequence[Request] = ()) -> bool:
        """Do free resources cover the request's projected prefill need?

        ``pending`` are requests already accepted but not yet spliced into
        ``state`` (e.g. admitted earlier in the same frontend tick) — their
        projected charge counts against the budget too, so a burst of
        individually-admissible requests cannot jointly over-commit."""
        raise NotImplementedError

    def never_fits(self, req: Request) -> Optional[str]:
        """Reason string when the request cannot fit even an empty cache
        (fail fast at submit instead of head-of-line blocking), else None."""
        return None

    # ---- telemetry ---------------------------------------------------------

    def memory_stats(self, state) -> dict:
        raise NotImplementedError

    def sample_metrics(self, state) -> None:
        """Per-step gauge sampling hook (host-side, outside jit): record
        this backend's cache-pressure observables into ``self.obs``.  The
        scheduler calls it once per tick when observability is on; the
        default records nothing."""


@register_cache_backend("slot")
class SlotBackend(CacheBackend):
    """Dense static-capacity slot cache (the PR-1/PR-2 baseline layout).

    Admission budget is the projected live-token total.  The projection
    uses the per-policy prefill keep bounds (`layer_keep_bound`) — pool
    conservation makes imbalanced policies *much* cheaper than the old
    ``L·H·min(prompt+gen, C)`` static-capacity charge (see the audit note
    in DESIGN.md §7).
    """

    name = "slot"

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.pa: Optional[PlanArrays] = None  # for per-shard projection

    def init_state(self, pa, batch, dtype):
        self.pa = pa
        return _serve.init_serve_state(self.cfg, pa, batch, self.ccfg,
                                       dtype=dtype)

    def from_prefill(self, state, pa):
        self.pa = pa
        return state

    def splice(self, state, sub, rows):
        return _serve.splice_state(state, sub, rows)

    def release_rows(self, state, rows):
        return _serve.reset_state_rows(state, rows)

    def migrate_cache(self, cache, old_pa, new_pa, active_rows=None):
        migrated = migrate_cache(cache, old_pa, new_pa)

        def commit():
            self.pa = new_pa
            return migrated

        return migrated.lengths, commit

    def live_tokens(self, state) -> int:
        if state.cache is None:
            return 0
        return int(np.asarray(state.cache.lengths).sum())

    def per_shard_live(self, state) -> np.ndarray:
        """(n_shards,) realized Σ lengths per model shard."""
        if state.cache is None:
            return np.zeros(self.n_shards, np.int64)
        per_slot = np.asarray(state.cache.lengths).sum(axis=(0, 2))  # (S,)
        return per_slot.reshape(self.n_shards, -1).sum(axis=1)

    def per_shard_cost(self, req) -> np.ndarray:
        """(n_shards,) expected Σ-lengths a request adds per model shard.

        Each head's per-layer projected tokens (prefill keep bound / H plus
        decode growth, clipped at capacity) land on the shards holding its
        replicas, split ``1/r`` per replica — the expectation of the strided
        row split the runtime actually performs.  Requires a live plan
        (``init_state`` / ``from_prefill`` record it).
        """
        if self.cfg.attention_free or self.pa is None:
            return np.zeros(self.n_shards)
        sh = np.asarray(self.pa.slot_head)  # (L, S)
        rc = np.asarray(self.pa.replica_count)
        L, S = sh.shape
        H, cap = self.cfg.n_kv_heads, self.ccfg.static_capacity()
        row_cap = min(req.prompt_len + req.max_new_tokens, cap)
        cost = np.zeros(self.n_shards)
        for l in range(L):
            bound = layer_keep_bound(self.ccfg.policy, self.ccfg,
                                     req.prompt_len, H, l, L) / H
            per_head = min(bound + req.max_new_tokens, row_cap)
            w = np.where(sh[l] >= 0, per_head / rc[l], 0.0)  # (S,)
            cost += w.reshape(self.n_shards, -1).sum(axis=1)
        return cost

    def request_cost(self, req):
        if self.cfg.attention_free:
            return 0
        return projected_request_tokens(
            self.ccfg.policy, self.ccfg, req.prompt_len, req.max_new_tokens,
            self.cfg.n_layers, self.cfg.n_kv_heads)

    def admissible(self, state, req, pending=()):
        if self.max_live_tokens is not None:
            reserved = sum(self.request_cost(p) for p in pending)
            if (self.live_tokens(state) + reserved + self.request_cost(req)
                    > self.max_live_tokens):
                return False
        if (self.max_live_tokens_per_shard is not None
                and not self.cfg.attention_free and self.pa is not None):
            # per-model-shard budget (DESIGN.md §10): the bottleneck shard
            # gates admission, so an imbalanced plan saturates one shard's
            # budget while balanced plans keep admitting — the fig8 signal
            load = self.per_shard_live(state) + self.per_shard_cost(req)
            for p in pending:
                load = load + self.per_shard_cost(p)
            if (load > self.max_live_tokens_per_shard).any():
                return False
        return True

    def never_fits(self, req):
        if self.max_live_tokens is not None:
            cost = self.request_cost(req)
            if cost > self.max_live_tokens:
                return (f"projected cost {cost} tokens exceeds "
                        f"max_live_tokens={self.max_live_tokens} even on "
                        f"an empty cache")
        if (self.max_live_tokens_per_shard is not None
                and not self.cfg.attention_free and self.pa is not None):
            worst = self.per_shard_cost(req).max()
            if worst > self.max_live_tokens_per_shard:
                return (f"projected per-shard cost {worst:.0f} tokens "
                        f"exceeds max_live_tokens_per_shard="
                        f"{self.max_live_tokens_per_shard} even on an "
                        f"empty cache")
        return None

    def sample_metrics(self, state) -> None:
        if state.cache is None:
            return
        m = self.obs.metrics
        live = self.live_tokens(state)
        lens = np.asarray(state.cache.lengths)
        cap = int(np.prod(lens.shape)) * self.ccfg.static_capacity()
        m.gauge("cache_live_tokens",
                help="Σ retained KV tokens across the live cache"
                ).set(live)
        m.gauge("cache_utilization",
                help="live tokens / static slot capacity (slot backend "
                     "pressure; the paged analog is pool_free_blocks)"
                ).set(live / max(1, cap))

    def memory_stats(self, state) -> dict:
        if state.cache is None:
            return {"backend": self.name, "cache_bytes": 0, "live_tokens": 0}
        c = state.cache
        L, S, B, C, Dh = c.k.shape
        item = c.k.dtype.itemsize
        live = int(np.asarray(c.lengths).sum())
        return {
            "backend": self.name,
            "cache_bytes": int(2 * L * S * B * C * Dh * item),
            "live_tokens": live,
            "capacity_tokens": int(L * S * B * C),
            "utilization": live / max(1, L * S * B * C),
        }


def make_cache_backend(name: str, model_cfg: ModelConfig,
                       ccfg: CompressionConfig,
                       max_live_tokens: Optional[int] = None,
                       paging: Optional[PagingConfig] = None,
                       n_shards: int = 1,
                       max_live_tokens_per_shard: Optional[int] = None,
                       pool_partitions: int = 1,
                       row_partitions: int = 1,
                       obs=None, donate_state: bool = True) -> CacheBackend:
    """Instantiate a registered backend by name (geometry kwargs: see the
    `CacheBackend` docstring)."""
    from repro.api.registry import get_cache_backend
    return get_cache_backend(name)(
        model_cfg, ccfg, max_live_tokens=max_live_tokens, paging=paging,
        n_shards=n_shards,
        max_live_tokens_per_shard=max_live_tokens_per_shard,
        pool_partitions=pool_partitions, row_partitions=row_partitions,
        obs=obs, donate_state=donate_state)
