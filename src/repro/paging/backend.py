"""`PagedBackend`: the block-pool cache backend (DESIGN.md §9).

Bridges the host-side allocator (`BlockPool`) and the device-side arrays
(`PagedCache`) behind the `CacheBackend` interface.  The backend keeps a
host ``numpy`` mirror of the block table as the single source of truth for
*topology* (which blocks belong to which (layer, slot, row)); every
topology change rebuilds the device table from the mirror, while *content*
(K/V values, lengths) flows only through the pure array ops so the jitted
decode step stays functional.

Admission is a free-**block** budget: a request is admissible when every
layer's free list covers its projected prefill blocks plus one growth block
per owned head.  Growth beyond that is intentionally *not* reserved —
decode-time exhaustion is handled by the scheduler preempting the youngest
request (the recompute policy), which this backend signals via
``PoolExhausted``.  A request whose worst-case need exceeds the whole pool
fails fast at submit (`never_fits`).

The device half of an admission is one jitted program (`paged_splice`):
the sub-cache's pagination and the row merges of `splice_state`, with the
live state donated under ``donate_state`` so every pool scatter writes in
place.  The caller may not touch the pre-splice state afterwards.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.registry import register_cache_backend
from repro.cache.slot_cache import PlanArrays
from repro.cache.slot_cache import migrate_cache as migrate_slot_cache
from repro.compression.policies import layer_keep_bound
from repro.paging import kvquant
from repro.paging.block_pool import BlockPool
from repro.paging.paged_cache import (
    PagedCache,
    block_hbm_bytes,
    build_table,
    init_paged_cache,
    max_blocks_per_row,
    paged_to_slot,
    paginate_rows,
    release_rows,
)
from repro.serving import engine as _serve
from repro.serving.cache_backend import CacheBackend


def paged_splice(state, sub, rows, table_write, table_store, kinds):
    """Admission's device work: page ``sub``'s cache into the blocks of
    ``table_write`` (`paginate_rows`), then merge its last tokens (and
    SSM/conv rows) into ``rows`` (`splice_state`)."""
    cache = paginate_rows(state.cache, sub.cache, rows, table_write,
                          table_store=table_store, kinds=kinds)
    return _serve.splice_state(state, sub, rows, cache=cache)


@functools.cache
def _jitted(fn, donate: bool):
    """``fn`` jitted once per donation mode; argument 0 (the live state,
    or an empty cache being filled) is donated when ``donate``."""
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


def _owner_mask_np(pa: PlanArrays, rows: np.ndarray) -> np.ndarray:
    """(L, S, len(rows)) bool — the §2 strided owner rule, on the host."""
    sh = np.asarray(pa.slot_head)
    rc = np.asarray(pa.replica_count)[:, :, None]
    ri = np.asarray(pa.replica_idx)[:, :, None]
    rows = np.asarray(rows, np.int64)[None, None, :]
    return (sh >= 0)[:, :, None] & ((rows % rc) == ri)


@register_cache_backend("paged")
class PagedBackend(CacheBackend):
    name = "paged"

    def __init__(self, model_cfg, ccfg, max_live_tokens=None, paging=None,
                 n_shards=1, max_live_tokens_per_shard=None,
                 pool_partitions=1, row_partitions=1, obs=None,
                 donate_state=True):
        super().__init__(model_cfg, ccfg, max_live_tokens=max_live_tokens,
                         paging=paging, n_shards=n_shards,
                         max_live_tokens_per_shard=max_live_tokens_per_shard,
                         pool_partitions=pool_partitions,
                         row_partitions=row_partitions, obs=obs,
                         donate_state=donate_state)
        self.capacity = ccfg.static_capacity()
        self.block_size = self.paging.block_size
        self.max_blocks = max_blocks_per_row(self.capacity, self.block_size)
        self.pool: Optional[BlockPool] = None
        self.table: Optional[np.ndarray] = None  # host mirror (L, S, B, M)
        self.pa = None  # the plan, and its host copy (the `pa` setter)
        self.n_rows: Optional[int] = None  # global batch width
        # copy-on-write backlog (DESIGN.md §14): (layer, old_id, new_id)
        # device content copies queued by `prepare_decode` when a row's next
        # append would land in a shared (refcount > 1) block.  Kept across
        # calls so a PoolExhausted mid-CoW retries without losing the queue
        # (the old block's content stays live — someone still holds a ref).
        self._pending_cow: list = []
        self.cow_copies = 0  # lifetime count of privatized blocks
        # quantized storage (DESIGN.md §15): resolved spec, the static
        # (L, H) kind grid, and the scale-reset backlog — freshly allocated
        # growth blocks reuse pool slots whose scale entries are stale, so
        # their scales must reset to 0 before the first quantize-on-write
        # append (the running max would otherwise inherit a huge stale
        # scale and flush small tokens to code 0).  Same PoolExhausted
        # retry semantics as the CoW queue.
        self.kv_quant = kvquant.spec_from_paging(self.paging)
        self.kv_kinds = (kvquant.kind_grid(self.kv_quant, self.cfg.n_layers,
                                           self.cfg.n_kv_heads)
                         if self.kv_quant is not None else None)
        self.model_dtype = None  # stashed by init_state (the logical dtype)
        self._pending_scale_reset: list = []  # (layer, [block ids])

    @property
    def pa(self) -> Optional[PlanArrays]:
        return self._pa

    @pa.setter
    def pa(self, pa: Optional[PlanArrays]) -> None:
        # the owner rule and the kind lookup read the plan on every
        # admission and decode tick: fetch it to the host once per plan
        self._pa = pa
        self._pa_np = None if pa is None else jax.device_get(pa)

    @property
    def partitions(self):
        """(slot_parts, row_parts) — the mesh pool split (DESIGN.md §10)."""
        return (self.pool_partitions, self.row_partitions)

    # ---- state lifecycle ---------------------------------------------------

    def _slot_kinds(self, pa) -> Optional[np.ndarray]:
        """(L, S) per-slot kind codes under ``pa``'s head placement (None on
        the fp32 path) — the host-side twin of the decode step's in-trace
        ``slot_head`` → kind lookup."""
        if self.kv_kinds is None:
            return None
        return kvquant.slot_kinds(self.kv_kinds, np.asarray(pa.slot_head))

    def init_state(self, pa, batch, dtype):
        self.pa = pa
        self.n_rows = int(batch)
        self.model_dtype = dtype
        if self.cfg.attention_free:
            return _serve.init_serve_state(self.cfg, pa, batch, self.ccfg,
                                           dtype=dtype)
        cache, self.pool = init_paged_cache(
            self.cfg.n_layers, int(pa.slot_head.shape[1]), batch,
            self.capacity, self.cfg.head_dim, self.paging, dtype=dtype,
            partitions=self.partitions, kv_quant=self.kv_quant)
        self.pool.obs = self.obs  # alloc/free/exhaustion counters (§12)
        self.table = np.zeros(cache.block_table.shape, np.int32)
        return _serve.init_serve_state(self.cfg, pa, batch, self.ccfg,
                                       dtype=dtype, cache=cache)

    def from_prefill(self, state, pa):
        """One-shot adoption: re-house a full-batch slot prefill in blocks
        sized to its realized retained lengths (all rows live)."""
        if state.cache is None:
            self.pa = pa
            return state
        slot = state.cache
        L, S, B, C, Dh = slot.k.shape
        if C != self.capacity:
            raise ValueError(f"prefill capacity {C} != backend capacity "
                             f"{self.capacity}")
        empty = self.init_state(pa, B, slot.k.dtype)  # fresh pool + mirror
        own = _owner_mask_np(self._pa_np, np.arange(B))
        table = build_table(np.asarray(slot.lengths), self.pool,
                            self.block_size, self.max_blocks, own=own,
                            partitions=self.partitions, n_rows=B)
        self.table = table.copy()
        cache = _jitted(paginate_rows, self.donate_state)(
            empty.cache, slot, jnp.arange(B, dtype=jnp.int32), table,
            kinds=self._slot_kinds(self._pa_np))
        self._observe_quant_error(slot)
        return dataclasses.replace(state, cache=cache)

    def splice(self, state, sub, rows, shared_blocks=None):
        """Admit: allocate blocks for the sub-state's realized lengths and
        scatter its contents in.  Atomic on ``PoolExhausted``.

        ``shared_blocks`` (optional, (L, S, len(rows), M) int32) carries
        prefix-cache donor block ids (DESIGN.md §14): each (layer, slot,
        row)'s shared *full* blocks, contiguous from column 0, already
        holding the matched prefix content on device.  Fresh blocks are
        allocated only for the remainder; shared ids are incref'd (never
        written — `paginate_rows` null-redirects their columns) and the
        stored table maps the row onto the shared blocks directly, so a
        cache hit costs ``need − shared`` new blocks.
        """
        if state.cache is None:
            return _serve.splice_state(state, sub, rows)
        rows_np = np.asarray(rows, np.int64)
        leftovers = self.table[:, :, rows_np, :]
        if (leftovers > 0).any():  # defensive: target rows must be retired
            self.pool.free_table(leftovers.reshape(self.table.shape[0], -1))
            self.table[:, :, rows_np, :] = 0
        own = _owner_mask_np(self._pa_np, rows_np)
        # the one read the allocator needs, fetched with the first token the
        # scheduler reads next (a fetched array keeps its host copy)
        lengths, _ = jax.device_get((sub.cache.lengths, sub.last_tokens))
        if shared_blocks is None:
            table_write = table_full = build_table(
                lengths, self.pool, self.block_size, self.max_blocks,
                own=own, partitions=self.partitions, rows=rows_np,
                n_rows=self.n_rows)
        else:
            table_write, table_full = self._share_blocks(
                shared_blocks, lengths, own, rows_np)
        self.table[:, :, rows_np, :] = table_full
        old_pool = state.cache.k_pool
        state = _jitted(paged_splice, self.donate_state)(
            state, sub, rows_np.astype(np.int32), table_write, table_full,
            self._slot_kinds(self._pa_np))
        self.obs.metrics.counter(
            "paged_splice_total",
            help="admission splices into the paged pools, by whether the "
                 "live pools were written in place (donated) or copied"
        ).inc(mode="inplace" if old_pool.is_deleted() else "copy")
        self._observe_quant_error(sub.cache)
        return state

    def _share_blocks(self, shared_blocks, lengths, own, rows_np):
        """Shared-prefix allocation for `splice` → (write table, stored
        table): fresh blocks for the tokens past the shared full blocks,
        the shared ids incref'd and mapped first in the stored table, and
        their columns zeroed in the write table."""
        shared = np.asarray(shared_blocks, np.int32)
        n_sh = (shared > 0).sum(axis=-1)  # (L, S, R) full shared blocks
        # fresh blocks cover only tokens past the shared full blocks; the
        # allocation trial runs BEFORE any incref/mirror change so a
        # PoolExhausted here leaves pool and table untouched (atomicity)
        lens_adj = np.maximum(lengths - n_sh * self.block_size, 0)
        fresh = build_table(lens_adj, self.pool,
                            self.block_size, self.max_blocks, own=own,
                            partitions=self.partitions, rows=rows_np,
                            n_rows=self.n_rows)
        L, S, R, M = fresh.shape
        for l in range(L):
            ids = shared[l][shared[l] > 0]
            if ids.size:
                self.pool.incref(l, ids)
        table_full = np.zeros_like(fresh)
        for l, s, r in zip(*np.nonzero(own | (n_sh > 0))):
            f = int(n_sh[l, s, r])
            fr = fresh[l, s, r][fresh[l, s, r] > 0]
            nf = min(fr.size, M - f)
            table_full[l, s, r, :f] = shared[l, s, r, :f]
            table_full[l, s, r, f:f + nf] = fr[:nf]
            if fr.size > nf:  # fully-shared row at capacity: growth block
                self.pool.decref(l, fr[nf:])  # has no table home, return it
        # write addressing zeroes the shared columns (null-redirect): the
        # shared blocks already hold the prefix content and must never be
        # written through a refcount > 1 table entry
        col = np.arange(M)[None, None, None, :]
        table_write = np.where(col < n_sh[..., None], 0, table_full)
        return table_write, table_full

    def _observe_quant_error(self, slot) -> None:
        """Quantization-error observability (DESIGN.md §15): on each
        admission, roundtrip the spliced sub-cache through the codec and
        record the relative error — the live quality signal for the
        kv_dtype / override-map knobs.  Skipped when obs is off (the
        roundtrip costs a second encode pass)."""
        if self.kv_kinds is None or not self.obs.enabled:
            return
        # (L, S, 1, 1): broadcasts over the (L, S, B, M) block axes
        kinds = jnp.asarray(self._slot_kinds(self._pa_np))[:, :, None, None]
        err_k, den_k = kvquant.roundtrip_error(slot.k, slot.pos,
                                               self.block_size, kinds)
        err_v, den_v = kvquant.roundtrip_error(slot.v, slot.pos,
                                               self.block_size, kinds)
        tokens = int(np.asarray(slot.lengths).sum())
        self.obs.metrics.counter(
            "kv_quant_tokens_total",
            help="KV tokens quantized into the paged pools").inc(tokens)
        self.obs.metrics.gauge(
            "kv_quant_rel_err",
            help="mean relative KV quantization error over the last "
                 "admitted sub-cache (Σ|deq(q(x))−x| / Σ|x|)"
        ).set(float((err_k + err_v) / max(den_k + den_v, 1e-9)))

    def release_rows(self, state, rows):
        if state.cache is None:
            return _serve.reset_state_rows(state, rows)
        rows_np = np.asarray(rows, np.int64)
        held = self.table[:, :, rows_np, :]
        self.pool.free_table(held.reshape(self.table.shape[0], -1))
        self.table[:, :, rows_np, :] = 0
        cache = release_rows(state.cache, jnp.asarray(rows_np, jnp.int32))
        return _serve.reset_state_rows(state, rows, cache=cache)

    def prepare_decode(self, state, active, n_tokens: int = 1):
        """Allocate the blocks backing each active row's next appends.

        The next write index is ``lengths`` while a row is below capacity
        (the recency ring past that only revisits already-allocated
        blocks); ``n_tokens`` consecutive appends need the blocks through
        ``(min(len + n_tokens, capacity) - 1) // bs``, so an owned
        (layer, slot, row) may take several *provisional* blocks before
        the tick (speculative decoding, DESIGN.md §16 — rejected windows
        hand them back through `trim_rows`).  Raises ``PoolExhausted``
        when a layer's free list runs dry — the scheduler's preemption
        signal.

        Copy-on-write (DESIGN.md §14): before allocating growth, any owned
        next write that would land in a *shared* (refcount > 1) block —
        only the recency ring can wrap into the shared prefix region —
        gets a private block first: alloc in the same partition, decref
        the shared id, queue a device content copy.  Checking the *first*
        write block suffices for any ``n_tokens``: later writes of the
        window land in blocks this call allocates fresh (refcount 1), and
        at-capacity rows (the only ring-wrap case) are clamped to a
        single-token window by the scheduler.  A defensive recheck after
        allocation turns any surviving shared-write into a hard error
        instead of silent corruption.
        """
        if state.cache is None:
            return state
        if n_tokens < 1:
            raise ValueError(f"n_tokens must be >= 1, got {n_tokens}")
        cache = state.cache
        B = cache.positions.shape[0]
        rows = np.arange(B) if active is None else np.asarray(list(active))
        if rows.size == 0:
            return state
        lens = np.asarray(cache.lengths)[:, :, rows]  # (L, S, R)
        own = _owner_mask_np(self._pa_np, rows)
        blk = self._next_write_blocks(state, lens)  # (L, S, R)
        dirty = False
        if int(self.pool.refcount.max()) > 1:
            dirty = self._cow_next_writes(rows, own, blk)
        have = (self.table[:, :, rows, :] > 0).sum(axis=-1)  # (L, S, R)
        growing = own & (lens < self.capacity)
        end = np.minimum(lens + n_tokens, self.capacity)  # exclusive
        need = np.where(growing, (end - 1) // self.block_size + 1, have)
        missing = need - have
        if missing.max(initial=0) > 0:
            dirty = True
            L, S = self.table.shape[0], self.table.shape[1]
            slot_parts, row_parts = self.partitions
            s_per = S // slot_parts
            b_per = -(-self.n_rows // row_parts)
            for l in range(L):
                for sp in range(slot_parts):
                    sl = slice(sp * s_per, (sp + 1) * s_per)
                    for rp in range(row_parts):
                        cols = np.nonzero(rows // b_per == rp)[0]
                        if cols.size == 0:
                            continue
                        miss = missing[l, sl][:, cols]
                        n_lp = int(np.maximum(miss, 0).sum())
                        if n_lp == 0:
                            continue
                        ids = self.pool.alloc(l, n_lp,
                                              partition=sp * row_parts + rp)
                        if self.kv_kinds is not None:
                            # reused pool slots carry stale scales; zero
                            # them before the first quantize-on-write
                            self._pending_scale_reset.append((l, list(ids)))
                        hv = have[l, sl][:, cols]
                        at = 0
                        for s, c in zip(*np.nonzero(miss > 0)):
                            m, h = int(miss[s, c]), int(hv[s, c])
                            self.table[l, sp * s_per + s, rows[cols[c]],
                                       h:h + m] = ids[at:at + m]
                            at += m
        if int(self.pool.refcount.max()) > 1:
            # defensive recheck: CoW above must have privatized every owned
            # next write — reject in-place mutation of shared blocks
            tbl = self.table[:, :, rows, :]
            bid = np.take_along_axis(tbl, blk[..., None], axis=-1)[..., 0]
            l_ix = np.arange(tbl.shape[0])[:, None, None]
            still = own & (bid > 0) & (self.pool.refcount[l_ix, bid] > 1)
            if still.any():
                l, s, r = next(zip(*np.nonzero(still)))
                raise RuntimeError(
                    f"next decode append for (layer {l}, slot {s}, row "
                    f"{rows[r]}) targets shared block "
                    f"{int(bid[l, s, r])} (refcount > 1); copy-on-write "
                    f"failed to privatize it")
        if (not dirty and not self._pending_cow
                and not self._pending_scale_reset):
            return state
        cache = self._apply_pending_cow(cache)
        return dataclasses.replace(state, cache=dataclasses.replace(
            cache, block_table=jnp.asarray(self.table)))

    def trim_rows(self, state, rows):
        """Release provisional blocks no longer covered by ``lengths``.

        Speculative verify rolls rejected window entries back *in-trace*
        (device ``lengths`` drop to the committed run, DESIGN.md §16); the
        host mirror still maps the blocks that backed them.  For the given
        rows, decref every mapped block past ``ceil(len / bs)`` — blocks
        taken by `prepare_decode(n_tokens=...)` for writes that were
        rejected or never made — and zero its mirror entries.  Refcounts
        make this safe under sharing: a block another row still references
        merely drops a reference.  Returns the state with the updated
        device table (identity when nothing was trimmed).
        """
        if state.cache is None:
            return state
        rows_np = np.asarray(list(rows), np.int64)
        if rows_np.size == 0:
            return state
        lens = np.asarray(state.cache.lengths)[:, :, rows_np]  # (L, S, R)
        keep = -(-lens // self.block_size)  # ceil: blocks still covered
        tbl = self.table[:, :, rows_np, :]  # (L, S, R, M)
        M = tbl.shape[-1]
        past = np.arange(M)[None, None, None, :] >= keep[..., None]
        drop = np.where(past, tbl, 0)
        if drop.max(initial=0) == 0:
            return state
        self.pool.free_table(drop.reshape(self.table.shape[0], -1))
        self.table[:, :, rows_np, :] = np.where(past, 0, tbl)
        return dataclasses.replace(state, cache=dataclasses.replace(
            state.cache, block_table=jnp.asarray(self.table)))

    def _next_write_blocks(self, state, lens: np.ndarray) -> np.ndarray:
        """(L, S, R) block index of each pair's next append — the host
        mirror of `ring_write_index` (below capacity: ``lens``; at
        capacity: the shared ring phase)."""
        cap = self.capacity
        ring = max(1, min(max(1, self.ccfg.decode_margin), cap))
        cyc = (cap - ring) + int(state.decode_steps) % ring
        return np.where(lens < cap, lens, cyc) // self.block_size

    def _cow_next_writes(self, rows, own, blk) -> bool:
        """Privatize shared blocks under the next write index.  Mutates
        the mirror + pool and queues content copies; returns True if any
        block was replaced.  PoolExhausted mid-loop is safe to retry: the
        queue survives and completed replacements stay consistent."""
        tbl = self.table[:, :, rows, :]  # (L, S, R, M)
        bid = np.take_along_axis(tbl, blk[..., None], axis=-1)[..., 0]
        L = tbl.shape[0]
        l_ix = np.arange(L)[:, None, None]
        hit = own & (bid > 0) & (self.pool.refcount[l_ix, bid] > 1)
        if not hit.any():
            return False
        for l, s, r in zip(*np.nonzero(hit)):
            old = int(bid[l, s, r])
            new = int(self.pool.alloc(
                l, 1, partition=self.pool.partition_of(old))[0])
            self.pool.decref(l, np.asarray([old]))
            self.table[l, s, rows[r], int(blk[l, s, r])] = new
            self._pending_cow.append((int(l), old, new))
            self.cow_copies += 1
        return True

    def _apply_pending_cow(self, cache):
        """Flush queued CoW content copies into the device pools.

        Applied strictly in queue order: a freed-then-reallocated id can
        appear as a copy *destination* only after all entries reading it
        as a *source* (they were queued while it was still shared), so
        sequential application never reads clobbered content.

        Quantized pools (DESIGN.md §15): a privatized block copies codes
        AND scale verbatim — bit-exact, never a second quantization — and
        queued scale resets (fresh growth blocks) flush here too, before
        the first append can run a quantize-on-write against them.
        """
        if not self._pending_cow and not self._pending_scale_reset:
            return cache
        kp, vp, pp = cache.k_pool, cache.v_pool, cache.pos_pool
        ks, vs = cache.k_scale, cache.v_scale
        if ks is not None:
            # resets before copies: a reset-queued id freed by preemption
            # and re-handed-out as a CoW destination must end with the
            # donor's copied scale, not a zero
            for l, ids in self._pending_scale_reset:
                idx = jnp.asarray(ids, jnp.int32)
                ks = ks.at[l, idx].set(0.0)
                vs = vs.at[l, idx].set(0.0)
        for l, old, new in self._pending_cow:
            kp = kp.at[l, new].set(kp[l, old])
            vp = vp.at[l, new].set(vp[l, old])
            pp = pp.at[l, new].set(pp[l, old])
            if ks is not None:
                ks = ks.at[l, new].set(ks[l, old])
                vs = vs.at[l, new].set(vs[l, old])
        self._pending_cow.clear()
        self._pending_scale_reset.clear()
        return dataclasses.replace(cache, k_pool=kp, v_pool=vp, pos_pool=pp,
                                   k_scale=ks, v_scale=vs)

    def migrate_cache(self, cache, old_pa, new_pa, active_rows=None):
        """Trial re-layout for a replan: materialize → migrate → allocate
        in a *fresh* trial allocator; the expensive device re-pagination is
        deferred into ``commit()`` (rejection — the common case under
        hysteresis — then never pays it).

        Raising ``PoolExhausted`` (ownership moves can change block
        rounding) happens during the allocation trial, before scoring, and
        leaves the backend untouched — the scheduler records the replan as
        rejected.
        """
        # dequantize through the live scale pools (same scale/kind lookup as
        # the decode kernel) so the trial sees real values, and back in the
        # model dtype so re-pagination re-quantizes from full precision —
        # the slot↔paged bit-consistency rule (DESIGN.md §15)
        slot = paged_to_slot(cache, self.capacity,
                             kinds=self._slot_kinds(old_pa),
                             out_dtype=self.model_dtype)
        slot2 = migrate_slot_cache(slot, old_pa, new_pa)
        B = int(cache.positions.shape[0])
        rows = np.arange(B) if active_rows is None else np.asarray(
            list(active_rows))
        own = np.zeros((self.table.shape[0], self.table.shape[1], B), bool)
        if rows.size:
            own[:, :, rows] = _owner_mask_np(new_pa, rows)
        trial = BlockPool(self.pool.n_layers, self.pool.n_blocks,
                          n_partitions=self.pool.n_partitions)
        trial.obs = self.obs  # trial allocations are real allocator work
        table = build_table(np.asarray(slot2.lengths), trial,
                            self.block_size, self.max_blocks, own=own,
                            partitions=self.partitions, n_rows=B)

        def commit():
            # pin the pool size to the live cache's (pool_hbm_bytes and
            # n_blocks are mutually exclusive sizing modes, and the byte
            # budget already resolved to this block count); dtype is the
            # *logical* model dtype — the storage dtype falls out of
            # kv_quant (the pre-fix code passed cache.k_pool.dtype, which
            # under quantization is int8 and would have desugared the
            # re-paginated pools into int8-as-model-dtype garbage)
            empty, _ = init_paged_cache(
                self.cfg.n_layers, int(new_pa.slot_head.shape[1]), B,
                self.capacity, self.cfg.head_dim,
                dataclasses.replace(self.paging, n_blocks=cache.n_blocks,
                                    pool_hbm_bytes=0),
                dtype=self.model_dtype or cache.k_pool.dtype,
                partitions=self.partitions, kv_quant=self.kv_quant)
            cand = _jitted(paginate_rows, self.donate_state)(
                empty, slot2, jnp.arange(B, dtype=jnp.int32), table,
                kinds=self._slot_kinds(new_pa))
            self.pool, self.table, self.pa = trial, table, new_pa
            return cand

        return slot2.lengths, commit

    # ---- admission accounting ----------------------------------------------

    def _layer_blocks(self, prompt_len: int, max_new: int,
                      worst_case: bool) -> np.ndarray:
        """(L,) projected block need per layer.

        ``worst_case=False``: prefill bound + one growth block per owned
        head (the admission check; later growth is preemption's problem).
        ``worst_case=True``: the full-generation bound (fail-fast check).
        """
        H, L = self.cfg.n_kv_heads, self.cfg.n_layers
        bs = self.block_size
        out = np.zeros(L, np.int64)
        for l in range(L):
            tokens = layer_keep_bound(self.ccfg.policy, self.ccfg,
                                      prompt_len, H, l, L)
            if worst_case:
                tokens = min(tokens + H * max_new,
                             H * min(prompt_len + max_new, self.capacity))
                out[l] = tokens // bs + H
            else:
                out[l] = tokens // bs + 2 * H  # rounding + 1 growth block/head
        return out

    def _partition_need(self, prompt_len: int, max_new: int,
                        worst_case: bool) -> np.ndarray:
        """(L, P) projected block need per (layer, pool partition).

        The per-layer token bound splits across partitions proportional to
        the plan's occupied slots there (replicas split rows, so a
        partition's expected share of a request's tokens tracks its share
        of owned slots); the per-head growth/rounding slack charges where
        the heads physically sit.  Budgets and admission are therefore
        **per model shard** — one shard's full partition blocks admission
        even when the pool has global headroom (DESIGN.md §10).
        """
        P = self.pool_partitions
        sh = np.asarray(self._pa_np.slot_head)  # (L, S)
        L, S = sh.shape
        occ = (sh >= 0).reshape(L, P, S // P).sum(axis=2)  # (L, P)
        frac = occ / np.maximum(occ.sum(axis=1, keepdims=True), 1)
        H, bs = self.cfg.n_kv_heads, self.block_size
        out = np.zeros((L, P), np.int64)
        for l in range(L):
            tokens = layer_keep_bound(self.ccfg.policy, self.ccfg,
                                      prompt_len, H, l, L)
            if worst_case:
                tokens = min(tokens + H * max_new,
                             H * min(prompt_len + max_new, self.capacity))
                slack = occ[l]
            else:
                slack = 2 * occ[l]  # rounding + 1 growth block per slot
            out[l] = (np.ceil(tokens * frac[l] / bs).astype(np.int64)
                      + slack)
        return out

    def request_cost(self, req):
        if self.cfg.attention_free:
            return 0
        return int(self._layer_blocks(req.prompt_len, req.max_new_tokens,
                                      worst_case=True).sum())

    def admissible(self, state, req, pending=()):
        if self.cfg.attention_free or self.pool is None:
            return True
        if self.pool.n_partitions > 1:
            need = self._partition_need(req.prompt_len, req.max_new_tokens,
                                        worst_case=False)  # (L, slot_parts)
            for p in pending:  # accepted-not-yet-spliced charge (see base)
                need = need + self._partition_need(
                    p.prompt_len, p.max_new_tokens, worst_case=False)
            free = self.pool.free_blocks_by_partition()
            L = free.shape[0]
            # the request lands in one (unknown) row partition — require the
            # worst one to fit, so admission never over-commits a shard
            free = free.reshape(L, self.pool_partitions,
                                self.row_partitions).min(axis=2)
            return bool((free >= need).all())
        need = self._discount_shared(
            self._layer_blocks(req.prompt_len, req.max_new_tokens,
                               worst_case=False), req)
        for p in pending:
            need = need + self._discount_shared(
                self._layer_blocks(p.prompt_len, p.max_new_tokens,
                                   worst_case=False), p)
        return bool((self.pool.free_blocks() >= need).all())

    @staticmethod
    def _discount_shared(need: np.ndarray, req) -> np.ndarray:
        """Admission charges only *unshared* blocks (DESIGN.md §14): a
        prefix-cache hit stamps ``req.prefix_shared_blocks`` ((L,) full
        blocks reused from the index) and those never leave the pool's
        allocated set twice."""
        sh = getattr(req, "prefix_shared_blocks", None)
        if sh is None:
            return need
        return np.maximum(need - np.asarray(sh, np.int64), 0)

    def never_fits(self, req):
        if self.cfg.attention_free:
            return None
        if self.pool is not None and self.pool.n_partitions > 1:
            need = self._partition_need(req.prompt_len, req.max_new_tokens,
                                        worst_case=True)
            usable = self.pool.part_size - 1
            if int(need.max()) > usable:
                return (f"worst-case need of {int(need.max())} blocks in "
                        f"one (layer, model-shard) partition exceeds the "
                        f"partition ({usable} usable blocks)")
            return None
        need = self._layer_blocks(req.prompt_len, req.max_new_tokens,
                                  worst_case=True)
        usable = (self.pool.usable_blocks if self.pool is not None
                  else self.paging.n_blocks - 1 if self.paging.n_blocks
                  else None)
        if usable is not None and int(need.max()) > usable:
            return (f"worst-case need of {int(need.max())} blocks/layer "
                    f"exceeds the pool ({usable} usable blocks/layer)")
        return None

    # ---- telemetry ---------------------------------------------------------

    def sample_metrics(self, state) -> None:
        if self.pool is None:
            return
        self.pool.sample_gauges(self.obs.metrics)
        if state.cache is not None:
            live = int(np.asarray(state.cache.lengths).sum())
            self.obs.metrics.gauge(
                "cache_live_tokens",
                help="Σ retained KV tokens across the live cache"
            ).set(live)
            if isinstance(state.cache, PagedCache):
                per_block = block_hbm_bytes(
                    self.block_size, self.cfg.head_dim,
                    state.cache.k_pool.dtype, self.kv_kinds is not None)
                self.obs.metrics.gauge(
                    "kv_bytes_per_token",
                    help="HBM bytes pinned per live KV token (allocated "
                         "blocks x per-block footprint incl. scales / "
                         "live tokens) — the decode-bandwidth unit the "
                         "kv_dtype knob halves (DESIGN.md §15)"
                ).set(self.pool.blocks_in_use() * per_block / max(live, 1))

    def memory_stats(self, state) -> dict:
        if (state.cache is not None
                and not isinstance(state.cache, PagedCache)):
            # prefill() leaves the cache in slot layout until generate()
            # adopts it — report the dense footprint it actually occupies
            c = state.cache
            L, S, B, C, Dh = c.k.shape
            return {"backend": self.name, "layout": "slot (pre-adoption)",
                    "block_size": self.block_size,
                    "blocks_in_use": 0, "blocks_total": 0,
                    "cache_bytes": int(2 * L * S * B * C * Dh
                                       * c.k.dtype.itemsize),
                    "pool_bytes": 0, "slot_equivalent_bytes": 0,
                    "live_tokens": int(np.asarray(c.lengths).sum())}
        if state.cache is None or self.pool is None:
            return {"backend": self.name, "block_size": self.block_size,
                    "blocks_in_use": 0, "blocks_total": 0, "cache_bytes": 0,
                    "pool_bytes": 0, "slot_equivalent_bytes": 0,
                    "live_tokens": 0}
        c = state.cache
        L, N, bs, Dh = c.k_pool.shape
        _, S, B, M = c.block_table.shape
        quantized = c.k_scale is not None
        # K + V payload + (quantized) the two fp32 scale entries; the
        # slot-equivalent baseline stays in the *model* dtype — that is the
        # dense cache this pool replaces, and the ratio between the two is
        # the bytes-aware capacity win (DESIGN.md §15)
        block_bytes = block_hbm_bytes(bs, Dh, c.k_pool.dtype, quantized)
        model_item = jnp.dtype(self.model_dtype or c.k_pool.dtype).itemsize
        in_use = self.pool.blocks_in_use()
        usable = self.pool.usable_blocks
        return {
            "backend": self.name,
            "block_size": bs,
            "kv_dtype": self.paging.kv_dtype,
            "blocks_in_use": in_use,
            "blocks_total": L * usable,
            "cache_bytes": in_use * block_bytes,
            "pool_bytes": L * usable * block_bytes,
            "slot_equivalent_bytes": int(2 * L * S * B * self.capacity
                                         * Dh * model_item),
            "live_tokens": int(np.asarray(c.lengths).sum()),
        }
