"""Slot-layout KV cache: what prefill writes, and a replan's intermediate.

Layout (see DESIGN.md §2):  per layer, every model shard owns
``slots_per_shard`` *slots*; globally the cache tensors are

    k, v     : (L, S, B, C, Dh)   S = total slots (sharded over "model"),
                                   C = static capacity per slot-row
    lengths  : (L, S, B) int32     retained tokens per (slot, row); 0 for
                                   unowned rows and empty slots
    positions: (B,) int32          next absolute position per row (for RoPE)

Prefill and chunked prefill fill this layout (`fill_from_selection`,
`append_selection`); the paged backend (`paging/backend.py`) re-houses it
in blocks, where the live cache and every decode step are.  A replan
round-trips the paged cache through this layout (`migrate_cache`).  Under
the ``mesh`` executor (DESIGN.md §10) S splits over the model mesh axis
and B over the data axis inside the prefill StepFn's ``shard_map``; the
ops below are written batch- and slot-local, so they run unchanged on one
device or per-shard slices (``migrate_cache``'s head-layout round-trip
runs on global arrays between steps, where XLA repartitions freely).

Replicas of one head split the batch by the strided rule
``owner(slot, b) = (b % replica_count) == replica_idx``; a slot only ever has
nonzero ``lengths`` on rows it owns, which simultaneously implements
best-effort assignment, fair-copying, and empty-slot padding: work inside the
decode kernel is proportional to Σ lengths.

Decode appends (`paging.paged_cache.paged_append_token`) are ring-buffered
in the tail of the capacity region once a row is full (`ring_write_index`):
keys are stored post-RoPE (rotation at absolute positions), so attention is
order-independent and overwriting the oldest *dynamic* entry implements a
recency window without any re-sorting.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.placement import HeadPlacement


@jax.tree_util.register_dataclass
@dataclass
class PlanArrays:
    """Runtime form of a HeadPlacement.

    slot_head / replica_idx / replica_count: (L, S) int32.
    first_slot: (L, Hkv) int32 — the replica-0 slot of each head (used by
    prefill to recover original-layout weights from the slot layout without
    storing a second copy).
    """

    slot_head: jnp.ndarray
    replica_idx: jnp.ndarray
    replica_count: jnp.ndarray
    first_slot: jnp.ndarray

    @staticmethod
    def from_plan(plan: HeadPlacement) -> "PlanArrays":
        arrs = plan.as_arrays()
        sh = arrs["slot_head"]
        L, S = sh.shape
        first = np.zeros((L, plan.n_heads), dtype=np.int32)
        for l in range(L):
            for h in range(plan.n_heads):
                slots = np.nonzero(sh[l] == h)[0]
                first[l, h] = int(slots[0])
        return PlanArrays(
            slot_head=jnp.asarray(arrs["slot_head"]),
            replica_idx=jnp.asarray(arrs["replica_idx"]),
            replica_count=jnp.asarray(arrs["replica_count"]),
            first_slot=jnp.asarray(first),
        )

    def owner_mask(self, layer: int, batch: int) -> jnp.ndarray:
        """(S, B) bool — slot owns row."""
        return self.owner_mask_rows(layer, jnp.arange(batch, dtype=jnp.int32))

    def owner_mask_rows(self, layer: int, rows: jnp.ndarray) -> jnp.ndarray:
        """(S, len(rows)) bool ownership for explicit *global* row ids.

        The strided owner rule keys on the global batch-row index, so a
        sub-batch (e.g. a freshly admitted request prefilled alone) must be
        masked with the rows it will occupy in the live cache, not with
        ``arange(sub_batch)`` — otherwise its KV lands on the wrong replica.
        """
        rows = jnp.asarray(rows, jnp.int32)[None, :]
        rc = self.replica_count[layer][:, None]
        ri = self.replica_idx[layer][:, None]
        valid = (self.slot_head[layer] >= 0)[:, None]
        return valid & ((rows % rc) == ri)

    def owner_mask_all(self, batch: int) -> jnp.ndarray:
        """(L, S, B) bool — vectorized owner_mask over every layer."""
        rows = jnp.arange(batch, dtype=jnp.int32)[None, None, :]
        rc = self.replica_count[:, :, None]
        ri = self.replica_idx[:, :, None]
        valid = (self.slot_head >= 0)[:, :, None]
        return valid & ((rows % rc) == ri)


@jax.tree_util.register_dataclass
@dataclass
class SlotCache:
    k: jnp.ndarray  # (L, S, B, C, Dh)
    v: jnp.ndarray  # (L, S, B, C, Dh)
    lengths: jnp.ndarray  # (L, S, B) int32
    pos: jnp.ndarray  # (L, S, B, C) int32 — absolute position of each entry
    positions: jnp.ndarray  # (B,) int32

    @property
    def capacity(self) -> int:
        return self.k.shape[3]

    @property
    def n_slots(self) -> int:
        return self.k.shape[1]


def init_cache(n_layers: int, n_slots: int, batch: int, capacity: int,
               head_dim: int, dtype=jnp.bfloat16) -> SlotCache:
    return SlotCache(
        k=jnp.zeros((n_layers, n_slots, batch, capacity, head_dim), dtype),
        v=jnp.zeros((n_layers, n_slots, batch, capacity, head_dim), dtype),
        lengths=jnp.zeros((n_layers, n_slots, batch), jnp.int32),
        pos=jnp.full((n_layers, n_slots, batch, capacity), -1, jnp.int32),
        positions=jnp.zeros((batch,), jnp.int32),
    )


def ring_write_index(lengths: jnp.ndarray, total_appended: jnp.ndarray,
                     capacity: int, ring: int) -> jnp.ndarray:
    """Write position for the next token.

    While a row is below capacity, append at ``lengths``.  Once full, cycle
    through the last ``ring`` positions (a recency window) — overwritten
    entries are the oldest *dynamic* tokens; the head of the buffer (the
    compression-selected prefix) is preserved.
    ``total_appended`` counts decode appends so far (for the cycle phase).
    """
    ring = max(1, min(ring, capacity))
    ring_start = capacity - ring
    cyc = ring_start + total_appended % ring  # phase shared across rows; a ring
    return jnp.where(lengths < capacity, lengths, cyc).astype(jnp.int32)


def fill_from_selection(
    cache: SlotCache,
    layer: int,
    k_full: jnp.ndarray,  # (B, T, Hkv, Dh) post-RoPE prefill keys
    v_full: jnp.ndarray,  # (B, T, Hkv, Dh)
    sel_idx: jnp.ndarray,  # (B, Hkv, C) selected positions into T
    sel_len: jnp.ndarray,  # (B, Hkv) int32 retained counts (<= C)
    plan: PlanArrays,
    rows: Optional[jnp.ndarray] = None,  # (B,) global row ids for ownership
) -> SlotCache:
    """Scatter the compression-selected prefill KV into slot layout.

    ``rows`` overrides the global row ids used by the strided owner rule —
    required when prefilling a sub-batch destined for specific rows of a
    larger live cache (continuous batching admission, DESIGN.md §7).
    """
    L, S, B, C, Dh = cache.k.shape
    heads = plan.slot_head[layer]  # (S,)
    safe_heads = jnp.maximum(heads, 0)
    own = (plan.owner_mask(layer, B) if rows is None
           else plan.owner_mask_rows(layer, rows))  # (S, B)
    # per-slot gather: idx (S, B, C) over T
    idx = jnp.take(sel_idx, safe_heads, axis=1).transpose(1, 0, 2)  # (S, B, C)

    def gather_one(kf, vf, ix):  # kf: (T, Hkv, Dh), ix: (S, C)
        hh = safe_heads  # (S,)
        kv_h = kf[:, hh, :]  # (T, S, Dh)
        vv_h = vf[:, hh, :]
        k_s = jnp.take_along_axis(kv_h.transpose(1, 0, 2), ix[..., None], axis=1)
        v_s = jnp.take_along_axis(vv_h.transpose(1, 0, 2), ix[..., None], axis=1)
        return k_s, v_s  # (S, C, Dh)

    k_sel, v_sel = jax.vmap(gather_one)(k_full, v_full, idx.transpose(1, 0, 2))
    # (B, S, Csel, Dh) -> (S, B, Csel, Dh); pad Csel up to cache capacity
    k_sel = k_sel.transpose(1, 0, 2, 3).astype(cache.k.dtype)
    v_sel = v_sel.transpose(1, 0, 2, 3).astype(cache.v.dtype)
    if k_sel.shape[2] < C:
        pad = C - k_sel.shape[2]
        k_sel = jnp.pad(k_sel, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_sel = jnp.pad(v_sel, ((0, 0), (0, 0), (0, pad), (0, 0)))
    elif k_sel.shape[2] > C:
        raise ValueError(
            f"selection capacity {k_sel.shape[2]} exceeds cache capacity {C}")
    lens = jnp.take(sel_len, safe_heads, axis=1).T  # (S, B)
    lens = jnp.where(own, lens, 0).astype(jnp.int32)
    k_sel = jnp.where(own[..., None, None], k_sel, 0)
    v_sel = jnp.where(own[..., None, None], v_sel, 0)
    # entry positions == selected indices (prefill positions are arange(T));
    # pad/invalid entries get -1 (always outside any window, masked by length)
    pos_sel = idx.astype(jnp.int32)  # (S, B, C_sel)
    if pos_sel.shape[2] < C:
        pos_sel = jnp.pad(pos_sel, ((0, 0), (0, 0), (0, C - pos_sel.shape[2])),
                          constant_values=-1)
    pos_sel = jnp.where(own[..., None], pos_sel, -1)
    return SlotCache(
        k=cache.k.at[layer].set(k_sel),
        v=cache.v.at[layer].set(v_sel),
        lengths=cache.lengths.at[layer].set(lens),
        pos=cache.pos.at[layer].set(pos_sel),
        positions=cache.positions,
    )


def append_selection(
    cache: SlotCache,
    layer: int,
    k_full: jnp.ndarray,  # (B, Ck, Hkv, Dh) post-RoPE chunk keys
    v_full: jnp.ndarray,  # (B, Ck, Hkv, Dh)
    sel_idx: jnp.ndarray,  # (B, Hkv, Csel) selected positions into Ck
    sel_len: jnp.ndarray,  # (B, Hkv) int32 retained counts (<= Csel)
    plan: PlanArrays,
    rows: jnp.ndarray,  # (B,) global row ids for ownership
    start: jnp.ndarray,  # (B,) int32 absolute position of chunk token 0
) -> SlotCache:
    """Append a chunk's compression-selected KV *after* existing entries.

    The chunked-prefill counterpart of `fill_from_selection` (DESIGN.md
    §14): instead of replacing the layer's slice, selected entries land at
    columns ``lengths .. lengths+keep`` and entry positions are made
    absolute (``start + sel_idx``), so each chunk's keep-set accumulates
    into the slot layout and attention over the cache stays
    order-independent (keys are post-RoPE, positions explicit).  The caller
    guarantees headroom (``keep <= C - lengths``); columns past capacity are
    dropped defensively.
    """
    L, S, B, C, Dh = cache.k.shape
    heads = plan.slot_head[layer]  # (S,)
    safe_heads = jnp.maximum(heads, 0)
    own = plan.owner_mask_rows(layer, rows)  # (S, B)
    idx = jnp.take(sel_idx, safe_heads, axis=1).transpose(1, 0, 2)  # (S,B,Cs)

    def gather_one(kf, vf, ix):  # kf: (Ck, Hkv, Dh), ix: (S, Csel)
        hh = safe_heads  # (S,)
        kv_h = kf[:, hh, :]  # (Ck, S, Dh)
        vv_h = vf[:, hh, :]
        k_s = jnp.take_along_axis(kv_h.transpose(1, 0, 2), ix[..., None], axis=1)
        v_s = jnp.take_along_axis(vv_h.transpose(1, 0, 2), ix[..., None], axis=1)
        return k_s, v_s  # (S, Csel, Dh)

    k_sel, v_sel = jax.vmap(gather_one)(k_full, v_full, idx.transpose(1, 0, 2))
    k_sel = k_sel.transpose(1, 0, 2, 3).astype(cache.k.dtype)  # (S,B,Cs,Dh)
    v_sel = v_sel.transpose(1, 0, 2, 3).astype(cache.v.dtype)
    Csel = k_sel.shape[2]
    lens_new = jnp.take(sel_len, safe_heads, axis=1).T  # (S, B)
    lens_new = jnp.where(own, lens_new, 0).astype(jnp.int32)
    # absolute entry positions; invalid tail masked out by the column drop
    pos_sel = (start[None, :, None] + idx).astype(jnp.int32)  # (S, B, Csel)
    cur = cache.lengths[layer]  # (S, B)
    j = jnp.arange(Csel, dtype=jnp.int32)
    cols = cur[:, :, None] + j[None, None, :]  # (S, B, Csel)
    valid = j[None, None, :] < lens_new[:, :, None]
    cols = jnp.where(valid, cols, C)  # C = out of range -> mode="drop"
    s_ix = jnp.arange(S)[:, None, None]
    b_ix = jnp.arange(B)[None, :, None]
    k_layer = cache.k[layer].at[s_ix, b_ix, cols].set(k_sel, mode="drop")
    v_layer = cache.v[layer].at[s_ix, b_ix, cols].set(v_sel, mode="drop")
    p_layer = cache.pos[layer].at[s_ix, b_ix, cols].set(pos_sel, mode="drop")
    new_len = jnp.minimum(cur + lens_new, C)
    return SlotCache(
        k=cache.k.at[layer].set(k_layer),
        v=cache.v.at[layer].set(v_layer),
        lengths=cache.lengths.at[layer].set(new_len),
        pos=cache.pos.at[layer].set(p_layer),
        positions=cache.positions,
    )


# ---------------------------------------------------------------------------
# Row masks and replan migration
# ---------------------------------------------------------------------------


def rows_to_mask(rows, batch: int) -> jnp.ndarray:
    """(B,) bool mask from int row indices (bool input passes through)."""
    rows = jnp.asarray(rows)
    if rows.dtype == jnp.bool_:
        return rows
    return jnp.zeros((batch,), jnp.bool_).at[rows].set(True)


def gather_head_layout(cache: SlotCache, plan: PlanArrays):
    """Slot layout → original head layout.

    Returns ``(k, v, lengths, pos)`` with shapes ``(L, H, B, C, Dh)`` /
    ``(L, H, B)`` / ``(L, H, B, C)``.  Every (head, row) pair has exactly one
    owning slot (replicas partition the batch), so a masked sum over slots
    recovers the unique per-head entry.
    """
    L, S, B, C, Dh = cache.k.shape
    H = int(plan.first_slot.shape[1])
    own = plan.owner_mask_all(B)  # (L, S, B)
    onehot = (plan.slot_head[:, :, None]
              == jnp.arange(H, dtype=jnp.int32)[None, None, :])  # (L, S, H)
    ow = own.astype(jnp.float32)
    oh = onehot.astype(jnp.float32)
    k = jnp.einsum("lsh,lsb,lsbcd->lhbcd", oh, ow, cache.k.astype(jnp.float32))
    v = jnp.einsum("lsh,lsb,lsbcd->lhbcd", oh, ow, cache.v.astype(jnp.float32))
    lens = jnp.einsum("lsh,lsb,lsb->lhb", oh, ow,
                      cache.lengths.astype(jnp.float32))
    pos = jnp.einsum("lsh,lsb,lsbc->lhbc", oh, ow,
                     cache.pos.astype(jnp.float32))
    return (k.astype(cache.k.dtype), v.astype(cache.v.dtype),
            lens.astype(jnp.int32), jnp.round(pos).astype(jnp.int32))


def migrate_cache(cache: SlotCache, old_plan: PlanArrays,
                  new_plan: PlanArrays) -> SlotCache:
    """Re-layout a live cache for a new HeadPlacement (online replanning).

    Gathers the cache back to original head layout under ``old_plan``, then
    scatters it into the slot/ownership layout of ``new_plan``.  Capacity and
    the slot-grid width must match (replans keep ``slots_per_shard`` fixed);
    row ``positions`` are plan-independent and carried through unchanged.
    """
    L, S, B, C, Dh = cache.k.shape
    if new_plan.slot_head.shape != old_plan.slot_head.shape:
        raise ValueError(
            f"plan slot grids differ: {old_plan.slot_head.shape} vs "
            f"{new_plan.slot_head.shape}")
    k_h, v_h, len_h, pos_h = gather_head_layout(cache, old_plan)
    heads = jnp.maximum(new_plan.slot_head, 0)  # (L, S)
    own = new_plan.owner_mask_all(B)  # (L, S, B)
    idx = heads[:, :, None, None, None]
    k_s = jnp.take_along_axis(k_h, idx, axis=1)  # (L, S, B, C, Dh)
    v_s = jnp.take_along_axis(v_h, idx, axis=1)
    len_s = jnp.take_along_axis(len_h, heads[:, :, None], axis=1)
    pos_s = jnp.take_along_axis(pos_h, heads[:, :, None, None], axis=1)
    return SlotCache(
        k=jnp.where(own[..., None, None], k_s, 0),
        v=jnp.where(own[..., None, None], v_s, 0),
        lengths=jnp.where(own, len_s, 0).astype(jnp.int32),
        pos=jnp.where(own[..., None], pos_s, -1),
        positions=cache.positions,
    )
