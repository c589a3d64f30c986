"""Paged slot-layout KV cache: block pools + block tables (DESIGN.md §9).

The live KV cache.  The slot layout prefill writes (`cache/slot_cache.py`)
pads every (slot, row) to the static capacity ``C``, so a head compressed
to 12% of ``C`` would still reserve 100% of it.  The paged layout stores the
same logical cache in fixed-size blocks allocated proportional to each
(slot, row)'s *realized* retained length:

    k_pool, v_pool : (L, N, bs, Dh)   N blocks of bs tokens per layer
    pos_pool       : (L, N, bs) int32 absolute entry positions (−1 = empty)
    block_table    : (L, S, B, M) int32  block ids per (slot, row);
                                         0 = the reserved null block
    lengths        : (L, S, B) int32  same semantics as the slot cache
    positions      : (B,) int32       next absolute position per row
    k_scale, v_scale : (L, N) fp32    per-block dequant scales, present only
                                      under a quantized ``kv_dtype``
                                      (None on the fp32 path — DESIGN.md §15)

``M = ceil(C / bs)`` so a fully-retained row is still representable; the win
is that *partially* retained rows (the common case under imbalanced
compression) only pin ``ceil(len / bs)`` blocks.  Logical column ``c`` of a
(slot, row) lives at offset ``c % bs`` of block ``table[c // bs]``, so a
block gather followed by a reshape reconstructs the exact contiguous
``(S, B, C, Dh)`` slot view (`paged_to_slot`, the jnp oracle) — decode
masking, ring appends, and the ownership rule (§2) are the slot layout's.

Allocation topology (which table entries are nonzero) is owned by the
host-side ``BlockPool``; every function here trusts the table it is given.
All ops are pure on the array pytree, mirroring the slot-cache API.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache.slot_cache import SlotCache, ring_write_index, rows_to_mask
from repro.paging import kvquant
from repro.paging.block_pool import BlockPool, PagingConfig, blocks_for_tokens


@jax.tree_util.register_dataclass
@dataclass
class PagedCache:
    k_pool: jnp.ndarray  # (L, N, bs, Dh)
    v_pool: jnp.ndarray  # (L, N, bs, Dh)
    pos_pool: jnp.ndarray  # (L, N, bs) int32
    block_table: jnp.ndarray  # (L, S, B, M) int32; 0 = null block
    lengths: jnp.ndarray  # (L, S, B) int32
    positions: jnp.ndarray  # (B,) int32
    k_scale: Optional[jnp.ndarray] = None  # (L, N) fp32 per-block scales
    v_scale: Optional[jnp.ndarray] = None  # (L, N) fp32 per-block scales

    @property
    def block_size(self) -> int:
        return self.k_pool.shape[2]

    @property
    def n_blocks(self) -> int:
        return self.k_pool.shape[1]

    @property
    def max_blocks(self) -> int:
        return self.block_table.shape[3]

    @property
    def n_slots(self) -> int:
        return self.block_table.shape[1]


def max_blocks_per_row(capacity: int, block_size: int) -> int:
    return blocks_for_tokens(capacity, block_size)


def block_hbm_bytes(block_size: int, head_dim: int, dtype,
                    quantized: bool) -> int:
    """HBM bytes one K+V block pins: payload plus, when quantized, the two
    fp32 scale-pool entries (the bytes-aware admission unit, DESIGN.md §15)."""
    item = jnp.dtype(dtype).itemsize
    return 2 * block_size * head_dim * item + (8 if quantized else 0)


def init_paged_cache(
    n_layers: int, n_slots: int, batch: int, capacity: int, head_dim: int,
    paging: PagingConfig, dtype=jnp.bfloat16,
    partitions: Tuple[int, int] = (1, 1),
    kv_quant: Optional[kvquant.KVQuantSpec] = None,
) -> Tuple[PagedCache, BlockPool]:
    """Empty paged cache + its allocator.

    ``paging.n_blocks == 0`` sizes the pool to the slot-cache worst case
    (``S·B·M + 1`` per layer-partition): every (slot, row) can be fully
    allocated, so this mode can never preempt — it trades no memory but
    validates the paged data path end to end.  ``paging.pool_hbm_bytes``
    instead sizes the pool from a byte budget using the *actual* storage
    dtype's block footprint — the bytes-aware admission mode (§15): a
    quantized pool fits ~itemsize-ratio more blocks in the same budget, and
    the downstream block-count admission needs no other change.

    ``partitions = (slot_parts, row_parts)`` (the mesh executor,
    DESIGN.md §10) splits each layer's pool into equal partitions indexed
    ``p = slot_part · row_parts + row_part`` — blocks for (slot s, row r)
    live in the partition of (s's model shard, r's data shard), so the
    pool array shards cleanly over ``(model, data)`` and every append and
    gather stays device-local.  A configured ``paging.n_blocks`` is
    rounded up to a multiple of the partition count.

    ``kv_quant`` (from ``kvquant.spec_from_paging``) switches the pools to
    int8 code storage with zero-initialized (L, N) scale pools; ``dtype``
    then only enters the worst-case/byte pool sizing as the *logical* model
    dtype, not the storage dtype.
    """
    bs = paging.block_size
    M = max_blocks_per_row(capacity, bs)
    slot_parts, row_parts = partitions
    if slot_parts < 1 or n_slots % slot_parts:
        raise ValueError(
            f"{n_slots} slots do not split into {slot_parts} partitions")
    if row_parts < 1 or batch % row_parts:
        raise ValueError(
            f"{batch} rows do not split into {row_parts} partitions")
    n_partitions = slot_parts * row_parts
    pool_dtype = jnp.int8 if kv_quant is not None else dtype
    if paging.n_blocks:
        part = -(-paging.n_blocks // n_partitions)  # ceil: round up
    elif paging.pool_hbm_bytes:
        per_block = block_hbm_bytes(bs, head_dim, pool_dtype,
                                    kv_quant is not None)
        total = paging.pool_hbm_bytes // (n_layers * per_block)
        part = max(2, total // n_partitions)  # floor: budget is a cap
    else:
        part = (n_slots // slot_parts) * (batch // row_parts) * M + 1
    n_blocks = part * n_partitions

    def scale():  # one buffer per pool: donation cannot alias a shared one
        return (jnp.zeros((n_layers, n_blocks), jnp.float32)
                if kv_quant is not None else None)

    cache = PagedCache(
        k_pool=jnp.zeros((n_layers, n_blocks, bs, head_dim), pool_dtype),
        v_pool=jnp.zeros((n_layers, n_blocks, bs, head_dim), pool_dtype),
        pos_pool=jnp.full((n_layers, n_blocks, bs), -1, jnp.int32),
        block_table=jnp.zeros((n_layers, n_slots, batch, M), jnp.int32),
        lengths=jnp.zeros((n_layers, n_slots, batch), jnp.int32),
        positions=jnp.zeros((batch,), jnp.int32),
        k_scale=scale(), v_scale=scale(),
    )
    return cache, BlockPool(n_layers, n_blocks, n_partitions=n_partitions)


# ---------------------------------------------------------------------------
# Views
# ---------------------------------------------------------------------------
# ref.paged_fairkv_decode_ref carries its own single-layer block gather
# (oracles stay self-contained so the parity test cannot compare a bug
# against itself).


def paged_to_slot(cache: PagedCache, capacity: int,
                  kinds: Optional[jnp.ndarray] = None,
                  out_dtype=None) -> SlotCache:
    """Full materialization into a SlotCache (replan migration, prefix
    seeds).

    Entries outside each (slot, row)'s valid prefix are zeroed (pos −1) so
    the result obeys the slot-cache masking contract exactly; the decode
    output over the result is bit-identical to the paged path.

    **Deep copy by construction**: the result is a pure gather — pool
    tensors are never aliased into the output, so materializing rows whose
    blocks are shared (refcount > 1 under prefix reuse, DESIGN.md §14)
    copies the shared content and can never mutate it.  The pool-
    conservation regression test in tests/test_prefix.py pins this down.

    Quantized pools dequantize through the scale pools — the *same*
    scale/kind interpretation the decode kernel applies (DESIGN.md §15), so
    slot↔paged migration stays bit-consistent with the decode path.
    ``kinds`` is the (L, S) per-slot kind grid (``kvquant.slot_kinds``;
    all-int8 assumed when omitted); ``out_dtype`` casts the dequantized
    values (the model dtype — default fp32).
    """
    L, N, bs, Dh = cache.k_pool.shape
    _, S, B, M = cache.block_table.shape
    gids = (jnp.arange(L, dtype=jnp.int32)[:, None, None, None] * N
            + jnp.maximum(cache.block_table, 0))  # (L, S, B, M)
    k = cache.k_pool.reshape(L * N, bs, Dh)[gids]  # (L, S, B, M, bs, Dh)
    v = cache.v_pool.reshape(L * N, bs, Dh)[gids]
    if cache.k_scale is not None:
        kind = (jnp.zeros((L, S), jnp.int32) if kinds is None
                else jnp.asarray(kinds, jnp.int32))
        kind = kind[:, :, None, None, None, None]
        ksc = cache.k_scale.reshape(-1)[gids][..., None, None]
        vsc = cache.v_scale.reshape(-1)[gids][..., None, None]
        k = kvquant.decode(k, ksc, kind)
        v = kvquant.decode(v, vsc, kind)
        if out_dtype is not None:
            k, v = k.astype(out_dtype), v.astype(out_dtype)
    k = k.reshape(L, S, B, M * bs, Dh)
    v = v.reshape(L, S, B, M * bs, Dh)
    pos = cache.pos_pool.reshape(L * N, bs)[gids].reshape(L, S, B, M * bs)
    k, v, pos = k[..., :capacity, :], v[..., :capacity, :], pos[..., :capacity]
    valid = (jnp.arange(capacity, dtype=jnp.int32)[None, None, None, :]
             < cache.lengths[..., None])  # (L, S, B, C)
    return SlotCache(
        k=jnp.where(valid[..., None], k, 0),
        v=jnp.where(valid[..., None], v, 0),
        lengths=cache.lengths,
        pos=jnp.where(valid, pos, -1),
        positions=cache.positions,
    )


# ---------------------------------------------------------------------------
# Writes
# ---------------------------------------------------------------------------


def paged_append_token(
    cache: PagedCache,
    layer: int,
    k_new: jnp.ndarray,  # (S, B, Dh) post-RoPE
    v_new: jnp.ndarray,  # (S, B, Dh)
    own: jnp.ndarray,  # (S, B) bool
    decode_step: jnp.ndarray,  # scalar int32: appends since prefill
    capacity: int,
    ring: int = 128,
    table_layer: Optional[jnp.ndarray] = None,  # (S, B, M) addressing override
    kinds: Optional[jnp.ndarray] = None,  # (S,) per-slot kind codes
) -> PagedCache:
    """Append one token for owned (slot, row) pairs.

    The write index (including the full-row recency ring) is the slot
    layout's `ring_write_index`; the backend must have allocated the block
    covering it (`prepare_decode`) before the jitted step runs.  Unowned
    pairs — and, defensively, owned pairs whose block is missing — are
    redirected into the null block, never corrupting live data.  Owned
    pairs' lengths grow by one, up to ``capacity``.

    ``table_layer`` overrides the table used for *addressing* only (the
    stored ``block_table`` is untouched): the mesh executor passes a
    partition-localized view when pool ids in the stored table are global
    but the pool array in scope is one shard's partition (DESIGN.md §10).

    Quantized pools (``cache.k_scale is not None``) quantize on write
    (DESIGN.md §15): the target block's scale grows monotonically
    (``max(old, amax(|token|)/qmax)``), the whole block is dequantized at
    the old scale, the token inserted, and the block re-encoded at the new
    scale.  When the scale did not grow the re-encode is an exact identity
    on the untouched entries (codes round-trip), so repeated appends into
    one block never compound error.  ``kinds`` carries the (S,) per-slot
    interpretation (``kvquant.slot_kinds`` row — all-int8 when omitted);
    invalid pairs rewrite their gathered null-block values unchanged, so
    duplicate null-redirected scatters stay write-idempotent.
    """
    bs = cache.block_size
    lengths = cache.lengths[layer]  # (S, B)
    idx = ring_write_index(lengths, decode_step, capacity, ring)  # (S, B)
    blk, off = idx // bs, idx % bs
    table = cache.block_table[layer] if table_layer is None else table_layer
    bid = jnp.take_along_axis(table, blk[..., None], axis=2)[..., 0]  # (S, B)
    valid = own & (bid > 0)
    bid = jnp.where(valid, bid, 0)
    kl, vl, pl = cache.k_pool[layer], cache.v_pool[layer], cache.pos_pool[layer]
    p_new = jnp.broadcast_to(cache.positions[None, :], own.shape)
    p_upd = jnp.where(valid, p_new, pl[bid, off]).astype(jnp.int32)
    new_len = jnp.where(own, jnp.minimum(lengths + 1, capacity), lengths)
    if cache.k_scale is None:
        k_upd = jnp.where(valid[..., None], k_new.astype(kl.dtype),
                          kl[bid, off])
        v_upd = jnp.where(valid[..., None], v_new.astype(vl.dtype),
                          vl[bid, off])
        k_pool = cache.k_pool.at[layer].set(kl.at[bid, off].set(k_upd))
        v_pool = cache.v_pool.at[layer].set(vl.at[bid, off].set(v_upd))
        k_scale = v_scale = None
    else:
        kind = (jnp.zeros((own.shape[0],), jnp.int32) if kinds is None
                else jnp.asarray(kinds, jnp.int32))
        kind_sb = jnp.broadcast_to(kind[:, None], own.shape)  # (S, B)
        qmax = kvquant.qmax_of(kind_sb)
        ksc, vsc = cache.k_scale[layer], cache.v_scale[layer]  # (N,)
        onehot = (jnp.arange(bs, dtype=jnp.int32)[None, None, :]
                  == off[..., None])  # (S, B, bs)
        ins = valid[..., None] & onehot  # entries receiving the new token

        def requant(pool_l, scale_l, token):
            token = token.astype(jnp.float32)
            old_s = scale_l[bid]  # (S, B)
            new_s = jnp.where(
                valid,
                jnp.maximum(old_s, jnp.max(jnp.abs(token), axis=-1) / qmax),
                old_s)
            block = kvquant.decode(pool_l[bid], old_s[..., None, None],
                                   kind_sb[..., None, None])  # (S, B, bs, Dh)
            block = jnp.where(ins[..., None], token[:, :, None, :], block)
            codes = kvquant.encode(block, new_s[..., None, None],
                                   kind_sb[..., None, None])
            codes = jnp.where(valid[..., None, None], codes, pool_l[bid])
            return (pool_l.at[bid].set(codes),
                    scale_l.at[bid].set(jnp.where(valid, new_s, old_s)))

        kl_new, ksc_new = requant(kl, ksc, k_new)
        vl_new, vsc_new = requant(vl, vsc, v_new)
        k_pool = cache.k_pool.at[layer].set(kl_new)
        v_pool = cache.v_pool.at[layer].set(vl_new)
        k_scale = cache.k_scale.at[layer].set(ksc_new)
        v_scale = cache.v_scale.at[layer].set(vsc_new)
    return PagedCache(
        k_pool=k_pool,
        v_pool=v_pool,
        pos_pool=cache.pos_pool.at[layer].set(pl.at[bid, off].set(p_upd)),
        block_table=cache.block_table,
        lengths=cache.lengths.at[layer].set(new_len.astype(jnp.int32)),
        positions=cache.positions,
        k_scale=k_scale, v_scale=v_scale,
    )


def paginate_rows(
    cache: PagedCache,
    sub: SlotCache,
    rows: jnp.ndarray,  # (B_sub,) target global rows
    table_sub,  # (L, S, B_sub, M) int32 freshly allocated ids
    table_store=None,  # (L, S, B_sub, M) int32 stored ids
    kinds=None,  # (L, S) int32 per-slot kind codes
) -> PagedCache:
    """Copy a prefilled slot sub-cache into freshly allocated blocks.

    Pure and traceable (the tables may be host or device int32 arrays):
    the paged backend runs it inside one jitted program with the live
    cache donated (`backend.splice`), so the scatters write the pools in
    place; called eagerly it is the reference that program is tested
    against.

    ``table_sub`` comes from the backend's allocator (`BlockPool.alloc`):
    entry ``[l, s, b, j]`` is the block holding columns
    ``[j·bs, (j+1)·bs)`` of that (slot, row), 0 past the allocated count.
    One global scatter per tensor; unallocated tail blocks are redirected
    into the null block.  The target rows' table/lengths/positions are fully
    replaced (they must have been released first).

    ``table_store`` (optional) decouples the *stored* block table from the
    write addressing: shared-prefix admission (DESIGN.md §14) stores the
    full table (shared donor blocks + fresh tail) while passing a write
    table whose shared entries are zeroed — the null-redirect then
    guarantees refcount>1 blocks are never written, which is the
    copy-on-write immutability rule.  Default: store ``table_sub`` itself.

    Quantized pools block-quantize the sub-cache on the way in
    (``kvquant.quantize_blocks``): per-block scales from the valid-entry
    amax, invalid entries zero-coded, scales scattered through the same
    null-redirected gids as the payload (DESIGN.md §15).  ``kinds`` is the
    (L, S) per-slot interpretation grid (all-int8 when omitted).
    """
    L, N, bs, Dh = cache.k_pool.shape
    _, S, B_sub, C, _ = sub.k.shape
    M = table_sub.shape[3]
    pad = M * bs - C
    if pad < 0:
        raise ValueError(f"sub capacity {C} exceeds table span {M * bs}")
    k_sub = jnp.pad(sub.k, ((0, 0),) * 3 + ((0, pad), (0, 0)))
    v_sub = jnp.pad(sub.v, ((0, 0),) * 3 + ((0, pad), (0, 0)))
    p_sub = jnp.pad(sub.pos, ((0, 0),) * 3 + ((0, pad),), constant_values=-1)
    k_scales = v_scales = None
    if cache.k_scale is not None:
        kind = (jnp.zeros((L, S), jnp.int32) if kinds is None
                else jnp.asarray(kinds, jnp.int32))
        kind = kind[:, :, None, None]  # broadcasts over (L, S, B_sub, M)
        k_sub, k_scales = kvquant.quantize_blocks(k_sub, p_sub, bs, kind)
        v_sub, v_scales = kvquant.quantize_blocks(v_sub, p_sub, bs, kind)
    k_sub = k_sub.reshape(L, S, B_sub, M, bs, Dh)
    v_sub = v_sub.reshape(L, S, B_sub, M, bs, Dh)
    p_sub = p_sub.reshape(L, S, B_sub, M, bs)
    # (layer, block) scatter targets, indexed on the pools' own (L, N) axes
    # so a block axis sharded over a mesh stays sharded; unallocated entries
    # null-redirect to block 0 of layer 0
    tbl = jnp.asarray(table_sub, jnp.int32)
    live = tbl > 0
    lid = jnp.where(live, jnp.arange(L, dtype=jnp.int32)[:, None, None, None],
                    0)
    bid = jnp.where(live, tbl, 0)
    k_pool = cache.k_pool.at[lid, bid].set(k_sub.astype(cache.k_pool.dtype))
    v_pool = cache.v_pool.at[lid, bid].set(v_sub.astype(cache.v_pool.dtype))
    pos_pool = cache.pos_pool.at[lid, bid].set(p_sub)
    k_scale, v_scale = cache.k_scale, cache.v_scale
    if k_scales is not None:
        k_scale = k_scale.at[lid, bid].set(k_scales)
        v_scale = v_scale.at[lid, bid].set(v_scales)
    rows = jnp.asarray(rows, jnp.int32)
    stored = table_sub if table_store is None else table_store
    return PagedCache(
        k_pool=k_pool, v_pool=v_pool, pos_pool=pos_pool,
        block_table=cache.block_table.at[:, :, rows, :].set(
            jnp.asarray(stored, jnp.int32)),
        lengths=cache.lengths.at[:, :, rows].set(sub.lengths),
        positions=cache.positions.at[rows].set(sub.positions),
        k_scale=k_scale, v_scale=v_scale,
    )


def release_rows(cache: PagedCache, rows) -> PagedCache:
    """Device half of row retirement: clear table/lengths/positions.

    ``rows`` is a (B,) bool mask or an int index array.  Pool contents are
    left in place — unreferenced blocks are recycled by the host allocator
    (`BlockPool.decref`), which the backend drives.
    """
    m = rows_to_mask(rows, cache.positions.shape[0])
    return PagedCache(
        k_pool=cache.k_pool, v_pool=cache.v_pool, pos_pool=cache.pos_pool,
        block_table=jnp.where(m[None, None, :, None], 0, cache.block_table),
        lengths=jnp.where(m[None, None, :], 0, cache.lengths),
        positions=jnp.where(m, 0, cache.positions),
        k_scale=cache.k_scale, v_scale=cache.v_scale,
    )


def build_table(
    lengths: np.ndarray,  # (L, S, B_sub) realized retained lengths
    pool: BlockPool,
    block_size: int,
    max_blocks: int,
    own: Optional[np.ndarray] = None,  # (L, S, B_sub) bool ownership
    partitions: Tuple[int, int] = (1, 1),  # (slot_parts, row_parts)
    rows: Optional[np.ndarray] = None,  # (B_sub,) target *global* row ids
    n_rows: Optional[int] = None,  # global batch width (row partitioning)
) -> np.ndarray:
    """Allocate blocks proportional to realized lengths → (L, S, B_sub, M)
    table.

    Owned (slot, row) pairs get at least one block even at length 0 so the
    first decode append always has a home (matching the slot cache, where
    every owned pair can append immediately).  Under a partitioned pool
    (mesh executor) a (slot s, global row r) pair draws from partition
    ``(s // (S/slot_parts)) · row_parts + r // (n_rows/row_parts)`` — its
    (model, data) shard's pool slice; ``rows`` are the target global row
    ids of the (possibly sub-batch) ``lengths`` columns.  Atomic: on
    ``PoolExhausted`` everything allocated so far is returned to the pool
    before re-raising.
    """
    L, S, B = lengths.shape
    slot_parts, row_parts = partitions
    if pool.n_partitions != slot_parts * row_parts:
        raise ValueError(
            f"pool has {pool.n_partitions} partitions, expected "
            f"{slot_parts}x{row_parts}")
    if S % slot_parts:
        raise ValueError(
            f"{S} slots do not split into {slot_parts} partitions")
    s_per = S // slot_parts
    rows = np.arange(B) if rows is None else np.asarray(rows, np.int64)
    n_rows = B if n_rows is None else int(n_rows)
    b_per = -(-n_rows // row_parts)
    row_part = rows // b_per  # (B_sub,) data partition of each column
    need = -(-np.asarray(lengths, np.int64) // block_size)  # ceil-div
    if own is not None:
        need = np.maximum(need, np.asarray(own, np.int64))
    if need.max(initial=0) > max_blocks:
        raise ValueError(
            f"row needs {need.max()} blocks > max_blocks {max_blocks}")
    table = np.zeros((L, S, B, max_blocks), np.int32)
    fill = (np.arange(max_blocks, dtype=np.int64)[None, None, :]
            < need[..., None])  # (L, S, B, M) slots to fill
    done = []  # (layer, ids) already allocated, for rollback
    try:
        for l in range(L):
            for sp in range(slot_parts):
                sl = slice(sp * s_per, (sp + 1) * s_per)
                for rp in range(row_parts):
                    cols = np.nonzero(row_part == rp)[0]
                    if cols.size == 0:
                        continue
                    sub_need = need[l, sl][:, cols]
                    ids = pool.alloc(l, int(sub_need.sum()),
                                     partition=sp * row_parts + rp)
                    done.append((l, ids))
                    # row-major mask == sequential per-(slot,row) fill
                    part = np.zeros((s_per, cols.size, max_blocks), np.int32)
                    part[fill[l, sl][:, cols]] = ids
                    sub = table[l, sl]
                    sub[:, cols] = part
                    table[l, sl] = sub
    except Exception:
        for l, ids in done:
            if ids:
                pool.decref(l, ids)
        raise
    return table
