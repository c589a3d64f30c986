"""Pure-jnp oracles for the Pallas kernels.

These are the semantics-defining references: kernels must match them (fp32
accumulation) across the shape/dtype sweeps in tests/test_kernels.py and
tests/test_paged_kernel.py.  They are also the production fallback path on
CPU and in the XLA-only dry-run.  The decode chain is `fairkv_decode_ref`
(one query over a contiguous slot-layout view) → `fairkv_decode_mq_ref`
(the speculative window) → `paged_fairkv_decode_ref` (the same math over
block pools, the oracle of the serving kernel).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30

# fp8 availability probe — deliberately duplicated from repro.paging.kvquant:
# oracles stay self-contained (no imports from the subsystems they validate)
_HAS_FP8 = hasattr(jnp, "float8_e4m3fn")


def dequant_block_codes(codes, scale, kind):
    """int8 block codes → fp32 under per-block ``scale`` and kind
    (0 = int8, 1 = fp8-bitcast) — the oracle's own copy of the paged-pool
    dequant semantics (DESIGN.md §15).  fp8 NaN bit patterns (possible in
    never-written pool garbage) flush to 0 so masked positions cannot
    poison the probability-weighted sum through 0·NaN.
    """
    f = codes.astype(jnp.float32)
    if _HAS_FP8:
        f8 = jax.lax.bitcast_convert_type(
            codes, jnp.float8_e4m3fn).astype(jnp.float32)
        f8 = jnp.where(f8 == f8, f8, 0.0)
        f = jnp.where(kind == 1, f8, f)
    return f * scale


def fairkv_decode_ref(
    q: jnp.ndarray,  # (B, S, G, Dh) — one new query per row per slot group
    k: jnp.ndarray,  # (S, B, C, Dh) slot-layout cache keys (post-RoPE)
    v: jnp.ndarray,  # (S, B, C, Dh)
    lengths: jnp.ndarray,  # (S, B) int32 — retained tokens per (slot, row)
    attn_cap: float = 0.0,
    k_pos: Optional[jnp.ndarray] = None,  # (S, B, C) absolute entry positions
    q_pos: Optional[jnp.ndarray] = None,  # (B,) current positions
    window: int = 0,  # >0: sliding-window mask via k_pos/q_pos
) -> jnp.ndarray:
    """Decode attention over the slot-layout cache.

    Rows a slot does not own have ``lengths == 0`` and yield exactly 0 output
    (their o-projection contribution vanishes, so the cross-shard psum
    reassembles the batch — DESIGN.md §2).
    Returns (B, S, G, Dh).
    """
    B, S, G, Dh = q.shape
    C = k.shape[2]
    scores = jnp.einsum("bsgd,sbcd->bsgc", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(Dh)
    if attn_cap > 0:
        scores = attn_cap * jnp.tanh(scores / attn_cap)
    valid = jnp.arange(C)[None, None, :] < lengths.transpose(1, 0)[..., None]
    if window > 0:
        assert k_pos is not None and q_pos is not None
        in_win = k_pos.transpose(1, 0, 2) > (q_pos[:, None, None] - window)
        valid &= in_win
    scores = jnp.where(valid[:, :, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    nonempty = valid.any(axis=-1)[:, :, None, None]
    probs = jnp.where(nonempty, probs, 0.0)
    out = jnp.einsum("bsgc,sbcd->bsgd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def fairkv_decode_mq_ref(
    q: jnp.ndarray,  # (B, S, Q, G, Dh) — Q query positions per row per slot
    k: jnp.ndarray,  # (S, B, C, Dh) slot-layout cache keys (post-RoPE)
    v: jnp.ndarray,  # (S, B, C, Dh)
    lengths: jnp.ndarray,  # (S, B) int32 — retained tokens AFTER the appends
    attn_cap: float = 0.0,
    k_pos: Optional[jnp.ndarray] = None,  # (S, B, C) absolute entry positions
    q_pos: Optional[jnp.ndarray] = None,  # (B,) position of query index 0
    q_lens: Optional[jnp.ndarray] = None,  # (B,) valid queries per row (<= Q)
    window: int = 0,
) -> jnp.ndarray:
    """Multi-query decode attention — the speculative-verify oracle.

    Query index ``i`` of row ``b`` sits at absolute position ``q_pos[b]+i``
    and attends causally *within the speculative window*: with
    ``qn = q_lens[b]`` valid queries and ``lengths`` counting the cache
    after all ``qn`` appends, query ``i`` sees the first
    ``lengths - (qn - 1 - i)`` entries (its own token included, later
    speculative tokens excluded).  Query indices at or past ``qn`` are
    garbage lanes (the scheduler masks them downstream); they are clamped
    to the full length so they still compute finite values.  With Q == 1
    and ``q_lens == 1`` this is exactly `fairkv_decode_ref`.
    Returns (B, S, Q, G, Dh).
    """
    B, S, Q, G, Dh = q.shape
    C = k.shape[2]
    if q_lens is None:
        q_lens = jnp.full((B,), Q, jnp.int32)
    scores = jnp.einsum("bsqgd,sbcd->bsqgc", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(Dh)
    if attn_cap > 0:
        scores = attn_cap * jnp.tanh(scores / attn_cap)
    ln = lengths.transpose(1, 0)  # (B, S)
    qi = jnp.arange(Q)[None, None, :]  # (1, 1, Q)
    limit = ln[:, :, None] - (q_lens[:, None, None] - 1 - qi)
    limit = jnp.minimum(limit, ln[:, :, None])  # (B, S, Q)
    valid = jnp.arange(C)[None, None, None, :] < limit[..., None]  # (B,S,Q,C)
    if window > 0:
        assert k_pos is not None and q_pos is not None
        qp = q_pos[:, None, None] + qi  # (B, 1, Q)
        in_win = (k_pos.transpose(1, 0, 2)[:, :, None, :]
                  > (qp[..., None] - window))
        valid &= in_win
    scores = jnp.where(valid[:, :, :, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    nonempty = valid.any(axis=-1)[:, :, :, None, None]
    probs = jnp.where(nonempty, probs, 0.0)
    out = jnp.einsum("bsqgc,sbcd->bsqgd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_fairkv_decode_ref(
    q: jnp.ndarray,  # (B, S, G, Dh) or (B, S, Q, G, Dh) multi-query
    k_pool: jnp.ndarray,  # (N, bs, Dh) — one layer's pools
    v_pool: jnp.ndarray,  # (N, bs, Dh)
    pos_pool: jnp.ndarray,  # (N, bs) int32
    block_table: jnp.ndarray,  # (S, B, M) int32; 0 = null block
    lengths: jnp.ndarray,  # (S, B) int32
    capacity: int,
    attn_cap: float = 0.0,
    q_pos: Optional[jnp.ndarray] = None,
    window: int = 0,
    k_scale: Optional[jnp.ndarray] = None,  # (N,) fp32 per-block scales
    v_scale: Optional[jnp.ndarray] = None,  # (N,)
    kinds: Optional[jnp.ndarray] = None,  # (S,) int32 per-slot kind codes
    q_lens: Optional[jnp.ndarray] = None,  # (B,) valid queries (5D q only)
) -> jnp.ndarray:
    """Oracle for the paged decode kernel (`kernels.paged_fairkv_decode`).

    Gathers each (slot, row)'s blocks into the contiguous view the slot
    cache would hold — column ``c`` at offset ``c % bs`` of block
    ``table[c // bs]`` — then applies `fairkv_decode_ref` unchanged, so the
    paged path's semantics are *defined* as slot-layout semantics over the
    gathered view.  Quantized pools (``k_scale is not None``) dequantize
    the gathered blocks first (`dequant_block_codes`) — all-int8 kinds
    assumed when ``kinds`` is omitted.  A 5-D ``q`` selects the multi-query
    (speculative-verify) semantics of `fairkv_decode_mq_ref`.
    """
    ids = jnp.maximum(block_table, 0)
    S, B, M = ids.shape
    bs, Dh = k_pool.shape[1], k_pool.shape[2]
    k = k_pool[ids]  # (S, B, M, bs, Dh)
    v = v_pool[ids]
    if k_scale is not None:
        kind = (jnp.zeros((S,), jnp.int32) if kinds is None
                else jnp.asarray(kinds, jnp.int32))
        kind = kind[:, None, None, None, None]
        k = dequant_block_codes(k, k_scale[ids][..., None, None], kind)
        v = dequant_block_codes(v, v_scale[ids][..., None, None], kind)
    k = k.reshape(S, B, M * bs, Dh)[:, :, :capacity]
    v = v.reshape(S, B, M * bs, Dh)[:, :, :capacity]
    pos = pos_pool[ids].reshape(S, B, M * bs)[:, :, :capacity]
    if q.ndim == 5:
        return fairkv_decode_mq_ref(q, k, v, lengths, attn_cap, k_pos=pos,
                                    q_pos=q_pos, q_lens=q_lens, window=window)
    return fairkv_decode_ref(q, k, v, lengths, attn_cap, k_pos=pos,
                             q_pos=q_pos, window=window)


def snapkv_scores_ref(
    q_obs: jnp.ndarray,  # (B, W, Hq, Dh) observation-window queries (RoPE'd)
    k: jnp.ndarray,  # (B, T, Hkv, Dh)
    obs_positions: jnp.ndarray,  # (B, W)
    k_positions: jnp.ndarray,  # (B, T)
    attn_cap: float = 0.0,
) -> jnp.ndarray:
    """Observation-window importance: Σ_{w,g} softmax_T(q_w · k) → (B, Hkv, T).

    (Pooling is applied by the caller — it is cheap and policy-specific.)
    """
    B, W, Hq, Dh = q_obs.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q_obs.reshape(B, W, Hkv, G, Dh).astype(jnp.float32)
    s = jnp.einsum("bwhgd,bthd->bhgwt", qg, k.astype(jnp.float32)) / math.sqrt(Dh)
    if attn_cap > 0:
        s = attn_cap * jnp.tanh(s / attn_cap)
    causal = k_positions[:, None, :] <= obs_positions[:, :, None]  # (B, W, T)
    s = jnp.where(causal[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(causal[:, None, None], p, 0.0)
    return p.sum(axis=(2, 3))  # (B, Hkv, T)


def prefill_attention_ref(
    q: jnp.ndarray,  # (B, T, Hq, Dh) prompt queries (RoPE'd)
    k: jnp.ndarray,  # (B, T, Hkv, Dh)
    v: jnp.ndarray,  # (B, T, Hkv, Dh)
    window: int = 0,
    attn_cap: float = 0.0,
) -> jnp.ndarray:
    """Causal prefill attention at positions ``arange(T)`` → (B, T, Hq, Dh):
    the model's ``attention`` (fp32; dense up to 2048 keys, the chunked
    flash scan above, so memory stays O(T) at any prompt length)."""
    from repro.models.layers import attention
    B, T = q.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    return attention(q, k, v, pos, pos, window=window, attn_cap=attn_cap)


def ssd_chunk_ref(x, dt, A_log, B_, C_, D_, chunk=64):
    """Oracle for the SSD chunk kernel — delegates to the model implementation
    (itself validated against a naive sequential scan in tests)."""
    from repro.models.ssm import ssd_chunked
    return ssd_chunked(x, dt, A_log, B_, C_, D_, chunk=chunk)
