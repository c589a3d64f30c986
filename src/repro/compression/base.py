"""KV-cache compression policy interface.

A policy looks at per-position importance scores gathered during prefill and
decides, per (batch row, kv head), *which* positions to retain and *how many*
(the per-head budget).  Balanced policies give every head the same budget;
imbalanced policies (Ada-SnapKV, HeadKV — the paper's targets) redistribute a
layer-wide pool across heads, which is what creates the unfair head load.

Scores come from the SnapKV observation-window statistic: softmax attention of
the last ``obs_window`` queries onto all positions, summed over the window and
the query group, then 1-D max-pooled (kernel ``pool``) for locality.

All selections are jit-friendly: static top-``capacity`` per head plus a
length mask (``arange < keep``), so every policy lowers to the same shapes.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class CompressionConfig:
    policy: str = "ada_snapkv"
    budget: int = 1024  # mean retained tokens per kv head
    capacity: int = 0  # static per-head cap; 0 -> alpha_max * budget
    alpha_max: float = 2.0  # capacity multiplier for imbalanced policies
    obs_window: int = 32
    pool: int = 7
    sink: int = 4  # always-keep prefix tokens (StreamingLLM sinks)
    decode_margin: int = 64  # extra capacity for decode appends
    # HeadKV: fraction of the pool pre-allocated uniformly ("base budget")
    headkv_base_ratio: float = 0.2
    # PyramidKV: budget decays linearly across layers by +/- this fraction
    pyramid_beta: float = 0.6

    def static_capacity(self) -> int:
        cap = self.capacity or int(round(self.alpha_max * self.budget))
        return cap + self.decode_margin


def pool_scores(scores: jnp.ndarray, pool: int) -> jnp.ndarray:
    """1-D max pool along the last axis (SnapKV's clustering trick)."""
    if pool <= 1:
        return scores
    pad = pool // 2
    padded = jnp.pad(scores, [(0, 0)] * (scores.ndim - 1) + [(pad, pad)],
                     constant_values=-jnp.inf)
    windows = [padded[..., i:i + scores.shape[-1]] for i in range(pool)]
    return jnp.stack(windows, axis=0).max(axis=0)


def observation_scores(
    q_obs: jnp.ndarray,  # (B, W, Hq, Dh) — already RoPE'd
    k: jnp.ndarray,  # (B, T, Hkv, Dh)
    obs_positions: jnp.ndarray,  # (B, W)
    k_positions: jnp.ndarray,  # (B, T)
    pool: int = 7,
    attn_cap: float = 0.0,
) -> jnp.ndarray:
    """(B, Hkv, T) pooled importance of every position."""
    B, W, Hq, Dh = q_obs.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q_obs.reshape(B, W, Hkv, G, Dh).astype(jnp.float32)
    s = jnp.einsum("bwhgd,bthd->bhgwt", qg, k.astype(jnp.float32))
    s = s / jnp.sqrt(jnp.float32(Dh))
    if attn_cap > 0:
        s = attn_cap * jnp.tanh(s / attn_cap)
    causal = k_positions[:, None, :] <= obs_positions[:, :, None]  # (B, W, T)
    s = jnp.where(causal[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    imp = p.sum(axis=(2, 3))  # (B, Hkv, T)
    return pool_scores(imp, pool)


def topk_select(
    scores: jnp.ndarray,  # (B, Hkv, T)
    keep: jnp.ndarray,  # (B, Hkv) int32, <= capacity
    capacity: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Static top-``capacity`` indices + per-head validity lengths.

    Returned indices are sorted ascending (original temporal order) so RoPE
    positions stay monotone in the cache — convenient for debugging; attention
    itself is order-independent.
    """
    T = scores.shape[-1]
    capacity = min(capacity, T)
    _, idx = jax.lax.top_k(scores, capacity)  # (B, Hkv, C)
    keep = jnp.minimum(keep, capacity).astype(jnp.int32)
    # mask invalid tail with T-1 (harmless position), sort ascending
    valid = jnp.arange(capacity)[None, None, :] < keep[..., None]
    idx = jnp.where(valid, idx, T - 1)
    idx = jnp.sort(idx, axis=-1)
    # after sorting, valid entries are a prefix only if T-1 sorts last — it
    # does (max index), except genuine selections of T-1; lengths stay `keep`.
    return idx.astype(jnp.int32), keep
