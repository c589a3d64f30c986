"""Operations and bytes of the served model, computed from shapes.

Everything here is plain arithmetic on the cell's model dict (see
``weights.py``) and its compression settings, so every PR computes the
same numbers in the same way:

- ``headkv_keep``: the per-(layer, head) prompt tokens HeadKV retains, the
  rule a planner profile and the reference both follow;
- ``decode_flops`` / ``prefill_flops``: model FLOPs of one token / prompt;
- ``paged_decode_bytes``: HBM bytes the paged decode kernel must read for
  the live blocks of one decode step (the fig9 byte model of the repo's
  benchmarks, taken over as the yardstick: K + V of every valid block);
- ``peaks``: the chip's published peaks, keyed by ``device_kind``.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """``{"bf16_flops": ..., "hbm_bytes_per_s": ...}`` of one chip; a kind
    not in ``peaks.json`` is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


# ---- compression -----------------------------------------------------------


def static_capacity(comp: dict) -> int:
    """Per-(slot, row) cache capacity: ``alpha_max · budget`` + margin."""
    cap = comp.get("capacity", 0) or int(round(comp["alpha_max"]
                                               * comp["budget"]))
    return cap + comp["decode_margin"]


def importance(n_layers: int, n_heads: int, spec: dict) -> np.ndarray:
    """(L, H) seeded per-head importance: lognormal with std ``sigma``."""
    rng = np.random.default_rng(spec["seed"])
    return rng.lognormal(0.0, spec["sigma"], size=(n_layers, n_heads)
                         ).astype(np.float32)


def headkv_keep(imp: np.ndarray, comp: dict, prompt_len: int) -> np.ndarray:
    """(L, H) int prompt tokens HeadKV keeps: a uniform base share of the
    layer pool plus an importance-proportional share, clipped to
    [min(sink + obs_window, budget), min(capacity, T)].  Float32 throughout,
    truncated to int, as the policy computes it."""
    f32 = np.float32
    H = imp.shape[1]
    budget = comp["budget"]
    pool = H * budget
    base = int(round(comp["headkv_base_ratio"] * budget))
    imp = imp.astype(f32)
    share = imp / np.maximum(imp.sum(axis=1, keepdims=True), f32(1e-9))
    keep = f32(base) + f32(pool - H * base) * share
    lo = min(comp["sink"] + comp["obs_window"], budget)
    hi = min(static_capacity(comp), prompt_len)
    return np.clip(keep, f32(lo), f32(hi)).astype(np.int32)


def live_lengths(keep: np.ndarray, appended: int, capacity: int) -> np.ndarray:
    """(L, H) cache lengths after ``appended`` decode appends (the recency
    ring keeps a full head at capacity)."""
    return np.minimum(keep + appended, capacity)


# ---- FLOPs -----------------------------------------------------------------


def matmul_params(m: dict) -> int:
    """Weights one token multiplies through: every layer's projections and
    MLP, plus the unembedding over the real vocabulary."""
    D, Hq, Hkv, Dh, F = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                         m["head_dim"], m["d_ff"])
    per_layer = D * (Hq + 2 * Hkv) * Dh + Hq * Dh * D + 3 * D * F
    return m["n_layers"] * per_layer + m["vocab_size"] * D


def decode_flops(m: dict, lengths: np.ndarray) -> float:
    """Model FLOPs of one decode token whose per-(layer, head) retained
    cache lengths (after its own append) are ``lengths`` (L, H): 2 per
    weight, plus q·k and p·v over every retained entry of each query head."""
    G = m["n_heads"] // m["n_kv_heads"]
    attn = 4.0 * G * m["head_dim"] * float(np.asarray(lengths).sum())
    return 2.0 * matmul_params(m) + attn


def prefill_flops(m: dict, prompt_len: int) -> float:
    """Model FLOPs of one prompt's prefill: every token's projections and
    MLP, the causal attention (half the T×T score matrix), and one
    unembedding row (only the last position's logits are formed)."""
    D, Hq, Hkv, Dh, F = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                         m["head_dim"], m["d_ff"])
    T = prompt_len
    per_layer = D * (Hq + 2 * Hkv) * Dh + Hq * Dh * D + 3 * D * F
    proj = 2.0 * T * m["n_layers"] * per_layer
    attn = 4.0 * Hq * Dh * (T * (T + 1) / 2) * m["n_layers"]
    return proj + attn + 2.0 * m["vocab_size"] * D


# ---- bytes -----------------------------------------------------------------


def paged_decode_bytes(lengths: np.ndarray, block_size: int, head_dim: int,
                       itemsize: int) -> float:
    """HBM bytes the paged decode kernel must read for one step: K and V of
    every valid block of every owned (layer, head, row), ``lengths`` giving
    the per-(…, head) retained lengths after the step's append.  An owned
    pair always holds at least one block (the backend's one-block floor)."""
    blocks = np.maximum(-(-np.asarray(lengths, np.int64) // block_size), 1)
    return float(blocks.sum()) * block_size * head_dim * 2 * itemsize
