"""Serving engine: prefill (+compression) → slot-layout cache → decode.

The FairKV plan enters the runtime in two places:

1. **Weight layout** — ``slotify_params`` permutes/replicates the attention
   projections into slot layout once at load time: per layer,
   ``wq: (S, D, G, Dh)``, ``wk/wv: (S, D, Dh)``, ``wo: (S, G, Dh, D)`` with
   slot s carrying kv-head ``slot_head[l, s]`` (zeros for empty slots).  The
   slot dim shards over the "model" mesh axis, so each shard physically owns
   exactly the heads the planner gave it.

2. **Cache ownership** — replicas split the batch by the strided owner rule;
   unowned (slot, row) pairs keep ``lengths == 0`` forever, so their decode
   output is exactly zero and the o-projection contraction over S (an
   all-reduce across model shards) reassembles the full batch.

Prefill writes the slot layout (`SlotCache`), which the paged backend
re-houses in blocks (`paging/backend.py`); the decode step is the paper's
measured quantity, and its attention inner loop is
``kernels.ops.paged_fairkv_decode`` over the block pool (the native Pallas
kernel on TPU, the jnp oracle elsewhere).
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache.slot_cache import (
    PlanArrays,
    SlotCache,
    append_selection,
    fill_from_selection,
    init_cache,
    rows_to_mask,
)
from repro.compression.base import CompressionConfig
from repro.compression.policies import select as policy_select
from repro.configs.base import ModelConfig
from repro.core.placement import HeadPlacement
from repro.distributed.sharding import constrain
from repro.kernels import ops as K
from repro.models import layers as L
from repro.models import ssm as S
from repro.models import transformer as M
from repro.paging.paged_cache import PagedCache, paged_append_token


# ---------------------------------------------------------------------------
# Serve state
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclass
class ServeState:
    # slot layout as prefill writes it; a PagedCache once live (the paged
    # backend re-houses every prefill in blocks)
    cache: Optional[Union[SlotCache, PagedCache]]
    ssm_state: Optional[jnp.ndarray]  # (L, B, H, P, N) fp32
    conv_state: Optional[jnp.ndarray]  # (L, B, W-1, conv_dim)
    cross_k: Optional[jnp.ndarray]  # (L, B, T_enc, Hkv, Dh)
    cross_v: Optional[jnp.ndarray]
    last_tokens: jnp.ndarray  # (B,)
    decode_steps: jnp.ndarray  # scalar int32


# ---------------------------------------------------------------------------
# Slot-layout weights
# ---------------------------------------------------------------------------


def slotify_layer(pl: dict, slot_head: np.ndarray, cfg: ModelConfig) -> dict:
    """Build slot-layout q/k/v/o (+bias) weights for one layer."""
    G, Dh, D = cfg.q_per_kv, cfg.head_dim, cfg.d_model
    S_ = slot_head.shape[0]
    heads = np.maximum(slot_head, 0)
    empty = slot_head < 0
    wq = pl["wq"].reshape(D, cfg.n_kv_heads, G, Dh)
    out = dict(pl)
    q_s = jnp.take(wq, heads, axis=1).transpose(1, 0, 2, 3)  # (S, D, G, Dh)
    k_s = jnp.take(pl["wk"], heads, axis=1).transpose(1, 0, 2)  # (S, D, Dh)
    v_s = jnp.take(pl["wv"], heads, axis=1).transpose(1, 0, 2)
    wo = pl["wo"].reshape(cfg.n_kv_heads, G, Dh, D)
    o_s = jnp.take(wo, heads, axis=0)  # (S, G, Dh, D)
    mask = jnp.asarray(~empty, q_s.dtype)
    out["wq_s"] = q_s * mask[:, None, None, None]
    out["wk_s"] = k_s * mask[:, None, None]
    out["wv_s"] = v_s * mask[:, None, None]
    out["wo_s"] = o_s * mask[:, None, None, None]
    if cfg.qkv_bias and "bq" in pl:
        bq = pl["bq"].reshape(cfg.n_kv_heads, G, Dh)
        out["bq_s"] = jnp.take(bq, heads, axis=0) * mask[:, None, None]
        out["bk_s"] = jnp.take(pl["bk"], heads, axis=0) * mask[:, None]
        out["bv_s"] = jnp.take(pl["bv"], heads, axis=0) * mask[:, None]
    if "attn_out_norm" in pl:  # hybrid: per-branch norm scale in slot layout
        sc = pl["attn_out_norm"].reshape(cfg.n_kv_heads, G, Dh)
        out["attn_out_norm_s"] = jnp.take(sc, heads, axis=0)  # (S, G, Dh)
    for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv"):
        out.pop(k, None)
    return out


def slotify_params(params: dict, plan: HeadPlacement, cfg: ModelConfig) -> dict:
    """Serve-layout params: attention weights per plan; everything else kept."""
    if cfg.attention_free:
        return params
    arrs = plan.as_arrays()["slot_head"]
    out = dict(params)
    out["layers"] = [
        slotify_layer(pl, arrs[i], cfg) for i, pl in enumerate(params["layers"])
    ]
    return out


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------


def prefill(
    serve_params: dict,
    batch: Dict[str, jnp.ndarray],
    cfg: ModelConfig,
    plan: PlanArrays,
    ccfg: CompressionConfig,
    head_importance: Optional[np.ndarray] = None,
    rows: Optional[jnp.ndarray] = None,
    model_axis: Optional[str] = None,
) -> Tuple[ServeState, jnp.ndarray, jnp.ndarray]:
    """Run the full prompt, compress each layer's KV into the slot cache.

    Prefill attention runs in *original head layout* (slot layout only pays
    off once per-head lengths diverge); q/k/v are recovered from the slot
    weights of the replica-0 slots so only one weight copy is kept.

    ``rows`` (optional, (B,) int32) are the *global* batch-row ids this
    sub-batch will occupy in a larger live cache: the strided owner rule is
    evaluated at those ids so the resulting sub-cache can be paged into
    those rows (continuous-batching admission).  Default: arange(B).

    ``model_axis`` names the mesh axis the slot dim is sharded over when the
    call runs inside ``shard_map`` (DESIGN.md §10): the replica-0 weight
    recovery all-gathers the slot-dim weights (prefill attention needs every
    head), while the compression selection and the per-slot cache fill stay
    local — each model shard fills exactly the slots it owns.

    Returns (state, last_logits (B, V), lengths (L, Hkv, B) — the realized
    per-head retained lengths, i.e. the paper's workload observable).
    """
    h, positions = M.embed_inputs(serve_params, batch, cfg)
    B, T, D = h.shape
    Hkv, G, Dh = cfg.n_kv_heads, cfg.q_per_kv, cfg.head_dim
    n_layers = cfg.n_layers
    cap = ccfg.static_capacity()

    enc_kvs = None
    cross_k = cross_v = None
    if cfg.is_encoder_decoder:
        enc_out = M.encode(serve_params, batch["frames"], cfg)
        enc_kvs = M.encoder_cross_kv(serve_params, enc_out, cfg)
        cross_k = jnp.stack([kv[0] for kv in enc_kvs])
        cross_v = jnp.stack([kv[1] for kv in enc_kvs])

    has_attn = not cfg.attention_free
    cache = (init_cache(n_layers, plan.slot_head.shape[1], B, cap, Dh,
                        dtype=h.dtype) if has_attn else None)
    ssm_state = conv_state = None
    if cfg.family in ("ssm", "hybrid"):
        s = cfg.ssm
        ssm_state = jnp.zeros((n_layers, B, s.num_heads, s.head_dim,
                               s.state_size), jnp.float32)
        conv_state = jnp.zeros(
            (n_layers, B, s.conv_width - 1,
             s.d_inner + 2 * s.n_groups * s.state_size), h.dtype)

    lengths_all = []
    W = min(ccfg.obs_window, T)
    for i, pl in enumerate(serve_params["layers"]):
        hn = L.rms_norm(h, pl["ln1"], cfg.rms_eps)
        if cfg.family == "hybrid":
            attn_flat, cache, lens = _prefill_attention(
                pl, hn, positions, cfg, i, cache, plan, ccfg, W,
                head_importance, rows, model_axis)
            a = L.rms_norm(attn_flat, pl["attn_out_norm"], cfg.rms_eps)
            attn_out = _slot_o_proj(pl, a, cfg, plan, i, model_axis)
            ssm_out, (cs, ss) = M.ssm_block_full(pl, hn, cfg, return_state=True)
            conv_state = conv_state.at[i].set(cs)
            ssm_state = ssm_state.at[i].set(ss)
            h = h + 0.5 * (attn_out + ssm_out)
            lengths_all.append(lens)
        elif cfg.family == "ssm":
            ssm_out, (cs, ss) = M.ssm_block_full(pl, hn, cfg, return_state=True)
            conv_state = conv_state.at[i].set(cs)
            ssm_state = ssm_state.at[i].set(ss)
            h = h + ssm_out
        else:
            attn_flat, cache, lens = _prefill_attention(
                pl, hn, positions, cfg, i, cache, plan, ccfg, W,
                head_importance, rows, model_axis)
            h = h + _slot_o_proj(pl, attn_flat, cfg, plan, i, model_axis)
            lengths_all.append(lens)
        if enc_kvs is not None:
            hc = L.rms_norm(h, pl["ln_cross"], cfg.rms_eps)
            h = h + M.cross_attn_block(pl, hc, enc_kvs[i], cfg)
        if cfg.d_ff > 0 or cfg.moe.num_experts > 0:
            hn2 = L.rms_norm(h, pl["ln2"], cfg.rms_eps)
            mlp_out, _ = M.mlp_block(pl, hn2, cfg)
            h = h + mlp_out
        h = constrain(h, "batch", "seq", "d_model")

    h_last = L.rms_norm(h[:, -1:], serve_params["final_norm"], cfg.rms_eps)
    table = serve_params.get("head", serve_params["embed"])
    logits = L.unembed(h_last, table, cfg.logit_softcap)[:, 0]
    if cache is not None:
        cache = SlotCache(k=cache.k, v=cache.v, lengths=cache.lengths,
                          pos=cache.pos,
                          positions=jnp.full((B,), T, jnp.int32))
    state = ServeState(
        cache=cache, ssm_state=ssm_state, conv_state=conv_state,
        cross_k=cross_k, cross_v=cross_v,
        last_tokens=jnp.argmax(
            logits[..., :cfg.vocab_size], axis=-1).astype(jnp.int32),
        decode_steps=jnp.int32(0))
    lengths = (jnp.stack(lengths_all) if lengths_all
               else jnp.zeros((0, Hkv, B), jnp.int32))
    return state, logits, lengths


def _take0(w, idx):
    """take along axis 0 through QTensor or plain array."""
    from repro.serving.quant import QTensor
    if isinstance(w, QTensor):
        sc = (jnp.take(w.scale, idx, axis=0) if w.scale.shape[0] > 1
              else w.scale)
        return QTensor(q=jnp.take(w.q, idx, axis=0), scale=sc)
    return jnp.take(w, idx, axis=0)


def _full_slots(w, model_axis: Optional[str]):
    """Reassemble the global slot dim inside ``shard_map`` (identity
    outside).  Prefill recovers original-layout weights through
    ``first_slot``, whose indices are global — a shard's local slot slice
    does not contain every head's replica-0 slot."""
    if model_axis is None:
        return w
    return jax.lax.all_gather(w, model_axis, axis=0, tiled=True)


def first_weights(pl: dict, plan: PlanArrays, layer_idx: int,
                  model_axis: Optional[str] = None) -> dict:
    """Recover original-layout q/k/v/o weights from each head's replica-0
    slot (a cheap gather — no second weight copy is stored)."""
    from repro.serving.quant import deq
    fs = plan.first_slot[layer_idx]  # (Hkv,)
    out = {
        "wq": deq(_take0(_full_slots(pl["wq_s"], model_axis), fs)),  # (Hkv, D, G, Dh)
        "wk": deq(_take0(_full_slots(pl["wk_s"], model_axis), fs)),  # (Hkv, D, Dh)
        "wv": deq(_take0(_full_slots(pl["wv_s"], model_axis), fs)),
        "wo": deq(_take0(_full_slots(pl["wo_s"], model_axis), fs)),  # (Hkv, G, Dh, D)
    }
    if "bq_s" in pl:
        out["bq"] = jnp.take(_full_slots(pl["bq_s"], model_axis), fs, axis=0)
        out["bk"] = jnp.take(_full_slots(pl["bk_s"], model_axis), fs, axis=0)
        out["bv"] = jnp.take(_full_slots(pl["bv_s"], model_axis), fs, axis=0)
    return out


def _prefill_attention(pl, hn, positions, cfg, layer_idx, cache, plan, ccfg,
                       W, head_importance, rows=None, model_axis=None):
    """Full attention + compression + slot-cache fill for one layer.

    The attention is ``kernels.ops.prefill_attention`` for every prompt
    length (positions are ``arange(T)``)."""
    B, T, D = hn.shape
    Hkv, G, Dh = cfg.n_kv_heads, cfg.q_per_kv, cfg.head_dim
    fw = first_weights(pl, plan, layer_idx, model_axis)
    q = jnp.einsum("btd,hdgx->bthgx", hn, fw["wq"])  # (B,T,Hkv,G,Dh)
    k = jnp.einsum("btd,hdx->bthx", hn, fw["wk"])
    v = jnp.einsum("btd,hdx->bthx", hn, fw["wv"])
    if "bq" in fw:
        q = q + fw["bq"]
        k = k + fw["bk"]
        v = v + fw["bv"]
    q = q.reshape(B, T, Hkv * G, Dh)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    window = M.layer_window(cfg, layer_idx)
    out = K.prefill_attention(q, k, v, window=window,
                              attn_cap=cfg.attn_softcap)
    out_flat = out.reshape(B, T, Hkv * G * Dh)

    # --- compression ---------------------------------------------------------
    q_obs = q[:, T - W:]
    scores = K.snapkv_scores(q_obs, k, positions[:, T - W:], positions,
                             attn_cap=cfg.attn_softcap)
    from repro.compression.base import pool_scores
    scores = pool_scores(scores, ccfg.pool)
    if window > 0:
        # sliding-window layers never need positions older than the window
        pos = jnp.arange(T)
        scores = jnp.where(pos[None, None, :] >= T - window, scores, -jnp.inf)
    kw = {}
    if ccfg.policy == "headkv" and head_importance is not None:
        kw["head_importance"] = jnp.asarray(head_importance[layer_idx])
    idx, keep = policy_select(ccfg.policy, scores, ccfg, layer_idx,
                              cfg.n_layers, **kw)
    cache = fill_from_selection(cache, layer_idx, k, v, idx, keep, plan,
                                rows=rows)
    return out_flat, cache, keep.transpose(1, 0)  # lens (Hkv, B)


def _slot_o_proj(pl, attn_flat, cfg, plan, layer_idx, model_axis=None):
    """(B, T, Hkv·G·Dh) → (B, T, D) via the first-replica o weights."""
    D = cfg.d_model
    from repro.serving.quant import deq
    fs = plan.first_slot[layer_idx]
    wo = deq(_take0(_full_slots(pl["wo_s"], model_axis), fs))
    wo = wo.reshape(cfg.n_kv_heads * cfg.q_per_kv * cfg.head_dim, D)
    return jnp.einsum("bte,ed->btd", attn_flat, wo)


# ---------------------------------------------------------------------------
# Chunked prefill (DESIGN.md §14)
# ---------------------------------------------------------------------------


def _cache_head_view(cache, layer, plan, rows, n_heads, model_axis=None):
    """Head-layout view of one layer's slot cache for the given rows.

    Returns ``(k (B,H,C,Dh), v (B,H,C,Dh), len_h (B,H), pos_h (B,H,C))``.
    Every (head, row) pair has exactly one owning slot, so a 0/1-weighted
    einsum over slots recovers the head layout; under ``shard_map`` the slot
    dim (cache slices *and* plan arrays) is all-gathered over ``model_axis``
    first — chunk attention needs every head, like monolithic prefill's
    weight recovery.
    """
    sh = plan.slot_head[layer]       # (S,)
    ri = plan.replica_idx[layer]
    rc = plan.replica_count[layer]
    kl, vl = cache.k[layer], cache.v[layer]     # (S, B, C, Dh)
    ln, ps = cache.lengths[layer], cache.pos[layer]
    if model_axis is not None:
        def ag(x):
            return jax.lax.all_gather(x, model_axis, axis=0, tiled=True)
        sh, ri, rc = ag(sh), ag(ri), ag(rc)
        kl, vl, ln, ps = ag(kl), ag(vl), ag(ln), ag(ps)
    rows = jnp.asarray(rows, jnp.int32)
    own = (sh >= 0)[:, None] & ((rows[None, :] % rc[:, None]) == ri[:, None])
    oh = sh[:, None] == jnp.arange(n_heads, dtype=sh.dtype)[None, :]  # (S, H)
    w = (oh[:, None, :] & own[:, :, None]).astype(jnp.float32)  # (S, B, H)
    k_h = jnp.einsum("sbh,sbcd->bhcd", w, kl.astype(jnp.float32))
    v_h = jnp.einsum("sbh,sbcd->bhcd", w, vl.astype(jnp.float32))
    len_h = jnp.einsum("sbh,sb->bh", w, ln.astype(jnp.float32))
    pos_h = jnp.einsum("sbh,sbc->bhc", w, ps.astype(jnp.float32))
    return (k_h.astype(cache.k.dtype), v_h.astype(cache.v.dtype),
            len_h.astype(jnp.int32), jnp.round(pos_h).astype(jnp.int32))


def _chunk_attention(pl, hn, positions, valid, cfg, layer_idx, cache, plan,
                     ccfg, quota_l, head_importance, rows, model_axis=None):
    """Attention over (retained cache ‖ current chunk) + boundary compression.

    The cache is per-head (earlier chunks' keep-sets differ per head), so
    attention runs with each (row, head) pair as its own batch element of
    `dense_attention` — keys are the head's retained entries concatenated
    with the chunk's fresh keys, masked by retained length / ``valid`` and
    the standard causal+window rule over *absolute* positions (cache keys
    are post-RoPE, so order never matters).  At the chunk boundary the
    snapkv observation scores are computed over the chunk's keys only and
    the policy's selection is appended after the existing entries
    (`append_selection`), clamped to the per-chunk ``quota_l`` and the
    remaining slot capacity.
    """
    B, Ck, D = hn.shape
    Hkv, G, Dh = cfg.n_kv_heads, cfg.q_per_kv, cfg.head_dim
    C = cache.k.shape[3]
    fw = first_weights(pl, plan, layer_idx, model_axis)
    q = jnp.einsum("btd,hdgx->bthgx", hn, fw["wq"])  # (B,Ck,Hkv,G,Dh)
    k = jnp.einsum("btd,hdx->bthx", hn, fw["wk"])
    v = jnp.einsum("btd,hdx->bthx", hn, fw["wv"])
    if "bq" in fw:
        q = q + fw["bq"]
        k = k + fw["bk"]
        v = v + fw["bv"]
    q = q.reshape(B, Ck, Hkv * G, Dh)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    window = M.layer_window(cfg, layer_idx)

    k_c, v_c, len_h, pos_h = _cache_head_view(cache, layer_idx, plan, rows,
                                              Hkv, model_axis)
    # (row, head) pairs as batch: per-head caches have distinct keys
    qh = (q.reshape(B, Ck, Hkv, G, Dh).transpose(0, 2, 1, 3, 4)
          .reshape(B * Hkv, Ck, G, Dh))
    kx = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Ck, 1, Dh)
    vx = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Ck, 1, Dh)
    k_cat = jnp.concatenate([k_c.reshape(B * Hkv, C, 1, Dh).astype(kx.dtype),
                             kx], axis=1)
    v_cat = jnp.concatenate([v_c.reshape(B * Hkv, C, 1, Dh).astype(vx.dtype),
                             vx], axis=1)
    q_pos = jnp.broadcast_to(positions[:, None, :], (B, Hkv, Ck))
    k_pos = jnp.concatenate([pos_h.reshape(B * Hkv, C),
                             q_pos.reshape(B * Hkv, Ck)], axis=1)
    in_cache = jnp.arange(C, dtype=jnp.int32)[None, None, :] < len_h[..., None]
    in_chunk = jnp.arange(Ck, dtype=jnp.int32)[None, :] < valid[:, None]
    kv_mask = jnp.concatenate(
        [in_cache.reshape(B * Hkv, C),
         jnp.broadcast_to(in_chunk[:, None, :], (B, Hkv, Ck))
         .reshape(B * Hkv, Ck)], axis=1)
    out = L.dense_attention(qh, k_cat, v_cat, q_pos.reshape(B * Hkv, Ck),
                            k_pos, window=window, attn_cap=cfg.attn_softcap,
                            kv_mask=kv_mask, causal=True)
    out_flat = (out.reshape(B, Hkv, Ck, G, Dh).transpose(0, 2, 1, 3, 4)
                .reshape(B, Ck, Hkv * G * Dh))

    # --- chunk-boundary compression -------------------------------------
    W = min(ccfg.obs_window, Ck)
    obs_ix = jnp.clip(valid[:, None] - W + jnp.arange(W, dtype=jnp.int32),
                      0, Ck - 1)  # (B, W): last W *valid* chunk queries
    q_obs = jnp.take_along_axis(q, obs_ix[:, :, None, None], axis=1)
    pos_obs = jnp.take_along_axis(positions, obs_ix, axis=1)
    scores = K.snapkv_scores(q_obs, k, pos_obs, positions,
                             attn_cap=cfg.attn_softcap)
    t_ix = jnp.arange(Ck, dtype=jnp.int32)
    scores = jnp.where(t_ix[None, None, :] < valid[:, None, None],
                       scores, -jnp.inf)
    from repro.compression.base import pool_scores
    scores = pool_scores(scores, ccfg.pool)
    if window > 0:
        end = (jnp.asarray(valid, jnp.int32) + positions[:, 0])[:, None, None]
        scores = jnp.where(positions[:, None, :] >= end - window,
                           scores, -jnp.inf)
    kw = {}
    if ccfg.policy == "headkv" and head_importance is not None:
        kw["head_importance"] = jnp.asarray(head_importance[layer_idx])
    idx, keep = policy_select(ccfg.policy, scores, ccfg, layer_idx,
                              cfg.n_layers, **kw)
    keep = jnp.minimum(keep, valid[:, None])          # only real tokens
    keep = jnp.minimum(keep, quota_l)                 # incremental budget
    keep = jnp.minimum(keep, C - len_h)               # slot headroom
    keep = jnp.maximum(keep, 0).astype(jnp.int32)
    cache = append_selection(cache, layer_idx, k, v, idx, keep, plan,
                             rows=rows, start=positions[:, 0])
    return out_flat, cache, (len_h + keep).transpose(1, 0)  # (Hkv, B)


def prefill_chunk(
    serve_params: dict,
    tokens: jnp.ndarray,  # (B, Ck) fixed-width chunk (padded past ``valid``)
    cfg: ModelConfig,
    plan: PlanArrays,
    ccfg: CompressionConfig,
    state: ServeState,
    rows: jnp.ndarray,  # (B,) global row ids
    start: jnp.ndarray,  # (B,) int32 absolute position of chunk token 0
    valid: jnp.ndarray,  # (B,) int32 real tokens in this chunk (<= Ck)
    quota: jnp.ndarray,  # (L,) int32 per-head keep cap for this chunk
    head_importance: Optional[np.ndarray] = None,
    model_axis: Optional[str] = None,
) -> Tuple[ServeState, jnp.ndarray, jnp.ndarray]:
    """Process one fixed-width prompt chunk against an accumulating cache.

    The chunked twin of `prefill` (DESIGN.md §14): the prompt arrives
    ``chunk_tokens`` at a time, each chunk attends over the *retained*
    entries of earlier chunks plus its own keys, and the compression policy
    runs at the chunk boundary so per-head keep-budgets accrue
    incrementally.  ``tokens`` is always the same static width — the
    scheduler pads the last chunk and passes ``valid`` — so local and mesh
    executors trace this exactly once per shape.

    Dense decoder-only families only: ssm/hybrid recurrences and enc-dec /
    vlm inputs do not thread through a chunk boundary (the scheduler falls
    back to monolithic prefill for them).

    Returns (state, logits (B, V) at the last valid token, lengths
    (L, Hkv, B) — *cumulative* retained lengths after this chunk).
    """
    if cfg.family != "dense" or cfg.attention_free:
        raise ValueError(
            f"chunked prefill supports dense attention families only, "
            f"got family={cfg.family!r}")
    if cfg.is_encoder_decoder or cfg.is_vlm:
        raise ValueError("chunked prefill does not support enc-dec / vlm")
    h = L.embed(tokens, serve_params["embed"])
    if cfg.name.startswith("gemma2"):
        h = h * jnp.asarray(math.sqrt(cfg.d_model), h.dtype)
    B, Ck, _ = h.shape
    start = jnp.asarray(start, jnp.int32)
    valid = jnp.asarray(valid, jnp.int32)
    quota = jnp.asarray(quota, jnp.int32)
    positions = start[:, None] + jnp.arange(Ck, dtype=jnp.int32)[None, :]
    cache = state.cache
    lengths_all = []
    for i, pl in enumerate(serve_params["layers"]):
        hn = L.rms_norm(h, pl["ln1"], cfg.rms_eps)
        attn_flat, cache, lens = _chunk_attention(
            pl, hn, positions, valid, cfg, i, cache, plan, ccfg, quota[i],
            head_importance, rows, model_axis)
        h = h + _slot_o_proj(pl, attn_flat, cfg, plan, i, model_axis)
        lengths_all.append(lens)
        if cfg.d_ff > 0 or cfg.moe.num_experts > 0:
            hn2 = L.rms_norm(h, pl["ln2"], cfg.rms_eps)
            mlp_out, _ = M.mlp_block(pl, hn2, cfg)
            h = h + mlp_out
        h = constrain(h, "batch", "seq", "d_model")

    last_ix = jnp.maximum(valid - 1, 0)
    h_last = jnp.take_along_axis(h, last_ix[:, None, None], axis=1)  # (B,1,D)
    h_last = L.rms_norm(h_last, serve_params["final_norm"], cfg.rms_eps)
    table = serve_params.get("head", serve_params["embed"])
    logits = L.unembed(h_last, table, cfg.logit_softcap)[:, 0]
    cache = dataclasses.replace(
        cache, positions=(start + valid).astype(jnp.int32))
    new_state = ServeState(
        cache=cache, ssm_state=state.ssm_state, conv_state=state.conv_state,
        cross_k=state.cross_k, cross_v=state.cross_v,
        last_tokens=jnp.argmax(
            logits[..., :cfg.vocab_size], axis=-1).astype(jnp.int32),
        decode_steps=state.decode_steps)
    lengths = jnp.stack(lengths_all)
    return new_state, logits, lengths


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def decode_step(
    serve_params: dict,
    state: ServeState,
    cfg: ModelConfig,
    plan: PlanArrays,
    ccfg: CompressionConfig,
    tokens: Optional[jnp.ndarray] = None,
    active: Optional[jnp.ndarray] = None,
    rows: Optional[jnp.ndarray] = None,
    model_axis: Optional[str] = None,
    data_axis: Optional[str] = None,
    paged_impl: str = "auto",
    kv_kinds=None,
) -> Tuple[ServeState, jnp.ndarray]:
    """One decode step for the whole batch.  Returns (state, logits (B, V)).

    ``active`` ((B,) bool, optional) marks the rows that carry a live request
    under continuous batching: cache appends and position increments are
    suppressed on inactive rows, so a retired row's ``lengths`` stay 0 (its
    decode-attention output stays exactly zero) until the scheduler splices a
    new request in.  ``None`` treats every row as active (one-shot serving).

    ``rows`` ((B,) int32, optional) are the *global* batch-row ids of the
    rows this call sees — the strided replica-owner rule keys on global ids,
    so a mesh executor running this step inside ``shard_map`` (batch rows
    sharded over the data axis) must pass each shard's global row slice.
    Default: arange(B) (the full batch is visible, today's local path).

    ``model_axis`` names the mesh axis the slot dim is sharded over inside
    ``shard_map``: per-slot attention stays local, and the o-projection
    contraction over S becomes a psum that reassembles the full activation
    (DESIGN.md §10).  ``data_axis`` names the batch-row axis — the paged
    pool partitions over *both* axes (blocks of (slot, row) live on the
    (model shard of the slot, data shard of the row) device), so the
    block-id localization needs both indices.  ``None`` (default) is the
    single-device path.

    ``paged_impl`` selects the paged decode-attention implementation
    (``kernels.ops.PAGED_DECODE_IMPLS``: native "pallas" kernel, "jnp"
    oracle, or "auto" — DESIGN.md §11).  It is *static*
    configuration (the executors close over ``PagingConfig.decode_impl``),
    so it never affects the StepFn's trace signature.

    ``kv_kinds`` ((L, H) int numpy, static like ``paged_impl``) is the
    per-(layer, head) quantized-storage kind grid (DESIGN.md §15).  The
    per-*slot* kinds the kernel needs are derived in-trace from the traced
    plan's ``slot_head``, so one compiled StepFn serves every replan even
    under a per-head dtype override map.
    """
    tokens = state.last_tokens if tokens is None else tokens
    B = tokens.shape[0]
    h = L.embed(tokens[:, None], serve_params["embed"])  # (B, 1, D)
    if cfg.name.startswith("gemma2"):
        h = h * jnp.asarray(math.sqrt(cfg.d_model), h.dtype)
    positions = (state.cache.positions if state.cache is not None
                 else state.decode_steps.astype(jnp.int32) + jnp.zeros((B,), jnp.int32))
    if cfg.is_encoder_decoder:
        h = h + serve_params["dec_pos"][positions][:, None]
    cache, ssm_state, conv_state = state.cache, state.ssm_state, state.conv_state

    for i, pl in enumerate(serve_params["layers"]):
        hn = L.rms_norm(h, pl["ln1"], cfg.rms_eps)
        if cfg.family == "hybrid":
            attn_flat, cache = _decode_attention(pl, hn, positions, cfg, i,
                                                 cache, plan, state.decode_steps,
                                                 ccfg, active, rows, model_axis,
                                                 data_axis, paged_impl, kv_kinds)
            a = _slot_rms_norm(attn_flat, pl["attn_out_norm_s"],
                               cfg.n_heads * cfg.head_dim, cfg.rms_eps,
                               model_axis)
            attn_out = _decode_slot_o(pl, a, cfg, model_axis)
            ssm_out, ssm_state, conv_state = _decode_ssm(
                pl, hn, cfg, i, ssm_state, conv_state)
            h = h + 0.5 * (attn_out + ssm_out)
        elif cfg.family == "ssm":
            ssm_out, ssm_state, conv_state = _decode_ssm(
                pl, hn, cfg, i, ssm_state, conv_state)
            h = h + ssm_out
        else:
            attn_flat, cache = _decode_attention(pl, hn, positions, cfg, i,
                                                 cache, plan, state.decode_steps,
                                                 ccfg, active, rows, model_axis,
                                                 data_axis, paged_impl, kv_kinds)
            h = h + _decode_slot_o(pl, attn_flat, cfg, model_axis)
        if cfg.is_encoder_decoder:
            hc = L.rms_norm(h, pl["ln_cross"], cfg.rms_eps)
            h = h + M.cross_attn_block(
                pl, hc, (state.cross_k[i], state.cross_v[i]), cfg)
        if cfg.d_ff > 0 or cfg.moe.num_experts > 0:
            hn2 = L.rms_norm(h, pl["ln2"], cfg.rms_eps)
            mlp_out, _ = M.mlp_block(pl, hn2, cfg)
            h = h + mlp_out
        h = constrain(h, "batch", None, "d_model")

    h = L.rms_norm(h, serve_params["final_norm"], cfg.rms_eps)
    table = serve_params.get("head", serve_params["embed"])
    logits = L.unembed(h, table, cfg.logit_softcap)[:, 0]  # (B, V)
    if cache is not None:
        pos_next = (cache.positions + 1 if active is None
                    else jnp.where(active, cache.positions + 1,
                                   cache.positions))
        cache = dataclasses.replace(cache, positions=pos_next)
    new_state = ServeState(
        cache=cache, ssm_state=ssm_state, conv_state=conv_state,
        cross_k=state.cross_k, cross_v=state.cross_v,
        last_tokens=jnp.argmax(
            logits[..., :cfg.vocab_size], axis=-1).astype(jnp.int32),
        decode_steps=state.decode_steps + 1)
    return new_state, logits


def _decode_attention(pl, hn, positions, cfg, layer_idx, cache, plan,
                      decode_steps, ccfg, active=None, rows=None,
                      model_axis=None, data_axis=None, paged_impl="auto",
                      kv_kinds=None):
    """Slot-layout attention for one new token over the paged cache
    (DESIGN.md §9); appends the token's K/V to it first."""
    B = hn.shape[0]
    G, Dh = cfg.q_per_kv, cfg.head_dim
    from repro.serving.quant import deq
    x = hn[:, 0]  # (B, D)
    q = jnp.einsum("bd,sdgx->bsgx", x, deq(pl["wq_s"]))  # (B, S, G, Dh)
    k_new = jnp.einsum("bd,sdx->bsx", x, deq(pl["wk_s"]))  # (B, S, Dh)
    v_new = jnp.einsum("bd,sdx->bsx", x, deq(pl["wv_s"]))
    if "bq_s" in pl:
        q = q + pl["bq_s"]
        k_new = k_new + pl["bk_s"][None]
        v_new = v_new + pl["bv_s"][None]
    # RoPE at each row's absolute position
    q = _rope_slots(q, positions, cfg)
    k_new = _rope_slots(k_new[:, :, None, :], positions, cfg)[:, :, 0, :]
    own = (plan.owner_mask(layer_idx, B) if rows is None
           else plan.owner_mask_rows(layer_idx, rows))  # (S, B)
    if active is not None:
        own = own & active[None, :]
    window = M.layer_window(cfg, layer_idx)
    # appends scatter one token into each owned row's write block; the
    # decode reads through `ops.paged_fairkv_decode` (the native
    # block-table kernel on TPU, §11)
    capacity = ccfg.static_capacity()
    table_l = cache.block_table[layer_idx]  # (S, B, M)
    if model_axis is not None:
        # mesh (DESIGN.md §10): the pool shards over (model, data) —
        # blocks of (slot, row) live on the (slot's model shard, row's
        # data shard) device — so each device holds an N_part-block
        # partition while the table stores *global* block ids.  The
        # partition-aware allocator guarantees locality, so subtracting
        # the partition offset localizes the ids; anything that falls
        # outside (the global null block 0 on later partitions,
        # defensively a foreign id) redirects to local block 0 — every
        # partition reserves its local block 0 as a null block.
        n_part = cache.k_pool.shape[1]
        part_idx = jax.lax.axis_index(model_axis)
        if data_axis is not None:
            row_parts = jax.lax.psum(1, data_axis)
            part_idx = (part_idx * row_parts
                        + jax.lax.axis_index(data_axis))
        loc = table_l - part_idx * n_part
        table_l = jnp.where((loc > 0) & (loc < n_part), loc, 0)
    kinds = None
    if cache.k_scale is not None:
        # per-slot dequant kinds from the *traced* plan: the static
        # (L, H) kind grid indexed by slot_head, so a replan that moves
        # heads across slots reuses the same compiled step (§15)
        grid_l = (jnp.zeros((cfg.n_kv_heads,), jnp.int32)
                  if kv_kinds is None
                  else jnp.asarray(kv_kinds[layer_idx], jnp.int32))
        kinds = jnp.take(grid_l,
                         jnp.maximum(plan.slot_head[layer_idx], 0))
    cache = paged_append_token(cache, layer_idx, k_new.swapaxes(0, 1),
                               v_new.swapaxes(0, 1), own, decode_steps,
                               capacity, ring=max(1, ccfg.decode_margin),
                               table_layer=table_l, kinds=kinds)
    out = K.paged_fairkv_decode(
        q, cache.k_pool[layer_idx], cache.v_pool[layer_idx],
        cache.pos_pool[layer_idx], table_l,
        cache.lengths[layer_idx], capacity, attn_cap=cfg.attn_softcap,
        q_pos=positions, window=window, impl=paged_impl,
        k_scale=(None if cache.k_scale is None
                 else cache.k_scale[layer_idx]),
        v_scale=(None if cache.v_scale is None
                 else cache.v_scale[layer_idx]),
        kinds=kinds)
    return out, cache  # (B, S, G, Dh)


def _rope_slots(q, positions, cfg):
    """RoPE over (B, S, G, Dh) at per-row positions."""
    B, S_, G, Dh = q.shape
    q2 = q.reshape(B, 1, S_ * G, Dh)  # one 'seq' position per row
    q2 = L.apply_rope(q2, positions[:, None], cfg.rope_theta)
    return q2.reshape(B, S_, G, Dh)


def _slot_rms_norm(x, scale_slot, n_channels, eps, model_axis=None):
    """RMS norm over the slot layout (B, S, G, Dh).

    Unowned-slot entries are exactly zero (the decode attention guarantees
    it), and every head contributes through exactly one owned slot per row,
    so Σx² over (S, G, Dh) equals the original-channel Σx²; the mean
    divides by the *true* channel count (Hq·Dh), not the padded slot width.
    Under ``shard_map`` the Σ over S is a (tiny) cross-shard psum.
    """
    xf = x.astype(jnp.float32)
    ss = (xf * xf).sum(axis=(1, 2, 3), keepdims=True)
    if model_axis is not None:
        ss = jax.lax.psum(ss, model_axis)
    ss = ss / n_channels
    return (xf * jax.lax.rsqrt(ss + eps)
            * (1.0 + scale_slot.astype(jnp.float32))[None]).astype(x.dtype)


def _decode_slot_o(pl, attn, cfg, model_axis=None):
    """(B, S, G, Dh) → (B, 1, D); contraction over S psums across shards.

    This is the one collective of the mesh decode StepFn: every (head, row)
    pair has exactly one owning slot, so the per-shard partial contractions
    sum to the full batch's activation (DESIGN.md §10)."""
    from repro.serving.quant import deq
    out = jnp.einsum("bsgx,sgxd->bd", attn, deq(pl["wo_s"]))
    if model_axis is not None:
        out = jax.lax.psum(out, model_axis)
    return out[:, None]


def _decode_ssm(pl, hn, cfg, layer_idx, ssm_state, conv_state):
    s = cfg.ssm
    d_in, G, N, H, P = s.d_inner, s.n_groups, s.state_size, s.num_heads, s.head_dim
    B = hn.shape[0]
    z, xBC, dt = M.ssm_split(pl, hn, cfg)  # (B, 1, ...)
    cs = conv_state[layer_idx]  # (B, W-1, conv_dim)
    xBC, new_cs = S.conv1d_causal(xBC, pl["conv_w"], cs)
    xBC = jax.nn.silu(xBC)
    x, B_, C_ = jnp.split(xBC[:, 0], [d_in, d_in + G * N], axis=-1)
    y, new_ss = S.ssd_decode_step(
        x.reshape(B, H, P), dt[:, 0], pl["A_log"],
        B_.reshape(B, G, N), C_.reshape(B, G, N), pl["ssm_D"],
        ssm_state[layer_idx])
    y = y.reshape(B, 1, d_in)
    y = L.rms_norm(y * jax.nn.silu(z), pl["ssm_norm"])
    out = y @ pl["out_proj"]
    return out, ssm_state.at[layer_idx].set(new_ss), conv_state.at[layer_idx].set(new_cs)


# ---------------------------------------------------------------------------
# Speculative decoding: propose + verify (DESIGN.md §16)
# ---------------------------------------------------------------------------


def _spec_supported(cfg: ModelConfig) -> None:
    if cfg.family != "dense" or cfg.attention_free:
        raise ValueError(
            "speculative decoding supports dense attention families only, "
            f"got family={cfg.family!r}")
    if cfg.is_encoder_decoder or cfg.is_vlm:
        raise ValueError("speculative decoding does not support enc-dec/vlm")


def propose_step(
    serve_params: dict,
    state: ServeState,
    cfg: ModelConfig,
    plan: PlanArrays,
    ccfg: CompressionConfig,
    depths: jnp.ndarray,  # (B,) int32 — speculative tokens per row (<= max_k)
    active: Optional[jnp.ndarray] = None,
    rows: Optional[jnp.ndarray] = None,
    model_axis: Optional[str] = None,
    data_axis: Optional[str] = None,
    paged_impl: str = "auto",
    kv_kinds=None,
    draft_layers: int = 0,  # static; 0 = full depth (self-check mode)
    max_k: int = 1,  # static unroll bound; per-row depth is traced
) -> Tuple[ServeState, jnp.ndarray]:
    """Draft ``max_k`` tokens autoregressively in ONE jitted call.

    The draft is the layer-truncated early exit of the target
    (`models.draft_view`), its head placement the leading slice of the
    target plan (`core.planner.draft_plan`) — so the draft's KV appends
    land in the *target's* paged cache at the target's own layers < d
    (real KV; verify fills layers >= d).  ``max_k`` masked single-decode
    steps are unrolled into this one trace: step ``i`` runs with
    ``active & (i < depths)``, so per-row adaptive depth changes never
    retrace (the zero-recompile invariant — depth is data, not shape).

    Positions, ``decode_steps`` and ``last_tokens`` are restored to their
    pre-propose values in the returned state: the verify pass re-derives
    the position advance from the accepted run, and the tick counts as one
    ring step regardless of depth.  Returns (state, proposals (B, max_k))
    — entries past a row's depth are garbage lanes the scheduler masks.
    """
    _spec_supported(cfg)
    from repro.core.planner import draft_plan

    d = draft_layers if draft_layers > 0 else cfg.n_layers
    sp_d = M.draft_view(serve_params, d)
    plan_d = draft_plan(plan, d)
    B = state.last_tokens.shape[0]
    active_b = (jnp.ones((B,), bool) if active is None else active)
    depths = jnp.asarray(depths, jnp.int32)
    st = state
    proposals = []
    for i in range(max_k):
        act_i = active_b & (jnp.int32(i) < depths)
        st, _ = decode_step(sp_d, st, cfg, plan_d, ccfg,
                            tokens=st.last_tokens, active=act_i, rows=rows,
                            model_axis=model_axis, data_axis=data_axis,
                            paged_impl=paged_impl, kv_kinds=kv_kinds)
        proposals.append(st.last_tokens)
    props = (jnp.stack(proposals, axis=1) if proposals
             else jnp.zeros((B, 0), jnp.int32))
    cache = dataclasses.replace(st.cache, positions=state.cache.positions)
    new_state = ServeState(
        cache=cache, ssm_state=st.ssm_state, conv_state=st.conv_state,
        cross_k=st.cross_k, cross_v=st.cross_v,
        last_tokens=state.last_tokens, decode_steps=state.decode_steps)
    return new_state, props


def verify_step(
    serve_params: dict,
    state: ServeState,
    cfg: ModelConfig,
    plan: PlanArrays,
    ccfg: CompressionConfig,
    tokens: jnp.ndarray,  # (B, Q) int32: [t0, p1..p_{Q-1}] (garbage past q_lens)
    q_lens: jnp.ndarray,  # (B,) int32 valid window per row (1 <= q_len <= Q)
    active: Optional[jnp.ndarray] = None,
    rows: Optional[jnp.ndarray] = None,
    model_axis: Optional[str] = None,
    data_axis: Optional[str] = None,
    paged_impl: str = "auto",
    kv_kinds=None,
    draft_layers: int = 0,  # static; layers < d were filled by propose
) -> Tuple[ServeState, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One batched verify pass over the speculative window.

    Runs the full target over ``Q = max_k + 1`` tokens per row — the last
    committed token followed by the draft's proposals — through the
    multi-query paged kernel (5-D q, `fairkv_decode_mq_ref` semantics).
    Appends per layer restore the uniform-length invariant: draft layers
    already hold the window's first ``q_len - 1`` entries (propose wrote
    real KV), so only the final token appends there; verify-only layers
    append every valid token in query order, walking the same
    quantize-on-write scale evolution as sequential decode.

    The greedy verdicts ``g[:, i] = argmax`` are exactly what single-token
    decode would have emitted given the same prefix, so committing the
    accepted run ``g[:, :n_commit]`` is bit-identical to non-speculative
    greedy decode at any acceptance rate.  Rejected entries roll back
    *in-trace* (lengths drop to ``base + n_commit``); the host-side block
    trim (`paging.backend`) reclaims now-uncovered provisional blocks.

    Returns (state, g (B, Q), n_commit (B,), logits (B, Q, V)).
    """
    _spec_supported(cfg)
    d = draft_layers if draft_layers > 0 else cfg.n_layers
    B, Q = tokens.shape
    active_b = (jnp.ones((B,), bool) if active is None else active)
    q_lens = jnp.asarray(q_lens, jnp.int32)
    h = L.embed(tokens, serve_params["embed"])  # (B, Q, D)
    if cfg.name.startswith("gemma2"):
        h = h * jnp.asarray(math.sqrt(cfg.d_model), h.dtype)
    cache = state.cache
    positions = cache.positions
    positions_q = positions[:, None] + jnp.arange(Q, dtype=jnp.int32)[None, :]

    for i, pl in enumerate(serve_params["layers"]):
        hn = L.rms_norm(h, pl["ln1"], cfg.rms_eps)
        attn, cache = _verify_attention(
            pl, hn, positions_q, q_lens, cfg, i, cache, plan,
            state.decode_steps, ccfg, i < d, active_b, rows, model_axis,
            data_axis, paged_impl, kv_kinds)
        h = h + _verify_slot_o(pl, attn, cfg, model_axis)
        if cfg.d_ff > 0 or cfg.moe.num_experts > 0:
            hn2 = L.rms_norm(h, pl["ln2"], cfg.rms_eps)
            mlp_out, _ = M.mlp_block(pl, hn2, cfg)
            h = h + mlp_out
        h = constrain(h, "batch", None, "d_model")

    h = L.rms_norm(h, serve_params["final_norm"], cfg.rms_eps)
    table = serve_params.get("head", serve_params["embed"])
    logits = L.unembed(h, table, cfg.logit_softcap)  # (B, Q, V)
    g = jnp.argmax(logits[..., :cfg.vocab_size], axis=-1).astype(jnp.int32)

    # leading run of proposals the target itself would have emitted
    if Q > 1:
        iq = jnp.arange(Q - 1, dtype=jnp.int32)[None, :]
        ok = (tokens[:, 1:] == g[:, :-1]) & (iq + 1 < q_lens[:, None])
        n_acc = jnp.cumprod(ok.astype(jnp.int32), axis=1).sum(axis=1)
    else:
        n_acc = jnp.zeros((B,), jnp.int32)
    n_commit = jnp.minimum(n_acc + 1, q_lens)  # accepted run + bonus/fix

    # in-trace rollback: rejected speculative entries drop out of `lengths`
    # on every owned (slot, row) — the appended values become invisible to
    # the kernel's length mask; the backend's host trim frees their blocks
    rows_b = (jnp.arange(B, dtype=jnp.int32) if rows is None
              else jnp.asarray(rows, jnp.int32))
    own_all = ((plan.slot_head >= 0)[:, :, None]
               & ((rows_b[None, None, :] % plan.replica_count[:, :, None])
                  == plan.replica_idx[:, :, None]))  # (L, S, B)
    trim = jnp.where(active_b, q_lens - n_commit, 0)  # (B,)
    lengths = cache.lengths - jnp.where(own_all, trim[None, None, :], 0)
    pos_next = jnp.where(active_b, positions + n_commit, positions)
    cache = dataclasses.replace(cache, lengths=lengths, positions=pos_next)
    last = jnp.take_along_axis(
        g, jnp.maximum(n_commit - 1, 0)[:, None], axis=1)[:, 0]
    new_state = ServeState(
        cache=cache, ssm_state=state.ssm_state, conv_state=state.conv_state,
        cross_k=state.cross_k, cross_v=state.cross_v,
        last_tokens=jnp.where(active_b, last, state.last_tokens),
        decode_steps=state.decode_steps + 1)
    return new_state, g, n_commit, logits


def _verify_attention(pl, hn, positions_q, q_lens, cfg, layer_idx, cache,
                      plan, decode_steps, ccfg, draft_filled, active, rows,
                      model_axis=None, data_axis=None, paged_impl="auto",
                      kv_kinds=None):
    """Multi-query slot attention over the speculative window (one layer).

    ``hn`` is (B, Q, D); every token projects and RoPEs at its own absolute
    position, then appends into the paged cache:

    - ``draft_filled`` layers already hold the window's first ``q_len - 1``
      entries (real KV written by propose); only query index ``q_len - 1``
      appends.
    - verify-only layers append all ``q_len`` valid tokens in query order —
      per-row sequential writes into the block, so quantize-on-write scales
      evolve exactly as under single-token decode.

    After the appends every live (slot, row) sits at ``base + q_len`` and
    the multi-query kernel masks query ``i`` to its causal prefix
    ``base + i + 1`` (`fairkv_decode_mq_ref`).  Returns
    ((B, S, Q, G, Dh), cache).
    """
    B, Q, _ = hn.shape
    from repro.serving.quant import deq
    q = jnp.einsum("bqd,sdgx->bsqgx", hn, deq(pl["wq_s"]))  # (B, S, Q, G, Dh)
    k_new = jnp.einsum("bqd,sdx->bsqx", hn, deq(pl["wk_s"]))  # (B, S, Q, Dh)
    v_new = jnp.einsum("bqd,sdx->bsqx", hn, deq(pl["wv_s"]))
    if "bq_s" in pl:
        q = q + pl["bq_s"][None, :, None]
        k_new = k_new + pl["bk_s"][None, :, None]
        v_new = v_new + pl["bv_s"][None, :, None]
    q = _rope_slots_mq(q, positions_q, cfg)
    k_new = _rope_slots_mq(k_new[:, :, :, None, :], positions_q,
                           cfg)[:, :, :, 0, :]
    own = (plan.owner_mask(layer_idx, B) if rows is None
           else plan.owner_mask_rows(layer_idx, rows))  # (S, B)
    own = own & active[None, :]
    window = M.layer_window(cfg, layer_idx)
    capacity = ccfg.static_capacity()
    table_l = cache.block_table[layer_idx]  # (S, B, M)
    if model_axis is not None:
        # same partition-localization as `_decode_attention` (DESIGN.md §10)
        n_part = cache.k_pool.shape[1]
        part_idx = jax.lax.axis_index(model_axis)
        if data_axis is not None:
            row_parts = jax.lax.psum(1, data_axis)
            part_idx = part_idx * row_parts + jax.lax.axis_index(data_axis)
        loc = table_l - part_idx * n_part
        table_l = jnp.where((loc > 0) & (loc < n_part), loc, 0)
    kinds = None
    if cache.k_scale is not None:
        grid_l = (jnp.zeros((cfg.n_kv_heads,), jnp.int32) if kv_kinds is None
                  else jnp.asarray(kv_kinds[layer_idx], jnp.int32))
        kinds = jnp.take(grid_l, jnp.maximum(plan.slot_head[layer_idx], 0))
    for qi in range(Q):
        m_q = (q_lens == qi + 1) if draft_filled else (qi < q_lens)
        own_q = own & m_q[None, :]
        # the appended entry's recorded position is `cache.positions` —
        # point it at this token's absolute position for the write
        cache = paged_append_token(
            dataclasses.replace(cache, positions=positions_q[:, qi]),
            layer_idx, k_new[:, :, qi].swapaxes(0, 1),
            v_new[:, :, qi].swapaxes(0, 1), own_q, decode_steps, capacity,
            ring=max(1, ccfg.decode_margin), table_layer=table_l, kinds=kinds)
    cache = dataclasses.replace(cache, positions=positions_q[:, 0])
    out = K.paged_fairkv_decode(
        q, cache.k_pool[layer_idx], cache.v_pool[layer_idx],
        cache.pos_pool[layer_idx], table_l, cache.lengths[layer_idx],
        capacity, attn_cap=cfg.attn_softcap, q_pos=positions_q[:, 0],
        window=window, impl=paged_impl,
        k_scale=(None if cache.k_scale is None
                 else cache.k_scale[layer_idx]),
        v_scale=(None if cache.v_scale is None
                 else cache.v_scale[layer_idx]),
        kinds=kinds, q_lens=q_lens)
    return out, cache


def _rope_slots_mq(q, positions_q, cfg):
    """RoPE over (B, S, Q, G, Dh) at per-(row, query) positions (B, Q)."""
    B, S_, Q, G, Dh = q.shape
    q2 = q.transpose(0, 2, 1, 3, 4).reshape(B, Q, S_ * G, Dh)
    q2 = L.apply_rope(q2, positions_q, cfg.rope_theta)
    return q2.reshape(B, Q, S_, G, Dh).transpose(0, 2, 1, 3, 4)


def _verify_slot_o(pl, attn, cfg, model_axis=None):
    """(B, S, Q, G, Dh) → (B, Q, D); the same single o-projection psum as
    `_decode_slot_o` — multi-query verify adds no new mesh collective."""
    from repro.serving.quant import deq
    out = jnp.einsum("bsqgx,sgxd->bqd", attn, deq(pl["wo_s"]))
    if model_axis is not None:
        out = jax.lax.psum(out, model_axis)
    return out


# ---------------------------------------------------------------------------
# Row-level state ops (continuous batching, DESIGN.md §7)
# ---------------------------------------------------------------------------


_KEEP = object()  # sentinel: "no cache override" (None is a real value)


def init_serve_state(cfg: ModelConfig, plan: PlanArrays, batch: int,
                     ccfg: CompressionConfig, dtype=jnp.float32,
                     cache=_KEEP) -> ServeState:
    """Empty B-row ServeState: every row retired (lengths 0, positions 0).

    The continuous-batching scheduler starts from this and splices prefilled
    requests into rows as they are admitted.  The default cache is an empty
    slot-layout one (a chunked prefill's seed); ``cache`` substitutes
    another (the paged backend's `PagedCache`) while reusing the
    SSM/conv/token initialization.  Encoder-decoder models are not
    supported (their cross-KV shape depends on per-request encoder inputs).
    """
    if cfg.is_encoder_decoder:
        raise NotImplementedError(
            "continuous batching does not support encoder-decoder models")
    if cache is _KEEP:
        cache = None
        if not cfg.attention_free:
            cache = init_cache(cfg.n_layers, int(plan.slot_head.shape[1]),
                               batch, ccfg.static_capacity(), cfg.head_dim,
                               dtype=dtype)
    ssm_state = conv_state = None
    if cfg.family in ("ssm", "hybrid"):
        s = cfg.ssm
        ssm_state = jnp.zeros((cfg.n_layers, batch, s.num_heads, s.head_dim,
                               s.state_size), jnp.float32)
        conv_state = jnp.zeros(
            (cfg.n_layers, batch, s.conv_width - 1,
             s.d_inner + 2 * s.n_groups * s.state_size), dtype)
    return ServeState(cache=cache, ssm_state=ssm_state, conv_state=conv_state,
                      cross_k=None, cross_v=None,
                      last_tokens=jnp.zeros((batch,), jnp.int32),
                      decode_steps=jnp.int32(0))


def splice_state(state: ServeState, sub: ServeState,
                 rows: jnp.ndarray, cache) -> ServeState:
    """Splice a prefilled sub-batch state into ``rows`` of the live state.

    ``sub`` must come from ``prefill(..., rows=rows)`` so its slot-cache
    ownership matches the target global rows.  ``decode_steps`` keeps the
    live value — the ring-write phase is global, not per-request.
    ``cache`` is the live cache with ``sub``'s rows already paged in (the
    paged backend's `paginate_rows`; None for attention-free models); the
    SSM/conv/token rows merge here.
    """
    rows = jnp.asarray(rows, jnp.int32)
    ssm = state.ssm_state
    if ssm is not None:
        ssm = ssm.at[:, rows].set(sub.ssm_state)
    conv = state.conv_state
    if conv is not None:
        conv = conv.at[:, rows].set(sub.conv_state.astype(conv.dtype))
    return ServeState(
        cache=cache, ssm_state=ssm, conv_state=conv,
        cross_k=state.cross_k, cross_v=state.cross_v,
        last_tokens=state.last_tokens.at[rows].set(sub.last_tokens),
        decode_steps=state.decode_steps)


def reset_state_rows(state: ServeState, rows, cache) -> ServeState:
    """Retire rows: clear their SSM/conv state and last tokens so the rows
    can be handed back to the freelist.  ``cache`` is the live cache with
    the rows already released (the paged backend's `release_rows`, which
    zeroes their lengths so their decode output is exactly zero; None for
    attention-free models)."""
    m = rows_to_mask(rows, state.last_tokens.shape[0])
    ssm = state.ssm_state
    if ssm is not None:
        ssm = jnp.where(m[None, :, None, None, None], 0, ssm)
    conv = state.conv_state
    if conv is not None:
        conv = jnp.where(m[None, :, None, None], 0, conv)
    return ServeState(
        cache=cache, ssm_state=ssm, conv_state=conv,
        cross_k=state.cross_k, cross_v=state.cross_v,
        last_tokens=jnp.where(m, 0, state.last_tokens),
        decode_steps=state.decode_steps)
