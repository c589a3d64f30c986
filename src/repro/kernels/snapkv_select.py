"""Pallas TPU kernel: SnapKV observation-window importance scores.

Computes, per (batch row, kv head), the total softmax attention mass each
position receives from the last W queries:

    imp[b, h, t] = Σ_{w, g} softmax_T(q[b, w, h, g] · k[b, :, h])_t

This is the compression-policy hot spot at prefill (W·T·Dh work per head vs
T·budget for selection).  Two-phase grid over T blocks:

  phase 0 (c < nT):  online (m, l) logsumexp accumulation per query
  phase 1 (c >= nT): emit Σ_{w,g} exp(s - m)/l for block c - nT

Both phases stream the same K blocks; the q tile (W·G, Dh) stays VMEM-
resident across the whole (b, h) program.  Key positions travel as
``(B, 1, T)`` and scores as ``(B, Hkv, 1, T)`` so each block's last two
dims are ``(1, block_t)`` — legal for the TPU lowering at any batch.
Validated in interpret mode against ``ref.snapkv_scores_ref``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(
    obs_pos_ref,  # (B, W) int32 scalar prefetch
    q_ref,  # (1, W*G, Dh)
    k_ref,  # (1, 1, block_t, Dh)
    kpos_ref,  # (1, 1, block_t) int32
    o_ref,  # (1, 1, 1, block_t) f32
    m_ref,  # (W*G, 1) f32
    l_ref,  # (W*G, 1) f32
    *,
    block_t: int,
    n_blocks: int,
    g: int,
    scale: float,
    attn_cap: float,
):
    b, h, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def scores_and_mask():
        q = q_ref[0].astype(jnp.float32)  # (W*G, Dh)
        k = k_ref[0, 0].astype(jnp.float32)  # (blk, Dh)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (W*G, blk)
        if attn_cap > 0:
            s = attn_cap * jnp.tanh(s / attn_cap)
        # (W*G, 1) position of each row's query (rows are w-major, g-minor),
        # read as W scalars — no in-kernel gather
        r = jax.lax.broadcasted_iota(jnp.int32, (s.shape[0], 1), 0)
        qp_row = jnp.zeros_like(r)
        for w in range(s.shape[0] // g):
            qp_row = jnp.where(r >= w * g, obs_pos_ref[b, w], qp_row)
        causal = kpos_ref[0] <= qp_row  # (1, blk) vs (W*G, 1)
        return jnp.where(causal, s, NEG_INF), causal

    @pl.when(c < n_blocks)
    def _phase_lse():
        s, causal = scores_and_mask()
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.where(causal, jnp.exp(s - m_new), 0.0)
        l_ref[...] = l_ref[...] * jnp.exp(m_prev - m_new) + p.sum(
            axis=1, keepdims=True)
        m_ref[...] = m_new

    @pl.when(c >= n_blocks)
    def _phase_emit():
        s, causal = scores_and_mask()
        m = m_ref[...]
        l = l_ref[...]
        p = jnp.where(causal, jnp.exp(s - m), 0.0) / jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = p.sum(axis=0, keepdims=True).astype(o_ref.dtype)


def snapkv_scores_pallas(
    q_obs: jnp.ndarray,  # (B, W, Hq, Dh)
    k: jnp.ndarray,  # (B, T, Hkv, Dh)
    obs_positions: jnp.ndarray,  # (B, W) int32
    k_positions: jnp.ndarray,  # (B, T) int32
    attn_cap: float = 0.0,
    block_t: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    B, W, Hq, Dh = q_obs.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    block_t = min(block_t, T)
    n_blocks = pl.cdiv(T, block_t)
    if T % block_t != 0:
        pad = n_blocks * block_t - T
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_positions = jnp.pad(k_positions, ((0, 0), (0, pad)),
                              constant_values=jnp.iinfo(jnp.int32).max)
    # (B, Hkv, W*G, Dh) query tile per (b, h)
    qt = q_obs.reshape(B, W, Hkv, G, Dh).transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, W * G, Dh)

    def q_map(b, h, c, opos):
        return (b * Hkv + h, 0, 0)

    def k_map(b, h, c, opos):
        cc = jnp.where(c < n_blocks, c, c - n_blocks)
        return (b, h, cc, 0)

    def kpos_map(b, h, c, opos):
        cc = jnp.where(c < n_blocks, c, c - n_blocks)
        return (b, 0, cc)

    def o_map(b, h, c, opos):
        cc = jnp.maximum(c - n_blocks, 0)
        return (b, h, 0, cc)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, 2 * n_blocks),
        in_specs=[
            pl.BlockSpec((1, W * G, Dh), q_map),
            pl.BlockSpec((1, 1, block_t, Dh), k_map),
            pl.BlockSpec((1, 1, block_t), kpos_map),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, block_t), o_map),
        scratch_shapes=[
            pltpu.VMEM((W * G, 1), jnp.float32),
            pltpu.VMEM((W * G, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel, block_t=block_t, n_blocks=n_blocks, g=G,
        scale=1.0 / math.sqrt(Dh), attn_cap=attn_cap)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, 1, n_blocks * block_t),
                                       jnp.float32),
        interpret=interpret,
        name="snapkv_select",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(obs_positions, qt.reshape(B * Hkv, W * G, Dh),
      k.transpose(0, 2, 1, 3), k_positions.reshape(B, 1, -1))
    return out[:, :, 0, :T]
