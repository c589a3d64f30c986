"""Pallas TPU kernel: slot-layout decode attention with per-(slot,row)
dynamic KV lengths — the FairKV hot loop.

Design (TPU-adapted flash-decoding):
- grid = (S, B, n_kv_blocks); one program attends one (slot, row) over one
  KV block of ``block_c`` positions.
- ``lengths`` (S, B) rides in scalar-prefetch; the K/V BlockSpec index maps
  clamp the block index to the last *valid* block, so all grid steps past
  ``ceil(len/block_c)`` map to the same block — the Pallas TPU pipeline skips
  the redundant copy when consecutive indices are equal, making HBM→VMEM
  traffic (the decode bottleneck) proportional to the retained length.  This
  is exactly the property FairKV balances across shards (DESIGN.md §2).
- online softmax (m, l, acc) in VMEM scratch, fp32; the final block writes
  ``acc / l`` (zeros for rows the slot does not own, i.e. len == 0).
- optional sliding-window masking via per-entry absolute positions
  (gemma2 local layers / hymba), and gemma2's attention softcap.  The
  positions travel as ``(S, B, 1, C)`` so their block's last two dims are
  ``(1, block_c)`` — legal for the TPU lowering at any batch width.

Validated in interpret mode against ``ref.fairkv_decode_ref`` over
shape/dtype sweeps (tests/test_kernels.py).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(
    # scalar prefetch
    lengths_ref,  # (S, B) int32
    q_pos_ref,  # (B,) int32
    # inputs
    q_ref,  # (1, 1, G, Dh)
    k_ref,  # (1, 1, block_c, Dh)
    v_ref,  # (1, 1, block_c, Dh)
    kpos_ref,  # (1, 1, 1, block_c) int32
    # output
    o_ref,  # (1, 1, G, Dh)
    # scratch
    acc_ref,  # (G, Dh) f32
    m_ref,  # (G, 1) f32
    l_ref,  # (G, 1) f32
    *,
    block_c: int,
    n_blocks: int,
    scale: float,
    attn_cap: float,
    window: int,
):
    s, b, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    ln = lengths_ref[s, b]
    n_valid = (ln + block_c - 1) // block_c

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(c < n_valid)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (G, Dh)
        k = k_ref[0, 0].astype(jnp.float32)  # (blk, Dh)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (G, blk)
        if attn_cap > 0:
            scores = attn_cap * jnp.tanh(scores / attn_cap)
        offs = c * block_c + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        valid = offs < ln
        if window > 0:
            kp = kpos_ref[0, 0]  # (1, blk) int32
            valid &= kp > (q_pos_ref[b] - window)
        scores = jnp.where(valid, scores, NEG_INF)
        m_prev = m_ref[...]  # (G, 1)
        m_new = jnp.maximum(m_prev, scores.max(axis=1, keepdims=True))
        # explicit mask: when every entry is masked, m_new stays NEG_INF and
        # exp(NEG_INF - NEG_INF) would be 1 — the mask zeroes it instead
        p = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_new
        v = v_ref[0, 0].astype(jnp.float32)  # (blk, Dh)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv

    @pl.when(c == n_blocks - 1)
    def _finalize():
        l = l_ref[...]
        out = acc_ref[...] / jnp.where(l > 0, l, 1.0)
        out = jnp.where(l > 0, out, 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def fairkv_decode_pallas(
    q: jnp.ndarray,  # (B, S, G, Dh)
    k: jnp.ndarray,  # (S, B, C, Dh)
    v: jnp.ndarray,  # (S, B, C, Dh)
    lengths: jnp.ndarray,  # (S, B) int32
    attn_cap: float = 0.0,
    k_pos: Optional[jnp.ndarray] = None,  # (S, B, C) int32
    q_pos: Optional[jnp.ndarray] = None,  # (B,) int32
    window: int = 0,
    block_c: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    B, S, G, Dh = q.shape
    C = k.shape[2]
    block_c = min(block_c, C)
    n_blocks = pl.cdiv(C, block_c)
    if C % block_c != 0:  # pad capacity to a block multiple
        pad = n_blocks * block_c - C
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        if k_pos is not None:
            k_pos = jnp.pad(k_pos, ((0, 0), (0, 0), (0, pad)),
                            constant_values=-1)
    if k_pos is None:
        k_pos = jnp.zeros(k.shape[:3], jnp.int32)
    if q_pos is None:
        q_pos = jnp.zeros((B,), jnp.int32)

    def q_map(s, b, c, lens, qp):
        return (b, s, 0, 0)

    def last_valid(s, b, c, lens):
        ln = lens[s, b]
        return jnp.minimum(c, jnp.maximum((ln + block_c - 1) // block_c - 1, 0))

    def kv_map(s, b, c, lens, qp):
        return (s, b, last_valid(s, b, c, lens), 0)

    def kpos_map(s, b, c, lens, qp):
        return (s, b, 0, last_valid(s, b, c, lens))

    def o_map(s, b, c, lens, qp):
        return (b, s, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, B, n_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, G, Dh), q_map),
            pl.BlockSpec((1, 1, block_c, Dh), kv_map),
            pl.BlockSpec((1, 1, block_c, Dh), kv_map),
            pl.BlockSpec((1, 1, 1, block_c), kpos_map),
        ],
        out_specs=pl.BlockSpec((1, 1, G, Dh), o_map),
        scratch_shapes=[
            pltpu.VMEM((G, Dh), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel, block_c=block_c, n_blocks=n_blocks,
        scale=1.0 / math.sqrt(Dh), attn_cap=attn_cap, window=window)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, G, Dh), q.dtype),
        interpret=interpret,
        name="fairkv_decode",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(lengths, q_pos, q, k, v, k_pos.reshape(S, B, 1, k_pos.shape[2]))
    return out
