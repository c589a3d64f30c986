"""Causal flash attention for the serving prefill: JAX's splash attention
kernel (`jax.experimental.pallas.ops.tpu.splash_attention`), laid out for
GQA.

The admission prefill attends every prompt position to every earlier one
(positions ``arange(T)``, optional sliding window and softcap).  Splash
attention does that in one pass over VMEM tiles: bf16 operands with fp32
accumulation, the online softmax in fp32 scratch, no score block in HBM,
and every block the mask hides wholly (above the diagonal, below the
window) skipped.  It runs in MQA form once per KV head, the G query heads
of that KV head being its heads; the KV heads and the batch are vmapped.

Splash takes no score scale, so q is scaled by ``1/sqrt(head_dim)`` in
fp32 before the kernel and rounded back to its dtype.  The prompt is padded
to a whole number of blocks: padded keys lie after every real query, where
the causal mask hides them, and padded query rows are sliced off.

A kernel written for this prefill, with the G query heads folded into one
q block so each K/V block was fetched once per KV head, ran within 4% of
this one at 2k / 4k / 8k tokens on a v5e (PERF.md §6); the library
kernel is kept.  Validated in interpret mode against the model's
``dense_attention`` in tests/test_prefill_kernel.py; dispatched via
``ops.prefill_attention``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as _splash,
    splash_attention_mask as _mask,
)

# q and K/V block in tokens, and keys per compute step within a K/V block:
# the fastest of a block sweep on a v5e at qwen1.5-110b's widths (PERF.md §6)
BLOCK = 1024
BLOCK_KV_COMPUTE = 512
_LANES = 128


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def blocks_for(seq_len: int):
    """(block, padded length) in tokens for a prompt of ``seq_len``: a
    prompt shorter than ``BLOCK`` is one block, rounded up to the lanes."""
    block = min(BLOCK, _round_up(seq_len, _LANES))
    return block, _round_up(seq_len, block)


@functools.lru_cache(maxsize=None)
def _kernel(padded: int, group: int, window: int, attn_cap: float,
            interpret: bool):
    """The kernel of one shape.  Its block tables are made on the host once
    and kept as concrete arrays, even when first asked for inside a
    trace.  Padded lengths are whole blocks, so a process holds a few
    entries per layer window, not one per prompt length."""
    block, _ = blocks_for(padded)
    shape = (padded, padded)
    mask = (_mask.LocalMask(shape, (window - 1, 0), offset=0) if window > 0
            else _mask.CausalMask(shape))
    compute = BLOCK_KV_COMPUTE if block % BLOCK_KV_COMPUTE == 0 else block
    with jax.ensure_compile_time_eval():
        return _splash.make_splash_mqa_single_device(
            _mask.MultiHeadMask([mask] * group),
            block_sizes=_splash.BlockSizes(block_q=block, block_kv=block,
                                           block_kv_compute=compute),
            attn_logits_soft_cap=attn_cap if attn_cap > 0 else None,
            interpret=interpret)


def prefill_flash(
    q: jnp.ndarray,  # (B, T, Hq, Dh), positions arange(T)
    k: jnp.ndarray,  # (B, T, Hkv, Dh)
    v: jnp.ndarray,  # (B, T, Hkv, Dh)
    window: int = 0,
    attn_cap: float = 0.0,
    interpret: bool = False,
) -> jnp.ndarray:
    """Causal GQA attention of a whole prompt → (B, T, Hq, Dh): the
    contract of ``ref.prefill_attention_ref``."""
    B, T, Hq, Dh = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    _, padded = blocks_for(T)
    kernel = _kernel(padded, G, int(window), float(attn_cap), bool(interpret))
    pad = ((0, 0), (0, padded - T), (0, 0), (0, 0))
    q = (q.astype(jnp.float32) * (1.0 / math.sqrt(Dh))).astype(q.dtype)
    q = jnp.pad(q, pad).reshape(B, padded, Hkv, G, Dh).transpose(0, 2, 3, 1, 4)
    k = jnp.pad(k, pad).transpose(0, 2, 1, 3)  # (B, Hkv, padded, Dh)
    v = jnp.pad(v, pad).transpose(0, 2, 1, 3)
    out = jax.vmap(jax.vmap(kernel))(q, k, v)  # (B, Hkv, G, padded, Dh)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, padded, Hq, Dh)[:, :T]
