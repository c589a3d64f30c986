"""Pallas TPU kernel: native paged decode attention over block pools.

The serving path's one decode attention (DESIGN.md §11), the paged analog
of vLLM's PagedAttention: it consumes the ``(N, bs, Dh)`` pools and the
``(S, B, M)`` block table directly, never materializing a capacity-sized
``(S, B, C, Dh)`` view, so HBM→VMEM traffic (the decode bottleneck) is
proportional to the **allocated blocks** — the realized retained lengths
FairKV balances across shards.

Design (TPU-adapted flash-decoding over block tables):
- grid = (S, B, M); one program attends one (slot, row) over one pool
  block of ``bs`` positions (logical columns ``[j·bs, (j+1)·bs)``).  The
  query axes fold into one ``(Q·G, Dh)`` block: Q = 1 for single-token
  decode, Q = k + 1 for the speculative-verify window (DESIGN.md §16).
- the block table, ``lengths``, ``q_pos`` and ``q_lens`` ride in scalar
  prefetch; the K/V BlockSpec index maps resolve ``table[s, b, j]`` per
  grid step.  Steps
  past ``ceil(len/bs)`` clamp to the *last valid* block's pool index, so
  consecutive grid steps map to the same block and the Pallas TPU pipeline
  skips the redundant copy — null and past-length blocks cost no bandwidth.
- every block's last two dims equal the array's (the TPU lowering's rule
  for blocks narrower than an (8, 128) tile): positions travel as
  ``(N, 1, bs)`` and scales as ``(N, 1, 1)``.
- rows with no valid blocks resolve to the table's first entry (the null
  block); its garbage never reaches the output because the in-kernel
  length mask zeroes every score past ``lengths[s, b]``.
- online softmax (m, l, acc) in VMEM scratch, fp32; the final grid step
  writes ``acc / l`` (exact zeros for rows the slot does not own).
- sliding-window masking uses the pool's per-entry absolute positions
  (gemma2 local layers / hymba) and gemma2's attention softcap is applied
  before masking, the same math as the jnp oracle.

Quantized pools (DESIGN.md §15): when the backend stores int8 codes the
kernel takes two extra ``(N, 1, 1)`` fp32 scale operands whose BlockSpecs ride
the *same* block-id index map as K/V — each grid step's HBM→VMEM copy is
then ``2·bs·Dh`` bytes of codes plus 8 bytes of scale instead of
``2·bs·Dh·itemsize`` bytes of floats, and the dequant
(``codes → fp32 · scale``) happens in-register inside the online-softmax
loop.  A fifth scalar-prefetch operand carries the (S,) per-slot kind
codes (0 = int8, 1 = fp8-bitcast) selecting the dequant interpretation per
program.  The unquantized path takes neither — the quantized knob off
compiles the same kernel as before it existed.

Validated in interpret mode against ``ref.paged_fairkv_decode_ref``
(tests/test_paged_kernel.py); dispatched via ``ops.paged_fairkv_decode``.
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

# kernels stay self-contained (no repro.paging import): local fp8 probe,
# matching kvquant.fp8_supported / ref._HAS_FP8
_HAS_FP8 = hasattr(jnp, "float8_e4m3fn")


def _dequant(codes, scale, kind):
    """In-kernel block dequant: int8 codes → fp32 at the block's scale.

    ``kind`` selects int8 (codes are signed integers) vs fp8 (codes are
    bitcast float8_e4m3fn); fp8 NaN bit patterns — possible only in
    never-written garbage the length mask will discard — flush to 0 so they
    cannot poison ``p·v`` through 0·NaN.
    """
    f = codes.astype(jnp.float32)
    if _HAS_FP8:
        f8 = jax.lax.bitcast_convert_type(
            codes, jnp.float8_e4m3fn).astype(jnp.float32)
        f8 = jnp.where(f8 == f8, f8, 0.0)
        f = jnp.where(kind == 1, f8, f)
    return f * scale


def _query_index(rows: int, n_q: int, group: int):
    """(rows, 1) int32 query index of each folded ``(Q·G)`` score row
    (``row // group``), built from compares — no vector integer division."""
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    qi = jnp.zeros_like(r)
    for i in range(1, n_q):
        qi = jnp.where(r >= i * group, i, qi)
    return qi


def _kernel(
    *refs,
    bs: int,
    n_blocks: int,
    n_q: int,
    group: int,
    scale: float,
    attn_cap: float,
    window: int,
    quantized: bool,
):
    """One program attends the folded ``(Q·G, Dh)`` query block of one
    (slot, row) over one pool block.  With ``n_q > 1`` (speculative verify)
    the causal mask within the window is a per-query length limit: query
    ``i`` of a row with ``qn`` valid queries sees the first
    ``len − (qn − 1 − i)`` entries (own token included, later speculative
    tokens excluded)."""
    # operand order mirrors the pallas_call below: scalar prefetch (table,
    # lengths, q_pos, q_lens[, kinds]), then inputs (q, k, v, kpos[,
    # k_scale, v_scale]), output, scratch
    if quantized:
        (table_ref, lengths_ref, q_pos_ref, q_lens_ref, kinds_ref,
         q_ref, k_ref, v_ref, kpos_ref, ksc_ref, vsc_ref,
         o_ref, acc_ref, m_ref, l_ref) = refs
    else:
        (table_ref, lengths_ref, q_pos_ref, q_lens_ref,
         q_ref, k_ref, v_ref, kpos_ref,
         o_ref, acc_ref, m_ref, l_ref) = refs
    s, b, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    ln = lengths_ref[s, b]
    n_valid = (ln + bs - 1) // bs

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j < n_valid)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (Q·G, Dh)
        if quantized:
            k = _dequant(k_ref[0], ksc_ref[0], kinds_ref[s])  # (bs, Dh)
        else:
            k = k_ref[0].astype(jnp.float32)  # (bs, Dh)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (Q·G, bs)
        if attn_cap > 0:
            scores = attn_cap * jnp.tanh(scores / attn_cap)
        offs = j * bs + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        qp = q_pos_ref[b]
        if n_q == 1:
            valid = offs < ln  # masks the last block's partial fill too
        else:
            qi = _query_index(scores.shape[0], n_q, group)  # (Q·G, 1)
            qn = q_lens_ref[b]
            # per-query causal limit; garbage lanes (qi >= qn) clamp to ln
            valid = offs < jnp.minimum(ln - (qn - 1 - qi), ln)
            qp = qp + qi  # query i sits at q_pos + i
        if window > 0:
            # (1, bs) int32 absolute entry positions of this block
            valid &= kpos_ref[0] > (qp - window)
        scores = jnp.where(valid, scores, NEG_INF)
        m_prev = m_ref[...]  # (Q·G, 1)
        m_new = jnp.maximum(m_prev, scores.max(axis=1, keepdims=True))
        # explicit mask: when every entry is masked, m_new stays NEG_INF and
        # exp(NEG_INF - NEG_INF) would be 1 — the mask zeroes it instead
        p = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_new
        if quantized:
            v = _dequant(v_ref[0], vsc_ref[0], kinds_ref[s])  # (bs, Dh)
        else:
            v = v_ref[0].astype(jnp.float32)  # (bs, Dh)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv

    @pl.when(j == n_blocks - 1)
    def _finalize():
        l = l_ref[...]
        out = acc_ref[...] / jnp.where(l > 0, l, 1.0)
        out = jnp.where(l > 0, out, 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def paged_fairkv_decode_pallas(
    q: jnp.ndarray,  # (B, S, G, Dh); (B, S, Q, G, Dh) = multi-query verify
    k_pool: jnp.ndarray,  # (N, bs, Dh) — one layer's key pool
    v_pool: jnp.ndarray,  # (N, bs, Dh)
    pos_pool: jnp.ndarray,  # (N, bs) int32
    block_table: jnp.ndarray,  # (S, B, M) int32; <=0 = null block
    lengths: jnp.ndarray,  # (S, B) int32
    capacity: int,
    attn_cap: float = 0.0,
    q_pos: Optional[jnp.ndarray] = None,  # (B,) int32
    window: int = 0,
    interpret: bool = False,
    k_scale: Optional[jnp.ndarray] = None,  # (N,) fp32 per-block scales
    v_scale: Optional[jnp.ndarray] = None,  # (N,)
    kinds: Optional[jnp.ndarray] = None,  # (S,) int32 per-slot kind codes
    q_lens: Optional[jnp.ndarray] = None,  # (B,) valid queries (5D q only)
) -> jnp.ndarray:
    """Decode attention over one paged layer — same contract as
    ``ref.paged_fairkv_decode_ref``, consuming pools + table directly.

    A 5-D ``q`` selects the multi-query speculative-verify semantics; both
    forms fold the query axes into one ``(Q·G, Dh)`` block per program
    (Q = 1 for single-token decode).  Operands are laid out so every
    block's last two dims equal the array's — ``pos_pool`` as
    ``(N, 1, bs)``, scales as ``(N, 1, 1)`` — which is what the TPU
    lowering requires of blocks narrower than an (8, 128) tile.
    """
    if q.ndim == 5:
        B, S, Q, G, Dh = q.shape
    else:
        (B, S, G, Dh), Q = q.shape, 1
    N, bs, _ = k_pool.shape
    M = block_table.shape[2]
    if M * bs < capacity:
        raise ValueError(
            f"block table spans {M}x{bs} tokens < capacity {capacity}")
    table = jnp.asarray(block_table, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    q_pos = (jnp.zeros((B,), jnp.int32) if q_pos is None
             else jnp.asarray(q_pos, jnp.int32))
    q_lens = (jnp.full((B,), Q, jnp.int32) if q_lens is None
              else jnp.asarray(q_lens, jnp.int32))
    quantized = k_scale is not None

    # *rest absorbs the scalar-prefetch refs past (table, lengths)
    def q_map(s, b, j, tbl, lens, *rest):
        return (b, s, 0, 0)

    def pool_map(s, b, j, tbl, lens, *rest):
        # clamp past-length grid steps to the last valid block so
        # consecutive steps map to equal indices (pipeline skips the copy);
        # rows with no valid blocks resolve to entry 0 (the null block)
        ln = lens[s, b]
        last_valid = jnp.maximum((ln + bs - 1) // bs - 1, 0)
        jj = jnp.minimum(j, last_valid)
        return (jnp.maximum(tbl[s, b, jj], 0), 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, Q * G, Dh), q_map),
        pl.BlockSpec((1, bs, Dh), pool_map),
        pl.BlockSpec((1, bs, Dh), pool_map),
        pl.BlockSpec((1, 1, bs), pool_map),
    ]
    prefetch = [table, lengths, q_pos, q_lens]
    inputs = [q.reshape(B, S, Q * G, Dh), k_pool, v_pool,
              jnp.asarray(pos_pool, jnp.int32).reshape(N, 1, bs)]
    if quantized:
        prefetch.append(jnp.zeros((S,), jnp.int32) if kinds is None
                        else jnp.asarray(kinds, jnp.int32))
        inputs += [jnp.asarray(k_scale, jnp.float32).reshape(N, 1, 1),
                   jnp.asarray(v_scale, jnp.float32).reshape(N, 1, 1)]
        in_specs += [pl.BlockSpec((1, 1, 1), pool_map)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(S, B, M),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, Q * G, Dh), q_map),
        scratch_shapes=[
            pltpu.VMEM((Q * G, Dh), jnp.float32),
            pltpu.VMEM((Q * G, 1), jnp.float32),
            pltpu.VMEM((Q * G, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel, bs=bs, n_blocks=M, n_q=Q, group=G,
        scale=1.0 / math.sqrt(Dh), attn_cap=attn_cap, window=window,
        quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, Q * G, Dh), q.dtype),
        interpret=interpret,
        name="paged_fairkv_decode",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*prefetch, *inputs)
    return out.reshape(q.shape)
