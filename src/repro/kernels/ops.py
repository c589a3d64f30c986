"""Jit'd kernel entry points with backend dispatch.

``backend`` (slot-layout kernels):
- "jnp"       pure-jnp reference (always available; used under pjit where the
              XLA partitioner handles sharding)
- "pallas"    the Pallas TPU kernel (TARGET path; on CPU runs via
              ``interpret=True`` for correctness validation)
- "auto"      pallas on TPU, jnp elsewhere

``impl`` (paged decode):
- "pallas"    native block-table kernel (`kernels/paged_fairkv_decode.py`):
              HBM traffic proportional to allocated blocks (TARGET path)
- "gather"    materialize capacity-sized contiguous views, reuse the slot
              kernel (`kernels/paged_decode.py`) — the migration/debug path
- "jnp"       pure-jnp oracle (`ref.paged_fairkv_decode_ref`)
- "auto"      pallas on TPU, jnp elsewhere

Prefill attention (`prefill_attention`) takes no knob: the flash kernel
(`kernels/prefill_flash.py`) on TPU, ``ref.prefill_attention_ref`` (the
model's ``attention``) elsewhere.

``REPRO_PALLAS_INTERPRET=1`` forces every "auto" dispatch onto the Pallas
kernels in interpret mode off-TPU — the CI ``kernels-interpret`` gate uses
it so kernel regressions fail in a named job instead of hiding behind the
jnp fallback.  On a TPU it is an error: interpret mode there would run the
serving path without its compiled kernels.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

from repro.kernels import ref as _ref

# paged decode implementations accepted by `paged_fairkv_decode` (and by
# `PagingConfig.decode_impl`, which validates against this tuple)
PAGED_DECODE_IMPLS = ("auto", "pallas", "gather", "jnp")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _force_interpret() -> bool:
    """True when REPRO_PALLAS_INTERPRET forces Pallas-interpret off-TPU;
    raises when it is set on a TPU backend."""
    if os.environ.get("REPRO_PALLAS_INTERPRET", "") in ("", "0"):
        return False
    if _on_tpu():
        raise RuntimeError(
            "REPRO_PALLAS_INTERPRET is set but the backend is a TPU; unset "
            "it — interpret mode would bypass the compiled kernels")
    return True


def _use_pallas(backend: str) -> bool:
    if backend == "jnp":
        return False
    if backend == "auto":
        return _force_interpret() or _on_tpu()
    return True


def pallas_in_decode(paged_impl: str = "auto") -> bool:
    """True when the decode step's attention resolves to a Pallas kernel
    under the current backend/env — the mesh executor must then build its
    decode ``shard_map`` with ``check_vma=False`` (``pallas_call`` has no
    varying-manual-axes rule for the static checker; the psum-reassembly
    contract is unchanged, only its static verification is skipped)."""
    # slot kernel and "auto"/"gather" paged dispatch all hit pallas then
    return _use_pallas("auto") or paged_impl == "pallas"


def fairkv_decode(q, k, v, lengths, attn_cap: float = 0.0,
                  k_pos=None, q_pos=None, window: int = 0,
                  backend: str = "auto", block_c: int = 128,
                  interpret: Optional[bool] = None):
    """Slot-layout decode attention (see ref.fairkv_decode_ref)."""
    if not _use_pallas(backend):
        return _ref.fairkv_decode_ref(q, k, v, lengths, attn_cap,
                                      k_pos=k_pos, q_pos=q_pos, window=window)
    from repro.kernels.fairkv_decode import fairkv_decode_pallas
    ipret = (not _on_tpu()) if interpret is None else interpret
    return fairkv_decode_pallas(q, k, v, lengths, attn_cap=attn_cap,
                                k_pos=k_pos, q_pos=q_pos, window=window,
                                block_c=block_c, interpret=ipret)


def paged_fairkv_decode(q, k_pool, v_pool, pos_pool, block_table, lengths,
                        capacity: int, attn_cap: float = 0.0, q_pos=None,
                        window: int = 0, impl: str = "auto",
                        block_c: int = 128,
                        interpret: Optional[bool] = None,
                        k_scale=None, v_scale=None, kinds=None,
                        q_lens=None):
    """Paged decode attention (see ref.paged_fairkv_decode_ref).

    Same contract as ``fairkv_decode`` with (k, v, k_pos) replaced by one
    layer's (pools, block table); ``impl`` picks the implementation (module
    docstring).  All impls agree on the valid prefix — the native kernel is
    validated against the oracle in tests/test_paged_kernel.py and holds
    token parity with the gather and slot paths through `Engine.generate`.

    ``k_scale``/``v_scale`` ((N,) fp32) and ``kinds`` ((S,) int32) carry the
    quantized-pool dequant state (DESIGN.md §15); every impl applies the
    identical dequant semantics, so quantized parity tests compare real
    implementations rather than a shared helper against itself.

    A 5-D ``q`` of shape (B, S, Q, G, Dh) selects the multi-query
    speculative-verify path (DESIGN.md §16): query ``i`` of row ``b``
    attends causally within the speculative window, ``q_lens`` ((B,) int32,
    default all-Q) bounding the valid queries per row.  Every impl applies
    the same per-query mask, so the verify kernel validates against the
    same oracle chain as single-token decode.
    """
    if impl not in PAGED_DECODE_IMPLS:
        raise ValueError(
            f"unknown paged decode impl {impl!r}; known: "
            f"{list(PAGED_DECODE_IMPLS)}")
    if impl == "auto":
        impl = "pallas" if _use_pallas("auto") else "jnp"
    if impl == "jnp":
        return _ref.paged_fairkv_decode_ref(
            q, k_pool, v_pool, pos_pool, block_table, lengths, capacity,
            attn_cap, q_pos=q_pos, window=window,
            k_scale=k_scale, v_scale=v_scale, kinds=kinds, q_lens=q_lens)
    if impl == "gather":
        from repro.kernels.paged_decode import paged_fairkv_decode_gather
        return paged_fairkv_decode_gather(
            q, k_pool, v_pool, pos_pool, block_table, lengths, capacity,
            attn_cap=attn_cap, q_pos=q_pos, window=window, backend="auto",
            block_c=block_c, interpret=interpret,
            k_scale=k_scale, v_scale=v_scale, kinds=kinds, q_lens=q_lens)
    from repro.kernels.paged_fairkv_decode import paged_fairkv_decode_pallas
    ipret = (not _on_tpu()) if interpret is None else interpret
    return paged_fairkv_decode_pallas(
        q, k_pool, v_pool, pos_pool, block_table, lengths, capacity,
        attn_cap=attn_cap, q_pos=q_pos, window=window, interpret=ipret,
        k_scale=k_scale, v_scale=v_scale, kinds=kinds, q_lens=q_lens)


def prefill_attention_impl() -> str:
    """The implementation ``prefill_attention`` runs: "pallas" on TPU (or
    forced by ``REPRO_PALLAS_INTERPRET``), "jnp" elsewhere.  Executors
    label ``prefill_attn_total`` with it per prefill call."""
    return "pallas" if _use_pallas("auto") else "jnp"


def prefill_attention(q, k, v, window: int = 0, attn_cap: float = 0.0):
    """Causal attention of a whole prompt at positions ``arange(T)`` (see
    ref.prefill_attention_ref): q (B, T, Hq, Dh), k/v (B, T, Hkv, Dh) →
    (B, T, Hq, Dh)."""
    if prefill_attention_impl() == "jnp":
        return _ref.prefill_attention_ref(q, k, v, window, attn_cap)
    from repro.kernels.prefill_flash import prefill_flash
    return prefill_flash(q, k, v, window=window, attn_cap=attn_cap,
                         interpret=not _on_tpu())


def snapkv_scores(q_obs, k, obs_positions, k_positions, attn_cap: float = 0.0,
                  backend: str = "auto", block_t: int = 128,
                  interpret: Optional[bool] = None):
    """Observation-window importance scores (see ref.snapkv_scores_ref)."""
    if not _use_pallas(backend):
        return _ref.snapkv_scores_ref(q_obs, k, obs_positions, k_positions,
                                      attn_cap)
    from repro.kernels.snapkv_select import snapkv_scores_pallas
    ipret = (not _on_tpu()) if interpret is None else interpret
    return snapkv_scores_pallas(q_obs, k, obs_positions, k_positions,
                                attn_cap=attn_cap, block_t=block_t,
                                interpret=ipret)
