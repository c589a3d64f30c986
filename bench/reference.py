"""Plain float32 reference of the served decoder, with HeadKV's keep rule.

It follows the published decoder the cell runs: an embedding, the layers
of its family (``families/<family>.py``: ``prompt_layer``/``decode_layer``,
built from the shared pieces of ``ref_layers.py``, each layer told its
``layer_kind``), a final RMSNorm and an unembedding (tied or not), with
the multipliers of the families that publish them (Granite), whether the
program folds them into its weights or applies them itself: the
embeddings times ``embedding_multiplier``, attention scores times
``attention_multiplier`` (1/sqrt(head_dim) where none is published), each
residual branch times ``residual_multiplier``, the logits divided by
``logits_scaling``.

On top of that it applies what the served path does to the cache, so that
its logits are those of the same computation, exactly:

- HeadKV selection at the end of prefill: SnapKV observation scores (the
  softmax of the last ``obs_window`` queries over the prompt, summed over
  the window and the query group, max-pooled by ``pool``), sinks and the
  observation window always kept, and each head keeping the top
  ``headkv_keep`` positions (``counts.py``);
- decode attention over the kept prompt entries plus the generated tokens,
  with the cache's recency ring once a head reaches its capacity (the ring's
  write phase is the engine's global decode-step count, given per request
  as ``d0``, the count at its first decode append).

It imports nothing of the program and takes nothing the program made: the
weights are rebuilt from the seed (``weights.py``), layer by layer, so a
full-width model fits beside its activations.  Every matmul runs at
``Precision.HIGHEST``.  ``precision="fp8"`` is the control: every weight
matmul takes both operands rounded to float8_e4m3 (per-channel and per-row
absmax scales; the family's ``FP8_WEIGHTS`` names the layer weights), the
step below the bfloat16 the configurations serve in.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

import families
from counts import headkv_keep, static_capacity
from ref_layers import fp8_round, mm, rms
from weights import (checkpoint_final_norm, checkpoint_layer, embed_table,
                     head_table)

DEC_PAD = 256  # decode queries are padded to a multiple of this


@dataclass
class Served:
    """One finished request: its prompt, the tokens it was served, and the
    engine's decode-step count at its first decode append."""

    prompt: np.ndarray
    tokens: np.ndarray
    d0: int


def ring_deaths(keep: np.ndarray, n_dec: int, d0: int, capacity: int,
                ring: int, n_pad: int) -> tuple:
    """When each cache entry stops being visible, per (layer, head).

    Replays the cache's appends: decode token k lands at column
    ``lengths`` while the head is below ``capacity``, else at
    ``capacity − R + (d0 + k) mod R`` (R = the ring width), overwriting
    what was there; lengths stop at capacity.  Returns ``death_p``
    (L, H, capacity) for prompt columns (column j holds the j-th kept
    position) and ``death_d`` (L, H, n_pad) for decode tokens: the first
    decode query that no longer sees the entry (a large value if none)."""
    L, H = keep.shape
    R = max(1, min(ring, capacity))
    K = keep.reshape(-1).astype(np.int64)
    LH = K.size
    never = n_pad + 1
    death_p = np.full((LH, capacity), never, np.int64)
    death_d = np.zeros((LH, n_pad), np.int64)
    death_d[:, :n_dec] = never
    col = np.arange(capacity)[None, :]
    occ = np.where(col < K[:, None], col, -1)  # prompt column j -> id j
    length = K.copy()
    ar = np.arange(LH)
    for k in range(n_dec):
        c = np.where(length < capacity, length,
                     capacity - R + (d0 + k) % R)
        prev = occ[ar, c]
        p = (prev >= 0) & (prev < capacity)
        death_p[ar[p], prev[p]] = k
        d = prev >= capacity
        death_d[ar[d], prev[d] - capacity] = k
        occ[ar, c] = capacity + k
        length = np.minimum(length + 1, capacity)
    return (death_p.reshape(L, H, capacity),
            death_d.reshape(L, H, n_pad))


def _load_layer(m, key, dtype, fp8):
    """Rebuild layer i in float32 (or fp8-rounded) on the device.  ``i``
    is static: a family's ``base_layer`` and ``extras`` may branch on it
    (a router's bias on its MoE layers alone)."""
    def fn(key, i):
        w = checkpoint_layer(m, key, i, dtype)
        if fp8:
            for name, axes in families.of(m).FP8_WEIGHTS.items():
                if name in w:
                    w[name] = fp8_round(w[name], axes)
        return w
    return jax.jit(fn, static_argnums=1)


def logits(m: dict, comp: dict, imp: np.ndarray, seed_key, dtype,
           served: list, precision: str = "fp32") -> list:
    """Per request, the (n, V) logits at every served position: position 0
    is the prompt's last token, position k + 1 the k-th decode step."""
    fp8 = {"fp32": False, "fp8": True}[precision]
    fam = families.of(m)
    mk = tuple(sorted(m.items()))
    ck = tuple(sorted(comp.items()))
    L = m["n_layers"]
    cap = static_capacity(comp)
    emb = (embed_table(m, seed_key, dtype).astype(jnp.float32)
           * m["embedding_multiplier"])
    state = []
    for r in served:
        T, n = len(r.prompt), len(r.tokens)
        n_dec = n - 1
        n_pad = max(DEC_PAD, -(-n_dec // DEC_PAD) * DEC_PAD)
        keep = headkv_keep(imp, comp, T)
        dp, dd = ring_deaths(keep, n_dec, r.d0, cap, comp["decode_margin"],
                             n_pad)
        dec_in = np.zeros(n_pad, np.int32)
        dec_in[:n_dec] = r.tokens[:-1]
        state.append({
            "T": T, "n": n, "keep": keep, "death_p": dp, "death_d": dd,
            "hp": emb[jnp.asarray(r.prompt)], "hd": emb[jnp.asarray(dec_in)]})
    del emb
    load = _load_layer(m, seed_key, dtype, fp8)
    for i in range(L):
        lw = load(seed_key, i)
        kind = fam.layer_kind(m, i)
        for st in state:
            keep_i = jnp.asarray(st["keep"][i])
            hp, kp, vp, sel = fam.prompt_layer(lw, st["hp"], keep_i, m=mk,
                                               comp=ck, fp8=fp8, kind=kind)
            kcap = sel.shape[1]
            st["hd"] = fam.decode_layer(
                lw, st["hd"], st["T"], kp, vp, sel, keep_i,
                jnp.asarray(st["death_p"][i][:, :kcap]),
                jnp.asarray(st["death_d"][i]), m=mk, fp8=fp8, kind=kind)
            st["hp"] = hp
        del lw
    fnorm = checkpoint_final_norm(m, seed_key, dtype)
    head = head_table(m, seed_key, dtype).astype(jnp.float32)
    if fp8:
        head = fp8_round(head, 1)
    out = []
    for st in state:
        h = jnp.concatenate([st["hp"][-1:], st["hd"][:st["n"] - 1]])
        x = rms(h, fnorm, m["rms_eps"])
        lg = (mm(x, head, "nd,vd->nv", fp8)[:, :m["vocab_size"]]
              / m["logits_scaling"])
        out.append(lg)
        del st["hp"], st["hd"]
    return out


def served_gap(ref_logits, tokens) -> np.ndarray:
    """Per position, how far the served token's reference logit lies below
    the reference's best (0 where the served token is the argmax)."""
    lg = jnp.asarray(ref_logits)
    tok = jnp.asarray(np.asarray(tokens, np.int32))
    got = jnp.take_along_axis(lg, tok[:, None], axis=1)[:, 0]
    return np.asarray(lg.max(axis=1) - got)
