"""Plain float32 reference of the served decoder, with HeadKV's keep rule.

It follows the published dense GQA decoder the cells run: RMSNorm (applied
as ``x · rsqrt(mean x² + eps) · (1 + scale)``), rotary embeddings (half-split
"rotate half" form, base ``rope_theta``), grouped-query attention with
optional q/k/v biases, a SwiGLU MLP, and an unembedding (tied or not),
with the multipliers of the families that publish them (Granite): the
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
absmax scales), the step below the bfloat16 the configurations serve in.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from counts import headkv_keep, static_capacity
from weights import (checkpoint_final_norm, checkpoint_layer, embed_table,
                     head_table)

HI = jax.lax.Precision.HIGHEST
NEG = -1e30
FP8_MAX = 448.0  # largest finite float8_e4m3fn
PROMPT_CHUNK = 512  # queries per block of the prompt's causal attention
DEC_PAD = 256  # decode queries are padded to a multiple of this


@dataclass
class Served:
    """One finished request: its prompt, the tokens it was served, and the
    engine's decode-step count at its first decode append."""

    prompt: np.ndarray
    tokens: np.ndarray
    d0: int


def _fp8_round(x, axis):
    """Round to float8_e4m3 with an absmax scale along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _mm(x, w, spec, fp8):
    """einsum of activations ``x`` (contracting its last axis) with a weight
    whose contraction axes come first; ``fp8`` rounds the activations per
    row (the weights were rounded when they were loaded)."""
    if fp8:
        x = _fp8_round(x, -1)
    return jnp.einsum(spec, x, w, precision=HI)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x, pos, theta):
    """x: (n, heads, Dh) at absolute positions ``pos`` (n,)."""
    Dh = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _qkv(lw, x, pos, m, fp8):
    q = _mm(x, lw["wq"], "nd,dhx->nhx", fp8)
    k = _mm(x, lw["wk"], "nd,dhx->nhx", fp8)
    v = _mm(x, lw["wv"], "nd,dhx->nhx", fp8)
    if m["qkv_bias"]:
        q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
    return (_rope(q, pos, m["rope_theta"]), _rope(k, pos, m["rope_theta"]),
            v)


def _mlp(lw, h, m, fp8):
    x = _rms(h, lw["ln2"], m["rms_eps"])
    a = _mm(x, lw["w1"], "nd,df->nf", fp8)
    b = _mm(x, lw["w3"], "nd,df->nf", fp8)
    return h + m["residual_multiplier"] * _mm(jax.nn.silu(a) * b, lw["w2"],
                                              "nf,fd->nd", fp8)


def _pool(scores, width):
    """Max-pool (Hkv, T) along T with −inf padding."""
    if width <= 1:
        return scores
    pad = width // 2
    T = scores.shape[-1]
    p = jnp.pad(scores, ((0, 0), (pad, pad)), constant_values=-jnp.inf)
    return jnp.stack([p[:, i:i + T] for i in range(width)]).max(axis=0)


@partial(jax.jit, static_argnames=("m", "comp", "fp8"))
def _prompt_layer(lw, h, keep, *, m, comp, fp8):
    """One layer over a whole prompt.  Returns (h, k, v, sel): k/v the
    post-RoPE keys and values (T, Hkv, Dh), sel (Hkv, Kcap) each head's
    kept positions in ascending order, padded with T."""
    m, comp = dict(m), dict(comp)
    T = h.shape[0]
    Hq, Hkv, Dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    G = Hq // Hkv
    pos = jnp.arange(T, dtype=jnp.int32)
    x = _rms(h, lw["ln1"], m["rms_eps"])
    q, k, v = _qkv(lw, x, pos, m, fp8)
    # causal attention, a block of queries at a time
    c = min(PROMPT_CHUNK, T)
    nb = -(-T // c)
    qp = jnp.pad(q, ((0, nb * c - T), (0, 0), (0, 0)))
    qb = qp.reshape(nb, c, Hkv, G, Dh)

    def block(args):
        i, qi = args
        s = jnp.einsum("chgd,thd->hgct", qi, k,
                       precision=HI) * m["attention_multiplier"]
        qpos = i * c + jnp.arange(c)
        s = jnp.where(pos[None, :] <= qpos[:, None], s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgct,thd->chgd", p, v, precision=HI)

    o = jax.lax.map(block, (jnp.arange(nb), qb)).reshape(nb * c, Hq, Dh)[:T]
    h = h + m["residual_multiplier"] * _mm(o, lw["wo"], "nhx,hxd->nd", fp8)
    # HeadKV selection from the observation window's scores
    W = min(comp["obs_window"], T)
    qo = q[T - W:].reshape(W, Hkv, G, Dh)
    s = jnp.einsum("whgd,thd->hgwt", qo, k,
                   precision=HI) * m["attention_multiplier"]
    s = jnp.where(pos[None, :] <= pos[T - W:][:, None], s, NEG)
    scores = _pool(jax.nn.softmax(s, axis=-1).sum(axis=(1, 2)), comp["pool"])
    must = (pos < comp["sink"]) | (pos >= T - comp["obs_window"])
    scores = jnp.where(must[None, :], jnp.inf, scores)
    kcap = min(static_capacity(comp), T)
    _, idx = jax.lax.top_k(scores, kcap)
    idx = jnp.where(jnp.arange(kcap)[None, :] < keep[:, None], idx, T)
    return _mlp(lw, h, m, fp8), k, v, jnp.sort(idx, axis=-1)


@partial(jax.jit, static_argnames=("m", "fp8"))
def _decode_layer(lw, h, T, kp, vp, sel, keep, death_p, death_d, *, m, fp8):
    """One layer over a request's decode tokens (n padded queries at
    positions T, T+1, ...), each seeing the kept prompt entries and the
    decode entries its cache held at that step (``death_*``: the first
    query no longer seeing an entry)."""
    m = dict(m)
    n = h.shape[0]
    Hq, Hkv, Dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    G = Hq // Hkv
    qpos = T + jnp.arange(n, dtype=jnp.int32)
    x = _rms(h, lw["ln1"], m["rms_eps"])
    q, k, v = _qkv(lw, x, qpos, m, fp8)
    qg = q.reshape(n, Hkv, G, Dh)
    kcap = sel.shape[1]
    safe = jnp.minimum(sel, kp.shape[0] - 1)  # (Hkv, Kcap)
    heads = jnp.arange(Hkv)[:, None]
    ks, vs = kp[safe, heads], vp[safe, heads]  # (Hkv, Kcap, Dh)
    qi = jnp.arange(n)
    sp = jnp.einsum("nhgd,hkd->hgnk", qg, ks, precision=HI)
    pm = ((jnp.arange(kcap)[None, None, :] < keep[:, None, None])
          & (qi[None, :, None] < death_p[:, None, :]))  # (Hkv, n, Kcap)
    sd = jnp.einsum("nhgd,mhd->hgnm", qg, k, precision=HI)
    dm = ((qi[None, None, :] <= qi[None, :, None])
          & (qi[None, :, None] < death_d[:, None, :]))  # (Hkv, n, n)
    s = jnp.concatenate([sp, sd], axis=-1) * m["attention_multiplier"]
    mask = jnp.concatenate([pm, dm], axis=-1)[:, None]
    s = jnp.where(mask, s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask, p, 0.0)
    o = (jnp.einsum("hgnk,hkd->nhgd", p[..., :kcap], vs, precision=HI)
         + jnp.einsum("hgnm,mhd->nhgd", p[..., kcap:], v, precision=HI))
    h = h + m["residual_multiplier"] * _mm(o.reshape(n, Hq, Dh), lw["wo"],
                                           "nhx,hxd->nd", fp8)
    return _mlp(lw, h, m, fp8)


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
    """Rebuild layer i in float32 (or fp8-rounded) on the device."""
    def fn(key, i):
        w = checkpoint_layer(m, key, i, dtype)
        if fp8:
            for name, axes in (("wq", 0), ("wk", 0), ("wv", 0), ("wo", (0, 1)),
                               ("w1", 0), ("w3", 0), ("w2", 0)):
                w[name] = _fp8_round(w[name], axes)
        return w
    return jax.jit(fn)


def logits(m: dict, comp: dict, imp: np.ndarray, seed_key, dtype,
           served: list, precision: str = "fp32") -> list:
    """Per request, the (n, V) logits at every served position: position 0
    is the prompt's last token, position k + 1 the k-th decode step."""
    fp8 = {"fp32": False, "fp8": True}[precision]
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
        for st in state:
            keep_i = jnp.asarray(st["keep"][i])
            hp, kp, vp, sel = _prompt_layer(lw, st["hp"], keep_i, m=mk,
                                            comp=ck, fp8=fp8)
            kcap = sel.shape[1]
            st["hd"] = _decode_layer(
                lw, st["hd"], st["T"], kp, vp, sel, keep_i,
                jnp.asarray(st["death_p"][i][:, :kcap]),
                jnp.asarray(st["death_d"][i]), m=mk, fp8=fp8)
            st["hp"] = hp
        del lw
    fnorm = checkpoint_final_norm(m, seed_key, dtype)
    head = head_table(m, seed_key, dtype).astype(jnp.float32)
    if fp8:
        head = _fp8_round(head, 1)
    out = []
    for st in state:
        h = jnp.concatenate([st["hp"][-1:], st["hd"][:st["n"] - 1]])
        x = _rms(h, fnorm, m["rms_eps"])
        lg = (_mm(x, head, "nd,vd->nv", fp8)[:, :m["vocab_size"]]
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
