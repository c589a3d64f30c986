"""The reference's pieces that every family's layers share (``families/``).

Plain float32 ``jax.numpy`` at ``Precision.HIGHEST``: RMSNorm (applied as
``x · rsqrt(mean x² + eps) · (1 + scale)``), rotary embeddings (half-split
"rotate half" form, base ``rope_theta``), grouped-query projections with
optional q/k/v biases, causal attention over a prompt a block of queries
at a time, HeadKV's selection at the end of prefill, and decode attention
over the kept prompt entries plus the generated tokens, with the cache's
recency ring (``reference.ring_deaths``).  ``fp8`` rounds a matmul's
activations to float8_e4m3 (the control, ``reference.py``).

A sliding-window layer (``window`` > 0; 0 is full attention) follows what
the program does on one: a query at position p attends to the keys at
positions above ``p − window`` (and at most p); HeadKV's selection masks
the pooled scores of positions below ``T − window`` before it adds the
sinks and the observation window, so the sinks are still kept on such a
layer (the guarantee overrides the mask), and the positions it then keeps
from outside the window are never attended, since every decode query sits
at T or later.

``m`` and ``comp`` are the cell's model dict and compression settings
(``cells.py``), as plain dicts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from counts import static_capacity

HI = jax.lax.Precision.HIGHEST
NEG = -1e30
FP8_MAX = 448.0  # largest finite float8_e4m3fn
PROMPT_CHUNK = 512  # queries per block of the prompt's causal attention


def fp8_round(x, axis):
    """Round to float8_e4m3 with an absmax scale along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def mm(x, w, spec, fp8):
    """einsum of activations ``x`` (contracting its last axis) with a weight
    whose contraction axes come first; ``fp8`` rounds the activations per
    row (the weights were rounded when they were loaded)."""
    if fp8:
        x = fp8_round(x, -1)
    return jnp.einsum(spec, x, w, precision=HI)


def rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def rope(x, pos, theta):
    """x: (n, heads, Dh) at absolute positions ``pos`` (n,)."""
    Dh = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def qkv(lw, x, pos, m, fp8, rotate=True):
    """q (n, Hq, Dh), k and v (n, Hkv, Dh) of ``x`` at ``pos``; q and k
    rotated by RoPE unless ``rotate`` is off."""
    q = mm(x, lw["wq"], "nd,dhx->nhx", fp8)
    k = mm(x, lw["wk"], "nd,dhx->nhx", fp8)
    v = mm(x, lw["wv"], "nd,dhx->nhx", fp8)
    if m["qkv_bias"]:
        q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
    if not rotate:
        return q, k, v
    return rope(q, pos, m["rope_theta"]), rope(k, pos, m["rope_theta"]), v


def pool(scores, width):
    """Max-pool (Hkv, T) along T with −inf padding."""
    if width <= 1:
        return scores
    pad = width // 2
    T = scores.shape[-1]
    p = jnp.pad(scores, ((0, 0), (pad, pad)), constant_values=-jnp.inf)
    return jnp.stack([p[:, i:i + T] for i in range(width)]).max(axis=0)


def causal_attention(q, k, v, m, window=0):
    """Causal attention of a whole prompt, a block of queries at a time:
    q (T, Hq, Dh), k/v (T, Hkv, Dh) -> (T, Hq, Dh); ``window`` > 0 limits
    each query to that many positions, its own included."""
    T, Hq, Dh = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    pos = jnp.arange(T, dtype=jnp.int32)
    c = min(PROMPT_CHUNK, T)
    nb = -(-T // c)
    qp = jnp.pad(q, ((0, nb * c - T), (0, 0), (0, 0)))
    qb = qp.reshape(nb, c, Hkv, G, Dh)

    def block(args):
        i, qi = args
        s = jnp.einsum("chgd,thd->hgct", qi, k,
                       precision=HI) * m["attention_multiplier"]
        qpos = i * c + jnp.arange(c)
        seen = pos[None, :] <= qpos[:, None]
        if window > 0:
            seen &= pos[None, :] > qpos[:, None] - window
        s = jnp.where(seen, s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgct,thd->chgd", p, v, precision=HI)

    return jax.lax.map(block, (jnp.arange(nb), qb)).reshape(nb * c, Hq,
                                                            Dh)[:T]


def headkv_select(q, k, keep, m, comp, window=0):
    """HeadKV's selection from the observation window's scores: SnapKV
    observation scores (the softmax of the last ``obs_window`` queries over
    the prompt, summed over the window and the query group, max-pooled by
    ``pool``; on a ``window`` layer those of positions below T − window
    masked), sinks and the observation window always kept, each head
    keeping its ``keep`` top positions (``counts.headkv_keep``).  Returns
    (Hkv, Kcap) kept positions in ascending order, padded with T."""
    T, Hq, Dh = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    pos = jnp.arange(T, dtype=jnp.int32)
    W = min(comp["obs_window"], T)
    qo = q[T - W:].reshape(W, Hkv, G, Dh)
    s = jnp.einsum("whgd,thd->hgwt", qo, k,
                   precision=HI) * m["attention_multiplier"]
    s = jnp.where(pos[None, :] <= pos[T - W:][:, None], s, NEG)
    scores = pool(jax.nn.softmax(s, axis=-1).sum(axis=(1, 2)), comp["pool"])
    if window > 0:
        scores = jnp.where(pos[None, :] >= T - window, scores, -jnp.inf)
    must = (pos < comp["sink"]) | (pos >= T - comp["obs_window"])
    scores = jnp.where(must[None, :], jnp.inf, scores)
    kcap = min(static_capacity(comp), T)
    _, idx = jax.lax.top_k(scores, kcap)
    idx = jnp.where(jnp.arange(kcap)[None, :] < keep[:, None], idx, T)
    return jnp.sort(idx, axis=-1)


def decode_attention(q, k, v, kp, vp, sel, keep, death_p, death_d, m,
                     window=0):
    """Attention of a request's decode queries q (n, Hq, Dh), with their own
    keys and values k/v (n, Hkv, Dh), over the kept prompt entries (``kp``,
    ``vp`` the prompt's keys and values, ``sel`` and ``keep`` the selection)
    and the decode entries the cache held at each step (``death_*``: the
    first query no longer seeing an entry) -> (n, Hq, Dh).  Query i sits
    at position T + i (T the prompt's length); ``window`` > 0 hides the
    entries at or below its position − window."""
    n, Hq, Dh = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
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
    if window > 0:
        T = kp.shape[0]
        pm &= sel[:, None, :] > T + qi[None, :, None] - window
        dm &= qi[None, None, :] > qi[None, :, None] - window
    s = jnp.concatenate([sp, sd], axis=-1) * m["attention_multiplier"]
    mask = jnp.concatenate([pm, dm], axis=-1)[:, None]
    s = jnp.where(mask, s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask, p, 0.0)
    o = (jnp.einsum("hgnk,hkd->nhgd", p[..., :kcap], vs, precision=HI)
         + jnp.einsum("hgnm,mhd->nhgd", p[..., kcap:], v, precision=HI))
    return o.reshape(n, Hq, Dh)
