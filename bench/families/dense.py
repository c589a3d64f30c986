"""The dense family: a GQA decoder with optional q/k/v biases and a SwiGLU
MLP of width ``d_ff`` in every layer, all layers of one kind (Qwen1.5,
Granite; Granite's published multipliers are folded into the served
weights, ``weights.py``, as the program applies none of them).

The family contract is in ``families/__init__.py``.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ref_layers import (causal_attention, decode_attention, headkv_select,
                        mm, qkv, rms)
from weights import NORM_SCALE

MODEL_KEYS = {"intermediate_size": "d_ff"}
PROGRAM_FIELDS = {"d_ff": "d_ff"}
SMOKE = {"num_hidden_layers": 2, "hidden_size": 64,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "head_dim": 16, "intermediate_size": 128, "vocab_size": 256}
FOLDED = {"wq": "q", "bq": "q", "wo": "r", "w2": "r"}
PROGRAM_APPLIES = ()
BIAS_SCALE = 0.5  # std of the seeded q/k/v biases
FP8_WEIGHTS = {"wq": 0, "wk": 0, "wv": 0, "wo": (0, 1),
               "w1": 0, "w3": 0, "w2": 0}


# ---- weights ---------------------------------------------------------------


def base_layer(m: dict, key: jax.Array, i: int, dtype) -> dict:
    """Layer ``i`` exactly as ``init_params`` draws it from its key."""
    D, Hq, Hkv, Dh, F = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                         m["head_dim"], m["d_ff"])
    k8 = jax.random.split(key, 8)
    ka = jax.random.split(k8[0], 4)
    s = 1.0 / math.sqrt(D)
    w = {
        "ln1": jnp.zeros((D,), dtype), "ln2": jnp.zeros((D,), dtype),
        "wq": (jax.random.normal(ka[0], (D, Hq, Dh)) * s).astype(dtype),
        "wk": (jax.random.normal(ka[1], (D, Hkv, Dh)) * s).astype(dtype),
        "wv": (jax.random.normal(ka[2], (D, Hkv, Dh)) * s).astype(dtype),
        "wo": (jax.random.normal(ka[3], (Hq, Dh, D))
               * (1.0 / math.sqrt(Hq * Dh))).astype(dtype),
    }
    if m["qkv_bias"]:
        w["bq"] = jnp.zeros((Hq, Dh), dtype)
        w["bk"] = jnp.zeros((Hkv, Dh), dtype)
        w["bv"] = jnp.zeros((Hkv, Dh), dtype)
    k1, k2, k3 = jax.random.split(k8[3], 3)
    w["w1"] = (jax.random.normal(k1, (D, F)) / math.sqrt(D)).astype(dtype)
    w["w3"] = (jax.random.normal(k2, (D, F)) / math.sqrt(D)).astype(dtype)
    w["w2"] = (jax.random.normal(k3, (F, D)) / math.sqrt(F)).astype(dtype)
    return w


def extras(m: dict, i: int) -> dict:
    """Both norms, and the q/k/v biases where the model has them."""
    D, Hq, Hkv, Dh = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                      m["head_dim"])
    out = {"ln1": ((D,), NORM_SCALE), "ln2": ((D,), NORM_SCALE)}
    if m["qkv_bias"]:
        out.update(bq=((Hq, Dh), BIAS_SCALE), bk=((Hkv, Dh), BIAS_SCALE),
                   bv=((Hkv, Dh), BIAS_SCALE))
    return out


def multipliers(m: dict) -> dict:
    """None: Granite publishes its multipliers as keys."""
    return {}


# ---- reference layers ------------------------------------------------------


def layer_kind(m: dict, i: int) -> str:
    """Every layer is a full-attention SwiGLU layer."""
    return "full"


def _mlp(lw, h, m, fp8):
    x = rms(h, lw["ln2"], m["rms_eps"])
    a = mm(x, lw["w1"], "nd,df->nf", fp8)
    b = mm(x, lw["w3"], "nd,df->nf", fp8)
    return h + m["residual_multiplier"] * mm(jax.nn.silu(a) * b, lw["w2"],
                                             "nf,fd->nd", fp8)


@partial(jax.jit, static_argnames=("m", "comp", "fp8", "kind"))
def prompt_layer(lw, h, keep, *, m, comp, fp8, kind):
    """One layer over a whole prompt.  Returns (h, k, v, sel): k/v the
    post-RoPE keys and values (T, Hkv, Dh), sel (Hkv, Kcap) each head's
    kept positions in ascending order, padded with T."""
    m, comp = dict(m), dict(comp)
    pos = jnp.arange(h.shape[0], dtype=jnp.int32)
    x = rms(h, lw["ln1"], m["rms_eps"])
    q, k, v = qkv(lw, x, pos, m, fp8)
    o = causal_attention(q, k, v, m)
    h = h + m["residual_multiplier"] * mm(o, lw["wo"], "nhx,hxd->nd", fp8)
    sel = headkv_select(q, k, keep, m, comp)
    return _mlp(lw, h, m, fp8), k, v, sel


@partial(jax.jit, static_argnames=("m", "fp8", "kind"))
def decode_layer(lw, h, T, kp, vp, sel, keep, death_p, death_d, *, m, fp8,
                 kind):
    """One layer over a request's decode tokens (n padded queries at
    positions T, T+1, ...), each seeing the kept prompt entries and the
    decode entries its cache held at that step."""
    m = dict(m)
    qpos = T + jnp.arange(h.shape[0], dtype=jnp.int32)
    x = rms(h, lw["ln1"], m["rms_eps"])
    q, k, v = qkv(lw, x, qpos, m, fp8)
    o = decode_attention(q, k, v, kp, vp, sel, keep, death_p, death_d, m)
    h = h + m["residual_multiplier"] * mm(o, lw["wo"], "nhx,hxd->nd", fp8)
    return _mlp(lw, h, m, fp8)


# ---- FLOPs -----------------------------------------------------------------


def layer_matmul_params(m: dict) -> int:
    """Every layer's projections and MLP."""
    D, Hq, Hkv, Dh, F = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                         m["head_dim"], m["d_ff"])
    per_layer = D * (Hq + 2 * Hkv) * Dh + Hq * Dh * D + 3 * D * F
    return m["n_layers"] * per_layer


def prefill_attn_flops(m: dict, T: int) -> float:
    """q·k and p·v of causal attention: half the T×T score matrix."""
    return 4.0 * m["n_heads"] * m["head_dim"] * (T * (T + 1) / 2) \
        * m["n_layers"]


def decode_attn_flops(m: dict, lengths) -> float:
    """q·k and p·v over every retained entry of each query head."""
    G = m["n_heads"] // m["n_kv_heads"]
    return 4.0 * G * m["head_dim"] * float(np.asarray(lengths).sum())
