"""A family module for the CPU tests only (it is not under
``bench/families/``, and gemma2 is no benchmark configuration): the
registry's ``gemma2-9b`` as the program serves it.  Its layers differ by
index, even layers attending through a sliding window
(``ModelConfig.layer_is_local``), and the program applies its embedding
multiplier, √d_model, itself; the family derives it from the width
(``multipliers``), so it follows the smoke cut.  Which vectors the
benchmark seeds depends on the index as well (``extras``), as a router's
bias on the MoE layers alone of a mixed stack would.  The tests copy this file into a directory
of their own and point ``families.DIR`` there, so it enters the harness
as a new family's file would, with no edit to any file under ``bench/``.

Its configuration maps gemma2's two softcaps and sets both to 0 under
``overrides``, since the reference has none.
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

MODEL_KEYS = {"intermediate_size": "d_ff",
              "sliding_window": "sliding_window",
              "attn_logit_softcapping": "attn_softcap",
              "final_logit_softcapping": "logit_softcap"}
PROGRAM_FIELDS = {k: k for k in MODEL_KEYS.values()}
SMOKE = {"num_hidden_layers": 2, "hidden_size": 64,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "head_dim": 16, "intermediate_size": 128, "vocab_size": 256,
         "sliding_window": 16}
FOLDED = {"wq": "q", "wo": "r", "w2": "r"}
PROGRAM_APPLIES = ("embedding_multiplier",)
FP8_WEIGHTS = {"wq": 0, "wk": 0, "wv": 0, "wo": (0, 1),
               "w1": 0, "w3": 0, "w2": 0}


def base_layer(m: dict, key: jax.Array, i: int, dtype) -> dict:
    """Layer ``i`` as ``init_params`` draws a dense layer without q/k/v
    biases."""
    D, Hq, Hkv, Dh, F = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                         m["head_dim"], m["d_ff"])
    k8 = jax.random.split(key, 8)
    ka = jax.random.split(k8[0], 4)
    s = 1.0 / math.sqrt(D)
    k1, k2, k3 = jax.random.split(k8[3], 3)
    return {
        "ln1": jnp.zeros((D,), dtype), "ln2": jnp.zeros((D,), dtype),
        "wq": (jax.random.normal(ka[0], (D, Hq, Dh)) * s).astype(dtype),
        "wk": (jax.random.normal(ka[1], (D, Hkv, Dh)) * s).astype(dtype),
        "wv": (jax.random.normal(ka[2], (D, Hkv, Dh)) * s).astype(dtype),
        "wo": (jax.random.normal(ka[3], (Hq, Dh, D))
               * (1.0 / math.sqrt(Hq * Dh))).astype(dtype),
        "w1": (jax.random.normal(k1, (D, F)) / math.sqrt(D)).astype(dtype),
        "w3": (jax.random.normal(k2, (D, F)) / math.sqrt(D)).astype(dtype),
        "w2": (jax.random.normal(k3, (F, D)) / math.sqrt(F)).astype(dtype),
    }


def extras(m: dict, i: int) -> dict:
    """Both norms on a windowed layer; a full layer keeps its MLP norm at
    ``init_params``' zero."""
    D = (m["d_model"],)
    if layer_kind(m, i):
        return {"ln1": (D, NORM_SCALE), "ln2": (D, NORM_SCALE)}
    return {"ln1": (D, NORM_SCALE)}


def multipliers(m: dict) -> dict:
    """The embedding scale, √d_model, which the program applies."""
    return {"embedding_multiplier": math.sqrt(m["d_model"])}


def layer_kind(m: dict, i: int) -> int:
    """The layer's attention window: even layers local, odd ones full (0)."""
    return m["sliding_window"] if i % 2 == 0 else 0


def _mlp(lw, h, m, fp8):
    x = rms(h, lw["ln2"], m["rms_eps"])
    a = mm(x, lw["w1"], "nd,df->nf", fp8)
    b = mm(x, lw["w3"], "nd,df->nf", fp8)
    return h + m["residual_multiplier"] * mm(jax.nn.silu(a) * b, lw["w2"],
                                             "nf,fd->nd", fp8)


@partial(jax.jit, static_argnames=("m", "comp", "fp8", "kind"))
def prompt_layer(lw, h, keep, *, m, comp, fp8, kind):
    m, comp = dict(m), dict(comp)
    pos = jnp.arange(h.shape[0], dtype=jnp.int32)
    x = rms(h, lw["ln1"], m["rms_eps"])
    q, k, v = qkv(lw, x, pos, m, fp8)
    o = causal_attention(q, k, v, m, window=kind)
    h = h + m["residual_multiplier"] * mm(o, lw["wo"], "nhx,hxd->nd", fp8)
    sel = headkv_select(q, k, keep, m, comp, window=kind)
    return _mlp(lw, h, m, fp8), k, v, sel


@partial(jax.jit, static_argnames=("m", "fp8", "kind"))
def decode_layer(lw, h, T, kp, vp, sel, keep, death_p, death_d, *, m, fp8,
                 kind):
    m = dict(m)
    qpos = T + jnp.arange(h.shape[0], dtype=jnp.int32)
    x = rms(h, lw["ln1"], m["rms_eps"])
    q, k, v = qkv(lw, x, qpos, m, fp8)
    o = decode_attention(q, k, v, kp, vp, sel, keep, death_p, death_d, m,
                         window=kind)
    h = h + m["residual_multiplier"] * mm(o, lw["wo"], "nhx,hxd->nd", fp8)
    return _mlp(lw, h, m, fp8)


def layer_matmul_params(m: dict) -> int:
    D, Hq, Hkv, Dh, F = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                         m["head_dim"], m["d_ff"])
    return m["n_layers"] * (D * (Hq + 2 * Hkv) * Dh + Hq * Dh * D + 3 * D * F)


def _windows(m: dict) -> list:
    return [layer_kind(m, i) for i in range(m["n_layers"])]


def prefill_attn_flops(m: dict, T: int) -> float:
    """q·k and p·v over the positions each query sees."""
    seen = sum(W * (W + 1) / 2 + (T - W) * W if 0 < W < T
               else T * (T + 1) / 2 for W in _windows(m))
    return 4.0 * m["n_heads"] * m["head_dim"] * seen


def decode_attn_flops(m: dict, lengths) -> float:
    """A window layer's query sees at most ``window`` retained entries."""
    lens = np.asarray(lengths, np.float64)
    caps = np.array([W or np.inf for W in _windows(m)])[:, None]
    G = m["n_heads"] // m["n_kv_heads"]
    return 4.0 * G * m["head_dim"] * float(np.minimum(lens, caps).sum())
