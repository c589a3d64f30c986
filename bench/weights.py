"""Seeded weights of a benchmark cell, made on the device in one call.

The served model's weights come from the program's own initializer
(``init_params``), which draws every matrix from one PRNG key tree.  Its
RMSNorm scales and QKV biases start at zero, which would leave the norm
scale and the bias path untested, so the benchmark adds seeded values to
them (``perturb``).

A family whose published decoder scales the embeddings, the attention
scores, the residual branches and the logits (Granite) is served by the
program's plain decoder with the multipliers folded into the weights, as
a checkpoint converter does: q and its bias times ``attention_multiplier
· sqrt(head_dim)``, the output and down projections times
``residual_multiplier / embedding_multiplier`` (the residual stream then
runs divided by the embedding multiplier, and RMSNorm's eps by its square,
``cells.program_model``), the final norm's scale divided by
``logits_scaling``.  The seed's checkpoint of such a family is drawn at the
scales where its folded form is what ``init_params`` draws
(``checkpoint_layer``): at the plain decoder's scales, Granite's 1/64 on
the attention scores and 0.22 on the branches leave attention near uniform
and the stream the token's own embedding, so the served tokens would
ignore the cache and no check could see a fault in it.

The plain reference (``reference.py``) must not take the program's weights,
so this module also rebuilds any single tensor from the seed alone, by the
same key tree (``layer_weights``, ``embed_table``, ``head_table``,
``final_norm``).  It imports nothing of the program; a test checks that the
two paths give identical arrays.

``m`` is the cell's model dict (``cells.model_dict``): ``n_layers``,
``d_model``, ``n_heads``, ``n_kv_heads``, ``head_dim``, ``d_ff``,
``vocab_size``, ``padded_vocab``, ``qkv_bias``, ``tie_embeddings`` and the
four multipliers (``cells.MULTIPLIERS``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NORM_SCALE = 0.1  # std of the added RMSNorm scales (applied as 1 + scale)
BIAS_SCALE = 0.5  # std of the added q/k/v biases
_PERTURB_TAG = 1_000_003  # fold-in base of the perturbation keys


def root_key(seed: int) -> jax.Array:
    """PRNG key of a seed of any size up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _layer_key(m: dict, key: jax.Array, i: int) -> jax.Array:
    return jax.random.split(key, m["n_layers"] + 4)[2 + i]


def base_layer(m: dict, key: jax.Array, i: int, dtype) -> dict:
    """Layer ``i`` exactly as ``init_params`` draws it (dense family)."""
    D, Hq, Hkv, Dh, F = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                         m["head_dim"], m["d_ff"])
    k8 = jax.random.split(_layer_key(m, key, i), 8)
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


def _layer_extras(m: dict, key: jax.Array, i: int, dtype) -> dict:
    """The benchmark's seeded norm scales and biases of layer ``i``."""
    D, Hq, Hkv, Dh = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    k5 = jax.random.split(jax.random.fold_in(key, _PERTURB_TAG + i), 5)
    out = {
        "ln1": (NORM_SCALE * jax.random.normal(k5[0], (D,))).astype(dtype),
        "ln2": (NORM_SCALE * jax.random.normal(k5[1], (D,))).astype(dtype),
    }
    if m["qkv_bias"]:
        out["bq"] = (BIAS_SCALE * jax.random.normal(k5[2], (Hq, Dh))
                     ).astype(dtype)
        out["bk"] = (BIAS_SCALE * jax.random.normal(k5[3], (Hkv, Dh))
                     ).astype(dtype)
        out["bv"] = (BIAS_SCALE * jax.random.normal(k5[4], (Hkv, Dh))
                     ).astype(dtype)
    return out


def layer_weights(m: dict, key: jax.Array, i: int, dtype) -> dict:
    """Layer ``i`` as the benchmark serves it."""
    w = base_layer(m, key, i, dtype)
    w.update(_layer_extras(m, key, i, dtype))
    return w


def embed_table(m: dict, key: jax.Array, dtype) -> jax.Array:
    k = jax.random.split(key, m["n_layers"] + 4)[0]
    return (jax.random.normal(k, (m["padded_vocab"], m["d_model"]))
            * 0.02).astype(dtype)


def head_table(m: dict, key: jax.Array, dtype) -> jax.Array:
    """Unembedding rows; the embedding itself when they are tied."""
    if m["tie_embeddings"]:
        return embed_table(m, key, dtype)
    k = jax.random.split(key, m["n_layers"] + 4)[1]
    return (jax.random.normal(k, (m["padded_vocab"], m["d_model"]))
            * 0.02).astype(dtype)


def final_norm(m: dict, key: jax.Array, dtype) -> jax.Array:
    k = jax.random.fold_in(key, _PERTURB_TAG - 1)
    return (NORM_SCALE * jax.random.normal(k, (m["d_model"],))).astype(dtype)


def perturb(params: dict, m: dict, key: jax.Array, dtype) -> dict:
    """Add the seeded norm scales and biases to ``init_params`` output."""
    out = dict(params)
    out["final_norm"] = final_norm(m, key, dtype)
    layers = []
    for i, pl in enumerate(params["layers"]):
        pl = dict(pl)
        pl.update(_layer_extras(m, key, i, dtype))
        layers.append(pl)
    out["layers"] = layers
    return out



def multiplier_factors(m: dict) -> tuple:
    """(q, r, z): how the served weights differ from the checkpoint of a
    family with multipliers — q and its bias times ``q``, the output and
    down projections times ``r``, the final norm's scale divided by ``z``
    (all 1 for a plain decoder)."""
    return (m["attention_multiplier"] * math.sqrt(m["head_dim"]),
            m["residual_multiplier"] / m["embedding_multiplier"],
            m["logits_scaling"])


def checkpoint_layer(m: dict, key: jax.Array, i: int, dtype) -> dict:
    """Layer ``i`` of the seed's checkpoint, in float32, as the published
    decoder with its multipliers takes it: the served layer with the
    folding undone."""
    q, r, _ = multiplier_factors(m)
    w = {k: v.astype(jnp.float32)
         for k, v in layer_weights(m, key, i, dtype).items()}
    for name, c in (("wq", q), ("bq", q), ("wo", r), ("w2", r)):
        if name in w:
            w[name] = w[name] / c
    return w


def checkpoint_final_norm(m: dict, key: jax.Array, dtype) -> jax.Array:
    """The checkpoint's final norm scale (applied as 1 + scale), float32."""
    z = multiplier_factors(m)[2]
    return (1.0 + final_norm(m, key, dtype).astype(jnp.float32)) * z - 1.0
