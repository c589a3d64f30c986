"""Seeded weights of a benchmark cell, made on the device in one call.

The served model's weights come from the program's own initializer
(``init_params``), which draws every matrix from one PRNG key tree.  Its
RMSNorm scales and biases start at a constant (zero), which would leave the
norm scales and the bias paths untested, so the benchmark adds seeded
values to them (``perturb``): to the vectors its family names for each
layer (``extras``), and to the final norm.

A family whose published decoder scales the embeddings, the attention
scores, the residual branches and the logits (Granite) is served by the
program's plain decoder with the multipliers folded into the weights, as
a checkpoint converter does: q and its bias times ``attention_multiplier
· sqrt(head_dim)``, the output and down projections times
``residual_multiplier / embedding_multiplier`` (the residual stream then
runs divided by the embedding multiplier, and RMSNorm's eps by its square,
``cells.program_model``), the final norm's scale divided by
``logits_scaling``.  A multiplier the program applies itself (its family's
``PROGRAM_APPLIES``: gemma2's embedding scale) is left out of the folding,
as its factor 1.  The seed's checkpoint of such a family is drawn at the
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

A layer's own draw is its family's (``families/<family>.py``
``base_layer``); this module keeps ``init_params``' key tree, the tables
outside the layers, the seeded additions and the folding.

``m`` is the cell's model dict (``cells.model_dict``): ``n_layers``,
``d_model``, ``n_heads``, ``n_kv_heads``, ``head_dim``, ``vocab_size``,
``padded_vocab``, ``qkv_bias``, ``tie_embeddings``, the four multipliers
(``families.MULTIPLIERS``), ``family`` and the family's own keys.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import families

NORM_SCALE = 0.1  # std of the added RMSNorm scales (applied as 1 + scale)
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


def _layer_extras(m: dict, key: jax.Array, i: int, dtype) -> dict:
    """The benchmark's seeded values of the extras its family names for
    layer ``i``: the j-th name drawn from the j-th key of one split of the
    layer's perturbation key (split's j-th key does not depend on how many
    are drawn)."""
    spec = families.of(m).extras(m, i)
    keys = jax.random.split(jax.random.fold_in(key, _PERTURB_TAG + i),
                            len(spec))
    return {name: (std * jax.random.normal(k, tuple(shape))).astype(dtype)
            for k, (name, (shape, std)) in zip(keys, spec.items())}


def _add_extras(layer: dict, extras: dict, i: int) -> dict:
    """``layer`` with its extras replaced by their seeded values; an extra
    the layer does not have, or has at another shape, is an error."""
    for name, v in extras.items():
        if name not in layer or layer[name].shape != v.shape:
            raise ValueError(
                f"layer {i} has no weight {name} of shape {v.shape} to seed "
                f"(its family's extras name it)")
    return {**layer, **extras}


def layer_weights(m: dict, key: jax.Array, i: int, dtype) -> dict:
    """Layer ``i`` as the benchmark serves it."""
    w = families.of(m).base_layer(m, _layer_key(m, key, i), i, dtype)
    return _add_extras(w, _layer_extras(m, key, i, dtype), i)


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
    """Put the seeded final norm and extras into ``init_params`` output."""
    out = dict(params)
    out["final_norm"] = final_norm(m, key, dtype)
    out["layers"] = [_add_extras(pl, _layer_extras(m, key, i, dtype), i)
                     for i, pl in enumerate(params["layers"])]
    return out


def multiplier_factors(m: dict) -> tuple:
    """(q, r, z): how the served weights differ from the checkpoint of a
    family with multipliers — q and its bias times ``q``, the output and
    down projections times ``r``, the final norm's scale divided by ``z``
    (all 1 for a plain decoder; which weights are q's and which are a
    branch's output is the family's ``FOLDED``).  A multiplier the program
    applies itself (``PROGRAM_APPLIES``) counts as 1."""
    applied = families.of(m).PROGRAM_APPLIES
    f = {k: 1.0 if k in applied else m[k] for k in families.MULTIPLIERS}
    q = (1.0 if "attention_multiplier" in applied
         else f["attention_multiplier"] * math.sqrt(m["head_dim"]))
    return (q, f["residual_multiplier"] / f["embedding_multiplier"],
            f["logits_scaling"])


def checkpoint_layer(m: dict, key: jax.Array, i: int, dtype) -> dict:
    """Layer ``i`` of the seed's checkpoint, in float32, as the published
    decoder with its multipliers takes it: the served layer with the
    folding undone on the weights its family names (``FOLDED``)."""
    factor = dict(zip("qr", multiplier_factors(m)[:2]))
    w = {k: v.astype(jnp.float32)
         for k, v in layer_weights(m, key, i, dtype).items()}
    for name, c in families.of(m).FOLDED.items():
        if name in w:
            w[name] = w[name] / factor[c]
    return w


def checkpoint_final_norm(m: dict, key: jax.Array, dtype) -> jax.Array:
    """The checkpoint's final norm scale (applied as 1 + scale), float32."""
    z = multiplier_factors(m)[2]
    return (1.0 + final_norm(m, key, dtype).astype(jnp.float32)) * z - 1.0
