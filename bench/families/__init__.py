"""Model families: everything in the harness that depends on a layer's
mathematics, one file per family, found by name.

A configuration file names its family under ``"family"`` (``dense`` where
the key is absent); the family is ``bench/families/<family>.py``, loaded by
file name as ``run._reader`` loads ``bench/metrics/<name>.py``.  So a
configuration of a new family enters the benchmark by new files alone: its
family module, its configuration, traffic and check files, and its readers.

A family module supplies:

- ``MODEL_KEYS``: its published config keys mapped to model-dict keys,
  added to ``SHARED_KEYS`` (which it may not repeat).  ``cells.model_dict``
  reads both.
- ``PROGRAM_FIELDS``: each of its model-dict keys mapped to the program's
  ``ModelConfig`` field it sets, dotted into a nested group where it sits in
  one (``"moe.d_expert"``).  ``cells.model_config`` checks every field so
  named against the registry, and its overrides, as it checks the shared
  ones.
- ``SMOKE``: the family's widths at smoke size, under the published keys,
  for the CPU tests (``bench/tests/smoke_cell.py``).
- ``base_layer(m, key, i, dtype)``: layer ``i`` exactly as the program's
  ``init_params`` draws it, ``key`` being that layer's key in
  ``init_params``' tree.  ``weights.py`` keeps the tree, the embedding,
  head and final-norm tables, the seeded norm scales and q/k/v biases it
  adds (``perturb``), and the multiplier folding.
- ``FOLDED``: the layer weights that carry a folded multiplier, each mapped
  to ``"q"`` (scaled with q: times ``attention_multiplier · sqrt(head_dim)``)
  or ``"r"`` (a residual branch's output: times ``residual_multiplier /
  embedding_multiplier``); ``weights.checkpoint_layer`` undoes them.
- ``prompt_layer(lw, h, keep, *, m, comp, fp8)`` and ``decode_layer(lw, h,
  T, kp, vp, sel, keep, death_p, death_d, *, m, fp8)``: the reference's
  per-layer functions, as ``reference.logits`` calls them (``m`` and
  ``comp`` as sorted item tuples, static under ``jax.jit``).  The pieces a
  family reuses unchanged are in ``ref_layers.py``; which of them a layer
  takes (a window mask on some layers, say) is the family's choice.
- ``FP8_WEIGHTS``: the layer weights the fp8 control rounds, each mapped to
  the axes of its absmax scale.
- ``layer_matmul_params(m)``: weights one token multiplies through in the
  layer stack (every layer; experts only as many as a token is routed to).
  ``counts`` adds the unembedding.
- ``prefill_attn_flops(m, T)`` and ``decode_attn_flops(m, lengths)``: the
  attention FLOPs of a ``T``-token prompt and of one decode token whose
  per-(layer, head) retained cache lengths are ``lengths``.
  ``counts.prefill_flops``/``decode_flops`` add them to the matmuls.

``load`` checks that contract when it loads a module.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

DIR = Path(__file__).resolve().parent
DEFAULT = "dense"

# published config key -> model-dict key, for keys every family has; each
# model-dict key here is also the name of the ``ModelConfig`` field it sets
SHARED_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps",
    "tie_word_embeddings": "tie_embeddings",
    "qkv_bias": "qkv_bias",
}
FUNCTIONS = ("base_layer", "prompt_layer", "decode_layer",
             "layer_matmul_params", "prefill_attn_flops", "decode_attn_flops")
TABLES = ("MODEL_KEYS", "PROGRAM_FIELDS", "SMOKE", "FOLDED", "FP8_WEIGHTS")

_loaded = {}  # path -> module: a family's jitted layers compile once


class FamilyError(ValueError):
    """A family module is missing, or does not keep the contract."""


def load(name: str):
    """The family module ``DIR/<name>.py``, checked against the contract."""
    path = DIR / f"{name}.py"
    if path not in _loaded:
        if not path.is_file():
            raise FamilyError(f"no family {name!r}: {path} does not exist")
        spec = importlib.util.spec_from_file_location(
            "bench_family_" + name.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        check(mod, path)
        _loaded[path] = mod
    return _loaded[path]


def of(m: dict):
    """The family module of a model dict (``cells.model_dict``)."""
    return load(m["family"])


def check(mod, path) -> None:
    """Raise `FamilyError` naming ``path`` where ``mod`` breaks the
    contract of this package's docstring."""
    def bad(msg):
        raise FamilyError(f"{path}: {msg}")

    for name in TABLES:
        if not isinstance(getattr(mod, name, None), dict):
            bad(f"lacks the dict {name}")
    for name in FUNCTIONS:
        if not callable(getattr(mod, name, None)):
            bad(f"lacks the function {name}")
    again = sorted(set(mod.MODEL_KEYS) & set(SHARED_KEYS))
    if again:
        bad(f"MODEL_KEYS repeats the shared keys {again}")
    names = set(mod.MODEL_KEYS.values())
    if names & set(SHARED_KEYS.values()) or len(names) != len(mod.MODEL_KEYS):
        bad("MODEL_KEYS maps two published keys to one model-dict key")
    if set(mod.PROGRAM_FIELDS) != names:
        bad(f"PROGRAM_FIELDS names {sorted(mod.PROGRAM_FIELDS)}, not the "
            f"model-dict keys of MODEL_KEYS {sorted(names)}")
    unknown = sorted(set(mod.SMOKE) - set(SHARED_KEYS) - set(mod.MODEL_KEYS))
    if unknown:
        bad(f"SMOKE sets {unknown}, which are no published keys of the family")
    folds = sorted(set(mod.FOLDED.values()) - {"q", "r"})
    if folds:
        bad(f"FOLDED maps to {folds}; only 'q' and 'r' are folded")
