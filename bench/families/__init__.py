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
  head and final-norm tables, the seeded extras it adds (``perturb``), and
  the multiplier folding.  ``i`` is a Python int everywhere, the
  reference's jitted loader included, so the draw may branch on it.
- ``extras(m, i)``: the vectors of layer ``i`` that ``init_params`` leaves
  at a constant and the benchmark seeds instead (``weights.perturb`` and
  the reference alike), each name mapped to ``(shape, std)``: the seeded
  value is ``std`` times a standard normal draw.  The family chooses each
  std for what the vector does (dense: ``weights.NORM_SCALE`` for an
  RMSNorm scale, applied as ``1 + scale``; 0.5 for a q/k/v bias).  Each
  name must be a weight of the program's layer ``i``, of that shape; the
  order fixes which seeded key draws it.  A layer without the vector (a
  router bias on a dense layer) leaves it out: ``i`` is a Python int, as
  for ``base_layer``.
- ``multipliers(m)``: the multipliers (names in ``MULTIPLIERS``) that the
  family derives from the model dict rather than reads as published keys,
  such as an embedding scale of sqrt(hidden size) that a flag switches on;
  ``cells.model_dict`` sets them once the widths are in the dict, so they
  follow a cut of the widths (the smoke size).  A configuration may not
  state one of them as well.
- ``PROGRAM_APPLIES``: a tuple of the names in ``MULTIPLIERS`` that the
  program applies itself.  The harness folds the others into the served
  weights, as a checkpoint converter does (``weights.py``,
  ``cells.program_model``); one the program applies is not folded, since
  it would then be applied twice.  The reference applies every multiplier
  as published either way.
- ``FOLDED``: the layer weights that carry a folded multiplier, each mapped
  to ``"q"`` (scaled with q: times ``attention_multiplier · sqrt(head_dim)``)
  or ``"r"`` (a residual branch's output: times ``residual_multiplier /
  embedding_multiplier``); ``weights.checkpoint_layer`` undoes them.
- ``layer_kind(m, i)``: a hashable value that says how layer ``i``
  differs from the others (its attention window, whether it routes), the
  same for layers of one kind.  ``reference.logits`` hands it to the layer
  functions below as their static ``kind``, so layers of one kind share
  one compile.
- ``prompt_layer(lw, h, keep, *, m, comp, fp8, kind)`` and
  ``decode_layer(lw, h, T, kp, vp, sel, keep, death_p, death_d, *, m, fp8,
  kind)``: the reference's per-layer functions, as ``reference.logits``
  calls them (``m`` and ``comp`` as sorted item tuples, static under
  ``jax.jit``, like ``kind``).  The pieces a family reuses unchanged are in
  ``ref_layers.py``, with a sliding window and RoPE as arguments; which of
  them a layer takes is the family's choice, by its ``kind``.
- ``FP8_WEIGHTS``: the layer weights the fp8 control rounds, each mapped to
  the axes of its absmax scale (a layer without one of them, a dense layer
  beside MoE ones, is rounded in those it has).
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
# published multipliers some families add to the plain decoder (Granite);
# a configuration without them runs the plain one.  ``None`` is
# 1 / sqrt(head_dim).
MULTIPLIERS = {
    "embedding_multiplier": 1.0,
    "attention_multiplier": None,
    "residual_multiplier": 1.0,
    "logits_scaling": 1.0,
}
FUNCTIONS = ("base_layer", "extras", "multipliers", "layer_kind",
             "prompt_layer", "decode_layer", "layer_matmul_params",
             "prefill_attn_flops", "decode_attn_flops")
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
    if not isinstance(getattr(mod, "PROGRAM_APPLIES", None), tuple):
        bad("lacks the tuple PROGRAM_APPLIES")
    unknown = sorted(set(mod.PROGRAM_APPLIES) - set(MULTIPLIERS))
    if unknown:
        bad(f"PROGRAM_APPLIES names {unknown}, which are not among the "
            f"multipliers {sorted(MULTIPLIERS)}")
