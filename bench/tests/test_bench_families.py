"""The family seam: every family module keeps the contract, an unknown
family fails where the cell is loaded, and a family module in another
directory is found by name and used by ``cells``, ``weights``,
``reference`` and ``counts`` — among them one whose layers differ by index
and whose program applies a multiplier itself (``gemma2_family.py``),
served by the paged engine and held to the reference."""
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cells
import counts
import families
import reference
import weights
from repro.configs import get_config
from smoke_cell import (CONFIGS, against_reference, agrees, cell_at_smoke,
                        serve_paged, smoke_cell)

MODULES = sorted(p.stem for p in families.DIR.glob("*.py")
                 if not p.stem.startswith("_"))
GEMMA2 = Path(__file__).with_name("gemma2_family.py")
# gemma-2-9b's published config, as a configuration file would hold it,
# with both softcaps off (the reference has none)
GEMMA2_CONFIG = {
    "arch": "gemma2-9b", "family": "gemma2", "num_hidden_layers": 42,
    "hidden_size": 3584, "num_attention_heads": 16, "num_key_value_heads": 8,
    "head_dim": 256, "intermediate_size": 14336, "vocab_size": 256000,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-06, "tie_word_embeddings": True,
    "qkv_bias": False, "sliding_window": 4096,
    "attn_logit_softcapping": 0.0, "final_logit_softcapping": 0.0,
    "overrides": ["attn_softcap", "logit_softcap"],
    "engine": json.loads((cells.BENCH / "configs" / "qwen1.5-110b.json")
                         .read_text())["engine"],
}
# the test family with one half of its seam taken away
GEMMA2_FAULTS = {
    "window": "\ndef layer_kind(m, i):\n    return 0\n",
    "multiplier": "\nPROGRAM_APPLIES = ()\n",
}
# the extra lines that give a copy of dense a published key of a nested
# group of the program's ModelConfig, and an fp8 weight its layers lack
# (as a dense layer lacks a MoE layer's router)
NESTED = '''
MODEL_KEYS = {**MODEL_KEYS, "num_experts": "n_experts"}
PROGRAM_FIELDS = {**PROGRAM_FIELDS, "n_experts": "moe.num_experts"}
FP8_WEIGHTS = {**FP8_WEIGHTS, "router": 0}
'''


@pytest.mark.parametrize("name", MODULES)
def test_every_family_module_keeps_the_contract(name):
    fam = families.load(name)
    families.check(fam, families.DIR / f"{name}.py")
    assert set(fam.SMOKE) <= set(families.SHARED_KEYS) | set(fam.MODEL_KEYS)
    assert set(fam.PROGRAM_APPLIES) <= set(families.MULTIPLIERS)
    # at smoke size, every configuration of the family: each layer has a
    # hashable kind, and its extras are weights of its layer, at their shape
    for config in CONFIGS:
        m = smoke_cell(config, "saturated").model
        if m["family"] != name:
            continue
        for i in range(m["n_layers"]):
            hash(fam.layer_kind(m, i))
            layer = fam.base_layer(m, jax.random.PRNGKey(0), i, jnp.float32)
            for extra, (shape, std) in fam.extras(m, i).items():
                assert layer[extra].shape == tuple(shape), (config, i, extra)
                assert std > 0, (config, i, extra)


def test_a_module_that_breaks_the_contract_is_refused(tmp_path,
                                                      monkeypatch):
    src = (families.DIR / "dense.py").read_text()
    (tmp_path / "nosmoke.py").write_text(src + "\ndel SMOKE\n")
    (tmp_path / "again.py").write_text(
        src + '\nMODEL_KEYS = {**MODEL_KEYS, "hidden_size": "d_model"}\n')
    monkeypatch.setattr(families, "DIR", tmp_path)
    with pytest.raises(families.FamilyError, match="lacks the dict SMOKE"):
        families.load("nosmoke")
    with pytest.raises(families.FamilyError, match="repeats the shared keys"):
        families.load("again")


@pytest.mark.parametrize("lines,error", [
    ("del layer_kind", "lacks the function layer_kind"),
    ("del extras", "lacks the function extras"),
    ("del multipliers", "lacks the function multipliers"),
    ("del PROGRAM_APPLIES", "lacks the tuple PROGRAM_APPLIES"),
    ('PROGRAM_APPLIES = ("embedding_scale",)',
     r"PROGRAM_APPLIES names \['embedding_scale'\]"),
], ids=["no-layer-kind", "no-extras", "no-multipliers",
        "no-program-applies", "unknown-multiplier"])
def test_a_module_without_the_widened_contract_is_refused(lines, error,
                                                          tmp_path,
                                                          monkeypatch):
    path = tmp_path / "widened.py"
    path.write_text((families.DIR / "dense.py").read_text() + f"\n{lines}\n")
    monkeypatch.setattr(families, "DIR", tmp_path)
    with pytest.raises(families.FamilyError, match=error) as e:
        families.load("widened")
    assert str(path) in str(e.value)


@pytest.mark.parametrize("derived,error", [
    ('{"embedding_scale": 2.0}', r"derives \['embedding_scale'\], which are not"),
    ('{"logits_scaling": 2.0}', r"derives \['logits_scaling'\], which the"),
], ids=["unknown", "stated-twice"])
def test_a_derived_multiplier_is_checked(derived, error, tmp_path,
                                         monkeypatch):
    """A family may derive only the known multipliers, and only those the
    configuration does not state."""
    path = tmp_path / "derives.py"
    path.write_text((families.DIR / "dense.py").read_text()
                    + f"\ndef multipliers(m):\n    return {derived}\n")
    config = json.loads((cells.BENCH / "configs" / "granite-3-2b.json")
                        .read_text())
    assert "logits_scaling" in config
    monkeypatch.setattr(families, "DIR", tmp_path)
    with pytest.raises(families.FamilyError, match=error) as e:
        cells.model_dict(dict(config, family="derives"))
    assert str(path) in str(e.value)


def _tree(root, family):
    """A checkout holding one cell whose configuration names ``family``."""
    bench = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
    w = bench["workloads"][0]
    cfg = json.loads((cells.ROOT / bench["configs"][0]["file"]).read_text())
    cfg["family"] = family
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    (root / bench["configs"][0]["file"]).write_text(json.dumps(cfg))
    mix = f"bench/traffic/{w['traffic']}.json"
    (root / mix).write_text((cells.ROOT / mix).read_text())
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return w["name"]


def test_an_unknown_family_fails_with_the_path_it_looked_for(tmp_path):
    workload = _tree(tmp_path, "nonesuch")
    with pytest.raises(families.FamilyError) as e:
        cells.load(workload, root=tmp_path)
    assert str(families.DIR / "nonesuch.py") in str(e.value)


def _served(m, seed):
    rng = np.random.default_rng(seed)
    return [reference.Served(
        prompt=rng.integers(0, m["vocab_size"], T).astype(np.int32),
        tokens=rng.integers(0, m["vocab_size"], n).astype(np.int32), d0=d0)
        for T, n, d0 in ((48, 12, 0), (64, 20, 5))]


@pytest.mark.parametrize("config", ["qwen1.5-110b"])
def test_a_family_in_another_directory_is_found_and_used(config, tmp_path,
                                                         monkeypatch):
    """A copy of dense under another name, in a directory of its own, gives
    what dense gives: the program's config, the weights, the reference's
    logits (bitwise, fp32 and the fp8 control, which rounds only the fp8
    weights a layer has) and the FLOP counts."""
    dense = smoke_cell(config, "poisson", dtype="float32", decode_margin=4)
    m0, comp = dense.model, dense.compression
    imp = counts.importance(m0["n_layers"], m0["n_kv_heads"],
                            dense.traffic["importance"])
    key = weights.root_key(2 ** 32 + 5)
    want = reference.logits(m0, comp, imp, key, jnp.float32, _served(m0, 1))
    want_fp8 = reference.logits(m0, comp, imp, key, jnp.float32,
                                _served(m0, 1), "fp8")
    want_cfg = cells.model_config(dense)
    want_w = [weights.layer_weights(m0, key, i, jnp.float32)
              for i in range(m0["n_layers"])]
    want_flops = ([counts.prefill_flops(m0, T) for T in (48, 64, 8192)],
                  counts.decode_flops(m0, np.full((m0["n_layers"],
                                                   m0["n_kv_heads"]), 37)))

    cell = smoke_cell(config, "poisson", dtype="float32", decode_margin=4)
    (tmp_path / "mirror.py").write_text(
        (families.DIR / "dense.py").read_text() + NESTED)
    monkeypatch.setattr(families, "DIR", tmp_path)  # dense is not there
    cell.config["family"] = "mirror"
    cell.config["num_experts"] = 0
    m = cell.model
    assert m["family"] == "mirror" and m["n_experts"] == 0
    assert families.of(m).__file__ == str(tmp_path / "mirror.py")
    assert cells.model_config(cell) == want_cfg

    for i, a in enumerate(want_w):
        b = weights.layer_weights(m, key, i, jnp.float32)
        assert sorted(a) == sorted(b)
        for name in a:
            np.testing.assert_array_equal(np.asarray(a[name]),
                                          np.asarray(b[name]))
    got = reference.logits(m, comp, imp, key, jnp.float32, _served(m, 1))
    got_fp8 = reference.logits(m, comp, imp, key, jnp.float32, _served(m, 1),
                               "fp8")
    for x, y in zip(want + want_fp8, got + got_fp8):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    lens = np.full((m["n_layers"], m["n_kv_heads"]), 37)
    assert ([counts.prefill_flops(m, T) for T in (48, 64, 8192)],
            counts.decode_flops(m, lens)) == want_flops

    # the family's key of a nested group is checked as a shared one is
    cell.config["num_experts"] = 4
    with pytest.raises(ValueError, match="n_experts"):
        cells.model_config(cell)
    cell.config["overrides"].append("n_experts")
    got_cfg = cells.model_config(cell)
    assert got_cfg.moe.num_experts == 4
    assert got_cfg.moe.top_k == want_cfg.moe.top_k
    assert got_cfg.d_ff == want_cfg.d_ff


def test_a_list_valued_key_comes_as_a_tuple(tmp_path, monkeypatch):
    """A per-layer pattern reaches the model dict hashable, as the
    reference's jitted layers take the dict as a static argument."""
    (tmp_path / "pattern.py").write_text(
        (families.DIR / "dense.py").read_text()
        + '\nMODEL_KEYS = {**MODEL_KEYS, "layer_types": "layer_types"}'
        + '\nPROGRAM_FIELDS = {**PROGRAM_FIELDS, "layer_types": "x"}\n')
    config = json.loads((cells.BENCH / "configs" / "qwen1.5-110b.json")
                        .read_text())
    monkeypatch.setattr(families, "DIR", tmp_path)
    config["family"] = "pattern"
    config["layer_types"] = ["sliding_attention", "full_attention"]
    m = cells.model_dict(config)
    assert m["layer_types"] == ("sliding_attention", "full_attention")
    hash(tuple(sorted(m.items())))


def test_dotted_fields_reach_nested_groups():
    base = get_config("qwen1.5-110b")
    cfg = cells.with_fields(base, {"n_layers": 2, "moe.num_experts": 8,
                                   "moe.top_k": 2, "ssm.state_size": 16})
    assert (cfg.n_layers, cfg.moe.num_experts, cfg.moe.top_k) == (2, 8, 2)
    assert cfg.ssm.state_size == 16
    assert cfg.moe.d_expert == base.moe.d_expert
    assert cells.program_field(cfg, "moe.top_k") == 2
    assert cells.program_field(cfg, "d_ff") == base.d_ff


def _gemma2_cell(tmp_path, monkeypatch, fault=None):
    """The gemma2 smoke cell (float32, a decode margin that makes the ring
    wrap) of the test family, copied into its own directory (with one half
    of its seam taken away under ``fault``)."""
    name = "gemma2" if fault is None else f"gemma2_no_{fault}"
    src = GEMMA2.read_text() + GEMMA2_FAULTS.get(fault, "")
    (tmp_path / f"{name}.py").write_text(src)
    monkeypatch.setattr(families, "DIR", tmp_path)
    return cell_at_smoke("gemma2-9b", dict(GEMMA2_CONFIG, family=name),
                         "saturated", dtype="float32", decode_margin=4)


def test_a_family_whose_layers_differ_by_index_enters_by_its_file_alone(
        tmp_path, monkeypatch):
    """Windowed and full layers, the embedding multiplier applied by the
    program: prompts longer than the window, decodes that cross it, and the
    paged engine agrees with the reference."""
    cell = _gemma2_cell(tmp_path, monkeypatch)
    m = cell.model
    assert families.of(m).__file__ == str(tmp_path / "gemma2.py")
    cfg = cells.model_config(cell)
    assert (cfg.sliding_window, cfg.attn_softcap, cfg.logit_softcap) == (
        16, 0.0, 0.0)
    assert [cfg.layer_is_local(i) for i in range(m["n_layers"])] == [
        families.of(m).layer_kind(m, i) > 0 for i in range(m["n_layers"])]
    # the family derives the embedding multiplier from the width, so it
    # follows the smoke cut; nothing is folded, as the program applies it
    assert m["embedding_multiplier"] == 8.0
    assert cells.model_dict(GEMMA2_CONFIG)["embedding_multiplier"] == (
        math.sqrt(3584))
    assert cfg.rms_eps == m["rms_eps"] == 1e-6
    assert weights.multiplier_factors(m) == (1.0, 1.0, 1.0)
    # the seeded extras differ by index: the MLP norm on windowed layers
    key = weights.root_key(1234)
    ln2 = [np.asarray(weights.layer_weights(m, key, i, jnp.float32)["ln2"])
           for i in range(m["n_layers"])]
    assert [bool(x.any()) for x in ln2] == [True, False]
    reqs, served = serve_paged(cell, 1234)
    window = m["sliding_window"]
    assert all(len(r.prompt) > window for r in served)
    assert max(len(r.tokens) for r in served) > window
    a = against_reference(m, cell, 1234, reqs, served)
    assert agrees(a), a
    assert a["wrapped"], "no head reached capacity: the ring went untested"


@pytest.mark.parametrize("fault", sorted(GEMMA2_FAULTS))
def test_each_half_of_the_family_seam_matters(fault, tmp_path, monkeypatch):
    """The gemma2 test family reads not correct against the engine with its
    windows dropped from the reference, and again with its embedding
    multiplier folded into the weights as well as applied by the program."""
    broken = _gemma2_cell(tmp_path, monkeypatch, fault)
    if fault == "window":
        # the engine serves the sound family; the reference drops the window
        reqs, served = serve_paged(_gemma2_cell(tmp_path, monkeypatch), 1234)
        assert not any(families.of(broken.model).layer_kind(broken.model, i)
                       for i in range(broken.model["n_layers"]))
    else:
        # the harness folds what the program also applies
        assert cells.model_config(broken).rms_eps == 1e-6 / 64
        reqs, served = serve_paged(broken, 1234)
    a = against_reference(broken.model, broken, 1234, reqs, served)
    assert not agrees(a), a
