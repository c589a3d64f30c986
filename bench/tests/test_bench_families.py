"""The family seam: every family module keeps the contract, an unknown
family fails where the cell is loaded, and a family module in another
directory is found by name and used by ``cells``, ``weights``,
``reference`` and ``counts``."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

import cells
import counts
import families
import reference
import weights
from repro.configs import get_config
from smoke_cell import smoke_cell

MODULES = sorted(p.stem for p in families.DIR.glob("*.py")
                 if not p.stem.startswith("_"))
# the extra lines that give a copy of dense a published key of a nested
# group of the program's ModelConfig
NESTED = '''
MODEL_KEYS = {**MODEL_KEYS, "num_experts": "n_experts"}
PROGRAM_FIELDS = {**PROGRAM_FIELDS, "n_experts": "moe.num_experts"}
'''


@pytest.mark.parametrize("name", MODULES)
def test_every_family_module_keeps_the_contract(name):
    fam = families.load(name)
    families.check(fam, families.DIR / f"{name}.py")
    assert set(fam.SMOKE) <= set(families.SHARED_KEYS) | set(fam.MODEL_KEYS)


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
    logits (bitwise) and the FLOP counts."""
    dense = smoke_cell(config, "poisson", dtype="float32", decode_margin=4)
    m0, comp = dense.model, dense.compression
    imp = counts.importance(m0["n_layers"], m0["n_kv_heads"],
                            dense.traffic["importance"])
    key = weights.root_key(2 ** 32 + 5)
    want = reference.logits(m0, comp, imp, key, jnp.float32, _served(m0, 1))
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
    for x, y in zip(want, got):
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
