"""A cell of ``BENCHMARK.json``, found by name, and the engine it builds.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
files are ``bench/configs/<config>.json`` and ``bench/traffic/<mix>.json``.
Nothing here is specific to one cell, so a later change adds a cell by
adding files and entries alone.

A configuration file holds the model as it is run, under the published
config's own key names, plus the engine settings that belong to the
model (``engine``: dtype, paging, placement).  The executor follows from
the cell's ``chips``.  A traffic file holds the mix (``traffic.py``), the
batch width and the HeadKV settings.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

# published config key -> model dict key; the model dict is what
# ``weights``/``counts``/``reference`` read
MODEL_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
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


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict  # the configuration file
    traffic_name: str
    traffic: dict  # the traffic file

    @property
    def model(self) -> dict:
        return model_dict(self.config)

    @property
    def compression(self) -> dict:
        return dict(self.traffic["compression"])


def model_dict(config: dict) -> dict:
    missing = sorted(set(MODEL_KEYS) - set(config))
    if missing:
        raise KeyError(f"configuration lacks {missing}")
    m = {name: config[key] for key, name in MODEL_KEYS.items()}
    for key, default in MULTIPLIERS.items():
        m[key] = float(config.get(key, default or m["head_dim"] ** -0.5))
    m["padded_vocab"] = -(-m["vocab_size"] // 128) * 128
    return m


def program_model(m: dict) -> dict:
    """The sizes the program runs.  It has no multipliers: it serves them
    folded into its weights (``weights.checkpoint_layer`` undoes the folding
    for the reference) and carries the residual stream divided by the
    embedding multiplier, so RMSNorm's eps shrinks by its square and the
    function is unchanged."""
    p = dict(m)
    p["rms_eps"] = m["rms_eps"] / m["embedding_multiplier"] ** 2
    return p


def load(workload: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((root / cfg["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name=workload, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic)


def model_config(cell: Cell):
    """The program's `ModelConfig`: the registry's architecture with the
    configuration file's sizes, which must agree with it or be overrides."""
    from repro.configs import get_config
    base = get_config(cell.config["arch"])
    m = program_model(cell.model)
    changed = {k: m[k] for k in MODEL_KEYS.values()
               if getattr(base, k) != m[k]}
    allowed = set(cell.config.get("overrides", ()))
    bad = sorted(set(changed) - allowed)
    if bad:
        raise ValueError(
            f"{cell.config_name}: {bad} differ from the registry's "
            f"{base.name} and are not listed under 'overrides'")
    return base.with_overrides(**changed)


def engine_config(cell: Cell, n_blocks: int):
    """`EngineConfig` of a cell; ``n_blocks`` sizes the paged pool."""
    from repro.api import EngineConfig
    from repro.compression.base import CompressionConfig
    from repro.core.planner import PlannerConfig
    from repro.paging.block_pool import PagingConfig
    from repro.serving.scheduler import SchedulerConfig
    eng = cell.config["engine"]
    rows = int(cell.traffic["rows"])
    comp = cell.compression
    max_seq = max(cell.traffic["prompt_buckets"]) + cell.traffic["output_max"]
    return EngineConfig(
        model=model_config(cell),
        compression=CompressionConfig(**comp),
        planner=PlannerConfig(**eng["planner"], batch_cap=rows),
        scheduler=SchedulerConfig(max_rows=rows,
                                  **eng.get("scheduler", {})),
        n_shards=int(eng["n_shards"]),
        dtype=eng["dtype"],
        max_seq_len=max_seq,
        cache_backend="paged",
        paging=PagingConfig(n_blocks=int(n_blocks), **eng["paging"]),
        executor="local" if cell.chips == 1 else "mesh",
    )
