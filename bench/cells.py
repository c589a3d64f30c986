"""A cell of ``BENCHMARK.json``, found by name, and the engine it builds.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
files are ``bench/configs/<config>.json`` and ``bench/traffic/<mix>.json``.
Nothing here is specific to one cell, so a later change adds a cell by
adding files and entries alone.

A configuration file holds the model as it is run, under the published
config's own key names, plus the engine settings that belong to the
model (``engine``: dtype, paging, placement), and names its family
(``"family"``, ``families.DEFAULT`` where absent: the module under
``bench/families/`` that knows the family's own keys and layers).  The
executor follows from the cell's ``chips``.  A traffic file holds the mix
(``traffic.py``), the batch width and the HeadKV settings.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, replace
from pathlib import Path

import families

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


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


def family_name(config: dict) -> str:
    return config.get("family", families.DEFAULT)


def family_of(config: dict):
    return families.load(family_name(config))


def model_keys(config: dict) -> dict:
    """Published config key -> model-dict key: the shared ones and the
    family's own."""
    return {**families.SHARED_KEYS, **family_of(config).MODEL_KEYS}


def model_dict(config: dict) -> dict:
    """What ``weights``/``counts``/``reference`` and the family read: the
    configuration's sizes under the model-dict keys (a list, such as a
    per-layer pattern, as a tuple: the reference's jitted layers take the
    dict as a static argument), the multipliers (published, or derived by
    the family from the sizes: ``multipliers``), the padded vocabulary and
    the family's name."""
    keys = model_keys(config)
    missing = sorted(set(keys) - set(config))
    if missing:
        raise KeyError(f"configuration lacks {missing}")
    m = {}
    for key, name in keys.items():
        v = config[key]
        m[name] = tuple(v) if isinstance(v, list) else v
    for key, default in families.MULTIPLIERS.items():
        m[key] = float(config.get(key, default or m["head_dim"] ** -0.5))
    fam = family_of(config)
    derived = fam.multipliers(m)
    unknown = sorted(set(derived) - set(families.MULTIPLIERS))
    if unknown:
        raise families.FamilyError(
            f"{fam.__file__}: multipliers() derives {unknown}, which are not "
            f"among {sorted(families.MULTIPLIERS)}")
    twice = sorted(set(derived) & set(config))
    if twice:
        raise families.FamilyError(
            f"{fam.__file__}: multipliers() derives {twice}, which the "
            f"configuration states as well")
    m.update({k: float(v) for k, v in derived.items()})
    m["padded_vocab"] = -(-m["vocab_size"] // 128) * 128
    m["family"] = family_name(config)
    return m


def program_model(m: dict) -> dict:
    """The sizes the program runs.  It serves the multipliers its family
    does not list under ``PROGRAM_APPLIES`` folded into its weights
    (``weights.checkpoint_layer`` undoes the folding for the reference);
    with the embedding multiplier folded it carries the residual stream
    divided by it, so RMSNorm's eps shrinks by its square and the function
    is unchanged."""
    p = dict(m)
    if "embedding_multiplier" not in families.of(m).PROGRAM_APPLIES:
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
    family_of(config)  # an unknown family fails here, with its path
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
    fields = {k: k for k in families.SHARED_KEYS.values()}
    fields.update(family_of(cell.config).PROGRAM_FIELDS)
    changed = {k: m[k] for k, f in fields.items()
               if program_field(base, f) != m[k]}
    allowed = set(cell.config.get("overrides", ()))
    bad = sorted(set(changed) - allowed)
    if bad:
        raise ValueError(
            f"{cell.config_name}: {bad} differ from the registry's "
            f"{base.name} and are not listed under 'overrides'")
    return with_fields(base, {fields[k]: v for k, v in changed.items()})


def program_field(cfg, field: str):
    """A field of a `ModelConfig`, dotted into a nested group
    (``"moe.d_expert"``)."""
    for part in field.split("."):
        cfg = getattr(cfg, part)
    return cfg


def with_fields(cfg, values: dict):
    """``cfg`` with the dotted fields of ``values`` replaced."""
    top, nested = {}, {}
    for field, v in values.items():
        head, _, rest = field.partition(".")
        if rest:
            nested.setdefault(head, {})[rest] = v
        else:
            top[head] = v
    for head, sub in nested.items():
        top[head] = with_fields(getattr(cfg, head), sub)
    return replace(cfg, **top)


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
