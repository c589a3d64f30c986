"""A cell at smoke size for the CPU tests: a real configuration file with
its widths cut to its family's toy (``SMOKE``), and a small mix of the
same kind as the cell's own (its traffic file's arrival).

The cells are those of ``BENCHMARK.json`` plus any whose check file
(``bench/checks/<cell>.json``) exists without an entry there; a check file
carries the cell's limits at smoke size under ``"smoke_limits"``.
"""
import json

import cells
import run

CONFIGS = sorted(p.stem for p in (cells.BENCH / "configs").glob("*.json"))
SMOKE_TOKENS = 10  # compared positions a smoke run needs (requests are short)


def _cell_files() -> dict:
    """{cell: (configuration, traffic mix)}."""
    bench = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
    out = {w["name"]: (w["config"], w["traffic"])
           for w in bench["workloads"]}
    mixes = [p.stem for p in (cells.BENCH / "traffic").glob("*.json")]
    for p in (cells.BENCH / "checks").glob("*.json"):
        if p.stem not in out:
            found = [(c, t) for c in CONFIGS for t in mixes
                     if p.stem == f"{c}.{t}"]
            if len(found) != 1:
                raise ValueError(f"{p}: its name is not one configuration "
                                 f"and one traffic mix")
            out[p.stem] = found[0]
    return out


CELLS = _cell_files()


def smoke_limits(workload: str) -> dict:
    """The cell's own check file with the limits of the smoke size and
    fewer positions."""
    own = run.load_limits(workload)
    assert set(own["limits"]) == set(own["smoke_limits"])
    return dict(own, min_sampled_tokens=SMOKE_TOKENS,
                limits=own["smoke_limits"])


def smoke_of(workload: str, **kw) -> cells.Cell:
    """The smoke cell of a benchmark cell: its configuration, and the
    arrival of its traffic file."""
    config, mix = CELLS[workload]
    tr = json.loads((cells.BENCH / "traffic" / f"{mix}.json").read_text())
    return smoke_cell(config, tr["arrival"], **kw)


def smoke_cell(config: str, arrival: str, dtype: str = "bfloat16",
               decode_margin: int = 8) -> cells.Cell:
    cfg = json.loads((cells.BENCH / "configs" / f"{config}.json").read_text())
    smoke = cells.family_of(cfg).SMOKE
    cfg.update(smoke)
    keys = cells.model_keys(cfg)
    cfg["overrides"] = [keys[k] for k in smoke] + ["rms_eps"]
    cfg["engine"]["dtype"] = dtype
    cfg["engine"]["paging"] = {"block_size": 16, "decode_impl": "auto",
                               "kv_dtype": "fp32"}
    tr = {"arrival": arrival, "rows": 4, "backlog": 2, "n_requests": 24,
          "schedule_seed": 3,
          "rate_rps": 20.0, "prompt_buckets": [48, 64],
          "prompt_weights": [0.5, 0.5], "output_min": 8, "output_max": 24,
          "compression": {"policy": "headkv", "budget": 16, "alpha_max": 2.0,
                          "headkv_base_ratio": 0.2, "obs_window": 8,
                          "sink": 2, "pool": 7,
                          "decode_margin": decode_margin},
          "importance": {"sigma": 1.0, "seed": 5}}
    return cells.Cell(name="smoke", chips=1, config_name=config, config=cfg,
                      traffic_name="smoke", traffic=tr)
