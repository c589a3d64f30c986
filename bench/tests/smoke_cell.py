"""A cell at smoke size for the CPU tests: a real configuration file with
its widths cut to its family's toy (``SMOKE``), and a small mix of the
same kind as the cell's own (its traffic file's arrival).

The cells are those of ``BENCHMARK.json`` plus any whose check file
(``bench/checks/<cell>.json``) exists without an entry there; a check file
carries the cell's limits at smoke size under ``"smoke_limits"``.

``serve_paged`` and ``against_reference`` hold a smoke cell's paged engine,
served in float32, to the plain reference logit by logit.
"""
import dataclasses
import json

import jax.numpy as jnp
import numpy as np

import cells
import counts
import reference
import run
import weights

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
    return cell_at_smoke(config, cfg, arrival, dtype, decode_margin)


def cell_at_smoke(config: str, cfg: dict, arrival: str,
                  dtype: str = "bfloat16",
                  decode_margin: int = 8) -> cells.Cell:
    """The smoke cell of the configuration ``cfg`` (a configuration file's
    contents) named ``config``."""
    cfg = dict(cfg, overrides=list(cfg.get("overrides", ())),
               engine=dict(cfg["engine"]))
    smoke = cells.family_of(cfg).SMOKE
    cfg.update(smoke)
    keys = cells.model_keys(cfg)
    cfg["overrides"] = (cfg["overrides"] + [keys[k] for k in smoke]
                        + ["rms_eps"])
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


# an fp32 engine and the fp32 reference agree to rounding
LOGIT_ATOL = 2e-4  # on every logit of every served position
GAP_LIMIT = 1e-4  # a served token's logit below the reference's best


def serve_paged(cell: cells.Cell, seed: int) -> tuple:
    """(requests, served): three requests (prompts of 48, 64 and 57 tokens,
    30, 12 and 25 served tokens, the third admitted mid-stream at another
    ring phase) through the cell's paged engine with its logits kept."""
    from repro.api import Engine
    from repro.serving.request import Request
    m = cell.model
    imp = counts.importance(m["n_layers"], m["n_kv_heads"],
                            cell.traffic["importance"])
    params = run.make_params(cells.model_config(cell), m, seed,
                             cell.config["engine"]["dtype"])
    ecfg = cells.engine_config(cell, run.pool_blocks(cell, imp))
    ecfg = ecfg.replace(scheduler=dataclasses.replace(ecfg.scheduler,
                                                      collect_logits=True))
    eng = Engine.build(ecfg, params=params,
                       profile=run.plan_profile(cell, imp),
                       head_importance=imp)
    rng = np.random.default_rng(0)
    reqs = [Request(req_id=i, prompt=rng.integers(0, 256, T).astype(np.int32),
                    max_new_tokens=n)
            for i, (T, n) in enumerate([(48, 30), (64, 12), (57, 25)])]
    d0 = {}
    for r in reqs[:2]:
        eng.submit(r)
    step = 0
    while any(not r.is_finished for r in reqs):
        if step == 3:
            eng.submit(reqs[2])  # admitted mid-stream, at another phase
        ev = eng.step()
        for rid, _ in ev["admitted"]:
            d0[rid] = int(eng.scheduler.state.decode_steps) - 1
        step += 1
    served = [reference.Served(prompt=r.prompt,
                               tokens=np.asarray(r.generated, np.int32),
                               d0=d0[r.req_id]) for r in reqs]
    return reqs, served


def against_reference(m: dict, cell: cells.Cell, seed: int, reqs: list,
                      served: list) -> dict:
    """The widest distance between a served logit and the fp32 reference's
    (model dict ``m``, the seed's weights), the widest served-token gap
    (``reference.served_gap``), and whether any head's cache reached
    capacity, so that the ring wrapped."""
    imp = counts.importance(m["n_layers"], m["n_kv_heads"],
                            cell.traffic["importance"])
    ref = reference.logits(m, cell.compression, imp, weights.root_key(seed),
                           jnp.float32, served)
    cap = counts.static_capacity(cell.compression)
    out = {"logit_diff": 0.0, "gap": 0.0, "wrapped": False}
    for r, lg in zip(reqs, ref):
        got, want = np.stack(r.logits)[:, :m["vocab_size"]], np.asarray(lg)
        assert got.shape == want.shape, (r.req_id, got.shape, want.shape)
        out["logit_diff"] = max(out["logit_diff"], widest(np.abs(got - want)))
        out["gap"] = max(out["gap"],
                         widest(reference.served_gap(lg, r.generated)))
        keep = counts.headkv_keep(imp, cell.compression, len(r.prompt))
        out["wrapped"] |= bool((keep + len(r.generated) - 1 > cap).any())
    return out


def widest(x) -> float:
    """The largest of ``x``; infinite where any of it is not finite, so
    that a NaN never reads as agreement."""
    x = np.asarray(x)
    return float(x.max()) if np.isfinite(x).all() else float("inf")


def agrees(a: dict) -> bool:
    """The engine matches the reference (``against_reference``)."""
    return a["logit_diff"] <= LOGIT_ATOL and a["gap"] < GAP_LIMIT
