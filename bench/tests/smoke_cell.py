"""A cell at smoke size for the CPU tests: a real configuration file with
its widths cut to a toy, and a small mix of the same kind as the real one."""
import json

import cells
import run

SMOKE_WIDTHS = {"num_hidden_layers": 2, "hidden_size": 64,
                "num_attention_heads": 4, "num_key_value_heads": 2,
                "head_dim": 16, "intermediate_size": 128, "vocab_size": 256}
# the smoke cell of each benchmark cell: (configuration, arrival)
CELLS = {"granite-3-2b.decode-long": ("granite-3-2b", "saturated"),
         "qwen1.5-110b.prefill-mix": ("qwen1.5-110b", "poisson")}
# the numbers each cell's check file compares, with limits for the smoke
# size, set between what sound bfloat16 runs and the fp8 control read there
# on seeds 1, 3 and 2**31 + 99 (largest sound / smallest control mean gap):
# granite 4.1e-4 / 0.0143, qwen 2.4e-5 / 0.00245
SMOKE_LIMITS = {"granite-3-2b.decode-long": {"mean_logit_gap": 0.003},
                "qwen1.5-110b.prefill-mix": {"mean_logit_gap": 0.0006}}


def smoke_limits(workload: str) -> dict:
    """The cell's own check file with the limits of the smoke size and
    fewer positions (smoke requests are short)."""
    own = run.load_limits(workload)
    assert set(own["limits"]) == set(SMOKE_LIMITS[workload])
    return dict(own, min_sampled_tokens=10, limits=SMOKE_LIMITS[workload])


def smoke_cell(config: str, arrival: str, dtype: str = "bfloat16",
               decode_margin: int = 8) -> cells.Cell:
    cfg = json.loads((cells.BENCH / "configs" / f"{config}.json").read_text())
    cfg.update(SMOKE_WIDTHS)
    cfg["overrides"] = ["n_layers", "d_model", "n_heads", "n_kv_heads",
                        "head_dim", "d_ff", "vocab_size", "rms_eps"]
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
