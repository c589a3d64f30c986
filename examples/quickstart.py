"""Quickstart: FairKV end to end on a reduced model (CPU, ~1 min).

Everything goes through the `repro.api` facade:

1. build an `Engine` and measure the per-head workload profile (the
   compression statistic) with a profiling prefill,
2. rebuild the engine under three planners (SHA / best-effort /
   fair-copying) against the measured profile,
3. `Engine.generate` a batch under each plan,
4. show that logits are identical (the plan is a layout, not math) while
   the simulated shard balance improves,
5. print the realized cache-memory footprint — the paged block pool only
   pins memory proportional to the realized per-head retained lengths, so
   the footprint line shows the win over a dense slot cache in one glance.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import argparse

import jax.numpy as jnp

from repro.api import (
    CompressionConfig,
    Engine,
    EngineConfig,
    PagingConfig,
    PlannerConfig,
)
from repro.configs.base import InputShape
from repro.training.data import SyntheticLM

ARCH = "minitron-8b"
SHARDS = 8
BUDGET = 24
T, B, GEN = 96, 2, 8


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--block-size", type=int, default=8,
                    help="tokens per KV block")
    args = ap.parse_args(argv)

    base_cfg = EngineConfig.smoke(
        ARCH, n_shards=SHARDS, max_seq_len=T + GEN + 8,
        compression=CompressionConfig(policy="ada_snapkv", budget=BUDGET,
                                      alpha_max=2.0, obs_window=8, sink=2,
                                      decode_margin=8),
        planner=PlannerConfig(mode="sha", batch_cap=B),
        paging=PagingConfig(block_size=args.block_size))
    data = SyntheticLM(base_cfg.model, InputShape("qs", T, B, "prefill"))
    batch = data.get_batch(0)

    # --- profiling pass (paper §4.1): run the compression policy on a sample
    # batch and measure the per-head retained lengths; the planner consumes
    # the measured profile, exactly like the paper's offline statistics
    probe = Engine.build(base_cfg)
    profile = probe.measure_profile(data.get_batch(123))
    print(f"measured profile per-head mean budgets: "
          f"{profile.mean(0).round(1).tolist()}\n")

    results = {}
    mem = None
    for mode, ch in [("sha", 0), ("fairkv_nodp", 0), ("fairkv_dp", 4)]:
        cfg = base_cfg.replace(planner=PlannerConfig(
            mode=mode, extra_copies=ch, batch_cap=B))
        # shared params: the plan is a layout over one set of weights
        eng = Engine.build(cfg, params=probe.params, profile=profile)
        res = eng.generate(batch, GEN)
        results[mode] = {
            "logits": jnp.asarray(res.logits),
            "E": res.efficiency,
            "makespan": res.makespan,
            "tokens": res.tokens[:, -1],
        }
        mem = eng.memory_stats()
        print(f"{mode:13s} E={res.efficiency:.3f} "
              f"makespan={res.makespan:8.1f} "
              f"last tokens={res.tokens[:, -1].tolist()}")

    d = float(jnp.abs(results["sha"]["logits"]
                      - results["fairkv_dp"]["logits"]).max())
    print(f"\nplan-invariance: max |logits_SHA - logits_FairKV| = {d:.2e}")
    gain = results["sha"]["makespan"] / results["fairkv_dp"]["makespan"]
    print(f"balance gain (SHA makespan / FairKV-DP makespan) = {gain:.2f}x")
    assert d < 1e-3

    # --- realized memory footprint of the paged cache ------------------------
    slot_eq = mem["slot_equivalent_bytes"]
    print(f"\ncache footprint: {mem['cache_bytes']} B in "
          f"{mem['blocks_in_use']} blocks of {mem['block_size']} tokens "
          f"vs a dense slot cache's {slot_eq} B "
          f"({slot_eq / max(1, mem['cache_bytes']):.2f}x saved)")


if __name__ == "__main__":
    main()
