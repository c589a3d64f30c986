"""Figure 13 (repo extension): self-speculative decoding throughput.

Three arms over one identical burst trace on the paged backend, one
warmed engine per arm (DESIGN.md §16):

- **single_token** — the baseline continuous scheduler, one greedy token
  per decode tick.
- **speculative** — the gated arm: a half-depth early-exit draft
  (``draft_layers = L // 2``) proposes ``max_k`` tokens per tick at
  ``d / L`` of the target's cost each, and one batched multi-query
  verify pass commits the accepted run.
- **full_depth_draft** — reported, not gated: ``draft_layers = 0`` makes
  the draft the target itself, isolating the dispatch-amortization part
  of the win (fewer scheduler ticks) from the cheap-draft part.

**The early-exit operating point.**  Self-speculative decoding pays off
when the truncated forward agrees with the full model often (LayerSkip
reports 70-90% on trained checkpoints).  This repo's smoke models have
random weights, where a truncated draft accepts ~10% — the system would
be benchmarked at an operating point no deployment runs at.  The suite
therefore synthesizes the high-agreement regime structurally: the top
``L - d`` layers' residual contributions are zeroed (``wo`` and ``w2``),
making the half-depth draft agree with the target *exactly* (acceptance
1.0) while propose still runs only ``d`` of ``L`` layers.  Every arm
shares these same weights, and tokens are asserted bit-identical across
arms — the speedup is never bought with different output.

Prompts are fixed-length so prefill compiles once in the warm trace;
the timed window is decode-bound, which is what speculation targets.

Acceptance (``REPRO_BENCH_SMOKE=0``): ``speedup >= 1.3`` at
``acceptance >= 0.7`` in the speculative arm (smoke gate: ``1.1``).  A CPU
correctness figure: the speedup is CPU wall clock, not a device time.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import (
    CompressionConfig,
    Engine,
    EngineConfig,
    PagingConfig,
    PlannerConfig,
    SchedulerConfig,
    SpeculationConfig,
    init_params,
    latency_percentiles,
    synthesize_requests,
)

SMOKE = os.environ.get("REPRO_BENCH_SMOKE", "") not in ("", "0")

ARCH = "minitron-8b"
SEED = 17
ROWS = 4
# the trace is identical in smoke and full mode (it is already small);
# only the gate differs — wall-clock ratios need the decode-bound window
N_REQUESTS = 8
PROMPT = 12  # fixed length: one prefill compile, decode-bound timed window
GEN = 48
MAX_K = 7
CAP = PROMPT + GEN + 8
GATE_SPEEDUP = 1.1 if SMOKE else 1.3
GATE_ACCEPTANCE = 0.7


def _cfg(spec: SpeculationConfig | None = None) -> EngineConfig:
    return EngineConfig.smoke(
        ARCH, n_shards=4, max_seq_len=CAP,
        compression=CompressionConfig(policy="none", budget=CAP,
                                      capacity=CAP, obs_window=8, sink=2,
                                      decode_margin=8),
        planner=PlannerConfig(mode="fairkv_dp", extra_copies=6,
                              batch_cap=ROWS),
        scheduler=SchedulerConfig(max_rows=ROWS, enable_replan=False),
        cache_backend="paged", paging=PagingConfig(block_size=8),
        speculation=spec or SpeculationConfig())


def early_exit_params(cfg: EngineConfig, draft_layers: int) -> dict:
    """Init params, then zero the residual contributions (attention
    o-projection + MLP down-projection) of every layer >= draft_layers:
    the truncated forward equals the full forward by construction, so the
    draft's acceptance is exactly 1.0 at ``d / L`` propose cost."""
    params = init_params(cfg.model, jax.random.PRNGKey(cfg.seed),
                         dtype=jnp.float32, max_seq_len=cfg.max_seq_len)
    for i in range(draft_layers, cfg.model.n_layers):
        pl = dict(params["layers"][i])
        pl["wo"] = jnp.zeros_like(pl["wo"])
        pl["w2"] = jnp.zeros_like(pl["w2"])
        params["layers"][i] = pl
    return params


def _reqs(vocab: int, seed: int):
    return synthesize_requests(N_REQUESTS, 10.0, vocab, min_prompt=PROMPT,
                               max_prompt=PROMPT, max_new_tokens=GEN,
                               seed=seed)


REPEATS = 5  # timed repeats per arm; best wall wins (shields CPU noise)


def prepare_arm(name: str, spec: SpeculationConfig | None,
                params: dict) -> Engine:
    """Build + warm one arm's engine (compiles prefill and the arm's
    StepFn keys outside every timed window)."""
    cfg = _cfg(spec)
    eng = Engine.build(cfg, params=params)
    warm = eng.run_trace(_reqs(cfg.model.vocab_size, SEED + 1),
                         max_steps=4000)
    assert warm["finished"] == warm["total"], (name, warm)
    return eng


def time_arm(name: str, eng: Engine) -> tuple:
    """One timed burst on a warmed engine -> (wall_s, summary, requests)."""
    # drop prior requests so each timed trace drains on its own count
    # (and so stats().speculation sums the last window only)
    eng.scheduler.finished.clear()
    reqs = _reqs(eng.cfg.model.vocab_size, SEED)
    t0 = time.time()
    out = eng.run_trace(reqs, max_steps=4000)
    wall = time.time() - t0
    assert out["finished"] == out["total"], (name, out)
    return wall, out, reqs


def summarize_arm(eng: Engine, best: tuple) -> dict:
    wall, out, reqs = best
    st = eng.stats()
    eng.scheduler.backend.pool.check_invariants()
    pct = latency_percentiles(reqs)
    return {
        "tokens": {r.req_id: tuple(r.generated) for r in reqs},
        "wall_s": wall, "steps": out["steps"],
        "generated_tokens": sum(r.n_generated for r in reqs),
        "tokens_per_s": sum(r.n_generated for r in reqs) / wall,
        "acceptance": st.speculation.acceptance,
        "proposed": st.speculation.proposed,
        "p50_itl_s": pct.get("p50_itl_s"), "p99_itl_s": pct.get("p99_itl_s"),
        "p50_ttft_s": pct.get("p50_ttft_s"),
    }


def main():
    n_layers = _cfg().model.n_layers
    draft = max(1, n_layers // 2)
    params = early_exit_params(_cfg(), draft)
    specs = {
        "single_token": None,
        # the gate arm: cheap early-exit draft at structural acceptance 1.0
        "speculative": SpeculationConfig(enabled=True, max_k=MAX_K,
                                         draft_layers=draft),
        # draft == target: isolates the tick-amortization share of the win
        "full_depth_draft": SpeculationConfig(enabled=True, max_k=MAX_K),
    }
    engines = {name: prepare_arm(name, spec, params)
               for name, spec in specs.items()}
    # interleave the timed repeats round-robin across arms so slow drift
    # of the shared CPU hits every arm equally instead of biasing one
    best: dict = {}
    for _ in range(REPEATS):
        for name, eng in engines.items():
            run = time_arm(name, eng)
            if name not in best or run[0] < best[name][0]:
                best[name] = run
    arms = {name: summarize_arm(eng, best[name])
            for name, eng in engines.items()}
    base = arms["single_token"]

    metrics = {"conditions": {
        "smoke": SMOKE, "arch": ARCH, "rows": ROWS, "n": N_REQUESTS,
        "prompt": PROMPT, "gen": GEN, "max_k": MAX_K,
        "n_layers": n_layers, "draft_layers": draft, "seed": SEED,
        "gate_speedup": GATE_SPEEDUP, "gate_acceptance": GATE_ACCEPTANCE,
    }}
    for name, r in arms.items():
        acc = "n/a" if r["acceptance"] is None else f"{r['acceptance']:.3f}"
        itl = "n/a" if r["p50_itl_s"] is None else f"{r['p50_itl_s']:.4f}"
        print(f"fig13/{ARCH}/{name},{r['wall_s'] * 1e6:.0f},"
              f"tokens_per_s={r['tokens_per_s']:.2f};steps={r['steps']};"
              f"acceptance={acc};proposed={r['proposed'] or 0};"
              f"p50_itl_s={itl}")
        # speculation must never change the output tokens
        assert r["tokens"] == base["tokens"], (name, "token mismatch")
        metrics[name] = {k: v for k, v in r.items() if k != "tokens"}

    spec_arm = arms["speculative"]
    speedup = spec_arm["tokens_per_s"] / base["tokens_per_s"]
    metrics["speedup"] = speedup
    metrics["speedup_full_depth"] = (arms["full_depth_draft"]["tokens_per_s"]
                                     / base["tokens_per_s"])
    print(f"fig13/speedup,0,spec_over_single={speedup:.3f};"
          f"full_depth={metrics['speedup_full_depth']:.3f};"
          f"acceptance={spec_arm['acceptance']:.3f}")
    assert spec_arm["acceptance"] >= GATE_ACCEPTANCE, (
        f"gated arm acceptance {spec_arm['acceptance']} < {GATE_ACCEPTANCE}")
    assert speedup >= GATE_SPEEDUP, (
        f"speculative speedup {speedup:.3f} < gate {GATE_SPEEDUP}")
    return metrics


if __name__ == "__main__":
    main()
