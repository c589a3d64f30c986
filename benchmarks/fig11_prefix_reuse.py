"""Figure 11 (repo extension): shared-prefix block reuse + chunked prefill.

Two arms, both on the paged backend (DESIGN.md §14):

**Arm A — effective capacity.**  A burst of requests sharing an 80%-long
prompt prefix hits a deliberately small block pool.  Without sharing, each
request charges its full prompt against the pool, so only a couple fit
concurrently; with the prefix index, every hit charges only its unshared
blocks (the shared ones are refcounted, stored once) and the same pool
holds several times more concurrent requests.  The observable is peak
concurrency (active + chunk-prefilling rows) over the trace — the
"effective capacity" of the pool — plus the bytes the pool never had to
hold twice.

**Arm B — chunked prefill vs head-of-line blocking.**  A long "aggressor"
prompt arrives while a cohort of short interactive requests streams in.
Monolithic prefill runs the whole aggressor prompt inside one scheduler
tick, stalling every concurrent decode; chunked prefill (fixed-width
chunks interleaved with decode ticks) bounds the per-tick prefill work, so
the short cohort's wall-clock TTFT — p99 especially — drops.  Both arms
run the same trace on warmed engines (compile cost paid before the
measured window).

Acceptance (``REPRO_BENCH_SMOKE=0``): ``capacity_gain >= 2.0`` (Arm A) and
``p99 TTFT chunked < monolithic`` for the short cohort (Arm B).  A CPU
correctness figure.
"""
from __future__ import annotations

import os
import time

import numpy as np

from repro.api import (
    CompressionConfig,
    Engine,
    EngineConfig,
    PagingConfig,
    PlannerConfig,
    PrefixConfig,
    SchedulerConfig,
    latency_percentiles,
)
from repro.serving.request import Request

SMOKE = os.environ.get("REPRO_BENCH_SMOKE", "") not in ("", "0")

ARCH = "minitron-8b"
BS = 16  # KV block size
SEED = 13

# --- Arm A: effective capacity under an 80%-shared burst --------------------
CAP_ROWS = 8
CAP_SHARED_LEN = 48  # template prefix (3 full chunks)
CAP_PROMPT = 64      # total prompt: 48 shared + 16 unique suffix
CAP_GEN = 8
CAP_N = 6 if SMOKE else 12
CAP_SHARED_FRAC = 0.8  # a fifth of the burst stays fully private

# --- Arm B: chunked prefill vs head-of-line blocking ------------------------
HOL_CHUNK = 32
HOL_AGGRESSOR = 128 if SMOKE else 256  # long-prompt tick-staller
HOL_SHORT = 16
HOL_SHORT_N = 6 if SMOKE else 12
HOL_GEN = 6
# one admission wave: every short shares the aggressor's prefill tick, so
# p99 TTFT measures head-of-line blocking rather than row-queue wait
HOL_ROWS = HOL_SHORT_N + 1


def _cfg(*, enabled: bool, chunk: int, n_blocks: int, rows: int,
         max_seq: int, budget: int = 128) -> EngineConfig:
    return EngineConfig.smoke(
        ARCH, max_seq_len=max_seq,
        compression=CompressionConfig(policy="none", budget=budget,
                                      capacity=budget, decode_margin=16,
                                      obs_window=8),
        planner=PlannerConfig(batch_cap=rows),
        scheduler=SchedulerConfig(max_rows=rows, enable_replan=False),
        cache_backend="paged",
        paging=PagingConfig(block_size=BS, n_blocks=n_blocks),
        prefix=PrefixConfig(enabled=enabled, chunk_tokens=chunk))


# ---------------------------------------------------------------------------
# Arm A
# ---------------------------------------------------------------------------


def capacity_trace(vocab: int):
    """One early donor + a step-8 burst, CAP_SHARED_FRAC of it sharing the
    donor's 48-token prefix (the donor registers the prefix at its chunk
    boundaries before the burst lands)."""
    rng = np.random.default_rng(SEED)
    shared = rng.integers(1, vocab, size=CAP_SHARED_LEN).astype(np.int32)
    n_shared = max(1, int(round(CAP_SHARED_FRAC * CAP_N)))
    reqs = []
    for i in range(CAP_N):
        if i < n_shared:
            sfx = rng.integers(1, vocab, size=CAP_PROMPT - CAP_SHARED_LEN)
            prompt = np.concatenate([shared, sfx.astype(np.int32)])
        else:
            prompt = rng.integers(1, vocab, size=CAP_PROMPT).astype(np.int32)
        reqs.append(Request(req_id=i, prompt=prompt,
                            arrival_step=0 if i == 0 else 8,
                            max_new_tokens=CAP_GEN))
    return reqs


def run_capacity(enabled: bool) -> dict:
    """Peak concurrency of the burst against a pool sized for ~2 private
    requests (admission needs prompt·H/bs + 2H blocks per layer)."""
    probe = _cfg(enabled=False, chunk=0, n_blocks=64, rows=CAP_ROWS,
                 max_seq=CAP_PROMPT + CAP_GEN + 8)
    H = probe.model.n_kv_heads
    private_need = CAP_PROMPT * H // BS + 2 * H
    n_blocks = int(2.3 * private_need) + 1  # ~2 private requests + null
    eng = Engine.build(_cfg(enabled=enabled, chunk=BS, n_blocks=n_blocks,
                            rows=CAP_ROWS, max_seq=CAP_PROMPT + CAP_GEN + 8))
    sched = eng._ensure_scheduler()
    peak, steps = 0, 0
    for _ in eng.stream(capacity_trace(eng.cfg.model.vocab_size),
                        max_steps=2000):
        peak = max(peak, len(sched.active) + len(sched.prefilling))
        steps = sched.step_idx
    assert all(r.is_finished for r in sched.finished), "trace did not drain"
    sched.backend.pool.check_invariants()
    pst = eng.stats().prefix  # consolidated typed snapshot (DESIGN.md §8)
    snap = eng.metrics()
    saved = 0
    if "prefix_bytes_saved" in snap:  # peak gauge over the run is not kept;
        saved = snap["prefix_bytes_saved"]["series"][0]["value"]
    return {
        "peak_concurrent": peak, "steps": steps, "n_blocks": n_blocks,
        "pool_blocks_per_layer": n_blocks, "hits": pst.hits or 0,
        "misses": pst.misses or 0,
        "final_bytes_saved": saved,
        "preemptions": sum(r.n_preemptions for r in sched.finished),
    }


# ---------------------------------------------------------------------------
# Arm B
# ---------------------------------------------------------------------------


def hol_trace(vocab: int, warm: bool = False, base: int = 0):
    """A long aggressor and a short interactive cohort arriving in the
    same burst.  Arrivals are step-indexed, so a later-arriving request
    never waits on an earlier slow tick — the cohort must land in the
    aggressor's admission step to pay (or dodge) its prefill wall time.
    ``base`` offsets arrivals past the warmup trace on a reused engine
    (the scheduler's step counter is monotonic across traces)."""
    rng = np.random.default_rng(SEED + (1 if warm else 2))
    id0 = 100 if not warm else 0
    reqs = [Request(req_id=id0,
                    prompt=rng.integers(1, vocab, size=HOL_AGGRESSOR)
                    .astype(np.int32),
                    arrival_step=base, max_new_tokens=HOL_GEN)]
    n = 2 if warm else HOL_SHORT_N
    for i in range(n):
        reqs.append(Request(
            req_id=id0 + i + 1,
            prompt=rng.integers(1, vocab, size=HOL_SHORT).astype(np.int32),
            arrival_step=base, max_new_tokens=HOL_GEN))
    return reqs


def run_hol(chunk: int) -> dict:
    """One warmed engine per mode; percentiles over the measured cohort.

    Driven through ``Engine.stream`` (not ``run_trace``): completion is
    judged on the measured requests alone, so the warmup trace's finished
    entries can't truncate the measured window.
    """
    eng = Engine.build(_cfg(enabled=False, chunk=chunk, n_blocks=0,
                            rows=HOL_ROWS, budget=HOL_AGGRESSOR,
                            max_seq=HOL_AGGRESSOR + HOL_GEN + 8))
    vocab = eng.cfg.model.vocab_size
    eng.run_trace(hol_trace(vocab, warm=True), max_steps=2000)  # compile
    base = eng._ensure_scheduler().step_idx
    reqs = hol_trace(vocab, base=base)
    t0 = time.time()
    for _ in eng.stream(reqs, max_steps=base + 2000):
        pass
    wall = time.time() - t0
    shorts = [r for r in reqs if r.req_id > 100]
    assert all(r.is_finished for r in reqs), "trace did not drain"
    pct = latency_percentiles(shorts)
    return {
        "wall_s": wall,
        "p50_ttft_s": pct.get("p50_ttft_s"),
        "p99_ttft_s": pct.get("p99_ttft_s"),
        "p99_ttft_steps": pct.get("p99_ttft_steps"),
        "aggressor_ttft_s": next(r for r in reqs
                                 if r.req_id == 100).ttft_seconds(),
    }


# ---------------------------------------------------------------------------


def main():
    metrics = {
        "conditions": {
            "smoke": SMOKE, "arch": ARCH, "block_size": BS, "seed": SEED,
            "capacity": {"rows": CAP_ROWS, "prompt": CAP_PROMPT,
                         "shared_len": CAP_SHARED_LEN, "n": CAP_N,
                         "shared_fraction": CAP_SHARED_FRAC,
                         "gen": CAP_GEN},
            "hol": {"rows": HOL_ROWS, "chunk": HOL_CHUNK,
                    "aggressor": HOL_AGGRESSOR, "short": HOL_SHORT,
                    "short_n": HOL_SHORT_N, "gen": HOL_GEN},
        },
    }

    # Arm A
    arm_a = {}
    for name, enabled in (("no_sharing", False), ("sharing", True)):
        t0 = time.time()
        arm_a[name] = run_capacity(enabled)
        print(f"fig11/capacity_{name},{(time.time() - t0) * 1e6:.0f},"
              f"peak={arm_a[name]['peak_concurrent']};"
              f"steps={arm_a[name]['steps']};"
              f"hits={arm_a[name]['hits']}")
    gain = (arm_a["sharing"]["peak_concurrent"]
            / max(arm_a["no_sharing"]["peak_concurrent"], 1))
    metrics["capacity"] = arm_a
    metrics["capacity_gain"] = gain
    print(f"fig11/capacity_gain,0,sharing_over_private={gain:.2f};"
          f"bytes_saved={arm_a['sharing']['final_bytes_saved']}")
    assert arm_a["sharing"]["hits"] >= 1, "sharing arm never hit the index"

    # Arm B
    arm_b = {}
    for name, chunk in (("monolithic", 0), ("chunked", HOL_CHUNK)):
        t0 = time.time()
        arm_b[name] = run_hol(chunk)
        print(f"fig11/hol_{name},{(time.time() - t0) * 1e6:.0f},"
              f"p99_ttft_ms={arm_b[name]['p99_ttft_s'] * 1e3:.1f};"
              f"p50_ttft_ms={arm_b[name]['p50_ttft_s'] * 1e3:.1f}")
    ttft_ratio = (arm_b["monolithic"]["p99_ttft_s"]
                  / max(arm_b["chunked"]["p99_ttft_s"], 1e-9))
    metrics["hol"] = arm_b
    metrics["hol_p99_ttft_ratio"] = ttft_ratio
    print(f"fig11/hol_p99_ttft,0,mono_over_chunked={ttft_ratio:.2f}")

    if not SMOKE:
        assert gain >= 2.0, (
            f"sharing must >= 2x effective capacity, got {gain:.2f}x "
            f"(peaks {arm_a['sharing']['peak_concurrent']} vs "
            f"{arm_a['no_sharing']['peak_concurrent']})")
        assert ttft_ratio > 1.0, (
            f"chunked prefill must lower short-cohort p99 TTFT, got "
            f"mono/chunked = {ttft_ratio:.3f}")
    return metrics


if __name__ == "__main__":
    main()
