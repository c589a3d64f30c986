"""Figure 9 (repo extension): paged decode — materialized views vs the
native kernel, as an analytic HBM-bytes model.

A paged decode that gathers each row's blocks into full capacity-sized
``(S, B, C, Dh)`` contiguous views every step (the design the native kernel
replaced) reads the allocated blocks, *writes* ``S·B·C`` columns, and a
dense kernel reads them back — dense-cache-scale HBM traffic at the hottest
point of the stack, no matter how little the compression retained.  The
native kernel (`kernels/paged_fairkv_decode.py`, ``impl="pallas"``)
consumes the pools and block tables directly, so its HBM→VMEM traffic is
proportional to the **allocated blocks** — the realized retained lengths
FairKV balances (DESIGN.md §11).

The numbers are an explicit HBM-bytes model, not a device measurement,
evaluated on *measured* realized lengths (the real Ada-SnapKV selection at
paper-like operating points, placed by the fairkv_dp planner):

- ``native_bytes``  = K+V reads of every owned (layer, slot, row)'s
  allocated blocks (one-block floor included).
- ``gather_bytes``  = the same block reads, plus writing the capacity-sized
  views, plus a dense kernel re-reading them (unowned (slot, row) pairs
  pay full capacity too — a gather cannot skip them).

Modeled decode throughput at a reference HBM bandwidth turns the byte ratio
into tokens/step-time: ``tokens_per_step_gain = gather_bytes /
native_bytes`` (the batch is identical on both sides, so the throughput
gain is exactly the byte ratio).  The acceptance gate is native >= 1.2x at
C >= 1024 under ``REPRO_BENCH_SMOKE=0`` — the full-size conditions are
recorded in the metrics dict (`conditions`) and asserted when not smoke.

Ride-alongs keep the model honest on CPU: an interpret-mode parity check of
the native kernel against ``ref.paged_fairkv_decode_ref`` on a random paged
layer, and a wall-clock sanity timing of the jnp oracle.  A CPU
correctness figure; returns a metrics dict (recorded by ``run.py`` in
``BENCH.json`` by default).
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import realized_lengths
from benchmarks.fig7_paged_memory import paged_row_blocks
from repro.api import PlannerConfig, build_plan, profile_from_lengths
from repro.kernels import ops as K

SMOKE = os.environ.get("REPRO_BENCH_SMOKE", "") not in ("", "0")

# paper-ish operating points (trimmed under smoke); alpha_max * budget is
# the static capacity C, so ratios 0.05 / 0.10 land at C = 1640 / 3277 for
# T = 8192 — the C >= 1024 regime the acceptance gate reads
N_LAYERS = 4 if SMOKE else 8
N_HEADS = 8
N_SHARDS = 4
T = 2048 if SMOKE else 8192
BATCH = 8
BLOCK_SIZE = 16
ALPHA_MAX = 4.0
RATIOS = [0.05] if SMOKE else [0.02, 0.05, 0.10]
HEAD_SKEW = 1.0  # Ada-SnapKV-style imbalanced profile
HBM_GBPS = 819.0  # reference bandwidth (v5e-class), for step-time scaling
DTYPE_BYTES = 2  # bf16 serving dtype


def byte_model(ratio: float) -> dict:
    """materialized-view vs native HBM bytes per decode step at one
    compression ratio, on realized Ada-SnapKV lengths placed by the
    fairkv_dp planner."""
    budget = max(8, int(round(ratio * T)))
    lengths = realized_lengths(N_LAYERS, N_HEADS, budget, BATCH, T=T,
                               head_skew=HEAD_SKEW, policy="ada_snapkv",
                               alpha_max=ALPHA_MAX)
    prof = profile_from_lengths(lengths)
    plan = build_plan(prof, N_SHARDS, PlannerConfig(
        mode="fairkv_dp", extra_copies=4, batch_cap=BATCH))
    S = plan.n_shards * plan.slots_per_shard
    cap = int(round(ALPHA_MAX * budget))
    Dh = 64
    # allocated blocks over all (layer, slot, row) under plan ownership
    alloc_blocks = int(paged_row_blocks(lengths, plan, BLOCK_SIZE).sum())
    alloc_tokens = alloc_blocks * BLOCK_SIZE
    view_tokens = N_LAYERS * S * BATCH * cap  # what the gather materializes
    kv = 2 * DTYPE_BYTES * Dh  # K + V bytes per token column
    native_bytes = kv * alloc_tokens
    gather_bytes = kv * (alloc_tokens + 2 * view_tokens)
    gain = gather_bytes / native_bytes
    step_us = lambda b: b / (HBM_GBPS * 1e9) * 1e6
    return {
        "ratio": budget / T, "budget": budget, "capacity": cap,
        "alloc_tokens": alloc_tokens, "view_tokens": view_tokens,
        "native_bytes": native_bytes, "gather_bytes": gather_bytes,
        "native_step_us": step_us(native_bytes),
        "gather_step_us": step_us(gather_bytes),
        "tokens_per_step_gain": gain,
    }


def interpret_parity() -> float:
    """Native-kernel interpret run vs the jnp oracle on a random paged
    layer (`repro.paging.testing.make_paged_layer`, the construction the
    parity tests gate) — the check the kernels-interpret CI job runs in
    force."""
    from repro.paging.testing import make_paged_layer
    rng = np.random.default_rng(0)
    S, B, G, Dh, C, bs = 4, 2, 4, 64, 128, 16
    kp, vp, pos, table, lens = make_paged_layer(rng, S, B, C, bs, Dh)
    q = jnp.asarray(rng.normal(size=(B, S, G, Dh)), jnp.float32)
    args = (q, kp, vp, pos, table, lens, C)
    ref = K.paged_fairkv_decode(*args, impl="jnp")
    out = K.paged_fairkv_decode(*args, impl="pallas", interpret=True)
    return float(jnp.abs(out - ref).max())


def cpu_wall_us(impl: str, iters: int = 20) -> float:
    """Wall-clock of one jitted paged decode on CPU (sanity telemetry; the
    byte model above is the committed signal — CPU has no HBM hierarchy)."""
    rng = np.random.default_rng(1)
    S, B, G, Dh, C, bs = 8, 4, 4, 64, 256 if SMOKE else 512, 16
    M = -(-C // bs)
    N = S * B * M + 1
    lens = jnp.asarray(rng.integers(1, C + 1, size=(S, B)), jnp.int32)
    table = jnp.asarray(
        1 + np.arange(S * B * M).reshape(S, B, M), jnp.int32)
    kp = jnp.asarray(rng.normal(size=(N, bs, Dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(N, bs, Dh)), jnp.float32)
    pos = jnp.zeros((N, bs), jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, S, G, Dh)), jnp.float32)

    fn = jax.jit(lambda *a: K.paged_fairkv_decode(*a, C, impl=impl))
    args = (q, kp, vp, pos, table, lens)
    fn(*args).block_until_ready()  # compile outside the timed loop
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6


def main():
    metrics = {
        "conditions": {
            "smoke": SMOKE, "n_layers": N_LAYERS, "n_heads": N_HEADS,
            "n_shards": N_SHARDS, "T": T, "batch": BATCH,
            "block_size": BLOCK_SIZE, "alpha_max": ALPHA_MAX,
            "head_skew": HEAD_SKEW, "policy": "ada_snapkv",
            "hbm_gbps": HBM_GBPS, "dtype_bytes": DTYPE_BYTES,
        },
        "model": [],
    }
    for ratio in RATIOS:
        t0 = time.time()
        r = byte_model(ratio)
        metrics["model"].append(r)
        print(f"fig9/model/ratio_{r['ratio']:.3f},"
              f"{(time.time() - t0) * 1e6:.0f},"
              f"C={r['capacity']};gather_MB={r['gather_bytes'] / 1e6:.1f};"
              f"native_MB={r['native_bytes'] / 1e6:.1f};"
              f"tokens_per_step_gain={r['tokens_per_step_gain']:.2f}")
    big = [r for r in metrics["model"] if r["capacity"] >= 1024]
    if big:
        metrics["min_gain_at_C_ge_1024"] = min(
            r["tokens_per_step_gain"] for r in big)
        print(f"fig9/gain_at_C_ge_1024,0,"
              f"min={metrics['min_gain_at_C_ge_1024']:.2f}")
        if not SMOKE:
            assert metrics["min_gain_at_C_ge_1024"] >= 1.2, metrics

    err = interpret_parity()
    metrics["interpret_max_err"] = err
    print(f"fig9/interpret_parity,0,max_err={err:.2e}")
    assert err < 1e-5, err

    wall = {"jnp": cpu_wall_us("jnp")}
    metrics["cpu_wall_us"] = wall
    print(f"fig9/cpu_wall,0," + ";".join(
        f"{k}={v:.0f}us" for k, v in wall.items()))
    return metrics


if __name__ == "__main__":
    main()
