"""Figure 12 (repo extension): quantized KV storage — bytes vs quality.

PR 9 stores the paged K/V pools in int8 (fp8 where the jax build supports
it) with per-block fp32 scales (DESIGN.md §15).  Decode on the HBM-bound
accelerator path is bytes-limited: per step it streams every retained KV
block once, so shrinking the payload dtype converts directly into decode
bandwidth and — through bytes-aware pool sizing — into batch capacity.
Three measurements, all against the fp32 storage baseline:

1. **Modeled decode HBM bytes per token** — the bytes one (layer, slot,
   row) at capacity ``C`` streams per decode step (payload blocks + scale
   entries + the position columns both arms read), from the same
   ``block_hbm_bytes`` unit the admission path charges.  Gate: int8
   reduction >= 1.7x at every C >= 1024.

2. **Quality proxy (Table 1 machinery)** — build a paged layer with
   Ada-SnapKV realized lengths (`benchmarks.common.realized_lengths`),
   quantize it with the shared fixture helper, and compare the decode
   reference output against fp32 storage with `cosine_similarity` — the
   same metric Table 1 uses for retained-profile agreement.  Gate: int8
   cosine >= 0.98.

3. **Equal-HBM max batch (fig7 extension)** — rerun fig7's analytic sweep
   with the pool sized in *bytes* instead of fp32 blocks: at the byte
   budget a dense fp32 cache spends on BATCH rows, the int8 pool admits
   ~4x the blocks, so the sustainable batch beats the fp32 paged pool's
   at every compression ratio.  Gate: int8 batch > fp32 batch for every
   ratio.

``REPRO_BENCH_SMOKE=1`` shrinks the sweep for CI; the gates still run.

A CPU correctness figure; returns a metrics dict (recorded by
``run.py``).
"""
from __future__ import annotations

import os
import time

import jax.numpy as jnp
import numpy as np

from benchmarks.common import realized_lengths
from benchmarks.fig7_paged_memory import (
    BATCH,
    BLOCK_SIZE,
    HEAD_SKEW,
    N_HEADS,
    N_LAYERS,
    N_SHARDS,
    RATIOS,
    T,
    paged_row_blocks,
)
from repro.api import PlannerConfig, build_plan, profile_from_lengths
from repro.core import cosine_similarity
from repro.kernels.ref import paged_fairkv_decode_ref
from repro.paging.block_pool import blocks_for_tokens
from repro.paging.kvquant import KIND_FP8, KIND_INT8, fp8_supported
from repro.paging.paged_cache import block_hbm_bytes
from repro.paging.testing import make_paged_layer, quantize_paged_layer

SMOKE = os.environ.get("REPRO_BENCH_SMOKE", "") not in ("", "0")

KV_DTYPE = "int8"  # storage dtype this suite measures (run.py metadata)

DH = 128  # modeled head dim; both arms share it, the ratio does not
CAPACITIES = [1024, 2048] if SMOKE else [1024, 2048, 4096, 8192]

# quality fixture: paged layer with Ada-SnapKV ragged lengths
Q_SLOTS = 8
Q_ROWS = 2 if SMOKE else 4
Q_GROUP = 2
Q_DH = 64
Q_CAP = 1024
Q_BUDGET = 64 if SMOKE else 128


def decode_bytes_per_step(capacity: int, dtype, quantized: bool) -> int:
    """HBM bytes one (layer, slot, row) at ``capacity`` retained tokens
    streams per decode step: every payload block once (plus its two fp32
    scale entries when quantized, via the same `block_hbm_bytes` unit the
    admission path charges) and the int32 position column both arms read."""
    blocks = blocks_for_tokens(capacity, BLOCK_SIZE)
    payload = blocks * block_hbm_bytes(BLOCK_SIZE, DH, dtype, quantized)
    positions = blocks * BLOCK_SIZE * 4  # pos_pool, dtype-independent
    return payload + positions


def bytes_sweep() -> dict:
    """Bytes-per-token table and the C >= 1024 int8 reduction gate."""
    rows = []
    for cap in CAPACITIES:
        fp32 = decode_bytes_per_step(cap, jnp.float32, False)
        int8 = decode_bytes_per_step(cap, jnp.int8, True)
        rows.append({
            "capacity": cap,
            "fp32_bytes_per_token": fp32 / cap,
            "int8_bytes_per_token": int8 / cap,
            "reduction": fp32 / int8,
        })
    reductions = [r["reduction"] for r in rows if r["capacity"] >= 1024]
    return {"per_capacity": rows,
            "min_reduction_at_C_ge_1024": min(reductions)}


def quality_fixture():
    """Paged layer + query batch with Ada-SnapKV realized lengths."""
    rng = np.random.default_rng(9)
    lengths = realized_lengths(1, Q_SLOTS, Q_BUDGET, Q_ROWS, T=2048,
                               head_skew=HEAD_SKEW, policy="ada_snapkv",
                               alpha_max=4.0)[0]
    lengths = np.clip(lengths, 1, Q_CAP).astype(np.int32)
    layer = make_paged_layer(rng, Q_SLOTS, Q_ROWS, Q_CAP, BLOCK_SIZE, Q_DH,
                             lengths=lengths)
    q = jnp.asarray(rng.normal(size=(Q_ROWS, Q_SLOTS, Q_GROUP, Q_DH))
                    .astype(np.float32))
    return layer, q


def quality_cosine(layer, q, kind: int) -> float:
    """Cosine (Table 1 metric) of quantized-storage decode vs fp32."""
    k, v, pos, table, lengths = layer
    ref = paged_fairkv_decode_ref(q, k, v, pos, table, lengths, Q_CAP)
    kinds = np.full((Q_SLOTS,), kind, np.int32)
    kc, vc, ks, vs = quantize_paged_layer(k, v, table, kinds)
    out = paged_fairkv_decode_ref(q, kc, vc, pos, table, lengths, Q_CAP,
                                  k_scale=ks, v_scale=vs,
                                  kinds=jnp.asarray(kinds))
    return cosine_similarity(np.asarray(ref), np.asarray(out))


def equal_hbm_batch(ratio: float) -> dict:
    """fig7's analytic max batch with the pool sized in bytes per dtype."""
    budget = max(8, int(round(ratio * T)))
    alpha_max = 4.0
    lengths = realized_lengths(N_LAYERS, N_HEADS, budget, BATCH, T=T,
                               head_skew=HEAD_SKEW, policy="ada_snapkv",
                               alpha_max=alpha_max)
    prof = profile_from_lengths(lengths)
    plan = build_plan(prof, N_SHARDS, PlannerConfig(
        mode="fairkv_dp", extra_copies=4, batch_cap=BATCH))
    S = plan.n_shards * plan.slots_per_shard
    cap_blocks = blocks_for_tokens(int(round(alpha_max * budget)),
                                   BLOCK_SIZE)
    # equal HBM: the bytes a dense fp32 cache spends on BATCH rows
    fp32_block = block_hbm_bytes(BLOCK_SIZE, DH, jnp.float32, False)
    int8_block = block_hbm_bytes(BLOCK_SIZE, DH, jnp.int8, True)
    hbm_bytes = N_LAYERS * S * BATCH * cap_blocks * fp32_block
    mean_row = float(paged_row_blocks(lengths, plan, BLOCK_SIZE).mean())
    fp32_batch = int(hbm_bytes // (mean_row * fp32_block))
    int8_batch = int(hbm_bytes // (mean_row * int8_block))
    return {
        "budget": budget,
        "ratio": budget / T,
        "slot_batch": BATCH,
        "paged_fp32_batch": fp32_batch,
        "paged_int8_batch": int8_batch,
        "int8_gain_vs_slot": int8_batch / BATCH,
        "int8_gain_vs_paged_fp32": int8_batch / max(fp32_batch, 1),
        "mean_row_blocks": mean_row,
    }


def main():
    metrics = {"kv_dtype": KV_DTYPE, "block_size": BLOCK_SIZE,
               "head_dim": DH, "fp8_supported": fp8_supported()}

    # --- 1. modeled decode bytes --------------------------------------------
    t0 = time.time()
    metrics["bytes"] = bytes_sweep()
    red = metrics["bytes"]["min_reduction_at_C_ge_1024"]
    print(f"fig12/bytes,{(time.time() - t0) * 1e6:.0f},"
          f"min_reduction_at_C_ge_1024={red:.2f}")

    # --- 2. quality proxy ---------------------------------------------------
    t0 = time.time()
    layer, q = quality_fixture()
    cos = {"int8": quality_cosine(layer, q, KIND_INT8)}
    if fp8_supported():
        cos["fp8"] = quality_cosine(layer, q, KIND_FP8)
    metrics["cosine"] = cos
    print(f"fig12/quality,{(time.time() - t0) * 1e6:.0f},"
          + ";".join(f"cosine_{k}={v:.4f}" for k, v in cos.items()))

    # --- 3. equal-HBM max batch (fig7 extension) ----------------------------
    metrics["equal_hbm"] = []
    for ratio in RATIOS:
        t0 = time.time()
        r = equal_hbm_batch(ratio)
        metrics["equal_hbm"].append(r)
        print(f"fig12/max_batch/ratio_{r['ratio']:.3f},"
              f"{(time.time() - t0) * 1e6:.0f},"
              f"fp32_batch={r['paged_fp32_batch']};"
              f"int8_batch={r['paged_int8_batch']};"
              f"gain_vs_fp32={r['int8_gain_vs_paged_fp32']:.2f}")
    metrics["min_int8_gain_vs_paged_fp32"] = min(
        r["int8_gain_vs_paged_fp32"] for r in metrics["equal_hbm"])

    # --- gates (ISSUE 9 acceptance; pure math + deterministic compute, so
    # they hold under smoke too) ---------------------------------------------
    metrics["gate_bytes_reduction"] = bool(red >= 1.7)
    metrics["gate_cosine"] = bool(cos["int8"] >= 0.98)
    metrics["gate_equal_hbm"] = all(
        r["paged_int8_batch"] > r["paged_fp32_batch"]
        for r in metrics["equal_hbm"])
    assert metrics["gate_bytes_reduction"], metrics["bytes"]
    assert metrics["gate_cosine"], cos
    assert metrics["gate_equal_hbm"], metrics["equal_hbm"]
    print(f"fig12/gates,0,bytes={red:.2f}>=1.7;"
          f"cosine_int8={cos['int8']:.4f}>=0.98;equal_hbm=ok")
    return metrics


if __name__ == "__main__":
    main()
