"""Serving-engine system tests: the two FairKV runtime invariants
(plan-invariance of logits; decode == train-forward without compression),
compression-policy behaviour, and cache mechanics."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache.slot_cache import PlanArrays, ring_write_index
from repro.compression.base import CompressionConfig
from repro.compression.policies import BALANCED, IMBALANCED, POLICIES, select
from repro.configs import get_smoke_config
from repro.core import PlannerConfig, build_plan, synthetic_profile
from repro.models import forward_train, init_params
from repro.paging.backend import PagedBackend
from repro.serving import decode_step, prefill, slotify_params

FAST_ARCHS = ["minitron-8b", "gemma2-9b", "granite-moe-1b-a400m",
              "hymba-1.5b", "mamba2-1.3b", "whisper-small"]


def _setup(arch, policy="none", budget=64, n_shards=4, T=24, B=2, extra=6):
    cfg = get_smoke_config(arch)
    if cfg.moe.num_experts:
        cfg = cfg.with_overrides(moe=dataclasses.replace(
            cfg.moe, capacity_factor=float(cfg.moe.num_experts)))
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32,
                         max_seq_len=128)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T + extra)),
                         jnp.int32)
    batch = {"tokens": tokens[:, :T]}
    if cfg.is_vlm:
        batch["image_embeds"] = jnp.asarray(
            rng.normal(size=(B, cfg.num_image_tokens, cfg.d_model)) * 0.1,
            jnp.float32)
    if cfg.is_encoder_decoder:
        batch["frames"] = jnp.asarray(
            rng.normal(size=(B, cfg.encoder_seq_len, cfg.d_model)) * 0.1,
            jnp.float32)
    full_T = T + (cfg.num_image_tokens if cfg.is_vlm else 0)
    ccfg = CompressionConfig(policy=policy, budget=budget, alpha_max=1.0,
                             obs_window=8, sink=2, decode_margin=8,
                             capacity=full_T if policy == "none" else 0)
    return cfg, params, batch, tokens, ccfg


def _run(cfg, params, batch, tokens, ccfg, mode, ch, n_shards=4, steps=5):
    T = batch["tokens"].shape[1]
    if cfg.attention_free:
        plan = build_plan(np.ones((cfg.n_layers, 1)), 1,
                          PlannerConfig(mode="sha", slots_per_shard=1))
    else:
        prof = synthetic_profile(cfg.n_layers, cfg.n_kv_heads, budget=64,
                                 skew=1.0, seed=1)
        plan = build_plan(prof, n_shards,
                          PlannerConfig(mode=mode, extra_copies=ch))
    pa = PlanArrays.from_plan(plan)
    sp = slotify_params(params, plan, cfg)
    state, logits0, lens = prefill(sp, batch, cfg, pa, ccfg)
    # the prefill's slot-layout cache is paged into the pool decode reads
    backend = PagedBackend(cfg, ccfg)
    state = backend.from_prefill(state, pa)
    out = [logits0]
    for t in range(steps):
        state = backend.prepare_decode(state, None)
        state, lg = decode_step(sp, state, cfg, pa, ccfg,
                                tokens=tokens[:, T + t])
        out.append(lg)
    return jnp.stack(out, 1), lens


@pytest.mark.parametrize("arch", FAST_ARCHS)
def test_plan_invariance(arch):
    """SHA and FairKV-DP plans must produce identical logits: the plan is a
    layout, not a math change."""
    cfg, params, batch, tokens, ccfg = _setup(arch)
    a, _ = _run(cfg, params, batch, tokens, ccfg, "sha", 0)
    if cfg.attention_free:
        pytest.skip("attention-free: single trivial plan")
    b, _ = _run(cfg, params, batch, tokens, ccfg, "fairkv_dp", 6)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


@pytest.mark.parametrize("arch", FAST_ARCHS)
def test_decode_matches_train_forward(arch):
    """With no compression, serve logits == train logits position-wise."""
    cfg, params, batch, tokens, ccfg = _setup(arch)
    serve, _ = _run(cfg, params, batch, tokens, ccfg, "sha", 0)
    T = batch["tokens"].shape[1]
    full = dict(batch)
    full["tokens"] = tokens[:, :T + 5]
    gold, _ = forward_train(params, full, cfg, remat=False)
    gold = gold[:, T - 1:T + 5]
    rel = float(jnp.abs(serve - gold).max() / jnp.abs(gold).max())
    assert rel < 2e-3, rel


def test_compressed_decode_close_to_uncompressed():
    """Ada-SnapKV at half budget should still approximate the full-cache
    logits (sanity, not a quality benchmark)."""
    cfg, params, batch, tokens, _ = _setup("minitron-8b", T=48)
    ccfg_full = CompressionConfig(policy="none", budget=48, capacity=48,
                                  obs_window=8, sink=2, decode_margin=8)
    ccfg_ada = CompressionConfig(policy="ada_snapkv", budget=24, alpha_max=2.0,
                                 obs_window=8, sink=2, decode_margin=8)
    full, _ = _run(cfg, params, batch, tokens, ccfg_full, "sha", 0)
    ada, lens = _run(cfg, params, batch, tokens, ccfg_ada, "fairkv_dp", 4)
    # imbalanced budgets realized
    assert int(lens.max()) > int(lens.min())
    # sanity only: random-weight attention is diffuse, so fidelity at half
    # budget is far below a trained model's; the quality ordering across
    # policies is measured by benchmarks/table3_quality_proxy.py
    cos = float((full * ada).sum()
                / (jnp.linalg.norm(full) * jnp.linalg.norm(ada)))
    assert np.isfinite(cos) and cos > 0.5, cos


# ---------------------------------------------------------------------------
# compression policies
# ---------------------------------------------------------------------------


def _scores(B=2, H=4, T=64, seed=0):
    rng = np.random.default_rng(seed)
    base = rng.exponential(1.0, size=(B, H, T))
    return jnp.asarray(base, jnp.float32)


@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_policy_shapes_and_bounds(policy):
    cfg = CompressionConfig(policy=policy, budget=16, alpha_max=2.0,
                            obs_window=4, sink=2, decode_margin=4)
    idx, keep = select(policy, _scores(), cfg, layer_idx=1, n_layers=4)
    B, H, T = 2, 4, 64
    cap = cfg.static_capacity()
    assert idx.shape == (B, H, min(cap, T) if cap <= T else cap)
    assert keep.shape == (B, H)
    assert int(keep.max()) <= cap
    assert int(idx.max()) < T and int(idx.min()) >= 0


def test_balanced_policies_uniform_budgets():
    for policy in sorted(BALANCED):
        cfg = CompressionConfig(policy=policy, budget=16, obs_window=4, sink=2)
        _, keep = select(policy, _scores(), cfg, 0, 4)
        per_head = np.asarray(keep)
        assert (per_head == per_head[0, 0]).all(), policy


def test_imbalanced_policies_nonuniform_budgets():
    scores = _scores(seed=3)
    # concentrate mass on head 0 to force imbalance
    scores = scores.at[:, 0].mul(8.0)
    for policy in sorted(IMBALANCED):
        cfg = CompressionConfig(policy=policy, budget=16, alpha_max=2.0,
                                obs_window=4, sink=2)
        _, keep = select(policy, scores, cfg, 0, 4)
        per_head = np.asarray(keep)
        assert per_head.std() > 0, policy
        # head 0 gets more than the mean (it is the heavy head)
        assert per_head[:, 0].mean() > per_head.mean()


def test_pyramid_budgets_decay_with_depth():
    cfg = CompressionConfig(policy="pyramidkv", budget=32, obs_window=4, sink=2)
    keeps = []
    for layer in range(4):
        _, keep = select("pyramidkv", _scores(), cfg, layer, 4)
        keeps.append(int(np.asarray(keep)[0, 0]))
    assert keeps[0] > keeps[-1], keeps


def test_ada_snapkv_conserves_pool():
    """Ada-KV redistributes the layer pool: Σ budgets ≈ H·budget."""
    cfg = CompressionConfig(policy="ada_snapkv", budget=16, alpha_max=4.0,
                            obs_window=2, sink=1, decode_margin=0)
    scores = _scores(B=1, H=4, T=256, seed=2)
    _, keep = select("ada_snapkv", scores, cfg, 0, 1)
    total = int(np.asarray(keep).sum())
    assert abs(total - 4 * 16) <= 16, total  # ties/floors allow slack


# ---------------------------------------------------------------------------
# slot cache mechanics
# ---------------------------------------------------------------------------


def test_ring_write_index_cycles_in_tail():
    lengths = jnp.asarray([[10]], jnp.int32)
    cap, ring = 10, 4
    idxs = [int(ring_write_index(lengths, jnp.int32(t), cap, ring)[0, 0])
            for t in range(8)]
    assert all(cap - ring <= i < cap for i in idxs)
    assert len(set(idxs)) == ring  # visits the whole ring


def test_append_token_ownership():
    """A decode append grows exactly the (slot, row) pairs the owner rule
    gives it; the others stay empty (their writes hit the null block)."""
    from repro.paging.block_pool import PagingConfig
    from repro.paging.paged_cache import (build_table, init_paged_cache,
                                          paged_append_token)
    cache, pool = init_paged_cache(1, 4, 4, 8, 4, PagingConfig(block_size=4),
                                   dtype=jnp.float32)
    prof = np.ones((1, 2))
    plan = build_plan(prof, 4, PlannerConfig(mode="sha", slots_per_shard=1))
    pa = PlanArrays.from_plan(plan)
    own = pa.owner_mask(0, 4)
    table = build_table(np.zeros((1, 4, 4), np.int64), pool, 4, 2,
                        own=np.asarray(own)[None])
    cache = dataclasses.replace(cache, block_table=jnp.asarray(table))
    k_new = jnp.ones((4, 4, 4))
    cache = paged_append_token(cache, 0, k_new, k_new, own, jnp.int32(0), 8,
                               ring=2)
    lens = np.asarray(cache.lengths[0])
    assert (lens == np.asarray(own).astype(np.int32)).all()
