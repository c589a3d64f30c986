"""Cell builders: (arch × shape × mesh) → jit-able step + ShapeDtypeStruct args.

Every assigned cell lowers one of three steps (serving steps via the
``repro.api`` facade's low-level passthroughs):
- train_4k     → ``train_step``       (params, opt_state, batch)
- prefill_32k  → ``api.prefill``      (serve_params, batch, plan, ccfg)
- decode_32k / long_500k → ``api.decode_step`` (serve_params, state, plan,
  ccfg)

All array arguments are ShapeDtypeStructs (no allocation); plan arrays are
tiny and concrete (the planner is real).  Compression settings per cell are
the paper's operating point (Ada-SnapKV, budget 1024) except long_500k,
which exercises the uncompressed long-context path where the arch allows it
(DESIGN.md §4).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import api
from repro.cache.slot_cache import PlanArrays
from repro.compression.base import CompressionConfig
from repro.configs.base import InputShape, ModelConfig
from repro.core.placement import HeadPlacement
from repro.core.planner import PlannerConfig
from repro.core.profiles import synthetic_profile
from repro.distributed.param_specs import guarded, tree_shardings
from repro.distributed.sharding import ShardingRules, serve_rules, train_rules, use_rules
from repro.models import transformer as M
from repro.paging.block_pool import PagingConfig
from repro.paging.paged_cache import PagedCache, max_blocks_per_row
from repro.training.optimizer import AdamWState, OptimizerConfig
from repro.training.train_loop import train_step

BF16 = jnp.bfloat16


# ---------------------------------------------------------------------------
# Compression operating point per cell
# ---------------------------------------------------------------------------


def cell_ccfg(cfg: ModelConfig, shape: InputShape) -> CompressionConfig:
    if shape.name == "long_500k":
        if cfg.sliding_window > 0 and not cfg.local_global_alternate:
            # pure sliding-window attention (hymba): cache holds one window
            return CompressionConfig(policy="none", budget=cfg.sliding_window,
                                     capacity=cfg.sliding_window,
                                     decode_margin=64)
        # gemma2-style: global layers hold the full 500k retained context
        return CompressionConfig(policy="none", budget=shape.seq_len,
                                 capacity=shape.seq_len, decode_margin=64)
    return CompressionConfig(policy="ada_snapkv", budget=1024,
                             alpha_max=1.5, decode_margin=64)


def cell_plan(cfg: ModelConfig, n_model_shards: int,
              planner_mode: str = "fairkv_dp", extra_copies: int = 4,
              seed: int = 0, batch_cap: Optional[int] = None
              ) -> Optional[HeadPlacement]:
    if cfg.attention_free:
        return None
    profile = synthetic_profile(cfg.n_layers, cfg.n_kv_heads, budget=1024,
                                skew=1.0, seed=seed)
    return api.build_plan(profile, n_model_shards,
                          PlannerConfig(mode=planner_mode,
                                        extra_copies=extra_copies,
                                        batch_cap=batch_cap))


# ---------------------------------------------------------------------------
# ShapeDtypeStruct builders
# ---------------------------------------------------------------------------


def _sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def batch_sds(cfg: ModelConfig, shape: InputShape, rules: ShardingRules,
              seq_len: Optional[int] = None) -> Dict[str, Any]:
    B = shape.global_batch
    S = seq_len if seq_len is not None else shape.seq_len
    if cfg.is_vlm:
        S = max(1, S - cfg.num_image_tokens)
    bspec = rules.rules.get("batch")
    out = {"tokens": _sds((B, S), jnp.int32,
                          NamedSharding(rules.mesh, P(guarded(rules, B, "batch"), None)))}
    if cfg.is_vlm:
        out["image_embeds"] = _sds(
            (B, cfg.num_image_tokens, cfg.d_model), BF16,
            NamedSharding(rules.mesh, P(guarded(rules, B, "batch"), None, None)))
    if cfg.is_encoder_decoder:
        out["frames"] = _sds(
            (B, cfg.encoder_seq_len, cfg.d_model), BF16,
            NamedSharding(rules.mesh, P(guarded(rules, B, "batch"), None, None)))
    return out


def params_sds(cfg: ModelConfig, shape: InputShape, dtype=BF16):
    """Abstract param tree via eval_shape (no allocation)."""
    max_seq = max(shape.seq_len + 64, 4096) if cfg.is_encoder_decoder else 4096
    return jax.eval_shape(
        partial(M.init_params, cfg, dtype=dtype, max_seq_len=max_seq),
        jax.random.PRNGKey(0))


def serve_params_sds(cfg: ModelConfig, shape: InputShape,
                     plan: Optional[HeadPlacement], dtype=BF16,
                     quantize: bool = False):
    from repro.serving.quant import quantize_serve_params
    base = params_sds(cfg, shape, dtype)
    if plan is not None and not cfg.attention_free:
        base = jax.eval_shape(partial(api.slotify_params, plan=plan, cfg=cfg), base)
    if quantize:
        base = jax.eval_shape(quantize_serve_params, base)
    return base


def _with_shardings(tree_sds, rules: ShardingRules, mode: str):
    sh = tree_shardings(tree_sds, rules, mode)
    return jax.tree.map(lambda s, d: _sds(s.shape, s.dtype, d), tree_sds, sh)


def opt_sds(p_sds) -> AdamWState:
    f32 = lambda t: jax.tree.map(lambda x: _sds(x.shape, jnp.float32, x.sharding), t)
    return AdamWState(step=_sds((), jnp.int32), master=f32(p_sds),
                      mu=f32(p_sds), nu=f32(p_sds))


def serve_state_sds(cfg: ModelConfig, shape: InputShape,
                    plan: Optional[HeadPlacement], ccfg: CompressionConfig,
                    rules: ShardingRules, dtype=BF16) -> api.ServeState:
    """Decode-time state, with explicit shardings: the paged cache the
    serving decode step runs on, its pool at the worst-case size
    (`init_paged_cache` with ``n_blocks == 0``), partitioned by model
    shard when the slots split evenly."""
    B = shape.global_batch
    L = cfg.n_layers
    cap = ccfg.static_capacity()
    mesh = rules.mesh

    def ns(*logical_per_dim_and_shape):
        shape_, logical = logical_per_dim_and_shape
        return NamedSharding(mesh, P(*(guarded(rules, d, l)
                                       for d, l in zip(shape_, logical))))

    cache = None
    if not cfg.attention_free:
        S_ = plan.n_slots
        Dh = cfg.head_dim
        bs = PagingConfig().block_size
        M_ = max_blocks_per_row(cap, bs)
        n_model = mesh.shape.get("model", 1)
        parts = n_model if S_ % n_model == 0 else 1
        N = parts * ((S_ // parts) * B * M_ + 1)
        pool_shape = (L, N, bs, Dh)
        pool_log = (None, "kv_slot", None, None)
        table_shape = (L, S_, B, M_)
        cache = PagedCache(
            k_pool=_sds(pool_shape, dtype, ns(pool_shape, pool_log)),
            v_pool=_sds(pool_shape, dtype, ns(pool_shape, pool_log)),
            pos_pool=_sds((L, N, bs), jnp.int32,
                          ns((L, N, bs), pool_log[:3])),
            block_table=_sds(table_shape, jnp.int32,
                             ns(table_shape, (None, "kv_slot", "batch", None))),
            lengths=_sds((L, S_, B), jnp.int32,
                         ns((L, S_, B), (None, "kv_slot", "batch"))),
            positions=_sds((B,), jnp.int32, ns((B,), ("batch",))),
        )
    ssm_state = conv_state = None
    if cfg.family in ("ssm", "hybrid"):
        s = cfg.ssm
        st_shape = (L, B, s.num_heads, s.head_dim, s.state_size)
        ssm_state = _sds(st_shape, jnp.float32,
                         ns(st_shape, (None, "batch", "heads", None, None)))
        cv_shape = (L, B, s.conv_width - 1,
                    s.d_inner + 2 * s.n_groups * s.state_size)
        conv_state = _sds(cv_shape, dtype,
                          ns(cv_shape, (None, "batch", None, "ff")))
    cross_k = cross_v = None
    if cfg.is_encoder_decoder:
        ck = (L, B, cfg.encoder_seq_len, cfg.n_kv_heads, cfg.head_dim)
        cross_k = _sds(ck, dtype, ns(ck, (None, "batch", None, "kv_heads", None)))
        cross_v = _sds(ck, dtype, ns(ck, (None, "batch", None, "kv_heads", None)))
    return api.ServeState(
        cache=cache, ssm_state=ssm_state, conv_state=conv_state,
        cross_k=cross_k, cross_v=cross_v,
        last_tokens=_sds((B,), jnp.int32, ns((B,), ("batch",))),
        decode_steps=_sds((), jnp.int32, NamedSharding(mesh, P())),
    )


def plan_arrays_concrete(plan: Optional[HeadPlacement], cfg: ModelConfig,
                         rules: ShardingRules) -> Optional[PlanArrays]:
    if plan is None:
        return None
    pa = PlanArrays.from_plan(plan)
    mesh = rules.mesh
    slot_spec = NamedSharding(
        mesh, P(None, guarded(rules, plan.n_slots, "kv_slot")))
    rep = NamedSharding(mesh, P(None, None))
    return PlanArrays(
        slot_head=jax.device_put(pa.slot_head, slot_spec),
        replica_idx=jax.device_put(pa.replica_idx, slot_spec),
        replica_count=jax.device_put(pa.replica_count, slot_spec),
        first_slot=jax.device_put(pa.first_slot, rep),
    )


# ---------------------------------------------------------------------------
# Cell artifacts: (fn, args, donate) per step kind
# ---------------------------------------------------------------------------


@dataclass
class CellArtifacts:
    fn: Any  # python callable (pre-jit)
    args: Tuple  # SDS / concrete args
    donate_argnums: Tuple[int, ...]
    in_shardings: Any
    kind: str  # train | prefill | decode
    rules: ShardingRules
    meta: Dict[str, Any]


def build_cell(cfg: ModelConfig, shape: InputShape, mesh,
               planner_mode: str = "fairkv_dp", extra_copies: int = 4,
               dtype=BF16, weights_2d: bool = False,
               quantize: Optional[bool] = None) -> CellArtifacts:
    n_model = mesh.shape["model"]
    ccfg = cell_ccfg(cfg, shape)
    if quantize is None:
        # auto: bf16 1D-TP weight residency above ~10 GB/chip -> int8 weights
        # (production practice for >=100B on 16 GiB v5e; see serving/quant.py)
        quantize = cfg.param_count() * 2 / n_model > 10e9
    if shape.kind == "train":
        rules = train_rules(mesh)
        p_sds = _with_shardings(params_sds(cfg, shape, dtype), rules, "train")
        o_sds = opt_sds(p_sds)
        b_sds = batch_sds(cfg, shape, rules)
        ocfg = OptimizerConfig()

        def fn(params, opt_state, batch):
            with use_rules(rules):
                return train_step(params, opt_state, batch, cfg, ocfg,
                                  remat=True)

        return CellArtifacts(fn=fn, args=(p_sds, o_sds, b_sds),
                             donate_argnums=(0, 1),
                             in_shardings=None, kind="train", rules=rules,
                             meta={"ccfg": ccfg})

    plan = cell_plan(cfg, n_model, planner_mode, extra_copies,
                     batch_cap=shape.global_batch)
    long_ctx = shape.name == "long_500k"
    rules = serve_rules(mesh, long_context=long_ctx, weights_2d=weights_2d)
    sp_sds = _with_shardings(
        serve_params_sds(cfg, shape, plan, dtype, quantize=quantize),
        rules, "serve")
    pa = plan_arrays_concrete(plan, cfg, rules) if plan is not None else None

    if shape.kind == "prefill":
        b_sds = batch_sds(cfg, shape, rules)

        def fn(serve_params, batch, plan_arrays):
            with use_rules(rules):
                return api.prefill(serve_params, batch, cfg, plan_arrays, ccfg)

        return CellArtifacts(fn=fn, args=(sp_sds, b_sds, pa),
                             donate_argnums=(),
                             in_shardings=None, kind="prefill", rules=rules,
                             meta={"ccfg": ccfg, "plan": plan,
                                   "weights_2d": weights_2d,
                                   "quantize": quantize})

    # decode
    st_sds = serve_state_sds(cfg, shape, plan, ccfg, rules, dtype)

    def fn(serve_params, state, plan_arrays):
        with use_rules(rules):
            return api.decode_step(serve_params, state, cfg, plan_arrays, ccfg)

    return CellArtifacts(fn=fn, args=(sp_sds, st_sds, pa),
                         donate_argnums=(1,),
                         in_shardings=None, kind="decode", rules=rules,
                         meta={"ccfg": ccfg, "plan": plan,
                               "weights_2d": weights_2d,
                               "quantize": quantize})
