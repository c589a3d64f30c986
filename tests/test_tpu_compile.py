"""Ahead-of-time TPU compiles of the serving path's Pallas kernels.

Each decode test lowers one kernel at granite-3-2b widths (G = 4 query
heads per KV head, head_dim 64, bf16, block size 16), each prefill test the
prefill flash kernel at one configuration's widths, and compiles it for one
chip of a *described* v5e:2x2 topology — the TPU compiler runs here without
a chip; the mesh executor's prefill StepFn is compiled for all four.  This catches what interpret mode cannot: block shapes the TPU
lowering refuses, unsupported in-kernel ops, VMEM overruns.  Each asserts
the compiled module holds the kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every test worker
imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.paged_fairkv_decode import paged_fairkv_decode_pallas
from repro.kernels.prefill_flash import prefill_flash
from repro.kernels.snapkv_select import snapkv_scores_pallas

G, DH, BS = 4, 64, 16  # granite-3-2b: 32 query / 8 KV heads, head_dim 64
SLOTS, ROWS, N_BLOCKS, M = 12, 4, 512, 34  # 8 heads + 4 Fair-Copying slots
CAPACITY = M * BS


@pytest.fixture(scope="module")
def v5e_2x2():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read back
    # without a chip; keep the cache off around these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e_2x2.devices[0])


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _paged_shapes(one_chip, q_shape, pool_dtype, rows=ROWS):
    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    return (spec(q_shape, jnp.bfloat16),
            spec((N_BLOCKS, BS, DH), pool_dtype),
            spec((N_BLOCKS, BS, DH), pool_dtype),
            spec((N_BLOCKS, BS), jnp.int32),
            spec((SLOTS, rows, M), jnp.int32),
            spec((SLOTS, rows), jnp.int32),
            spec((rows,), jnp.int32))


def test_paged_decode_bf16_compiles_for_v5e(one_chip):
    def fn(q, k, v, pos, table, lens, q_pos):
        return paged_fairkv_decode_pallas(q, k, v, pos, table, lens, CAPACITY,
                                          q_pos=q_pos, window=256)
    hlo = _compiled_text(fn, *_paged_shapes(
        one_chip, (ROWS, SLOTS, G, DH), jnp.bfloat16))
    assert "tpu_custom_call" in hlo


def test_paged_decode_int8_compiles_for_v5e(one_chip):
    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def fn(q, k, v, pos, table, lens, q_pos, k_scale, v_scale, kinds):
        return paged_fairkv_decode_pallas(q, k, v, pos, table, lens, CAPACITY,
                                          q_pos=q_pos, k_scale=k_scale,
                                          v_scale=v_scale, kinds=kinds)
    hlo = _compiled_text(
        fn, *_paged_shapes(one_chip, (ROWS, SLOTS, G, DH), jnp.int8),
        spec((N_BLOCKS,), jnp.float32), spec((N_BLOCKS,), jnp.float32),
        spec((SLOTS,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_paged_verify_q5_compiles_for_v5e(one_chip):
    def fn(q, k, v, pos, table, lens, q_pos, q_lens):
        return paged_fairkv_decode_pallas(q, k, v, pos, table, lens, CAPACITY,
                                          q_pos=q_pos, q_lens=q_lens)
    shapes = _paged_shapes(one_chip, (ROWS, SLOTS, 5, G, DH), jnp.bfloat16)
    hlo = _compiled_text(fn, *shapes, shapes[-1])
    assert "tpu_custom_call" in hlo


def test_paged_decode_b8_compiles_for_v5e(one_chip):
    """Eight rows under a 256-token sliding window: the kernel's grid and
    window mask at the batch width of a full decode tick."""
    B = 8

    def fn(q, k, v, pos, table, lens, q_pos):
        return paged_fairkv_decode_pallas(q, k, v, pos, table, lens, CAPACITY,
                                          q_pos=q_pos, window=256)
    hlo = _compiled_text(fn, *_paged_shapes(
        one_chip, (B, SLOTS, G, DH), jnp.bfloat16, rows=B))
    assert "tpu_custom_call" in hlo


def test_snapkv_scores_b4_compiles_for_v5e(one_chip):
    B, W, T = 4, 8, 1024

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    hlo = _compiled_text(
        snapkv_scores_pallas, spec((B, W, 8 * G, DH), jnp.bfloat16),
        spec((B, T, 8, DH), jnp.bfloat16), spec((B, W), jnp.int32),
        spec((B, T), jnp.int32))
    assert "tpu_custom_call" in hlo


# (prompt length, KV heads, G, head_dim, window, attn_cap): qwen1.5-110b's
# longest B prompt; gemma2-9b's local layer (window, softcap, head_dim 256);
# hymba-1.5b (G = 5, window) at a length that is no block multiple
PREFILL_SHAPES = {
    "qwen1.5-110b-8192": (8192, 8, 8, 128, 0, 0.0),
    "gemma2-9b-local": (6144, 8, 2, 256, 4096, 50.0),
    "hymba-1.5b-t1000": (1000, 5, 5, 64, 1024, 0.0),
}


@pytest.mark.parametrize("name", sorted(PREFILL_SHAPES))
def test_prefill_flash_compiles_for_v5e(one_chip, name):
    T, hkv, g, dh, window, cap = PREFILL_SHAPES[name]

    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def fn(q, k, v):
        return prefill_flash(q, k, v, window=window, attn_cap=cap)
    hlo = _compiled_text(fn, spec((1, T, hkv * g, dh)), spec((1, T, hkv, dh)),
                         spec((1, T, hkv, dh)))
    assert "tpu_custom_call" in hlo


def test_mesh_prefill_stepfn_compiles_for_v5e(v5e_2x2, monkeypatch):
    """The mesh executor's prefill StepFn on a (data 1, model 4) mesh of the
    topology's four chips: one qwen1.5-110b layer at its published widths
    (64 query / 8 KV heads, head_dim 128, bf16), an 8192-token prompt and
    B's HeadKV settings, so the prefill kernel runs under ``shard_map``
    with every KV head on each shard (prefill gathers the replica-0
    weights)."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.api import EngineConfig
    from repro.cache.slot_cache import PlanArrays, SlotCache
    from repro.compression.base import CompressionConfig
    from repro.core.planner import PlannerConfig, build_plan
    from repro.core.profiles import synthetic_profile
    from repro.exec.base import make_executor
    from repro.kernels import ops as K
    from repro.models import init_params
    from repro.serving import engine as serve

    monkeypatch.setattr(K, "_on_tpu", lambda: True)  # dispatch as on the chip
    T, rows = 8192, 16
    cfg = EngineConfig.for_arch(
        "qwen1.5-110b", dtype="bfloat16", n_shards=4, executor="mesh",
        max_seq_len=T + 64,
        compression=CompressionConfig(policy="headkv", budget=512,
                                      alpha_max=4.0, headkv_base_ratio=0.2,
                                      obs_window=32, sink=4, pool=7,
                                      decode_margin=64),
        planner=PlannerConfig(mode="fairkv_dp", slots_per_shard=3,
                              extra_copies=4, batch_cap=rows))
    model = dataclasses.replace(cfg.model, n_layers=1)
    mesh = Mesh(np.array(v5e_2x2.devices).reshape(1, 4), ("data", "model"))
    ex = make_executor("mesh", model, cfg.compression,
                       exec_cfg=cfg.executor_cfg, mesh=mesh)
    plan = build_plan(synthetic_profile(1, model.n_kv_heads, budget=512,
                                        skew=cfg.profile_skew, seed=0),
                      cfg.n_shards, cfg.planner)
    params = jax.eval_shape(lambda: init_params(
        model, jax.random.PRNGKey(0), dtype=jnp.bfloat16,
        max_seq_len=cfg.max_seq_len))
    sp = jax.eval_shape(lambda p: serve.slotify_params(p, plan, model),
                        params)
    pa = PlanArrays.from_plan(plan)
    d = cfg.executor_cfg.data_axis

    def placed(x, spec):
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=NamedSharding(mesh, spec))

    sp_specs = ex._sp_specs(sp)
    state_specs = serve.ServeState(
        cache=ex._cache_specs(SlotCache(None, None, None, None, None)),
        ssm_state=None, conv_state=None, cross_k=None, cross_v=None,
        last_tokens=P(d), decode_steps=P())
    fn = ex._build_prefill(sp_specs, state_specs, True)
    args = (jax.tree.map(placed, sp, sp_specs),
            {"tokens": placed(jax.ShapeDtypeStruct((1, T), jnp.int32),
                              P(d))},
            jax.tree.map(placed, pa, ex._pa_specs()),
            placed(jax.ShapeDtypeStruct((1,), jnp.int32), P(d)),
            placed(jax.ShapeDtypeStruct((1, model.n_kv_heads), jnp.float32),
                   P()))
    hlo = fn.lower(*args).compile().as_text()
    assert "splash_mqa_fwd" in hlo
