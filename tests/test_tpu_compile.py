"""Ahead-of-time TPU compiles of the serving path's Pallas kernels.

Each test lowers one kernel at granite-3-2b widths (G = 4 query heads per
KV head, head_dim 64, bf16, block size 16) and compiles it for one chip of
a *described* v5e:2x2 topology — the TPU compiler runs here without a
chip.  This catches what interpret mode cannot: block shapes the TPU
lowering refuses, unsupported in-kernel ops, VMEM overruns.  Each asserts
the compiled module holds the kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every test worker
imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.fairkv_decode import fairkv_decode_pallas
from repro.kernels.paged_fairkv_decode import paged_fairkv_decode_pallas
from repro.kernels.snapkv_select import snapkv_scores_pallas

G, DH, BS = 4, 64, 16  # granite-3-2b: 32 query / 8 KV heads, head_dim 64
SLOTS, ROWS, N_BLOCKS, M = 12, 4, 512, 34  # 8 heads + 4 Fair-Copying slots
CAPACITY = M * BS


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _paged_shapes(one_chip, q_shape, pool_dtype):
    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    return (spec(q_shape, jnp.bfloat16),
            spec((N_BLOCKS, BS, DH), pool_dtype),
            spec((N_BLOCKS, BS, DH), pool_dtype),
            spec((N_BLOCKS, BS), jnp.int32),
            spec((SLOTS, ROWS, M), jnp.int32),
            spec((SLOTS, ROWS), jnp.int32),
            spec((ROWS,), jnp.int32))


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


def test_slot_decode_b8_compiles_for_v5e(one_chip):
    B, C = 8, 544

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def fn(q, k, v, lens, k_pos, q_pos):
        return fairkv_decode_pallas(q, k, v, lens, k_pos=k_pos, q_pos=q_pos,
                                    window=256)
    hlo = _compiled_text(
        fn, spec((B, SLOTS, G, DH), jnp.bfloat16),
        spec((SLOTS, B, C, DH), jnp.bfloat16),
        spec((SLOTS, B, C, DH), jnp.bfloat16), spec((SLOTS, B), jnp.int32),
        spec((SLOTS, B, C), jnp.int32), spec((B,), jnp.int32))
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
