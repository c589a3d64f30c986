"""Shape arithmetic: HeadKV keep counts, FLOPs, paged-decode bytes, peaks."""
import jax.numpy as jnp
import numpy as np
import pytest

import counts
from repro.compression.base import CompressionConfig
from repro.compression.policies import headkv

COMP = {"policy": "headkv", "budget": 256, "alpha_max": 4.0,
        "headkv_base_ratio": 0.2, "obs_window": 32, "sink": 4, "pool": 7,
        "decode_margin": 64}
# a dense model dict: the FLOP counts go through its family's module
M = {"family": "dense", "n_layers": 40, "d_model": 2048, "n_heads": 32,
     "n_kv_heads": 8, "head_dim": 64, "d_ff": 8192, "vocab_size": 49155}


@pytest.mark.parametrize("T", [2048, 3072, 300])
def test_headkv_keep_matches_the_policy(T):
    imp = counts.importance(4, 8, {"sigma": 1.0, "seed": 3})
    keep = counts.headkv_keep(imp, COMP, T)
    cfg = CompressionConfig(**COMP)
    for layer in range(4):
        scores = jnp.zeros((1, 8, T), jnp.float32)
        _, k = headkv(scores, cfg, layer, 4, head_importance=imp[layer])
        np.testing.assert_array_equal(np.asarray(k)[0], keep[layer])
    assert keep.min() >= min(4 + 32, 256) and keep.max() <= min(1088, T)


def test_capacity_and_ring_cap():
    assert counts.static_capacity(COMP) == 1088
    lens = counts.live_lengths(np.array([[100, 1000]]), 200, 1088)
    assert lens.tolist() == [[300, 1088]]


def test_decode_flops_by_hand():
    per_layer = 2048 * 48 * 64 + 32 * 64 * 2048 + 3 * 2048 * 8192
    weights = 40 * per_layer + 49155 * 2048
    lens = np.full((40, 8), 100)
    attn = 4 * 4 * 64 * 100 * 40 * 8
    assert counts.decode_flops(M, lens) == 2 * weights + attn


def test_prefill_flops_by_hand():
    T = 16
    per_layer = 2048 * 48 * 64 + 32 * 64 * 2048 + 3 * 2048 * 8192
    want = (2 * T * 40 * per_layer + 4 * 32 * 64 * (T * (T + 1) / 2) * 40
            + 2 * 49155 * 2048)
    assert counts.prefill_flops(M, T) == want


def test_paged_bytes_match_the_fig9_model():
    """fig9's native byte model: K+V reads of every owned pair's allocated
    blocks, one-block floor included — ``2 · dtype · Dh · blocks · bs``."""
    rng = np.random.default_rng(0)
    lens = rng.integers(0, 1100, size=(40, 8, 32))
    bs, Dh = 128, 64
    blocks = sum(max(1, -(-int(x) // bs)) for x in lens.ravel())
    fig9 = 2 * 2 * Dh * blocks * bs
    assert counts.paged_decode_bytes(lens, bs, Dh, 2) == fig9


def test_unknown_device_kind_raises():
    assert counts.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
