"""Pallas snapkv scoring kernel: shape/dtype sweeps vs the ref.py pure-jnp
oracle (interpret mode executes the kernel body on CPU).  The paged decode
kernel's sweeps are in tests/test_paged_kernel.py."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ref import snapkv_scores_ref
from repro.kernels.snapkv_select import snapkv_scores_pallas

RNG = np.random.default_rng(0)


def _scores_case(B, W, Hq, Hkv, Dh, T, block_t, cap=0.0, dtype=jnp.float32):
    q = jnp.asarray(RNG.normal(size=(B, W, Hq, Dh)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, T, Hkv, Dh)), dtype)
    kpos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    opos = jnp.broadcast_to(jnp.arange(T - W, T, dtype=jnp.int32), (B, W))
    ref = snapkv_scores_ref(q, k, opos, kpos, cap)
    out = snapkv_scores_pallas(q, k, opos, kpos, attn_cap=cap,
                               block_t=block_t, interpret=True)
    return float(jnp.abs(out - ref).max())


@pytest.mark.parametrize("B,W,Hq,Hkv,Dh,T,block", [
    (2, 8, 8, 2, 64, 256, 128),
    (1, 4, 4, 4, 32, 100, 32),   # MHA, ragged T
    (2, 16, 8, 8, 64, 128, 128),  # single block
])
def test_snapkv_scores_shapes(B, W, Hq, Hkv, Dh, T, block):
    assert _scores_case(B, W, Hq, Hkv, Dh, T, block) < 1e-5


def test_snapkv_scores_softcap():
    assert _scores_case(2, 8, 8, 2, 64, 256, 64, cap=50.0) < 1e-5


def test_snapkv_scores_mass_conservation():
    """Each query distributes prob mass 1 over its causal prefix, so the
    total importance mass equals W·G per (b, h)."""
    B, W, Hq, Hkv, Dh, T = 2, 8, 8, 2, 32, 96
    q = jnp.asarray(RNG.normal(size=(B, W, Hq, Dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, T, Hkv, Dh)), jnp.float32)
    kpos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    opos = jnp.broadcast_to(jnp.arange(T - W, T, dtype=jnp.int32), (B, W))
    out = snapkv_scores_pallas(q, k, opos, kpos, interpret=True)
    mass = np.asarray(out.sum(axis=-1))
    np.testing.assert_allclose(mass, W * (Hq // Hkv), rtol=1e-4)
