"""The fp32 reference against the engine's paged path, at smoke sizes.

The engine serves in float32 here so that the two must agree to rounding:
prefill logits, HeadKV's per-head selection and decode through the paged
cache, including the recency ring (a small decode margin makes heavy heads
reach capacity and wrap)."""
import json

import jax
import numpy as np
import pytest

import ref_digest
import reference
from ref_layers import qkv
from smoke_cell import (CONFIGS, GAP_LIMIT, LOGIT_ATOL, against_reference,
                        agrees, serve_paged, smoke_cell, widest)


def test_ring_deaths_by_hand():
    # one head keeping 3 prompt entries, capacity 5, ring 2, phase d0 = 1
    keep = np.array([[3]])
    dp, dd = reference.ring_deaths(keep, n_dec=5, d0=1, capacity=5, ring=2,
                                   n_pad=8)
    # appends: k0 -> col 3, k1 -> col 4, k2 -> col 3 + (1+2)%2 = 4,
    # k3 -> col 3, k4 -> col 4
    assert dp[0, 0, :3].tolist() == [9, 9, 9]  # prompt never overwritten
    assert dd[0, 0].tolist() == [3, 2, 4, 9, 9, 0, 0, 0]


@pytest.mark.parametrize("config", CONFIGS)
def test_reference_matches_the_paged_engine(config):
    cell = smoke_cell(config, "saturated", dtype="float32", decode_margin=4)
    reqs, served = serve_paged(cell, 1234)
    a = against_reference(cell.model, cell, 1234, reqs, served)
    assert a["logit_diff"] <= LOGIT_ATOL, a
    assert a["gap"] < GAP_LIMIT, a
    assert a["wrapped"], "no head reached capacity: the ring went untested"


def test_a_logit_that_is_not_finite_never_agrees():
    assert widest([0.5, 1e-6]) == 0.5
    assert widest([1e-6, np.nan]) == np.inf
    assert not agrees({"logit_diff": widest([0.0, np.nan]), "gap": 0.0})
    assert not agrees({"logit_diff": 0.0, "gap": widest([np.inf])})


def test_qkv_without_rotation_is_the_projection_at_position_zero():
    """RoPE is the identity at position 0, so ``rotate=False`` must give at
    every position what the rotated projection gives there; at others the
    rotation moves q and k and leaves v."""
    ks = jax.random.split(jax.random.PRNGKey(3), 7)
    D, Hq, Hkv, Dh, n = 32, 4, 2, 8, 6
    lw = {"wq": jax.random.normal(ks[0], (D, Hq, Dh)),
          "wk": jax.random.normal(ks[1], (D, Hkv, Dh)),
          "wv": jax.random.normal(ks[2], (D, Hkv, Dh)),
          "bq": jax.random.normal(ks[3], (Hq, Dh)),
          "bk": jax.random.normal(ks[4], (Hkv, Dh)),
          "bv": jax.random.normal(ks[5], (Hkv, Dh))}
    x = jax.random.normal(ks[6], (n, D))
    m = {"qkv_bias": True, "rope_theta": 10000.0}
    pos = np.arange(n, dtype=np.int32) + 100
    plain = qkv(lw, x, pos, m, False, rotate=False)
    at_zero = qkv(lw, x, np.zeros(n, np.int32), m, False)
    turned = qkv(lw, x, pos, m, False)
    for a, b in zip(plain, at_zero):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(plain[:2], turned[:2]):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() > 0.1
    np.testing.assert_array_equal(np.asarray(plain[2]), np.asarray(turned[2]))


def test_ref_digest_prints_the_same_fingerprints_twice(capsys):
    """The tool that compares two trees runs at smoke size and repeats
    itself: the same seed gives the same JSON."""
    args = ["--config", "qwen1.5-110b", "--seed", str(2 ** 31 + 11),
            "--dtypes", "float32"]
    outs = []
    for _ in range(2):
        assert ref_digest.main(args) == 0
        outs.append(capsys.readouterr().out.strip().splitlines()[-1])
    assert outs[0] == outs[1]
    got = json.loads(outs[0])
    assert got["cell"] == "qwen1.5-110b"
    assert set(got["float32"]) == {"served_weights", "logits_fp32",
                                   "gap_fp32", "logits_fp8", "gap_fp8"}
    assert got["float32"]["logits_fp32"] != got["float32"]["logits_fp8"]
