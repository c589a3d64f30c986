"""Native paged decode kernel (`kernels/paged_fairkv_decode.py`): interpret
mode vs the ``ref.paged_fairkv_decode_ref`` oracle over ragged lengths,
null-block tables, partial last blocks, window + softcap, and dtypes; the
``ops.paged_fairkv_decode`` impl dispatch; and gather↔native↔slot three-way
token parity through `Engine.generate` on the local and 2x4-mesh executors
(the mesh case runs in a subprocess so the fake-device count is set before
the first jax import, mirroring tests/test_executor.py).
"""
import json
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as K
from repro.kernels.paged_fairkv_decode import paged_fairkv_decode_pallas
from repro.kernels.ref import paged_fairkv_decode_ref
from repro.paging.kvquant import KIND_FP8, KIND_INT8, fp8_supported
from repro.paging.testing import make_paged_layer, quantize_paged_layer

from tests._hypothesis_compat import given, settings, st


def _compare(rng, S, B, G, Dh, C, bs, window=0, cap=0.0, dtype=jnp.float32,
             lengths=None):
    kp, vp, pp, tbl, lens = make_paged_layer(
        rng, S, B, C, bs, Dh, dtype=np.dtype(dtype), lengths=lengths)
    q = jnp.asarray(rng.normal(size=(B, S, G, Dh)), dtype)
    qpos = jnp.full((B,), C + 7, jnp.int32)
    ref = paged_fairkv_decode_ref(q, kp, vp, pp, tbl, lens, C, cap,
                                  q_pos=qpos, window=window)
    out = paged_fairkv_decode_pallas(q, kp, vp, pp, tbl, lens, C,
                                     attn_cap=cap, q_pos=qpos, window=window,
                                     interpret=True)
    return float(jnp.abs(out.astype(jnp.float32)
                         - ref.astype(jnp.float32)).max())


# ---------------------------------------------------------------------------
# kernel vs oracle (interpret mode)
# ---------------------------------------------------------------------------


@settings(max_examples=10)
@given(S=st.integers(2, 5), B=st.integers(1, 4), G=st.integers(1, 8),
       C=st.integers(6, 200), bs=st.sampled_from([2, 8, 16, 32, 64]),
       seed=st.integers(0, 10))
def test_paged_kernel_ragged_lengths(S, B, G, C, bs, seed):
    """Random ragged lengths (empty rows included), shuffled block ids,
    partial last blocks — the kernel must match the oracle everywhere."""
    rng = np.random.default_rng(seed)
    assert _compare(rng, S, B, G, 32, C, bs) < 1e-5


@pytest.mark.parametrize("S,B,G,Dh,C,bs", [
    (4, 3, 4, 64, 96, 16),    # several blocks, ragged
    (2, 2, 8, 64, 256, 32),   # GQA 8:1
    (3, 2, 1, 128, 200, 64),  # MHA, capacity not a block multiple
    (2, 2, 2, 32, 64, 64),    # single block per row
])
def test_paged_kernel_shapes(S, B, G, Dh, C, bs):
    rng = np.random.default_rng(0)
    assert _compare(rng, S, B, G, Dh, C, bs) < 1e-5


def test_paged_kernel_null_block_tables():
    """Rows with zero length hold all-null tables; their output must be
    exactly 0 (the §2 psum-reassembly contract) even though the null block
    holds garbage."""
    rng = np.random.default_rng(1)
    S, B, G, Dh, C, bs = 3, 2, 4, 32, 96, 16
    lengths = np.zeros((S, B), np.int32)
    kp, vp, pp, tbl, lens = make_paged_layer(rng, S, B, C, bs, Dh,
                                             lengths=lengths)
    assert int(np.asarray(tbl).max()) == 0  # nothing allocated
    q = jnp.asarray(rng.normal(size=(B, S, G, Dh)), jnp.float32)
    out = paged_fairkv_decode_pallas(q, kp, vp, pp, tbl, lens, C,
                                     interpret=True)
    assert float(jnp.abs(out).max()) == 0.0


def test_paged_kernel_mixed_null_rows():
    """Empty and full rows in one grid: the null-row clamp must not leak
    into neighbouring (slot, row) programs."""
    rng = np.random.default_rng(2)
    S, B, C, bs = 2, 3, 64, 16
    lengths = np.array([[0, C, 7], [C - 1, 0, bs]], np.int32)
    assert _compare(rng, S, B, 4, 32, C, bs, lengths=lengths) < 1e-5


def test_paged_kernel_last_block_partial_fill():
    """Lengths straddling a block boundary: the final block's tail past
    ``len`` holds garbage and must be masked."""
    rng = np.random.default_rng(3)
    S, B, C, bs = 3, 2, 96, 16
    lengths = np.array([[1, bs - 1], [bs, bs + 1], [C - 1, C]], np.int32)
    assert _compare(rng, S, B, 4, 32, C, bs, lengths=lengths) < 1e-5


def test_paged_kernel_window():
    rng = np.random.default_rng(4)
    assert _compare(rng, 3, 3, 4, 32, 96, 16, window=40) < 1e-5


def test_paged_kernel_softcap():
    rng = np.random.default_rng(5)
    assert _compare(rng, 2, 2, 8, 64, 128, 16, cap=50.0) < 1e-5


def test_paged_kernel_window_and_softcap():
    rng = np.random.default_rng(6)
    assert _compare(rng, 3, 2, 4, 32, 96, 16, window=30, cap=30.0) < 1e-5


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 0.03)])
def test_paged_kernel_dtypes(dtype, tol):
    rng = np.random.default_rng(7)
    assert _compare(rng, 3, 2, 4, 64, 96, 16, dtype=dtype) < tol


def test_paged_kernel_rejects_short_table():
    rng = np.random.default_rng(8)
    kp, vp, pp, tbl, lens = make_paged_layer(rng, 2, 2, 32, 16, 8)
    q = jnp.zeros((2, 2, 2, 8), jnp.float32)
    with pytest.raises(ValueError, match="capacity"):
        paged_fairkv_decode_pallas(q, kp, vp, pp, tbl, lens, 64,
                                   interpret=True)


# ---------------------------------------------------------------------------
# quantized pools (DESIGN.md §15): kernel vs oracle vs fp32
# ---------------------------------------------------------------------------

# dequantized output vs the fp32 reference on the same values: int8 keeps
# ~2 decimal digits per block, fp8 (e4m3) ~1; attention averaging keeps the
# output error well under one quantization step of the inputs
QUANT_TOL = {KIND_INT8: 0.05, KIND_FP8: 0.2}

needs_fp8 = pytest.mark.skipif(not fp8_supported(),
                               reason="jax lacks float8_e4m3fn")


def _compare_quant(rng, S, B, G, Dh, C, bs, kinds, window=0, cap=0.0,
                   lengths=None):
    """(pallas-vs-ref, gather-vs-ref, quantized-ref-vs-fp32-ref) max errors
    for one random quantized layer; ``kinds`` is the (S,) per-slot grid."""
    kp, vp, pp, tbl, lens = make_paged_layer(rng, S, B, C, bs, Dh,
                                             lengths=lengths)
    kinds = jnp.asarray(np.broadcast_to(kinds, (S,)), jnp.int32)
    kq, vq, ks, vs = quantize_paged_layer(kp, vp, tbl, kinds)
    q = jnp.asarray(rng.normal(size=(B, S, G, Dh)), jnp.float32)
    qpos = jnp.full((B,), C + 7, jnp.int32)
    fp32 = paged_fairkv_decode_ref(q, kp, vp, pp, tbl, lens, C, cap,
                                   q_pos=qpos, window=window)
    quant_kw = dict(k_scale=ks, v_scale=vs, kinds=kinds)
    ref = paged_fairkv_decode_ref(q, kq, vq, pp, tbl, lens, C, cap,
                                  q_pos=qpos, window=window, **quant_kw)
    out = paged_fairkv_decode_pallas(q, kq, vq, pp, tbl, lens, C,
                                     attn_cap=cap, q_pos=qpos, window=window,
                                     interpret=True, **quant_kw)
    gat = K.paged_fairkv_decode(q, kq, vq, pp, tbl, lens, C, attn_cap=cap,
                                q_pos=qpos, window=window, impl="gather",
                                **quant_kw)

    def err(a, b):
        return float(jnp.abs(a - b).max())

    return err(out, ref), err(gat, ref), err(ref, fp32)


@settings(max_examples=8)
@given(S=st.integers(2, 5), B=st.integers(1, 4), G=st.integers(1, 8),
       C=st.integers(6, 200), bs=st.sampled_from([2, 8, 16, 32, 64]),
       kind=st.sampled_from([KIND_INT8, KIND_FP8]), seed=st.integers(0, 10))
def test_paged_kernel_quantized_ragged(S, B, G, C, bs, kind, seed):
    """Quantized kernel parity over the same adversarial space as the fp32
    sweep: ragged lengths, shuffled blocks, null rows, partial last blocks.
    All three impls dequantize identically (tight bound vs the quantized
    oracle) and the codec error vs fp32 stays inside the per-dtype bound."""
    if kind == KIND_FP8 and not fp8_supported():
        return
    rng = np.random.default_rng(seed)
    pallas_err, gather_err, quant_err = _compare_quant(
        rng, S, B, G, 32, C, bs, kind)
    assert pallas_err < 1e-5
    assert gather_err < 1e-5
    assert quant_err < QUANT_TOL[kind]


@pytest.mark.parametrize("kind", [KIND_INT8,
                                  pytest.param(KIND_FP8, marks=needs_fp8)])
def test_paged_kernel_quantized_window_softcap(kind):
    rng = np.random.default_rng(21)
    pallas_err, gather_err, quant_err = _compare_quant(
        rng, 3, 2, 4, 32, 96, 16, kind, window=40, cap=30.0)
    assert pallas_err < 1e-5 and gather_err < 1e-5
    assert quant_err < QUANT_TOL[kind]


@needs_fp8
def test_paged_kernel_quantized_mixed_kinds():
    """int8 and fp8 slots in one grid: the per-slot kind prefetch operand
    must select the right dequant interpretation per program."""
    rng = np.random.default_rng(22)
    kinds = np.arange(4) % 2  # alternating int8 / fp8
    pallas_err, gather_err, quant_err = _compare_quant(
        rng, 4, 3, 4, 32, 96, 16, kinds)
    assert pallas_err < 1e-5 and gather_err < 1e-5
    assert quant_err < QUANT_TOL[KIND_FP8]


def test_paged_kernel_quantized_null_block_tables():
    """All-null quantized rows still output exactly 0 — garbage codes and
    zero scales never leak past the length mask (and fp8 NaN bit patterns
    are flushed, not propagated, in the masked tail)."""
    rng = np.random.default_rng(23)
    S, B, G, Dh, C, bs = 3, 2, 4, 32, 96, 16
    lengths = np.zeros((S, B), np.int32)
    kp, vp, pp, tbl, lens = make_paged_layer(rng, S, B, C, bs, Dh,
                                             lengths=lengths)
    kinds = jnp.ones((S,), jnp.int32) if fp8_supported() \
        else jnp.zeros((S,), jnp.int32)
    kq, vq, ks, vs = quantize_paged_layer(kp, vp, tbl, kinds)
    q = jnp.asarray(rng.normal(size=(B, S, G, Dh)), jnp.float32)
    out = paged_fairkv_decode_pallas(q, kq, vq, pp, tbl, lens, C,
                                     interpret=True, k_scale=ks, v_scale=vs,
                                     kinds=kinds)
    assert float(jnp.abs(out).max()) == 0.0


# ---------------------------------------------------------------------------
# multi-query q (speculative verify, DESIGN.md §16): kernel vs mq oracle
# ---------------------------------------------------------------------------


def _compare_mq(rng, S, B, Q, G, Dh, C, bs, window=0, cap=0.0,
                dtype=jnp.float32, q_lens=None, kinds=None):
    """(pallas-vs-ref, gather-vs-ref) max errors for a 5-D multi-query
    layer.  ``lengths`` count the cache AFTER the speculative appends, so
    they are drawn ≥ Q per (slot, row); ``q_lens`` defaults to a random
    ragged draw in [1, Q]."""
    lengths = rng.integers(Q, C + 1, size=(S, B)).astype(np.int32)
    kp, vp, pp, tbl, lens = make_paged_layer(rng, S, B, C, bs, Dh,
                                             dtype=np.dtype(dtype),
                                             lengths=lengths)
    quant_kw = {}
    if kinds is not None:
        kinds = jnp.asarray(np.broadcast_to(kinds, (S,)), jnp.int32)
        kq, vq, ks, vs = quantize_paged_layer(kp, vp, tbl, kinds)
        kp, vp = kq, vq
        quant_kw = dict(k_scale=ks, v_scale=vs, kinds=kinds)
    if q_lens is None:
        q_lens = rng.integers(1, Q + 1, size=(B,))
    q_lens = jnp.asarray(q_lens, jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, S, Q, G, Dh)), dtype)
    qpos = jnp.full((B,), C + 7, jnp.int32)  # query 0's absolute position
    ref = paged_fairkv_decode_ref(q, kp, vp, pp, tbl, lens, C, cap,
                                  q_pos=qpos, q_lens=q_lens, window=window,
                                  **quant_kw)
    out = paged_fairkv_decode_pallas(q, kp, vp, pp, tbl, lens, C,
                                     attn_cap=cap, q_pos=qpos,
                                     q_lens=q_lens, window=window,
                                     interpret=True, **quant_kw)
    gat = K.paged_fairkv_decode(q, kp, vp, pp, tbl, lens, C, attn_cap=cap,
                                q_pos=qpos, q_lens=q_lens, window=window,
                                impl="gather", **quant_kw)

    def err(a, b):
        return float(jnp.abs(a.astype(jnp.float32)
                             - b.astype(jnp.float32)).max())

    return err(out, ref), err(gat, ref)


@settings(max_examples=10)
@given(S=st.integers(2, 4), B=st.integers(1, 4), Q=st.integers(2, 5),
       G=st.integers(1, 8), C=st.integers(8, 128),
       bs=st.sampled_from([2, 8, 16, 32]), seed=st.integers(0, 10))
def test_paged_kernel_mq_ragged(S, B, Q, G, C, bs, seed):
    """Random speculative windows (ragged ``q_lens``) over ragged cache
    lengths: the in-window causal mask must match the mq oracle in both
    the pallas and gather impls."""
    rng = np.random.default_rng(seed)
    pallas_err, gather_err = _compare_mq(rng, S, B, Q, G, 32, C, bs)
    assert pallas_err < 1e-5
    assert gather_err < 1e-5


def test_paged_kernel_mq_q1_matches_4d():
    """A 5-D call with Q == 1 must be bitwise identical to the 4-D
    single-query path — same kernel, trivial mask."""
    rng = np.random.default_rng(30)
    S, B, G, Dh, C, bs = 3, 2, 4, 32, 96, 16
    kp, vp, pp, tbl, lens = make_paged_layer(rng, S, B, C, bs, Dh)
    q4 = jnp.asarray(rng.normal(size=(B, S, G, Dh)), jnp.float32)
    qpos = jnp.full((B,), C + 7, jnp.int32)
    out4 = paged_fairkv_decode_pallas(q4, kp, vp, pp, tbl, lens, C,
                                      q_pos=qpos, interpret=True)
    out5 = paged_fairkv_decode_pallas(q4[:, :, None], kp, vp, pp, tbl, lens,
                                      C, q_pos=qpos,
                                      q_lens=jnp.ones((B,), jnp.int32),
                                      interpret=True)
    assert out5.shape == (B, S, 1, G, Dh)
    assert bool((out4 == out5[:, :, 0]).all())


def test_paged_kernel_mq_causal_window():
    """Query ``i`` must see exactly ``len - (qn - 1 - i)`` cache entries:
    with all-identical K the causal limit is invisible, so plant a marker
    value in the last cache slots and check each query's exposure via the
    oracle, then kernel parity on the same layer."""
    rng = np.random.default_rng(31)
    S, B, Q, G, Dh, C, bs = 2, 2, 3, 2, 32, 64, 16
    q_lens = np.array([3, 2], np.int32)
    pallas_err, gather_err = _compare_mq(rng, S, B, Q, G, Dh, C, bs,
                                         q_lens=q_lens)
    assert pallas_err < 1e-5 and gather_err < 1e-5


def test_paged_kernel_mq_garbage_lanes_do_not_leak():
    """Lanes at ``qi >= q_lens[b]`` are scratch (the scheduler discards
    them): perturbing their q values must not change any valid lane."""
    rng = np.random.default_rng(32)
    S, B, Q, G, Dh, C, bs = 2, 2, 4, 2, 32, 64, 16
    lengths = rng.integers(Q, C + 1, size=(S, B)).astype(np.int32)
    kp, vp, pp, tbl, lens = make_paged_layer(rng, S, B, C, bs, Dh,
                                             lengths=lengths)
    q_lens = jnp.asarray([2, 3], jnp.int32)
    qpos = jnp.full((B,), C + 7, jnp.int32)
    q = np.asarray(rng.normal(size=(B, S, Q, G, Dh)), np.float32)
    out_a = paged_fairkv_decode_pallas(jnp.asarray(q), kp, vp, pp, tbl,
                                       lens, C, q_pos=qpos, q_lens=q_lens,
                                       interpret=True)
    q2 = q.copy()
    q2[0, :, 2:] = 1e3  # garbage lanes of row 0 (q_lens=2)
    q2[1, :, 3:] = -1e3  # garbage lane of row 1 (q_lens=3)
    out_b = paged_fairkv_decode_pallas(jnp.asarray(q2), kp, vp, pp, tbl,
                                       lens, C, q_pos=qpos, q_lens=q_lens,
                                       interpret=True)
    assert bool((out_a[0, :, :2] == out_b[0, :, :2]).all())
    assert bool((out_a[1, :, :3] == out_b[1, :, :3]).all())


def test_paged_kernel_mq_window_softcap():
    rng = np.random.default_rng(33)
    pallas_err, gather_err = _compare_mq(rng, 2, 2, 3, 4, 32, 96, 16,
                                         window=40, cap=30.0)
    assert pallas_err < 1e-5 and gather_err < 1e-5


@pytest.mark.parametrize("kind", [KIND_INT8,
                                  pytest.param(KIND_FP8, marks=needs_fp8)])
def test_paged_kernel_mq_quantized(kind):
    """Quantized pools through the multi-query path: all impls dequantize
    identically under the speculative causal mask."""
    rng = np.random.default_rng(34)
    pallas_err, gather_err = _compare_mq(rng, 3, 2, 3, 4, 32, 96, 16,
                                         kinds=kind)
    assert pallas_err < 1e-5 and gather_err < 1e-5


# ---------------------------------------------------------------------------
# ops dispatch
# ---------------------------------------------------------------------------


def test_ops_dispatch_impls_agree():
    rng = np.random.default_rng(9)
    kp, vp, pp, tbl, lens = make_paged_layer(rng, 3, 2, 96, 16, 32)
    q = jnp.asarray(rng.normal(size=(2, 3, 4, 32)), jnp.float32)
    qpos = jnp.full((2,), 99, jnp.int32)
    outs = {impl: K.paged_fairkv_decode(q, kp, vp, pp, tbl, lens, 96,
                                        q_pos=qpos, impl=impl)
            for impl in ("jnp", "gather", "pallas")}
    if K._force_interpret():
        # the gather's inner slot kernel is pallas-interpret here (the CI
        # kernels-interpret gate) — reduction order differs from the ref
        assert float(jnp.abs(outs["gather"] - outs["jnp"]).max()) < 1e-5
    else:
        # jnp and gather are the same math in the same order -> exact
        assert bool((outs["jnp"] == outs["gather"]).all())
    assert float(jnp.abs(outs["pallas"] - outs["jnp"]).max()) < 1e-5


def test_ops_dispatch_rejects_unknown_impl():
    q = jnp.zeros((1, 1, 1, 8), jnp.float32)
    with pytest.raises(ValueError, match="bogus"):
        K.paged_fairkv_decode(q, q, q, q[..., 0], q[..., 0, 0], None, 8,
                              impl="bogus")


def test_force_interpret_env_routes_auto_to_pallas(monkeypatch):
    """REPRO_PALLAS_INTERPRET=1 (the CI kernels-interpret gate) must route
    "auto" dispatch onto the Pallas kernels in interpret mode off-TPU."""
    rng = np.random.default_rng(10)
    kp, vp, pp, tbl, lens = make_paged_layer(rng, 2, 2, 64, 16, 32)
    q = jnp.asarray(rng.normal(size=(2, 2, 4, 32)), jnp.float32)
    ref = K.paged_fairkv_decode(q, kp, vp, pp, tbl, lens, 64, impl="jnp")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert K._force_interpret()
    out = K.paged_fairkv_decode(q, kp, vp, pp, tbl, lens, 64, impl="auto")
    assert float(jnp.abs(out - ref).max()) < 1e-5
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert not K._force_interpret()


def test_force_interpret_env_is_an_error_on_tpu(monkeypatch):
    """On a TPU the env knob must not silently swap the compiled kernels
    for interpret mode: "auto" dispatch raises instead."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(K, "_on_tpu", lambda: True)
    with pytest.raises(RuntimeError, match="REPRO_PALLAS_INTERPRET"):
        K._use_pallas("auto")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert K._use_pallas("auto")


def test_paging_config_validates_decode_impl():
    from repro.api import EngineConfig, PagingConfig
    with pytest.raises(ValueError, match="pallas"):
        PagingConfig(decode_impl="cuda")
    cfg = EngineConfig.smoke("minitron-8b",
                             paging=PagingConfig(decode_impl="pallas"))
    assert cfg.paging.decode_impl == "pallas"


# ---------------------------------------------------------------------------
# three-way token parity through Engine.generate (local executor)
# ---------------------------------------------------------------------------


def _engine_cfg(backend, impl="auto", rows=2, T=16, gen=3, kv_dtype="fp32"):
    from repro.api import (CompressionConfig, EngineConfig, PagingConfig,
                           PlannerConfig, SchedulerConfig)
    return EngineConfig.smoke(
        "minitron-8b", n_shards=4, max_seq_len=T + gen + 8,
        compression=CompressionConfig(policy="ada_snapkv", budget=16,
                                      alpha_max=2.0, obs_window=8, sink=2,
                                      decode_margin=8),
        planner=PlannerConfig(mode="fairkv_dp", extra_copies=4,
                              batch_cap=rows),
        scheduler=SchedulerConfig(max_rows=rows, enable_replan=False),
        cache_backend=backend,
        paging=PagingConfig(block_size=8, decode_impl=impl,
                            kv_dtype=kv_dtype))


def test_engine_generate_three_way_token_parity_local():
    """gather, native-pallas (interpret), and jnp paged decode — and the
    slot backend — produce identical tokens through `Engine.generate`."""
    from repro.api import Engine
    B, T, GEN = 2, 16, 3
    prompts = np.random.default_rng(0).integers(0, 256, (B, T))
    slot_eng = Engine.build(_engine_cfg("slot"))
    base = slot_eng.generate(prompts, GEN)
    for impl in ("jnp", "gather", "pallas"):
        eng = Engine.build(_engine_cfg("paged", impl), params=slot_eng.params)
        res = eng.generate(prompts, GEN)
        assert np.array_equal(base.tokens, res.tokens), impl
        assert np.array_equal(base.lengths, res.lengths), impl
        # one decode trace per engine: the impl knob is static config
        assert eng.executor.decode_traces == 1, impl


@pytest.mark.parametrize("kv_dtype", ["int8",
                                      pytest.param("fp8", marks=needs_fp8)])
def test_engine_generate_quantized_impl_agreement(kv_dtype):
    """Quantized end-to-end: all three paged decode impls see the identical
    codes/scales, so their tokens must agree with each other; lengths match
    the fp32 slot baseline; and the kv_dtype knob is static StepFn config —
    exactly one decode trace per engine (compile-once per dtype)."""
    from repro.api import Engine
    B, T, GEN = 2, 16, 3
    prompts = np.random.default_rng(0).integers(0, 256, (B, T))
    slot_eng = Engine.build(_engine_cfg("slot"))
    base = slot_eng.generate(prompts, GEN)
    results = {}
    for impl in ("jnp", "gather", "pallas"):
        eng = Engine.build(_engine_cfg("paged", impl, kv_dtype=kv_dtype),
                           params=slot_eng.params)
        res = eng.generate(prompts, GEN)
        assert np.array_equal(base.lengths, res.lengths), impl
        assert eng.executor.decode_traces == 1, impl
        results[impl] = res.tokens
    assert np.array_equal(results["jnp"], results["gather"])
    assert np.array_equal(results["jnp"], results["pallas"])


# ---------------------------------------------------------------------------
# three-way token parity on the 2x4 mesh executor (subprocess: the fake
# device count must be set before the first jax import)
# ---------------------------------------------------------------------------


SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, __SRC__)
import json
import numpy as np
from repro.api import (CompressionConfig, Engine, EngineConfig, PagingConfig,
                       PlannerConfig, SchedulerConfig)
from repro.launch.mesh import make_host_mesh

B, T, GEN = 4, 16, 3

def cfg_for(backend, impl, executor):
    return EngineConfig.smoke(
        "minitron-8b", n_shards=4, max_seq_len=T + GEN + 8,
        compression=CompressionConfig(policy="ada_snapkv", budget=16,
                                      alpha_max=2.0, obs_window=8, sink=2,
                                      decode_margin=8),
        planner=PlannerConfig(mode="fairkv_dp", extra_copies=4, batch_cap=B),
        scheduler=SchedulerConfig(max_rows=B, enable_replan=False),
        cache_backend=backend, executor=executor,
        paging=PagingConfig(block_size=8, decode_impl=impl))

prompts = np.random.default_rng(0).integers(0, 256, (B, T))
loc = Engine.build(cfg_for("slot", "auto", "local"))
base = loc.generate(prompts, GEN)
out = {}
for impl in ("jnp", "gather", "pallas"):
    mesh = make_host_mesh(model=4, data=2)
    eng = Engine.build(cfg_for("paged", impl, "mesh"), mesh=mesh,
                       params=loc.params)
    res = eng.generate(prompts, GEN)
    out[impl] = {
        "tokens_equal": bool(np.array_equal(base.tokens, res.tokens)),
        "lengths_equal": bool(np.array_equal(base.lengths, res.lengths)),
        "decode_traces": eng.executor.decode_traces,
    }
print(json.dumps(out))
"""


def test_engine_generate_three_way_token_parity_mesh_2x4():
    """All three paged decode impls on the (data=2, model=4) mesh executor
    match the local slot baseline token-for-token, one decode trace each."""
    import repro
    src = list(repro.__path__)[0].rsplit("/repro", 1)[0]
    code = SUBPROC.replace("__SRC__", repr(src))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    results = json.loads(out.stdout.strip().splitlines()[-1])
    for impl, rec in results.items():
        assert rec["tokens_equal"], (impl, rec)
        assert rec["lengths_equal"], (impl, rec)
        assert rec["decode_traces"] == 1, (impl, rec)
