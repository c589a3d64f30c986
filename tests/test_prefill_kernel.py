"""Prefill flash attention (`kernels/prefill_flash.py`, splash attention
laid out for GQA): interpret mode against the model's ``dense_attention``
over GQA groups, head dims, prompt lengths that are and are not block
multiples, sliding windows that skip whole blocks, softcaps and batches;
the block and padding choice; the ``ops.prefill_attention`` dispatch (its
jnp path the model's ``attention`` at every length) and the
``prefill_attn_total`` counter; token parity of the kernel with the jnp
path through `Engine.generate` on the local and the 2x4-mesh executors; and
the benchmark's B smoke cell judged correct with the kernel in interpret
mode (subprocesses where the environment must be set before the first jax
import).
"""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as K
from repro.kernels import prefill_flash as PF
from repro.kernels.ref import prefill_attention_ref
from repro.models.layers import attention, dense_attention

SRC = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(K.__file__))))
ROOT = os.path.dirname(SRC)


def _qkv(rng, B, Hkv, T, G, Dh, dtype=jnp.float32):
    """q (B, T, Hkv·G, Dh), k and v (B, T, Hkv, Dh)."""
    q = jnp.asarray(rng.normal(size=(B, T, Hkv * G, Dh)), dtype)
    k = jnp.asarray(rng.normal(size=(B, T, Hkv, Dh)), dtype)
    v = jnp.asarray(rng.normal(size=(B, T, Hkv, Dh)), dtype)
    return q, k, v


def _ref(q, k, v, window=0, cap=0.0):
    """``dense_attention`` at positions ``arange(T)``."""
    B, T = k.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    return dense_attention(q, k, v, pos, pos, window=window, attn_cap=cap)


def _err(a, b):
    return float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())


# ---------------------------------------------------------------------------
# kernel vs dense_attention (interpret mode)
# ---------------------------------------------------------------------------


# (B, Hkv, T, G, Dh, window, attn_cap)
CASES = {
    "g8-d128-block-multiple": (1, 2, 256, 8, 128, 0, 0.0),
    "g4-d64-block-multiple": (1, 2, 256, 4, 64, 0, 0.0),
    "g1-d256": (1, 2, 128, 1, 256, 0, 0.0),
    "t200-g8": (1, 1, 200, 8, 64, 0, 0.0),
    "t3072-g4": (1, 1, 3072, 4, 64, 0, 0.0),
    "t1100-pads-to-two-blocks": (1, 1, 1100, 2, 64, 0, 0.0),
    "window-skips-whole-blocks": (1, 1, 3072, 2, 64, 700, 0.0),
    "window-t200-g1": (1, 1, 200, 1, 128, 64, 0.0),
    "softcap-g8": (1, 1, 128, 8, 128, 0, 30.0),
    "window-and-softcap-d256": (1, 1, 256, 2, 256, 64, 50.0),
    "batch-2": (2, 2, 96, 4, 64, 0, 0.0),
    "g6-not-a-power-of-two": (1, 1, 100, 6, 64, 30, 0.0),
    "default-blocks": (1, 1, 640, 8, 128, 0, 0.0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_prefill_kernel_matches_dense(case):
    B, Hkv, T, G, Dh, window, cap = CASES[case]
    rng = np.random.default_rng(len(case))
    q, k, v = _qkv(rng, B, Hkv, T, G, Dh)
    ref = _ref(q, k, v, window, cap)
    out = PF.prefill_flash(q, k, v, window=window, attn_cap=cap,
                           interpret=True)
    assert out.shape == (B, T, Hkv * G, Dh)
    assert _err(out, ref) < 1e-5, case


def test_prefill_kernel_bf16_operands():
    """bf16 in (the serving dtype), fp32 accumulation, p cast to bf16 for
    p·v: within bf16 rounding of the fp32 reference on the same inputs."""
    rng = np.random.default_rng(3)
    q, k, v = _qkv(rng, 1, 2, 256, 8, 128, jnp.bfloat16)
    out = PF.prefill_flash(q, k, v, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = _ref(q.astype(jnp.float32), k.astype(jnp.float32),
               v.astype(jnp.float32))
    assert _err(out, ref) < 2e-2


def test_prefill_kernel_is_causal():
    """Changing keys and values at positions ≥ p leaves every output before
    p unchanged, bit for bit (masked entries contribute exact zeros)."""
    rng = np.random.default_rng(4)
    q, k, v = _qkv(rng, 1, 1, 192, 4, 64)
    p = 77
    k2 = k.at[:, p:].set(100.0)
    v2 = v.at[:, p:].set(-100.0)
    a = PF.prefill_flash(q, k, v, interpret=True)
    b = PF.prefill_flash(q, k2, v2, interpret=True)
    assert bool((a[:, :p] == b[:, :p]).all())
    assert not bool((a[:, p:] == b[:, p:]).all())


def test_blocks_follow_the_shape():
    assert PF.blocks_for(8192) == (PF.BLOCK, 8192)
    assert PF.blocks_for(3000) == (PF.BLOCK, 3072)
    # short prompts: one block, rounded up to the lanes
    assert PF.blocks_for(40) == (128, 128)
    assert PF.blocks_for(640) == (640, 640)
    # past one block the prompt pads to whole blocks
    assert PF.blocks_for(1100) == (PF.BLOCK, 2 * PF.BLOCK)


# ---------------------------------------------------------------------------
# ops dispatch
# ---------------------------------------------------------------------------


def test_auto_dispatch_is_jnp_on_cpu(monkeypatch):
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert K.prefill_attention_impl() == "jnp"
    rng = np.random.default_rng(5)
    q, k, v = _qkv(rng, 1, 2, 48, 4, 32)
    out = K.prefill_attention(q, k, v, window=16, attn_cap=20.0)
    ref = prefill_attention_ref(q, k, v, 16, 20.0)
    assert bool((out == ref).all())
    assert _err(out, _ref(q, k, v, 16, 20.0)) < 1e-5


@pytest.mark.parametrize("T", [48, 3072])
def test_jnp_path_is_the_models_attention_at_every_length(T):
    """The jnp path is ``layers.attention`` bit for bit, so prompts above
    2048 keys keep its O(T)-memory flash scan."""
    rng = np.random.default_rng(T)
    q, k, v = _qkv(rng, 1, 1, T, 2, 16)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (1, T))
    out = prefill_attention_ref(q, k, v, 100, 0.0)
    want = attention(q, k, v, pos, pos, window=100)
    assert bool((out == want).all())
    assert _err(out, _ref(q, k, v, 100)) < 1e-5


def test_env_forces_the_kernel_in_interpret_mode(monkeypatch):
    rng = np.random.default_rng(6)
    q, k, v = _qkv(rng, 1, 2, 48, 4, 32)
    ref = _ref(q, k, v, 16, 20.0)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert K.prefill_attention_impl() == "pallas"
    out = K.prefill_attention(q, k, v, window=16, attn_cap=20.0)
    assert out.shape == ref.shape
    assert _err(out, ref) < 1e-5
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert K.prefill_attention_impl() == "jnp"


def test_env_is_an_error_on_tpu(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(K, "_on_tpu", lambda: True)
    with pytest.raises(RuntimeError, match="REPRO_PALLAS_INTERPRET"):
        K.prefill_attention_impl()


# ---------------------------------------------------------------------------
# Engine.generate: token parity and the counter (local executor)
# ---------------------------------------------------------------------------


def _counter(eng, impl):
    fam = eng.metrics().get("prefill_attn_total", {"series": []})
    return sum(s["value"] for s in fam["series"]
               if s["labels"].get("impl") == impl)


@pytest.mark.parametrize("arch", ["qwen1.5-110b", "gemma2-9b"])
def test_engine_generate_kernel_token_parity_local(arch, monkeypatch):
    """The kernel (interpret) and the jnp path serve the same tokens, one
    prefill compile per prompt shape, and each engine counts its prefill
    calls under the impl it runs.  Prompts of 40 tokens cross gemma2's
    smoke window (16) on its local layers."""
    from repro.api import Engine, EngineConfig
    prompts = np.random.default_rng(0).integers(0, 256, (2, 40))
    cfg = EngineConfig.smoke(arch)
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    base_eng = Engine.build(cfg)
    base = base_eng.generate(prompts, 4)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    eng = Engine.build(cfg, params=base_eng.params)
    res = eng.generate(prompts, 4)
    assert np.array_equal(base.tokens, res.tokens)
    assert np.array_equal(base.lengths, res.lengths)
    assert eng.executor.prefill_traces == 1
    assert (_counter(base_eng, "jnp"), _counter(base_eng, "pallas")) == (1, 0)
    assert (_counter(eng, "pallas"), _counter(eng, "jnp")) == (1, 0)


# ---------------------------------------------------------------------------
# token parity on the 2x4 mesh executor (subprocess: the fake device count
# must be set before the first jax import)
# ---------------------------------------------------------------------------


MESH = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, __SRC__)
import json
import numpy as np
from repro.api import (CompressionConfig, Engine, EngineConfig, PagingConfig,
                       PlannerConfig, SchedulerConfig)
from repro.launch.mesh import make_host_mesh

B, T, GEN = 4, 40, 3
out = {}
for arch in ("qwen1.5-110b", "gemma2-9b"):
    cfg = EngineConfig.smoke(
        arch, n_shards=4, max_seq_len=T + GEN + 8,
        compression=CompressionConfig(policy="ada_snapkv", budget=16,
                                      alpha_max=2.0, obs_window=8, sink=2,
                                      decode_margin=8),
        planner=PlannerConfig(mode="fairkv_dp", extra_copies=4, batch_cap=B),
        scheduler=SchedulerConfig(max_rows=B, enable_replan=False),
        cache_backend="paged", executor="mesh",
        paging=PagingConfig(block_size=8, decode_impl="jnp"))
    prompts = np.random.default_rng(0).integers(0, 256, (B, T))
    runs = {}
    params = None
    for impl in ("jnp", "pallas"):
        if impl == "pallas":
            os.environ["REPRO_PALLAS_INTERPRET"] = "1"
        eng = Engine.build(cfg, mesh=make_host_mesh(model=4, data=2),
                           params=params)
        params = eng.params
        res = eng.generate(prompts, GEN)
        os.environ.pop("REPRO_PALLAS_INTERPRET", None)
        fam = eng.metrics()["prefill_attn_total"]["series"]
        runs[impl] = {
            "tokens": np.asarray(res.tokens).tolist(),
            "lengths": np.asarray(res.lengths).tolist(),
            "prefill_traces": eng.executor.prefill_traces,
            "counter": {s["labels"]["impl"]: s["value"] for s in fam},
        }
    out[arch] = runs
print(json.dumps(out))
"""


def test_engine_generate_kernel_token_parity_mesh_2x4():
    """On the (data=2, model=4) mesh executor the kernel (interpret) and
    the jnp path serve the same tokens and lengths for both smoke models,
    one prefill compile each, counted under the impl that ran."""
    code = MESH.replace("__SRC__", repr(SRC))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    results = json.loads(out.stdout.strip().splitlines()[-1])
    for arch, runs in results.items():
        assert runs["pallas"]["tokens"] == runs["jnp"]["tokens"], arch
        assert runs["pallas"]["lengths"] == runs["jnp"]["lengths"], arch
        for impl, rec in runs.items():
            assert rec["prefill_traces"] == 1, (arch, impl)
            assert rec["counter"] == {impl: 1.0}, (arch, impl)


# ---------------------------------------------------------------------------
# the benchmark's B smoke cell, kernel in interpret mode
# ---------------------------------------------------------------------------


BENCH_B = r"""
import sys
sys.path[:0] = [__TESTS__, __BENCH__, __SRC__]
import json
import run
from smoke_cell import smoke_limits, smoke_of

W = "qwen1.5-110b.prefill-mix"
engines = []
res = run.run(W, 2 ** 31 + 99, 2.0, False, require_chip=False,
              limits=smoke_limits(W), cell=smoke_of(W),
              fault=engines.append)
m = engines[0].metrics()
calls = sum(s["count"] for s in m["stepfn_wall_s"]["series"]
            if s["labels"]["kind"] == "prefill")
counter = {s["labels"]["impl"]: s["value"]
           for s in m["prefill_attn_total"]["series"]}
print(json.dumps({"correct": res["correct"], "checks": res["checks"],
                  "prefill_calls": calls, "counter": counter}))
"""


def test_bench_b_smoke_cell_is_correct_with_the_kernel():
    """B's smoke cell (bf16, the harness of `bench/run.py` with the chip
    check off) is judged correct with the prefill kernel in interpret
    mode, and every prefill call is counted under ``impl="pallas"``."""
    bench = os.path.join(ROOT, "bench")
    code = (BENCH_B.replace("__TESTS__", repr(os.path.join(bench, "tests")))
            .replace("__BENCH__", repr(bench)).replace("__SRC__", repr(SRC)))
    env = dict(os.environ, REPRO_PALLAS_INTERPRET="1", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["prefill_calls"] > 0
    assert res["counter"] == {"pallas": res["prefill_calls"]}
