"""Executor layer (DESIGN.md §10): registry/config wiring, StepFn no-retrace
guarantees, per-shard admission, partitioned block pool, and local↔mesh
parity on a multi-device host mesh.

The parity tests run in a subprocess (the fake-device count must be set
before the first jax import, like tests/test_distributed.py): one process
drives `Engine.generate` through the ``local`` and ``mesh`` executors on
identical weights/plans — imbalanced profiles WITH replicas, both cache
backends, 2- and 8-device meshes — and asserts identical tokens and cache
lengths, plus a replan that must not recompile the decode StepFn.
"""
import json
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import (
    CompressionConfig,
    Engine,
    EngineConfig,
    ExecutorConfig,
    PlannerConfig,
    SchedulerConfig,
    list_executors,
    make_executor,
    synthesize_requests,
)

ARCH = "minitron-8b"


def _ecfg(**kw):
    base = dict(
        n_shards=4, max_seq_len=48,
        compression=CompressionConfig(policy="ada_snapkv", budget=16,
                                      alpha_max=2.0, obs_window=8, sink=2,
                                      decode_margin=8),
        planner=PlannerConfig(mode="fairkv_dp", extra_copies=4, batch_cap=2))
    base.update(kw)
    return EngineConfig.smoke(ARCH, **base)


# ---------------------------------------------------------------------------
# registry / config / mesh plumbing
# ---------------------------------------------------------------------------


def test_builtin_executors_registered():
    assert set(list_executors()) >= {"local", "mesh"}


def test_config_rejects_unknown_executor():
    with pytest.raises(ValueError, match="local"):
        _ecfg(executor="bogus")


def test_executor_config_rejects_same_axes():
    with pytest.raises(ValueError, match="differ"):
        ExecutorConfig(data_axis="x", model_axis="x")


def test_engine_rejects_mesh_with_local_executor():
    """Regression: Engine(..., mesh=) used to store the mesh as 'reserved'
    and silently ignore it; it must now be either used (executor='mesh')
    or rejected."""
    cfg = _ecfg()  # executor defaults to "local"
    with pytest.raises(ValueError, match="executor='mesh'"):
        Engine.build(cfg, mesh=object())


def test_local_executor_rejects_mesh():
    cfg = _ecfg()
    with pytest.raises(ValueError, match="mesh"):
        make_executor("local", cfg.model, cfg.compression, mesh=object())


def test_mesh_executor_requires_mesh():
    cfg = _ecfg(executor="mesh")
    with pytest.raises(ValueError, match="make_host_mesh"):
        Engine.build(cfg)


def test_mesh_executor_rejects_moe():
    """MoE's capacity-bounded dispatch sizes expert capacity from the
    global token count — data-sharded replication changes drop behavior
    (verified non-equivalent), so the mesh executor must refuse it."""
    from repro.launch.mesh import make_host_mesh
    cfg = EngineConfig.smoke("qwen3-moe-30b-a3b", executor="mesh")
    with pytest.raises(NotImplementedError, match="expert parallelism"):
        Engine.build(cfg, mesh=make_host_mesh(model=1, data=1))


def test_make_host_mesh_oversubscription_raises():
    """Regression: was a bare assert (vanishes under python -O)."""
    from repro.launch.mesh import make_host_mesh
    n = len(jax.devices())
    with pytest.raises(ValueError, match=f"only {n} available"):
        make_host_mesh(model=n + 1, data=2)


# ---------------------------------------------------------------------------
# StepFn no-retrace (local executor; the mesh variant runs in the subprocess)
# ---------------------------------------------------------------------------


def test_decode_compiles_once_across_requests_and_replan():
    """The decode StepFn must compile exactly once per (shape, backend):
    weights and plan arrays are arguments, so admissions and replans swap
    values through the same executable.  The aggressive trigger settings
    (the serve_continuous example's) make the trace fire a live replan —
    slot weights and plan arrays actually swap mid-flight."""
    cfg = _ecfg(scheduler=SchedulerConfig(max_rows=4, replan_window=4,
                                          replan_threshold=1.05,
                                          replan_cooldown=10),
                planner=PlannerConfig(mode="fairkv_dp", extra_copies=4,
                                      batch_cap=4),
                max_seq_len=64)
    eng = Engine.build(cfg)
    reqs = synthesize_requests(8, 0.4, cfg.model.vocab_size, min_prompt=12,
                               max_prompt=28, max_new_tokens=10, seed=3)
    out = eng.run_trace(reqs, max_steps=500)
    assert out["finished"] == 8
    assert any(ev["accepted"] for ev in out["replan_log"]), out["replan_log"]
    assert eng.executor.decode_traces == 1


def test_oneshot_replan_does_not_retrace():
    cfg = _ecfg(max_seq_len=40)
    eng = Engine.build(cfg)
    prompts = np.random.default_rng(0).integers(0, cfg.model.vocab_size,
                                                (2, 16))
    eng.generate(prompts, 3)
    assert eng.executor.decode_traces == 1
    prof = np.asarray(eng.profile)[:, ::-1].copy()
    eng.replan(profile=prof)
    eng.generate(prompts, 3)
    assert eng.executor.decode_traces == 1


def test_local_step_hlo_audit():
    """prefill_hlo / decode_hlo hand back the compiled modules the engine's
    own arguments produce (the chip smoke scans them for its kernels)."""
    cfg = _ecfg(max_seq_len=40)
    eng = Engine.build(cfg)
    prompts = {"tokens": np.random.default_rng(0).integers(
        0, cfg.model.vocab_size, (2, 16)).astype(np.int32)}
    eng.generate(prompts["tokens"], 2)
    ex = eng.executor
    assert "HloModule" in ex.prefill_hlo(eng.sp, prompts, eng.pa)
    assert "HloModule" in ex.decode_hlo(eng.sp, eng.state, eng.pa,
                                        eng.state.last_tokens)


# ---------------------------------------------------------------------------
# per-model-shard admission (slot backend)
# ---------------------------------------------------------------------------


def test_per_shard_budget_gates_admission():
    """A per-shard budget must gate on the bottleneck shard: a request that
    fits the global sum but overloads one shard is not admissible."""
    from repro.serving.cache_backend import make_cache_backend
    from repro.serving.request import Request

    cfg = _ecfg()
    eng = Engine.build(cfg)  # supplies a live plan geometry (4 shards)
    backend = make_cache_backend(
        "slot", cfg.model, cfg.compression, n_shards=cfg.n_shards,
        max_live_tokens_per_shard=10_000)
    state = backend.init_state(eng.plan_arrays, 2, jnp.float32)
    req = Request(req_id=0, prompt=np.zeros(16, np.int32), arrival_step=0,
                  max_new_tokens=4)
    cost = backend.per_shard_cost(req)
    assert cost.shape == (cfg.n_shards,)
    assert cost.sum() > 0
    assert backend.admissible(state, req)
    # shrink the per-shard budget below the hottest shard's projected cost
    backend.max_live_tokens_per_shard = int(cost.max()) - 1
    assert not backend.admissible(state, req)
    assert "per-shard" in backend.never_fits(req)


def test_scheduler_rejects_request_never_fitting_per_shard():
    cfg = _ecfg(scheduler=SchedulerConfig(max_rows=2, enable_replan=False,
                                          max_live_tokens_per_shard=8),
                max_seq_len=40)
    eng = Engine.build(cfg)
    with pytest.raises(ValueError, match="never be admitted"):
        eng.submit(np.zeros(16, np.int32), max_new_tokens=4)


# ---------------------------------------------------------------------------
# partitioned block pool (mesh paged layout)
# ---------------------------------------------------------------------------


def test_block_pool_partitions():
    from repro.paging.block_pool import BlockPool, PoolExhausted

    pool = BlockPool(n_layers=2, n_blocks=12, n_partitions=3)
    assert pool.part_size == 4
    assert pool.usable_blocks == 12 - 3  # one null block per partition
    ids0 = pool.alloc(0, 2, partition=0)
    ids2 = pool.alloc(0, 3, partition=2)
    assert all(0 < b < 4 for b in ids0)  # partition 0: global ids 1..3
    assert all(8 < b < 12 for b in ids2)  # partition 2: global ids 9..11
    with pytest.raises(PoolExhausted, match="partition 1"):
        pool.alloc(0, 4, partition=1)  # only 3 usable per partition
    pool.decref(0, ids0 + ids2)  # partition inferred from the id
    pool.check_invariants()
    assert pool.free_blocks(0) == 9
    with pytest.raises(ValueError, match="null block"):
        pool.decref(0, [8])  # partition 2's null block


def test_build_table_respects_partitions():
    from repro.paging.block_pool import BlockPool
    from repro.paging.paged_cache import build_table

    L, S, B, bs, M = 1, 4, 4, 4, 2
    pool = BlockPool(L, 4 * (2 * 2 * M + 1), n_partitions=4)  # (2 slot, 2 row)
    lengths = np.full((L, S, B), 5)  # 2 blocks each
    table = build_table(lengths, pool, bs, M, partitions=(2, 2),
                        rows=np.arange(B), n_rows=B)
    part = pool.part_size
    for s in range(S):
        for b in range(B):
            p = (s // 2) * 2 + (b // 2)
            ids = table[0, s, b]
            assert all(p * part < i < (p + 1) * part for i in ids), (s, b, ids)
    pool.check_invariants()


# ---------------------------------------------------------------------------
# local ↔ mesh parity + mesh no-retrace (multi-device subprocess)
# ---------------------------------------------------------------------------


SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, __SRC__)
import json
import numpy as np
import jax, jax.numpy as jnp
from repro.api import (CompressionConfig, Engine, EngineConfig,
                       PlannerConfig, SchedulerConfig, synthesize_requests)
from repro.launch.mesh import make_host_mesh

B, T, GEN = 4, 20, 4

def cfg_for(backend, n_shards, skew, seed, executor="local", rows=4):
    from repro.api import PagingConfig
    return EngineConfig.smoke(
        "minitron-8b", n_shards=n_shards, max_seq_len=T + GEN + 8,
        compression=CompressionConfig(policy="ada_snapkv", budget=16,
                                      alpha_max=2.0, obs_window=8, sink=2,
                                      decode_margin=8),
        planner=PlannerConfig(mode="fairkv_dp", extra_copies=4,
                              batch_cap=rows),
        scheduler=SchedulerConfig(max_rows=rows, enable_replan=False),
        cache_backend=backend, paging=PagingConfig(block_size=8),
        executor=executor, profile_skew=skew, profile_seed=seed)

results = []
CASES = __CASES__
for backend, data, model, n_shards, skew, seed in CASES:
    prompts = np.random.default_rng(seed).integers(0, 256, (B, T))
    loc = Engine.build(cfg_for(backend, n_shards, skew, seed))
    res_l = loc.generate(prompts, GEN)
    mesh = make_host_mesh(model=model, data=data)
    msh = Engine.build(cfg_for(backend, n_shards, skew, seed,
                               executor="mesh"),
                       mesh=mesh, params=loc.params)
    res_m = msh.generate(prompts, GEN)
    has_replicas = any(int(lp.replica_count.max()) > 1
                       for lp in msh.plan.layers)
    rec = {
        "case": [backend, data, model, n_shards, skew, seed],
        "replicas": has_replicas,
        "tokens_equal": bool(np.array_equal(res_l.tokens, res_m.tokens)),
        "lengths_equal": bool(np.array_equal(res_l.lengths, res_m.lengths)),
        "state_lengths_equal": bool(np.array_equal(
            np.asarray(loc.state.cache.lengths),
            np.asarray(msh.state.cache.lengths))),
        "logits_close": bool(np.allclose(res_l.logits, res_m.logits,
                                         rtol=1e-4, atol=1e-4)),
        "decode_traces_after_gen": msh.executor.decode_traces,
    }
    # replan on both (same inputs -> same plan) and decode again: tokens
    # must still agree and the mesh decode StepFn must NOT recompile
    prof = np.asarray(loc.profile)[:, ::-1].copy()
    loc.replan(profile=prof)
    msh.replan(profile=prof)
    res_l2 = loc.generate(prompts, GEN)
    res_m2 = msh.generate(prompts, GEN)
    rec["tokens_equal_after_replan"] = bool(
        np.array_equal(res_l2.tokens, res_m2.tokens))
    rec["decode_traces_after_replan"] = msh.executor.decode_traces
    results.append(rec)

# continuous mode on the mesh: identical trace tokens vs local, one trace
backend = CASES[0][0]
mesh = make_host_mesh(model=4, data=2)
eng_l = Engine.build(cfg_for(backend, 4, 2.0, 1))
eng_m = Engine.build(cfg_for(backend, 4, 2.0, 1, executor="mesh"),
                     mesh=mesh, params=eng_l.params)
for eng in (eng_l, eng_m):
    reqs = synthesize_requests(5, 0.6, 256, min_prompt=10, max_prompt=18,
                               max_new_tokens=4, seed=2)
    out = eng.run_trace(reqs, max_steps=300)
    assert out["finished"] == out["total"], out
toks_l = {r.req_id: r.generated for r in eng_l.finished_requests}
toks_m = {r.req_id: r.generated for r in eng_m.finished_requests}
results.append({"case": ["continuous", backend],
                "tokens_equal": toks_l == toks_m,
                "decode_traces": eng_m.executor.decode_traces})
print(json.dumps(results))
"""


def _run_subproc(cases):
    import repro
    src = list(repro.__path__)[0].rsplit("/repro", 1)[0]
    code = SUBPROC.replace("__SRC__", repr(src)).replace(
        "__CASES__", repr(cases))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("backend", ["slot", "paged"])
def test_mesh_parity_multidevice_subprocess(backend):
    """local and mesh executors produce identical tokens and cache lengths
    on imbalanced plans with replicas — 2-device (1x2) and 8-device (2x4)
    meshes, profile-seed variation on the 8-device case — and the decode
    StepFn compiles exactly once per engine across generate + replan."""
    cases = [(backend, 1, 2, 2, 2.0, 1),
             (backend, 2, 4, 4, 2.0, 1)]
    if backend == "slot":  # property-style variation (kept off the slow arm)
        cases.append((backend, 2, 4, 4, 3.0, 7))
    results = _run_subproc(cases)
    gen = [r for r in results if r["case"][0] == backend]
    cont = [r for r in results if r["case"][0] == "continuous"]
    assert any(r["replicas"] for r in gen), "no case exercised replicas"
    for r in gen:
        assert r["tokens_equal"], r
        assert r["lengths_equal"], r
        assert r["state_lengths_equal"], r
        assert r["logits_close"], r
        assert r["tokens_equal_after_replan"], r
        assert r["decode_traces_after_gen"] == 1, r
        assert r["decode_traces_after_replan"] == 1, r
    for r in cont:
        assert r["tokens_equal"], r
        assert r["decode_traces"] == 1, r
