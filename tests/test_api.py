"""`repro.api` facade: registries, EngineConfig validation, Engine parity.

The load-bearing test is end-to-end parity: `Engine.generate` must produce
bit-compatible logits/tokens with the hand-wired
``init → plan → slot weights → prefill → decode loop`` it replaced, so the
facade is a pure re-packaging, not a behavioral fork.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.api import (
    ASSIGNMENT_ENGINE_REGISTRY,
    POLICY_REGISTRY,
    CompressionConfig,
    Engine,
    EngineConfig,
    PlannerConfig,
    SchedulerConfig,
    list_engines,
    list_policies,
    register_assignment_engine,
    register_policy,
    synthesize_requests,
)
from repro.compression.policies import select, snapkv
from repro.core.assignment import assign_items

ARCH = "minitron-8b"


def _ccfg(**kw):
    base = dict(policy="ada_snapkv", budget=16, alpha_max=2.0, obs_window=8,
                sink=2, decode_margin=8)
    base.update(kw)
    return CompressionConfig(**base)


def _ecfg(**kw):
    base = dict(n_shards=4, max_seq_len=48, compression=_ccfg(),
                planner=PlannerConfig(mode="fairkv_dp", extra_copies=4,
                                      batch_cap=2))
    base.update(kw)
    return EngineConfig.smoke(ARCH, **base)


# ---------------------------------------------------------------------------
# registries
# ---------------------------------------------------------------------------


def test_builtin_registrations_present():
    assert set(list_policies()) >= {"streaming_llm", "snapkv", "pyramidkv",
                                    "h2o", "ada_snapkv", "headkv"}
    assert set(list_engines()) >= {"auto", "backtracking", "greedy"}


def test_duplicate_registration_rejected():
    with pytest.raises(ValueError, match="already registered"):
        register_policy("snapkv")(lambda *a, **k: None)
    with pytest.raises(ValueError, match="already registered"):
        register_assignment_engine("auto")(lambda *a, **k: None)


def test_unknown_names_list_registered():
    with pytest.raises(KeyError, match="snapkv"):
        POLICY_REGISTRY["nope"]
    # Mapping .get keeps the standard default-returning contract
    assert POLICY_REGISTRY.get("nope") is None
    assert POLICY_REGISTRY.get("nope", snapkv) is snapkv
    with pytest.raises(KeyError, match="greedy"):
        assign_items([1.0, 2.0], 2, 1, engine="nope")
    with pytest.raises(KeyError, match="ada_snapkv"):
        select("nope", jnp.zeros((1, 2, 8)), _ccfg(), 0, 1)


def test_local_policy_roundtrip():
    """A test-local @register_policy flows through EngineConfig validation
    and compression.policies.select without touching core files."""
    name = "test_local_policy"

    @register_policy(name)
    def _policy(scores, cfg, layer_idx, n_layers, **kw):
        return snapkv(scores, cfg, layer_idx, n_layers)

    try:
        assert name in list_policies()
        cfg = _ecfg(compression=_ccfg(policy=name))  # validates
        assert cfg.compression.policy == name
        scores = jnp.asarray(
            np.random.default_rng(0).random((1, 2, 24)), jnp.float32)
        idx, keep = select(name, scores, cfg.compression, 0, 2)
        ref_idx, ref_keep = select("snapkv", scores, cfg.compression, 0, 2)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_idx))
        np.testing.assert_array_equal(np.asarray(keep), np.asarray(ref_keep))
    finally:
        POLICY_REGISTRY.unregister(name)
    assert name not in list_policies()


def test_local_engine_roundtrip():
    name = "test_local_engine"

    @register_assignment_engine(name)
    def _engine(weights, n_shards, slots_per_shard, **kw):
        # worst possible solver: everything on shard 0 (capacity allowing)
        out = [[] for _ in range(n_shards)]
        for i in range(len(weights)):
            out[i // slots_per_shard].append(i)
        return out

    try:
        cfg = _ecfg(planner=PlannerConfig(engine=name))  # validates
        assert cfg.planner.engine == name
        assert assign_items([3.0, 1.0], 2, 1, engine=name) == [[0], [1]]
    finally:
        ASSIGNMENT_ENGINE_REGISTRY.unregister(name)


def test_backtracking_rejects_item_group():
    """Regression (core/assignment): an explicit engine='backtracking' with
    replica groups used to silently degrade to greedy; it must raise."""
    with pytest.raises(ValueError, match="backtracking"):
        assign_items([3.0, 2.0, 2.0, 1.0], 2, 2, engine="backtracking",
                     item_group=[0, 0, 1, 1])
    # 'auto' still handles replica groups by falling back to greedy
    out = assign_items([3.0, 2.0, 2.0, 1.0], 2, 2, engine="auto",
                       item_group=[0, 0, 1, 1])
    for shard in out:
        groups = [[0, 0, 1, 1][i] for i in shard]
        assert len(groups) == len(set(groups))  # replicas on distinct shards


# ---------------------------------------------------------------------------
# EngineConfig validation
# ---------------------------------------------------------------------------


def test_config_rejects_unknown_policy():
    with pytest.raises(ValueError, match=r"ada_snapkv"):
        _ecfg(compression=_ccfg(policy="bogus"))


def test_config_rejects_unknown_planner_mode():
    with pytest.raises(ValueError, match=r"fairkv_dp"):
        _ecfg(planner=PlannerConfig(mode="bogus"))


def test_config_rejects_unknown_engine():
    with pytest.raises(ValueError, match=r"greedy"):
        _ecfg(planner=PlannerConfig(engine="bogus"))


def test_config_rejects_bad_scalars():
    with pytest.raises(ValueError, match="dtype"):
        _ecfg(dtype="float8")
    with pytest.raises(ValueError, match="n_shards"):
        _ecfg(n_shards=0)
    with pytest.raises(ValueError, match="max_rows"):
        _ecfg(scheduler=SchedulerConfig(max_rows=0))


def test_config_replace_revalidates():
    cfg = _ecfg()
    with pytest.raises(ValueError):
        cfg.replace(compression=_ccfg(policy="bogus"))


# ---------------------------------------------------------------------------
# Engine parity with the hand-wired path
# ---------------------------------------------------------------------------


def test_generate_parity_with_handwired_loop():
    from repro.cache.slot_cache import PlanArrays
    from repro.core import build_plan, synthetic_profile
    from repro.paging.backend import PagedBackend
    from repro.serving import decode_step, prefill, slotify_params

    T, B, GEN = 24, 2, 4
    cfg = _ecfg(max_seq_len=T + GEN + 8)
    eng = Engine.build(cfg)
    prompts = np.random.default_rng(0).integers(
        0, cfg.model.vocab_size, (B, T))
    res = eng.generate(prompts, GEN)

    # hand-wired: same params, same profile inputs -> same plan
    profile = synthetic_profile(cfg.model.n_layers, cfg.model.n_kv_heads,
                                budget=cfg.compression.budget,
                                skew=cfg.profile_skew, seed=cfg.profile_seed)
    plan = build_plan(profile, cfg.n_shards, cfg.planner)
    pa = PlanArrays.from_plan(plan)
    sp = slotify_params(eng.params, plan, cfg.model)
    batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
    state, logits, lengths = prefill(sp, batch, cfg.model, pa,
                                     cfg.compression)
    backend = PagedBackend(cfg.model, cfg.compression, paging=cfg.paging)
    state = backend.from_prefill(state, pa)
    ref_logits = [np.asarray(logits)]
    ref_tokens = [np.asarray(state.last_tokens)]
    for _ in range(GEN):
        state = backend.prepare_decode(state, None)
        state, logits = decode_step(sp, state, cfg.model, pa,
                                    cfg.compression)
        ref_logits.append(np.asarray(logits))
        ref_tokens.append(np.asarray(state.last_tokens))

    np.testing.assert_array_equal(res.tokens, np.stack(ref_tokens, axis=1))
    np.testing.assert_allclose(res.logits, np.stack(ref_logits, axis=1),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(res.lengths, np.asarray(lengths))
    assert res.efficiency == pytest.approx(
        plan.efficiency(np.asarray(lengths, np.float64).mean(axis=2)))


@pytest.mark.parametrize("arch", ["minitron-8b", "qwen1.5-110b", "gemma2-9b",
                                  "granite-3-2b"])
def test_generate_on_paged_cache_matches_train_forward(arch):
    """`Engine.generate` serves each architecture its one-shot path runs
    for through the paged cache: uncompressed, the logits at every served
    position equal the training forward's on the same tokens."""
    from repro.models import forward_train
    from repro.paging.paged_cache import PagedCache
    T, B, GEN = 20, 2, 4
    cfg = EngineConfig.smoke(
        arch, n_shards=2, max_seq_len=T + GEN + 8,
        compression=_ccfg(policy="none", budget=64, alpha_max=1.0,
                          capacity=T),
        planner=PlannerConfig(mode="fairkv_dp", extra_copies=2, batch_cap=B))
    eng = Engine.build(cfg)
    prompts = np.random.default_rng(0).integers(
        0, cfg.model.vocab_size, (B, T))
    res = eng.generate(prompts, GEN)
    assert isinstance(eng.state.cache, PagedCache)
    assert eng.executor.decode_traces == 1
    fed = np.concatenate([prompts, res.tokens[:, :GEN]], axis=1)
    gold, _ = forward_train(eng.params, {"tokens": jnp.asarray(fed)},
                            cfg.model, remat=False)
    gold = np.asarray(gold[:, T - 1:])
    rel = float(np.abs(res.logits - gold).max() / np.abs(gold).max())
    assert rel < 2e-3, rel


def test_generate_teacher_forcing_feeds_given_tokens():
    T, B, GEN = 16, 1, 3
    cfg = _ecfg(max_seq_len=T + GEN + 8)
    eng = Engine.build(cfg)
    rng = np.random.default_rng(1)
    prompts = rng.integers(0, cfg.model.vocab_size, (B, T))
    teacher = rng.integers(0, cfg.model.vocab_size, (B, GEN))
    free = eng.generate(prompts, GEN)
    eng2 = Engine.build(cfg, params=eng.params)
    forced = eng2.generate(prompts, GEN, teacher_tokens=teacher)
    # prefill logits identical; decode logits diverge once fed tokens differ
    np.testing.assert_allclose(free.logits[:, 0], forced.logits[:, 0],
                               rtol=1e-5, atol=1e-5)
    assert not np.allclose(free.logits[:, -1], forced.logits[:, -1])


def test_prefill_pages_and_measure_profile_leaves_the_pool():
    """`Engine.prefill` holds its cache in the paged pool; the profiling
    prefill of `measure_profile` touches neither the held state nor the
    pool's allocator, and a one-shot replan migrates the paged cache."""
    from repro.paging.paged_cache import PagedCache
    T, B = 16, 2
    cfg = _ecfg(max_seq_len=T + 8)
    eng = Engine.build(cfg)
    prompts = np.random.default_rng(0).integers(
        0, cfg.model.vocab_size, (B, T))
    eng.prefill(prompts)
    assert isinstance(eng.state.cache, PagedCache)
    state, pool = eng.state, eng.backend.pool
    in_use = pool.blocks_in_use()
    assert in_use > 0
    prof = eng.measure_profile(prompts[:, ::-1].copy())
    assert prof.shape == (cfg.model.n_layers, cfg.model.n_kv_heads)
    assert eng.state is state and eng.backend.pool is pool
    assert pool.blocks_in_use() == in_use
    lens = np.asarray(state.cache.lengths).sum()
    ev = eng.replan(profile=prof)
    assert ev["migrated_cache"]
    assert isinstance(eng.state.cache, PagedCache)
    assert int(np.asarray(eng.state.cache.lengths).sum()) == lens
    eng.backend.pool.check_invariants()


# ---------------------------------------------------------------------------
# continuous mode through the facade
# ---------------------------------------------------------------------------


def test_stream_yields_every_token_in_order():
    cfg = _ecfg(scheduler=SchedulerConfig(max_rows=2, enable_replan=False),
                max_seq_len=32)
    eng = Engine.build(cfg)
    reqs = synthesize_requests(3, 0.5, cfg.model.vocab_size, min_prompt=8,
                               max_prompt=12, max_new_tokens=4, seed=0)
    events = list(eng.stream(reqs, max_steps=200))
    assert len(eng.finished_requests) == 3
    by_req = {}
    for ev in events:
        by_req.setdefault(ev.req_id, []).append(ev)
    for req in reqs:
        evs = by_req[req.req_id]
        assert [e.index for e in evs] == list(range(req.n_generated))
        assert [e.token for e in evs] == req.generated
        assert evs[-1].finished and not any(e.finished for e in evs[:-1])
    steps = [e.step for e in events]
    assert steps == sorted(steps)  # stream is step-ordered


def test_replan_with_speeds_reaches_live_scheduler():
    """Regression: replan(shard_speeds=...) on a continuous-mode engine must
    flow into the scheduler (live-cache migration + accept/reject), not
    silently rebuild a plan the next step() reverts."""
    cfg = _ecfg(scheduler=SchedulerConfig(max_rows=2, enable_replan=False),
                max_seq_len=32)
    eng = Engine.build(cfg)
    reqs = synthesize_requests(2, 10.0, cfg.model.vocab_size, min_prompt=8,
                               max_prompt=10, max_new_tokens=8, seed=0)
    for r in reqs:
        eng.submit(r)
    eng.step()
    eng.step()
    ev = eng.replan(shard_speeds=[1.0, 1.0, 1.0, 0.5])
    assert "accepted" in ev  # scheduler-path event, not the one-shot dict
    assert eng.plan is eng.scheduler.plan  # engine refs follow the scheduler
    # speeds persist so later trigger-fired replans don't revert mitigation
    np.testing.assert_array_equal(eng.scheduler.shard_speeds,
                                  [1.0, 1.0, 1.0, 0.5])


def test_replan_oneshot_swaps_plan():
    cfg = _ecfg()
    eng = Engine.build(cfg)
    old_plan = eng.plan
    prof = np.asarray(eng.profile) * np.linspace(
        1.0, 3.0, eng.profile.shape[1])[None, :]
    out = eng.replan(profile=prof)
    assert eng.plan is not old_plan
    assert out["migrated_cache"] is False  # no live cache yet


# ---------------------------------------------------------------------------
# EngineConfig.to_dict / from_dict round-trip (DESIGN.md §8)
# ---------------------------------------------------------------------------


def _roundtrip(cfg):
    import json
    data = json.loads(json.dumps(cfg.to_dict()))  # force JSON types
    return EngineConfig.from_dict(data)


def test_config_dict_roundtrip_defaults():
    cfg = _ecfg()
    assert _roundtrip(cfg) == cfg


def test_config_dict_roundtrip_property():
    """Round-trip over the registered option space: every policy,
    executor, and planner mode survives ``to_dict -> json -> from_dict``
    unchanged (tuples, nested sub-configs, and dtype-override dicts
    included)."""
    from tests._hypothesis_compat import given, settings, st
    from repro.api import (PagingConfig, SpeculationConfig, list_engines,
                           list_executors, list_policies)
    from repro.core.planner import PLANNER_MODES

    @settings(max_examples=15)
    @given(policy=st.sampled_from(sorted(list_policies())),
           mode=st.sampled_from(sorted(PLANNER_MODES)),
           engine=st.sampled_from(sorted(list_engines())),
           executor=st.sampled_from(sorted(list_executors())),
           kv=st.sampled_from(["fp32", "int8"]),
           spec=st.booleans(), max_k=st.integers(1, 6))
    def run(policy, mode, engine, executor, kv, spec, max_k):
        overrides = {(0, 1): "int8"} if kv == "int8" else {}
        cfg = _ecfg(
            compression=_ccfg(policy=policy),
            planner=PlannerConfig(mode=mode, engine=engine, extra_copies=4,
                                  batch_cap=2),
            executor=executor,
            paging=PagingConfig(block_size=8, kv_dtype=kv,
                                kv_dtype_overrides=overrides),
            speculation=SpeculationConfig(enabled=spec, max_k=max_k))
        back = _roundtrip(cfg)
        assert back == cfg
        assert back.paging.kv_dtype_overrides == \
            cfg.paging.kv_dtype_overrides

    run()


def test_config_from_dict_rejects_unknown_keys():
    data = _ecfg().to_dict()
    data["speculation"]["maxk"] = 3  # typo'd nested key
    with pytest.raises(ValueError) as ei:
        EngineConfig.from_dict(data)
    msg = str(ei.value)
    assert "maxk" in msg and "engine.speculation" in msg
    assert "max_k" in msg  # valid keys listed for the typo'd level
    data = _ecfg().to_dict()
    data["bogus_top"] = 1
    with pytest.raises(ValueError, match="bogus_top"):
        EngineConfig.from_dict(data)


def test_config_from_dict_revalidates():
    """from_dict goes through the constructors, so semantic validation
    (registry names, cross-field rules) still fires on edited files."""
    data = _ecfg().to_dict()
    data["compression"]["policy"] = "not_a_policy"
    with pytest.raises(ValueError, match="not_a_policy"):
        EngineConfig.from_dict(data)
    data = _ecfg().to_dict()
    data["speculation"]["enabled"] = True  # deeper draft than the model
    data["speculation"]["draft_layers"] = 99
    with pytest.raises(ValueError, match="draft_layers"):
        EngineConfig.from_dict(data)


# ---------------------------------------------------------------------------
# Engine.stats(): the consolidated snapshot vs the legacy accessors
# ---------------------------------------------------------------------------


def test_stats_idle_engine_always_constructible():
    from repro.api import EngineStats
    eng = Engine.build(_ecfg())
    st = eng.stats()
    assert isinstance(st, EngineStats)
    assert st.scheduler.mode == "idle"
    assert st.pool.detail == {} and st.pool.backend is None
    assert st.plan.n_shards == 4  # plan exists from build
    assert not st.speculation.enabled
    assert isinstance(st.to_dict(), dict)
    # legacy accessors keep their historical raising behavior when empty
    with pytest.raises(RuntimeError):
        eng.memory_stats()
    with pytest.raises(RuntimeError):
        eng.imbalance()


def test_stats_continuous_matches_legacy_accessors():
    cfg = _ecfg(scheduler=SchedulerConfig(max_rows=2, enable_replan=False),
                max_seq_len=32)
    eng = Engine.build(cfg)
    reqs = synthesize_requests(3, 0.5, cfg.model.vocab_size, min_prompt=8,
                               max_prompt=12, max_new_tokens=4, seed=0)
    out = eng.run_trace(reqs, max_steps=200)
    assert out["finished"] == 3
    st = eng.stats()
    assert st.scheduler.mode == "continuous"
    assert st.scheduler.finished == 3
    assert st.scheduler.steps == eng.scheduler.step_idx
    assert st.scheduler.imbalance == pytest.approx(eng.imbalance())
    assert st.scheduler.replan_log == eng.replan_log
    assert st.pool.detail == eng.memory_stats()
    assert st.pool.backend == "paged"
    assert st.to_dict()["scheduler"]["finished"] == 3
