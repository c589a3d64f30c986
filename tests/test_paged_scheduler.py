"""The paged cache through the scheduler/Engine: trace parity of the native
decode kernel (interpret mode) with the jnp oracle, pool-exhaustion
preemption (no corruption), fail-fast on never-fits requests, online replan
migration, and config validation."""
import numpy as np
import pytest

from repro.api import (
    CompressionConfig,
    Engine,
    EngineConfig,
    PagingConfig,
    PlannerConfig,
    SchedulerConfig,
    synthesize_requests,
)
from repro.serving.request import Request

ARCH = "minitron-8b"


def _cfg(impl="jnp", n_blocks=0, rows=2, block_size=8, replan=False,
         cache_backend="paged", **sched_kw):
    scfg = dict(max_rows=rows, enable_replan=replan, collect_logits=True)
    if replan:
        scfg.update(replan_window=2, replan_threshold=1.01, replan_cooldown=2,
                    replan_min_rows=1)
    scfg.update(sched_kw)
    return EngineConfig.smoke(
        ARCH, n_shards=4, max_seq_len=64,
        compression=CompressionConfig(policy="ada_snapkv", budget=12,
                                      alpha_max=2.0, obs_window=8, sink=2,
                                      decode_margin=8),
        planner=PlannerConfig(mode="fairkv_dp", extra_copies=4,
                              batch_cap=rows),
        scheduler=SchedulerConfig(**scfg),
        cache_backend=cache_backend,
        paging=PagingConfig(block_size=block_size, n_blocks=n_blocks,
                            decode_impl=impl))


def _reqs(vocab, n=5, gen=6, seed=0):
    return synthesize_requests(n, 0.5, vocab, min_prompt=12, max_prompt=24,
                               max_new_tokens=gen, seed=seed)


@pytest.fixture(scope="module")
def ref_run():
    """Reference trace through the jnp oracle (+ shared params)."""
    cfg = _cfg("jnp")
    eng = Engine.build(cfg)
    reqs = _reqs(cfg.model.vocab_size)
    out = eng.run_trace(reqs, max_steps=500)
    assert out["finished"] == out["total"]
    return cfg, eng.params, reqs, out


def test_paged_trace_matches_slot_exactly(ref_run):
    """Same trace through the native paged kernel (interpret mode):
    identical tokens per request, and logits within the kernel-vs-oracle
    bound of tests/test_paged_kernel.py (the kernel's online softmax sums
    in another order than the oracle).  Through the oracle, the block
    geometry is storage, not math: half-size blocks give identical logits.
    """
    cfg, params, ref_reqs, _ = ref_run
    halves = _reqs(cfg.model.vocab_size)
    out = Engine.build(_cfg("jnp", block_size=4),
                       params=params).run_trace(halves, max_steps=500)
    assert out["finished"] == out["total"]
    for a, b in zip(ref_reqs, halves):
        assert a.generated == b.generated, a.req_id
        for la, lb in zip(a.logits, b.logits):
            np.testing.assert_array_equal(la, lb)
    eng = Engine.build(_cfg("pallas"), params=params)
    reqs = _reqs(cfg.model.vocab_size)
    out = eng.run_trace(reqs, max_steps=500)
    assert out["finished"] == out["total"]
    for a, b in zip(ref_reqs, reqs):
        assert a.generated == b.generated, a.req_id
        for la, lb in zip(a.logits, b.logits):
            np.testing.assert_allclose(la, lb, rtol=0, atol=1e-5)
    # every block returned to the pool once all requests retired
    backend = eng.scheduler.backend
    assert backend.pool.blocks_in_use() == 0
    backend.pool.check_invariants()
    assert out["memory"]["backend"] == "paged"


def test_pool_exhaustion_preempts_not_corrupts(ref_run):
    """An undersized pool forces decode-growth preemption; the preempted
    request replays deterministically, so final tokens still match an
    ample-pool run and the allocator stays consistent."""
    cfg, params, _, _ = ref_run
    # pool sized so two requests co-run at prefill but their decode growth
    # (lengths -> static capacity, 4 blocks/head at bs=8) cannot both fit:
    # steady state needs 2 req x 2 heads x 4 blocks = 16 > 15 usable.
    paged_cfg = _cfg(n_blocks=16)
    eng = Engine.build(paged_cfg, params=params)
    reqs = [Request(req_id=0, prompt=np.arange(12, dtype=np.int32) % 50,
                    arrival_step=0, max_new_tokens=18),
            Request(req_id=1, prompt=(np.arange(12, dtype=np.int32) + 7) % 50,
                    arrival_step=0, max_new_tokens=18)]
    out = eng.run_trace(reqs, max_steps=500)
    assert out["finished"] == out["total"] == 2
    assert out["preemptions"] >= 1
    assert sum(r.n_preemptions for r in reqs) == out["preemptions"]
    backend = eng.scheduler.backend
    assert backend.pool.blocks_in_use() == 0
    backend.pool.check_invariants()
    # no corruption: replay tokens equal an ample-pool run of the same trace
    eng2 = Engine.build(_cfg(), params=params)
    reqs2 = [Request(req_id=r.req_id, prompt=r.prompt.copy(),
                     arrival_step=r.arrival_step,
                     max_new_tokens=r.max_new_tokens) for r in reqs]
    out2 = eng2.run_trace(reqs2, max_steps=500)
    assert out2["preemptions"] == 0
    for a, b in zip(reqs, reqs2):
        assert a.generated == b.generated, a.req_id


def test_never_fits_fails_fast(ref_run):
    """A request whose worst-case block need exceeds the whole pool is
    rejected at submit (no head-of-line blocking)."""
    cfg, params, _, _ = ref_run
    eng = Engine.build(_cfg(n_blocks=4), params=params)
    with pytest.raises(ValueError, match="never be admitted"):
        eng.submit(np.arange(20, dtype=np.int32) % 50, max_new_tokens=18)


def test_paged_online_replan_matches_slot_tokens(ref_run):
    """Online replanning (the paged cache migrated through the slot
    layout) is a layout change: an aggressive replan schedule must not
    alter the generated tokens vs the replan-free reference."""
    cfg, params, ref_reqs, _ = ref_run
    eng = Engine.build(_cfg(replan=True), params=params)
    reqs = _reqs(cfg.model.vocab_size)
    out = eng.run_trace(reqs, max_steps=500)
    assert out["finished"] == out["total"]
    assert len(eng.replan_log) >= 1  # the trigger actually fired
    for a, b in zip(ref_reqs, reqs):
        assert a.generated == b.generated, a.req_id
    backend = eng.scheduler.backend
    backend.pool.check_invariants()


def test_unknown_cache_backend_rejected():
    """The paged pool is the only cache store: any other name — the
    retired dense slot backend included — fails at construction."""
    for name in ("pagedd", "slot"):
        with pytest.raises(ValueError, match="unknown cache backend"):
            _cfg(cache_backend=name)
    assert _cfg().cache_backend == "paged"


def test_paging_config_validated():
    with pytest.raises(ValueError, match="block_size"):
        PagingConfig(block_size=0)


def test_paged_memory_smaller_than_slot(ref_run):
    """The point of the subsystem: under an imbalanced policy the paged
    footprint undercuts a dense slot cache's (``slot_equivalent_bytes``)."""
    cfg, params, _, _ = ref_run
    eng = Engine.build(_cfg(), params=params)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.model.vocab_size, size=(2, 20))
    eng.generate(prompts.astype(np.int32), 4)
    mem = eng.memory_stats()
    assert mem["cache_bytes"] < mem["slot_equivalent_bytes"]
    assert mem["blocks_in_use"] > 0
