"""Continuous-batching scheduler: freelist, admission, retirement, replan
hysteresis, and end-to-end per-row isolation (co-scheduled logits == solo)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compression.base import CompressionConfig
from repro.configs import get_smoke_config
from repro.core import PlannerConfig, build_plan, synthetic_profile
from repro.kernels import ops as K
from repro.models import init_params
from repro.serving import (
    Request,
    RequestState,
    ReplanTrigger,
    RowFreelist,
    Scheduler,
    SchedulerConfig,
)

ARCH = "minitron-8b"


# ---------------------------------------------------------------------------
# freelist
# ---------------------------------------------------------------------------


def test_freelist_lowest_first_and_exhaustion():
    fl = RowFreelist(3)
    assert [fl.acquire() for _ in range(3)] == [0, 1, 2]
    assert fl.acquire() is None
    assert fl.in_use == 3
    fl.release(1)
    fl.release(0)
    assert fl.acquire() == 0  # lowest-index-first after release
    assert fl.acquire() == 1
    assert len(fl) == 0


def test_freelist_rejects_double_free_and_bad_row():
    fl = RowFreelist(2)
    with pytest.raises(ValueError):
        fl.release(0)  # never acquired -> still free
    row = fl.acquire()
    fl.release(row)
    with pytest.raises(ValueError):
        fl.release(row)
    with pytest.raises(ValueError):
        fl.release(7)


# ---------------------------------------------------------------------------
# replan trigger hysteresis
# ---------------------------------------------------------------------------


def test_trigger_requires_full_window_above_threshold():
    tr = ReplanTrigger(window=4, threshold=1.2, cooldown=10)
    for _ in range(20):
        tr.observe(1.1)
    assert not tr.ready(20)  # never above threshold
    for step, imb in enumerate([1.5, 1.5, 1.5], start=21):
        tr.observe(imb)
        assert not tr.ready(step)  # window not yet full of high values
    tr.observe(1.5)
    assert tr.ready(24)


def test_trigger_dip_resets_hysteresis():
    tr = ReplanTrigger(window=3, threshold=1.2, cooldown=0)
    for imb in [1.5, 1.5, 1.1, 1.5, 1.5]:
        tr.observe(imb)
    assert not tr.ready(5)  # the dip is still inside the window
    tr.observe(1.5)
    assert tr.ready(6)


def test_trigger_cooldown_blocks_refire():
    tr = ReplanTrigger(window=2, threshold=1.2, cooldown=5)
    tr.observe(1.5)
    tr.observe(1.5)
    assert tr.ready(10)
    tr.fire(10)
    for step in range(11, 15):
        tr.observe(1.5)
        assert not tr.ready(step)  # window refills but cooldown holds
    tr.observe(1.5)
    assert tr.ready(15)


# ---------------------------------------------------------------------------
# scheduler fixtures
# ---------------------------------------------------------------------------


def _setup(max_rows=2, mode="fairkv_dp", ch=4, **scfg_kw):
    cfg = get_smoke_config(ARCH)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32,
                         max_seq_len=64)
    ccfg = CompressionConfig(policy="ada_snapkv", budget=12, alpha_max=2.0,
                             obs_window=8, sink=2, decode_margin=8)
    prof = synthetic_profile(cfg.n_layers, cfg.n_kv_heads, budget=12,
                             skew=1.0, seed=1)
    pcfg = PlannerConfig(mode=mode, extra_copies=ch, batch_cap=max_rows)
    plan = build_plan(prof, 4, pcfg)
    scfg = SchedulerConfig(max_rows=max_rows, collect_logits=True, **scfg_kw)
    sched = Scheduler(cfg, params, plan, ccfg, scfg, planner_cfg=pcfg)
    return cfg, sched


def _req(req_id, T, arrival=0, gen=4, seed=0, vocab=256):
    rng = np.random.default_rng(seed + 100 * req_id)
    prompt = rng.integers(0, vocab, size=T).astype(np.int32)
    return Request(req_id=req_id, prompt=prompt, arrival_step=arrival,
                   max_new_tokens=gen)


# ---------------------------------------------------------------------------
# admission
# ---------------------------------------------------------------------------


def test_admission_blocks_on_full_batch_then_reuses_freed_row():
    cfg, sched = _setup(max_rows=2, enable_replan=False)
    reqs = [_req(i, 14, gen=3, vocab=cfg.vocab_size) for i in range(3)]
    for r in reqs:
        sched.submit(r)
    ev = sched.step()
    assert sorted(row for _, row in ev["admitted"]) == [0, 1]
    assert reqs[2].state is RequestState.QUEUED
    assert not sched.admissible(reqs[2])  # no free rows
    # run until a row frees; the queued request must land in it
    for _ in range(8):
        ev = sched.step()
        if reqs[2].state is not RequestState.QUEUED:
            break
    assert reqs[2].row in (0, 1) or reqs[2].is_finished
    assert reqs[2].admit_step > reqs[0].admit_step


def test_admission_rejects_impossible_token_budget():
    cfg, sched = _setup(max_rows=2, enable_replan=False,
                        max_live_tokens=1)  # absurdly small budget
    r = _req(0, 14, vocab=cfg.vocab_size)
    # the request could never fit -> fail fast instead of head-of-line block
    with pytest.raises(ValueError, match="never be admitted"):
        sched.submit(r)


def test_admission_respects_token_budget():
    cfg, probe = _setup(max_rows=2, enable_replan=False)
    a = _req(0, 14, gen=5, vocab=cfg.vocab_size)
    b = _req(1, 14, gen=8, vocab=cfg.vocab_size)
    # budget fits one request (the larger of the two) but not both at once
    budget = probe._estimated_cost(b) + 1
    _, sched = _setup(max_rows=2, enable_replan=False,
                      max_live_tokens=budget)
    sched.submit(a)
    sched.submit(b)
    sched.step()
    # free rows exist, but the projected total exceeds the budget -> b waits
    assert a.state is RequestState.DECODING
    assert b.state is RequestState.QUEUED
    assert len(sched.freelist) == 1
    while not b.is_finished:
        sched.step()
    assert b.admit_step >= a.finish_step  # admitted only after a freed tokens


def test_admission_projection_uses_policy_pool_bound():
    """Audit regression (PR-3): the projected live-token cost must come
    from the per-policy keep bounds (pool conservation), not the static
    capacity C — the old ``L·H·min(prompt+gen, C)`` charge blocked
    admissions the cache could easily hold.  The tighter bound must remain
    a true upper bound on the realized footprint."""
    cfg, probe = _setup(max_rows=2, enable_replan=False)
    prompt, gen = 30, 4
    a = _req(0, prompt, gen=gen, vocab=cfg.vocab_size)
    cap = probe.ccfg.static_capacity()
    old_cost = cfg.n_layers * cfg.n_kv_heads * min(prompt + gen, cap)
    new_cost = probe._estimated_cost(a)
    assert new_cost < old_cost, (new_cost, old_cost)

    # validity: the realized footprint of a full solo run never exceeds
    # the projection (otherwise the tighter bound would overcommit)
    probe.submit(a)
    live_max = 0
    while not a.is_finished:
        probe.step()
        live_max = max(live_max, probe.live_tokens())
    live_a_prefill = None  # prefill-only footprint for the budget below
    assert live_max <= new_cost, (live_max, new_cost)

    # behavior: a budget the old projection would refuse now admits two
    # requests concurrently
    _, m = _setup(max_rows=2, enable_replan=False)
    a1 = _req(0, prompt, gen=gen, vocab=cfg.vocab_size)
    m.submit(a1)
    m.step()
    live_a_prefill = m.live_tokens()
    budget = live_a_prefill + new_cost
    assert budget < live_a_prefill + old_cost  # old rule: b would wait
    _, sched = _setup(max_rows=2, enable_replan=False,
                      max_live_tokens=budget)
    a2 = _req(0, prompt, gen=gen, vocab=cfg.vocab_size)
    b2 = _req(1, prompt, gen=gen, vocab=cfg.vocab_size)
    sched.submit(a2)
    sched.submit(b2)
    sched.step()
    assert a2.state is RequestState.DECODING
    assert b2.state is RequestState.DECODING  # co-admitted under the budget


# ---------------------------------------------------------------------------
# retirement
# ---------------------------------------------------------------------------


def test_retired_row_is_zero_and_decode_output_exactly_zero():
    cfg, sched = _setup(max_rows=2, enable_replan=False)
    a = _req(0, 14, gen=2, vocab=cfg.vocab_size)
    b = _req(1, 18, gen=8, vocab=cfg.vocab_size)
    sched.submit(a)
    sched.submit(b)
    while not a.is_finished:
        sched.step()
    assert a.state is RequestState.FINISHED
    assert not b.is_finished  # b still decoding on its row
    row = 0  # a was admitted first -> row 0
    cache = sched.state.cache
    lens = np.asarray(cache.lengths)
    assert lens[:, :, row].sum() == 0
    assert (np.asarray(cache.positions)[row] == 0)
    # the decode kernel's output for the retired row is exactly zero
    S = cache.k.shape[1]
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, S, cfg.q_per_kv, cfg.head_dim)),
                    jnp.float32)
    out = K.fairkv_decode(q, cache.k[0], cache.v[0], cache.lengths[0],
                          k_pos=cache.pos[0],
                          q_pos=jnp.zeros((2,), jnp.int32))
    assert float(jnp.abs(out[row]).max()) == 0.0
    assert sched.freelist.in_use == 1  # the row went back to the freelist


# ---------------------------------------------------------------------------
# end-to-end stream + per-row isolation
# ---------------------------------------------------------------------------


def _run_stream(sched, reqs, max_steps=200):
    out = sched.run(reqs, max_steps=max_steps)
    assert out["finished"] == out["total"], out
    return out


def test_stream_all_finish_with_mid_stream_admissions():
    cfg, sched = _setup(max_rows=2, enable_replan=False)
    reqs = [_req(0, 14, arrival=0, gen=4, vocab=cfg.vocab_size),
            _req(1, 18, arrival=0, gen=5, vocab=cfg.vocab_size),
            _req(2, 12, arrival=1, gen=4, vocab=cfg.vocab_size),
            _req(3, 16, arrival=2, gen=3, vocab=cfg.vocab_size)]
    out = _run_stream(sched, reqs)
    assert out["mid_stream_admissions"] >= 1
    assert all(r.is_finished for r in reqs)
    assert all(r.n_generated == r.max_new_tokens for r in reqs)
    # the batch never held more rows than configured
    assert sched.freelist.n_rows == 2
    # the run ends empty; the summary keeps the fullest tick's shard loads
    peak = np.asarray(out["peak_shard_load"])
    assert sched.live_tokens() == 0
    assert peak.shape == (sched.plan.n_shards,) and peak.sum() > 0


def test_co_scheduled_logits_match_solo_run():
    """Per-row isolation: a request decoded alongside others produces the
    same tokens and (near-)identical logits as the same request run alone."""
    cfg, sched = _setup(max_rows=2, enable_replan=False)
    reqs = [_req(0, 14, arrival=0, gen=4, vocab=cfg.vocab_size),
            _req(1, 18, arrival=0, gen=5, vocab=cfg.vocab_size),
            _req(2, 12, arrival=1, gen=4, vocab=cfg.vocab_size)]
    _run_stream(sched, reqs)

    for shared in reqs:
        _, solo_sched = _setup(max_rows=2, enable_replan=False)
        solo = Request(req_id=shared.req_id, prompt=shared.prompt,
                       arrival_step=0,
                       max_new_tokens=shared.max_new_tokens)
        _run_stream(solo_sched, [solo])
        assert solo.generated == shared.generated, shared.req_id
        for lg_solo, lg_shared in zip(solo.logits, shared.logits):
            np.testing.assert_allclose(lg_solo, lg_shared, atol=2e-4)


def test_attention_free_arch_streams():
    """SSM models (no slot cache) ride the same lifecycle: state splicing
    covers ssm/conv rows and the load metrics degrade gracefully."""
    cfg = get_smoke_config("mamba2-1.3b")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32,
                         max_seq_len=64)
    ccfg = CompressionConfig(policy="ada_snapkv", budget=12, obs_window=8,
                             sink=2, decode_margin=8)
    plan = build_plan(np.ones((cfg.n_layers, 1)), 1,
                      PlannerConfig(mode="sha", slots_per_shard=1))
    sched = Scheduler(cfg, params, plan, ccfg,
                      SchedulerConfig(max_rows=2))
    reqs = [_req(0, 12, arrival=0, gen=3, vocab=cfg.vocab_size),
            _req(1, 14, arrival=0, gen=4, vocab=cfg.vocab_size),
            _req(2, 12, arrival=2, gen=3, vocab=cfg.vocab_size)]
    out = _run_stream(sched, reqs)
    assert out["mid_stream_admissions"] >= 1
    assert sched.live_tokens() == 0 and sched.imbalance() == 1.0


def test_stream_with_online_replan_matches_no_replan():
    """Replanning is a layout change, not a math change: an aggressive
    replan schedule must not alter the generated tokens."""
    cfg, sched_plain = _setup(max_rows=2, enable_replan=False)
    mk = lambda: [_req(0, 14, arrival=0, gen=6, vocab=cfg.vocab_size),
                  _req(1, 18, arrival=0, gen=8, vocab=cfg.vocab_size),
                  _req(2, 12, arrival=2, gen=6, vocab=cfg.vocab_size)]
    plain = mk()
    _run_stream(sched_plain, plain)

    _, sched_replan = _setup(max_rows=2, replan_window=2,
                             replan_threshold=1.01, replan_cooldown=2,
                             replan_min_rows=1)
    replanned = mk()
    _run_stream(sched_replan, replanned)
    assert len(sched_replan.replan_log) >= 1  # trigger actually exercised
    for a, b in zip(plain, replanned):
        assert a.generated == b.generated, a.req_id
