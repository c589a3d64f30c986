"""Frontend subsystem (DESIGN.md §13): DRR fair-queue properties
(starvation-freedom, token conservation), the SLO admission decision
table, the FCFS baseline queue, cancellation pool conservation on the
paged backend, and the synchronous frontend pump end to end."""
import math

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
from repro.frontend import (
    AdmissionController,
    DeficitRoundRobin,
    FCFSController,
    FrontendConfig,
    FrontendScheduler,
    SingleQueue,
    run_frontend_trace,
)
from repro.frontend import queues as q
from repro.frontend.admission import ADMIT, DEGRADE, QUEUE, REJECT
from repro.serving.request import Request, RequestState
from tests._hypothesis_compat import given, settings, st

ARCH = "minitron-8b"


def _cfg(rows=2, n_blocks=0, block_size=8, **sched_kw):
    scfg = dict(max_rows=rows, enable_replan=False)
    scfg.update(sched_kw)
    return EngineConfig.smoke(
        ARCH, n_shards=4, max_seq_len=64,
        compression=CompressionConfig(policy="ada_snapkv", budget=12,
                                      alpha_max=2.0, obs_window=8, sink=2,
                                      decode_margin=8),
        planner=PlannerConfig(mode="fairkv_dp", extra_copies=4,
                              batch_cap=rows),
        scheduler=SchedulerConfig(**scfg),
        paging=PagingConfig(block_size=block_size, n_blocks=n_blocks))


@pytest.fixture(scope="module")
def shared_params():
    """Build once; every engine in this module reuses the params (and the
    jit cache, since shapes match)."""
    cfg = _cfg()
    return cfg, Engine.build(cfg).params


# ---------------------------------------------------------------------------
# DRR properties (pure queue, no engine)
# ---------------------------------------------------------------------------


@settings(max_examples=12)
@given(st.integers(1, 32), st.integers(1, 4),
       st.lists(st.integers(1, 50), min_size=1, max_size=6),
       st.integers(2, 30))
def test_drr_never_starves_backlogged_tenant(quantum, cap_mult, victim_costs,
                                             n_aggressor):
    """A victim tenant competing with an aggressor flooding cheap requests
    still admits each head item within ceil(cost/quantum) ticks of it
    reaching the head (costs clamped to cap, offers always accepted)."""
    cap = quantum * cap_mult
    drr = DeficitRoundRobin(quantum, cap)
    victim_costs = [min(c, cap) for c in victim_costs]
    for i, c in enumerate(victim_costs):
        drr.push("victim", ("v", i, c))
    for i in range(n_aggressor):
        drr.push("aggressor", ("a", i, 1))

    def cost(item):
        return item[2]

    ticks_waited = 0
    while drr.backlog("victim"):
        head = drr.items("victim")[0]
        admitted = drr.tick(cost, lambda t, item: q.ADMITTED)
        ticks_waited += 1
        if any(i == head for _, i in admitted):
            bound = math.ceil(head[2] / quantum)
            assert ticks_waited <= bound, (
                f"head {head} took {ticks_waited} ticks, bound {bound}")
            ticks_waited = 0
        # refill aggressor pressure so the victim is never alone
        drr.push("aggressor", ("a", 10_000 + ticks_waited, 1))
        assert ticks_waited <= math.ceil(cap / quantum) + 1, "starved"


@settings(max_examples=12)
@given(st.integers(1, 24), st.integers(1, 4),
       st.lists(st.integers(1, 60), min_size=1, max_size=10),
       st.integers(0, 2**31 - 1))
def test_drr_token_conservation(quantum, cap_mult, costs, seed):
    """For every tenant after every tick:
    ``deficit == refilled - charged - forfeited`` exactly, and
    ``0 <= deficit <= cap`` — across mixed admit/reject/block/stall
    verdicts, mid-stream pushes, and *oversized* costs above the cap
    (admitted by draining the banked deficit)."""
    cap = quantum * cap_mult
    drr = DeficitRoundRobin(quantum, cap)
    rng = np.random.default_rng(seed)
    tenants = ["a", "b", "c"]
    for i, c in enumerate(costs):
        drr.push(tenants[i % len(tenants)], (i, c))

    verdicts = (q.ADMITTED, q.REJECTED, q.BLOCKED, q.STALL)

    def offer(tenant, item):
        return verdicts[int(rng.integers(len(verdicts)))]

    for tick in range(12):
        drr.tick(lambda item: item[1], offer)
        if tick == 4:  # mid-stream arrival exercises re-backlogging
            drr.push(tenants[tick % len(tenants)], (1000 + tick, quantum))
        for t in tenants:
            refilled, charged, forfeited = drr.counters(t)
            assert drr.deficit(t) == pytest.approx(
                refilled - charged - forfeited)
            assert 0.0 <= drr.deficit(t) <= cap + 1e-9


def test_drr_oversized_item_reaches_controller():
    """A head item priced above the banked-deficit cap can never be
    covered by quota — it must still be offered once the deficit saturates
    at the cap (charging the whole bank), not head-of-line block its
    tenant forever (regression: clients of such requests hung)."""
    drr = DeficitRoundRobin(4, 8)
    drr.push("t", ("big", 100))  # cost 100 >> cap 8
    drr.push("t", ("small", 2))
    offered, admitted = [], []
    for _ in range(math.ceil(8 / 4) + 1):
        admitted += drr.tick(
            lambda item: item[1],
            lambda t, item: (offered.append(item), q.ADMITTED)[1])
    assert ("big", 100) in offered, "oversized item never reached offer()"
    assert [i for _, i in admitted] == [("big", 100), ("small", 2)]
    refilled, charged, forfeited = drr.counters("t")
    assert drr.deficit("t") == pytest.approx(refilled - charged - forfeited)
    assert 0.0 <= drr.deficit("t") <= 8.0


def test_drr_validates_config():
    with pytest.raises(ValueError, match="quantum"):
        DeficitRoundRobin(0, 10)
    with pytest.raises(ValueError, match="cap"):
        DeficitRoundRobin(16, 8)


def test_drr_backlog_bound_and_remove():
    drr = DeficitRoundRobin(4, 8, max_queue_per_tenant=2)
    assert drr.push("t", "x") and drr.push("t", "y")
    assert not drr.push("t", "z"), "backlog bound must refuse"
    assert drr.remove("t", "x")
    assert not drr.remove("t", "x"), "double-remove must be False"
    assert drr.items("t") == ["y"]


def test_single_queue_is_strict_fcfs():
    """The baseline queue admits in global arrival order regardless of
    tenant, and a blocked head blocks everyone behind it."""
    sq = SingleQueue()
    for i, tenant in enumerate(["a", "b", "a", "c"]):
        sq.push(tenant, i)
    admitted = sq.tick(lambda i: 1.0,
                       lambda t, i: q.ADMITTED if i < 2 else q.BLOCKED)
    assert [i for _, i in admitted] == [0, 1]
    assert sq.items() == [2, 3], "head-of-line block keeps order intact"
    assert sq.deficit("a") == 0.0  # quota-free surface


# ---------------------------------------------------------------------------
# admission decision table (stub scheduler, no engine)
# ---------------------------------------------------------------------------


class _StubBackend:
    def __init__(self, never=None, fits_upto=10_000):
        self.never = never
        self.fits_upto = fits_upto  # admissible iff max_new_tokens <= this

    def never_fits(self, req):
        return self.never

    def admissible(self, state, req, pending=()):
        return req.max_new_tokens <= self.fits_upto

    def request_cost(self, req):
        return req.prompt_len + req.max_new_tokens


class _StubSched:
    def __init__(self, free=1, step_idx=0, backend=None):
        self.freelist = list(range(free))
        self.step_idx = step_idx
        self.backend = backend if backend is not None else _StubBackend()
        self.state = None


def _req(priority=1, arrival=0, gen=16, deadline_s=None, arrival_time=None):
    return Request(req_id=0, prompt=np.zeros(4, np.int32),
                   arrival_step=arrival, max_new_tokens=gen,
                   priority=priority, deadline_s=deadline_s,
                   arrival_time=arrival_time)


def test_admission_admit_when_fits():
    d = AdmissionController(FrontendConfig()).decide(_StubSched(), _req())
    assert d.action == ADMIT


def test_admission_queue_blocks_globally_when_no_row():
    d = AdmissionController(FrontendConfig()).decide(
        _StubSched(free=0), _req())
    assert d.action == QUEUE and d.global_block and not d.preempt


def test_admission_preempt_arms_for_urgent_class():
    cfg = FrontendConfig()
    cls = cfg.class_for(0)  # interactive: preempt_below
    assert cls.preempt_below
    sched = _StubSched(free=0, step_idx=cls.ttft_slo_steps // 2)
    d = AdmissionController(cfg).decide(sched, _req(priority=0))
    assert d.action == QUEUE and d.preempt
    young = AdmissionController(cfg).decide(
        _StubSched(free=0), _req(priority=0))
    assert not young.preempt, "young requests must not thrash rows"


def test_admission_sheds_blown_slo():
    cfg = FrontendConfig()
    waited = cfg.class_for(0).shed_after_steps + 1
    d = AdmissionController(cfg).decide(
        _StubSched(step_idx=waited), _req(priority=0))
    assert d.action == REJECT and d.reason == "slo_blown"


def test_admission_rejects_exceeded_deadline():
    d = AdmissionController(FrontendConfig()).decide(
        _StubSched(), _req(deadline_s=0.0, arrival_time=0.0))
    assert d.action == REJECT and d.reason == "deadline_exceeded"


def test_admission_degrades_under_pressure_to_largest_fit():
    """Full ask inadmissible, backend fits asks <= 6, batch class floor 4:
    once the SLO clock is half-spent the controller offers exactly 6."""
    cfg = FrontendConfig()
    cls = cfg.class_for(2)  # batch: degrade_floor 4
    assert cls.degrade_floor == 4
    sched = _StubSched(backend=_StubBackend(fits_upto=6),
                       step_idx=cls.ttft_slo_steps // 2)
    d = AdmissionController(cfg).decide(sched, _req(priority=2, gen=16))
    assert d.action == DEGRADE and d.degrade_to == 6
    # a young request prefers waiting for its full ask
    young = AdmissionController(cfg).decide(
        _StubSched(backend=_StubBackend(fits_upto=6)), _req(priority=2))
    assert young.action == QUEUE


def test_admission_never_fits_degrades_or_rejects():
    cfg = FrontendConfig()
    sched = _StubSched(backend=_StubBackend(never="too long"))
    d = AdmissionController(cfg).decide(sched, _req(priority=1))
    assert d.action == REJECT and "never_fits" in d.reason
    # batch class has a floor; the stub still reports never_fits for the
    # floor probe, so the degrade escape must NOT fire
    d2 = AdmissionController(cfg).decide(sched, _req(priority=2))
    assert d2.action == REJECT


def test_fcfs_controller_is_naive():
    cfg = FrontendConfig(admission="fcfs")
    c = FCFSController(cfg)
    assert c.decide(_StubSched(), _req()).action == ADMIT
    d = c.decide(_StubSched(free=0), _req())
    assert d.action == QUEUE and d.global_block
    assert c.decide(_StubSched(backend=_StubBackend(never="x")),
                    _req()).action == REJECT


# ---------------------------------------------------------------------------
# cancellation conserves the paged pool (engine-level regression)
# ---------------------------------------------------------------------------


def test_cancel_returns_blocks_to_pool(shared_params):
    """Cancel a mid-decode request on the paged backend: its blocks return
    to the pool immediately (admitting a new request proves capacity), the
    allocator invariants hold, and full drain-out ends at zero in-use."""
    _, params = shared_params
    eng = Engine.build(_cfg(rows=2), params=params)
    vocab = eng.cfg.model.vocab_size
    reqs = synthesize_requests(3, 5.0, vocab, min_prompt=12, max_prompt=20,
                               max_new_tokens=6, seed=3)
    for r in reqs:
        eng.submit(r)
    sched = eng.scheduler
    while not sched.active:
        eng.step()
    victim_id = next(iter(sched.active.values())).req_id
    pool = sched.backend.pool
    in_use_before = pool.blocks_in_use()
    assert in_use_before > 0
    assert eng.cancel(victim_id)
    assert pool.blocks_in_use() < in_use_before, "blocks must free now"
    pool.check_invariants()
    victim = next(r for r in sched.finished if r.req_id == victim_id)
    assert victim.state is RequestState.CANCELLED
    assert not eng.cancel(victim_id), "already-terminal id must be False"
    assert not eng.cancel(10_000), "unknown id must be False"
    # freed capacity is immediately reusable
    extra = synthesize_requests(1, 5.0, vocab, min_prompt=12, max_prompt=20,
                                max_new_tokens=6, seed=9)[0]
    extra.req_id = 50
    eng.submit(extra)
    for _ in range(200):
        if len(sched.finished) == 4:
            break
        eng.step()
    assert len(sched.finished) == 4
    assert all(r.is_finished for r in sched.finished)
    assert pool.blocks_in_use() == 0
    pool.check_invariants()
    assert sched.n_cancellations == 1


# ---------------------------------------------------------------------------
# the synchronous frontend pump end to end
# ---------------------------------------------------------------------------


def _frontend(eng, **fe_kw):
    fe_kw.setdefault("quantum_tokens", 64)
    fe_kw.setdefault("quota_cap_tokens", 512)
    return FrontendScheduler(eng._ensure_scheduler(), FrontendConfig(**fe_kw))


def _tenant_trace(vocab, n=8, gen=4, seed=11):
    return synthesize_requests(
        n, 2.0, vocab, min_prompt=8, max_prompt=16, max_new_tokens=gen,
        seed=seed, tenant_mix={"fast": 1.0, "slow": 1.0},
        tenant_priorities={"fast": 0, "slow": 2})


def test_frontend_trace_slo_mode(shared_params):
    cfg, params = shared_params
    eng = Engine.build(cfg, params=params)
    fe = _frontend(eng)
    out = run_frontend_trace(fe, _tenant_trace(cfg.model.vocab_size),
                             max_steps=400)
    assert out["converged"] and out["finished"] == out["total"]
    assert out["admission"] == "slo"
    assert out["generated_tokens"] >= out["goodput_tokens"] > 0
    assert set(out["tenants"]) == {"fast", "slow"}
    assert out["slo_attained"] + out["slo_missed"] == out["total"]
    # §13 observability contract on the engine's own registry
    prom = eng.metrics_prometheus()
    for family in ("slo_attained_total", "slo_missed_total",
                   "goodput_tokens_total", "frontend_admission_total"):
        assert f"{family}{{" in prom, family
    assert 'tenant="fast"' in prom


def test_frontend_latency_comes_from_the_perf_counter_stamps(shared_params):
    """TTFT runs from the frontend's submission, and ITL is one sample per
    inter-token gap, as in the scheduler's own ``itl_s``."""
    cfg, params = shared_params
    eng = Engine.build(cfg, params=params)
    fe = _frontend(eng)
    reqs = _tenant_trace(cfg.model.vocab_size)
    out = run_frontend_trace(fe, reqs, max_steps=400)
    assert out["finished"] == out["total"]
    served = [r for r in reqs if r.t_first is not None]
    assert served
    for r in served:
        assert r.t_submit <= r.t_admit <= r.t_first
        assert r.ttft_seconds() == r.t_first - r.t_submit
    # the frontend takes no latency sample from a request it cancelled
    itl = eng.obs.metrics.get("frontend_itl_seconds")
    assert sum(itl.count(tenant=t) for t in ("fast", "slow")) == sum(
        len(r.itl_gaps) for r in served if not r.cancelled)
    # the registry also keeps the gaps of a run a preemption threw away
    n_itl = eng.obs.metrics.get("itl_s").count()
    assert n_itl >= sum(len(r.itl_gaps) for r in served)
    if not any(r.n_preemptions for r in reqs):
        assert n_itl == sum(len(r.itl_gaps) for r in served)


def test_frontend_drain_sheds_queued_finishes_live(shared_params):
    cfg, params = shared_params
    eng = Engine.build(cfg, params=params)
    fe = _frontend(eng)
    reqs = _tenant_trace(cfg.model.vocab_size, n=6, seed=13)
    for r in reqs:
        r.arrival_step = 0
        fe.submit(r)
    fe.pump()  # admits up to the 2 free rows, rest stays tenant-queued
    live = len(fe.sched.active)
    assert live > 0 and len(fe.queue) > 0
    fe.drain()
    assert len(fe.queue) == 0, "queued requests shed at drain"
    for _ in range(200):
        if fe.idle:
            break
        fe.pump()
    assert fe.idle
    assert len(fe.finished) == len(reqs)
    shed = [r for r in fe.finished if fe.reject_reasons.get(r.req_id)]
    assert all(fe.reject_reasons[r.req_id] == "draining" for r in shed)
    done = [r for r in fe.finished if r.state is RequestState.FINISHED]
    assert len(done) == live, "live rows decode to completion"
    # post-drain ingress is refused outright
    late = _tenant_trace(cfg.model.vocab_size, n=1, seed=17)[0]
    late.req_id = 99
    assert not fe.submit(late)
    assert fe.reject_reasons[99] == "draining"


def test_frontend_backlog_bound_and_cancel(shared_params):
    cfg, params = shared_params
    eng = Engine.build(cfg, params=params)
    fe = _frontend(eng, max_queue_per_tenant=1, quantum_tokens=128)
    reqs = _tenant_trace(cfg.model.vocab_size, n=4, seed=19)
    for i, r in enumerate(reqs):
        r.req_id = i
        r.tenant, r.priority = "fast", 0
    # fill both rows (pump between submissions: the tenant queue is
    # bounded at one waiter, so admissions must drain it first)
    fe.submit(reqs[0])
    fe.pump()
    fe.submit(reqs[1])
    fe.pump()
    assert len(fe.sched.active) == 2
    assert fe.submit(reqs[2])  # queued (backlog 1/1)
    assert not fe.submit(reqs[3]), "tenant backlog bound must refuse"
    assert fe.reject_reasons[3] == "tenant_backlog_full"
    # cancel the queued one before admission: terminal, engine never sees it
    assert fe.cancel(2)
    assert fe.reject_reasons[2] == "cancelled"
    assert len(fe.queue) == 0
    assert fe.cancel(2) is False


def test_frontend_serves_requests_costing_more_than_quota_cap(shared_params):
    """A quota cap far below every request's projected cost must not hang
    the trace: the DRR's saturation path still surfaces each request to
    the admission controller, which admits (or sheds) it (regression:
    such requests were never offered, admitted, or rejected)."""
    cfg, params = shared_params
    eng = Engine.build(cfg, params=params)
    fe = _frontend(eng, quantum_tokens=1, quota_cap_tokens=1)
    assert all(
        fe.sched.backend.request_cost(r) > fe.queue.cap
        for r in _tenant_trace(cfg.model.vocab_size, n=4, seed=23)), \
        "precondition: every request must outprice the quota cap"
    out = run_frontend_trace(fe, _tenant_trace(cfg.model.vocab_size, n=4,
                                               seed=23), max_steps=400)
    assert out["converged"] and out["finished"] == out["total"]


def test_frontend_quota_calibrated_to_backend_units(shared_params):
    """FrontendConfig quotas are denominated in request tokens; the DRR
    charges backend cost units (L·H-scaled).  The constructor must scale
    the knobs so a default-sized cap covers a typical request's cost."""
    cfg, params = shared_params
    eng = Engine.build(cfg, params=params)
    fe = _frontend(eng)  # quantum 64 / cap 512 request tokens
    req = _tenant_trace(cfg.model.vocab_size, n=1)[0]  # <= 20 tokens
    assert fe.sched.backend.request_cost(req) <= fe.queue.cap, (
        "a ~20-token request must fit a 512-token quota cap after "
        "unit calibration")


def test_frontend_submit_clamps_priority_to_configured_classes(
        shared_params):
    """A client-supplied out-of-range priority (e.g. -5) would outrank
    every configured class and arm preemption; submit clamps it to the
    configured ladder on both ends."""
    cfg, params = shared_params
    eng = Engine.build(cfg, params=params)
    fe = _frontend(eng)
    lo = _tenant_trace(cfg.model.vocab_size, n=2, seed=29)
    lo[0].priority, lo[1].priority = -5, 99
    for r in lo:
        fe.submit(r)
    assert lo[0].priority == 0, "clamped to the most urgent class"
    assert lo[1].priority == 2, "clamped to the least urgent class"


def test_backend_admissible_charges_pending(shared_params):
    """Several admissions in one pump tick are checked against the same
    un-spliced state; the ``pending`` charge must make the joint check
    fail where each individual one passes."""
    cfg, params = shared_params
    req = Request(req_id=0, prompt=np.zeros(16, np.int32), max_new_tokens=4)
    probe = Engine.build(cfg, params=params)._ensure_scheduler().backend
    need = probe._layer_blocks(16, 4, worst_case=False)
    # a pool that holds one request's charge and a half: one fits, not two
    eng = Engine.build(_cfg(n_blocks=1 + int(need.max() * 1.5)),
                       params=params)
    sched = eng._ensure_scheduler()
    b = sched.backend
    assert b.admissible(sched.state, req)
    assert not b.admissible(sched.state, req, pending=[req])
