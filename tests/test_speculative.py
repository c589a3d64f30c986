"""Speculative decoding (DESIGN.md §16): token parity vs single-token
greedy decode at acceptance 1.0 (full-depth self-draft), partial
(truncated draft), and 0 (adversarial proposals) — on the local executor
in-process and the 2x4 host mesh in a subprocess — plus zero-recompile
trace accounting, scheduler-level parity through `Engine.run_trace`
(including int8 pools and ring-wrap CoW under shared prefixes), pool
conservation under reject-rollback, and the §12 speculation metrics.
"""
import json
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (
    CompressionConfig,
    Engine,
    EngineConfig,
    PagingConfig,
    PlannerConfig,
    PrefixConfig,
    Request,
    SchedulerConfig,
    SpeculationConfig,
    synthesize_requests,
)

ARCH = "minitron-8b"
B, T, GEN = 4, 20, 10
CAP = T + GEN + 8


def _cfg(executor="local", spec=None, kv_dtype="fp32", rows=B, max_seq=CAP,
         budget=64, margin=8, prefix=None, **sched_kw):
    scfg = dict(max_rows=rows, enable_replan=False)
    scfg.update(sched_kw)
    return EngineConfig.smoke(
        ARCH, n_shards=4, max_seq_len=max_seq,
        compression=CompressionConfig(policy="none", budget=budget,
                                      capacity=budget, alpha_max=1.0,
                                      obs_window=8, sink=2,
                                      decode_margin=margin),
        planner=PlannerConfig(mode="fairkv_dp", extra_copies=6,
                              batch_cap=rows),
        scheduler=SchedulerConfig(**scfg),
        cache_backend="paged",
        paging=PagingConfig(block_size=8, kv_dtype=kv_dtype),
        executor=executor,
        prefix=prefix or PrefixConfig(),
        speculation=spec or SpeculationConfig())


_PARAMS_CACHE: dict = {}


def _shared_params():
    if "p" not in _PARAMS_CACHE:
        _PARAMS_CACHE["p"] = Engine.build(_cfg()).params
    return _PARAMS_CACHE["p"]


@pytest.fixture(scope="module")
def params():
    return _shared_params()


# ---------------------------------------------------------------------------
# executor level: propose/verify vs sequential decode (local, in-process)
# ---------------------------------------------------------------------------

_PROMPTS = np.random.default_rng(0).integers(0, 256, (B, T))


def _fresh(eng):
    eng.prefill(_PROMPTS)  # paged into a fresh pool
    return eng.state


def _run_ref(eng):
    """GEN single-token greedy decode steps -> (B, GEN) tokens."""
    state = _fresh(eng)
    toks = []
    for _ in range(GEN):
        state = eng.backend.prepare_decode(state, None)
        state, _ = eng.executor.decode(eng.sp, state, eng.pa,
                                       state.last_tokens)
        toks.append(np.asarray(state.last_tokens))
    eng.state = state
    return np.stack(toks, 1)


def _run_spec(eng, draft_layers, max_k, adversarial=False):
    """The scheduler's speculation tick protocol, hand-driven: returns
    (tokens (B, GEN), acceptance, ticks).  With ``adversarial`` every
    proposal is replaced by a guaranteed-wrong token, forcing acceptance
    0 (n_commit == 1 on every tick)."""
    vocab = eng.cfg.model.vocab_size
    state = _fresh(eng)
    committed = [[] for _ in range(B)]
    accepted = proposed = ticks = 0
    while min(len(c) for c in committed) < GEN:
        lens = np.asarray(state.cache.lengths)
        headroom = CAP - lens.max(axis=(0, 1))
        depth = np.minimum(max_k, np.maximum(headroom - 1, 0)).astype(
            np.int32)
        if ticks % 2 == 1:  # vary traced depths: must not retrace
            depth = np.minimum(depth, np.maximum(1, max_k - 1))
        ticks += 1
        q_len = depth + 1
        state = eng.backend.prepare_decode(state, None,
                                           n_tokens=int(q_len.max()))
        st, props = eng.executor.propose(eng.sp, state, eng.pa,
                                         jnp.asarray(depth),
                                         draft_layers=draft_layers,
                                         max_k=max_k)
        props = np.asarray(props)
        if adversarial:
            # full-depth drafts propose exactly the greedy continuation,
            # so shifting every lane guarantees a first-position mismatch
            props = (props + 1) % vocab
        tokens = np.concatenate([np.asarray(st.last_tokens)[:, None],
                                 props], axis=1)
        st2, g, n_commit, _ = eng.executor.verify(eng.sp, st, eng.pa,
                                                  jnp.asarray(tokens),
                                                  jnp.asarray(q_len),
                                                  draft_layers=draft_layers)
        st2 = eng.backend.trim_rows(st2, np.arange(B))
        g_np, nc = np.asarray(g), np.asarray(n_commit)
        if adversarial:
            assert (nc == 1).all(), nc  # every proposal rejected
        for b in range(B):
            committed[b].extend(g_np[b, :nc[b]].tolist())
        proposed += int(depth.sum())
        accepted += int((nc - 1).sum())
        state = st2
        eng.state = state
    eng.backend.pool.check_invariants()  # conservation after rollbacks
    return (np.stack([np.array(c[:GEN]) for c in committed]),
            accepted / max(proposed, 1), ticks)


def test_spec_executor_local_parity_and_zero_recompile(params):
    """Full-depth draft (acceptance 1.0) and truncated draft (partial
    acceptance) both reproduce the sequential greedy tokens bit-exactly;
    propose/verify each compile once per (draft_layers, max_k) static key
    and survive varying traced depths AND an online replan uncompiled."""
    eng = Engine.build(_cfg(), params=params)
    ref = _run_ref(eng)
    nL = eng.cfg.model.n_layers

    spec, acc, _ = _run_spec(eng, nL, 3)  # self-check mode: acc = 1.0
    assert np.array_equal(ref, spec)
    assert acc == 1.0
    assert eng.executor.step_traces["propose"] == 1
    assert eng.executor.step_traces["verify"] == 1

    spec, acc, ticks = _run_spec(eng, max(1, nL // 2), 3)  # new static key
    assert np.array_equal(ref, spec)
    assert 0.0 <= acc <= 1.0 and ticks <= GEN
    assert eng.executor.step_traces["propose"] == 2
    assert eng.executor.step_traces["verify"] == 2

    prof = np.asarray(eng.profile)[:, ::-1].copy()
    eng.replan(profile=prof)
    ref2 = _run_ref(eng)
    spec2, acc2, _ = _run_spec(eng, nL, 3)
    assert np.array_equal(ref2, spec2)
    assert acc2 == 1.0
    assert eng.executor.step_traces["propose"] == 2  # cached: no retrace
    assert eng.executor.step_traces["verify"] == 2


def test_spec_executor_acceptance_zero_parity(params):
    """Adversarial wrong proposals: the verify pass must reject the whole
    window every tick (n_commit == 1) yet still commit the exact greedy
    token — speculation at acceptance 0 degrades to single-token decode,
    never to wrong tokens.  Rollback must leave the pool conserved."""
    eng = Engine.build(_cfg(), params=params)
    ref = _run_ref(eng)
    nL = eng.cfg.model.n_layers
    spec, acc, ticks = _run_spec(eng, nL, 3, adversarial=True)
    assert np.array_equal(ref, spec)
    assert acc == 0.0
    assert ticks == GEN  # one committed token per tick


# ---------------------------------------------------------------------------
# executor level: 2x4 host mesh (subprocess so XLA_FLAGS lands pre-import)
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
                       PagingConfig, PlannerConfig, SchedulerConfig)
from repro.launch.mesh import make_host_mesh

B, T, GEN = 4, 20, 8
CAP = T + GEN + 8

def cfg_for(executor="local"):
    return EngineConfig.smoke(
        "minitron-8b", n_shards=4, max_seq_len=CAP,
        compression=CompressionConfig(policy="none", budget=64,
                                      capacity=CAP, alpha_max=1.0,
                                      obs_window=8, sink=2, decode_margin=8),
        planner=PlannerConfig(mode="fairkv_dp", extra_copies=6, batch_cap=B),
        scheduler=SchedulerConfig(max_rows=B, enable_replan=False),
        cache_backend="paged", paging=PagingConfig(block_size=8),
        executor=executor)

prompts = np.random.default_rng(0).integers(0, 256, (B, T))

def fresh(eng):
    eng.prefill(prompts)
    return eng.state

def run_ref(eng):
    state = fresh(eng)
    toks = []
    for _ in range(GEN):
        state = eng.backend.prepare_decode(state, None)
        state, _ = eng.executor.decode(eng.sp, state, eng.pa,
                                       state.last_tokens)
        toks.append(np.asarray(state.last_tokens))
    eng.state = state
    return np.stack(toks, 1)

def run_spec(eng, draft_layers, max_k):
    state = fresh(eng)
    committed = [[] for _ in range(B)]
    accepted = proposed = ticks = 0
    while min(len(c) for c in committed) < GEN:
        lens = np.asarray(state.cache.lengths)
        headroom = CAP - lens.max(axis=(0, 1))
        depth = np.minimum(max_k, np.maximum(headroom - 1, 0)).astype(
            np.int32)
        if ticks % 2 == 1:
            depth = np.minimum(depth, np.maximum(1, max_k - 1))
        ticks += 1
        q_len = depth + 1
        state = eng.backend.prepare_decode(state, None,
                                           n_tokens=int(q_len.max()))
        st, props = eng.executor.propose(eng.sp, state, eng.pa,
                                         jnp.asarray(depth),
                                         draft_layers=draft_layers,
                                         max_k=max_k)
        tokens = np.concatenate([np.asarray(st.last_tokens)[:, None],
                                 np.asarray(props)], axis=1)
        st2, g, n_commit, _ = eng.executor.verify(eng.sp, st, eng.pa,
                                                  jnp.asarray(tokens),
                                                  jnp.asarray(q_len),
                                                  draft_layers=draft_layers)
        st2 = eng.backend.trim_rows(st2, np.arange(B))
        g_np, nc = np.asarray(g), np.asarray(n_commit)
        for b in range(B):
            committed[b].extend(g_np[b, :nc[b]].tolist())
        proposed += int(depth.sum())
        accepted += int((nc - 1).sum())
        state = st2
        eng.state = state
    eng.backend.pool.check_invariants()
    return (np.stack([np.array(c[:GEN]) for c in committed]),
            accepted / max(proposed, 1))

loc = Engine.build(cfg_for())
ref = run_ref(loc)
nL = loc.cfg.model.n_layers
mesh = make_host_mesh(model=4, data=2)
msh = Engine.build(cfg_for("mesh"), mesh=mesh, params=loc.params)
refm = run_ref(msh)
out = {"mesh_ref_equals_local": bool(np.array_equal(ref, refm))}
spec_f, acc_f = run_spec(msh, nL, 3)
out["full_match"] = bool(np.array_equal(refm, spec_f))
out["full_acc"] = acc_f
spec_p, acc_p = run_spec(msh, max(1, nL // 2), 3)
out["partial_match"] = bool(np.array_equal(refm, spec_p))
out["partial_acc"] = acc_p
out["traces_before_replan"] = dict(msh.executor.step_traces)
msh.replan(profile=np.asarray(msh.profile).copy())
refm2 = run_ref(msh)
spec_r, _ = run_spec(msh, nL, 3)
out["replan_match"] = bool(np.array_equal(refm2, spec_r))
out["traces"] = dict(msh.executor.step_traces)
print(json.dumps(out))
"""


def test_spec_mesh_parity_multidevice_subprocess():
    """Mesh propose/verify: bit-identical to sequential decode on a 2x4
    host mesh at full and partial acceptance, matching the local
    executor's reference, with one compile per static key surviving an
    online replan."""
    import repro
    src = list(repro.__path__)[0].rsplit("/repro", 1)[0]
    code = SUBPROC.replace("__SRC__", repr(src))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["mesh_ref_equals_local"], res
    assert res["full_match"] and res["full_acc"] == 1.0, res
    assert res["partial_match"], res
    assert res["replan_match"], res
    assert res["traces"]["propose"] == 2, res  # full + partial keys only
    assert res["traces"]["verify"] == 2, res
    assert res["traces"] == res["traces_before_replan"], res


# ---------------------------------------------------------------------------
# scheduler level: Engine.run_trace with speculation on
# ---------------------------------------------------------------------------


def _run_trace(cfg, params, reqs=None):
    eng = Engine.build(cfg, params=params)
    reqs = reqs or synthesize_requests(6, 0.5, 256, min_prompt=8,
                                       max_prompt=20, max_new_tokens=10,
                                       seed=3)
    out = eng.run_trace(reqs, max_steps=400)
    assert out["finished"] == out["total"], out
    toks = {r.req_id: tuple(r.generated) for r in eng.finished_requests}
    return eng, toks, out


def test_spec_scheduler_full_draft_parity_and_metrics(params):
    """Full-depth self-draft through the continuous scheduler: identical
    per-request tokens in strictly fewer decode ticks, acceptance 1.0 in
    the §12 counters, spec_depth gauge and per-request acceptance
    histogram exported, pool conserved, stats() consistent."""
    _, ref, out_ref = _run_trace(_cfg(), params)
    spec = SpeculationConfig(enabled=True, max_k=3)
    eng, toks, out = _run_trace(_cfg(spec=spec), params)
    assert toks == ref
    assert out["steps"] < out_ref["steps"]
    m = eng.scheduler.obs.metrics
    prop = m.counter_value("spec_proposed_total")
    acc = m.counter_value("spec_accepted_total")
    assert prop > 0 and acc == prop  # full-depth draft: all accepted
    snap = eng.metrics()
    assert any(k.startswith("spec_depth") for k in snap)
    assert any(k.startswith("spec_acceptance") for k in snap)
    eng.scheduler.backend.pool.check_invariants()
    st = eng.stats()
    assert st.speculation.enabled and st.speculation.max_k == 3
    assert st.speculation.proposed == int(prop)
    assert st.speculation.acceptance == 1.0


def test_spec_scheduler_partial_draft_parity_and_adaptive_depth(params):
    """A 1-layer draft accepts rarely: tokens still match the plain run
    bit-exactly, per-request accounting stays within bounds, and the
    adaptive controller walks depth down toward min_k."""
    _, ref, _ = _run_trace(_cfg(), params)
    spec = SpeculationConfig(enabled=True, max_k=3, draft_layers=1,
                             min_k=1, low_acceptance=0.4)
    eng, toks, _ = _run_trace(_cfg(spec=spec), params)
    assert toks == ref
    reqs = eng.finished_requests
    assert all(0 <= r.spec_accepted <= r.spec_proposed for r in reqs)
    total_p = sum(r.spec_proposed for r in reqs)
    total_a = sum(r.spec_accepted for r in reqs)
    assert total_a < total_p  # the truncated draft did get rejected
    st = eng.stats()
    assert st.speculation.acceptance == pytest.approx(total_a / total_p)
    eng.scheduler.backend.pool.check_invariants()


def test_spec_scheduler_int8_pool_conservation(params):
    """Reject-rollback over quantized pools: a low-acceptance draft on
    int8 KV must match the plain int8 run token-for-token (scale
    evolution included) and leave zero leaked blocks."""
    _, ref_i8, _ = _run_trace(_cfg(kv_dtype="int8"), params)
    spec = SpeculationConfig(enabled=True, max_k=3, draft_layers=1)
    eng, toks, _ = _run_trace(_cfg(spec=spec, kv_dtype="int8"), params)
    assert toks == ref_i8
    pool = eng.scheduler.backend.pool
    pool.check_invariants()
    assert sum(r.spec_proposed for r in eng.finished_requests) > 0


def test_spec_scheduler_ring_wrap_cow(params):
    """Speculation over shared prefixes with ring-wrap: the donor hits
    capacity (the headroom clamp drops its depth to 0, so no speculative
    window ever contains a ring write) and its ring appends copy-on-write
    out of the registered entry.

    The donor's own post-wrap tokens are ring-phase dependent — the phase
    is the global ``decode_steps`` counter, and speculation reaches
    capacity in fewer ticks than plain decode shifts it (the same
    phase-dependence chunked prefill has, see
    ``test_cow_privatizes_ring_wrap_writes``) — so parity is asserted on
    its below-capacity prefix only.  The proof that CoW kept the shared
    entry intact is the LATE second request: it stays below capacity, so
    its tokens are phase-independent and must match the no-speculation
    engine exactly."""
    vocab = _cfg().model.vocab_size
    rng = np.random.default_rng(7)
    shared = rng.integers(1, vocab, size=48).astype(np.int32)
    sfx = [rng.integers(1, vocab, size=8).astype(np.int32)
           for _ in range(2)]

    def reqs():
        # donor: 56-token prompt, capacity 64 -> wraps after 8 of 24
        return [Request(req_id=0, prompt=np.concatenate([shared, sfx[0]]),
                        arrival_step=0, max_new_tokens=24),
                Request(req_id=1, prompt=np.concatenate([shared, sfx[1]]),
                        arrival_step=40, max_new_tokens=6)]

    def cow_cfg(spec=None):
        return _cfg(spec=spec, rows=3, budget=32, margin=32, max_seq=128,
                    prefix=PrefixConfig(enabled=True, chunk_tokens=16))

    _, ref, _ = _run_trace(cow_cfg(), params, reqs=reqs())
    spec = SpeculationConfig(enabled=True, max_k=3)
    eng, toks, _ = _run_trace(cow_cfg(spec=spec), params, reqs=reqs())
    assert toks[1] == ref[1]  # late sharer: full parity through CoW
    assert toks[0][:9] == ref[0][:9]  # donor parity up to the wrap
    backend = eng.scheduler.backend
    assert backend.cow_copies > 0, "trace never exercised copy-on-write"
    assert not backend._pending_cow
    backend.pool.check_invariants()
