"""Shared-prefix block reuse + chunked prefill (DESIGN.md §14).

Covers the full §14 surface: the content-addressed index (hash-chain keys,
longest-prefix lookup, LRU eviction vs pins, refcount bookkeeping), chunked
prefill parity against monolithic prefill, block sharing through the
scheduler (token parity with refcount > 1 actually observed mid-trace),
copy-on-write under ring-wrap decode appends, safe materialization of
shared blocks (paged_to_slot / migrate_cache pool conservation), admission
discounting, TTFT accounting across prefill chunks, and local/mesh chunked
parity on a multi-device subprocess.

All engine-level tests use policy "none" in float32: compression quotas are
per-chunk ceilings, so exact chunked-vs-monolithic parity is guaranteed
only without compression (DESIGN.md §14 caveat).
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
    SchedulerConfig,
    synthesize_requests,
)
from repro.paging.block_pool import BlockPool
from repro.paging.paged_cache import paged_to_slot
from repro.prefix import PrefixIndex
from repro.serving.request import Request
from tests._hypothesis_compat import given, settings, st

ARCH = "minitron-8b"
BS = 16  # block size used by every engine-level test here


def _cfg(enabled=False, chunk=0, budget=128, margin=8, n_blocks=256,
         rows=3, max_seq=256, entries=256, kv_dtype="fp32", **sched_kw):
    scfg = dict(max_rows=rows, enable_replan=False, collect_logits=True)
    scfg.update(sched_kw)
    return EngineConfig.smoke(
        ARCH, max_seq_len=max_seq,
        compression=CompressionConfig(policy="none", budget=budget,
                                      capacity=budget, decode_margin=margin,
                                      obs_window=8),
        planner=PlannerConfig(batch_cap=rows),
        scheduler=SchedulerConfig(**scfg),
        cache_backend="paged",
        paging=PagingConfig(block_size=BS, n_blocks=n_blocks,
                            kv_dtype=kv_dtype),
        prefix=PrefixConfig(enabled=enabled, chunk_tokens=chunk,
                            max_entries=entries))


_PARAMS_CACHE: dict = {}


def _shared_params():
    """One parameter set shared by every engine in this module (engines
    differ only in prefix/chunk/capacity config, never in model shape).
    A plain memo rather than a fixture so the hypothesis-shim property
    test (whose runner takes no pytest fixtures) can reach it too."""
    if "p" not in _PARAMS_CACHE:
        _PARAMS_CACHE["p"] = Engine.build(_cfg()).params
    return _PARAMS_CACHE["p"]


@pytest.fixture(scope="module")
def params():
    return _shared_params()


def _shared_reqs(vocab, shared_len=48, n_shared=3, suffix=20, gen=6,
                 spacing=8, seed=0):
    """n_shared requests sharing a `shared_len` prefix (full chunks at
    chunk=16), spaced so the donor registers before the next arrival,
    plus one fully random request."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(1, vocab, size=shared_len).astype(np.int32)
    reqs = []
    for i in range(n_shared):
        sfx = rng.integers(1, vocab, size=suffix).astype(np.int32)
        reqs.append(Request(req_id=i, prompt=np.concatenate([shared, sfx]),
                            arrival_step=i * spacing, max_new_tokens=gen))
    reqs.append(Request(req_id=n_shared,
                        prompt=rng.integers(1, vocab, size=40).astype(np.int32),
                        arrival_step=1, max_new_tokens=gen))
    return reqs


def _clone(reqs):
    return [Request(req_id=r.req_id, prompt=r.prompt.copy(),
                    arrival_step=r.arrival_step,
                    max_new_tokens=r.max_new_tokens) for r in reqs]


def _tokens(eng):
    return {r.req_id: list(r.generated) for r in eng.scheduler.finished}


# ---------------------------------------------------------------------------
# PrefixIndex unit tests (no engine)
# ---------------------------------------------------------------------------


def test_chain_keys_commit_to_every_prior_token():
    idx = PrefixIndex(chunk_tokens=4)
    a = np.arange(16, dtype=np.int32)
    b = a.copy()
    b[9] = 99  # diverge inside chunk 2
    ka, kb = dict(idx.chain_keys(a)), dict(idx.chain_keys(b))
    assert sorted(ka) == sorted(kb) == [4, 8, 12, 16]
    assert ka[4] == kb[4] and ka[8] == kb[8]
    assert ka[12] != kb[12] and ka[16] != kb[16]  # chain: divergence sticks
    # deterministic across instances
    assert dict(PrefixIndex(chunk_tokens=4).chain_keys(a)) == ka
    # partial tail chunks get no boundary
    assert [t for t, _ in idx.chain_keys(a[:11])] == [4, 8]


def _register_boundary(idx, pool, prompt, tokens, blocks_per_layer=2):
    """Register `tokens` boundary of `prompt` with freshly-alloc'd blocks."""
    key = dict(idx.chain_keys(prompt))[tokens]
    L, H, M = pool.n_layers, 2, 4
    table = np.zeros((L, H, M), np.int32)
    lengths = np.zeros((L, H), np.int32)
    for l in range(L):
        ids = pool.alloc(l, blocks_per_layer * H)
        table[l, :, :blocks_per_layer] = np.asarray(ids).reshape(
            H, blocks_per_layer)
        lengths[l, :] = blocks_per_layer * idx.chunk_tokens
    assert idx.register(key, tokens, table, lengths)
    return idx._entries[key]


def test_lookup_longest_match_is_strict():
    pool = BlockPool(2, 64)
    idx = PrefixIndex(chunk_tokens=4)
    idx.pool = pool
    prompt = np.arange(20, dtype=np.int32)
    e4 = _register_boundary(idx, pool, prompt, 4)
    e8 = _register_boundary(idx, pool, prompt, 8)
    assert idx.lookup(prompt) is e8          # longest boundary wins
    assert idx.lookup(prompt[:8]) is e4      # strict: 8 == len -> not usable
    assert idx.lookup(prompt[:4]) is None    # nothing strictly shorter
    assert idx.lookup(prompt[::-1].copy()) is None  # different content
    assert idx.stats()["hits"] == 2 and idx.stats()["misses"] == 2
    # a hole in the chain (middle boundary evicted) must not stop the scan
    assert idx.lookup(prompt) is e8  # refreshes e8 -> e4 is now LRU
    assert idx.evict_lru()
    assert e8.key in idx._entries and len(idx) == 1
    assert idx.lookup(prompt) is e8


def test_register_increfs_and_evict_decrefs():
    pool = BlockPool(2, 64)
    idx = PrefixIndex(chunk_tokens=4)
    idx.pool = pool
    prompt = np.arange(12, dtype=np.int32)
    entry = _register_boundary(idx, pool, prompt, 8)
    held = entry.block_count()
    assert held == 2 * 2 * 2  # L * H * blocks_per_layer
    for l in range(2):
        ids = entry.table[l][entry.table[l] > 0]
        assert (pool.refcount[l, ids] == 2).all()  # alloc ref + index ref
    # duplicate registration is a refresh, not a second incref
    assert not idx.register(entry.key, 8, entry.table, entry.lengths)
    for l in range(2):
        ids = entry.table[l][entry.table[l] > 0]
        assert (pool.refcount[l, ids] == 2).all()
    # drop the alloc-time refs (donor retired), then evict: blocks free
    for l in range(2):
        pool.decref(l, entry.table[l][entry.table[l] > 0].tolist())
    assert idx.evict_lru()
    assert pool.blocks_in_use() == 0
    pool.check_invariants()


def test_eviction_respects_pins_and_flush_raises():
    pool = BlockPool(1, 64)
    idx = PrefixIndex(chunk_tokens=4, max_entries=2)
    idx.pool = pool
    prompt = np.arange(24, dtype=np.int32)
    e1 = _register_boundary(idx, pool, prompt, 4)
    idx.pin(e1)
    assert not idx.evict_lru()  # only entry is pinned
    _register_boundary(idx, pool, prompt, 8)
    _register_boundary(idx, pool, prompt, 12)  # over max_entries=2
    assert len(idx) == 2 and e1.key in idx._entries  # LRU victim was e2
    assert idx.stats()["evictions"] == 1
    with pytest.raises(RuntimeError):
        idx.flush()  # pinned entry still live
    idx.unpin(e1)
    with pytest.raises(ValueError):
        idx.unpin(e1)  # double-unpin
    idx.flush()
    assert len(idx) == 0
    pool.check_invariants()


def test_prefix_config_validation():
    with pytest.raises(ValueError):
        PrefixConfig(enabled=True, chunk_tokens=0)  # sharing needs chunking
    with pytest.raises(ValueError):
        _cfg(enabled=True, chunk=16).replace(cache_backend="slot")


# ---------------------------------------------------------------------------
# chunked prefill parity + TTFT accounting
# ---------------------------------------------------------------------------


def test_chunked_matches_monolithic_local(params):
    """Chunked prefill (prefix sharing off) is a pure re-chunking of the
    same math: identical tokens AND logits per request, including a prompt
    shorter than one chunk (monolithic fast path)."""
    vocab = _cfg().model.vocab_size
    rng = np.random.default_rng(3)
    reqs = [Request(req_id=i, prompt=rng.integers(1, vocab, size=t)
                    .astype(np.int32), arrival_step=a, max_new_tokens=5)
            for i, (t, a) in enumerate([(50, 0), (12, 1), (33, 2), (64, 4)])]
    mono = Engine.build(_cfg(), params=params)
    mono.run_trace(_clone(reqs), max_steps=400)
    chunked = Engine.build(_cfg(chunk=16), params=params)
    out = chunked.run_trace(reqs, max_steps=400)
    assert out["finished"] == out["total"]
    assert _tokens(mono) == _tokens(chunked)
    # logits agree to float32 reduction-order noise (chunked attention
    # accumulates per chunk); the sampled tokens are bitwise identical
    by_id = {r.req_id: r for r in mono.scheduler.finished}
    for r in chunked.scheduler.finished:
        for la, lb in zip(by_id[r.req_id].logits, r.logits):
            np.testing.assert_allclose(la, lb, rtol=1e-4, atol=1e-4)
    # every block returned once all requests retired
    assert chunked.scheduler.backend.pool.blocks_in_use() == 0
    chunked.scheduler.backend.pool.check_invariants()


def test_ttft_spans_all_prefill_chunks(params):
    """TTFT is measured from submission across *all* chunks: a 64-token
    prompt at chunk 16 takes 4 ticks to first token, vs 0 monolithic."""
    vocab = _cfg().model.vocab_size
    prompt = np.random.default_rng(5).integers(1, vocab, size=64)
    results = {}
    for name, cfg in [("mono", _cfg(rows=1)), ("chunked", _cfg(chunk=16,
                                                               rows=1))]:
        eng = Engine.build(cfg, params=params)
        r = Request(req_id=0, prompt=prompt.astype(np.int32),
                    max_new_tokens=4)
        eng.run_trace([r], max_steps=100)
        assert r.first_token_step is not None
        assert r.t_first is not None and r.ttft_seconds() > 0
        results[name] = r
    assert results["mono"].first_token_step == results["mono"].admit_step
    chunked = results["chunked"]
    # 64 tokens / 16-token chunks = 4 chunks, one per tick, first token
    # stamped when the last chunk finishes
    assert chunked.first_token_step - chunked.admit_step == 3
    assert chunked.ttft_steps() == 3
    assert results["mono"].generated == chunked.generated


# ---------------------------------------------------------------------------
# block sharing through the scheduler
# ---------------------------------------------------------------------------


def test_prefix_sharing_parity_with_observed_refcounts(params):
    """The acceptance-gate test: a shared-prefix trace through the
    prefix-enabled engine produces hits, drives pool_max_refcount > 1
    while requests are live, and generates exactly the tokens of a
    no-sharing chunked engine and a monolithic engine."""
    vocab = _cfg().model.vocab_size
    reqs = _shared_reqs(vocab)
    eng = Engine.build(_cfg(enabled=True, chunk=16), params=params)
    max_ref = 0
    for _ in eng.stream(_clone(reqs), max_steps=400):
        max_ref = max(max_ref, int(eng.scheduler.backend.pool.refcount.max()))
    sched = eng.scheduler
    assert all(r.is_finished for r in sched.finished)
    assert len(sched.finished) == len(reqs)
    st_ = eng.prefix_stats()
    assert st_["hits"] >= 1, st_
    assert st_["entries"] >= 1, st_
    assert max_ref > 1, "sharing never materialized (no refcount > 1)"
    # hit requests were stamped with their discount
    hit = [r for r in sched.finished if r.prefix_hit_tokens > 0]
    assert hit and all(r.prefix_shared_blocks.sum() > 0 for r in hit)
    sched.backend.pool.check_invariants()

    plain = Engine.build(_cfg(chunk=16), params=params)
    plain.run_trace(_clone(reqs), max_steps=400)
    assert _tokens(eng) == _tokens(plain)
    mono = Engine.build(_cfg(), params=params)
    mono.run_trace(_clone(reqs), max_steps=400)
    assert _tokens(eng) == _tokens(mono)

    # after every request retired, only the index holds blocks; flushing
    # it returns the pool to empty (conservation over the whole trace).
    # blocks_held is ref-weighted (nested boundary entries share blocks),
    # so compare in-use against the DISTINCT block set
    distinct = {(l, int(b)) for e in sched.prefix._entries.values()
                for l in range(e.table.shape[0])
                for b in e.table[l].ravel() if b > 0}
    assert sched.backend.pool.blocks_in_use() == len(distinct)
    assert len(distinct) <= st_["blocks_held"]
    sched.prefix.flush()
    assert sched.backend.pool.blocks_in_use() == 0
    sched.backend.pool.check_invariants()

    # §12 wiring: hit/miss counters and sharing gauges were exported
    m = eng.metrics()
    assert m["prefix_hits_total"]["series"][0]["value"] == st_["hits"]
    assert "prefix_shared_blocks" in m and "prefix_bytes_saved" in m


@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 10_000), frac=st.floats(0.3, 1.0))
def test_property_no_cross_request_corruption(seed, frac):
    """Random shared-prefix traces: sharing never changes any request's
    tokens (no cross-request corruption), and the pool survives intact."""
    cfg = _cfg(enabled=True, chunk=16)
    vocab = cfg.model.vocab_size
    reqs = synthesize_requests(6, 0.4, vocab, min_prompt=36, max_prompt=56,
                               max_new_tokens=5, seed=seed,
                               prefix_templates=2, prefix_len=32,
                               shared_fraction=frac)
    eng = Engine.build(cfg, params=_shared_params())
    out = eng.run_trace(reqs, max_steps=600)
    assert out["finished"] == out["total"]
    eng.scheduler.backend.pool.check_invariants()
    plain = Engine.build(_cfg(chunk=16), params=_shared_params())
    plain.run_trace(_clone(reqs), max_steps=600)
    assert _tokens(eng) == _tokens(plain)


def test_cow_privatizes_ring_wrap_writes(params):
    """Static capacity 64 (cap 32 + margin 32), ring 32, shared prefix 48
    tokens: once the donor's lengths hit capacity, its ring-wrap appends
    land inside the index-held prefix range (blocks 2-3 of 4) and MUST
    copy-on-write — writing in place would corrupt the registered entry.

    The proof is a LATE second request that hits the prefix only after the
    donor has wrapped: it stays below capacity (small gen), so its tokens
    are ring-phase independent and must equal the no-sharing engine's —
    which can only happen if the entry content survived the donor's
    overwrites bit-identically.  (Concurrent sharers can't be compared
    across engines once the ring wraps: chunk-count differences shift
    their decode phase — that head start IS the TTFT win.)"""
    cfg = _cfg(enabled=True, chunk=16, budget=32, margin=32, max_seq=128)
    vocab = cfg.model.vocab_size
    rng = np.random.default_rng(7)
    shared = rng.integers(1, vocab, size=48).astype(np.int32)
    sfx = [rng.integers(1, vocab, size=8).astype(np.int32) for _ in range(2)]
    reqs = [
        # donor: wraps (56 + 24 > 64) over its own registered blocks
        Request(req_id=0, prompt=np.concatenate([shared, sfx[0]]),
                arrival_step=0, max_new_tokens=24),
        # late hit: seeds from the entry after the donor's wrap-writes
        Request(req_id=1, prompt=np.concatenate([shared, sfx[1]]),
                arrival_step=40, max_new_tokens=6),
    ]
    eng = Engine.build(cfg, params=params)
    out = eng.run_trace(reqs, max_steps=400)
    assert out["finished"] == out["total"]
    backend = eng.scheduler.backend
    assert backend.cow_copies > 0, "trace never exercised copy-on-write"
    assert not backend._pending_cow  # every queued copy was flushed
    assert reqs[1].prefix_hit_tokens == 48  # the late request did share
    backend.pool.check_invariants()

    plain = Engine.build(_cfg(chunk=16, budget=32, margin=32, max_seq=128),
                         params=params)
    plain.run_trace(_clone(reqs), max_steps=400)
    assert _tokens(eng) == _tokens(plain)
    assert plain.scheduler.backend.cow_copies == 0  # nothing shared there


def test_cow_privatizes_quantized_scales(params):
    """The ring-wrap CoW scenario above with ``kv_dtype='int8'``: a shared
    quantized block privatized before a wrap append must copy the per-block
    *scale* entries along with the payload (DESIGN.md §15).

    Exact no-sharing parity (the fp32 oracle above) does not transfer: a
    seeded row shares the donor's codes and scales bit-for-bit, while a
    self-prefilled row quantizes its own block layout — same values,
    different grain, legitimately different rounding.  Two sharing-specific
    oracles replace it:

    - the *donor* still matches a quantized no-sharing engine token-for-
      token (its grain is self-prefilled in both) — so the privatized
      copies it decodes through carry the right codes AND scales;
    - the late sharer's tokens are invariant to whether the donor wrapped:
      a second sharing run whose donor stops before wrapping (no CoW at
      all) seeds the identical entry, so any divergence would mean the
      wrap run's CoW let the donor corrupt the registered codes or scales.
    """
    def sharing_run(donor_gen):
        cfg = _cfg(enabled=True, chunk=16, budget=32, margin=32, max_seq=128,
                   kv_dtype="int8")
        vocab = cfg.model.vocab_size
        rng = np.random.default_rng(7)
        shared = rng.integers(1, vocab, size=48).astype(np.int32)
        sfx = [rng.integers(1, vocab, size=8).astype(np.int32)
               for _ in range(2)]
        reqs = [
            Request(req_id=0, prompt=np.concatenate([shared, sfx[0]]),
                    arrival_step=0, max_new_tokens=donor_gen),
            Request(req_id=1, prompt=np.concatenate([shared, sfx[1]]),
                    arrival_step=40, max_new_tokens=6),
        ]
        eng = Engine.build(cfg, params=params)
        out = eng.run_trace(reqs, max_steps=400)
        assert out["finished"] == out["total"]
        assert reqs[1].prefix_hit_tokens == 48
        return eng, reqs

    wrap, wrap_reqs = sharing_run(donor_gen=24)  # 56 + 24 > 64: ring wraps
    backend = wrap.scheduler.backend
    assert backend.cow_copies > 0, "trace never exercised copy-on-write"
    assert not backend._pending_cow
    assert not backend._pending_scale_reset  # flushed with the copies
    # the state really is quantized storage with live scale pools
    cache = wrap.scheduler.state.cache
    assert cache.k_pool.dtype == jnp.int8
    assert cache.k_scale is not None and float(cache.k_scale.max()) > 0
    backend.pool.check_invariants()

    # donor oracle: same grain as a quantized no-sharing engine
    plain = Engine.build(_cfg(chunk=16, budget=32, margin=32, max_seq=128,
                              kv_dtype="int8"), params=params)
    plain.run_trace(_clone(wrap_reqs), max_steps=400)
    assert plain.scheduler.backend.cow_copies == 0
    assert _tokens(wrap)[0] == _tokens(plain)[0]

    # sharer oracle: identical seeded entry, donor never wraps
    nowrap, _ = sharing_run(donor_gen=2)
    assert nowrap.scheduler.backend.cow_copies == 0
    assert _tokens(wrap)[1] == _tokens(nowrap)[1]


def test_admission_discounts_shared_blocks():
    """Admission charges only unshared blocks for a stamped hit."""
    from repro.paging.backend import PagedBackend
    need = np.asarray([4, 4, 4], np.int64)
    req = Request(req_id=0, prompt=np.arange(8, dtype=np.int32))
    np.testing.assert_array_equal(
        PagedBackend._discount_shared(need, req), need)  # miss: full
    req.prefix_shared_blocks = np.asarray([3, 5, 0], np.int64)
    np.testing.assert_array_equal(
        PagedBackend._discount_shared(need, req), [1, 0, 4])


def test_shared_admission_fits_where_private_cannot(params):
    """Effective capacity: a pool sized so a single private 64-token
    prompt blocks the next admission supports overlapping requests when
    48 of those tokens are shared (the fig11 capacity claim, scaled to
    the smoke model)."""
    base = _cfg()
    vocab, H = base.model.vocab_size, base.model.n_kv_heads
    # admission charges ceil(64·H/16) + 2H = 6H blocks for a private
    # request; size the usable pool at 9H so one private request (5H live
    # after growth) starves the second (free 4H < 6H), while a 48-token
    # hit (discounted to 3H) still fits
    n_blocks = 9 * H + 1  # +1: block 0 is the null block

    def build(enabled):
        return Engine.build(_cfg(enabled=enabled, chunk=16,
                                 n_blocks=n_blocks, rows=4), params=params)

    reqs = _shared_reqs(vocab, shared_len=48, n_shared=4, suffix=16, gen=8,
                        spacing=4, seed=11)[:-1]  # drop the random req

    def peak_active(eng, reqs):
        peak = 0
        for _ in eng.stream(reqs, max_steps=600):
            sched = eng.scheduler
            peak = max(peak, len(sched.active) + len(sched.prefilling))
        assert all(r.is_finished for r in eng.scheduler.finished)
        return peak

    p_shared = peak_active(build(True), _clone(reqs))
    p_private = peak_active(build(False), _clone(reqs))
    assert p_shared > p_private, (p_shared, p_private)


# ---------------------------------------------------------------------------
# safe materialization of shared blocks
# ---------------------------------------------------------------------------


def test_materialize_and_migrate_conserve_shared_pool(params):
    """paged_to_slot is a pure gather (deep copy) and a migrate trial
    leaves the live pool untouched — with refcount > 1 blocks live."""
    vocab = _cfg().model.vocab_size
    reqs = _shared_reqs(vocab, gen=12)
    eng = Engine.build(_cfg(enabled=True, chunk=16), params=params)
    sched = eng._ensure_scheduler()
    it = eng.stream(reqs, max_steps=400)
    for _ in it:
        if int(sched.backend.pool.refcount.max()) > 1 and len(
                sched.active) >= 2 and not sched.prefilling:
            break
    backend = sched.backend
    assert int(backend.pool.refcount.max()) > 1  # sharing is live NOW
    ref0 = backend.pool.refcount.copy()
    in_use0 = backend.pool.blocks_in_use()
    table0 = backend.table.copy()

    slot = paged_to_slot(sched.state.cache, backend.capacity)
    # shared rows materialized identical content (same blocks gathered)
    shared_rows = sorted(sched.active)[:2]
    k = np.asarray(slot.k)
    lens = np.asarray(slot.lengths)
    for l in range(k.shape[0]):
        for s in range(k.shape[1]):
            n = int(min(lens[l, s, shared_rows[0]],
                        lens[l, s, shared_rows[1]], 48))
            if n > 0 and np.array_equal(
                    table0[l, s, shared_rows[0], :n // BS],
                    table0[l, s, shared_rows[1], :n // BS]):
                np.testing.assert_array_equal(
                    k[l, s, shared_rows[0], :n], k[l, s, shared_rows[1], :n])
    # the gather copied, never aliased or mutated, the pool
    np.testing.assert_array_equal(backend.pool.refcount, ref0)
    assert backend.pool.blocks_in_use() == in_use0
    np.testing.assert_array_equal(backend.table, table0)

    # a migrate *trial* (uncommitted — the hysteresis-rejected common case)
    # must also leave pool, refcounts, and mirror untouched
    rows = np.asarray(sorted(sched.active))
    lens2, _commit = backend.migrate_cache(sched.state.cache, sched.pa,
                                           sched.pa, active_rows=rows)
    np.testing.assert_array_equal(backend.pool.refcount, ref0)
    np.testing.assert_array_equal(backend.table, table0)
    backend.pool.check_invariants()
    # migration materialized every live row's full length
    np.testing.assert_array_equal(np.asarray(lens2), lens)

    for _ in it:  # drain to completion: sharing still winds down cleanly
        pass
    assert all(r.is_finished for r in sched.finished)
    sched.prefix.flush()
    assert backend.pool.blocks_in_use() == 0
    backend.pool.check_invariants()


# ---------------------------------------------------------------------------
# local / mesh chunked parity (multi-device subprocess)
# ---------------------------------------------------------------------------


SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, __SRC__)
import json
import numpy as np
from repro.api import (CompressionConfig, Engine, EngineConfig, PagingConfig,
                       PlannerConfig, PrefixConfig, SchedulerConfig)
from repro.launch.mesh import make_host_mesh
from repro.serving.request import Request


def cfg_for(executor, chunk):
    return EngineConfig.smoke(
        "minitron-8b", n_shards=4, max_seq_len=128,
        compression=CompressionConfig(policy="none", budget=96, capacity=96,
                                      decode_margin=8, obs_window=8),
        planner=PlannerConfig(mode="fairkv_dp", extra_copies=4, batch_cap=4),
        scheduler=SchedulerConfig(max_rows=4, enable_replan=False),
        cache_backend="paged", paging=PagingConfig(block_size=16),
        executor=executor,
        prefix=PrefixConfig(enabled=False, chunk_tokens=chunk))


def reqs_for(vocab):
    rng = np.random.default_rng(9)
    shared = rng.integers(1, vocab, size=32).astype(np.int32)
    out = []
    for i, (t, a) in enumerate([(52, 0), (14, 1), (37, 3), (64, 5)]):
        if t > 32:
            sfx = rng.integers(1, vocab, size=t - 32).astype(np.int32)
            prompt = np.concatenate([shared, sfx])
        else:  # shorter than one chunk: monolithic fast path on the mesh
            prompt = rng.integers(1, vocab, size=t).astype(np.int32)
        out.append(Request(req_id=i, prompt=prompt, arrival_step=a,
                           max_new_tokens=5))
    return out


loc = Engine.build(cfg_for("local", 16))
vocab = loc.cfg.model.vocab_size
out_l = loc.run_trace(reqs_for(vocab), max_steps=400)
mesh = make_host_mesh(model=4, data=2)
msh = Engine.build(cfg_for("mesh", 16), mesh=mesh, params=loc.params)
out_m = msh.run_trace(reqs_for(vocab), max_steps=400)
mono = Engine.build(cfg_for("local", 0), params=loc.params)
out_o = mono.run_trace(reqs_for(vocab), max_steps=400)
toks = [{r.req_id: list(r.generated) for r in e.scheduler.finished}
        for e in (loc, msh, mono)]
traces_after_first = msh.executor.prefill_chunk_traces
# a second identical trace must not add chunk-step compilations
msh2_reqs = reqs_for(vocab)
msh.run_trace(msh2_reqs, max_steps=400)
print(json.dumps({
    "all_finished": all(o["finished"] == o["total"]
                        for o in (out_l, out_m, out_o)),
    "mesh_eq_local": toks[0] == toks[1],
    "chunked_eq_mono": toks[0] == toks[2],
    "chunk_traces": traces_after_first,
    "chunk_traces_second_trace": msh.executor.prefill_chunk_traces,
}))
"""


def test_mesh_chunked_parity_multidevice_subprocess():
    """Chunked prefill on a 2x4 host mesh: tokens identical to the local
    executor and to monolithic prefill, with the chunk StepFn compiled a
    bounded number of times (fixed chunk width -> no per-chunk or
    per-trace recompiles)."""
    import repro
    src = list(repro.__path__)[0].rsplit("/repro", 1)[0]
    code = SUBPROC.replace("__SRC__", repr(src))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["all_finished"]
    assert rec["mesh_eq_local"], rec
    assert rec["chunked_eq_mono"], rec
    assert rec["chunk_traces"] <= 2, rec
    assert rec["chunk_traces_second_trace"] == rec["chunk_traces"], rec
