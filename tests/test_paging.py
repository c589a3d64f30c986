"""Paged KV backend: BlockPool invariants, paged-vs-slot decode parity
(bit-for-bit, property-tested over random placements/lengths), append
parity including the recency ring, and slot↔paged round-trips."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.cache.slot_cache import SlotCache, append_token
from repro.kernels.ref import fairkv_decode_ref, paged_fairkv_decode_ref
from repro.paging.block_pool import BlockPool, PoolExhausted
from repro.paging.paged_cache import (
    build_table,
    init_paged_cache,
    max_blocks_per_row,
    paged_append_token,
    paged_to_slot,
    paginate_rows,
)
from repro.paging.block_pool import PagingConfig

from tests._hypothesis_compat import given, settings, st


# ---------------------------------------------------------------------------
# BlockPool invariants
# ---------------------------------------------------------------------------


def test_pool_alloc_free_round_trip():
    pool = BlockPool(n_layers=2, n_blocks=8)
    assert pool.usable_blocks == 7
    ids = pool.alloc(0, 5)
    assert len(set(ids)) == 5 and 0 not in ids
    assert pool.free_blocks(0) == 2 and pool.free_blocks(1) == 7
    assert pool.blocks_in_use() == 5
    pool.decref(0, ids)
    assert pool.free_blocks(0) == 7 and pool.blocks_in_use() == 0
    # deterministic reuse: lowest ids first, same sequence after round-trip
    assert pool.alloc(0, 5) == ids
    pool.check_invariants()


def test_pool_refcount_never_negative():
    pool = BlockPool(n_layers=1, n_blocks=4)
    (b,) = pool.alloc(0, 1)
    pool.incref(0, [b])
    pool.decref(0, [b])  # refcount 2 -> 1: still allocated
    assert pool.free_blocks(0) == 2
    pool.decref(0, [b])  # 1 -> 0: freed
    assert pool.free_blocks(0) == 3
    with pytest.raises(ValueError, match="double free"):
        pool.decref(0, [b])
    with pytest.raises(ValueError, match="null block"):
        pool.decref(0, [0])
    with pytest.raises(ValueError):
        pool.incref(0, [b])  # unallocated
    pool.check_invariants()


def test_pool_exhaustion_is_atomic():
    pool = BlockPool(n_layers=1, n_blocks=4)
    pool.alloc(0, 2)
    free_before = pool.free_blocks(0)
    with pytest.raises(PoolExhausted):
        pool.alloc(0, 2)  # only 1 free
    assert pool.free_blocks(0) == free_before  # nothing handed out
    pool.check_invariants()


def test_build_table_rolls_back_on_exhaustion():
    # layer 1 cannot satisfy the request -> layer 0's allocations must be
    # returned (atomicity), leaving the pool exactly as before
    pool = BlockPool(n_layers=2, n_blocks=4)
    lengths = np.full((2, 1, 1), 10)  # needs 3 blocks/layer at bs=4
    pool.alloc(1, 2)  # leave layer 1 with 1 free
    free0 = pool.free_blocks(0)
    with pytest.raises(PoolExhausted):
        build_table(lengths, pool, block_size=4, max_blocks=3)
    assert pool.free_blocks(0) == free0
    pool.check_invariants()


# ---------------------------------------------------------------------------
# slot -> paged construction + decode parity (property test)
# ---------------------------------------------------------------------------


def _random_slot_layer(rng, S, B, C, Dh, L=2):
    """A SlotCache with random contents and random lengths; some (slot,
    row) pairs unowned (length 0), some full (length C)."""
    k = rng.normal(size=(L, S, B, C, Dh)).astype(np.float32)
    v = rng.normal(size=(L, S, B, C, Dh)).astype(np.float32)
    lengths = rng.integers(0, C + 1, size=(L, S, B)).astype(np.int32)
    lengths[:, 0] = 0  # an entirely-unowned slot
    if S > 1:
        lengths[:, 1] = C  # a full slot (ring regime)
    pos = np.broadcast_to(np.arange(C, dtype=np.int32), (L, S, B, C)).copy()
    pos[lengths[..., None] <= np.arange(C)] = -1
    return SlotCache(k=jnp.asarray(k), v=jnp.asarray(v),
                     lengths=jnp.asarray(lengths), pos=jnp.asarray(pos),
                     positions=jnp.full((B,), C, jnp.int32))


def _paginate(slot, bs, extra_tokens=0):
    """Slot cache -> (PagedCache, pool); blocks sized for lengths (+extra
    per-entry tokens so appends have a home, mimicking prepare_decode)."""
    L, S, B, C, Dh = slot.k.shape
    M = max_blocks_per_row(C, bs)
    paged, pool = init_paged_cache(L, S, B, C, Dh,
                                   PagingConfig(block_size=bs),
                                   dtype=slot.k.dtype)
    lens = np.asarray(slot.lengths)
    alloc_for = np.minimum(lens + extra_tokens, C)
    table = build_table(alloc_for, pool, bs, M, own=lens > 0)
    paged = paginate_rows(paged, slot, jnp.arange(B, dtype=jnp.int32), table)
    return paged, pool


@settings(max_examples=12)
@given(S=st.integers(2, 5), B=st.integers(1, 4), G=st.integers(1, 4),
       C=st.integers(5, 40), bs=st.integers(2, 16), seed=st.integers(0, 10))
def test_paged_decode_parity_bitwise(S, B, G, C, bs, seed):
    """Paged decode == slot decode, bit for bit, over random placements,
    lengths (owned and unowned rows), capacities, and block sizes."""
    Dh = 8
    rng = np.random.default_rng(seed)
    slot = _random_slot_layer(rng, S, B, C, Dh, L=1)
    paged, _ = _paginate(slot, bs)
    q = jnp.asarray(rng.normal(size=(B, S, G, Dh)), jnp.float32)
    qpos = jnp.full((B,), C + 3, jnp.int32)
    for window in (0, max(2, C // 2)):
        ref = fairkv_decode_ref(q, slot.k[0], slot.v[0], slot.lengths[0],
                                k_pos=slot.pos[0], q_pos=qpos, window=window)
        out = paged_fairkv_decode_ref(
            q, paged.k_pool[0], paged.v_pool[0], paged.pos_pool[0],
            paged.block_table[0], paged.lengths[0], C,
            q_pos=qpos, window=window)
        assert np.array_equal(np.asarray(ref), np.asarray(out)), (
            f"parity broke at window={window}")


@settings(max_examples=8)
@given(S=st.integers(2, 4), B=st.integers(1, 3), C=st.integers(6, 24),
       bs=st.integers(2, 8), steps=st.integers(1, 6), seed=st.integers(0, 10))
def test_paged_append_parity(S, B, C, bs, steps, seed):
    """Decode appends (including ring overwrites on full rows) produce the
    same lengths and the same valid-prefix contents as the slot cache."""
    Dh = 4
    ring = max(1, C // 3)
    rng = np.random.default_rng(100 + seed)
    slot = _random_slot_layer(rng, S, B, C, Dh, L=1)
    paged, _ = _paginate(slot, bs, extra_tokens=steps)
    own = np.asarray(slot.lengths[0]) > 0  # owned pairs only
    own_j = jnp.asarray(own)
    for t in range(steps):
        k_new = jnp.asarray(rng.normal(size=(S, B, Dh)), jnp.float32)
        v_new = jnp.asarray(rng.normal(size=(S, B, Dh)), jnp.float32)
        slot = append_token(slot, 0, k_new, v_new, own_j, jnp.int32(t),
                            ring=ring)
        paged = paged_append_token(paged, 0, k_new, v_new, own_j,
                                   jnp.int32(t), C, ring=ring)
    assert np.array_equal(np.asarray(slot.lengths), np.asarray(paged.lengths))
    back = paged_to_slot(paged, C)
    lens = np.asarray(slot.lengths[0])
    for s in range(S):
        for b in range(B):
            n = int(lens[s, b])
            np.testing.assert_array_equal(
                np.asarray(slot.k[0, s, b, :n]), np.asarray(back.k[0, s, b, :n]))
            np.testing.assert_array_equal(
                np.asarray(slot.v[0, s, b, :n]), np.asarray(back.v[0, s, b, :n]))
            np.testing.assert_array_equal(
                np.asarray(slot.pos[0, s, b, :n]),
                np.asarray(back.pos[0, s, b, :n]))


def test_round_trip_slot_paged_slot_exact():
    """slot → paged → slot preserves every valid entry, lengths, positions;
    masked (invalid) entries come back zeroed per the §2 contract."""
    rng = np.random.default_rng(7)
    S, B, C, Dh, bs = 4, 3, 20, 8, 8
    slot = _random_slot_layer(rng, S, B, C, Dh, L=2)
    paged, pool = _paginate(slot, bs)
    back = paged_to_slot(paged, C)
    assert np.array_equal(np.asarray(slot.lengths), np.asarray(back.lengths))
    assert np.array_equal(np.asarray(slot.positions),
                          np.asarray(back.positions))
    lens = np.asarray(slot.lengths)
    valid = np.arange(C)[None, None, None, :] < lens[..., None]
    np.testing.assert_array_equal(
        np.where(valid[..., None], np.asarray(slot.k), 0), np.asarray(back.k))
    # allocation is proportional to realized lengths (+1-block floor)
    expected = sum(-(-max(int(l), 1) // bs) for l in lens.reshape(-1) if l > 0)
    assert pool.blocks_in_use() == expected
    pool.check_invariants()


def test_unowned_rows_gather_zero_output():
    """A fully-unowned (length 0) paged row decodes to exactly zero — the
    §2 psum-reassembly contract carries over to the paged layout."""
    rng = np.random.default_rng(3)
    S, B, C, Dh, bs = 3, 2, 12, 8, 4
    slot = _random_slot_layer(rng, S, B, C, Dh, L=1)
    paged, _ = _paginate(slot, bs)
    q = jnp.asarray(rng.normal(size=(B, S, 2, Dh)), jnp.float32)
    out = paged_fairkv_decode_ref(
        q, paged.k_pool[0], paged.v_pool[0], paged.pos_pool[0],
        paged.block_table[0], paged.lengths[0], C)
    assert float(np.abs(np.asarray(out)[:, 0]).max()) == 0.0  # slot 0 unowned


# ---------------------------------------------------------------------------
# quantized pools (DESIGN.md §15): slot↔paged bit-consistency + migration
# ---------------------------------------------------------------------------


def _paginate_quant(slot, bs, kinds):
    """Quantized variant of `_paginate`: int8 pools + per-block scales,
    per-slot ``kinds`` ((L, S) int32) selecting int8 vs fp8 encoding."""
    from repro.paging.kvquant import KVQuantSpec
    L, S, B, C, Dh = slot.k.shape
    M = max_blocks_per_row(C, bs)
    paged, pool = init_paged_cache(
        L, S, B, C, Dh, PagingConfig(block_size=bs, kv_dtype="int8"),
        dtype=slot.k.dtype, kv_quant=KVQuantSpec(base="int8"))
    lens = np.asarray(slot.lengths)
    table = build_table(lens, pool, bs, M, own=lens > 0)
    paged = paginate_rows(paged, slot, jnp.arange(B, dtype=jnp.int32), table,
                          kinds=np.asarray(kinds, np.int32))
    return paged, pool


def test_quantized_paged_to_slot_matches_decode_bitwise():
    """`paged_to_slot` must dequantize through the same scale pool as the
    decode path: slot-ref attention over the materialized values equals
    paged-ref attention over the codes bit for bit — the invariant that
    keeps slot↔paged migration consistent with what decode saw (§15)."""
    rng = np.random.default_rng(11)
    S, B, C, Dh, bs, L = 4, 3, 20, 8, 8, 2
    slot = _random_slot_layer(rng, S, B, C, Dh, L=L)
    # mixed kinds: alternate int8 / fp8 per slot, varied per layer
    kinds = (np.add.outer(np.arange(L), np.arange(S)) % 2).astype(np.int32)
    paged, _ = _paginate_quant(slot, bs, kinds)
    assert paged.k_pool.dtype == jnp.int8 and paged.k_scale is not None
    back = paged_to_slot(paged, C, kinds=kinds)
    q = jnp.asarray(rng.normal(size=(B, S, 2, Dh)), jnp.float32)
    qpos = jnp.full((B,), C + 3, jnp.int32)
    for layer in range(L):
        ref = fairkv_decode_ref(q, back.k[layer], back.v[layer],
                                back.lengths[layer], k_pos=back.pos[layer],
                                q_pos=qpos)
        out = paged_fairkv_decode_ref(
            q, paged.k_pool[layer], paged.v_pool[layer],
            paged.pos_pool[layer], paged.block_table[layer],
            paged.lengths[layer], C, q_pos=qpos,
            k_scale=paged.k_scale[layer], v_scale=paged.v_scale[layer],
            kinds=jnp.asarray(kinds[layer]))
        assert np.array_equal(np.asarray(ref), np.asarray(out)), layer
    # dequantized values approximate the originals within codec tolerance
    lens = np.asarray(slot.lengths)
    valid = np.arange(C)[None, None, None, :] < lens[..., None]
    err = np.abs(np.where(valid[..., None], np.asarray(slot.k), 0)
                 - np.asarray(back.k))
    assert float(err.max()) < 0.35  # fp8 e4m3 worst-case block step


def test_migrate_quantized_cache_decode_parity():
    """Migrating a quantized cache (trial-commit through `migrate_cache`)
    re-paginates via full precision: the committed pools decode within
    codec tolerance of the originals — never double-quantized garbage,
    never int8 codes reinterpreted as model values (§15)."""
    from repro.api import (CompressionConfig, Engine, EngineConfig,
                           PagingConfig as PC, PlannerConfig, SchedulerConfig)
    from repro.serving.request import Request
    cfg = EngineConfig.smoke(
        "minitron-8b", n_shards=4, max_seq_len=64,
        compression=CompressionConfig(policy="none", budget=32, capacity=32,
                                      decode_margin=8, obs_window=8),
        planner=PlannerConfig(batch_cap=2),
        scheduler=SchedulerConfig(max_rows=2, enable_replan=False),
        cache_backend="paged",
        paging=PC(block_size=8, kv_dtype="int8"))
    eng = Engine.build(cfg)
    rng = np.random.default_rng(5)
    reqs = [Request(req_id=i,
                    prompt=rng.integers(1, cfg.model.vocab_size,
                                        size=24).astype(np.int32),
                    arrival_step=i, max_new_tokens=20) for i in range(2)]
    # stop mid-generation: finished rows are released (blocks freed), and
    # migrating an empty cache would make the parity check vacuous
    eng.run_trace(reqs, max_steps=8)
    backend = eng.scheduler.backend
    cache = eng.scheduler.state.cache
    assert cache.k_pool.dtype == jnp.int8
    assert int(np.asarray(cache.lengths).max()) > 0  # live quantized rows
    _, commit = backend.migrate_cache(cache, backend.pa, backend.pa)
    cand = commit()
    assert cand.k_pool.dtype == jnp.int8  # storage format survives
    kinds = np.asarray(
        np.take_along_axis(np.asarray(backend.kv_kinds, np.int32),
                           np.maximum(np.asarray(backend.pa.slot_head), 0),
                           axis=1))
    q = jnp.asarray(rng.normal(size=(2, cache.block_table.shape[1], 2,
                                     cfg.model.head_dim)), jnp.float32)
    qpos = jnp.full((2,), 60, jnp.int32)
    for layer in (0, cache.k_pool.shape[0] - 1):
        a = paged_fairkv_decode_ref(
            q, cache.k_pool[layer], cache.v_pool[layer],
            cache.pos_pool[layer], cache.block_table[layer],
            cache.lengths[layer], backend.capacity, q_pos=qpos,
            k_scale=cache.k_scale[layer], v_scale=cache.v_scale[layer],
            kinds=jnp.asarray(kinds[layer]))
        b = paged_fairkv_decode_ref(
            q, cand.k_pool[layer], cand.v_pool[layer],
            cand.pos_pool[layer], cand.block_table[layer],
            cand.lengths[layer], backend.capacity, q_pos=qpos,
            k_scale=cand.k_scale[layer], v_scale=cand.v_scale[layer],
            kinds=jnp.asarray(kinds[layer]))
        assert np.array_equal(np.asarray(cache.lengths[layer]),
                              np.asarray(cand.lengths[layer]))
        assert float(jnp.abs(a - b).max()) < 0.05, layer


# ---------------------------------------------------------------------------
# Admission splice: one jitted, donated program (DESIGN.md §9)
# ---------------------------------------------------------------------------


def _bits(x):
    """Raw bytes of an array, so float comparisons are bitwise."""
    return np.atleast_1d(np.asarray(x)).view(np.uint8)


@pytest.mark.parametrize("case", ["bf16", "int8", "shared_prefix"])
def test_jitted_splice_bitwise_equals_eager(case):
    """The donated splice program writes the live pools, scales, table,
    lengths, positions and last tokens bit for bit as the eager
    `paginate_rows` + `splice_state` do — for a bf16 pool, an int8 pool
    with mixed per-slot kinds, and a shared-prefix splice whose write
    table null-redirects the shared columns — and consumes the pre-splice
    buffers (it ran in place)."""
    from repro.paging.backend import _jitted, paged_splice
    from repro.paging.kvquant import KVQuantSpec
    from repro.serving.engine import ServeState, splice_state

    rng = np.random.default_rng(3)
    L, S, B, C, Dh, bs = 2, 4, 3, 20, 8, 8
    M = max_blocks_per_row(C, bs)
    quant = case == "int8"
    paging = PagingConfig(block_size=bs, n_blocks=96,
                          kv_dtype="int8" if quant else "fp32")
    kinds = ((np.add.outer(np.arange(L), np.arange(S)) % 2).astype(np.int32)
             if quant else None)

    def slot(batch):
        s = _random_slot_layer(rng, S, batch, C, Dh, L=L)
        return SlotCache(k=s.k.astype(jnp.bfloat16),
                         v=s.v.astype(jnp.bfloat16), lengths=s.lengths,
                         pos=s.pos, positions=s.positions)

    # a live cache holding every row, then a new request spliced into row 1
    live, pool = init_paged_cache(
        L, S, B, C, Dh, paging, dtype=jnp.bfloat16,
        kv_quant=KVQuantSpec(base="int8") if quant else None)
    full = slot(B)
    lens = np.asarray(full.lengths)
    live = paginate_rows(live, full, jnp.arange(B, dtype=jnp.int32),
                         build_table(lens, pool, bs, M, own=lens > 0),
                         kinds=kinds)
    sub = slot(1)
    sub_lens = np.asarray(sub.lengths)
    table = build_table(sub_lens, pool, bs, M, own=sub_lens > 0,
                        rows=np.asarray([1]), n_rows=B)
    table_write = table.copy()
    if case == "shared_prefix":  # column 0 maps donor blocks, never written
        table_write[..., 0] = 0
    rows = np.asarray([1], np.int32)
    state = ServeState(cache=live, ssm_state=None, conv_state=None,
                       cross_k=None, cross_v=None,
                       last_tokens=jnp.asarray([5, 6, 7], jnp.int32),
                       decode_steps=jnp.int32(4))
    sub_state = ServeState(cache=sub, ssm_state=None, conv_state=None,
                           cross_k=None, cross_v=None,
                           last_tokens=jnp.asarray([9], jnp.int32),
                           decode_steps=jnp.int32(0))

    eager = splice_state(state, sub_state, rows, cache=paginate_rows(
        live, sub, rows, table_write, table_store=table, kinds=kinds))
    # to the host first: eager passes leaves such as decode_steps through,
    # and the donated call consumes them
    want = [(path, np.asarray(x))
            for path, x in jax.tree_util.tree_leaves_with_path(eager)]
    jitted = _jitted(paged_splice, True)(state, sub_state, rows, table_write,
                                         table, kinds)
    assert live.k_pool.is_deleted() and live.v_pool.is_deleted()
    got = jax.tree_util.tree_leaves(jitted)
    assert len(want) == len(got) == (10 if quant else 8)
    for (path, a), b in zip(want, got):
        assert a.dtype == b.dtype and a.shape == b.shape, path
        assert np.array_equal(_bits(a), _bits(b)), path
    assert np.array_equal(np.asarray(jitted.cache.block_table)[:, :, 1],
                          table[:, :, 0])
    assert int(np.asarray(jitted.last_tokens)[1]) == 9


@pytest.mark.parametrize("donate", [True, False])
def test_splice_donation_follows_donate_state(donate, monkeypatch):
    """Through the engine: with ``donate_state`` the splice consumes the
    pre-splice pools (in place) and counts each admission under
    ``paged_splice_total{mode="inplace"}``; without it they stay readable
    and every admission counts under ``mode="copy"``."""
    from repro.api import (CompressionConfig, Engine, EngineConfig,
                           ExecutorConfig, PlannerConfig, SchedulerConfig)
    from repro.paging.backend import PagedBackend
    from repro.serving.request import Request

    cfg = EngineConfig.smoke(
        "minitron-8b", n_shards=4, max_seq_len=48,
        compression=CompressionConfig(policy="ada_snapkv", budget=12,
                                      alpha_max=2.0, obs_window=8, sink=2,
                                      decode_margin=8),
        planner=PlannerConfig(mode="fairkv_dp", extra_copies=4, batch_cap=2),
        scheduler=SchedulerConfig(max_rows=2, enable_replan=False),
        cache_backend="paged", paging=PagingConfig(block_size=8),
        executor_cfg=ExecutorConfig(donate_state=donate))
    eng = Engine.build(cfg)
    seen = []
    splice = PagedBackend.splice

    def spy(self, state, sub, rows, **kw):
        out = splice(self, state, sub, rows, **kw)
        pools = (state.cache.k_pool, state.cache.v_pool, state.cache.pos_pool)
        seen.append([p.is_deleted() for p in pools])
        if not donate:  # the pre-splice state is still whole
            np.asarray(state.cache.k_pool)
        return out

    monkeypatch.setattr(PagedBackend, "splice", spy)
    rng = np.random.default_rng(1)
    reqs = [Request(req_id=i, prompt=rng.integers(
        1, cfg.model.vocab_size, size=12 + 4 * i).astype(np.int32),
        arrival_step=i, max_new_tokens=3) for i in range(3)]
    out = eng.run_trace(reqs, max_steps=200)
    assert out["finished"] == out["total"] == 3
    assert eng.scheduler.backend.donate_state is donate
    assert len(seen) == 3  # one splice per admission
    assert all(all(d) if donate else not any(d) for d in seen), seen
    m = eng.obs.metrics
    assert m.counter_value("sched_admissions_total") == 3
    mode, other = ("inplace", "copy") if donate else ("copy", "inplace")
    assert m.counter_value("paged_splice_total", mode=mode) == 3
    assert m.counter_value("paged_splice_total", mode=other) == 0
