"""Slot-layout ops around the live paged cache: the strided owner mask,
row masks, and the head-layout gather / migrate a replan runs through.
The paged cache's own row ops (release, splice, append) are tested in
tests/test_paging.py."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache.slot_cache import (
    PlanArrays,
    SlotCache,
    gather_head_layout,
    init_cache,
    migrate_cache,
    rows_to_mask,
)
from repro.core import PlannerConfig, build_plan, synthetic_profile

L, B, CAP, DH = 2, 4, 6, 4


def _plan(mode="sha", n_heads=2, n_shards=4, slots=1, ch=0, seed=1):
    prof = synthetic_profile(L, n_heads, budget=8, skew=1.0, seed=seed)
    return build_plan(prof, n_shards,
                      PlannerConfig(mode=mode, slots_per_shard=slots,
                                    extra_copies=ch, batch_cap=B))


def _filled_cache(pa, rng_seed=0):
    """A cache with ownership-respecting random contents and lengths."""
    rng = np.random.default_rng(rng_seed)
    S = int(pa.slot_head.shape[1])
    cache = init_cache(L, S, B, CAP, DH, dtype=jnp.float32)
    own = np.asarray(pa.owner_mask_all(B))  # (L, S, B)
    lens = rng.integers(1, CAP, size=(L, S, B)).astype(np.int32) * own
    ent = np.arange(CAP)[None, None, None, :]
    valid = ent < lens[..., None]
    k = rng.normal(size=(L, S, B, CAP, DH)).astype(np.float32) * valid[..., None]
    v = rng.normal(size=(L, S, B, CAP, DH)).astype(np.float32) * valid[..., None]
    pos = np.where(valid, ent, -1).astype(np.int32)
    return SlotCache(k=jnp.asarray(k), v=jnp.asarray(v),
                     lengths=jnp.asarray(lens), pos=jnp.asarray(pos),
                     positions=jnp.asarray(lens.max(axis=(0, 1)), jnp.int32))


# ---------------------------------------------------------------------------
# owner_mask_rows
# ---------------------------------------------------------------------------


def test_owner_mask_rows_matches_global_rows():
    pa = PlanArrays.from_plan(_plan())  # 2 heads on 4 shards -> rc == 2
    full = np.asarray(pa.owner_mask(0, B))  # (S, B)
    sub = np.asarray(pa.owner_mask_rows(0, jnp.asarray([1, 3])))
    np.testing.assert_array_equal(sub, full[:, [1, 3]])
    # a replicated plan must disagree between row 0 and row 1 somewhere
    assert (full[:, 0] != full[:, 1]).any()


def test_owner_mask_all_matches_per_layer():
    pa = PlanArrays.from_plan(_plan(mode="fairkv_dp", n_heads=3, ch=4, slots=2))
    allm = np.asarray(pa.owner_mask_all(B))
    for l in range(L):
        np.testing.assert_array_equal(allm[l], np.asarray(pa.owner_mask(l, B)))


# ---------------------------------------------------------------------------
# row masks
# ---------------------------------------------------------------------------


def test_rows_to_mask_roundtrip():
    m = np.asarray(rows_to_mask(jnp.asarray([0, 3]), B))
    np.testing.assert_array_equal(m, [True, False, False, True])
    passthrough = rows_to_mask(jnp.asarray(m), B)
    np.testing.assert_array_equal(np.asarray(passthrough), m)


# ---------------------------------------------------------------------------
# gather / migrate (online replanning)
# ---------------------------------------------------------------------------


def test_gather_head_layout_inverts_ownership():
    pa = PlanArrays.from_plan(_plan(mode="fairkv_dp", n_heads=3, ch=4,
                                    slots=2))
    cache = _filled_cache(pa)
    k_h, v_h, len_h, pos_h = gather_head_layout(cache, pa)
    H = 3
    assert k_h.shape == (L, H, B, CAP, DH)
    # per (head, row): the owning slot's lengths match
    sh = np.asarray(pa.slot_head)
    own = np.asarray(pa.owner_mask_all(B))
    lens = np.asarray(cache.lengths)
    for l in range(L):
        for h in range(H):
            for b in range(B):
                owners = [s for s in range(sh.shape[1])
                          if sh[l, s] == h and own[l, s, b]]
                assert len(owners) == 1
                assert int(np.asarray(len_h)[l, h, b]) == lens[l, owners[0], b]


def test_migrate_cache_roundtrip_preserves_head_layout():
    """old plan -> new plan migration preserves the per-head contents."""
    plan_a = _plan(mode="sha")
    plan_b = _plan(mode="fairkv_dp", ch=4, seed=2)
    pa, pb = PlanArrays.from_plan(plan_a), PlanArrays.from_plan(plan_b)
    cache = _filled_cache(pa)
    orig = gather_head_layout(cache, pa)
    migrated = migrate_cache(cache, pa, pb)
    back = gather_head_layout(migrated, pb)
    for a, b in zip(orig, back):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    # positions carried through untouched
    np.testing.assert_array_equal(np.asarray(migrated.positions),
                                  np.asarray(cache.positions))
    # ownership respected in the new layout: unowned (slot, row) empty
    own_b = np.asarray(pb.owner_mask_all(B))
    lens_b = np.asarray(migrated.lengths)
    assert (lens_b[~own_b] == 0).all()


def test_migrate_cache_rejects_grid_mismatch():
    plan_a = _plan(n_shards=4)
    plan_b = _plan(n_shards=2)
    pa, pb = PlanArrays.from_plan(plan_a), PlanArrays.from_plan(plan_b)
    cache = _filled_cache(pa)
    with pytest.raises(ValueError):
        migrate_cache(cache, pa, pb)
