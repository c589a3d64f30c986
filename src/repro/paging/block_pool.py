"""Host-side block allocator for the paged KV backend (DESIGN.md §9).

The device-side cache is a per-layer pool of fixed-size K/V blocks
(``paged_cache.PagedCache``); this module owns the *topology*: which blocks
of each layer's pool are free, and how many references each allocated block
holds.  Allocation decisions are host-side Python (the scheduler runs on the
host anyway), while the arrays the decisions describe live on device — the
same split vLLM uses between its block manager and its paged attention
kernel.

Block id 0 of every layer is the reserved **null block**: block-table entries
that point nowhere hold 0, and masked writes (unowned rows, unallocated
slots) are redirected into it, so a scatter never needs data-dependent shape
logic.  The null block's contents are garbage by design; every read path
masks by retained length before the garbage can surface.

Refcounts exist so a future copy-on-write fork (shared-prefix requests) can
reuse blocks; today every block has refcount 1 while allocated.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, List, Optional

import numpy as np

from repro.obs import NULL_OBS


class PoolExhausted(RuntimeError):
    """Raised when an allocation cannot be satisfied.

    The scheduler treats this as a *preemption signal*, not an error: it
    frees the youngest active request back to QUEUED and retries — the pool
    never hands out a block it does not have, so exhaustion can never
    corrupt live cache contents.
    """


@dataclass(frozen=True)
class PagingConfig:
    """Knobs for the paged KV cache (the serving stack's one cache store).

    ``block_size``: tokens per K/V block (per slot-row, per layer).
    ``n_blocks``: per-layer pool size *including* the reserved null block;
    0 sizes the pool to the dense worst case (every (slot, row) fully
    allocated) so nothing can ever be preempted — the default, a
    correctness mode.  Undersize it deliberately to trade preemptions for
    HBM (the fig7 benchmark's equal-HBM comparison).
    ``decode_impl``: the paged decode-attention implementation
    (``kernels.ops.PAGED_DECODE_IMPLS``): "pallas" is the native
    block-table kernel (HBM traffic proportional to allocated blocks,
    DESIGN.md §11), "jnp" is the pure-jnp oracle it is tested against, and
    "auto" (default) picks pallas on TPU and jnp elsewhere.  Validated here
    at construction (`EngineConfig` composes this config), so a typo fails
    before any StepFn traces.
    ``kv_dtype``: KV pool storage format (DESIGN.md §15) — "fp32" (the
    default: pools in the engine dtype, no quantization, bit-identical to
    pre-quantization behavior), "int8", or "fp8" (requires a jax with
    float8_e4m3fn).  Quantized pools carry parallel per-block scale pools
    and dequantize in the decode inner loop.
    ``kv_dtype_overrides``: per-(layer, head) format overrides — a mapping
    ``{(layer, head): "int8"|"fp8"}`` (or the equivalent tuple of triples),
    the planner's per-head precision axis; only meaningful when
    ``kv_dtype`` is quantized.
    ``pool_hbm_bytes``: size the per-layer pool from an HBM byte budget
    instead of a block count (mutually exclusive with ``n_blocks > 0``) —
    the bytes-aware admission knob: at the same byte budget an int8 pool
    holds ~2x the blocks of an fp32-equivalent pool, so admission
    (block-count based) automatically admits ~2x the tokens.
    """

    block_size: int = 16
    n_blocks: int = 0
    decode_impl: str = "auto"
    kv_dtype: str = "fp32"
    kv_dtype_overrides: tuple = ()
    pool_hbm_bytes: int = 0

    def __post_init__(self):
        if self.block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {self.block_size}")
        if self.n_blocks < 0:
            raise ValueError(f"n_blocks must be >= 0, got {self.n_blocks}")
        from repro.kernels.ops import PAGED_DECODE_IMPLS
        if self.decode_impl not in PAGED_DECODE_IMPLS:
            raise ValueError(
                f"unknown decode_impl {self.decode_impl!r}; known: "
                f"{list(PAGED_DECODE_IMPLS)}")
        from repro.paging import kvquant
        if self.kv_dtype not in kvquant.KV_DTYPES:
            raise ValueError(
                f"unknown kv_dtype {self.kv_dtype!r}; known: "
                f"{list(kvquant.KV_DTYPES)}")
        if self.kv_dtype == "fp8" and not kvquant.fp8_supported():
            raise ValueError(
                "kv_dtype='fp8' requires a jax with float8_e4m3fn support")
        # canonicalize the override map to a sorted hashable tuple (the
        # frozen dataclass must stay usable as a static jit argument)
        ov = self.kv_dtype_overrides
        if isinstance(ov, dict):
            ov = tuple((lh[0], lh[1], dt) for lh, dt in ov.items())
        ov = tuple(sorted((int(l), int(h), str(dt)) for l, h, dt in ov))
        object.__setattr__(self, "kv_dtype_overrides", ov)
        if ov and self.kv_dtype == "fp32":
            raise ValueError(
                "kv_dtype_overrides require a quantized base kv_dtype")
        for l, h, dt in ov:
            if dt not in kvquant.QUANT_DTYPES:
                raise ValueError(
                    f"kv_dtype override ({l}, {h}) -> {dt!r}: must be one "
                    f"of {list(kvquant.QUANT_DTYPES)}")
            if dt == "fp8" and not kvquant.fp8_supported():
                raise ValueError(
                    f"kv_dtype override ({l}, {h}) -> 'fp8' requires a jax "
                    "with float8_e4m3fn support")
            if l < 0 or h < 0:
                raise ValueError(
                    f"kv_dtype override ({l}, {h}): indices must be >= 0")
        if self.pool_hbm_bytes < 0:
            raise ValueError(
                f"pool_hbm_bytes must be >= 0, got {self.pool_hbm_bytes}")
        if self.pool_hbm_bytes and self.n_blocks:
            raise ValueError(
                "pool_hbm_bytes and n_blocks are mutually exclusive pool "
                "sizing modes; set exactly one (or neither for worst-case)")


def blocks_for_tokens(tokens: int, block_size: int) -> int:
    """ceil(tokens / block_size) — blocks needed to hold ``tokens`` entries."""
    return -(-int(tokens) // int(block_size))


class BlockPool:
    """Free-list + refcounts over each layer's block pool.

    Deterministic: blocks are handed out lowest-id-first per layer, so
    identical request traces produce identical block tables (mirrors the
    scheduler's lowest-row-first freelist).

    ``n_partitions > 1`` splits every layer's pool into equal contiguous
    partitions with *independent* free lists — the mesh executor's layout
    (DESIGN.md §10), where partition ``p`` is the slice of the pool that
    physically lives on model shard ``p`` and only blocks of that partition
    may back the shard's slots.  Every partition reserves its local block 0
    (global id ``p · part_size``) as a null block, so a shard-local view of
    the pool keeps the null-redirect convention.  Block ids remain *global*
    everywhere on the host; the partition of an id is ``id // part_size``.
    """

    def __init__(self, n_layers: int, n_blocks: int, n_partitions: int = 1):
        if n_partitions < 1:
            raise ValueError(f"n_partitions must be >= 1, got {n_partitions}")
        if n_blocks % n_partitions:
            raise ValueError(
                f"{n_blocks} blocks/layer do not split into "
                f"{n_partitions} equal partitions")
        part = n_blocks // n_partitions
        if part < 2:
            raise ValueError(
                f"need >= 2 blocks per partition (1 null + 1 usable), got "
                f"{part} ({n_blocks} blocks / {n_partitions} partitions)")
        self.n_layers = int(n_layers)
        self.n_blocks = int(n_blocks)
        self.n_partitions = int(n_partitions)
        self.part_size = part
        # observability handle (DESIGN.md §12): alloc/free/exhaustion
        # counters; the owning backend swaps in the engine's live Obs
        self.obs = NULL_OBS
        nulls = [p * part for p in range(n_partitions)]
        self.refcount = np.zeros((n_layers, n_blocks), np.int32)
        self.refcount[:, nulls] = 1  # null blocks: pinned forever
        # descending so list.pop() returns the lowest free id
        self._free: List[List[List[int]]] = [
            [list(range((p + 1) * part - 1, p * part, -1))
             for p in range(n_partitions)]
            for _ in range(n_layers)]

    # ---- introspection -----------------------------------------------------

    def free_blocks(self, layer: Optional[int] = None,
                    partition: Optional[int] = None):
        """Free count for one layer (summed over partitions unless one is
        named), or (L,) array for all layers."""
        if layer is not None:
            if partition is not None:
                return len(self._free[layer][partition])
            return sum(len(f) for f in self._free[layer])
        return np.asarray([sum(len(f) for f in fs) for fs in self._free],
                          np.int64)

    def free_blocks_by_partition(self) -> np.ndarray:
        """(L, n_partitions) free counts."""
        return np.asarray([[len(f) for f in fs] for fs in self._free],
                          np.int64)

    def blocks_in_use(self) -> int:
        """Total allocated blocks across layers (null blocks excluded)."""
        usable = self.n_layers * self.usable_blocks
        return int(usable - int(self.free_blocks().sum()))

    @property
    def usable_blocks(self) -> int:
        """Allocatable blocks per layer (null blocks are never handed out)."""
        return self.n_blocks - self.n_partitions

    def partition_of(self, block_id: int) -> int:
        return int(block_id) // self.part_size

    def sample_gauges(self, metrics) -> None:
        """Record the pool-pressure gauges (DESIGN.md §12): free/in-use
        totals, per-partition free counts, max refcount, and fragmentation
        — free blocks stranded outside each layer's *tightest* partition.
        Admission gates on the worst partition, so stranded blocks are free
        yet unusable for the next admission."""
        free = self.free_blocks_by_partition()  # (L, P)
        metrics.gauge(
            "pool_free_blocks",
            help="free KV blocks, summed over layers and partitions"
        ).set(int(free.sum()))
        metrics.gauge(
            "pool_blocks_in_use",
            help="allocated KV blocks across all layers (nulls excluded)"
        ).set(self.blocks_in_use())
        g = metrics.gauge(
            "pool_free_blocks_partition",
            help="free KV blocks per pool partition (one partition per "
                 "(model shard, data shard) pair), summed over layers")
        for p, v in enumerate(free.sum(axis=0)):
            g.set(int(v), partition=str(p))
        metrics.gauge(
            "pool_fragmentation_blocks",
            help="free blocks outside each layer's tightest partition — "
                 "free but unusable for the admission the tightest "
                 "partition is about to refuse"
        ).set(int((free - free.min(axis=1, keepdims=True)).sum()))
        metrics.gauge(
            "pool_max_refcount",
            help="max block refcount (copy-on-write sharing depth; 1 = "
                 "no sharing)"
        ).set(int(self.refcount.max()))

    # ---- alloc / free ------------------------------------------------------

    def alloc(self, layer: int, n: int, partition: int = 0) -> List[int]:
        """Allocate ``n`` blocks in ``layer``'s ``partition`` (refcount 1
        each); returned ids are global.

        Atomic: raises ``PoolExhausted`` without handing out anything if the
        partition has fewer than ``n`` free blocks.
        """
        free = self._free[layer][partition]
        if n > len(free):
            self.obs.metrics.counter(
                "pool_exhausted_total",
                help="allocations refused by an empty free list (the "
                     "scheduler's preemption signal)").inc()
            raise PoolExhausted(
                f"layer {layer} partition {partition}: requested {n} "
                f"blocks, {len(free)} free "
                f"(pool {self.usable_blocks}/layer)")
        ids = [free.pop() for _ in range(n)]
        self.refcount[layer, ids] = 1
        self.obs.metrics.counter(
            "pool_alloc_blocks_total",
            help="KV blocks handed out by the pool").inc(n)
        return ids

    def incref(self, layer: int, ids: Iterable[int]) -> None:
        for b in ids:
            if self.refcount[layer, b] < 1:
                raise ValueError(f"incref of unallocated block {b} "
                                 f"in layer {layer}")
            self.refcount[layer, b] += 1

    def decref(self, layer: int, ids: Iterable[int]) -> None:
        """Drop one reference per id; blocks reaching 0 return to their
        partition's free list.  Refcounts can never go negative:
        over-freeing raises."""
        freed: List[int] = []
        for b in ids:
            b = int(b)
            if b % self.part_size == 0:
                raise ValueError(f"null block {b} cannot be freed")
            rc = int(self.refcount[layer, b])
            if rc <= 0:
                raise ValueError(
                    f"double free: block {b} of layer {layer} has "
                    f"refcount {rc}")
            self.refcount[layer, b] = rc - 1
            if rc == 1:
                freed.append(b)
        if freed:
            self.obs.metrics.counter(
                "pool_freed_blocks_total",
                help="KV blocks returned to the pool "
                     "(refcount reached 0)").inc(len(freed))
            for p in {self.partition_of(b) for b in freed}:
                fl = self._free[layer][p]
                fl.extend(b for b in freed if self.partition_of(b) == p)
                fl.sort(reverse=True)  # lowest-id-first via pop()

    def free_table(self, table: np.ndarray) -> None:
        """Decref every nonzero entry of an (L, ..., M) id table slice."""
        for layer in range(self.n_layers):
            ids = table[layer].reshape(-1)
            ids = ids[ids > 0]
            if ids.size:
                self.decref(layer, ids.tolist())

    def clone(self) -> "BlockPool":
        """Deep copy — used to *trial* a migration before committing."""
        out = BlockPool.__new__(BlockPool)
        out.n_layers, out.n_blocks = self.n_layers, self.n_blocks
        out.n_partitions, out.part_size = self.n_partitions, self.part_size
        out.obs = self.obs
        out.refcount = self.refcount.copy()
        out._free = [[list(f) for f in fs] for fs in self._free]
        return out

    def check_invariants(self) -> None:
        """Debug/test hook: free lists and refcounts partition the pool."""
        nulls = {p * self.part_size for p in range(self.n_partitions)}
        for layer in range(self.n_layers):
            free = set()
            for p, fl in enumerate(self._free[layer]):
                assert all(self.partition_of(b) == p for b in fl), (
                    f"layer {layer}: foreign id in partition {p} free list")
                free.update(fl)
            n_free = sum(len(f) for f in self._free[layer])
            assert not (free & nulls), "null block leaked into a free list"
            assert len(free) == n_free, "duplicate free ids"
            for b in range(self.n_blocks):
                if b in nulls:
                    continue
                rc = int(self.refcount[layer, b])
                assert rc >= 0, f"negative refcount {rc}"
                assert (b in free) == (rc == 0), (
                    f"layer {layer} block {b}: refcount {rc} but "
                    f"{'free' if b in free else 'allocated'}")
