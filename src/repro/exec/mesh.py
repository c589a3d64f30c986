"""`MeshExecutor`: the StepFns under ``shard_map`` on a (data, model) mesh.

This is the execution path that makes Fair-Copying *physical* (DESIGN.md
§10): the slot dim — slot-layout attention weights, the slot cache, and the
paged backend's block tables and pools — shards over the ``model`` axis, so
each model shard owns exactly the head replicas the planner placed on it;
batch rows shard over ``data``, and replicas of one head split those rows
by the strided owner rule evaluated at *global* row ids.  Each (head, row)
pair then has exactly one owning slot somewhere on the mesh, so the decode
o-projection's per-shard partial contractions psum to the full batch — the
step's single collective.

Decode runs fully local otherwise: per-slot attention, cache appends, MLP
and unembed (replicated weights, batch-sharded rows).  Prefill runs in
original head layout, which needs every head's replica-0 weights — those
are all-gathered over ``model`` per layer (cheap next to prompt attention),
while the compression selection and per-slot cache fill stay local.
Prefill's non-cache outputs are replicated over ``model`` by construction
(identical math from identical gathered inputs), which shard_map's static
varying-manual-axes checker cannot prove — hence ``check_vma=False``
there.

Paged backend: the pool shards over ``model`` into per-shard partitions;
the partition-aware allocator (`repro.paging.block_pool.BlockPool` with
``n_partitions > 1``) guarantees a slot's blocks live in its shard's
partition, and the decode step localizes the stored global block ids by
subtracting the partition offset (`serving.engine._decode_attention`).

Constraints (checked at construction / call time): dense decoder-only
attention models, unquantized weights, ``n_slots`` divisible by the
model-axis size, decode batch divisible by the data-axis size (prefill
pads sub-batches automatically — continuous admission prefills one
request at a time).  MoE is excluded: its capacity-bounded dispatch sizes
expert capacity from the *global* token count (``models/moe.py``), so a
data-sharded batch changes drop behavior — supporting it needs expert
parallelism or per-shard capacity scaling, not replication.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.api.registry import register_executor
from repro.cache.slot_cache import PlanArrays, SlotCache
from repro.exec.base import Executor
from repro.paging.paged_cache import PagedCache
from repro.serving import engine as _serve

_FAMILIES = ("dense",)


@register_executor("mesh")
class MeshExecutor(Executor):
    name = "mesh"

    def __init__(self, model_cfg, ccfg, exec_cfg=None, mesh=None,
                 paging=None, obs=None):
        super().__init__(model_cfg, ccfg, exec_cfg=exec_cfg, mesh=mesh,
                         paging=paging, obs=obs)
        if mesh is None:
            raise ValueError(
                "executor='mesh' needs a mesh; build one with "
                "repro.launch.mesh.make_host_mesh(model=..., data=...) and "
                "pass it via Engine.build(..., mesh=...)")
        ec = self.exec_cfg
        for ax in (ec.data_axis, ec.model_axis):
            if ax not in mesh.axis_names:
                raise ValueError(
                    f"mesh axes {mesh.axis_names} do not include "
                    f"{ax!r}; ExecutorConfig names axes "
                    f"({ec.data_axis!r}, {ec.model_axis!r})")
        if model_cfg.family not in _FAMILIES:
            raise NotImplementedError(
                f"mesh executor supports dense decoder-only attention "
                f"models, got family {model_cfg.family!r} "
                f"({model_cfg.name}); use executor='local' (moe needs "
                f"expert parallelism: capacity-bounded dispatch is global-"
                f"batch dependent)")
        self.data_size = int(mesh.shape[ec.data_axis])
        self.model_size = int(mesh.shape[ec.model_axis])
        # memoized (shard_map + jit) StepFns keyed by arg structure
        self._prefill_jits = {}
        self._prefill_chunk_jits = {}
        self._decode_jits = {}
        self._propose_jits = {}
        self._verify_jits = {}

    @property
    def pool_partitions(self) -> int:
        return self.model_size

    @property
    def row_partitions(self) -> int:
        return self.data_size

    # ---- partition specs ---------------------------------------------------

    def _check_quant(self, sp):
        from repro.serving.quant import QTensor
        leaves = jax.tree.leaves(
            sp, is_leaf=lambda t: isinstance(t, QTensor))
        if any(isinstance(t, QTensor) for t in leaves):
            raise NotImplementedError(
                "mesh executor does not support quantized slot weights yet")

    def _sp_specs(self, sp):
        """Slot-layout leaves (dict key '*_s', slot dim leading) shard over
        model; everything else — embeddings, norms, MLP/MoE weights, the
        unembed table — is replicated (batch rows carry the data axis)."""
        m = self.exec_cfg.model_axis

        def leaf_spec(path, leaf):
            key = getattr(path[-1], "key", None)
            if isinstance(key, str) and key.endswith("_s"):
                return P(m, *([None] * (leaf.ndim - 1)))
            return P()

        return jax.tree_util.tree_map_with_path(leaf_spec, sp)

    def _pa_specs(self):
        m = self.exec_cfg.model_axis
        # first_slot holds *global* slot ids (prefill's replica-0 gather) —
        # it stays replicated while the (L, S) arrays shard over model
        return PlanArrays(slot_head=P(None, m), replica_idx=P(None, m),
                          replica_count=P(None, m), first_slot=P())

    def _cache_specs(self, cache):
        d, m = self.exec_cfg.data_axis, self.exec_cfg.model_axis
        if isinstance(cache, PagedCache):
            # the pool splits over BOTH axes: blocks of (slot, row) live on
            # the (slot's model shard, row's data shard) device, so appends
            # and gathers stay device-local (module docstring)
            n_dev = self.model_size * self.data_size
            if cache.n_blocks % n_dev:
                raise ValueError(
                    f"paged pool of {cache.n_blocks} blocks/layer does not "
                    f"split over model x data = {n_dev} devices; the "
                    f"backend must be built with pool_partitions="
                    f"{self.model_size}, row_partitions={self.data_size}")
            # quantized pools carry (L, N) per-block scale arrays that shard
            # over the same (model, data) split of the block axis as the
            # payload pools (DESIGN.md §15); None on the fp32 path keeps the
            # pytree structure matching
            scale = P(None, (m, d)) if cache.k_scale is not None else None
            return PagedCache(
                k_pool=P(None, (m, d)), v_pool=P(None, (m, d)),
                pos_pool=P(None, (m, d)),
                block_table=P(None, m, d), lengths=P(None, m, d),
                positions=P(d), k_scale=scale, v_scale=scale)
        return SlotCache(k=P(None, m, d), v=P(None, m, d),
                         lengths=P(None, m, d), pos=P(None, m, d),
                         positions=P(d))

    def _state_specs(self, state):
        d = self.exec_cfg.data_axis
        return _serve.ServeState(
            cache=self._cache_specs(state.cache),
            ssm_state=None, conv_state=None, cross_k=None, cross_v=None,
            last_tokens=P(d), decode_steps=P())

    def _check_grid(self, pa):
        S = int(pa.slot_head.shape[1])
        if S % self.model_size:
            raise ValueError(
                f"{S} slots do not split over model={self.model_size}; "
                f"plan with n_shards (or slots_per_shard) a multiple of "
                f"the mesh model-axis size")

    # ---- prefill -----------------------------------------------------------

    def _build_prefill(self, sp_specs, state_specs, has_hi):
        cfg, ccfg = self.cfg, self.ccfg
        ec = self.exec_cfg

        def prefill_step(sp, batch, pa, rows, head_importance):
            self.prefill_traces += 1  # runs at trace time only
            return _serve.prefill(sp, batch, cfg, pa, ccfg,
                                  head_importance=head_importance, rows=rows,
                                  model_axis=ec.model_axis)

        d = ec.data_axis
        fn = jax.shard_map(
            prefill_step, mesh=self.mesh,
            in_specs=(sp_specs, {"tokens": P(d)}, self._pa_specs(), P(d),
                      P() if has_hi else None),
            out_specs=(state_specs, P(d), P(None, None, d)),
            # non-cache outputs are replicated over model by construction
            # (identical math from all-gathered weights); not statically
            # provable, so the vma checker is off here (module docstring)
            check_vma=False)
        return jax.jit(fn)

    def prefill(self, sp, batch, pa, rows=None, head_importance=None):
        self._check_quant(sp)
        self._check_grid(pa)
        tokens = batch["tokens"]
        B = int(tokens.shape[0])
        if set(batch) != {"tokens"}:
            raise NotImplementedError(
                f"mesh prefill supports token prompts, got batch keys "
                f"{sorted(batch)}")
        if rows is None:
            rows = jnp.arange(B, dtype=jnp.int32)
        rows = jnp.asarray(rows, jnp.int32)
        # pad the sub-batch up to the data-axis width (continuous admission
        # prefills one request at a time); padded rows reuse the last real
        # row id — their output is sliced off before anything consumes it
        pad = (-B) % self.data_size
        if pad:
            tokens = jnp.concatenate(
                [tokens, jnp.zeros((pad, tokens.shape[1]), tokens.dtype)])
            rows = jnp.concatenate([rows, jnp.repeat(rows[-1:], pad)])
        hi = None if head_importance is None else jnp.asarray(head_importance)

        # a template state fixes the out-spec structure (always slot layout)
        state_specs = _serve.ServeState(
            cache=self._cache_specs(SlotCache(None, None, None, None, None)),
            ssm_state=None, conv_state=None, cross_k=None, cross_v=None,
            last_tokens=P(self.exec_cfg.data_axis), decode_steps=P())
        sp_specs = self._sp_specs(sp)
        key = (jax.tree.structure(sp_specs), hi is not None)
        if key not in self._prefill_jits:
            self._prefill_jits[key] = self._build_prefill(
                sp_specs, state_specs, hi is not None)
        args = (sp, {"tokens": tokens}, pa, rows, hi)
        self._count_prefill_attn()
        if self.obs.enabled:
            state, logits, lengths = self._observe_step(
                "prefill", self._prefill_jits[key], args)
        else:
            state, logits, lengths = self._prefill_jits[key](*args)
        if pad:
            state = _slice_state_rows(state, B)
            logits, lengths = logits[:B], lengths[..., :B]
        return state, logits, lengths

    # ---- chunked prefill (DESIGN.md §14) -----------------------------------

    def _build_prefill_chunk(self, sp_specs, state_specs, has_hi):
        cfg, ccfg = self.cfg, self.ccfg
        ec = self.exec_cfg

        def prefill_chunk_step(sp, tokens, pa, state, rows, start, valid,
                               quota, head_importance):
            self.prefill_chunk_traces += 1  # runs at trace time only
            return _serve.prefill_chunk(sp, tokens, cfg, pa, ccfg, state,
                                        rows, start, valid, quota,
                                        head_importance=head_importance,
                                        model_axis=ec.model_axis)

        d = ec.data_axis
        fn = jax.shard_map(
            prefill_chunk_step, mesh=self.mesh,
            in_specs=(sp_specs, P(d), self._pa_specs(), state_specs, P(d),
                      P(d), P(d), P(), P() if has_hi else None),
            out_specs=(state_specs, P(d), P(None, None, d)),
            # chunk attention all-gathers the cache over model; non-cache
            # outputs are replicated by construction (same as prefill)
            check_vma=False)
        donate = (3,) if ec.donate_state else ()
        return jax.jit(fn, donate_argnums=donate)

    def prefill_chunk(self, sp, tokens, pa, state, rows, start, valid, quota,
                      head_importance=None):
        self._check_quant(sp)
        self._check_grid(pa)
        if not isinstance(state.cache, SlotCache):
            raise NotImplementedError(
                "mesh chunked prefill accumulates into a slot-layout "
                "sub-state (pagination happens at splice)")
        tokens = jnp.asarray(tokens, jnp.int32)
        B = int(tokens.shape[0])
        rows = jnp.asarray(rows, jnp.int32)
        start = jnp.asarray(start, jnp.int32)
        valid = jnp.asarray(valid, jnp.int32)
        # pad the sub-batch up to the data-axis width; padded rows repeat
        # the last real row with valid=0, so they select nothing and their
        # state columns are sliced off before anything consumes them
        pad = (-B) % self.data_size
        if pad:
            tokens = jnp.concatenate(
                [tokens, jnp.zeros((pad, tokens.shape[1]), tokens.dtype)])
            rows = jnp.concatenate([rows, jnp.repeat(rows[-1:], pad)])
            start = jnp.concatenate([start, jnp.zeros((pad,), jnp.int32)])
            valid = jnp.concatenate([valid, jnp.zeros((pad,), jnp.int32)])
            state = _pad_state_rows(state, pad)
        hi = None if head_importance is None else jnp.asarray(head_importance)
        state_specs = _serve.ServeState(
            cache=self._cache_specs(SlotCache(None, None, None, None, None)),
            ssm_state=None, conv_state=None, cross_k=None, cross_v=None,
            last_tokens=P(self.exec_cfg.data_axis), decode_steps=P())
        sp_specs = self._sp_specs(sp)
        key = (jax.tree.structure(sp_specs), hi is not None)
        if key not in self._prefill_chunk_jits:
            self._prefill_chunk_jits[key] = self._build_prefill_chunk(
                sp_specs, state_specs, hi is not None)
        args = (sp, tokens, pa, state, rows, start, valid,
                jnp.asarray(quota, jnp.int32), hi)
        if self.obs.enabled:
            state, logits, lengths = self._observe_step(
                "prefill_chunk", self._prefill_chunk_jits[key], args)
        else:
            state, logits, lengths = self._prefill_chunk_jits[key](*args)
        if pad:
            state = _slice_state_rows(state, B)
            logits, lengths = logits[:B], lengths[..., :B]
        return state, logits, lengths

    # ---- decode ------------------------------------------------------------

    def _build_decode(self, sp_specs, state_specs):
        cfg, ccfg, impl = self.cfg, self.ccfg, self.paged_impl
        ec = self.exec_cfg
        kinds = self.kv_kinds

        def decode_step(sp, state, pa, tokens, active, rows):
            self.decode_traces += 1  # runs at trace time only
            return _serve.decode_step(sp, state, cfg, pa, ccfg,
                                      tokens=tokens, active=active, rows=rows,
                                      model_axis=ec.model_axis,
                                      data_axis=ec.data_axis,
                                      paged_impl=impl, kv_kinds=kinds)

        d = ec.data_axis
        # the static varying-axes checker stays on for XLA-only decode; a
        # Pallas kernel in the trace (TPU, impl="pallas", or forced
        # interpret) has no rule for it, so the check is dropped there
        # (semantics unchanged — ops.pallas_in_decode)
        from repro.kernels.ops import pallas_in_decode
        fn = jax.shard_map(
            decode_step, mesh=self.mesh,
            in_specs=(sp_specs, state_specs, self._pa_specs(), P(d), P(d),
                      P(d)),
            out_specs=(state_specs, P(d)),
            check_vma=not pallas_in_decode(self.paged_impl))
        donate = (1,) if ec.donate_state else ()
        return jax.jit(fn, donate_argnums=donate)

    def _decode_jit_for(self, sp, state):
        self._check_quant(sp)
        sp_specs = self._sp_specs(sp)
        state_specs = self._state_specs(state)
        key = (type(state.cache).__name__, jax.tree.structure(sp_specs))
        if key not in self._decode_jits:
            self._decode_jits[key] = self._build_decode(sp_specs, state_specs)
        return self._decode_jits[key]

    def decode(self, sp, state, pa, tokens, active=None, rows=None):
        self._check_grid(pa)
        tokens, active, rows = self._norm_decode_args(tokens, active, rows)
        B = int(tokens.shape[0])
        if B % self.data_size:
            raise ValueError(
                f"decode batch {B} does not split over data="
                f"{self.data_size}; size the batch (scheduler max_rows / "
                f"generate batch) as a multiple of the data-axis width")
        jit = self._decode_jit_for(sp, state)
        args = (sp, self.shard_state(state), pa, tokens, active, rows)
        if not self.obs.enabled:
            return jit(*args)
        return self._observe_step("decode", jit, args)

    # ---- speculative propose / verify (DESIGN.md §16) ----------------------

    def _build_propose(self, sp_specs, state_specs, draft_layers, max_k):
        cfg, ccfg, impl = self.cfg, self.ccfg, self.paged_impl
        ec = self.exec_cfg
        kinds = self.kv_kinds

        def propose_step(sp, state, pa, depths, active, rows):
            self.propose_traces += 1  # runs at trace time only
            return _serve.propose_step(sp, state, cfg, pa, ccfg, depths,
                                       active=active, rows=rows,
                                       model_axis=ec.model_axis,
                                       data_axis=ec.data_axis,
                                       paged_impl=impl, kv_kinds=kinds,
                                       draft_layers=draft_layers, max_k=max_k)

        d = ec.data_axis
        from repro.kernels.ops import pallas_in_decode
        fn = jax.shard_map(
            propose_step, mesh=self.mesh,
            in_specs=(sp_specs, state_specs, self._pa_specs(), P(d), P(d),
                      P(d)),
            out_specs=(state_specs, P(d, None)),
            check_vma=not pallas_in_decode(self.paged_impl))
        donate = (1,) if ec.donate_state else ()
        return jax.jit(fn, donate_argnums=donate)

    def _build_verify(self, sp_specs, state_specs, draft_layers):
        cfg, ccfg, impl = self.cfg, self.ccfg, self.paged_impl
        ec = self.exec_cfg
        kinds = self.kv_kinds

        def verify_step(sp, state, pa, tokens, q_lens, active, rows):
            self.verify_traces += 1  # runs at trace time only
            return _serve.verify_step(sp, state, cfg, pa, ccfg, tokens,
                                      q_lens, active=active, rows=rows,
                                      model_axis=ec.model_axis,
                                      data_axis=ec.data_axis,
                                      paged_impl=impl, kv_kinds=kinds,
                                      draft_layers=draft_layers)

        d = ec.data_axis
        from repro.kernels.ops import pallas_in_decode
        fn = jax.shard_map(
            verify_step, mesh=self.mesh,
            in_specs=(sp_specs, state_specs, self._pa_specs(), P(d, None),
                      P(d), P(d), P(d)),
            out_specs=(state_specs, P(d, None), P(d), P(d, None, None)),
            check_vma=not pallas_in_decode(self.paged_impl))
        donate = (1,) if ec.donate_state else ()
        return jax.jit(fn, donate_argnums=donate)

    def _check_spec_batch(self, B):
        if B % self.data_size:
            raise ValueError(
                f"speculative batch {B} does not split over data="
                f"{self.data_size}; size the batch as a multiple of the "
                f"data-axis width")

    def propose(self, sp, state, pa, depths, active=None, rows=None, *,
                draft_layers, max_k):
        self._check_grid(pa)
        _, active, rows = self._norm_decode_args(state.last_tokens, active,
                                                 rows)
        B = int(active.shape[0])
        self._check_spec_batch(B)
        self._check_quant(sp)
        sp_specs = self._sp_specs(sp)
        key = (type(state.cache).__name__, jax.tree.structure(sp_specs),
               draft_layers, max_k)
        if key not in self._propose_jits:
            self._propose_jits[key] = self._build_propose(
                sp_specs, self._state_specs(state), draft_layers, max_k)
        args = (sp, self.shard_state(state), pa,
                jnp.asarray(depths, jnp.int32), active, rows)
        if not self.obs.enabled:
            return self._propose_jits[key](*args)
        return self._observe_step("propose", self._propose_jits[key], args)

    def verify(self, sp, state, pa, tokens, q_lens, active=None, rows=None, *,
               draft_layers):
        self._check_grid(pa)
        tokens = jnp.asarray(tokens, jnp.int32)
        _, active, rows = self._norm_decode_args(tokens[:, 0], active, rows)
        B = int(tokens.shape[0])
        self._check_spec_batch(B)
        self._check_quant(sp)
        sp_specs = self._sp_specs(sp)
        key = (type(state.cache).__name__, jax.tree.structure(sp_specs),
               draft_layers)
        if key not in self._verify_jits:
            self._verify_jits[key] = self._build_verify(
                sp_specs, self._state_specs(state), draft_layers)
        args = (sp, self.shard_state(state), pa, tokens,
                jnp.asarray(q_lens, jnp.int32), active, rows)
        if not self.obs.enabled:
            return self._verify_jits[key](*args)
        return self._observe_step("verify", self._verify_jits[key], args)

    def shard_params(self, sp):
        from jax.sharding import NamedSharding
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
            sp, self._sp_specs(sp))

    def shard_state(self, state):
        """Place every state leaf under the decode in_specs.

        The decode, propose and verify entry points pass their state
        through here: host-built leaves — the paged backend re-uploads its
        block-table mirror whenever blocks are allocated or trimmed — arrive
        unplaced, and jit keys its trace cache on argument shardings, so an
        unplaced table would retrace the StepFn.  Leaves already placed
        pass through untouched (``device_put`` returns them as they are)."""
        from jax.sharding import NamedSharding
        specs = self._state_specs(state)
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
            state, specs)

    def decode_hlo(self, sp, state, pa, tokens):
        tokens, active, rows = self._norm_decode_args(tokens, None, None)
        lowered = self._decode_jit_for(sp, state).lower(
            sp, state, pa, tokens, active, rows)
        return lowered.compile().as_text()


def _pad_state_rows(state, pad: int):
    """Widen a slot-layout sub-state by ``pad`` batch rows (repeat the last
    row's content) so it splits over the data axis; inverse of
    `_slice_state_rows`."""
    c = state.cache

    def rep(x, axis):
        last = jnp.take(x, jnp.asarray([x.shape[axis] - 1]), axis=axis)
        return jnp.concatenate([x, jnp.repeat(last, pad, axis=axis)],
                               axis=axis)

    cache = None if c is None else SlotCache(
        k=rep(c.k, 2), v=rep(c.v, 2), lengths=rep(c.lengths, 2),
        pos=rep(c.pos, 2), positions=rep(c.positions, 0))
    return _serve.ServeState(
        cache=cache, ssm_state=None, conv_state=None, cross_k=None,
        cross_v=None, last_tokens=rep(state.last_tokens, 0),
        decode_steps=state.decode_steps)


def _slice_state_rows(state, n: int):
    """Drop padded batch rows from a prefill result (slot layout)."""
    c = state.cache
    cache = None if c is None else SlotCache(
        k=c.k[:, :, :n], v=c.v[:, :, :n], lengths=c.lengths[:, :, :n],
        pos=c.pos[:, :, :n], positions=c.positions[:n])
    return _serve.ServeState(
        cache=cache, ssm_state=None, conv_state=None, cross_k=None,
        cross_v=None, last_tokens=state.last_tokens[:n],
        decode_steps=state.decode_steps)
