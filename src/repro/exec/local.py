"""`LocalExecutor`: single-device jit StepFns (the default path).

Owns exactly the two jitted callables the serving stack used to scatter
across `api.engine.Engine._decode_fn` and `serving.scheduler._make_decode`.
Weights (``sp``) and plan arrays (``pa``) are traced *arguments*, so a
replan swaps placements by passing different values through the same
executable — no retrace (DESIGN.md §10).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.api.registry import register_executor
from repro.exec.base import Executor
from repro.serving import engine as _serve


@register_executor("local")
class LocalExecutor(Executor):
    name = "local"

    def __init__(self, model_cfg, ccfg, exec_cfg=None, mesh=None,
                 paging=None, obs=None):
        if mesh is not None:
            raise ValueError(
                "the 'local' executor runs on a single device and ignores "
                "meshes; pass executor='mesh' to run on one, or drop mesh=")
        super().__init__(model_cfg, ccfg, exec_cfg=exec_cfg, mesh=None,
                         paging=paging, obs=obs)
        self._prefill_jit = None
        self._prefill_chunk_jit = None
        self._decode_jit = None
        # speculative StepFns memoized per static (draft_layers, max_k) —
        # per-row depths are traced, so adaptive depth reuses these
        self._propose_jits = {}
        self._verify_jits = {}

    # ---- StepFn construction ----------------------------------------------

    def _build_prefill(self):
        cfg, ccfg = self.cfg, self.ccfg

        def fn(sp, batch, pa, rows, head_importance):
            self.prefill_traces += 1  # runs at trace time only
            return _serve.prefill(sp, batch, cfg, pa, ccfg,
                                  head_importance=head_importance, rows=rows)

        return jax.jit(fn)

    def _build_prefill_chunk(self):
        cfg, ccfg = self.cfg, self.ccfg

        def fn(sp, tokens, pa, state, rows, start, valid, quota,
               head_importance):
            self.prefill_chunk_traces += 1  # runs at trace time only
            return _serve.prefill_chunk(sp, tokens, cfg, pa, ccfg, state,
                                        rows, start, valid, quota,
                                        head_importance=head_importance)

        donate = (3,) if self.exec_cfg.donate_state else ()
        return jax.jit(fn, donate_argnums=donate)

    def _build_decode(self):
        cfg, ccfg, impl = self.cfg, self.ccfg, self.paged_impl
        kinds = self.kv_kinds

        def fn(sp, state, pa, tokens, active, rows):
            self.decode_traces += 1  # runs at trace time only
            return _serve.decode_step(sp, state, cfg, pa, ccfg,
                                      tokens=tokens, active=active, rows=rows,
                                      paged_impl=impl, kv_kinds=kinds)

        donate = (1,) if self.exec_cfg.donate_state else ()
        return jax.jit(fn, donate_argnums=donate)

    def _build_propose(self, draft_layers, max_k):
        cfg, ccfg, impl = self.cfg, self.ccfg, self.paged_impl
        kinds = self.kv_kinds

        def fn(sp, state, pa, depths, active, rows):
            self.propose_traces += 1  # runs at trace time only
            return _serve.propose_step(sp, state, cfg, pa, ccfg, depths,
                                       active=active, rows=rows,
                                       paged_impl=impl, kv_kinds=kinds,
                                       draft_layers=draft_layers, max_k=max_k)

        donate = (1,) if self.exec_cfg.donate_state else ()
        return jax.jit(fn, donate_argnums=donate)

    def _build_verify(self, draft_layers):
        cfg, ccfg, impl = self.cfg, self.ccfg, self.paged_impl
        kinds = self.kv_kinds

        def fn(sp, state, pa, tokens, q_lens, active, rows):
            self.verify_traces += 1  # runs at trace time only
            return _serve.verify_step(sp, state, cfg, pa, ccfg, tokens,
                                      q_lens, active=active, rows=rows,
                                      paged_impl=impl, kv_kinds=kinds,
                                      draft_layers=draft_layers)

        donate = (1,) if self.exec_cfg.donate_state else ()
        return jax.jit(fn, donate_argnums=donate)

    # ---- entry points ------------------------------------------------------

    def prefill(self, sp, batch, pa, rows=None, head_importance=None):
        if self._prefill_jit is None:
            self._prefill_jit = self._build_prefill()
        B = batch["tokens"].shape[0]
        if rows is None:
            rows = jnp.arange(B, dtype=jnp.int32)
        hi = None if head_importance is None else jnp.asarray(head_importance)
        args = (sp, batch, pa, jnp.asarray(rows, jnp.int32), hi)
        if not self.obs.enabled:
            return self._prefill_jit(*args)
        return self._observe_step("prefill", self._prefill_jit, args)

    def prefill_chunk(self, sp, tokens, pa, state, rows, start, valid, quota,
                      head_importance=None):
        if self._prefill_chunk_jit is None:
            self._prefill_chunk_jit = self._build_prefill_chunk()
        hi = None if head_importance is None else jnp.asarray(head_importance)
        args = (sp, jnp.asarray(tokens, jnp.int32), pa, state,
                jnp.asarray(rows, jnp.int32), jnp.asarray(start, jnp.int32),
                jnp.asarray(valid, jnp.int32), jnp.asarray(quota, jnp.int32),
                hi)
        if not self.obs.enabled:
            return self._prefill_chunk_jit(*args)
        return self._observe_step("prefill_chunk", self._prefill_chunk_jit,
                                  args)

    def decode(self, sp, state, pa, tokens, active=None, rows=None):
        if self._decode_jit is None:
            self._decode_jit = self._build_decode()
        tokens, active, rows = self._norm_decode_args(tokens, active, rows)
        args = (sp, state, pa, tokens, active, rows)
        if not self.obs.enabled:
            return self._decode_jit(*args)
        return self._observe_step("decode", self._decode_jit, args)

    def propose(self, sp, state, pa, depths, active=None, rows=None, *,
                draft_layers, max_k):
        key = (draft_layers, max_k)
        if key not in self._propose_jits:
            self._propose_jits[key] = self._build_propose(draft_layers, max_k)
        _, active, rows = self._norm_decode_args(state.last_tokens, active,
                                                 rows)
        args = (sp, state, pa, jnp.asarray(depths, jnp.int32), active, rows)
        if not self.obs.enabled:
            return self._propose_jits[key](*args)
        return self._observe_step("propose", self._propose_jits[key], args)

    def verify(self, sp, state, pa, tokens, q_lens, active=None, rows=None, *,
               draft_layers):
        if draft_layers not in self._verify_jits:
            self._verify_jits[draft_layers] = self._build_verify(draft_layers)
        tokens = jnp.asarray(tokens, jnp.int32)
        _, active, rows = self._norm_decode_args(tokens[:, 0], active, rows)
        args = (sp, state, pa, tokens, jnp.asarray(q_lens, jnp.int32),
                active, rows)
        if not self.obs.enabled:
            return self._verify_jits[draft_layers](*args)
        return self._observe_step("verify", self._verify_jits[draft_layers],
                                  args)

    def prefill_hlo(self, sp, batch, pa):
        if self._prefill_jit is None:
            self._prefill_jit = self._build_prefill()
        rows = jnp.arange(batch["tokens"].shape[0], dtype=jnp.int32)
        lowered = self._prefill_jit.lower(sp, batch, pa, rows, None)
        return lowered.compile().as_text()

    def decode_hlo(self, sp, state, pa, tokens):
        if self._decode_jit is None:
            self._decode_jit = self._build_decode()
        tokens, active, rows = self._norm_decode_args(tokens, None, None)
        lowered = self._decode_jit.lower(sp, state, pa, tokens, active, rows)
        return lowered.compile().as_text()
