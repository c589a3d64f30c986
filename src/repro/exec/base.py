"""`Executor`: the device-execution strategy behind the serving stack.

An executor owns the *compiled step functions* (StepFns) of the serving hot
path — one prefill step and one decode step — and nothing else: what to
compute (prefill/compression/decode math) lives in ``repro.serving.engine``;
where and how it runs (which devices, which sharding, which donation) lives
here (DESIGN.md §10).  Two built-ins register with
``@repro.api.register_executor``:

- ``"local"`` — single-device ``jax.jit`` (the PR-1..3 baseline path).
- ``"mesh"``  — ``shard_map`` over a ``(data, model)`` mesh: slot-dim
  weights and both cache backends shard over ``model``, batch rows over
  ``data``; the o-projection contraction over slots is the step's one
  collective (a psum that reassembles the full batch).

StepFn contract (the no-retrace rule): the jitted callables close over the
*static* configuration only (`ModelConfig`, `CompressionConfig`, mesh/axis
names).  Everything a replan changes — slot-layout weights and plan arrays —
is a **traced argument**, so swapping placements re-executes the same
executable; as long as the slot grid and capacity are shape-stable the
decode StepFn compiles exactly once per (batch shape, cache backend).
``tokens``/``active``/``rows`` are always materialized arrays (never None
inside the trace) so one decode trace serves one-shot generation, teacher
forcing, and continuous batching alike.  The decode ``state`` argument is
donated by default (``ExecutorConfig.donate_state``) so the cache updates
in place across the hot loop.

StepFns come in the named kinds of the ``STEP_KINDS`` table — prefill,
prefill_chunk, decode, propose, verify (the last two are the speculative-
decoding pair, DESIGN.md §16).  ``step_traces[kind]`` counts actual
(re)traces per kind — the regression observable for "replans must not
recompile" — and the ``stepfn_compiles_total{kind=}`` metric keys off the
same table; the legacy ``decode_traces`` / ``prefill_traces`` /
``prefill_chunk_traces`` attributes remain as views into it.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.registry import get_executor
from repro.compression.base import CompressionConfig
from repro.configs.base import ModelConfig
from repro.obs import NULL_OBS

# the StepFn kind table: every compiled step an executor owns is one of
# these, and everything keyed per-kind — trace counters, the
# `stepfn_compiles_total{kind=}` / `stepfn_wall_s{kind=}` metrics, trace
# spans — derives from this tuple rather than hand-written attribute pairs.
STEP_KINDS = ("prefill", "prefill_chunk", "decode", "propose", "verify")


@dataclass(frozen=True)
class ExecutorConfig:
    """Execution-level knobs (validated by `EngineConfig`).

    ``donate_state``: donate the decode StepFn's state argument (the cache
    buffers are rewritten in place; keep True unless debugging aliasing).
    ``data_axis`` / ``model_axis``: mesh axis names the ``mesh`` executor
    binds batch rows / the slot dim to.
    """

    donate_state: bool = True
    data_axis: str = "data"
    model_axis: str = "model"

    def __post_init__(self):
        if not self.data_axis or not self.model_axis:
            raise ValueError("data_axis and model_axis must be non-empty")
        if self.data_axis == self.model_axis:
            raise ValueError(
                f"data_axis and model_axis must differ, both are "
                f"{self.data_axis!r}")


class Executor:
    """Interface; see the module docstring for the StepFn contract.

    ``paging`` (a `PagingConfig`, optional) carries the *static* paged
    decode knobs the StepFns close over — today ``decode_impl``, the paged
    decode-attention implementation (DESIGN.md §11).  Like the model and
    compression configs it is trace-static: changing it means a new
    executor, never a silent retrace.
    """

    name: str = "?"

    def __init__(self, model_cfg: ModelConfig, ccfg: CompressionConfig,
                 exec_cfg: Optional[ExecutorConfig] = None, mesh=None,
                 paging=None, obs=None):
        self.cfg = model_cfg
        self.ccfg = ccfg
        self.exec_cfg = exec_cfg or ExecutorConfig()
        self.mesh = mesh
        self.paging = paging
        self.paged_impl = "auto" if paging is None else paging.decode_impl
        # static per-(layer, head) KV storage-kind grid (DESIGN.md §15):
        # resolved once from the paging config, closed over by the decode
        # StepFns, and indexed by the *traced* plan's slot_head in-trace —
        # so a replan that moves heads across slots changes dequant kinds
        # without retracing.  None on the fp32 path.
        if paging is not None and getattr(paging, "kv_dtype", "fp32") != "fp32":
            from repro.paging import kvquant
            spec = kvquant.spec_from_paging(paging)
            self.kv_kinds = kvquant.kind_grid(
                spec, model_cfg.n_layers, model_cfg.n_kv_heads)
        else:
            self.kv_kinds = None
        # observability handle (DESIGN.md §12): StepFn wall-time histograms
        # + compile instant events; NULL_OBS (no-op) unless the Engine
        # facade threads its live Obs through
        self.obs = obs if obs is not None else NULL_OBS
        # actual (re)trace counts per StepFn kind, incremented from inside
        # the traced fns — the no-retrace regression observable (a replan
        # must not bump them).  One entry per STEP_KINDS row.
        self.step_traces = {k: 0 for k in STEP_KINDS}

    # legacy per-kind trace attributes — views into the STEP_KINDS table
    # (kept so existing zero-recompile assertions read unchanged)

    @property
    def prefill_traces(self) -> int:
        return self.step_traces["prefill"]

    @prefill_traces.setter
    def prefill_traces(self, v: int) -> None:
        self.step_traces["prefill"] = v

    @property
    def prefill_chunk_traces(self) -> int:
        return self.step_traces["prefill_chunk"]

    @prefill_chunk_traces.setter
    def prefill_chunk_traces(self, v: int) -> None:
        self.step_traces["prefill_chunk"] = v

    @property
    def decode_traces(self) -> int:
        return self.step_traces["decode"]

    @decode_traces.setter
    def decode_traces(self, v: int) -> None:
        self.step_traces["decode"] = v

    @property
    def propose_traces(self) -> int:
        return self.step_traces["propose"]

    @propose_traces.setter
    def propose_traces(self, v: int) -> None:
        self.step_traces["propose"] = v

    @property
    def verify_traces(self) -> int:
        return self.step_traces["verify"]

    @verify_traces.setter
    def verify_traces(self, v: int) -> None:
        self.step_traces["verify"] = v

    # ---- geometry ----------------------------------------------------------

    @property
    def pool_partitions(self) -> int:
        """Model-axis partitions the paged block pool must be split into
        (1 = single flat pool; the mesh executor returns its model size)."""
        return 1

    @property
    def row_partitions(self) -> int:
        """Data-axis partitions of the paged pool / batch rows (1 = no
        batch sharding; the mesh executor returns its data size)."""
        return 1

    def shard_params(self, sp: dict) -> dict:
        """Lay slot-layout weights out for this executor, once per plan.

        The mesh executor places them under its in_specs (slot-dim leaves
        split over ``model``, the rest replicated), so a StepFn call does
        not re-transfer the weights.  Identity on single-device
        executors."""
        return sp

    def shard_state(self, state):
        """Lay a ServeState out for this executor.

        Cache backends build state with no layout information — the empty
        continuous state, and the block table each time the paged backend
        re-uploads its host mirror.  The mesh executor places such state
        under its decode in_specs here (its decode / propose / verify entry
        points do so on every call), so the cache is sharded before the
        first step and a re-uploaded table never retraces a StepFn.
        Identity on single-device executors."""
        return state

    # ---- StepFns -----------------------------------------------------------

    def prefill(self, sp: dict, batch: dict, pa,
                rows: Optional[jnp.ndarray] = None,
                head_importance: Optional[np.ndarray] = None) -> Tuple:
        """Compiled prefill step → (ServeState, logits (B, V),
        lengths (L, Hkv, B)).  ``rows`` are the global batch-row ids the
        strided owner rule is evaluated at (default arange(B))."""
        raise NotImplementedError

    def prefill_chunk(self, sp: dict, tokens: jnp.ndarray, pa, state,
                      rows: jnp.ndarray, start, valid, quota,
                      head_importance: Optional[np.ndarray] = None) -> Tuple:
        """Compiled chunked-prefill step (DESIGN.md §14) → (ServeState,
        logits (B, V), lengths (L, Hkv, B)).

        ``tokens`` is a fixed-width (B, chunk_tokens) slice (last chunk
        zero-padded, ``valid`` (B,) counts real tokens), ``start`` (B,) the
        absolute position of each row's chunk, and ``quota`` (L,) the
        per-head keep cap the boundary compression is clamped to.  All are
        traced arguments, so one trace serves every chunk of every prompt."""
        raise NotImplementedError

    def decode(self, sp: dict, state, pa, tokens: jnp.ndarray,
               active: Optional[jnp.ndarray] = None,
               rows: Optional[jnp.ndarray] = None) -> Tuple:
        """Compiled decode step → (ServeState, logits (B, V)).

        ``active``/``rows`` default to all-active / arange(B); they are
        materialized before the call so every mode shares one trace."""
        raise NotImplementedError

    def propose(self, sp: dict, state, pa, depths: jnp.ndarray,
                active: Optional[jnp.ndarray] = None,
                rows: Optional[jnp.ndarray] = None, *,
                draft_layers: int, max_k: int) -> Tuple:
        """Compiled speculative propose step (DESIGN.md §16) →
        (ServeState, proposals (B, max_k)).

        ``depths`` ((B,) int32) is the per-row speculation depth — a traced
        argument, so adaptive depth changes reuse the compiled step;
        ``draft_layers``/``max_k`` are static (one trace per pair)."""
        raise NotImplementedError

    def verify(self, sp: dict, state, pa, tokens: jnp.ndarray,
               q_lens: jnp.ndarray, active: Optional[jnp.ndarray] = None,
               rows: Optional[jnp.ndarray] = None, *,
               draft_layers: int) -> Tuple:
        """Compiled speculative verify step (DESIGN.md §16) →
        (ServeState, g (B, Q), n_commit (B,), logits (B, Q, V)).

        ``tokens`` is the fixed-width (B, max_k + 1) window [t0, p1..pk]
        (one trace per width), ``q_lens`` ((B,) int32) the per-row valid
        window — traced, so depth changes never recompile."""
        raise NotImplementedError

    # ---- observability -----------------------------------------------------

    def _observe_step(self, kind: str, fn, args) -> Tuple:
        """Run one jitted StepFn call under observation (DESIGN.md §12).

        Records a wall-time histogram sample and a trace span per call, and
        a compile instant event + counter whenever the call actually
        (re)traced — turning the §10 zero-recompile invariant into an
        asserted metric (``stepfn_compiles_total{kind="decode"}`` must stay
        at its warm value).  Blocks on the result so the sample is real
        device time, not dispatch time; the host consumes the result
        synchronously right after in every caller, so no pipelining is
        lost.  Collection is host-side only — nothing here runs inside the
        trace.  Callers skip this entirely when obs is disabled.
        """
        if kind not in STEP_KINDS:
            raise ValueError(
                f"unknown StepFn kind {kind!r}; known: {list(STEP_KINDS)}")
        before = self.step_traces[kind]
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        obs = self.obs
        m = obs.metrics
        obs.trace.complete(f"stepfn_{kind}", t0, dt, executor=self.name)
        if self.step_traces[kind] > before:
            m.counter(
                "stepfn_compiles_total",
                help="StepFn (re)traces; decode must stay at one per "
                     "(shape, backend) across replans (DESIGN.md §10)",
            ).inc(kind=kind, executor=self.name)
            obs.trace.instant(f"stepfn_{kind}_compile", executor=self.name)
        m.histogram(
            "stepfn_wall_s",
            help="StepFn wall time per invocation, seconds (blocked on "
                 "device completion)",
        ).observe(dt, kind=kind, executor=self.name)
        return out

    # ---- shared normalization ---------------------------------------------

    def _norm_decode_args(self, tokens, active, rows):
        if isinstance(tokens, jax.ShapeDtypeStruct):
            # abstract lowering (dry-run audit): no values to materialize
            B = tokens.shape[0]
            return (tokens, jax.ShapeDtypeStruct((B,), jnp.bool_),
                    jax.ShapeDtypeStruct((B,), jnp.int32))
        tokens = jnp.asarray(tokens, jnp.int32)
        B = tokens.shape[0]
        if active is None:
            active = jnp.ones((B,), jnp.bool_)
        if rows is None:
            rows = jnp.arange(B, dtype=jnp.int32)
        return tokens, jnp.asarray(active), jnp.asarray(rows, jnp.int32)

    # ---- audit -------------------------------------------------------------

    def prefill_hlo(self, sp: dict, batch: dict, pa) -> str:
        """Compiled HLO of the prefill StepFn for ``batch`` (rows
        arange(B), no head importance) — the audit twin of `decode_hlo`."""
        raise NotImplementedError

    def decode_hlo(self, sp: dict, state, pa, tokens: jnp.ndarray) -> str:
        """Compiled (post-SPMD) HLO of the decode StepFn for the given
        arguments — feed to ``repro.distributed.hlo_stats`` for the
        collective audit.  Lowering traces, so call it outside any
        trace-count assertion window."""
        raise NotImplementedError


def make_executor(name: str, model_cfg: ModelConfig, ccfg: CompressionConfig,
                  exec_cfg: Optional[ExecutorConfig] = None,
                  mesh=None, paging=None, obs=None) -> Executor:
    """Instantiate a registered executor by name."""
    return get_executor(name)(model_cfg, ccfg, exec_cfg=exec_cfg, mesh=mesh,
                              paging=paging, obs=obs)
