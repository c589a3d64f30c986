"""`repro.api` — the unified front door for FairKV serving (DESIGN.md §8).

Every entry point (launch drivers, examples, benchmarks) composes the stack
through this package instead of hand-wiring
``ModelConfig → init params → plan → slot weights → prefill → decode``:

- `EngineConfig` — one validated config (model + compression + planner +
  scheduler); unknown policy / planner-mode / engine names fail at
  construction with the registered-name list.
- `Engine` — facade owning params, plan, slot weights, and cache;
  `generate` (one-shot batch), `submit`/`step`/`stream`/`run_trace`
  (continuous), `replan` (online replanning), `measure_profile`.
- `register_policy` / `register_assignment_engine` — decorator registries
  so third-party compression policies and placement solvers plug in
  without touching core modules; `list_policies` / `list_engines` feed
  validation and ``--help`` text.

The facade also re-exports the underlying building blocks (`build_plan`,
`slotify_params`, `prefill`, `decode_step`, the sub-configs, request/trace
helpers) for planner-level studies and sharded-launch harnesses that need
pieces below the `Engine` surface — importing them from here keeps
``repro.api`` the single dependency edge into the serving stack.

Heavyweight modules load lazily (PEP 562): the registry decorators must be
importable from ``compression``/``core`` provider modules without dragging
in the full serving stack (which would cycle back into them mid-import).
"""
from __future__ import annotations

from repro.api.registry import (  # noqa: F401
    ASSIGNMENT_ENGINE_REGISTRY,
    EXECUTOR_REGISTRY,
    POLICY_REGISTRY,
    Registry,
    get_assignment_engine,
    get_executor,
    get_policy,
    list_engines,
    list_executors,
    list_policies,
    register_assignment_engine,
    register_executor,
    register_policy,
)

# name -> "module:attr" table for lazy (PEP 562) exports
_LAZY = {
    # facade
    "EngineConfig": "repro.api.config:EngineConfig",
    "Engine": "repro.api.engine:Engine",
    "GenerationResult": "repro.api.engine:GenerationResult",
    "StreamEvent": "repro.api.engine:StreamEvent",
    # consolidated stats snapshot (DESIGN.md §8)
    "EngineStats": "repro.api.stats:EngineStats",
    "SchedulerStats": "repro.api.stats:SchedulerStats",
    "PoolStats": "repro.api.stats:PoolStats",
    "PrefixStats": "repro.api.stats:PrefixStats",
    "PlanStats": "repro.api.stats:PlanStats",
    "SpeculationStats": "repro.api.stats:SpeculationStats",
    # sub-configs
    "ModelConfig": "repro.configs.base:ModelConfig",
    "CompressionConfig": "repro.compression.base:CompressionConfig",
    "PlannerConfig": "repro.core.planner:PlannerConfig",
    "SchedulerConfig": "repro.serving.scheduler:SchedulerConfig",
    "SpeculationConfig": "repro.serving.speculation:SpeculationConfig",
    "PLANNER_MODES": "repro.core.planner:PLANNER_MODES",
    # arch registry
    "get_config": "repro.configs.base:get_config",
    "get_smoke_config": "repro.configs.base:get_smoke_config",
    "list_archs": "repro.configs.base:list_archs",
    # planning building blocks (planner-level studies, no model needed)
    "build_plan": "repro.core.planner:build_plan",
    "plan_kv_dtypes": "repro.core.planner:plan_kv_dtypes",
    "replan_for_stragglers": "repro.core.planner:replan_for_stragglers",
    "assign_items": "repro.core.assignment:assign_items",
    "HeadPlacement": "repro.core.placement:HeadPlacement",
    "PlanArrays": "repro.cache.slot_cache:PlanArrays",
    "synthetic_profile": "repro.core.profiles:synthetic_profile",
    "profile_from_lengths": "repro.core.profiles:profile_from_lengths",
    "select_policy": "repro.compression.policies:select",
    # low-level serving ops (sharded launch harness, parity tests)
    "init_params": "repro.models:init_params",
    "slotify_params": "repro.serving.engine:slotify_params",
    "prefill": "repro.serving.engine:prefill",
    "decode_step": "repro.serving.engine:decode_step",
    "ServeState": "repro.serving.engine:ServeState",
    # continuous-batching surface
    "Scheduler": "repro.serving.scheduler:Scheduler",
    "Request": "repro.serving.request:Request",
    "RequestState": "repro.serving.request:RequestState",
    "synthesize_requests": "repro.serving.request:synthesize_requests",
    "poisson_arrivals": "repro.serving.request:poisson_arrivals",
    "latency_percentiles": "repro.serving.request:latency_percentiles",
    # shared-prefix reuse + chunked prefill (DESIGN.md §14)
    "PrefixConfig": "repro.prefix:PrefixConfig",
    "PrefixIndex": "repro.prefix:PrefixIndex",
    "PrefixEntry": "repro.prefix:PrefixEntry",
    # paged cache backend (DESIGN.md §9)
    "PagingConfig": "repro.paging.block_pool:PagingConfig",
    "PoolExhausted": "repro.paging.block_pool:PoolExhausted",
    "BlockPool": "repro.paging.block_pool:BlockPool",
    "PagedCache": "repro.paging.paged_cache:PagedCache",
    # executor layer (DESIGN.md §10)
    "Executor": "repro.exec.base:Executor",
    "ExecutorConfig": "repro.exec.base:ExecutorConfig",
    "make_executor": "repro.exec.base:make_executor",
    # observability (DESIGN.md §12)
    "Obs": "repro.obs:Obs",
    "ObsConfig": "repro.obs:ObsConfig",
    "MetricsRegistry": "repro.obs:MetricsRegistry",
    "TraceBuffer": "repro.obs:TraceBuffer",
    # serving front end (DESIGN.md §13)
    "FrontendConfig": "repro.frontend.config:FrontendConfig",
    "PriorityClass": "repro.frontend.config:PriorityClass",
    "FrontendScheduler": "repro.frontend.core:FrontendScheduler",
    "run_frontend_trace": "repro.frontend.core:run_frontend_trace",
    "EngineLoop": "repro.frontend.bridge:EngineLoop",
    "FrontendServer": "repro.frontend.http:FrontendServer",
    "serve_http": "repro.frontend.http:serve_http",
}

__all__ = sorted(
    ["ASSIGNMENT_ENGINE_REGISTRY", "EXECUTOR_REGISTRY", "POLICY_REGISTRY",
     "Registry", "get_assignment_engine", "get_executor", "get_policy",
     "list_engines", "list_executors", "list_policies",
     "register_assignment_engine", "register_executor", "register_policy",
     *_LAZY])


def __getattr__(name: str):
    try:
        target = _LAZY[name]
    except KeyError:
        raise AttributeError(
            f"module 'repro.api' has no attribute {name!r}") from None
    import importlib
    module, attr = target.split(":")
    value = getattr(importlib.import_module(module), attr)
    globals()[name] = value  # cache for subsequent lookups
    return value


def __dir__():
    return __all__
