"""`EngineConfig`: one validated config for the whole serving stack.

Composes the four sub-configs that every entry point used to wire by hand —
``ModelConfig`` (architecture), ``CompressionConfig`` (per-head KV budgets),
``PlannerConfig`` (FairKV placement), ``SchedulerConfig`` (continuous
batching) — plus the engine-level knobs (shard count, dtype, sequence
headroom, profile seeding) that previously lived as loose locals in each
caller.

``__post_init__`` validates every *name-typed* field against the live
registries (``repro.api.registry``) and the planner-mode list, so a typo'd
policy / planner mode / assignment engine fails at construction time with
the registered-name list — instead of surfacing as a bare ``KeyError`` deep
inside a jitted trace, or worse, silently selecting a fallback.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field

import jax.numpy as jnp

from repro.api.registry import list_engines, list_executors, list_policies
from repro.compression.base import CompressionConfig
from repro.configs import get_config, get_smoke_config
from repro.configs.base import ModelConfig, MoEConfig, SSMConfig
from repro.core.planner import PLANNER_MODES, PlannerConfig
from repro.exec.base import ExecutorConfig
from repro.frontend.config import FrontendConfig
from repro.obs import ObsConfig
from repro.paging.block_pool import PagingConfig
from repro.prefix import PrefixConfig
from repro.serving.scheduler import SchedulerConfig
from repro.serving.speculation import SpeculationConfig

# the one dtype-name table: validation and Engine's resolution both read it
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
          "float16": jnp.float16}


@dataclass(frozen=True)
class EngineConfig:
    """Everything `Engine.build` needs, validated at construction.

    ``dtype`` is a string (``float32`` / ``bfloat16`` / ``float16``) so the
    config stays hashable and printable; `Engine` resolves it to a jnp dtype.
    ``profile_skew`` / ``profile_seed`` parameterize the synthetic per-head
    workload profile used when the caller does not supply a measured one.
    """

    model: ModelConfig
    compression: CompressionConfig = field(default_factory=CompressionConfig)
    planner: PlannerConfig = field(default_factory=PlannerConfig)
    scheduler: SchedulerConfig = field(default_factory=SchedulerConfig)
    n_shards: int = 1
    dtype: str = "float32"
    max_seq_len: int = 512
    seed: int = 0  # PRNG seed for default parameter init
    profile_skew: float = 1.0
    profile_seed: int = 1
    # the KV cache is the paged block pool (DESIGN.md §9), sized and tuned
    # by ``paging``; ``cache_backend`` accepts only "paged" and stays for
    # callers that still name it
    cache_backend: str = "paged"
    paging: PagingConfig = field(default_factory=PagingConfig)
    # device-execution strategy (DESIGN.md §10): "local" (single-device jit)
    # or "mesh" (shard_map over a (data, model) mesh, passed to Engine.build
    # via mesh=); third parties extend via @repro.api.register_executor
    executor: str = "local"
    executor_cfg: ExecutorConfig = field(default_factory=ExecutorConfig)
    # observability (DESIGN.md §12): metrics registry + span trace threaded
    # through scheduler/executor/backend; ObsConfig(enabled=False) swaps
    # every collection point for shared no-op singletons
    obs: ObsConfig = field(default_factory=ObsConfig)
    # multi-tenant serving front end (DESIGN.md §13): fair queuing, SLO
    # admission, HTTP ingress; only `serve --http` / `FrontendServer` read
    # it, so offline engines pay nothing for the default
    frontend: FrontendConfig = field(default_factory=FrontendConfig)
    # shared-prefix block reuse + chunked prefill (DESIGN.md §14): chunking
    # needs chunk_tokens > 0 and a dense-attention model; block sharing
    # additionally needs a single-partition pool — the scheduler degrades
    # gracefully when a piece is missing
    prefix: PrefixConfig = field(default_factory=PrefixConfig)
    # speculative decoding (DESIGN.md §16): draft-propose + multi-query
    # verify over the paged cache; disabled by default (zero-cost)
    speculation: SpeculationConfig = field(default_factory=SpeculationConfig)

    def __post_init__(self):
        if not isinstance(self.model, ModelConfig):
            raise TypeError(
                f"model must be a ModelConfig, got {type(self.model).__name__}")
        policy = self.compression.policy
        if policy != "none" and policy not in list_policies():
            raise ValueError(
                f"unknown compression policy {policy!r}; registered: "
                f"{list_policies()} (plus 'none'); add policies with "
                f"@repro.api.register_policy")
        if self.planner.mode not in PLANNER_MODES:
            raise ValueError(
                f"unknown planner mode {self.planner.mode!r}; known: "
                f"{list(PLANNER_MODES)}")
        if self.planner.engine not in list_engines():
            raise ValueError(
                f"unknown assignment engine {self.planner.engine!r}; "
                f"registered: {list_engines()}; add engines with "
                f"@repro.api.register_assignment_engine")
        if self.dtype not in DTYPES:
            raise ValueError(
                f"unknown dtype {self.dtype!r}; known: {list(DTYPES)}")
        if self.n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {self.n_shards}")
        if self.max_seq_len < 1:
            raise ValueError(
                f"max_seq_len must be >= 1, got {self.max_seq_len}")
        if self.compression.budget < 1:
            raise ValueError(
                f"compression.budget must be >= 1, got "
                f"{self.compression.budget}")
        if self.scheduler.max_rows < 1:
            raise ValueError(
                f"scheduler.max_rows must be >= 1, got "
                f"{self.scheduler.max_rows}")
        if self.cache_backend != "paged":
            raise ValueError(
                f"unknown cache backend {self.cache_backend!r}; the KV "
                f"cache is the paged block pool, 'paged' (size and tune it "
                f"through paging=PagingConfig(...))")
        if not isinstance(self.paging, PagingConfig):
            raise TypeError(
                f"paging must be a PagingConfig, got "
                f"{type(self.paging).__name__}")
        # quantized KV pools (DESIGN.md §15): per-head overrides must
        # address real (layer, head) cells of this model — catch it at
        # construction, not in-trace
        if self.paging.kv_dtype != "fp32":
            L, H = self.model.n_layers, self.model.n_kv_heads
            for lyr, hd, dt in self.paging.kv_dtype_overrides:
                if lyr >= L or hd >= H:
                    raise ValueError(
                        f"paging.kv_dtype override ({lyr}, {hd}) -> {dt!r} "
                        f"out of range for model {self.model.name!r} with "
                        f"{L} layers x {H} kv heads")
        if self.executor not in list_executors():
            raise ValueError(
                f"unknown executor {self.executor!r}; registered: "
                f"{list_executors()}; add executors with "
                f"@repro.api.register_executor")
        if not isinstance(self.executor_cfg, ExecutorConfig):
            raise TypeError(
                f"executor_cfg must be an ExecutorConfig, got "
                f"{type(self.executor_cfg).__name__}")
        if not isinstance(self.obs, ObsConfig):
            raise TypeError(
                f"obs must be an ObsConfig, got {type(self.obs).__name__}")
        if not isinstance(self.frontend, FrontendConfig):
            raise TypeError(
                f"frontend must be a FrontendConfig, got "
                f"{type(self.frontend).__name__}")
        if not isinstance(self.prefix, PrefixConfig):
            raise TypeError(
                f"prefix must be a PrefixConfig, got "
                f"{type(self.prefix).__name__}")
        if not isinstance(self.speculation, SpeculationConfig):
            raise TypeError(
                f"speculation must be a SpeculationConfig, got "
                f"{type(self.speculation).__name__}")
        if self.speculation.enabled:
            if (self.model.family != "dense" or self.model.attention_free
                    or self.model.is_encoder_decoder or self.model.is_vlm):
                raise ValueError(
                    "speculative decoding supports dense decoder-only "
                    f"models; got family={self.model.family!r} for "
                    f"{self.model.name!r}")
            if self.speculation.draft_layers > self.model.n_layers:
                raise ValueError(
                    f"speculation.draft_layers="
                    f"{self.speculation.draft_layers} exceeds the model's "
                    f"{self.model.n_layers} layers")

    # ---- constructors ------------------------------------------------------

    @classmethod
    def for_arch(cls, arch: str, *, smoke: bool = False,
                 **overrides) -> "EngineConfig":
        """Config for a registered architecture id (``--arch`` names).

        ``smoke=True`` uses the arch's reduced CPU-testable variant.
        Remaining keyword arguments override `EngineConfig` fields.
        """
        model = get_smoke_config(arch) if smoke else get_config(arch)
        return cls(model=model, **overrides)

    @classmethod
    def smoke(cls, arch: str, **overrides) -> "EngineConfig":
        """Shorthand for ``for_arch(arch, smoke=True, ...)``."""
        return cls.for_arch(arch, smoke=True, **overrides)

    def replace(self, **changes) -> "EngineConfig":
        """`dataclasses.replace` that re-runs validation."""
        return dataclasses.replace(self, **changes)

    # ---- JSON round-trip ---------------------------------------------------

    def to_dict(self) -> dict:
        """JSON-serializable nested dict; `from_dict` round-trips it.

        Tuples serialize as JSON lists — `from_dict` re-tuples them, and
        every sub-config's own ``__post_init__`` re-validates on rebuild,
        so ``EngineConfig.from_dict(json.loads(json.dumps(cfg.to_dict())))
        == cfg`` for any constructible config."""
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, data: dict) -> "EngineConfig":
        """Rebuild from a `to_dict()` / JSON-file dict.

        Strict: unknown keys raise ``ValueError`` naming the offending
        path and the valid field names for that (sub-)config — a typo'd
        key in a config file fails loudly instead of being ignored.
        Missing keys fall back to the field defaults (``model`` is the one
        required section)."""
        return _config_from_dict(cls, data, "engine")


# nested rebuild targets for fields whose *type annotation* names a config
# class but whose default gives no instance to sniff (e.g. the required
# ``model`` field); default-factory fields are detected structurally
_CONFIG_TYPES = {c.__name__: c for c in (
    ModelConfig, MoEConfig, SSMConfig, CompressionConfig, PlannerConfig,
    SchedulerConfig, PagingConfig, ExecutorConfig, ObsConfig,
    FrontendConfig, PrefixConfig)}
_CONFIG_TYPES["SpeculationConfig"] = SpeculationConfig


def _field_default(f):
    if f.default_factory is not dataclasses.MISSING:  # type: ignore[misc]
        return f.default_factory()
    if f.default is not dataclasses.MISSING:
        return f.default
    return None


def _nested_type(f):
    """The dataclass type a dict value of this field rebuilds into."""
    proto = _field_default(f)
    if dataclasses.is_dataclass(proto):
        return type(proto)
    name = f.type if isinstance(f.type, str) else getattr(
        f.type, "__name__", None)
    return _CONFIG_TYPES.get(name)


def _element_type(f):
    """For tuple-of-dataclass fields (e.g. ``FrontendConfig.classes``)."""
    proto = _field_default(f)
    if (isinstance(proto, tuple) and proto
            and dataclasses.is_dataclass(proto[0])):
        return type(proto[0])
    return None


def _config_from_dict(dc_cls, data, path):
    if not isinstance(data, dict):
        raise TypeError(
            f"{path}: expected an object/dict for {dc_cls.__name__}, got "
            f"{type(data).__name__}")
    fields = dataclasses.fields(dc_cls)
    names = [f.name for f in fields]
    unknown = sorted(set(data) - set(names))
    if unknown:
        raise ValueError(
            f"unknown key(s) {unknown} at {path!r} for {dc_cls.__name__}; "
            f"valid keys: {names}")
    kwargs = {}
    for f in fields:
        if f.name not in data:
            continue
        v = data[f.name]
        sub = _nested_type(f)
        elem = _element_type(f)
        if sub is not None and isinstance(v, dict):
            v = _config_from_dict(sub, v, f"{path}.{f.name}")
        elif elem is not None and isinstance(v, (list, tuple)):
            v = tuple(
                _config_from_dict(elem, e, f"{path}.{f.name}[{i}]")
                if isinstance(e, dict) else e
                for i, e in enumerate(v))
        elif isinstance(v, list):
            # JSON has no tuples; frozen-config validators expect them
            v = tuple(tuple(e) if isinstance(e, list) else e for e in v)
        kwargs[f.name] = v
    return dc_cls(**kwargs)
