"""Serving driver: ``python -m repro.launch.serve --arch <id> [--smoke]``.

Default (one-shot) mode: `repro.api.Engine.generate` — prefill + compression
(Ada-SnapKV by default) → FairKV plan → slot-layout decode over a fixed
batch.  Prints per-step latency, the realized per-head budget imbalance, the
plan's efficiency E, and the generated tokens.

``--continuous`` mode drives the continuous-batching scheduler through the
same facade (`Engine.run_trace`, DESIGN.md §7): a Poisson trace of requests
(``--rate`` arrivals per decode step, ``--requests`` total) flows through
admission → interleaved decode → retirement, with online replanning when the
realized per-shard KV imbalance drifts.  Prints per-request latency,
p50/p99, and the replan log.

``--http`` mode serves the multi-tenant asyncio front end (DESIGN.md §13)
over the continuous engine: ``POST /v1/generate`` (JSON), ``POST
/v1/stream`` (SSE per-token events), ``GET /metrics`` (Prometheus with
per-tenant goodput/latency families), ``GET /healthz``.  Admission is
SLO-aware (``--admission slo``, priority classes with degrade/shed and
tenant-fair deficit-round-robin quotas) or the FCFS baseline; SIGINT /
SIGTERM drain gracefully (finish live decodes, shed the queue, flush
``--metrics-out`` / ``--trace-out``).

``--executor mesh`` runs both modes' StepFns under ``shard_map`` on a
(data=``--data``, model=``--shards``) host mesh (DESIGN.md §10) and prints
the decode StepFn's per-device collective audit (parsed from the compiled
HLO via ``repro.distributed.hlo_stats``) — on CPU, fake the devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``.

Policy, planner, backend and executor names are validated by `EngineConfig`
against the live registries — ``--help`` lists whatever is registered,
including plugins.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys

import numpy as np

from repro.api import (
    PLANNER_MODES,
    CompressionConfig,
    Engine,
    EngineConfig,
    ObsConfig,
    PagingConfig,
    PlannerConfig,
    PrefixConfig,
    SchedulerConfig,
    SpeculationConfig,
    latency_percentiles,
    list_cache_backends,
    list_engines,
    list_executors,
    list_policies,
    synthesize_requests,
)
from repro.configs.base import InputShape
from repro.training.data import SyntheticLM


def _engine_config(args, max_seq_len: int, batch_cap: int,
                   scheduler: SchedulerConfig = SchedulerConfig()
                   ) -> EngineConfig:
    if getattr(args, "config", ""):
        return _engine_config_from_file(args, max_seq_len, batch_cap,
                                        scheduler)
    speculate = getattr(args, "speculate", 0)
    # attention-free archs get a trivial single-shard plan inside
    # Engine.build, so n_shards/planner pass through unconditionally
    return EngineConfig.for_arch(
        args.arch, smoke=args.smoke, n_shards=args.shards,
        dtype="float32" if args.smoke else "bfloat16",
        max_seq_len=max_seq_len,
        compression=CompressionConfig(
            policy=args.policy, budget=args.budget, alpha_max=2.0,
            obs_window=8, sink=2,
            decode_margin=max(8, getattr(args, "gen", 8))),
        planner=PlannerConfig(mode=args.planner, engine=args.engine,
                              extra_copies=args.copies, batch_cap=batch_cap,
                              slots_per_shard=args.slots_per_shard or None),
        scheduler=scheduler,
        # --prefix-cache needs block refcounts, --kv-dtype needs block
        # storage, and --speculate needs provisional-block rollback — all
        # paged-backend features; promote slot (the default) rather than
        # erroring on the common invocation — any other backend choice
        # still errors through EngineConfig validation
        cache_backend=("paged"
                       if ((getattr(args, "prefix_cache", False)
                            or getattr(args, "kv_dtype", "fp32") != "fp32"
                            or speculate > 0)
                           and args.cache_backend == "slot")
                       else args.cache_backend),
        paging=PagingConfig(block_size=args.block_size,
                            n_blocks=args.pool_blocks,
                            decode_impl=args.paged_impl,
                            kv_dtype=getattr(args, "kv_dtype", "fp32"),
                            pool_hbm_bytes=getattr(args, "pool_hbm_bytes",
                                                   0)),
        prefix=PrefixConfig(
            enabled=getattr(args, "prefix_cache", False),
            chunk_tokens=(getattr(args, "prefill_chunk", 0)
                          or (32 if getattr(args, "prefix_cache", False)
                              else 0)),
            max_entries=getattr(args, "prefix_entries", 256)),
        speculation=SpeculationConfig(
            enabled=speculate > 0, max_k=max(1, speculate),
            draft_layers=getattr(args, "draft_layers", 0)),
        executor=args.executor,
        obs=ObsConfig(enabled=not args.no_obs,
                      print_every=args.obs_print_every))


# explicit CLI flag -> EngineConfig field path, for --config overrides.
# Only flags that map 1:1 onto config fields appear here; trace-shape flags
# (--gen, --rows, ...) keep driving the workload, not the config.
_CLI_FIELD_MAP = {
    "shards": ("n_shards",),
    "policy": ("compression", "policy"),
    "budget": ("compression", "budget"),
    "planner": ("planner", "mode"),
    "engine": ("planner", "engine"),
    "copies": ("planner", "extra_copies"),
    "slots_per_shard": ("planner", "slots_per_shard"),
    "cache_backend": ("cache_backend",),
    "block_size": ("paging", "block_size"),
    "pool_blocks": ("paging", "n_blocks"),
    "paged_impl": ("paging", "decode_impl"),
    "kv_dtype": ("paging", "kv_dtype"),
    "pool_hbm_bytes": ("paging", "pool_hbm_bytes"),
    "executor": ("executor",),
    "draft_layers": ("speculation", "draft_layers"),
}


def _set_path(cfg: EngineConfig, path, value) -> EngineConfig:
    if len(path) == 1:
        return cfg.replace(**{path[0]: value})
    sub = dataclasses.replace(getattr(cfg, path[0]), **{path[1]: value})
    return cfg.replace(**{path[0]: sub})


def _engine_config_from_file(args, max_seq_len: int, batch_cap: int,
                             scheduler: SchedulerConfig) -> EngineConfig:
    """``--config cfg.json``: the file is the base `EngineConfig`
    (`EngineConfig.from_dict`, strict about unknown keys); flags the user
    *explicitly typed* override the file, flag defaults do not.  The
    trace-shape-derived fields (``max_seq_len``, ``planner.batch_cap``,
    scheduler rows) are raised to what the requested workload needs so a
    config written for one trace still runs a larger one."""
    import json

    with open(args.config) as f:
        cfg = EngineConfig.from_dict(json.load(f))
    explicit = getattr(args, "_explicit", set())
    for dest, path in _CLI_FIELD_MAP.items():
        if dest in explicit:
            cfg = _set_path(cfg, path, getattr(args, dest))
    if "speculate" in explicit:
        cfg = cfg.replace(speculation=dataclasses.replace(
            cfg.speculation, enabled=args.speculate > 0,
            max_k=max(1, args.speculate)))
    if cfg.speculation.enabled and cfg.cache_backend == "slot":
        cfg = cfg.replace(cache_backend="paged")
    # workload-derived floors (never shrink what the file asked for)
    cfg = cfg.replace(max_seq_len=max(cfg.max_seq_len, max_seq_len))
    if cfg.planner.batch_cap is None or cfg.planner.batch_cap < batch_cap:
        cfg = cfg.replace(planner=dataclasses.replace(
            cfg.planner, batch_cap=batch_cap))
    if scheduler.max_rows > cfg.scheduler.max_rows:
        cfg = cfg.replace(scheduler=dataclasses.replace(
            cfg.scheduler, max_rows=scheduler.max_rows))
    return cfg


def _build_engine(args, ecfg: EngineConfig, params=None) -> Engine:
    """Engine on the configured executor (mesh: a (data, model) host mesh).
    ``params`` (original layout) skips the seeded init — engines of one
    model can then share a single weight copy."""
    mesh = None
    if ecfg.executor == "mesh":
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(model=args.shards, data=args.data)
    return Engine.build(ecfg, mesh=mesh, params=params)


def _collective_audit(eng: Engine) -> None:
    """Print the decode StepFn's per-device collective traffic (mesh only).

    The audit is the §10 contract check made visible: the decode hot loop
    should psum exactly once per attention layer (the o-projection) and
    all-gather nothing — weight gathers belong to prefill.
    """
    if eng.cfg.executor != "mesh":
        return
    from repro.distributed.hlo_stats import collective_stats
    sched = eng.scheduler
    sp, pa = (sched.sp, sched.pa) if sched is not None else (eng.sp, eng.pa)
    state = sched.state if sched is not None else eng.state
    hlo = eng.executor.decode_hlo(sp, state, pa, state.last_tokens)
    stats = collective_stats(hlo)
    total = sum(v["bytes"] for v in stats.values())
    detail = ", ".join(f"{k}×{v['count']} ({v['bytes'] / 1e3:.1f} kB)"
                       for k, v in sorted(stats.items())) or "none"
    print(f"decode StepFn collectives/device: {detail} | "
          f"total {total / 1e3:.1f} kB")


def _export_obs(eng: Engine, args) -> None:
    """Write the Prometheus / Chrome-trace exports when paths were given."""
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            f.write(eng.metrics_prometheus())
        print(f"metrics -> {args.metrics_out}")
    if args.trace_out:
        with open(args.trace_out, "w") as f:
            f.write(eng.trace_export())
        print(f"trace -> {args.trace_out} (load in Perfetto / "
              f"chrome://tracing)")


def _scheduler_config(args) -> SchedulerConfig:
    return SchedulerConfig(
        max_rows=args.rows,
        max_live_tokens=args.max_live_tokens or None,
        replan_window=args.replan_window,
        replan_threshold=args.replan_threshold,
        replan_cooldown=args.replan_cooldown,
        enable_replan=not args.no_replan,
    )


def _install_drain_handlers(eng: Engine):
    """SIGINT/SIGTERM → `Engine.drain` (graceful: stop admitting, finish
    live decodes; queued/unsubmitted requests are shed).  Returns a restore
    callback.  A second signal falls through to the previous handler, so
    Ctrl-C twice still kills a stuck drain."""
    import signal

    prev = {}

    def _drain(signum, frame):
        print(f"\nsignal {signum}: draining (live rows decode to "
              f"completion; queued requests are shed) ...", flush=True)
        eng.drain()
        # restore immediately: the next signal interrupts for real
        for sig, h in prev.items():
            signal.signal(sig, h)

    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            prev[sig] = signal.signal(sig, _drain)
        except ValueError:  # not the main thread (embedded use)
            pass

    def restore() -> None:
        for sig, h in prev.items():
            try:
                signal.signal(sig, h)
            except ValueError:
                pass

    return restore


def run_continuous(args) -> None:
    """Poisson-trace continuous batching via the facade."""
    min_prompt = args.min_prompt
    tkw = {}
    if getattr(args, "prefix_templates", 0) > 0:
        # shared templates need room for a unique suffix on every prompt
        min_prompt = max(min_prompt, args.prefix_len + 4)
        tkw = dict(prefix_templates=args.prefix_templates,
                   prefix_len=args.prefix_len,
                   shared_fraction=args.shared_fraction)
    max_prompt = max(min_prompt, args.max_prompt)
    scfg = _scheduler_config(args)
    ecfg = _engine_config(args, max_prompt + args.gen + 8, args.rows, scfg)
    eng = _build_engine(args, ecfg)
    reqs = synthesize_requests(args.requests, args.rate,
                               ecfg.model.vocab_size,
                               min_prompt=min_prompt,
                               max_prompt=max_prompt,
                               max_new_tokens=args.gen, seed=args.seed,
                               **tkw)
    print(f"continuous: {len(reqs)} requests, rate {args.rate}/step, "
          f"{args.rows} rows, planner {args.planner}")
    restore = _install_drain_handlers(eng)
    try:
        out = eng.run_trace(reqs, max_steps=args.max_steps)
    finally:
        restore()
        # a drained (signalled) run still flushes its exports — that's the
        # point of graceful shutdown
        _export_obs(eng, args)
    for r in eng.finished_requests:
        print(f"req {r.req_id}: prompt {r.prompt_len:3d} | arrive "
              f"{r.arrival_step:3d} admit {r.admit_step:3d} finish "
              f"{r.finish_step:3d} | queued {r.queueing_steps():2d} steps | "
              f"{r.n_generated} tokens")
    pct = latency_percentiles(eng.finished_requests)

    def fmt(key: str, scale: float = 1.0, unit: str = "") -> str:
        # absent key = no request recorded the observable: print n/a, not nan
        v = pct.get(key)
        return "n/a" if v is None else f"{v * scale:.0f}{unit}"

    note = (f" ({out['tokens_per_s_note']})"
            if "tokens_per_s_note" in out else "")
    print(f"steps {out['steps']} | {out['generated_tokens']} tokens in "
          f"{out['wall_s']:.1f}s = {out['tokens_per_s']:.1f} tok/s{note} | "
          f"latency p50 {fmt('p50_steps')} / p99 {fmt('p99_steps')} steps")
    print(f"ttft p50 {fmt('p50_ttft_s', 1e3, ' ms')} / p99 "
          f"{fmt('p99_ttft_s', 1e3, ' ms')} | itl p50 "
          f"{fmt('p50_itl_s', 1e3, ' ms')} / p99 "
          f"{fmt('p99_itl_s', 1e3, ' ms')}")
    print(f"mid-stream admissions: {out['mid_stream_admissions']} | "
          f"replans: {out['replans']} | preemptions: {out['preemptions']}")
    st = eng.stats()  # one typed snapshot (DESIGN.md §8)
    if st.pool.backend == "paged":
        print(f"paged cache: {st.pool.blocks_in_use}/{st.pool.blocks_total} "
              f"blocks ({st.pool.cache_bytes} B) vs slot-equivalent "
              f"{st.pool.slot_equivalent_bytes} B")
    if st.prefix.enabled:
        print(f"prefix cache: {st.prefix.hits} hits / {st.prefix.misses} "
              f"misses | {st.prefix.entries} entries holding "
              f"{st.prefix.blocks_held} blocks | {st.prefix.evictions} "
              f"evictions")
    if st.speculation.enabled:
        acc = ("n/a" if st.speculation.acceptance is None
               else f"{st.speculation.acceptance:.2f}")
        print(f"speculation: {st.speculation.accepted}/"
              f"{st.speculation.proposed} draft tokens accepted "
              f"(acceptance {acc}, max_k {st.speculation.max_k}, "
              f"draft layers {st.speculation.draft_layers or 'all'})")
    for ev in st.scheduler.replan_log:
        tag = "accepted" if ev["accepted"] else "rejected"
        print(f"  replan @ step {ev['step']} ({tag}): imbalance "
              f"{ev['imbalance_before']:.3f} -> {ev['imbalance_after']:.3f}")
    _collective_audit(eng)
    if out.get("drained"):
        # graceful shutdown: cancelled requests are expected, not a failure
        print(f"drained: {out['cancelled']} request(s) shed, "
              f"{out['finished'] - out['cancelled']} decoded to completion")
        return
    if out["finished"] != out["total"]:
        raise RuntimeError(
            f"only {out['finished']}/{out['total']} requests finished")
    if args.smoke and out["mid_stream_admissions"] < 1:
        raise RuntimeError("smoke trace produced no mid-stream admission — "
                           "raise --requests or lower --rows")


def run_http(args) -> None:
    """``--http``: the multi-tenant asyncio serving front end
    (DESIGN.md §13) over the continuous-batching engine.

    SIGINT/SIGTERM drain gracefully: the listener closes, queued requests
    are shed with 503-style terminal events, live rows decode to
    completion, and ``--metrics-out`` / ``--trace-out`` are flushed.
    """
    import asyncio
    import signal

    from repro.frontend import FrontendConfig, FrontendServer

    max_prompt = max(args.min_prompt, args.max_prompt)
    ecfg = _engine_config(args, max_prompt + args.gen + 8, args.rows,
                          _scheduler_config(args))
    fcfg = FrontendConfig(
        host=args.host, port=args.port, admission=args.admission,
        quantum_tokens=args.quantum, quota_cap_tokens=args.quota_cap,
        max_prompt_tokens=max_prompt, max_new_tokens_cap=args.gen)
    eng = _build_engine(args, ecfg)

    async def _main() -> None:
        server = FrontendServer(eng, fcfg)
        await server.start()
        print(f"serving on http://{server.host}:{server.port} "
              f"(admission={fcfg.admission}, rows={args.rows}, "
              f"backend={ecfg.cache_backend}, executor={ecfg.executor})",
              flush=True)
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                loop.add_signal_handler(sig, stop.set)
            except (NotImplementedError, RuntimeError):
                signal.signal(sig, lambda *_: stop.set())
        await stop.wait()
        print("signal received: draining (live rows decode to completion, "
              "queued requests shed) ...", flush=True)
        await server.shutdown(drain=True)
        summary = server.engine_loop.fe.summary()
        print(f"drained after {summary['steps']} steps | "
              f"{summary['finished']} terminal requests | goodput "
              f"{summary['goodput_tokens']:.0f} tokens", flush=True)

    try:
        asyncio.run(_main())
    finally:
        _export_obs(eng, args)


def run_oneshot(args) -> None:
    """Fixed-batch serve: one prefill + ``--gen`` decode steps."""
    ecfg = _engine_config(args, args.prompt_len + args.gen + 8, args.batch)
    eng = _build_engine(args, ecfg)
    data = SyntheticLM(ecfg.model, InputShape("cli", args.prompt_len,
                                              args.batch, "prefill"))
    res = eng.generate(data.get_batch(0), args.gen, collect_logits=False)
    if res.lengths.size:
        lens_np = np.asarray(res.lengths, np.float64)
        print(f"prefill {res.prefill_s * 1e3:7.1f} ms | realized per-head "
              f"budget min/mean/max = {lens_np.min():.0f}/{lens_np.mean():.0f}"
              f"/{lens_np.max():.0f} | plan E = "
              f"{res.efficiency:.3f} ({args.planner})")
    print(f"decode  {np.median(res.step_s) * 1e3:7.1f} ms/step (median of "
          f"{args.gen}; first {res.step_s[0] * 1e3:.0f} ms incl. compile)")
    pool = eng.stats().pool
    if pool.backend == "paged":
        print(f"paged cache: {pool.cache_bytes} B in "
              f"{pool.blocks_in_use} blocks vs slot-equivalent "
              f"{pool.slot_equivalent_bytes} B")
    _collective_audit(eng)
    _export_obs(eng, args)
    for b in range(min(args.batch, 2)):
        print(f"row {b}: {res.tokens[b].tolist()}")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="",
                    help="architecture id (required unless --config "
                         "provides the model)")
    ap.add_argument("--config", default="",
                    help="JSON EngineConfig file (EngineConfig.to_dict "
                         "format) used as the base config; explicitly "
                         "typed CLI flags override file values, flag "
                         "defaults do not")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--prompt-len", type=int, default=96)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--budget", type=int, default=32)
    ap.add_argument("--policy", default="ada_snapkv",
                    help=f"compression policy; registered: {list_policies()}")
    ap.add_argument("--planner", default="fairkv_dp",
                    choices=list(PLANNER_MODES))
    ap.add_argument("--engine", default="auto",
                    help="assignment engine; registered: "
                         f"{list_engines()}")
    ap.add_argument("--shards", type=int, default=4,
                    help="logical model shards for the plan")
    ap.add_argument("--copies", type=int, default=4, help="CH")
    ap.add_argument("--slots-per-shard", type=int, default=0,
                    help="head slots per shard (0 = ceil(kv heads / "
                         "shards), which leaves no spare slot for --copies)")
    # --- cache backend (DESIGN.md §9) ----------------------------------------
    ap.add_argument("--cache-backend", default="slot",
                    help=f"cache storage backend; registered: "
                         f"{list_cache_backends()}")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged backend: tokens per KV block")
    ap.add_argument("--pool-blocks", type=int, default=0,
                    help="paged backend: blocks per layer pool "
                         "(0 = slot-equivalent worst case)")
    ap.add_argument("--paged-impl", default="auto",
                    choices=["auto", "pallas", "gather", "jnp"],
                    help="paged backend: decode-attention implementation "
                         "(DESIGN.md §11; auto = native pallas kernel on "
                         "TPU, jnp oracle elsewhere)")
    ap.add_argument("--kv-dtype", default="fp32",
                    choices=["fp32", "int8", "fp8"],
                    help="paged backend: KV block storage format "
                         "(DESIGN.md §15; quantized pools carry per-block "
                         "scales and dequantize in the decode kernel)")
    ap.add_argument("--pool-hbm-bytes", type=int, default=0,
                    help="paged backend: size the per-layer pool from an "
                         "HBM byte budget instead of --pool-blocks "
                         "(bytes-aware admission: int8 pools hold ~4x the "
                         "blocks of fp32 at the same budget)")
    # --- speculative decoding (DESIGN.md §16) --------------------------------
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="speculative decoding: propose up to K draft "
                         "tokens per tick and verify them in one "
                         "multi-query pass (0 = off; implies "
                         "--cache-backend paged)")
    ap.add_argument("--draft-layers", type=int, default=0,
                    help="early-exit depth of the self-speculative draft "
                         "(first N layers + the target's unembedding; "
                         "0 = all layers, acceptance 1.0 — a correctness "
                         "baseline, not a speedup)")
    # --- shared-prefix reuse + chunked prefill (DESIGN.md §14) ---------------
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="split prompt prefill into chunks of this many "
                         "tokens, interleaved with decode ticks (0 = "
                         "monolithic prefill); dense-attention models only")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="content-addressed shared-prefix block reuse "
                         "(requires --cache-backend paged; implies "
                         "--prefill-chunk 32 when no chunk size is given)")
    ap.add_argument("--prefix-entries", type=int, default=256,
                    help="prefix index capacity (LRU-evicted entries)")
    ap.add_argument("--prefix-templates", type=int, default=0,
                    help="continuous trace: number of shared prompt "
                         "templates (0 = fully random prompts)")
    ap.add_argument("--prefix-len", type=int, default=0,
                    help="continuous trace: tokens per shared template")
    ap.add_argument("--shared-fraction", type=float, default=0.8,
                    help="continuous trace: fraction of requests that "
                         "start with a template prefix")
    # --- executor (DESIGN.md §10) --------------------------------------------
    ap.add_argument("--executor", default="local",
                    help=f"device execution strategy; registered: "
                         f"{list_executors()}.  'mesh' runs the StepFns "
                         f"under shard_map on a (data, model) host mesh "
                         f"(set XLA_FLAGS=--xla_force_host_platform_"
                         f"device_count=N to fake devices on CPU) and "
                         f"prints the decode collective audit")
    ap.add_argument("--data", type=int, default=1,
                    help="mesh executor: data-axis width (batch rows shard "
                         "over it; model axis width is --shards)")
    # --- continuous batching -------------------------------------------------
    ap.add_argument("--continuous", action="store_true",
                    help="run the continuous-batching scheduler on a "
                         "Poisson request trace")
    ap.add_argument("--rows", type=int, default=2,
                    help="batch rows (concurrent requests)")
    ap.add_argument("--requests", type=int, default=5)
    ap.add_argument("--rate", type=float, default=0.5,
                    help="Poisson arrival rate, requests per decode step")
    ap.add_argument("--min-prompt", type=int, default=12)
    ap.add_argument("--max-prompt", type=int, default=24)
    ap.add_argument("--max-steps", type=int, default=2000)
    ap.add_argument("--max-live-tokens", type=int, default=0,
                    help="admission token budget (0 = rows-only admission)")
    ap.add_argument("--replan-window", type=int, default=8)
    ap.add_argument("--replan-threshold", type=float, default=1.25)
    ap.add_argument("--replan-cooldown", type=int, default=16)
    ap.add_argument("--no-replan", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    # --- HTTP serving front end (DESIGN.md §13) ------------------------------
    ap.add_argument("--http", action="store_true",
                    help="serve the multi-tenant asyncio HTTP front end "
                         "(POST /v1/generate, POST /v1/stream [SSE], "
                         "GET /metrics, GET /healthz) over the continuous "
                         "engine; SIGINT/SIGTERM drain gracefully")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000,
                    help="listen port (0 = ephemeral, printed on start)")
    ap.add_argument("--admission", default="slo", choices=["slo", "fcfs"],
                    help="admission controller: 'slo' (priority classes, "
                         "degrade/shed, tenant-fair DRR) or 'fcfs' "
                         "(baseline global queue)")
    ap.add_argument("--quantum", type=int, default=512,
                    help="DRR per-tenant token refill per engine tick")
    ap.add_argument("--quota-cap", type=int, default=8192,
                    help="DRR banked-deficit cap per tenant (tokens)")
    # --- observability (DESIGN.md §12) ---------------------------------------
    ap.add_argument("--no-obs", action="store_true",
                    help="disable the metrics/trace subsystem entirely")
    ap.add_argument("--obs-print-every", type=int, default=0,
                    help="scheduler steps between one-line stats prints "
                         "(0 = off)")
    ap.add_argument("--metrics-out", default="",
                    help="write Prometheus text metrics here on exit")
    ap.add_argument("--trace-out", default="",
                    help="write Chrome trace-event JSON here on exit "
                         "(Perfetto-loadable)")
    return ap


def main() -> None:
    ap = build_parser()
    args = ap.parse_args()
    if not args.arch and not args.config:
        ap.error("one of --arch or --config is required")
    # record which flags the user explicitly typed (vs argparse defaults):
    # --config merging applies only the former.  Matches both "--flag value"
    # and "--flag=value" spellings.
    argv = sys.argv[1:]
    args._explicit = {
        a.dest for a in ap._actions
        if any(tok == opt or tok.startswith(opt + "=")
               for opt in a.option_strings for tok in argv)}

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.http:
        run_http(args)
    elif args.continuous:
        run_continuous(args)
    else:
        run_oneshot(args)


if __name__ == "__main__":
    main()
