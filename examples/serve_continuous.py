"""Example: streaming continuous batching through the `repro.api` facade.

A short Poisson trace of requests flows through `Engine.stream` on a smoke
config: requests queue while the batch is full, get admitted into freed rows
mid-stream, and — because Ada-SnapKV's per-head budgets are imbalanced — the
realized per-shard KV load drifts.  The replan trigger is set aggressively so
the trace demonstrates an online replan: the head placement is rebuilt from
the *realized* profile, the live paged cache is migrated into the new slot
layout, and decoding continues without interruption.  `Engine.stream` yields one
`StreamEvent` per generated token, so the example also shows request-level
token streaming.

``--executor mesh`` runs the same trace with the StepFns under
``shard_map`` on a (data, model) host mesh (DESIGN.md §10) — fake the
devices first with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

Run:  PYTHONPATH=src JAX_PLATFORMS=cpu python examples/serve_continuous.py \
          [--executor mesh [--data 2]]
"""
import argparse

from repro.api import (
    CompressionConfig,
    Engine,
    EngineConfig,
    PagingConfig,
    PlannerConfig,
    SchedulerConfig,
    latency_percentiles,
    list_executors,
    synthesize_requests,
)

ARCH = "minitron-8b"
ROWS = 4
SHARDS = 4
GEN = 10


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--executor", default="local",
                    help=f"execution strategy; registered: {list_executors()}")
    ap.add_argument("--data", type=int, default=1,
                    help="mesh executor: data-axis width")
    args = ap.parse_args(argv)

    mesh = None
    if args.executor == "mesh":
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(model=SHARDS, data=args.data)

    cfg = EngineConfig.smoke(
        ARCH, n_shards=SHARDS, max_seq_len=64,
        compression=CompressionConfig(policy="ada_snapkv", budget=16,
                                      alpha_max=2.0, obs_window=8, sink=2,
                                      decode_margin=8),
        # plan against a synthetic profile; the replan will use the realized
        # one (EngineConfig.profile_seed/skew control the synthetic draw)
        planner=PlannerConfig(mode="fairkv_dp", extra_copies=4,
                              batch_cap=ROWS),
        scheduler=SchedulerConfig(max_rows=ROWS, replan_window=4,
                                  replan_threshold=1.05, replan_cooldown=10),
        paging=PagingConfig(block_size=args.block_size),
        executor=args.executor)
    eng = Engine.build(cfg, mesh=mesh)

    reqs = synthesize_requests(8, rate=0.4, vocab_size=cfg.model.vocab_size,
                               min_prompt=12, max_prompt=28,
                               max_new_tokens=GEN, seed=3)
    print(f"{len(reqs)} requests, arrivals at steps "
          f"{[r.arrival_step for r in reqs]}")
    n_tokens = 0
    for ev in eng.stream(reqs, max_steps=500):
        n_tokens += 1
        if ev.finished:
            print(f"  [stream] req {ev.req_id} finished at step {ev.step} "
                  f"({ev.index + 1} tokens)")
    assert len(eng.finished_requests) == len(reqs), (
        f"only {len(eng.finished_requests)}/{len(reqs)} requests finished "
        f"within max_steps")

    print("\nper-request latency:")
    for r in eng.finished_requests:
        print(f"  req {r.req_id}: prompt {r.prompt_len:3d} | queued "
              f"{r.queueing_steps():2d} steps | total {r.latency_steps():3d} "
              f"steps | {r.n_generated} tokens")
    pct = latency_percentiles(eng.finished_requests)
    # decode starts the same tick the first request is admitted, so
    # mid-stream == admitted after the earliest admission tick (matches the
    # scheduler's run() accounting)
    first_admit = min(r.admit_step for r in eng.finished_requests)
    mid = sum(1 for r in eng.finished_requests
              if r.admit_step > first_admit)
    print(f"\np50 {pct['p50_steps']:.0f} / p99 {pct['p99_steps']:.0f} steps | "
          f"{n_tokens} tokens streamed | mid-stream admissions {mid}")
    mem = eng.stats().pool.detail
    print(f"paged cache: {mem['blocks_in_use']} blocks in use "
          f"(pool {mem['pool_bytes']} B) vs slot-equivalent "
          f"{mem['slot_equivalent_bytes']} B")
    if eng.replan_log:
        for ev in eng.replan_log:
            tag = "accepted" if ev["accepted"] else "rejected"
            print(f"replan @ step {ev['step']} ({tag}): realized imbalance "
                  f"{ev['imbalance_before']:.3f} -> "
                  f"{ev['imbalance_after']:.3f}")
    else:
        print("no replan fired (trace too balanced) — rerun with a different "
              "seed or lower SchedulerConfig.replan_threshold")


if __name__ == "__main__":
    main()
