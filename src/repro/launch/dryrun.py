import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this prints/records:
- memory_analysis()  — per-device bytes (proves the cell fits a v5e chip)
- cost_analysis()    — per-device FLOPs / bytes accessed
- the collective schedule (op → bytes) parsed from the compiled HLO

Results land in experiments/dryrun/<arch>__<shape>__<mesh>.json, which the
roofline report (benchmarks/roofline.py) consumes.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun [--arch A] [--shape S]
        [--mesh single|multi|both] [--planner fairkv_dp|sha|fairkv_nodp]
"""

import argparse
import gc
import json
import time
import traceback

import jax

from repro.configs import ALL_ARCHS, SHAPES, get_config
from repro.distributed.hlo_stats import collective_stats, while_body_stats
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_cell

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             planner_mode: str = "fairkv_dp", verbose: bool = True) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape_name in cfg.shape_skips:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": cfg.shape_skips[shape_name]}
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    cell = build_cell(cfg, shape, mesh, planner_mode=planner_mode)
    jitted = jax.jit(cell.fn, donate_argnums=cell.donate_argnums)
    with mesh:
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    colls = collective_stats(hlo)
    bodies = while_body_stats(hlo)
    # XLA:CPU emulates bf16 (and int8-dequant) matmuls by f32 upcasts of the
    # operands — a CPU-only artifact (TPU bf16 is MXU-native; int8 dequant
    # fuses into the weight read).  Subtract the bound (f32 copy = 2x bf16
    # bytes, 4x int8 bytes) to estimate the TPU peak; validated against
    # f32-compiled cells (EXPERIMENTS.md §Dry-run).
    import numpy as _np
    emu_bytes = 0
    for leaf in jax.tree.leaves(cell.args):
        dt = getattr(leaf, "dtype", None)
        if dt not in (jax.numpy.bfloat16, jax.numpy.int8):
            continue
        shd = getattr(leaf, "sharding", None)
        per_dev = (int(_np.prod(shd.shard_shape(leaf.shape)))
                   if shd is not None else leaf.size)
        emu_bytes += per_dev * (2 if dt == jax.numpy.bfloat16 else 1) * 2
        if dt == jax.numpy.int8:
            emu_bytes += per_dev * 2  # int8 -> f32 is 4x
    raw_peak = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    adj_peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes
                + max(0, ma.temp_size_in_bytes - emu_bytes))
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "kind": cell.kind,
        "planner": planner_mode,
        "status": "ok",
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "weights_2d": bool(cell.meta.get("weights_2d", False)),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "emulation_bound_bytes": int(emu_bytes),
            "peak_per_device_gb_cpuraw": round(raw_peak / 1e9, 3),
            "peak_per_device_gb": round(adj_peak / 1e9, 3),
        },
        "cost": {
            "flops_per_device": ca.get("flops"),
            "bytes_per_device": ca.get("bytes accessed"),
        },
        "collectives": colls,
        "while_bodies": bodies,
    }
    if verbose:
        mem = rec["memory"]
        print(f"  args {mem['argument_bytes']/1e9:8.2f} GB | "
              f"temp {mem['temp_bytes']/1e9:8.2f} GB | "
              f"peak {mem['peak_per_device_gb']:8.2f} GB/dev | "
              f"flops/dev {rec['cost']['flops_per_device'] or 0:.3e} | "
              f"lower {t_lower:5.1f}s compile {t_compile:5.1f}s")
        tot = sum(c["bytes"] for c in colls.values())
        print(f"  collectives: " + ", ".join(
            f"{k}×{v['count']} ({v['bytes']/1e6:.1f} MB)"
            for k, v in sorted(colls.items())) +
            f" | total {tot/1e6:.1f} MB/dev")
    return rec


def run_executor_audit(arch: str, out_dir: str,
                       planner_mode: str = "fairkv_dp") -> dict:
    """Lower the `mesh` executor's decode StepFn (DESIGN.md §10) on the
    production (data=16, model=16) mesh from abstract args and record its
    per-device collective schedule.

    This audits the *serving* execution path the Engine actually runs —
    unlike the shape cells above, which lower the raw step functions under
    GSPMD.  The §10 contract is visible in the numbers: exactly one psum
    (all-reduce) per attention layer from the o-projection, and no
    weight all-gathers in the decode hot loop.
    """
    import jax.numpy as jnp
    from repro.compression.base import CompressionConfig
    from repro.exec.mesh import MeshExecutor
    from repro.launch.specs import cell_plan, serve_params_sds
    from repro.api import PlanArrays, ServeState
    from repro.paging.block_pool import PagingConfig
    from repro.paging.paged_cache import PagedCache, max_blocks_per_row

    cfg = get_config(arch)
    shape = SHAPES["decode_32k"]
    mesh = make_production_mesh()  # (data=16, model=16)
    n_model = mesh.shape["model"]
    ccfg = CompressionConfig(policy="ada_snapkv", budget=1024,
                             alpha_max=1.5, decode_margin=64)
    plan = cell_plan(cfg, n_model, planner_mode,
                     batch_cap=shape.global_batch)
    pa = PlanArrays.from_plan(plan)
    sp_sds = serve_params_sds(cfg, shape, plan, jnp.bfloat16, quantize=False)
    B, L, S = shape.global_batch, cfg.n_layers, plan.n_slots
    cap, Dh = ccfg.static_capacity(), cfg.head_dim
    sds = jax.ShapeDtypeStruct
    # the worst-case paged pool, one partition per (model, data) device
    # (`init_paged_cache` with n_blocks == 0)
    n_data = mesh.shape["data"]
    bs = PagingConfig().block_size
    M = max_blocks_per_row(cap, bs)
    N = n_model * n_data * ((S // n_model) * (B // n_data) * M + 1)
    state_sds = ServeState(
        cache=PagedCache(
            k_pool=sds((L, N, bs, Dh), jnp.bfloat16),
            v_pool=sds((L, N, bs, Dh), jnp.bfloat16),
            pos_pool=sds((L, N, bs), jnp.int32),
            block_table=sds((L, S, B, M), jnp.int32),
            lengths=sds((L, S, B), jnp.int32),
            positions=sds((B,), jnp.int32)),
        ssm_state=None, conv_state=None, cross_k=None, cross_v=None,
        last_tokens=sds((B,), jnp.int32), decode_steps=sds((), jnp.int32))
    executor = MeshExecutor(cfg, ccfg, mesh=mesh)
    t0 = time.time()
    hlo = executor.decode_hlo(sp_sds, state_sds, pa,
                              sds((B,), jnp.int32))
    colls = collective_stats(hlo)
    rec = {"arch": arch, "kind": "executor_decode", "planner": planner_mode,
           "mesh": "single", "shape": "decode_32k", "status": "ok",
           "compile_s": round(time.time() - t0, 2),
           "collectives": colls,
           "while_bodies": while_body_stats(hlo)}
    total = sum(c["bytes"] for c in colls.values())
    print("  executor decode StepFn collectives: " + ", ".join(
        f"{k}×{v['count']} ({v['bytes'] / 1e6:.2f} MB)"
        for k, v in sorted(colls.items())) + f" | total {total / 1e6:.2f} MB/dev")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir,
                           f"{arch}__executor_decode.json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="default: all")
    ap.add_argument("--shape", default=None, help="default: all applicable")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--planner", default="fairkv_dp",
                    choices=["sha", "fairkv_nodp", "fairkv_dp"])
    ap.add_argument("--executor-audit", action="store_true",
                    help="audit the mesh executor's decode StepFn "
                         "collectives instead of the shape-cell sweep "
                         "(requires --arch; dense attention archs)")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()

    if args.executor_audit:
        if not args.arch:
            raise SystemExit("--executor-audit requires --arch")
        run_executor_audit(args.arch, args.out, args.planner)
        return

    archs = [args.arch] if args.arch else ALL_ARCHS
    # cheap compiles first so partial sweeps still cover every arch
    default_order = ["decode_32k", "long_500k", "prefill_32k", "train_4k"]
    shapes = [args.shape] if args.shape else default_order
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)

    n_ok = n_skip = n_fail = 0
    for shape_name in shapes:
        for arch in archs:
            for multi in meshes:
                tag = f"{arch}__{shape_name}__{'multi' if multi else 'single'}"
                print(f"[{tag}] planner={args.planner}")
                try:
                    rec = run_cell(arch, shape_name, multi, args.planner)
                except Exception as e:  # record failures, keep going
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": "multi" if multi else "single",
                           "status": "fail", "error": f"{type(e).__name__}: {e}"}
                status = rec["status"]
                n_ok += status == "ok"
                n_skip += status == "skipped"
                n_fail += status == "fail"
                if status == "skipped":
                    print(f"  SKIP: {rec['reason'][:100]}")
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(rec, f, indent=1)
                gc.collect()
    print(f"\ndone: {n_ok} ok, {n_skip} skipped (documented), {n_fail} failed")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
