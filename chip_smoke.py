"""Serving-path smoke test on a TPU: granite-3-2b at its published widths.

Drives `repro.api.Engine` exactly as ``python -m repro.launch.serve``
builds it (``serve._engine_config`` / ``serve._build_engine``), in bf16,
with random weights made from ``--seed``, and checks what comes out.

``python chip_smoke.py`` needs one chip and runs three phases in this one
process:

  (a) ``Engine.generate``: one prefill of 4 prompts of 1024 tokens, then
      8 decode steps over the paged cache.
  (b) ``Engine.run_trace`` on the continuous scheduler with Ada-SnapKV
      per-head budgets and the FairKV-DP planner: 8 requests of 1024
      prompt tokens and 32 new tokens each, on 4 rows.
  (c) the first decode step's logits with the native paged Pallas kernel
      against the jnp oracle, on the prompts of (a).

``python chip_smoke.py --four-chips`` needs four chips and runs only the
mesh executor on a (data=1, model=4) mesh: the traffic of (b) once under
``fairkv_dp`` and once under ``sha`` (plain tensor parallelism).  It checks
that greedy tokens agree between the plans, that decode compiles once per
engine, and that the decode step's only collectives are one all-reduce
per layer.

The run fails — non-zero exit, no result line — when JAX finds no TPU, a
request does not finish, a logit is not finite, a compiled prefill or
decode step holds no Pallas kernel (``tpu_custom_call``), or two logit sets
that must agree differ by more than bf16 noise.  On success the last line
of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# two logit sets that differ only in summation order must agree within
# this share of the larger set's peak magnitude (8 bf16 ulps)
BF16_TOL = 8 * 2.0 ** -8


class SmokeFailure(RuntimeError):
    pass


@dataclasses.dataclass(frozen=True)
class Setup:
    """Model and traffic of every phase; the defaults are the chip run."""

    arch: str = "granite-3-2b"
    smoke: bool = False  # the arch's reduced fp32 variant (CPU rehearsal)
    prompt: int = 1024  # tokens per prompt, one length: prefill compiles once
    budget: int = 256  # Ada-SnapKV mean per-head KV budget
    rows: int = 4  # batch rows
    requests: int = 8  # continuous-trace requests
    gen: int = 32  # new tokens per continuous request
    oneshot_gen: int = 8  # decode steps of phase (a)
    seed: int = 0
    require_kernels: bool = True  # compiled steps must hold a Pallas kernel

    @property
    def max_seq_len(self) -> int:
        return self.prompt + self.gen + 8

    def args(self, *extra: str) -> argparse.Namespace:
        """serve's own flags: 4 logical shards of 3 head slots, so the
        8 KV heads leave 4 slots for Fair-Copying replicas."""
        from repro.launch import serve
        argv = ["--arch", self.arch, "--budget", str(self.budget),
                "--shards", "4", "--slots-per-shard", "3", "--copies", "4",
                "--seed", str(self.seed), *extra]
        if self.smoke:
            argv.append("--smoke")
        return serve.build_parser().parse_args(argv)

    def oneshot_args(self, *extra: str) -> argparse.Namespace:
        return self.args("--prompt-len", str(self.prompt),
                         "--batch", str(self.rows),
                         "--gen", str(self.oneshot_gen), *extra)

    def continuous_args(self, *extra: str) -> argparse.Namespace:
        return self.args("--continuous", "--rows", str(self.rows),
                         "--requests", str(self.requests),
                         "--min-prompt", str(self.prompt),
                         "--max-prompt", str(self.prompt),
                         "--gen", str(self.gen), *extra)


def log(msg: str) -> None:
    print(msg, flush=True)


def build(setup: Setup, args, params, *scheduler):
    """serve's own engine construction; ``scheduler``: its SchedulerConfig
    for a continuous engine."""
    from repro.launch import serve
    ecfg = serve._engine_config(args, setup.max_seq_len, setup.rows,
                                *scheduler)
    return serve._build_engine(args, ecfg, params=params)


def init_weights(setup: Setup):
    """One random weight set for every engine of the run."""
    import jax

    from repro.api.config import DTYPES
    from repro.launch import serve
    from repro.models import init_params
    args = setup.oneshot_args()
    ecfg = serve._engine_config(args, setup.max_seq_len, setup.rows)
    params = init_params(ecfg.model, jax.random.PRNGKey(setup.seed),
                         dtype=DTYPES[ecfg.dtype],
                         max_seq_len=setup.max_seq_len)
    n = sum(x.size for x in jax.tree.leaves(params))
    m = ecfg.model
    log(f"model {m.name}: {m.n_layers} layers, d_model {m.d_model}, "
        f"{m.n_heads} query / {m.n_kv_heads} KV heads, head_dim "
        f"{m.head_dim}, d_ff {m.d_ff}, vocab {m.vocab_size}; "
        f"{n / 1e9:.3f} B params in {ecfg.dtype}, seed {setup.seed}")
    return params


def prompt_batch(setup: Setup, model_cfg) -> dict:
    from repro.configs.base import InputShape
    from repro.training.data import SyntheticLM
    data = SyntheticLM(model_cfg, InputShape("chip_smoke", setup.prompt,
                                             setup.rows, "prefill"))
    return data.get_batch(0)


def compiles(eng) -> dict:
    """Per-kind StepFn compile counts (``stepfn_compiles_total``)."""
    fam = eng.metrics().get("stepfn_compiles_total", {"series": []})
    out: dict = {}
    for s in fam["series"]:
        kind = s["labels"]["kind"]
        out[kind] = out.get(kind, 0) + int(s["value"])
    return out


def decode_wall(eng) -> tuple:
    """(sum seconds, count) of the decode StepFn wall-time histogram."""
    fam = eng.metrics().get("stepfn_wall_s", {"series": []})
    tot = n = 0
    for s in fam["series"]:
        if s["labels"]["kind"] == "decode":
            tot, n = tot + s["sum"], n + s["count"]
    return tot, n


def check_finite(what: str, x) -> None:
    x = np.asarray(x, np.float32)
    bad = int((~np.isfinite(x)).sum())
    if bad:
        raise SmokeFailure(f"{what}: {bad} of {x.size} logits not finite")


def check_kernel(setup: Setup, what: str, hlo: str) -> None:
    found = "tpu_custom_call" in hlo
    log(f"{what}: Pallas kernel in compiled HLO: {found}")
    if setup.require_kernels and not found:
        raise SmokeFailure(f"{what}: no tpu_custom_call in the compiled HLO")


def check_close(what: str, got, ref) -> float:
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    err = float(np.abs(got - ref).max())
    scale = float(np.abs(ref).max())
    log(f"{what}: max |diff| {err:.6g} vs peak |logit| {scale:.6g} "
        f"(limit {BF16_TOL * scale:.6g}); argmax agreement "
        f"{float((got.argmax(-1) == ref.argmax(-1)).mean()):.4f}")
    if not err <= BF16_TOL * scale:
        raise SmokeFailure(f"{what}: logits differ by {err:.6g} > "
                           f"{BF16_TOL * scale:.6g}")
    return err


def phase_oneshot(setup: Setup, params) -> None:
    log("== phase (a): Engine.generate")
    args = setup.oneshot_args()
    eng = build(setup, args, params)
    prompts = prompt_batch(setup, eng.cfg.model)
    res = eng.generate(prompts, args.gen, collect_logits=True)
    check_finite("phase (a) logits", res.logits)
    steps = np.asarray(res.step_s[1:] or res.step_s) * 1e3
    log(f"prefill {setup.rows} x {setup.prompt} tokens: "
        f"{res.prefill_s * 1e3:.1f} ms (compile included) | decode "
        f"{np.median(steps):.2f} ms/step median of {len(steps)} after the "
        f"first (rough, host clock) | {res.tokens.size} tokens generated")
    log(f"realized per-head budget min/mean/max "
        f"{res.lengths.min()}/{res.lengths.mean():.1f}/{res.lengths.max()}"
        f" | plan E {res.efficiency:.4f}")
    log(f"phase (a) compiles: {compiles(eng)}")
    ex = eng.executor
    check_kernel(setup, "phase (a) prefill", ex.prefill_hlo(
        eng.sp, prompts, eng.pa))
    check_kernel(setup, "phase (a) decode", ex.decode_hlo(
        eng.sp, eng.state, eng.pa, eng.state.last_tokens))


def run_continuous(setup: Setup, params, *extra: str):
    """Build a continuous engine from serve's flags and run the trace;
    returns (engine, requests).  Fails unless decode compiled
    exactly once, at warm-up."""
    from repro.api import synthesize_requests
    from repro.launch import serve
    args = setup.continuous_args(*extra)
    scfg = dataclasses.replace(serve._scheduler_config(args),
                               collect_logits=True)
    eng = build(setup, args, params, scfg)
    reqs = synthesize_requests(args.requests, args.rate,
                               eng.cfg.model.vocab_size,
                               min_prompt=args.min_prompt,
                               max_prompt=args.max_prompt,
                               max_new_tokens=args.gen, seed=args.seed)
    eng.warmup()  # compiles the decode step outside the run
    warm = compiles(eng).get("decode", 0)
    wall0, n0 = decode_wall(eng)
    out = eng.run_trace(reqs, max_steps=args.max_steps)
    wall1, n1 = decode_wall(eng)
    after = compiles(eng)
    log(f"requests finished {out['finished']}/{out['total']} | tokens "
        f"generated {out['generated_tokens']} | {out['steps']} steps in "
        f"{out['wall_s']:.2f} s (prefill compile included) | mid-stream "
        f"admissions {out['mid_stream_admissions']} | preemptions "
        f"{out['preemptions']} | replans {out['replans']}")
    if out["finished"] != out["total"]:
        raise SmokeFailure(f"only {out['finished']}/{out['total']} "
                           f"requests finished")
    for r in reqs:
        if r.n_generated != args.gen:
            raise SmokeFailure(f"request {r.req_id} generated "
                               f"{r.n_generated}/{args.gen} tokens")
        check_finite(f"request {r.req_id} logits", np.stack(r.logits))
    per_step = (wall1 - wall0) / max(n1 - n0, 1)
    log(f"decode {per_step * 1e3:.2f} ms/step mean over {n1 - n0} steps "
        f"(rough, host clock) | compiles {after} (decode {warm} at "
        f"warm-up)")
    pool = eng.scheduler.state.cache.k_pool
    log(f"KV pool {pool.dtype} {tuple(pool.shape)} (layers, blocks, "
        f"block size, head_dim)")
    load = np.asarray(out["peak_shard_load"])
    log(f"per-shard KV load at peak ({eng.cfg.planner.mode}): "
        f"{load.astype(int).tolist()} tokens, max/mean "
        f"{load.max() / max(load.mean(), 1e-9):.4f}")
    if warm != 1 or after.get("decode", 0) != 1:
        raise SmokeFailure(f"decode compiled {warm} time(s) at warm-up and "
                           f"{after.get('decode', 0)} in all; want 1")
    return eng, reqs


def phase_continuous(setup: Setup, params) -> None:
    log("== phase (b): Engine.run_trace, ada_snapkv, fairkv_dp")
    eng, _ = run_continuous(setup, params)
    sched, ex = eng.scheduler, eng.executor
    tokens = {"tokens": np.zeros((1, setup.prompt), np.int32)}
    check_kernel(setup, "phase (b) prefill", ex.prefill_hlo(
        sched.sp, tokens, sched.pa))
    check_kernel(setup, "phase (b) decode", ex.decode_hlo(
        sched.sp, sched.state, sched.pa, sched.state.last_tokens))


def phase_kernel_parity(setup: Setup, params) -> None:
    log("== phase (c): first decode step, paged Pallas kernel vs jnp "
        "oracle")
    logits = {}
    for impl in ("pallas", "jnp"):
        args = setup.oneshot_args("--paged-impl", impl)
        eng = build(setup, args, params)
        res = eng.generate(prompt_batch(setup, eng.cfg.model), 1,
                           collect_logits=True)
        logits[impl] = np.asarray(res.logits[:, 1], np.float32)
        check_finite(f"{impl} decode logits", logits[impl])
        if impl == "pallas":
            check_kernel(setup, "phase (c) paged decode",
                         eng.executor.decode_hlo(eng.sp, eng.state, eng.pa,
                                                 eng.state.last_tokens))
        del eng, res
        gc.collect()
    check_close("pallas vs jnp decode logits", logits["pallas"],
                logits["jnp"])


def four_chips(setup: Setup, params) -> None:
    from repro.distributed.hlo_stats import collective_stats
    runs = {}
    for planner in ("fairkv_dp", "sha"):
        log(f"== four chips: mesh (data=1, model=4), planner {planner}")
        eng, reqs = run_continuous(
            setup, params, "--executor", "mesh", "--data", "1",
            "--planner", planner, "--no-replan")
        sched = eng.scheduler
        hlo = eng.executor.decode_hlo(sched.sp, sched.state, sched.pa,
                                      sched.state.last_tokens)
        stats = collective_stats(hlo)
        log(f"{planner} decode collectives/device: "
            f"{ {k: int(v['count']) for k, v in sorted(stats.items())} }")
        n_layers = eng.cfg.model.n_layers
        ar = int(stats.get("all-reduce", {}).get("count", 0))
        if ar != n_layers or "all-gather" in stats:
            raise SmokeFailure(
                f"{planner}: decode audit wants {n_layers} all-reduces and "
                f"no all-gather, got {stats}")
        check_kernel(setup, f"{planner} mesh decode", hlo)
        runs[planner] = {r.req_id: (list(r.generated), np.stack(r.logits))
                         for r in reqs}
        del eng, sched, reqs
        gc.collect()
    compare_plans(runs["fairkv_dp"], runs["sha"])


def compare_plans(a: dict, b: dict) -> None:
    """Greedy tokens must agree between two placements of one model.  The
    plans sum the o-projection over different shard groupings, so bf16
    rounding differs; a request may part ways only where its two top
    logits are within that noise, and logits on the common prefix must
    agree within it."""
    exact, worst = 0, 0.0
    for rid in sorted(a):
        ta, la = a[rid]
        tb, lb = b[rid]
        n = next((i for i, (x, y) in enumerate(zip(ta, tb)) if x != y), None)
        upto = len(ta) if n is None else n + 1
        diff = float(np.abs(la[:upto] - lb[:upto]).max())
        worst = max(worst, diff)
        scale = float(np.abs(lb[:upto]).max())
        if n is None:
            exact += 1
        else:
            gap = abs(float(la[n, ta[n]]) - float(la[n, tb[n]]))
            log(f"request {rid}: tokens part at index {n} with a top-2 "
                f"logit gap of {gap:.6g}")
            if gap > BF16_TOL * scale:
                raise SmokeFailure(
                    f"request {rid}: plans chose tokens {ta[n]} vs {tb[n]} "
                    f"at index {n}, top-2 gap {gap:.6g} is not a tie")
        if diff > BF16_TOL * scale:
            raise SmokeFailure(f"request {rid}: logits differ by {diff:.6g}"
                               f" > {BF16_TOL * scale:.6g}")
    log(f"fairkv_dp vs sha: {exact}/{len(a)} requests with identical greedy "
        f"tokens; max |logit diff| on common prefixes {worst:.6g}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (data=1, model=4) mesh comparison "
                         "of fairkv_dp and sha")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the traffic")
    opts = ap.parse_args(argv)

    import jax

    dev = jax.devices()
    if dev[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{dev[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    want = 4 if opts.four_chips else 1
    if len(dev) < want:
        print(f"chip_smoke: needs {want} chips, JAX found {len(dev)}",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    log(f"device: {dev[0].device_kind} x {len(dev)} | jax {jax.__version__}"
        f" | compile cache {enable_compile_cache()}")
    setup = Setup(seed=opts.seed)
    t0 = time.perf_counter()
    params = init_weights(setup)
    phases = ([four_chips] if opts.four_chips else
              [phase_oneshot, phase_continuous, phase_kernel_parity])
    for phase in phases:
        t = time.perf_counter()
        phase(setup, params)
        gc.collect()
        log(f"-- {phase.__name__} done in {time.perf_counter() - t:.1f} s")
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
