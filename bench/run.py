"""One run of one benchmark cell on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The run, in order:

1. refuses to go on (non-zero exit, no result line) unless JAX finds a TPU
   and at least the cell's ``chips``;
2. turns on the persistent compile cache (``JAX_COMPILATION_CACHE_DIR``, or
   ``.jax_cache/`` in the checkout);
3. makes the weights on the device from ``--seed`` in one jitted call;
4. builds the `Engine` (continuous scheduler, paged pool, HeadKV with the
   mix's seeded importance, the FairKV planner given the same importance as
   its profile);
5. warms the cell's own shapes: one throwaway request per prompt length,
   then ``Engine.warmup()``;
6. fills the batch (saturated mixes) — everything so far is set-up;
7. measures for ``--seconds``: the harness submits each request once its
   due time has passed and calls ``Engine.step()`` in between, stamping
   every output token with the host clock;
8. frees the program's state and checks what it served against the plain
   reference (``reference.py``), then prints the result.

With ``--trace 1`` it also records a ``jax.profiler`` trace of the window's
first seconds and reports the cell's per-layer metrics instead of its
end-to-end ones.  Metric values come from the readers in
``bench/metrics/<name>.py``; everything cell-specific is read from
``BENCHMARK.json`` and the files it names.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

import cells  # noqa: E402
import counts  # noqa: E402
import stats  # noqa: E402
import trace_reduce  # noqa: E402
import traffic as traffic_gen  # noqa: E402

TRACE_SECONDS = 4.0  # profiled stretch at the start of a --trace 1 window
DRAIN_SECONDS = 60.0  # wait past the window for answers already due
TRACE_DIR = ROOT / ".bench_run" / "trace"  # fixed, inside the checkout
WARM_ID = 1 << 40  # request ids of the warm-up's throwaway requests


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def log(msg: str) -> None:
    print(msg, flush=True)


# ---- set-up ----------------------------------------------------------------


def devices_for(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Counts programs traced and compiled while armed (JAX's own events)."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traced",
              "/jax/core/compile/backend_compile_duration": "compiled"}

    def __init__(self):
        self.armed = False
        self.counts = {"traced": 0, "compiled": 0}

    def __call__(self, event, duration_secs, **kwargs):
        kind = self.EVENTS.get(event)
        if kind is not None and self.armed:
            self.counts[kind] += 1

    def __enter__(self):
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self)


def make_params(model_cfg, m: dict, seed: int, dtype_name: str):
    """The served weights, made on the device in one jitted call."""
    import jax

    from repro.api.config import DTYPES
    from repro.models import init_params
    from weights import perturb, root_key
    dtype = DTYPES[dtype_name]
    fn = jax.jit(lambda k: perturb(init_params(model_cfg, k, dtype=dtype),
                                   m, k, dtype))
    params = fn(root_key(seed))
    jax.block_until_ready(params)
    return params


def pool_blocks(cell: cells.Cell, imp: np.ndarray) -> int:
    """Per-layer pool size that holds every row at its largest need, so no
    request is ever preempted: each head of a row may grow to its kept
    prompt tokens plus the mix's longest output (capped at capacity)."""
    comp = cell.compression
    cap = counts.static_capacity(comp)
    bs = int(cell.config["engine"]["paging"]["block_size"])
    out_max = int(cell.traffic["output_max"])
    per_row = 0
    for T in cell.traffic["prompt_buckets"]:
        lens = counts.live_lengths(counts.headkv_keep(imp, comp, T),
                                   out_max, cap)
        blocks = np.maximum(-(-lens // bs), 1)  # (L, H)
        per_row = max(per_row, int(blocks.sum(axis=1).max()))
    return int(cell.traffic["rows"]) * per_row + 1


def plan_profile(cell: cells.Cell, imp: np.ndarray) -> np.ndarray:
    """(L, H) expected retained prompt tokens: the planner's profile."""
    comp, tr = cell.compression, cell.traffic
    w = np.asarray(tr["prompt_weights"], np.float64)
    keeps = [counts.headkv_keep(imp, comp, T).astype(np.float64)
             for T in tr["prompt_buckets"]]
    return sum(wi * k for wi, k in zip(w / w.sum(), keeps))


def build_engine(cell: cells.Cell, params, imp: np.ndarray):
    from repro.api import Engine
    ecfg = cells.engine_config(cell, pool_blocks(cell, imp))
    mesh = None
    if cell.chips > 1:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(model=cell.chips, data=1)
    return Engine.build(ecfg, params=params, profile=plan_profile(cell, imp),
                        head_importance=imp, mesh=mesh)


# ---- the driven loop -------------------------------------------------------


class Feeder:
    """Submits requests, steps the engine, stamps every output token."""

    def __init__(self, eng):
        self.eng = eng
        self.sched = None
        self.log = stats.TokenLog()
        self.live = {}  # index -> (Request, tokens seen)
        self.reqs = {}  # index -> Request
        self.d0 = {}  # req_id -> decode-step count at its first decode
        self.finish_t = {}  # index -> host time it finished
        self.ticks = []  # (host time, [(index, decode appends so far)])
        self.lateness = []  # submit time - due time (s)
        self.preempted = 0

    def submit(self, idx: int, prompt, max_new: int, due: float) -> None:
        from repro.serving.request import Request
        req = Request(req_id=idx, prompt=np.asarray(prompt, np.int32),
                      max_new_tokens=int(max_new))
        self.eng.submit(req)
        self.sched = self.eng.scheduler
        self.reqs[idx] = req
        self.live[idx] = (req, 0)
        self.log.due[idx] = due
        self.lateness.append(time.perf_counter() - due)

    def step(self) -> float:
        import jax
        with jax.profiler.TraceAnnotation("bench.step"):
            ev = self.eng.step()
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.readout"):
            if ev["admitted"]:
                d = int(self.sched.state.decode_steps) - 1
                for rid, _row in ev["admitted"]:
                    self.d0[rid] = d
            decoded = []
            for idx, (req, seen) in list(self.live.items()):
                n = len(req.generated)
                if n < seen:  # preempted and recomputed from scratch
                    self.preempted += 1
                    self.log.stamps.pop(idx, None)
                    seen = 0
                if n > seen:
                    self.log.add(idx, n - seen, t)
                    if n >= 2:
                        decoded.append((idx, n - 1))
                self.live[idx] = (req, n)
                if req.is_finished:
                    self.finish_t[idx] = t
                    del self.live[idx]
            self.ticks.append((t, decoded))
        return t


# ---- metrics ---------------------------------------------------------------


def _reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, workload: str, trace: bool) -> list:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def stepfn_totals(eng) -> dict:
    """{kind: (seconds, calls)} of the ``stepfn_wall_s`` histogram."""
    fam = eng.metrics().get("stepfn_wall_s", {"series": []})
    out = {}
    for s in fam["series"]:
        k = s["labels"]["kind"]
        t, n = out.get(k, (0.0, 0))
        out[k] = (t + s["sum"], n + s["count"])
    return out


# ---- correctness -----------------------------------------------------------


def sample_served(drv: Feeder, t0: float, k: int, seed: int) -> list:
    """Requests finished after the window opened: the one with the most
    served tokens plus ``k - 1`` drawn from the seed."""
    from reference import Served
    done = sorted((i for i, t in drv.finish_t.items()
                   if t > t0 and i < WARM_ID),
                  key=lambda i: (-len(drv.reqs[i].generated), i))
    if not done:
        return []
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    rest = done[1:]
    pick = [done[0]] + [rest[j] for j in sorted(
        rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False))]
    return [Served(prompt=np.asarray(drv.reqs[i].prompt, np.int32),
                   tokens=np.asarray(drv.reqs[i].generated, np.int32),
                   d0=drv.d0[drv.reqs[i].req_id]) for i in pick]


def gap_stats(gaps: list) -> dict:
    """The numbers the check compares, over every sampled position: the
    widest gap, the mean gap, and the share of positions whose served token
    is not the reference's argmax."""
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    if g.size == 0:
        return {"max_logit_gap": float("inf"), "mean_logit_gap": float("inf"),
                "mismatch_share": 1.0, "tokens": 0}
    return {"max_logit_gap": float(g.max()), "mean_logit_gap": float(g.mean()),
            "mismatch_share": float((g > 0).mean()), "tokens": int(g.size)}


def check_served(cell: cells.Cell, seed: int, served: list,
                 control: bool = False) -> dict:
    """Gap statistics (``gap_stats``) of the served tokens under the plain
    reference.  ``control`` adds, under ``"control"``, the same statistics
    for the control's tokens: at each served position, the token that the
    reference computed in fp8 (``reference.py``) puts first."""
    import jax.numpy as jnp

    import reference
    from weights import root_key
    imp = counts.importance(cell.model["n_layers"], cell.model["n_kv_heads"],
                            cell.traffic["importance"])
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
             "float16": jnp.float16}[cell.config["engine"]["dtype"]]
    args = (cell.model, cell.compression, imp, root_key(seed), dtype, served)
    lg = reference.logits(*args, precision="fp32")
    gaps = [reference.served_gap(x, r.tokens) for x, r in zip(lg, served)]
    out = gap_stats(gaps)
    if gaps and out["tokens"]:
        i = int(np.argmax([g.max() for g in gaps]))
        j = int(np.argmax(gaps[i]))
        log(f"widest gap {gaps[i][j]:.6f} at position {j} of "
            f"{len(gaps[i])} served tokens of a {len(served[i].prompt)}-token "
            f"prompt (decode step count at its first decode {served[i].d0})")
    if control:
        low = reference.logits(*args, precision="fp8")
        cg = [reference.served_gap(x, np.asarray(jnp.argmax(c, axis=1)))
              for x, c in zip(lg, low)]
        out["control"] = gap_stats(cg)
    return out


def judge(chk: dict, limits: dict, failed: int) -> tuple:
    """(correct, checks): each number the cell's check file names beside
    its limit, the sampled positions beside their least, and whether all
    hold with no request due in the window left unanswered."""
    checks = {k: {"value": chk[k], "limit": v}
              for k, v in limits["limits"].items()}
    checks["sampled_tokens"] = {"value": chk["tokens"],
                                "limit": limits["min_sampled_tokens"]}
    correct = bool(all(chk[k] <= v for k, v in limits["limits"].items())
                   and chk["tokens"] >= limits["min_sampled_tokens"]
                   and failed == 0)
    return correct, checks


def load_limits(workload: str) -> dict:
    return json.loads((BENCH / "checks" / f"{workload}.json").read_text())


# ---- one run ---------------------------------------------------------------


def run(workload: str, seed: int, seconds: float, trace: bool,
        require_chip: bool = True, cell: cells.Cell = None,
        limits: dict = None, fault=None, control: bool = False) -> dict:
    """One run; returns the result object.  ``cell``/``limits`` replace the
    ones ``BENCHMARK.json`` names, and ``fault`` (a callable given the
    engine) breaks the timed path — both for the harness's own tests.
    ``control`` judges the control's tokens in the program's place
    (``check_served``): the result's ``correct`` and ``checks`` are then
    the control's, and the program's go under ``"program"``."""
    import jax
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = cell or cells.load(workload)
    limits = limits or load_limits(workload)
    devs = devices_for(cell.chips, require_chip)
    cache_dir = None
    if require_chip:
        from repro.launch.compile_cache import enable_compile_cache
        cache_dir = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev0 = devs[0]
    log(f"cell {cell.name}: {cell.config_name} x {cell.traffic_name}, "
        f"{cell.chips} chip(s) {dev0.device_kind} | seed {seed} | "
        f"compile cache {cache_dir}")

    m = cell.model
    model_cfg = cells.model_config(cell)
    params = make_params(model_cfg, m, seed, cell.config["engine"]["dtype"])
    imp = counts.importance(m["n_layers"], m["n_kv_heads"],
                            cell.traffic["importance"])
    eng = build_engine(cell, params, imp)
    del params
    if fault is not None:
        fault(eng)
    tr = cell.traffic
    drv = Feeder(eng)
    warm_rng = np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                      int(seed) >> 32, 3])
    for j, T in enumerate(tr["prompt_buckets"]):
        drv.submit(WARM_ID + j, warm_rng.integers(0, m["vocab_size"], T),
                   2, time.perf_counter())
    while drv.live:
        drv.step()
    eng.warmup()
    planned = traffic_gen.plan(tr, seed, m["vocab_size"])
    nxt = 0
    with CompileCounter() as cc:
        if tr["arrival"] == "saturated":
            while nxt < len(planned) and planned[nxt].fill:
                p = planned[nxt]
                drv.submit(p.index, p.prompt, p.max_new_tokens,
                           time.perf_counter())
                nxt += 1
            while drv.sched.queue:
                drv.step()
        cc.armed = True
        stepfn0 = stepfn_totals(eng)
        tick0 = len(drv.ticks)
        prof = None
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(
                str(TRACE_DIR), profiler_options=_profile_options())
            prof = jax.profiler.TraceAnnotation("bench.traced")
            prof.__enter__()
        t0 = time.perf_counter()
        setup_s = t0 - T_START
        t_end = t0 + seconds
        t_tr = t0 + min(TRACE_SECONDS, seconds)
        t = t0
        while t < t_end:
            if tr["arrival"] == "saturated":
                while len(drv.sched.queue) < tr["backlog"] and nxt < len(
                        planned):
                    p = planned[nxt]
                    drv.submit(p.index, p.prompt, p.max_new_tokens,
                               time.perf_counter())
                    nxt += 1
            else:
                now = time.perf_counter()
                while nxt < len(planned) and t0 + planned[nxt].due_s <= now:
                    p = planned[nxt]
                    drv.submit(p.index, p.prompt, p.max_new_tokens,
                               t0 + p.due_s)
                    nxt += 1
            t = drv.step()
            if prof is not None and t >= t_tr:
                prof.__exit__(None, None, None)
                prof = None
                t_tr = t
                jax.profiler.stop_trace()
        t1 = t
        queue_end = len(drv.sched.queue)
        stepfn1 = stepfn_totals(eng)
        cc.armed = False
        window_ticks = drv.ticks[tick0:]
        # answers due in the window that have not come yet are waited for
        missing = [i for i, d in drv.log.due.items()
                   if t0 <= d < t1 and i not in drv.log.stamps]
        while missing and time.perf_counter() < t1 + DRAIN_SECONDS:
            drv.step()
            missing = [i for i in missing if i not in drv.log.stamps]
    mem_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devs)
    log(f"window {t1 - t0:.3f} s, {len(window_ticks)} steps, {queue_end} "
        f"queued at its close | set-up "
        f"{setup_s:.3f} s | generator lateness mean "
        f"{np.mean(drv.lateness):.6f} s max {np.max(drv.lateness):.6f} s")
    log(f"compiles inside the window: {cc.counts['compiled']} "
        f"(programs traced: {cc.counts['traced']}) | preemptions "
        f"{drv.preempted + eng.scheduler.n_preemptions} | peak HBM "
        f"{mem_peak} bytes | KV pool "
        f"{eng.scheduler.state.cache.k_pool.dtype} "
        f"{tuple(eng.scheduler.state.cache.k_pool.shape)}")

    ctx = {
        "cell": cell, "model": m, "chips": cell.chips, "seconds": seconds,
        "t0": t0, "t1": t1, "setup_s": setup_s, "log": drv.log,
        "ticks": window_ticks,
        "stepfn": {k: (v[0] - stepfn0.get(k, (0.0, 0))[0],
                       v[1] - stepfn0.get(k, (0.0, 0))[1])
                   for k, v in stepfn1.items()},
        "peaks": counts.peaks(dev0.device_kind) if require_chip else None,
        "imp": imp, "reqs": drv.reqs, "trace": None,
        "admitted_prompts": _admitted_prompts(drv, t0, t1),
    }
    wanted = cell_metrics(bench, workload, trace)
    readers = {x["name"]: _reader(x["name"]) for x in wanted}
    breakdown = None
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": cell.chips, "memory_peak_bytes": mem_peak}
    if trace:
        kernels = {n: r.KERNEL for n, r in readers.items()
                   if hasattr(r, "KERNEL")}
        tr_data = trace_reduce.load(trace_reduce.find_xplane(TRACE_DIR))
        w0, w1 = trace_reduce.window_of(tr_data, "bench.traced")
        red = trace_reduce.reduce(tr_data, w0, w1, kernels)
        ctx["trace"] = red
        ctx["trace_ticks"] = [x for x in window_ticks if x[0] <= t_tr]
        ctx["trace_host_s"] = t_tr - t0
        device["busy_s"] = red["busy_ns"] / 1e9
        device["window_s"] = red["window_ns"] / 1e9
        breakdown = {"device_ops": trace_reduce.top(red["op_ns"]),
                     "idle_gaps": trace_reduce.top(red["idle_gaps"])}
    metrics = {}
    for x in wanted:
        v = readers[x["name"]].read(ctx)
        if v is None:
            if not trace:
                raise RuntimeError(f"end-to-end metric {x['name']} read "
                                   f"nothing")
            continue
        metrics[x["name"]] = {"value": float(v), "unit": x["unit"]}

    due_in = [i for i, d in drv.log.due.items() if t0 <= d < t1]
    got = set(drv.log.stamps)
    served_in = {i for i, s in drv.log.stamps.items()
                 if any(t0 < x <= t1 for x in s)}
    attempted = len(served_in | set(due_in))
    failed = sum(1 for i in due_in if i not in got)

    served = sample_served(drv, t0, int(limits["sample_requests"]), seed)
    sched = eng.scheduler
    del eng, drv, sched, ctx
    gc.collect()
    chk = check_served(cell, seed, served, control=control)
    correct, checks = judge(chk, limits, failed)
    program = None
    if control:
        # the control's tokens in the program's place, through the same
        # comparison: the run's verdict and checks are the control's
        program = {"correct": correct, "checks": checks}
        correct, checks = judge(chk["control"], limits, failed)
    for k, v in checks.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device, "queue_end": queue_end}
    if breakdown is not None:
        result["breakdown"] = breakdown
    # every number the check computes, compared or not
    result["readings"] = {"program": {k: v for k, v in chk.items()
                                      if k != "control"}}
    if program is not None:
        result["program"] = program
        result["readings"]["control"] = chk["control"]
    result["checks"] = checks
    return result


def _admitted_prompts(drv: Feeder, t0: float, t1: float) -> list:
    """Prompt lengths of the requests whose first token came in the window
    (their prefills ran there)."""
    out = []
    for i, s in drv.log.stamps.items():
        if s and t0 < s[0] <= t1 and i < WARM_ID:
            out.append(len(drv.reqs[i].prompt))
    return out


def _profile_options():
    import jax
    po = jax.profiler.ProfileOptions()
    po.python_tracer_level = 0  # Python function tracing would slow the host
    po.host_tracer_level = 2
    return po


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    try:
        res = run(a.workload, a.seed, a.seconds, bool(a.trace))
    except NoChip as e:
        print(f"bench/run.py: {e}; nothing was measured", file=sys.stderr)
        return 2
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
