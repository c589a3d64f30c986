"""Observability subsystem (DESIGN.md §12): registry semantics, trace
export validity, engine integration, local↔mesh metrics parity, the
zero-recompile invariant as an asserted metric, and the disabled path.

The mesh parity test runs in a subprocess (the fake-device count must be
set before the first jax import, like tests/test_executor.py): the same
continuous trace drives a local and a mesh engine, and every deterministic
counter/gauge family must agree between the two registries.
"""
import json
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.api import (
    CompressionConfig,
    Engine,
    EngineConfig,
    PagingConfig,
    PlannerConfig,
    SchedulerConfig,
    synthesize_requests,
)
from repro.obs import (
    NULL_OBS,
    MetricsRegistry,
    Obs,
    ObsConfig,
    TraceBuffer,
)

ARCH = "minitron-8b"


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


def test_counter_labels_and_total():
    reg = MetricsRegistry()
    c = reg.counter("req_total", help="requests")
    c.inc()
    c.inc(2, tenant="a")
    c.inc(3, tenant="b")
    assert c.value() == 1.0
    assert c.value(tenant="a") == 2.0
    assert c.total() == 6.0
    assert reg.counter_value("req_total", tenant="b") == 3.0
    assert reg.counter_value("never_touched") == 0.0
    with pytest.raises(ValueError, match="decrease"):
        c.inc(-1)


def test_counter_preregister_zero_series():
    reg = MetricsRegistry()
    reg.counter("outcomes").inc(0, outcome="accepted")
    snap = reg.snapshot()["outcomes"]["series"]
    assert snap == [{"labels": {"outcome": "accepted"}, "value": 0.0}]


def test_gauge_last_write_wins():
    reg = MetricsRegistry()
    g = reg.gauge("load")
    g.set(3.0, shard="0")
    g.set(7.0, shard="0")
    assert g.value(shard="0") == 7.0
    assert g.value(shard="9", default=-1.0) == -1.0


def test_registry_memoizes_and_rejects_kind_mismatch():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(TypeError, match="registered as counter"):
        reg.gauge("x")


def test_histogram_buckets_cumulative():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    s = reg.snapshot()["lat"]["series"][0]
    assert s["count"] == 5
    assert s["buckets"] == {"0.1": 1, "1": 3, "10": 4, "+Inf": 5}
    assert s["sum"] == pytest.approx(56.05)
    assert h.mean() == pytest.approx(56.05 / 5)
    # boundary lands in the bucket whose upper bound it equals
    h2 = reg.histogram("edge", buckets=(1.0, 2.0))
    h2.observe(1.0)
    assert reg.snapshot()["edge"]["series"][0]["buckets"]["1"] == 1


def test_histogram_rejects_bad_buckets():
    reg = MetricsRegistry()
    with pytest.raises(ValueError, match="strictly"):
        reg.histogram("bad", buckets=(1.0, 1.0))
    with pytest.raises(ValueError, match="strictly"):
        reg.histogram("bad2", buckets=())


def test_snapshot_deterministic():
    def build():
        reg = MetricsRegistry()
        reg.gauge("b").set(1, z="1", a="2")
        reg.counter("a").inc(5, shard="3")
        reg.histogram("c", buckets=(1.0,)).observe(0.5)
        return reg

    r1, r2 = build(), build()
    assert r1.snapshot() == r2.snapshot()
    assert r1.to_prometheus() == r2.to_prometheus()
    assert r1.to_jsonl() == r2.to_jsonl()
    assert list(r1.snapshot()) == sorted(r1.snapshot())


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("req_total", help="all requests").inc(2, shard="0")
    reg.gauge("depth").set(3.5)
    reg.histogram("lat", buckets=(0.5, 1.0)).observe(0.2)
    text = reg.to_prometheus()
    lines = text.splitlines()
    assert "# TYPE req_total counter" in lines
    assert "# HELP req_total all requests" in lines
    assert 'req_total{shard="0"} 2' in lines
    assert "depth 3.5" in lines
    assert 'lat_bucket{le="0.5"} 1' in lines
    assert 'lat_bucket{le="+Inf"} 1' in lines
    assert "lat_sum 0.2" in lines
    assert "lat_count 1" in lines
    # every non-comment line is "<name or name{labels}> <value>"
    for ln in lines:
        if ln.startswith("#"):
            continue
        body, val = ln.rsplit(" ", 1)
        float(val)
        assert body and not body.startswith("{")


def test_prometheus_escapes_label_values():
    reg = MetricsRegistry()
    reg.counter("c").inc(1, path='a"b\\c')
    text = reg.to_prometheus()
    assert 'path="a\\"b\\\\c"' in text


def test_jsonl_parses_per_line():
    reg = MetricsRegistry()
    reg.counter("a").inc(1, k="v")
    reg.histogram("h", buckets=(1.0,)).observe(2.0)
    lines = reg.to_jsonl().strip().splitlines()
    assert len(lines) == 2
    recs = [json.loads(ln) for ln in lines]
    assert {r["name"] for r in recs} == {"a", "h"}


# ---------------------------------------------------------------------------
# trace buffer
# ---------------------------------------------------------------------------


def test_trace_chrome_schema():
    tr = TraceBuffer(capacity=16)
    with tr.span("step", rows=3):
        pass
    tr.instant("compile", kind="decode")
    tr.complete("external", time.perf_counter(), 0.25)
    doc = json.loads(tr.export_json())
    evs = doc["traceEvents"]
    assert len(evs) == 3
    for ev in evs:
        assert ev["ph"] in ("X", "i")
        assert isinstance(ev["ts"], float) and ev["ts"] >= 0
        assert "name" in ev and "pid" in ev and "tid" in ev
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
    assert evs[0]["args"] == {"rows": 3}
    assert evs[2]["dur"] == pytest.approx(0.25e6, rel=0.05)


def test_trace_ring_is_bounded():
    tr = TraceBuffer(capacity=4)
    for i in range(10):
        tr.instant("e", i=i)
    evs = json.loads(tr.export_json())["traceEvents"]
    assert [e["args"]["i"] for e in evs] == [6, 7, 8, 9]


def test_trace_span_records_exception():
    tr = TraceBuffer()
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    (ev,) = tr.to_chrome()["traceEvents"]
    assert ev["args"]["error"] == "RuntimeError"


# ---------------------------------------------------------------------------
# Obs handle + disabled path
# ---------------------------------------------------------------------------


def test_obs_config_validation():
    with pytest.raises(ValueError, match="trace_capacity"):
        ObsConfig(trace_capacity=0)
    with pytest.raises(ValueError, match="print_every"):
        ObsConfig(print_every=-1)


def test_obs_build_disabled_is_null():
    obs = Obs.build(ObsConfig(enabled=False))
    assert not obs.enabled
    assert obs.metrics is NULL_OBS.metrics
    assert obs.trace is NULL_OBS.trace


def test_null_obs_noops():
    m, tr = NULL_OBS.metrics, NULL_OBS.trace
    m.counter("a", help="h").inc(5, k="v")
    m.gauge("b").set(1.0)
    m.histogram("c").observe(0.5)
    with tr.span("s"):
        tr.instant("i")
    assert m.snapshot() == {}
    assert m.to_prometheus() == ""
    assert m.counter_value("a", k="v") == 0.0
    assert json.loads(tr.export_json())["traceEvents"] == []


def test_null_obs_overhead_smoke():
    """The disabled path must stay cheap: 100k no-op observations in well
    under a second (loose bound — this guards against accidentally putting
    real work on the disabled path, not against CI jitter)."""
    m = NULL_OBS.metrics
    c = m.counter("x")
    t0 = time.perf_counter()
    for _ in range(100_000):
        c.inc(1.0, shard="0")
    assert time.perf_counter() - t0 < 1.0


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------


def _ecfg(**kw):
    base = dict(
        n_shards=4, max_seq_len=48,
        compression=CompressionConfig(policy="ada_snapkv", budget=16,
                                      alpha_max=2.0, obs_window=8, sink=2,
                                      decode_margin=8),
        planner=PlannerConfig(mode="fairkv_dp", extra_copies=4, batch_cap=4),
        scheduler=SchedulerConfig(max_rows=4, enable_replan=False))
    base.update(kw)
    return EngineConfig.smoke(ARCH, **base)


REQUIRED_FAMILIES = {
    "sched_admissions_total", "sched_retirements_total",
    "sched_replans_total", "shard_load_tokens", "shard_projected_load",
    "sched_imbalance", "sched_active_rows", "sched_queue_depth",
    "ttft_s", "itl_s", "e2e_s", "stepfn_wall_s", "stepfn_compiles_total",
}


def _drive(eng, n=5, seed=2, gen=4):
    reqs = synthesize_requests(n, 0.6, eng.cfg.model.vocab_size,
                               min_prompt=10, max_prompt=18,
                               max_new_tokens=gen, seed=seed)
    out = eng.run_trace(reqs, max_steps=300)
    assert out["finished"] == out["total"], out
    return out


def test_engine_continuous_populates_metrics_and_trace():
    eng = Engine.build(_ecfg(cache_backend="paged",
                             paging=PagingConfig(block_size=8)))
    out = _drive(eng)
    snap = eng.metrics()
    assert REQUIRED_FAMILIES <= set(snap), sorted(REQUIRED_FAMILIES - set(snap))
    # paged backend adds the pool-pressure gauges
    assert {"pool_free_blocks", "pool_blocks_in_use",
            "pool_free_blocks_partition", "pool_fragmentation_blocks",
            "pool_max_refcount", "pool_alloc_blocks_total",
            "pool_freed_blocks_total", "cache_live_tokens"} <= set(snap)
    m = eng.obs.metrics
    assert m.counter_value("sched_admissions_total") == out["finished"]
    assert m.counter_value("sched_retirements_total") == out["finished"]
    assert m.get("ttft_s").count() == out["finished"]
    # per-shard gauges exist for every model shard
    shard_series = snap["shard_load_tokens"]["series"]
    assert {s["labels"]["shard"] for s in shard_series} == {"0", "1", "2", "3"}
    # the export surfaces parse
    assert "sched_admissions_total" in eng.metrics_prometheus()
    doc = json.loads(eng.trace_export())
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"sched.step", "sched.admit", "sched.decode", "sched.retire",
            "exec.prefill", "exec.decode", "exec.decode.wait"} <= names
    # run() summary carries the satellite telemetry
    assert out["latency"]["n_finished"] == out["finished"]
    assert "p50_ttft_s" in out["latency"] and "p50_itl_s" in out["latency"]
    assert np.isfinite(out["tokens_per_s"])


def test_engine_oneshot_populates_ttft_itl():
    eng = Engine.build(_ecfg())
    prompts = np.random.default_rng(0).integers(
        0, eng.cfg.model.vocab_size, (2, 12))
    eng.generate(prompts, 3)
    assert eng.obs.metrics.get("ttft_s").count() == 1
    assert eng.obs.metrics.get("itl_s").count() == 3
    assert eng.obs.metrics.get("stepfn_wall_s").count(
        kind="decode", executor="local") == 3


def test_zero_recompile_invariant_as_metric():
    """The PR-4 no-retrace contract, asserted through the obs counter: a
    live replan (weights + plan arrays swapped mid-flight) must leave
    stepfn_compiles_total{kind=decode} at its warm value."""
    eng = Engine.build(_ecfg(
        scheduler=SchedulerConfig(max_rows=4, replan_window=4,
                                  replan_threshold=1.05, replan_cooldown=10),
        max_seq_len=64))
    reqs = synthesize_requests(8, 0.4, eng.cfg.model.vocab_size,
                               min_prompt=12, max_prompt=28,
                               max_new_tokens=10, seed=3)
    out = eng.run_trace(reqs, max_steps=500)
    assert out["finished"] == 8
    assert any(ev["accepted"] for ev in out["replan_log"])
    m = eng.obs.metrics
    assert m.counter_value("stepfn_compiles_total", kind="decode",
                           executor="local") == 1
    assert m.counter_value("sched_replans_total", outcome="accepted") >= 1
    assert (m.counter_value("sched_replans_total", outcome="accepted")
            == out["replans"])
    # the metric agrees with the executor's own trace counter
    assert eng.executor.decode_traces == 1


# every span the scheduler and the executor put in a profiler capture
SCHED_SPANS = {"sched.step", "sched.admit", "sched.splice",
               "sched.first_token", "sched.admit_chunked",
               "sched.prefill_chunk", "sched.prepare_decode", "sched.decode",
               "sched.readout", "sched.retire", "sched.load", "sched.replan"}
EXEC_SPANS = {"exec.prefill", "exec.prefill.wait", "exec.prefill_chunk",
              "exec.prefill_chunk.wait", "exec.decode", "exec.decode.wait"}


def _program_spans(log_dir):
    """(name, start_ns, end_ns, args) of every ``sched.*``/``exec.*`` event
    on the host planes of the capture under ``log_dir``."""
    from pathlib import Path

    from jax.profiler import ProfileData
    (path,) = Path(log_dir).rglob("*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("sched.", "exec.")):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def test_spans_reach_the_profiler_capture(tmp_path):
    """Under a ``jax.profiler`` capture, every scheduler phase and StepFn
    call lands on the host plane, nested as the tick runs them and keyed
    by request id; the perf_counter stamps order submit <= admit <= first
    token, and ``itl_s`` takes one sample per inter-token gap."""
    import jax

    from repro.api import PrefixConfig

    # prompts of 10-28 tokens against 16-token chunks: both admission
    # paths (monolithic and chunked) run
    eng = Engine.build(_ecfg(cache_backend="paged",
                             paging=PagingConfig(block_size=8),
                             prefix=PrefixConfig(chunk_tokens=16)))
    reqs = synthesize_requests(6, 0.6, eng.cfg.model.vocab_size,
                               min_prompt=10, max_prompt=28,
                               max_new_tokens=4, seed=2)
    with jax.profiler.trace(str(tmp_path)):
        out = eng.run_trace(reqs, max_steps=300)
        eng.scheduler.replan(profile=np.asarray(eng.profile))
        for _ in range(3):  # idle ticks: nothing queued or live
            eng.step()
    assert out["finished"] == out["total"]
    spans = _program_spans(tmp_path)
    names = {n for n, *_ in spans}
    assert SCHED_SPANS | EXEC_SPANS <= names, sorted(
        (SCHED_SPANS | EXEC_SPANS) - names)

    def inside(inner, outer_name):
        return [o for o in spans if o[0] == outer_name
                and o[1] <= inner[1] and inner[2] <= o[2]]

    ids = {r.req_id for r in reqs}
    admits = [s for s in spans if s[0] == "sched.admit"]
    assert admits and {s[3]["req"] for s in admits} <= ids
    assert all({"prompt_len", "row"} <= set(s[3]) for s in admits)
    prefills = [s for s in spans if s[0] == "exec.prefill"]
    assert len(prefills) == len(admits)
    for p in prefills:
        (admit,) = inside(p, "sched.admit")
        assert len(inside(admit, "sched.step")) == 1
        assert len(inside(p, "exec.prefill.wait")) == 0  # child, not parent
    for w in (s for s in spans if s[0] == "exec.prefill.wait"):
        assert len(inside(w, "exec.prefill")) == 1
    for r in reqs:
        assert r.t_submit <= r.t_admit <= r.t_first <= r.t_last
        assert r.ttft_seconds() == r.t_first - r.t_submit
        assert len(r.itl_gaps) == r.n_generated - 1
    m = eng.obs.metrics
    gaps = sum(r.n_generated - 1 for r in reqs)
    assert m.get("itl_s").count() == gaps
    assert m.get("ttft_s").count() == len(reqs)
    # the run summary's itl_s is the same per-gap quantity
    every_gap = [g for r in reqs for g in r.itl_gaps]
    assert out["latency"]["p50_itl_s"] == pytest.approx(
        float(np.percentile(every_gap, 50)))
    ph = m.get("sched_phase_s")
    assert ph.count(phase="step") == out["steps"] + 3
    assert ph.count(phase="admit") == len(admits)
    assert ph.count(phase="retire") == len(reqs)
    # a tick is busy when it called a StepFn: its sched.step span holds an
    # exec.* span (the three idle ticks hold none)
    ticks = [s for s in spans if s[0] == "sched.step"]
    busy = [t for t in ticks if any(
        s[0].startswith("exec.") and t[1] <= s[1] and s[2] <= t[2]
        for s in spans)]
    assert 0 < len(busy) < len(ticks)


def test_null_trace_records_nothing():
    from repro.obs import NULL_TRACE
    with NULL_TRACE.span("sched.step", step=0):
        with NULL_TRACE.span("exec.decode"):
            NULL_TRACE.instant("preempt", req=1)
    NULL_TRACE.complete("sched.load", time.perf_counter(), 0.1)
    assert len(NULL_TRACE) == 0
    assert NULL_TRACE.to_chrome()["traceEvents"] == []


def test_disabled_obs_keeps_outputs_identical():
    """enabled=False must change nothing but the telemetry: same tokens,
    empty exports."""
    outs = {}
    for enabled in (True, False):
        eng = Engine.build(_ecfg(obs=ObsConfig(enabled=enabled)))
        _drive(eng, n=3)
        outs[enabled] = {r.req_id: list(r.generated)
                         for r in eng.finished_requests}
        if not enabled:
            assert eng.metrics() == {}
            assert eng.metrics_prometheus() == ""
            assert json.loads(eng.trace_export())["traceEvents"] == []
    assert outs[True] == outs[False]


# ---------------------------------------------------------------------------
# local ↔ mesh metrics parity (multi-device subprocess)
# ---------------------------------------------------------------------------


SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, __SRC__)
import json
import numpy as np
from repro.api import (CompressionConfig, Engine, EngineConfig, ObsConfig,
                       PagingConfig, PlannerConfig, SchedulerConfig,
                       synthesize_requests)
from repro.launch.mesh import make_host_mesh

def cfg_for(executor):
    return EngineConfig.smoke(
        "minitron-8b", n_shards=4, max_seq_len=32,
        compression=CompressionConfig(policy="ada_snapkv", budget=16,
                                      alpha_max=2.0, obs_window=8, sink=2,
                                      decode_margin=8),
        planner=PlannerConfig(mode="fairkv_dp", extra_copies=4, batch_cap=4),
        scheduler=SchedulerConfig(max_rows=4, enable_replan=False),
        cache_backend="paged", paging=PagingConfig(block_size=8),
        executor=executor, profile_skew=2.0, profile_seed=1)

eng_l = Engine.build(cfg_for("local"))
eng_m = Engine.build(cfg_for("mesh"), mesh=make_host_mesh(model=4, data=2),
                     params=eng_l.params)
snaps = {}
for name, eng in (("local", eng_l), ("mesh", eng_m)):
    reqs = synthesize_requests(5, 0.6, 256, min_prompt=10, max_prompt=16,
                               max_new_tokens=4, seed=2)
    out = eng.run_trace(reqs, max_steps=300)
    assert out["finished"] == out["total"], out
    snap = eng.metrics()
    # deterministic families only: counts and end-state gauges, not wall time
    snaps[name] = {
        "families": sorted(snap),
        "admissions": snap["sched_admissions_total"]["series"],
        "retirements": snap["sched_retirements_total"]["series"],
        "shard_load": snap["shard_load_tokens"]["series"],
        "imbalance": snap["sched_imbalance"]["series"],
        "pool_alloc": snap["pool_alloc_blocks_total"]["series"],
        "pool_freed": snap["pool_freed_blocks_total"]["series"],
        "cache_live": snap["cache_live_tokens"]["series"],
        "ttft_count": eng.obs.metrics.get("ttft_s").count(),
        "itl_count": eng.obs.metrics.get("itl_s").count(),
        "decode_compiles": eng.obs.metrics.counter_value(
            "stepfn_compiles_total", kind="decode", executor=eng.cfg.executor),
        "trace_names": sorted({e["name"] for e in json.loads(
            eng.trace_export())["traceEvents"]}),
    }
print(json.dumps(snaps))
"""


def test_mesh_metrics_parity_multidevice_subprocess():
    """The same continuous trace on a local engine and a 2x4-mesh engine
    must land identical deterministic metrics (admissions, retirements,
    per-shard load, pool counters, latency-sample counts) in both
    registries — and both decode StepFns compile exactly once, observed
    through the metric itself."""
    import repro
    src = list(repro.__path__)[0].rsplit("/repro", 1)[0]
    code = SUBPROC.replace("__SRC__", repr(src))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    snaps = json.loads(out.stdout.strip().splitlines()[-1])
    loc, msh = snaps["local"], snaps["mesh"]
    for key in ("families", "admissions", "retirements", "shard_load",
                "imbalance", "pool_alloc", "pool_freed", "cache_live",
                "ttft_count", "itl_count", "trace_names"):
        assert loc[key] == msh[key], (key, loc[key], msh[key])
    assert loc["decode_compiles"] == 1
    assert msh["decode_compiles"] == 1
