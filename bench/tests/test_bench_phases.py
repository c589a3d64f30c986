"""The readers of the program's own phases: the scheduler's ``sched.*`` and
the executor's ``exec.*`` spans and the requests' perf_counter stamps — on
the smoke cell, on a program trace recorded here, and on hand-counted
intervals (nanoseconds)."""
import time
import types

import pytest

import phases
import run
import stats
import trace_reduce
from smoke_cell import smoke_limits, smoke_of

SEED = 2 ** 31 + 99
CELL = "qwen1.5-110b.prefill-mix"


@pytest.fixture(scope="module")
def smoke_ctx():
    """The context the harness hands its readers, from one sound run of the
    smoke cell."""
    seen = {}
    real = run._reader

    def reader(name):
        mod = real(name)

        def read(ctx):
            seen["ctx"] = ctx
            return mod.read(ctx)

        return types.SimpleNamespace(read=read)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(run, "_reader", reader)
        res = run.run(CELL, SEED, 2.0, False, require_chip=False,
                      limits=smoke_limits(CELL),
                      cell=smoke_of(CELL))
    assert res["correct"], res["checks"]
    return seen["ctx"]


def _read(name, ctx):
    return run._reader(name).read(ctx)


def test_scheduler_readers_on_the_smoke_cell(smoke_ctx):
    wait = _read("queue_wait_p95_ms", smoke_ctx)
    admit = _read("admit_ms", smoke_ctx)
    assert wait is not None and 0.0 <= wait < float("inf")
    # one admission is one prefill call plus its splice and readout
    assert admit is not None
    assert admit >= _read("prefill_step_ms", smoke_ctx)
    # the run captured no trace: the readers of the capture read nothing
    assert phases.traced(smoke_ctx) is None
    assert _read("step_host_ms", smoke_ctx) is None
    assert _read("host_phase_idle_share", smoke_ctx) is None


def test_decode_readers_on_the_smoke_cell(smoke_ctx):
    assert _read("decode_step_ms", smoke_ctx) > 0
    ctx = dict(smoke_ctx, peaks={"bf16_flops": 1e12})
    mfu = _read("decode_step_mfu", ctx)
    # decode_mfu's FLOPs of the same ticks, over the decode StepFn's time
    # in place of a traced stretch's
    s, _ = ctx["stepfn"]["decode"]
    same = dict(ctx, trace_ticks=ctx["ticks"], trace_host_s=s)
    assert mfu > 0 and mfu == pytest.approx(_read("decode_mfu", same))
    # the run captured no trace: the kernel's roofline reads nothing
    assert _read("paged_decode_roofline", smoke_ctx) is None


def test_window_change_reads_the_splices_of_the_window(smoke_ctx):
    """The registry's snapshots at the window's ends: every admission of the
    window spliced its request into the live pools once, in place."""
    m0, m1 = smoke_ctx["metrics0"], smoke_ctx["metrics1"]
    t0, t1 = smoke_ctx["t0"], smoke_ctx["t1"]
    admitted = sum(1 for r in smoke_ctx["reqs"].values()
                   if r.t_admit is not None and t0 <= r.t_admit <= t1)
    assert admitted > 0
    assert phases.window_change(m0, m1, "paged_splice_total",
                                mode="inplace") == admitted
    assert phases.window_change(m0, m1, "paged_splice_total",
                                mode="copy") == 0
    assert smoke_ctx["stepfn"]["prefill"] == phases.window_change(
        m0, m1, "stepfn_wall_s", kind="prefill")


def _fam(kind, *series):
    return {"kind": kind, "help": "", "series": list(series)}


def test_window_change_by_hand():
    m0 = {"c": _fam("counter", {"labels": {"mode": "a"}, "value": 2.0}),
          "h": _fam("histogram", {"labels": {"kind": "x", "n": "1"},
                                  "sum": 0.5, "count": 2})}
    m1 = {"c": _fam("counter", {"labels": {"mode": "a"}, "value": 5.0},
                    {"labels": {"mode": "b"}, "value": 1.0}),
          "h": _fam("histogram", {"labels": {"kind": "x", "n": "1"},
                                  "sum": 2.0, "count": 5},
                    {"labels": {"kind": "x", "n": "2"}, "sum": 1.0,
                     "count": 1},
                    {"labels": {"kind": "y", "n": "1"}, "sum": 9.0,
                     "count": 9}),
          "g": _fam("gauge", {"labels": {}, "value": 3.0})}
    assert phases.window_change(m0, m1, "c", mode="a") == 3.0
    assert phases.window_change(m0, m1, "c", mode="b") == 1.0  # new series
    assert phases.window_change(m0, m1, "c") == 4.0
    assert phases.window_change(m0, m1, "h", kind="x") == (2.5, 4)
    assert phases.window_change(m0, m1, "h", n=1) == (10.5, 12)
    assert phases.window_change(m0, m1, "absent") is None
    with pytest.raises(ValueError):
        phases.window_change(m0, m1, "g")


# two busy ticks (an admission, a decode) and an idle spin between them
TICKS = {"devices": {}, "host": [
    (0, 100, "sched.step"), (10, 60, "exec.prefill"),
    (30, 60, "exec.prefill.wait"), (20, 95, "sched.admit"),
    (110, 120, "sched.step"), (112, 118, "sched.load"),
    (130, 200, "sched.step"), (140, 180, "exec.decode"),
    (150, 180, "exec.decode.wait"), (185, 199, "np.asarray(jax.Array)")]}


def test_step_host_ms_reads_the_busy_ticks_alone():
    # host outside the StepFn calls: 100 - 50 and 70 - 40; the spin is out
    assert phases.busy_tick_host_ns(TICKS, 0, 200) == [50, 30]
    assert phases.busy_tick_host_ns(TICKS, 0, 150) == [50]
    ctx = {phases.CTX_KEY: (TICKS, 0, 200)}
    assert _read("step_host_ms", ctx) == pytest.approx(40e-6)


def _stamped(submit, admit):
    return types.SimpleNamespace(t_submit=submit, t_admit=admit)


def test_queue_wait_leaves_out_admissions_after_the_capture_stopped():
    """The capture stops 4 s into a 10-s window and the host then writes
    it out: a request due before the stop but admitted after it waited
    behind the write-out and is left out, as is one due after the stop; one
    never admitted counts as infinite."""
    log = stats.TokenLog()
    reqs = {}
    for idx, due, admit in ((0, 1.0, 1.2), (1, 2.0, 2.1), (2, 3.0, 3.6),
                            (3, 3.5, 7.0), (4, 5.0, 5.1)):
        log.due[idx] = due
        reqs[idx] = _stamped(due, admit)
    ctx = {"t0": 0.0, "t1": 10.0, "log": log, "reqs": reqs,
           "trace_host_s": 4.0}
    assert _read("queue_wait_p95_ms", ctx) == pytest.approx(
        1e3 * stats.percentile([0.2, 0.1, 0.6], 95))
    reqs[1].t_admit = None
    assert _read("queue_wait_p95_ms", ctx) == float("inf")
    del ctx["trace_host_s"]  # no capture: the whole window counts
    reqs[1].t_admit = 2.1
    assert _read("queue_wait_p95_ms", ctx) == pytest.approx(
        1e3 * stats.percentile([0.2, 0.1, 0.6, 3.5, 0.1], 95))


def test_ttft_splits_into_four_terms_on_one_clock(smoke_ctx):
    terms = phases.ttft_terms(smoke_ctx)
    got, missing = smoke_ctx["log"].ttfts(smoke_ctx["t0"], smoke_ctx["t1"])
    assert terms and missing == 0 and len(terms) == len(got)
    log = smoke_ctx["log"]
    for idx, parts in terms.items():
        assert all(x >= 0.0 for x in parts), (idx, parts)
        assert sum(parts) == pytest.approx(log.stamps[idx][0] - log.due[idx],
                                           abs=1e-9)
    waits = [p[1] for p in terms.values()]
    assert _read("queue_wait_p95_ms", smoke_ctx) == pytest.approx(
        1e3 * stats.percentile(waits, 95))


def test_host_phase_idle_share_on_a_recorded_program_trace(tmp_path,
                                                           monkeypatch):
    """A smoke engine's own spans, captured by the profiler here, with the
    device taken as busy exactly while the host waits on it (each
    ``exec.*.wait``): every other stretch of the window is idle, and the
    sched, exec and harness shares add up to it."""
    import jax
    import numpy as np

    from repro.api import (CompressionConfig, Engine, EngineConfig,
                           PagingConfig, SchedulerConfig)
    eng = Engine.build(EngineConfig.smoke(
        "minitron-8b", n_shards=4, max_seq_len=48,
        compression=CompressionConfig(policy="headkv", budget=16,
                                      obs_window=8, decode_margin=8),
        scheduler=SchedulerConfig(max_rows=4, enable_replan=False),
        cache_backend="paged", paging=PagingConfig(block_size=8)))
    rng = np.random.default_rng(0)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.traced"):
            for i in range(4):
                time.sleep(0.05)  # the generator waits between ticks
                eng.submit(rng.integers(0, 256, 20), max_new_tokens=3)
                eng.step()
            while eng.scheduler.active or eng.scheduler.queue:
                eng.step()
            for _ in range(2):  # idle spins: nothing live or queued
                eng.step()
    # the harness loads the capture from its trace directory, once, and
    # hands it to the readers
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path)
    ctx = {phases.CTX_KEY: run.load_capture()}
    trace, w0, w1 = phases.traced(ctx)
    assert phases.traced(ctx)[0] is trace
    waits = [(s, e, n) for s, e, n in trace["host"]
             if n.startswith("exec.") and n.endswith(".wait")]
    assert waits
    trace["devices"] = {"/device:TPU:0": waits}
    share = _read("host_phase_idle_share", ctx)
    # every busy tick holds its StepFn calls, and the spins are left out
    ticks = [h for h in trace["host"] if h[2] == "sched.step"]
    busy = phases.busy_tick_host_ns(trace, w0, w1)
    assert 0 < len(busy) < len(ticks) and all(ns > 0 for ns in busy)
    red = trace_reduce.reduce(trace, w0, w1, {})
    idle = phases.idle_split(phases.idle_by_span(trace, w0, w1))
    assert sum(idle.values()) == pytest.approx(w1 - w0 - red["busy_ns"])
    assert all(v > 0 for v in idle.values()), idle
    assert share == pytest.approx(100.0 * idle["sched"] / (w1 - w0))


# one chip; the host's spans nest as a tick runs them, and a runtime event
# (not a program span) lies over the gap inside the prefill's device wait
HAND = {
    "devices": {"/device:TPU:0": [(8, 35, "fusion.1"), (48, 88, "fusion.2"),
                                  (140, 172, "fusion.3"), (176, 179, "copy")]},
    "host": [(0, 200, "bench.traced"), (5, 195, "bench.step"),
             (10, 190, "sched.step"), (20, 100, "sched.admit"),
             (30, 90, "exec.prefill"), (40, 90, "exec.prefill.wait"),
             (36, 47, "np.asarray(jax.Array)"), (92, 98, "sched.splice"),
             (120, 180, "sched.decode"), (125, 175, "exec.decode"),
             (130, 175, "exec.decode.wait"), (182, 188, "sched.load")],
}


def test_host_phase_idle_share_by_hand():
    # gaps (midpoint -> innermost program span): [0,8] (4) none -> harness;
    # [35,48] (41.5) exec.prefill.wait; [88,140] (114) sched.step;
    # [172,176] (174) exec.decode.wait; [179,200] (189.5) sched.step
    idle = phases.idle_by_span(HAND, 0, 200)
    assert idle == pytest.approx({"harness": 8, "exec.prefill.wait": 13,
                                  "sched.step": 52 + 21,
                                  "exec.decode.wait": 4})
    split = phases.idle_split(idle)
    assert split == pytest.approx({"sched": 73, "exec": 17, "harness": 8})
    red = trace_reduce.reduce(HAND, 0, 200, {})
    assert sum(split.values()) == pytest.approx(200 - red["busy_ns"])
    ctx = {phases.CTX_KEY: (HAND, 0, 200)}
    assert _read("host_phase_idle_share", ctx) == pytest.approx(36.5)
    total = _read("device_idle_share.ttft", {"trace": red})
    assert sum(100 * v / 200 for v in split.values()) == pytest.approx(total)
    line = phases.idle_line(idle, 200)
    assert "sched 36.500 [sched.step 36.500]" in line
    assert "harness 4.000 | total 49.000" in line


def test_readers_read_nothing_from_a_program_without_them():
    """A program without the stamps or the spans (the parent of the change
    that added them) reads as nothing, not zero."""
    log = stats.TokenLog()
    log.due[0] = 1.0
    log.add(0, 1, 1.5)
    bare = {"t0": 0.0, "t1": 2.0, "log": log,
            "reqs": {0: types.SimpleNamespace(prompt=[1, 2])},
            "stepfn": {"prefill": (0.1, 1)},
            phases.CTX_KEY: ({"devices": HAND["devices"],
                              "host": [h for h in HAND["host"]
                                       if not h[2].startswith(("sched.",
                                                               "exec."))]},
                             0, 200)}
    for name in ("queue_wait_p95_ms", "admit_ms", "step_host_ms",
                 "host_phase_idle_share"):
        assert _read(name, bare) is None, name
    assert phases.ttft_terms(bare) == {}
