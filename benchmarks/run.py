"""Benchmark driver: one suite per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (one per measured entity) and
writes a machine-readable summary (``BENCH.json`` by default — the git sha
recorded inside identifies the run, so the filename stays stable): per-suite
wall time, ok flag, whatever metrics dict the suite's ``main()`` returned,
plus the git sha — so the perf trajectory of this repo is diffable across
PRs instead of living in scrollback.

Suites live in a registry (name → module), so single-figure runs stop
paying for the full sweep::

    python benchmarks/run.py --list            # show suite names
    python benchmarks/run.py --only fig6       # just fig6
    python benchmarks/run.py --only fig1,fig3  # a comma-set
    python benchmarks/run.py --skip table3     # everything else
    python benchmarks/run.py --out ''          # disable the JSON artifact

Skipped suites are never imported, so their (potentially heavy) JAX
tracing cost is not paid either.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import subprocess
import sys
import time
import traceback

# make ``import benchmarks.<suite>`` work however run.py is invoked
# (``python benchmarks/run.py`` puts benchmarks/ itself on sys.path, not
# the repo root that contains the package)
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

# name -> module path; each module exposes main() (optionally returning a
# metrics dict for the JSON artifact).  Ordered as the paper presents them
# (cheap simulation suites first, end-to-end system last).
SUITES = {
    "table1": "benchmarks.table1_cosine_similarity",
    "table2": "benchmarks.table2_gpu_utilization",
    "fig1": "benchmarks.fig1_latency_linearity",
    "fig3": "benchmarks.fig3_throughput_gain",
    "fig4": "benchmarks.fig4_ablation",
    "fig5": "benchmarks.fig5_dp_size",
    "fig6": "benchmarks.fig6_continuous_throughput",
    "fig7": "benchmarks.fig7_paged_memory",
    "fig8": "benchmarks.fig8_fair_copying_tp",
    "fig9": "benchmarks.fig9_paged_kernel",
    "fig10": "benchmarks.fig10_goodput",
    "fig11": "benchmarks.fig11_prefix_reuse",
    "fig12": "benchmarks.fig12_quantized_kv",
    "fig13": "benchmarks.fig13_speculative",
    "table3": "benchmarks.table3_quality_proxy",
}


def _parse_names(value: str) -> list:
    names = [n.strip() for n in value.split(",") if n.strip()]
    unknown = [n for n in names if n not in SUITES]
    if unknown:
        raise SystemExit(
            f"unknown suite(s) {unknown}; known: {list(SUITES)}")
    return names


def select_suites(only: str = "", skip: str = "") -> list:
    """Resolve --only/--skip into an ordered suite-name list."""
    names = _parse_names(only) if only else list(SUITES)
    for n in (_parse_names(skip) if skip else []):
        if n in names:
            names.remove(n)
    return names


def git_sha() -> str:
    try:
        return subprocess.check_output(
            ["git", "rev-parse", "HEAD"], text=True,
            stderr=subprocess.DEVNULL).strip()
    except Exception:
        return "unknown"


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--list", action="store_true",
                    help="print the registered suite names and exit")
    ap.add_argument("--only", default="",
                    help="comma-separated suites to run (default: all)")
    ap.add_argument("--skip", default="",
                    help="comma-separated suites to exclude")
    ap.add_argument("--out", default="BENCH.json",
                    help="machine-readable results path ('' disables); the "
                         "git sha inside the JSON identifies the run")
    args = ap.parse_args(argv)

    if args.list:
        for name, module in SUITES.items():
            print(f"{name}\t{module}")
        return

    names = select_suites(args.only, args.skip)
    if not names:
        raise SystemExit("no suites selected (--only/--skip removed all)")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    report = {"git_sha": git_sha(),
              "started_at": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
              "suites": {}}
    for name in names:
        t0 = time.time()
        metrics = None
        kv_dtype = "fp32"
        try:
            module = importlib.import_module(SUITES[name])
            # storage dtype the suite measures (PR 9); fp32 unless declared
            kv_dtype = getattr(module, "KV_DTYPE", "fp32")
            metrics = module.main()
        except Exception:
            traceback.print_exc()
            failed.append(name)
        wall_us = (time.time() - t0) * 1e6
        print(f"{name}/_suite,{wall_us:.0f},ok={name not in failed}")
        entry = {"ok": name not in failed, "wall_us": wall_us,
                 "kv_dtype": kv_dtype}
        if isinstance(metrics, dict):
            entry["metrics"] = metrics
        report["suites"][name] = entry
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        print(f"# wrote {args.out}", file=sys.stderr)
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
