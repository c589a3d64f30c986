"""Readings that a cell's correctness limit is set from, on the chip.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3 [--control]

For each seed, in this one process: a run of the cell exactly as
``run.py`` makes it (same set-up, load and window), then the check's
numbers (``run.gap_stats``: how far the served tokens' reference logits
lie below the reference's best) and the verdict the cell's check file
gives them.  With ``--control`` the same for the control: the tokens the
reference computed with float8 weights and activations puts first, read at
the same positions and judged by the same comparison (``run.judge``), which
must come out not correct.  The lower reading of a limit is the largest
program reading over a dozen seeds or more; the upper is the smallest
control reading.  Prints one JSON line per seed.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import run as R


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    a = ap.parse_args(argv)
    for seed in (int(x) for x in a.seeds.split(",")):
        try:
            res = R.run(a.workload, seed, a.seconds, False,
                        control=a.control)
        except R.NoChip as e:
            print(f"bench/control.py: {e}", file=sys.stderr)
            return 2
        verdict = res["program"] if a.control else res
        out = {"seed": seed,
               "program": dict(res["readings"]["program"],
                               correct=verdict["correct"])}
        if a.control:
            out["control"] = dict(res["readings"]["control"],
                                  correct=res["correct"])
        print(json.dumps(out), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
