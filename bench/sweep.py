"""Find an open-loop cell's knee once, on the chip.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 1.5,2,2.5

Runs the cell as ``run.py`` does at each fixed arrival rate in turn (one
process, so the set-up compiles once) and prints, per rate, its end-to-end
metrics, the queue left at the window's close and the requests still
unanswered after the drain.  The knee is the highest rate whose queue does
not grow through the window; the cell's mix then fixes a rate below it.
"""
from __future__ import annotations

import argparse
import json
import sys

import cells
import run as R


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    a = ap.parse_args(argv)
    for rate in (float(x) for x in a.rates.split(",")):
        cell = cells.load(a.workload)
        cell.traffic["rate_rps"] = rate
        try:
            res = R.run(a.workload, a.seed, a.seconds, False, cell=cell)
        except R.NoChip as e:
            print(f"bench/sweep.py: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"rate_rps": rate, "queue_end": res["queue_end"],
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "metrics": {k: v["value"]
                                      for k, v in res["metrics"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
