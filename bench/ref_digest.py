"""Fingerprints of what the harness computes from a seed, to show that a
change to the harness leaves them bit for bit as they were.

    python3 bench/ref_digest.py --workload <cell> --seed <n>   # on the chip
    python3 bench/ref_digest.py --config <name> --seed <n>     # CPU, smoke

Run it on two trees (the script may be copied into the older one) and
compare the printed JSON.  It prints, for a cell of ``BENCHMARK.json`` at
its full size, or a configuration of ``bench/configs/`` at its smoke size
(``bench/tests/smoke_cell.py``), in each weight dtype asked for:

- ``logits``: sha256 of the reference's float32 logits (``reference.py``,
  fp32 and the fp8 control) over a few requests: at full size one drawn
  from each of the traffic plan's prompt lengths, longest first, plus one
  more; at smoke size two of 48 and 64 tokens.  The served tokens are drawn
  from the seed, so the gap statistics are not those of a run;
- ``served_weights``: sha256 of every leaf of ``perturb(init_params(...))``
  (``run.make_params``), in tree order;
- ``flops``: ``counts.prefill_flops`` at each prompt length and
  ``counts.decode_flops`` at the kept lengths of the longest.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
for p in (str(BENCH / "tests"), str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

import cells  # noqa: E402
import counts  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import traffic  # noqa: E402
import weights  # noqa: E402


def sha(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(str((a.dtype, a.shape)).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def requests(cell: cells.Cell, seed: int, smoke: bool) -> list:
    m = cell.model
    rng = np.random.default_rng(seed)
    if smoke:
        lens = [(48, 12), (64, 20)]
        prompts = [rng.integers(0, m["vocab_size"], T) for T, _ in lens]
    else:
        plan = traffic.plan(cell.traffic, seed, m["vocab_size"])
        by_len = {}
        for p in plan:
            by_len.setdefault(len(p.prompt), p)
        pick = [by_len[T] for T in sorted(by_len, reverse=True)]
        first = {p.index for p in pick}
        pick.append(next(p for p in plan if p.index not in first))
        lens = [(len(p.prompt), p.max_new_tokens) for p in pick]
        prompts = [p.prompt for p in pick]
    return [reference.Served(
        prompt=np.asarray(pr, np.int32),
        tokens=rng.integers(0, m["vocab_size"], n).astype(np.int32),
        d0=int(rng.integers(0, 1000))) for pr, (_, n) in zip(prompts, lens)]


def digests(cell: cells.Cell, seed: int, smoke: bool, dtypes) -> dict:
    import jax
    import jax.numpy as jnp
    m, comp = cell.model, cell.compression
    imp = counts.importance(m["n_layers"], m["n_kv_heads"],
                            cell.traffic["importance"])
    served = requests(cell, seed, smoke)
    lens = sorted({len(r.prompt) for r in served})
    keep = counts.headkv_keep(imp, comp, lens[-1])
    out = {"requests": [[len(r.prompt), len(r.tokens)] for r in served],
           "flops": {"prefill": [counts.prefill_flops(m, T) for T in lens],
                     "decode": counts.decode_flops(m, keep)}}
    for name in dtypes:
        params = run.make_params(cells.model_config(cell), m, seed, name)
        got = {"served_weights": sha(jax.tree.leaves(params))}
        del params
        for precision in ("fp32", "fp8"):
            lg = reference.logits(m, comp, imp, weights.root_key(seed),
                                  getattr(jnp, name), served, precision)
            got[f"logits_{precision}"] = sha(lg)
            gaps = np.concatenate([reference.served_gap(x, r.tokens)
                                   for x, r in zip(lg, served)])
            got[f"gap_{precision}"] = [float(gaps.mean()), float(gaps.max())]
        out[name] = got
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    which = ap.add_mutually_exclusive_group(required=True)
    which.add_argument("--workload", help="a cell, at its full size")
    which.add_argument("--config", help="a configuration, at smoke size")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--dtypes", default="float32,bfloat16")
    a = ap.parse_args(argv)
    smoke = a.config is not None
    if smoke:
        from smoke_cell import smoke_cell
        cell = smoke_cell(a.config, "saturated")
    else:
        cell = cells.load(a.workload)
    res = digests(cell, a.seed, smoke, a.dtypes.split(","))
    print(json.dumps({"cell": a.config or a.workload, "seed": a.seed, **res}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
