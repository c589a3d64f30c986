"""The fp32 reference against the engine's paged path, at smoke sizes.

The engine serves in float32 here so that the two must agree to rounding:
prefill logits, HeadKV's per-head selection and decode through the paged
cache, including the recency ring (a small decode margin makes heavy heads
reach capacity and wrap)."""
import dataclasses

import numpy as np
import pytest

import cells
import counts
import reference
import run
import weights
from repro.api import Engine
from repro.serving.request import Request
from smoke_cell import CONFIGS, smoke_cell


def test_ring_deaths_by_hand():
    # one head keeping 3 prompt entries, capacity 5, ring 2, phase d0 = 1
    keep = np.array([[3]])
    dp, dd = reference.ring_deaths(keep, n_dec=5, d0=1, capacity=5, ring=2,
                                   n_pad=8)
    # appends: k0 -> col 3, k1 -> col 4, k2 -> col 3 + (1+2)%2 = 4,
    # k3 -> col 3, k4 -> col 4
    assert dp[0, 0, :3].tolist() == [9, 9, 9]  # prompt never overwritten
    assert dd[0, 0].tolist() == [3, 2, 4, 9, 9, 0, 0, 0]


@pytest.mark.parametrize("config", CONFIGS)
def test_reference_matches_the_paged_engine(config):
    cell = smoke_cell(config, "saturated", dtype="float32", decode_margin=4)
    m, seed = cell.model, 1234
    imp = counts.importance(m["n_layers"], m["n_kv_heads"],
                            cell.traffic["importance"])
    params = run.make_params(cells.model_config(cell), m, seed, "float32")
    ecfg = cells.engine_config(cell, run.pool_blocks(cell, imp))
    ecfg = ecfg.replace(scheduler=dataclasses.replace(ecfg.scheduler,
                                                      collect_logits=True))
    eng = Engine.build(ecfg, params=params,
                       profile=run.plan_profile(cell, imp),
                       head_importance=imp)
    rng = np.random.default_rng(0)
    reqs = [Request(req_id=i, prompt=rng.integers(0, 256, T).astype(np.int32),
                    max_new_tokens=n)
            for i, (T, n) in enumerate([(48, 30), (64, 12), (57, 25)])]
    d0 = {}
    for r in reqs[:2]:
        eng.submit(r)
    step = 0
    while any(not r.is_finished for r in reqs):
        if step == 3:
            eng.submit(reqs[2])  # admitted mid-stream, at another phase
        ev = eng.step()
        for rid, _ in ev["admitted"]:
            d0[rid] = int(eng.scheduler.state.decode_steps) - 1
        step += 1
    served = [reference.Served(prompt=r.prompt,
                               tokens=np.asarray(r.generated, np.int32),
                               d0=d0[r.req_id]) for r in reqs]
    import jax.numpy as jnp
    ref = reference.logits(m, cell.compression, imp, weights.root_key(seed),
                           jnp.float32, served)
    cap = counts.static_capacity(cell.compression)
    wrapped = False
    for r, lg in zip(reqs, ref):
        got = np.stack(r.logits)[:, :m["vocab_size"]]
        np.testing.assert_allclose(got, np.asarray(lg), atol=2e-4, rtol=0)
        keep = counts.headkv_keep(imp, cell.compression, len(r.prompt))
        wrapped |= bool((keep + len(r.generated) - 1 > cap).any())
        assert reference.served_gap(lg, r.generated).max() < 1e-4
    assert wrapped, "no head reached capacity: the ring went untested"
