"""Distribution layer: sharding rules, param specs, HLO collective parser,
and a subprocess multi-device lowering test (8 fake CPU devices)."""
import json
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.hlo_stats import collective_stats, while_body_stats
from repro.distributed.param_specs import guarded, tree_pspecs
from repro.distributed.sharding import ShardingRules, serve_rules, train_rules
from repro.launch.mesh import make_host_mesh


def _mesh11():
    return make_host_mesh(model=1, data=1)


def test_guarded_divisibility():
    mesh = _mesh11()
    rules = ShardingRules(mesh=mesh, rules={"heads": "model"})
    # 25 heads on a 1-wide axis: divisible, keeps the axis
    assert guarded(rules, 25, "heads") == "model"
    assert guarded(rules, 25, "missing") is None


def test_tree_pspecs_train_layout():
    mesh = _mesh11()
    rules = train_rules(mesh)
    tree = {"layers": [{"w1": jnp.zeros((8, 16)), "ln1": jnp.zeros((8,))}],
            "embed": jnp.zeros((32, 8))}
    specs = tree_pspecs(tree, rules, "train")
    assert specs["layers"][0]["w1"] == P("data", "model")
    assert specs["layers"][0]["ln1"] == P()
    assert specs["embed"] == P("model", "data")


def test_qtensor_specs_follow_parent():
    from repro.serving.quant import quantize_weight
    mesh = _mesh11()
    rules = serve_rules(mesh)
    qt = quantize_weight(jnp.ones((8, 16)), channel_axis=1)
    specs = tree_pspecs({"layers": [{"w1": qt}]}, rules, "serve")
    assert specs["layers"][0]["w1"].q == P(None, "model")
    assert specs["layers"][0]["w1"].scale == P()


def test_collective_parser():
    hlo = textwrap.dedent("""\
    HloModule test
    %body (x: bf16[4,8]) -> bf16[4,8] {
      ROOT %ar = bf16[4,8]{1,0} all-reduce(bf16[4,8] %x), replica_groups={}
    }
    ENTRY %main (a: bf16[16,8]) -> bf16[16,8] {
      %ag = bf16[16,8]{1,0} all-gather(bf16[4,8]{1,0} %a), dimensions={0}
      %rs = f32[2,8]{1,0} reduce-scatter(f32[16,8]{1,0} %x), dimensions={0}
      ROOT %out = bf16[16,8]{1,0} all-reduce(bf16[16,8]{1,0} %ag)
    }
    """)
    stats = collective_stats(hlo)
    assert stats["all-gather"]["count"] == 1
    assert stats["all-gather"]["bytes"] == 16 * 8 * 2
    # 2 all-reduce (body + entry), each 2x bytes
    assert stats["all-reduce"]["count"] == 2
    assert stats["reduce-scatter"]["bytes"] == 2 * 8 * 4
    bodies = while_body_stats(hlo)
    assert "body" in bodies
    assert bodies["body"]["bytes"] == 2 * 4 * 8 * 2


SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, {src!r})
import json
import jax, jax.numpy as jnp
from repro.configs import get_smoke_config, SHAPES
from repro.configs.base import InputShape
from jax.sharding import AxisType
from repro.launch.specs import build_cell

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
cfg = get_smoke_config({arch!r})
shape = InputShape("mini_{kind}", 64, 4, {kind!r})
cell = build_cell(cfg, shape, mesh, quantize=False)
with mesh:
    compiled = jax.jit(cell.fn, donate_argnums=cell.donate_argnums).lower(
        *cell.args).compile()
ma = compiled.memory_analysis()
print(json.dumps({{"ok": True, "args": ma.argument_size_in_bytes}}))
"""


@pytest.mark.parametrize("arch,kind", [
    ("minitron-8b", "decode"),
    ("gemma2-9b", "train"),
    ("qwen3-moe-30b-a3b", "decode"),
    ("mamba2-1.3b", "decode"),
])
def test_multidevice_lowering_subprocess(arch, kind):
    """Lower + compile a reduced cell on an 8-device CPU mesh in a clean
    subprocess (device count must be set before jax import)."""
    import repro
    # repro is a namespace package: __file__ is None, use __path__
    src = list(repro.__path__)[0].rsplit("/repro", 1)[0]
    code = SUBPROC.format(src=src, arch=arch, kind=kind)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok"]
