"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips.

A FUNCTION (not a module constant) so importing never touches jax device
state; the dry-run sets ``xla_force_host_platform_device_count=512`` before
any jax import (see launch/dryrun.py lines 1-2).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1, data: int = 1) -> Mesh:
    """Small (data, model) mesh over however many (host) devices exist —
    tests/examples/the ``mesh`` executor on a dev box.

    Oversubscription is a real error, not an assert (asserts vanish under
    ``python -O``): requesting more mesh slots than devices exist would
    otherwise surface as an opaque failure deep inside ``make_mesh``.
    """
    n = len(jax.devices())
    if model * data > n:
        raise ValueError(
            f"requested mesh (data={data}, model={model}) = {model * data} "
            f"devices, but only {n} available; set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N before "
            f"the first jax import to fake host devices")
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
