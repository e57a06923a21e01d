"""Production meshes.  Function (not module constant) so importing never
touches jax device state."""
from __future__ import annotations

import math

import jax


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes: GSPMD propagates shardings from
    the placed arguments, as the model code and its sharding rules
    (distributed/sharding.py) expect.  Enter it with ``jax.set_mesh``."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16)=("data","model") single pod; (2,16,16)=("pod","data","model")
    for the 2-pod, 512-chip configuration."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "(launch/dryrun.py sets this)")
    return make_mesh(shape, axes, devices=devices[:n])


def make_debug_mesh(data: int = 2, model: int = 2):
    """Small mesh for tests (host platform device count >= data*model)."""
    return make_mesh((data, model), ("data", "model"),
                     devices=jax.devices()[: data * model])
