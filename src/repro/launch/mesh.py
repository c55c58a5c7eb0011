"""Production mesh construction.

Functions (not module constants) so importing never touches jax device state --
required because dryrun.py must set XLA_FLAGS before the first jax init.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType

# Every mesh axis layout this repo constructs (production, local, tests). The
# sharding-table analyzer (repro.analysis.sharding) sweeps PARAM_AXES x rule
# sets against each of these, so a rule that maps two dims of one leaf onto
# the same mesh axis is caught offline for every layout we can ever run on —
# not just the one a particular test happens to build. Keep in sync with the
# constructors below (they assert against this table).
MESH_AXIS_LAYOUTS: Tuple[Tuple[str, ...], ...] = (
    ("data", "model"),            # single pod / local default
    ("pod", "data", "model"),     # multi-pod: leading DCN axis
)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips. Multi-pod adds the DCN 'pod'
    axis: (pod=2, data=16, model=16) = 512 chips.

    When the process exposes more devices than the mesh needs (the dry-run forces
    512 host devices and then builds the 256-chip single-pod mesh), the first
    prod(shape) devices are used.
    """
    import math
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = MESH_AXIS_LAYOUTS[1] if multi_pod else MESH_AXIS_LAYOUTS[0]
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) == n:
        return make_mesh(shape, axes)
    if len(devs) < n:
        raise RuntimeError(f"need {n} devices for mesh {shape}, have {len(devs)} "
                           "(dry-run must set xla_force_host_platform_device_count)")
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.asarray(devs[:n]).reshape(shape), axes,
                axis_types=(AxisType.Auto,) * len(axes))


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with every axis Auto: the sharding rules here are
    GSPMD constraints, and Explicit axes (jax.make_mesh's default) reject the
    gathers and constraints the models use."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(model: int = 1, pod: int = 1):
    """Whatever this host has: (data=n/(pod*model), model), with a leading DCN
    'pod' axis when pod > 1 -- used by tests/examples/local dry-runs.

    Raises when the requested axis sizes do not tile the device count: the old
    behavior silently built a (n//model, model) mesh that DROPPED devices (8
    devices, model=3 -> a 6-device mesh with 2 chips idle).
    """
    n = len(jax.devices())
    if model < 1 or pod < 1:
        raise ValueError(f"mesh axis sizes must be >= 1, got model={model} pod={pod}")
    if n % (model * pod):
        divisors = [d for d in range(1, n + 1) if n % d == 0]
        raise ValueError(
            f"make_local_mesh: model={model} * pod={pod} does not divide the "
            f"device count {n} — a (n//model, model) mesh would silently drop "
            f"{n - (n // (model * pod)) * model * pod} device(s). Pick axis "
            f"sizes whose product divides {n} (divisors: {divisors}).")
    data = n // (model * pod)
    if pod > 1:
        return make_mesh((pod, data, model), MESH_AXIS_LAYOUTS[1])
    return make_mesh((data, model), MESH_AXIS_LAYOUTS[0])
