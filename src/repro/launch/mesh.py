"""Production meshes.  A FUNCTION, not a module constant — importing this
module must never touch jax device state (smoke tests see 1 device)."""
from __future__ import annotations

import jax


def auto_mesh(shape, axes):
    """jax.make_mesh with every axis Auto (GSPMD-propagated shardings).
    Every mesh in the repo goes through here."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips).

    Axes: 'pod' = inter-pod data parallelism (DCN-ish links), 'data' =
    in-pod data parallelism / FSDP / sequence-parallel fallback, 'model' =
    tensor/expert parallelism (ICI-local).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Dev mesh over whatever devices exist (CPU tests, examples)."""
    n = len(jax.devices())
    assert n % model == 0
    return auto_mesh((n // model, model), ("data", "model"))
