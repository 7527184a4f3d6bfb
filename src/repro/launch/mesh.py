"""Production mesh builders.

Functions (not module-level constants) so importing never touches jax device
state.  The dry-run overrides the host device count via XLA_FLAGS *before*
importing jax (see launch/dryrun.py, first two lines).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

AUTO2 = (AxisType.Auto,) * 2


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2x16x16 = 512 chips across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_host_mesh():
    """Single-device mesh for CPU smoke tests / benchmarks."""
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=AUTO2)


def make_mesh_for(devices: int, *, model_parallel: int = 16):
    """Elastic helper: best-effort (data, model) mesh over ``devices`` chips."""
    model = min(model_parallel, devices)
    while devices % model:
        model -= 1
    return jax.make_mesh((devices // model, model), ("data", "model"),
                         axis_types=AUTO2)
