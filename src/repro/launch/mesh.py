"""Production meshes (brief: MULTI-POD DRY-RUN §1).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (device counts lock on first backend init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """single pod: (data=16, model=16) = 256 chips (one v5e pod);
    multi-pod:  (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # why: the launch layer's production mesh factory — the serve seam
    # (repro/serve/mesh.py) covers serving; this covers training runs
    # repro: allow[mesh-discipline]
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(shape))


def make_smoke_mesh(data: int = 2, model: int = 2):
    """Tiny mesh for CPU integration tests (requires forced host devices)."""
    # why: test-only mesh factory, same ownership story as the
    # production factory above
    # repro: allow[mesh-discipline]
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def mesh_chips(mesh) -> int:
    n = 1
    for s in mesh.shape.values():
        n *= s
    return n


def mesh_name(mesh) -> str:
    return "x".join(f"{k}{v}" for k, v in mesh.shape.items())
