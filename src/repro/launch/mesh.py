"""Production meshes.

Single pod  : (data=16, model=16)            = 256 chips (one v5e pod)
Multi-pod   : (pod=2, data=16, model=16)     = 512 chips (2 pods)

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run must set
XLA_FLAGS before any device query.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Tiny mesh over however many (host) devices exist — tests/examples."""
    n = len(jax.devices())
    data = n // model
    return jax.make_mesh(
        (data, model), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
