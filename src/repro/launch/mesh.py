"""Production meshes.  A FUNCTION (not a module constant) so importing this
module never touches jax device state."""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_host_mesh"]


def _auto(n_axes: int) -> tuple:
    """Auto axes: the partitioner places what the sharding rules leave open
    (``jax.make_mesh`` defaults to Explicit axes)."""
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16) ('data','model') single pod; (2,16,16) ('pod','data','model')
    for the 512-chip two-pod dry run."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return jax.make_mesh((data, model), ("data", "model"), axis_types=_auto(2))
