"""Production meshes. Functions, not module constants — importing this module
never touches jax device state."""

from __future__ import annotations

import jax


def _mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) data x model single pod (256 chips); (2, 16, 16) pod x data x
    model for the 2-pod = 512-chip multi-pod dry-run."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh_for(n_pods: int, data: int = 16, model: int = 16):
    """Elastic variant: any pod count (1000+ node fleets pick n_pods here)."""
    if n_pods == 1:
        return _mesh((data, model), ("data", "model"))
    return _mesh((n_pods, data, model), ("pod", "data", "model"))
