"""Production meshes. Functions, not module constants — importing this module
never touches jax device state."""
from __future__ import annotations

from repro.parallel.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_for(devices: int, model_parallel: int = 1, pods: int = 1):
    """Elastic helper: lay available devices out as (pod, data, model)."""
    data = devices // (model_parallel * pods)
    assert data * model_parallel * pods == devices, \
        f"{devices} devices don't tile (pods={pods}, tp={model_parallel})"
    if pods > 1:
        return make_mesh((pods, data, model_parallel), ("pod", "data", "model"))
    return make_mesh((data, model_parallel), ("data", "model"))
