"""Production mesh definitions (functions, never module-level constants)."""
from __future__ import annotations

from repro.dist.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e production mesh: 16x16 per pod; 2 pods multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def dp_axes(mesh) -> tuple:
    """Data-parallel axes of a production mesh (everything but 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def make_host_mesh():
    """1-device mesh for CPU tests (policy plumbing without sharding)."""
    return make_mesh((1, 1), ("data", "model"))


def make_local_mesh():
    """All of this host's devices as (n, 1) ("data", "model"): one FL
    replica slot per chip (1 on a one-chip host, 4 on a 2x2 v5e host)."""
    import jax
    return make_mesh((len(jax.devices()), 1), ("data", "model"))
