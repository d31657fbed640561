"""The narrow slice of JAX mesh API the repo uses, in one place (JAX 0.9).

Everything imports ``shard_map`` and ``make_mesh`` from here, so a future
API move is one edit.
"""
from __future__ import annotations

import os

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=None, **kw):
    """``jax.shard_map``.  ``check_vma`` toggles the replication-checking
    machinery; sparse collectives and ppermute chains are not
    representable in it, so the hot paths pass False."""
    if check_vma is not None:
        kw["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def make_mesh(shape, axis_names):
    """``jax.make_mesh`` with Auto axis types (GSPMD-partitioned axes)."""
    return jax.make_mesh(
        shape, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def ensure_fake_host_devices(n: int = 8) -> None:
    """Append ``--xla_force_host_platform_device_count=n`` to XLA_FLAGS
    unless a count is already configured.  Must run before jax initializes
    its backend (importing jax is fine; touching devices is not).  Used by
    tests/conftest.py and the benchmarks so mesh code paths run on CPU."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()
