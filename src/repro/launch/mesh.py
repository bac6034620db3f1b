"""Mesh builders for the production pods.

Functions, not module-level constants: importing this module never touches
jax device state. The dry-run entry point (repro.launch.dryrun) sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; everything else (tests, benches) sees the 1 real CPU device and uses
``make_cpu_mesh``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_cpu_mesh() -> Mesh:
    """Degenerate (1, 1) mesh on the host device — lets every sharded code
    path run unchanged in tests on one CPU."""
    return _make_mesh((1, 1), ("data", "model"))


def make_serve_mesh(dp: int = 2, mp: int = 4) -> Mesh:
    """``(data, model)`` mesh for the sharded serving/stream lane: the
    scheduler's paged KV slot axis shards over ``data`` (each shard owns a
    range of the page pool) and KV heads over ``model`` — see
    ``repro.sharding.partition.cache_specs`` and docs/sharding.md. Raises
    when the runtime has fewer than ``dp * mp`` devices; the forced-CPU CI
    lane provides 8 via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
    (which must be set before the first jax import)."""
    need = dp * mp
    have = len(jax.devices())
    if have < need:
        raise ValueError(
            f"make_serve_mesh({dp}, {mp}) needs {need} devices, runtime has "
            f"{have} — set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{need} before importing jax (or use make_cpu_mesh)")
    return _make_mesh((dp, mp), ("data", "model"))


__all__ = ["make_production_mesh", "make_cpu_mesh", "make_serve_mesh"]
