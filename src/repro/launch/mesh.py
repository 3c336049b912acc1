"""Production mesh definitions.

A function, not a module-level constant, so importing this module never
touches jax device state (the dry-run forces 512 host devices *before*
any jax initialization; everything else sees the real topology).

Single pod: (data=16, model=16) — 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the `pod` axis is
the DCN dimension (gradient reduce / FSDP outer axis), `model` stays
inside the ICI domain.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes) -> jax.sharding.Mesh:
    # `make_mesh` defaults to Explicit axes; every sharding rule here
    # (with_sharding_constraint, gather_rows) assumes Auto propagation.
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh(model_parallel: int = 1) -> jax.sharding.Mesh:
    """Whatever this process actually has (tests / smoke runs)."""
    n = len(jax.devices())
    mp = min(model_parallel, n)
    return _make_mesh((n // mp, mp), ("data", "model"))


def make_serving_mesh(mesh_shape: Sequence[int]) -> jax.sharding.Mesh:
    """A ("data", "model") mesh of exactly ``prod(mesh_shape)`` local
    devices — the serving stack's knob for sharded cold starts.  A 1-d
    shape means pure model parallelism: ``(4,)`` == ``(1, 4)``."""
    shape = tuple(int(s) for s in mesh_shape)
    if len(shape) == 1:
        shape = (1,) + shape
    if len(shape) != 2:
        raise ValueError(f"mesh_shape must be 1- or 2-d, got {mesh_shape}")
    need = shape[0] * shape[1]
    have = len(jax.devices())
    if need > have:
        raise ValueError(
            f"mesh_shape {shape} needs {need} devices, have {have} "
            f"(CPU simulation: set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need})")
    return _make_mesh(shape, ("data", "model"))
