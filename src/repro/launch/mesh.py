"""Production mesh builders.

Functions, not module-level constants, so importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first init).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi_pod=True -> 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh(shape, axes):
    """Arbitrary mesh for tests / elastic restore experiments.

    Axes are Auto (``jax.make_mesh`` defaults to Explicit): the model
    code leaves sharding propagation to the compiler."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def describe(mesh) -> str:
    return (f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} "
            f"({mesh.devices.size} devices)")
