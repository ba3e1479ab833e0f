"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state; the dry-run sets XLA_FLAGS before any jax import.
Axes are Auto-typed: the model and engine code shard through
``NamedSharding``/``shard_map``, not explicit-sharding types.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips).

    Uses the first prod(shape) available devices, so a 512-host-device
    dry-run process can build both meshes.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512"
        )
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes),
        devices=devices[:n],
    )


def make_host_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over whatever host devices exist (tests)."""
    n = 1
    for s in shape:
        n *= s
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes),
        devices=jax.devices()[:n],
    )
