"""Production mesh construction.

A FUNCTION (not module-level constant) so importing never touches jax device
state. The dry-run entrypoint sets ``XLA_FLAGS=--xla_force_host_platform_
device_count=512`` before any jax import; everything else sees 1 device.

Every mesh is built with ``AxisType.Auto`` axes: the round engine's jnp code
contracts over sharded dimensions (``field.matmul`` inside a cloud step), which
explicit-sharding axes — ``jax.make_mesh``'s default — refuse to trace.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """Any (shape, axes) mesh with auto-sharded axes — used by checkpoint
    resharding tests, the elastic-scaling path and every helper below.
    ``devices`` defaults to all visible devices."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for smoke tests."""
    return make_mesh((1, 1), ("data", "model"))


def make_dispatch_mesh(n_model: int = 1):
    """All visible devices as a ``("data", "model")`` mesh for the
    device-resident query dispatcher (``repro.core.mesh_dispatch``):
    tuple-axis shards spread over ``data``, the c Shamir share planes over
    ``model``. ``n_model`` must divide the device count; the default keeps
    every device on the data axis (the CI smoke lane forces 8 host devices
    via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``)."""
    n = jax.device_count()
    if n % n_model != 0:
        raise ValueError(f"n_model={n_model} does not divide the "
                         f"{n}-device platform")
    return make_mesh((n // n_model, n_model), ("data", "model"))
