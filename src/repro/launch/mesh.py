"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set
``xla_force_host_platform_device_count`` *before* first jax init.

Single pod: 256 chips as (16, 16) = ("data", "model").
Multi-pod:  2 pods x 256 chips as (2, 16, 16) = ("pod", "data", "model");
the "pod" axis is the DCN-class boundary the hierarchical collectives
(parallel/collectives.py) treat differently from ICI.
"""

from __future__ import annotations

import jax


def _make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(shape: tuple[int, ...] = None,
                   axes: tuple[str, ...] = None) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    if shape is None:
        shape, axes = (1, n), ("data", "model")
    return _make_mesh(shape, axes)
