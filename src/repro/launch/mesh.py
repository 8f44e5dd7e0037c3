"""Production mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before any jax
initialization).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, devices):
    """``jax.make_mesh`` with every axis in Auto mode: the serving and
    training paths shard through GSPMD hints (``shard_hint``), which
    explicit axes — ``make_mesh``'s default since JAX 0.7 — reject."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def _take_devices(shape, what: str):
    n = 1
    for s in shape:
        n *= int(s)
    devices = jax.devices()
    if len(devices) < n:
        raise ValueError(
            f"{what}: mesh shape {tuple(shape)} requires {n} devices but "
            f"only {len(devices)} are available "
            f"({devices[0].platform if devices else 'no'} backend). "
            f"For CPU testing set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} before "
            f"the first jax import.")
    return devices[:n]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    devices = _take_devices(shape, "make_production_mesh")
    return auto_mesh(shape, axes, devices)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over local devices (CPU tests of the sharded paths)."""
    devices = _take_devices((data, model), "make_local_mesh")
    return auto_mesh((data, model), ("data", "model"), devices)
