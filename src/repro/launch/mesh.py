"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips (data, model).
Multi-pod: 2x16x16 = 512 chips (pod, data, model) — the pod axis carries
data parallelism across pods (DCN-ish), model stays within a pod (ICI).
"""
from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD propagation),
    the sharding mode the models and the dry-run are written for."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(tuple(axis_names)),
        devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh for tests (host devices)."""
    return make_mesh((n_data, n_model), ("data", "model"))


def make_device_mesh(n_devices=None):
    """1-D ``dev`` mesh for the placement-scheduled multi-device
    executor (``Engine.run(..., mesh=...)``): destination shards are
    LPT-assigned to these devices and halo sub-fibers move over the
    mesh axis.  ``n_devices=None`` takes every local device; an int
    takes the first N (``XLA_FLAGS=--xla_force_host_platform_device_count=4``
    forces virtual host devices for tests/CI)."""
    devs = jax.devices()
    n = len(devs) if n_devices is None else int(n_devices)
    if n < 1 or n > len(devs):
        raise ValueError(
            f"make_device_mesh: asked for {n} devices but "
            f"{len(devs)} are available")
    return make_mesh((n,), ("dev",), devices=devs[:n])
