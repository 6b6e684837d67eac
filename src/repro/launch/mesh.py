"""Production mesh construction (pure function — importing this module never
touches jax device state).

Single pod: 16×16 = 256 chips, axes (data, model).
Multi-pod:  2×16×16 = 512 chips, axes (pod, data, model) — "pod" is the
slow-ICI/DCN dimension; only data parallelism (gradient reduce) crosses it.
"""

from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh with the same axis names (CPU tests / smoke runs)."""
    return _auto_mesh((1, 1), ("data", "model"))


def make_data_mesh(n_data: int, *, model: int = 1):
    """A ``(data, model)`` mesh over the first ``n_data × model`` devices.

    The device-count-sweep entry point (``bench_sharding``, the multi-device
    tests): on a host forced to N CPU devices
    (``XLA_FLAGS=--xla_force_host_platform_device_count=N``) this builds
    submeshes of any size that fits, so one process can sweep 1/2/4/8-way
    sharding without restarting.
    """
    import numpy as np

    need = n_data * model
    devs = jax.devices()
    if need > len(devs):
        raise ValueError(f"mesh ({n_data}, {model}) needs {need} devices, "
                         f"host has {len(devs)}")
    grid = np.asarray(devs[:need]).reshape(n_data, model)
    return jax.sharding.Mesh(grid, ("data", "model"))


def axis_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dp_axes(mesh) -> tuple[str, ...]:
    """Axes that carry data parallelism (pod joins data when present)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
