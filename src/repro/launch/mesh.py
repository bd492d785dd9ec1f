"""Production meshes.  Functions, not module constants — importing this
module never touches jax device state (dry-run sets
``xla_force_host_platform_device_count`` before first jax init).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with every axis ``Auto``: the model code places
    activations with ``with_sharding_constraint`` (``models/pspec.py``) and
    leaves the rest to GSPMD, which an ``Explicit`` axis (the default of
    ``jax.make_mesh``) refuses."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_test_mesh(n_devices: int | None = None, model: int = 2):
    """Small (data, model) mesh for in-process tests (requires the
    host-device override, e.g.
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``).

    Raises ``ValueError`` instead of silently building a zero-extent mesh
    when fewer than ``model`` devices are available.
    """
    n = n_devices or len(jax.devices())
    if model < 1 or n // model < 1:
        raise ValueError(
            f"make_test_mesh needs at least model={model} devices, have "
            f"{n}; run under XLA_FLAGS=--xla_force_host_platform_device_"
            f"count=N (before jax initializes) or lower `model`")
    return auto_mesh((n // model, model), ("data", "model"))
