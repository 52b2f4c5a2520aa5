"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — only the dry-run
process sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512``.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips as ("data", "model") = (16, 16).
    Multi-pod: 2 pods = 512 chips as ("pod", "data", "model") = (2, 16, 16).

    The dry-run process forces 512 host devices; the single-pod mesh uses
    the first 256 of them.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    devices = jax.devices()[: int(np.prod(shape))]
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_dev_mesh(n_devices: int | None = None, model: int | None = None):
    """Small mesh over the locally available devices (tests / examples)."""
    n = n_devices or len(jax.devices())
    model = model or (2 if n % 2 == 0 and n > 1 else 1)
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
