"""Mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (device count is locked on first jax init, so a
caller that forces a host device count sets ``XLA_FLAGS`` before any jax
import).

Every axis is ``AxisType.Auto``: the step builders place activations with
``with_sharding_constraint`` (``models/act_sharding.py``), which refers
only to Auto axes, and ``jax.make_mesh`` defaults to Explicit ones.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple, devices=None):
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def make_dev_mesh(*, data: int = 1, model: int = 1):
    """Small mesh over however many devices the process actually has
    (CPU smoke tests / examples)."""
    devices = jax.devices()
    assert data * model <= len(devices), (data, model, len(devices))
    return _auto_mesh(
        (data, model), ("data", "model"), devices=devices[: data * model]
    )
