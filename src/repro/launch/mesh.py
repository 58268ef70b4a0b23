"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state; dryrun.py sets XLA_FLAGS before importing.

Mesh layout (TPU v5e pods of 256 chips):
  single-pod:  (16, 16)      axes ("data", "model")
  multi-pod:   (2, 16, 16)   axes ("pod", "data", "model")

The "pod" axis extends data parallelism across pod boundaries (gradient
all-reduce crosses DCI hierarchically); nothing in the code assumes 2 pods
— growing the leading axis scales to N pods.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis in Auto (GSPMD-propagated) mode,
    the layout the sharding rules in ``repro.distributed`` assume."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Small mesh over the real local devices (tests / examples)."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return make_mesh((n // model_axis, model_axis), ("data", "model"))
