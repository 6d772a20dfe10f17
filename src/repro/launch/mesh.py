"""Production meshes (DESIGN.md §6).

``make_production_mesh`` is a FUNCTION so importing this module never touches
jax device state.  The dry-run launches with
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` (set in dryrun.py
*before any jax import*); everything else sees the real device count.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with Auto axes.  jax >= 0.7 defaults to Explicit
    axes, under which the GSPMD-style in/out shardings and activation
    constraints this code base relies on are refused (e.g. a
    ``ShardingTypeError`` in the embedding gather)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(*, tp: int = 1):
    """Whatever this host offers, as a ('data','model') mesh — used by smoke
    tests and the CPU example drivers.  ``tp`` sets the model-axis size
    (tensor parallelism); it must divide the host device count, the rest
    becomes the data axis."""
    n = len(jax.devices())
    if tp < 1 or n % tp:
        raise ValueError(f"tp={tp} must be >= 1 and divide the host device "
                         f"count ({n})")
    return make_mesh((n // tp, tp), ("data", "model"))


def describe(mesh) -> str:
    return "x".join(f"{k}={v}" for k, v in mesh.shape.items())
