"""Activation-sharding context (sequence parallelism, DESIGN.md §6).

The residual stream between blocks is what scan saves for the backward pass;
left unconstrained it is replicated over the 'model' axis and dominates HBM
(dry-run probe: deepseek-67b ≈ 100 GB/device).  Constraining it to
P((pod, data), 'model', None) — sequence-sharded over TP — makes GSPMD insert
the classic SP all-gather/reduce-scatter pairs and cuts saved activations by
the TP degree.

Model code calls ``constrain_activations(x)``; launchers opt in via
``set_activation_spec`` or the scoped :func:`activation_spec` context
manager.  Smoke tests (1-device mesh) leave it unset, and the test suite's
autouse fixture calls :func:`reset` after every test so one engine enabling
sharding can never leak into the next.

Specs are stored RAW and pruned lazily at apply time against the axes the
active mesh actually has (recorded at install when a ``mesh`` is given,
otherwise discovered from the ambient mesh context).  Pruning only at
install time was a bug: ``set_activation_spec(DEFAULT_TRAIN_SPEC)`` without
a mesh stored a spec naming 'pod', which then crashed on any single-pod
mesh.
"""
from __future__ import annotations

import contextlib

import jax
from jax.sharding import PartitionSpec as P


class _ActivationState:
    """The installed constraint: the raw (unpruned) spec plus the axis names
    of the mesh it was installed with (None = discover lazily)."""

    __slots__ = ("spec", "axes")

    def __init__(self) -> None:
        self.spec: P | None = None
        self.axes: tuple[str, ...] | None = None


_STATE = _ActivationState()


def reset() -> None:
    """Clear the installed spec and axes (test isolation hook)."""
    _STATE.spec = None
    _STATE.axes = None


def set_activation_spec(spec: P | None, mesh=None) -> None:
    """Install the residual-stream constraint.  The spec is stored raw;
    pruning to the mesh's axes happens at apply time (``mesh`` here only
    records which axes exist, sparing the lazy discovery)."""
    _STATE.spec = spec
    _STATE.axes = tuple(mesh.axis_names) if (mesh is not None
                                             and spec is not None) else None


@contextlib.contextmanager
def activation_spec(spec: P | None, mesh=None):
    """Scoped :func:`set_activation_spec`: installs ``spec`` for the body
    and restores whatever was installed before on exit — engines and tests
    use this so enabling sharding cannot pollute the rest of the process."""
    prev = (_STATE.spec, _STATE.axes)
    set_activation_spec(spec, mesh)
    try:
        yield
    finally:
        _STATE.spec, _STATE.axes = prev


def _ambient_axes() -> tuple[str, ...] | None:
    """Axis names of the mesh active right now: the recorded install-time
    axes, else the ambient ``jax.set_mesh`` context (how the launchers trace
    their jitted steps)."""
    if _STATE.axes is not None:
        return _STATE.axes
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else tuple(mesh.axis_names)


def _pruned(spec: P, axes: tuple[str, ...]) -> P:
    from .sharding import _filter_axes
    return P(*(_filter_axes(e, axes) for e in spec))


def constrain(x: jax.Array, spec: P) -> jax.Array:
    """Generic pruned sharding constraint for internal activations (MoE
    dispatch buffers etc.).  No-op unless a launcher enabled sharding with a
    mesh (``set_activation_spec(spec, mesh)``)."""
    if _STATE.axes is None:
        return x
    return jax.lax.with_sharding_constraint(x, _pruned(spec, _STATE.axes))


def get_activation_spec() -> P | None:
    """The spec as it would apply right now (pruned to the known axes)."""
    if _STATE.spec is None:
        return None
    axes = _ambient_axes()
    return _pruned(_STATE.spec, axes) if axes is not None else _STATE.spec


def constrain_activations(x: jax.Array) -> jax.Array:
    """Apply the context spec to a (B, S, D) residual-stream activation.
    No-op when unset or when the sequence dim cannot shard (decode, S=1).
    The spec is pruned here, against the axes of the mesh actually active,
    so a spec installed without a mesh cannot crash a mesh lacking 'pod'."""
    if _STATE.spec is None or x.ndim != 3 or x.shape[1] == 1:
        return x
    spec = _STATE.spec
    axes = _ambient_axes()
    if axes is not None:
        spec = _pruned(spec, axes)
    return jax.lax.with_sharding_constraint(x, spec)


DEFAULT_TRAIN_SPEC = P(("pod", "data"), "model", None)
