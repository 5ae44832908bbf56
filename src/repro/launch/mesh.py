"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (the dry-run process forces 512 host devices before
any jax import; tests see the single real CPU device).

Every axis is ``AxisType.Auto``: the sharding rules in ``launch/sharding.py``
place arrays with ``NamedSharding`` and let the compiler propagate the rest,
which ``jax.make_mesh``'s default ``Explicit`` axes would refuse.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; multi_pod adds the 2-pod 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2, multi_pod: bool = False):
    """Small mesh for subprocess integration tests (8 host devices)."""
    if multi_pod:
        return _auto_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return _auto_mesh((n_data, n_model), ("data", "model"))
