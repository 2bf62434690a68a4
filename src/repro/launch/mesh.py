"""Production mesh builders.

A function (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16×16 = 256 chips (data, model).
Multi-pod: 2×16×16 = 512 chips (pod, data, model).
"""

from __future__ import annotations

import jax


def _auto(axes):
    # Auto axes: model code places activations with
    # ``with_sharding_constraint``, which Explicit axes (make_mesh's
    # default) refuse
    return (jax.sharding.AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def make_local_mesh(model: int = 1, data: int = 0):
    """Small mesh over however many local devices exist (tests)."""
    n = len(jax.devices())
    data = data or (n // model)
    axes = ("data", "model")
    return jax.make_mesh((data, model), axes, axis_types=_auto(axes))
