"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state — required because the
dry-run forces 512 host devices via XLA_FLAGS before any jax import, while
tests and benches must see the single real device.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = ("data", "model") = 256 chips.
    Multi-pod:  (2, 16, 16) = ("pod", "data", "model") = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(model: int = 1):
    """Whatever devices exist, data-major (tests / examples)."""
    n = len(jax.devices())
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))


def mesh_name(mesh) -> str:
    return "x".join(str(s) for s in mesh.devices.shape)
