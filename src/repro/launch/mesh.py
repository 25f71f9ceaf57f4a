"""Mesh construction.

Importing this module never touches jax device state; meshes are built only
inside ``make_mesh``.
"""
from __future__ import annotations

import jax

from repro.parallel.sharding import AXIS_DATA, AXIS_MODEL, AXIS_POD


def make_mesh(data: int, model: int, pod: int = 1):
    """Arbitrary mesh for tests / elastic resizing."""
    if pod > 1:
        return jax.make_mesh((pod, data, model), (AXIS_POD, AXIS_DATA,
                                                  AXIS_MODEL))
    return jax.make_mesh((data, model), (AXIS_DATA, AXIS_MODEL))
