"""Production mesh definitions.

``make_production_mesh()`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — required for the
dry-run's device-count override to work.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 ("data","model") single pod (256 chips) or 2x16x16
    ("pod","data","model") two pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(shape, axes):
    """Small test mesh on the host platform (subprocess tests)."""
    return _make_mesh(shape, axes)
