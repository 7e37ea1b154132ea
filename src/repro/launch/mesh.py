"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import to make 512 placeholder CPU devices available.

Every mesh axis is ``Auto``: the model pins shardings with
``with_sharding_constraint`` and leaves the rest to GSPMD, which
``jax.make_mesh``'s default ``Explicit`` axes would refuse.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many local devices exist (tests/examples)."""
    return _mesh((data, model), ("data", "model"))
