"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — the dry-run must set XLA_FLAGS
before the first jax device query.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """The assigned production mesh: 16x16 = 256 chips per pod (v5e),
    2 pods = 512 chips when ``multi_pod``.

    Axes: ("data", "model"), plus a leading "pod" axis in multi-pod mode.
    Gradient/batch parallelism runs over ("pod", "data"); tensor/expert
    parallelism over "model".
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(axis: str = "batch"):
    """All local devices on one axis (Anakin replication / tests)."""
    return jax.make_mesh(
        (len(jax.devices()),), (axis,), axis_types=(AxisType.Auto,)
    )
