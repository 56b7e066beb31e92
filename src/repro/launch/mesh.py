"""Production mesh builders.

Functions (never module-level constants) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before any jax
device initialization.

Every mesh is built with ``AxisType.Auto`` axes: the model's sharding
rules (``distributed.sharding``) place arrays with
``with_sharding_constraint`` and leave propagation to the compiler, which
``jax.make_mesh``'s default Explicit axes refuse.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes over the first ``prod(shape)``
    devices."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1, pod: int | None = None):
    """Small mesh for tests/examples on whatever devices exist."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
