"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — required because the
dry-run forces 512 host devices via XLA_FLAGS *before* jax initializes, while
smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes.  The sharding rules here
    place arrays with NamedSharding and let the partitioner propagate
    through every op; under ``Explicit`` axes (``jax.make_mesh``'s
    default) ops such as a slab's dynamic_update_slice or a gather
    refuse operands whose shardings differ."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading "pod"
    axis (DCN between pods, ICI within)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 1, n_model: int = 1):
    """Tiny mesh over however many local devices exist (CPU tests)."""
    n = len(jax.devices())
    n_model = min(n_model, n)
    n_data = min(n_data, n // n_model)
    return make_mesh((n_data, n_model), ("data", "model"))
