"""Production and debug mesh shapes.

Functions, not module-level constants — importing this module never
touches jax device state.  Both build through ``core.context.make_mesh``
(``Auto`` axes; asking for more devices than exist raises)."""
from __future__ import annotations

from ..core.context import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 4, pod: int = 0):
    """Small mesh for tests (same axis names as production)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
