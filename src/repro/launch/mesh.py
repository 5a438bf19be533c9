"""A small named mesh for tests and CPU compile rehearsals.

A function, not a module-level constant — importing this module never
touches jax device state.  It builds through ``core.context.make_mesh``
(``Auto`` axes; asking for more devices than exist raises)."""
from __future__ import annotations

from ..core.context import make_mesh


def make_debug_mesh(data: int = 2, model: int = 4, pod: int = 0):
    """A ``(data, model)`` mesh, or ``(pod, data, model)`` with ``pod``."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
