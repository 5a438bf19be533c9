"""Select the table kernels' implementation per backend.

Pallas TPU kernels are the *target*; on CPU the pure-jnp references
execute instead, and tests exercise the kernels via ``interpret=True``.
``REPRO_KERNEL_IMPL`` (``ref | pallas | pallas_interpret``) overrides the
platform default; it is the only ``REPRO_*`` variable the library reads.
Which local algorithm each operator runs is fixed in code
(``local_ops.JOIN_IMPL`` and its siblings), as are the kernels' widths
(``kernels/tuning.py``).
"""
import os

import jax


def backend_platform() -> str:
    return jax.devices()[0].platform


def table_kernel_impl() -> str:
    """Impl for the table-engine Pallas kernels (radix + hash-join probe)."""
    env = os.environ.get("REPRO_KERNEL_IMPL")
    if env:
        return env
    return "pallas" if backend_platform() == "tpu" else "ref"
