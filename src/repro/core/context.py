"""HPTMT execution context — the BSP/loosely-synchronous execution model.

The paper (§2.2) mandates loosely-synchronous execution: every worker runs
the same program and synchronizes only at communication operators — no
central scheduler.  In JAX this is *exactly* the SPMD model: one jitted
program, sharded over a named mesh; collectives are the only sync points.

:class:`HptmtContext` mirrors ``CylonEnv(config=MPIConfig(), distributed=
True)`` from the paper's Listing 1: it owns the mesh, the flattened row
axis used for table operators, and factory helpers for shard_map-based
distributed operators.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def shard_map(f: Callable, *, mesh, in_specs, out_specs,
              check: bool = False) -> Callable:
    """``jax.shard_map`` with replication checking off by default: table
    ops return per-shard results on purpose."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """The one way this repo builds a device mesh.

    Every axis is ``AxisType.Auto``: ``jax.make_mesh`` defaults to
    ``Explicit`` axes, which ``with_sharding_constraint`` (the model's
    sharding policy) refuses.  The mesh takes the first ``prod(shape)``
    devices; asking for more devices than exist raises — a run never
    silently shrinks to the devices there are.
    """
    shape, axes = tuple(int(s) for s in shape), tuple(axes)
    need, have = math.prod(shape), len(jax.devices())
    if need > have:
        raise ValueError(
            f"mesh {dict(zip(axes, shape))} needs {need} devices, "
            f"{have} present ({jax.devices()[0].platform}); on the CPU, "
            f"force host devices with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need}")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=jax.devices()[:need])


@dataclasses.dataclass(frozen=True)
class HptmtContext:
    """Execution context binding table/tensor operators to a mesh.

    ``row_axes`` — mesh axes across which table rows are decomposed
    (the paper's row decomposition; usually ``("pod","data")`` or
    ``("data",)``).  ``world_size`` is their product — the number of
    table partitions (= paper's "parallelism").
    """

    mesh: Mesh
    row_axes: tuple[str, ...] = ("data",)

    @property
    def world_size(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.row_axes]))

    @property
    def rows_spec(self) -> P:
        return P(self.row_axes)

    def table_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.rows_spec)

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    # -- rank of the current shard inside shard_map ----------------------
    def axis_index(self):
        idx = jax.lax.axis_index(self.row_axes[0])
        for a in self.row_axes[1:]:
            idx = idx * self.mesh.shape[a] + jax.lax.axis_index(a)
        return idx


def make_context(mesh: Mesh | None = None,
                 row_axes: Sequence[str] | None = None) -> HptmtContext:
    if mesh is None:
        mesh = make_mesh((len(jax.devices()),), ("data",))
    if row_axes is None:
        row_axes = ("data",) if "data" in mesh.axis_names else \
            (mesh.axis_names[0],)
    return HptmtContext(mesh=mesh, row_axes=tuple(row_axes))
