"""Jitted wrapper around the radix histogram/rank kernel.

``partition_plan`` is the op the table engine and the MoE layer both call:
given per-row partition ids it returns, for every row, a stable destination
slot ``dest = global_offset[pid] + rank_within_pid`` plus the per-partition
histogram — i.e. everything needed to scatter rows into partition-grouped
order (table Shuffle) or into per-expert buckets (MoE dispatch).
"""
import functools

import jax
import jax.numpy as jnp

from ..tuning import TILE
from .kernel import radix_histogram_ranks_tiles
from .ref import radix_histogram_ranks_ref


@functools.partial(jax.jit, static_argnames=("num_partitions", "impl", "tile"))
def _radix_histogram_ranks(pid: jnp.ndarray, num_partitions: int,
                           impl: str = "ref", tile: int = TILE):
    n = pid.shape[0]
    if impl == "ref" or n < tile:
        return radix_histogram_ranks_ref(pid, num_partitions)

    n_tiles = -(-n // tile)
    pad = n_tiles * tile - n
    # pad with partition id P (an extra, ignored bucket would break the
    # one-hot width) -> use id 0 but mask ranks/hist afterwards via a
    # sentinel-free approach: pad ids with 0 and subtract the pad rows'
    # contribution from hist[0]; pad rows sit at the tail so their ranks
    # never collide with real rows' dest slots once masked by callers.
    pid_p = jnp.pad(pid, (0, pad), constant_values=0)
    tiles = pid_p.reshape(n_tiles, tile)
    hist_t, rank_t = radix_histogram_ranks_tiles(
        tiles, num_partitions,
        interpret=(impl == "pallas_interpret"))
    # cross-tile exclusive scan: rank of row in tile t = within-tile rank
    # + sum of matching counts in earlier tiles.
    tile_offsets = jnp.cumsum(hist_t, axis=0) - hist_t      # (n_tiles, P)
    ranks = (rank_t + jnp.take_along_axis(
        tile_offsets, tiles, axis=1)).reshape(-1)[:n]
    hist = jnp.sum(hist_t, axis=0).at[0].add(-pad)
    return hist, ranks


def radix_histogram_ranks(pid: jnp.ndarray, num_partitions: int,
                          impl: str = "ref", tile: int = TILE):
    """hist (P,), ranks (n,) — stable within-partition ranks.

    impl: 'ref' (pure jnp), 'pallas' (TPU), 'pallas_interpret' (CPU check).
    """
    return _radix_histogram_ranks(pid, num_partitions, impl=impl, tile=tile)


@functools.partial(jax.jit, static_argnames=("num_partitions", "impl", "tile"))
def _partition_plan(pid: jnp.ndarray, num_partitions: int,
                    impl: str = "ref", tile: int = TILE):
    hist, ranks = _radix_histogram_ranks(pid, num_partitions, impl=impl,
                                         tile=tile)
    offsets = jnp.cumsum(hist) - hist
    return hist, offsets[pid] + ranks


def partition_plan(pid: jnp.ndarray, num_partitions: int,
                   impl: str = "ref", tile: int = TILE):
    """(hist, dest): dest[i] = exclusive_offset[pid[i]] + rank[i].

    Scattering row i to slot ``dest[i]`` groups rows by partition, stable
    within each partition (exactly Cylon's hash-partition layout).
    """
    return _partition_plan(pid, num_partitions, impl=impl, tile=tile)
