"""Pallas TPU radix histogram + within-tile rank kernel.

Tiling: the row axis is blocked into ``(n_tiles, 1, tile)`` (the unit
middle axis keeps every block's last two dimensions equal to the array's
own, as Mosaic requires); each grid step loads one ``(1, tile)`` row of
partition ids into VMEM and reduces its one-hot occupancy two ways:

* per-tile histogram  ``(1, P)``      (sum over rows), and
* within-tile ranks   ``(1, tile)``   (exclusive prefix count over rows,
  read at each row's own partition).

The prefix count runs chunk by chunk on the MXU (``tile_scan``); the
cross-tile exclusive scan (cheap, ``(n_tiles, P)``) is composed outside
the kernel in ``ops.py`` — keeping the kernel embarrassingly parallel over
tiles (``dimension_semantics=("parallel",)``).

VMEM budget: the one-hot is ``P x 128`` per chunk whatever the tile —
P=512 means 256 KiB, far under the 16 MiB scoped VMEM of TPU v5e.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tile_scan import tile_hist_ranks


def _kernel(pid_ref, hist_ref, rank_ref, *, num_partitions: int):
    tile = pid_ref.shape[2]
    hist_ref[0] = tile_hist_ranks(lambda lo, hi: pid_ref[0, :, lo:hi],
                                  rank_ref, tile, num_partitions)


def radix_histogram_ranks_tiles(pid_tiles: jnp.ndarray, num_partitions: int,
                                *, interpret: bool = False):
    """``pid_tiles``: int32 ``(n_tiles, tile)`` -> (hist ``(n_tiles, P)``,
    ranks ``(n_tiles, tile)``)."""
    n_tiles, tile = pid_tiles.shape
    kern = functools.partial(_kernel, num_partitions=num_partitions)
    hist, ranks = pl.pallas_call(
        kern,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((1, 1, tile), lambda i: (i, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, 1, num_partitions), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, tile), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_tiles, 1, num_partitions), jnp.int32),
            jax.ShapeDtypeStruct((n_tiles, 1, tile), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(pid_tiles[:, None, :])
    return hist[:, 0, :], ranks[:, 0, :]
