"""Pallas TPU radix digit histogram + within-tile rank kernel.

The hot loop of one LSD radix pass.  Tiling mirrors the ``hash_partition``
kernel: the row axis is blocked into ``(n_tiles, 1, tile)``; each grid
step loads one ``(1, tile)`` row of int32 *sort words* into VMEM, extracts
the ``radix_bits``-wide digit at ``shift`` in VREGs (arithmetic shift +
mask — exact at every offset because the mask discards sign-extension
bits), and reduces the one-hot digit occupancy two ways:

* per-tile digit histogram  ``(1, D)``    (sum over rows), and
* within-tile digit ranks   ``(1, tile)`` (exclusive prefix count over
  rows, read at each row's own digit; chunked on the MXU by
  ``tile_scan``).

Fusing digit extraction into the kernel means a pass streams each word
through VMEM exactly once; the cross-tile exclusive scan (cheap,
``(n_tiles, D)``) is composed outside in ``ops.py``, keeping the kernel
embarrassingly parallel over tiles.

VMEM budget: the one-hot is ``D x 128`` per chunk whatever the tile —
D=256 (the 8-bit default) is 128 KiB, D=2048 (11 bits) 1 MiB; the 1-bit
compaction fast path (D=2) is a sliver.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tile_scan import tile_hist_ranks


def _kernel(words_ref, hist_ref, rank_ref, *, shift: int, radix_bits: int):
    tile = words_ref.shape[2]
    num_digits = 1 << radix_bits

    def digits(lo, hi):
        return (words_ref[0, :, lo:hi] >> shift) \
            & jnp.int32(num_digits - 1)

    hist_ref[0] = tile_hist_ranks(digits, rank_ref, tile, num_digits)


def digit_histogram_ranks_tiles(word_tiles: jnp.ndarray, shift: int,
                                radix_bits: int, *,
                                interpret: bool = False):
    """``word_tiles``: int32 ``(n_tiles, tile)`` -> (hist ``(n_tiles, D)``,
    ranks ``(n_tiles, tile)``) for ``D = 2**radix_bits``."""
    n_tiles, tile = word_tiles.shape
    num_digits = 1 << radix_bits
    kern = functools.partial(_kernel, shift=shift, radix_bits=radix_bits)
    hist, ranks = pl.pallas_call(
        kern,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((1, 1, tile), lambda i: (i, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, 1, num_digits), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, tile), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_tiles, 1, num_digits), jnp.int32),
            jax.ShapeDtypeStruct((n_tiles, 1, tile), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(word_tiles[:, None, :])
    return hist[:, 0, :], ranks[:, 0, :]
