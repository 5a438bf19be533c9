"""Pallas TPU fused hash + histogram + rank kernel.

The single-pass grouping behind ``bucketing.group_to_slabs``: where the
unfused path ran one pass to hash rows into bucket ids and a *second*
kernel pass (``hash_partition``) to histogram/rank them, this kernel does
both in one sweep over each tile — the murmur mix-chain over the key
bit-planes stays in VREGs and feeds the one-hot occupancy matrix
directly, so bucket ids are never materialized to HBM between passes.

Tiling (same scheme as ``hash_partition/kernel.py``): the row axis is
blocked into ``(n_tiles, ., tile)``; each grid step loads one ``(1, K,
tile)`` slab of bit-planes plus its ``(1, 1, tile)`` validity row into
VMEM and, one 128-row chunk at a time (``tile_scan``), mixes the K planes
into a per-row bucket id, then reduces the ``(P+1)``-wide one-hot (P real
buckets + 1 trash column for invalid rows) into the per-tile histogram
``(1, P+1)`` and the within-tile ranks ``(1, tile)``.  The cross-tile
exclusive scan is composed outside in ``ops.py``, keeping the grid
embarrassingly parallel (``dimension_semantics=("parallel",)``).

VMEM budget: the one-hot is ``(P+1) x 128`` per chunk whatever the tile —
P=512 means 257 KiB, far under the 16 MiB scoped VMEM of TPU v5e.
``tile`` defaults to ``kernels/tuning.py``'s ``TILE``.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tile_scan import tile_hist_ranks
from .ref import _GOLDEN, _mix32


def _kernel(bits_ref, valid_ref, bid_ref, hist_ref, rank_ref, *,
            num_buckets: int, num_keys: int):
    tile = valid_ref.shape[2]

    def bucket_ids(lo, hi):
        h = jnp.full((1, hi - lo), jnp.uint32(_GOLDEN))
        for k in range(num_keys):
            u = jax.lax.bitcast_convert_type(bits_ref[0, k:k + 1, lo:hi],
                                             jnp.uint32)
            h = _mix32(h ^ (u + jnp.uint32(_GOLDEN) + (h << 6) + (h >> 2)))
        bid = (h % jnp.uint32(num_buckets)).astype(jnp.int32)
        bid = jnp.where(valid_ref[0, :, lo:hi] > 0, bid, num_buckets)
        bid_ref[0, :, lo:hi] = bid
        return bid

    hist_ref[0] = tile_hist_ranks(bucket_ids, rank_ref, tile,
                                  num_buckets + 1)


def fused_bucket_ranks_tiles(bits_tiles: jnp.ndarray,
                             valid_tiles: jnp.ndarray, num_buckets: int,
                             *, interpret: bool = False):
    """``bits_tiles`` int32 ``(n_tiles, K, tile)``, ``valid_tiles`` int32
    ``(n_tiles, tile)`` -> (bid ``(n_tiles, tile)``, hist ``(n_tiles,
    P+1)``, ranks ``(n_tiles, tile)``)."""
    n_tiles, num_keys, tile = bits_tiles.shape
    kern = functools.partial(_kernel, num_buckets=num_buckets,
                             num_keys=num_keys)
    row_spec = pl.BlockSpec((1, 1, tile), lambda i: (i, 0, 0))
    row_shape = jax.ShapeDtypeStruct((n_tiles, 1, tile), jnp.int32)
    bid, hist, ranks = pl.pallas_call(
        kern,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((1, num_keys, tile), lambda i: (i, 0, 0)),
            row_spec,
        ],
        out_specs=[
            row_spec,
            pl.BlockSpec((1, 1, num_buckets + 1), lambda i: (i, 0, 0)),
            row_spec,
        ],
        out_shape=[
            row_shape,
            jax.ShapeDtypeStruct((n_tiles, 1, num_buckets + 1), jnp.int32),
            row_shape,
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(bits_tiles, valid_tiles[:, None, :])
    return bid[:, 0, :], hist[:, 0, :], ranks[:, 0, :]
