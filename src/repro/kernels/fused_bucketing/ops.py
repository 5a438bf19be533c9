"""Jitted wrapper around the fused hash+histogram+rank kernel.

:func:`fused_bucket_ranks` is the op ``bucketing.group_to_slabs`` calls:
given key bit-planes and a validity mask it returns, in one fused pass,
each row's bucket id, the per-bucket histogram (trash bucket included)
and each row's stable within-bucket rank — everything the slab scatter
needs.  The tile shape is ``kernels/tuning.py``'s ``TILE`` unless the
caller passes one.
"""
import functools

import jax
import jax.numpy as jnp

from ..tuning import TILE
from .kernel import fused_bucket_ranks_tiles
from .ref import fused_bucket_ranks_ref


@functools.partial(jax.jit, static_argnames=("num_buckets", "impl", "tile"))
def _fused_bucket_ranks(bits: tuple, valid: jnp.ndarray, num_buckets: int,
                        impl: str, tile: int):
    n = valid.shape[0]
    if impl == "ref" or n < tile:
        return fused_bucket_ranks_ref(bits, valid, num_buckets)

    n_tiles = -(-n // tile)
    pad = n_tiles * tile - n
    # pad rows carry valid=0 -> the kernel routes them to the trash
    # bucket P; they sit at the tail, so real rows' cross-tile offsets
    # are unaffected — only the trash histogram column needs the pad
    # contribution subtracted.
    bt = jnp.stack([jnp.pad(b, (0, pad)) for b in bits]) \
        .reshape(len(bits), n_tiles, tile).transpose(1, 0, 2)
    vt = jnp.pad(valid.astype(jnp.int32), (0, pad)).reshape(n_tiles, tile)
    bid_t, hist_t, rank_t = fused_bucket_ranks_tiles(
        bt, vt, num_buckets, interpret=(impl == "pallas_interpret"))
    # cross-tile exclusive scan: rank of row in tile t = within-tile rank
    # + sum of its bucket's counts in earlier tiles.
    tile_offsets = jnp.cumsum(hist_t, axis=0) - hist_t    # (n_tiles, P+1)
    ranks = (rank_t + jnp.take_along_axis(
        tile_offsets, bid_t, axis=1)).reshape(-1)[:n]
    hist = jnp.sum(hist_t, axis=0).at[num_buckets].add(-pad)
    return bid_t.reshape(-1)[:n], hist, ranks


def fused_bucket_ranks(bits: tuple, valid: jnp.ndarray, num_buckets: int,
                       *, impl: str = "ref", tile: int = TILE):
    """(bid (n,), hist (P+1,), ranks (n,)) — see ``ref.py`` for the
    contract.  impl: 'ref' (pure jnp), 'pallas' (TPU), 'pallas_interpret'
    (CPU check)."""
    return _fused_bucket_ranks(tuple(bits), valid, num_buckets, impl, tile)
