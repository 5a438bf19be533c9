"""Pallas TPU bucketed hash-join probe kernel.

Tiling: the grid is one step per hash bucket.  Each step loads that
bucket's probe slab (``(K, Lc)`` key bit-planes + ``(Lc,)`` occupancy) and
build slab (``(K, C)`` + ``(C,)``) into VMEM and materializes the dense
``(Lc, C)`` equality matrix in VREGs — all static indexing, pure VPU work
(broadcast-compare) plus one MXU product for the prefix count, the same
idiom as the ``hash_partition`` radix kernel.  Per bucket it reduces the match matrix two ways:

* per-probe-row match counts ``(1, Lc)``     (sum over chain slots), and
* within-row match ranks     ``(1, Lc, C)``  (exclusive prefix count over
  chain slots, ``-1`` where the pair does not match — a product with a
  strictly triangular matrix, ``tile_scan``: Mosaic has no ``cumsum``).

Occupancy rows travel as ``(B, 1, n)`` so every block's last two
dimensions equal the array's own, as Mosaic requires.

Buckets are independent (``dimension_semantics=("parallel",)``); the
output-slot assembly (offsets cumsum + scatter) is composed outside the
kernel in ``ops.py``/``local_ops`` where XLA handles the dynamic scatter.

VMEM budget: the match matrix dominates at ``Lc*C*4`` bytes — Lc=C=256
means 256 KiB, far under the 16 MiB scoped VMEM of TPU v5e.  ``Lc``/``C``
multiples of 128 (or at least 8) are recommended for lane alignment.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tile_scan import exclusive_cumsum_lanes


def _kernel(pbits_ref, pocc_ref, bbits_ref, bocc_ref,
            counts_ref, rank_ref, *, num_keys: int):
    pocc = pocc_ref[0, 0, :]                               # (Lc,)
    bocc = bocc_ref[0, 0, :]                               # (C,)
    match = (pocc[:, None] > 0) & (bocc[None, :] > 0)      # (Lc, C)
    for k in range(num_keys):
        match = match & (pbits_ref[0, k, :][:, None]
                         == bbits_ref[0, k, :][None, :])
    m = match.astype(jnp.int32)
    counts_ref[0, 0, :] = jnp.sum(m, axis=1)
    excl = exclusive_cumsum_lanes(m)
    rank_ref[0, :, :] = jnp.where(match, excl, -1)


def bucket_probe_buckets(pbits: jnp.ndarray, pocc: jnp.ndarray,
                         bbits: jnp.ndarray, bocc: jnp.ndarray,
                         *, interpret: bool = False):
    """pbits (B, K, Lc) int32, pocc (B, Lc) int32, bbits (B, K, C),
    bocc (B, C) -> (counts (B, Lc) int32, rank (B, Lc, C) int32)."""
    n_buckets, num_keys, probe_cap = pbits.shape
    chain_cap = bbits.shape[2]
    kern = functools.partial(_kernel, num_keys=num_keys)
    counts, rank = pl.pallas_call(
        kern,
        grid=(n_buckets,),
        in_specs=[
            pl.BlockSpec((1, num_keys, probe_cap), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, probe_cap), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, num_keys, chain_cap), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, chain_cap), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, probe_cap), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, probe_cap, chain_cap), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_buckets, 1, probe_cap), jnp.int32),
            jax.ShapeDtypeStruct((n_buckets, probe_cap, chain_cap),
                                 jnp.int32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(pbits, pocc[:, None, :], bbits, bocc[:, None, :])
    return counts[:, 0, :], rank
