"""Pallas TPU bucketed hash semi-join membership kernel.

Tiling: the grid is one step per hash bucket (the same layout as the
``hash_join`` probe kernel).  Each step loads that bucket's probe slab
(``(K, Lc)`` key bit-planes + ``(Lc,)`` occupancy) and build slab
(``(K, C)`` + ``(C,)``) into VMEM and materializes the dense ``(Lc, C)``
equality matrix in VREGs — all static indexing, pure VPU work
(broadcast-compare + one row reduction).  Per bucket it reduces the match
matrix a single way:

* ``member`` ``(1, Lc)`` — any build slot matches the probe slot.

That is the whole output: membership filtering needs no match ranks and
no pair-space scatter, so the semi-join's VMEM working set is the same
``Lc*C`` compare matrix as the join probe but its HBM traffic is
``O(Lc)`` instead of ``O(Lc*C)``.

Buckets are independent (``dimension_semantics=("parallel",)``); mapping
members back to original row order is composed outside the kernel in
``ops.py`` where XLA handles the dynamic scatter.

Occupancy and member rows travel as ``(B, 1, n)`` so every block's last
two dimensions equal the array's own, as Mosaic requires.

VMEM budget: the match matrix dominates at ``Lc*C*4`` bytes — Lc=C=512
(the full-capacity exact-sizing ceiling) means 1 MiB, far under the
16 MiB scoped VMEM of TPU v5e.  ``Lc``/``C`` multiples of 128 (or at least 8)
are recommended for lane alignment.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(pbits_ref, pocc_ref, bbits_ref, bocc_ref, member_ref,
            *, num_keys: int):
    pocc = pocc_ref[0, 0, :]                               # (Lc,)
    bocc = bocc_ref[0, 0, :]                               # (C,)
    match = (pocc[:, None] > 0) & (bocc[None, :] > 0)      # (Lc, C)
    for k in range(num_keys):
        match = match & (pbits_ref[0, k, :][:, None]
                         == bbits_ref[0, k, :][None, :])
    member_ref[0, 0, :] = (jnp.sum(match.astype(jnp.int32), axis=1)
                        > 0).astype(jnp.int32)


def bucket_member_buckets(pbits: jnp.ndarray, pocc: jnp.ndarray,
                          bbits: jnp.ndarray, bocc: jnp.ndarray,
                          *, interpret: bool = False):
    """pbits (B, K, Lc) int32, pocc (B, Lc) int32, bbits (B, K, C),
    bocc (B, C) -> member (B, Lc) int32 0/1."""
    n_buckets, num_keys, probe_cap = pbits.shape
    chain_cap = bbits.shape[2]
    kern = functools.partial(_kernel, num_keys=num_keys)
    member = pl.pallas_call(
        kern,
        grid=(n_buckets,),
        in_specs=[
            pl.BlockSpec((1, num_keys, probe_cap), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, probe_cap), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, num_keys, chain_cap), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, chain_cap), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, probe_cap), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_buckets, 1, probe_cap),
                                       jnp.int32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(pbits, pocc[:, None, :], bbits, bocc[:, None, :])
    return member[:, 0, :]
