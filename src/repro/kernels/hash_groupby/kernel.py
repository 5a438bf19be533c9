"""Pallas TPU bucketed hash-accumulate groupby kernel.

Tiling: the grid is one step per hash bucket (the same layout as the
``hash_join`` probe kernel).  Each step loads that bucket's slab (``(K,
C)`` key bit-planes, ``(C,)`` occupancy, ``(V, C)`` float32 value columns)
into VMEM and materializes the dense ``(C, C)`` key-equality matrix in
VREGs — all static indexing, pure VPU work (broadcast-compare + masked
row reductions).  Per bucket it reduces the equality matrix four ways:

* ``rep``    ``(1, C)`` — slot is its key's first occurrence (no earlier
  equal slot: reduction over the strict lower triangle);
* ``counts`` ``(1, C)`` — group sizes;
* ``sums`` / ``mins`` / ``maxs`` ``(1, V, C)`` — masked value reductions
  per group, every aggregate in the same single pass (no sort anywhere).

Buckets are independent (``dimension_semantics=("parallel",)``); the
canonical-order output assembly (representative compaction + key ranking)
is composed outside the kernel in ``ops.py``/``local_ops`` where XLA
handles the dynamic scatters.

Occupancy, ``rep`` and ``counts`` rows travel as ``(B, 1, C)`` so every
block's last two dimensions equal the array's own, as Mosaic requires.

VMEM budget: the equality matrix dominates at ``C*C*4`` bytes — C=512
(the full-capacity exact-sizing ceiling) means 1 MiB, far under the
16 MiB scoped VMEM of TPU v5e.  ``C`` multiples of 128 (or at least 8) are
recommended for lane alignment.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(kbits_ref, occ_ref, vals_ref,
            rep_ref, counts_ref, sums_ref, mins_ref, maxs_ref,
            *, num_keys: int, num_vals: int):
    occ = occ_ref[0, 0, :]                                 # (C,)
    eq = (occ[:, None] > 0) & (occ[None, :] > 0)           # (C, C)
    for k in range(num_keys):
        eq = eq & (kbits_ref[0, k, :][:, None]
                   == kbits_ref[0, k, :][None, :])
    m = eq.astype(jnp.int32)
    counts_ref[0, 0, :] = jnp.sum(m, axis=1)
    cap = occ.shape[0]
    earlier = jax.lax.broadcasted_iota(jnp.int32, (cap, cap), 1) \
        < jax.lax.broadcasted_iota(jnp.int32, (cap, cap), 0)  # j < i
    rep = (occ > 0) & (jnp.sum(m * earlier.astype(jnp.int32), axis=1) == 0)
    rep_ref[0, 0, :] = rep.astype(jnp.int32)
    for v in range(num_vals):
        x = vals_ref[0, v, :][None, :]                     # (1, C)
        sums_ref[0, v, :] = jnp.sum(jnp.where(eq, x, 0.0), axis=1)
        mins_ref[0, v, :] = jnp.min(jnp.where(eq, x, jnp.inf), axis=1)
        maxs_ref[0, v, :] = jnp.max(jnp.where(eq, x, -jnp.inf), axis=1)


def bucket_accumulate_buckets(kbits: jnp.ndarray, occ: jnp.ndarray,
                              vals: jnp.ndarray, *,
                              interpret: bool = False):
    """kbits (B, K, C) int32, occ (B, C) int32, vals (B, V, C) f32 ->
    (rep (B, C) int32, counts (B, C) int32, sums/mins/maxs (B, V, C))."""
    n_buckets, num_keys, cap = kbits.shape
    num_vals = vals.shape[1]
    kern = functools.partial(_kernel, num_keys=num_keys, num_vals=num_vals)
    val_spec = pl.BlockSpec((1, num_vals, cap), lambda i: (i, 0, 0))
    val_shape = jax.ShapeDtypeStruct((n_buckets, num_vals, cap),
                                     jnp.float32)
    row_spec = pl.BlockSpec((1, 1, cap), lambda i: (i, 0, 0))
    row_shape = jax.ShapeDtypeStruct((n_buckets, 1, cap), jnp.int32)
    rep, counts, sums, mins, maxs = pl.pallas_call(
        kern,
        grid=(n_buckets,),
        in_specs=[
            pl.BlockSpec((1, num_keys, cap), lambda i: (i, 0, 0)),
            row_spec,
            val_spec,
        ],
        out_specs=[row_spec, row_spec, val_spec, val_spec, val_spec],
        out_shape=[row_shape, row_shape, val_shape, val_shape, val_shape],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(kbits, occ[:, None, :], vals)
    return rep[:, 0, :], counts[:, 0, :], sums, mins, maxs
