"""Mosaic-lowerable scan blocks shared by the table kernels.

Pallas TPU has no ``cumsum`` lowering, so the kernels never call it.
Every within-tile exclusive count they need is a product with a strictly
triangular 0/1 matrix on the MXU instead.  Inputs are 0/1 in bfloat16 and
the products accumulate in float32, so each count is exact while it stays
below 2**24 — far above the widest chunk (``CHUNK`` rows) or slab (512
slots) a kernel scans.

Layout rule: per-row vectors travel as ``(1, n)`` rows (rows on lanes);
one-hot matrices are ``(width, n)`` (one id per sublane row), so a chunk's
ranks reduce over sublanes straight into a lane-major row with no
relayout.
"""
import jax
import jax.numpy as jnp

# rows per scan step: one MXU pass on v5e (128x128); ``tuning.TILE`` is a
# multiple of it
CHUNK = 128


def strict_upper(n: int) -> jnp.ndarray:
    """(n, n) bfloat16 with ``[j, i] = 1`` iff ``j < i``: ``x @ U`` is the
    exclusive prefix sum of ``x`` along its last axis."""
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return (row < col).astype(jnp.bfloat16)


def exclusive_cumsum_lanes(m: jnp.ndarray) -> jnp.ndarray:
    """Exclusive prefix sum along the last axis of a 0/1 matrix, int32."""
    return jnp.dot(m.astype(jnp.bfloat16), strict_upper(m.shape[-1]),
                   preferred_element_type=jnp.float32).astype(jnp.int32)


def tile_hist_ranks(ids_of, rank_ref, tile: int, width: int) -> jnp.ndarray:
    """Histogram and stable within-id ranks of one tile of ids.

    ``ids_of(lo, hi)`` returns the ``(1, hi - lo)`` int32 ids of rows
    ``[lo, hi)`` (ids outside ``[0, width)`` count nowhere); each chunk's
    ranks — the number of earlier rows of the tile with the same id — are
    stored to ``rank_ref[0, :, lo:hi]``.  Returns the ``(1, width)``
    histogram.  The one-hot working set is ``width x CHUNK``, whatever the
    tile, so VMEM stays flat whatever the tile.
    """
    c = CHUNK if tile % CHUNK == 0 else tile
    upper = strict_upper(c)
    ids_col = jax.lax.broadcasted_iota(jnp.int32, (width, c), 0)
    seen = jnp.zeros((width, 1), jnp.int32)       # counts of earlier chunks
    for lo in range(0, tile, c):
        onehot = ids_col == ids_of(lo, lo + c)                  # (width, c)
        before = jnp.dot(onehot.astype(jnp.bfloat16), upper,
                         preferred_element_type=jnp.float32) \
            .astype(jnp.int32) + seen
        rank_ref[0, :, lo:lo + c] = jnp.sum(
            jnp.where(onehot, before, 0), axis=0, keepdims=True)
        seen = seen + jnp.sum(onehot.astype(jnp.int32), axis=1,
                              keepdims=True)
    return seen.T
