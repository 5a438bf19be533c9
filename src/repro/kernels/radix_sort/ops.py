"""Jitted multi-pass LSD radix rank/permutation engine.

The missing primitive behind every sort-shaped table operator: a *stable
rank* of each row under multi-key lexicographic order, computed as a chain
of counting-sort digit passes (``kernel.py`` on TPU, ``ref.py`` elsewhere)
— **no ``sort`` primitive anywhere in the jaxpr**.

Key columns are first mapped to int32 *sort words* whose unsigned order
equals ``jax.lax.sort``'s ascending order (:func:`sortable_word`): int32
gets the sign-bit bias; float32 follows XLA's total-order comparator —
``-0.0`` and ``0.0`` canonicalized equal, all NaNs canonicalized equal and
greatest — so the induced permutation is *bit-identical* to a stable
``jax.lax.sort`` over the same keys (descending keys are pre-transformed
by the caller, exactly like the XLA backend).  Each word then takes
``32 / radix_bits`` stable passes, least-significant digit first, followed
by a final 1-bit validity pass that moves padding rows to the end.

Public ops:

* :func:`radix_permutation` — the stable gather index (``out[i] =
  rows[perm[i]]``), drop-in for ``jax.lax.sort``'s iota payload;
* :func:`radix_rank` — its inverse (each row's output position);
* :func:`stable_partition_perm` — the 1-bit fast path: one pass over a
  boolean, bit-identical to ``argsort(~keep, stable=True)`` — the
  ``compact()``/shuffle-compaction hot loop;
* :func:`grouped_ranks` — (hist, stable within-partition ranks) for *any*
  partition count: the multi-pass generalization of
  ``hash_partition.radix_histogram_ranks`` (whose one-hot caps at
  ``bucketing.MAX_RADIX_BUCKETS``).
"""
import functools

import jax
import jax.numpy as jnp

from ..tuning import RADIX_BITS, TILE
from .kernel import digit_histogram_ranks_tiles
from .ref import digit_histogram_ranks_ref, extract_digits

_SIGN = jnp.int32(-2 ** 31)


def sortable_word(col: jnp.ndarray) -> jnp.ndarray:
    """Key column -> int32 word; unsigned word order == lax.sort order.

    Floats replicate XLA's sort comparator canonicalization: ``-0.0`` ==
    ``0.0`` and every NaN equal (and greatest), so ties keep original row
    order under the stable passes — exactly ``lax.sort``'s behavior.
    """
    if jnp.issubdtype(col.dtype, jnp.floating):
        col = col.astype(jnp.float32)
        col = jnp.where(col == 0.0, jnp.zeros_like(col), col)
        col = jnp.where(jnp.isnan(col), jnp.full_like(col, jnp.nan), col)
        bits = jax.lax.bitcast_convert_type(col, jnp.int32)
        # sign-magnitude -> biased two's complement: negative floats flip
        # all bits, non-negative flip only the sign bit
        return jnp.where(bits < 0, ~bits, bits ^ _SIGN)
    return col.astype(jnp.int32) ^ _SIGN


def _digit_pass(words: jnp.ndarray, shift: int, radix_bits: int,
                impl: str, tile: int):
    """(hist (D,), stable within-digit ranks (n,)) for one pass."""
    n = words.shape[0]
    if impl == "ref" or n < tile:
        return digit_histogram_ranks_ref(words, shift, radix_bits)
    n_tiles = -(-n // tile)
    pad = n_tiles * tile - n
    # pad word 0 has digit 0 at every shift; pad rows sit at the tail so
    # real rows' cross-tile offsets are unaffected — only hist[0] needs
    # the pad contribution subtracted.
    tiles = jnp.pad(words, (0, pad)).reshape(n_tiles, tile)
    hist_t, rank_t = digit_histogram_ranks_tiles(
        tiles, shift, radix_bits,
        interpret=(impl == "pallas_interpret"))
    tile_offsets = jnp.cumsum(hist_t, axis=0) - hist_t    # (n_tiles, D)
    d_tiles = extract_digits(tiles, shift, radix_bits)
    ranks = (rank_t + jnp.take_along_axis(
        tile_offsets, d_tiles, axis=1)).reshape(-1)[:n]
    hist = jnp.sum(hist_t, axis=0).at[0].add(-pad)
    return hist, ranks


def _scatter_pass(perm: jnp.ndarray, words: jnp.ndarray, shift: int,
                  radix_bits: int, impl: str, tile: int) -> jnp.ndarray:
    """One stable counting-sort pass: ``words`` are the current-order sort
    words (already gathered through ``perm``); returns the refined perm."""
    n = perm.shape[0]
    d = extract_digits(words, shift, radix_bits)
    hist, ranks = _digit_pass(words, shift, radix_bits, impl, tile)
    offsets = jnp.cumsum(hist) - hist
    dest = offsets[d] + ranks
    return jnp.zeros((n,), jnp.int32).at[dest].set(perm)


@functools.partial(jax.jit,
                   static_argnames=("impl", "radix_bits", "tile"))
def _radix_permutation(cols: tuple, invalid: jnp.ndarray, *,
                       impl: str = "ref",
                       radix_bits: int = RADIX_BITS,
                       tile: int = TILE) -> jnp.ndarray:
    n = invalid.shape[0]
    perm = jnp.arange(n, dtype=jnp.int32)
    for col in reversed(cols):                 # least-significant key first
        w = sortable_word(col)
        for shift in range(0, 32, radix_bits):
            perm = _scatter_pass(perm, w[perm], shift, radix_bits, impl,
                                 tile)
    # most-significant: validity (padding rows move to the end, stably)
    flag = invalid[perm].astype(jnp.int32)
    return _scatter_pass(perm, flag, 0, 1, impl, tile)


def radix_permutation(cols: tuple, invalid: jnp.ndarray, *,
                      impl: str = "ref", radix_bits: int = RADIX_BITS,
                      tile: int = TILE) -> jnp.ndarray:
    """Stable gather index sorting by ``cols`` lexicographically ascending,
    rows with ``invalid`` set last — bit-identical to the permutation of a
    stable ``lax.sort((invalid, *cols, iota))``.

    impl: 'ref' (pure jnp), 'pallas' (TPU), 'pallas_interpret' (CPU check).
    ``radix_bits``/``tile`` default to ``kernels/tuning.py``'s widths.
    """
    return _radix_permutation(tuple(cols), invalid, impl=impl,
                              radix_bits=radix_bits, tile=tile)


@functools.partial(jax.jit,
                   static_argnames=("impl", "radix_bits", "tile"))
def _radix_rank(cols: tuple, invalid: jnp.ndarray, *, impl: str,
                radix_bits: int, tile: int) -> jnp.ndarray:
    n = invalid.shape[0]
    perm = _radix_permutation(cols, invalid, impl=impl,
                              radix_bits=radix_bits, tile=tile)
    iota = jnp.arange(n, dtype=jnp.int32)
    return jnp.zeros((n,), jnp.int32).at[perm].set(iota)


def radix_rank(cols: tuple, invalid: jnp.ndarray, *, impl: str = "ref",
               radix_bits: int = RADIX_BITS,
               tile: int = TILE) -> jnp.ndarray:
    """Each row's stable output position under the same order (the inverse
    of :func:`radix_permutation`): valid rows with globally distinct keys
    get exactly their canonical (key-sorted) slot in ``[0, n_valid)``."""
    return _radix_rank(tuple(cols), invalid, impl=impl,
                       radix_bits=radix_bits, tile=tile)


@functools.partial(jax.jit, static_argnames=("impl", "tile"))
def _stable_partition_perm(keep: jnp.ndarray, *, impl: str,
                           tile: int) -> jnp.ndarray:
    n = keep.shape[0]
    perm = jnp.arange(n, dtype=jnp.int32)
    flag = jnp.logical_not(keep).astype(jnp.int32)
    return _scatter_pass(perm, flag, 0, 1, impl, tile)


def stable_partition_perm(keep: jnp.ndarray, *, impl: str = "ref",
                          tile: int = TILE) -> jnp.ndarray:
    """1-bit fast path: gather index moving ``keep`` rows to the front,
    stable — bit-identical to ``argsort(~keep, stable=True)`` in a single
    counting pass (the compaction hot loop of ``compact()``/``select()``
    and the shuffle's receive side)."""
    return _stable_partition_perm(keep, impl=impl, tile=tile)


@functools.partial(jax.jit,
                   static_argnames=("num_partitions", "impl", "radix_bits",
                                    "tile"))
def _grouped_ranks(pid: jnp.ndarray, num_partitions: int, *,
                   impl: str, radix_bits: int, tile: int):
    """(hist (P,), stable within-partition ranks (n,)) for any ``P``.

    The histogram is one scatter-add; ranks come from the global stable
    rank under ascending ``pid`` (``ceil(log2 P / radix_bits)`` digit
    passes over the id bits) minus the partition's exclusive offset —
    semantics identical to ``hash_partition.radix_histogram_ranks`` but
    with per-pass one-hot width ``2**radix_bits`` instead of ``P``, so
    large partition counts stay sort-free.
    """
    n = pid.shape[0]
    hist = jnp.zeros((num_partitions,), jnp.int32).at[pid].add(1)
    nbits = max(1, (num_partitions - 1).bit_length())
    perm = jnp.arange(n, dtype=jnp.int32)
    for shift in range(0, nbits, radix_bits):
        perm = _scatter_pass(perm, pid[perm], shift, radix_bits, impl,
                             tile)
    iota = jnp.arange(n, dtype=jnp.int32)
    grank = jnp.zeros((n,), jnp.int32).at[perm].set(iota)
    offsets = jnp.cumsum(hist) - hist
    return hist, grank - offsets[pid]


def grouped_ranks(pid: jnp.ndarray, num_partitions: int, *,
                  impl: str = "ref", radix_bits: int = RADIX_BITS,
                  tile: int = TILE):
    """(hist (P,), stable within-partition ranks (n,)) for any ``P`` —
    see :func:`_grouped_ranks`."""
    return _grouped_ranks(pid, num_partitions, impl=impl,
                          radix_bits=radix_bits, tile=tile)
