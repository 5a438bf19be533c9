"""Jitted bucketed hash-join build + probe plan.

:func:`hash_join_plan` is the op the table engine calls for
``join(impl="hash")``: it buckets both sides by a murmur-style key hash
(build side = the chain table, probe side = the left rows), runs the
bucketed probe (Pallas kernel on TPU, pure-jnp ref elsewhere) and returns
everything the caller needs to scatter matched pairs into a static-capacity
output: per-left-row match counts plus, per (probe slot, chain slot) pair,
the original row ids and the within-row match rank.

Static-shape contract (the same philosophy as the table shuffle): a bucket
holds at most ``bucket_capacity`` build rows and ``probe_capacity`` probe
rows.  Overflowing rows are dropped and *counted* (``build_dropped`` /
``probe_dropped``) — callers size the capacities so both are zero, and the
conformance suite checks the counters trip exactly at capacity.

The plan takes **key bit-planes**, not raw key columns: the engine
extracts them once per side (``bucketing.BucketPlan`` /
``bucketing.key_bits`` — floats bitcast to int32 after normalizing
``-0.0`` to ``+0.0``) and shares them with the host-side sizing pass, so
build and probe never re-hash the same columns.  Multi-column keys are
exact — the hash only picks the bucket; equality is decided on the full
key bits.  NaN float keys compare equal-by-bits (joins on NaN keys are
out of contract, as they are for the sort-merge path's sort order).
"""
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..bucketing import (EXACT_SLAB_CAP, bucket_ids,  # noqa: F401
                         group_to_slabs, key_bits)
from .kernel import bucket_probe_buckets
from .ref import bucket_probe_ref


def _group(bits: tuple, valid: jnp.ndarray, num_buckets: int,
           slab_cap: int, impl: str, bid=None):
    """Bucket-grouped slabs (see kernels.bucketing.group_to_slabs)."""
    slab_bits, occ, row, _, dropped = group_to_slabs(
        bits, valid, num_buckets, slab_cap, impl, bid=bid)
    return slab_bits, occ, row, dropped


class HashJoinPlan(NamedTuple):
    """Probe results mapped back to original row ids.

    ``match_counts`` is indexed by original left row (0 for padding rows
    and for probe-dropped rows); the pair-space arrays are indexed by
    (bucket, probe slot, chain slot) and carry original row ids.
    """

    match_counts: jnp.ndarray    # (Lcap,) int32
    probed: jnp.ndarray          # (Lcap,) bool: left row made it into a slab
    probe_row: jnp.ndarray       # (B, Lc) int32 original left row per slot
    rank: jnp.ndarray            # (B, Lc, C) int32 match rank, -1 = no match
    build_row: jnp.ndarray       # (B, C) int32 original right row per slot
    build_dropped: jnp.ndarray   # () int32 right rows lost to chain overflow
    probe_dropped: jnp.ndarray   # () int32 left rows lost to probe overflow


@functools.partial(jax.jit, static_argnames=("num_buckets",
                                             "bucket_capacity",
                                             "probe_capacity", "impl"))
def hash_join_plan(left_bits: tuple, left_valid: jnp.ndarray,
                   right_bits: tuple, right_valid: jnp.ndarray, *,
                   num_buckets: int, bucket_capacity: int,
                   probe_capacity: int, impl: str = "ref",
                   left_bid: jnp.ndarray | None = None,
                   right_bid: jnp.ndarray | None = None) -> HashJoinPlan:
    """Bucketed build (right) + probe (left) over parallel key bit-planes.

    impl: 'ref' (pure jnp), 'pallas' (TPU), 'pallas_interpret' (CPU check).
    ``left_bid`` / ``right_bid`` carry precomputed bucket ids (the eager
    sizing path's hash, via ``BucketPlan``) so the plan doesn't re-hash.
    """
    B, C, Lc = num_buckets, bucket_capacity, probe_capacity
    lbits, rbits = tuple(left_bits), tuple(right_bits)
    lcap = left_valid.shape[0]

    bslab, bocc, brow, build_dropped = _group(rbits, right_valid, B, C,
                                              impl, bid=right_bid)
    pslab, pocc, prow, probe_dropped = _group(lbits, left_valid, B, Lc,
                                              impl, bid=left_bid)

    num_keys = len(lbits)
    pb = pslab.reshape(num_keys, B, Lc).transpose(1, 0, 2)
    bb = bslab.reshape(num_keys, B, C).transpose(1, 0, 2)
    po = pocc.reshape(B, Lc)
    bo = bocc.reshape(B, C)
    if impl == "ref":
        counts_g, rank_g = bucket_probe_ref(pb, po, bb, bo)
    else:
        counts_g, rank_g = bucket_probe_buckets(
            pb, po, bb, bo, interpret=(impl == "pallas_interpret"))

    # counts + probed back to original left-row order in ONE stacked
    # scatter (trash slot lcap for empties)
    idx = jnp.where(pocc > 0, prow, lcap)
    packed = (jnp.zeros((2, lcap + 1), jnp.int32)
              .at[:, idx].set(jnp.stack([counts_g.reshape(-1),
                                         (pocc > 0).astype(jnp.int32)]))
              [:, :lcap])
    return HashJoinPlan(match_counts=packed[0], probed=packed[1] > 0,
                        probe_row=prow.reshape(B, Lc),
                        rank=rank_g,
                        build_row=brow.reshape(B, C),
                        build_dropped=build_dropped,
                        probe_dropped=probe_dropped)


def workload_hash_join_sizes(keys_per_shard: int, slab: int = 256) -> dict:
    """Bucket sizing for a known duplicate-heavy workload (the paper's
    10%-key-uniqueness joins): ~4 distinct keys (~40 rows at 10x
    duplication) per bucket on average, ``slab``-slot build/probe slabs
    (>6x headroom over the expected max bucket load).  Returns kwargs for
    ``local_ops.join`` / ``dist_join(local_join_sizes=...)``."""
    target = max(8, keys_per_shard // 4)
    num_buckets = 1 << max(0, int(target - 1).bit_length())
    return {"num_buckets": num_buckets, "bucket_capacity": slab,
            "probe_capacity": slab}


def default_hash_join_sizes(left_capacity: int, right_capacity: int,
                            num_buckets: int | None = None):
    """(num_buckets, bucket_capacity, probe_capacity) heuristics.

    Small tables (both capacities <= ``bucketing.EXACT_SLAB_CAP``) get
    full-capacity slabs: every key distribution — including all-equal
    keys — fits with zero overflow, so the auto-sized hash backend is
    exact wherever the sort-merge backend is.  Larger tables get ~16
    build rows per bucket on average with 4x headroom per slab — an
    assumption of ~uniform key spread; with *concrete* (non-traced) keys
    the engine upgrades the slab capacities to the distribution-proof
    two-pass ``bucketing.plan_bucket_sizes`` planner.  A caller-chosen
    ``num_buckets`` keeps the slab capacities consistent with *that*
    bucket count; size explicitly for skewed large-table keys under
    ``jit`` (the capacities are worst-case *per bucket*, so heavy
    duplication needs deeper, fewer buckets)."""
    small = max(left_capacity, right_capacity) <= EXACT_SLAB_CAP
    if num_buckets is None:
        if small:
            num_buckets = 8
        else:
            target = max(1, right_capacity // 16)
            num_buckets = 1 << min(16, max(3, (target - 1).bit_length()))
    if small:
        return num_buckets, max(8, right_capacity), max(8, left_capacity)
    chain = max(8, -(-right_capacity // num_buckets) * 4)
    probe = max(8, -(-left_capacity // num_buckets) * 4)
    return num_buckets, chain, probe
