"""Jitted bucketed hash-accumulate groupby plan.

:func:`hash_groupby_plan` is the op the table engine calls for
``groupby_aggregate(impl="hash")`` (and, key-only, for
``drop_duplicates(impl="hash")``): it buckets the table's rows by a
murmur-style key hash using the shared ``kernels.bucketing`` slab
machinery, then runs the bucketed accumulate (Pallas kernel on TPU,
pure-jnp ref elsewhere), which computes **sum/count/min/max for every
distinct key in one dense pass — no sort anywhere in the plan**.  Equal
keys always share a bucket, so per-bucket aggregation is exact; the
bucket slabs keep original row order, so each group's representative
slot is the key's *first occurrence* in the table (what pandas
``drop_duplicates`` keeps).

Static-shape contract (the same philosophy as the hash join): a bucket
holds at most ``bucket_capacity`` rows.  Overflowing rows are dropped and
*counted* (``dropped``) — callers size the capacity so the counter is
zero, and the conformance suite checks it trips exactly at capacity.

The plan takes **key bit-planes**, not raw key columns: the engine
extracts them once (``bucketing.BucketPlan`` / ``bucketing.key_bits`` —
floats bitcast to int32 after normalizing ``-0.0`` to ``+0.0``) and
shares them with the host-side sizing pass, so sizing and aggregation
never re-hash the same columns.  Multi-column keys are exact — the hash
only picks the bucket; group identity is decided on the full key bits.
NaN float keys group equal-by-bits (grouping on NaN keys is out of
contract, as it is for the sort backend's sort order).
"""
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..bucketing import (EXACT_SLAB_CAP, default_bucket_count,  # noqa: F401
                         group_to_slabs, key_bits)
from .kernel import bucket_accumulate_buckets
from .ref import bucket_accumulate_ref


class HashGroupbyPlan(NamedTuple):
    """Per-slot accumulate results in bucket-slab space.

    The slab arrays are indexed by (bucket, slot); ``row`` maps a slot
    back to its original table row (group representatives map to the
    key's first occurrence).  Aggregates are only meaningful at slots
    with ``rep != 0``.
    """

    rep: jnp.ndarray       # (B, C) int32: slot is a group representative
    row: jnp.ndarray       # (B, C) int32 original row per slot
    counts: jnp.ndarray    # (B, C) int32 group sizes
    sums: jnp.ndarray      # (B, V, C) f32 per-value-column group sums
    mins: jnp.ndarray      # (B, V, C) f32
    maxs: jnp.ndarray      # (B, V, C) f32
    dropped: jnp.ndarray   # () int32 rows lost to bucket overflow


@functools.partial(jax.jit, static_argnames=("num_buckets",
                                             "bucket_capacity", "impl"))
def hash_groupby_plan(key_bits_planes: tuple, valid: jnp.ndarray,
                      values: tuple = (), *, num_buckets: int,
                      bucket_capacity: int, impl: str = "ref",
                      bid: jnp.ndarray | None = None) -> HashGroupbyPlan:
    """Bucketed hash-accumulate over parallel key bit-planes / value
    columns.

    impl: 'ref' (pure jnp), 'pallas' (TPU), 'pallas_interpret' (CPU check).
    ``values`` may be empty (key-only grouping, e.g. drop_duplicates); a
    dummy zero column keeps the kernel signature static.  ``bid`` carries
    precomputed bucket ids (the eager sizing path's hash, via
    ``BucketPlan``) so the plan doesn't re-hash.
    """
    B, C = num_buckets, bucket_capacity
    bits = tuple(key_bits_planes)
    vals = tuple(v.astype(jnp.float32) for v in values) \
        or (jnp.zeros_like(valid, jnp.float32),)
    slab_bits, occ, row, val_slabs, dropped = group_to_slabs(
        bits, valid, B, C, impl, payload=vals, bid=bid)

    num_keys = len(bits)
    kb = slab_bits.reshape(num_keys, B, C).transpose(1, 0, 2)
    oc = occ.reshape(B, C)
    vs = jnp.stack(val_slabs).reshape(len(vals), B, C).transpose(1, 0, 2)
    if impl == "ref":
        rep, counts, sums, mins, maxs = bucket_accumulate_ref(kb, oc, vs)
    else:
        rep, counts, sums, mins, maxs = bucket_accumulate_buckets(
            kb, oc, vs, interpret=(impl == "pallas_interpret"))
    return HashGroupbyPlan(rep=rep, row=row.reshape(B, C), counts=counts,
                           sums=sums, mins=mins, maxs=maxs,
                           dropped=dropped)


def default_hash_groupby_sizes(capacity: int,
                               num_buckets: int | None = None):
    """(num_buckets, bucket_capacity) heuristics.

    Small tables (capacity <= ``bucketing.EXACT_SLAB_CAP``) get
    full-capacity slabs: every key distribution — including all-equal
    keys — aggregates with zero overflow, so the auto-sized hash backend
    is exact wherever the sort backend is.  Larger tables get ~16 rows
    per bucket on average (``bucketing.default_bucket_count``) with 4x
    headroom — an assumption of ~uniform key spread; with *concrete*
    (non-traced) keys the engine upgrades this to the distribution-proof
    two-pass ``bucketing.plan_bucket_sizes`` planner, and skewed traced
    workloads should pass explicit deeper, fewer buckets (the capacities
    are worst-case *per bucket*).  Any bucket count is sort-free: past
    ``bucketing.MAX_RADIX_BUCKETS`` the slab grouping switches from the
    single-pass one-hot ranking to the multi-pass ``kernels/radix_sort``
    rank."""
    if capacity <= EXACT_SLAB_CAP:
        return num_buckets or 8, max(8, capacity)
    if num_buckets is None:
        num_buckets = default_bucket_count(capacity)
    return num_buckets, max(8, -(-capacity // num_buckets) * 4)
