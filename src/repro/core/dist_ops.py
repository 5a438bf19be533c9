"""Distributed HPTMT table operators (paper §2.1.2, Tables 4–5).

Every distributed operator is *communication ∘ local operator*, exactly the
paper's recipe:

=================  =======================================================
distributed op     implementation (paper Table 5)
=================  =======================================================
shuffle            hash partition (Pallas radix kernel) + ``all_to_all``
join               shuffle both sides + local join; the local backend is
                   pluggable via ``local_impl`` — ``"sortmerge"`` (binary
                   search over sorted keys, default) or ``"hash"``
                   (bucketed Pallas build+probe, kernels/hash_join) —
                   so the distributed join runs hash-local end to end
broadcast join     ``all_gather`` small side + local join   (beyond-paper)
groupby            shuffle + local groupby-aggregate; the local backend is
                   pluggable via ``local_impl`` — ``"sort"`` (default) or
                   ``"hash"`` (bucketed Pallas hash-accumulate,
                   kernels/hash_groupby)
unique             shuffle + local drop_duplicates (under ``"hash"`` a
                   key-only hash groupby — same pluggable backend)
sort (OrderBy)     sample-sort: local sort + splitter ``all_gather`` +
                   range partition + ``all_to_all`` + local sort; the
                   local sorts are pluggable via ``local_impl`` —
                   ``"xla"`` (``lax.sort``, default) or ``"radix"``
                   (multi-pass LSD rank, kernels/radix_sort) — so the
                   distributed sort runs sort-primitive-free end to end
difference/        shuffle both sides + local set op; the local semi-join
intersect/isin     backend is pluggable via ``local_impl`` —
                   ``"sortmerge"`` (default) or ``"hash"`` (bucketed
                   membership probe, kernels/hash_semi) — so the
                   distributed set ops run hash-local end to end
repartition        global-rank range partition + ``all_to_all``
                   (straggler/skew mitigation)
=================  =======================================================

All functions here run **inside** ``jax.shard_map`` over the context's row
axes — the BSP model: every worker executes this same trace; the
collectives are the only synchronization points.  Use
:class:`DistributedPipeline` to wrap a whole pipeline in one shard_map
(one XLA program = one BSP superstep chain).

Static-shape contract: a shuffle can route at most ``slots_per_dest`` rows
from one sender to one receiver and materialize at most ``out_capacity``
rows per receiver.  Overflowing rows are dropped and *counted* (returned as
a metric) — tests and callers size capacities so overflow is zero;
production configs use ``overcommit`` headroom (default 2x).

Chunked-execution contract: tables larger than device memory run through
``core/morsel.py``, which streams fixed-capacity host-side chunks
through :func:`distribute_table` and loops them over these same
operators — join with a device-resident (or re-streamed) build side,
groupby as partial aggregates folded through
``local_ops.merge_partial_aggregates``, sort as per-chunk sample-sort
runs k-way-merged on the host.  Each chunk re-enters one cached
:class:`DistributedPipeline` program (same static shapes every morsel),
and the per-chunk overflow counters aggregate into one across-chunks
total, so the counted-overflow contract survives chunking unchanged.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from . import local_ops as L
from .. import trace
from .context import HptmtContext, shard_map
from .kernel_backend import table_kernel_impl
from .partition import hash_columns, partition_ids
from .table import Table, narrow_column as _narrow_column
from ..kernels import bucketing as _bucketing
from ..kernels.hash_partition import radix_histogram_ranks
from ..kernels.radix_sort import radix_permutation, stable_partition_perm

# --------------------------------------------------------------------------
# global <-> local adapters
# --------------------------------------------------------------------------


def distribute_table(ctx: HptmtContext, data: Mapping[str, np.ndarray],
                     capacity_per_shard: int | None = None) -> Table:
    """Host-side: build a *global* row-sharded Table from numpy columns.

    Rows are block-distributed over the row axes (the paper's row
    decomposition).  The global table's ``nvalid`` is a ``(world,)`` vector
    of per-shard counts.

    Columns follow the engine dtype contract (``core/table.py``):
    floats narrow to float32; integer values outside the int32 range
    *raise* instead of truncating (aliased key bits would fabricate join
    matches).  ``capacity_per_shard=None`` means rows-per-shard; an
    explicit non-positive capacity is an error, never silently coerced.
    """
    world = ctx.world_size
    arrays = {k: np.asarray(v) for k, v in data.items()}
    n = len(next(iter(arrays.values())))
    with trace.span("table/distribute", rows=n, world=world):
        per = math.ceil(n / world) if n else 1
        if capacity_per_shard is None:
            cap = per
        else:
            if capacity_per_shard <= 0:
                raise ValueError(
                    f"capacity_per_shard must be positive, got "
                    f"{capacity_per_shard} (pass None for rows-per-shard)")
            cap = capacity_per_shard
        if cap < per:
            raise ValueError(f"capacity_per_shard {cap} < rows/shard {per}")
        cols, nvalid = {}, np.zeros((world,), np.int32)
        for s in range(world):
            lo, hi = min(s * per, n), min((s + 1) * per, n)
            nvalid[s] = hi - lo
        for k, v in arrays.items():
            v = _narrow_column(k, v)
            buf = np.zeros((world, cap), v.dtype)
            for s in range(world):
                lo, hi = min(s * per, n), min((s + 1) * per, n)
                buf[s, : hi - lo] = v[lo:hi]
            cols[k] = jax.device_put(
                buf.reshape(world * cap),
                NamedSharding(ctx.mesh, ctx.rows_spec))
        nvalid = jax.device_put(jnp.asarray(nvalid),
                                NamedSharding(ctx.mesh, ctx.rows_spec))
        return Table(columns=cols, nvalid=nvalid)


def collect_table(ctx: HptmtContext, table: Table) -> dict[str, np.ndarray]:
    """Host-side: gather a global row-sharded Table back to numpy (valid
    rows only, shard order preserved).  The ``table/device_wait`` span
    waits for the program that makes ``table``, so what is left of
    ``table/collect`` is the copy and the host-side concatenation."""
    world = ctx.world_size
    with trace.span("table/collect"):
        with trace.span("table/device_wait"):
            jax.block_until_ready(table)
        nvalid = np.asarray(table.nvalid).reshape(world)
        out = {}
        for k, v in table.columns.items():
            v = np.asarray(v).reshape(world, -1)
            out[k] = np.concatenate([v[s, : nvalid[s]]
                                     for s in range(world)])
    return out


def _to_local(table: Table) -> Table:
    """Inside shard_map: nvalid arrives as shape (1,), squeeze to scalar."""
    return Table(columns=dict(table.columns),
                 nvalid=table.nvalid.reshape(()))


def _to_global(table: Table) -> Table:
    return Table(columns=dict(table.columns),
                 nvalid=table.nvalid.reshape((1,)))


# --------------------------------------------------------------------------
# The shuffle — HPTMT's Table communication operator (paper Table 4)
# --------------------------------------------------------------------------


@jax.named_scope("shuffle")
def shuffle_by_pid(ctx: HptmtContext, table: Table, pid: jnp.ndarray,
                   slots_per_dest: int, out_capacity: int):
    """Route each valid row to shard ``pid[row]`` via one ``all_to_all``.

    Returns ``(table, dropped)`` where ``dropped`` counts rows lost to the
    static ``slots_per_dest``/``out_capacity`` bounds (0 when sized right).
    """
    world = ctx.world_size
    valid = table.valid_mask
    names = table.names
    # trash partition `world` for padding rows
    pid = jnp.where(valid, pid, world)
    hist, ranks = radix_histogram_ranks(pid, world + 1,
                                        impl=table_kernel_impl())
    ok = valid & (ranks < slots_per_dest) & (pid < world)
    flat = jnp.where(ok, pid * slots_per_dest + ranks,
                     world * slots_per_dest)
    nslots = world * slots_per_dest

    # send side: every column (bitcast to an int32 plane) plus the
    # occupancy plane land in the (ncols+1, nslots) send slabs via ONE
    # stacked scatter — not one scatter per column.
    planes = [_bucketing.pack_i32(table.columns[n]) for n in names] \
        + [ok.astype(jnp.int32)]
    stacked = jnp.stack(planes)                     # (ncols+1, cap)
    send = (jnp.zeros((len(planes), nslots + 1), jnp.int32)
            .at[:, flat].set(stacked)[:, :nslots]
            .reshape(len(planes), world, slots_per_dest))
    # ONE all_to_all moves all columns together: block d of axis 1 goes
    # to shard d, so per (column, destination) the payload is exactly the
    # old per-column transfer.
    recv = jax.lax.all_to_all(send, ctx.row_axes, split_axis=1,
                              concat_axis=1, tiled=True) \
        .reshape(len(planes), nslots)
    recv_valid = recv[-1] > 0
    n_recv = jnp.sum(recv_valid, dtype=jnp.int32)
    # receive side: write the all_to_all output straight into the
    # out_capacity slabs with one stacked scatter — each valid row's slot
    # is its rank among valid rows in slot order (cumsum), which is
    # bit-identical to the stable-partition + gather compaction it
    # replaces, without materializing the intermediate table.
    pos = jnp.cumsum(recv_valid.astype(jnp.int32)) - 1
    okr = recv_valid & (pos < out_capacity)
    dest = jnp.where(okr, pos, out_capacity)
    out = (jnp.zeros((len(names), out_capacity + 1), jnp.int32)
           .at[:, dest].set(recv[:-1])[:, :out_capacity])
    cols = {n: _bucketing.unpack_i32(out[i], table.columns[n].dtype)
            for i, n in enumerate(names)}
    compacted = Table(columns=cols,
                      nvalid=jnp.minimum(n_recv, out_capacity))
    sent_dropped = jnp.sum(
        jnp.maximum(hist[:world] - slots_per_dest, 0), dtype=jnp.int32)
    recv_dropped = jnp.maximum(n_recv - out_capacity, 0)
    dropped = jax.lax.psum(sent_dropped, ctx.row_axes) + \
        jax.lax.psum(recv_dropped, ctx.row_axes)
    return compacted, dropped


def default_shuffle_sizes(ctx: HptmtContext, capacity: int,
                          overcommit: float = 2.0):
    world = ctx.world_size
    slots = max(1, math.ceil(capacity * overcommit / world))
    out_cap = max(capacity, math.ceil(capacity * overcommit))
    return slots, out_cap


def _pad8(load: float, headroom: float) -> int:
    """Observed load -> static capacity: headroom cushion, lane-aligned."""
    return max(8, -(-int(math.ceil(load * headroom)) // 8) * 8)


@trace.span("table/plan_join_sizes")
def plan_dist_join_sizes(left_keys: Sequence[np.ndarray],
                         right_keys: Sequence[np.ndarray], *, world: int,
                         how: str = "inner", headroom: float = 1.25,
                         local_impl: str | None = None,
                         num_buckets: int | None = None) -> dict:
    """Host-side whole-join capacity oracle for a shuffle-strategy
    :func:`dist_join`.

    Sizes every static capacity of the distributed join from the *actual*
    key distributions, once, before any device work: the shuffle slabs
    (per-destination slot bound and receive capacity per side), the join
    output capacity, and — under the hash local backend — the per-bucket
    build/probe slab depths.  Equal keys co-locate (partition id and
    bucket id are functions of the key value only), so per-destination and
    per-bucket loads are exact host-side regardless of how rows are
    block-distributed among senders: a destination receives at most the
    total count of its keys, whatever the sender split.  Every bound is
    the observed per-key/per-destination maximum times ``headroom``,
    rounded up to a multiple of 8 — the distributed join's overflow
    counter is zero by construction for these keys, with static shapes
    far below the blind ``overcommit`` heuristics.

    ``left_keys`` / ``right_keys`` are parallel sequences of *concrete*
    key columns (the same arrays later fed to :func:`distribute_table`);
    the per-key hash chain reuses the engine's own ``hash_columns`` /
    ``bucketing.bucket_ids``, so the plan prices exactly the routing the
    shuffle and the hash kernels will perform.

    Returns ``{"shuffle_sizes": {"left": (slots_per_dest, out_capacity),
    "right": ...}, "out_capacity": ..., "local_join_sizes": ...}`` —
    keyword-compatible with :func:`dist_join` (``local_join_sizes`` is
    ``None`` unless ``local_impl='hash'``).
    """
    lcols = [np.asarray(_narrow_column(f"k{i}", np.asarray(c)))
             for i, c in enumerate(left_keys)]
    rcols = [np.asarray(_narrow_column(f"k{i}", np.asarray(c)))
             for i, c in enumerate(right_keys)]
    nl, nr = len(lcols[0]), len(rcols[0])
    # partition ids with each side's own dtype (what shuffle hashes) ...
    pid = np.concatenate([
        np.asarray(hash_columns([jnp.asarray(c) for c in lcols])
                   % jnp.uint32(world)).astype(np.int64),
        np.asarray(hash_columns([jnp.asarray(c) for c in rcols])
                   % jnp.uint32(world)).astype(np.int64)])
    # ... but key identity in the promoted common dtype (what the local
    # join compares), mirroring the engine's key promotion rule.
    planes = []
    for lc, rc in zip(lcols, rcols):
        dt = np.promote_types(lc.dtype, rc.dtype)
        dt = np.float32 if np.issubdtype(dt, np.floating) else np.int32
        planes.append(np.asarray(_bucketing.key_bits(
            jnp.asarray(np.concatenate([lc.astype(dt), rc.astype(dt)])))))
    bits = np.stack(planes, axis=1)                       # (nl+nr, K)
    if bits.shape[1] == 1:
        # one key plane: the 1-D unique sorts int32 directly, where the
        # row-wise (axis=0) one sorts opaque records — minutes at 2^27
        uniq, first, inv = np.unique(bits[:, 0], return_index=True,
                                     return_inverse=True)
        uniq = uniq[:, None]
    else:
        uniq, first, inv = np.unique(bits, axis=0, return_index=True,
                                     return_inverse=True)
    inv = inv.reshape(-1)
    n_uniq = uniq.shape[0]
    cl = np.bincount(inv[:nl], minlength=n_uniq).astype(np.float64)
    cr = np.bincount(inv[nl:], minlength=n_uniq).astype(np.float64)
    upid = pid[first]

    def _side(counts):
        recv = np.bincount(upid, weights=counts, minlength=world)
        cap = _pad8(recv.max() if n_uniq else 0, headroom)
        return cap, cap        # slots_per_dest bound == receive capacity

    lsizes, rsizes = _side(cl), _side(cr)
    matches = cl * cr
    if how == "left":
        matches = matches + np.where(cr == 0, cl, 0)
    per_dest = np.bincount(upid, weights=matches, minlength=world)
    out_cap = _pad8(per_dest.max() if n_uniq else 0, headroom)

    local_sizes = None
    if local_impl == "hash":
        B = num_buckets or _bucketing.default_bucket_count(
            max(lsizes[1], rsizes[1]))
        ubid = np.asarray(_bucketing.bucket_ids(
            tuple(jnp.asarray(uniq[:, k]) for k in range(uniq.shape[1])),
            B)).astype(np.int64)
        db = upid * B + ubid
        local_sizes = dict(
            num_buckets=B,
            bucket_capacity=_pad8(
                np.bincount(db, weights=cr, minlength=world * B).max()
                if n_uniq else 0, headroom),
            probe_capacity=_pad8(
                np.bincount(db, weights=cl, minlength=world * B).max()
                if n_uniq else 0, headroom))
    return {"shuffle_sizes": {"left": lsizes, "right": rsizes},
            "out_capacity": out_cap, "local_join_sizes": local_sizes}


def shuffle(ctx: HptmtContext, table: Table, key_cols: Sequence[str],
            *, overcommit: float = 2.0,
            slots_per_dest: int | None = None,
            out_capacity: int | None = None):
    """Hash shuffle: co-locate equal keys on the same shard."""
    s, oc = default_shuffle_sizes(ctx, table.capacity, overcommit)
    pid = partition_ids(table, list(key_cols), ctx.world_size)
    return shuffle_by_pid(ctx, table, pid,
                          slots_per_dest or s, out_capacity or oc)


# --------------------------------------------------------------------------
# Distributed relational operators = shuffle + local op (paper Table 5)
# --------------------------------------------------------------------------


def dist_join(ctx: HptmtContext, left: Table, right: Table, *,
              left_on: Sequence[str], right_on: Sequence[str] | None = None,
              how: str = "inner", out_capacity: int | None = None,
              overcommit: float = 2.0, strategy: str = "shuffle",
              local_impl: str | None = None,
              local_join_sizes: Mapping[str, int] | None = None,
              shuffle_sizes: Mapping[str, tuple[int, int]] | None = None):
    """Distributed join (paper Fig. 4 operator).

    ``strategy='shuffle'``: hash-shuffle both sides on the key, local join
    (Cylon's algorithm).  ``strategy='broadcast'``: all_gather the (small)
    right side and join locally — no shuffle of the big side (beyond-paper
    optimization; pick when |right| << |left|).

    ``local_impl`` selects the local join backend ('sortmerge' | 'hash',
    default ``local_ops.JOIN_IMPL``); ``local_join_sizes`` forwards
    hash-backend static sizing (``num_buckets`` / ``bucket_capacity`` /
    ``probe_capacity``) — both backends return drop-in identical results,
    so the whole distributed join runs hash-local under one shard_map.
    ``shuffle_sizes`` overrides the blind ``overcommit`` shuffle heuristic
    with explicit per-side ``{"left"/"right": (slots_per_dest,
    out_capacity)}`` bounds — :func:`plan_dist_join_sizes` computes these
    (and ``out_capacity`` / ``local_join_sizes``) exactly from concrete
    keys host-side.
    """
    right_on = list(right_on) if right_on is not None else list(left_on)
    jkw = dict(local_join_sizes or {})
    if strategy == "broadcast":
        g = all_gather_table(ctx, right)
        out, jdrop = L.join(left, g, left_on=list(left_on),
                            right_on=right_on, how=how,
                            out_capacity=out_capacity or left.capacity,
                            impl=local_impl, return_overflow=True, **jkw)
        return out, jax.lax.psum(jdrop, ctx.row_axes)
    # hash both sides with the same key columns -> same pid function
    lp = partition_ids(left, list(left_on), ctx.world_size)
    rp_tbl = right.rename(dict(zip(right_on, left_on))) \
        if right_on != list(left_on) else right
    rp = partition_ids(rp_tbl, list(left_on), ctx.world_size)
    if shuffle_sizes is not None:
        ls, loc = shuffle_sizes["left"]
        rs, roc = shuffle_sizes["right"]
    else:
        ls, loc = default_shuffle_sizes(ctx, left.capacity, overcommit)
        rs, roc = default_shuffle_sizes(ctx, right.capacity, overcommit)
    lsh, ldrop = shuffle_by_pid(ctx, left, lp, ls, loc)
    rsh, rdrop = shuffle_by_pid(ctx, right, rp, rs, roc)
    # the local join's overflow (output capacity, hash bucket/probe slabs)
    # joins the shuffle drops in one "rows lost anywhere" counter
    out, jdrop = L.join(lsh, rsh, left_on=list(left_on), right_on=right_on,
                        how=how, out_capacity=out_capacity or loc,
                        impl=local_impl, return_overflow=True, **jkw)
    return out, ldrop + rdrop + jax.lax.psum(jdrop, ctx.row_axes)


def dist_groupby(ctx: HptmtContext, table: Table, by: Sequence[str],
                 aggs: Mapping[str, Sequence[str] | str],
                 overcommit: float = 2.0, local_impl: str | None = None,
                 groupby_sizes: Mapping[str, int] | None = None):
    """Distributed GroupBy+Aggregate: shuffle on keys + local groupby.

    ``local_impl`` selects the local aggregation backend ('sort' | 'hash',
    default ``local_ops.GROUPBY_IMPL``); ``groupby_sizes`` forwards
    hash-backend static sizing (``num_buckets`` / ``bucket_capacity``).
    Both backends return drop-in identical results, so the whole
    distributed groupby runs hash-local under one shard_map; the hash
    path's bucket-overflow drops join the shuffle drops in the returned
    counter.

    Note: mean aggregations are computed from shuffled raw rows, so they are
    exact (not an average-of-averages)."""
    sh, dropped = shuffle(ctx, table, by, overcommit=overcommit)
    out, gdrop = L.groupby_aggregate(sh, list(by), aggs, impl=local_impl,
                                     return_overflow=True,
                                     **dict(groupby_sizes or {}))
    return out, dropped + jax.lax.psum(gdrop, ctx.row_axes)


def dist_unique(ctx: HptmtContext, table: Table, subset: Sequence[str],
                overcommit: float = 2.0, local_impl: str | None = None,
                groupby_sizes: Mapping[str, int] | None = None):
    """Paper §4.3: 'the distributed unique operator ensures no duplicate
    records are used for deep learning across all processes'.

    Shuffle on the key + local drop_duplicates — which under
    ``local_impl='hash'`` is a *key-only hash groupby* on the
    ``kernels/hash_groupby`` plan, sharing the pluggable aggregation
    backend (``groupby_sizes`` forwards its static sizing)."""
    sh, dropped = shuffle(ctx, table, subset, overcommit=overcommit)
    out, gdrop = L.drop_duplicates(sh, list(subset), impl=local_impl,
                                   return_overflow=True,
                                   **dict(groupby_sizes or {}))
    return out, dropped + jax.lax.psum(gdrop, ctx.row_axes)


def dist_difference(ctx: HptmtContext, a: Table, b: Table,
                    on: Sequence[str], overcommit: float = 2.0,
                    local_impl: str | None = None,
                    semi_sizes: Mapping[str, int] | None = None):
    """Distributed Difference: shuffle both sides on the key + local
    difference.  Equal keys co-locate (the partition hash is over key
    *values*), so per-shard membership is global membership.

    ``local_impl`` selects the local semi-join backend ('sortmerge' |
    'hash', default ``local_ops.SEMI_IMPL``); ``semi_sizes``
    forwards hash-backend static sizing (``num_buckets`` /
    ``bucket_capacity`` / ``probe_capacity``).  The hash path's slab
    overflow drops join the shuffle drops in the returned counter."""
    ash, d1 = shuffle(ctx, a, on, overcommit=overcommit)
    bsh, d2 = shuffle(ctx, b, on, overcommit=overcommit)
    out, over = L.difference(ash, bsh, on=list(on), impl=local_impl,
                             return_overflow=True,
                             **dict(semi_sizes or {}))
    return out, d1 + d2 + jax.lax.psum(over, ctx.row_axes)


def dist_intersect(ctx: HptmtContext, a: Table, b: Table,
                   on: Sequence[str], overcommit: float = 2.0,
                   local_impl: str | None = None,
                   dedup_impl: str | None = None,
                   semi_sizes: Mapping[str, int] | None = None):
    """Distributed Intersect: shuffle both sides on the key + local
    intersect.  ``local_impl`` selects the local semi-join backend
    ('sortmerge' | 'hash'), ``dedup_impl`` the local dedup backend
    ('sort' | 'hash'); ``semi_sizes`` forwards hash-backend static
    sizing.  Slab-overflow drops join the shuffle drops in the counter."""
    ash, d1 = shuffle(ctx, a, on, overcommit=overcommit)
    bsh, d2 = shuffle(ctx, b, on, overcommit=overcommit)
    out, over = L.intersect(ash, bsh, on=list(on), impl=local_impl,
                            dedup_impl=dedup_impl, return_overflow=True,
                            **dict(semi_sizes or {}))
    return out, d1 + d2 + jax.lax.psum(over, ctx.row_axes)


def dist_isin(ctx: HptmtContext, table: Table, col: str, values: Table,
              values_col: str, overcommit: float = 2.0,
              local_impl: str | None = None,
              semi_sizes: Mapping[str, int] | None = None):
    """Distributed membership filter: rows of ``table`` whose ``col`` is
    present among ``values[values_col]`` anywhere in the world.

    Both sides are shuffled on their key column — ``partition_ids``
    hashes column *values* (name-independent), so a table row and its
    matching value land on the same shard — then the local :func:`isin`
    mask selects.  ``local_impl`` / ``semi_sizes`` as in
    :func:`dist_difference`.  Returns ``(filtered_table, dropped)``."""
    tsh, d1 = shuffle(ctx, table, [col], overcommit=overcommit)
    vsh, d2 = shuffle(ctx, values, [values_col], overcommit=overcommit)
    mask, over = L.isin(tsh, col, vsh, values_col, impl=local_impl,
                        return_overflow=True, **dict(semi_sizes or {}))
    return L.select(tsh, mask), d1 + d2 + jax.lax.psum(over, ctx.row_axes)


# --------------------------------------------------------------------------
# Distributed sort (sample sort) — paper Table 5 "Sorting tables"
# --------------------------------------------------------------------------


def dist_sort(ctx: HptmtContext, table: Table, by: Sequence[str],
              ascending: bool = True, n_samples: int = 32,
              overcommit: float = 2.0, local_impl: str | None = None):
    """Sample-sort: local sort, splitter all_gather, range partition,
    all_to_all, local sort.  Globally sorted = shard order + local order.

    ``local_impl`` selects the local sort backend ('xla' | 'radix',
    default ``local_ops.SORT_IMPL``) for the pre-shuffle and final
    local sorts; under 'radix' the gathered splitter candidates are also
    ranked by the radix engine, so the whole distributed sort is
    sort-primitive-free.  Both backends return drop-in bit-identical
    results (same splitters, same routing, same shard-local order)."""
    by = list(by)
    world = ctx.world_size
    ts = L.sort_values(table, by, ascending=ascending, impl=local_impl)
    cap = ts.capacity
    s = min(n_samples, cap)
    # evenly sample valid rows (clamp handles nvalid < s)
    pos = (jnp.arange(s) * jnp.maximum(ts.nvalid, 1)) // s
    pos = jnp.clip(pos, 0, cap - 1)
    valid_s = jnp.arange(s) < jnp.minimum(ts.nvalid, s)
    sample_keys = []
    for k in by:
        col = L._sort_key(ts.columns[k], ascending)[pos]
        col = jnp.where(valid_s, col, L._sentinel_max(col))
        sample_keys.append(col)
    gathered = [jax.lax.all_gather(c, ctx.row_axes, tiled=True)
                for c in sample_keys]                     # (world*s,)
    if local_impl == "radix":
        sperm = radix_permutation(tuple(gathered),
                                  jnp.zeros((world * s,), bool),
                                  impl=table_kernel_impl())
        sorted_keys = tuple(c[sperm] for c in gathered)
    else:
        iota = jnp.arange(world * s, dtype=jnp.int32)
        sorted_keys = jax.lax.sort((*gathered, iota),
                                   num_keys=len(gathered),
                                   is_stable=True)[:-1]
    # world-1 splitters at quantile positions
    spl_pos = (jnp.arange(1, world) * (world * s)) // world
    splitters = tuple(op[spl_pos] for op in sorted_keys)
    row_keys = tuple(
        jnp.where(ts.valid_mask,
                  L._sort_key(ts.columns[k], ascending),
                  L._sentinel_max(ts.columns[k]))
        for k in by)
    pid = _rank_against_splitters(splitters, row_keys)
    slots, out_cap = default_shuffle_sizes(ctx, cap, overcommit)
    sh, dropped = shuffle_by_pid(ctx, ts, pid, slots, out_cap)
    out = L.sort_values(sh, by, ascending=ascending, impl=local_impl)
    return out, dropped


def _rank_against_splitters(splitters: tuple, row_keys: tuple) -> jnp.ndarray:
    """pid = number of splitters <= key (vectorized lex compare)."""
    nspl = splitters[0].shape[0]
    cap = row_keys[0].shape[0]
    pid = jnp.zeros((cap,), jnp.int32)
    for i in range(nspl):
        spl = tuple(s[i] for s in splitters)
        spl_b = tuple(jnp.broadcast_to(s, (cap,)) for s in spl)
        le = ~L._tuple_less(row_keys, spl_b)   # splitter <= key
        pid = pid + le.astype(jnp.int32)
    return pid


# --------------------------------------------------------------------------
# Repartition / rebalance — skew (straggler) mitigation
# --------------------------------------------------------------------------


def dist_repartition(ctx: HptmtContext, table: Table,
                     overcommit: float = 1.5):
    """Exact load rebalance: row global-rank r goes to shard r // ceil(N/W).

    BSP stragglers are dominated by data skew after shuffles (DESIGN.md §4);
    this restores near-perfect balance with one all_to_all."""
    world = ctx.world_size
    nv = table.nvalid
    counts = jax.lax.all_gather(nv, ctx.row_axes)          # (world,)
    my = ctx.axis_index()
    prefix = jnp.sum(jnp.where(jnp.arange(world) < my, counts, 0))
    total = jnp.sum(counts)
    target = jnp.maximum((total + world - 1) // world, 1)
    r = prefix + jnp.arange(table.capacity, dtype=jnp.int32)
    pid = jnp.minimum(r // target, world - 1).astype(jnp.int32)
    # one sender contributes at most min(capacity, target) rows to a single
    # destination, and each destination receives at most target <= capacity
    # rows in total -> capacity bounds are exact (never drops).
    return shuffle_by_pid(ctx, table, pid,
                          slots_per_dest=table.capacity,
                          out_capacity=table.capacity)


# --------------------------------------------------------------------------
# Distributed column scaling (sklearn StandardScaler with *global* stats)
# --------------------------------------------------------------------------


def dist_standard_scale(ctx: HptmtContext, table: Table,
                        cols: Sequence[str],
                        local_impl: str | None = None) -> Table:
    """(x - mean) / std per column with mean/std over ALL shards' valid
    rows (exact psum moments) — the distributed equivalent of the paper's
    sklearn preprocessing step.  Per-shard scaling would silently change
    results with parallelism; this keeps them parallelism-invariant.

    Two-pass like the local op: global means first (psum of sums), then
    the psum'd variance of deviations about them — exact even when
    ``|mean| >> std`` (the one-pass ``E[x^2] - m^2`` form cancels in
    float32).  ``local_impl`` selects how each shard computes its
    per-column moments (``L.column_moments``): inline masked reductions
    (None, the fast path) or the pluggable 'sort'/'hash' aggregation
    backend — so a whole preprocessing pipeline can run one backend end
    to end."""
    out = dict(table.columns)
    s1, _, n = L.column_moments(table, cols, impl=local_impl)
    n = jnp.maximum(jax.lax.psum(n, ctx.row_axes), 1.0)
    means = {k: jax.lax.psum(s1[k], ctx.row_axes) / n for k in cols}
    _, sd2, _ = L.column_moments(table, cols, impl=local_impl,
                                 center=means)
    for k in cols:
        x = out[k].astype(jnp.float32)
        v = jax.lax.psum(sd2[k], ctx.row_axes) / n
        out[k] = (x - means[k]) / jnp.sqrt(v + 1e-12)
    return Table(columns=out, nvalid=table.nvalid)


# --------------------------------------------------------------------------
# Broadcast / gather of tables (paper Table 4: Broadcast for tables)
# --------------------------------------------------------------------------


def all_gather_table(ctx: HptmtContext, table: Table) -> Table:
    """Replicate a (small) table on every shard: capacity*world rows."""
    world = ctx.world_size
    cap = table.capacity
    valid = table.valid_mask
    cols = {}
    for k, v in table.columns.items():
        g = jax.lax.all_gather(v, ctx.row_axes, tiled=True)
        cols[k] = g
    gvalid = jax.lax.all_gather(valid, ctx.row_axes, tiled=True)
    perm = stable_partition_perm(gvalid, impl=table_kernel_impl())
    out = Table(columns={k: v[perm] for k, v in cols.items()},
                nvalid=jnp.sum(gvalid, dtype=jnp.int32))
    return out


# --------------------------------------------------------------------------
# Whole-pipeline runner: one shard_map = one BSP program
# --------------------------------------------------------------------------


@dataclasses.dataclass
class DistributedPipeline:
    """Wrap a table pipeline ``fn(ctx, *local_tables, **kw) -> pytree`` into
    a single jitted shard_map program (the paper's single-source,
    single-runtime execution: data engineering composed as one SPMD
    program).

    Output pytree leaves: ``Table`` -> row-sharded global table; scalar
    leaves (e.g. the ``dropped`` counters) are auto-lifted to a leading
    per-shard axis of size 1 and come back stacked ``(world,)``; other
    arrays must already carry a leading per-shard axis.

    The jitted program is built once per instance and reused across calls
    (kwarg-free calls only — kwargs close over the trace, so a call with
    kwargs rebuilds).  Chunk loops (``core/morsel.py``) rely on this:
    every morsel re-enters the *same* compiled executable, so the
    per-chunk cost is execution, not tracing.

    ``donate_argnums`` donates the corresponding *table* arguments'
    buffers to the call (``jax.jit`` donation): chunk loops donate the
    fold accumulator they rebind each iteration — append/merge keeps its
    static capacity, so XLA writes the fold in place instead of
    allocating a fresh accumulator per chunk.  Never donate a table the
    caller reads again (e.g. the resident build side of a probe loop),
    and don't donate tables whose buffers match no output shape (e.g.
    per-morsel chunks vs. overcommitted shuffle slabs) — that donation
    is a warning-generating no-op.
    """

    ctx: HptmtContext
    fn: Callable
    donate_argnums: tuple[int, ...] = ()
    _jitted: Callable | None = dataclasses.field(
        default=None, init=False, repr=False, compare=False)

    def _build(self, **kwargs):
        ctx = self.ctx
        spec = ctx.rows_spec

        def lift(x):
            if isinstance(x, Table):
                return _to_global(x)
            x = jnp.asarray(x)
            return x[None] if x.ndim == 0 else x

        def wrapped(*ts):
            local = [_to_local(t) for t in ts]
            out = self.fn(ctx, *local, **kwargs)
            return jax.tree_util.tree_map(
                lift, out, is_leaf=lambda x: isinstance(x, Table))

        # the program is named after `fn` (jit_<fn>), so each pipeline is
        # told apart in a trace's modules
        wrapped.__name__ = wrapped.__qualname__ = getattr(
            self.fn, "__name__", "pipeline")
        # `spec` is a valid pytree *prefix* for the whole in/out trees
        f = shard_map(wrapped, mesh=ctx.mesh, in_specs=spec,
                      out_specs=spec)
        return jax.jit(f, donate_argnums=tuple(self.donate_argnums))

    def __call__(self, *tables: Table, **kwargs):
        if kwargs:
            return self._build(**kwargs)(*tables)
        if self._jitted is None:
            self._jitted = self._build()
        return self._jitted(*tables)
