"""Out-of-core, morsel-driven execution over the distributed operators.

Without chunking, a table has to fit device memory in one piece.  This module
removes that ceiling the way the paper's predecessor systems do (Cylon's
streaming shuffle, linear-dag's blockwise operators over HDF5): the
*operator contract* — communication ∘ local operator with counted
overflow — is the unit of scalability, not the materialized table.  Host
memory (or a memory-mapped file: ``np.memmap`` columns work unchanged)
holds the full relation; the device only ever holds one fixed-capacity
**morsel** per side plus the operator's resident state.

Execution model
---------------
:class:`ChunkedTable` is the host-side source: numpy columns cut into
fixed-``chunk_rows`` morsels, each streamed through
:func:`~repro.core.dist_ops.distribute_table` (same dtype contract:
floats narrow to float32, out-of-int32-range integers raise).  Each
chunked operator builds its per-chunk step as a *kwarg-free*
:class:`~repro.core.dist_ops.DistributedPipeline`, so the whole chunk
loop re-enters one compiled XLA program — the per-morsel cost is
execution + host↔device copies, never re-tracing:

``chunked_dist_join``
    The **build** side is hash-shuffled once and kept device-resident
    per shard (accumulated through :func:`local_ops.append_rows` when the
    build side itself arrives in chunks); the **probe** side streams:
    shuffle each probe morsel on the key, local-join it against the
    resident build shard, collect the output morsel to the host.  Equal
    keys co-locate under the same partition hash for every chunk, so
    per-chunk joins compose to the exact global join.  With
    ``build='restream'`` neither side is resident: each probe morsel is
    shuffled once, then joined against every (re-shuffled) build morsel
    — inner joins only, since an inner join distributes over build
    partition while a left join does not.

``chunked_dist_groupby``
    Per morsel: shuffle on the keys + local *partial* aggregation
    (``mean`` decomposes into sum+count, see
    :func:`local_ops.partial_agg_columns`), then fold into a
    device-resident accumulator table with
    :func:`local_ops.merge_partial_aggregates` — the merge re-runs the
    pluggable aggregation backend (the existing hash-groupby slabs)
    over accumulator + partial, so it stays canonical (key-sorted) and
    associative.  A final device-side pass maps partials to the
    requested aggregates (``mean = sum / max(count, 1)``) — identical
    to the monolithic formula, so results are bit-identical whenever
    float addition is exact (integer-valued data), and agree to
    addition-order rounding otherwise.

``chunked_dist_sort``
    Per morsel: a full :func:`~repro.core.dist_ops.dist_sort`
    (sample-sort) producing one globally-sorted *run* on the host; runs
    then fold through a stable vectorized k-way merge (adjacent pairwise
    merges, earlier chunks win ties).  Because the monolithic sample
    sort's equal keys also tie in original row order, the chunked result
    is bit-identical to the monolithic one, ties included.

Overflow contract
-----------------
Every stage keeps the engine's "dropped, never silently lost" rule: the
per-chunk shuffle, local-operator, append, and merge counters are
psum'd on device and **summed across chunks** on the host — each
operator returns ``(result, total_dropped)`` and callers size
capacities so the total stays zero.
"""
from __future__ import annotations

import math
from typing import Callable, Mapping, Sequence

import jax
import numpy as np

from . import dist_ops as D
from . import local_ops as L
from .context import HptmtContext
from .table import narrow_column

__all__ = [
    "ChunkedTable",
    "chunked_dist_join",
    "chunked_dist_groupby",
    "chunked_dist_sort",
    "merge_sorted_runs",
]


class ChunkedTable:
    """Host-side chunked table: numpy columns streamed as fixed-size
    morsels.

    ``data`` maps column name -> 1-D numpy array (all equal length; a
    ``np.memmap`` works — chunks are slices, nothing is copied until a
    chunk is distributed).  ``chunk_rows`` is the morsel size: every
    chunk has exactly ``chunk_rows`` rows except the last (and a
    zero-row table yields exactly one empty chunk — the terminal-morsel
    shape the operators must handle).
    """

    def __init__(self, data: Mapping[str, np.ndarray], chunk_rows: int):
        if chunk_rows <= 0:
            raise ValueError(f"chunk_rows must be positive, got "
                             f"{chunk_rows}")
        self.columns = {k: np.asarray(v) for k, v in data.items()}
        if not self.columns:
            raise ValueError("ChunkedTable needs at least one column")
        lengths = {k: len(v) for k, v in self.columns.items()}
        if len(set(lengths.values())) != 1:
            raise ValueError(f"columns must have equal length: {lengths}")
        self.nrows = next(iter(lengths.values()))
        self.chunk_rows = int(chunk_rows)

    @property
    def names(self) -> tuple[str, ...]:
        return tuple(self.columns.keys())

    @property
    def num_chunks(self) -> int:
        return max(1, math.ceil(self.nrows / self.chunk_rows))

    def chunk(self, i: int) -> dict[str, np.ndarray]:
        lo = i * self.chunk_rows
        hi = min(lo + self.chunk_rows, self.nrows)
        return {k: v[lo:hi] for k, v in self.columns.items()}

    def chunks(self):
        for i in range(self.num_chunks):
            yield self.chunk(i)

    def capacity_per_shard(self, world: int) -> int:
        """The fixed per-shard device capacity one morsel needs — the
        same for every chunk (the last, smaller chunk reuses it so the
        jitted step sees one shape)."""
        return max(1, math.ceil(self.chunk_rows / world))

    def distribute(self, ctx: HptmtContext,
                   capacity_per_shard: int | None = None):
        """Stream the chunks through ``distribute_table``: yields one
        global row-sharded Table per morsel, all with the same static
        capacity."""
        cap = capacity_per_shard or self.capacity_per_shard(ctx.world_size)
        for chunk in self.chunks():
            yield D.distribute_table(ctx, chunk, capacity_per_shard=cap)


def _as_chunked(data, default_chunk_rows: int | None = None):
    if isinstance(data, ChunkedTable):
        return data
    n = len(next(iter(data.values())))
    return ChunkedTable(data, default_chunk_rows or max(n, 1))


def _dropped(d) -> int:
    """Host-side value of a pipeline's psum'd (replicated) drop counter."""
    a = np.asarray(d)
    return int(a.max()) if a.size else 0


def _emit(parts: list, sink, out: dict):
    if sink is not None:
        sink(out)
    else:
        parts.append(out)


def _concat_parts(parts: list[dict] | None):
    if parts is None:
        return None
    cols: dict[str, list] = {}
    for p in parts:
        for k, v in p.items():
            cols.setdefault(k, []).append(v)
    return {k: np.concatenate(v) for k, v in cols.items()}


# --------------------------------------------------------------------------
# Chunked distributed join
# --------------------------------------------------------------------------


def chunked_dist_join(ctx: HptmtContext, left, right, *,
                      left_on: Sequence[str],
                      right_on: Sequence[str] | None = None,
                      how: str = "inner",
                      build: str = "resident",
                      out_capacity_per_shard: int | None = None,
                      build_capacity_per_shard: int | None = None,
                      overcommit: float = 2.0,
                      local_impl: str | None = None,
                      local_join_sizes: Mapping[str, int] | None = None,
                      sink: Callable[[dict], None] | None = None):
    """Morsel-driven distributed join: stream the probe (left) side in
    chunks against a build (right) side, past-device-memory sized.

    ``left`` / ``right`` are :class:`ChunkedTable` or plain column
    mappings.  ``build='resident'`` (default): the right side is
    shuffled once into a device-resident per-shard build table of
    capacity ``build_capacity_per_shard`` (default: rows-per-shard x
    ``overcommit`` for partition-imbalance headroom) — supports
    ``how='inner'|'left'``.  ``build='restream'``: the right side is
    re-streamed per probe morsel (block-nested loop; inner joins only —
    a left join does not distribute over build partition).

    ``out_capacity_per_shard`` bounds one morsel's join output per shard
    (default: the shuffled probe-morsel capacity — size it up for
    multiplicative keys).  Returns ``(columns, dropped)`` with
    ``columns`` the host-side numpy result (chunk-major, shard-major
    within a chunk — chunk boundaries permute row order exactly like
    shard boundaries already do; content is bit-identical to the
    monolithic ``dist_join``) and ``dropped`` the overflow total across
    every chunk's shuffle + local join (+ build append).  When ``sink``
    is given each output morsel is handed to it instead and ``columns``
    is None.
    """
    if how not in ("inner", "left"):
        raise ValueError("how must be 'inner' or 'left'")
    if build not in ("resident", "restream"):
        raise ValueError("build must be 'resident' or 'restream'")
    if build == "restream" and how != "inner":
        raise ValueError("build='restream' supports inner joins only: a "
                         "left join does not distribute over build "
                         "partition (unmatched rows would duplicate "
                         "per build morsel)")
    left_on = list(left_on)
    right_on = list(right_on) if right_on is not None else list(left_on)
    left = _as_chunked(left)
    right = _as_chunked(right)
    world = ctx.world_size
    pcap = left.capacity_per_shard(world)
    _, ploc = D.default_shuffle_sizes(ctx, pcap, overcommit)
    out_cap = out_capacity_per_shard or ploc
    sizes = dict(local_join_sizes or {})
    dropped = 0
    parts: list[dict] | None = None if sink is not None else []

    if build == "resident":
        bcap = build_capacity_per_shard or max(
            1, math.ceil(right.nrows / world * overcommit))
        acc = D.distribute_table(
            ctx, {k: narrow_column(k, v[:0]) for k, v in
                  right.columns.items()},
            capacity_per_shard=bcap)

        def build_step(c, a, chunk):
            sh, d = D.shuffle(c, chunk, right_on, overcommit=overcommit)
            a2, ad = L.append_rows(a, sh)
            return a2, d + jax.lax.psum(ad, c.row_axes)

        # donate the accumulator (rebound each iteration) so the append
        # folds in place — the per-chunk morsel's buffers match no output
        # shape (the shuffle slab is overcommitted), so donating it would
        # be a no-op
        build_pipe = D.DistributedPipeline(ctx, build_step,
                                           donate_argnums=(0,))
        for g in right.distribute(ctx):
            acc, d = build_pipe(acc, g)
            dropped += _dropped(d)

        def probe_step(c, b, chunk):
            sh, d = D.shuffle(c, chunk, left_on, overcommit=overcommit)
            out, jd = L.join(sh, b, left_on=left_on, right_on=right_on,
                             how=how, out_capacity=out_cap,
                             impl=local_impl, return_overflow=True,
                             **sizes)
            return out, d + jax.lax.psum(jd, c.row_axes)

        # no donation: the resident build side (arg 0) is read again on
        # every subsequent chunk, and the probe morsel's buffers match no
        # output shape (join output is sized out_cap, not the morsel cap)
        probe_pipe = D.DistributedPipeline(ctx, probe_step)
        for g in left.distribute(ctx):
            out, d = probe_pipe(acc, g)
            dropped += _dropped(d)
            _emit(parts, sink, D.collect_table(ctx, out))
        return _concat_parts(parts), dropped

    # restream: block-nested loop — shuffle each probe morsel once, join
    # it against every (re-shuffled) build morsel; inner joins are
    # additive over build partition, so the emitted morsels compose.
    shuffle_probe = D.DistributedPipeline(
        ctx, lambda c, t: D.shuffle(c, t, left_on, overcommit=overcommit))
    shuffle_build = D.DistributedPipeline(
        ctx, lambda c, t: D.shuffle(c, t, right_on, overcommit=overcommit))

    def join_step(c, l, r):
        out, jd = L.join(l, r, left_on=left_on, right_on=right_on,
                         how="inner", out_capacity=out_cap,
                         impl=local_impl, return_overflow=True, **sizes)
        return out, jax.lax.psum(jd, c.row_axes)

    # no donation: the shuffled probe morsel (arg 0) is re-joined against
    # every build morsel, and the build morsel's buffers only match the
    # join output's shapes by coincidence of chunk sizing
    join_pipe = D.DistributedPipeline(ctx, join_step)
    for pg in left.distribute(ctx):
        psh, d = shuffle_probe(pg)
        dropped += _dropped(d)
        for bg in right.distribute(ctx):
            bsh, d = shuffle_build(bg)
            dropped += _dropped(d)
            out, d = join_pipe(psh, bsh)
            dropped += _dropped(d)
            _emit(parts, sink, D.collect_table(ctx, out))
    return _concat_parts(parts), dropped


# --------------------------------------------------------------------------
# Chunked distributed groupby (partial aggregates + associative merge)
# --------------------------------------------------------------------------


def chunked_dist_groupby(ctx: HptmtContext, table, by: Sequence[str],
                         aggs: Mapping[str, Sequence[str] | str], *,
                         group_capacity_per_shard: int | None = None,
                         overcommit: float = 2.0,
                         local_impl: str | None = None,
                         groupby_sizes: Mapping[str, int] | None = None):
    """Morsel-driven distributed GroupBy+Aggregate.

    Streams ``table`` (a :class:`ChunkedTable` or column mapping) chunk
    by chunk: shuffle on the keys, local *partial* aggregation, and an
    associative :func:`local_ops.merge_partial_aggregates` fold into a
    device-resident accumulator of ``group_capacity_per_shard`` groups
    per shard (default: the shuffled-morsel capacity — size it to the
    expected per-shard distinct-key count; overflowing *groups* are
    dropped and counted, never silently lost).  A key is pinned to one
    shard by the partition hash, so the final accumulator equals the
    monolithic ``dist_groupby`` result per shard — bit-identically when
    float addition is exact (see the module docstring).

    Returns ``(columns, dropped)``: the host-collected canonical result
    (one row per key, key-sorted within its shard) and the chunk-summed
    overflow total.
    """
    by = list(by)
    aggs_norm = {c: [ops] if isinstance(ops, str) else list(ops)
                 for c, ops in aggs.items()}
    partials = L.partial_agg_columns(aggs_norm)
    table = _as_chunked(table)
    world = ctx.world_size
    cap = table.capacity_per_shard(world)
    _, oc = D.default_shuffle_sizes(ctx, cap, overcommit)
    gcap = group_capacity_per_shard or oc
    sizes = dict(groupby_sizes or {})

    acc0 = {k: narrow_column(k, table.columns[k][:0]) for k in by}
    for col, ops in partials.items():
        for op in ops:
            dt = np.int32 if op == "count" else np.float32
            acc0[f"{col}_{op}"] = np.zeros(0, dt)
    acc = D.distribute_table(ctx, acc0, capacity_per_shard=gcap)

    def step(c, a, chunk):
        sh, d1 = D.shuffle(c, chunk, by, overcommit=overcommit)
        part, d2 = L.groupby_aggregate(sh, by, partials, impl=local_impl,
                                       return_overflow=True, **sizes)
        merged, d3 = L.merge_partial_aggregates(a, part, by,
                                                impl=local_impl, **sizes,
                                                return_overflow=True)
        return merged, d1 + jax.lax.psum(d2 + d3, c.row_axes)

    # donate the accumulator (rebound each fold — merge keeps its
    # capacity, so XLA folds the merge in place)
    pipe = D.DistributedPipeline(ctx, step, donate_argnums=(0,))
    dropped = 0
    for g in table.distribute(ctx):
        acc, d = pipe(acc, g)
        dropped += _dropped(d)

    def finalize(c, a):
        cols = {k: a.columns[k] for k in by}
        for col, ops in aggs_norm.items():
            for op in ops:
                if op == "mean":
                    cnt = a.columns[f"{col}_count"]
                    v = a.columns[f"{col}_sum"] / \
                        jax.numpy.maximum(cnt, 1).astype(jax.numpy.float32)
                else:
                    v = a.columns[f"{col}_{op}"]
                cols[f"{col}_{op}"] = v
        return L.Table(columns=cols, nvalid=a.nvalid)

    out = D.DistributedPipeline(ctx, finalize)(acc)
    return D.collect_table(ctx, out), dropped


# --------------------------------------------------------------------------
# Chunked distributed sort (sorted runs + stable host k-way merge)
# --------------------------------------------------------------------------


def _np_sort_key(col: np.ndarray, ascending: bool) -> np.ndarray:
    """Host mirror of ``local_ops._sort_key`` (order-reversal transform)."""
    if ascending:
        return col
    if np.issubdtype(col.dtype, np.floating):
        return -col
    return ~col


def _np_tuple_less(a: tuple, b: tuple) -> np.ndarray:
    res = np.zeros(a[0].shape, bool)
    eq = np.ones(a[0].shape, bool)
    for x, y in zip(a, b):
        res = res | (eq & (x < y))
        eq = eq & (x == y)
    return res


def _np_lex_searchsorted(sorted_keys: tuple, query_keys: tuple,
                         side: str) -> np.ndarray:
    """Host mirror of ``local_ops.lex_searchsorted`` (vectorized binary
    search over parallel lexicographically-sorted key columns)."""
    n = len(sorted_keys[0]) if sorted_keys else 0
    m = len(query_keys[0]) if query_keys else 0
    lo = np.zeros(m, np.int64)
    hi = np.full(m, n, np.int64)
    iters = max(1, int(n - 1).bit_length() + 1) if n > 0 else 0
    for _ in range(iters):
        mid = (lo + hi) // 2
        midc = np.clip(mid, 0, max(n - 1, 0))
        at_mid = tuple(k[midc] for k in sorted_keys)
        if side == "left":
            go_right = _np_tuple_less(at_mid, query_keys)
        else:
            go_right = ~_np_tuple_less(query_keys, at_mid)
        go_right = go_right & (mid < hi)
        lo = np.where(go_right, mid + 1, lo)
        hi = np.where(go_right, hi, mid)
    return lo


def _merge_two_runs(a: dict, b: dict, by: list, ascending: bool) -> dict:
    ak = tuple(_np_sort_key(a[k], ascending) for k in by)
    bk = tuple(_np_sort_key(b[k], ascending) for k in by)
    n, m = len(ak[0]), len(bk[0])
    # stable positions: a row i lands at i + |b rows strictly less|,
    # b row j at j + |a rows less-or-equal| — a (the earlier run) wins ties
    pos_a = np.arange(n) + _np_lex_searchsorted(bk, ak, "left")
    pos_b = np.arange(m) + _np_lex_searchsorted(ak, bk, "right")
    out = {}
    for k in a:
        col = np.empty(n + m, a[k].dtype)
        col[pos_a] = a[k]
        col[pos_b] = b[k]
        out[k] = col
    return out


def merge_sorted_runs(runs: list[dict], by: Sequence[str],
                      ascending: bool = True) -> dict:
    """Stable k-way merge of sorted runs (host-side, vectorized).

    Adjacent pairwise merges keep run order, so ties resolve to the
    earlier run — matching the monolithic sample sort's original-row
    tie order when runs are consecutive chunks."""
    by = list(by)
    if not runs:
        return {}
    runs = list(runs)
    while len(runs) > 1:
        nxt = []
        for i in range(0, len(runs) - 1, 2):
            nxt.append(_merge_two_runs(runs[i], runs[i + 1], by, ascending))
        if len(runs) % 2:
            nxt.append(runs[-1])
        runs = nxt
    return runs[0]


def chunked_dist_sort(ctx: HptmtContext, table, by: Sequence[str],
                      ascending: bool = True, *,
                      n_samples: int = 32, overcommit: float = 2.0,
                      local_impl: str | None = None):
    """Morsel-driven distributed OrderBy: each chunk runs the full
    sample sort (``dist_sort``) into a globally-sorted host run; runs
    fold through the stable k-way merge.  Bit-identical to the
    monolithic ``dist_sort`` — equal keys tie in original row order both
    ways.  Returns ``(columns, dropped)``.
    """
    by = list(by)
    table = _as_chunked(table)
    pipe = D.DistributedPipeline(
        ctx, lambda c, t: D.dist_sort(c, t, by, ascending=ascending,
                                      n_samples=n_samples,
                                      overcommit=overcommit,
                                      local_impl=local_impl))
    runs, dropped = [], 0
    for g in table.distribute(ctx):
        out, d = pipe(g)
        dropped += _dropped(d)
        runs.append(D.collect_table(ctx, out))
    return merge_sorted_runs(runs, by, ascending), dropped
