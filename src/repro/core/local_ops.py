"""Local (single-partition) HPTMT table operators.

These are the paper's Table-2 operators — Select, Project, Union,
Difference, Intersect, Join, OrderBy, Aggregate, GroupBy (+ the UNOMT
helpers: unique/drop_duplicates, isin, dropna/fillna, map, astype) —
implemented as pure, jittable, *static-shape* JAX functions over
:class:`repro.core.table.Table`.

TPU adaptation notes (see DESIGN.md §2):
* every op is mask-aware: rows ``>= nvalid`` are padding;
* local join has two backends selected by ``impl`` (default
  ``JOIN_IMPL``):

  - ``"sortmerge"`` — binary search over sorted keys; exact for any key
    distribution, O((L+R) log) sorts per call;
  - ``"hash"`` — bucketed build+probe on the ``kernels/hash_join`` Pallas
    kernel; no sorts, but static per-bucket capacities (overflow is
    counted, see the kernel package README) — the paper's hash-local-join
    fast path for shuffled (10%-unique-key style) workloads;

* the aggregation family (groupby_aggregate, drop_duplicates) has the
  same two backends via ``impl`` (default ``GROUPBY_IMPL``):

  - ``"sort"`` — lexicographic tuple sort + segment reductions;
  - ``"hash"`` — bucketed hash-accumulate on the ``kernels/hash_groupby``
    Pallas kernel: sum/count/mean/min/max per distinct key in one pass,
    **no sort primitive anywhere on the path** (canonical key order is
    recovered with a multi-pass radix rank over the distinct keys,
    ``kernels/radix_sort``);

* OrderBy (sort_values) itself has two backends via ``impl`` (default
  ``SORT_IMPL``):

  - ``"xla"`` — one stable ``jax.lax.sort`` over (validity, keys, iota);
  - ``"radix"`` — the ``kernels/radix_sort`` multi-pass LSD engine: a
    chain of stable counting-sort digit passes, **no sort primitive in
    the jaxpr** — bit-identical rows/order/dtypes either way
    (conformance: tests/test_sort_backends.py);

  ``compact()``/``select()`` (and the shuffle's receive side in
  dist_ops) always take the engine's 1-bit fast path — a single
  counting pass that is bit-identical to the stable boolean argsort it
  replaces, so row compaction never sorts;

  both emit *canonicalized* output — one row per distinct key, sorted by
  key, counts int32 — so they are bit-identical and drop-in
  interchangeable (conformance: tests/test_groupby_backends.py; float
  ``sum``/``mean`` are bit-identical whenever addition is exact, e.g.
  integer-valued data, and agree to rounding otherwise);

* the membership family (isin, semi_mask, intersect, difference) has two
  backends via ``impl`` (default ``SEMI_IMPL``):

  - ``"sortmerge"`` — sort the right key set, binary-search each probe;
  - ``"hash"`` — bucketed build+probe membership on ``kernels/hash_semi``:
    one boolean per probe row, no join materialization, **no sort
    primitive anywhere on the path**;

  both compare key pairs in their *promoted* common dtype (as do the
  join backends), so mixed-dtype probes cannot collide distinct keys —
  bit-identical masks either way (conformance:
  tests/test_setop_backends.py);

* multi-column keys are exact in both backends: lexicographic binary
  search (:func:`lex_searchsorted`) / full key-bit equality — no hash
  collisions, no int64 packing.
"""
from __future__ import annotations

from functools import partial
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp

from ..kernels import bucketing
from ..kernels.hash_groupby import (default_hash_groupby_sizes,
                                    hash_groupby_plan)
from ..kernels.hash_join import default_hash_join_sizes, hash_join_plan
from ..kernels.hash_semi import default_hash_semi_sizes, hash_semi_plan
from ..kernels.radix_sort import (radix_permutation, radix_rank,
                                  stable_partition_perm)
from .kernel_backend import table_kernel_impl as _default_kernel_impl
from .table import Table, isnull_values, null_like

# The local algorithm each operator family runs when its caller names none
# (``impl=None``; the dist_* wrappers and morsel loops forward their
# ``local_impl`` unchanged).  The other backends run only by an explicit
# ``impl=``.
JOIN_IMPL = "sortmerge"
GROUPBY_IMPL = "sort"
SORT_IMPL = "xla"
SEMI_IMPL = "sortmerge"

# --------------------------------------------------------------------------
# small helpers
# --------------------------------------------------------------------------


def _sentinel_max(col: jax.Array) -> jax.Array:
    if jnp.issubdtype(col.dtype, jnp.floating):
        return jnp.asarray(jnp.inf, col.dtype)
    return jnp.asarray(jnp.iinfo(col.dtype).max, col.dtype)


@jax.named_scope("select")
def compact(table: Table, keep: jax.Array,
            kernel_impl: str | None = None) -> Table:
    """Move rows where ``keep`` holds to the front (stable); drop the rest.

    Runs the radix engine's 1-bit fast path (one stable counting pass,
    ``kernels/radix_sort``) — bit-identical to the boolean
    ``argsort(~keep, stable=True)`` it replaces, with no sort primitive.
    """
    keep = keep & table.valid_mask
    perm = stable_partition_perm(keep,
                                 impl=kernel_impl or _default_kernel_impl())
    return table.gather_rows(perm, jnp.sum(keep, dtype=jnp.int32))


# --------------------------------------------------------------------------
# Select / Project / head / take / concat
# --------------------------------------------------------------------------


def select(table: Table, mask: jax.Array) -> Table:
    """Paper's Select: keep rows where ``mask`` (bool (capacity,)) holds."""
    return compact(table, mask)


def project(table: Table, names: Sequence[str]) -> Table:
    """Paper's Project: keep a subset of columns."""
    return Table(columns={n: table.columns[n] for n in names},
                 nvalid=table.nvalid)


def head(table: Table, n) -> Table:
    return table.with_nvalid(jnp.minimum(table.nvalid, jnp.int32(n)))


def take(table: Table, idx: jax.Array, count) -> Table:
    return table.gather_rows(idx, count)


def concat(a: Table, b: Table) -> Table:
    """Union-all of two same-schema tables (capacity = sum of capacities)."""
    if set(a.names) != set(b.names):
        raise ValueError(f"schema mismatch: {a.names} vs {b.names}")
    cap_a, cap_b = a.capacity, b.capacity
    out_cap = cap_a + cap_b
    i = jnp.arange(out_cap, dtype=jnp.int32)
    from_a = i < a.nvalid
    ia = jnp.clip(i, 0, cap_a - 1)
    ib = jnp.clip(i - a.nvalid, 0, cap_b - 1)
    cols = {}
    for n in a.names:
        ca, cb = a.columns[n], b.columns[n].astype(a.columns[n].dtype)
        cols[n] = jnp.where(from_a, ca[ia], cb[ib])
    return Table(columns=cols, nvalid=a.nvalid + b.nvalid)


@jax.named_scope("append")
def append_rows(acc: Table, t: Table):
    """Append ``t``'s valid rows after ``acc``'s, *keeping acc's static
    capacity* (unlike :func:`concat`, which grows it).

    The fixed-capacity accumulator op behind the morsel-driven chunk
    loops (``core/morsel.py``): under ``jit`` the accumulator's shape
    never changes, so every chunk iteration reuses one compiled program.
    Rows past ``acc.capacity`` are dropped and **counted** — the same
    counted-overflow contract as the shuffle — and the count is returned:
    ``(appended, dropped)``.
    """
    if set(acc.names) != set(t.names):
        raise ValueError(f"schema mismatch: {acc.names} vs {t.names}")
    cap = acc.capacity
    i = jnp.arange(t.capacity, dtype=jnp.int32)
    slot = acc.nvalid + i
    ok = (i < t.nvalid) & (slot < cap)
    flat = jnp.where(ok, slot, cap)
    cols = {}
    for n in acc.names:
        src = t.columns[n].astype(acc.columns[n].dtype)
        buf = jnp.concatenate(
            [acc.columns[n], jnp.zeros((1,), acc.columns[n].dtype)])
        cols[n] = buf.at[flat].set(src)[:cap]
    total = acc.nvalid + t.nvalid
    out = Table(columns=cols, nvalid=jnp.minimum(total, cap))
    return out, jnp.maximum(total - cap, 0)


# --------------------------------------------------------------------------
# OrderBy (sort_values)
# --------------------------------------------------------------------------


def _sort_key(col: jax.Array, ascending: bool) -> jax.Array:
    if ascending:
        return col
    if jnp.issubdtype(col.dtype, jnp.floating):
        return -col
    return ~col  # two's-complement: exact order reversal, no overflow


def sort_values(table: Table, by: Sequence[str],
                ascending: bool | Sequence[bool] = True, *,
                impl: str | None = None,
                kernel_impl: str | None = None) -> Table:
    """Paper's OrderBy: stable multi-key sort; padding rows stay at the end.

    ``impl`` picks the backend (default ``SORT_IMPL``):
    ``"xla"`` (one stable ``jax.lax.sort``) or ``"radix"`` (multi-pass LSD
    radix rank on the ``kernels/radix_sort`` engine — no ``sort``
    primitive in the jaxpr).  Both emit *bit-identical* output — same
    rows, same order, same dtypes, including the stable order of equal
    keys and the padding region — so they are drop-in interchangeable
    (conformance: tests/test_sort_backends.py).  ``kernel_impl``
    (ref | pallas | pallas_interpret) selects the radix digit kernel.
    """
    by = list(by)
    if isinstance(ascending, bool):
        ascending = [ascending] * len(by)
    impl = impl or SORT_IMPL
    keys = [_sort_key(table.columns[k], a) for k, a in zip(by, ascending)]
    if impl == "xla":
        invalid = (~table.valid_mask).astype(jnp.int32)
        iota = jnp.arange(table.capacity, dtype=jnp.int32)
        out = jax.lax.sort((invalid, *keys, iota), num_keys=1 + len(keys),
                           is_stable=True)
        perm = out[-1]
    elif impl == "radix":
        perm = radix_permutation(
            tuple(keys), ~table.valid_mask,
            impl=kernel_impl or _default_kernel_impl())
    else:
        raise ValueError(f"unknown sort impl {impl!r} "
                         "(expected 'xla' or 'radix')")
    return table.gather_rows(perm, table.nvalid)


# --------------------------------------------------------------------------
# Lexicographic vectorized binary search (exact, multi-key, static shape)
# --------------------------------------------------------------------------


def _tuple_less(a: tuple, b: tuple) -> jax.Array:
    """a < b lexicographically (element-wise over vectors)."""
    res = jnp.zeros(a[0].shape, bool)
    eq = jnp.ones(a[0].shape, bool)
    for x, y in zip(a, b):
        res = res | (eq & (x < y))
        eq = eq & (x == y)
    return res


def lex_searchsorted(sorted_keys: tuple, query_keys: tuple,
                     side: str = "left") -> jax.Array:
    """``searchsorted`` over a tuple of parallel sorted key columns.

    ``sorted_keys[i]`` all share shape ``(n,)`` and are lexicographically
    sorted; ``query_keys[i]`` share shape ``(m,)``.  Returns int32 ``(m,)``
    insertion points.  Exact (comparison-based), O(m log n).
    """
    n = sorted_keys[0].shape[0]
    m = query_keys[0].shape[0]
    lo = jnp.zeros((m,), jnp.int32)
    hi = jnp.full((m,), n, jnp.int32)
    iters = max(1, int(n - 1).bit_length() + 1) if n > 0 else 1

    def body(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) // 2
        midc = jnp.clip(mid, 0, n - 1)
        at_mid = tuple(k[midc] for k in sorted_keys)
        if side == "left":
            go_right = _tuple_less(at_mid, query_keys)        # k[mid] < q
        else:
            go_right = ~_tuple_less(query_keys, at_mid)       # k[mid] <= q
        go_right = go_right & (mid < hi)
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return lo


def _sorted_keys_with_sentinel(table: Table, by: Sequence[str]):
    """Sort table by ``by``; overwrite padding keys with +max sentinels so the
    full-capacity key arrays are globally sorted."""
    ts = sort_values(table, by)
    valid = ts.valid_mask
    keys = []
    for k in by:
        col = ts.columns[k]
        keys.append(jnp.where(valid, col, _sentinel_max(col)))
    return ts, tuple(keys)


# --------------------------------------------------------------------------
# Unique / drop_duplicates
# --------------------------------------------------------------------------


def drop_duplicates(table: Table, subset: Sequence[str] | None = None, *,
                    impl: str | None = None, return_overflow: bool = False,
                    num_buckets: int | None = None,
                    bucket_capacity: int | None = None,
                    kernel_impl: str | None = None):
    """Keep the first occurrence of each distinct key (paper: Unique).

    ``impl`` picks the backend (default ``GROUPBY_IMPL``):
    ``"sort"`` (stable sort + boundary compaction) or ``"hash"`` (key-only
    hash groupby on the ``kernels/hash_groupby`` plan — no sort).  Both
    emit the *canonical* table: one row per distinct key, sorted by the
    ``subset`` columns, payload columns taken from the key's first
    occurrence — bit-identical across backends.  The hash backend adds
    static ``num_buckets`` / ``bucket_capacity`` sizing (auto-sized from
    the capacity when omitted); rows overflowing a bucket slab are
    dropped and counted (``return_overflow=True`` returns the count).
    """
    subset = list(subset) if subset is not None else list(table.names)
    impl = impl or GROUPBY_IMPL
    if impl == "sort":
        out, over = _sort_drop_duplicates(table, subset), jnp.int32(0)
    elif impl == "hash":
        out, over = _hash_drop_duplicates(table, subset, num_buckets,
                                          bucket_capacity, kernel_impl)
    else:
        raise ValueError(f"unknown groupby impl {impl!r} "
                         "(expected 'sort' or 'hash')")
    if return_overflow:
        return out, over
    return out


def _sort_drop_duplicates(table: Table, subset: list) -> Table:
    ts = sort_values(table, subset)
    valid = ts.valid_mask
    neq_prev = jnp.zeros(ts.capacity, bool)
    for k in subset:
        col = ts.columns[k]
        prev = jnp.roll(col, 1)
        neq_prev = neq_prev | (col != prev)
    first = jnp.arange(ts.capacity) == 0
    boundary = (first | neq_prev) & valid
    return compact(ts, boundary)


def _hash_drop_duplicates(table: Table, subset: list, num_buckets,
                          bucket_capacity, kernel_impl):
    """Key-only hash groupby: the plan's group representatives *are* the
    first occurrences; ranking them by key reproduces the sort backend's
    output exactly — without a sort."""
    plan = _run_hash_groupby_plan(table, subset, (), num_buckets,
                                  bucket_capacity, kernel_impl)
    _, grow, final, ngroups, cap = _canonical_group_layout(
        table, subset, plan, kernel_impl)
    out_cols = {n: _place_groups(table.columns[n][grow], final, cap)
                for n in table.names}
    return Table(columns=out_cols, nvalid=ngroups), plan.dropped


unique = drop_duplicates


# --------------------------------------------------------------------------
# GroupBy + Aggregate
# --------------------------------------------------------------------------

_AGGS = ("sum", "count", "mean", "min", "max")


def groupby_aggregate(table: Table, by: Sequence[str],
                      aggs: Mapping[str, Sequence[str] | str], *,
                      impl: str | None = None,
                      return_overflow: bool = False,
                      num_buckets: int | None = None,
                      bucket_capacity: int | None = None,
                      kernel_impl: str | None = None):
    """Paper's GroupBy followed by Aggregate.

    ``aggs`` maps value-column name -> aggregation(s) in
    {sum,count,mean,min,max}.  Output columns are named ``{col}_{agg}``;
    one row per distinct key, capacity preserved.

    ``impl`` picks the backend (default ``GROUPBY_IMPL``):
    ``"sort"`` (lexicographic sort + segment reductions) or ``"hash"``
    (bucketed hash-accumulate on the ``kernels/hash_groupby`` kernel — no
    sort anywhere on the path).  Both emit the *canonical* table: one row
    per distinct key, sorted by the ``by`` columns, counts int32, value
    aggregates float32 — bit-identical across backends (float sum/mean
    bit-identical whenever addition is exact, to rounding otherwise).
    The hash backend adds static ``num_buckets`` / ``bucket_capacity``
    sizing (auto-sized from the capacity when omitted) and ``kernel_impl``
    (ref | pallas | pallas_interpret); rows overflowing a bucket slab are
    dropped and counted (``return_overflow=True`` returns the count).
    """
    by = list(by)
    aggs = {c: [ops] if isinstance(ops, str) else list(ops)
            for c, ops in aggs.items()}
    for ops in aggs.values():
        for op in ops:
            if op not in _AGGS:
                raise ValueError(f"unknown aggregation {op!r}")
    impl = impl or GROUPBY_IMPL
    if impl == "sort":
        out, over = _sort_groupby(table, by, aggs), jnp.int32(0)
    elif impl == "hash":
        out, over = _hash_groupby(table, by, aggs, num_buckets,
                                  bucket_capacity, kernel_impl)
    else:
        raise ValueError(f"unknown groupby impl {impl!r} "
                         "(expected 'sort' or 'hash')")
    if return_overflow:
        return out, over
    return out


@jax.named_scope("groupby")
def _sort_groupby(table: Table, by: list,
                  aggs: Mapping[str, list]) -> Table:
    """Sort backend: lexicographic sort, group-boundary detection, segment
    reductions indexed by group id."""
    ts = sort_values(table, by)
    valid = ts.valid_mask
    cap = ts.capacity
    neq_prev = jnp.zeros(cap, bool)
    for k in by:
        col = ts.columns[k]
        neq_prev = neq_prev | (col != jnp.roll(col, 1))
    boundary = ((jnp.arange(cap) == 0) | neq_prev) & valid
    ngroups = jnp.sum(boundary, dtype=jnp.int32)
    seg = jnp.cumsum(boundary.astype(jnp.int32)) - 1          # 0-based
    # padding rows -> trash segment (cap-1 is free whenever padding exists)
    seg = jnp.where(valid, seg, cap - 1)

    out_cols: dict[str, jax.Array] = {}
    for k in by:
        out_cols[k] = ts.columns[k]
    counts = jax.ops.segment_sum(valid.astype(jnp.int32), seg,
                                 num_segments=cap)
    countf = jnp.maximum(counts, 1).astype(jnp.float32)
    for col_name, ops in aggs.items():
        fcol = ts.columns[col_name].astype(jnp.float32)
        for op in ops:
            if op == "sum":
                v = jax.ops.segment_sum(jnp.where(valid, fcol, 0.0), seg, cap)
            elif op == "count":
                v = counts
            elif op == "mean":
                s = jax.ops.segment_sum(jnp.where(valid, fcol, 0.0), seg, cap)
                v = s / countf
            elif op == "min":
                v = jax.ops.segment_min(
                    jnp.where(valid, fcol, jnp.inf), seg, cap)
            else:  # max
                v = jax.ops.segment_max(
                    jnp.where(valid, fcol, -jnp.inf), seg, cap)
            out_cols[f"{col_name}_{op}"] = v

    # segment g's result sits at index g; boundary row g sits at the g-th
    # boundary position — compacting boundary rows aligns keys with index g.
    key_tbl = compact(Table(columns={k: out_cols[k] for k in by},
                            nvalid=ts.nvalid), boundary)
    cols = dict(key_tbl.columns)
    for name, v in out_cols.items():
        if name not in by:
            cols[name] = v  # already indexed by group id
    return Table(columns=cols, nvalid=ngroups)


def _planned_sizes(bplan: bucketing.BucketPlan, nvalid, capacity: int,
                   num_buckets, explicit_capacity):
    """Distribution-proof static sizing via the two-pass bucket planner.

    Above ``bucketing.EXACT_SLAB_CAP`` the uniform auto-sizing heuristic
    can overflow on skewed keys; when the key bit-planes are *concrete*
    (an eager call — not traced under jit/shard_map) the planner
    histograms the actual bucket loads host-side and sizes the slab to
    cover the real maximum.  The hash it runs is memoized on the
    :class:`~..kernels.bucketing.BucketPlan`, so the kernel plan reuses
    the same bucket ids instead of re-hashing.  Returns ``(num_buckets,
    bucket_capacity)`` or ``None`` when planning is not applicable
    (explicit capacity, exact-slab range, or traced inputs — the
    heuristic applies there).
    """
    if explicit_capacity is not None or capacity <= bucketing.EXACT_SLAB_CAP:
        return None
    if isinstance(nvalid, jax.core.Tracer) or not bplan.concrete:
        return None
    n = int(nvalid)
    B, C = bucketing.plan_bucket_sizes(num_buckets=num_buckets,
                                       plan=bplan, nvalid=n)
    # slab sizes are static args of the jitted plans: quantize the planned
    # capacity to the next power of two so shifting key distributions
    # retrace at most log2(capacity) times, not once per observed load
    return B, 1 << max(3, (C - 1).bit_length())


def _run_hash_groupby_plan(table: Table, by: list, value_cols: tuple,
                           num_buckets, bucket_capacity, kernel_impl):
    keys = tuple(table.columns[k] for k in by)
    bp = bucketing.BucketPlan(keys, table.valid_mask)
    planned = _planned_sizes(bp, table.nvalid, table.capacity,
                             num_buckets, bucket_capacity)
    if planned is not None:
        B, C = planned
        bid = bp.bucket_ids_for(B)   # the sizing pass's hash, reused
    else:
        B, C = default_hash_groupby_sizes(table.capacity, num_buckets)
        C = bucket_capacity or C
        bid = None
    return hash_groupby_plan(
        bp.bits, table.valid_mask,
        tuple(table.columns[c] for c in value_cols),
        num_buckets=B, bucket_capacity=C,
        impl=kernel_impl or _default_kernel_impl(), bid=bid)


def _canonical_group_layout(table: Table, by: list, plan,
                            kernel_impl: str | None = None):
    """Map the plan's group representatives to canonical (key-sorted)
    output rows without a sort.

    Representatives are first compacted bucket-major (scatter by running
    count), then each group's key — gathered from its first-occurrence
    row — is ranked by the ``kernels/radix_sort`` multi-pass radix rank:
    group keys are globally distinct (equal keys share a bucket), so each
    valid group's stable rank is a bijection onto ``[0, ngroups)``.
    O(passes * capacity * 2^radix_bits) counting work — linear in the
    capacity, replacing the earlier O(capacity^2) pairwise count-smaller
    — and still no ``sort`` primitive.

    Returns (scat, grow, final, ngroups, cap): the slab->compact scatter
    function (for the plan's per-slot aggregates), per compacted group
    its representative row, its canonical output slot (``cap`` = trash),
    the group count, and the output capacity.
    """
    cap = table.capacity
    rep = plan.rep.reshape(-1) > 0
    ridx = jnp.cumsum(rep.astype(jnp.int32)) - 1   # ridx < cap: one rep/row
    ngroups = jnp.sum(rep, dtype=jnp.int32)
    slot = jnp.where(rep, ridx, cap)

    def scat(x):
        return jnp.zeros((cap + 1,), x.dtype).at[slot].set(x)[:cap]

    grow = scat(plan.row.reshape(-1))
    gvalid = jnp.zeros((cap + 1,), bool).at[slot].set(rep)[:cap]
    gkeys = tuple(table.columns[k][grow] for k in by)
    rank = radix_rank(gkeys, ~gvalid,
                      impl=kernel_impl or _default_kernel_impl())
    final = jnp.where(gvalid, rank, cap)
    return scat, grow, final, ngroups, cap


def _place_groups(x: jax.Array, final: jax.Array, cap: int) -> jax.Array:
    """Scatter compacted group entries into their canonical slots."""
    return jnp.zeros((cap + 1,), x.dtype).at[final].set(x)[:cap]


def _hash_groupby(table: Table, by: list, aggs: Mapping[str, list],
                  num_buckets, bucket_capacity, kernel_impl):
    """Hash backend: bucketed hash-accumulate (kernels/hash_groupby)
    instead of a sort.  The plan aggregates every distinct key inside its
    hash bucket in one dense pass; canonical key order is recovered with
    the multi-pass radix rank (no sort primitive on this path)."""
    value_cols = tuple(aggs)
    plan = _run_hash_groupby_plan(table, by, value_cols, num_buckets,
                                  bucket_capacity, kernel_impl)
    scat, grow, final, ngroups, cap = _canonical_group_layout(
        table, by, plan, kernel_impl)
    out_cols: dict[str, jax.Array] = {
        k: _place_groups(table.columns[k][grow], final, cap) for k in by}
    counts = _place_groups(scat(plan.counts.reshape(-1)), final, cap)
    countf = jnp.maximum(counts, 1).astype(jnp.float32)
    for i, (col_name, ops) in enumerate(aggs.items()):
        s = _place_groups(scat(plan.sums[:, i, :].reshape(-1)), final, cap)
        for op in ops:
            if op == "sum":
                v = s
            elif op == "count":
                v = counts
            elif op == "mean":
                v = s / countf
            elif op == "min":
                v = _place_groups(scat(plan.mins[:, i, :].reshape(-1)),
                                  final, cap)
            else:  # max
                v = _place_groups(scat(plan.maxs[:, i, :].reshape(-1)),
                                  final, cap)
            out_cols[f"{col_name}_{op}"] = v
    return Table(columns=out_cols, nvalid=ngroups), plan.dropped


# merge rule per partial-aggregate column suffix: how two partials of the
# same group combine into the partial of their union
_PARTIAL_MERGE = {"sum": "sum", "count": "sum", "min": "min", "max": "max"}


def partial_agg_columns(aggs: Mapping[str, Sequence[str] | str]):
    """Expand requested aggregations to the *partial* set that chunked
    (morsel) execution accumulates: ``mean`` needs ``sum`` + ``count``,
    everything else is its own partial.  Returns ``{col: [partial ops]}``
    in canonical (sum, count, min, max) order."""
    out: dict[str, list] = {}
    for col, ops in aggs.items():
        ops = [ops] if isinstance(ops, str) else list(ops)
        need = set()
        for op in ops:
            if op not in _AGGS:
                raise ValueError(f"unknown aggregation {op!r}")
            need.update(("sum", "count") if op == "mean" else (op,))
        out[col] = [op for op in ("sum", "count", "min", "max")
                    if op in need]
    return out


def merge_partial_aggregates(acc: Table, part: Table, by: Sequence[str], *,
                             impl: str | None = None,
                             return_overflow: bool = False,
                             num_buckets: int | None = None,
                             bucket_capacity: int | None = None,
                             kernel_impl: str | None = None):
    """Merge two canonical partial-aggregate tables into one with
    ``acc``'s capacity — the associative combine step of morsel-driven
    groupby (``core/morsel.py``).

    Both inputs carry the ``by`` key columns plus partial columns named
    ``{col}_{op}`` with ``op`` in sum/count/min/max (the shape
    :func:`groupby_aggregate` emits, see :func:`partial_agg_columns`).
    Equal keys combine through the matching merge reduction — sum of
    sums, sum of counts, min of mins, max of maxs — by re-running the
    pluggable aggregation backend (``impl`` = 'sort' | 'hash': the merge
    reuses the existing hash-groupby slabs, no new kernel) over the
    concatenation, so the output is again canonical (one row per key,
    key-sorted) and the merge is associative: any chunking of the input
    rows folds to the same table.

    Counts stay exact int32 (the float32 re-sum is exact below 2^24 rows
    per group — the engine's whole-table capacity bound is int32, and
    per-chunk partial counts are bounded by chunk capacity).  Groups past
    ``acc.capacity`` (and hash-slab overflow under ``impl='hash'``) are
    dropped and **counted**: ``return_overflow=True`` returns
    ``(merged, dropped)``.
    """
    by = list(by)
    t = concat(acc, part)
    merge_op: dict[str, str] = {}
    for name in acc.names:
        if name in by:
            continue
        _, _, suffix = name.rpartition("_")
        if suffix not in _PARTIAL_MERGE:
            raise ValueError(
                f"column {name!r} is not a partial-aggregate column "
                "(expected a _sum/_count/_min/_max suffix)")
        merge_op[name] = _PARTIAL_MERGE[suffix]
    g, over = groupby_aggregate(t, by, {n: [op] for n, op in
                                        merge_op.items()},
                                impl=impl, return_overflow=True,
                                num_buckets=num_buckets,
                                bucket_capacity=bucket_capacity,
                                kernel_impl=kernel_impl)
    cap = acc.capacity
    cols = {k: g.columns[k][:cap] for k in by}
    for name, op in merge_op.items():
        v = g.columns[f"{name}_{op}"][:cap]
        if name.endswith("_count"):
            v = v.astype(jnp.int32)
        cols[name] = v
    out = Table(columns=cols, nvalid=jnp.minimum(g.nvalid, cap))
    dropped = over + jnp.maximum(g.nvalid - cap, 0)
    if return_overflow:
        return out, dropped
    return out


def aggregate(table: Table, col: str, op: str) -> jax.Array:
    """Whole-column masked reduction -> scalar (paper's Aggregate).

    ``count`` returns int32 (matching the groupby backends' count
    columns); every other aggregation returns float32."""
    valid = table.valid_mask
    x = table.columns[col].astype(jnp.float32)
    n = jnp.maximum(table.nvalid.astype(jnp.float32), 1.0)
    if op == "sum":
        return jnp.sum(jnp.where(valid, x, 0.0))
    if op == "count":
        return table.nvalid.astype(jnp.int32)
    if op == "mean":
        return jnp.sum(jnp.where(valid, x, 0.0)) / n
    if op == "min":
        return jnp.min(jnp.where(valid, x, jnp.inf))
    if op == "max":
        return jnp.max(jnp.where(valid, x, -jnp.inf))
    if op == "std":
        m = jnp.sum(jnp.where(valid, x, 0.0)) / n
        v = jnp.sum(jnp.where(valid, (x - m) ** 2, 0.0)) / n
        return jnp.sqrt(v)
    raise ValueError(f"unknown aggregation {op!r}")


# --------------------------------------------------------------------------
# Join (pluggable backend: sort-merge / bucketed hash; static output
# capacity either way)
# --------------------------------------------------------------------------


def join(left: Table, right: Table, *,
         left_on: Sequence[str], right_on: Sequence[str] | None = None,
         how: str = "inner", out_capacity: int | None = None,
         suffix: str = "_r", return_overflow: bool = False,
         impl: str | None = None, num_buckets: int | None = None,
         bucket_capacity: int | None = None,
         probe_capacity: int | None = None,
         kernel_impl: str | None = None):
    """Paper's Join: inner/left join with static output capacity.

    ``impl`` picks the backend (default ``JOIN_IMPL``):
    ``"sortmerge"`` or ``"hash"``.  Both emit *identical* output — same
    rows, same order: left-row-major, and within a left row its matches in
    the right table's original row order — so they are drop-in
    interchangeable (conformance: tests/test_join_backends.py).

    ``out_capacity`` defaults to ``left.capacity``; overflowing output
    rows are dropped and counted (``return_overflow=True`` returns the
    count).  The hash backend adds ``num_buckets`` / ``bucket_capacity`` /
    ``probe_capacity`` static sizing (auto-sized from the table capacities
    when omitted; rows overflowing a bucket slab are dropped and counted
    into the same overflow metric) and ``kernel_impl``
    (ref | pallas | pallas_interpret) for the probe kernel.
    """
    if how not in ("inner", "left"):
        raise ValueError("how must be 'inner' or 'left'")
    impl = impl or JOIN_IMPL
    left_on = list(left_on)
    right_on = list(right_on) if right_on is not None else left_on
    out_cap = out_capacity or left.capacity
    if impl == "sortmerge":
        return _sortmerge_join(left, right, left_on, right_on, how, out_cap,
                               suffix, return_overflow)
    if impl == "hash":
        return _hash_join(left, right, left_on, right_on, how, out_cap,
                          suffix, return_overflow, num_buckets,
                          bucket_capacity, probe_capacity, kernel_impl)
    raise ValueError(f"unknown join impl {impl!r} "
                     "(expected 'sortmerge' or 'hash')")


def _emit_layout(match_counts: jax.Array, lvalid: jax.Array, how: str):
    """(inclusive cumsum, exclusive offsets, total) of per-left-row emit
    counts — the left-row-major layout shared by both join backends (left
    join emits 1 slot for each ``lvalid`` row with no matches)."""
    if how == "left":
        emit = jnp.where(lvalid & (match_counts == 0), 1, match_counts)
    else:
        emit = match_counts
    cum = jnp.cumsum(emit)
    offs = cum - emit
    total = cum[-1] if emit.shape[0] > 0 else jnp.int32(0)
    return cum, offs, total


def _slot_rows(offs: jax.Array, out_cap: int) -> jax.Array:
    """Left row of each of ``out_cap`` output slots, from the emit layout's
    exclusive ``offs``: every row scatters its index to its first slot and
    a running max carries it over the row's other slots — O(L + out_cap),
    no search.  Rows sharing an offset are zero-emit rows followed by the
    one row that emits there, so the scatter's ``max`` keeps that row.
    Slots at or past ``min(total, out_cap)`` are padding and get some row
    in range."""
    rows = jnp.arange(offs.shape[0], dtype=jnp.int32)
    first = (jnp.zeros((out_cap,), jnp.int32)
             .at[offs].max(rows, mode="drop", indices_are_sorted=True))
    return jax.lax.cummax(first)


def _sortmerge_join(left: Table, right: Table, left_on, right_on, how,
                    out_cap, suffix, return_overflow):
    """Sort-merge backend: the right table is sorted by its keys; each left
    row binary-searches its match range ``[lo, hi)``; output slot ``j`` is
    mapped back to its left row by one scatter and a running max over the
    emit layout (``_slot_rows``) — fully vectorized, no dynamic shapes."""
    rs, rkeys = _sorted_keys_with_sentinel(right, right_on)
    # compare every key pair in the *promoted* common dtype (casting the
    # sorted keys is order-preserving: int32 -> float32 is monotonic), so
    # a mixed-dtype probe cannot collide distinct keys
    dts = tuple(jnp.promote_types(left.columns[k].dtype,
                                  rs.columns[rk].dtype)
                for k, rk in zip(left_on, right_on))
    qkeys = tuple(left.columns[k].astype(dt)
                  for k, dt in zip(left_on, dts))
    rkeys = tuple(rk.astype(dt) for rk, dt in zip(rkeys, dts))
    with jax.named_scope("join/match"):
        lo = lex_searchsorted(rkeys, qkeys, side="left")
        hi = lex_searchsorted(rkeys, qkeys, side="right")
    lo = jnp.minimum(lo, right.nvalid)
    hi = jnp.minimum(hi, right.nvalid)
    lvalid = left.valid_mask
    match_counts = jnp.where(lvalid, hi - lo, 0)
    _, offs, total = _emit_layout(match_counts, lvalid, how)

    # output slot j -> left row (scatter + running max), then its right
    # row j - offs + lo with one gather of the per-row shift; a left
    # join's unmatched row shifts its one slot below 0.  Then the gathers
    # of every column.
    with jax.named_scope("join/expand"):
        j = jnp.arange(out_cap, dtype=jnp.int32)
        lrow = _slot_rows(offs, out_cap)
        shift = jnp.where(match_counts > 0, lo - offs, -out_cap)
        r = j + shift[lrow]
        matched = r >= 0
        rrow = jnp.clip(r, 0, max(right.capacity - 1, 0))

        cols: dict[str, jax.Array] = {}
        for n in left.names:
            cols[n] = left.columns[n][lrow]
        drop_keys = set(right_on) if left_on == right_on else set()
        for n in rs.names:
            if n in drop_keys:
                continue
            name = n + suffix if n in cols else n
            v = rs.columns[n][rrow]
            if how == "left":
                v = jnp.where(matched, v, null_like(v))
            cols[name] = v
    out = Table(columns=cols, nvalid=jnp.minimum(total, out_cap))
    if return_overflow:
        return out, jnp.maximum(total - out_cap, 0)
    return out


def _hash_join(left: Table, right: Table, left_on, right_on, how,
               out_cap, suffix, return_overflow, num_buckets,
               bucket_capacity, probe_capacity, kernel_impl):
    """Hash backend: bucketed build+probe (kernels/hash_join) instead of
    two sorts.  The plan yields per-left-row match counts plus per
    (probe slot, chain slot) match ranks; matched pairs are scattered into
    their output slots (offset of the left row + rank of the match), which
    reproduces the sort-merge output ordering exactly because chain order
    is original-right-row order."""
    B, C, Lc = default_hash_join_sizes(left.capacity, right.capacity,
                                       num_buckets)
    # compare in the promoted common dtype (same rule as the sort-merge
    # backend): the hash only picks the bucket, equality is on the
    # promoted key bits.  Bit-planes are extracted ONCE per side here and
    # shared by the sizing pass and the kernel plan (BucketPlan).
    qkeys, rkeys = _promoted_semi_keys(left, right, list(left_on),
                                       list(right_on))
    lbp = bucketing.BucketPlan(qkeys, left.valid_mask)
    rbp = bucketing.BucketPlan(rkeys, right.valid_mask)
    # two-pass planner (concrete keys, above the exact-slab range): size
    # the build chains / probe slabs to the real per-bucket maxima
    big = max(left.capacity, right.capacity)
    built = _planned_sizes(rbp, right.nvalid, big, B, bucket_capacity)
    if built is not None:
        C = built[1]
    probed = _planned_sizes(lbp, left.nvalid, big, B, probe_capacity)
    if probed is not None:
        Lc = probed[1]
    C = bucket_capacity or C
    Lc = probe_capacity or Lc
    plan = hash_join_plan(lbp.bits, left.valid_mask, rbp.bits,
                          right.valid_mask,
                          num_buckets=B, bucket_capacity=C,
                          probe_capacity=Lc,
                          impl=kernel_impl or _default_kernel_impl(),
                          left_bid=(lbp.bucket_ids_for(B)
                                    if probed is not None else None),
                          right_bid=(rbp.bucket_ids_for(B)
                                     if built is not None else None))

    # a probe-dropped left row's match status is unknown: it is excluded
    # from emission entirely (counted in probe_dropped), never emitted as
    # a fake unmatched row — "overflow rows are dropped and counted"
    lvalid = left.valid_mask & plan.probed
    mc = plan.match_counts
    cum, offs, total = _emit_layout(mc, lvalid, how)

    # ONE scatter over the pair space: each matched (bucket, probe slot,
    # chain slot) pair writes its own flat pair index to output slot
    # offs[left row] + within-row match rank; the row ids are then
    # *decoded* from the pair index with out_cap-sized gathers (pair //
    # C walks the probe slots, so probe_row/build_row recover the
    # original rows) instead of scattering three pair-space planes.
    slot = offs[plan.probe_row][:, :, None] + plan.rank      # (B, Lc, C)
    keep = (plan.rank >= 0) & (slot < out_cap)
    flat = jnp.where(keep, slot, out_cap).reshape(-1)
    npairs = B * Lc * C
    pair_ids = jnp.arange(npairs, dtype=jnp.int32)
    buf = (jnp.full((out_cap + 1,), -1, jnp.int32)
           .at[flat].set(pair_ids)[:out_cap])
    matched = buf >= 0
    pp = jnp.maximum(buf, 0)
    # pair = (b*Lc + l)*C + c  ->  probe slot index b*Lc+l = pair // C,
    # build slot index b*C + c = (pair // (Lc*C))*C + pair % C
    out_lrow = jnp.where(matched,
                         plan.probe_row.reshape(-1)[pp // C], 0)
    out_rrow = jnp.where(
        matched,
        plan.build_row.reshape(-1)[(pp // (Lc * C)) * C + pp % C], 0)
    if how == "left":
        un = lvalid & (mc == 0)
        flat_u = jnp.where(un & (offs < out_cap), offs, out_cap)
        ubuf = (jnp.zeros((out_cap + 1,), jnp.int32)
                .at[flat_u].set(jnp.arange(left.capacity, dtype=jnp.int32))
                [:out_cap])
        out_lrow = jnp.where(matched, out_lrow, ubuf)

    cols: dict[str, jax.Array] = {}
    for n in left.names:
        cols[n] = left.columns[n][out_lrow]
    drop_keys = set(right_on) if left_on == right_on else set()
    for n in right.names:
        if n in drop_keys:
            continue
        name = n + suffix if n in cols else n
        v = right.columns[n][out_rrow]
        if how == "left":
            v = jnp.where(matched, v, null_like(v))
        cols[name] = v
    out = Table(columns=cols, nvalid=jnp.minimum(total, out_cap))
    if return_overflow:
        overflow = (jnp.maximum(total - out_cap, 0)
                    + plan.build_dropped + plan.probe_dropped)
        return out, overflow
    return out


def cartesian_product(left: Table, right: Table, out_capacity: int,
                      suffix: str = "_r", return_overflow: bool = False):
    """Paper's Cartesian Product (static output capacity).

    Output rows beyond ``out_capacity`` are dropped and *counted* — the
    same "dropped and counted" contract as join/groupby overflow
    (``return_overflow=True`` returns the count; callers size the
    capacity so it stays zero)."""
    n2 = jnp.maximum(right.nvalid, 1)
    j = jnp.arange(out_capacity, dtype=jnp.int32)
    lrow = jnp.clip(j // n2, 0, max(left.capacity - 1, 0))
    rrow = jnp.clip(j % n2, 0, max(right.capacity - 1, 0))
    total = left.nvalid * right.nvalid
    cols = {n: left.columns[n][lrow] for n in left.names}
    for n in right.names:
        name = n + suffix if n in cols else n
        cols[name] = right.columns[n][rrow]
    out = Table(columns=cols, nvalid=jnp.minimum(total, out_capacity))
    if return_overflow:
        return out, jnp.maximum(total - out_capacity, 0)
    return out


# --------------------------------------------------------------------------
# Membership + set operators (pluggable semi-join backend: sort-merge /
# bucketed hash membership probe — no join materialization either way)
# --------------------------------------------------------------------------


def _promoted_semi_keys(left: Table, right: Table, left_on: list,
                        right_on: list):
    """Both sides' key columns cast to their *promoted* common dtype.

    Comparing in either side's dtype can collide distinct keys (e.g. a
    float32 3.7 probe truncated to int32 3), so membership — like the
    join backends — compares every key pair in ``jnp.promote_types`` of
    the two column dtypes (int32 x float32 -> float32)."""
    q, v = [], []
    for lk, rk in zip(left_on, right_on):
        lc, rc = left.columns[lk], right.columns[rk]
        dt = jnp.promote_types(lc.dtype, rc.dtype)
        q.append(lc.astype(dt))
        v.append(rc.astype(dt))
    return tuple(q), tuple(v)


def _sortmerge_semi(qkeys: tuple, lvalid: jax.Array, vkeys: tuple,
                    rnvalid) -> jax.Array:
    """Sort-merge membership: sort the right key set, binary-search each
    left key's match range — member iff the range is non-empty."""
    vt = Table(columns={f"k{i}": c for i, c in enumerate(vkeys)},
               nvalid=rnvalid)
    _, skeys = _sorted_keys_with_sentinel(vt, list(vt.names))
    lo = lex_searchsorted(skeys, qkeys, side="left")
    hi = lex_searchsorted(skeys, qkeys, side="right")
    lo = jnp.minimum(lo, rnvalid)
    hi = jnp.minimum(hi, rnvalid)
    return (hi > lo) & lvalid


def _hash_semi(qkeys: tuple, left: Table, vkeys: tuple, right: Table,
               num_buckets, bucket_capacity, probe_capacity, kernel_impl):
    """Hash membership: build the right side's key set into bucket slabs
    (kernels/hash_semi, the hash_groupby/bucketing slab plan) and probe
    each left key — one boolean per row, no join materialization, no
    sort primitive.  Probe-dropped rows report False and are counted."""
    B, C, Lc = default_hash_semi_sizes(left.capacity, right.capacity,
                                       num_buckets)
    # bit-planes extracted ONCE per side, shared by the sizing pass and
    # the kernel plan (BucketPlan caches the hash between them)
    lbp = bucketing.BucketPlan(qkeys, left.valid_mask)
    rbp = bucketing.BucketPlan(vkeys, right.valid_mask)
    # two-pass planner (concrete keys, above the exact-slab range): size
    # the build/probe slabs to the real per-bucket maxima
    big = max(left.capacity, right.capacity)
    built = _planned_sizes(rbp, right.nvalid, big, B, bucket_capacity)
    if built is not None:
        C = built[1]
    probed = _planned_sizes(lbp, left.nvalid, big, B, probe_capacity)
    if probed is not None:
        Lc = probed[1]
    C = bucket_capacity or C
    Lc = probe_capacity or Lc
    plan = hash_semi_plan(lbp.bits, left.valid_mask, rbp.bits,
                          right.valid_mask,
                          num_buckets=B, bucket_capacity=C,
                          probe_capacity=Lc,
                          impl=kernel_impl or _default_kernel_impl(),
                          left_bid=(lbp.bucket_ids_for(B)
                                    if probed is not None else None),
                          right_bid=(rbp.bucket_ids_for(B)
                                     if built is not None else None))
    mask = plan.member & left.valid_mask
    return mask, plan.build_dropped + plan.probe_dropped


def semi_mask(left: Table, right: Table, left_on: Sequence[str],
              right_on: Sequence[str] | None = None, *,
              impl: str | None = None, return_overflow: bool = False,
              num_buckets: int | None = None,
              bucket_capacity: int | None = None,
              probe_capacity: int | None = None,
              kernel_impl: str | None = None):
    """Semi-join membership mask: per left row, does its key appear among
    the right table's valid keys?

    ``impl`` picks the backend (default ``SEMI_IMPL``): ``"sortmerge"``
    (binary search over the sorted right key set) or ``"hash"`` (bucketed
    build+probe membership on the
    ``kernels/hash_semi`` plan — no join materialization, no ``sort``
    primitive anywhere on the path).  Both emit the *bit-identical* mask
    — key pairs are compared in their promoted common dtype either way —
    so they are drop-in interchangeable (conformance:
    tests/test_setop_backends.py).

    The hash backend adds static ``num_buckets`` / ``bucket_capacity`` /
    ``probe_capacity`` sizing (auto-sized from the table capacities when
    omitted) and ``kernel_impl`` (ref | pallas | pallas_interpret); rows
    overflowing a slab are dropped — reported non-member — and counted
    (``return_overflow=True`` returns the count)."""
    left_on = list(left_on)
    right_on = list(right_on) if right_on is not None else left_on
    impl = impl or SEMI_IMPL
    qkeys, vkeys = _promoted_semi_keys(left, right, left_on, right_on)
    if impl == "sortmerge":
        mask, over = _sortmerge_semi(qkeys, left.valid_mask, vkeys,
                                     right.nvalid), jnp.int32(0)
    elif impl == "hash":
        mask, over = _hash_semi(qkeys, left, vkeys, right, num_buckets,
                                bucket_capacity, probe_capacity,
                                kernel_impl)
    else:
        raise ValueError(f"unknown semi impl {impl!r} "
                         "(expected 'sortmerge' or 'hash')")
    if return_overflow:
        return mask, over
    return mask


def _semi_mask(left: Table, right: Table, on: Sequence[str],
               **kwargs):
    """Same-named-columns :func:`semi_mask` (the set operators' shape)."""
    return semi_mask(left, right, on, on, **kwargs)


def isin(table: Table, col: str, values: Table, values_col: str, *,
         impl: str | None = None, return_overflow: bool = False,
         num_buckets: int | None = None, bucket_capacity: int | None = None,
         probe_capacity: int | None = None, kernel_impl: str | None = None):
    """Bool mask: table[col] present among valid values[values_col].

    A single-key :func:`semi_mask` — the paper's membership filter
    (UNOMT Fig. 11).  Keys are compared in the promoted common dtype of
    the two columns, so e.g. a float32 probe against an int32 values
    table cannot collide distinct keys.  See :func:`semi_mask` for the
    backend (``impl``) and overflow contracts."""
    return semi_mask(table, values, [col], [values_col], impl=impl,
                     return_overflow=return_overflow,
                     num_buckets=num_buckets,
                     bucket_capacity=bucket_capacity,
                     probe_capacity=probe_capacity, kernel_impl=kernel_impl)


def intersect(a: Table, b: Table, on: Sequence[str] | None = None, *,
              impl: str | None = None, dedup_impl: str | None = None,
              return_overflow: bool = False,
              num_buckets: int | None = None,
              bucket_capacity: int | None = None,
              probe_capacity: int | None = None,
              kernel_impl: str | None = None):
    """Paper's Intersect: distinct rows of ``a`` present in ``b``.

    ``impl`` selects the semi-join backend (see :func:`semi_mask`);
    ``dedup_impl`` the dedup backend (see :func:`drop_duplicates`,
    default ``GROUPBY_IMPL``).  Output is the canonical
    table (one row per distinct key, sorted by the ``on`` columns) —
    bit-identical across all backend combinations.
    ``return_overflow=True`` returns the summed semi + dedup overflow."""
    on = list(on) if on is not None else list(a.names)
    mask, s_over = _semi_mask(a, b, on, impl=impl, return_overflow=True,
                              num_buckets=num_buckets,
                              bucket_capacity=bucket_capacity,
                              probe_capacity=probe_capacity,
                              kernel_impl=kernel_impl)
    out, d_over = drop_duplicates(compact(a, mask), on, impl=dedup_impl,
                                  return_overflow=True,
                                  kernel_impl=kernel_impl)
    if return_overflow:
        return out, s_over + d_over
    return out


def difference(a: Table, b: Table, on: Sequence[str] | None = None, *,
               impl: str | None = None, return_overflow: bool = False,
               num_buckets: int | None = None,
               bucket_capacity: int | None = None,
               probe_capacity: int | None = None,
               kernel_impl: str | None = None):
    """Paper's Difference: rows of ``a`` with no match in ``b`` (all
    occurrences, original row order).

    ``impl`` selects the semi-join backend (see :func:`semi_mask`); both
    backends emit bit-identical output.  Under the hash backend a
    probe-dropped row's membership is unknown, so it is excluded and
    counted (``return_overflow=True``), never guessed into the output."""
    on = list(on) if on is not None else list(a.names)
    mask, over = _semi_mask(a, b, on, impl=impl, return_overflow=True,
                            num_buckets=num_buckets,
                            bucket_capacity=bucket_capacity,
                            probe_capacity=probe_capacity,
                            kernel_impl=kernel_impl)
    out = compact(a, a.valid_mask & ~mask)
    if return_overflow:
        return out, over
    return out


def union(a: Table, b: Table, on: Sequence[str] | None = None, *,
          impl: str | None = None, return_overflow: bool = False,
          num_buckets: int | None = None,
          bucket_capacity: int | None = None,
          kernel_impl: str | None = None):
    """Paper's Union: concat + dedup on the ``on`` key columns (all
    columns when omitted), keeping each key's first occurrence — ``a``'s
    rows win ties against ``b``'s.

    ``impl`` selects the dedup backend ('sort' | 'hash', see
    :func:`drop_duplicates`) with its static
    sizing; rows overflowing a hash bucket slab are dropped and counted
    (``return_overflow=True`` returns the count) — never silently lost."""
    on = list(on) if on is not None else list(a.names)
    return drop_duplicates(concat(a, b), on, impl=impl,
                           return_overflow=return_overflow,
                           num_buckets=num_buckets,
                           bucket_capacity=bucket_capacity,
                           kernel_impl=kernel_impl)


# --------------------------------------------------------------------------
# Null handling (UNOMT ops: isnull / notnull / dropna / fillna)
# --------------------------------------------------------------------------


def isnull(table: Table, col: str) -> jax.Array:
    return isnull_values(table.columns[col]) & table.valid_mask


def dropna(table: Table, subset: Sequence[str] | None = None) -> Table:
    subset = list(subset) if subset is not None else list(table.names)
    bad = jnp.zeros(table.capacity, bool)
    for k in subset:
        bad = bad | isnull_values(table.columns[k])
    return compact(table, ~bad)


def fillna(table: Table, values: Mapping[str, float]) -> Table:
    cols = dict(table.columns)
    for k, v in values.items():
        col = cols[k]
        cols[k] = jnp.where(isnull_values(col),
                            jnp.asarray(v, col.dtype), col)
    return Table(columns=cols, nvalid=table.nvalid)


# --------------------------------------------------------------------------
# Column-wise math used by the UNOMT pipeline (scikit-learn-style scaling)
# --------------------------------------------------------------------------


def column_moments(table: Table, cols: Sequence[str],
                   impl: str | None = None,
                   center: Mapping[str, jax.Array] | None = None):
    """Per-column moments over valid rows: ``({col: sum(x)},
    {col: sum((x - center)**2)}, count)`` float32 scalars.

    ``center`` maps column -> scalar (0.0 when omitted: the raw second
    moment).  Calling twice — first for sums, then centered on the means
    — gives the numerically stable two-pass variance (see
    :func:`standard_scale`); the one-pass ``E[x^2] - m^2`` form
    catastrophically cancels in float32 when ``|mean| >> std``.

    ``impl=None`` uses inline masked reductions (the fast path);
    ``"sort"`` / ``"hash"`` route the same moments through the pluggable
    aggregation backend as a constant-key :func:`groupby_aggregate` — so
    a preprocessing pipeline can exercise one aggregation backend end to
    end (conformance: tests/test_groupby_backends.py).
    """
    center = dict(center) if center is not None else {}
    zero = jnp.float32(0.0)
    if impl is None:
        valid = table.valid_mask
        s1, sd2 = {}, {}
        for k in cols:
            x = table.columns[k].astype(jnp.float32)
            d = x - center.get(k, zero)
            s1[k] = jnp.sum(jnp.where(valid, x, 0.0))
            sd2[k] = jnp.sum(jnp.where(valid, d * d, 0.0))
        return s1, sd2, table.nvalid.astype(jnp.float32)
    cap = table.capacity
    aug = {"__k": jnp.zeros((cap,), jnp.int32)}
    aggs: dict[str, list] = {}
    for k in cols:
        x = table.columns[k].astype(jnp.float32)
        d = x - center.get(k, zero)
        aug[k] = x
        aug[f"__sq_{k}"] = d * d
        aggs[k] = ["sum"]
        aggs[f"__sq_{k}"] = ["sum"]
    # constant key -> a single group in one bucket: the bucket slab must
    # hold every row, so size it to the full capacity explicitly
    g = groupby_aggregate(Table(columns=aug, nvalid=table.nvalid),
                          ["__k"], aggs, impl=impl, num_buckets=8,
                          bucket_capacity=cap)
    nz = table.nvalid > 0
    s1 = {k: jnp.where(nz, g.columns[f"{k}_sum"][0], 0.0) for k in cols}
    sd2 = {k: jnp.where(nz, g.columns[f"__sq_{k}_sum"][0], 0.0)
           for k in cols}
    return s1, sd2, table.nvalid.astype(jnp.float32)


def standard_scale(table: Table, cols: Sequence[str],
                   impl: str | None = None) -> Table:
    """(x - mean) / std per column over valid rows (sklearn StandardScaler).

    Two-pass: mean first, then the variance of deviations about it —
    exact even when ``|mean| >> std``.  ``impl`` selects the moment
    computation (see :func:`column_moments`); the default inline path
    and both aggregation backends agree to float addition-order
    rounding."""
    out = dict(table.columns)
    s1, _, n = column_moments(table, cols, impl=impl)
    n = jnp.maximum(n, 1.0)
    means = {k: s1[k] / n for k in cols}
    _, sd2, _ = column_moments(table, cols, impl=impl, center=means)
    for k in cols:
        x = out[k].astype(jnp.float32)
        out[k] = (x - means[k]) / jnp.sqrt(sd2[k] / n + 1e-12)
    return Table(columns=out, nvalid=table.nvalid)
