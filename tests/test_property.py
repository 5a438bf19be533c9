"""Hypothesis property-based tests on the table engine's invariants.

Strategy: small random tables (int key column + float value column, random
capacity padding).  Each property is an algebraic law of the relational
operators — the kind of invariant the HPTMT composition model relies on.
"""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="optional dev dependency (requirements-dev.txt)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import local_ops as L
from repro.core.partition import hash_columns, partition_ids
from repro.core.table import Table

from conftest import as_sets

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")


@st.composite
def tables(draw, max_rows=24, key_range=8):
    n = draw(st.integers(0, max_rows))
    pad = draw(st.integers(0, 8))
    keys = draw(st.lists(st.integers(0, key_range - 1),
                         min_size=n, max_size=n))
    vals = draw(st.lists(
        st.floats(-100, 100, allow_nan=False, width=32),
        min_size=n, max_size=n))
    return Table.from_dict(
        {"k": np.asarray(keys, np.int32),
         "v": np.asarray(vals, np.float32)},
        capacity=max(n + pad, 1))


@given(tables())
def test_nvalid_never_exceeds_capacity(t):
    assert int(t.nvalid) <= t.capacity


def sort_law(t, impl=None):
    out = L.sort_values(t, ["k"], impl=impl)
    assert int(out.nvalid) == int(t.nvalid)
    got = out.to_numpy()
    want = t.to_numpy()
    np.testing.assert_array_equal(np.sort(got["k"]), np.sort(want["k"]))
    assert (np.diff(got["k"]) >= 0).all()
    # row payloads stay attached to their keys (multiset of pairs equal)
    assert as_sets(got) == as_sets(want)


def dedup_law(t, impl=None):
    out = L.drop_duplicates(t, ["k"], impl=impl).to_numpy()
    keys = t.to_numpy()["k"]
    assert set(out["k"]) == set(keys)
    assert len(out["k"]) == len(np.unique(keys))


def groupby_total_law(t, impl=None):
    out = L.groupby_aggregate(t, ["k"], {"v": "sum"}, impl=impl)
    total_groups = float(L.aggregate(out, "v_sum", "sum"))
    total_rows = float(L.aggregate(t, "v", "sum"))
    np.testing.assert_allclose(total_groups, total_rows, rtol=1e-4,
                               atol=1e-4)


def join_count_law(a, b, impl=None):
    na = a.to_numpy()["k"]
    nb = b.to_numpy()["k"]
    want = sum(int((na == k).sum()) * int((nb == k).sum())
               for k in np.unique(na))
    out, overflow = L.join(a, b, left_on=["k"], out_capacity=1024,
                           return_overflow=True, impl=impl)
    assert int(out.nvalid) == want
    assert int(overflow) == 0


def partition_law(a, b, impl=None, dedup_impl=None):
    """difference(a,b) ∪ semijoin(a,b) == a (as key sets)."""
    inter = set(L.intersect(a, b, ["k"], impl=impl,
                            dedup_impl=dedup_impl).to_numpy()["k"])
    diff = set(L.difference(a, b, ["k"], impl=impl).to_numpy()["k"])
    keys = set(a.to_numpy()["k"])
    assert inter | diff == keys
    assert inter & diff == set()


def union_self_law(t, impl=None):
    u = L.union(t, t, impl=impl).to_numpy()
    d = L.drop_duplicates(t, impl=impl).to_numpy()
    assert as_sets(u) == as_sets(d)


@given(tables())
def test_sort_is_permutation_and_ordered(t):
    sort_law(t)


@given(tables())
def test_dedup_subset_of_input_and_unique(t):
    dedup_law(t)


@given(tables(), st.integers(0, 7))
def test_select_conjunction_composes(t, cut):
    m1 = t["k"] >= cut
    m2 = t["k"] % 2 == 0
    seq = L.select(L.select(t, m1), L.select(t, m1)["k"] % 2 == 0)
    joint = L.select(t, m1 & m2)
    assert as_sets(seq.to_numpy()) == as_sets(joint.to_numpy())


@given(tables())
def test_groupby_sum_preserves_total(t):
    groupby_total_law(t)


@st.composite
def mixed_key_tables(draw, max_rows=20):
    """Tables with a mixed-dtype (int32, float32) key pair and an
    integer-valued float value column (exact sums in any addition order,
    so the groupby backends must agree bit-for-bit)."""
    n = draw(st.integers(0, max_rows))
    pad = draw(st.integers(0, 6))
    ik = draw(st.lists(st.integers(0, 5), min_size=n, max_size=n))
    # float keys off a small exact grid; no -0.0, no NaN (out of contract)
    fk = draw(st.lists(st.sampled_from([x * 0.5 for x in range(-4, 5)]),
                       min_size=n, max_size=n))
    iv = draw(st.lists(st.integers(-50, 50), min_size=n, max_size=n))
    return Table.from_dict(
        {"ik": np.asarray(ik, np.int32),
         "fk": np.asarray(fk, np.float32),
         "v": np.asarray(iv, np.float32)},
        capacity=max(n + pad, 1))


@given(mixed_key_tables(),
       st.lists(st.booleans(), min_size=1, max_size=3))
def test_sort_backends_bit_identical_and_match_oracle(t, asc):
    """OrderBy invariants over mixed-dtype multi-key tables with per-key
    ascending flags: the radix and xla backends are bit-identical (full
    columns — padding rows stay last in the same order), and both match
    the pandas-semantics oracle including stability of ties (stable
    semantics pin tie order to original row order on every side)."""
    from oracles import np_sort_values

    by = ["ik", "fk", "v"][: len(asc)]
    x = L.sort_values(t, by, asc, impl="xla")
    r = L.sort_values(t, by, asc, impl="radix")
    assert int(x.nvalid) == int(r.nvalid) == int(t.nvalid)
    for c in t.names:
        np.testing.assert_array_equal(np.asarray(x.columns[c]),
                                      np.asarray(r.columns[c]),
                                      err_msg=c)
    data = t.to_numpy()
    want = np_sort_values(data, by, asc)
    got = r.to_numpy()
    for c in want:
        np.testing.assert_array_equal(got[c], want[c].astype(got[c].dtype),
                                      err_msg=f"oracle {c}")


@given(tables(), st.integers(0, 7))
def test_compact_is_stable_boolean_argsort(t, cut):
    """The 1-bit radix fast path behind compact/select: bit-identical to
    the stable argsort compaction, padding rows preserved in order."""
    keep = (t["k"] >= cut) & t.valid_mask
    got = L.compact(t, keep)
    perm = jnp.argsort(jnp.logical_not(keep), stable=True)
    want = t.gather_rows(perm, jnp.sum(keep, dtype=jnp.int32))
    assert int(got.nvalid) == int(want.nvalid)
    for c in t.names:
        np.testing.assert_array_equal(np.asarray(got.columns[c]),
                                      np.asarray(want.columns[c]),
                                      err_msg=c)


@given(mixed_key_tables())
def test_groupby_backends_bit_identical(t):
    aggs = {"v": ["sum", "count", "mean", "min", "max"]}
    s = L.groupby_aggregate(t, ["ik", "fk"], aggs, impl="sort")
    h, over = L.groupby_aggregate(t, ["ik", "fk"], aggs, impl="hash",
                                  return_overflow=True)
    assert int(over) == 0
    assert int(s.nvalid) == int(h.nvalid)
    sn, hn = s.to_numpy(), h.to_numpy()
    assert set(sn) == set(hn)
    for c in sn:
        assert sn[c].dtype == hn[c].dtype, c
        np.testing.assert_array_equal(sn[c], hn[c], err_msg=c)
    assert hn["v_count"].dtype == np.int32


@given(mixed_key_tables())
def test_dedup_backends_bit_identical(t):
    s = L.drop_duplicates(t, ["ik", "fk"], impl="sort")
    h, over = L.drop_duplicates(t, ["ik", "fk"], impl="hash",
                                return_overflow=True)
    assert int(over) == 0
    sn, hn = s.to_numpy(), h.to_numpy()
    for c in sn:
        np.testing.assert_array_equal(sn[c], hn[c], err_msg=c)


@given(tables(), tables())
def test_join_row_count_is_sum_of_key_products(a, b):
    join_count_law(a, b)


@given(tables(), tables())
def test_intersect_difference_partition_left(a, b):
    partition_law(a, b)


@given(tables())
def test_union_with_self_is_dedup(t):
    union_self_law(t)


# The laws above, on the backend of each family that runs only when a
# caller names it (the tests above run the defaults).
OTHER_BACKEND_LAWS = {
    "sort_radix": lambda a, b: sort_law(a, "radix"),
    "dedup_hash": lambda a, b: dedup_law(a, "hash"),
    "groupby_total_hash": lambda a, b: groupby_total_law(a, "hash"),
    "join_count_hash": lambda a, b: join_count_law(a, b, "hash"),
    "partition_hash": lambda a, b: partition_law(a, b, "hash", "hash"),
    "union_self_hash": lambda a, b: union_self_law(a, "hash"),
}


@pytest.mark.parametrize("law", sorted(OTHER_BACKEND_LAWS))
@given(a=tables(), b=tables())
def test_law_on_other_backend(law, a, b):
    OTHER_BACKEND_LAWS[law](a, b)


@given(mixed_key_tables(), mixed_key_tables())
def test_semi_backends_bit_identical_mixed_keys(a, b):
    """The semi-join backends on mixed-dtype (int32, float32) multi-key
    tables: the sortmerge and hash membership masks are bit-identical
    over the FULL capacity (padding rows are never members), and
    intersect/difference/union outputs match bit-for-bit."""
    on = ["ik", "fk"]
    ms = L.semi_mask(a, b, on, impl="sortmerge")
    mh, over = L.semi_mask(a, b, on, impl="hash", return_overflow=True)
    assert int(over) == 0
    np.testing.assert_array_equal(np.asarray(ms), np.asarray(mh))
    assert not np.asarray(ms)[int(a.nvalid):].any()
    for op in ("intersect", "difference"):
        s = getattr(L, op)(a, b, on=on, impl="sortmerge")
        h = getattr(L, op)(a, b, on=on, impl="hash")
        assert int(s.nvalid) == int(h.nvalid), op
        sn, hn = s.to_numpy(), h.to_numpy()
        for c in sn:
            assert sn[c].dtype == hn[c].dtype, (op, c)
            np.testing.assert_array_equal(sn[c], hn[c],
                                          err_msg=f"{op} {c}")
    us = L.union(a, b, on=on, impl="sort").to_numpy()
    uh = L.union(a, b, on=on, impl="hash").to_numpy()
    for c in us:
        np.testing.assert_array_equal(us[c], uh[c],
                                      err_msg=f"union {c}")


@given(mixed_key_tables(), mixed_key_tables())
def test_intersect_difference_partition_mixed_keys(a, b):
    """difference(a,b) ⊎ semijoin(a,b) == a (as row multisets) on
    mixed-dtype multi-key tables — for BOTH semi backends."""
    an = a.to_numpy()
    rows = as_sets(an)
    for impl in ("sortmerge", "hash"):
        mask = np.asarray(L.semi_mask(a, b, ["ik", "fk"],
                                      impl=impl))[:int(a.nvalid)]
        inside = as_sets({c: v[mask] for c, v in an.items()})
        d = L.difference(a, b, on=["ik", "fk"], impl=impl).to_numpy()
        assert sorted(inside + as_sets(d)) == rows, impl


@given(mixed_key_tables(), mixed_key_tables())
def test_union_matches_dedup_oracle_mixed_keys(a, b):
    """union(a, b, on) == drop_duplicates(concat(a, b), on): keep-first
    canonical output, a's rows winning key ties."""
    from oracles import np_drop_duplicates

    an, bn = a.to_numpy(), b.to_numpy()
    cat = {c: np.concatenate([an[c], bn[c]]) for c in an}
    want = np_drop_duplicates(cat, ["ik", "fk"])
    got = L.union(a, b, on=["ik", "fk"]).to_numpy()
    for c in want:
        np.testing.assert_array_equal(got[c],
                                      want[c].astype(got[c].dtype),
                                      err_msg=c)


@given(tables())
def test_concat_counts_add(t):
    out = L.concat(t, t)
    assert int(out.nvalid) == 2 * int(t.nvalid)


@given(tables(), st.integers(1, 8))
def test_partition_ids_in_range_and_hash_deterministic(t, parts):
    pid = np.asarray(partition_ids(t, ["k"], parts))
    assert ((pid >= 0) & (pid < parts)).all()
    h1 = np.asarray(hash_columns([t["k"]]))
    h2 = np.asarray(hash_columns([t["k"]]))
    np.testing.assert_array_equal(h1, h2)
    # equal keys hash equal -> equal partition (valid rows only; padding
    # rows are masked to pid 0 by design)
    n = int(t.nvalid)
    keys = np.asarray(t["k"])[:n]
    for u in np.unique(keys):
        assert len(np.unique(pid[:n][keys == u])) == 1


@given(st.lists(st.floats(-1e5, 1e5, allow_nan=False, width=32),
                min_size=1, max_size=32))
def test_float_hash_normalizes_negative_zero(vals):
    col = jnp.asarray(np.asarray(vals, np.float32))
    h_pos = np.asarray(hash_columns([jnp.abs(col) * 0.0]))
    h_neg = np.asarray(hash_columns([-(jnp.abs(col) * 0.0)]))
    np.testing.assert_array_equal(h_pos, h_neg)
