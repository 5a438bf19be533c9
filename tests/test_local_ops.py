"""Local operator tests against numpy oracles (paper Table 2 operators)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import local_ops as L
from repro.core.table import INT_NULL, Table

from conftest import as_sets, np_join_inner


def mk(data, capacity=None):
    return Table.from_dict(data, capacity=capacity)


# --------------------------------------------------------------------------
# select / project / concat
# --------------------------------------------------------------------------


def test_select_masks_and_compacts():
    t = mk({"a": [1, 2, 3, 4, 5]}, capacity=8)
    out = L.select(t, t["a"] % 2 == 1)
    np.testing.assert_array_equal(out.to_numpy()["a"], [1, 3, 5])


def test_select_ignores_padding_rows():
    t = mk({"a": [1, 2]}, capacity=6)
    # mask true everywhere, including padding: padding must not leak in
    out = L.select(t, jnp.ones(6, bool))
    np.testing.assert_array_equal(out.to_numpy()["a"], [1, 2])


def test_project():
    t = mk({"a": [1], "b": [2], "c": [3]})
    out = L.project(t, ["c", "a"])
    assert out.names == ("c", "a")


def test_concat_with_padding():
    a = mk({"x": [1, 2]}, capacity=4)
    b = mk({"x": [3]}, capacity=3)
    out = L.concat(a, b)
    np.testing.assert_array_equal(out.to_numpy()["x"], [1, 2, 3])
    assert out.capacity == 7


def test_concat_schema_mismatch():
    with pytest.raises(ValueError):
        L.concat(mk({"x": [1]}), mk({"y": [1]}))


# --------------------------------------------------------------------------
# sort
# --------------------------------------------------------------------------


def test_sort_single_key(rng, impl=None):
    vals = rng.integers(0, 50, 40)
    t = mk({"k": vals, "i": np.arange(40)}, capacity=64)
    out = L.sort_values(t, ["k"], impl=impl).to_numpy()
    np.testing.assert_array_equal(out["k"], np.sort(vals))


def test_sort_is_stable(rng, impl=None):
    keys = rng.integers(0, 4, 32)
    t = mk({"k": keys, "i": np.arange(32)})
    out = L.sort_values(t, ["k"], impl=impl).to_numpy()
    for k in range(4):
        sub = out["i"][out["k"] == k]
        assert (np.diff(sub) > 0).all(), "within-key order must be stable"


def test_sort_multi_key_matches_lexsort(rng, impl=None):
    a = rng.integers(0, 5, 30)
    b = rng.integers(0, 5, 30)
    t = mk({"a": a, "b": b}, capacity=40)
    out = L.sort_values(t, ["a", "b"], impl=impl).to_numpy()
    order = np.lexsort((b, a))
    np.testing.assert_array_equal(out["a"], a[order])
    np.testing.assert_array_equal(out["b"], b[order])


def test_sort_descending(rng, impl=None):
    vals = rng.integers(-100, 100, 25)
    t = mk({"k": vals})
    out = L.sort_values(t, ["k"], ascending=False, impl=impl).to_numpy()
    np.testing.assert_array_equal(out["k"], np.sort(vals)[::-1])


def test_sort_descending_float(rng, impl=None):
    vals = rng.normal(size=25).astype(np.float32)
    t = mk({"k": vals})
    out = L.sort_values(t, ["k"], ascending=False, impl=impl).to_numpy()
    np.testing.assert_allclose(out["k"], np.sort(vals)[::-1])


def test_sort_keeps_padding_at_end():
    t = mk({"k": [3, 1, 2]}, capacity=6)
    out = L.sort_values(t, ["k"])
    assert int(out.nvalid) == 3
    np.testing.assert_array_equal(out.to_numpy()["k"], [1, 2, 3])


# --------------------------------------------------------------------------
# dedup / unique
# --------------------------------------------------------------------------


def test_drop_duplicates(rng, impl=None):
    keys = rng.integers(0, 8, 50)
    t = mk({"k": keys, "v": np.arange(50)}, capacity=64)
    out = L.drop_duplicates(t, ["k"], impl=impl).to_numpy()
    assert sorted(out["k"]) == sorted(np.unique(keys))
    # keeps the FIRST occurrence of each key
    for k, v in zip(out["k"], out["v"]):
        first = np.nonzero(keys == k)[0][0]
        assert v == first


def test_drop_duplicates_idempotent(rng, impl=None):
    keys = rng.integers(0, 5, 30)
    t = mk({"k": keys})
    once = L.drop_duplicates(t, ["k"], impl=impl)
    twice = L.drop_duplicates(once, ["k"], impl=impl)
    assert as_sets(once.to_numpy()) == as_sets(twice.to_numpy())


def test_drop_duplicates_multi_col():
    t = mk({"a": [1, 1, 2, 1], "b": [1, 1, 2, 2]})
    out = L.drop_duplicates(t, ["a", "b"]).to_numpy()
    assert as_sets(out) == [(1.0, 1.0), (1.0, 2.0), (2.0, 2.0)]


# --------------------------------------------------------------------------
# groupby / aggregate
# --------------------------------------------------------------------------


def test_groupby_sum_mean_count(rng, impl=None):
    keys = rng.integers(0, 6, 64)
    vals = rng.normal(size=64).astype(np.float32)
    t = mk({"k": keys, "v": vals}, capacity=80)
    out = L.groupby_aggregate(t, ["k"], {"v": ["sum", "mean", "count"]},
                              impl=impl)
    o = out.to_numpy()
    for i, k in enumerate(o["k"]):
        sub = vals[keys == k]
        np.testing.assert_allclose(o["v_sum"][i], sub.sum(), rtol=1e-5)
        np.testing.assert_allclose(o["v_mean"][i], sub.mean(), rtol=1e-5)
        assert o["v_count"][i] == len(sub)
    assert o["v_count"].dtype == np.int32    # counts are int32, not float
    assert int(out.nvalid) == len(np.unique(keys))


def test_groupby_min_max(rng, impl=None):
    keys = rng.integers(0, 4, 40)
    vals = rng.normal(size=40).astype(np.float32)
    t = mk({"k": keys, "v": vals})
    o = L.groupby_aggregate(t, ["k"], {"v": ["min", "max"]},
                            impl=impl).to_numpy()
    for i, k in enumerate(o["k"]):
        sub = vals[keys == k]
        np.testing.assert_allclose(o["v_min"][i], sub.min(), rtol=1e-6)
        np.testing.assert_allclose(o["v_max"][i], sub.max(), rtol=1e-6)


def test_groupby_multi_key():
    t = mk({"a": [1, 1, 2, 2, 1], "b": [1, 1, 1, 1, 2],
            "v": [1.0, 2.0, 3.0, 4.0, 5.0]})
    o = L.groupby_aggregate(t, ["a", "b"], {"v": "sum"}).to_numpy()
    got = {(int(a), int(b)): s for a, b, s in zip(o["a"], o["b"], o["v_sum"])}
    assert got == {(1, 1): 3.0, (2, 1): 7.0, (1, 2): 5.0}


def test_groupby_unknown_agg():
    t = mk({"k": [1], "v": [1.0]})
    with pytest.raises(ValueError):
        L.groupby_aggregate(t, ["k"], {"v": "median"})


def test_scalar_aggregate(rng):
    vals = rng.normal(size=33).astype(np.float32)
    t = mk({"v": vals}, capacity=64)
    assert np.isclose(float(L.aggregate(t, "v", "sum")), vals.sum(),
                      rtol=1e-5)
    assert np.isclose(float(L.aggregate(t, "v", "mean")), vals.mean(),
                      rtol=1e-5)
    assert np.isclose(float(L.aggregate(t, "v", "min")), vals.min())
    assert np.isclose(float(L.aggregate(t, "v", "max")), vals.max())
    count = L.aggregate(t, "v", "count")
    assert count.dtype == np.int32 and int(count) == 33
    assert np.isclose(float(L.aggregate(t, "v", "std")), vals.std(),
                      rtol=1e-4)


# --------------------------------------------------------------------------
# join
# --------------------------------------------------------------------------


def test_inner_join_matches_oracle(rng, impl=None):
    left = {"k": rng.integers(0, 10, 30), "lv": np.arange(30)}
    right = {"k": rng.integers(0, 10, 20), "rv": np.arange(20) * 10}
    lt, rt = mk(left, capacity=40), mk(right, capacity=25)
    out = L.join(lt, rt, left_on=["k"], out_capacity=200, impl=impl).to_numpy()
    want = np_join_inner(left, right, "k")
    assert as_sets(out) == as_sets(want)


def test_left_join_unmatched_gets_null():
    lt = mk({"k": [1, 2, 3], "lv": [10, 20, 30]})
    rt = mk({"k": [2], "rv": [99]})
    out = L.join(lt, rt, left_on=["k"], how="left",
                 out_capacity=4).to_numpy()
    assert len(out["k"]) == 3
    rv = dict(zip(out["k"], out["rv"]))
    assert rv[2] == 99
    assert rv[1] == INT_NULL and rv[3] == INT_NULL


def test_join_multi_key():
    lt = mk({"a": [1, 1, 2], "b": [1, 2, 1], "lv": [10, 20, 30]})
    rt = mk({"a": [1, 2], "b": [2, 1], "rv": [5, 6]})
    out = L.join(lt, rt, left_on=["a", "b"], out_capacity=4).to_numpy()
    assert as_sets(out, ["a", "b", "lv", "rv"]) == [
        (1.0, 2.0, 20.0, 5.0), (2.0, 1.0, 30.0, 6.0)]


def test_join_different_key_names():
    lt = mk({"k": [1, 2], "lv": [10, 20]})
    rt = mk({"j": [2, 1], "rv": [5, 6]})
    out = L.join(lt, rt, left_on=["k"], right_on=["j"],
                 out_capacity=4).to_numpy()
    got = {(int(a), int(b)) for a, b in zip(out["k"], out["rv"])}
    assert got == {(1, 6), (2, 5)}


def test_join_overflow_counted():
    lt = mk({"k": [1, 1, 1]})
    rt = mk({"k": [1, 1, 1]})
    out, overflow = L.join(lt, rt, left_on=["k"], out_capacity=4,
                           return_overflow=True)
    assert int(out.nvalid) == 4
    assert int(overflow) == 5            # 9 matches, 4 kept


def test_join_name_collision_gets_suffix():
    lt = mk({"k": [1], "v": [10]})
    rt = mk({"k": [1], "v": [20]})
    out = L.join(lt, rt, left_on=["k"], out_capacity=2)
    assert "v" in out.names and "v_r" in out.names


def test_join_empty_right():
    lt = mk({"k": [1, 2]})
    rt = mk({"k": np.array([], np.int32)})
    out = L.join(lt, rt, left_on=["k"], out_capacity=4)
    assert int(out.nvalid) == 0


# name: (match counts of the valid left rows, padding rows, how, out_capacity)
SLOT_CASES = {
    "zero_emit_start_mid_end": ([0, 0, 3, 0, 2, 1, 0, 0, 4, 0, 0], 2,
                                "inner", 16),
    "all_zero_emit": ([0, 0, 0, 0, 0], 2, "inner", 8),
    "single_row": ([6], 0, "inner", 8),
    "truncated": ([2, 0, 5, 3, 0, 4], 1, "inner", 7),
    "roomy": ([1, 3, 0, 2], 3, "inner", 23),
    "left_unmatched": ([0, 2, 0, 0, 3, 0], 2, "left", 12),
}


@pytest.mark.parametrize("case", list(SLOT_CASES))
def test_sortmerge_slot_rows_match_searchsorted(case, rng):
    """The sortmerge join maps output slot j to its left row with a scatter
    and a running max; every slot below min(total, out_capacity) gets the
    row a clipped ``searchsorted(cum, j, side="right")`` gives, and the
    join emits that row's matches in the right table's row order."""
    counts, pad, how, out_cap = SLOT_CASES[case]
    n = len(counts)
    cap = n + pad
    mc = np.zeros(cap, np.int32)
    mc[:n] = counts
    valid = np.arange(cap) < n
    emit = np.where(valid & (mc == 0), 1, mc) if how == "left" else mc
    cum = np.cumsum(emit)
    offs = cum - emit
    total = int(cum[-1])
    n_out = min(total, out_cap)
    j = np.arange(n_out)
    want_lrow = np.clip(np.searchsorted(cum, j, side="right"), 0, cap - 1)

    _, got_offs, got_total = L._emit_layout(jnp.asarray(mc),
                                            jnp.asarray(valid), how)
    lrow = np.asarray(L._slot_rows(got_offs, out_cap))
    assert int(got_total) == total
    np.testing.assert_array_equal(lrow[:n_out], want_lrow)
    assert ((lrow >= 0) & (lrow < cap)).all()        # padding slots too

    # the same layout through the join: left row i has key i and
    # counts[i] matches, scattered over the right table
    rk = rng.permutation(np.repeat(np.arange(n, dtype=np.int32), counts))
    lt = mk({"k": np.arange(n, dtype=np.int32)}, capacity=cap)
    rt = mk({"k": rk, "rv": np.arange(len(rk), dtype=np.int32)},
            capacity=len(rk) + 3)
    out, overflow = L.join(lt, rt, left_on=["k"], how=how,
                           out_capacity=out_cap, return_overflow=True,
                           impl="sortmerge")
    got = out.to_numpy()
    assert int(overflow) == max(total - out_cap, 0)
    np.testing.assert_array_equal(got["k"], want_lrow)
    want_rv = [np.flatnonzero(rk == r)[s - offs[r]] if mc[r] else INT_NULL
               for s, r in zip(j, want_lrow)]
    np.testing.assert_array_equal(got["rv"], np.asarray(want_rv, np.int32))


def _loop_carry_shapes(fn, *args) -> list:
    """Shapes of every ``scan``/``while`` output anywhere in the jaxpr (a
    loop's outputs are its carry, plus a scan's stacked ys)."""
    shapes = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in ("scan", "while"):
                shapes.extend(v.aval.shape for v in eqn.outvars)
            for v in eqn.params.values():
                for x in (v if isinstance(v, (list, tuple)) else (v,)):
                    if hasattr(x, "jaxpr"):
                        walk(x.jaxpr)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return shapes


@pytest.mark.parametrize("how", ["inner", "left"])
def test_sortmerge_expand_has_no_per_slot_search(how, rng):
    """Mapping output slots to left rows is a scatter and a running max:
    no loop of the sortmerge join carries an ``out_capacity``-sized array
    (a per-slot binary search would).  The match step's search over the
    left rows is such a loop, which shows the walk sees loops at all."""
    out_cap = 203                      # no table capacity divides it
    lt = mk({"k": rng.integers(0, 10, 30), "lv": np.arange(30)},
            capacity=40)
    rt = mk({"k": rng.integers(0, 10, 20), "rv": np.arange(20)},
            capacity=25)
    shapes = _loop_carry_shapes(
        lambda a, b: L.join(a, b, left_on=["k"], how=how,
                            out_capacity=out_cap, impl="sortmerge"),
        lt, rt)
    assert (40,) in shapes, shapes
    assert not any(out_cap in s for s in shapes), shapes


def test_cartesian_product():
    lt = mk({"a": [1, 2]})
    rt = mk({"b": [10, 20, 30]})
    out = L.cartesian_product(lt, rt, out_capacity=8).to_numpy()
    assert len(out["a"]) == 6
    assert as_sets(out) == sorted(
        [(float(a), float(b)) for a in [1, 2] for b in [10, 20, 30]])


# --------------------------------------------------------------------------
# membership / set ops
# --------------------------------------------------------------------------


def test_isin():
    t = mk({"k": [1, 2, 3, 4]}, capacity=6)
    vals = mk({"v": [2, 4, 9]})
    mask = np.asarray(L.isin(t, "k", vals, "v"))
    np.testing.assert_array_equal(mask[:4], [False, True, False, True])
    assert not mask[4:].any()


def test_intersect_and_difference(rng, impl=None):
    a_keys = rng.integers(0, 12, 30)
    b_keys = rng.integers(0, 12, 30)
    a = mk({"k": a_keys}, capacity=40)
    b = mk({"k": b_keys}, capacity=40)
    inter = L.intersect(a, b, ["k"], impl=impl).to_numpy()["k"]
    diff = L.difference(a, b, ["k"], impl=impl).to_numpy()["k"]
    want_inter = np.intersect1d(a_keys, b_keys)
    np.testing.assert_array_equal(np.sort(inter), want_inter)
    want_diff = a_keys[~np.isin(a_keys, b_keys)]
    np.testing.assert_array_equal(np.sort(diff), np.sort(want_diff))


def test_union_dedups():
    a = mk({"k": [1, 2, 2]})
    b = mk({"k": [2, 3]})
    out = L.union(a, b).to_numpy()["k"]
    np.testing.assert_array_equal(np.sort(out), [1, 2, 3])


# --------------------------------------------------------------------------
# nulls / scaling
# --------------------------------------------------------------------------


def test_dropna_float_and_int():
    t = mk({"x": [1.0, np.nan, 3.0],
            "y": [1, 2, 3]})
    out = L.dropna(t, ["x"]).to_numpy()
    np.testing.assert_array_equal(out["y"], [1, 3])
    t2 = Table(columns={"y": jnp.array([1, INT_NULL, 3], jnp.int32)},
               nvalid=jnp.int32(3))
    out2 = L.dropna(t2, ["y"]).to_numpy()
    np.testing.assert_array_equal(out2["y"], [1, 3])


def test_fillna():
    t = mk({"x": [1.0, np.nan, 3.0]})
    out = L.fillna(t, {"x": -1.0}).to_numpy()
    np.testing.assert_allclose(out["x"], [1.0, -1.0, 3.0])


def test_isnull_masks_padding():
    t = mk({"x": [np.nan, 1.0]}, capacity=4)
    m = np.asarray(L.isnull(t, "x"))
    np.testing.assert_array_equal(m, [True, False, False, False])


def test_standard_scale(rng):
    vals = rng.normal(3.0, 2.5, 100).astype(np.float32)
    t = mk({"x": vals}, capacity=128)
    out = L.standard_scale(t, ["x"])
    live = out.to_numpy()["x"]
    assert abs(live.mean()) < 1e-4
    assert abs(live.std() - 1.0) < 1e-3


# --------------------------------------------------------------------------
# lex_searchsorted
# --------------------------------------------------------------------------


def test_lex_searchsorted_matches_numpy(rng):
    base = np.sort(rng.integers(0, 100, 50).astype(np.int32))
    q = rng.integers(-5, 105, 30).astype(np.int32)
    got_l = np.asarray(L.lex_searchsorted((jnp.asarray(base),),
                                          (jnp.asarray(q),), side="left"))
    got_r = np.asarray(L.lex_searchsorted((jnp.asarray(base),),
                                          (jnp.asarray(q),), side="right"))
    np.testing.assert_array_equal(got_l, np.searchsorted(base, q, "left"))
    np.testing.assert_array_equal(got_r, np.searchsorted(base, q, "right"))


def test_lex_searchsorted_two_keys():
    a = jnp.array([1, 1, 2, 2, 3], jnp.int32)
    b = jnp.array([1, 3, 1, 2, 0], jnp.int32)
    # query (2, 1): left insertion point is 2, right is 3
    lo = L.lex_searchsorted((a, b), (jnp.array([2]), jnp.array([1])),
                            side="left")
    hi = L.lex_searchsorted((a, b), (jnp.array([2]), jnp.array([1])),
                            side="right")
    assert int(lo[0]) == 2 and int(hi[0]) == 3


# --------------------------------------------------------------------------
# the tests above that run a family's default algorithm, on the family's
# other backend (which runs only when a caller names it)
# --------------------------------------------------------------------------

OTHER_BACKEND = [
    (test_sort_single_key, "radix"),
    (test_sort_is_stable, "radix"),
    (test_sort_multi_key_matches_lexsort, "radix"),
    (test_sort_descending, "radix"),
    (test_sort_descending_float, "radix"),
    (test_drop_duplicates, "hash"),
    (test_drop_duplicates_idempotent, "hash"),
    (test_groupby_sum_mean_count, "hash"),
    (test_groupby_min_max, "hash"),
    (test_inner_join_matches_oracle, "hash"),
    (test_intersect_and_difference, "hash"),
]


@pytest.mark.parametrize("test, impl", OTHER_BACKEND,
                         ids=[t.__name__[5:] for t, _ in OTHER_BACKEND])
def test_on_other_backend(test, impl, rng):
    test(rng, impl=impl)
