"""Semi-join / set-operator backend conformance suite (hash == sortmerge
== pandas oracle).

The two local membership backends promise *drop-in identical* output:
``isin``/``semi_mask`` emit the same boolean mask, ``difference`` the
same rows in ``a``'s original order, ``intersect``/``union`` the same
canonical table (one row per distinct key, sorted by key, keep-first
payload) — bit-identical rows, order and dtypes.  This suite pins that
contract over key distributions x kernel impls, pins the promoted-dtype
comparison rule (a float32 3.7 probe must NOT match an int32 3 — the
seed's cast-to-values-dtype bug), checks the hash path's jaxpr carries
**no ``sort`` primitive**, checks the static-capacity overflow counters
trip exactly at slab capacity, and runs the distributed set ops at world
sizes 1/2/4 in subprocesses with forced host devices.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import local_ops as L
from repro.core.table import Table

from oracles import np_difference, np_intersect, np_isin, np_union
from test_groupby_backends import _jaxpr_primitives, \
    assert_tables_identical

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

ROWS = 48

DISTS = ["uniform", "skewed", "allequal", "alldistinct", "empty"]


def make_pair(dist: str, rng):
    """(a, b) dicts sharing the schema {'k','v'} with overlapping-but-not-
    equal key sets, per key distribution."""
    if dist == "uniform":
        ka = rng.integers(0, 12, ROWS)
        kb = rng.integers(6, 18, ROWS // 2)
    elif dist == "skewed":                     # one heavy key + sparse tail
        ka = np.where(rng.random(ROWS) < 0.6, 3,
                      rng.integers(0, 40, ROWS))
        kb = np.where(rng.random(ROWS // 2) < 0.5, 3,
                      rng.integers(20, 60, ROWS // 2))
    elif dist == "allequal":
        ka = np.full(ROWS, 7)
        kb = np.full(ROWS // 2, 7)
    elif dist == "alldistinct":
        ka = rng.permutation(ROWS)
        kb = rng.permutation(ROWS)[:ROWS // 2] + ROWS // 2
    else:                                      # empty probe side
        ka = np.zeros(0, np.int64)
        kb = rng.integers(0, 12, ROWS // 2)
    a = {"k": ka.astype(np.int32),
         "v": rng.integers(-100, 100, len(ka)).astype(np.float32)}
    b = {"k": kb.astype(np.int32),
         "v": rng.integers(-100, 100, len(kb)).astype(np.float32)}
    return a, b


def tables(a, b, pad=5):
    n_a = len(next(iter(a.values())))
    n_b = len(next(iter(b.values())))
    ta = Table.from_dict(a, capacity=max(n_a, 1) + pad)
    tb = Table.from_dict(b, capacity=max(n_b, 1) + pad)
    return ta, tb


@pytest.mark.parametrize("dist", DISTS)
@pytest.mark.parametrize("kernel_impl", ["ref", "pallas_interpret"])
def test_isin_backends_identical(dist, kernel_impl, rng):
    a, b = make_pair(dist, rng)
    ta, tb = tables(a, b)
    ms, s_over = L.isin(ta, "k", tb, "k", impl="sortmerge",
                        return_overflow=True)
    mh, h_over = L.isin(ta, "k", tb, "k", impl="hash",
                        return_overflow=True, kernel_impl=kernel_impl)
    assert int(s_over) == int(h_over) == 0
    np.testing.assert_array_equal(np.asarray(ms), np.asarray(mh),
                                  err_msg=f"isin {dist}")
    want = np_isin(a, "k", b, "k")
    np.testing.assert_array_equal(np.asarray(ms)[:len(a["k"])], want,
                                  err_msg=f"isin {dist} vs oracle")
    # padding rows are never members
    assert not np.asarray(ms)[len(a["k"]):].any()


@pytest.mark.parametrize("dist", DISTS)
@pytest.mark.parametrize("op", ["difference", "intersect", "union"])
def test_setop_backends_identical(dist, op, rng):
    a, b = make_pair(dist, rng)
    ta, tb = tables(a, b)
    if op == "union":
        # union's impl selects the *dedup* backend (sort | hash)
        s = L.union(ta, tb, on=["k"], impl="sort")
        h, over = L.union(ta, tb, on=["k"], impl="hash",
                          return_overflow=True)
        want = np_union(a, b, ["k"])
    else:
        fn = getattr(L, op)
        s = fn(ta, tb, on=["k"], impl="sortmerge")
        h, over = fn(ta, tb, on=["k"], impl="hash", return_overflow=True)
        want = (np_difference if op == "difference"
                else np_intersect)(a, b, ["k"])
    assert int(over) == 0
    assert int(s.nvalid) == int(h.nvalid)
    assert_tables_identical(s.to_numpy(), h.to_numpy(), f"{op} {dist}")
    got = h.to_numpy()
    for c in want:
        np.testing.assert_array_equal(got[c], want[c].astype(got[c].dtype),
                                      err_msg=f"{op} {dist} col={c}")


def test_isin_promoted_dtype_no_false_positives(rng):
    """The seed bug: isin cast the query column to the values column's
    dtype, so a float32 3.7 probe truncated to int32 3 and matched.  Both
    backends must now compare in the promoted common dtype: 3.7 is NOT a
    member, 3.0 IS."""
    q = Table.from_dict({"x": np.array([3.7, 3.0, -1.5, 2.0],
                                       np.float32)}, capacity=6)
    vals = Table.from_dict({"y": np.array([3, 2, 9], np.int32)},
                           capacity=4)
    want = np.array([False, True, False, True])
    for impl in ("sortmerge", "hash"):
        got = np.asarray(L.isin(q, "x", vals, "y", impl=impl))[:4]
        np.testing.assert_array_equal(got, want, err_msg=impl)
    # and the mirrored direction: int probe vs float values — int 3
    # matches 3.0 but nothing matches 3.5
    q2 = Table.from_dict({"x": np.array([3, 4], np.int32)}, capacity=4)
    v2 = Table.from_dict({"y": np.array([3.0, 3.5], np.float32)},
                         capacity=4)
    for impl in ("sortmerge", "hash"):
        got = np.asarray(L.isin(q2, "x", v2, "y", impl=impl))[:2]
        np.testing.assert_array_equal(got, [True, False], err_msg=impl)


def test_multi_and_mixed_dtype_keys(rng):
    """int32 + float32 key columns, compared pairwise in promoted dtype:
    both backends agree bit-identically and with the oracle."""
    n = 40
    a = {"ik": rng.integers(0, 4, n).astype(np.int32),
         "fk": (rng.integers(-3, 4, n) * 0.5).astype(np.float32),
         "v": rng.integers(-50, 50, n).astype(np.float32)}
    b = {"ik": rng.integers(0, 4, n // 2).astype(np.int32),
         "fk": (rng.integers(-3, 4, n // 2) * 0.5).astype(np.float32),
         "v": rng.integers(-50, 50, n // 2).astype(np.float32)}
    ta, tb = tables(a, b)
    on = ["ik", "fk"]
    ms = L.semi_mask(ta, tb, on, impl="sortmerge")
    mh = L.semi_mask(ta, tb, on, impl="hash")
    np.testing.assert_array_equal(np.asarray(ms), np.asarray(mh))
    for op, oracle in (("difference", np_difference),
                       ("intersect", np_intersect)):
        s = getattr(L, op)(ta, tb, on=on, impl="sortmerge")
        h = getattr(L, op)(ta, tb, on=on, impl="hash")
        assert_tables_identical(s.to_numpy(), h.to_numpy(), op)
        want = oracle(a, b, on)
        got = h.to_numpy()
        for c in want:
            np.testing.assert_array_equal(
                got[c], want[c].astype(got[c].dtype),
                err_msg=f"mixed {op} col={c}")


def test_union_respects_key_subset_and_tie_order(rng):
    """The seed's union had no ``on=``: dedup ran over ALL columns, so
    rows equal on the key but different in payload both survived.  With
    ``on=`` the output has one row per key, payload from the key's first
    occurrence — ``a``'s rows win ties against ``b``'s."""
    a = {"k": np.array([1, 2], np.int32),
         "v": np.array([10., 20.], np.float32)}
    b = {"k": np.array([2, 3], np.int32),
         "v": np.array([99., 30.], np.float32)}
    ta, tb = tables(a, b)
    for impl in ("sort", "hash"):
        u = L.union(ta, tb, on=["k"], impl=impl).to_numpy()
        np.testing.assert_array_equal(u["k"], [1, 2, 3], err_msg=impl)
        np.testing.assert_array_equal(u["v"], [10., 20., 30.],
                                      err_msg=impl)  # a's v=20 wins
    # backward compat: no on= dedups full rows, both (2,20) and (2,99) stay
    full = L.union(ta, tb).to_numpy()
    assert len(full["k"]) == 4


def test_union_counts_overflow(rng):
    """Union overflow is counted, never silent: all-equal keys with a slab
    smaller than the group trip the dedup backend's counter."""
    n = 16
    a = {"k": np.full(n, 1, np.int32),
         "v": np.arange(n, dtype=np.float32)}
    b = {"k": np.full(n, 1, np.int32),
         "v": np.arange(n, dtype=np.float32)}
    ta, tb = tables(a, b, pad=0)
    u, over = L.union(ta, tb, on=["k"], impl="hash", return_overflow=True,
                      num_buckets=4, bucket_capacity=8)
    assert int(u.nvalid) == 1
    assert int(over) == 2 * n - 8


def test_semi_overflow_counters_trip_at_capacity():
    """All-equal keys with slabs smaller than the group: build and probe
    overflow are both counted; a probe-dropped row reports non-member
    (excluded from difference's complement too — it is counted, not
    guessed)."""
    n = 24
    t = Table.from_dict({"k": np.full(n, 1, np.int32)}, capacity=n)
    vals = Table.from_dict({"k": np.full(4, 1, np.int32)}, capacity=4)
    # probe side overflows: only probe_capacity probes fit the slab
    mask, over = L.isin(t, "k", vals, "k", impl="hash", num_buckets=4,
                        probe_capacity=8, return_overflow=True)
    assert int(over) == n - 8
    assert int(np.asarray(mask).sum()) == 8
    # build side overflows: members still resolve from surviving builds
    mask, over = L.isin(vals, "k", t, "k", impl="hash", num_buckets=4,
                        bucket_capacity=8, return_overflow=True)
    assert int(over) == n - 8
    assert int(np.asarray(mask).sum()) == 4


def test_cartesian_product_counts_overflow(rng):
    """The seed bug: cartesian_product clamped ``nvalid`` to the output
    capacity with no signal that rows were lost."""
    a = Table.from_dict({"k": np.arange(4, dtype=np.int32)}, capacity=4)
    b = Table.from_dict({"j": np.arange(3, dtype=np.int32)}, capacity=4)
    out, over = L.cartesian_product(a, b, out_capacity=8,
                                    return_overflow=True)
    assert int(out.nvalid) == 8
    assert int(over) == 4            # 4*3 = 12 pairs, 8 slots
    out2, over2 = L.cartesian_product(a, b, out_capacity=16,
                                      return_overflow=True)
    assert int(out2.nvalid) == 12
    assert int(over2) == 0
    # default signature unchanged (no tuple)
    assert isinstance(L.cartesian_product(a, b, out_capacity=8), Table)


@pytest.mark.parametrize("capacity", [ROWS + 5, 4096],
                         ids=["small", "above_exact_slab"])
def test_hash_path_contains_no_sort_primitive(capacity, rng):
    """The acceptance contract: the hash semi backend replaces the
    sort-based membership entirely — its jaxpr must not contain `sort`,
    at small capacities (full-capacity slabs) AND above ``EXACT_SLAB_CAP``
    where auto-sizing switches to the bucket-count heuristic."""
    a, b = make_pair("uniform", rng)
    ta = Table.from_dict(a, capacity=capacity)
    tb = Table.from_dict(b, capacity=capacity)
    prims = _jaxpr_primitives(
        lambda x, y: L.isin(x, "k", y, "k", impl="hash"), ta, tb)
    assert "sort" not in prims, sorted(prims)
    prims = _jaxpr_primitives(
        lambda x, y: L.difference(x, y, on=["k"], impl="hash"), ta, tb)
    assert "sort" not in prims, sorted(prims)
    prims = _jaxpr_primitives(
        lambda x, y: L.intersect(x, y, on=["k"], impl="hash",
                                 dedup_impl="hash"), ta, tb)
    assert "sort" not in prims, sorted(prims)
    # the sortmerge backend, for contrast, does sort
    prims = _jaxpr_primitives(
        lambda x, y: L.isin(x, "k", y, "k", impl="sortmerge"), ta, tb)
    assert "sort" in prims


def test_env_default_backend(rng):
    a, b = make_pair("uniform", rng)
    ta, tb = tables(a, b)
    assert L.SEMI_IMPL == "sortmerge"
    default = np.asarray(L.isin(ta, "k", tb, "k"))
    for impl in ("sortmerge", "hash"):
        np.testing.assert_array_equal(
            np.asarray(L.isin(ta, "k", tb, "k", impl=impl)), default,
            err_msg=f"default vs {impl}")
    with pytest.raises(ValueError):
        L.isin(ta, "k", tb, "k", impl="nope")
    with pytest.raises(ValueError):
        L.difference(ta, tb, on=["k"], impl="nope")


def test_join_backends_promote_mixed_key_dtypes(rng):
    """The promoted-dtype rule extends to the join backends: a float32
    3.7 probe must not join an int32 3 build row, and both backends must
    agree."""
    left = Table.from_dict({"k": np.array([3.7, 3.0], np.float32),
                            "lv": np.array([0., 1.], np.float32)},
                           capacity=4)
    right = Table.from_dict({"k": np.array([3], np.int32),
                             "rv": np.array([7.], np.float32)},
                            capacity=2)
    for impl in ("sortmerge", "hash"):
        out = L.join(left, right, left_on=["k"], out_capacity=8,
                     impl=impl).to_numpy()
        assert len(out["k"]) == 1, impl
        assert out["lv"][0] == 1.0, impl     # only the 3.0 row joined


@pytest.mark.parametrize("world", [1, 2, 4])
def test_dist_setop_conformance(world):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={world}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(HERE, "dist", "setop_conformance.py"), str(world)],
        env=env, capture_output=True, text=True, timeout=1800)
    sys.stdout.write(proc.stdout[-4000:])
    sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0, \
        f"setop conformance failed (world={world})"
    assert "SETOP CONFORMANCE PASSED" in proc.stdout


def test_fused_semi_plan_three_scatters():
    """One stacked scatter per slab family on the fused bucketing path:
    build slabs, probe slabs, packed member/probed result — exactly three
    ``scatter`` eqns in the semi plan's jaxpr."""
    import jax.numpy as jnp
    from repro.kernels.hash_semi import hash_semi_plan
    from test_groupby_backends import _count_scatter_eqns
    n = 64
    bits = (jnp.arange(n, dtype=jnp.int32),)
    valid = jnp.ones((n,), bool)
    cnt = _count_scatter_eqns(
        lambda b, v: hash_semi_plan(b, v, b, v, num_buckets=8,
                                    bucket_capacity=16, probe_capacity=16,
                                    impl="ref"), bits, valid)
    assert cnt == 3, cnt


@pytest.mark.parametrize("op", ["isin", "difference", "intersect"])
def test_hash_semi_key_bits_once_per_side(op, monkeypatch, rng):
    """BucketPlan extracts the key bit-planes exactly once per side and
    shares them between the sizing pass and the build/probe kernel plan
    — no re-hash between build and probe of the same columns."""
    from repro.kernels import bucketing
    calls = []
    real = bucketing.key_bits

    def counting(col):
        calls.append(col.shape)
        return real(col)

    monkeypatch.setattr(bucketing, "key_bits", counting)
    a, b = make_pair("uniform", rng)
    ta, tb = tables(a, b)
    if op == "isin":
        L.isin(ta, "k", tb, "k", impl="hash")
        expect = 2                     # probe side + values side
    elif op == "difference":
        L.difference(ta, tb, ["k"], impl="hash")
        expect = 2
    else:
        L.intersect(ta, tb, ["k"], impl="hash", dedup_impl="hash")
        expect = 3                     # semi (2 sides) + key-only dedup
    assert len(calls) == expect, calls
