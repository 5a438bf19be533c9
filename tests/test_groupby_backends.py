"""GroupBy/Aggregate backend conformance suite (hash == sort == pandas
oracle).

The two local aggregation backends promise *drop-in identical* output —
the canonical table: one row per distinct key, rows sorted by the key
columns, counts int32, value aggregates float32.  This suite pins that
contract over key distributions x agg sets x kernel impls, checks the
hash path's jaxpr carries **no ``sort`` primitive**, checks the
static-capacity overflow counter trips exactly at bucket capacity, and
runs the distributed groupby/unique/standard-scale at world sizes 1/2/4
in subprocesses with forced host devices.

Value columns are *integer-valued* floats: float addition is then exact
in any association, so even ``sum``/``mean`` are bit-identical across
backends (the canonicalization contract: with arbitrary floats the
backends agree to addition-order rounding — see kernels/README.md).
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import local_ops as L
from repro.core.table import Table

from oracles import np_drop_duplicates, np_groupby_aggregate, \
    np_standard_scale

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

ROWS = 48

DISTS = ["uniform", "skewed", "allequal", "alldistinct", "empty"]

AGG_SETS = [
    {"v": ["sum", "count"]},
    {"v": ["mean", "min", "max"], "w": "sum"},
    {"v": ["sum", "count", "mean", "min", "max"],
     "w": ["min", "count"]},
]


def make_data(dist: str, rng) -> dict:
    if dist == "uniform":
        k = rng.integers(0, 12, ROWS)
    elif dist == "skewed":                     # one heavy key + sparse tail
        k = np.where(rng.random(ROWS) < 0.6, 3,
                     rng.integers(0, 40, ROWS))
    elif dist == "allequal":
        k = np.full(ROWS, 7)
    elif dist == "alldistinct":
        k = rng.permutation(ROWS)
    else:                                      # empty
        k = np.zeros(0, np.int64)
    n = len(k)
    return {"k": k.astype(np.int32),
            # integer-valued floats -> exact sums in any addition order
            "v": rng.integers(-100, 100, n).astype(np.float32),
            "w": rng.integers(0, 50, n).astype(np.float32)}


def assert_tables_identical(a: dict, b: dict, msg=""):
    assert set(a) == set(b), msg
    for c in a:
        assert a[c].dtype == b[c].dtype, f"{msg} col={c} dtype"
        np.testing.assert_array_equal(a[c], b[c], err_msg=f"{msg} col={c}")


def run_both(t: Table, by, aggs, kernel_impl="ref"):
    s, s_over = L.groupby_aggregate(t, by, aggs, impl="sort",
                                    return_overflow=True)
    h, h_over = L.groupby_aggregate(t, by, aggs, impl="hash",
                                    return_overflow=True,
                                    kernel_impl=kernel_impl)
    assert int(s_over) == int(h_over) == 0
    assert int(s.nvalid) == int(h.nvalid)
    return s, h


@pytest.mark.parametrize("dist", DISTS)
@pytest.mark.parametrize("aggs", AGG_SETS, ids=["sum_count", "mmm_wsum",
                                                "all_aggs"])
@pytest.mark.parametrize("kernel_impl", ["ref", "pallas_interpret"])
def test_local_backends_identical(dist, aggs, kernel_impl, rng):
    data = make_data(dist, rng)
    t = Table.from_dict(data, capacity=max(len(data["k"]), 1) + 5)
    s, h = run_both(t, ["k"], aggs, kernel_impl)
    assert_tables_identical(s.to_numpy(), h.to_numpy(), f"{dist}")
    want = np_groupby_aggregate(data, ["k"], aggs)
    got = h.to_numpy()
    assert set(got) == set(want)
    for c in want:
        # integer-valued data: exact agreement with the float64 oracle
        np.testing.assert_array_equal(
            got[c].astype(np.float64), want[c].astype(np.float64),
            err_msg=f"{dist} vs oracle col={c}")
    if "v_count" in got:
        assert got["v_count"].dtype == np.int32


def test_multi_and_mixed_dtype_keys(rng):
    """int32 + float32 key columns: bit-plane equality and the pairwise
    canonical rank must match the sort backend's lexicographic order."""
    n = 40
    data = {"ik": rng.integers(0, 4, n).astype(np.int32),
            "fk": (rng.integers(-3, 4, n) * 0.5).astype(np.float32),
            "v": rng.integers(-50, 50, n).astype(np.float32)}
    t = Table.from_dict(data, capacity=n + 3)
    aggs = {"v": ["sum", "count", "mean", "min", "max"]}
    s, h = run_both(t, ["ik", "fk"], aggs)
    assert_tables_identical(s.to_numpy(), h.to_numpy(), "mixed keys")
    want = np_groupby_aggregate(data, ["ik", "fk"], aggs)
    got = h.to_numpy()
    for c in want:
        np.testing.assert_array_equal(got[c].astype(np.float64),
                                      want[c].astype(np.float64),
                                      err_msg=f"mixed keys col={c}")


@pytest.mark.parametrize("dist", DISTS)
def test_dedup_backends_identical(dist, rng):
    data = make_data(dist, rng)
    t = Table.from_dict(data, capacity=max(len(data["k"]), 1) + 4)
    ds = L.drop_duplicates(t, ["k"], impl="sort")
    dh, over = L.drop_duplicates(t, ["k"], impl="hash",
                                 return_overflow=True)
    assert int(over) == 0
    assert_tables_identical(ds.to_numpy(), dh.to_numpy(), f"dedup {dist}")
    want = np_drop_duplicates(data, ["k"])
    got = dh.to_numpy()
    for c in want:   # payload rows come from each key's FIRST occurrence
        np.testing.assert_array_equal(got[c], want[c].astype(got[c].dtype),
                                      err_msg=f"dedup {dist} col={c}")


def test_standard_scale_impls_agree(rng):
    data = {"x": rng.normal(size=50).astype(np.float32),
            "y": rng.normal(size=50).astype(np.float32)}
    t = Table.from_dict(data, capacity=64)
    want = np_standard_scale(data, ["x", "y"])
    for impl in (None, "sort", "hash"):
        got = L.standard_scale(t, ["x", "y"], impl=impl).to_numpy()
        for c in ("x", "y"):
            np.testing.assert_allclose(got[c], want[c], rtol=1e-4,
                                       atol=1e-4, err_msg=f"{impl}/{c}")


def test_standard_scale_large_mean_is_stable(rng):
    """|mean| >> std: the two-pass variance must not cancel (the one-pass
    E[x^2] - m^2 form collapses to ~0 variance in float32 here and blows
    the scaled values up ~1e3x)."""
    x = (16000.0 + 0.1 * rng.normal(size=64)).astype(np.float32)
    t = Table.from_dict({"x": x}, capacity=64)
    for impl in (None, "sort", "hash"):
        got = L.standard_scale(t, ["x"], impl=impl).to_numpy()["x"]
        assert np.isfinite(got).all(), impl
        np.testing.assert_allclose(got.std(), 1.0, atol=0.05,
                                   err_msg=str(impl))
        np.testing.assert_allclose(got.mean(), 0.0, atol=0.05,
                                   err_msg=str(impl))


def _jaxpr_primitives(fn, *args):
    prims = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            prims.add(eqn.primitive.name)
            for v in eqn.params.values():
                for x in (v if isinstance(v, (list, tuple)) else (v,)):
                    if hasattr(x, "jaxpr"):
                        walk(x.jaxpr)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return prims


def _count_scatter_eqns(fn, *args) -> int:
    """Number of ``scatter`` eqns (``.at[].set``) anywhere in the jaxpr —
    the fused-bucketing regression pin: one stacked scatter per slab
    family, not one scatter per column."""
    n = 0

    def walk(jaxpr):
        nonlocal n
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scatter":
                n += 1
            for v in eqn.params.values():
                for x in (v if isinstance(v, (list, tuple)) else (v,)):
                    if hasattr(x, "jaxpr"):
                        walk(x.jaxpr)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return n


def test_fused_groupby_plan_single_scatter():
    """The fused bucketing path writes ALL slab columns — key planes,
    occupancy, row ids and every value payload — with ONE stacked
    scatter: the groupby plan's jaxpr carries exactly one ``scatter``
    eqn, however many value columns ride along."""
    import jax.numpy as jnp
    from repro.kernels.hash_groupby import hash_groupby_plan
    n = 64
    bits = (jnp.arange(n, dtype=jnp.int32),)
    valid = jnp.ones((n,), bool)
    vals = (jnp.ones((n,), jnp.float32), jnp.zeros((n,), jnp.float32))
    cnt = _count_scatter_eqns(
        lambda b, v, w: hash_groupby_plan(b, v, w, num_buckets=8,
                                          bucket_capacity=16, impl="ref"),
        bits, valid, vals)
    assert cnt == 1, cnt


@pytest.mark.parametrize("capacity", [ROWS + 5, 4096],
                         ids=["small", "above_exact_slab"])
def test_hash_path_contains_no_sort_primitive(capacity, rng):
    """The acceptance contract: the hash backend replaces the sort-based
    groupby/dedup entirely — its jaxpr must not contain `sort`, at small
    capacities (full-capacity slabs) AND above ``EXACT_SLAB_CAP`` where
    auto-sizing switches to the bucket-count heuristic (which must stay
    within the radix ranking's sort-free range)."""
    data = make_data("uniform", rng)
    t = Table.from_dict(data, capacity=capacity)
    aggs = {"v": ["sum", "count", "mean", "min", "max"]}
    prims = _jaxpr_primitives(
        lambda tt: L.groupby_aggregate(tt, ["k"], aggs, impl="hash"), t)
    assert "sort" not in prims, sorted(prims)
    prims = _jaxpr_primitives(
        lambda tt: L.drop_duplicates(tt, ["k"], impl="hash"), t)
    assert "sort" not in prims, sorted(prims)
    # the sort backend, for contrast, does sort
    prims = _jaxpr_primitives(
        lambda tt: L.groupby_aggregate(tt, ["k"], aggs, impl="sort"), t)
    assert "sort" in prims


def test_overflow_counter_trips_at_capacity():
    """All-equal keys with a bucket slab smaller than the group: surviving
    rows aggregate exactly, the rest are counted as dropped."""
    n = 24
    t = Table.from_dict({"k": np.full(n, 1, np.int32),
                         "v": np.arange(n, dtype=np.float32)},
                        capacity=n)
    out, over = L.groupby_aggregate(t, ["k"], {"v": ["sum", "count"]},
                                    impl="hash", return_overflow=True,
                                    num_buckets=4, bucket_capacity=8)
    assert int(out.nvalid) == 1
    assert int(over) == n - 8
    got = out.to_numpy()
    # slabs keep original row order: the first 8 rows survive
    assert got["v_count"][0] == 8
    assert got["v_sum"][0] == float(np.arange(8).sum())
    # dedup counts the same overflow
    dd, over = L.drop_duplicates(t, ["k"], impl="hash",
                                 return_overflow=True, num_buckets=4,
                                 bucket_capacity=8)
    assert int(dd.nvalid) == 1
    assert int(over) == n - 8


def test_env_default_backend(rng):
    data = make_data("uniform", rng)
    t = Table.from_dict(data, capacity=ROWS)
    assert L.GROUPBY_IMPL == "sort"
    default = L.groupby_aggregate(t, ["k"], {"v": "sum"})
    for impl in ("sort", "hash"):
        got = L.groupby_aggregate(t, ["k"], {"v": "sum"}, impl=impl)
        assert_tables_identical(got.to_numpy(), default.to_numpy(),
                                f"default vs {impl}")
    with pytest.raises(ValueError):
        L.groupby_aggregate(t, ["k"], {"v": "sum"}, impl="nope")
    with pytest.raises(ValueError):
        L.drop_duplicates(t, ["k"], impl="nope")


def test_counts_are_int32(rng):
    data = make_data("uniform", rng)
    t = Table.from_dict(data, capacity=ROWS)
    for impl in ("sort", "hash"):
        out = L.groupby_aggregate(t, ["k"], {"v": "count"}, impl=impl)
        assert out.columns["v_count"].dtype == np.int32, impl
    assert L.aggregate(t, "v", "count").dtype == np.int32


@pytest.mark.parametrize("world", [1, 2, 4])
def test_dist_groupby_conformance(world):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={world}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(HERE, "dist", "groupby_conformance.py"), str(world)],
        env=env, capture_output=True, text=True, timeout=1800)
    sys.stdout.write(proc.stdout[-4000:])
    sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0, \
        f"groupby conformance failed (world={world})"
    assert "GROUPBY CONFORMANCE PASSED" in proc.stdout
