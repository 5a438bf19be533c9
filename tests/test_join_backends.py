"""Join backend conformance suite (hash == sort-merge == numpy oracle).

The two local join backends promise *drop-in identical* output — same
rows, same order (left-row-major; within a left row, matches in the right
table's original row order).  This suite pins that contract over
randomized key distributions x join types x kernel impls, checks the
static-capacity overflow counters trip exactly at capacity, and runs the
distributed join at world sizes 1/2/4 in subprocesses with forced host
devices (the in-process suite keeps the single real CPU device).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import local_ops as L
from repro.core.table import Table

from oracles import np_join

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

ROWS = 48


def make_sides(dist: str, rng):
    if dist == "unique":
        lk = rng.permutation(np.arange(ROWS, dtype=np.int32))
        rk = rng.permutation(np.arange(ROWS, dtype=np.int32))
    elif dist == "dup10":             # the paper's 10%-key-uniqueness
        nk = max(ROWS // 10, 1)
        lk = rng.integers(0, nk, ROWS).astype(np.int32)
        rk = rng.integers(0, nk, ROWS).astype(np.int32)
    elif dist == "alldup":
        lk = np.full(ROWS, 3, np.int32)
        rk = np.full(ROWS, 3, np.int32)
    elif dist == "empty_left":
        lk = np.zeros(0, np.int32)
        rk = rng.integers(0, 8, ROWS).astype(np.int32)
    elif dist == "empty_right":
        lk = rng.integers(0, 8, ROWS).astype(np.int32)
        rk = np.zeros(0, np.int32)
    else:                             # both sides empty
        lk = rk = np.zeros(0, np.int32)
    left = {"k": lk, "lv": rng.normal(size=len(lk)).astype(np.float32)}
    right = {"k": rk, "rv": rng.normal(size=len(rk)).astype(np.float32)}
    return left, right


def assert_tables_equal(a: dict, b: dict, msg=""):
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(np.nan_to_num(a[k], nan=-1e9),
                                      np.nan_to_num(b[k], nan=-1e9),
                                      err_msg=f"{msg} col={k}")


DISTS = ["unique", "dup10", "alldup", "empty_left", "empty_right",
         "empty_both"]
OUT_CAP = ROWS * ROWS + ROWS          # alldup worst case


@pytest.mark.parametrize("dist", DISTS)
@pytest.mark.parametrize("how", ["inner", "left"])
@pytest.mark.parametrize("kernel_impl", ["ref", "pallas_interpret"])
def test_local_backends_identical(dist, how, kernel_impl, rng):
    left, right = make_sides(dist, rng)
    lt = Table.from_dict(left, capacity=max(len(left["k"]), 1) + 5)
    rt = Table.from_dict(right, capacity=max(len(right["k"]), 1) + 3)
    sm, sm_over = L.join(lt, rt, left_on=["k"], how=how,
                         out_capacity=OUT_CAP, return_overflow=True,
                         impl="sortmerge")
    hj, hj_over = L.join(lt, rt, left_on=["k"], how=how,
                         out_capacity=OUT_CAP, return_overflow=True,
                         impl="hash", num_buckets=8,
                         bucket_capacity=max(ROWS, 8),
                         probe_capacity=max(ROWS, 8),
                         kernel_impl=kernel_impl)
    assert int(sm.nvalid) == int(hj.nvalid)
    assert int(sm_over) == int(hj_over) == 0
    assert_tables_equal(sm.to_numpy(), hj.to_numpy(), f"{dist}/{how}")
    assert_tables_equal(hj.to_numpy(), np_join(left, right, how),
                        f"{dist}/{how} vs oracle")


@pytest.mark.parametrize("how", ["inner", "left"])
def test_multi_key_and_renamed_keys(how, rng):
    left = {"a": rng.integers(0, 4, 30).astype(np.int32),
            "b": rng.integers(0, 3, 30).astype(np.int32),
            "lv": rng.normal(size=30).astype(np.float32)}
    right = {"a": rng.integers(0, 4, 25).astype(np.int32),
             "b": rng.integers(0, 3, 25).astype(np.int32),
             "rv": rng.normal(size=25).astype(np.float32)}
    lt = Table.from_dict(left, capacity=34)
    rt = Table.from_dict(right, capacity=29)
    kw = dict(left_on=["a", "b"], how=how, out_capacity=512,
              return_overflow=True)
    sm, so = L.join(lt, rt, impl="sortmerge", **kw)
    hj, ho = L.join(lt, rt, impl="hash", num_buckets=4,
                    bucket_capacity=32, probe_capacity=32, **kw)
    assert int(so) == int(ho) == 0
    assert_tables_equal(sm.to_numpy(), hj.to_numpy(), f"multikey/{how}")


def test_overflow_counters_trip_at_capacity(rng):
    """alldup keys with slabs below the duplicate count: dropped rows are
    counted, surviving matches are exact."""
    n = 24
    left = {"k": np.full(n, 1, np.int32),
            "lv": np.arange(n, dtype=np.float32)}
    right = {"k": np.full(n, 1, np.int32),
             "rv": np.arange(n, dtype=np.float32)}
    lt = Table.from_dict(left, capacity=n)
    rt = Table.from_dict(right, capacity=n)
    # build-side overflow: chains hold 8 of 24 right rows
    out, over = L.join(lt, rt, left_on=["k"], out_capacity=n * n,
                       return_overflow=True, impl="hash", num_buckets=4,
                       bucket_capacity=8, probe_capacity=n)
    assert int(out.nvalid) == n * 8
    assert int(over) == n - 8
    # probe-side overflow: only 8 of 24 left rows probe
    out, over = L.join(lt, rt, left_on=["k"], out_capacity=n * n,
                       return_overflow=True, impl="hash", num_buckets=4,
                       bucket_capacity=n, probe_capacity=8)
    assert int(out.nvalid) == 8 * n
    assert int(over) == n - 8
    # left join: probe-dropped rows are DROPPED (counted), never emitted
    # as fake unmatched rows with nulled right columns
    out, over = L.join(lt, rt, left_on=["k"], how="left",
                       out_capacity=n * n, return_overflow=True,
                       impl="hash", num_buckets=4, bucket_capacity=n,
                       probe_capacity=8)
    assert int(out.nvalid) == 8 * n
    assert int(over) == n - 8
    assert not np.isnan(out.to_numpy()["rv"]).any()
    # out_capacity overflow: identical truncation to sort-merge
    for impl, kw in (("sortmerge", {}),
                     ("hash", dict(num_buckets=4, bucket_capacity=n,
                                   probe_capacity=n))):
        out, over = L.join(lt, rt, left_on=["k"], out_capacity=100,
                           return_overflow=True, impl=impl, **kw)
        assert int(out.nvalid) == 100, impl
        assert int(over) == n * n - 100, impl


def test_env_default_backend(rng):
    # "unique" keys: within the auto-sizing heuristic's contract (heavy
    # duplication needs explicit bucket sizing, see default_hash_join_sizes)
    left, right = make_sides("unique", rng)
    lt = Table.from_dict(left, capacity=ROWS)
    rt = Table.from_dict(right, capacity=ROWS)
    assert L.JOIN_IMPL == "sortmerge"
    default = L.join(lt, rt, left_on=["k"], out_capacity=OUT_CAP)
    for impl in ("sortmerge", "hash"):
        got = L.join(lt, rt, left_on=["k"], out_capacity=OUT_CAP, impl=impl)
        assert_tables_equal(got.to_numpy(), default.to_numpy(),
                            f"default vs {impl}")
    with pytest.raises(ValueError):
        L.join(lt, rt, left_on=["k"], impl="nope")


@pytest.mark.parametrize("world", [1, 2, 4])
def test_dist_join_conformance(world):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={world}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(HERE, "dist", "join_conformance.py"), str(world)],
        env=env, capture_output=True, text=True, timeout=1800)
    sys.stdout.write(proc.stdout[-4000:])
    sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0, f"join conformance failed (world={world})"
    assert "JOIN CONFORMANCE PASSED" in proc.stdout


def test_fused_join_plan_three_scatters():
    """The fused bucketing path issues ONE stacked scatter per slab
    family: build slabs, probe slabs, and the packed match-counts/probed
    result — exactly three ``scatter`` eqns in the join plan's jaxpr,
    regardless of key-column count."""
    import jax.numpy as jnp
    from repro.kernels.hash_join import hash_join_plan
    from test_groupby_backends import _count_scatter_eqns
    n = 64
    bits = (jnp.arange(n, dtype=jnp.int32),
            jnp.arange(n, dtype=jnp.int32) % 7)
    valid = jnp.ones((n,), bool)
    cnt = _count_scatter_eqns(
        lambda b, v: hash_join_plan(b, v, b, v, num_buckets=8,
                                    bucket_capacity=16, probe_capacity=16,
                                    impl="ref"), bits, valid)
    assert cnt == 3, cnt
