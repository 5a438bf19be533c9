"""Out-of-core morsel execution tests (core/morsel.py).

Host-side unit tests for the chunking source and the k-way run merge,
input-validation contracts, the distribute_table satellite fixes
(capacity validation, int32-range key refusal), and the world 1/2/4
subprocess conformance runs pinning chunked == monolithic.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from repro.core import dist_ops as D
from repro.core import morsel as M
from repro.core.context import make_context

from oracles import np_sort_values

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


@pytest.fixture(scope="module")
def ctx1():
    return make_context(jax.make_mesh((1,), ("rows",)))


# --------------------------------------------------------------------------
# ChunkedTable source
# --------------------------------------------------------------------------


def test_chunked_table_chunking():
    t = M.ChunkedTable({"a": np.arange(10)}, chunk_rows=4)
    assert t.num_chunks == 3
    assert [len(c["a"]) for c in t.chunks()] == [4, 4, 2]
    np.testing.assert_array_equal(
        np.concatenate([c["a"] for c in t.chunks()]), np.arange(10))


def test_chunked_table_empty_yields_one_terminal_morsel():
    t = M.ChunkedTable({"a": np.zeros(0, np.int32)}, chunk_rows=4)
    assert t.num_chunks == 1
    assert [len(c["a"]) for c in t.chunks()] == [0]


def test_chunked_table_fixed_capacity_per_shard():
    t = M.ChunkedTable({"a": np.arange(10)}, chunk_rows=4)
    assert t.capacity_per_shard(4) == 1
    assert t.capacity_per_shard(1) == 4


def test_chunked_table_validation():
    with pytest.raises(ValueError, match="chunk_rows"):
        M.ChunkedTable({"a": np.arange(3)}, chunk_rows=0)
    with pytest.raises(ValueError, match="equal length"):
        M.ChunkedTable({"a": np.arange(3), "b": np.arange(4)}, 2)
    with pytest.raises(ValueError, match="at least one column"):
        M.ChunkedTable({}, 2)


def test_chunked_table_distribute_constant_capacity(ctx1):
    t = M.ChunkedTable({"a": np.arange(10, dtype=np.int32)}, chunk_rows=4)
    caps = [g.capacity for g in t.distribute(ctx1)]
    assert caps == [4, 4, 4]          # last (smaller) chunk reuses the cap


# --------------------------------------------------------------------------
# k-way run merge (host side)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("ascending", [True, False])
def test_merge_sorted_runs_matches_stable_sort(ascending, rng):
    data = {"k": rng.integers(0, 9, 200).astype(np.int32),
            "v": np.arange(200, dtype=np.int32)}
    want = np_sort_values(data, ["k"], ascending=ascending)
    runs = []
    for lo in range(0, 200, 48):      # consecutive chunks, chunk-local sort
        chunk = {c: v[lo:lo + 48] for c, v in data.items()}
        runs.append(np_sort_values(chunk, ["k"], ascending=ascending))
    got = M.merge_sorted_runs(runs, ["k"], ascending=ascending)
    for c in want:                    # ties resolve to original row order
        np.testing.assert_array_equal(got[c], want[c], err_msg=c)


def test_merge_sorted_runs_descending_floats_and_multikey(rng):
    data = {"k": rng.integers(0, 5, 120).astype(np.float32),
            "s": rng.integers(0, 3, 120).astype(np.int32),
            "v": np.arange(120, dtype=np.int32)}
    want = np_sort_values(data, ["k", "s"], ascending=False)
    runs = [np_sort_values({c: v[lo:lo + 40] for c, v in data.items()},
                           ["k", "s"], ascending=False)
            for lo in range(0, 120, 40)]
    got = M.merge_sorted_runs(runs, ["k", "s"], ascending=False)
    for c in want:
        np.testing.assert_array_equal(got[c], want[c], err_msg=c)


def test_merge_sorted_runs_degenerate():
    assert M.merge_sorted_runs([], ["k"]) == {}
    one = {"k": np.arange(4, dtype=np.int32)}
    np.testing.assert_array_equal(
        M.merge_sorted_runs([one], ["k"])["k"], one["k"])
    empty = {"k": np.zeros(0, np.int32)}
    out = M.merge_sorted_runs([empty, one, empty], ["k"])
    np.testing.assert_array_equal(out["k"], one["k"])


# --------------------------------------------------------------------------
# operator argument validation
# --------------------------------------------------------------------------


def test_restream_left_join_rejected(ctx1):
    d = {"k": np.arange(4, dtype=np.int32)}
    with pytest.raises(ValueError, match="restream"):
        M.chunked_dist_join(ctx1, d, d, left_on=["k"], how="left",
                            build="restream")
    with pytest.raises(ValueError, match="how"):
        M.chunked_dist_join(ctx1, d, d, left_on=["k"], how="outer")
    with pytest.raises(ValueError, match="build"):
        M.chunked_dist_join(ctx1, d, d, left_on=["k"], build="nope")


# --------------------------------------------------------------------------
# distribute_table satellite fixes (capacity validation, dtype contract)
# --------------------------------------------------------------------------


def test_distribute_table_rejects_nonpositive_capacity(ctx1):
    data = {"k": np.arange(4, dtype=np.int32)}
    for bad in (0, -3):
        with pytest.raises(ValueError, match="must be positive"):
            D.distribute_table(ctx1, data, capacity_per_shard=bad)
    # None still means rows-per-shard (never coerced through `or`)
    g = D.distribute_table(ctx1, data, capacity_per_shard=None)
    assert g.capacity == 4


def test_distribute_table_rejects_out_of_int32_keys(ctx1):
    bad = {"k": np.array([1, 1 + 2 ** 32], dtype=np.int64)}
    with pytest.raises(ValueError, match="int32 range"):
        D.distribute_table(ctx1, bad)
    ok = D.distribute_table(
        ctx1, {"k": np.array([1, 2 ** 31 - 1], dtype=np.int64)})
    np.testing.assert_array_equal(
        np.asarray(ok.columns["k"]), [1, 2 ** 31 - 1])


def test_join_keys_around_2_31_no_false_matches(ctx1):
    """Regression: int64 keys 2^32 apart used to silently truncate to the
    same int32 bits and join as a false match; now ingestion raises."""
    left = {"k": np.array([1], dtype=np.int64),
            "lv": np.array([10.0], np.float32)}
    right = {"k": np.array([1 + 2 ** 32], dtype=np.int64),
             "rv": np.array([20.0], np.float32)}
    with pytest.raises(ValueError, match="false join matches"):
        M.chunked_dist_join(ctx1, left, right, left_on=["k"])
    # in-range int64 keys join exactly (no truncation of 2^31 - 1)
    right_ok = {"k": np.array([2 ** 31 - 1, 1], dtype=np.int64),
                "rv": np.array([20.0, 30.0], np.float32)}
    out, dropped = M.chunked_dist_join(ctx1, left, right_ok,
                                       left_on=["k"])
    assert dropped == 0
    np.testing.assert_array_equal(out["k"], [1])
    np.testing.assert_array_equal(out["rv"], [30.0])


# --------------------------------------------------------------------------
# world 1/2/4 conformance (subprocess, forced host devices)
# --------------------------------------------------------------------------


def _morsel_conformance(world, backend):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={world}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "dist", "morsel_conformance.py"),
         str(world), backend],
        env=env, capture_output=True, text=True, timeout=1800)
    sys.stdout.write(proc.stdout[-4000:])
    sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0, \
        f"morsel conformance failed (world={world}, {backend})"
    assert "MORSEL CONFORMANCE PASSED" in proc.stdout


@pytest.mark.parametrize("world", [1, 2, 4])
def test_morsel_conformance(world):
    _morsel_conformance(world, "default")


@pytest.mark.parametrize("world", [1, 2, 4])
def test_morsel_conformance_hash_backends(world):
    """Hash join and groupby, radix sort: reached only by argument."""
    _morsel_conformance(world, "hash")
