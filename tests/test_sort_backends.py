"""OrderBy backend conformance suite (radix == xla, == pandas oracle).

The two local sort backends promise *drop-in bit-identical* output — same
rows, same order, same dtypes, including the stable order of equal keys
and the padding region (contract 1 in kernels/README.md).  This suite
pins that contract over key distributions x multi-key/ascending-mix
specs x kernel impls, checks the radix path's jaxpr carries **no
``sort`` primitive** (the acceptance bar: OrderBy without a sort), checks
the 1-bit compaction fast path (``compact``/``select``) is bit-identical
to the stable boolean argsort it replaced, and runs the distributed
sample-sort at world sizes 1/2/4 in subprocesses with forced host
devices (``tests/dist/sort_conformance.py``), including a shard-skew
regression at world 4.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import local_ops as L
from repro.core.table import Table

from oracles import np_sort_values

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

ROWS = 48

DISTS = ["uniform", "skewed", "allequal", "alldistinct", "empty"]

# (by, ascending): single/multi key, per-key ascending mixes, int+float
KEYSPECS = [
    (["k"], True),
    (["k"], False),
    (["k", "f"], [True, False]),
    (["f", "k"], [False, True]),
    (["f", "k", "rid"], True),
]


def make_data(dist: str, rng) -> dict:
    if dist == "uniform":
        k = rng.integers(-12, 12, ROWS)
    elif dist == "skewed":                     # one heavy key + sparse tail
        k = np.where(rng.random(ROWS) < 0.6, 3,
                     rng.integers(-40, 40, ROWS))
    elif dist == "allequal":                   # ties only: pure stability
        k = np.full(ROWS, 7)
    elif dist == "alldistinct":
        k = rng.permutation(ROWS) - ROWS // 2
    else:                                      # empty
        k = np.zeros(0, np.int64)
    n = len(k)
    return {"k": k.astype(np.int32),
            # duplicate-heavy float key off an exact grid, negatives incl.
            "f": (rng.integers(-4, 5, n) * 0.5).astype(np.float32),
            "v": rng.normal(size=n).astype(np.float32),
            "rid": np.arange(n, dtype=np.int32)}   # pins tie stability


def run_both(t: Table, by, ascending, kernel_impl="ref"):
    x = L.sort_values(t, by, ascending, impl="xla")
    r = L.sort_values(t, by, ascending, impl="radix",
                      kernel_impl=kernel_impl)
    assert int(x.nvalid) == int(r.nvalid) == int(t.nvalid)
    return x, r


def assert_bit_identical(x: Table, r: Table, msg=""):
    """Full-column compare: valid rows AND the padding region agree."""
    assert set(x.names) == set(r.names), msg
    for c in x.names:
        a, b = np.asarray(x.columns[c]), np.asarray(r.columns[c])
        assert a.dtype == b.dtype, f"{msg} col={c} dtype"
        np.testing.assert_array_equal(a, b, err_msg=f"{msg} col={c}")


@pytest.mark.parametrize("dist", DISTS)
@pytest.mark.parametrize("spec", KEYSPECS,
                         ids=["k_asc", "k_desc", "kf_mix", "fk_mix",
                              "three_key"])
@pytest.mark.parametrize("kernel_impl", ["ref", "pallas_interpret"])
def test_local_backends_identical(dist, spec, kernel_impl, rng):
    by, ascending = spec
    data = make_data(dist, rng)
    t = Table.from_dict(data, capacity=max(len(data["k"]), 1) + 5)
    x, r = run_both(t, by, ascending, kernel_impl)
    assert_bit_identical(x, r, f"{dist}/{by}")
    want = np_sort_values(data, by, ascending)
    got = r.to_numpy()
    for c in want:   # stable pandas-semantics order, rid pins ties
        np.testing.assert_array_equal(
            got[c], want[c].astype(got[c].dtype),
            err_msg=f"{dist}/{by} vs oracle col={c}")


def test_above_tile_runs_real_kernel(rng):
    """n past the pallas tile boundary: the interpret-mode digit kernel
    (not the ref fallback) must still be bit-identical."""
    n = 1400
    data = {"k": rng.integers(-1000, 1000, n).astype(np.int32),
            "rid": np.arange(n, dtype=np.int32)}
    t = Table.from_dict(data, capacity=n + 13)
    x, r = run_both(t, ["k"], True, "pallas_interpret")
    assert_bit_identical(x, r, "above_tile")


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


def test_radix_path_contains_no_sort_primitive(rng):
    """The acceptance contract: sort_values(impl='radix') replaces the
    XLA sort entirely — its jaxpr must not contain ``sort``; the xla
    backend, for contrast, does sort."""
    data = make_data("uniform", rng)
    t = Table.from_dict(data, capacity=ROWS + 5)
    prims = _jaxpr_primitives(
        lambda tt: L.sort_values(tt, ["k", "f"], [True, False],
                                 impl="radix"), t)
    assert "sort" not in prims, sorted(prims)
    prims = _jaxpr_primitives(
        lambda tt: L.sort_values(tt, ["k"], impl="xla"), t)
    assert "sort" in prims


def test_compaction_paths_contain_no_sort_primitive(rng):
    """compact/select (and through them dropna etc.) run the radix
    engine's 1-bit pass unconditionally — no sort primitive left."""
    data = make_data("uniform", rng)
    t = Table.from_dict(data, capacity=ROWS + 5)
    prims = _jaxpr_primitives(lambda tt: L.select(tt, tt["k"] > 0), t)
    assert "sort" not in prims, sorted(prims)
    prims = _jaxpr_primitives(lambda tt: L.dropna(tt, ["v"]), t)
    assert "sort" not in prims, sorted(prims)


def test_compact_matches_stable_argsort_reference(rng):
    """The 1-bit fast path is bit-identical to the boolean stable argsort
    compaction it replaced (same rows, same order, padding included)."""
    data = make_data("uniform", rng)
    t = Table.from_dict(data, capacity=ROWS + 7)
    keep = t["k"] > 0
    got = L.compact(t, keep)
    keep_ref = keep & t.valid_mask
    perm = jnp.argsort(jnp.logical_not(keep_ref), stable=True)
    want = t.gather_rows(perm, jnp.sum(keep_ref, dtype=jnp.int32))
    assert int(got.nvalid) == int(want.nvalid)
    assert_bit_identical(want, got, "compact")


def test_env_default_backend(rng):
    data = make_data("uniform", rng)
    t = Table.from_dict(data, capacity=ROWS)
    assert L.SORT_IMPL == "xla"
    default = L.sort_values(t, ["k"])
    for impl in ("xla", "radix"):
        assert_bit_identical(default, L.sort_values(t, ["k"], impl=impl),
                             f"default vs {impl}")
    with pytest.raises(ValueError):
        L.sort_values(t, ["k"], impl="nope")


@pytest.mark.parametrize("world", [1, 2, 4])
def test_dist_sort_conformance(world):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={world}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(HERE, "dist", "sort_conformance.py"), str(world)],
        env=env, capture_output=True, text=True, timeout=1800)
    sys.stdout.write(proc.stdout[-4000:])
    sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0, f"sort conformance failed (world={world})"
    assert "SORT CONFORMANCE PASSED" in proc.stdout
