"""Subprocess worker for tests/test_empty_tables.py: zero-row and
empty-shard inputs through the distributed operators at a given world
size.

Usage: XLA_FLAGS=...device_count=W python empty_conformance.py W [B]

``B`` names the local algorithms: ``default`` (each family's default) or
``hash`` (hash join, groupby and semi-join, radix sort).

Three degenerate shapes per operator:

* ``zero``: a 0-row table (every shard empty);
* ``sparse``: fewer rows than shards (trailing shards empty after the
  block distribution);
* one-sided emptiness for the binary ops (empty probe vs empty build).

Every leg asserts the dropped counter is zero and the collected result
matches the numpy oracle.  Prints ``EMPTY CONFORMANCE PASSED``.
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from oracles import (as_sets, np_groupby_aggregate, np_isin, np_join,  # noqa: E402
                     np_sort_values)

BACKENDS = {"default": {"join": None, "groupby": None, "sort": None,
                        "semi": None},
            "hash": {"join": "hash", "groupby": "hash", "sort": "radix",
                     "semi": "hash"}}


def main():
    world = int(sys.argv[1])
    impl = BACKENDS[sys.argv[2] if len(sys.argv) > 2 else "default"]
    import jax
    from jax.sharding import Mesh
    from repro.core import dist_ops as D
    from repro.core.context import make_context

    dev = np.array(jax.devices()[:world])
    ctx = make_context(Mesh(dev, ("data",)))
    rng = np.random.default_rng(world)

    def dist(data, cap=32):
        return D.distribute_table(ctx, data, capacity_per_shard=cap)

    def run(fn, *tables):
        out, dropped = D.DistributedPipeline(ctx, fn)(*tables)
        assert int(np.max(np.asarray(dropped))) == 0
        return D.collect_table(ctx, out)

    zero = {"k": np.zeros(0, np.int32), "v": np.zeros(0, np.float32)}
    sparse = {"k": np.array([3, 1], np.int32),       # fewer rows than
              "v": np.array([1.0, 2.0], np.float32)}  # shards at world 4
    full = {"k": rng.integers(0, 4, 16).astype(np.int32),
            "v": rng.integers(0, 9, 16).astype(np.float32)}
    shapes = {"zero": zero, "sparse": sparse}

    for name, probe in shapes.items():
        # join: empty/sparse probe x full build, and full probe x empty build
        for how in ("inner", "left"):
            for lname, l, r in ((f"{name}-left", probe, full),
                                (f"{name}-right", full, probe)):
                got = run(lambda c, a, b, how=how: D.dist_join(
                    c, a, b, left_on=["k"], how=how, out_capacity=256,
                    local_impl=impl["join"]),
                    dist(l), dist(r))
                lv = {"k": l["k"], "lv": l["v"]}
                rv = {"k": r["k"], "rv": r["v"]}
                want = np_join(lv, rv, how)
                got = {"k": got["k"], "lv": got["v"], "rv": got["v_r"]}
                assert as_sets(got) == as_sets(want), (lname, how)
        # groupby
        got = run(lambda c, t: D.dist_groupby(
            c, t, ["k"], {"v": ["sum", "mean", "count"]},
            local_impl=impl["groupby"]), dist(probe))
        want = np_groupby_aggregate(probe, ["k"],
                                    {"v": ["sum", "mean", "count"]})
        assert as_sets(got) == as_sets(
            {k: np.asarray(v) for k, v in want.items()}), name
        # sort (shard order + local order == global order, even with
        # empty shards in between after range partition)
        got = run(lambda c, t: D.dist_sort(c, t, ["k"],
                                           local_impl=impl["sort"]),
                  dist(probe))
        want = np_sort_values(probe, ["k"])
        for k in want:
            np.testing.assert_array_equal(got[k], want[k],
                                          err_msg=f"{name} sort {k}")
        # isin: empty/sparse table x full values, and full x empty/sparse
        for lname, t, v in ((f"{name}-tbl", probe, full),
                            (f"{name}-vals", full, probe)):
            got = run(lambda c, a, b: D.dist_isin(
                c, a, "k", b, "k", local_impl=impl["semi"]),
                dist(t), dist(v))
            mask = np.asarray(np_isin(t, "k", v, "k"), dtype=bool)
            want = {k: np.asarray(col)[mask] for k, col in t.items()}
            assert as_sets(got) == as_sets(want), lname
        print(f"{name}: ok", flush=True)

    print("EMPTY CONFORMANCE PASSED")


if __name__ == "__main__":
    sys.exit(main())
