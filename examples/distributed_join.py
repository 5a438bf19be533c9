"""Distributed join (paper Fig. 4's operator) in isolation.

    PYTHONPATH=src python examples/distributed_join.py [--parallelism 4]
        [--local-impl sortmerge|hash]

Shows the HPTMT recipe explicitly: hash-partition -> all_to_all shuffle ->
local join (sort-merge by default; ``--local-impl hash`` runs the bucketed
Pallas hash-join backend instead), and verifies the result against a
single-partition oracle.
"""
import argparse
import sys

from repro.launch.env import enable_compile_cache, ensure_host_devices


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parallelism", type=int, default=4)
    ap.add_argument("--rows", type=int, default=50_000)
    ap.add_argument("--local-impl", default="sortmerge",
                    choices=["sortmerge", "hash"])
    args = ap.parse_args()

    ensure_host_devices(args.parallelism, sys.argv)
    enable_compile_cache()

    import numpy as np
    from repro.core import dist_ops as D, local_ops as L
    from repro.core.context import make_context, make_mesh
    from repro.core.table import Table

    world = args.parallelism
    ctx = make_context(make_mesh((world,), ("data",)))
    rng = np.random.default_rng(0)
    n = args.rows
    left = {"k": rng.integers(0, n // 10, n).astype(np.int32),
            "lv": rng.normal(size=n).astype(np.float32)}
    right = {"k": rng.integers(0, n // 10, n).astype(np.int32),
             "rv": rng.normal(size=n).astype(np.float32)}

    cap = (n // world) * 2
    gl = D.distribute_table(ctx, left, capacity_per_shard=cap)
    gr = D.distribute_table(ctx, right, capacity_per_shard=cap)
    sizes = None
    if args.local_impl == "hash":
        from repro.kernels.hash_join import workload_hash_join_sizes
        sizes = workload_hash_join_sizes(max(n // 10 // world, 1))
    pipe = D.DistributedPipeline(
        ctx, lambda c, a, b: D.dist_join(c, a, b, left_on=["k"],
                                         out_capacity=cap * 8,
                                         overcommit=3.0,
                                         local_impl=args.local_impl,
                                         local_join_sizes=sizes))
    out, dropped = pipe(gl, gr)
    got = D.collect_table(ctx, out)
    print(f"parallelism={world}: joined {len(got['k'])} rows "
          f"(dropped={int(np.max(np.asarray(dropped)))})")

    # single-partition oracle on a sample
    lt, rt = Table.from_dict(left), Table.from_dict(right)
    want = L.join(lt, rt, left_on=["k"], out_capacity=cap * 8 * world)
    assert len(got["k"]) == int(want.nvalid), \
        (len(got["k"]), int(want.nvalid))
    print("distributed join == local oracle row count: OK")


if __name__ == "__main__":
    main()
