"""Subprocess worker: distributed join at a given parallelism + backend.

Usage: XLA_FLAGS=...device_count=W python _subproc_join.py W rows impl
(``impl`` is the local join backend: sortmerge | hash).
Prints one JSON line:
{"world": W, "impl": impl, "seconds": s, "rows": N, "out_rows": M,
 "dropped": d}.
"""
import json
import sys
import time

import numpy as np


def main():
    world = int(sys.argv[1])
    rows = int(sys.argv[2])
    impl = sys.argv[3] if len(sys.argv) > 3 else "sortmerge"
    from repro.launch.env import enable_compile_cache
    enable_compile_cache()
    import jax
    from repro.core import dist_ops as D
    from repro.core.context import make_context, make_mesh

    ctx = make_context(make_mesh((world,), ("data",)))
    rng = np.random.default_rng(0)
    # paper Fig. 4: two relations, ~10% key uniqueness (high collision)
    nkeys = max(rows // 10, 1)
    left = {"k": rng.integers(0, nkeys, rows).astype(np.int32),
            "lv": rng.normal(size=rows).astype(np.float32)}
    right = {"k": rng.integers(0, nkeys, rows).astype(np.int32),
             "rv": rng.normal(size=rows).astype(np.float32)}
    gl = D.distribute_table(ctx, left)
    gr = D.distribute_table(ctx, right)
    # size every static capacity (shuffle slabs, join output, hash slabs)
    # exactly from the key distributions instead of blind overcommit
    plan = D.plan_dist_join_sizes([left["k"]], [right["k"]], world=world,
                                  local_impl=impl)
    pipe = D.DistributedPipeline(
        ctx, lambda c, a, b: D.dist_join(
            c, a, b, left_on=["k"],
            out_capacity=plan["out_capacity"],
            shuffle_sizes=plan["shuffle_sizes"],
            local_impl=impl,
            local_join_sizes=plan["local_join_sizes"]))
    out, dropped = pipe(gl, gr)             # compile + first run
    jax.block_until_ready(out.nvalid)
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        out, dropped = pipe(gl, gr)
        jax.block_until_ready(out.nvalid)
        ts.append(time.perf_counter() - t0)
    n_out = int(np.sum(np.asarray(out.nvalid)))
    print(json.dumps({"world": world, "impl": impl,
                      "seconds": float(np.median(ts)),
                      "rows": rows, "out_rows": n_out,
                      "dropped": int(np.max(np.asarray(dropped)))}))


if __name__ == "__main__":
    main()
