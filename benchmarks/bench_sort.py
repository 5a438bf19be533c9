"""Local OrderBy backend sweep — xla (lax.sort) vs multi-pass radix.

sort_values is the hot path of ``dist_sort`` (sample-sort) and of every
sort-based operator backend; the xla backend pays one stable
``lax.sort`` per call, the radix backend a fixed chain of counting-sort
digit passes (``kernels/radix_sort``) whose cost is linear in rows.
This sweep times both local backends (jitted, two-key sort) across key
cardinalities at a fixed row count against a numpy stable-sort baseline,
plus a ``dist_sort`` leg through a world-1 DistributedPipeline with each
local backend, and records the results into ``results/bench.json``.
Both backends must report bit-identical key columns (the conformance
contract) — asserted here on every config.
"""
from __future__ import annotations

from functools import partial

import jax
import numpy as np

from .common import Reporter, timeit

ROWS = 2048
CARDS = (16, 256, 2048)


def numpy_sort_baseline(keys: np.ndarray, vals: np.ndarray) -> float:
    def run():
        order = np.argsort(keys, kind="stable")
        return keys[order], vals[order]

    return timeit(run, warmup=1, iters=3)


def run(fast: bool = False):
    from repro.core import dist_ops as D, local_ops as L
    from repro.core.context import make_context, make_mesh

    rep = Reporter("sort_local_backends")
    rows = ROWS // 4 if fast else ROWS
    rng = np.random.default_rng(0)
    from repro.core.table import Table

    for nkeys in CARDS:
        nkeys = min(nkeys, rows)
        keys = rng.integers(-nkeys // 2, nkeys // 2, rows).astype(np.int32)
        vals = rng.integers(-100, 100, rows).astype(np.float32)
        rep.add(f"numpy_k{nkeys}", "seconds",
                numpy_sort_baseline(keys, vals), rows=rows)
        t = Table.from_dict({"k": keys, "v": vals})
        per_impl = {}
        for impl in ("xla", "radix"):
            fn = jax.jit(partial(L.sort_values, by=["k", "v"], impl=impl))
            out = jax.block_until_ready(fn(t))
            secs = timeit(lambda: jax.block_until_ready(fn(t)))
            per_impl[impl] = (secs, np.asarray(out.columns["k"]))
            rep.add(f"{impl}_k{nkeys}", "seconds", secs, rows=rows)
        np.testing.assert_array_equal(per_impl["xla"][1],
                                      per_impl["radix"][1],
                                      err_msg="backends diverged")
        rep.add(f"radix_k{nkeys}", "speedup_vs_xla",
                per_impl["xla"][0] / per_impl["radix"][0])

    # dist_sort leg (world 1 in-process; multi-device scaling lives in
    # tests/dist/sort_conformance.py, run under forced host devices)
    ctx = make_context(make_mesh((1,), ("data",)))
    data = {"k": rng.integers(-1000, 1000, rows).astype(np.int32),
            "v": rng.normal(size=rows).astype(np.float32)}
    for impl in ("xla", "radix"):
        gt = D.distribute_table(ctx, data)
        pipe = D.DistributedPipeline(
            ctx, lambda c, a, impl=impl: D.dist_sort(c, a, ["k"],
                                                     local_impl=impl))
        out, dropped = jax.block_until_ready(pipe(gt))
        assert int(np.max(np.asarray(dropped))) == 0, impl
        secs = timeit(lambda: jax.block_until_ready(pipe(gt)))
        rep.add(f"dist_{impl}_w1", "seconds", secs, rows=rows)
    rep.save()
    return rep


if __name__ == "__main__":
    run()
