"""Subprocess worker for tests/test_morsel.py: chunked (morsel-driven)
execution conformance at a given world size.

Usage: XLA_FLAGS=...device_count=W python morsel_conformance.py W [B]

``B`` names the local algorithms: ``default`` (each family's default) or
``hash`` (hash join and groupby, radix sort), passed to both the chunked
and the monolithic operators.

Checks the out-of-core chunk loops against the monolithic distributed
operators on data that *fits*, where results must agree exactly:

* join (build-resident and build-restreamed): same content — row order is
  permuted by chunk boundaries exactly as shard boundaries already
  permute it, so both sides are canonicalized by a full lexsort before
  the exact compare; also checked against the numpy oracle;
* groupby: bit-identical arrays (same shard assignment per key, canonical
  per-shard layout, exact partial sums on integer-valued floats);
* sort: bit-identical arrays including tie order (both paths tie in
  original row order);
* zero-row inputs stream one empty terminal morsel through every op.

All legs assert the aggregated across-chunk dropped counter is zero.
Prints ``MORSEL CONFORMANCE PASSED`` on success.
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from oracles import as_sets, np_groupby_aggregate, np_join  # noqa: E402

BACKENDS = {"default": {"join": None, "groupby": None, "sort": None},
            "hash": {"join": "hash", "groupby": "hash", "sort": "radix"}}


def canon(d: dict) -> dict:
    order = np.lexsort(tuple(np.nan_to_num(d[k], nan=-1e9)
                             for k in sorted(d)))
    return {k: v[order] for k, v in d.items()}


def assert_same(a: dict, b: dict, msg=""):
    assert set(a) == set(b), msg
    for k in a:
        np.testing.assert_array_equal(
            np.nan_to_num(a[k], nan=-1e9), np.nan_to_num(b[k], nan=-1e9),
            err_msg=f"{msg} col={k}")


def main():
    world = int(sys.argv[1])
    impl = BACKENDS[sys.argv[2] if len(sys.argv) > 2 else "default"]
    import jax
    from jax.sharding import Mesh
    from repro.core import dist_ops as D
    from repro.core import morsel as M
    from repro.core.context import make_context

    dev = np.array(jax.devices()[:world])
    ctx = make_context(Mesh(dev, ("data",)))
    rng = np.random.default_rng(world)

    rows, nkeys, chunk = 2000, 150, 300
    left = {"k": rng.integers(0, nkeys, rows).astype(np.int64),
            "lv": rng.integers(-50, 50, rows).astype(np.float64)}
    right = {"k": np.arange(nkeys, dtype=np.int64),
             "rv": rng.integers(0, 100, nkeys).astype(np.float64)}
    out_cap = 8192

    # ---- join: chunked (resident + restream) vs monolithic vs oracle
    gl = D.distribute_table(ctx, left)
    gr = D.distribute_table(ctx, right)
    pipe = D.DistributedPipeline(ctx, lambda c, a, b: D.dist_join(
        c, a, b, left_on=["k"], out_capacity=out_cap,
        local_impl=impl["join"]))
    mono, md = pipe(gl, gr)
    assert int(np.max(np.asarray(md))) == 0
    mono = canon(D.collect_table(ctx, mono))
    for build, rchunk in (("resident", nkeys), ("restream", 64)):
        out, dropped = M.chunked_dist_join(
            ctx, M.ChunkedTable(left, chunk),
            M.ChunkedTable(right, rchunk), left_on=["k"], build=build,
            out_capacity_per_shard=out_cap, local_impl=impl["join"])
        assert dropped == 0, build
        assert_same(canon(out), mono, f"join/{build}")
        print(f"join/{build}: ok ({len(out['k'])} rows)", flush=True)
    lk32 = {"k": left["k"].astype(np.int32),
            "lv": left["lv"].astype(np.float32)}
    rk32 = {"k": right["k"].astype(np.int32),
            "rv": right["rv"].astype(np.float32)}
    assert as_sets(mono) == as_sets(np_join(lk32, rk32, "inner"))

    # ---- left join through the resident build path (odd keys unmatched)
    rsub = {k: v[::2] for k, v in right.items()}
    rsub32 = {k: v[::2] for k, v in rk32.items()}
    outl, dl = M.chunked_dist_join(
        ctx, M.ChunkedTable(left, chunk), rsub, left_on=["k"],
        how="left", out_capacity_per_shard=out_cap,
        local_impl=impl["join"])
    assert dl == 0
    assert np.isnan(outl["rv"]).any()   # unmatched rows really occur
    assert as_sets(canon(outl)) == as_sets(np_join(lk32, rsub32, "left"))
    print("join/left: ok", flush=True)

    # ---- groupby: chunked partial-merge vs monolithic, bit-identical
    # (explicit slab sizes: the traced hash-backend heuristic undersizes
    # hot buckets at this duplication level, same idiom as
    # groupby_conformance.py)
    gsizes = {"num_buckets": 8, "bucket_capacity": rows}
    aggs = {"lv": ["sum", "mean", "count", "min", "max"]}
    gp = D.DistributedPipeline(ctx, lambda c, t: D.dist_groupby(
        c, t, ["k"], aggs, local_impl=impl["groupby"],
        groupby_sizes=gsizes))
    monog, gd = gp(gl)
    assert int(np.max(np.asarray(gd))) == 0
    monog = D.collect_table(ctx, monog)
    cg, cgd = M.chunked_dist_groupby(ctx, M.ChunkedTable(left, chunk),
                                     ["k"], aggs,
                                     group_capacity_per_shard=nkeys,
                                     local_impl=impl["groupby"],
                                     groupby_sizes=gsizes)
    assert cgd == 0
    assert_same(cg, monog, "groupby")
    want = np_groupby_aggregate(lk32, ["k"], aggs)
    got = canon(cg)
    wantc = canon({k: np.asarray(v) for k, v in want.items()})
    for k in wantc:
        np.testing.assert_allclose(got[k].astype(np.float64), wantc[k],
                                   rtol=1e-6, err_msg=f"groupby oracle {k}")
    print(f"groupby: ok ({len(cg['k'])} groups, bit-identical)",
          flush=True)

    # ---- sort: chunked runs + k-way merge vs monolithic, bit-identical
    for ascending in (True, False):
        sp = D.DistributedPipeline(ctx, lambda c, t, a=ascending:
                                   D.dist_sort(c, t, ["k"], ascending=a,
                                               local_impl=impl["sort"]))
        monos, sd = sp(gl)
        assert int(np.max(np.asarray(sd))) == 0
        monos = D.collect_table(ctx, monos)
        cs, csd = M.chunked_dist_sort(ctx, M.ChunkedTable(left, chunk),
                                      ["k"], ascending=ascending,
                                      local_impl=impl["sort"])
        assert csd == 0
        assert_same(cs, monos, f"sort asc={ascending}")
        print(f"sort asc={ascending}: ok (ties bit-identical)", flush=True)

    # ---- zero-row sources: one empty terminal morsel per op
    empty = {"k": np.zeros(0, np.int64), "lv": np.zeros(0, np.float64)}
    eo, ed = M.chunked_dist_join(ctx, empty, right, left_on=["k"],
                                 local_impl=impl["join"])
    assert ed == 0 and len(eo["k"]) == 0
    eo, ed = M.chunked_dist_join(ctx, empty, M.ChunkedTable(right, 64),
                                 left_on=["k"], build="restream",
                                 local_impl=impl["join"])
    assert ed == 0 and len(eo["k"]) == 0
    eg, ed = M.chunked_dist_groupby(ctx, empty, ["k"], {"lv": "mean"},
                                    local_impl=impl["groupby"])
    assert ed == 0 and len(eg["k"]) == 0
    es, ed = M.chunked_dist_sort(ctx, empty, ["k"],
                                 local_impl=impl["sort"])
    assert ed == 0 and len(es["k"]) == 0
    print("empty sources: ok", flush=True)

    print("MORSEL CONFORMANCE PASSED")


if __name__ == "__main__":
    sys.exit(main())
