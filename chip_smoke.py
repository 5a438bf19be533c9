#!/usr/bin/env python3
"""Chip smoke test: the system's main paths once, on TPU, at real sizes.

    python chip_smoke.py              # one chip: phases (a)-(e)
    python chip_smoke.py --chips 4    # four chips: phase (c) at world 4
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [--chips 4]

Phases, each printing its own line(s) first — which implementation ran,
the sizes, the bytes on the device, the result:

(a) device   — JAX must see a TPU; anything else fails the run.
(b) kernels  — every table kernel with ``impl="pallas"`` against its
               ``ref`` at several tiles, bit for bit.
(c) tables   — the paper's Fig. 4 workload (two tables, int32 key and
               float32 value, 10% key uniqueness, from ``--seed``):
               distribute_table -> plan_dist_join_sizes -> dist_join ->
               local combine -> dist_groupby sum/count -> select ->
               collect_table, checked against numpy with every drop
               counter at zero.
(d) serving  — ServingEngine on full-width lm100m with the drug and cell
               feature stores: 8 requests on 4 slots, the accounting
               identity, and one request's greedy tokens against the
               one-shot prefill/decode path.
(e) training — full-width lm100m takes 3 steps of ``make_train_step``;
               the loss stays finite.

``--chips 4`` runs phase (c) only, at world 4 on four chips with four
times the rows.  ``--rehearse`` lets the CPU stand in for the chip at toy
sizes with the kernels in interpret mode, to check the script itself; it
is never a chip run.  Any failure raises, so the exit code is non-zero,
and the last line of standard output is printed only when every phase
passed: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))


@dataclasses.dataclass(frozen=True)
class Sizes:
    rows_per_chip: int        # rows of each joined table, per chip
    tiles: tuple              # (tile, radix_bits) of the tile kernels
    slabs: tuple              # slab depths of the bucket kernels
    kernel_rows: int          # rows per bucket-kernel check
    reduced_model: bool


# 2**24 rows per table per chip: the join emits ~10 rows per input row,
# and the compiled pipeline needs 7.17 GiB of HBM temp at world 1 and
# 8.12 GiB per chip at world 4, of v5e's 15.75 GiB.  1.5 x 2**24 fits too
# (10.75 / 13.04 GiB) but makes the four-chip run longer than it is worth.
CHIP = Sizes(rows_per_chip=1 << 24,
             tiles=((512, 4), (1024, 8), (2048, 11)),
             slabs=(128, 256), kernel_rows=1 << 14, reduced_model=False)
REHEARSAL = Sizes(rows_per_chip=1 << 12, tiles=((128, 8),), slabs=(16,),
                  kernel_rows=1 << 10, reduced_model=True)
BUCKETS = 512


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def compile_seconds() -> float:
    """Seconds XLA has spent compiling in this process, from the program's
    own counter (persistent-cache hits compile nothing)."""
    from repro import trace
    return trace.counters()["compile_s"]


def device_bytes() -> str:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    if "bytes_in_use" not in stats:
        return "device bytes not reported"
    return (f"device bytes in use {stats['bytes_in_use']:,}, "
            f"peak {stats.get('peak_bytes_in_use', 0):,}")


# ------------------------------------------------------------------ (a)
def phase_device(rehearse: bool, chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    log("device", f"platform={d.platform} kind={d.device_kind} "
                  f"count={len(devs)}")
    if d.platform != "tpu" and not rehearse:
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{d.platform!r}); this is a chip test")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but JAX sees "
                         f"{len(devs)} device(s)")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# ------------------------------------------------------------------ (b)
def _same(name: str, got, want) -> None:
    import jax
    import numpy as np
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        if not np.array_equal(np.asarray(g), np.asarray(w)):
            raise AssertionError(f"{name}: pallas differs from ref")


def phase_kernels(sizes: Sizes, impl: str, seed: int) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.fused_bucketing import fused_bucket_ranks
    from repro.kernels.hash_groupby import hash_groupby_plan
    from repro.kernels.hash_join import hash_join_plan
    from repro.kernels.hash_partition import radix_histogram_ranks
    from repro.kernels.hash_semi import hash_semi_plan
    from repro.kernels.radix_sort import (radix_permutation,
                                          stable_partition_perm)

    rng = np.random.default_rng(seed)
    for tile, bits in sizes.tiles:
        n = 4 * max(tile, 1024) + 77       # full tiles and a ragged one
        pid = jnp.asarray(rng.integers(0, BUCKETS, n, dtype=np.int32))
        ik = jnp.asarray(rng.integers(-2 ** 31, 2 ** 31, n, dtype=np.int64)
                         .astype(np.int32))
        fk = jnp.asarray(rng.standard_normal(n, dtype=np.float32))
        flag = jnp.asarray(rng.random(n) < 0.3)
        checks = {
            "hash_partition": lambda i: radix_histogram_ranks(
                pid, BUCKETS, impl=i, tile=tile),
            "radix_sort": lambda i: radix_permutation(
                (ik, fk), flag, impl=i, radix_bits=bits, tile=tile),
            "radix_sort(1-bit)": lambda i: stable_partition_perm(
                flag, impl=i, tile=tile),
            "fused_bucketing": lambda i: fused_bucket_ranks(
                (ik, pid), ~flag, BUCKETS, impl=i, tile=tile),
        }
        for name, run in checks.items():
            _same(name, run(impl), run("ref"))
            log("kernels", f"{name} impl={impl} vs ref tile={tile} "
                           f"radix_bits={bits} rows={n} "
                           f"buckets={BUCKETS}: identical")

    n = sizes.kernel_rows
    lk = jnp.asarray(rng.integers(0, n // 4, n, dtype=np.int32))
    rk = jnp.asarray(rng.integers(0, n // 4, n, dtype=np.int32))
    lv, rv = (jnp.asarray(rng.random(n) < 0.9) for _ in range(2))
    # integer-valued floats: sums are exact in any order, so bit-identical
    x = jnp.asarray(rng.integers(-50, 50, n).astype(np.float32))
    for slab in sizes.slabs:
        kw = dict(num_buckets=BUCKETS, bucket_capacity=slab)
        checks = {
            "hash_join": lambda i: hash_join_plan(
                (lk,), lv, (rk,), rv, probe_capacity=slab, impl=i, **kw),
            "hash_semi": lambda i: hash_semi_plan(
                (lk,), lv, (rk,), rv, probe_capacity=slab, impl=i, **kw),
            "hash_groupby": lambda i: hash_groupby_plan(
                (lk,), lv, (x,), impl=i, **kw),
        }
        for name, run in checks.items():
            _same(name, run(impl), run("ref"))
            log("kernels", f"{name} impl={impl} vs ref rows={n} "
                           f"buckets={BUCKETS} slab={slab}: identical")
    log("kernels", device_bytes())


# ------------------------------------------------------------------ (c)
def fig4_tables(rows: int, seed: int):
    """Two tables of ``rows`` rows, int32 key and float32 value, keys
    drawn from ``rows // 10`` values (Fig. 4: 10% key uniqueness)."""
    import numpy as np
    nkeys = rows // 10
    rng = np.random.default_rng(seed)
    left = {"k": rng.integers(0, nkeys, rows, dtype=np.int32),
            "lv": rng.standard_normal(rows, dtype=np.float32)}
    right = {"k": rng.integers(0, nkeys, rows, dtype=np.int32),
             "rv": rng.standard_normal(rows, dtype=np.float32)}
    return left, right, nkeys


def table_pipeline(plan: dict, groups_cap: int):
    """join -> groupby sum/count -> select even keys, as one program;
    returns (groups, join rows, join/combine/groupby/select drops).

    The groupby runs in two phases, as a combiner does: each shard first
    aggregates its own join output locally and keeps at most
    ``groups_cap`` partial rows (counted overflow), then ``dist_groupby``
    merges the partials.  Straight on the join output, the groupby's
    shuffle needs send slabs of world x capacity rows — 47 GiB per chip
    at world 4 — because the join left every row on the shard its key
    hashes to.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import dist_ops as D, local_ops as L
    from repro.core.table import Table

    def shrink(t):
        acc = Table(columns={n: jnp.zeros((groups_cap,), v.dtype)
                             for n, v in t.columns.items()},
                    nvalid=jnp.int32(0))
        return L.append_rows(acc, t)

    def pipeline(c, a, b):
        j, jdrop = D.dist_join(c, a, b, left_on=["k"],
                               out_capacity=plan["out_capacity"],
                               shuffle_sizes=plan["shuffle_sizes"])
        part, pdrop = shrink(L.groupby_aggregate(
            j, ["k"], {"lv": "sum", "rv": ["sum", "count"]}))
        # every partial row is already on its key's shard: size the
        # shuffle so one sender can fill a destination
        g, gdrop = D.dist_groupby(
            c, part, ["k"], {"lv_sum": "sum", "rv_sum": "sum",
                             "rv_count": "sum"},
            overcommit=float(c.world_size))
        s, sdrop = shrink(L.select(g, g.columns["k"] % 2 == 0))
        return (s, j.nvalid, jdrop, jax.lax.psum(pdrop, c.row_axes), gdrop,
                jax.lax.psum(sdrop, c.row_axes))
    return pipeline


def phase_tables(sizes: Sizes, world: int, seed: int, compiled) -> None:
    import jax
    import numpy as np

    from repro.core import dist_ops as D
    from repro.core.context import make_context, make_mesh
    from repro.core.kernel_backend import join_impl, table_kernel_impl

    rows = sizes.rows_per_chip * world
    t0 = time.perf_counter()
    left, right, nkeys = fig4_tables(rows, seed)
    ctx = make_context(make_mesh((world,), ("data",)))
    gl = D.distribute_table(ctx, left)
    gr = D.distribute_table(ctx, right)
    plan = D.plan_dist_join_sizes([left["k"]], [right["k"]], world=world)
    # twice the keys a shard holds on average
    groups_cap = 2 * -(-nkeys // world)
    log("tables", f"world={world} rows={rows:,} per table, keys={nkeys:,}, "
                  f"join_impl={join_impl()} "
                  f"kernel_impl={table_kernel_impl()} "
                  f"join out_capacity/shard={plan['out_capacity']:,} "
                  f"set-up {time.perf_counter() - t0:.1f}s; "
                  f"{device_bytes()}")
    pipe = D.DistributedPipeline(ctx, table_pipeline(plan, groups_cap))
    t0, c0 = time.perf_counter(), compiled()
    out, joined, *drops = pipe(gl, gr)
    jax.block_until_ready(out.nvalid)
    got = D.collect_table(ctx, out)
    secs = time.perf_counter() - t0
    joined = int(np.asarray(joined).sum())
    drops = [int(np.asarray(d).max()) for d in drops]
    log("tables", f"joined {joined:,} rows, {len(got['k']):,} even-key "
                  f"groups collected, drops join/combine/groupby/select="
                  f"{drops}, first call {secs:.1f}s of which XLA compile "
                  f"{compiled() - c0:.1f}s; {device_bytes()}")

    cl = np.bincount(left["k"], minlength=nkeys).astype(np.int64)
    cr = np.bincount(right["k"], minlength=nkeys).astype(np.int64)
    lsum = np.bincount(left["k"], weights=left["lv"], minlength=nkeys)
    rsum = np.bincount(right["k"], weights=right["rv"], minlength=nkeys)
    pairs = cl * cr
    want_k = np.flatnonzero((pairs > 0) & (np.arange(nkeys) % 2 == 0))
    if any(drops):
        raise AssertionError(f"rows dropped: {drops}")
    if joined != int(pairs.sum()):
        raise AssertionError(f"join rows {joined} != {int(pairs.sum())}")
    order = np.argsort(got["k"], kind="stable")
    got = {k: v[order] for k, v in got.items()}
    np.testing.assert_array_equal(got["k"], want_k)
    # counts are summed as float32: exact below 2**24
    np.testing.assert_array_equal(got["rv_count_sum"], pairs[want_k])
    # each left value meets every right row of its key, and vice versa
    np.testing.assert_allclose(got["lv_sum_sum"], (lsum * cr)[want_k],
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(got["rv_sum_sum"], (rsum * cl)[want_k],
                               rtol=1e-4, atol=1e-3)
    log("tables", f"join rows, per-key counts and sums match numpy "
                  f"({len(want_k):,} groups), zero drops")


# ------------------------------------------------------------------ (d)
def phase_serving(sizes: Sizes, seed: int, compiled) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config, get_reduced
    from repro.core.context import make_context
    from repro.launch.serve import N_CELLS, N_DRUGS, unomt_feature_stores
    from repro.models import model as M
    from repro.serving import Request, ServingEngine

    cfg = get_reduced("lm100m") if sizes.reduced_model \
        else get_config("lm100m")
    slots, P, G = 4, 32, 16
    params = M.init_params(jax.random.PRNGKey(seed), cfg)
    stores = unomt_feature_stores(make_context(), slots=slots, seed=seed)
    eng = ServingEngine(cfg, params, slots=slots, prompt_capacity=P,
                        gen_capacity=G, queue_capacity=16,
                        feature_stores=stores)
    log("serving", f"lm100m layers={cfg.n_layers} d_model={cfg.d_model} "
                   f"vocab={cfg.vocab} params={cfg.param_count():,} "
                   f"slots={slots} prompt_cap={P} gen_cap={G} "
                   f"stores={sorted(stores)}; {device_bytes()}")
    rng = np.random.default_rng(seed)
    reqs = [Request(req_id=i,
                    prompt=rng.integers(0, cfg.vocab, rng.integers(1, P + 1))
                    .astype(np.int32),
                    gen_len=int(rng.integers(1, G + 1)),
                    drug_id=int(rng.integers(0, N_DRUGS)),
                    cell_id=int(rng.integers(0, N_CELLS)))
            for i in range(8)]
    t0, c0 = time.perf_counter(), compiled()
    for r in reqs:
        if not eng.submit(r):
            raise AssertionError(f"request {r.req_id} rejected")
    done = eng.run_until_drained()
    secs = time.perf_counter() - t0
    m = eng.metrics
    ident = (m.count("submitted"), m.count("completed"),
             m.count("rejected"), m.count("feature_misses"))
    log("serving", f"{len(done)} done, submitted/completed/rejected/"
                   f"feature_misses={ident}, tokens="
                   f"{m.count('tokens_generated')}, feature drops="
                   f"{[s.dropped for s in stores.values()]}, first pass "
                   f"{secs:.1f}s of which XLA compile {compiled() - c0:.1f}s; "
                   f"{device_bytes()}")
    if ident[0] != ident[1] + ident[2] + ident[3]:
        raise AssertionError(f"accounting identity violated: {ident}")
    if ident[1] != len(reqs) or any(s.dropped for s in stores.values()):
        raise AssertionError("not every request completed cleanly")
    for r in done:
        if len(r.out_tokens) != r.gen_len or not r.features:
            raise AssertionError(f"request {r.req_id}: "
                                 f"{len(r.out_tokens)}/{r.gen_len} tokens, "
                                 f"features={bool(r.features)}")

    # one request against the one-shot prefill/decode path
    r = max(done, key=lambda q: q.gen_len)
    p_len = len(r.prompt)
    prefill = jax.jit(M.make_prefill(cfg, None, decode_len=P + G))
    serve = jax.jit(M.make_serve_step(cfg, None))
    logits, caches = prefill(params, {"tokens": jnp.asarray(r.prompt[None])})
    want = [int(jnp.argmax(logits, -1)[0])]
    for i in range(r.gen_len - 1):
        logits, caches = serve(params, caches,
                               jnp.asarray([[want[-1]]], jnp.int32),
                               jnp.int32(p_len + i))
        want.append(int(jnp.argmax(logits, -1)[0]))
    if r.out_tokens != want:
        raise AssertionError(f"request {r.req_id}: engine {r.out_tokens} "
                             f"!= one-shot {want}")
    log("serving", f"request {r.req_id} (prompt {p_len}, gen {r.gen_len}) "
                   f"greedy tokens match the one-shot path")


# ------------------------------------------------------------------ (e)
def phase_training(sizes: Sizes, seed: int, compiled) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config, get_reduced
    from repro.data.synthetic import lm_batch_at
    from repro.models import model as M
    from repro.optim import adamw

    cfg = get_reduced("lm100m") if sizes.reduced_model \
        else get_config("lm100m")
    batch, seq = (2, 64) if sizes.reduced_model else (8, 512)
    opt_cfg = adamw.AdamWConfig(total_steps=3)
    params = M.init_params(jax.random.PRNGKey(seed), cfg)
    opt = adamw.init(params, opt_cfg)
    step = jax.jit(M.make_train_step(cfg, None, opt_cfg),
                   donate_argnums=(0, 1))
    losses = []
    t0, c0 = time.perf_counter(), compiled()
    for s in range(3):
        b = {k: jnp.asarray(v) for k, v in
             lm_batch_at(s, vocab=cfg.vocab, batch=batch, seq=seq,
                         seed=seed).items()}
        params, opt, met = step(params, opt, b)
        losses.append(float(met["loss"]))
    secs = time.perf_counter() - t0
    log("training", f"lm100m layers={cfg.n_layers} d_model={cfg.d_model} "
                    f"batch={batch} seq={seq} losses={losses} "
                    f"(3 steps {secs:.1f}s of which XLA compile "
                    f"{compiled() - c0:.1f}s); "
                    f"{device_bytes()}")
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU stand-in at toy sizes (never a chip run)")
    args = ap.parse_args()

    from repro.launch.env import (enable_compile_cache,
                                  ensure_host_devices, on_cpu)
    sizes = CHIP
    if args.rehearse:
        sizes = REHEARSAL
        if on_cpu():
            os.environ.setdefault("REPRO_KERNEL_IMPL", "pallas_interpret")
            ensure_host_devices(args.chips, sys.argv)
    enable_compile_cache()

    device = phase_device(args.rehearse, args.chips)
    impl = "pallas" if device["platform"] == "tpu" else "pallas_interpret"
    if args.chips == 4:
        phase_tables(sizes, 4, args.seed, compile_seconds)
    else:
        phase_kernels(sizes, impl, args.seed)
        phase_tables(sizes, 1, args.seed, compile_seconds)
        phase_serving(sizes, args.seed, compile_seconds)
        phase_training(sizes, args.seed, compile_seconds)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
