"""Serving launcher: continuous-batching decode fused with feature joins.

    PYTHONPATH=src python -m repro.launch.serve --arch lm100m --reduced \
        [--requests 32] [--slots 4] [--prompt-len 32] [--gen 16] \
        [--queue-capacity 64] [--no-features] [--mesh data=1,model=2]

Thin CLI over :class:`repro.serving.ServingEngine`: generates a stream of
requests (random prompts of *heterogeneous* lengths, each carrying
drug/cell feature keys), submits them through the bounded admission
queue, and runs the engine until drained — continuous batching refills
freed decode slots while the rest of the batch keeps generating, and
every request's keys resolve against UNOMT feature tables through the
distributed join path before its prompt enters a slot.  Prints the full
metrics snapshot (counters / gauges / latency summaries), one line per
program span (``repro.trace``: count, p50, max, self p50) and the
process's compile and GC counters, and asserts the accounting identity:
submitted == completed + rejected + feature_misses.
"""
import argparse
import math
import sys
import time

from .env import enable_compile_cache, ensure_host_devices


N_DRUGS, N_CELLS = 256, 128


def unomt_feature_stores(ctx, *, slots: int, seed: int = 0) -> dict:
    """The drug and cell feature stores a request's keys resolve against:
    UNOMT descriptor+fingerprint rows per drug, RNA rows per cell line."""
    import numpy as np

    from ..data.unomt import gen_unomt_tables
    from ..serving import FeatureStore

    raw = gen_unomt_tables(n_drugs=N_DRUGS, n_cells=N_CELLS, seed=seed)
    drug = dict(raw["descriptors"])
    drug.update({k: v for k, v in raw["fingerprints"].items()
                 if k != "drug_id"})
    # rna carries duplicate records (paper: drop-duplicates) — keep the
    # first row per key so store keys are unique
    _, first = np.unique(raw["rna"]["cell_id"], return_index=True)
    rna = {k: v[first] for k, v in raw["rna"].items()}
    cap = max(slots, 8)
    return {
        "drug_id": FeatureStore(ctx, "drug_id", drug, probe_capacity=cap,
                                chunk_rows=64),
        "cell_id": FeatureStore(ctx, "cell_id", rna, probe_capacity=cap,
                                chunk_rows=64),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="lm100m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32,
                    help="max prompt length (requests vary below it)")
    ap.add_argument("--gen", type=int, default=16,
                    help="max tokens generated (requests vary below it)")
    ap.add_argument("--queue-capacity", type=int, default=64)
    ap.add_argument("--no-features", action="store_true",
                    help="skip the feature-store stage")
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    shape = {}
    if args.mesh:
        shape = {kv.split("=")[0]: int(kv.split("=")[1])
                 for kv in args.mesh.split(",")}
        ensure_host_devices(math.prod(shape.values()),
                            ["-m", "repro.launch.serve", *sys.argv[1:]])
    enable_compile_cache()

    import jax
    import numpy as np

    from .. import trace
    from ..configs import get_config, get_reduced
    from ..core.context import make_context, make_mesh
    from ..models import model as M
    from ..models.sharding import make_policy
    from ..serving import Request, ServingEngine

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    policy = None
    if shape:
        mesh = make_mesh(tuple(shape.values()), tuple(shape.keys()))
        policy = make_policy(mesh, "fsdp_tp")

    params = M.init_params(jax.random.PRNGKey(0), cfg)

    stores = {}
    if not args.no_features:
        stores = unomt_feature_stores(make_context(), slots=args.slots,
                                      seed=args.seed)

    engine = ServingEngine(cfg, params, policy=policy, slots=args.slots,
                           prompt_capacity=args.prompt_len,
                           gen_capacity=args.gen,
                           queue_capacity=args.queue_capacity,
                           feature_stores=stores)

    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    rejected_ids = []
    for i in range(args.requests):
        req = Request(
            req_id=i,
            prompt=rng.integers(0, cfg.vocab,
                                rng.integers(1, args.prompt_len + 1)
                                ).astype(np.int32),
            gen_len=int(rng.integers(1, args.gen + 1)),
            drug_id=int(rng.integers(0, N_DRUGS)),
            cell_id=int(rng.integers(0, N_CELLS)))
        if not engine.submit(req):
            rejected_ids.append(i)
        if (i + 1) % max(args.slots * 4, 8) == 0:
            engine.step()                  # interleave arrivals and decode
    done = engine.run_until_drained()
    dt = time.perf_counter() - t0

    m = engine.metrics
    snap = m.snapshot()
    print(f"[serve] {len(done)} completed / {len(rejected_ids)} rejected "
          f"of {args.requests} in {dt:.2f}s "
          f"({m.count('tokens_generated') / dt:.0f} tok/s)")
    for k in sorted(snap["counters"]):
        print(f"  counter {k:>18} = {snap['counters'][k]}")
    for k, g in snap["gauges"].items():
        print(f"  gauge   {k:>18} = last {g['last']:.0f} max {g['max']:.0f}")
    for k, s in snap["latency"].items():
        if s["count"]:
            print(f"  series  {k:>18} = p50 {s['p50'] * 1e3:.1f}ms "
                  f"p99 {s['p99'] * 1e3:.1f}ms n={s['count']}")
    for k, s in trace.summary().items():
        print(f"  span    {k:>20} = n={s['count']} p50 "
              f"{s['p50_s'] * 1e3:.3f}ms max {s['max_s'] * 1e3:.3f}ms "
              f"self p50 {s['self_p50_s'] * 1e3:.3f}ms")
    c = trace.counters()
    print(f"  process compiles {c['compiles']} ({c['compile_s']:.2f}s), "
          f"gc collections by generation {c['gc']}")
    assert m.count("submitted") == m.count("completed") + \
        m.count("rejected") + m.count("feature_misses"), \
        "accounting identity violated"
    for r in done:
        assert len(r.out_tokens) == r.gen_len, (r.req_id, r.status)
        if stores and r.status == "done":
            assert r.features, f"request {r.req_id} served without features"
    print("serve OK")


if __name__ == "__main__":
    main()
