"""Subprocess worker: continuous-batching serve soak with feature joins.

Usage: XLA_FLAGS=...device_count=W python _subproc_serve.py W requests \
           slots prompt_cap gen_cap queue_cap

Drives :class:`repro.serving.ServingEngine` (reduced lm100m, greedy
decode) with a *bursty, skewed* closed-loop load generator:

* arrivals come in bursts of random size with a random number of engine
  steps between bursts — the continuous-batching scheduler sees queue
  buildup, backpressure, and idle-slot stretches, not a smooth stream;
* feature keys are Zipf-skewed (a hot drug/cell dominates), exercising
  the skew-proof probe sizing of the feature-store shuffle/join;
* requests rejected by the bounded admission queue are *counted* and
  retried until admitted — at the end every request has completed, and
  the accounting identity ``submitted == completed + rejected`` is
  asserted along with zero feature-path drops (no silent loss anywhere).

Every completed request is checked: exactly ``gen_len`` tokens out and
its joined features bit-equal to the numpy gather reference.

Prints one JSON line with wall seconds, sustained tokens/s, feature
rows/s, and latency percentiles.
"""
import collections
import json
import sys
import time

import numpy as np


def main():
    world = int(sys.argv[1])
    n_requests = int(sys.argv[2])
    slots = int(sys.argv[3])
    prompt_cap = int(sys.argv[4])
    gen_cap = int(sys.argv[5])
    queue_cap = int(sys.argv[6])

    from repro.launch.env import enable_compile_cache
    enable_compile_cache()
    import jax
    from repro.configs import get_reduced
    from repro.core.context import make_context, make_mesh
    from repro.models import model as M
    from repro.serving import FeatureStore, Request, ServingEngine

    ctx = make_context(make_mesh((world,), ("data",)))
    cfg = get_reduced("lm100m")
    params = M.init_params(jax.random.PRNGKey(0), cfg)

    rng = np.random.default_rng(0)
    n_drugs, n_cells, n_feat = 512, 256, 4
    drug_feat = rng.normal(size=(n_drugs, n_feat)).astype(np.float32)
    cell_feat = rng.normal(size=(n_cells, n_feat)).astype(np.float32)
    drug = {"drug_id": np.arange(n_drugs, dtype=np.int32),
            **{f"d{j}": drug_feat[:, j] for j in range(n_feat)}}
    rna = {"cell_id": np.arange(n_cells, dtype=np.int32),
           **{f"r{j}": cell_feat[:, j] for j in range(n_feat)}}
    cap = max(slots, 8)
    stores = {
        "drug_id": FeatureStore(ctx, "drug_id", drug, probe_capacity=cap,
                                chunk_rows=128),
        "cell_id": FeatureStore(ctx, "cell_id", rna, probe_capacity=cap,
                                chunk_rows=128),
    }
    eng = ServingEngine(cfg, params, slots=slots,
                        prompt_capacity=prompt_cap, gen_capacity=gen_cap,
                        queue_capacity=queue_cap, feature_stores=stores)

    # Zipf-skewed keys: a handful of hot drugs/cells dominate
    zipf = lambda n, size: ((rng.zipf(1.3, size) - 1) % n).astype(int)
    dids = zipf(n_drugs, n_requests)
    cids = zipf(n_cells, n_requests)
    pending = collections.deque(
        Request(req_id=i,
                prompt=rng.integers(0, cfg.vocab,
                                    rng.integers(1, prompt_cap + 1)
                                    ).astype(np.int32),
                gen_len=int(rng.integers(1, gen_cap + 1)),
                drug_id=int(dids[i]), cell_id=int(cids[i]))
        for i in range(n_requests))
    retry = collections.deque()
    done = []

    t0 = time.perf_counter()
    while pending or retry or eng.busy:
        burst = int(rng.integers(1, 2 * queue_cap))
        for _ in range(burst):
            src = retry if retry else pending
            if not src:
                break
            r = src.popleft()
            if not eng.submit(r):
                retry.append(r)           # counted; retried later
                break                     # backpressure: stop the burst
        for _ in range(int(rng.integers(1, 5))):
            done.extend(eng.step())
            if not eng.busy:
                break
    done.extend(eng.run_until_drained())
    wall = time.perf_counter() - t0

    m = eng.metrics
    # no silent drops anywhere: every submit is accounted for, every
    # request eventually completed, the feature path dropped nothing
    assert m.count("submitted") == m.count("completed") + \
        m.count("rejected") + m.count("feature_misses"), m.snapshot()
    assert m.count("feature_misses") == 0, m.snapshot()
    assert len(done) == n_requests, (len(done), n_requests)
    assert sorted(r.req_id for r in done) == list(range(n_requests))
    for s in stores.values():
        assert s.dropped == 0, "feature path dropped rows"
    for r in done:
        assert r.status == "done" and len(r.out_tokens) == r.gen_len, \
            (r.req_id, r.status)
        for j in range(n_feat):          # joined features are correct
            assert r.features[f"d{j}"] == drug_feat[r.drug_id, j], r.req_id
            assert r.features[f"r{j}"] == cell_feat[r.cell_id, j], r.req_id

    print(json.dumps({
        "world": world, "requests": n_requests, "slots": slots,
        "seconds": wall,
        "completed": m.count("completed"),
        "rejected": m.count("rejected"),
        "decode_steps": m.count("decode_steps"),
        "tokens_generated": m.count("tokens_generated"),
        "tokens_per_sec": m.count("tokens_generated") / wall,
        "feature_rows": m.count("feature_rows"),
        "rows_per_sec": m.count("feature_rows") / wall,
        "p50_latency_s": m.percentile("latency", 50),
        "p99_latency_s": m.percentile("latency", 99),
        "p50_ttft_s": m.percentile("ttft", 50),
        "max_queue_depth": m.gauges["queue_depth"]["max"],
    }))


if __name__ == "__main__":
    main()
