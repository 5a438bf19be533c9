"""Driver for served language models: an open loop of chat requests into
the repo's ``ServingEngine``, each request's features fetched from two
``FeatureStore``s.

Set-up builds the weights on the device from the seed in the served
dtype, the drug and cell feature tables from the seed, the engine, and
warms every program one request touches.  The window submits each planned
request when it falls due (``traffic_gen.plan``), steps the engine while
it has work, and stops offering load when ``--seconds`` have passed; it
then steps on until every submitted request has its first token.  Latency
runs from the time a request was due.

Once the window has closed and the program is freed, the reference
(``reference/dense_lm.py``) is run over a sample of the finished requests
drawn from the seed, the longest among them, and every served feature is
compared with the source tables.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import flops, harness, traffic_gen
from bench.reference.dense_lm import padded_vocab

# the first-token latency is judged at its median; its 80th percentile, the
# highest with ten of the ~61 requests of a 51 s window beyond it, swings
# with the bursts each seed's arrivals hold and is a per-layer metric
TTFT_Q, TTFT_TAIL_Q = 50, 80


def arch_config(config: dict):
    """The program's configuration of the model, checked against the file."""
    from repro.configs import get_config, get_reduced
    d = config["dims"]
    cfg = (get_reduced if config.get("reduced_arch") else get_config)(
        config["arch"])
    ours = {"n_layers": d["num_hidden_layers"], "d_model": d["hidden_size"],
            "n_heads": d["num_attention_heads"],
            "n_kv_heads": d["num_key_value_heads"], "d_head": d["head_dim"],
            "d_ff": d["intermediate_size"], "vocab": d["vocab_size"],
            "tie_embeddings": d["tie_word_embeddings"],
            "rope_theta": d["rope_theta"], "norm_eps": d["rms_norm_eps"]}
    theirs = {k: getattr(cfg, k) for k in ours}
    if ours != theirs or cfg.padded_vocab() != padded_vocab(d):
        raise ValueError(f"program config {theirs} != bench config {ours}")
    return cfg


def feature_tables(stores: dict, seed: int) -> dict:
    """Per store: unique int32 keys ``0..n-1`` and float32 feature columns,
    from the seed."""
    rng = np.random.default_rng([seed, 1])
    out = {}
    for attr, s in stores.items():
        cols = {attr: np.arange(s["rows"], dtype=np.int32)}
        for j in range(s["features"]):
            cols[f"{s['prefix']}{j}"] = rng.standard_normal(
                s["rows"], dtype=np.float32)
        out[attr] = cols
    return out


class Tracker:
    """Client-side record of every submitted request: when it was due,
    when each of its tokens reached the host."""

    def __init__(self):
        self.due: dict[int, float] = {}
        self.times: dict[int, list[float]] = {}
        self.reqs: dict = {}

    def submitted(self, req, due: float) -> None:
        self.due[req.req_id] = due
        self.times[req.req_id] = []
        self.reqs[req.req_id] = req

    def observe(self, reqs, now: float, dims: dict) -> float:
        """Stamp the tokens ``reqs`` gained; returns the useful FLOPs of
        the step that produced them."""
        work = 0.0
        for r in reqs:
            ts = self.times.get(r.req_id)
            if ts is None:
                continue
            n = len(r.out_tokens)
            while len(ts) < n:
                if not ts:                   # prefill's token
                    ts.append(r.t_first)
                    work += flops.prefill_flops(dims, len(r.prompt))
                else:
                    ts.append(now)
                    work += flops.decode_flops(dims,
                                               len(r.prompt) + len(ts) - 1)
        return work

    def waiting(self) -> int:
        return sum(1 for i, r in self.reqs.items()
                   if not self.times[i] and r.status in ("queued", "active"))


def run(*, cell, config, traffic, seed, seconds, trace, rehearse, device,
        t_start, control=False) -> harness.Run:
    import jax
    import jax.numpy as jnp

    from bench.reference import dense_lm
    from repro.core.context import make_context
    from repro.models import model as M
    from repro.serving import FeatureStore, Request, ServingEngine

    if rehearse:
        config = dict(config, **traffic["rehearsal"]["config"])
        traffic = dict(traffic, **traffic["rehearsal"]["traffic"])
    dims = config["dims"]
    eng_kw = traffic["engine"]
    spans = harness.Spans()
    compiles = harness.compile_counter()
    setup = {}

    t = time.perf_counter()
    cfg = arch_config(config)
    params = dense_lm.make_weights(seed, dims)
    want = jax.eval_shape(lambda k: M.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    if jax.tree_util.tree_structure(want) != \
            jax.tree_util.tree_structure(params) or any(
                a.shape != b.shape for a, b in zip(
                    jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(params))):
        raise ValueError("bench weights do not match the program's layout")
    jax.block_until_ready(params)
    setup["weights_s"] = time.perf_counter() - t

    t = time.perf_counter()
    tables = feature_tables(traffic["stores"], seed)
    ctx = make_context()
    stores = {}
    for attr, s in traffic["stores"].items():
        st = FeatureStore(ctx, attr, tables[attr],
                          probe_capacity=max(eng_kw["slots"], 8),
                          chunk_rows=s["chunk_rows"])
        lookup = st.lookup

        def timed(keys, _lookup=lookup):
            with spans("feature_lookup"):
                return _lookup(keys)
        st.lookup = timed
        stores[attr] = st
    setup["store_ingest_s"] = time.perf_counter() - t

    eng = ServingEngine(cfg, params, slots=eng_kw["slots"],
                        prompt_capacity=eng_kw["prompt_capacity"],
                        gen_capacity=eng_kw["gen_capacity"],
                        queue_capacity=eng_kw["queue_capacity"],
                        feature_stores=stores)
    # warm-up: one request touches the feature fetch, the slot prefill,
    # the cache insert and the decode step
    t = time.perf_counter()
    eng.submit(Request(req_id=-1, prompt=np.ones(8, np.int32), gen_len=2,
                       drug_id=0, cell_id=0))
    eng.run_until_drained()
    setup["warm_s"] = time.perf_counter() - t
    n0, setup["compile_s"] = compiles()
    base = dict(eng.metrics.counters)
    qw0 = len(eng.metrics.series["queue_wait"])
    setup_s = time.perf_counter() - t_start
    harness.log(f"set-up {setup_s:.3f}s {setup} compiles {n0} params "
                f"{cfg.param_count()}")

    key_space = {a: (s["rows"], s["zipf_s"])
                 for a, s in traffic["stores"].items()}
    planned = traffic_gen.plan(traffic, seconds, seed, dims["vocab_size"],
                               key_space)
    tr = harness.Trace(trace, spans, f"{cell['name']}-{seed}", seconds,
                       float(traffic["trace_seconds"]))
    track = Tracker()
    step_at, step_s, step_flops, late = [], [], [], []
    clock = time.perf_counter

    def step():
        t = clock()
        with spans("engine.step"):
            done = eng.step()
        now = clock()
        reqs = [eng.batch.request_at(s) for s in eng.batch.active()] + done
        step_at.append(t)
        step_s.append(now - t)
        step_flops.append(track.observe(reqs, now, dims))

    i = 0
    t0 = clock()
    while True:
        now = clock()
        if now - t0 >= seconds:
            break
        tr.poll(now - t0)
        while i < len(planned) and planned[i].due <= now - t0:
            p = planned[i]
            req = Request(req_id=p.idx, prompt=p.prompt, gen_len=p.gen_len,
                          **p.keys)
            with spans("submit"):
                eng.submit(req)
            track.submitted(req, t0 + p.due)
            late.append(clock() - (t0 + p.due))
            i += 1
        if eng.busy:
            step()
        else:
            nxt = planned[i].due if i < len(planned) else seconds
            time.sleep(max(0.0, min(nxt, seconds) - (clock() - t0)))
    t1 = clock()
    tr.stop()
    n_window_steps = len(step_s)
    backlog = len(eng.queue)
    qw1 = len(eng.metrics.series["queue_wait"])
    while track.waiting():                       # every first token
        step()
    n1, _ = compiles()

    # ---------------------------------------------------------- readings
    ttft, itl, tokens = [], [], 0
    for rid, ts in track.times.items():
        if ts:
            ttft.append(ts[0] - track.due[rid])
        tokens += sum(1 for x in ts if x <= t1)
        itl += [b - a for a, b in zip(ts, ts[1:]) if b <= t1]
    m = eng.metrics
    cnt = {k: m.count(k) - base.get(k, 0) for k in
           ("submitted", "completed", "rejected", "feature_misses")}
    in_flight = len(eng.queue) + len(eng.batch.active())
    harness.log(f"window {t1 - t0:.3f}s submitted {cnt['submitted']} "
                f"of {len(planned)} planned, steps {n_window_steps}, "
                f"compiles in window {n1 - n0}, generator late p50/max "
                f"{np.median(late) if late else 0:.6f}/"
                f"{max(late, default=0):.6f}s, counters {cnt}, "
                f"queued at close {backlog}, step ms p50/max "
                f"{1e3 * float(np.median(step_s[:n_window_steps] or [0])):.3f}/"
                f"{1e3 * max(step_s[:n_window_steps], default=0):.3f}")
    if tr.t0 is not None:                  # does tracing slow the loop?
        w = n_window_steps
        gap = [0.0] + [a - b - d for a, b, d in zip(
            step_at[1:w], step_at[:w - 1], step_s[:w - 1])]
        for part, keep in (("traced", True), ("untraced", False)):
            i = [j for j in range(w) if (tr.t0 <= step_at[j] < tr.t1) == keep]
            med = [1e3 * float(np.median([x[j] for j in i] or [0]))
                   for x in (step_s, gap)]
            harness.log(f"{part}: {len(i)} steps, ms p50 step {med[0]:.3f} "
                        f"gap between steps {med[1]:.3f}")
    harness.log("ttft ms p50/p80/p90/p95 " + "/".join(
        f"{1e3 * harness.nearest_rank(ttft, q):.3f}" for q in (50, 80, 90, 95))
        + f" over {len(ttft)} requests; {len(itl)} inter-token gaps")

    r = harness.Run()
    r.attempted = cnt["submitted"]
    r.failed = cnt["rejected"] + cnt["feature_misses"]
    r.end_to_end = {
        "serve_ttft_p50_ms": 1e3 * harness.nearest_rank(ttft, TTFT_Q),
        "serve_itl_p99_ms": 1e3 * harness.nearest_rank(itl, 99),
        "serve_tokens_per_s": tokens / (t1 - t0),
        "setup_s": setup_s,
    }
    r.samples = {"serve_ttft_p50_ms": len(ttft), "serve_itl_p99_ms": len(itl)}
    pk = harness.peaks(device["kind"]) if not rehearse else None
    w = n_window_steps
    qwait = m.series["queue_wait"][qw0:qw1]
    r.record = {
        "cell": cell["name"], "world": 1, "trace": tr.reduce(1),
        "mfu_serve_step": (100 * sum(step_flops[:w]) / (
            sum(step_s[:w]) * pk["bf16_flops_per_s"])) if pk else None,
        "feature_fetch_ms": 1e3 * spans.total("feature_lookup", t0, t1) / max(
            1, sum(1 for n, a, b in spans.records
                   if n == "feature_lookup" and t0 <= a and b <= t1)),
        "queue_wait_p95_ms": 1e3 * harness.nearest_rank(qwait, 95),
        "ttft_p80_ms": 1e3 * harness.nearest_rank(ttft, TTFT_TAIL_Q),
        "backlog_at_close": backlog, "completed": cnt["completed"],
    }

    # features and accounting, on the host
    feat_gap, count_gap = 0, 0
    finished = [q for q in track.reqs.values() if q.status == "done"]
    for q in finished:
        count_gap += int(len(q.out_tokens) != q.gen_len)
        for attr in traffic["stores"]:
            src = tables[attr]
            key = getattr(q, attr)
            for name, col in src.items():
                if name != attr and (q.features is None or
                                     q.features.get(name) != float(col[key])):
                    feat_gap += 1
    identity_gap = abs(cnt["submitted"] - cnt["completed"] - cnt["rejected"]
                       - cnt["feature_misses"] - in_flight)
    dropped = sum(s.dropped for s in stores.values())

    progs = [eng._prefill.lower(
                 eng.params, {"tokens": jnp.zeros(
                     (1, eng.prompt_capacity), jnp.int32)},
                 jnp.int32(1)).compile(),
             eng._decode.lower(eng.params, eng.caches,
                               jnp.asarray(eng.batch.tokens),
                               jnp.asarray(eng.batch.cache_lens)).compile()]
    r.memory_peak_bytes = harness.memory_peak_bytes(jax.devices()[:1], progs)

    # the reference, once the program is freed
    samp = sample(finished, seed, int(traffic["check"]["sample_tokens"]),
                  int(traffic["check"]["sample_requests"]))
    seqs = [(np.asarray(q.prompt), np.asarray(q.out_tokens)) for q in samp]
    del eng, params, stores, progs, track
    gc.collect()
    t = time.perf_counter()
    ref_w = dense_lm.make_weights(seed, dims)
    gaps = dense_lm.served_gaps(ref_w, dims, eng_kw["prompt_capacity"]
                                + eng_kw["gen_capacity"], seqs)
    harness.log(f"reference over {len(seqs)} requests, "
                f"{sum(len(s) for _, s in seqs)} served tokens, "
                f"{time.perf_counter() - t:.3f}s")
    if control:
        low = dense_lm.served_gaps(ref_w, dims, eng_kw["prompt_capacity"]
                                   + eng_kw["gen_capacity"], seqs, "fp8")
        r.record["control"] = {"logit_gap": max(
            (float(g.max()) for g in low), default=float("inf"))}
    lim = traffic["limits"]
    r.check("logit_gap", max((float(g.max()) for g in gaps),
                             default=float("inf")), lim["logit_gap"])
    r.check("feature_gap", feat_gap, 0)
    r.check("token_count_gap", count_gap, 0)
    r.check("identity_gap", identity_gap, 0)
    r.check("feature_rows_dropped", dropped, 0)
    return r


def sample(finished: list, seed: int, tokens: int, requests: int) -> list:
    """The finished request with the most served tokens, then others drawn
    from the seed until ``tokens`` served tokens and ``requests`` requests
    are covered."""
    if not finished:
        return []
    rng = np.random.default_rng([seed, 2])
    longest = max(finished, key=lambda q: len(q.out_tokens))
    rest = [q for q in finished if q is not longest]
    out, n = [longest], len(longest.out_tokens)
    for j in rng.permutation(len(rest)):
        if n >= tokens and len(out) >= requests:
            break
        out.append(rest[j])
        n += len(rest[j].out_tokens)
    return out
