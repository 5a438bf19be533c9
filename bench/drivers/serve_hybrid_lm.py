"""Driver for served hybrid models (Mamba-2 and attention mixers, experts
held as one chip's share of an expert-parallel layer): an open loop of
requests into the repo's ``ServingEngine``, each request's features
fetched from two ``FeatureStore``s — ``serve_lm``'s loop, stores, sample
and records, with this model's weights, FLOP counts and reference.

Set-up builds the weights on the device from the seed in the served
dtype, the feature tables, the engine, and warms every program one
request touches.  The window submits each planned request when it falls
due, steps the engine while it has work, stops offering load when
``--seconds`` have passed, then steps on until every submitted request has
its first token.  Latency runs from the time a request was due.

Once the window has closed and the program is freed, the reference
(``reference/granite_hybrid_lm.py``) is run over a sample of the finished
requests drawn from the seed; every served feature is compared with the
source tables, and the routed pairs the engine dropped are counted.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from bench import harness, hybrid_flops, traffic_gen
from bench.drivers.serve_lm import TTFT_Q, TTFT_TAIL_Q, Tracker, \
    feature_tables, sample


def arch_config(config: dict):
    """The program's configuration of this chip's share, checked against
    the file: the registry's model with the file's depth and held
    experts."""
    from repro.configs import get_config, get_reduced
    c = config
    base = (get_reduced if c.get("reduced_arch") else get_config)(c["arch"])
    cfg = dataclasses.replace(base, n_layers=c["num_hidden_layers"],
                              n_experts=c["num_local_experts"],
                              expert_offset=c["expert_offset"])
    ours = {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "intermediate_size": cfg.d_expert_ff,
            "shared_intermediate_size": cfg.d_shared_ff,
            "vocab_size": cfg.padded_vocab(),
            "tie_word_embeddings": cfg.tie_embeddings,
            "rms_norm_eps": cfg.norm_eps, "router_width": cfg.router_width,
            "num_experts_per_tok": cfg.top_k,
            "mamba_n_heads": cfg.ssm_heads, "mamba_d_head": cfg.ssm_head_dim,
            "mamba_d_state": cfg.ssm_state, "mamba_n_groups": cfg.ssm_groups,
            "mamba_d_conv": cfg.ssm_conv, "mamba_chunk_size": cfg.ssm_chunk,
            "mamba_expand": cfg.ssm_expand,
            "embedding_multiplier": cfg.embedding_multiplier,
            "attention_multiplier": cfg.attention_multiplier,
            "residual_multiplier": cfg.residual_multiplier,
            "logits_scaling": cfg.logits_scaling,
            "position_embedding_type": "rope" if cfg.use_rope else "nope",
            "attention_bias": cfg.qkv_bias, "mamba_conv_bias": True,
            "mamba_proj_bias": False,
            "layer_types": ["attention" if cfg._layer_has_attention(i)
                            else "mamba" for i in range(cfg.n_layers)]}
    theirs = {k: c[k] for k in ours}
    theirs["layer_types"] = list(c["layer_types"][:cfg.n_layers])
    if ours != theirs or cfg.d_head * cfg.n_heads != cfg.d_model:
        raise ValueError(f"program config {ours} != bench config {theirs}")
    return cfg


class HybridTracker(Tracker):
    """``serve_lm.Tracker`` with this model's useful FLOPs."""

    def observe(self, reqs, now: float, config: dict) -> float:
        work = 0.0
        for r in reqs:
            ts = self.times.get(r.req_id)
            if ts is None:
                continue
            while len(ts) < len(r.out_tokens):
                if not ts:                   # prefill's token
                    ts.append(r.t_first)
                    work += hybrid_flops.prefill_flops(config, len(r.prompt))
                else:
                    ts.append(now)
                    work += hybrid_flops.decode_flops(
                        config, len(r.prompt) + len(ts) - 1)
        return work


def run(*, cell, config, traffic, seed, seconds, trace, rehearse, device,
        t_start, control=False) -> harness.Run:
    import jax
    import jax.numpy as jnp

    from bench.reference import granite_hybrid_lm as ref
    from repro.core.context import make_context
    from repro.models import model as M
    from repro.serving import FeatureStore, Request, ServingEngine

    if rehearse:
        config = dict(config, **traffic["rehearsal"]["config"])
        traffic = dict(traffic, **traffic["rehearsal"]["traffic"])
    eng_kw = traffic["engine"]
    spans = harness.Spans()
    compiles = harness.compile_counter()
    setup = {}

    t = time.perf_counter()
    cfg = arch_config(config)
    params = ref.make_weights(seed, config)
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
    planned = traffic_gen.plan(traffic, seconds, seed,
                               config["vocab_size"], key_space)
    tr = harness.Trace(trace, spans, f"{cell['name']}-{seed}", seconds,
                       float(traffic["trace_seconds"]))
    track = HybridTracker()
    step_at, step_s, step_flops, late, live = [], [], [], [], []
    clock = time.perf_counter

    def step():
        t = clock()
        live.append(float(sum(eng.batch.cache_lens[s] + 1
                              for s in eng.batch.active())))
        with spans("engine.step"):
            done = eng.step()
        now = clock()
        reqs = [eng.batch.request_at(s) for s in eng.batch.active()] + done
        step_at.append(t)
        step_s.append(now - t)
        step_flops.append(track.observe(reqs, now, config))

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
    w = n_window_steps
    harness.log(f"window {t1 - t0:.3f}s submitted {cnt['submitted']} "
                f"of {len(planned)} planned, steps {w}, "
                f"compiles in window {n1 - n0}, generator late p50/max "
                f"{np.median(late) if late else 0:.6f}/"
                f"{max(late, default=0):.6f}s, counters {cnt}, "
                f"queued at close {backlog}, step ms p50/max "
                f"{1e3 * float(np.median(step_s[:w] or [0])):.3f}/"
                f"{1e3 * max(step_s[:w], default=0):.3f}, moe pairs dropped "
                f"{m.count('moe_pairs_dropped')}")
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
    qwait = m.series["queue_wait"][qw0:qw1]
    # the live cache positions a decode step read, over the traced steps
    traced = [j for j in range(w) if tr.t0 is not None
              and tr.t0 <= step_at[j] < tr.t1 and live[j] > 0]
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
        "decode_bytes": hybrid_flops.decode_bytes(
            config, eng.n_slots, float(np.mean([live[j] for j in traced])))
        if traced else None,
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
    pairs_dropped = m.count("moe_pairs_dropped")

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
    ref_w = ref.make_weights(seed, config)
    lens = (eng_kw["prompt_capacity"] + eng_kw["gen_capacity"],
            eng_kw["gen_capacity"])
    gaps = ref.served_gaps(ref_w, config, *lens, seqs)
    harness.log(f"reference over {len(seqs)} requests, "
                f"{sum(len(s) for _, s in seqs)} served tokens, "
                f"{time.perf_counter() - t:.3f}s")
    if control:
        low = ref.served_gaps(ref_w, config, *lens, seqs, "fp8")
        r.record["control"] = {"logit_gap": max(
            (float(g.max()) for g in low), default=float("inf"))}
    lim = traffic["limits"]
    r.check("logit_gap", max((float(g.max()) for g in gaps),
                             default=float("inf")), lim["logit_gap"])
    r.check("moe_pairs_dropped", pairs_dropped, 0)
    r.check("feature_gap", feat_gap, 0)
    r.check("token_count_gap", count_gap, 0)
    r.check("identity_gap", identity_gap, 0)
    r.check("feature_rows_dropped", dropped, 0)
    return r
