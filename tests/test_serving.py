"""Serving engine tests (repro/serving/).

Host-side unit tests for the admission queue (rejections counted at
capacity, never silent), the fixed-shape slot batcher, and the metrics
registry; engine-level tests on the reduced LM config (single jit trace
across heterogeneous request sizes, static batch shape across refills,
greedy-decode conformance against the one-shot serve path, feature
fusion and the accounting identity); a reduced-config e2e smoke through
the ``repro.launch.serve`` CLI with a pre-set ``XLA_FLAGS`` (the
append-merge re-exec fix); and world 2/4 subprocess conformance for the
feature-fetch path.
"""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import trace
from repro.configs import get_reduced
from repro.core.context import make_context
from repro.models import model as M
from repro.serving import (AdmissionQueue, FeatureStore, Request,
                           ServingEngine, ServingMetrics, SlotBatch)

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


# --------------------------------------------------------------------------
# admission queue: counted rejections
# --------------------------------------------------------------------------


def test_queue_rejects_counted_at_capacity():
    m = ServingMetrics()
    q = AdmissionQueue(2, m)
    assert q.offer("a") and q.offer("b")
    assert not q.offer("c")          # full: refused, counted
    assert not q.offer("d")
    assert m.count("submitted") == 4
    assert m.count("rejected") == 2
    assert len(q) == 2
    assert q.pop() == "a"            # FIFO
    assert q.offer("e")              # freed capacity admits again
    assert m.count("rejected") == 2
    # identity: everything offered is accounted for
    assert m.count("submitted") == len(q) + 1 + m.count("rejected")


def test_queue_validates_capacity():
    with pytest.raises(ValueError, match="capacity"):
        AdmissionQueue(0)
    assert AdmissionQueue(1).pop() is None


# --------------------------------------------------------------------------
# slot batcher: static shapes, refill semantics
# --------------------------------------------------------------------------


def test_slot_batch_lifecycle():
    b = SlotBatch(3)
    assert b.free() == [0, 1, 2] and b.occupancy == 0
    b.occupy(1, "r1", first_token=7, prompt_len=4, gen_target=2)
    assert b.active() == [1] and b.cache_lens[1] == 4 and b.tokens[1, 0] == 7
    with pytest.raises(ValueError, match="occupied"):
        b.occupy(1, "r2", first_token=0, prompt_len=1, gen_target=1)
    nxt = np.zeros((3, 1), np.int32)
    nxt[1, 0] = 9
    seen = []
    done = b.advance(nxt, on_token=lambda s, r, t: seen.append((s, r, t)))
    assert done == [1] and seen == [(1, "r1", 9)]       # hit gen_target=2
    assert b.cache_lens[1] == 5 and b.tokens[1, 0] == 9
    assert b.release(1) == "r1" and b.free() == [0, 1, 2]
    with pytest.raises(ValueError, match="free"):
        b.release(1)
    # shapes never change across occupy/release cycles
    assert b.cache_lens.shape == (3,) and b.tokens.shape == (3, 1)


def test_slot_batch_advance_skips_idle_slots():
    b = SlotBatch(2)
    b.occupy(0, "r", first_token=1, prompt_len=2, gen_target=5)
    before = b.cache_lens.copy()
    b.advance(np.zeros((2, 1), np.int32))
    assert b.cache_lens[1] == before[1]      # idle slot untouched
    assert b.cache_lens[0] == before[0] + 1


# --------------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------------


def test_metrics_registry():
    m = ServingMetrics()
    m.inc("x"), m.inc("x", 2)
    m.gauge("g", 3), m.gauge("g", 1)
    for v in (0.1, 0.2, 0.3):
        m.observe("lat", v)
    assert m.count("x") == 3 and m.count("missing") == 0
    assert m.gauges["g"] == {"last": 1.0, "max": 3.0}
    s = m.summary("lat")
    assert s["count"] == 3 and abs(s["p50"] - 0.2) < 1e-9
    snap = m.snapshot()
    assert snap["counters"]["x"] == 3 and "lat" in snap["latency"]
    assert m.summary("none") == {"count": 0}
    assert np.isnan(m.percentile("none", 50))


# --------------------------------------------------------------------------
# engine on the reduced config
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    """One engine run over heterogeneous requests + feature stores;
    several tests assert different properties of the same run."""
    cfg = get_reduced("lm100m")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    ctx = make_context(jax.make_mesh((1,), ("rows",)))
    n_keys = 32
    rng = np.random.default_rng(1)
    feats = {"drug_id": np.arange(n_keys, dtype=np.int32),
             "d0": rng.normal(size=n_keys).astype(np.float32)}
    store = FeatureStore(ctx, "drug_id", feats, probe_capacity=8,
                         chunk_rows=8)
    eng = ServingEngine(cfg, params, slots=2, prompt_capacity=12,
                        gen_capacity=6, queue_capacity=4,
                        feature_stores={"drug_id": store})
    reqs = []
    # heterogeneous prompt lengths and gen lengths, incl. the gen_len=1
    # immediate-completion edge and one key with no feature row
    for i, (p_len, g) in enumerate([(12, 6), (1, 1), (5, 3), (9, 2),
                                    (3, 4), (7, 1), (2, 5), (11, 3)]):
        reqs.append(Request(
            req_id=i, prompt=rng.integers(0, cfg.vocab, p_len
                                          ).astype(np.int32),
            gen_len=g, drug_id=(999 if i == 3 else i)))
    t0 = time.perf_counter()
    rejected = [r for r in reqs if not eng.submit(r)]
    done = eng.run_until_drained()
    # resubmit anything rejected by the small queue (accounted above)
    for r in rejected:
        assert eng.submit(r)
    done += eng.run_until_drained()
    # the engine's spans of this run alone (the recorder is process-wide)
    spans = {n: [r for r in trace.records(n) if r.start >= t0]
             for n in ("serve/step", "serve/refill", "serve/feature_fetch",
                       "serve/prefill", "serve/decode", "serve/device_wait")}
    return eng, store, feats, reqs, rejected, done, spans


def test_engine_every_admitted_request_completes(served):
    eng, store, feats, reqs, rejected, done, _ = served
    m = eng.metrics
    assert m.count("submitted") == m.count("completed") + \
        m.count("rejected") + m.count("feature_misses")
    assert m.count("rejected") == len(rejected)
    by_id = {r.req_id: r for r in done}
    assert sorted(by_id) == list(range(len(reqs)))   # nobody lost
    for r in done:
        if r.req_id == 3:
            assert r.status == "feature_miss"        # counted terminal
        else:
            assert r.status == "done"
            assert len(r.out_tokens) == r.gen_len
            np.testing.assert_allclose(
                r.features["d0"], feats["d0"][r.drug_id])   # joined row
    assert m.count("feature_misses") == 1
    assert store.dropped == 0


def test_engine_one_trace_across_heterogeneous_requests(served):
    eng, *_ = served
    # every prompt length / gen length re-entered the same cached
    # executables: fixed padded prefill shape, fixed decode batch shape
    assert eng._prefill._cache_size() == 1
    assert eng._decode._cache_size() == 1
    assert eng._insert._cache_size() == 1


def test_engine_static_batch_shape_across_refills(served):
    eng, *_ = served
    struct = M.cache_struct(eng.cfg, eng.n_slots, eng.decode_len)
    got = jax.tree_util.tree_map(lambda x: x.shape, eng.caches)
    want = jax.tree_util.tree_map(lambda s: s.shape, struct)
    assert got == want


def test_engine_spans_one_prefill_per_admitted_request(served):
    eng, _, _, reqs, _, done, spans = served
    pre = spans["serve/prefill"]
    admitted = [r for r in done if r.status == "done"]
    assert len(pre) == eng.metrics.count("admitted") == len(admitted)
    assert sorted(p.attrs["req_id"] for p in pre) == \
        sorted(r.req_id for r in admitted)
    for p in pre:
        r = reqs[p.attrs["req_id"]]
        assert p.attrs["prompt_len"] == len(r.prompt)
        assert p.attrs["positions"] == eng.prompt_capacity
        assert p.parent == "serve/refill"
    fetched = sorted(i for f in spans["serve/feature_fetch"]
                     for i in f.attrs["req_ids"])
    assert fetched == sorted(r.req_id for r in done)   # misses included


def test_engine_spans_one_decode_per_step(served):
    eng, *_, spans = served
    dec, wait = spans["serve/decode"], spans["serve/device_wait"]
    assert len(dec) == eng.metrics.count("decode_steps") > 0
    assert len(wait) == len(dec)
    assert all(w.parent == "serve/decode" for w in wait)
    assert all(d.parent == "serve/step" for d in dec)
    host = trace.self_times("serve/decode", ["serve/device_wait"])
    assert all(h >= 0 for h in host)


def test_engine_queue_wait_ends_before_the_first_token(served):
    _, _, _, reqs, _, done, _ = served
    for r in done:
        assert r.t_submit <= r.t_admit
        if r.status == "done":
            assert r.t_admit - r.t_submit <= r.t_first - r.t_submit


def test_engine_validates_request_bounds(served):
    eng, *_ = served
    with pytest.raises(ValueError, match="prompt length"):
        eng.submit(Request(req_id=99, prompt=np.zeros(13, np.int32),
                           gen_len=1, drug_id=0))
    with pytest.raises(ValueError, match="gen_len"):
        eng.submit(Request(req_id=99, prompt=np.zeros(1, np.int32),
                           gen_len=7, drug_id=0))


def test_engine_matches_oneshot_greedy_decode():
    """A request decoded through slot refill + per-slot cache lengths
    emits the same greedy tokens as the one-shot prefill/serve path."""
    cfg = get_reduced("lm100m")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    P, G = 10, 5
    rng = np.random.default_rng(2)
    for p_len in (P, 4):             # full-capacity and right-padded
        prompt = rng.integers(0, cfg.vocab, p_len).astype(np.int32)

        # reference: exact-length one-shot decode (launch/serve's loop)
        prefill = jax.jit(M.make_prefill(cfg, None, decode_len=P + G))
        serve = jax.jit(M.make_serve_step(cfg, None))
        logits, caches = prefill(params, {"tokens": jnp.asarray(
            prompt[None])})
        tok = int(jnp.argmax(logits, -1)[0])
        want = [tok]
        for i in range(G - 1):
            logits, caches = serve(params, caches,
                                   jnp.asarray([[tok]], jnp.int32),
                                   jnp.int32(p_len + i))
            tok = int(jnp.argmax(logits, -1)[0])
            want.append(tok)

        eng = ServingEngine(cfg, params, slots=3, prompt_capacity=P,
                            gen_capacity=G, queue_capacity=4)
        req = Request(req_id=0, prompt=prompt, gen_len=G)
        assert eng.submit(req)
        done = eng.run_until_drained()
        assert done[0].out_tokens == want, f"p_len={p_len}"


def test_engine_rejects_nonlm_config():
    import dataclasses
    cfg = dataclasses.replace(get_reduced("lm100m"), frontend="vision")
    with pytest.raises(ValueError, match="decoder-only"):
        ServingEngine(cfg, params={}, slots=1)


def test_feature_store_validation():
    ctx = make_context(jax.make_mesh((1,), ("rows",)))
    with pytest.raises(ValueError, match="probe_capacity"):
        FeatureStore(ctx, "k", {"k": np.arange(4)}, probe_capacity=0)
    with pytest.raises(ValueError, match="key column"):
        FeatureStore(ctx, "nope", {"k": np.arange(4)}, probe_capacity=4)
    store = FeatureStore(ctx, "k", {"k": np.arange(4)}, probe_capacity=4)
    with pytest.raises(ValueError, match="exceed"):
        store.lookup(np.zeros(5, np.int32))
    with pytest.raises(ValueError, match="1-D"):
        store.lookup(np.zeros((2, 2), np.int32))


# --------------------------------------------------------------------------
# e2e smoke through the CLI (XLA_FLAGS preset: the append-merge fix)
# --------------------------------------------------------------------------


def test_serve_cli_e2e_reduced_with_preset_xla_flags():
    env = dict(os.environ)
    # pre-existing unrelated XLA flag: the launcher must append the
    # device-count flag (the old code skipped re-exec and crashed the
    # mesh build); a stale count must be replaced, then terminate
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1 " \
                       "--xla_cpu_enable_fast_math=false"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "lm100m",
         "--reduced", "--requests", "6", "--slots", "2", "--prompt-len",
         "8", "--gen", "4", "--queue-capacity", "8",
         "--mesh", "data=1,model=2"],
        env=env, capture_output=True, text=True, timeout=1800)
    sys.stdout.write(proc.stdout[-4000:])
    sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0
    assert "serve OK" in proc.stdout


# --------------------------------------------------------------------------
# world 2/4 feature-fetch conformance (subprocess, forced host devices)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("world", [2, 4])
def test_serving_feature_conformance(world):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={world}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(HERE, "dist", "serving_conformance.py"), str(world)],
        env=env, capture_output=True, text=True, timeout=1800)
    sys.stdout.write(proc.stdout[-4000:])
    sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0, f"serving conformance failed " \
                                 f"(world={world})"
    assert "SERVING CONFORMANCE PASSED" in proc.stdout
