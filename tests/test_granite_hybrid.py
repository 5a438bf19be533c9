"""granite-4.0-h-small (Mamba-2 + NoPE attention + held experts with a
shared one, Granite's multipliers) against the plain float32 reference
``bench/reference/granite_hybrid_lm.py``, at the reduced size on seeded
random weights built in the program's layout."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import granite_hybrid_lm as ref
from repro.configs import get_reduced
from repro.models import layers as Ly
from repro.models import mamba as Mb
from repro.models import model as M
from repro.models import moe as Moe

ARCH = "granite-4.0-h-small"


def ref_config(cfg) -> dict:
    """The reference's configuration (``config.json`` keys) of ``cfg``."""
    return {
        "hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layers,
        "vocab_size": cfg.padded_vocab(),
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "mamba_n_heads": cfg.ssm_heads, "mamba_d_head": cfg.ssm_head_dim,
        "mamba_d_state": cfg.ssm_state, "mamba_n_groups": cfg.ssm_groups,
        "mamba_d_conv": cfg.ssm_conv, "intermediate_size": cfg.d_expert_ff,
        "shared_intermediate_size": cfg.d_shared_ff,
        "num_local_experts": cfg.n_experts, "router_width": cfg.router_width,
        "expert_offset": cfg.expert_offset, "num_experts_per_tok": cfg.top_k,
        "rms_norm_eps": cfg.norm_eps,
        "layer_types": ["attention" if cfg._layer_has_attention(i) else "mamba"
                        for i in range(cfg.n_layers)],
        "embedding_multiplier": cfg.embedding_multiplier,
        "attention_multiplier": cfg.attention_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "logits_scaling": cfg.logits_scaling}


def test_reference_weights_are_the_programs_layout():
    cfg = get_reduced(ARCH)
    w = jax.eval_shape(lambda: ref.make_weights(0, ref_config(cfg)))
    want = jax.eval_shape(lambda k: M.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(w) == \
        jax.tree_util.tree_structure(want)
    assert [a.shape for a in jax.tree_util.tree_leaves(w)] == \
        [a.shape for a in jax.tree_util.tree_leaves(want)]


@pytest.mark.parametrize("lengths", [(13, 3), (24, 8)])
def test_slot_prefill_then_decode_matches_reference(lengths):
    """Two slots, each prefilled right-padded to the capacity and written
    into the running cache, then decoded through it teacher-forced: every
    logit row against the reference's full forward pass.  Lengths cover a
    prompt that is no multiple of the chunk (8), one far below the
    capacity (24), one at it and one of exactly a chunk."""
    cfg = get_reduced(ARCH)
    conf = ref_config(cfg)
    w = ref.make_weights(7, conf)
    P, G = 24, 6
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, cfg.vocab, n + G - 1).astype(np.int32)
            for n in lengths]
    prefill = jax.jit(M.make_slot_prefill(cfg, None, decode_len=P + G))
    serve = jax.jit(M.make_serve_step(cfg, None))
    insert = jax.jit(M.write_cache_slot)
    caches = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                                    M.cache_struct(cfg, 2, P + G))
    got = [[], []]
    for slot, (n, seq) in enumerate(zip(lengths, seqs)):
        padded = np.zeros((1, P), np.int32)
        padded[0, :n] = seq[:n]
        logits, one, st = prefill(w, {"tokens": jnp.asarray(padded)},
                                  jnp.int32(n))
        assert int(st["moe_pairs_dropped"]) == 0
        caches = insert(caches, one, jnp.int32(slot))
        got[slot].append(np.asarray(logits[0]))
    for i in range(G - 1):
        toks = np.array([[s[n + i]] for n, s in zip(lengths, seqs)],
                        np.int32)
        lens = np.array([n + i for n in lengths], np.int32)
        logits, caches, st = serve(w, caches, jnp.asarray(toks),
                                   jnp.asarray(lens))
        assert int(st["moe_pairs_dropped"]) == 0
        assert st["expert_pairs"].shape == (cfg.n_layers, cfg.n_experts)
        for slot in range(2):
            got[slot].append(np.asarray(logits[slot]))
    fwd = jax.jit(lambda t: ref.forward(w, t, conf))
    for n, seq, rows in zip(lengths, seqs, got):
        want = np.asarray(fwd(jnp.asarray(seq)))[n - 1:]
        err = np.abs(np.stack(rows) - want) / want.std()
        # the program's residual stream and products are bfloat16 (8-bit
        # mantissa) through ten layers against a float32 reference: its
        # logits sit 1.6-3.9% of their spread off on average and at most
        # 27% in one entry; a multiplier left out moves them 9-65% on
        # average, a wrong score scale 9-12%
        assert err.mean() < 0.06 and err.max() < 0.5, (n, err.mean(),
                                                       err.max())


def _sequential_ssd(x, dt, A, Bm, Cm):
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t, one
    position after another (numpy, float64)."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2:]
    grp = np.arange(H) // (H // G)
    y = np.zeros((Bsz, S, H, P))
    hs = np.zeros((Bsz, S, H, P, N))
    for b in range(Bsz):
        h = np.zeros((H, P, N))
        for t in range(S):
            h = np.exp(dt[b, t] * A)[:, None, None] * h + \
                (dt[b, t][:, None] * x[b, t])[..., None] * \
                Bm[b, t][grp][:, None, :]
            y[b, t] = np.einsum("hpn,hn->hp", h, Cm[b, t][grp])
            hs[b, t] = h
    return y, hs


def test_ssd_chunked_matches_sequential_recurrence():
    """The chunked SSD form (chunk 8) against the recurrence, and the
    state of a right-padded sequence taken at its true length: dt is
    zeroed past it, so the padding leaves the state unchanged."""
    rng = np.random.default_rng(1)
    Bsz, S, H, P, G, N = 2, 21, 4, 3, 2, 5
    x = rng.standard_normal((Bsz, S, H, P)).astype(np.float32)
    dt = np.log1p(np.exp(rng.standard_normal((Bsz, S, H)))).astype(
        np.float32)
    A = -np.exp(rng.standard_normal(H)).astype(np.float32)
    Bm = rng.standard_normal((Bsz, S, G, N)).astype(np.float32)
    Cm = rng.standard_normal((Bsz, S, G, N)).astype(np.float32)
    want, hs = _sequential_ssd(*(v.astype(np.float64)
                                 for v in (x, dt, A, Bm, Cm)))
    y, hT = Mb.ssd_chunked(jnp.asarray(x), jnp.asarray(dt), jnp.asarray(A),
                           jnp.asarray(Bm), jnp.asarray(Cm), 8)
    # float32 against float64, summed in another order: 1e-4 of unit-scale
    # values
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hT), hs[:, -1], rtol=1e-4,
                               atol=1e-4)
    lengths = np.array([21, 11])
    _, hL = Mb.ssd_chunked(jnp.asarray(x),
                           Mb._live(jnp.asarray(dt), jnp.asarray(lengths)),
                           jnp.asarray(A), jnp.asarray(Bm), jnp.asarray(Cm),
                           8)
    for b, n in enumerate(lengths):
        np.testing.assert_allclose(np.asarray(hL[b]), hs[b, n - 1],
                                   rtol=1e-4, atol=1e-4)


def test_mamba2_full_sequence_matches_stepwise_decode():
    cfg = get_reduced(ARCH)
    p = Mb.mamba2_init(jax.random.PRNGKey(3), cfg)
    B, S = 2, 19
    x = jax.random.normal(jax.random.PRNGKey(4), (B, S, cfg.d_model))
    y_full, st = Mb.mamba2_apply(p, cfg, x, return_state=True)
    conv = cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
    state = {"conv": jnp.zeros((B, cfg.ssm_conv - 1, conv)),
             "ssm": jnp.zeros((B, cfg.ssm_heads, cfg.ssm_head_dim,
                               cfg.ssm_state))}
    ys = []
    for t in range(S):
        y_t, state = Mb.mamba2_step(p, cfg, x[:, t:t + 1], state)
        ys.append(y_t)
    # both paths round the projections to bfloat16; the chunked scan's
    # matrix products and the step's elementwise update differ in order
    np.testing.assert_allclose(np.asarray(jnp.concatenate(ys, 1), np.float32),
                               np.asarray(y_full, np.float32),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(state["ssm"]),
                               np.asarray(st["ssm"]), rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(state["conv"]),
                               np.asarray(st["conv"]), rtol=1e-6)


def test_held_expert_shares_add_up_to_the_whole_layer():
    """Four disjoint shares of 3 experts of a 12-wide router, each routing
    over all 12 and computing only its own experts, plus the shared expert
    once, give the whole layer: the program's own whole layer (every
    expert held) and the reference's."""
    cfg = get_reduced(ARCH)
    W = cfg.router_width
    whole = dataclasses.replace(cfg, n_experts=W, expert_offset=0)
    conf = ref_config(whole)
    lw = ref.make_weights(5, conf, dtype=jnp.float32)["layers"]["sub0"]
    m = jax.tree_util.tree_map(lambda a: a[0], lw["ffn_moe"])
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 11, cfg.d_model))
    total, pairs = 0.0, 0
    for off in range(0, W, cfg.n_experts):
        share = dataclasses.replace(cfg, expert_offset=off)
        p = {"router": m["router"]} | {
            k: m[k][off:off + cfg.n_experts]
            for k in ("e_gate", "e_up", "e_down")}
        y, _, counts = Moe.moe_dense(p, share, x)
        total, pairs = total + y, pairs + int(counts.sum())
    assert pairs == x.shape[0] * x.shape[1] * cfg.top_k
    total = total + Ly.swiglu(m["shared"], x)
    mine, stats = Moe.moe_apply(m, whole, x)
    # same bfloat16 products, the pairs' float32 sum split four ways
    np.testing.assert_allclose(np.asarray(total), np.asarray(mine),
                               rtol=1e-5, atol=1e-6)
    want = jax.vmap(lambda t: ref._moe(m, t, ref.shape(conf), None))(x)
    # bfloat16 inputs and weights in the program, float32 in the reference
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=3e-2, atol=3e-2 * float(
                                   np.abs(want).max()))


def test_multipliers_at_their_defaults_leave_the_dense_stack_unchanged():
    """granite-3-2b's program config keeps every multiplier at its
    default; the stack's logits are bit-identical to the plain stack (no
    multiplier applied) built from the same layers."""
    cfg = get_reduced("granite-3-2b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(np.random.default_rng(2).integers(
        0, cfg.vocab, (2, 16)), jnp.int32)

    @jax.jit
    def program(params, toks):
        x, _, _, _ = M.backbone(params, cfg, {"tokens": toks}, None,
                                M.opts_from_cfg(cfg))
        return M.logits(params, cfg, x)

    @jax.jit
    def plain(params, toks):
        x = Ly.embed_lookup(params["embed"], toks)
        pos = jnp.broadcast_to(jnp.arange(toks.shape[1]), toks.shape)

        def layer(x, p):
            h = Ly.rms_norm(p["ln1"], x, cfg.norm_eps)
            y, _ = Ly.attn_apply(p["attn"], cfg, h, pos, train_mode=False)
            x = x + y
            h = Ly.rms_norm(p["ln2"], x, cfg.norm_eps)
            return x + Ly.swiglu(p["ffn_mlp"], h), None

        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = Ly.rms_norm(params["final_norm"], x, cfg.norm_eps)
        return Ly.logits_out(None, x, tied_embed=params["embed"])

    np.testing.assert_array_equal(np.asarray(program(params, toks)),
                                  np.asarray(plain(params, toks)))


@pytest.mark.parametrize("arch", ["falcon-mamba-7b", "jamba-1.5-large-398b",
                                  ARCH])
def test_padded_slot_prefill_takes_the_state_at_the_true_length(arch):
    """A prompt right-padded to the slot capacity leaves every SSM layer's
    conv and SSM state as the prompt alone leaves it (the padding's dt is
    zeroed), and so the same first token."""
    cfg = get_reduced(arch)
    params = M.init_params(jax.random.PRNGKey(1), cfg)
    P, n = 16, 5
    prompt = np.random.default_rng(3).integers(0, cfg.vocab, n).astype(
        np.int32)
    padded = np.zeros((1, P), np.int32)
    padded[0, :n] = prompt
    logits, caches, _ = jax.jit(M.make_slot_prefill(cfg, None, decode_len=P))(
        params, {"tokens": jnp.asarray(padded)}, jnp.int32(n))
    want_logits, want, _ = jax.jit(M.make_prefill(cfg, None, decode_len=P))(
        params, {"tokens": jnp.asarray(prompt[None])})
    leaves = jax.tree_util.tree_leaves_with_path(caches)
    states = {jax.tree_util.keystr(k): v for k, v in leaves
              if "conv" in jax.tree_util.keystr(k)
              or "ssm" in jax.tree_util.keystr(k)}
    assert states
    for k, v in jax.tree_util.tree_leaves_with_path(want):
        name = jax.tree_util.keystr(k)
        if name in states:
            # float32 state; the padded and exact runs round their bfloat16
            # projections alike, and sum the same terms
            np.testing.assert_allclose(np.asarray(states[name]),
                                       np.asarray(v), rtol=1e-4, atol=1e-5,
                                       err_msg=name)
    assert int(jnp.argmax(logits)) == int(jnp.argmax(want_logits))


def test_engine_matches_oneshot_greedy_decode():
    """granite-4.0-h-small through the engine (Mamba-2 state and KV in one
    slot cache, held experts) emits the one-shot path's greedy tokens for
    prompts below the slot capacity; no routed pair dropped."""
    from test_serving import engine_matches_oneshot
    cfg = get_reduced(ARCH)
    eng = engine_matches_oneshot(cfg, ref.make_weights(3, ref_config(cfg)))
    assert eng.metrics.count("moe_pairs_dropped") == 0
