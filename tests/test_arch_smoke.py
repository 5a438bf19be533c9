"""Per-architecture smoke tests (assignment deliverable f).

For each of the 10 assigned architectures: instantiate the REDUCED config
of the same family and run one forward/train step on CPU, asserting output
shapes and no NaNs.  (The FULL configs are exercised only via the dry-run.)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, SHAPES, cells_for, get_config, \
    get_reduced
from repro.models import model as M
from repro.optim import adamw

B, S = 2, 32


def _batch(cfg, B=B, S=S, seed=0):
    rng = np.random.default_rng(seed)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32),
    }
    if cfg.frontend == "vision":
        batch["patch_embeds"] = jnp.asarray(
            rng.normal(size=(B, cfg.frontend_tokens, cfg.d_model)),
            jnp.bfloat16)
    if cfg.is_encdec:
        batch["frames"] = jnp.asarray(
            rng.normal(size=(B, S // cfg.enc_len_ratio, cfg.d_model)),
            jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_train_step(arch):
    cfg = get_reduced(arch)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    opt_cfg = adamw.AdamWConfig()
    opt = adamw.init(params, opt_cfg)
    step = jax.jit(M.make_train_step(cfg, None, opt_cfg))
    p2, o2, metrics = step(params, opt, _batch(cfg))
    loss = float(metrics["loss"])
    assert np.isfinite(loss)
    assert loss > 0
    assert int(o2["step"]) == 1
    # params actually moved
    d0 = jax.tree_util.tree_leaves(params)[0]
    d1 = jax.tree_util.tree_leaves(p2)[0]
    assert not np.allclose(np.asarray(d0), np.asarray(d1))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_loss_decreases(arch):
    """Two steps on the same batch must reduce the loss (sanity that the
    whole grad path is wired for every family)."""
    cfg = get_reduced(arch)
    params = M.init_params(jax.random.PRNGKey(1), cfg)
    opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=0)
    opt = adamw.init(params, opt_cfg)
    step = jax.jit(M.make_train_step(cfg, None, opt_cfg))
    batch = _batch(cfg, seed=1)
    losses = []
    for _ in range(3):
        params, opt, metrics = step(params, opt, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_prefill_then_decode(arch):
    """Prefill emits caches; serve_step consumes them; logits stay finite
    and shaped (B, V)."""
    cfg = get_reduced(arch)
    params = M.init_params(jax.random.PRNGKey(2), cfg)
    decode_len = S + 4
    prefill = jax.jit(M.make_prefill(cfg, None, decode_len=decode_len))
    serve = jax.jit(M.make_serve_step(cfg, None))
    batch = _batch(cfg)
    batch.pop("labels")
    logits, caches, _ = prefill(params, batch)
    V = cfg.padded_vocab()
    assert logits.shape == (B, V)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    for i in range(2):
        logits, caches, _ = serve(params, caches, tok, jnp.int32(S + i))
        assert logits.shape == (B, V)
        assert np.isfinite(np.asarray(logits, np.float32)).all()
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]


def test_decode_matches_teacher_forcing_dense():
    """Strong consistency: greedy decode logits == full-sequence forward
    logits at the same positions (dense arch; bf16 tolerance)."""
    cfg = get_reduced("granite-3-2b")
    params = M.init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (1, 8)), jnp.int32)

    # full forward logits at the last position
    opts = M.opts_from_cfg(cfg)
    x, _, _, _ = M.backbone(params, cfg, {"tokens": toks}, None, opts)
    from repro.models import layers as Ly
    full_logits = Ly.logits_out(
        params.get("lm_head"), x,
        tied_embed=params["embed"] if cfg.tie_embeddings else None)

    # prefill on first 7 tokens, decode token 8
    decode_len = 12
    prefill = M.make_prefill(cfg, None, decode_len=decode_len)
    serve = M.make_serve_step(cfg, None)
    _, caches, _ = prefill(params, {"tokens": toks[:, :7]})
    step_logits, _, _ = serve(params, caches, toks[:, 7:8], jnp.int32(7))
    np.testing.assert_allclose(np.asarray(step_logits),
                               np.asarray(full_logits[:, 7]),
                               rtol=3e-2, atol=3e-2)


def test_decode_matches_teacher_forcing_ssm():
    cfg = get_reduced("falcon-mamba-7b")
    params = M.init_params(jax.random.PRNGKey(4), cfg)
    rng = np.random.default_rng(4)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (1, 8)), jnp.int32)
    opts = M.opts_from_cfg(cfg)
    x, _, _, _ = M.backbone(params, cfg, {"tokens": toks}, None, opts)
    from repro.models import layers as Ly
    full_logits = Ly.logits_out(
        params.get("lm_head"), x,
        tied_embed=params["embed"] if cfg.tie_embeddings else None)
    prefill = M.make_prefill(cfg, None, decode_len=12)
    serve = M.make_serve_step(cfg, None)
    _, caches, _ = prefill(params, {"tokens": toks[:, :7]})
    step_logits, _, _ = serve(params, caches, toks[:, 7:8], jnp.int32(7))
    np.testing.assert_allclose(np.asarray(step_logits),
                               np.asarray(full_logits[:, 7]),
                               rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    """Config registry carries the exact published sizes."""
    cfg = get_config(arch)
    spec = {
        "qwen1.5-110b": (80, 8192, 64, 8, 49152, 152064),
        "minitron-4b": (32, 3072, 24, 8, 9216, 256000),
        "mistral-large-123b": (88, 12288, 96, 8, 28672, 32768),
        "granite-3-2b": (40, 2048, 32, 8, 8192, 49155),
        "qwen3-moe-235b-a22b": (94, 4096, 64, 4, 0, 151936),
        "granite-moe-3b-a800m": (32, 1536, 24, 8, 0, 49155),
        "internvl2-2b": (24, 2048, 16, 8, 8192, 92553),
        "seamless-m4t-large-v2": (24, 1024, 16, 16, 8192, 256206),
        "falcon-mamba-7b": (64, 4096, 0, 0, 0, 65024),
        "jamba-1.5-large-398b": (72, 8192, 64, 8, 24576, 65536),
        "granite-4.0-h-small": (40, 4096, 32, 8, 0, 100352),
    }[arch]
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.d_ff, cfg.vocab) == spec


def test_param_counts_in_published_ballpark():
    """Analytic param counts should be near the advertised sizes."""
    expect = {
        "qwen1.5-110b": 111e9,
        "minitron-4b": 4.8e9,        # embeddings dominate (256k vocab)
        "mistral-large-123b": 123e9,
        "granite-3-2b": 2.6e9,
        "qwen3-moe-235b-a22b": 235e9,
        "falcon-mamba-7b": 7.3e9,
        "jamba-1.5-large-398b": 398e9,
    }
    for arch, n in expect.items():
        got = get_config(arch).param_count()
        assert 0.75 * n < got < 1.30 * n, (arch, got, n)


def test_moe_active_params():
    cfg = get_config("qwen3-moe-235b-a22b")
    active = cfg.active_param_count()
    assert 15e9 < active < 30e9          # A22B
    assert active < cfg.param_count() / 5


def test_cells_for_respects_skips():
    # ssm/hybrid run long_500k; pure-attention archs skip it
    assert "long_500k" in cells_for("falcon-mamba-7b")
    assert "long_500k" in cells_for("jamba-1.5-large-398b")
    assert "long_500k" not in cells_for("qwen1.5-110b")
    for arch in ARCH_IDS:
        assert "train_4k" in cells_for(arch)
        assert "decode_32k" in cells_for(arch)


def test_shapes_registry():
    assert SHAPES["train_4k"].seq_len == 4096
    assert SHAPES["train_4k"].global_batch == 256
    assert SHAPES["prefill_32k"].kind == "prefill"
    assert SHAPES["decode_32k"].kind == "decode"
    assert SHAPES["long_500k"].seq_len == 524_288
