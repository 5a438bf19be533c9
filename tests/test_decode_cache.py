"""The decode step's cache write: each slot's new K and V go into the
donated stacked cache at its own position, and nothing else of the cache is
copied or rewritten.

The written caches are checked bit for bit against the step as it used to
be — each attention layer blends its token into its cache with a one-hot
mask inside the layer scan, then attends over the written cache — and the
compiled step is checked for what it must not hold: a copy of a whole
stacked K or V leaf, or temporaries the size of the cache."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import attention as A
from repro.models import layers as Ly
from repro.models import mamba as Mb
from repro.models import model as M
from repro.models import transformer as Tf

S = 24                                   # cache positions


def one_hot_step(params, cfg, caches, tokens, cache_len):
    """The serve step with the one-hot write: every layer's whole cache is
    rewritten (``cache * (1 - oh) + new * oh``) and returned by the scan."""
    opts = M.opts_from_cfg(cfg)
    cl = jnp.asarray(cache_len)
    B = tokens.shape[0]

    def attn(p, h, cache):
        _, new = Ly.attn_decode(p, cfg, h, cache, cache_len)
        q = Ly._split_heads(Ly.dense(p["wq"], h), cfg.n_heads, cfg.d_head)
        if cfg.qk_norm:
            q = Ly.rms_norm(p["q_norm"], q, cfg.norm_eps)
        if cfg.use_rope:
            q = Ly.rope(q, cl if cl.ndim == 0 else cl[:, None],
                        cfg.rope_theta)
        oh = (jnp.arange(S)[None, :] == jnp.broadcast_to(cl, (B,))[:, None])
        oh = oh.astype(cache["k"].dtype)[:, None, :, None]
        cache = {n: cache[n] * (1 - oh) + new[n] * oh for n in ("k", "v")}
        o = A.decode_attention(q, cache["k"], cache["v"], cache_len,
                               scale=Ly.score_scale(cfg))
        y = o.transpose(0, 2, 1, 3).reshape(B, 1, -1)
        return Ly.dense(p["wo"], y), cache

    def layer(p, x, cache):
        h = Ly.rms_norm(p["ln1"], x, cfg.norm_eps)
        if "attn" in p:
            y, cache = attn(p["attn"], h, cache)
        else:
            step = Mb.mamba2_step if cfg.ssm_heads else Mb.mamba_step
            y, cache = step(p["mamba"], cfg, h, cache)
        x, _ = Tf._apply_ffn(p, cfg, x + y * cfg.residual_multiplier, None,
                             opts, decode=True)
        return x, cache

    def body(x, inp):
        p, cache = inp
        if cfg.layer_period == 1:
            return layer(p, x, cache)
        out = {}
        for name in Tf._sublayers(cfg.layer_period):
            x, out[name] = layer(p[name], x, cache[name])
        return x, out

    x = Ly.embed_lookup(params["embed"], tokens) * cfg.embedding_multiplier
    x, caches = jax.lax.scan(body, x, (params["layers"], caches))
    x = Ly.rms_norm(params["final_norm"], x, cfg.norm_eps)
    return M.logits(params, cfg, x)[:, 0], caches


def random_caches(cfg, B, seed):
    """Every leaf filled with seeded noise, so that a position written where
    it should not be, or left unwritten, shows."""
    struct = M.cache_struct(cfg, B, S)
    keys = jax.random.split(jax.random.PRNGKey(seed),
                            len(jax.tree_util.tree_leaves(struct)))
    leaves = [jax.random.normal(k, s.shape, jnp.float32).astype(s.dtype)
              for k, s in zip(keys, jax.tree_util.tree_leaves(struct))]
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(struct), leaves)


@pytest.mark.parametrize("arch", ["granite-3-2b", "granite-4.0-h-small"])
@pytest.mark.parametrize("lens", ["per_slot", "scalar"])
def test_cache_write_matches_one_hot_write(arch, lens):
    """Four decode steps from the same caches through the serve step and
    the one-hot step: the same greedy tokens, logits within float32
    rounding (attention sums the new token apart from the cache), and the
    caches equal bit for bit after every step.  Per-slot lengths start at 0
    and end at the last position, ``S - 1``."""
    cfg = get_reduced(arch)
    params = M.init_params(jax.random.PRNGKey(1), cfg)
    B, steps = 4, 4
    if lens == "per_slot":
        cache_len = np.array([0, 5, 11, S - steps], np.int32)
    else:
        cache_len = np.int32(9)
    serve = jax.jit(M.make_serve_step(cfg, None))
    ref = jax.jit(lambda p, c, t, l: one_hot_step(p, cfg, c, t, l))
    got = want = random_caches(cfg, B, seed=2)
    tok = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab, (B, 1)), jnp.int32)
    for _ in range(steps):
        logits, got, _ = serve(params, got, tok, jnp.asarray(cache_len))
        ref_logits, want = ref(params, want, tok, jnp.asarray(cache_len))
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(ref_logits),
                                   rtol=1e-5, atol=1e-5)
        nxt = jnp.argmax(logits, -1)
        assert (np.asarray(nxt) == np.asarray(jnp.argmax(ref_logits, -1))
                ).all()
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        tok = nxt.astype(jnp.int32)[:, None]
        cache_len = cache_len + 1


def whole_leaf_copies(hlo: str, leaves) -> list[str]:
    """The ``copy`` ops of ``hlo`` whose result has a stacked leaf's
    shape."""
    shapes = {",".join(map(str, leaf.shape)) for leaf in leaves}
    return [line.strip() for line in hlo.splitlines()
            if re.search(r"= \w+\[([\d,]+)\]\{[^}]*\} copy\(", line)
            and re.search(r"\[([\d,]+)\]", line.split("=", 1)[1]).group(1)
            in shapes]


def test_donated_serve_step_holds_no_copy_of_the_cache():
    """The serve step compiled with its caches donated: temporaries under a
    quarter of the caches' bytes, and no copy of a whole stacked K or V
    leaf (the step used to copy both and rewrite them through the layer
    scan: temp 1.1 times the caches, three whole-leaf copies).  The caches
    are float32 here: XLA:CPU widens every bfloat16 slice to float32 and
    hoists the widened stack out of the layer loop, a host artifact that
    the chip's program does not have (``test_tpu_compile.py`` compiles the
    bfloat16 step for the chip).  Sixteen layers, so that one layer's
    working set is small beside the stack."""
    cfg = dataclasses.replace(get_reduced("lm100m"), n_layers=16)
    B, seq = 4, 512
    params = jax.eval_shape(lambda k: M.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    caches = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
        M.cache_struct(cfg, B, seq))
    serve = M.make_serve_step(cfg, None)
    compiled = jax.jit(serve, donate_argnums=(1,)).lower(
        params, caches, jax.ShapeDtypeStruct((B, 1), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32)).compile()
    cache_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree_util.tree_leaves(caches))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < cache_bytes / 4, (temp, cache_bytes)
    assert not whole_leaf_copies(compiled.as_text(),
                                 jax.tree_util.tree_leaves(caches))
