"""Composable model layers (functional; params are nested dicts).

Compute dtype is bf16 (params master fp32, cast at use); softmax, norms
and loss run fp32.  Sharding is annotated by the caller via
``repro.models.sharding.Policy`` — layers stay policy-free.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from . import attention as A

INIT_STD = 0.02


# --------------------------------------------------------------------------
# init helpers
# --------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, bias: bool = False,
               std: float = INIT_STD):
    p = {"w": jax.random.normal(key, (d_in, d_out), jnp.float32) * std}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def dense(p, x, compute_dtype=jnp.bfloat16):
    y = x.astype(compute_dtype) @ p["w"].astype(compute_dtype)
    if "b" in p:
        y = y + p["b"].astype(compute_dtype)
    return y


def rms_norm_init(d: int):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rms_norm(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------


def rope(x, positions, theta: float):
    """x: (B, H, S, D), positions: (B, S) or scalar broadcastable."""
    B, H, S, D = x.shape
    half = D // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if jnp.ndim(positions) == 0:
        positions = jnp.full((B, S), positions)
    ang = positions.astype(jnp.float32)[:, None, :, None] * freq  # (B,1,S,half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(jnp.float32), \
        x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


# --------------------------------------------------------------------------
# GQA attention block
# --------------------------------------------------------------------------


def attn_init(key, cfg, cross: bool = False):
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, hq * dh, bias=cfg.qkv_bias),
        "wk": dense_init(ks[1], d, hkv * dh, bias=cfg.qkv_bias),
        "wv": dense_init(ks[2], d, hkv * dh, bias=cfg.qkv_bias),
        "wo": dense_init(ks[3], hq * dh, d,
                         std=INIT_STD / math.sqrt(2 * cfg.n_layers)),
    }
    if cfg.qk_norm:
        p["q_norm"] = rms_norm_init(dh)
        p["k_norm"] = rms_norm_init(dh)
    return p


def score_scale(cfg):
    """The attention score scale: ``attention_multiplier`` where the config
    gives one (granite), else None (1/sqrt(d_head))."""
    return cfg.attention_multiplier or None


def _split_heads(y, n_heads, d_head):
    B, S, _ = y.shape
    return y.reshape(B, S, n_heads, d_head).transpose(0, 2, 1, 3)


def attn_apply(p, cfg, x, positions, *, causal=True, kv_x=None,
               attn_impl="xla", q_chunk=1024, k_chunk=1024, use_rope=True,
               policy=None, train_mode=True):
    """Full-sequence attention (train / prefill).  kv_x enables cross-attn.

    Returns (y, (k, v)) — k/v in (B, Hkv, S, D) layout for cache building.
    """
    kv_src = kv_x if kv_x is not None else x
    q = _split_heads(dense(p["wq"], x), cfg.n_heads, cfg.d_head)
    k = _split_heads(dense(p["wk"], kv_src), cfg.n_kv_heads, cfg.d_head)
    v = _split_heads(dense(p["wv"], kv_src), cfg.n_kv_heads, cfg.d_head)
    if cfg.qk_norm:
        q = rms_norm(p["q_norm"], q, cfg.norm_eps)
        k = rms_norm(p["k_norm"], k, cfg.norm_eps)
    if use_rope and cfg.use_rope and kv_x is None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    # GQA sharding (EXPERIMENTS.md §Perf iters 1-2): when Hkv doesn't
    # divide the model axis, unconstrained KV makes GSPMD replicate full
    # f32 score tiles per attention block.  Fix: replicate the (small)
    # KV, then repeat it to Hq *in the q-head-sharded layout* — each
    # device materializes only its own heads' KV, scores stay local, and
    # wq/wo column sharding stays head-aligned so weight grads shard too.
    k_cache, v_cache = k, v                  # caches keep the Hkv layout
    # GQA repeat is a training lever; prefill's cache-building layout is
    # left to GSPMD (measured: pins regress prefill cells)
    attn_policy = policy if (cfg.train.gqa_shard_opt and train_mode) \
        else None
    if attn_policy is not None and attn_policy.mesh is not None:
        world_m = policy.mesh.shape[policy.model_axis]
        if cfg.n_kv_heads % world_m != 0 and kv_x is None:
            G = cfg.n_heads // cfg.n_kv_heads
            b, m = policy.batch_axes, policy.model_axis
            from jax.sharding import PartitionSpec as P
            k = policy.sc(k, P(b, None, None, None))     # replicated
            v = policy.sc(v, P(b, None, None, None))
            k = policy.sc(jnp.repeat(k, G, axis=1), P(b, m, None, None))
            v = policy.sc(jnp.repeat(v, G, axis=1), P(b, m, None, None))
            q = policy.shard_heads(q)
    elif policy is not None:
        # paper-faithful baseline lowering (gqa_shard_opt=False)
        q, k, v = policy.shard_heads(q), policy.shard_kv(k), \
            policy.shard_kv(v)
    o = A.attention(q, k, v, causal=causal, impl=attn_impl,
                    q_chunk=q_chunk, k_chunk=k_chunk, policy=attn_policy,
                    scale=score_scale(cfg))
    B, S = x.shape[:2]
    y = o.transpose(0, 2, 1, 3).reshape(B, S, cfg.n_heads * cfg.d_head)
    return dense(p["wo"], y), (k_cache, v_cache)


def attn_decode(p, cfg, x, cache, cache_len, *, cross=False, policy=None):
    """One-token decode.  cache = {"k","v"} (B,Hkv,S,D); for cross
    attention the cache holds the (static) encoder memory.

    ``cache_len`` is a scalar or a ``(B,)`` vector of per-slot positions
    (continuous batching: each sequence in the batch decodes at its own
    length — the write, rope phase, and mask are all per-slot)."""
    q = _split_heads(dense(p["wq"], x), cfg.n_heads, cfg.d_head)
    if cfg.qk_norm:
        q = rms_norm(p["q_norm"], q, cfg.norm_eps)
    if not cross:
        cl = jnp.asarray(cache_len)
        pos = cl if cl.ndim == 0 else cl[:, None]        # rope: (B,1)
        k_new = _split_heads(dense(p["wk"], x), cfg.n_kv_heads, cfg.d_head)
        v_new = _split_heads(dense(p["wv"], x), cfg.n_kv_heads, cfg.d_head)
        if cfg.qk_norm:
            k_new = rms_norm(p["k_norm"], k_new, cfg.norm_eps)
        if cfg.use_rope:
            q = rope(q, pos, cfg.rope_theta)
            k_new = rope(k_new, pos, cfg.rope_theta)
        # one-hot scatter write (shard-friendly on a sharded S axis)
        with jax.named_scope("decode/cache_write"):
            S = cache["k"].shape[2]
            if cl.ndim == 0:
                onehot = (jnp.arange(S) == cl).astype(cache["k"].dtype)
                oh = onehot[None, None, :, None]
            else:                   # per-slot write position: (B,1,S,1)
                onehot = (jnp.arange(S)[None, :] == cl[:, None]) \
                    .astype(cache["k"].dtype)
                oh = onehot[:, None, :, None]
            dt = cache["k"].dtype
            cache = {"k": cache["k"] * (1 - oh) + k_new.astype(dt) * oh,
                     "v": cache["v"] * (1 - oh) + v_new.astype(dt) * oh}
        live_len = cache_len
    else:
        live_len = cache["k"].shape[2] - 1          # full encoder memory
    with jax.named_scope("decode/attention"):
        o = A.decode_attention(q, cache["k"], cache["v"], live_len,
                               scale=score_scale(cfg))
    B = x.shape[0]
    y = o.transpose(0, 2, 1, 3).reshape(B, 1, cfg.n_heads * cfg.d_head)
    return dense(p["wo"], y), cache


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------


def swiglu_init(key, d: int, f: int, n_layers: int):
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], d, f),
        "w_up": dense_init(ks[1], d, f),
        "w_down": dense_init(ks[2], f, d,
                             std=INIT_STD / math.sqrt(2 * n_layers)),
    }


def swiglu(p, x, policy=None):
    g = jax.nn.silu(dense(p["w_gate"], x))
    u = dense(p["w_up"], x)
    if policy is not None and policy.mesh is not None:
        # pin the f-dim to the model axis: without it GSPMD can resolve
        # the intermediate replicated inside period-stacked scan bodies,
        # which replicates the MLP weight grads (§Perf iter 5)
        from jax.sharding import PartitionSpec as P
        sp = P(policy.batch_axes, None, policy.model_axis)
        g, u = policy.sc(g, sp), policy.sc(u, sp)
    return dense(p["w_down"], g * u)


def gelu_mlp_init(key, d: int, f: int, n_layers: int):
    ks = jax.random.split(key, 2)
    return {
        "w_in": dense_init(ks[0], d, f),
        "w_out": dense_init(ks[1], f, d,
                            std=INIT_STD / math.sqrt(2 * n_layers)),
    }


def gelu_mlp(p, x, policy=None):
    h = jax.nn.gelu(dense(p["w_in"], x))
    if policy is not None and policy.mesh is not None:
        from jax.sharding import PartitionSpec as P
        h = policy.sc(h, P(policy.batch_axes, None, policy.model_axis))
    return dense(p["w_out"], h)


# --------------------------------------------------------------------------
# Embedding / logits
# --------------------------------------------------------------------------


def embed_init(key, vocab: int, d: int):
    return {"embed": jax.random.normal(key, (vocab, d), jnp.float32)
            * INIT_STD}


def embed_lookup(p, tokens, compute_dtype=jnp.bfloat16):
    return p["embed"].astype(compute_dtype)[tokens]


def logits_out(p_head, x, tied_embed=None):
    """x (B,S,d) -> logits fp32 (B,S,V)."""
    if tied_embed is not None:
        w = tied_embed["embed"].astype(jnp.bfloat16).T
    else:
        w = p_head["w"].astype(jnp.bfloat16)
    return jax.lax.dot_general(
        x.astype(jnp.bfloat16), w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
