"""Attention compute paths.

Three implementations share one contract (``q (B,Hq,Sq,D)``, ``k/v
(B,Hkv,Skv,D)`` -> ``(B,Hq,Sq,D)``):

* ``full``    — one einsum; used when the score matrix is small;
* ``chunked`` — online-softmax over (q-chunk, kv-chunk) tiles expressed as
  ``lax.scan`` (the XLA-native flash attention used for long-context
  shapes; per-step score tiles are ``jax.checkpoint``-ed so
  the backward never materializes the full score matrix);
* ``pallas``  — the fused ``kernels/flash_attention`` TPU kernel.

GQA is computed without repeating KV in HBM: q is grouped as
``(B, Hkv, G, Sq, D)`` and contracted against ungrouped KV.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..kernels.flash_attention import flash_attention as _pallas_flash

NEG_INF = -1e30


def _causal_mask(sq: int, skv: int, q_off, k_off):
    qi = jnp.arange(sq)[:, None] + q_off
    kj = jnp.arange(skv)[None, :] + k_off
    return kj <= qi                                       # (sq, skv) bool


def full_attention(q, k, v, *, causal: bool = True, scale=None,
                   policy=None):
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    qg = q.reshape(B, Hkv, G, Sq, D).astype(jnp.float32) * scale
    if policy is not None:
        qg, k, v = policy.shard_gqa_grouped(qg, k, v)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k.astype(jnp.float32))
    if causal:
        s = jnp.where(_causal_mask(Sq, Skv, Skv - Sq, 0)[None, None, None],
                      s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return o.reshape(B, Hq, Sq, D).astype(q.dtype)


def chunked_attention(q, k, v, *, causal: bool = True, q_chunk: int = 1024,
                      k_chunk: int = 1024, scale=None, policy=None):
    """Flash-style online softmax with lax.scan tiling (XLA path)."""
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    G = Hq // Hkv

    def _divisor_chunk(n, target):
        c = min(target, n)
        while n % c != 0:
            c -= 1
        return c

    qc = _divisor_chunk(Sq, q_chunk)
    kc = _divisor_chunk(Skv, k_chunk)
    nq, nk = Sq // qc, Skv // kc
    scale = scale if scale is not None else D ** -0.5

    if policy is not None:
        # constrain the grouped layout BEFORE tiling so every scan step
        # works on locally-sharded tiles (no involuntary score gathers)
        qg5 = q.reshape(B, Hkv, G, Sq, D)
        qg5, k, v = policy.shard_gqa_grouped(qg5, k, v)
        q = qg5.reshape(B, Hq, Sq, D)
    qg = (q.reshape(B, Hkv, G, nq, qc, D).astype(jnp.float32) * scale)
    qg = jnp.moveaxis(qg, 3, 0)                      # (nq, B, Hkv, G, qc, D)
    ks = jnp.moveaxis(k.reshape(B, Hkv, nk, kc, D), 2, 0)
    vs = jnp.moveaxis(v.reshape(B, Hkv, nk, kc, D), 2, 0)

    @jax.checkpoint
    def kv_step(carry, inp, qb, q_off):
        m, l, acc = carry
        kb, vb, k_off = inp
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qb, kb.astype(jnp.float32))
        if causal:
            mask = _causal_mask(qb.shape[-2], kb.shape[-2],
                                q_off + (Skv - Sq), k_off)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p, vb.astype(jnp.float32))
        return (m_new, l, acc), None

    def q_step(_, inp):
        qb, qi = inp                                  # (B,Hkv,G,qc,D)
        m0 = jnp.full(qb.shape[:-1], NEG_INF, jnp.float32)
        l0 = jnp.zeros(qb.shape[:-1], jnp.float32)
        a0 = jnp.zeros(qb.shape, jnp.float32)
        k_offs = jnp.arange(nk) * kc
        (m, l, acc), _ = jax.lax.scan(
            functools.partial(kv_step, qb=qb, q_off=qi * qc),
            (m0, l0, a0), (ks, vs, k_offs))
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, o

    _, outs = jax.lax.scan(q_step, None, (qg, jnp.arange(nq)))
    # outs: (nq, B, Hkv, G, qc, D)
    o = jnp.moveaxis(outs, 0, 3).reshape(B, Hkv, G, Sq, D)
    return o.reshape(B, Hq, Sq, D).astype(q.dtype)


def attention(q, k, v, *, causal: bool = True, impl: str = "xla",
              q_chunk: int = 1024, k_chunk: int = 1024, policy=None,
              scale=None):
    """Dispatching entry point used by the model layers.  ``scale``
    multiplies the scores (None: 1/sqrt(d_head))."""
    if scale is not None and impl in ("pallas", "pallas_interpret"):
        # the kernel scales by 1/sqrt(d_head): fold the rest into q
        q = (q * (scale * q.shape[-1] ** 0.5)).astype(q.dtype)
    if impl == "pallas":
        return _pallas_flash(q, k, v, causal=causal, impl="pallas")
    if impl == "pallas_interpret":
        return _pallas_flash(q, k, v, causal=causal, impl="pallas_interpret",
                             bq=min(128, q.shape[2]), bk=min(128, k.shape[2]))
    Sq, Skv = q.shape[2], k.shape[2]
    if Sq <= q_chunk and Skv <= k_chunk:
        return full_attention(q, k, v, causal=causal, policy=policy,
                              scale=scale)
    return chunked_attention(q, k, v, causal=causal, q_chunk=q_chunk,
                             k_chunk=k_chunk, policy=policy, scale=scale)


def decode_attention(q, k_cache, v_cache, cache_len, scale=None,
                     k_new=None, v_new=None):
    """Single-token decode: q (B,Hq,1,D) vs cache (B,Hkv,S,D).

    Without ``k_new``/``v_new`` the cache already holds the token at
    ``cache_len`` and positions ``>= cache_len + 1`` are masked.  With them
    (B,Hkv,1,D), the cache is scored below ``cache_len`` and the token's
    own K and V are scored beside it, so the cache need not be written
    before attention reads it.  ``cache_len`` is a scalar (whole batch at
    one position) or a ``(B,)`` vector (continuous batching: every slot
    at its own position — the serving engine's per-slot decode).
    Shard-friendly: reductions over the cache S axis lower to
    (all-)reduces when S is sharded — the flash-decoding pattern falls out
    of GSPMD automatically.
    """
    B, Hq, _, D = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    qg = q.reshape(B, Hkv, G, D).astype(jnp.float32) * scale
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, k_cache.astype(jnp.float32))
    cl = jnp.asarray(cache_len)
    if cl.ndim:                       # per-slot lengths: (B,) -> (B,1,1,1)
        cl = cl[:, None, None, None]
    pos = jnp.arange(S)[None, None, None, :]
    if k_new is None:
        s = jnp.where(pos <= cl, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgk,bhkd->bhgd", p, v_cache.astype(jnp.float32))
        return o.reshape(B, Hq, 1, D).astype(q.dtype)
    s = jnp.where(pos < cl, s, NEG_INF)
    s_new = jnp.einsum("bhgd,bhd->bhg", qg,
                       k_new[:, :, 0].astype(jnp.float32))[..., None]
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), s_new)
    e, e_new = jnp.exp(s - m), jnp.exp(s_new - m)
    o = jnp.einsum("bhgk,bhkd->bhgd", e, v_cache.astype(jnp.float32)) \
        + e_new * v_new[:, :, None, 0].astype(jnp.float32)
    o = o / (jnp.sum(e, axis=-1, keepdims=True) + e_new)
    return o.reshape(B, Hq, 1, D).astype(q.dtype)
