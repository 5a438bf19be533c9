"""Weights from a seed, and the plain float32 reference forward pass, of a
dense decoder-only transformer: pre-norm RMSNorm, grouped-query attention
with rotary positions (halves rotated, ``rope_theta``), SwiGLU MLP, tied
embeddings.

The weights are laid out as the serving program takes them, so the
benchmark builds them once from the seed and hands them over; the
reference builds its own copy from the same seed after the program has
been freed, and shares no code with the program.  The forward pass is
straight ``jax.numpy`` at ``float32`` with ``precision="highest"``, one
sequence at a time, layers scanned.

``quant="fp8"`` is the control: every matrix multiplication takes its
inputs rounded to float8 e4m3 with one scale per tensor, the precision
below the bfloat16 that the configuration serves in.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
INIT_STD = 0.02


def padded_vocab(dims: dict) -> int:
    m = int(dims["vocab_multiple"])
    return -(-int(dims["vocab_size"]) // m) * m


def make_weights(seed: int, dims: dict, dtype=jnp.bfloat16):
    """All weights in ``dtype``, drawn on the device in one jitted call.
    Projections are N(0, 0.02), output projections N(0, 0.02/sqrt(2L)),
    norm scales 1."""
    d, L = dims["hidden_size"], dims["num_hidden_layers"]
    hq, hkv = dims["num_attention_heads"], dims["num_key_value_heads"]
    dh, f = dims["head_dim"], dims["intermediate_size"]
    V = padded_vocab(dims)
    out_std = INIT_STD / math.sqrt(2 * L)

    def build(key):
        ks = iter(jax.random.split(key, 8))

        def normal(shape, std):
            return (jax.random.normal(next(ks), shape, F32) * std) \
                .astype(dtype)

        ones = jnp.ones((L, d), dtype)
        return {
            "embed": {"embed": normal((V, d), INIT_STD)},
            "layers": {
                "ln1": {"scale": ones},
                "attn": {"wq": {"w": normal((L, d, hq * dh), INIT_STD)},
                         "wk": {"w": normal((L, d, hkv * dh), INIT_STD)},
                         "wv": {"w": normal((L, d, hkv * dh), INIT_STD)},
                         "wo": {"w": normal((L, hq * dh, d), out_std)}},
                "ln2": {"scale": ones},
                "ffn_mlp": {"w_gate": {"w": normal((L, d, f), INIT_STD)},
                            "w_up": {"w": normal((L, d, f), INIT_STD)},
                            "w_down": {"w": normal((L, f, d), out_std)}},
            },
            "final_norm": {"scale": jnp.ones((d,), dtype)},
        }

    key = jax.random.key(seed % (1 << 63))
    return jax.jit(build)(key)


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(a, w, quant):
    a, w = a.astype(F32), w.astype(F32)
    if quant == "fp8":
        a, w = _fp8(a), _fp8(w)
    return jnp.matmul(a, w, precision="highest")


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope(x, theta):
    """x (S, H, D): rotate the two halves of each head by position."""
    S, _, D = x.shape
    half = D // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None, None] * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def forward(weights, tokens, dims: dict, quant: str | None = None):
    """Logits ``(S, padded vocab)`` in float32 for one sequence ``(S,)``."""
    hq, hkv = dims["num_attention_heads"], dims["num_key_value_heads"]
    dh, eps = dims["head_dim"], dims["rms_norm_eps"]
    theta = dims["rope_theta"]
    S = tokens.shape[0]
    x = weights["embed"]["embed"][tokens].astype(F32)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]

    def layer(x, w):
        h = _rms(x, w["ln1"]["scale"], eps)
        q = _mm(h, w["attn"]["wq"]["w"], quant).reshape(S, hq, dh)
        k = _mm(h, w["attn"]["wk"]["w"], quant).reshape(S, hkv, dh)
        v = _mm(h, w["attn"]["wv"]["w"], quant).reshape(S, hkv, dh)
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") \
            / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision="highest")
        x = x + _mm(o.reshape(S, hq * dh), w["attn"]["wo"]["w"], quant)
        h = _rms(x, w["ln2"]["scale"], eps)
        m = w["ffn_mlp"]
        g = jax.nn.silu(_mm(h, m["w_gate"]["w"], quant)) * \
            _mm(h, m["w_up"]["w"], quant)
        return x + _mm(g, m["w_down"]["w"], quant), None

    x, _ = jax.lax.scan(layer, x, weights["layers"])
    x = _rms(x, weights["final_norm"]["scale"], eps)
    return _mm(x, weights["embed"]["embed"].T, quant)


def served_gaps(weights, dims: dict, seq_len: int, sequences,
                quant: str | None = None):
    """For each ``(prompt, served)`` pair, the gap by which each served
    token's reference logit lies below the reference's best at its
    position.  With ``quant`` set, the gap of the token that the quantized
    forward puts first instead (the control).  Sequences are right-padded
    to ``seq_len`` so one program serves all."""
    import numpy as np

    def gaps(w, toks, nxt):
        logits = forward(w, toks, dims)
        pick = nxt if quant is None else \
            jnp.argmax(forward(w, toks, dims, quant), -1)
        return jnp.max(logits, -1) - jnp.take_along_axis(
            logits, pick[:, None], -1)[:, 0]

    gaps = jax.jit(gaps)
    out = []
    for prompt, served in sequences:
        p, g = len(prompt), len(served)
        full = np.concatenate([prompt, served]).astype(np.int32)
        toks = np.zeros(seq_len, np.int32)
        nxt = np.zeros(seq_len, np.int32)
        toks[:p + g - 1] = full[:-1]
        nxt[:p + g - 1] = full[1:]
        got = np.asarray(gaps(weights, jnp.asarray(toks), jnp.asarray(nxt)))
        out.append(got[p - 1:p - 1 + g])
    return out
