"""Weights from a seed, and the plain float32 reference forward pass, of a
Granite-4.0-H hybrid decoder: per layer RMSNorm, then a Mamba-2 mixer or
NoPE grouped-query attention, then a mixture of experts (top-k of the
router's width, softmax over the k logits, one SwiGLU per expert) beside
one shared SwiGLU; both branches scaled by ``residual_multiplier``,
embeddings by ``embedding_multiplier``, scores by ``attention_multiplier``
and logits divided by ``logits_scaling``; tied embeddings.

The configuration is the benchmark's file (the model's ``config.json``
keys): ``layer_types[:num_hidden_layers]`` gives each layer's mixer, and
the layer holds ``num_local_experts`` experts from ``expert_offset`` of a
router ``router_width`` wide (one chip's share; the pairs routed to other
experts are another chip's part of the layer and are left out here as in
the program).

The Mamba-2 mixer is its recurrence, one position after another:
h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t + D x_t, with
dt = softplus(dt_raw + dt_bias), A = -exp(A_log), after a depthwise causal
conv with bias and SiLU over (x, B, C); then rmsnorm(y * silu(z)) per
group and the output projection.  Attention and the experts are computed
directly: every held expert on every position, then gated.

The weights are laid out as the serving program takes them (its
``init_params`` tree), built once from the seed, so the benchmark hands
them to the program; the reference rebuilds its own copy from the same
seed and shares no code with the program.  The forward pass is
``jax.numpy`` at ``float32`` with ``precision="highest"``, one sequence at
a time.  ``quant="fp8"`` is the control: every matrix multiplication takes
its inputs rounded to float8 e4m3 with one scale per tensor.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
INIT_STD = 0.02
HI = "highest"


def shape(c: dict) -> dict:
    """The sizes the forward pass uses, from the configuration file."""
    d, hq = c["hidden_size"], c["num_attention_heads"]
    L = c["num_hidden_layers"]
    s = {"d": d, "L": L, "V": c["vocab_size"], "hq": hq,
         "hkv": c["num_key_value_heads"], "dh": c.get("head_dim") or d // hq,
         "H": c["mamba_n_heads"], "P": c["mamba_d_head"],
         "N": c["mamba_d_state"], "G": c["mamba_n_groups"],
         "K": c["mamba_d_conv"], "f": c["intermediate_size"],
         "fs": c["shared_intermediate_size"], "E": c["num_local_experts"],
         "W": c["router_width"], "off": c["expert_offset"],
         "k": c["num_experts_per_tok"], "eps": c["rms_norm_eps"],
         "types": list(c["layer_types"][:L])}
    s["Ei"] = s["H"] * s["P"]
    s["conv"] = s["Ei"] + 2 * s["G"] * s["N"]
    return s


def _period(types: list) -> int:
    """Layers of one repeating period of ``types``."""
    for p in range(1, len(types) + 1):
        if len(types) % p == 0 and types == types[:p] * (len(types) // p):
            return p
    return len(types)


def make_weights(seed: int, config: dict, dtype=jnp.bfloat16):
    """All weights in ``dtype``, drawn on the device in one jitted call:
    every projection and the router N(0, 1/fan_in) (unit-variance
    outputs), the embedding N(0, 0.02 / embedding_multiplier) so that it
    enters the residual stream at 0.02, conv U(+-1/sqrt(K)), dt at init
    log-uniform in [1e-3, 0.1] (dt_bias its softplus inverse), A_log
    log U(1, 16), D and norm scales 1.

    With the tied head, a residual stream that the embedding dominates
    makes every position's best logit its own token, and with projections
    far below unit gain the SSM state adds little to a layer; at these
    scales the layers' branches (each scaled by ``residual_multiplier``)
    and the state decide the logits, so the comparison sees what the
    layers compute, at the cell's widths and at toy widths alike."""
    s = shape(config)
    d, L, f, fs = s["d"], s["L"], s["f"], s["fs"]
    per = _period(s["types"])
    n = L // per

    def build(key):
        ks = iter(jax.random.split(key, 16 * per + 2))

        def normal(shp, std):
            return (jax.random.normal(next(ks), (n,) + shp, F32) * std
                    ).astype(dtype)

        def dense(i, o):
            return {"w": normal((i, o), i ** -0.5)}

        def ones(m):
            return jnp.ones((n, m), dtype)

        def layer(kind):
            p = {"ln1": {"scale": ones(d)}, "ln2": {"scale": ones(d)}}
            if kind == "attention":
                q, kv = s["hq"] * s["dh"], s["hkv"] * s["dh"]
                p["attn"] = {"wq": dense(d, q), "wk": dense(d, kv),
                             "wv": dense(d, kv), "wo": dense(q, d)}
            else:
                H, K, conv = s["H"], s["K"], s["conv"]
                dt = jnp.exp(jax.random.uniform(next(ks), (n, H), F32)
                             * math.log(100.0) + math.log(1e-3))
                p["mamba"] = {
                    "in_proj": dense(d, s["Ei"] + conv + H),
                    "conv_w": (jax.random.uniform(
                        next(ks), (n, K, conv), F32, -1.0, 1.0)
                        / math.sqrt(K)).astype(dtype),
                    "conv_b": jnp.zeros((n, conv), dtype),
                    "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
                    "A_log": jnp.log(jax.random.uniform(
                        next(ks), (n, H), F32, 1.0, 16.0)).astype(dtype),
                    "D": ones(H),
                    "norm": {"scale": ones(s["Ei"])},
                    "out_proj": dense(s["Ei"], d)}
            p["ffn_moe"] = {
                "router": normal((d, s["W"]), d ** -0.5),
                "e_gate": normal((s["E"], d, f), d ** -0.5),
                "e_up": normal((s["E"], d, f), d ** -0.5),
                "e_down": normal((s["E"], f, d), f ** -0.5),
                "shared": {"w_gate": dense(d, fs), "w_up": dense(d, fs),
                           "w_down": dense(fs, d)}}
            return p

        layers = {f"sub{j}": layer(s["types"][j]) for j in range(per)} \
            if per > 1 else layer(s["types"][0])
        emb = (jax.random.normal(next(ks), (s["V"], d), F32) * INIT_STD
               / config["embedding_multiplier"]).astype(dtype)
        return {"embed": {"embed": emb}, "layers": layers,
                "final_norm": {"scale": jnp.ones((d,), dtype)}}

    return jax.jit(build)(jax.random.key(seed % (1 << 63)))


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(a, w, quant):
    a, w = a.astype(F32), w.astype(F32)
    if quant == "fp8":
        a, w = _fp8(a), _fp8(w)
    return jnp.matmul(a, w, precision=HI)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _swiglu(m, x, quant):
    g = jax.nn.silu(_mm(x, m["w_gate"]["w"], quant))
    return _mm(g * _mm(x, m["w_up"]["w"], quant), m["w_down"]["w"], quant)


def _mamba2(w, x, s, quant):
    S = x.shape[0]
    H, P, N, G, K, Ei = s["H"], s["P"], s["N"], s["G"], s["K"], s["Ei"]
    zxbcdt = _mm(x, w["in_proj"]["w"], quant)
    z, xbc, dt = (zxbcdt[:, :Ei], zxbcdt[:, Ei:Ei + s["conv"]],
                  zxbcdt[:, Ei + s["conv"]:])
    xp = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    cw = w["conv_w"].astype(F32)
    xbc = jax.nn.silu(sum(xp[k:k + S] * cw[k] for k in range(K))
                      + w["conv_b"].astype(F32))
    xs = xbc[:, :Ei].reshape(S, H, P)
    Bm = xbc[:, Ei:Ei + G * N].reshape(S, G, N)
    Cm = xbc[:, Ei + G * N:].reshape(S, G, N)
    dt = jax.nn.softplus(dt + w["dt_bias"].astype(F32))
    A = -jnp.exp(w["A_log"].astype(F32))
    group = jnp.arange(H) // (H // G)

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = jnp.exp(dt_t * A)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[group][:, None, :]
        return h, jnp.einsum("hpn,hn->hp", h, c_t[group], precision=HI)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (xs, dt, Bm, Cm))
    y = (y + w["D"].astype(F32)[:, None] * xs).reshape(S, Ei)
    g = (y * jax.nn.silu(z)).reshape(S, G, Ei // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + s["eps"])
    g = g.reshape(S, Ei) * w["norm"]["scale"].astype(F32)
    return _mm(g, w["out_proj"]["w"], quant)


def _attention(w, x, s, scale, quant):
    S = x.shape[0]
    hq, hkv, dh = s["hq"], s["hkv"], s["dh"]
    q = _mm(x, w["wq"]["w"], quant).reshape(S, hq, dh)
    k = _mm(x, w["wk"]["w"], quant).reshape(S, hkv, dh)
    v = _mm(x, w["wv"]["w"], quant).reshape(S, hkv, dh)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]

    def head(i):                    # one query head at a time (memory)
        kv = i // (hq // hkv)
        sc = jnp.einsum("qd,kd->qk", q[:, i], k[:, kv], precision=HI) * scale
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return jnp.einsum("qk,kd->qd", p, v[:, kv], precision=HI)

    o = jax.lax.map(head, jnp.arange(hq))                 # (hq, S, dh)
    return _mm(jnp.moveaxis(o, 0, 1).reshape(S, hq * dh), w["wo"]["w"],
               quant)


def _moe(m, x, s, quant):
    logits = _mm(x, m["router"], quant)                   # (S, W)
    top, ids = jax.lax.top_k(logits, s["k"])
    gates = jax.nn.softmax(top, axis=-1)

    def expert(acc, e):
        g = jnp.sum(jnp.where(ids == s["off"] + e, gates, 0.0), -1)
        h = jax.nn.silu(_mm(x, m["e_gate"][e], quant)) \
            * _mm(x, m["e_up"][e], quant)
        return acc + g[:, None] * _mm(h, m["e_down"][e], quant), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(s["E"]))
    return y + _swiglu(m["shared"], x, quant)


def hidden(weights, tokens, config: dict, quant: str | None = None):
    """Final-normed hidden states ``(S, d)`` float32 of one sequence."""
    s = shape(config)
    per = _period(s["types"])
    r, eps = config["residual_multiplier"], s["eps"]
    scale = config["attention_multiplier"]
    x = weights["embed"]["embed"][tokens].astype(F32) \
        * config["embedding_multiplier"]
    for i, kind in enumerate(s["types"]):
        lw = weights["layers"] if per == 1 else \
            weights["layers"][f"sub{i % per}"]
        lw = jax.tree_util.tree_map(lambda a: a[i // per], lw)
        h = _rms(x, lw["ln1"]["scale"], eps)
        y = _attention(lw["attn"], h, s, scale, quant) \
            if kind == "attention" else _mamba2(lw["mamba"], h, s, quant)
        x = x + r * y
        h = _rms(x, lw["ln2"]["scale"], eps)
        x = x + r * _moe(lw["ffn_moe"], h, s, quant)
    return _rms(x, weights["final_norm"]["scale"], eps)


def logits_at(weights, x, config: dict, quant: str | None = None):
    """Logits ``(n, V)`` float32 of hidden states ``x`` ``(n, d)``."""
    return _mm(x, weights["embed"]["embed"].T, quant) \
        / config["logits_scaling"]


def forward(weights, tokens, config: dict, quant: str | None = None):
    """Logits ``(S, V)`` float32 for one sequence ``(S,)``."""
    return logits_at(weights, hidden(weights, tokens, config, quant),
                     config, quant)


def served_gaps(weights, config: dict, seq_len: int, gen_len: int,
                sequences, quant: str | None = None):
    """For each ``(prompt, served)`` pair, the gap by which each served
    token's reference logit lies below the reference's best at its
    position.  With ``quant`` set, the gap of the token that the quantized
    forward puts first instead (the control).  Sequences are right-padded
    to ``seq_len`` and the logits taken at ``gen_len`` positions from the
    prompt's last, so one program serves all."""
    def gaps(w, toks, at, nxt):
        logits = logits_at(w, hidden(w, toks, config)[at], config)
        pick = nxt if quant is None else jnp.argmax(logits_at(
            w, hidden(w, toks, config, quant)[at], config, quant), -1)
        return jnp.max(logits, -1) - jnp.take_along_axis(
            logits, pick[:, None], -1)[:, 0]

    gaps = jax.jit(gaps)
    out = []
    for prompt, served in sequences:
        p, g = len(prompt), len(served)
        toks = np.zeros(seq_len, np.int32)
        toks[:p + g - 1] = np.concatenate([prompt, served])[:-1]
        at = np.full(gen_len, p - 1, np.int32)
        at[:g] = np.arange(p - 1, p - 1 + g)
        nxt = np.zeros(gen_len, np.int32)
        nxt[:g] = served
        got = np.asarray(gaps(weights, jnp.asarray(toks), jnp.asarray(at),
                              jnp.asarray(nxt)))
        out.append(got[:g])
    return out
