"""Mamba-1 block (falcon-mamba, jamba mamba layers) and Mamba-2 (SSD)
block (granite-4.0-h).

Three scan paths:
* ``pallas``  — the fused ``kernels/mamba_scan`` TPU kernel;
* ``xla``     — chunked ``lax.scan`` (outer scan over time chunks, inner
  scan over steps, chunk body ``jax.checkpoint``-ed) so training backward
  materializes per-step ``(B,E,N)`` residuals for *one chunk at a time*
  instead of the whole sequence — the XLA analogue of the fused kernel's
  recompute;
* ``step``    — O(1) single-token decode with (conv, ssm) state.

Sharding: everything is elementwise in E (= d_inner), which is sharded
over the model axis; the two projections contract over d/E and reduce via
GSPMD as usual.

Mamba-2 (``mamba2_*``): per head a scalar A and a d_head-wide state of
d_state columns; B and C are shared by the heads of a group.  Prefill runs
the chunked SSD form (quadratic within a chunk, states carried between
chunks by a scan); decode is the O(1) recurrence.

A right-padded prefill (the serving engine's slot prefill) passes
``lengths``: dt is zeroed from each sequence's length on, so exp(dt*A) = 1
and the input term vanishes — the state passes through the padding — and
the conv state is taken from the inputs just before the length.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from ..kernels.mamba_scan import selective_scan as _scan_kernel
from . import layers as Ly

F32 = jnp.float32


def dt_rank(cfg) -> int:
    return cfg.dt_rank or max(1, math.ceil(cfg.d_model / 16))


def mamba_init(key, cfg):
    d, E, N, K = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_conv
    R = dt_rank(cfg)
    ks = jax.random.split(key, 6)
    std = Ly.INIT_STD
    A = jnp.tile(jnp.arange(1, N + 1, dtype=F32)[None, :], (E, 1))
    return {
        "in_proj": Ly.dense_init(ks[0], d, 2 * E),
        "conv_w": jax.random.normal(ks[1], (K, E), F32) * std,
        "conv_b": jnp.zeros((E,), F32),
        "x_proj": Ly.dense_init(ks[2], E, R + 2 * N),
        "dt_proj": {
            "w": jax.random.normal(ks[3], (R, E), F32) * (R ** -0.5),
            "b": jnp.log(jnp.expm1(jnp.full((E,), 0.01, F32))),
        },
        "A_log": jnp.log(A),
        "D": jnp.ones((E,), F32),
        "out_proj": Ly.dense_init(
            ks[4], E, d, std=std / math.sqrt(2 * cfg.n_layers)),
    }


def _causal_conv(x, w, b):
    """Depthwise causal conv via K shifted adds. x (B,S,E), w (K,E)."""
    K = w.shape[0]
    B, S, E = x.shape
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = jnp.zeros_like(x, dtype=F32)
    for k in range(K):
        y = y + xp[:, k:k + S].astype(F32) * w[k].astype(F32)
    return y + b.astype(F32)


def _ssm_inputs(p, cfg, xc):
    """xc (B,S,E) fp32 -> (delta (B,S,E), A (E,N), Bm, Cm (B,S,N))."""
    N = cfg.ssm_state
    R = dt_rank(cfg)
    proj = xc.astype(jnp.bfloat16) @ p["x_proj"]["w"].astype(jnp.bfloat16)
    proj = proj.astype(F32)
    dt_low, Bm, Cm = proj[..., :R], proj[..., R:R + N], proj[..., R + N:]
    delta = jax.nn.softplus(
        dt_low @ p["dt_proj"]["w"].astype(F32) + p["dt_proj"]["b"])
    A = -jnp.exp(p["A_log"].astype(F32))
    return delta, A, Bm, Cm


def _scan_chunked_xla(x, delta, A, Bm, Cm, D, h0, chunk: int = 128):
    """Chunked selective scan; returns (y (B,S,E), h_final (B,E,N))."""
    B, S, E = x.shape
    N = A.shape[1]
    c = min(chunk, S)
    assert S % c == 0
    nc = S // c

    def split(v):
        return jnp.moveaxis(v.reshape(B, nc, c, -1), 1, 0)

    xs = (split(x), split(delta), split(Bm), split(Cm))

    @jax.checkpoint
    def chunk_body(h, inp):
        xc, dc, bc, cc = inp                      # (B, c, *)

        def step(hh, t_inp):
            xt, dt_, bt, ct = t_inp               # (B,E),(B,E),(B,N),(B,N)
            dA = jnp.exp(dt_[..., None] * A[None])
            hh = dA * hh + (dt_ * xt)[..., None] * bt[:, None, :]
            y = jnp.einsum("ben,bn->be", hh, ct)
            return hh, y

        h, ys = jax.lax.scan(
            step, h, (jnp.moveaxis(xc, 1, 0), jnp.moveaxis(dc, 1, 0),
                      jnp.moveaxis(bc, 1, 0), jnp.moveaxis(cc, 1, 0)))
        return h, jnp.moveaxis(ys, 0, 1)          # (B, c, E)

    hT, ys = jax.lax.scan(chunk_body, h0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(B, S, E)
    return y + x * D[None, None], hT


def _live(dt, lengths):
    """``dt`` (B,S,...) zeroed at positions ``>= lengths`` (B,)."""
    if lengths is None:
        return dt
    pos = jnp.arange(dt.shape[1])[None, :]
    live = pos < jnp.asarray(lengths, jnp.int32).reshape(-1, 1)
    return dt * live.reshape(live.shape + (1,) * (dt.ndim - 2))


def _conv_tail(x_in, K: int, lengths):
    """The K-1 conv inputs just before each length (the padded end when
    ``lengths`` is None), zeros before the sequence start."""
    B, S, _ = x_in.shape
    xp = jnp.pad(x_in.astype(F32), ((0, 0), (K - 1, 0), (0, 0)))
    end = jnp.full((B,), S, jnp.int32) if lengths is None else \
        jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    idx = end[:, None] + jnp.arange(K - 1)[None, :]       # (B, K-1)
    return jnp.take_along_axis(xp, idx[..., None], axis=1)


def mamba_apply(p, cfg, x, *, impl: str = "xla", scan_chunk: int = 128,
                return_state: bool = False, policy=None, lengths=None):
    """Full-sequence mamba block.  x (B,S,d) -> (y, state | None).
    ``lengths`` (B,): true lengths of right-padded sequences.

    With ``policy`` the channel dim E (= d_inner) is explicitly sharded
    over the model axis (everything SSM-internal is elementwise in E).
    Without the constraints GSPMD fails to propagate through the chunked
    time scan and replicates in_proj/out_proj and their grads —
    EXPERIMENTS.md §Perf iter 4."""
    B, S, d = x.shape
    E, N, K = cfg.d_inner, cfg.ssm_state, cfg.ssm_conv

    def se(v):                          # shard (..., E-like) over model
        if policy is None or policy.mesh is None \
                or not cfg.train.ssm_shard_opt:
            return v
        from jax.sharding import PartitionSpec as P
        return policy.sc(v, P(policy.batch_axes, None, policy.model_axis))

    xz = se(Ly.dense(p["in_proj"], x))                   # (B,S,2E)
    x_in, z = xz[..., :E], xz[..., E:]
    xc = se(jax.nn.silu(_causal_conv(x_in, p["conv_w"], p["conv_b"])))
    delta, A, Bm, Cm = _ssm_inputs(p, cfg, xc)
    delta = se(_live(delta, lengths))
    if impl in ("pallas", "pallas_interpret") and not return_state:
        y = _scan_kernel(xc.astype(F32), delta, A, Bm, Cm, p["D"],
                         impl=impl)
        hT = None
    else:
        h0 = jnp.zeros((B, E, N), F32)
        if policy is not None and policy.mesh is not None \
                and cfg.train.ssm_shard_opt:
            from jax.sharding import PartitionSpec as P
            h0 = policy.sc(h0, P(policy.batch_axes, policy.model_axis,
                                 None))
        y, hT = _scan_chunked_xla(xc.astype(F32), delta, A, Bm, Cm,
                                  p["D"].astype(F32), h0, scan_chunk)
    y = se(y) * jax.nn.silu(z.astype(F32))
    out = Ly.dense(p["out_proj"], y.astype(x.dtype))
    if return_state:
        return out, {"conv": _conv_tail(x_in, K, lengths), "ssm": hT}
    return out, None


def mamba_step(p, cfg, x, state):
    """Single-token decode.  x (B,1,d); state {"conv" (B,K-1,E) fp32,
    "ssm" (B,E,N) fp32} -> (y (B,1,d), new state)."""
    B = x.shape[0]
    E, N, K = cfg.d_inner, cfg.ssm_state, cfg.ssm_conv
    xz = Ly.dense(p["in_proj"], x)                       # (B,1,2E)
    x_in, z = xz[..., :E], xz[..., E:]
    window = jnp.concatenate(
        [state["conv"], x_in.astype(F32)], axis=1)       # (B,K,E)
    xc = jnp.einsum("bke,ke->be", window, p["conv_w"].astype(F32)) \
        + p["conv_b"].astype(F32)
    xc = jax.nn.silu(xc)[:, None, :]                     # (B,1,E)
    delta, A, Bm, Cm = _ssm_inputs(p, cfg, xc)
    dA = jnp.exp(delta[:, 0, :, None] * A[None])         # (B,E,N)
    h = dA * state["ssm"] + (delta[:, 0] * xc[:, 0])[..., None] \
        * Bm[:, 0][:, None, :]
    y = jnp.einsum("ben,bn->be", h, Cm[:, 0]) \
        + xc[:, 0] * p["D"].astype(F32)[None]
    y = (y * jax.nn.silu(z[:, 0].astype(F32)))[:, None, :]
    out = Ly.dense(p["out_proj"], y.astype(x.dtype))
    new_state = {"conv": window[:, 1:], "ssm": h}
    return out, new_state


# --------------------------------------------------------------------------
# Mamba-2 (SSD)
# --------------------------------------------------------------------------


def mamba2_init(key, cfg):
    d, E, N, K = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_conv
    H, G = cfg.ssm_heads, cfg.ssm_groups
    conv = E + 2 * G * N
    ks = jax.random.split(key, 5)
    std = Ly.INIT_STD
    # dt at init log-uniform in [1e-3, 1e-1], A in [1, 16] (Mamba-2's)
    dt = jnp.exp(jax.random.uniform(ks[2], (H,), F32) * math.log(100.0)
                 + math.log(1e-3))
    return {
        "in_proj": Ly.dense_init(ks[0], d, E + conv + H),
        "conv_w": jax.random.uniform(ks[1], (K, conv), F32, -1.0, 1.0)
        / math.sqrt(K),
        "conv_b": jnp.zeros((conv,), F32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),       # softplus^-1(dt)
        "A_log": jnp.log(jax.random.uniform(ks[3], (H,), F32, 1.0, 16.0)),
        "D": jnp.ones((H,), F32),
        "norm": Ly.rms_norm_init(E),
        "out_proj": Ly.dense_init(
            ks[4], E, d, std=std / math.sqrt(2 * cfg.n_layers)),
    }


def _mamba2_split(p, cfg, zxbcdt):
    """in_proj output -> (z (..,E), xBC (..,conv) pre-conv, dt (..,H))."""
    E, N, G = cfg.d_inner, cfg.ssm_state, cfg.ssm_groups
    conv = E + 2 * G * N
    return zxbcdt[..., :E], zxbcdt[..., E:E + conv], zxbcdt[..., E + conv:]


def _gated_norm(p, cfg, y, z):
    """rmsnorm(y * silu(z)) over each of the ssm_groups groups of E."""
    G = cfg.ssm_groups
    h = y.astype(F32) * jax.nn.silu(z.astype(F32))
    hg = h.reshape(h.shape[:-1] + (G, -1))
    var = jnp.mean(hg * hg, axis=-1, keepdims=True)
    h = (hg * jax.lax.rsqrt(var + cfg.norm_eps)).reshape(h.shape)
    return h * p["norm"]["scale"].astype(F32)


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int, h0=None):
    """The SSD scan in chunks of ``chunk`` positions.

    x (B,S,H,P), dt (B,S,H), A (H,), Bm/Cm (B,S,G,N), all float32 ->
    (y (B,S,H,P) without the D skip, final state (B,H,P,N)).  Within a
    chunk, the quadratic form y_l = sum_{s<=l} C_l.B_s exp(a_l - a_s)
    dt_s x_s (a the running sum of dt*A); between chunks, a scan carries
    the state.  Sequences are right-padded to a whole chunk with dt = 0,
    which leaves the state as it was."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    Hg = H // G
    c = chunk
    pad = -S % c
    if pad:
        x, dt, Bm, Cm = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),)
                                 * (v.ndim - 2)) for v in (x, dt, Bm, Cm))
    nc = (S + pad) // c

    def chunks(v):                      # (B, S, ...) -> (nc, B, c, ...)
        return jnp.moveaxis(v.reshape((Bsz, nc, c) + v.shape[2:]), 1, 0)

    xs = (chunks((x * dt[..., None]).reshape(Bsz, S + pad, G, Hg, P)),
          chunks((dt * A).reshape(Bsz, S + pad, G, Hg)),
          chunks(Bm), chunks(Cm))
    causal = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
    if h0 is None:
        h0 = jnp.zeros((Bsz, H, P, N), F32)

    def body(h, inp):
        xdt, a, b, cc = inp     # (B,c,G,Hg,P) (B,c,G,Hg) (B,c,G,N) x2
        h = h.reshape(Bsz, G, Hg, P, N)
        acum = jnp.cumsum(a, axis=1)                      # (B,c,G,Hg)
        seg = acum[:, :, None] - acum[:, None, :]         # (B,l,s,G,Hg)
        L = jnp.exp(jnp.where(causal[None, :, :, None, None], seg,
                              -jnp.inf))
        cb = jnp.einsum("blgn,bsgn->blsg", cc, b)
        y = jnp.einsum("blsg,blsgj,bsgjp->blgjp", cb, L, xdt)
        y = y + jnp.einsum("blgn,bgjpn,blgj->blgjp", cc, h, jnp.exp(acum))
        last = acum[:, -1]                                # (B,G,Hg)
        h = jnp.exp(last)[..., None, None] * h + jnp.einsum(
            "bsgn,bsgj,bsgjp->bgjpn", b, jnp.exp(last[:, None] - acum), xdt)
        return h.reshape(Bsz, H, P, N), y.reshape(Bsz, c, H, P)

    hT, ys = jax.lax.scan(body, h0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(Bsz, S + pad, H, P)[:, :S]
    return y, hT


def mamba2_apply(p, cfg, x, *, return_state: bool = False, lengths=None):
    """Full-sequence Mamba-2 block.  x (B,S,d) -> (y, state | None);
    state {"conv" (B,K-1,conv) f32, "ssm" (B,H,P,N) f32}."""
    B, S, _ = x.shape
    H, P, K = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_conv
    E, N, G = cfg.d_inner, cfg.ssm_state, cfg.ssm_groups
    z, xbc_in, dt = _mamba2_split(p, cfg, Ly.dense(p["in_proj"], x))
    xbc = jax.nn.silu(_causal_conv(xbc_in, p["conv_w"], p["conv_b"]))
    xs, Bm, Cm = xbc[..., :E], xbc[..., E:E + G * N], xbc[..., E + G * N:]
    dt = _live(jax.nn.softplus(dt.astype(F32) + p["dt_bias"].astype(F32)),
               lengths)
    A = -jnp.exp(p["A_log"].astype(F32))
    xh = xs.reshape(B, S, H, P)
    with jax.named_scope("mamba2/ssd"):
        y, hT = ssd_chunked(xh, dt, A, Bm.reshape(B, S, G, N),
                            Cm.reshape(B, S, G, N), cfg.ssm_chunk)
    y = y + xh * p["D"].astype(F32)[:, None]
    y = _gated_norm(p, cfg, y.reshape(B, S, E), z)
    out = Ly.dense(p["out_proj"], y.astype(x.dtype))
    if return_state:
        return out, {"conv": _conv_tail(xbc_in, K, lengths), "ssm": hT}
    return out, None


def mamba2_step(p, cfg, x, state):
    """Single-token Mamba-2 decode.  x (B,1,d); state as
    :func:`mamba2_apply` returns it -> (y (B,1,d), new state)."""
    B = x.shape[0]
    H, P = cfg.ssm_heads, cfg.ssm_head_dim
    E, N, G = cfg.d_inner, cfg.ssm_state, cfg.ssm_groups
    with jax.named_scope("mamba2/step"):
        z, xbc_in, dt = _mamba2_split(p, cfg,
                                      Ly.dense(p["in_proj"], x)[:, 0])
        window = jnp.concatenate(
            [state["conv"], xbc_in.astype(F32)[:, None]], axis=1)
        xbc = jax.nn.silu(
            jnp.einsum("bkc,kc->bc", window, p["conv_w"].astype(F32))
            + p["conv_b"].astype(F32))
        xs = xbc[:, :E].reshape(B, G, H // G, P)
        Bm = xbc[:, E:E + G * N].reshape(B, G, 1, 1, N)
        Cm = xbc[:, E + G * N:].reshape(B, G, 1, 1, N)
        dt = jax.nn.softplus(dt.astype(F32) + p["dt_bias"].astype(F32))
        dt = dt.reshape(B, G, H // G)
        dA = jnp.exp(dt * -jnp.exp(p["A_log"].astype(F32)).reshape(
            G, H // G))
        h = state["ssm"].reshape(B, G, H // G, P, N)
        h = dA[..., None, None] * h + (dt[..., None] * xs)[..., None] * Bm
        y = jnp.sum(h * Cm, axis=-1) \
            + xs * p["D"].astype(F32).reshape(G, H // G, 1)
        y = _gated_norm(p, cfg, y.reshape(B, E), z)
        out = Ly.dense(p["out_proj"], y.astype(x.dtype))[:, None]
    return out, {"conv": window[:, 1:], "ssm": h.reshape(B, H, P, N)}
