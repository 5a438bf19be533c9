"""Layer stacks for all assigned architecture families.

A *layer* = (norm -> mixer -> residual) [+ (norm -> ffn -> residual)]
where mixer ∈ {GQA attention, mamba} and ffn ∈ {swiglu, gelu, moe, none}.
Uniform stacks (dense/moe/ssm/vlm, enc/dec halves of audio) are scanned
with stacked params; hybrids scan over *periods* (``cfg.layer_types``,
python-unrolled inside the scan body: jamba's 7 mamba layers and one
attention layer, granite-4.0-h's 10 with attention at the sixth) so a
heterogeneous mamba:attention interleave still compiles O(period) HLO.  Both residual branches are
scaled by ``cfg.residual_multiplier``.

Every layer reports MoE stats (``moe.moe_apply``): the load-balancing
``aux`` and the routed pairs ``dropped`` are summed over the stack; a
decode step on one device also returns ``expert_pairs`` (MoE layers x
held experts).

Three traversal modes share the layer definitions:
``train`` (no cache), ``prefill`` (emit per-layer cache), ``decode``
(consume+emit cache, one token).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from . import layers as Ly
from . import mamba as Mb
from . import moe as Moe

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class StackOpts:
    """Runtime knobs threaded through the stack (from TrainSettings)."""
    attn_impl: str = "xla"
    q_chunk: int = 1024
    k_chunk: int = 1024
    remat: str = "full"          # none | full | dots
    mamba_impl: str = "xla"
    mamba_chunk: int = 128
    moe_capacity: float = 1.25
    decode_len: int = 0          # static cache length for decode/prefill


def layer_kind(cfg, i: int) -> tuple[str, str, bool]:
    """(mixer, ffn, cross) for layer i."""
    mixer = "mamba" if not cfg._layer_has_attention(i) else "attn"
    if cfg._layer_has_moe(i):
        ffn = "moe"
    elif cfg.d_ff > 0:
        ffn = "gelu" if cfg.family == "audio" else "mlp"
    else:
        ffn = "none"
    return mixer, ffn, cfg.is_encdec


# --------------------------------------------------------------------------
# single-layer init / apply
# --------------------------------------------------------------------------


def layer_init(key, cfg, i: int, *, encoder: bool = False):
    mixer, ffn, cross = layer_kind(cfg, i)
    if encoder:
        mixer, ffn, cross = "attn", ("gelu" if cfg.family == "audio"
                                     else "mlp"), False
    ks = jax.random.split(key, 4)
    p: dict[str, Any] = {"ln1": Ly.rms_norm_init(cfg.d_model)}
    if mixer == "attn":
        p["attn"] = Ly.attn_init(ks[0], cfg)
    elif cfg.ssm_heads:
        p["mamba"] = Mb.mamba2_init(ks[0], cfg)
    else:
        p["mamba"] = Mb.mamba_init(ks[0], cfg)
    if cross and not encoder:
        p["ln_cross"] = Ly.rms_norm_init(cfg.d_model)
        p["cross"] = Ly.attn_init(ks[1], cfg, cross=True)
    if ffn != "none":
        p["ln2"] = Ly.rms_norm_init(cfg.d_model)
        if ffn == "moe":
            p["ffn_moe"] = Moe.moe_init(ks[2], cfg)
        elif ffn == "gelu":
            p["ffn_gelu"] = Ly.gelu_mlp_init(ks[2], cfg.d_model, cfg.d_ff,
                                             cfg.n_layers)
        else:
            p["ffn_mlp"] = Ly.swiglu_init(ks[2], cfg.d_model, cfg.d_ff,
                                          cfg.n_layers)
    return p


def no_stats():
    return {"aux": jnp.zeros((), F32), "dropped": jnp.zeros((), jnp.int32)}


def _apply_ffn(p, cfg, x, policy, opts, *, decode: bool):
    """-> (x, stats); stats as ``moe.moe_apply``'s (zeros without MoE)."""
    stats = no_stats()
    r = cfg.residual_multiplier
    # MLP f-dim pins are a *training* lever (§Perf iter 5); prefill/decode
    # layouts differ and the pins force resharding there (measured)
    mlp_pin = cfg.train.mlp_shard_opt and opts.decode_len == 0 \
        and not decode
    if "ffn_moe" in p:
        h = Ly.rms_norm(p["ln2"], x, cfg.norm_eps)
        y, stats = Moe.moe_apply(p["ffn_moe"], cfg, h, policy,
                                 decode=decode,
                                 capacity_factor=opts.moe_capacity)
        x = x + y * r
    elif "ffn_gelu" in p:
        pol = policy if mlp_pin else None
        x = x + Ly.gelu_mlp(p["ffn_gelu"],
                            Ly.rms_norm(p["ln2"], x, cfg.norm_eps),
                            policy=pol) * r
    elif "ffn_mlp" in p:
        pol = policy if mlp_pin else None
        x = x + Ly.swiglu(p["ffn_mlp"],
                          Ly.rms_norm(p["ln2"], x, cfg.norm_eps),
                          policy=pol) * r
    return x, stats


def layer_apply(p, cfg, x, positions, policy, opts, *,
                causal: bool = True, enc_out=None, want_cache: bool = False,
                lengths=None):
    """Full-sequence layer (train / prefill / encoder).  ``lengths`` (B,):
    true lengths of right-padded sequences (SSM state taken there).

    Returns (x, stats, cache) — cache is {} unless want_cache."""
    cache = {}
    r = cfg.residual_multiplier
    h = Ly.rms_norm(p["ln1"], x, cfg.norm_eps)
    if "attn" in p:
        y, (k, v) = Ly.attn_apply(
            p["attn"], cfg, h, positions, causal=causal,
            attn_impl=opts.attn_impl, q_chunk=opts.q_chunk,
            k_chunk=opts.k_chunk, policy=policy,
            train_mode=not want_cache and opts.decode_len == 0)
        x = x + y * r
        if want_cache:
            cache["k"], cache["v"] = _cache_pad(k, opts.decode_len), \
                _cache_pad(v, opts.decode_len)
    else:
        if cfg.ssm_heads:
            y, state = Mb.mamba2_apply(p["mamba"], cfg, h,
                                       return_state=want_cache,
                                       lengths=lengths)
        else:
            # SSM activation pins help training (§Perf iter 4) but force
            # resharding in the prefill layout — train-path only
            y, state = Mb.mamba_apply(
                p["mamba"], cfg, h, impl=opts.mamba_impl,
                scan_chunk=opts.mamba_chunk, return_state=want_cache,
                policy=None if want_cache else policy, lengths=lengths)
        x = x + y * r
        if want_cache:
            cache["conv"], cache["ssm"] = state["conv"], state["ssm"]
    if "cross" in p and enc_out is not None:
        hc = Ly.rms_norm(p["ln_cross"], x, cfg.norm_eps)
        yc, (ck, cv) = Ly.attn_apply(
            p["cross"], cfg, hc, positions, causal=False, kv_x=enc_out,
            attn_impl=opts.attn_impl, q_chunk=opts.q_chunk,
            k_chunk=opts.k_chunk, use_rope=False, policy=policy)
        x = x + yc
        if want_cache:
            cache["ck"], cache["cv"] = ck, cv
    x, stats = _apply_ffn(p, cfg, x, policy, opts, decode=False)
    return x, stats, cache


def _cache_pad(k, decode_len: int):
    """Grow prefill kv (B,H,S,D) to the static decode capacity."""
    if decode_len and k.shape[2] < decode_len:
        pad = decode_len - k.shape[2]
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return k


def layer_decode(p, cfg, x, cache, cache_len, policy, opts):
    """One-token decode through one layer; the cache is only read.
    Returns (x, new, stats): ``new`` is what the step writes — the token's
    K and V (B,Hkv,1,D) for attention, the whole new conv and SSM state
    for a mamba layer; cross-attention memory is never written."""
    r = cfg.residual_multiplier
    h = Ly.rms_norm(p["ln1"], x, cfg.norm_eps)
    if "attn" in p:
        y, new = Ly.attn_decode(p["attn"], cfg, h,
                                {"k": cache["k"], "v": cache["v"]},
                                cache_len, policy=policy)
    else:
        step = Mb.mamba2_step if cfg.ssm_heads else Mb.mamba_step
        y, new = step(p["mamba"], cfg, h, {"conv": cache["conv"],
                                           "ssm": cache["ssm"]})
    x = x + y * r
    if "cross" in p:
        hc = Ly.rms_norm(p["ln_cross"], x, cfg.norm_eps)
        yc, _ = Ly.attn_decode(p["cross"], cfg, hc,
                               {"k": cache["ck"], "v": cache["cv"]},
                               cache_len, cross=True, policy=policy)
        x = x + yc
    x, stats = _apply_ffn(p, cfg, x, policy, opts, decode=True)
    return x, new, stats


# --------------------------------------------------------------------------
# stacks (scan over layers / periods)
# --------------------------------------------------------------------------


def stack_init(key, cfg, *, encoder: bool = False):
    n = cfg.encoder_layers if encoder else cfg.n_layers
    per = 1 if encoder else cfg.layer_period
    n_groups = n // per
    keys = jax.random.split(key, n_groups)
    if per == 1:
        init_one = partial(layer_init, cfg=cfg, i=0, encoder=encoder)
        return jax.vmap(init_one)(keys)

    def init_period(k):
        ks = jax.random.split(k, per)
        return {f"sub{j}": layer_init(ks[j], cfg, j) for j in range(per)}

    return jax.vmap(init_period)(keys)


def _wrap_remat(fn, remat: str):
    if remat == "none":
        return fn
    if remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


def _sublayers(per: int):
    """Keys of one scanned group's layers (None: the group is a layer)."""
    return [None] if per == 1 else [f"sub{j}" for j in range(per)]


def stack_apply(stack_params, cfg, x, positions, policy, opts, *,
                causal: bool = True, enc_out=None, encoder: bool = False,
                want_cache: bool = False, lengths=None):
    """Scan the stack. Returns (x, stats summed over layers,
    stacked_caches | None)."""
    per = 1 if encoder else cfg.layer_period

    def body(carry, p_layer):
        x, total = carry
        caches = {}
        for name in _sublayers(per):
            x, st, cache = layer_apply(
                p_layer if name is None else p_layer[name], cfg, x,
                positions, policy, opts, causal=causal, enc_out=enc_out,
                want_cache=want_cache, lengths=lengths)
            total = {k: total[k] + st[k] for k in total}
            if name is None:
                caches = cache
            else:
                caches[name] = cache
        return (x, total), caches

    body = _wrap_remat(body, opts.remat)
    (x, stats), caches = jax.lax.scan(body, (x, no_stats()), stack_params)
    return x, stats, (caches if want_cache else None)


def write_kv(cache, new, cache_len):
    """Write one token's K or V per slot into a stacked cache leaf, in
    place: ``cache`` (G,B,Hkv,S,D), ``new`` (G,B,Hkv,1,D) of its dtype,
    slot ``b`` at its own position ``cache_len[b]`` in ``[0, S)``, or every
    slot at one scalar position.  One ``dynamic_update_slice`` of
    G x Hkv x D values per slot (of G x B x Hkv x D for a scalar); the rest
    of the cache is not touched."""
    cl = jnp.asarray(cache_len, jnp.int32)
    with jax.named_scope("decode/cache_write"):
        if cl.ndim == 0:
            return jax.lax.dynamic_update_slice(cache, new, (0, 0, 0, cl, 0))
        for b in range(cache.shape[1]):
            cache = jax.lax.dynamic_update_slice(
                cache, new[:, b:b + 1], (0, b, 0, cl[b], 0))
    return cache


def stack_decode(stack_params, cfg, x, caches, cache_len, policy, opts):
    """Decode one token through the whole stack; caches are stacked over
    the scan axis exactly as produced by stack_apply(want_cache=True).

    The layer scan only reads the caches.  It emits what the step writes:
    each attention layer's K and V of the token (n_groups,B,Hkv,1,D), which
    ``write_kv`` puts into the (donated) stacked leaves at each slot's
    ``cache_len`` after the scan, and each mamba layer's new state, which
    replaces its leaf whole.  Cross-attention leaves pass through.  So on
    one device no op but attention (each layer's K and V read once) touches
    a whole stacked K or V leaf.
    Returns (x, new_caches, stats): ``moe_pairs_dropped`` summed over the
    layers and, on one device, ``expert_pairs`` (MoE layers, held
    experts)."""
    per = cfg.layer_period

    def body(carry, inp):
        x, dropped = carry
        p_layer, cache = inp
        written, pairs = {}, []
        for name in _sublayers(per):
            x, new, st = layer_decode(
                p_layer if name is None else p_layer[name], cfg, x,
                cache if name is None else cache[name], cache_len, policy,
                opts)
            dropped = dropped + st["dropped"]
            if "pairs" in st:
                pairs.append(st["pairs"])
            if name is None:
                written = new
            else:
                written[name] = new
        return (x, dropped), (written, jnp.stack(pairs) if pairs else ())

    (x, dropped), (written, pairs) = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.int32)), (stack_params, caches))

    def merge(cache, new):
        return {name: (write_kv(leaf, new[name], cache_len)
                       if name in ("k", "v") else new.get(name, leaf))
                for name, leaf in cache.items()}

    if per == 1:
        new_caches = merge(caches, written)
    else:
        new_caches = {name: merge(caches[name], written[name])
                      for name in caches}
    stats = {"moe_pairs_dropped": dropped}
    if not isinstance(pairs, tuple):
        stats["expert_pairs"] = pairs.reshape(-1, pairs.shape[-1])
    return x, new_caches, stats
