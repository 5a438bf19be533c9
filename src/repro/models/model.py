"""Model assembly: init, forward, loss, train_step, prefill, serve_step.

All ten assigned architectures are built from the same pieces; the config
decides layer kinds (transformer.layer_kind) and the frontend stubs
([vlm]/[audio] per the assignment: precomputed patch/frame embeddings are
*inputs*, not modeled)."""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..optim import adamw
from . import layers as Ly
from . import mamba as Mb
from . import transformer as Tf
from .sharding import Policy
from .transformer import StackOpts

F32 = jnp.float32


def opts_from_cfg(cfg, *, decode_len: int = 0,
                  attn_impl: str = "xla") -> StackOpts:
    t = cfg.train
    return StackOpts(attn_impl=attn_impl, q_chunk=t.attn_q_chunk,
                     k_chunk=t.attn_k_chunk, remat=t.remat,
                     moe_capacity=t.moe_capacity_factor,
                     decode_len=decode_len)


# --------------------------------------------------------------------------
# params
# --------------------------------------------------------------------------


def init_params(key, cfg):
    ks = jax.random.split(key, 5)
    V = cfg.padded_vocab()
    params: dict[str, Any] = {
        "embed": Ly.embed_init(ks[0], V, cfg.d_model),
        "layers": Tf.stack_init(ks[1], cfg),
        "final_norm": Ly.rms_norm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = Ly.dense_init(ks[2], cfg.d_model, V)
    if cfg.is_encdec:
        params["encoder"] = {
            "layers": Tf.stack_init(ks[3], cfg, encoder=True),
            "norm": Ly.rms_norm_init(cfg.d_model),
        }
    return params


def _positions(B, S, offset=0):
    return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S)) \
        + offset


def _encode(params, cfg, frames, policy, opts):
    """Audio/enc-dec encoder over stub frame embeddings (B,Senc,d)."""
    x = frames.astype(jnp.bfloat16)
    pos = _positions(x.shape[0], x.shape[1])
    x, _stats, _ = Tf.stack_apply(params["encoder"]["layers"], cfg, x, pos,
                                  policy, opts, causal=False, encoder=True)
    return Ly.rms_norm(params["encoder"]["norm"], x, cfg.norm_eps)


def backbone(params, cfg, batch, policy, opts, *, want_cache=False,
             lengths=None):
    """Embed -> stack -> final norm.  Returns (x, stats, caches, n_prefix):
    ``stats`` are the MoE layers' ``aux`` and ``dropped``, summed.

    n_prefix = frontend tokens prepended (vlm) — loss/labels skip them.
    ``lengths`` (B,): true lengths of right-padded token rows."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = Ly.embed_lookup(params["embed"], tokens) * cfg.embedding_multiplier
    n_prefix = 0
    if cfg.frontend == "vision":
        patches = batch["patch_embeds"].astype(x.dtype)   # (B, P, d)
        x = jnp.concatenate([patches, x], axis=1)
        n_prefix = patches.shape[1]
    if policy is not None:
        x = policy.shard_activations(x)
    enc_out = None
    if cfg.is_encdec:
        enc_out = _encode(params, cfg, batch["frames"], policy, opts)
    pos = _positions(B, x.shape[1])
    if lengths is not None:
        lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32) + n_prefix,
                                   (B,))
    x, stats, caches = Tf.stack_apply(params["layers"], cfg, x, pos, policy,
                                      opts, causal=True, enc_out=enc_out,
                                      want_cache=want_cache, lengths=lengths)
    x = Ly.rms_norm(params["final_norm"], x, cfg.norm_eps)
    return x, stats, caches, n_prefix


# --------------------------------------------------------------------------
# loss (seq-chunked cross entropy: caps live logits at (B, S/chunks, V))
# --------------------------------------------------------------------------


def _head_weight(params, cfg):
    if cfg.tie_embeddings:
        return params["embed"]["embed"].T
    return params["lm_head"]["w"]


def logits(params, cfg, x):
    """x (B,S,d) -> logits fp32 (B,S,V), divided by ``logits_scaling``."""
    return Ly.logits_out(
        params.get("lm_head"), x,
        tied_embed=params["embed"] if cfg.tie_embeddings else None) \
        / cfg.logits_scaling


def ce_loss(params, cfg, x, labels, chunks: int = 1):
    """x (B,S,d) fp/bf16, labels (B,S) int32 (-1 = masked)."""
    B, S, d = x.shape
    w = _head_weight(params, cfg).astype(jnp.bfloat16)
    chunks = max(1, min(chunks, S))
    while S % chunks != 0:
        chunks -= 1
    c = S // chunks

    @jax.checkpoint
    def chunk_loss(_, inp):
        xc, yc = inp                                    # (B,c,d), (B,c)
        logits = jax.lax.dot_general(
            xc.astype(jnp.bfloat16), w, (((2,), (0,)), ((), ())),
            preferred_element_type=F32) / cfg.logits_scaling   # (B,c,V)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(yc, 0)[..., None], axis=-1)[..., 0]
        mask = (yc >= 0).astype(F32)
        return None, (jnp.sum((lse - gold) * mask), jnp.sum(mask))

    xs = (jnp.moveaxis(x.reshape(B, chunks, c, d), 1, 0),
          jnp.moveaxis(labels.reshape(B, chunks, c), 1, 0))
    _, (losses, counts) = jax.lax.scan(chunk_loss, None, xs)
    total, count = jnp.sum(losses), jnp.maximum(jnp.sum(counts), 1.0)
    return total / count


# matmul weights that every layer casts to bf16 at use anyway — casting
# them ONCE at the top (pinned to their sharding) moves the f32->bf16
# convert outside the layer scan, so FSDP weight gathers and per-layer
# gradient collectives travel in bf16 (numerics-identical: the dots were
# bf16 already; grad accumulation across microbatches stays f32).
# §Perf iteration 3 in EXPERIMENTS.md.
_BF16_CASTABLE = ("embed", "e_gate", "e_up", "e_down")


def _cast_weights_bf16(params, policy: Optional[Policy]):
    specs = policy.param_specs(params) if policy is not None \
        and policy.mesh is not None else None

    def cast(path, p, spec=None):
        names = [k.key for k in path
                 if isinstance(k, jax.tree_util.DictKey)]
        name = names[-1] if names else ""
        parent = names[-2] if len(names) > 1 else ""
        castable = (name == "w" and parent != "dt_proj") \
            or name in _BF16_CASTABLE
        if not (castable and p.dtype == jnp.float32 and p.ndim >= 2):
            return p
        c = p.astype(jnp.bfloat16)
        if spec is not None and policy is not None:
            c = policy.sc(c, spec)      # pin: reshard AFTER the cast
        return c

    if specs is None:
        return jax.tree_util.tree_map_with_path(cast, params)
    return jax.tree_util.tree_map_with_path(cast, params, specs)


def make_loss_fn(cfg, policy: Optional[Policy], opts: StackOpts,
                 aux_coeff: float = 0.01):
    def loss_fn(params, batch):
        if cfg.train.bf16_weight_cast:
            params = _cast_weights_bf16(params, policy)
        x, stats, _, n_prefix = backbone(params, cfg, batch, policy, opts)
        labels = batch["labels"]
        if n_prefix:
            x = x[:, n_prefix:]
        loss = ce_loss(params, cfg, x, labels, cfg.train.loss_seq_chunks)
        total = loss + aux_coeff * stats["aux"]
        return total, {"loss": loss, "moe_aux": stats["aux"],
                       "moe_pairs_dropped": stats["dropped"]}
    return loss_fn


# --------------------------------------------------------------------------
# train step (microbatched grad accumulation + AdamW)
# --------------------------------------------------------------------------


def make_train_step(cfg, policy: Optional[Policy],
                    opt_cfg: adamw.AdamWConfig, *,
                    attn_impl: str = "xla"):
    opts = opts_from_cfg(cfg, attn_impl=attn_impl)
    loss_fn = make_loss_fn(cfg, policy, opts)
    n_micro = max(1, cfg.train.microbatches)

    def shard_grads_2d(tree):
        """Perf iter 2a (EXPERIMENTS.md §Perf): keep the gradient
        accumulator ZeRO-sharded (2D).  An unconstrained accumulator is
        resolved replicated by GSPMD, which all-reduces every layer's
        full f32 grad once per MICROBATCH; the 2D constraint turns that
        into reduce-scatters (half the ring bytes) and feeds the ZeRO
        optimizer shards directly."""
        if policy is None or policy.mesh is None \
                or not cfg.train.grad_2d_accum:
            return tree
        specs = policy.param_specs(tree, use2d=True)
        return jax.tree_util.tree_map(
            lambda x, s: policy.sc(x, s), tree, specs)

    def train_step(params, opt_state, batch):
        if n_micro == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            grads = shard_grads_2d(grads)
        else:
            def split(v):
                return v.reshape((n_micro, v.shape[0] // n_micro)
                                 + v.shape[1:])
            micro = jax.tree_util.tree_map(split, batch)
            zero_g = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, F32), params)
            zero_g = shard_grads_2d(zero_g)

            def acc(carry, mb):
                g_sum, l_sum, a_sum = carry
                (l, met), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, mb)
                # constrain f32 grads 2D right at the backward output so
                # the layer-scan carry resolves sharded (reduce-scatter,
                # not all-reduce); accumulate f32
                g = shard_grads_2d(g)
                g_sum = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(F32), g_sum, g)
                return (g_sum, l_sum + l, a_sum + met["moe_aux"],
                        d_sum + met["moe_pairs_dropped"]), None

            (g_sum, l_sum, a_sum, d_sum), _ = jax.lax.scan(
                acc, (zero_g, jnp.zeros((), F32), jnp.zeros((), F32),
                      jnp.zeros((), jnp.int32)), micro)
            grads = jax.tree_util.tree_map(lambda g: g / n_micro, g_sum)
            loss = l_sum / n_micro
            metrics = {"loss": loss, "moe_aux": a_sum / n_micro,
                       "moe_pairs_dropped": d_sum}
        params, opt_state, opt_metrics = adamw.update(
            params, grads, opt_state, opt_cfg)
        metrics = dict(metrics, **opt_metrics)
        return params, opt_state, metrics

    return train_step


# --------------------------------------------------------------------------
# inference: prefill + single-token decode
# --------------------------------------------------------------------------


def make_prefill(cfg, policy: Optional[Policy], *, decode_len: int,
                 attn_impl: str = "xla"):
    opts = opts_from_cfg(cfg, decode_len=decode_len, attn_impl=attn_impl)

    def prefill(params, batch):
        x, stats, caches, _ = backbone(params, cfg, batch, policy, opts,
                                       want_cache=True)
        return logits(params, cfg, x[:, -1:])[:, 0], caches, \
            {"moe_pairs_dropped": stats["dropped"]}
    return prefill


def make_serve_step(cfg, policy: Optional[Policy], *,
                    attn_impl: str = "xla"):
    """One decode step: (params, caches, tokens (B,1), cache_len) ->
    (logits (B,V), new caches, stats) — ``stack_decode``'s stats.

    ``cache_len`` is a scalar (the one-shot serve loop: whole batch at the
    same position) or a ``(B,)`` vector of per-slot positions (the serving
    engine's continuous batching: each slot decodes at its own length —
    see ``repro.serving``)."""
    opts = opts_from_cfg(cfg, attn_impl=attn_impl)

    def serve_step(params, caches, tokens, cache_len):
        x = Ly.embed_lookup(params["embed"], tokens) \
            * cfg.embedding_multiplier                    # (B,1,d)
        x, new_caches, stats = Tf.stack_decode(
            params["layers"], cfg, x, caches, cache_len, policy, opts)
        x = Ly.rms_norm(params["final_norm"], x, cfg.norm_eps)
        return logits(params, cfg, x)[:, 0], new_caches, stats
    return serve_step


def make_slot_prefill(cfg, policy: Optional[Policy], *, decode_len: int,
                      attn_impl: str = "xla"):
    """Prefill for one continuous-batching slot refill.

    ``(params, batch, length) -> (logits (B,V), caches, stats)`` where
    ``batch['tokens']`` is a fixed-shape *right-padded* prompt ``(B,P)``
    and ``length`` is the true prompt length: logits are taken at
    position ``length - 1`` (the last real token — it attends only to
    real positions under the causal mask) instead of the padded end.
    Padding rows land in cache positions ``>= length`` but stay masked at
    decode (``decode_attention`` masks ``> cache_len``) and are
    overwritten token by token as the slot generates; SSM layers take
    their state at ``length`` (the padding leaves it unchanged).
    ``stats``: the prefill's ``moe_pairs_dropped``.  One fixed padded
    shape = one jit trace for every prompt length (heterogeneous request
    sizes all hit the cached executable)."""
    opts = opts_from_cfg(cfg, decode_len=decode_len, attn_impl=attn_impl)

    def slot_prefill(params, batch, length):
        x, stats, caches, n_prefix = backbone(params, cfg, batch, policy,
                                              opts, want_cache=True,
                                              lengths=length)
        idx = jnp.asarray(n_prefix + length - 1, jnp.int32)
        xl = jax.lax.dynamic_slice_in_dim(x, idx, 1, axis=1)
        return logits(params, cfg, xl)[:, 0], caches, \
            {"moe_pairs_dropped": stats["dropped"]}
    return slot_prefill


def write_cache_slot(caches, one, slot):
    """Scatter a single-sequence cache pytree (batch 1, as produced by
    ``make_slot_prefill``) into a running batch cache at batch index
    ``slot`` — the continuous-batching refill: freed slots take a new
    sequence's prefilled KV without touching the other slots.  All cache
    leaves are stacked ``(n_groups, B, ...)``, so the slot axis is 1."""
    def upd(b, o):
        start = (0, slot) + (0,) * (b.ndim - 2)
        return jax.lax.dynamic_update_slice(b, o.astype(b.dtype), start)
    return jax.tree_util.tree_map(upd, caches, one)


# --------------------------------------------------------------------------
# cache shape construction (for sharding specs and serving)
# --------------------------------------------------------------------------


def cache_struct(cfg, batch_size: int, decode_len: int,
                 enc_len: int = 0):
    """Abstract (ShapeDtypeStruct) cache pytree matching stack_apply's
    stacked layout."""
    per = cfg.layer_period
    n_groups = cfg.n_layers // per
    B = batch_size

    def one(kind_i):
        mixer, _, cross = Tf.layer_kind(cfg, kind_i)
        c = {}
        if mixer == "attn":
            kv = (B, cfg.n_kv_heads, decode_len, cfg.d_head)
            c["k"] = jax.ShapeDtypeStruct(kv, jnp.bfloat16)
            c["v"] = jax.ShapeDtypeStruct(kv, jnp.bfloat16)
        elif cfg.ssm_heads:          # mamba-2: heads x d_head x d_state
            conv = cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
            c["conv"] = jax.ShapeDtypeStruct(
                (B, cfg.ssm_conv - 1, conv), F32)
            c["ssm"] = jax.ShapeDtypeStruct(
                (B, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), F32)
        else:
            c["conv"] = jax.ShapeDtypeStruct(
                (B, cfg.ssm_conv - 1, cfg.d_inner), F32)
            c["ssm"] = jax.ShapeDtypeStruct(
                (B, cfg.d_inner, cfg.ssm_state), F32)
        if cross:
            ckv = (B, cfg.n_kv_heads, enc_len, cfg.d_head)
            c["ck"] = jax.ShapeDtypeStruct(ckv, jnp.bfloat16)
            c["cv"] = jax.ShapeDtypeStruct(ckv, jnp.bfloat16)
        return c

    def stack(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((n_groups,) + s.shape, s.dtype),
            tree)

    if per == 1:
        return stack(one(0))
    return stack({f"sub{j}": one(j) for j in range(per)})
