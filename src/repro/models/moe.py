"""Mixture-of-Experts with HPTMT shuffle dispatch.

The paper's central operator — the table Shuffle (hash partition +
``all_to_all``) — *is* MoE token dispatch: rows = tokens, partition key =
routed expert, destination shard = expert owner.  ``moe_shuffle`` composes
the same ``radix_histogram_ranks`` plan used by ``core.dist_ops.shuffle``
with an ``all_to_all`` over the model axis (expert parallelism), exactly
the paper's "distributed operator = communication + local operator"
recipe (DESIGN.md §2).

Three paths:
* ``moe_dense``   — one device: every expert it holds over every token,
  gated (smoke tests, 1 device, expert counts indivisible by the model
  axis, e.g. granite's 40, and one device's share of an expert-parallel
  layer, ``cfg.router_width`` set: it routes over every expert and
  computes the held ones);
* ``moe_shuffle`` — shard_map EP dispatch for train/prefill (seq sharded
  over the model axis inside the block).  Only the token payload crosses
  the wire (bf16); routing metadata stays local because the tiled
  all_to_all is slot-symmetric — the return trip lands each row back in
  the slot it was sent from;
* ``moe_decode``  — replicated-token decode: each rank serves its local
  experts and combines with ``psum`` (cheaper than all_to_all at step
  sizes of a few hundred tokens).

Uneven expert counts are parameter-padded to a multiple of 16
(``cfg.n_experts`` stays the routing width; pads receive no tokens).

Every path reports the routed pairs it dropped (``moe_pairs_dropped``):
capacity is counted, never silent.  A shared expert (``d_shared_ff``) is
added once, beside whichever path ran.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.context import shard_map
from ..kernels.hash_partition import radix_histogram_ranks
from . import layers as Ly

F32 = jnp.float32


def n_experts_padded(cfg) -> int:
    E = cfg.n_experts
    return math.ceil(E / 16) * 16 if E >= 16 else E


def moe_init(key, cfg):
    d = cfg.d_model
    E = n_experts_padded(cfg)
    f = cfg.d_expert_ff or cfg.d_ff
    ks = jax.random.split(key, 5)
    std = Ly.INIT_STD
    width = cfg.router_width or cfg.n_experts
    p = {
        "router": jax.random.normal(ks[0], (d, width), F32) * std,
        "e_gate": jax.random.normal(ks[1], (E, d, f), F32) * std,
        "e_up": jax.random.normal(ks[2], (E, d, f), F32) * std,
        "e_down": jax.random.normal(ks[3], (E, f, d), F32)
        * (std / math.sqrt(2 * cfg.n_layers)),
    }
    if cfg.d_shared_ff:
        p["shared"] = Ly.swiglu_init(ks[4], d, cfg.d_shared_ff, cfg.n_layers)
    return p


def _route(router, x2d, top_k: int):
    """x2d (T, d) -> (weights (T,k) f32, ids (T,k) i32, aux-loss scalar)."""
    logits = x2d.astype(F32) @ router.astype(F32)
    probs = jax.nn.softmax(logits, axis=-1)
    vals, ids = jax.lax.top_k(probs, top_k)
    w = vals / jnp.maximum(jnp.sum(vals, axis=-1, keepdims=True), 1e-9)
    E = router.shape[1]
    frac = jnp.mean(jax.nn.one_hot(ids[:, 0], E, dtype=F32), axis=0)
    pmean = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac * pmean)
    return w, ids.astype(jnp.int32), aux


def _expert_ffn(eg, eu, ed, xb):
    """xb (E_loc, C, d) -> (E_loc, C, d); bf16 GEMMs."""
    bf = jnp.bfloat16
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xb.astype(bf),
                               eg.astype(bf)))
    u = jnp.einsum("ecd,edf->ecf", xb.astype(bf), eu.astype(bf))
    return jnp.einsum("ecf,efd->ecd", g * u, ed.astype(bf))


# --------------------------------------------------------------------------
# one device: every expert it holds, over every token
# --------------------------------------------------------------------------


def moe_dense(p, cfg, x):
    """The ``cfg.n_experts`` experts held here, from ``cfg.expert_offset``,
    of a router ``cfg.router_width`` wide (0: it routes over the held
    experts alone).  x (B,S,d) -> (y, aux, pairs): y is the held experts'
    part of the layer (pairs routed elsewhere are another device's);
    ``pairs`` counts the routed pairs each held expert took.

    Every held expert runs over every token, and a gate of 0 weights each
    pair not routed to it, so nothing is dispatched and nothing drops.
    The gates scale the hidden activations, and the down projection sums
    over experts and hidden units in one contraction."""
    B, S, d = x.shape
    E = cfg.n_experts
    x2 = x.reshape(B * S, d)
    with jax.named_scope("moe/route"):
        w, ids, aux = _route(p["router"], x2, cfg.top_k)
        hot = jax.nn.one_hot(ids - cfg.expert_offset, E, dtype=F32)
        gates = jnp.einsum("tke,tk->te", hot, w)
        pairs = jnp.sum(hot, axis=(0, 1)).astype(jnp.int32)
    with jax.named_scope("moe/experts"):
        bf = jnp.bfloat16
        eg, eu, ed = (p["e_gate"][:E], p["e_up"][:E], p["e_down"][:E])
        h = jnp.einsum("td,edf->tef", x2.astype(bf), eg.astype(bf),
                       preferred_element_type=F32)
        u = jnp.einsum("td,edf->tef", x2.astype(bf), eu.astype(bf),
                       preferred_element_type=F32)
        a = (jax.nn.silu(h) * u * gates[..., None]).astype(bf)
        y = jnp.einsum("tef,efd->td", a, ed.astype(bf),
                       preferred_element_type=F32)
    return y.reshape(B, S, d).astype(x.dtype), aux, pairs


# --------------------------------------------------------------------------
# shuffle-dispatch EP (train / prefill) — the paper's operator
# --------------------------------------------------------------------------


def _batch_axes_for(policy, B: int):
    """Batch axes actually usable for B (drop them if indivisible)."""
    world_b = 1
    for a in policy.batch_axes:
        world_b *= policy.mesh.shape[a]
    return policy.batch_axes if B % world_b == 0 else ()


def moe_shuffle(p, cfg, x, policy, capacity_factor: float = 1.25):
    mesh = policy.mesh
    maxis = policy.model_axis
    world_m = mesh.shape[maxis]
    E = cfg.n_experts
    E_pad = n_experts_padded(cfg)
    if world_m == 1 or E_pad % world_m != 0 \
            or x.shape[1] % world_m != 0:
        y, aux, _ = moe_dense(p, cfg, x)
        return y, aux, jnp.zeros((), jnp.int32)
    baxes = _batch_axes_for(policy, x.shape[0])
    batch_spec = P(baxes, maxis, None)
    aux_spec = P(baxes, maxis)

    def local(x_loc, router, eg, eu, ed):
        b, s, d = x_loc.shape
        T = b * s
        k = cfg.top_k
        E_loc = E_pad // world_m
        C_send = max(1, math.ceil(T * k / E * capacity_factor))
        slots = E_loc * C_send
        x2 = x_loc.reshape(T, d)
        w, ids, aux = _route(router, x2, k)

        # ---- shuffle plan: stable rank of each routed row in its expert
        eid = ids.reshape(-1)                                 # (T*k,)
        src = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
        wf = w.reshape(-1).astype(F32)
        _, ranks = radix_histogram_ranks(eid, E)
        owner = eid // E_loc
        le = eid % E_loc
        ok = ranks < C_send
        flat = jnp.where(ok, owner * slots + le * C_send + ranks,
                         world_m * slots)

        payload = jnp.zeros((world_m * slots + 1, d), jnp.bfloat16) \
            .at[flat].set(x2.astype(jnp.bfloat16)[src])[:-1] \
            .reshape(world_m, slots, d)

        a2a = partial(jax.lax.all_to_all, axis_name=maxis, split_axis=0,
                      concat_axis=0, tiled=True)
        r_pay = a2a(payload)                       # (world_m, slots, d)
        xb = r_pay.reshape(world_m, E_loc, C_send, d) \
            .transpose(1, 0, 2, 3).reshape(E_loc, world_m * C_send, d)
        h = _expert_ffn(eg, eu, ed, xb)
        h = h.reshape(E_loc, world_m, C_send, d).transpose(1, 0, 2, 3) \
            .reshape(world_m, slots, d)
        y_rows = a2a(h).reshape(world_m * slots, d)  # back in my layout

        g = y_rows[jnp.clip(flat, 0, world_m * slots - 1)].astype(F32)
        contrib = g * (wf * ok)[:, None]
        y = jnp.zeros((T, d), F32).at[src].add(contrib)
        return (y.reshape(b, s, d).astype(x_loc.dtype),
                aux[None, None],
                jnp.sum(~ok, dtype=jnp.int32)[None, None])

    # cast to bf16 BEFORE the boundary: the fsdp_tp data-axis gather of
    # expert weights then moves half the bytes (§Perf iter 2c);
    # numerics-identical (the expert GEMMs cast at use anyway)
    cast = (lambda w: w.astype(jnp.bfloat16)) \
        if cfg.train.bf16_weight_cast else (lambda w: w)
    y, aux, dropped = shard_map(
        local, mesh=mesh,
        in_specs=(batch_spec, P(), P(maxis, None, None),
                  P(maxis, None, None), P(maxis, None, None)),
        out_specs=(batch_spec, aux_spec, aux_spec),
    )(x, p["router"], cast(p["e_gate"]), cast(p["e_up"]),
      cast(p["e_down"]))
    return y, jnp.mean(aux), jnp.sum(dropped)


# --------------------------------------------------------------------------
# decode path: replicated tokens, local experts, psum combine
# --------------------------------------------------------------------------


def moe_decode(p, cfg, x, policy, capacity_factor: float = 4.0):
    mesh = policy.mesh
    maxis = policy.model_axis
    world_m = mesh.shape[maxis]
    E = cfg.n_experts
    E_pad = n_experts_padded(cfg)
    if world_m == 1 or E_pad % world_m != 0:
        y, aux, _ = moe_dense(p, cfg, x)
        return y, aux, jnp.zeros((), jnp.int32)
    baxes = _batch_axes_for(policy, x.shape[0])
    batch_spec = P(baxes, None, None)
    aux_spec = P(baxes)

    def local(x_loc, router, eg, eu, ed):
        b, s, d = x_loc.shape
        T = b * s
        k = cfg.top_k
        E_loc = E_pad // world_m
        C = max(8, math.ceil(T * k / E * capacity_factor))
        x2 = x_loc.reshape(T, d)
        w, ids, aux = _route(router, x2, k)
        rank = jax.lax.axis_index(maxis)
        eid = ids.reshape(-1)
        src = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
        wf = w.reshape(-1).astype(F32)
        le = eid - rank * E_loc
        mine = (le >= 0) & (le < E_loc)
        le_or_trash = jnp.where(mine, le, E_loc)
        _, ranks = radix_histogram_ranks(le_or_trash, E_loc + 1)
        ok = mine & (ranks < C)
        flat = jnp.where(ok, le_or_trash * C + ranks, E_loc * C)
        xb = jnp.zeros((E_loc * C + 1, d), jnp.bfloat16) \
            .at[flat].set(x2.astype(jnp.bfloat16)[src])[:-1] \
            .reshape(E_loc, C, d)
        h = _expert_ffn(eg, eu, ed, xb).reshape(E_loc * C, d).astype(F32)
        g = h[jnp.clip(flat, 0, E_loc * C - 1)]
        contrib = g * (wf * ok)[:, None]
        part = jnp.zeros((T, d), F32).at[src].add(contrib)
        y = jax.lax.psum(part, maxis)
        dropped = jax.lax.psum(jnp.sum(mine & ~ok, dtype=jnp.int32), maxis)
        return y.reshape(b, s, d).astype(x_loc.dtype), aux[None], \
            dropped[None]

    y, aux, dropped = shard_map(
        local, mesh=mesh,
        in_specs=(batch_spec, P(), P(maxis, None, None),
                  P(maxis, None, None), P(maxis, None, None)),
        out_specs=(batch_spec, aux_spec, aux_spec),
    )(x, p["router"], p["e_gate"], p["e_up"], p["e_down"])
    return y, jnp.mean(aux), dropped[0]


def moe_apply(p, cfg, x, policy=None, *, decode: bool = False,
              capacity_factor: float = 1.25):
    """x (B,S,d) -> (y, stats): ``stats`` holds the load-balancing
    ``aux``, ``dropped`` (routed pairs no expert computed) and, on one
    device, ``pairs`` per held expert."""
    stats = {}
    if policy is None or policy.mesh is None:
        y, aux, stats["pairs"] = moe_dense(p, cfg, x)
        dropped = jnp.zeros((), jnp.int32)
    elif cfg.router_width not in (0, cfg.n_experts):
        raise NotImplementedError("a mesh shards every expert; a config "
                                  "that holds a share runs without one")
    elif decode or x.shape[1] < policy.mesh.shape[policy.model_axis]:
        y, aux, dropped = moe_decode(p, cfg, x, policy)
    else:
        y, aux, dropped = moe_shuffle(p, cfg, x, policy, capacity_factor)
    if "shared" in p:
        y = y + Ly.swiglu(p["shared"], x)
    stats.update(aux=aux, dropped=dropped)
    return y, stats
