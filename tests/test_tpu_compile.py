"""Compile rehearsal for TPU v5e: the Pallas kernels, compiled by the TPU
compiler for a described (not attached) chip.

Interpret mode cannot see what only Mosaic checks — block alignment,
primitives with no TPU lowering, VMEM over the scoped limit — so each
kernel of the table engine and the model's attention kernel is lowered
and compiled here with ``impl="pallas"``, and must come out holding a
``tpu_custom_call``.  Nothing runs: this proves the kernels compile, not
that they are right (``test_kernels.py`` checks that against ``ref``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library, and every test
worker imports this file.  Keep every such compile in this one file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.fused_bucketing import fused_bucket_ranks
from repro.kernels.hash_groupby import hash_groupby_plan
from repro.kernels.hash_join import hash_join_plan
from repro.kernels.hash_partition import radix_histogram_ranks
from repro.kernels.hash_semi import hash_semi_plan
from repro.kernels.radix_sort import radix_permutation, stable_partition_perm

BUCKETS, SLAB = 512, 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("tile", [1024, 2048])
def test_hash_partition_compiles(one_chip, tile):
    _compile(lambda pid: radix_histogram_ranks(pid, BUCKETS, impl="pallas",
                                               tile=tile),
             one_chip, ((4 * tile,), jnp.int32))


@pytest.mark.parametrize("tile,radix_bits", [(1024, 8), (2048, 11)])
def test_radix_digit_pass_compiles(one_chip, tile, radix_bits):
    _compile(lambda col, inv: radix_permutation(
        (col,), inv, impl="pallas", radix_bits=radix_bits, tile=tile),
        one_chip, ((4 * tile,), jnp.int32), ((4 * tile,), jnp.bool_))


def test_stable_partition_compiles(one_chip):
    _compile(lambda keep: stable_partition_perm(keep, impl="pallas",
                                                tile=1024),
             one_chip, ((4 * 1024 + 17,), jnp.bool_))


@pytest.mark.parametrize("tile", [1024, 2048])
def test_fused_bucketing_compiles(one_chip, tile):
    _compile(lambda a, b, v: fused_bucket_ranks(
        (a, b), v, BUCKETS, impl="pallas", tile=tile),
        one_chip, ((4 * tile,), jnp.int32), ((4 * tile,), jnp.int32),
        ((4 * tile,), jnp.bool_))


def _sides(n):
    return ((n,), jnp.int32), ((n,), jnp.bool_)


def test_hash_join_probe_compiles(one_chip):
    _compile(lambda lk, lv, rk, rv: hash_join_plan(
        (lk,), lv, (rk,), rv, num_buckets=BUCKETS, bucket_capacity=SLAB,
        probe_capacity=SLAB, impl="pallas").rank,
        one_chip, *_sides(8192), *_sides(8192))


def test_hash_semi_probe_compiles(one_chip):
    _compile(lambda lk, lv, rk, rv: hash_semi_plan(
        (lk,), lv, (rk,), rv, num_buckets=BUCKETS, bucket_capacity=SLAB,
        probe_capacity=SLAB, impl="pallas").member,
        one_chip, *_sides(8192), *_sides(8192))


def test_hash_groupby_accumulate_compiles(one_chip):
    _compile(lambda k, v, x: hash_groupby_plan(
        (k,), v, (x,), num_buckets=BUCKETS, bucket_capacity=SLAB,
        impl="pallas").sums,
        one_chip, *_sides(8192), ((8192,), jnp.float32))


def test_flash_attention_compiles_at_lm100m_width(one_chip):
    # lm100m: 12 heads of 64, training sequence 512
    qkv = ((8, 12, 512, 64), jnp.bfloat16)
    _compile(lambda q, k, v: fa_ops.flash_attention(q, k, v, causal=True,
                                                    impl="pallas"),
             one_chip, qkv, qkv, qkv)


def test_held_expert_layer_and_mamba2_step_compile(one_chip):
    """granite-4.0-h-small's decode-step pieces at published widths, for a
    64-slot batch: the held-expert layer (9 of 72 experts, top-10) runs
    its held experts over every token with no dispatch (no scatter), and
    the Mamba-2 step (128 heads x 64 x 128 state) compiles."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import mamba as Mb
    from repro.models import moe as Moe
    cfg = dataclasses.replace(get_config("granite-4.0-h-small"), n_layers=10,
                              n_experts=9)

    def shapes(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16,
                                           sharding=one_chip), tree)
    x = jax.ShapeDtypeStruct((64, 1, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)
    moe_p = shapes(jax.eval_shape(lambda k: Moe.moe_init(k, cfg),
                                  jax.random.PRNGKey(0)))
    text = jax.jit(lambda p, x: Moe.moe_apply(p, cfg, x)).lower(
        moe_p, x).compile().as_text()
    assert "scatter" not in text
    m_p = shapes(jax.eval_shape(lambda k: Mb.mamba2_init(k, cfg),
                                jax.random.PRNGKey(0)))
    conv = cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
    state = {"conv": jax.ShapeDtypeStruct((64, cfg.ssm_conv - 1, conv),
                                          jnp.float32, sharding=one_chip),
             "ssm": jax.ShapeDtypeStruct(
                 (64, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                 jnp.float32, sharding=one_chip)}
    jax.jit(lambda p, x, s: Mb.mamba2_step(p, cfg, x, s)).lower(
        m_p, x, state).compile()


def test_serve_step_writes_the_cache_in_place(one_chip):
    """The chat cell's decode body (granite-3.0-2b's widths, 16 slots, 2560
    positions; four layers) compiled for the chip with its bfloat16 caches
    donated: no copy of a whole stacked K or V leaf, and temporaries under
    a quarter of the caches' bytes (the one-hot write through the layer
    scan copied both leaves and held a whole cache of temp)."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import model as M
    from test_decode_cache import whole_leaf_copies
    cfg = dataclasses.replace(get_config("granite-3-2b"), n_layers=4)
    B, S = 16, 2560

    def on_chip(tree, dtype=None):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, dtype or s.dtype,
                                           sharding=one_chip), tree)
    params = on_chip(jax.eval_shape(lambda k: M.init_params(k, cfg),
                                    jax.random.PRNGKey(0)), jnp.bfloat16)
    caches = on_chip(M.cache_struct(cfg, B, S))
    serve = M.make_serve_step(cfg, None)
    compiled = jax.jit(serve, donate_argnums=(1,)).lower(
        params, caches,
        jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)).compile()
    leaves = jax.tree_util.tree_leaves(caches)
    cache_bytes = sum(s.size * s.dtype.itemsize for s in leaves)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < cache_bytes / 4, (temp, cache_bytes)
    assert not whole_leaf_copies(compiled.as_text(), leaves)
