"""MoE routing/dispatch tests (single-device paths; the shard_map EP path
is exercised on 8 devices in tests/dist/dist_checks.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import moe as Moe


def _cfg():
    return get_reduced("qwen3-moe-235b-a22b")


def test_router_weights_normalized():
    cfg = _cfg()
    router = jax.random.normal(jax.random.PRNGKey(0),
                               (cfg.d_model, cfg.n_experts), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, cfg.d_model))
    w, ids, aux = Moe._route(router, x, cfg.top_k)
    assert w.shape == (16, cfg.top_k)
    assert ids.shape == (16, cfg.top_k)
    np.testing.assert_allclose(np.asarray(jnp.sum(w, axis=-1)), 1.0,
                               rtol=1e-5)
    assert ((np.asarray(ids) >= 0)
            & (np.asarray(ids) < cfg.n_experts)).all()
    # balanced-ish random routing -> aux near 1 (perfectly balanced == 1)
    assert 0.5 < float(aux) < 4.0


def test_moe_dense_shapes_finite():
    cfg = _cfg()
    p = Moe.moe_init(jax.random.PRNGKey(2), cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, cfg.d_model),
                          jnp.float32)
    y, aux, _ = Moe.moe_dense(p, cfg, x)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y, np.float32)).all()
    assert np.isfinite(float(aux))


def test_moe_apply_without_mesh_falls_back_to_dense():
    cfg = _cfg()
    p = Moe.moe_init(jax.random.PRNGKey(4), cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 8, cfg.d_model))
    y1, a1 = Moe.moe_apply(p, cfg, x, policy=None)
    y2, a2, _ = Moe.moe_dense(p, cfg, x)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), rtol=1e-5)


def test_expert_padding():
    """granite has 40 experts -> padded to 48; pads take no tokens."""
    cfg = get_reduced("granite-moe-3b-a800m")
    from repro.configs import get_config
    full = get_config("granite-moe-3b-a800m")
    assert full.n_experts == 40
    assert Moe.n_experts_padded(full) == 48
    p = Moe.moe_init(jax.random.PRNGKey(6), cfg)
    # router only has n_experts outputs -> ids < n_experts always
    x = jax.random.normal(jax.random.PRNGKey(7), (64, cfg.d_model))
    _, ids, _ = Moe._route(p["router"], x, cfg.top_k)
    assert (np.asarray(ids) < cfg.n_experts).all()


def test_moe_grad_flows_to_router():
    cfg = _cfg()
    p = Moe.moe_init(jax.random.PRNGKey(8), cfg)
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 8, cfg.d_model))

    def loss(p):
        y, aux, _ = Moe.moe_dense(p, cfg, x)
        return jnp.sum(y.astype(jnp.float32) ** 2) + 0.01 * aux

    g = jax.grad(loss)(p)
    assert float(jnp.sum(jnp.abs(g["router"]))) > 0
    for leaf in jax.tree_util.tree_leaves(g):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()
