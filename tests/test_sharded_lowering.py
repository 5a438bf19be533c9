"""Lower and compile the sharded train step of reduced configs on a
one-device mesh, through the parameter and optimizer-state specs of
``launch/specs.py``."""
import jax
import pytest

from repro.configs import get_reduced
from repro.launch import specs as SP
from repro.launch.mesh import make_debug_mesh
from repro.models import model as M
from repro.models.sharding import make_policy
from repro.optim import adamw


def _lower_reduced_train(arch: str):
    cfg = get_reduced(arch)
    mesh = make_debug_mesh(1, 1)
    policy = make_policy(mesh, cfg.train.sharding)
    opt_cfg = adamw.AdamWConfig()
    # small synthetic cell: build specs by hand
    B, S = 4, 64
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    params = SP.param_specs(cfg, policy)
    opt_state = SP.opt_state_specs(cfg, policy, params, opt_cfg)
    batch = {
        "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32,
                                       sharding=policy.named(P())),
        "labels": jax.ShapeDtypeStruct((B, S), jnp.int32,
                                       sharding=policy.named(P())),
    }
    if cfg.frontend == "vision":
        batch["patch_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16,
            sharding=policy.named(P()))
    if cfg.is_encdec:
        batch["frames"] = jax.ShapeDtypeStruct(
            (B, S // cfg.enc_len_ratio, cfg.d_model), jnp.bfloat16,
            sharding=policy.named(P()))
    step = M.make_train_step(cfg, policy, opt_cfg)
    return jax.jit(step).lower(params, opt_state, batch)


@pytest.mark.parametrize("arch", ["granite-3-2b", "qwen3-moe-235b-a22b",
                                  "falcon-mamba-7b",
                                  "jamba-1.5-large-398b",
                                  "seamless-m4t-large-v2",
                                  "internvl2-2b"])
def test_lower_compile_reduced(arch):
    lowered = _lower_reduced_train(arch)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    assert cost.get("flops", 0) > 0

