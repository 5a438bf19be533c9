"""Training launcher — the paper's single-command spawn (``mpirun``
equivalent) for LM training with the full fault-tolerance stack.

    PYTHONPATH=src python -m repro.launch.train --arch lm100m \
        [--steps 300] [--batch 8] [--seq 512] [--reduced]
        [--mesh data=2,model=2]        # on the CPU: forced host devices
        [--ckpt-dir /tmp/lm_ckpt] [--ckpt-every 50]
        [--fail-at 120]                # failure-injection drill
        [--resume]                     # restore latest checkpoint

On a real multi-host cluster, run this same script once per host with
``jax.distributed.initialize()`` (the ``--coordinator`` flag) — the mesh
logic and the step function are identical; the SPMD program does not
change (loosely-synchronous model: no central scheduler).
"""
import argparse
import math
import sys

from .env import enable_compile_cache, ensure_host_devices


def _parse_mesh(s: str) -> dict:
    out = {}
    for kv in s.split(","):
        k, v = kv.split("=")
        out[k] = int(v)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="lm100m")
    ap.add_argument("--reduced", action="store_true",
                    help="use the arch's reduced() smoke config")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--mesh", default=None,
                    help="e.g. data=2,model=2 (on the CPU, forces host "
                         "devices; on chips, needs that many)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at", type=int, default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--coordinator", default=None,
                    help="host:port for jax.distributed.initialize "
                         "(real clusters)")
    args = ap.parse_args()

    mesh_shape = _parse_mesh(args.mesh) if args.mesh else None
    if mesh_shape:
        ensure_host_devices(math.prod(mesh_shape.values()),
                            ["-m", "repro.launch.train", *sys.argv[1:]])
    enable_compile_cache()

    import jax
    if args.coordinator:
        jax.distributed.initialize(coordinator_address=args.coordinator)
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..configs import get_config, get_reduced
    from ..core.context import make_mesh
    from ..data.synthetic import lm_batch_at
    from ..models import model as M
    from ..models.sharding import make_policy
    from ..optim import adamw
    from ..runtime.trainer import FailureInjector, Trainer, \
        run_with_restarts

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if mesh_shape:
        mesh = make_mesh(tuple(mesh_shape.values()),
                         tuple(mesh_shape.keys()))
        policy = make_policy(mesh, cfg.train.sharding)
    else:
        mesh, policy = None, None
    print(f"[launch] arch={cfg.name} params={cfg.param_count():,} "
          f"mesh={mesh_shape or 'single-device'}")

    opt_cfg = adamw.AdamWConfig(lr=args.lr, total_steps=args.steps)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    opt_state = adamw.init(params, opt_cfg)
    if policy is not None:
        shardings = policy.param_shardings(params)
        params = jax.tree_util.tree_map(jax.device_put, params, shardings)
        # optimizer state: ZeRO-1 2D layout; step scalar replicated so the
        # elastic restore template carries mesh-wide shardings end to end
        opt_sh = policy.param_shardings(params, for_opt=True)
        opt_state = {
            "m": jax.tree_util.tree_map(jax.device_put, opt_state["m"],
                                        opt_sh),
            "v": jax.tree_util.tree_map(jax.device_put, opt_state["v"],
                                        opt_sh),
            "step": jax.device_put(opt_state["step"],
                                   NamedSharding(mesh, P())),
        }
    raw_step = M.make_train_step(cfg, policy, opt_cfg)
    jit_step = jax.jit(raw_step, donate_argnums=(0, 1))

    def step_fn(state, batch):
        params, opt = state
        params, opt, metrics = jit_step(params, opt, batch)
        return (params, opt), metrics

    if mesh is not None:
        bsharding = NamedSharding(mesh, P(policy.batch_axes, None))
    else:
        bsharding = None

    def batches(start):
        s = start
        while True:
            b = lm_batch_at(s, vocab=cfg.vocab, batch=args.batch,
                            seq=args.seq)
            b = {k: jnp.asarray(v) for k, v in b.items()}
            if bsharding is not None:
                b = {k: jax.device_put(v, bsharding)
                     for k, v in b.items()}
            yield b
            s += 1

    trainer = Trainer(step_fn=step_fn, ckpt_dir=args.ckpt_dir,
                      ckpt_every=args.ckpt_every,
                      failure=FailureInjector(args.fail_at))
    state0 = (params, opt_state)
    if not args.resume:
        # fresh run: clear stale checkpoints so step counting is honest
        import shutil
        shutil.rmtree(args.ckpt_dir, ignore_errors=True)
    state, history = run_with_restarts(batches, trainer, state0,
                                       n_steps=args.steps)
    print(f"[done] loss {history[0]['loss']:.4f} -> "
          f"{history[-1]['loss']:.4f} over {len(history)} recorded steps")
    if trainer.monitor.stragglers:
        print(f"[monitor] stragglers flagged: "
              f"{trainer.monitor.stragglers[:5]}")


if __name__ == "__main__":
    main()
