"""UNOMT end-to-end (paper §4): data engineering + deep learning in ONE
distributed program with a single runtime — the paper's headline demo.

    PYTHONPATH=src python examples/unomt_e2e.py \
        [--parallelism 4] [--rows 20000] [--steps 200] [--compress]
        [--fail-at 120]   # inject a failure; training restarts from ckpt

Stages (paper Fig. 5):
  1. spawn workers        -> forced host devices + HptmtContext (mesh)
  2. data engineering     -> distributed join/unique/isin/scale pipeline
  3. table -> tensor      -> feature_label_arrays inside the same program
  4. data analytics       -> BSP DDP training of the drug-response net
"""
import argparse
import sys

from repro.launch.env import enable_compile_cache, ensure_host_devices


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parallelism", type=int, default=4)
    ap.add_argument("--rows", type=int, default=20_000)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--compress", action="store_true",
                    help="int8 error-feedback gradient allreduce")
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a failure at this step (restart drill)")
    ap.add_argument("--ckpt-dir", default="/tmp/unomt_ckpt")
    args = ap.parse_args()

    # stage 1: single-command spawn (the paper's mpirun equivalent)
    ensure_host_devices(args.parallelism, sys.argv)
    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import dist_ops as D
    from repro.core.context import make_context, make_mesh
    from repro.data.unomt import (feature_label_arrays, gen_unomt_tables,
                                  unomt_dist_pipeline)
    from repro.models import unomt_net
    from repro.optim import adamw, compression
    from repro.runtime.ddp import make_ddp_train_step
    from repro.runtime.trainer import (FailureInjector, Trainer,
                                       run_with_restarts)

    world = args.parallelism
    ctx = make_context(make_mesh((world,), ("data",)))
    print(f"[stage 1] {world} workers, mesh axes {ctx.mesh.axis_names}")

    # ---- stage 2: distributed data engineering --------------------------
    raw = gen_unomt_tables(n_response=args.rows, n_drugs=512, n_cells=256,
                           seed=0)
    caps = {k: max((len(next(iter(v.values()))) // world) * 2, 8)
            for k, v in raw.items()}
    gt = {k: D.distribute_table(ctx, v, capacity_per_shard=caps[k])
          for k, v in raw.items()}
    pipe = D.DistributedPipeline(
        ctx, lambda c, r, de, fp, rn: unomt_dist_pipeline(
            c, r, de, fp, rn, overcommit=3.0))
    feat, dropped = pipe(gt["response"], gt["descriptors"],
                         gt["fingerprints"], gt["rna"])
    n_rows = int(np.sum(np.asarray(feat.nvalid)))
    print(f"[stage 2] features: {n_rows} rows "
          f"(dropped={int(np.max(np.asarray(dropped)))})")

    # ---- stage 3: table -> tensors (still on the mesh) -------------------
    X, y, mask = D.DistributedPipeline(
        ctx, lambda c, t: feature_label_arrays(t))(feat)
    X = X.reshape(-1, X.shape[-1])
    y, mask = y.reshape(-1), mask.reshape(-1)
    print(f"[stage 3] X {X.shape} sharded {X.sharding.spec}")

    # ---- stage 4: BSP DDP training ---------------------------------------
    net_cfg = unomt_net.UnomtNetConfig(n_features=X.shape[1],
                                       d_hidden=512, n_res_blocks=3,
                                       n_dense_tail=2, dropout=0.0)
    params = unomt_net.init(jax.random.PRNGKey(0), net_cfg)
    opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=20,
                                total_steps=args.steps)
    ddp_step = make_ddp_train_step(
        lambda p, b: unomt_net.mse_loss(p, net_cfg, b), opt_cfg, ctx,
        compress=args.compress)

    def step_fn(state, batch):
        params, opt, res = state
        params, opt, res, metrics = ddp_step(params, opt, res, batch)
        return (params, opt, res), metrics

    def batches(start_step):
        while True:
            yield {"x": X, "y": y, "mask": mask}

    # replicate state on the mesh explicitly so checkpoint restore puts
    # arrays back mesh-wide (not committed to device 0)
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(ctx.mesh, P())
    put = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.device_put(x, rep), tree)
    state0 = (put(params), put(adamw.init(params, opt_cfg)),
              put(compression.init_residuals(params)))
    trainer = Trainer(step_fn=step_fn, ckpt_dir=args.ckpt_dir,
                      ckpt_every=50,
                      failure=FailureInjector(args.fail_at))
    state, history = run_with_restarts(batches, trainer, state0,
                                       n_steps=args.steps)
    print(f"[stage 4] loss {history[0]['loss']:.4f} -> "
          f"{history[-1]['loss']:.4f} over {len(history)} steps "
          f"({'compressed' if args.compress else 'exact'} allreduce)")
    stragglers = [h for h in history if h.get("straggler")]
    if stragglers:
        print(f"[monitor] {len(stragglers)} straggler steps flagged")
    assert history[-1]["loss"] < history[0]["loss"]
    print("unomt_e2e OK")


if __name__ == "__main__":
    main()
