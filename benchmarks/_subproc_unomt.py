"""Subprocess worker: distributed UNOMT data-engineering pipeline (paper
Figs. 13-15) and optional DDP training stage (Fig. 16).

Usage: python _subproc_unomt.py WORLD N_RESPONSE [train]
Prints one JSON line with timing.
"""
import json
import sys
import time

import numpy as np


def main():
    world = int(sys.argv[1])
    n = int(sys.argv[2])
    do_train = len(sys.argv) > 3 and sys.argv[3] == "train"
    from repro.launch.env import enable_compile_cache
    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import dist_ops as D
    from repro.core.context import make_context, make_mesh
    from repro.data.unomt import (feature_label_arrays, gen_unomt_tables,
                                  unomt_dist_pipeline)
    from repro.models import unomt_net
    from repro.optim import adamw

    ctx = make_context(make_mesh((world,), ("data",)))
    raw = gen_unomt_tables(n_response=n, n_drugs=512, n_cells=256, seed=0)
    caps = {k: max((len(next(iter(v.values()))) // world) * 2, 8)
            for k, v in raw.items()}
    gt = {k: D.distribute_table(ctx, v, capacity_per_shard=caps[k])
          for k, v in raw.items()}
    pipe = D.DistributedPipeline(
        ctx, lambda c, r, de, fp, rn: unomt_dist_pipeline(
            c, r, de, fp, rn, overcommit=3.0))

    def run_de():
        out, dropped = pipe(gt["response"], gt["descriptors"],
                            gt["fingerprints"], gt["rna"])
        jax.block_until_ready(out.nvalid)
        return out, dropped

    out, dropped = run_de()                      # compile
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        out, dropped = run_de()
        ts.append(time.perf_counter() - t0)
    result = {"world": world, "de_seconds": float(np.median(ts)),
              "rows": n, "dropped": int(np.max(np.asarray(dropped)))}

    if do_train:
        # stage 3+4: features -> DDP train steps on the same mesh
        from repro.runtime.ddp import make_ddp_train_step
        from repro.optim import compression
        X_parts, y_parts, m_parts = [], [], []
        # table is row-sharded; to_tensor per shard via one more pipeline
        feat_pipe = D.DistributedPipeline(
            ctx, lambda c, t: feature_label_arrays(t))
        X, y, mask = feat_pipe(out)
        cfg = unomt_net.UnomtNetConfig(n_features=17, d_hidden=256,
                                       n_res_blocks=2, n_dense_tail=1,
                                       dropout=0.0)
        params = unomt_net.init(jax.random.PRNGKey(0), cfg)
        opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=0)

        def loss_fn(p, batch):
            return unomt_net.mse_loss(p, cfg, batch)

        step = make_ddp_train_step(loss_fn, opt_cfg, ctx)
        opt = adamw.init(params, opt_cfg)
        res = compression.init_residuals(params)
        X = X.reshape(-1, X.shape[-1])
        y = y.reshape(-1)
        mask = mask.reshape(-1)
        batch = {"x": X, "y": y, "mask": mask}
        params, opt, res, _ = step(params, opt, res, batch)  # compile
        jax.block_until_ready(params)
        t0 = time.perf_counter()
        n_steps = 4
        for _ in range(n_steps):
            params, opt, res, metrics = step(params, opt, res, batch)
        jax.block_until_ready(params)
        result["train_seconds_per_step"] = (time.perf_counter() - t0) \
            / n_steps
        result["final_loss"] = float(np.asarray(metrics["loss"]))
    print(json.dumps(result))


if __name__ == "__main__":
    main()
