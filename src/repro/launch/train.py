"""End-to-end training driver (SPMD path).

Runs on anything from 1 CPU device (smoke configs) to the production mesh:
  PYTHONPATH=src python -m repro.launch.train --arch glm4_9b --smoke \
      --steps 200 --batch 8 --seq 64 --mesh 1,1 --ckpt /tmp/ck

Features exercised: queue-fed data pipeline, mixed-precision train step with
microbatching, ZeRO-1 state sharding, periodic consistent checkpoints with
retention, crash-resume (--resume), elastic mesh changes between runs
(checkpoint/elastic.py re-shards on restore).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import (OptimizerConfig, ParallelConfig, ShapeConfig,
                          get_config)
from repro.checkpoint.checkpoint import CheckpointManager
from repro.checkpoint.elastic import restore_for_mesh, save_global
from repro.data.pipeline import Pipeline, ShardedSource
from repro.launch.compile_cache import configure_compile_cache
from repro.models import api
from repro.optim import optimizers as opt
from repro.spmd import steps as steps_mod


def build_state(cfg, pcfg, ocfg, mesh, seed=0):
    """Initialise the bf16 working params and the optimizer state (which
    holds the only fp32 copy, as master weights) in one jitted call that
    writes each leaf straight into its sharding — no host-side fp32 tree,
    no second fp32 copy beside the master."""
    def init(key):
        params_f32, _ = api.init_model(cfg, key)
        state = opt.init_train_state(ocfg, params_f32)
        params = jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                              state["master"])
        return params, state

    with jax.set_mesh(mesh):
        shapes_f32, specs = api.abstract_params(cfg)
        params_shape, opt_shape = jax.eval_shape(init, jax.random.key(seed))
        psh = steps_mod.resolve_param_shardings(params_shape, specs, cfg,
                                                pcfg, mesh)
        osh = steps_mod.opt_state_shardings(opt_shape, shapes_f32, specs,
                                            cfg, pcfg, mesh)
        params, opt_state = jax.jit(init, out_shardings=(psh, osh))(
            jax.random.key(seed))
    return params, opt_state, specs, psh, osh


def train(cfg, *, steps, batch, seq, mesh, pcfg=None, ocfg=None,
          ckpt_dir=None, ckpt_every=50, resume=False, log_every=10,
          seed=0):
    pcfg = pcfg or ParallelConfig(remat="full", microbatches=1)
    ocfg = ocfg or OptimizerConfig(lr=1e-3, warmup_steps=20,
                                   total_steps=steps)
    params, opt_state, specs, psh, osh = build_state(cfg, pcfg, ocfg, mesh,
                                                     seed)
    start = 0
    mgr = CheckpointManager(ckpt_dir, keep=2, keep_best=1) if ckpt_dir \
        else None
    if resume and mgr and mgr.latest_step() is not None:
        start, state = restore_for_mesh(
            mgr, {"params": params, "opt": opt_state},
            {"params": psh, "opt": osh})
        params, opt_state = state["params"], state["opt"]
        print(f"[train] resumed from step {start}")

    src = ShardedSource(cfg, seq, seed=seed)
    pipe = Pipeline(src, batch, capacity=4)
    step_fn = steps_mod.make_train_step(cfg, pcfg, ocfg)
    with jax.set_mesh(mesh):
        jitted = jax.jit(step_fn, donate_argnums=(0, 1))
        losses, t0 = [], time.time()
        for s in range(start, steps):
            hostb = pipe.get()
            batch_dev = {k: jnp.asarray(v) for k, v in hostb.items()}
            params, opt_state, metr = jitted(
                params, opt_state, jnp.asarray(s, jnp.int32), batch_dev)
            losses.append(float(metr["loss"]))
            if (s + 1) % log_every == 0:
                dt = (time.time() - t0) / log_every
                tok_s = batch * seq / dt
                print(f"[train] step {s+1} loss={losses[-1]:.4f} "
                      f"gnorm={float(metr['grad_norm']):.3f} "
                      f"{dt*1e3:.0f} ms/step {tok_s:.0f} tok/s")
                t0 = time.time()
            if mgr and (s + 1) % ckpt_every == 0:
                save_global(mgr, s + 1,
                            {"params": params, "opt": opt_state},
                            metric=float(np.mean(losses[-10:])))
    pipe.close()
    if mgr:
        mgr.wait()
    return params, opt_state, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4_9b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced-width config (--no-smoke: published "
                         "widths)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--mesh", default="1,1")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()
    configure_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    d, m = (int(x) for x in args.mesh.split(","))
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(d, m)
    pcfg = ParallelConfig(remat="full", microbatches=args.microbatches)
    _, _, losses = train(cfg, steps=args.steps, batch=args.batch,
                         seq=args.seq, mesh=mesh, pcfg=pcfg,
                         ckpt_dir=args.ckpt, resume=args.resume)
    print(f"[train] done. loss {losses[0]:.4f} -> {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
