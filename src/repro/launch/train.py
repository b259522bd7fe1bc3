"""Distributed training launcher.

On a real TPU pod this runs the pjit'd collaborative train step on the
production mesh; on this CPU container it runs the same code path on a
host mesh (1 device) with a reduced config — the sharding rules, step
function and checkpointing are identical (the 512-chip program is proven
by launch/dryrun.py).

Run (CPU, reduced):
    PYTHONPATH=src python -m repro.launch.train --arch granite-8b \
        --preset smoke --steps 50
Run (pod):
    python -m repro.launch.train --arch qwen1.5-110b --mesh production
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.core import decomposition as deco
from repro.data import tokens as tok
from repro.distributed import sharding as shd
from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.steps import make_train_step
from repro.training import checkpoint as ckpt
from repro.training.optimizer import AdamState, AdamW
from repro.training.schedule import warmup_cosine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=registry.names())
    ap.add_argument("--preset", choices=registry.PRESETS, default="full",
                    help="config preset: smoke (CPU toy), chip or full")
    ap.add_argument("--mesh", default="host",
                    choices=["host", "production", "multipod"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=500)
    args = ap.parse_args()

    compile_cache.enable()
    cfg = registry.get_preset(args.arch, args.preset)
    mesh = {"host": make_host_mesh,
            "production": make_production_mesh,
            "multipod": lambda: make_production_mesh(multi_pod=True)}[args.mesh]()
    print(f"mesh {dict(mesh.shape)}  arch {cfg.name}  "
          f"batch {args.batch} x seq {args.seq}")

    opt = AdamW(lr=warmup_cosine(args.lr, 100, max(args.steps, 1000)))
    step_fn = make_train_step(cfg, opt)

    key = jax.random.PRNGKey(0)
    with jax.set_mesh(mesh):
        params = deco.init_collab_lm(key, cfg)
        opt_state = opt.init(params)
        pshard = shd.param_shardings(params, mesh)
        oshard = AdamState(count=shd.replicated(mesh), m=pshard, v=pshard)
        params = jax.device_put(params, pshard)
        opt_state = jax.device_put(opt_state, oshard)
        jit_step = jax.jit(step_fn, in_shardings=(pshard, oshard, None),
                           donate_argnums=(0, 1))

        batches = tok.lm_batches(0, cfg, args.batch, args.seq)
        t0 = time.time()
        for i in range(args.steps):
            batch = {k: jnp.asarray(v) for k, v in next(batches).items()}
            batch = jax.device_put(batch, shd.batch_shardings(batch, mesh))
            params, opt_state, metrics = jit_step(params, opt_state, batch)
            if i % 10 == 0 or i == args.steps - 1:
                print(f"step {i:5d}  loss {float(metrics['loss']):.4f}  "
                      f"gnorm {float(metrics['grad_norm']):.3f}  "
                      f"({(time.time()-t0)/(i+1):.2f}s/step)", flush=True)
            if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
                ckpt.save(args.ckpt_dir, i + 1, params, opt_state,
                          meta={"arch": cfg.name})
    if args.ckpt_dir:
        ckpt.save(args.ckpt_dir, args.steps, params, opt_state,
                  meta={"arch": cfg.name})
        print(f"checkpoint -> {args.ckpt_dir}")


if __name__ == "__main__":
    main()
