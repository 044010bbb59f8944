"""Distributed training launcher.

On real hardware this runs the sharded train step on the production mesh; on
this CPU container it runs reduced configs on the host mesh (the full configs
are exercised by dryrun.py).

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --steps 50
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs import ARCH_IDS, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.sharding import ShardingRules
from repro.models import init_params, set_sharding_rules
from repro.models.common import set_shard_context
from repro.training import (AdamWConfig, CheckpointManager, DataConfig,
                            init_adamw, make_batch, make_train_step)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="qwen3-0.6b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--full-config", action="store_true",
                    help="use the assigned (non-reduced) architecture; "
                    "requires a real TPU slice")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=not args.full_config)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh())
    rules = ShardingRules(cfg, mesh, "train", args.global_batch, args.seq)
    set_sharding_rules(rules.activation_rules())
    if rules.batch_shardable:
        set_shard_context({"mesh": mesh, "dp": rules.dp,
                           "tp": "model" if rules.tp_enabled else None,
                           "tp_size": rules.tp_n if rules.tp_enabled else 0})

    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = init_adamw(params)
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=args.steps)
    with mesh:
        step_fn = jax.jit(
            make_train_step(cfg, opt_cfg),
            in_shardings=(rules.params_shardings(params),
                          rules.opt_shardings(opt, params), None),
            donate_argnums=(0, 1))
        dcfg = DataConfig(seq_len=args.seq, global_batch=args.global_batch)
        mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
        t0 = time.time()
        for step in range(args.steps):
            batch = make_batch(cfg, dcfg, step)
            params, opt, metrics = step_fn(params, opt, batch)
            if step % 10 == 0 or step == args.steps - 1:
                print(f"step {step:4d} loss {float(metrics['loss']):.4f} "
                      f"({(time.time() - t0) / (step + 1):.2f} s/step)",
                      flush=True)
        if mgr:
            mgr.save(args.steps, params, opt)
    print("done.")


if __name__ == "__main__":
    main()
