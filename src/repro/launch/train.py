"""Training driver.

Runs on anything from 1 CPU (smoke/examples) to the production mesh
(``--mesh single|multi``): builds the mesh, shards params/optimizer by
the path rules, wires the fault-tolerant loop (auto-resume, async
checkpoints, NaN guard) around the pjit'd step.

  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
      --variant smoke --steps 100 --batch 8 --seq 128
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import arch_names, get_config
from repro.data import DataConfig, SyntheticLM
from repro.dist.context import act_ctx
from repro.dist.sharding import batch_spec, named, param_specs
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import transformer as T
from repro.optim import AdamWConfig, adamw_init
from repro.train import LoopConfig, TrainLoop, make_train_step

__all__ = ["main", "train"]


def train(arch: str, *, variant: str = "smoke", steps: int = 100,
          global_batch: int = 8, seq_len: int = 128, accum: int = 1,
          lr: float = 3e-4, ckpt_dir: str = "/tmp/repro_ckpt",
          ckpt_every: int = 50, mesh_kind: str = "host",
          log_fn=None) -> dict:
    cfg = get_config(arch, variant)
    if mesh_kind == "host":
        mesh = make_host_mesh()
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    dp = batch_spec(mesh)
    with jax.set_mesh(mesh), act_ctx(dp=dp[0], tp="model", mesh=mesh):
        key = jax.random.PRNGKey(0)
        params = T.init(key, cfg)
        opt_state = adamw_init(params)
        pspecs = param_specs(params, mesh)
        ospecs = {"m": pspecs, "v": pspecs, "step": P()}
        params = jax.device_put(params, named(mesh, pspecs))
        opt_state = jax.device_put(opt_state, named(mesh, ospecs))

        opt = AdamWConfig(lr=lr, warmup_steps=max(steps // 20, 5),
                          total_steps=steps)
        step = make_train_step(cfg, opt, accum=accum)
        bspec = {"inputs": P(dp[0], None), "labels": P(dp[0], None)}
        jstep = jax.jit(
            step,
            in_shardings=(named(mesh, pspecs), named(mesh, ospecs),
                          named(mesh, bspec)),
            out_shardings=(named(mesh, pspecs), named(mesh, ospecs), None),
            donate_argnums=(0, 1))

        data = SyntheticLM(DataConfig(
            vocab=cfg.vocab, seq_len=seq_len, global_batch=global_batch))

        losses = []

        def log(s, m):
            loss = float(m["loss"])
            if log_fn:
                log_fn(s, m)
            else:
                print(f"step {s:5d} loss {loss:.4f} "
                      f"gnorm {float(m['grad_norm']):.3f}", flush=True)

        loop = TrainLoop(
            step_fn=jstep, data=data,
            cfg=LoopConfig(total_steps=steps, ckpt_every=ckpt_every,
                           ckpt_dir=ckpt_dir, log_every=10),
            log_fn=log)
        params, opt_state, start = loop.resume_or_init(params, opt_state)
        t0 = time.time()
        params, opt_state, losses = loop.run(params, opt_state, start)
        dt = time.time() - t0
    return {"losses": losses, "seconds": dt,
            "first_loss": losses[0] if losses else None,
            "last_loss": losses[-1] if losses else None}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=arch_names())
    ap.add_argument("--variant", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", default="host",
                    choices=["host", "single", "multi"])
    args = ap.parse_args(argv)
    enable_compile_cache()
    out = train(args.arch, variant=args.variant, steps=args.steps,
                global_batch=args.batch, seq_len=args.seq,
                accum=args.accum, lr=args.lr, ckpt_dir=args.ckpt_dir,
                ckpt_every=args.ckpt_every, mesh_kind=args.mesh)
    print(f"done: loss {out['first_loss']:.4f} -> {out['last_loss']:.4f} "
          f"in {out['seconds']:.1f}s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
