"""Serving driver: load (or init) a model, run batched requests through
the symbiotic engine, print generations + scheduling stats.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
      --variant smoke --requests 8 --policy symbiotic
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import arch_names, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.serve import Request, SchedulerPolicy, ServingEngine
from repro.train.checkpoint import latest_step, restore_checkpoint

__all__ = ["main", "serve"]


def serve(arch: str, *, variant: str = "smoke", n_requests: int = 8,
          policy: str = "symbiotic", max_len: int = 96,
          max_new_tokens: int = 8, ckpt_dir: str | None = None,
          seed: int = 0) -> dict:
    cfg = get_config(arch, variant)
    if not cfg.causal:
        raise SystemExit(f"{arch} is encoder-only: no autoregressive "
                         "serving (use the forward path)")
    params = T.init(jax.random.PRNGKey(seed), cfg)
    if ckpt_dir and latest_step(ckpt_dir) is not None:
        tree, _ = restore_checkpoint(ckpt_dir, {"params": params,
                                                "opt": None})
        params = tree["params"]
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n_requests):
        plen = int(rng.integers(4, max(5, max_len // 4)))
        reqs.append(Request(i, rng.integers(0, cfg.vocab, size=plen),
                            max_new_tokens=max_new_tokens))
    eng = ServingEngine(cfg, params, max_len=max_len,
                        policy=SchedulerPolicy(kind=policy))
    eng.submit(reqs)
    t0 = time.time()
    stats = eng.run()
    stats["wall_s"] = time.time() - t0
    return stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=arch_names())
    ap.add_argument("--variant", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--policy", default="symbiotic",
                    choices=["fifo", "symbiotic", "refined"])
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()
    stats = serve(args.arch, variant=args.variant,
                  n_requests=args.requests, policy=args.policy,
                  max_len=args.max_len,
                  max_new_tokens=args.max_new_tokens,
                  ckpt_dir=args.ckpt_dir)
    print(f"policy={args.policy} rounds={stats['rounds']} "
          f"new_tokens={stats['total_new_tokens']} "
          f"modelled={stats['modelled_time_s'] * 1e3:.2f}ms "
          f"wall={stats['wall_s']:.1f}s")
    for rid, toks in sorted(stats["outputs"].items())[:4]:
        print(f"  req {rid}: {toks[:10]}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
