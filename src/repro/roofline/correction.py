"""Two-point depth extrapolation: validate the analytic cost model and
correct while-body-once undercounts from the compiled dry-run.

For a cell, lower + compile the SAME full-width config at reduced
depths ``prefix + 1*period`` and ``prefix + 2*period`` layers (accum=1).
The difference of any additive metric between the two compiles is one
layer-unit's true cost — XLA cannot hide it in a loop body because the
depth change is materialised in the program:

    unit_X  = X(2 units) - X(1 unit)
    total_X ~= X_measured_full + unit_X * (reps_full - 1)

Used two ways:
* ``validate_flops``: compare unit FLOPs against the analytic model of
  ``repro.roofline.flops`` (EXPERIMENTS.md appendix),
* ``corrected_collectives``: collective bytes with the per-unit slope
  restored (raw HLO parsing sees the scan body once).

Run from a fresh process (needs the 512-device host platform):

  PYTHONPATH=src python -m repro.roofline.correction --arch qwen1.5-0.5b
"""

from __future__ import annotations

import os

if __name__ == "__main__":  # device count must be set pre-jax-import
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=512")

import argparse
import sys


def measure_depths(arch: str, shape_name: str) -> dict:
    """Compile depth-1 and depth-2 variants; return per-unit metrics."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import SHAPES, get_config
    from repro.dist.context import act_ctx
    from repro.dist.sharding import batch_spec, named, param_specs
    from repro.launch.dryrun import _collective_bytes
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import input_specs, state_specs
    from repro.models import transformer as T
    from repro.models.transformer import unit_period
    from repro.optim.adamw import AdamWConfig
    from repro.train.step import make_train_step

    cfg_full = get_config(arch, "full")
    spec = SHAPES[shape_name]
    prefix, period = unit_period(cfg_full)
    mesh = make_production_mesh()
    out = {}
    dp = batch_spec(mesh)
    with jax.set_mesh(mesh), act_ctx(dp=dp[0], tp="model", mesh=mesh):
        for k in (1, 2):
            cfg = cfg_full.replace(n_layers=prefix + k * period)
            inp = input_specs(cfg, spec)
            if spec.kind == "train":
                state = state_specs(cfg, with_opt=True,
                                    opt_dtype=jnp.bfloat16)
                pspecs = param_specs(state["params"], mesh)
                ospecs = {"m": pspecs, "v": pspecs, "step": P()}
                bspecs = {kk: P(dp[0], *([None] * (len(v.shape) - 1)))
                          for kk, v in inp.items()}
                step = make_train_step(
                    cfg, AdamWConfig(state_dtype="bfloat16"), accum=1,
                    unroll=True)
                jitted = jax.jit(
                    step,
                    in_shardings=(named(mesh, pspecs), named(mesh, ospecs),
                                  named(mesh, bspecs)),
                    out_shardings=(named(mesh, pspecs),
                                   named(mesh, ospecs), None),
                    donate_argnums=(0, 1))
                compiled = jitted.lower(state["params"],
                                        state["opt_state"], inp).compile()
            else:
                state = state_specs(cfg, with_opt=False,
                                    param_dtype=jnp.bfloat16)
                pspecs = param_specs(state["params"], mesh, mode="serve")
                bspec = P(dp[0], *([None] * (len(inp["inputs"].shape) - 1)))
                def fwd(p, x):
                    feats, _ = T.forward_features(p, cfg, x, remat=False,
                                                  unroll=True)
                    h = T.head_matrix(p, cfg)
                    return feats[:, -1, :] @ h.astype(feats.dtype)
                jitted = jax.jit(
                    fwd, in_shardings=(named(mesh, pspecs),
                                       NamedSharding(mesh, bspec)))
                compiled = jitted.lower(state["params"],
                                        inp["inputs"]).compile()
            ca = compiled.cost_analysis()
            if isinstance(ca, list):
                ca = ca[0]
            out[k] = {
                "flops": float(ca.get("flops", 0.0)),
                "bytes": float(ca.get("bytes accessed", 0.0)),
                "coll": sum(_collective_bytes(compiled.as_text()).values()),
            }
    reps_full = (cfg_full.n_layers - prefix) // period
    unit = {m: out[2][m] - out[1][m] for m in ("flops", "bytes", "coll")}
    return {"arch": arch, "shape": shape_name, "prefix": prefix,
            "period": period, "reps_full": reps_full,
            "depth1": out[1], "depth2": out[2], "unit": unit}


def validate_flops(arch: str, shape_name: str) -> dict:
    """Measured per-unit FLOPs (x chips) vs the analytic model."""
    from repro.configs import SHAPES, get_config
    from repro.roofline.flops import (_attn_core_ctx,
                                      layer_fwd_flops_per_token)
    m = measure_depths(arch, shape_name)
    cfg = get_config(arch, "full")
    spec = SHAPES[shape_name]
    ctx = _attn_core_ctx(cfg, spec)
    per_tok = sum(layer_fwd_flops_per_token(cfg, cfg.first_dense_layers + u,
                                            ctx)
                  for u in range(m["period"]))
    tokens = spec.global_batch * spec.seq_len
    mult = 4.0 if spec.kind == "train" else 1.0
    analytic_unit = per_tok * tokens * mult
    measured_unit = m["unit"]["flops"] * 256  # per-partition -> global
    return {**m, "analytic_unit_flops": analytic_unit,
            "measured_unit_flops": measured_unit,
            "ratio": measured_unit / max(analytic_unit, 1.0)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--shape", default="train_4k")
    args = ap.parse_args(argv)
    r = validate_flops(args.arch, args.shape)
    print(f"{r['arch']} x {r['shape']}: unit(period={r['period']}) "
          f"measured {r['measured_unit_flops']:.3e} vs analytic "
          f"{r['analytic_unit_flops']:.3e} FLOPs -> ratio "
          f"{r['ratio']:.3f}")
    print(f"per-unit collective bytes: {r['unit']['coll'] / 2**20:.1f} MiB "
          f"(x{r['reps_full']} units for the corrected total)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
