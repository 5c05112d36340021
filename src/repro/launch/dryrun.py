import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

# NOTE: the two lines above MUST execute before any other import (JAX
# locks the device count at first init), which is why the module
# docstring lives in this comment block instead of the top of the file.
#
# from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, arch_names, get_config, shape_plan
from repro.dist.sharding import (batch_spec, cache_specs, named,
                                 param_specs)
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import cache_shape, input_specs, state_specs
from repro.models import transformer as T
from repro.optim.adamw import AdamWConfig
from repro.train.step import make_train_step

__all__ = ["dryrun_cell", "main"]

#: Gradient-accumulation factor per arch for the train_4k cell: the
#: production answer for fitting 1M-token global steps in 16 GB v5e HBM.
#: Microbatches are scanned, so the lowered HLO stays one microbatch
#: wide; the global batch spec is unchanged.
TRAIN_ACCUM = {
    "deepseek-v2-236b": 16,
    "jamba-v0.1-52b": 4,
    "mixtral-8x7b": 4,
    "internlm2-20b": 4,
    "mistral-nemo-12b": 4,
    "pixtral-12b": 4,
    "starcoder2-7b": 4,
    "hubert-xlarge": 2,
    "xlstm-125m": 2,
    "qwen1.5-0.5b": 1,
}


def _collective_bytes(hlo_text: str) -> dict[str, float]:
    """Sum operand bytes of collective ops in the optimized HLO."""
    import re
    out: dict[str, float] = {}
    dtype_bytes = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                   "s8": 1, "u8": 1, "f64": 8, "pred": 1, "s64": 8,
                   "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "s16": 2,
                   "u16": 2}
    pat = re.compile(
        r"(\w[\w-]*)\s*=\s*(?:\(([^)]*)\)|(\S+?))\s+"
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
        r"collective-permute)")
    for m in pat.finditer(hlo_text):
        outspec = m.group(2) or m.group(3)
        kind = m.group(4)
        total = 0.0
        for shape in re.finditer(r"(\w+)\[([\d,]*)\]", outspec):
            dt, dims = shape.group(1), shape.group(2)
            if dt not in dtype_bytes:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * dtype_bytes[dt]
        out[kind] = out.get(kind, 0.0) + total
    return out


def _mem_summary(compiled) -> dict[str, float]:
    try:
        ma = compiled.memory_analysis()
        alias = float(getattr(ma, "alias_size_in_bytes", 0.0))
        out = {
            "argument_bytes": float(ma.argument_size_in_bytes),
            "output_bytes": float(ma.output_size_in_bytes),
            "temp_bytes": float(ma.temp_size_in_bytes),
            "alias_bytes": alias,
            # donated inputs alias outputs: don't double count them
            "peak_bytes": float(ma.argument_size_in_bytes +
                                ma.output_size_in_bytes +
                                ma.temp_size_in_bytes - alias),
        }
        return out
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}


def _cost_summary(compiled) -> dict[str, float]:
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        return {"flops": float(ca.get("flops", 0.0)),
                "bytes_accessed": float(ca.get("bytes accessed", 0.0))}
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}


def dryrun_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
                collect_hlo: bool = True) -> dict:
    """Lower+compile one cell; returns the roofline record."""
    cfg = get_config(arch, "full")
    spec = SHAPES[shape_name]
    plan = shape_plan(cfg)
    if plan[shape_name] is not None:
        return {"arch": arch, "shape": shape_name,
                "skipped": plan[shape_name]}
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    record = {"arch": arch, "shape": shape_name,
              "mesh": "x".join(map(str, mesh.devices.shape)),
              "n_devices": mesh.devices.size}

    from repro.dist.context import act_ctx
    dp = batch_spec(mesh)
    with jax.set_mesh(mesh), act_ctx(dp=dp[0], tp="model", mesh=mesh):
        inp = input_specs(cfg, spec)
        if spec.kind == "train":
            state = state_specs(cfg, with_opt=True, opt_dtype=jnp.bfloat16)
            pspecs = param_specs(state["params"], mesh)
            # NOTE: mode="zero1" (pod-sharded optimizer moments) was
            # measured and REFUTED for this workload — the one-shot
            # update respec costs 2x the resident savings in cross-pod
            # traffic (EXPERIMENTS.md §Perf, deepseek iteration 3).
            ospecs = {"m": pspecs, "v": pspecs, "step": P()}
            bspecs = {k: P(dp[0], *([None] * (len(v.shape) - 1)))
                      for k, v in inp.items()}
            accum = TRAIN_ACCUM.get(arch, 1)
            record["accum"] = accum
            step = make_train_step(
                cfg, AdamWConfig(state_dtype="bfloat16"), accum=accum,
                remat=True)
            jitted = jax.jit(
                step,
                in_shardings=(named(mesh, pspecs), named(mesh, ospecs),
                              named(mesh, bspecs)),
                out_shardings=(named(mesh, pspecs), named(mesh, ospecs),
                               None),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(state["params"], state["opt_state"], inp)
        elif spec.kind == "prefill":
            state = state_specs(cfg, with_opt=False,
                                param_dtype=jnp.bfloat16)
            pspecs = param_specs(state["params"], mesh, mode="serve")
            bspec = P(dp[0], *([None] * (len(inp["inputs"].shape) - 1)))

            def fwd(params, inputs):
                # serving prefill: last-position logits only (the
                # (B, S, V) tensor never exists — see §Perf)
                return T.prefill_logits(params, cfg, inputs)

            out_spec = P(dp[0], "model") if cfg.vocab % 16 == 0 else \
                P(dp[0], None)
            jitted = jax.jit(
                fwd,
                in_shardings=(named(mesh, pspecs),
                              NamedSharding(mesh, bspec)),
                out_shardings=NamedSharding(mesh, out_spec),
            )
            lowered = jitted.lower(state["params"], inp["inputs"])
        else:  # decode
            state = state_specs(cfg, with_opt=False,
                                param_dtype=jnp.bfloat16)
            pspecs = param_specs(state["params"], mesh, mode="serve")
            # Unrolling is only safe with resident (TP-only) weights;
            # with FSDP fallback the hoisted per-layer all-gathers
            # would all be live at once (measured: 72 GiB on
            # deepseek-v2) — keep the scan so gathers stay in-loop.
            from repro.dist.sharding import serve_weights_resident
            unroll = serve_weights_resident(state["params"], mesh)
            cshape = cache_shape(cfg, spec)
            cspecs = cache_specs(cshape, mesh)
            tok_rank = len(inp["tok"].shape)
            tspec = P(dp[0], *([None] * (tok_rank - 1)))
            if spec.global_batch % _dp_size(mesh) != 0:
                tspec = P(*([None] * tok_rank))

            def serve(params, tok, cache, pos):
                return T.decode_step(params, cfg, tok, cache, pos,
                                     unroll=unroll)

            jitted = jax.jit(
                serve,
                in_shardings=(named(mesh, pspecs),
                              NamedSharding(mesh, tspec),
                              named(mesh, cspecs), None),
                out_shardings=(None, named(mesh, cspecs)),
                donate_argnums=(2,),
            )
            lowered = jitted.lower(state["params"], inp["tok"], cshape,
                                   inp["pos"])
        record["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        record["compile_s"] = round(time.time() - t1, 1)
        record["memory"] = _mem_summary(compiled)
        record["cost"] = _cost_summary(compiled)
        if collect_hlo:
            record["collectives"] = _collective_bytes(compiled.as_text())
    return record


def _dp_size(mesh) -> int:
    n = 1
    for a in ("pod", "data"):
        if a in mesh.shape:
            n *= mesh.shape[a]
    return n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=["no", "yes", "both"],
                    default="no")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    cells = []
    archs = arch_names() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    pods = {"no": [False], "yes": [True], "both": [False, True]}[
        args.multi_pod]
    results = []
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                try:
                    r = dryrun_cell(arch, shape, multi_pod=mp)
                except Exception as e:
                    r = {"arch": arch, "shape": shape, "multi_pod": mp,
                         "error": f"{type(e).__name__}: {e}"}
                    results.append(r)
                    print(f"[FAIL] {arch} x {shape} mp={mp}: "
                          f"{r['error'][:200]}", flush=True)
                    continue
                results.append(r)
                if "skipped" in r:
                    print(f"[skip] {arch} x {shape}: {r['skipped']}",
                          flush=True)
                    continue
                mem = r["memory"].get("peak_bytes", float("nan")) / 2**30
                fl = r["cost"].get("flops", float("nan"))
                coll = sum(r.get("collectives", {}).values()) / 2**30
                print(f"[ok]  {arch} x {shape} mesh={r['mesh']} "
                      f"peak={mem:.2f}GiB flops={fl:.3e} "
                      f"coll={coll:.2f}GiB "
                      f"(lower {r['lower_s']}s compile {r['compile_s']}s)",
                      flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    # Non-zero exit if any non-skipped cell failed.
    bad = [r for r in results
           if "skipped" not in r and
           ("error" in r or "error" in r.get("memory", {}))]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
