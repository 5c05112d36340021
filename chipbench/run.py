"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python3 chipbench/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

One process: makes the weights from the seed, builds the serving engine,
warms up every program the window will use, drives the engine by the
host clock for ``--seconds``, checks the served tokens against the
plain reference, and prints one JSON object as the last line of
standard output. ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` profiles a sub-window and reports its per-layer metrics.

Without a TPU, or with fewer chips than the cell asks for, it exits
with code 2 and prints no result.

The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` where that is set,
else ``chipbench/.jax_cache`` in this checkout.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_HERE = Path(__file__).resolve().parent
ROOT = _HERE.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

CACHE_DIR = _HERE / ".jax_cache"


def _enable_cache(jax) -> str:
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # cache every program, also the small ones a replayed prompt uses
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return env or str(CACHE_DIR)


def result_line(cell, run, trace: bool, device, n_devices: int) -> dict:
    from chipbench import harness
    entries = cell.per_layer if trace else cell.end_to_end
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": n_devices, "memory_peak_bytes": run.peak_bytes}
    if trace:
        dev["busy_s"] = run.busy_s
        dev["window_s"] = run.traced_window_s
    out = {"correct": run.correct, "attempted": run.attempted,
           "failed": run.failed,
           "metrics": harness.read_metrics(entries, run), "device": dev}
    if trace and run.breakdown:
        out["breakdown"] = run.breakdown
    out["checks"] = run.checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness, spec
    cell = spec.load_cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: needs a TPU; JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"chipbench: {cell.name} needs {cell.chips} chips; JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    harness._say(f"compile cache {_enable_cache(jax)}")
    harness._say(f"{cell.name} seed {args.seed} on {devices[0].device_kind} "
                 f"x{len(devices)}")
    run = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           _T_START, device=devices[0])
    harness._say(f"window {run.window_s:.3f}s, {run.attempted} requests, "
                 f"{run.tokens} tokens, compiles in window "
                 f"{run.compiles_in_window}")
    if run.kv_in_use is not None:
        harness._say(f"KV cache in use: {100 * run.kv_in_use:.2f}% of the "
                     f"positions the live requests' caches reserve")
    harness._say("longest steps (s, at s, live, calls, compose s, "
                 "execute s): " + str([tuple(round(x, 4) for x in st)
                                       for st in run.longest_steps]))
    if run.late_s:
        harness._say(f"generator lateness (submit - due): p50 "
                     f"{harness.percentile(run.late_s, 50):.6f}s max "
                     f"{max(run.late_s):.6f}s")
    line = result_line(cell, run, bool(args.trace), devices[0], len(devices))
    for name, c in run.checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
