"""Readings that set a cell's rate and its correctness limit, on the chip.

    python3 chipbench/calibrate.py sweep --workload W --seed N \\
        --seconds S --rates 1.0,1.5,2.0
    python3 chipbench/calibrate.py readings --workload W --seconds S \\
        --seeds 1,2,3 [--control 1,2] [--published rms_norm_eps=1e-5]

``sweep`` runs an open-loop cell once per rate, in one process, and
prints for each rate the backlog (requests submitted and not finished)
at the middle and at the end of the window: the knee is the highest
rate at which it does not grow. ``readings`` runs the cell once per
seed and prints the numbers its check compares, with the run's
end-to-end tails; for the seeds listed in ``--control`` it also reads,
on the same sampled requests, the numbers of the control: the
reference computed with float8 weights, put in the program's place.
The limit in ``chipbench/limits/<workload>.json`` is set between the
two. The benchmark's own runs never run the control.

``--published KEY=VALUE,...`` also reads the program against a
reference whose configuration has those keys set back to their
published values: how far a key changed in ``reduced`` moves the
comparison. ``--order-seed N`` replaces the mix's ``order_seed``: the
same sizes and gaps in another order, to show what the order changes.

Each line of output is one JSON object; the same lines go to
``chiprun_out/calibrate-<command>-<workload>.jsonl`` when that
directory exists.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_HERE = Path(__file__).resolve().parent
for p in (str(_HERE.parent / "src"), str(_HERE.parent)):
    if p not in sys.path:
        sys.path.insert(0, p)


def _emit(out, rec: dict) -> None:
    line = json.dumps(rec)
    print(line, flush=True)
    if out is not None:
        out.write(line + "\n")
        out.flush()


def _backlog_at(backlog, t: float) -> int:
    before = [n for s, n in backlog if s <= t]
    return before[-1] if before else 0


def sweep(cell, args, out) -> None:
    from chipbench import harness
    for rate in [float(r) for r in args.rates.split(",")]:
        traffic = dict(cell.traffic, rate_per_s=rate)
        c = dataclasses.replace(cell, traffic=traffic)
        t0 = time.perf_counter()
        run = harness.run_cell(c, args.seed, args.seconds, False, t0)
        half = args.seconds / 2
        first = [n for t, n in run.backlog if t < half]
        second = [n for t, n in run.backlog if t >= half]
        _emit(out, {"rate_per_s": rate, "attempted": run.attempted,
                    "backlog_mid": _backlog_at(run.backlog, half),
                    "backlog_end": _backlog_at(run.backlog, run.window_s),
                    "backlog_mean_first_half":
                        sum(first) / max(len(first), 1),
                    "backlog_mean_second_half":
                        sum(second) / max(len(second), 1),
                    "ttft_p50_s": harness.percentile(run.ttft_s, 50),
                    "ttft_p90_s": harness.percentile(run.ttft_s, 90),
                    "itl_p95_s": harness.percentile(run.itl_s, 95),
                    "tokens_per_s": run.tokens / run.window_s,
                    "correct": run.correct, "checks": run.checks})


def _widest(cell, ctx, n: int = 3) -> list:
    """The ``n`` widest gaps of the program: request, position, gap,
    and for a mixture of experts the reference's routing margin there
    at each layer."""
    import numpy as np

    from chipbench import check
    found = sorted(((float(g[j]), i, j) for i, g in enumerate(ctx["gaps"])
                    for j in range(len(g))), reverse=True)[:n]
    out = []
    for gap, i, j in found:
        r = ctx["picked"][i]
        pos = len(r.prompt) - 1 + j
        rec = {"rid": r.rid, "position": pos, "gap": gap}
        fam = check.family(cell.config, cell.bench_dir)
        if hasattr(fam, "router_margins"):
            ids = np.zeros(ctx["length"], np.int32)
            seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1])])
            ids[:len(seq)] = seq
            m = fam.router_margins(ctx["reference"], ids)
            rec["router_margin"] = m[:, pos].tolist()
            rec["router_margin_p01"] = float(np.quantile(m[:, :len(seq)],
                                                         0.01))
        out.append(rec)
    return out


def _seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def readings(cell, args, out) -> None:
    from chipbench import check, harness
    published = dict(kv.split("=") for kv in args.published.split(",")
                     if kv)
    control = set(_seeds(args.control))
    for seed in _seeds(args.seeds):
        t0 = time.perf_counter()
        run = harness.run_cell(cell, seed, args.seconds, False, t0)
        rec = {"seed": seed, "order_seed": cell.traffic["order_seed"],
               "correct": run.correct, "checks": run.checks,
               "attempted": run.attempted, "setup_s": run.setup_s,
               "compiles_in_window": run.compiles_in_window,
               "ttft_p90_s": harness.percentile(run.ttft_s, 90),
               "itl_p95_s": harness.percentile(run.itl_s, 95),
               "tokens_per_s": run.tokens / run.window_s,
               "late_max_s": max(run.late_s, default=0.0),
               "longest_steps": run.longest_steps}
        ctx = run.ctx
        if "gaps" in ctx:
            rec.update(check.gap_numbers(ctx["gaps"]))
            rec["widest"] = _widest(cell, ctx)
            rec["last_position"] = max(len(r.prompt) + len(r.tokens) - 2
                                       for r in ctx["picked"])
        if seed in control and "gaps" in ctx:
            t1 = time.perf_counter()
            g = check.gaps(ctx["reference"], ctx["picked"], ctx["length"],
                           control=True)
            rec.update(check.gap_numbers(g, prefix="control_"))
            rec["control_s"] = time.perf_counter() - t1
        if published and "gaps" in ctx:
            conf = dict(cell.config, **{k: float(v)
                                        for k, v in published.items()})
            ref = check.reference_for(conf, ctx["reference"].w,
                                      cell.bench_dir)
            g = check.gaps(ref, ctx["picked"], ctx["length"])
            rec.update(check.gap_numbers(g, prefix="published_"))
        run.ctx.clear()
        _emit(out, rec)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("command", choices=("sweep", "readings"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", default="",
                    help="seeds whose control is read too")
    ap.add_argument("--published", default="",
                    help="KEY=VALUE,...: also read against these values")
    ap.add_argument("--order-seed", type=int, default=None)
    args = ap.parse_args(argv)

    import jax
    from chipbench import spec
    from chipbench.run import _enable_cache
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    _enable_cache(jax)
    cell = spec.load_cell(args.workload)
    if args.order_seed is not None:
        cell = dataclasses.replace(cell, traffic=dict(
            cell.traffic, order_seed=args.order_seed))
    dest = _HERE.parent / "chiprun_out"
    out = None
    if dest.is_dir():
        out = open(dest / f"calibrate-{args.command}-{args.workload}.jsonl",
                   "a")
    try:
        (sweep if args.command == "sweep" else readings)(cell, args, out)
    finally:
        if out is not None:
            out.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
